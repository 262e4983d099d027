"""The arithmetic from records, counters and the reduced trace to numbers.

A metric's reader (``metrics/<name>.py``) is a few lines over these. Every
function returns None where it finds nothing to read.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear interpolation between order statistics, q in [0, 100]."""
    if not values:
        return None
    ordered = sorted(values)
    spot = (len(ordered) - 1) * q / 100.0
    low = int(spot)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (spot - low)


# the instant by which a request falls into the window, by the mix's
# ``counted_by``: ``due`` - it was due there, answered or not (a request
# nobody waits for is timed from when it was due); ``ended`` - it ended
# there, answered or failed (users who send their next when the last ends)
_COUNTED_AT = {
    "due": lambda r: r.get("due"),
    "ended": lambda r: r.get("done", r.get("failed_at")) if "frames" in r else None,
}


def counted(ctx) -> List[Dict[str, Any]]:
    """The requests this run counts: ``attempted``, and what every
    percentile is taken over."""
    opens, closes = ctx["window"]["opens"], ctx["window"]["closes"]
    at = _COUNTED_AT[ctx["counted_by"]]
    return [
        r for r in ctx["requests"]
        if at(r) is not None and opens <= at(r) < closes
    ]


def ttft_ms(ctx) -> List[float]:
    """First frame at the client minus the instant the request was due; a
    failed or unanswered request counts as the request limit."""
    worst = ctx["limit_s"] * 1e3
    out = []
    for r in counted(ctx):
        if r.get("frames") and "error" not in r:
            out.append((r["frames"][0][0] - r["due"]) * 1e3)
        else:
            out.append(worst)
    return out


def tpot_ms(ctx) -> List[float]:
    """(last frame - first frame) / (output tokens - 1), answered requests."""
    out = []
    for r in counted(ctx):
        tokens = len(r["output_ids"]) if r.get("output_ids") else sum(
            n for _, n in r.get("frames", [])
        )
        if "done" in r and tokens > 1:
            out.append((r["done"] - r["frames"][0][0]) * 1e3 / (tokens - 1))
    return out


def out_tok_s(ctx) -> Optional[float]:
    """Output tokens delivered to clients in the window over its seconds.
    A harvest hands a client a whole decode chunk at once, so counting each
    frame at its instant would quantise the count by what the harvests at
    the window's two edges happen to hold (a few per cent of a window).
    Instead a request's tokens after its first frame count as arriving
    evenly between that frame and its last, which is how the engine made
    them (the harness waits past the close for the next frame of every
    request the close cuts). One character of a frame is one token
    (benchmark/tokenizer.py)."""
    opens, closes = ctx["window"]["opens"], ctx["window"]["closes"]
    total = 0.0
    for r in ctx["requests"]:
        frames = r.get("frames") or []
        if not frames:
            continue
        first, last = frames[0][0], frames[-1][0]
        count = sum(n for _, n in frames)
        if last <= first:
            total += count if opens <= first < closes else 0
            continue
        head = frames[0][1]
        if opens <= first < closes:
            total += head
        inside = min(last, closes) - max(first, opens)
        if inside > 0:
            total += (count - head) * inside / (last - first)
    if total <= 0 or closes <= opens:
        return None
    return total / (closes - opens)


def spans_ms(ctx, start: str, end: str) -> List[float]:
    def instant(record, key):
        if key == "first_frame":
            return record["frames"][0][0] if record.get("frames") else None
        return record.get(key)

    out = []
    for r in counted(ctx):
        a, b = instant(r, start), instant(r, end)
        if a is not None and b is not None:
            out.append((b - a) * 1e3)
    return out


def prefill_useful_share(ctx) -> Optional[float]:
    """Prompt tokens over prompt tokens plus bucket padding, for the
    prefills the engine harvested in the window."""
    opens, closes = ctx["window"]["opens"], ctx["window"]["closes"]
    prompt = sum(
        len(r["prompt_ids"]) for r in ctx["requests"]
        if r.get("prompt_ids") and opens <= r.get("engine_first", -1.0) < closes
    )
    wasted = [ctx["counters"][end]["tokens_wasted"] for end in ("open", "close")]
    padding = wasted[1].get("prefill_padding", 0) - wasted[0].get("prefill_padding", 0)
    if prompt + padding <= 0:
        return None
    return 100.0 * prompt / (prompt + padding)


def slot_occupancy(ctx) -> Optional[float]:
    """Useful decode tokens over decode steps x slots, from the engine's
    chunk log (steps, active slots, seconds) over the window."""
    a = ctx["counters"]["open"]["chunk_log_len"]
    b = ctx["counters"]["close"]["chunk_log_len"]
    chunks = ctx["chunk_log"][a:b]
    steps = sum(c[0] for c in chunks)
    if not steps:
        return None
    return 100.0 * sum(c[0] * c[1] for c in chunks) / (steps * ctx["slots"])


def _decoded_between(record, begin: float, end: float):
    """(prompt tokens, lo, hi): the request's output tokens lo..hi (the
    first is 0 and comes from the prefill) fall in [begin, end), its tokens
    spread evenly from the engine's first token to its last frame. None
    where it decoded nothing there. Frames, not the result: a request still
    decoding at the run's end has no result, and one character of a frame
    is one token."""
    first, prompt = record.get("engine_first"), record.get("prompt_ids")
    frames = record.get("frames") or []
    count = sum(n for _, n in frames)
    if first is None or prompt is None or count < 2 or frames[-1][0] <= first:
        return None
    last = frames[-1][0]
    step = (last - first) / (count - 1)
    lo = max(1, int((begin - first) / step) + 1) if begin > first else 1
    hi = min(count - 1, int((end - first) / step)) if end < last else count - 1
    return (len(prompt), lo, hi) if hi >= lo else None


def work_flops(ctx, begin: float, end: float) -> float:
    """Model flops of every prompt whose prefill the engine harvested in
    [begin, end) and of every output token it emitted there, by the
    counts of the configuration's family (``ctx["family"]``)."""
    flops, sizes, total = ctx["family"], ctx["sizes"], 0.0
    for r in ctx["requests"]:
        first, prompt = r.get("engine_first"), r.get("prompt_ids")
        if first is not None and prompt is not None and begin <= first < end:
            total += flops.prompt_flops(sizes, len(prompt))
        decoded = _decoded_between(r, begin, end)
        if decoded:
            prompt_tokens, lo, hi = decoded
            for j in range(lo, hi + 1):
                total += flops.output_token_flops(sizes, prompt_tokens + j)
    return total


def mfu(ctx) -> Optional[float]:
    """Whole step: model flops of the work done in the traced window over
    the window's WALL seconds x the chip's bf16 peak. Never over busy
    time, so it cannot pass 100%."""
    trace = ctx.get("trace")
    if not trace:
        return None
    begin, end = trace["begin"]["at"], trace["end"]["at"]
    work = work_flops(ctx, begin, end)
    if end <= begin or work <= 0:
        return None
    return 100.0 * work / ((end - begin) * ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"])


def pallas_share(ctx) -> Optional[float]:
    trace = ctx.get("trace")
    if not trace or trace["busy_s"] <= 0 or trace["kernel_s"] <= 0:
        return None
    return 100.0 * trace["kernel_s"] / trace["busy_s"]


def device_idle_share(ctx) -> Optional[float]:
    trace = ctx.get("trace")
    if not trace or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def served_between(ctx, begin: float, end: float) -> Dict[str, Any]:
    """What the engine served in [begin, end), as a family's kernel counts
    take it: ``prompts``, the lengths of the prompts whose prefill it
    harvested there; ``decode_queries``, one for every output token after
    a request's first, and ``decode_keys``, the cached keys those queries
    saw between them (the j-th token of a request sees prompt + j)."""
    prompts, queries, keys = [], 0, 0
    for r in ctx["requests"]:
        first, prompt = r.get("engine_first"), r.get("prompt_ids")
        if first is not None and prompt is not None and begin <= first < end:
            prompts.append(len(prompt))
        decoded = _decoded_between(r, begin, end)
        if decoded:
            prompt_tokens, lo, hi = decoded
            queries += hi - lo + 1
            keys += (hi - lo + 1) * prompt_tokens + (lo + hi) * (hi - lo + 1) // 2
    return {"prompts": prompts, "decode_queries": queries, "decode_keys": keys}


def kernel_roofline(ctx, kernel: str, within: str) -> Optional[float]:
    """The kernel named ``kernel`` against its roofline: the larger of its
    flops over the bf16 peak and its bytes over the HBM peak, for the work
    the traced window really served (the family's ``kernel_work``, by the
    kernel's name), over the kernel's device time inside the programs
    whose kind starts with ``within``. None where the trace holds no such
    kernel or the family counts nothing for it."""
    trace = ctx.get("trace")
    if not trace:
        return None
    kernel_s = sum(
        p["kernels"][kernel]["seconds"] for p in trace["programs"]
        if p["kind"].startswith(within) and kernel in p["kernels"]
    )
    if kernel_s <= 0:
        return None
    served = served_between(ctx, trace["begin"]["at"], trace["end"]["at"])
    counted = ctx["family"].kernel_work(ctx["sizes"], kernel, served)
    if not counted:
        return None
    work, moved = counted
    peaks = ctx["peaks"]
    least = max(work / peaks["bf16_flops_per_s"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (kernel_s * ctx["chips"])
