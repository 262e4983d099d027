"""From a run's raw records to the result line: the comparison with the
plain reference, the metrics by their readers, the device record."""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

from . import harness, measure, spans, trace_reduce
from .reference import compare


def context(cell, raw, device) -> Dict[str, Any]:
    traffic = cell["traffic_file"]
    return {
        "counted_by": traffic["counted_by"],
        "requests": raw["records"],
        "window": raw["window"],
        "setup_s": raw["setup_s"],
        "counters": raw["counters"],
        "chunk_log": raw["chunk_log"],
        "slots": raw["slots"],
        "decode_chunk": raw["decode_chunk"],
        "chips": cell["chips"],
        "limit_s": float(traffic["request_limit_seconds"]),
        "family": cell["family"],
        "sizes": cell["family"].Sizes(cell["config_file"]),
        "peaks": harness.peaks_for(device["kind"]) if device["platform"] == "tpu" else None,
        "trace": None,
    }


def add_trace(ctx, raw) -> None:
    info = raw.get("trace")
    if not info:
        return
    path = trace_reduce.find_trace(info["dir"])
    if path is None:
        return
    span = info["end"]["at"] - info["begin"]["at"]
    reduced = trace_reduce.reduce_trace(path, span, ctx["chips"])
    if reduced is None:
        return
    reduced["begin"], reduced["end"] = info["begin"], info["end"]
    ctx["trace"] = reduced


# the numbers of the comparison with the reference; one is compared where
# the configuration's file gives it a limit
NUMBERS = ("max_logit_gap", "mean_logit_gap")


def compare_with_reference(cell, raw, seed: int, lowers=()) -> Dict[str, Any]:
    """Run once the window has closed, the peak has been read and the
    program's state is freed. Returns the comparison's numbers for what the
    program served (``program``) and, for a tool, for each lower precision
    of ``lowers`` put in the program's place on the same prompts and
    tokens (``control_<lower>``); ``None`` where nothing finished."""
    config_file, family = cell["config_file"], cell["family"]
    sizes = family.Sizes(config_file)
    finished = [
        r for r in raw["records"]
        if r.get("output_ids") and "done" in r and "error" not in r
        and r["done"] >= raw["window"]["opens"]
    ]
    sample = compare.draw_sample(
        finished, seed, int(config_file["correct"]["sample_requests"])
    )
    if not sample:
        return {"program": None}
    began = time.perf_counter()
    harness.release()
    weights = family.make_weights(sizes, raw["weights_seed"])
    pad_to = int(config_file["globals"]["max-seq-len"])
    out = {"program": compare.gaps(family, sizes, weights, sample, pad_to)}
    out["reference_s"] = time.perf_counter() - began
    for lower in lowers:
        out["control_" + lower] = compare.gaps(family, sizes, weights, sample, pad_to, lower)
    return out


def judge(cell, got: Optional[Dict[str, Any]], failed: int, ended_oddly: int,
          agent_errors: int):
    """Every number compared beside its limit, and whether all hold.
    ``got`` is what ``compare.gaps`` read: the program's in a run, a
    control's where a tool puts one in the program's place."""
    limits = cell["config_file"]["correct"]
    checks = {
        name: {"value": got[name] if got else None, "limit": limits[name]}
        for name in NUMBERS if name in limits
    }
    checks["requests_failed"] = {"value": failed, "limit": 0}
    checks["requests_ended_oddly"] = {"value": ended_oddly, "limit": 0}
    checks["agent_errors"] = {"value": agent_errors, "limit": 0}
    correct = all(
        pair["value"] is not None and pair["value"] <= pair["limit"]
        for pair in checks.values()
    )
    checks["requests_compared"] = {
        "value": len(got["prompt_lengths"]) if got else 0, "limit_at_least": 1,
    }
    return checks, correct


def result_line(cell, raw, seed: int, trace: bool, device) -> Dict[str, Any]:
    ctx = context(cell, raw, device)
    counted = measure.counted(ctx)
    failed = [r for r in counted if "error" in r or "done" not in r]
    bad_finish = [
        r for r in counted
        if "done" in r and r.get("finish") not in ("length", "stop")
    ]
    agent_errors = sum(raw["agent_errors"].values())
    compared = compare_with_reference(cell, raw, seed)
    if trace:
        add_trace(ctx, raw)
    metrics: Dict[str, Any] = {}
    for metric in cell["per_layer" if trace else "end_to_end"]:
        reader = harness.load_module("metrics", metric["name"])
        value = reader.read(ctx)
        if value is not None:
            metrics[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    program = compared["program"]
    checks, correct = judge(cell, program, len(failed), len(bad_finish), agent_errors)
    device_record = dict(device, memory_peak_bytes=raw["memory_peak_bytes"])
    line: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": len(counted),
        "failed": len(failed),
        "metrics": metrics,
        "device": device_record,
        "workload": cell["name"],
        "seed": seed,
        "window_s": raw["window"]["closes"] - raw["window"]["opens"],
    }
    if trace and ctx["trace"]:
        device_record["busy_s"] = ctx["trace"]["busy_s"]
        device_record["window_s"] = ctx["trace"]["window_s"]
        found = spans.of(ctx)
        line["breakdown"] = trace_reduce.breakdown(
            ctx["trace"], spans.idle_by_phase(found["read"]) if found else None
        )
    line["built_in_window"] = {
        "programs": len(raw["built_in_window"]), "seconds": sum(raw["built_in_window"]),
    }
    line["reference"] = dict(
        {k: v for k, v in (program or {}).items() if k not in checks and k != "each_request"},
        reference_s=compared.get("reference_s"),
    )
    line["compared"] = checks
    return line


def run_summary(raw, line) -> Dict[str, Any]:
    """What a look at one run needs, kept small (``.cache/<workload>/
    last_run.json``, overwritten by every run): the result's line, every
    request's instants, the engine's chunk log and the tokens delivered in
    every half second from the window's open."""
    opens = raw["window"]["opens"]
    requests, bins = [], {}
    for r in raw["records"]:
        if "frames" not in r:  # laid out for a closed loop, never sent
            continue
        frames = r["frames"]
        for at, tokens in frames:
            spot = int(2 * (at - opens))
            bins[spot] = bins.get(spot, 0) + tokens
        requests.append({
            "index": r["index"], "prompt_tokens": len(r.get("prompt_ids") or ()),
            "tokens": sum(n for _, n in frames),
            **{key: r[key] - opens for key in (
                "due", "sent", "engine_submit", "engine_first", "done", "failed_at",
            ) if r.get(key) is not None},
            **({"first_frame": frames[0][0] - opens, "last_frame": frames[-1][0] - opens}
               if frames else {}),
            **({"error": r["error"]} if "error" in r else {}),
        })
    return {
        "line": line,
        "window_s": raw["window"]["closes"] - opens,
        "requests": requests,
        "chunk_log": [list(c) for c in raw["chunk_log"]],
        "tokens_per_half_second": sorted(bins.items()),
    }


def say_compared(device, line) -> None:
    for name, pair in line["compared"].items():
        limit = pair.get("limit", pair.get("limit_at_least"))
        harness.say(device, f"compared {name}: {pair['value']} (limit {limit})")
    harness.say(device, f"programs built inside the window: {line['built_in_window']}")
    harness.say(device, f"correct: {line['correct']}")
