"""The engine's own phase spans and finished legs, laid on the device's trace.

The same ``.xplane.pb`` ``trace_reduce`` reads, anchored on the same
marker and cut to the same window, but keeping what the reduction drops:

- the ``engine.*`` host events (``langstream_tpu/runtime/tracing.py``'s
  ``phase``) with their attributes, on the profiler's clock;
- the device's program executions (``XLA Modules``) with start, end and
  name (``jit_<kind>``: the engine names its programs);
- the device's idle gaps, each cut by the phase span that covers it.

A program joins the dispatch span it was launched under by the runtime's
own ids: the device's module event carries a ``run_id``, and so does the
host event that enqueued the program; where the runtime enqueues from a
thread of its own, that event lies inside one that consumes a flow
(``_c``) whose producer (``_p``) is the launch on the engine's thread,
inside the span. A finished request (``runtime/journey.py``'s ring, on
``time.perf_counter()``) joins its prefill's dispatch span by the batch
number both carry, and the two clocks meet at the marker
(``ctx["trace"]["begin"]["at"]``). The device's events sit on a clock of
their own, a millisecond or so early: no program starts before its launch,
so the largest lead of a start over its launch is taken as the two clocks'
distance (``skew``), and the host's instants are moved onto the device's
clock by it. The window and the gaps stay as ``trace_reduce`` has them.

Returns None where there is no trace, no device plane or no ``engine.*``
annotation (a program from before the spans), so a reader leaves its
metric out and never reports 0. Parts repeat ``trace_reduce.py`` (the
planes' walk, the window): a ``benchmark`` issue may fold them.
"""

from __future__ import annotations

import os
import re
import statistics
from typing import Any, Dict, List, Optional, Tuple

from . import trace_reduce

PREFIX = "engine."
# the spans that tile the engine thread; ``engine.prefill_dispatch`` is a
# child of ``engine.admit`` and would count its time twice
CHILDREN = ("engine.prefill_dispatch",)
# an idle gap inside the emit span is the token loop's; one inside no span,
# or inside the blocking wait for work, is unexplained; every other span is
# scheduling (admission, batch and dispatch building, linger, the host's
# part of a harvest or of the wait for a chunk)
EMIT, WAIT = "engine.emit", "engine.wait_for_work"
# shorter gaps lie inside a program (``report.host_label``'s floor)
FLOOR_NS = 50e3
_MODULE = re.compile(r"^jit_([A-Za-z0-9_]+)")


def trace_path() -> Optional[str]:
    """``run.py`` puts the cache dir's ``jax`` folder in
    ``JAX_COMPILATION_CACHE_DIR``; the harness writes the trace beside it."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        return None
    return trace_reduce.find_trace(os.path.join(os.path.dirname(placed), "trace"))


def _stats(event) -> Dict[str, Any]:
    try:
        return {str(key): value for key, value in event.stats}
    except Exception:  # noqa: BLE001 - a stat that does not decode is skipped
        return {}


def kind_of(module_name: str) -> str:
    """``jit_prefill_dense(1234)`` -> ``prefill_dense``; '' for another's."""
    found = _MODULE.match(module_name)
    return found.group(1) if found else ""


def read_trace(path: str, span_s: float, chips: int = 1) -> Optional[Dict[str, Any]]:
    """Phases, programs and gaps of the window that starts at the marker
    and lasts ``span_s`` seconds; times in ns on the device's clock."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    mark_ns = None
    phases: List[Dict[str, Any]] = []
    produced: Dict[Tuple[str, str], float] = {}   # flow -> its producer's start
    consumers: Dict[str, List[Tuple[float, float, Tuple[str, str]]]] = {}
    enqueues: Dict[str, Tuple[float, str]] = {}   # run_id -> (start, line)
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for number, line in enumerate(plane.lines):
            key = f"{plane.name}/{number}"
            for event in line.events:
                name = event.name
                if name == trace_reduce.MARK:
                    mark_ns = event.start_ns if mark_ns is None else mark_ns
                    continue
                stats = _stats(event)
                if name.startswith(PREFIX):
                    phases.append({
                        "name": name, "start": event.start_ns,
                        "end": event.start_ns + event.duration_ns, "attrs": stats,
                    })
                    continue
                if "_p" in stats:
                    produced.setdefault(
                        (str(stats.get("_pt")), str(stats["_p"])), event.start_ns
                    )
                if "_c" in stats:
                    consumers.setdefault(key, []).append((
                        event.start_ns, event.start_ns + event.duration_ns,
                        (str(stats.get("_ct")), str(stats["_c"])),
                    ))
                if "run_id" in stats and "_p" in stats:
                    # a run's enqueue produces the flow its module event
                    # consumes (its completion callback, which carries the
                    # id too, consumes one)
                    enqueues.setdefault(str(stats["run_id"]), (event.start_ns, key))
    devices = devices[:chips]
    if not devices or not phases or mark_ns is None:
        return None
    lo, hi = mark_ns, mark_ns + span_s * 1e9
    programs: List[Dict[str, Any]] = []
    gaps: List[Tuple[float, float]] = []
    for plane in devices:
        for line in plane.lines:
            if line.name == "XLA Ops":
                intervals = [
                    (max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
                    for e in line.events
                    if e.start_ns + e.duration_ns > lo and e.start_ns < hi
                ]
                gaps += trace_reduce.gaps_of(intervals, lo, hi)
            elif line.name == "XLA Modules":
                for event in line.events:
                    run_id = _stats(event).get("run_id")
                    programs.append({
                        "name": event.name, "kind": kind_of(event.name),
                        "start": event.start_ns,
                        "end": event.start_ns + event.duration_ns,
                        "launched": _launched(
                            enqueues.get(str(run_id)), consumers, produced
                        ),
                    })
    phases.sort(key=lambda p: (p["start"], -p["end"]))
    programs.sort(key=lambda p: p["start"])
    skew = max(
        [p["launched"] - p["start"] for p in programs if p["launched"] is not None]
        + [0.0]
    )
    for span in phases:  # onto the device's clock
        span["start"] -= skew
        span["end"] -= skew
    _join(phases, programs, skew)
    return {
        "lo": lo, "hi": hi, "chips": len(devices), "phases": phases,
        "programs": programs, "gaps": gaps, "skew_ns": skew,
    }


def _launched(enqueue, consumers, produced) -> Optional[float]:
    """The host instant a program was launched at, from its enqueue
    ``(start, line)``: the enqueue itself, or where an event around it on
    its line consumes a flow, that flow's producer's start."""
    if enqueue is None:
        return None
    at, line = enqueue
    around = [c for c in consumers.get(line, ()) if c[0] <= at <= c[1]]
    if around:
        flow = max(around)[2]  # the innermost: the latest to start
        return produced.get(flow, at)
    return at


def _join(phases, programs, skew: float) -> None:
    """Give every dispatch span the programs launched inside it
    (``programs`` on the span, in order) and every program its span
    (``phase``: an index into ``phases``)."""
    dispatches = [
        (index, span) for index, span in enumerate(phases)
        if span["name"] in ("engine.prefill_dispatch", "engine.dispatch_decode")
    ]
    for span in phases:
        span["programs"] = []
    for program in programs:
        program["phase"] = None
        if program["launched"] is None:
            continue
        at = program["launched"] - skew
        for index, span in dispatches:
            if span["start"] <= at <= span["end"]:
                program["phase"] = index
                span["programs"].append(program)
                break


def tiling(phases) -> List[Dict[str, Any]]:
    return [p for p in phases if p["name"] not in CHILDREN]


def idle_by_phase(read: Dict[str, Any]) -> Dict[str, float]:
    """Seconds of device idle by the span that covers them: a span's name,
    ``""`` for no span, ``"inside_a_program"`` for gaps under the floor.
    The values sum to the window's idle seconds (a chip's mean)."""
    spans = tiling(read["phases"])
    out: Dict[str, float] = {}

    def add(name: str, ns: float) -> None:
        if ns > 0:
            out[name] = out.get(name, 0.0) + ns / 1e9 / read["chips"]

    cursor = 0
    for start, end in sorted(read["gaps"]):
        if end - start < FLOOR_NS:
            add("inside_a_program", end - start)
            continue
        while cursor < len(spans) and spans[cursor]["end"] <= start:
            cursor += 1
        covered, index = 0.0, cursor
        while index < len(spans) and spans[index]["start"] < end:
            span = spans[index]
            piece = min(end, span["end"]) - max(start, span["start"])
            add(span["name"], piece)
            covered += max(0.0, piece)
            index += 1
        add("", (end - start) - covered)
    return out


def idle_shares(read: Dict[str, Any]) -> Dict[str, float]:
    """The three shares of the window, in per cent; they sum to the
    device's idle share."""
    window = (read["hi"] - read["lo"]) / 1e9
    shares = {"emit": 0.0, "schedule": 0.0, "unspanned": 0.0}
    for name, seconds in idle_by_phase(read).items():
        if name == EMIT:
            key = "emit"
        elif name in ("", WAIT, "inside_a_program"):
            key = "unspanned"
        else:
            key = "schedule"
        shares[key] += 100.0 * seconds / window
    return shares


def first_token_parts(read: Dict[str, Any], legs, mark_at: float) -> List[Dict[str, Any]]:
    """For every finished leg whose prefill ran whole in the window: its
    submit-to-first-token time in ms, cut into queue (submit to slot),
    build (slot to the jit call), wait (to the program's start on the
    device), device (the program) and harvest (its end to the first token
    on the host). ``mark_at`` is the marker's instant on the legs' clock."""
    by_batch = {}
    for span in read["phases"]:
        if span["name"] == "engine.prefill_dispatch" and span["programs"]:
            by_batch[str(span["attrs"].get("batch"))] = span

    def ns(instant: float) -> float:
        return read["lo"] + (instant - mark_at) * 1e9 - read["skew_ns"]

    out = []
    for leg in legs:
        span = by_batch.get(str(leg.get("batch")))
        if span is None or leg.get("first_token") is None or leg.get("dispatched") is None:
            continue
        start = span["programs"][0]["start"]
        end = span["programs"][-1]["end"]
        if start < read["lo"] or end > read["hi"]:
            continue
        if not span["start"] - 1e6 <= ns(leg["dispatched"]) <= span["end"] + 1e6:
            continue  # another engine's batch of the same number
        submit, assigned = ns(leg["submit"]), ns(leg["assigned"])
        dispatched, first = ns(leg["dispatched"]), ns(leg["first_token"])
        out.append({
            "queue": (assigned - submit) / 1e6,
            "build": (dispatched - assigned) / 1e6,
            "wait": (start - dispatched) / 1e6,
            "device": (end - start) / 1e6,
            "harvest": (first - end) / 1e6,
            "whole": (first - submit) / 1e6,
            "trace_id": leg.get("trace_id"), "session_id": leg.get("session_id"),
        })
    return out


def decode_step_ms(read: Dict[str, Any]) -> Optional[float]:
    """Device time of the programs named as decode chunks that ran whole
    in the window, over the steps their dispatch spans give them."""
    seconds, steps = 0.0, 0
    for program in read["programs"]:
        if not program["kind"].startswith("decode_chunk") or program["phase"] is None:
            continue
        if program["start"] < read["lo"] or program["end"] > read["hi"]:
            continue
        span = read["phases"][program["phase"]]
        seconds += (program["end"] - program["start"]) / 1e9
        steps += int(span["attrs"].get("steps", 0))
    return 1e3 * seconds / steps if steps else None


def prefill_seconds(read: Dict[str, Any]) -> float:
    """Device seconds of programs named as prefills, cut to the window."""
    return sum(
        max(0.0, min(p["end"], read["hi"]) - max(p["start"], read["lo"])) / 1e9
        for p in read["programs"] if p["kind"].startswith("prefill")
    ) / read["chips"]


def of(ctx) -> Optional[Dict[str, Any]]:
    """What a metric's reader asks for, read once a run and kept on
    ``ctx``: ``read`` (:func:`read_trace`), ``legs`` (the ring), ``idle``
    (:func:`idle_shares`) and ``parts`` (:func:`first_token_parts`). None
    where there is nothing."""
    if "spans" in ctx:
        return ctx["spans"]
    ctx["spans"] = None
    trace, path = ctx.get("trace"), trace_path()
    if not trace or path is None:
        return None
    read = read_trace(path, trace["window_s"], ctx.get("chips", 1))
    if read is None:
        return None
    try:
        from langstream_tpu.runtime.journey import finished_legs
    except ImportError:  # a program from before the ring
        legs = []
    else:
        legs = finished_legs()
    ctx["spans"] = {
        "read": read, "legs": legs, "idle": idle_shares(read),
        "parts": first_token_parts(read, legs, trace["begin"]["at"]),
    }
    return ctx["spans"]


def idle_share(ctx, which: str) -> Optional[float]:
    found = of(ctx)
    return found["idle"][which] if found else None


def part_p50(ctx, part: str) -> Optional[float]:
    found = of(ctx)
    values = [p[part] for p in found["parts"]] if found else []
    return statistics.median(values) if values else None


def queue_wait_p50(ctx) -> Optional[float]:
    """Submit to slot assigned, requests submitted in the measured window."""
    found = of(ctx)
    if not found:
        return None
    opens, closes = ctx["window"]["opens"], ctx["window"]["closes"]
    values = [
        (leg["assigned"] - leg["submit"]) * 1e3 for leg in found["legs"]
        if opens <= leg["submit"] < closes
    ]
    return statistics.median(values) if values else None


def decode_step(ctx) -> Optional[float]:
    found = of(ctx)
    return decode_step_ms(found["read"]) if found else None


def prefill_busy_share(ctx) -> Optional[float]:
    found = of(ctx)
    busy = (ctx.get("trace") or {}).get("busy_s", 0.0)
    if not found or busy <= 0:
        return None
    seconds = prefill_seconds(found["read"])
    return 100.0 * seconds / busy if seconds > 0 else None
