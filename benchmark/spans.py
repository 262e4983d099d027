"""The engine's own phase spans and finished legs, laid on the device's trace.

``trace_reduce.reduce_trace`` walks the ``.xplane.pb`` once, anchors it on
the marker and cuts it to the window; this module takes what that walk
kept beside the device's ops:

- the ``engine.*`` host events (``langstream_tpu/runtime/tracing.py``'s
  ``phase``) with their attributes, on the profiler's clock;
- the device's program executions (``XLA Modules``) with start, end and
  kind (``jit_<kind>``: the engine names its programs);
- the device's idle gaps, each cut here by the phase span that covers it.

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
clock by it.

:func:`lay` returns None where there is no trace, no marker or no
``engine.*`` annotation (a program from before the spans), so a reader
leaves its metric out and never reports 0.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional

from . import trace_reduce

# the spans that tile the engine thread; ``engine.prefill_dispatch`` is a
# child of ``engine.admit`` and would count its time twice
CHILDREN = ("engine.prefill_dispatch",)
# an idle gap inside the emit span is the token loop's; one inside no span,
# or inside the blocking wait for work, is unexplained; every other span is
# scheduling (admission, batch and dispatch building, linger, the host's
# part of a harvest or of the wait for a chunk)
EMIT, WAIT = "engine.emit", "engine.wait_for_work"
# shorter gaps lie inside a program
FLOOR_NS = 50e3


def lay(reduced: Optional[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """Lay the reduced trace's phase spans on its programs, once: every
    program gains ``launched`` (the host instant of its launch) and
    ``phase`` (its dispatch span), the spans move onto the device's clock
    (``skew_ns``) and gain their ``programs``. Returns ``reduced``; times
    in ns on the device's clock."""
    if not reduced or not reduced["marked"] or not reduced["phases"]:
        return None
    if "skew_ns" in reduced:
        return reduced
    phases, programs, flows = reduced["phases"], reduced["programs"], reduced["flows"]
    for program in programs:
        program["launched"] = _launched(
            flows["enqueues"].get(str(program["run_id"])),
            flows["consumers"], flows["produced"],
        )
    phases.sort(key=lambda p: (p["start"], -p["end"]))
    skew = max(
        [p["launched"] - p["start"] for p in programs if p["launched"] is not None]
        + [0.0]
    )
    for span in phases:  # onto the device's clock
        span["start"] -= skew
        span["end"] -= skew
    _join(phases, programs, skew)
    reduced["skew_ns"] = skew
    return reduced


def read_trace(path: str, span_s: float, chips: int = 1) -> Optional[Dict[str, Any]]:
    """Phases, programs and gaps of the window that starts at the marker
    and lasts ``span_s`` seconds, from a trace's file (a test's or a
    tool's way in; a run reduces its trace once, in ``report.add_trace``)."""
    return lay(trace_reduce.reduce_trace(path, span_s, chips))


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
        if not program["whole"]:
            continue
        span = read["phases"][program["phase"]]
        seconds += (program["end"] - program["start"]) / 1e9
        steps += int(span["attrs"].get("steps", 0))
    return 1e3 * seconds / steps if steps else None


def prefill_seconds(read: Dict[str, Any]) -> float:
    """Device seconds of programs named as prefills, cut to the window."""
    return sum(
        p["seconds"] for p in read["programs"] if p["kind"].startswith("prefill")
    ) / read["chips"]


def of(ctx) -> Optional[Dict[str, Any]]:
    """What a metric's reader asks for, laid once a run and kept on
    ``ctx``: ``read`` (the run's reduced trace, :func:`lay`), ``legs``
    (the ring), ``idle`` (:func:`idle_shares`) and ``parts``
    (:func:`first_token_parts`). None where there is nothing."""
    if "spans" in ctx:
        return ctx["spans"]
    ctx["spans"] = None
    trace = ctx.get("trace")
    read = lay(trace)
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
