"""The event loop's thread and the engine thread's CPU time, from inside.

The program puts three spans on the event loop's thread, through the same
``tracing.phase`` as the engine's (a ``jax.profiler.TraceAnnotation``), so
they sit in the one ``.xplane.pb`` beside the device's ops:

- ``loop.deliver``: one delivery of a hand-over (``_LoopInbox._pump``):
  ``tokens``, ``first`` (1 where it carries the request's first token),
  ``done`` (1 where it carries the result), ``trace_id``, ``cpu_ms``;
- ``loop.gateway_in``: a chat frame's synchronous part in the gateway, up
  to the produce's await: ``trace_id``;
- ``loop.gateway_out``: an answer record's frame built, up to the send's
  await: ``trace_id``, ``index`` (the record's ``stream-index``).

Every ``engine.*`` span carries ``cpu_ms`` too: the engine thread's
``time.thread_time()`` over the span.

``trace_reduce.walk`` takes no notice of the ``loop.*`` spans (they carry
no flow and no run id), so no phase, flow, program or gap sees them. Here
they are walked from the run's own ``.xplane.pb`` (:func:`walk`: the
newest under ``.cache/<cell>/trace``, and only where its marker is the
one ``ctx["trace"]`` was cut on), move onto the clock ``spans.lay`` puts
the engine's spans on (the same ``skew_ns``), and a finished leg
(``journey.finished_legs()``) joins them by its ``trace_id``, its instants
moved the way ``spans.first_token_parts`` moves them. Every reader returns
None, never 0, where the trace has no ``loop.*`` span or no ``cpu_ms`` (a
program from before them).
"""

from __future__ import annotations

import glob
import os
import statistics
from typing import Any, Dict, List, Optional

from . import spans, trace_reduce

LOOP = "loop."
# where ``run.py`` keeps a cell's traced run (``harness.traced``)
TRACES = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache")

# the engine thread's spans that should be pure host work; the ones that
# block by design (wait_for_work, linger, wait_chunk, harvest_prefills)
# are left out
SCHEDULE = ("engine.admit", "engine.dispatch_decode", "engine.emit")


def _inside(span, read) -> bool:
    return read["lo"] <= span["start"] and span["end"] <= read["hi"]


def walk(path: str) -> Dict[str, Any]:
    """The host planes' marker (``mark_ns``) and ``loop.*`` events
    (``loop``: name, start, end and attributes in ns on the profiler's
    clock, and the ``line`` that holds each)."""
    import jax

    mark_ns, loop = None, []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for number, line in enumerate(plane.lines):
            for event in line.events:
                if event.name == trace_reduce.MARK and mark_ns is None:
                    mark_ns = event.start_ns
                elif event.name.startswith(LOOP):
                    loop.append({
                        "name": event.name, "start": event.start_ns,
                        "end": event.start_ns + event.duration_ns,
                        "attrs": trace_reduce._stats(event),
                        "line": f"{plane.name}/{number}",
                    })
    return {"mark_ns": mark_ns, "loop": loop}


def _walked(ctx, read) -> List[Dict[str, Any]]:
    """The run's ``loop.*`` events: ``ctx["loop_walk"]`` where it is
    given, else :func:`walk` of the newest traced run, where its marker is
    the one ``read`` was cut on (``lo``); [] where there is none."""
    if "loop_walk" not in ctx:
        found = sorted(
            glob.glob(os.path.join(TRACES, "*", "trace", "**", "*.xplane.pb"),
                      recursive=True),
            key=os.path.getmtime,
        )
        walked = walk(found[-1]) if found else None
        ctx["loop_walk"] = (
            walked["loop"] if walked and walked["mark_ns"] == read["lo"] else []
        )
    return ctx["loop_walk"]


def of(ctx) -> Optional[Dict[str, Any]]:
    """The run's ``loop.*`` spans on the device's clock (``loop``, in
    order of start, each inside the window or not: ``whole``), with
    ``spans.of``'s ``read`` and ``legs`` and ``ns`` (a leg's instant on
    the same clock); kept on ``ctx``. None where there is no such span."""
    if "loop_spans" in ctx:
        return ctx["loop_spans"]
    ctx["loop_spans"] = None
    found = spans.of(ctx)
    if not found:
        return None
    read = found["read"]
    walked = _walked(ctx, read)
    if not walked:
        return None
    skew, mark_at = read["skew_ns"], ctx["trace"]["begin"]["at"]
    loop = sorted(
        (
            dict(span, start=span["start"] - skew, end=span["end"] - skew)
            for span in walked
        ),
        key=lambda span: span["start"],
    )
    for span in loop:
        span["whole"] = _inside(span, read)

    def ns(instant: float) -> float:
        return read["lo"] + (instant - mark_at) * 1e9 - skew

    ctx["loop_spans"] = {"read": read, "legs": found["legs"], "loop": loop, "ns": ns}
    return ctx["loop_spans"]


def schedule_offcpu_share(ctx) -> Optional[float]:
    """100 x (wall - CPU) / wall over the window's ``engine.admit``,
    ``engine.dispatch_decode`` and ``engine.emit`` spans: the share of the
    schedule's host work the engine thread spent off the CPU (waiting
    for the GIL, or a blocking call inside)."""
    found = spans.of(ctx)
    if not found:
        return None
    read = found["read"]
    wall_ms = cpu_ms = 0.0
    for span in read["phases"]:
        if span["name"] in SCHEDULE and "cpu_ms" in span["attrs"] and _inside(span, read):
            wall_ms += (span["end"] - span["start"]) / 1e6
            cpu_ms += float(span["attrs"]["cpu_ms"])
    return 100.0 * (wall_ms - cpu_ms) / wall_ms if wall_ms > 0 else None


def loop_us_per_token(ctx) -> Optional[float]:
    """Microseconds of the window's ``loop.deliver`` spans over the tokens
    they carried."""
    found = of(ctx)
    delivers = [
        span for span in (found["loop"] if found else ())
        if span["name"] == "loop.deliver" and span["whole"]
    ]
    tokens = sum(int(span["attrs"].get("tokens", 0)) for span in delivers)
    if not tokens:
        return None
    return sum(span["end"] - span["start"] for span in delivers) / 1e3 / tokens


def _by_trace(found, name: str, **attrs) -> Dict[str, Dict[str, Any]]:
    """The first span of ``name`` inside the window for each trace id,
    among those whose attributes read as ``attrs``."""
    out: Dict[str, Dict[str, Any]] = {}
    for span in found["loop"]:
        if span["name"] != name or not span["whole"]:
            continue
        if any(str(span["attrs"].get(key)) != str(value) for key, value in attrs.items()):
            continue
        trace_id = str(span["attrs"].get("trace_id", ""))
        if trace_id:
            out.setdefault(trace_id, span)
    return out


def _legs_at(found, by_trace, instant: str):
    """(span, the leg's ``instant`` on the spans' clock) for every
    finished leg that has the instant and a span in ``by_trace``."""
    for leg in found["legs"]:
        span = by_trace.get(str(leg.get("trace_id") or ""))
        if span is not None and leg.get(instant) is not None:
            yield span, found["ns"](leg[instant])


def _p50(values: List[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def gateway_to_engine_p50(ctx) -> Optional[float]:
    """A request's ``loop.gateway_in`` start to its leg's ``submit``
    (``engine.generate``), in ms: gateway, questions topic, runner,
    agent."""
    found = of(ctx)
    if not found:
        return None
    frames = _by_trace(found, "loop.gateway_in")
    return _p50([
        (submit - frame["start"]) / 1e6
        for frame, submit in _legs_at(found, frames, "submit")
    ])


def inbox_wait_p50(ctx) -> Optional[float]:
    """A leg's ``first_token`` (the engine thread's harvest) to the start
    of the ``loop.deliver`` that carries it, in ms: the first token's
    wait for the loop's thread (the rest of the harvest's span, the
    inbox's queue, the GIL)."""
    found = of(ctx)
    if not found:
        return None
    firsts = _by_trace(found, "loop.deliver", first=1)
    return _p50([
        (deliver["start"] - first) / 1e6
        for deliver, first in _legs_at(found, firsts, "first_token")
    ])


def deliver_to_frame_p50(ctx) -> Optional[float]:
    """The start of a request's first ``loop.deliver`` to the end of its
    ``loop.gateway_out`` of ``index`` 0 (the first frame built, its send
    next), in ms: the provider, the chunk batcher, the answers topic and
    the gateway."""
    found = of(ctx)
    if not found:
        return None
    frames = _by_trace(found, "loop.gateway_out", index=0)
    values = [
        (frames[trace_id]["end"] - deliver["start"]) / 1e6
        for trace_id, deliver in _by_trace(found, "loop.deliver", first=1).items()
        if trace_id in frames
    ]
    return _p50(values)
