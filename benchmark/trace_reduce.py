"""From the profiler's ``.xplane.pb`` to the few numbers the metrics read.

One walk of the planes (:func:`walk`) and one window serve every reader.
Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO op and ``XLA Modules`` one per executed program.
Host planes hold the harness's own marker (``benchmark.mark``, a host
annotation at a known host instant), the engine's ``engine.*`` phase spans
and the runtime's launch events, which ``spans.py`` lays on the device's
programs. Busy time is the UNION of the op intervals; everything is
clipped to the span between the marker and the traced window's end, so
the profiler's start-up and wind-down are left out.

An op event's name is its HLO text. A Pallas kernel is a custom call whose
target is ``tpu_custom_call``, and the instruction's name is the name the
program gave the kernel (``%flash_decode.6`` is ``flash_decode``);
``while``, ``conditional`` and ``call`` only hold other ops and are left
out of per-op sums. A program is told by its name too: the engine names
every jitted program ``jit_<kind>_<layout>`` (``decode_chunk_dense``,
``prefill_paged``), and :func:`kind_of` reads the kind off the module
event. Nothing here counts layers or kernel calls to tell one program
from another.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

MARK = "benchmark.mark"
# the engine's phase spans (``langstream_tpu/runtime/tracing.py``'s ``phase``)
PHASES = "engine."


def find_trace(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


_OPCODE = re.compile(r"= .*?\s([a-z][a-z0-9\-]*)\(")
# ops that only hold other ops: their time is their body's, counted there
CONTAINERS = ("while", "conditional", "call")


def opcode(name: str) -> str:
    """An op event's name is its HLO text, ``%id = shape opcode(...)``."""
    found = _OPCODE.search(name)
    return found.group(1) if found else ""


def is_kernel(name: str) -> bool:
    """A Pallas (Mosaic) kernel: a custom call whose target is
    ``tpu_custom_call``, whatever XLA named the instruction."""
    return 'custom_call_target="tpu_custom_call"' in name


def short(name: str) -> str:
    """``%fusion.246 = bf16[32,28,128]{...} fusion(...)`` as
    ``fusion.246 fusion bf16[32,28,128]``."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{", 1)[0].strip()
    kind = "pallas-kernel" if is_kernel(name) else opcode(name)
    return f"{head.lstrip('%')} {kind} {shape}"[:96]


_SUFFIX = re.compile(r"\.\d+$")
_MODULE = re.compile(r"^jit_([A-Za-z0-9_]+)")


def kernel_name(name: str) -> str:
    """``%flash_decode.6 = ... custom-call(...)`` as ``flash_decode``: the
    name the program gave the kernel, without XLA's numbering."""
    return _SUFFIX.sub("", name.partition(" = ")[0].lstrip("%"))


def kind_of(module_name: str) -> str:
    """``jit_prefill_dense(1234)`` -> ``prefill_dense``; '' for another's."""
    found = _MODULE.match(module_name)
    return found.group(1) if found else ""


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    total, edge = 0.0, None
    for start, end in sorted(intervals):
        if edge is None or start > edge:
            total += end - start
            edge = end
        elif end > edge:
            total += end - edge
            edge = end
    return total / 1e9


def gaps_of(intervals: List[Tuple[float, float]], lo: float, hi: float):
    """Idle gaps (start_ns, end_ns) between the union's pieces in [lo, hi]."""
    out, edge = [], lo
    for start, end in sorted(intervals):
        if start > edge:
            out.append((edge, start))
        edge = max(edge, end)
    if hi > edge:
        out.append((edge, hi))
    return out


def _stats(event) -> Dict[str, Any]:
    try:
        return {str(key): value for key, value in event.stats}
    except Exception:  # noqa: BLE001 - a stat that does not decode is skipped
        return {}


def walk(path: str, chips: int = 1) -> Optional[Dict[str, Any]]:
    """The one walk of the planes. ``devices``: for each of the first
    ``chips`` device planes its op events ``(start, end, name)`` and its
    module events ``(start, end, name, run_id)``, in ns. From the host
    planes: the marker's instant (``mark_ns``), the ``engine.*`` spans
    with their attributes (``phases``) and what joins a program to its
    launch (``flows``: a flow's producer's start, the consumers on each
    line, and the enqueue of each ``run_id``). None without a device."""
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
            if len(devices) >= chips:
                continue
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events
                    ]
                elif line.name == "XLA Modules":
                    modules = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name,
                         _stats(e).get("run_id"))
                        for e in line.events
                    ]
            devices.append((ops, modules))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for number, line in enumerate(plane.lines):
            key = f"{plane.name}/{number}"
            for event in line.events:
                name = event.name
                if name == MARK:
                    mark_ns = event.start_ns if mark_ns is None else mark_ns
                    continue
                stats = _stats(event)
                if name.startswith(PHASES):
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
    if not devices:
        return None
    return {
        "mark_ns": mark_ns, "phases": phases, "devices": devices,
        "flows": {"produced": produced, "consumers": consumers, "enqueues": enqueues},
    }


def reduce_trace(path: str, span_s: float, chips: int = 1) -> Optional[Dict[str, Any]]:
    """``span_s``: seconds from the marker to the traced window's end, by
    the host's clock. Returns None where the trace holds no device op.

    ``programs`` holds every program execution of the trace, in order of
    start: ``name``, ``kind`` (:func:`kind_of`), ``start`` and ``end`` in
    ns, ``seconds`` inside the window, ``whole`` (it ran inside it),
    ``run_id`` and ``kernels``, which gives for each kernel by its name
    the ``calls`` and ``seconds`` of it inside the program and the window.
    ``gaps`` are the device's idle gaps ``(start_ns, end_ns)`` between
    ``lo`` and ``hi``, the window's two ends on the device's clock."""
    walked = walk(path, chips)
    if walked is None or not any(ops for ops, _ in walked["devices"]):
        return None
    mark_ns = walked["mark_ns"]
    first = min(ops[0][0] for ops, _ in walked["devices"] if ops)
    lo = mark_ns if mark_ns is not None else first
    hi = lo + span_s * 1e9
    busy, kernel_s = 0.0, 0.0
    op_seconds: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    programs: List[Dict[str, Any]] = []
    for ops, modules in walked["devices"]:
        clipped = [
            (max(s, lo), min(e, hi), name)
            for s, e, name in ops if e > lo and s < hi
        ]
        intervals = [(s, e) for s, e, _ in clipped]
        busy += union_seconds(intervals)
        gaps += gaps_of(intervals, lo, hi)
        kernels = []
        labels: Dict[str, str] = {}
        for s, e, name in clipped:
            label = labels.get(name)
            if label is None:
                label = labels[name] = (
                    "" if opcode(name) in CONTAINERS else short(name)
                )
            if not label:
                continue
            op_seconds[label] = op_seconds.get(label, 0.0) + (e - s) / 1e9
            if is_kernel(name):
                kernel_s += (e - s) / 1e9
                kernels.append((s, e, kernel_name(name)))
        kernels.sort()
        for s, e, name, run_id in modules:
            inside: Dict[str, Dict[str, float]] = {}
            for ks, ke, kernel in kernels:
                if ks >= s and ke <= e:
                    found = inside.setdefault(kernel, {"calls": 0, "seconds": 0.0})
                    found["calls"] += 1
                    found["seconds"] += ke - ks
            for found in inside.values():
                found["seconds"] /= 1e9
            programs.append({
                "name": name, "kind": kind_of(name), "start": s, "end": e,
                "seconds": max(0.0, min(e, hi) - max(s, lo)) / 1e9,
                "whole": s >= lo and e <= hi,
                "run_id": run_id, "kernels": inside,
            })
    programs.sort(key=lambda p: p["start"])
    count = len(walked["devices"])
    return {
        "window_s": span_s,
        "busy_s": busy / count,
        "kernel_s": kernel_s / count,
        "op_seconds": op_seconds,
        "programs": programs,
        "gaps": gaps,
        "gap_total_s": sum(e - s for s, e in gaps) / 1e9 / count,
        "marked": mark_ns is not None,
        "lo": lo, "hi": hi, "chips": count,
        "phases": walked["phases"], "flows": walked["flows"],
    }


# the name of the device's idle seconds that no phase span covers
NO_SPAN = "no_span"


def breakdown(reduced: Dict[str, Any], idle: Optional[Dict[str, float]]) -> Dict[str, Any]:
    """Top device ops by name, and the device's idle seconds by the
    engine's phase span that covers them (``spans.idle_by_phase``: a
    span's name, ``no_span`` for none, ``inside_a_program`` for gaps under
    the floor; ``idle`` None where the trace holds no span). The idle
    entries sum to the window's idle seconds."""
    top = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])[:10]
    if idle is None:
        idle = {"": reduced["gap_total_s"]}
    gaps = sorted(
        ((name or NO_SPAN, seconds) for name, seconds in idle.items()),
        key=lambda kv: -kv[1],
    )
    if len(gaps) > 10:
        gaps = gaps[:9] + [("other_spans", sum(seconds for _, seconds in gaps[9:]))]
    return {
        "device_ops": [[name, seconds] for name, seconds in top],
        "idle_gaps": [[name, seconds] for name, seconds in gaps],
    }
