"""From the profiler's ``.xplane.pb`` to the few numbers the metrics read.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO op and ``XLA Modules`` one per executed program.
Busy time is the UNION of the op intervals; everything is clipped to the
span between the harness's own marker (``benchmark.mark``, a host
annotation at a known host instant) and the traced window's end, so the
profiler's start-up and wind-down are left out.

An op event's name is its HLO text. A Pallas kernel is a custom call whose
target is ``tpu_custom_call``; ``while``, ``conditional`` and ``call`` only
hold other ops and are left out of per-op sums. Programs are told apart by
structure, not by XLA's changing names (``jit_run(<hash>)``): a program
execution that holds more kernel calls than the model has layers runs
several decode steps (a decode chunk); one that holds at most one a layer
is a prefill.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

MARK = "benchmark.mark"


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


def reduce_trace(path: str, span_s: float, layers: int, chips: int = 1) -> Optional[Dict[str, Any]]:
    """``span_s``: seconds from the marker to the traced window's end, by
    the host's clock. Returns None where the trace holds no device op."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    mark_ns = None
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:") and mark_ns is None:
            for line in plane.lines:
                for event in line.events:
                    if event.name == MARK:
                        mark_ns = event.start_ns
                        break
                if mark_ns is not None:
                    break
    devices = devices[:chips]
    if not devices:
        return None
    per_device = []
    for plane in devices:
        ops, modules = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events
                ]
            elif line.name == "XLA Modules":
                modules = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events
                ]
        per_device.append((ops, modules))
    if not any(ops for ops, _ in per_device):
        return None
    first = min(ops[0][0] for ops, _ in per_device if ops)
    lo = mark_ns if mark_ns is not None else first
    hi = lo + span_s * 1e9
    busy, kernel_s = 0.0, 0.0
    op_seconds: Dict[str, float] = {}
    all_gaps: List[Tuple[float, float]] = []
    programs: List[Dict[str, Any]] = []
    for ops, modules in per_device:
        clipped = [
            (max(s, lo), min(e, hi), name)
            for s, e, name in ops if e > lo and s < hi
        ]
        intervals = [(s, e) for s, e, _ in clipped]
        busy += union_seconds(intervals)
        all_gaps += gaps_of(intervals, lo, hi)
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
                kernels.append((s, e))
        kernels.sort()
        for s, e, name in modules:
            if e <= lo or s >= hi:
                continue
            inside = [(ks, ke) for ks, ke in kernels if ks >= s and ke <= e]
            programs.append({
                "name": name,
                "seconds": (min(e, hi) - max(s, lo)) / 1e9,
                "whole": s >= lo and e <= hi,
                "kernel_calls": len(inside),
                "kernel_seconds": sum(ke - ks for ks, ke in inside) / 1e9,
                "decode": len(inside) > layers,
            })
    count = len(per_device)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:200]
    return {
        "window_s": span_s,
        "busy_s": busy / count,
        "kernel_s": kernel_s / count,
        "op_seconds": op_seconds,
        "programs": programs,
        "gaps": [((s - lo) / 1e9, (e - lo) / 1e9) for s, e in longest],
        "gap_total_s": sum(e - s for s, e in all_gaps) / 1e9 / count,
        "marked": mark_ns is not None,
    }


def breakdown(reduced: Dict[str, Any], label) -> Dict[str, Any]:
    """Top device ops, and the idle gaps summed by what the host was
    doing (``label(start_s, end_s)`` in seconds after the marker)."""
    top = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])[:10]
    by_label: Dict[str, float] = {}
    listed = 0.0
    for start, end in reduced["gaps"]:
        name = label(start, end)
        by_label[name] = by_label.get(name, 0.0) + (end - start)
        listed += end - start
    rest = reduced["gap_total_s"] - listed
    if rest > 0:
        by_label["gaps_shorter_than_the_200_longest"] = rest
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
    return {
        "device_ops": [[name, seconds] for name, seconds in top],
        "idle_gaps": [[name, seconds] for name, seconds in gaps],
    }
