"""Step-level tracing + on-demand TPU profiling.

The reference has NO tracing subsystem (SURVEY §5: "Tracing / profiling:
ABSENT" — observability there is Prometheus counters + a periodic stats
dump, AgentRunner.java:598-618). This is a net-new subsystem of the TPU
build, in two layers:

1. **Span tracing** (any platform): lightweight in-process spans on
   ONE clock (``time.perf_counter``; wall time is that plus
   :data:`CLOCK_OFFSET`, taken once a process), parent links, and
   per-record attributes, kept in a bounded ring buffer per
   :class:`Tracer` and exportable as Chrome ``trace_event`` JSON (load
   in ``chrome://tracing`` / Perfetto). The runner wraps each hot-loop
   phase (read / process / write / commit) in spans when given a tracer.

2. **XLA device profiling** (TPU/CPU): :func:`profile` wraps
   ``jax.profiler.trace`` to capture an xplane trace of everything the
   devices ran — the tool for MXU utilization and HBM stalls. Written
   to a TensorBoard-compatible directory.

:func:`phase` joins the two: one call site a boundary enters a
``jax.profiler.TraceAnnotation`` (so any profiler session shows the span
on the host's lane beside the device's ops, on the profiler's clock) and,
when the component's tracer is enabled, records the same interval as a
:class:`Span`.

Overhead when disabled: a single ``if`` per call site (module-level
no-op tracer).
"""

from __future__ import annotations

import atexit
import contextlib
import contextvars
import glob
import json
import os
import threading
import time
import uuid
from collections import deque
from typing import Any, Deque, Dict, Iterator, List, Optional, Sequence

# the one wire-level trace-context contract: the gateway (or any other
# edge) stamps this record header on ingress; the runner re-attaches it
# on every emitted record so it survives topic hops; the engine tags its
# per-request spans with it. See docs/observability.md.
TRACE_ID_HEADER = "langstream-trace-id"


# the process's one clock is ``time.perf_counter()``; where an instant
# has to leave the process (the cross-replica journey ledger, the Chrome
# dump's ``ts``) its wall time is that plus this offset, taken once
CLOCK_OFFSET = time.time() - time.perf_counter()


def wall(instant: float) -> float:
    """Wall time (epoch seconds) of a ``time.perf_counter()`` instant."""
    return instant + CLOCK_OFFSET


def new_trace_id() -> str:
    return uuid.uuid4().hex


def trace_dir() -> str:
    """Directory for per-process Chrome-trace dumps; empty = tracing off
    (``get_tracer`` then hands out the shared no-op tracer)."""
    return os.environ.get("LANGSTREAM_TRACE_DIR", "")


class Span:
    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "start_ns",
        "duration_ns", "attributes",
    )

    def __init__(
        self,
        name: str,
        trace_id: str,
        span_id: int,
        parent_id: Optional[int],
        attributes: Optional[Dict[str, Any]] = None,
    ) -> None:
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start_ns = time.perf_counter_ns()
        self.duration_ns: Optional[int] = None
        self.attributes: Dict[str, Any] = attributes or {}

    @property
    def start_wall(self) -> float:
        return wall(self.start_ns / 1e9)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start": self.start_wall,
            "duration_ms": (
                None if self.duration_ns is None else self.duration_ns / 1e6
            ),
            "attributes": self.attributes,
        }


class Tracer:
    """Per-component span recorder with a bounded buffer."""

    def __init__(self, component: str, max_spans: int = 4096) -> None:
        self.component = component
        self.enabled = True
        self._spans: Deque[Span] = deque(maxlen=max_spans)
        self._lock = threading.Lock()
        self._counter = 0
        # ContextVar, not threading.local: the runner opens spans around
        # awaits in concurrent asyncio tasks on ONE event-loop thread —
        # a thread-local "current span" would cross-link unrelated tasks
        self._current: "contextvars.ContextVar[Optional[Span]]" = (
            contextvars.ContextVar(f"span_{component}", default=None)
        )

    def _next_id(self) -> int:
        with self._lock:
            self._counter += 1
            return self._counter

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        trace_id: str = "",
        **attributes: Any,
    ) -> Iterator[Span]:
        """Record a span; nests under the current thread's open span."""
        if not self.enabled:
            yield _NOOP_SPAN
            return
        parent = self._current.get()
        span = Span(
            name=name,
            trace_id=trace_id or (parent.trace_id if parent else ""),
            span_id=self._next_id(),
            parent_id=parent.span_id if parent else None,
            attributes=attributes,
        )
        token = self._current.set(span)
        try:
            yield span
        finally:
            span.duration_ns = time.perf_counter_ns() - span.start_ns
            self._current.reset(token)
            with self._lock:
                self._spans.append(span)

    def event(
        self,
        name: str,
        duration_s: float,
        *,
        start: float,
        trace_id: str = "",
        **attributes: Any,
    ) -> None:
        """Record an already-completed span from instants taken
        elsewhere (a request's stages are known only once it finishes).
        ``start`` is its ``time.perf_counter()`` instant."""
        if not self.enabled:
            return
        span = Span(
            name=name,
            trace_id=trace_id,
            span_id=self._next_id(),
            parent_id=None,
            attributes=attributes,
        )
        span.start_ns = int(start * 1e9)
        span.duration_ns = max(0, int(duration_s * 1e9))
        with self._lock:
            self._spans.append(span)

    def spans(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [s.to_dict() for s in self._spans]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    # ------------------------------------------------------------------ #
    # export
    # ------------------------------------------------------------------ #
    def chrome_trace(self) -> List[Dict[str, Any]]:
        """Chrome trace_event "X" (complete) events — open the JSON in
        chrome://tracing or Perfetto."""
        events = []
        with self._lock:
            spans = list(self._spans)
        for span in spans:
            if span.duration_ns is None:
                continue
            events.append({
                "name": span.name,
                "cat": self.component,
                "ph": "X",
                "ts": span.start_wall * 1e6,
                "dur": span.duration_ns / 1e3,
                "pid": 0,
                "tid": span.parent_id or span.span_id,
                "args": {"trace_id": span.trace_id, **span.attributes},
            })
        return events

    def dump(self, path: str) -> None:
        with open(path, "w") as fh:
            json.dump({"traceEvents": self.chrome_trace()}, fh)


class _NoopSpan:
    __slots__ = ()

    @property
    def attributes(self) -> Dict[str, Any]:
        # fresh throwaway dict per access: callers may write into a live
        # span's attributes, and the shared no-op must absorb that
        # without accumulating state
        return {}

    def __setattr__(self, *_a) -> None:  # pragma: no cover
        pass


_NOOP_SPAN = _NoopSpan()

_ANNOTATION = None  # jax.profiler.TraceAnnotation, resolved on first use


class phase:
    """One boundary, one call site::

        with tracing.phase("engine.emit", self.tracer, chunk=7) as span:
            ...
            span.set(tokens=emitted)

    Enters a ``jax.profiler.TraceAnnotation`` — with no profiler session
    that is the annotation's check of one flag — and, when ``tracer`` is
    enabled, records the same interval as a :class:`Span` with its true
    start. Attributes reach both; the profiler cuts a value at a comma,
    so lists are joined with ``:``. ``set`` adds what is known only at
    the end."""

    __slots__ = ("_annotation", "_recording", "_span")

    def __init__(
        self, name: str, tracer: "Tracer" = None, **attributes: Any
    ) -> None:
        global _ANNOTATION
        if _ANNOTATION is None:
            import jax

            _ANNOTATION = jax.profiler.TraceAnnotation
        self._annotation = _ANNOTATION(name, **attributes)
        self._recording = (
            tracer.span(name, **attributes)
            if tracer is not None and tracer.enabled else None
        )
        self._span = None

    def __enter__(self) -> "phase":
        self._annotation.__enter__()
        if self._recording is not None:
            self._span = self._recording.__enter__()
        return self

    def set(self, **attributes: Any) -> None:
        self._annotation.set_metadata(**attributes)
        if self._span is not None:
            self._span.attributes.update(attributes)

    def __exit__(self, *exc_info: Any) -> None:
        if self._recording is not None:
            self._recording.__exit__(*exc_info)
        self._annotation.__exit__(*exc_info)


class NoopTracer(Tracer):
    """Shared do-nothing tracer (the default when tracing is off)."""

    def __init__(self) -> None:
        super().__init__("noop", max_spans=1)
        self.enabled = False


NOOP = NoopTracer()


# ---------------------------------------------------------------------- #
# process-wide tracer registry + auto-dump
# ---------------------------------------------------------------------- #
_TRACERS: Dict[str, Tracer] = {}
_REGISTRY_LOCK = threading.Lock()
_DUMP_REGISTERED = False


def get_tracer(component: str) -> Tracer:
    """The process-wide tracer for a component (``gateway``, ``runner``,
    ``engine``...). Returns :data:`NOOP` unless ``LANGSTREAM_TRACE_DIR``
    is set, so call sites pay one attribute check when tracing is off.
    Real tracers are dumped to the trace dir at interpreter exit (and on
    demand via :func:`dump_all`)."""
    global _DUMP_REGISTERED
    if not trace_dir():
        return NOOP
    with _REGISTRY_LOCK:
        tracer = _TRACERS.get(component)
        if tracer is None:
            tracer = Tracer(component)
            _TRACERS[component] = tracer
        if not _DUMP_REGISTERED:
            _DUMP_REGISTERED = True
            atexit.register(dump_all)
    return tracer


def dump_all(directory: Optional[str] = None) -> List[str]:
    """Write one Chrome-trace JSON per registered tracer into the trace
    dir; file names carry the component and pid so a multi-pod run's
    dumps never collide and ``langstream-tpu trace`` can label them."""
    directory = directory or trace_dir()
    if not directory:
        return []
    os.makedirs(directory, exist_ok=True)
    paths = []
    with _REGISTRY_LOCK:
        tracers = dict(_TRACERS)
    for component, tracer in tracers.items():
        events = tracer.chrome_trace()
        if not events:
            continue
        path = os.path.join(
            directory, f"trace_{component}_{os.getpid()}.json"
        )
        tracer.dump(path)
        paths.append(path)
    return paths


# ---------------------------------------------------------------------- #
# cross-pod trace merging (`langstream-tpu trace`)
# ---------------------------------------------------------------------- #
def collect_trace_files(paths: Sequence[str]) -> List[str]:
    """Expand dirs into their ``*.json`` dumps; keep files as given."""
    out: List[str] = []
    for path in paths:
        if os.path.isdir(path):
            out.extend(sorted(glob.glob(os.path.join(path, "*.json"))))
        else:
            out.append(path)
    return out


def _event_trace_ids(event: Dict[str, Any]) -> List[str]:
    args = event.get("args") or {}
    ids = []
    if args.get("trace_id"):
        ids.append(str(args["trace_id"]))
    # batch-level spans (decode chunks) carry every rider's id
    if args.get("trace_ids"):
        ids.extend(
            t for t in str(args["trace_ids"]).split(",") if t
        )
    return ids


def merge_chrome_trace_files(
    paths: Sequence[str], trace_id: Optional[str] = None
) -> Dict[str, Any]:
    """Merge per-pod Chrome-trace dumps into ONE Perfetto-loadable
    timeline: each source file becomes a distinct ``pid`` (named after
    the file via process_name metadata), events keep their wall-clock
    ``ts`` so cross-pod ordering is real time. With ``trace_id``, only
    events belonging to that request survive."""
    events: List[Dict[str, Any]] = []
    for pid, path in enumerate(collect_trace_files(paths), start=1):
        with open(path) as handle:
            data = json.load(handle)
        # both Chrome trace shapes: {"traceEvents": [...]} or bare array
        source = data.get("traceEvents", []) if isinstance(data, dict) else data
        label = os.path.splitext(os.path.basename(path))[0]
        kept = []
        for event in source:
            if trace_id is not None and trace_id not in _event_trace_ids(event):
                continue
            event = dict(event)
            event["pid"] = pid
            kept.append(event)
        if kept:
            events.append({
                "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                "ts": 0, "args": {"name": label},
            })
            events.extend(kept)
    events.sort(key=lambda e: (e.get("ph") != "M", e.get("ts", 0)))
    return {"traceEvents": events}


def run_trace_merge(
    paths: Sequence[str],
    *,
    output: str = "merged_trace.json",
    trace_id: Optional[str] = None,
    list_ids: bool = False,
) -> List[str]:
    """The CLI body behind ``langstream-tpu trace``: expand paths, list
    ids or write the merged timeline, return the status lines to print."""
    files = collect_trace_files(paths)
    if not files:
        raise SystemExit(f"no trace dumps under {list(paths)}")
    if list_ids:
        summary = trace_summary(files)
        if not summary:
            return ["no trace ids found"]
        return [
            f"{tid}  components={','.join(entry['components'])}  "
            f"spans={entry['spans']}"
            + (
                f"  replicas={','.join(entry['replicas'])}"
                if entry.get("replicas") else ""
            )
            for tid, entry in sorted(summary.items())
        ]
    merged = merge_chrome_trace_files(files, trace_id=trace_id)
    with open(output, "w") as handle:
        json.dump(merged, handle)
    return [
        f"wrote {len(merged['traceEvents'])} events from {len(files)} "
        f"dump(s) -> {output} (open in Perfetto / chrome://tracing)"
    ]


def trace_summary(paths: Sequence[str]) -> Dict[str, Dict[str, Any]]:
    """Per-trace-id view over a set of dumps: which components a request
    crossed, how many spans each contributed, and — for spans stamped
    with a ``replica`` attr (gateway route decisions, engine handoff
    spans on identity-stamped serve processes) — which REPLICAS the
    request crossed, so a disaggregated prefill→decode path reads as
    two replicas under one id from ``langstream-tpu trace --list``."""
    out: Dict[str, Dict[str, Any]] = {}
    for path in collect_trace_files(paths):
        with open(path) as handle:
            data = json.load(handle)
        events = data.get("traceEvents", []) if isinstance(data, dict) else data
        for event in events:
            category = event.get("cat", "?")
            replica = (event.get("args") or {}).get("replica")
            for tid in _event_trace_ids(event):
                entry = out.setdefault(
                    tid,
                    {"components": set(), "spans": 0, "replicas": set()},
                )
                entry["components"].add(category)
                entry["spans"] += 1
                if replica:
                    entry["replicas"].add(str(replica))
    for entry in out.values():
        entry["components"] = sorted(entry["components"])
        entry["replicas"] = sorted(entry["replicas"])
    return out


@contextlib.contextmanager
def profile(log_dir: str, *, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture an XLA device profile (xplane) under ``log_dir`` —
    TensorBoard's profile plugin or xprof reads it. Wraps
    ``jax.profiler.trace``; everything the devices execute inside the
    block is captured (MXU utilization, HBM traffic, fusion names)."""
    import jax

    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(log_dir):
        yield
