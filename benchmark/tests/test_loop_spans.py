"""``loop_spans.py``: the readers over small synthetic traces, and the
chip's fixture with loop spans laid into it, which ``trace_reduce`` and
every reader of before must read as before."""

import json
import os
import shutil
import types

import jax
import pytest

from benchmark import loop_spans, spans, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "spans_fixture.xplane.pb")
MS = 1e6  # ns


def phase(name, start_ms, end_ms, **attrs):
    return {"name": name, "start": start_ms * MS, "end": end_ms * MS, "attrs": attrs}


def loop(name, start_ms, end_ms, **attrs):
    return dict(phase(name, start_ms, end_ms, **attrs), line="/host:CPU/1")


def reduced(phases):
    """A reduced trace of a 100 ms window from 0, no programs (so no
    skew), the marker at the legs' instant 50.0 s."""
    return {
        "marked": True, "phases": phases, "programs": [], "gaps": [],
        "flows": {"produced": {}, "consumers": {}, "enqueues": {}},
        "lo": 0.0, "hi": 100 * MS, "chips": 1,
        "begin": {"at": 50.0}, "end": {"at": 50.1},
    }


def ctx_of(trace, loops, legs=()):
    read = spans.lay(trace)
    return {
        "trace": trace, "spans": {"read": read, "legs": list(legs)},
        "loop_walk": loops,
    }


def at(ms):
    """A leg's instant (perf_counter seconds) that lies ``ms`` into the window."""
    return 50.0 + ms / 1e3


PHASES = [
    phase("engine.admit", 1, 5, cpu_ms=2.0),
    phase("engine.prefill_dispatch", 2, 3, cpu_ms=1.0),  # a child: not summed
    phase("engine.dispatch_decode", 5, 7, cpu_ms=2.0),
    phase("engine.wait_chunk", 7, 30, cpu_ms=0.1),       # blocks by design
    phase("engine.emit", 30, 34, cpu_ms=3.0),
    phase("engine.emit", 95, 105, cpu_ms=1.0),           # past the window
]
LOOPS = [
    loop("loop.gateway_in", 0.5, 0.6, trace_id="a"),
    loop("loop.deliver", 35, 35.2, tokens=1, first=1, done=0, trace_id="a", cpu_ms=0.1),
    loop("loop.gateway_out", 36.0, 36.1, trace_id="a", index=0),
    loop("loop.deliver", 40, 40.8, tokens=31, first=0, done=1, trace_id="a", cpu_ms=0.5),
    loop("loop.gateway_out", 41.0, 41.1, trace_id="a", index=1),
    loop("loop.gateway_in", 10, 10.2, trace_id="b"),
    loop("loop.deliver", 60, 60.4, tokens=8, first=1, done=0, trace_id="b", cpu_ms=0.2),
    loop("loop.gateway_out", 63.0, 63.5, trace_id="b", index="0"),
    loop("loop.deliver", 99.9, 100.2, tokens=32, first=0, done=1, trace_id="b"),  # past
]
LEGS = [
    {"trace_id": "a", "submit": at(2.6), "first_token": at(34.0)},
    {"trace_id": "b", "submit": at(14.2), "first_token": at(59.0)},
    {"trace_id": "c", "submit": at(20.0), "first_token": at(21.0)},  # no span
]


def test_the_readers_over_a_small_trace():
    ctx = ctx_of(reduced([dict(p) for p in PHASES]), [dict(s) for s in LOOPS], LEGS)
    # admit 4 - 2, dispatch 2 - 2, emit 4 - 3 of 10 ms: 3 ms off the CPU
    assert loop_spans.schedule_offcpu_share(ctx) == pytest.approx(30.0)
    # 0.2 + 0.8 + 0.4 ms over 1 + 31 + 8 tokens
    assert loop_spans.loop_us_per_token(ctx) == pytest.approx(1400.0 / 40)
    # gateway_in start to submit: 2.1 and 4.2 ms
    assert loop_spans.gateway_to_engine_p50(ctx) == pytest.approx(3.15)
    # first token to its deliver: 1.0 and 1.0 ms
    assert loop_spans.inbox_wait_p50(ctx) == pytest.approx(1.0)
    # first deliver's start to the end of frame 0: 1.1 and 3.5 ms
    assert loop_spans.deliver_to_frame_p50(ctx) == pytest.approx(2.3)


def test_the_spans_move_with_the_engines_by_the_same_skew():
    trace = reduced([dict(p) for p in PHASES])
    # a program that starts 0.5 ms before its launch: the host's events
    # all move 0.5 ms earlier, the legs' instants with them
    trace["programs"] = [{
        "name": "jit_decode_chunk_dense", "kind": "decode_chunk_dense",
        "start": 5.5 * MS, "end": 29 * MS, "seconds": 0.0235, "whole": True,
        "run_id": 1, "kernels": {},
    }]
    trace["flows"]["enqueues"] = {"1": (6.0 * MS, "/host:CPU/0")}
    ctx = ctx_of(trace, [dict(s) for s in LOOPS], LEGS)
    assert trace["skew_ns"] == pytest.approx(0.5 * MS)
    found = loop_spans.of(ctx)
    assert found["loop"][0]["start"] == pytest.approx(0.0)
    assert loop_spans.gateway_to_engine_p50(ctx) == pytest.approx(3.15)
    assert loop_spans.inbox_wait_p50(ctx) == pytest.approx(1.0)


def test_every_reader_is_none_without_what_it_reads(monkeypatch, tmp_path):
    bare = [dict(p, attrs={k: v for k, v in p["attrs"].items() if k != "cpu_ms"}) for p in PHASES]
    # a program from before the spans: no loop span, no cpu_ms
    ctx = ctx_of(reduced(bare), [], LEGS)
    assert loop_spans.of(ctx) is None
    for reader in (
        loop_spans.schedule_offcpu_share, loop_spans.loop_us_per_token,
        loop_spans.gateway_to_engine_p50, loop_spans.inbox_wait_p50,
        loop_spans.deliver_to_frame_p50,
    ):
        assert reader(ctx) is None
    # no trace at all, and no traced run's file to walk
    assert loop_spans.of({"trace": None}) is None
    monkeypatch.setattr(loop_spans, "TRACES", str(tmp_path))
    old = ctx_of(reduced([dict(p) for p in PHASES]), [], LEGS)
    del old["loop_walk"]
    assert loop_spans.loop_us_per_token(old) is None
    # loop spans, but none the legs join: the joins read None, not 0
    ctx = ctx_of(reduced([dict(p) for p in PHASES]), [dict(s) for s in LOOPS], [])
    assert loop_spans.gateway_to_engine_p50(ctx) is None
    assert loop_spans.inbox_wait_p50(ctx) is None
    assert loop_spans.loop_us_per_token(ctx) == pytest.approx(35.0)


# ------------------------------------------------------------------ #
# the chip's fixture with loop spans laid into its host planes
# ------------------------------------------------------------------ #
class _Event:
    def __init__(self, name, start_ns, duration_ns, stats):
        self.name, self.start_ns, self.duration_ns = name, start_ns, duration_ns
        self.stats = list(stats.items())


def _with_loop_spans(monkeypatch):
    """``ProfileData.from_file`` that lays ``loop.*`` events, as the
    program's ``tracing.phase`` writes them, into the fixture's host
    planes: on the engine's own line and on a line of their own."""
    real = jax.profiler.ProfileData.from_file

    def from_file(path):
        data = real(path)
        planes = []
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                planes.append(plane)
                continue
            lines = [
                types.SimpleNamespace(name=line.name, events=list(line.events))
                for line in plane.lines
            ]
            events = [e for line in lines for e in line.events]
            if events:
                lo = min(e.start_ns for e in events)
                hi = max(e.start_ns + e.duration_ns for e in events)
                step = (hi - lo) / 40
                laid = [
                    _Event("loop.deliver", lo + k * step, step / 2, {
                        "tokens": 4, "first": int(k == 0), "done": 0,
                        "trace_id": "t", "cpu_ms": 0.01,
                    })
                    for k in range(40)
                ]
                lines[0].events += laid[::2]
                lines.append(types.SimpleNamespace(
                    name="loop", events=laid[1::2] + [
                        _Event("loop.gateway_out", hi, 10.0, {"trace_id": "t", "index": 0}),
                    ],
                ))
            planes.append(types.SimpleNamespace(name=plane.name, lines=lines))
        return types.SimpleNamespace(planes=planes)

    monkeypatch.setattr(jax.profiler.ProfileData, "from_file", from_file)


def test_loop_spans_reach_no_phase_flow_program_or_gap(monkeypatch, tmp_path):
    with open(os.path.join(HERE, "spans_fixture.json")) as handle:
        meta = json.load(handle)
    span = meta["end"] - meta["begin"]
    before = trace_reduce.reduce_trace(TRACE, span)
    _with_loop_spans(monkeypatch)
    after = trace_reduce.reduce_trace(TRACE, span)
    assert after == before
    assert not any(p["name"].startswith("loop.") for p in after["phases"])
    read = spans.lay(after)
    # the parent's readings of this file (``test_spans.py``), unmoved
    by_phase = spans.idle_by_phase(read)
    assert by_phase == spans.idle_by_phase(spans.lay(before))
    assert sum(by_phase.values()) == pytest.approx(read["gap_total_s"], rel=1e-9)
    assert spans.idle_shares(read) == {
        "emit": 1.4992285612750829, "schedule": 66.83782870945078,
        "unspanned": 30.26902151665065,
    }
    # the run's file, found where ``run.py`` keeps a cell's trace
    monkeypatch.setattr(loop_spans, "TRACES", str(tmp_path))
    kept = tmp_path / "cell" / "trace" / "plugins" / "profile" / "run"
    kept.mkdir(parents=True)
    shutil.copy(TRACE, kept / "host.xplane.pb")
    ctx = {"trace": dict(read, begin={"at": meta["begin"]}), "window": None}
    ctx["spans"] = {"read": ctx["trace"], "legs": []}
    found = loop_spans.of(ctx)
    assert len(found["loop"]) == 41
    assert {s["name"] for s in found["loop"]} == {"loop.deliver", "loop.gateway_out"}
    assert [s["start"] for s in found["loop"]] == sorted(s["start"] for s in found["loop"])
    assert loop_spans.loop_us_per_token(ctx) > 0
    # a file cut on another marker is another run's: nothing is read
    ctx = {"trace": dict(read, begin={"at": meta["begin"]}, lo=read["lo"] + 1)}
    ctx["spans"] = {"read": ctx["trace"], "legs": []}
    assert loop_spans.of(ctx) is None
