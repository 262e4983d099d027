"""``spans.py`` over a small trace recorded on the chip
(``spans_fixture.xplane.pb`` and ``spans_fixture.json``: a tiny engine
serving six requests on four slots under the profiler, the marker first;
``tools/record_spans_fixture.py``)."""

import json
import os

import pytest

from benchmark import spans, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "spans_fixture.xplane.pb")
PARTS = ("queue", "build", "wait", "device", "harvest")


@pytest.fixture(scope="module")
def meta():
    with open(os.path.join(HERE, "spans_fixture.json")) as handle:
        return json.load(handle)


@pytest.fixture(scope="module")
def read(meta):
    return spans.read_trace(TRACE, meta["end"] - meta["begin"])


def test_gaps_cut_by_phase_sum_to_the_idle_total(read, meta):
    span = meta["end"] - meta["begin"]
    reduced = read  # one walk, one window: the reduction and the spans are one record
    by_phase = spans.idle_by_phase(read)
    assert sum(by_phase.values()) == pytest.approx(reduced["gap_total_s"], rel=1e-9)
    shares = spans.idle_shares(read)
    assert set(shares) == {"emit", "schedule", "unspanned"}
    assert sum(shares.values()) == pytest.approx(
        100.0 * (1.0 - reduced["busy_s"] / span), abs=1e-6
    )
    # every span that covers a gap is one of the engine's, and a tiny
    # engine between requests waits for work: unexplained, by the rule
    assert set(by_phase) <= {
        "", "inside_a_program", "engine.wait_for_work", "engine.linger",
        "engine.admit", "engine.dispatch_decode", "engine.wait_chunk",
        "engine.emit", "engine.harvest_prefills",
    }
    assert by_phase["engine.emit"] > 0 and by_phase["engine.dispatch_decode"] > 0


def test_the_spans_tile_the_engine_thread(read):
    top = spans.tiling(read["phases"])
    for before, after in zip(top, top[1:]):
        assert before["end"] <= after["start"]
    covered = sum(span["end"] - span["start"] for span in top)
    assert covered >= 0.95 * (top[-1]["end"] - top[0]["start"])
    children = [p for p in read["phases"] if p["name"] in spans.CHILDREN]
    admits = [p for p in top if p["name"] == "engine.admit"]
    for child in children:
        assert any(a["start"] <= child["start"] and child["end"] <= a["end"] for a in admits)


def test_programs_join_their_dispatch_spans_in_order(read):
    programs = read["programs"]
    assert programs and all(p["phase"] is not None for p in programs)
    assert {p["kind"] for p in programs} == {"prefill_dense", "decode_chunk_dense"}
    # no program starts before its launch, on the one clock
    assert all(p["start"] >= p["launched"] - read["skew_ns"] - 1 for p in programs)
    prefills = [p for p in read["phases"] if p["name"] == "engine.prefill_dispatch"]
    batches = [int(span["attrs"]["batch"]) for span in prefills]
    assert batches == sorted(batches) and len(set(batches)) == len(batches)
    starts = []
    for span in prefills:
        (program,) = span["programs"]
        assert program["kind"] == "prefill_dense"
        assert int(span["attrs"]["rows"]) == len(str(span["attrs"]["slots"]).split(":"))
        starts.append(program["start"])
    assert starts == sorted(starts)  # the device runs them as dispatched
    for span in read["phases"]:
        if span["name"] == "engine.dispatch_decode":
            (program,) = span["programs"]
            assert program["kind"] == "decode_chunk_dense"
            assert int(span["attrs"]["steps"]) == 4


def test_the_parts_of_first_token_time_sum_to_the_whole(read, meta):
    parts = spans.first_token_parts(read, meta["legs"], meta["begin"])
    assert len(parts) == len(meta["legs"]) == 6
    for part in parts:
        assert sum(part[name] for name in PARTS) == pytest.approx(part["whole"], abs=1e-6)
        assert all(part[name] >= 0 for name in PARTS)
        # the ring's clock and the profiler's agree: a leg's jit call lies
        # in its dispatch span, so the wait is the launch's few milliseconds
        assert part["wait"] < 10 and part["device"] < 1
    wholes = {p["trace_id"]: p["whole"] for p in parts}
    for leg in meta["legs"]:
        assert wholes[leg["trace_id"]] == pytest.approx(
            (leg["first_token"] - leg["submit"]) * 1e3, abs=1e-6
        )


def test_decode_steps_and_prefill_seconds_by_name(read):
    chunks = [p for p in read["programs"] if p["kind"] == "decode_chunk_dense"]
    whole = [p for p in chunks if p["start"] >= read["lo"] and p["end"] <= read["hi"]]
    expected = sum(p["end"] - p["start"] for p in whole) / 1e6 / (4 * len(whole))
    assert spans.decode_step_ms(read) == pytest.approx(expected)
    prefills = [p for p in read["programs"] if p["kind"] == "prefill_dense"]
    assert spans.prefill_seconds(read) == pytest.approx(
        sum(p["end"] - p["start"] for p in prefills) / 1e9
    )


def test_the_recorded_trace_reads_what_the_parent_read(read, meta):
    """The parent's readings of this file (PR 28, before the walk was
    folded into ``trace_reduce``), to the last digit."""
    assert spans.idle_by_phase(read) == {
        "engine.linger": 0.010772649, "engine.admit": 0.006257896999999999,
        "": 0.024040079000001772, "inside_a_program": 9.32299999999996e-06,
        "engine.harvest_prefills": 0.0036791489999999996,
        "engine.dispatch_decode": 0.023078396,
        "engine.wait_chunk": 0.009316030999999999, "engine.emit": 0.00119117,
    }
    assert spans.idle_shares(read) == {
        "emit": 1.4992285612750829, "schedule": 66.83782870945078,
        "unspanned": 30.26902151665065,
    }
    assert spans.decode_step_ms(read) == 0.03599857142857143
    assert spans.prefill_seconds(read) == 0.0001159
    assert read["busy_s"] == 0.001107501 and read["kernel_s"] == 0.0
    assert read["gap_total_s"] == 0.07834469400000177
    assert [p["kind"] for p in read["programs"]].count("decode_chunk_dense") == 7
    assert all(p["kernels"] == {} for p in read["programs"])  # head dim 16: no kernel


def test_the_breakdown_names_the_phases_and_sums_to_the_idle_seconds(read):
    out = trace_reduce.breakdown(read, spans.idle_by_phase(read))
    names = [name for name, _ in out["idle_gaps"]]
    assert len(names) <= 10 and len(set(names)) == len(names)
    assert {"engine.emit", "engine.admit", "engine.dispatch_decode", "no_span"} <= set(names)
    assert sum(seconds for _, seconds in out["idle_gaps"]) == pytest.approx(
        read["gap_total_s"], rel=1e-9
    )
    assert out["idle_gaps"][0] == ["no_span", 0.024040079000001772]


def test_a_runs_readers_share_the_runs_one_reduction(read, meta):
    """``report.add_trace`` reduces a run's trace once; every reader of
    spans lays that record and none walks the file again."""
    trace = dict(read, begin={"at": meta["begin"]}, end={"at": meta["end"]})
    ctx = {"trace": trace, "chips": 1, "window": {"opens": meta["begin"], "closes": meta["end"]}}
    assert spans.of(ctx)["read"] is trace
    assert spans.decode_step(ctx) == 0.03599857142857143
    assert spans.prefill_busy_share(ctx) == pytest.approx(100.0 * 0.0001159 / 0.001107501)
    assert spans.idle_share(ctx, "emit") == 1.4992285612750829


def test_a_trace_without_annotations_reads_none():
    # the reduction's own fixture: a marker and a device, no engine span
    assert spans.read_trace(os.path.join(HERE, "fixture.xplane.pb"), 0.0133) is None
    reduced = trace_reduce.reduce_trace(os.path.join(HERE, "fixture.xplane.pb"), 0.0133)
    assert reduced["marked"] and reduced["phases"] == [] and spans.lay(reduced) is None
    ctx = {"trace": None, "window": {"opens": 0.0, "closes": 1.0}}
    assert spans.of(ctx) is None and spans.idle_share(ctx, "emit") is None
    assert spans.part_p50(ctx, "wait") is None and spans.queue_wait_p50(ctx) is None
    assert spans.decode_step(ctx) is None and spans.prefill_busy_share(ctx) is None


def test_launches():
    # enqueued on the launching thread: the enqueue is the launch
    assert spans._launched((5.0, "a"), {}, {}) == 5.0
    # enqueued from the runtime's own thread, inside the consumer of a flow
    consumers = {"q": [(90.0, 120.0, ("7", "x")), (10.0, 20.0, ("7", "w"))]}
    assert spans._launched((100.0, "q"), consumers, {("7", "x"): 40.0}) == 40.0
    assert spans._launched(None, consumers, {}) is None


def test_idle_is_cut_by_the_span_that_covers_it():
    read = {
        "chips": 1, "lo": 0.0, "hi": 10e9,
        "gaps": [(1e9, 2e9), (3e9, 3e9 + 10e3), (5e9, 7e9)],
        "phases": [
            {"name": "engine.emit", "start": 0.5e9, "end": 1.5e9},
            {"name": "engine.admit", "start": 1.5e9, "end": 1.75e9},
            {"name": "engine.prefill_dispatch", "start": 1.5e9, "end": 1.6e9},
            {"name": "engine.wait_for_work", "start": 5e9, "end": 6e9},
        ],
    }
    by_phase = spans.idle_by_phase(read)
    assert by_phase == pytest.approx({
        "engine.emit": 0.5, "engine.admit": 0.25, "": 1.25,
        "inside_a_program": 10e-6, "engine.wait_for_work": 1.0,
    })
    assert spans.idle_shares(read) == pytest.approx(
        {"emit": 5.0, "schedule": 2.5, "unspanned": 22.5001}
    )
