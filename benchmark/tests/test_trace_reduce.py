import os

import pytest

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_counts_overlap_once():
    assert trace_reduce.union_seconds([]) == 0.0
    spans = [(0, 4e9), (2e9, 6e9), (8e9, 9e9), (8.5e9, 8.7e9)]
    assert trace_reduce.union_seconds(spans) == pytest.approx(7.0)


def test_gaps_are_what_the_union_leaves():
    spans = [(1e9, 2e9), (1.5e9, 3e9), (5e9, 6e9)]
    assert trace_reduce.gaps_of(spans, 0, 8e9) == [(0, 1e9), (3e9, 5e9), (6e9, 8e9)]


KERNEL = (
    '%closed_call.11 = bf16[32,28,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call('
    'bf16[32,28,128]{2,1,0} %fusion.1), custom_call_target="tpu_custom_call"'
)
CONCAT = (
    '%custom-call.68 = bf16[4,256,3584]{2,1,0:T(8,128)(2,1)S(1)} custom-call('
    'bf16[1,256,3584]{2,1,0} %slice-done), custom_call_target="ConcatBitcast"'
)
WHILE = (
    '%while.23 = (s32[]{:T(128)}, bf16[28,32,2048,4,128]{4,3,2,1,0:T(4,128)(2,1)}) '
    'while((s32[]{:T(128)}, bf16[28,32,2048,4,128]{4,3,2,1,0}) %tuple), body=%b'
)


def test_a_kernel_is_a_kernel_by_its_target_and_which_by_its_name():
    assert trace_reduce.is_kernel(KERNEL)
    assert not trace_reduce.is_kernel(CONCAT)
    assert trace_reduce.opcode(KERNEL) == "custom-call"
    assert trace_reduce.opcode(WHILE) == "while"
    assert trace_reduce.short(KERNEL) == "closed_call.11 pallas-kernel bf16[32,28,128]"
    assert trace_reduce.kernel_name(KERNEL) == "closed_call"
    assert trace_reduce.kernel_name(KERNEL.replace("closed_call.11", "flash_decode.6")) == "flash_decode"
    assert trace_reduce.kernel_name(KERNEL.replace("closed_call.11", "flash_decode_int8kv")) == "flash_decode_int8kv"


def test_a_program_is_told_by_its_name():
    assert trace_reduce.kind_of("jit_prefill_dense(12503282535071236251)") == "prefill_dense"
    assert trace_reduce.kind_of("jit_decode_chunk_paged") == "decode_chunk_paged"
    assert trace_reduce.kind_of("pjit_something") == ""


def test_breakdown_names_idle_by_the_phase_that_covers_it():
    reduced = {"op_seconds": {"a": 2.0, "b": 1.0}, "gap_total_s": 1.0}
    idle = {"engine.emit": 0.75, "": 0.2, "engine.admit": 0.04, "inside_a_program": 0.01}
    out = trace_reduce.breakdown(reduced, idle)
    assert out["device_ops"][0] == ["a", 2.0]
    assert out["idle_gaps"] == [
        ["engine.emit", 0.75], ["no_span", 0.2], ["engine.admit", 0.04],
        ["inside_a_program", 0.01],
    ]
    # a trace without spans: all of the idle time under the one name
    assert trace_reduce.breakdown(reduced, None)["idle_gaps"] == [["no_span", 1.0]]
    # at most ten entries, and they still sum to the idle seconds
    many = {f"engine.phase_{i}": float(i + 1) for i in range(14)}
    listed = trace_reduce.breakdown(reduced, many)["idle_gaps"]
    assert len(listed) == 10 and listed[-1][0] == "other_spans"
    assert sum(seconds for _, seconds in listed) == pytest.approx(sum(many.values()))


def test_the_recorded_trace_reduces_to_what_was_on_the_chip():
    """``fixture.xplane.pb``: recorded on a TPU v5 lite by
    tools/record_fixture.py. Four runs of one jitted program (a loop of six
    passes, each a Pallas kernel and two matmuls) with 2 ms sleeps between
    them, 13.3 ms from the marker to the end by the host's clock."""
    span = 0.013322397999900204
    reduced = trace_reduce.reduce_trace(os.path.join(HERE, "fixture.xplane.pb"), span)
    assert reduced["marked"] and reduced["window_s"] == span
    # busy is the union of the op intervals: four programs of about 150 us
    assert reduced["busy_s"] == pytest.approx(584.8e-6, rel=1e-3)
    assert reduced["busy_s"] + reduced["gap_total_s"] == pytest.approx(span)
    assert 100 * (1 - reduced["busy_s"] / span) == pytest.approx(95.6, abs=0.1)
    # the kernel is found by its target, 23 calls of it after the marker
    assert reduced["kernel_s"] == pytest.approx(15.78e-6, rel=1e-3)
    assert 100 * reduced["kernel_s"] / reduced["busy_s"] == pytest.approx(2.7, abs=0.1)
    # the programs and their kernels by name, never by a count of calls:
    # the fixture's program is ``jit_program`` and its kernel ``closed_call``
    programs = reduced["programs"]
    assert [p["kind"] for p in programs] == ["program"] * 4
    assert all(set(p["kernels"]) == {"closed_call"} for p in programs)
    assert [p["kernels"]["closed_call"]["calls"] for p in programs] == [5, 6, 6, 6]
    assert [p["kernels"]["closed_call"]["seconds"] for p in programs] == [
        3.43e-06, 4.118e-06, 4.116e-06, 4.115e-06,
    ]
    assert [p["seconds"] for p in programs] == [
        0.000129214, 0.000151892, 0.000151799, 0.000151957,
    ]
    assert [p["whole"] for p in programs] == [False, True, True, True]
    # the loop itself holds the other ops and is not summed beside them
    assert not any(" while " in name for name in reduced["op_seconds"])
    assert sum(reduced["op_seconds"].values()) == pytest.approx(reduced["busy_s"], rel=0.05)
    # the three sleeps between the programs are the longest gaps
    longest = sorted(e - s for s, e in reduced["gaps"])[-3:]
    assert all(2e6 < gap < 4e6 for gap in longest)
    assert all(reduced["lo"] <= s < e <= reduced["hi"] for s, e in reduced["gaps"])
    # the parent's readings of this file, to the last digit (PR 28)
    assert reduced["busy_s"] == 0.000584828
    assert reduced["kernel_s"] == 1.5779e-05
    assert reduced["gap_total_s"] == 0.012737569999900207
