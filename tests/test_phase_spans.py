"""The engine loop's phase spans, its program names and the ring of
finished legs (ISSUE 26): one clock, one call site a boundary, readable
from inside the process. CPU backend, tiny engine."""

import asyncio
import collections
import glob
import os
import time

import jax
import pytest

from langstream_tpu.providers.jax_local import engine as engine_lib
from langstream_tpu.providers.jax_local.engine import (
    DecodeEngine,
    SamplingParams,
)
from langstream_tpu.providers.jax_local.model import LlamaConfig, init_params
from langstream_tpu.runtime import journey, tracing

# docs/observability.md §1: the spans that tile the engine thread, and the
# one child
TILING = {
    "engine.wait_for_work", "engine.linger", "engine.admit",
    "engine.dispatch_decode", "engine.wait_chunk", "engine.emit",
    "engine.harvest_prefills",
}
CHILDREN = {"engine.prefill_dispatch"}


def make_engine(**options):
    config = LlamaConfig.tiny(max_seq_len=128)
    options.setdefault("max_slots", 4)
    options.setdefault("prefill_buckets", [16, 32])
    return DecodeEngine(
        config, init_params(config), max_seq_len=128, **options
    )


@pytest.fixture(scope="module")
def engine():
    engine = make_engine(decode_chunk=16)
    engine.start()
    yield engine
    engine.stop()


def generate(engine, prompts, tokens, **kwargs):
    async def main():
        return await asyncio.gather(*[
            engine.generate(
                prompt, SamplingParams(max_new_tokens=tokens), **kwargs
            )
            for prompt in prompts
        ])

    return asyncio.run(main())


def profiled(engine, tmp_path, tag, tokens):
    """Serve two requests of ``tokens`` output tokens under the profiler;
    returns the ``engine.*`` host events by thread line, and how many
    decode chunks the engine harvested meanwhile."""
    log_dir = str(tmp_path / tag)
    chunks = engine.stats["decode_chunks"]
    with tracing.profile(log_dir):
        generate(engine, [[1, 2, 3, 4], [9, 8, 7]], tokens)
        # the answers resolve INSIDE the last emit span: keep the session
        # open until the engine thread has left it and waited for work
        # once, or a busy machine stops the profiler with the span open
        idle = engine.stats["idle_time"]
        deadline = time.monotonic() + 30
        while engine.stats["idle_time"] == idle:
            assert time.monotonic() < deadline
            time.sleep(0.01)
    chunks = engine.stats["decode_chunks"] - chunks
    path = glob.glob(
        os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True
    )[0]
    data = jax.profiler.ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 {str(k): v for k, v in e.stats})
                for e in line.events if e.name.startswith("engine.")
            ]
            if events:
                lines.append(sorted(events, key=lambda e: (e[1], -e[2])))
    return lines, chunks


# ------------------------------------------------------------------ #
# (1) under jax.profiler the host plane holds the spans, tiled
# ------------------------------------------------------------------ #
def test_profiler_holds_tiled_phase_spans(engine, tmp_path):
    generate(engine, [[1, 2, 3, 4], [9, 8, 7]], 8)  # compile every shape
    generate(engine, [[1, 2, 3, 4], [9, 8, 7]], 64)
    short, short_chunks = profiled(engine, tmp_path, "short", 8)
    long, long_chunks = profiled(engine, tmp_path, "long", 64)
    for lines in (short, long):
        # one thread makes them all: the engine's
        assert len(lines) == 1
        events = lines[0]
        assert {name for name, *_ in events} <= TILING | CHILDREN
        top = [e for e in events if e[0] in TILING]
        # the tiling spans do not overlap; a child lies inside an admit
        for before, after in zip(top, top[1:]):
            assert before[2] <= after[1], (before, after)
        admits = [e for e in top if e[0] == "engine.admit"]
        children = [e for e in events if e[0] in CHILDREN]
        for child in children:
            assert any(a[1] <= child[1] and child[2] <= a[2] for a in admits)
            assert {"kind", "bucket", "rows", "batch", "slots"} <= set(child[3])
        # the order of a cycle: what an admit launched is harvested, batch
        # by batch, before the next decode dispatch, and nothing but the
        # harvests lies between them; a chunk is dispatched, waited for
        # and emitted before anything else happens
        order = [e[0] for e in top if e[0] != "engine.wait_for_work"]
        launched = []
        for name, start, end, attributes in top:
            if name == "engine.admit":
                launched += [
                    c[3]["batch"] for c in children
                    if start <= c[1] and c[2] <= end
                ]
            elif name == "engine.harvest_prefills":
                assert attributes["batch"] == launched.pop(0)
                assert int(attributes["joined"]) == int(attributes["rows"])
            elif name == "engine.dispatch_decode":
                assert not launched
        assert not launched and children
        for at, name in enumerate(order):
            if name == "engine.dispatch_decode":
                assert order[at + 1:at + 3] == [
                    "engine.wait_chunk", "engine.emit",
                ]
            if name == "engine.harvest_prefills":
                assert order[at + 1] in (
                    "engine.harvest_prefills", "engine.dispatch_decode",
                )
    assert long_chunks > short_chunks
    for lines, chunks in ((short, short_chunks), (long, long_chunks)):
        count = collections.Counter(e[0] for e in lines[0])
        # ONE emit span a harvested chunk, whatever it emitted, with the
        # tokens as an attribute; one dispatch span a chunk, with steps;
        # one harvest a prefill dispatch
        assert count["engine.emit"] == chunks
        assert count["engine.wait_chunk"] == chunks
        assert count["engine.dispatch_decode"] == chunks
        assert count["engine.harvest_prefills"] == count[
            "engine.prefill_dispatch"
        ] <= 2
        # the rest follow the loop's iterations, not the tokens: an admit
        # an iteration, and an iteration either waited for work or
        # dispatched a chunk (how many waited follows the machine's load)
        assert count["engine.admit"] <= (
            count["engine.wait_for_work"] + chunks + 1
        )
    emitted = sum(
        int(e[3]["tokens"]) for e in long[0] if e[0] == "engine.emit"
    )
    assert emitted == 2 * 63  # the first tokens came from the harvest
    assert all(
        int(e[3]["steps"]) > 0 for e in long[0]
        if e[0] == "engine.dispatch_decode"
    )


# ------------------------------------------------------------------ #
# (2) no session, no trace directory: nothing is stored
# ------------------------------------------------------------------ #
def test_a_phase_stores_nothing_with_tracing_off(engine, monkeypatch):
    monkeypatch.delenv("LANGSTREAM_TRACE_DIR", raising=False)
    assert tracing.get_tracer("engine") is tracing.NOOP
    assert engine.tracer is tracing.NOOP
    generate(engine, [[5, 6, 7]], 4)
    with tracing.phase("engine.test", tracing.NOOP, rows=3) as span:
        span.set(tokens=7)
    with tracing.phase("engine.test"):
        pass
    assert tracing.NOOP.spans() == []


def test_a_phase_records_a_span_with_its_true_start():
    tracer = tracing.Tracer("test")
    before = time.perf_counter_ns()
    with tracing.phase("engine.test", tracer, rows=3) as span:
        inside = time.perf_counter_ns()
        time.sleep(0.002)
        span.set(tokens=7)
    (recorded,) = tracer._spans
    assert before <= recorded.start_ns <= inside
    assert recorded.duration_ns >= 2e6
    assert recorded.attributes == {"rows": 3, "tokens": 7}
    # wall time is the one clock plus the process's one offset
    assert recorded.start_wall == pytest.approx(
        recorded.start_ns / 1e9 + tracing.CLOCK_OFFSET
    )
    assert abs(tracing.wall(time.perf_counter()) - time.time()) < 0.5
    # an event from instants taken elsewhere sits where they say
    tracer.event("engine.request", 0.25, start=10.0)
    assert tracer._spans[-1].start_ns == 10_000_000_000
    assert tracer._spans[-1].duration_ns == 250_000_000


# ------------------------------------------------------------------ #
# (3) the ring of finished legs
# ------------------------------------------------------------------ #
INSTANTS = ("submit", "assigned", "dispatched", "first_token", "finish")


def test_one_ring_record_a_finished_request_on_perf_counter(engine):
    journey.LEGS.clear()
    before = time.perf_counter()
    generate(
        engine, [[1, 2, 3], [4, 5, 6, 7, 8], list(range(1, 20))], 6,
        session_id=None, trace_id="ring-test",
    )
    after = time.perf_counter()
    legs = journey.finished_legs()
    assert len(legs) == 3
    for leg in legs:
        stamps = [leg[key] for key in INSTANTS]
        assert stamps == sorted(stamps)
        assert before <= stamps[0] and stamps[-1] <= after
        assert leg["trace_id"] == "ring-test"
        assert leg["admit_class"] == "cold" and leg["finish_reason"] == "length"
        assert leg["tokens"] == 6 and leg["batch"] is not None
        assert leg["bucket"] in (16, 32)
    assert sorted(leg["prompt_tokens"] for leg in legs) == [3, 5, 19]
    # requests of one prefill dispatch share its batch number
    by_bucket = {}
    for leg in legs:
        by_bucket.setdefault(leg["bucket"], set()).add(leg["batch"])
    assert len(by_bucket[32]) == 1


def test_the_ring_is_bounded_and_outlives_the_engine():
    assert journey.LEGS.maxlen == 4096
    journey.LEGS.clear()
    small = make_engine(max_slots=2, decode_chunk=4)
    small.start()
    generate(small, [[1, 2, 3]], 4)
    small.stop()
    del small
    assert len(journey.finished_legs()) == 1
    for index in range(journey.LEGS.maxlen + 10):
        journey.record_leg(submit=float(index))
    legs = journey.finished_legs()
    assert len(legs) == journey.LEGS.maxlen and legs[0]["submit"] == 10.0
    journey.LEGS.clear()


def test_cancelled_shed_and_resumed_requests_do_not_break_the_ring():
    journey.LEGS.clear()
    slow = make_engine(max_slots=1, decode_chunk=2, queue_timeout_s=0.05)
    slow.start()

    async def main():
        handle = []
        long_one = asyncio.ensure_future(slow.generate(
            [1, 2, 3], SamplingParams(max_new_tokens=100), handle=handle,
        ))
        # behind the one slot: shed at its admission deadline, no leg
        shed = asyncio.ensure_future(slow.generate(
            [4, 5, 6], SamplingParams(max_new_tokens=4),
        ))
        with pytest.raises(Exception) as caught:
            await shed
        assert "queue" in str(caught.value).lower()
        handle[0].cancel()
        cancelled = await long_one
        assert cancelled.finish_reason == "cancelled"
        # a resurrected session: its prompt carries the replayed tokens
        replay = [11, 12, 13]
        resumed = await slow.generate(
            [1, 2, 3] + replay[:-1], SamplingParams(max_new_tokens=6),
            request_fields={"replay_tokens": replay, "prompt_len": 3},
        )
        assert resumed.tokens[:3] == replay and len(resumed.tokens) == 6

    asyncio.run(main())
    slow.stop()
    legs = journey.finished_legs()
    assert [leg["finish_reason"] for leg in legs] == ["cancelled", "length"]
    for leg in legs:
        stamps = [leg[key] for key in INSTANTS if leg[key] is not None]
        assert stamps == sorted(stamps) and len(stamps) >= 4
    journey.LEGS.clear()


# ------------------------------------------------------------------ #
# (4) every program lowers under its own stable name
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_every_program_lowers_to_its_own_module_name(layout):
    options = (
        {"kv_layout": "paged", "kv_block_size": 16} if layout == "paged"
        else {}
    )
    engine = make_engine(decode_chunk=4, **options)
    assert engine.paged == (layout == "paged")
    names = {}
    for fn, avals in engine._variant_jobs():
        with engine.mesh:
            text = fn.lower(*engine._variant_args(avals)).as_text()
        module = text.split("module @", 1)[1].split(" ", 1)[0]
        assert module == "jit_" + fn.__name__
        names.setdefault(fn.__name__, fn)
    others = [engine._get_counts_restore()]
    if layout == "paged":
        others += [
            engine._get_block_copy(), engine._get_handoff_export(4),
            engine._get_handoff_import(4), engine._get_mixed(16),
            engine._get_spec_decode(2),
        ]
    else:
        others += [engine._get_copy_prefix(16), engine._get_spec_decode(2)]
    for fn in others:
        names.setdefault(fn.__name__, fn)
    # distinct programs never share a name, and every name tells the
    # layout it serves
    assert len({id(fn) for fn in names.values()}) == len(names)
    assert set(names) <= set(engine_lib.PROGRAM_KINDS)
    for name in names:
        assert name == "counts_restore" or name.endswith("_" + layout)
    expected = {
        kind for kind in engine_lib.PROGRAM_KINDS
        if kind.endswith("_" + layout) and not kind.startswith("init_cache")
    } | {"counts_restore"}
    assert set(names) == expected
    # a second engine gives the same names: they are stable
    again = make_engine(decode_chunk=4, **options)
    assert {fn.__name__ for fn, _ in again._variant_jobs()} <= set(names)


@pytest.mark.parametrize("flash", [True, False], ids=["flash", "xla"])
def test_prefill_dispatch_counts_flash_blocks(flash):
    """A cold dispatch whose attention is the flash kernel (interpret
    mode) carries the blocks the kernel computes and those of the causal
    bucket, as the host helper counts them for the prompts' lengths, and
    the stats add them up; where the attention is XLA, neither is set."""
    import dataclasses

    from langstream_tpu.ops.flash_attention import attention_blocks

    config = dataclasses.replace(
        LlamaConfig.tiny(max_seq_len=512), flash_interpret=flash
    )
    engine = DecodeEngine(
        config, init_params(config), max_slots=2, max_seq_len=512,
        prefill_buckets=[512], decode_chunk=4, prefix_cache=False,
    )
    engine.tracer = tracing.Tracer("flash-blocks")
    lengths = (20, 300)
    engine.start()
    try:
        generate(engine, [[1 + i % 200 for i in range(n)] for n in lengths], 2)
    finally:
        engine.stop()
    spans = [
        span.attributes for span in engine.tracer._spans
        if span.name == "engine.prefill_dispatch"
    ]
    assert spans and all(span["kind"] == "cold" for span in spans)
    computed, causal = attention_blocks(lengths, 512)
    if not flash:
        assert not any("attn_blocks" in span for span in spans)
        assert engine.stats["prefill_attn_blocks_causal"] == 0
        return
    assert computed < causal  # the 20-token row skips its second q block
    assert sum(span["attn_blocks"] for span in spans) == computed
    assert sum(span["attn_blocks_causal"] for span in spans) == causal
    for span in spans:
        assert span["attn_blocks"] <= span["attn_blocks_causal"]
    assert engine.stats["prefill_attn_blocks"] == computed
    assert engine.stats["prefill_attn_blocks_causal"] == causal
