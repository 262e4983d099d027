"""The engine harvests a cycle's prefills BEFORE it builds that cycle's
decode chunk (ISSUE 30): a prefilled slot decodes in the chunk that runs
behind its prefill, not a whole chunk later. CPU backend, tiny engine,
``decode_chunk`` 8; the loop's order is read from its own phase spans."""

import concurrent.futures
import time

import pytest

from langstream_tpu.providers.jax_local import engine as engine_lib
from langstream_tpu.providers.jax_local.engine import (
    DecodeEngine,
    GenerationRequest,
    SamplingParams,
)
from langstream_tpu.providers.jax_local.model import LlamaConfig, init_params
from langstream_tpu.runtime import tracing

CHUNK = 8
CONFIG = LlamaConfig.tiny(max_seq_len=128)
JOIN_STATS = ("prefill_rows", "prefill_join_rows")


def make_engine(**options):
    options.setdefault("max_slots", 4)
    options.setdefault("prefill_buckets", [16, 32])
    engine = DecodeEngine(
        CONFIG, init_params(CONFIG), max_seq_len=128, decode_chunk=CHUNK,
        **options,
    )
    engine.tracer = tracing.Tracer("harvest-order")
    engine.start()
    return engine


@pytest.fixture(scope="module")
def engine():
    engine = make_engine()
    yield engine
    engine.stop()


def prompt(seed, length=5):
    return [(7 * seed + 3 * j) % 250 + 1 for j in range(length)]


class Stream:
    """One request submitted straight to the engine (no event loop), so a
    token's callback runs on the engine's thread at the instant the loop
    emits it."""

    def __init__(self, tokens, new_tokens, on_first=None, **fields):
        self.stamps = []
        self.future = concurrent.futures.Future()
        self.on_first = on_first
        self.request = GenerationRequest(
            prompt_tokens=list(tokens),
            sampling=SamplingParams(max_new_tokens=new_tokens),
            on_token=self._on_token,
            future=self.future,
            **fields,
        )

    def _on_token(self, token, last):
        self.stamps.append((time.perf_counter_ns(), token))
        if len(self.stamps) == 1 and self.on_first is not None:
            self.on_first()

    @property
    def tokens(self):
        return [token for _, token in self.stamps]

    def wait(self):
        result = self.future.result(timeout=120)
        assert result.tokens == self.tokens
        return self.tokens


def solo(engine, tokens, new_tokens, **fields):
    stream = Stream(tokens, new_tokens, **fields)
    engine.submit(stream.request)
    return stream.wait()


def joined_while_running(engine, late, runner_tokens=1 + 4 * CHUNK):
    """A stream is decoding; ``late`` is submitted from its first token's
    callback, so ``late`` is admitted in a cycle whose chunk would run
    without it under the old order. Returns the spans of the episode."""
    engine.tracer.clear()
    runner = Stream(
        prompt(91, 6), runner_tokens,
        on_first=lambda: engine.submit(late.request),
    )
    engine.submit(runner.request)
    runner.wait()
    late.wait()
    spans = sorted(engine.tracer._spans, key=lambda span: span.start_ns)
    return [span for span in spans if span.name.startswith("engine.")]


def named(spans, name):
    return [span for span in spans if span.name == name]


def stats_delta(engine, before):
    return {key: engine.stats[key] - before[key] for key in JOIN_STATS}


def snapshot(engine):
    return {key: engine.stats[key] for key in JOIN_STATS}


# ------------------------------------------------------------------ #
# (1) the order of one cycle
# ------------------------------------------------------------------ #
def test_a_request_is_active_in_the_dispatch_of_the_cycle_that_admits_it(
    engine,
):
    late = Stream(prompt(3), 1 + CHUNK)
    spans = joined_while_running(engine, late)
    launches = named(spans, "engine.prefill_dispatch")
    assert len(launches) == 2  # the runner's, then the late one's
    launch = launches[1]
    after = [span for span in spans if span.start_ns > launch.start_ns]
    order = [
        span.name for span in after
        if span.name in ("engine.harvest_prefills", "engine.dispatch_decode")
    ]
    # its harvest comes before the next dispatch, and that dispatch
    # carries both streams
    assert order[:2] == ["engine.harvest_prefills", "engine.dispatch_decode"]
    harvest = named(after, "engine.harvest_prefills")[0]
    dispatch = named(after, "engine.dispatch_decode")[0]
    assert harvest.attributes["batch"] == launch.attributes["batch"]
    assert harvest.attributes["joined"] == 1
    assert dispatch.attributes["active"] == 2
    # no chunk ran between the launch and the harvest
    assert not [
        span for span in named(spans, "engine.wait_chunk")
        if launch.start_ns < span.start_ns < harvest.start_ns
    ]


def test_the_first_token_reaches_the_callback_before_its_chunks_tokens(
    engine,
):
    late = Stream(prompt(4), 1 + CHUNK)
    spans = joined_while_running(engine, late)
    launch = named(spans, "engine.prefill_dispatch")[1]
    dispatch = [
        span for span in named(spans, "engine.dispatch_decode")
        if span.start_ns > launch.start_ns
    ][0]
    stamps = [stamp for stamp, _ in late.stamps]
    assert len(stamps) == 1 + CHUNK
    # the first token is out before the chunk is even built; the chunk's
    # eight come after it has run
    assert launch.start_ns < stamps[0] < dispatch.start_ns
    assert all(
        stamp > dispatch.start_ns + dispatch.duration_ns
        for stamp in stamps[1:]
    )


@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_a_request_of_1_plus_8k_tokens_takes_k_chunks_alone(engine, chunks):
    log = len(engine.chunk_log)
    before = snapshot(engine)
    assert len(solo(engine, prompt(chunks), 1 + CHUNK * chunks)) == (
        1 + CHUNK * chunks
    )
    assert engine.chunk_log[log:] == [
        (CHUNK, 1, wall) for _, _, wall in engine.chunk_log[log:]
    ]
    assert len(engine.chunk_log) - log == chunks
    assert stats_delta(engine, before) == {
        "prefill_rows": 1, "prefill_join_rows": 1,
    }


def test_a_request_stays_one_cycle_less_than_before_beside_a_stream(engine):
    """Beside a running stream a request of 1 + 8k tokens is active in k
    dispatches, the first of them in the cycle that admitted it: its
    life is k cycles, where the old order made it k + 1."""
    log = len(engine.chunk_log)
    late = Stream(prompt(5), 1 + 2 * CHUNK)
    joined_while_running(engine, late, runner_tokens=1 + 4 * CHUNK)
    active = [n_active for _, n_active, _ in engine.chunk_log[log:]]
    # the runner's four chunks; the late request rides the second and
    # the third of them
    assert active == [1, 2, 2, 1]


# ------------------------------------------------------------------ #
# (2) the same tokens as alone, whatever the admission path
# ------------------------------------------------------------------ #
def _cold(engine):
    return dict(tokens=prompt(11, 9))


def _warm(engine):
    first = prompt(12, 8)
    answer = solo(engine, first, 4, session_id="warm-join")
    follow = first + answer + prompt(13, 6)
    return dict(tokens=follow, session_id="warm-join")


def _long(engine):
    # longer than the largest bucket: three windows, one record
    return dict(tokens=prompt(14, 75))


CASES = {
    "cold": ({}, _cold),
    "warm": ({}, _warm),
    "long-prompt": ({}, _long),
    "paged-split": ({"kv_layout": "paged", "kv_block_size": 16}, _cold),
    "pipelined": ({"pipeline_decode": True}, _cold),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_joining_request_decodes_the_tokens_it_decodes_alone(case):
    options, build = CASES[case]
    new_tokens = 1 + 2 * CHUNK
    oracle = make_engine(**options)
    try:
        expected = solo(oracle, new_tokens=new_tokens, **build(oracle))
    finally:
        oracle.stop()
    engine = make_engine(**options)
    try:
        fields = build(engine)
        before = snapshot(engine)
        hits = engine.stats["session_hits"]
        late = Stream(new_tokens=new_tokens, **fields)
        spans = joined_while_running(engine, late)
        assert late.tokens == expected and len(expected) == new_tokens
        # the runner's prefill and the late one's, both joined
        assert stats_delta(engine, before) == {
            "prefill_rows": 2, "prefill_join_rows": 2,
        }
        assert engine.stats["session_hits"] - hits == (case == "warm")
        harvests = named(spans, "engine.harvest_prefills")
        assert [span.attributes["joined"] for span in harvests] == [1, 1]
        assert engine.stats["prefill_join_wait"] > 0
    finally:
        engine.stop()


# ------------------------------------------------------------------ #
# (3) the counters
# ------------------------------------------------------------------ #
def test_a_request_its_first_token_ends_is_prefilled_and_does_not_join(
    engine,
):
    before = snapshot(engine)
    log = len(engine.chunk_log)
    assert len(solo(engine, prompt(21), 1)) == 1
    assert stats_delta(engine, before) == {
        "prefill_rows": 1, "prefill_join_rows": 0,
    }
    assert len(engine.chunk_log) == log


def test_the_join_counters_are_exported(engine):
    solo(engine, prompt(22), 1 + CHUNK)
    gauges = engine_lib.engines_snapshot()
    rows = gauges["jax_engine_prefill_rows_total"]
    joined = gauges["jax_engine_prefill_join_rows_total"]
    # process-wide sums over live engines: this engine's are in them
    assert rows >= engine.stats["prefill_rows"] >= 1
    assert joined >= engine.stats["prefill_join_rows"] >= 1
    assert joined <= rows
    assert gauges["jax_engine_prefill_join_wait_seconds_total"] >= (
        round(engine.stats["prefill_join_wait"], 6) - 1e-6
    )
    assert engine.stats["prefill_join_wait"] > 0


# ------------------------------------------------------------------ #
# (4) prompts past the largest bucket: one a cycle while slots decode
# ------------------------------------------------------------------ #
def _long(seed):
    return prompt(seed, 70)  # three windows of the 32 bucket


@pytest.fixture(scope="module")
def cold_engine():
    # no prefix reuse: a prompt asked twice is prefilled cold twice
    engine = make_engine(prefix_cache=False)
    yield engine
    engine.stop()


@pytest.mark.parametrize("decoding", [True, False], ids=["beside-a-stream", "idle"])
def test_decoding_slots_wait_for_one_long_prompt_a_cycle(cold_engine, decoding):
    """Two prompts past the largest bucket find two free slots at once.
    Beside a decoding stream the second waits for the cycle's chunk, so
    the stream stalls for one prompt's windows and not for both; with
    nothing decoding there is no one to stall and both go at once. Either
    way each decodes what it decodes alone."""
    engine = cold_engine
    alone = [solo(engine, _long(seed), 1 + CHUNK) for seed in (31, 32)]
    held = engine.stats["long_prompts_held"]
    log = len(engine.dispatch_log)
    pair = [Stream(_long(seed), 1 + CHUNK) for seed in (31, 32)]

    def submit_both():
        for stream in pair:
            engine.submit(stream.request)

    if decoding:
        runner = Stream(prompt(93, 6), 1 + 6 * CHUNK, on_first=submit_both)
        engine.submit(runner.request)
        runner.wait()
    else:
        submit_both()
    assert [stream.wait() for stream in pair] == alone
    kinds = [entry["kind"] for entry in engine.dispatch_log[log:]]
    runs = [len(run) for run in "".join(kind[0] for kind in kinds).split("d") if run]
    if decoding:
        assert engine.stats["long_prompts_held"] - held == 1
        assert runs == [1, 3, 3]  # the runner's bucket, then a prompt a cycle
    else:
        assert engine.stats["long_prompts_held"] == held
        assert sorted(runs) in ([6], [3, 3])  # one drain took both, or one each
    gauges = engine_lib.engines_snapshot()
    assert gauges["jax_engine_long_prompts_held_total"] >= engine.stats["long_prompts_held"]
