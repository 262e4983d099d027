"""Speculative decoding (ISSUE 7): the self-drafting prompt-lookup
drafter, the on-device accept/reject pass, and the engine's spec scan —
greedy token parity vs the non-speculative oracle across dense/paged ×
bf16/int8, rejection sampling's distribution preservation, paged
length-rewind at a block boundary, watchdog normalization, and the
flight/metrics acceptance evidence."""

import asyncio
import dataclasses
import os
import queue
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(max_seq_len=128, interpret=False):
    from langstream_tpu.providers.jax_local.model import LlamaConfig

    config = LlamaConfig.tiny(max_seq_len=max_seq_len)
    if interpret:
        # CPU hook: the fused paged kernel runs in Pallas interpret mode
        config = dataclasses.replace(config, flash_interpret=True)
    return config


def _engine(spec, *, paged=False, kv_quant=None, max_seq_len=128,
            spec_k=4, **kw):
    from langstream_tpu.providers.jax_local.engine import DecodeEngine
    from langstream_tpu.providers.jax_local.model import init_params

    config = _config(max_seq_len=max_seq_len, interpret=paged)
    paged_kw = (
        dict(kv_layout="paged", kv_block_size=8, paged_kernel="fused")
        if paged else {}
    )
    return DecodeEngine(
        config, init_params(config), max_slots=2, max_seq_len=max_seq_len,
        prefill_buckets=[32], kv_quant=kv_quant,
        spec_decode=spec, spec_k=spec_k, spec_ngram=2,
        **paged_kw, **kw,
    )


# a prompt with strong self-repetition — prompt-lookup territory
def _repetitive(n=30):
    return (list(range(1, 9)) * 8)[:n]


# ---------------------------------------------------------------------- #
# drafter units
# ---------------------------------------------------------------------- #
def _draft(history, length, *, ngram=2, k=3, width=16, active=True):
    import jax.numpy as jnp

    from langstream_tpu.providers.jax_local.spec_decode import draft_ngram

    row = history + [0] * (width - len(history))
    drafts, num = draft_ngram(
        jnp.asarray([row], dtype=jnp.int32),
        jnp.asarray([length], dtype=jnp.int32),
        jnp.asarray([active]),
        ngram=ngram, k=k,
    )
    return np.asarray(drafts)[0].tolist(), int(np.asarray(num)[0])


def test_drafter_proposes_continuation_of_suffix_match():
    # trailing 2-gram (2, 3) occurred at position 1; the drafter
    # proposes what followed it — overlap with the trailing n-gram
    # itself is fine (sources stay within known history)
    drafts, num = _draft([7, 2, 3, 4, 9, 2, 3], 7)
    assert num == 3
    assert drafts == [4, 9, 2]


def test_drafter_prefers_most_recent_match():
    # (2, 3) occurs twice; the later occurrence (followed by 8) wins —
    # recency tracks the local phrase the model is currently copying
    drafts, num = _draft([2, 3, 4, 2, 3, 8, 9, 2, 3], 9)
    assert num == 3
    assert drafts == [8, 9, 2]


def test_drafter_no_match_drafts_zero():
    # unique history: no earlier occurrence of the trailing n-gram →
    # k=0, and the verify step degenerates to a plain decode step
    drafts, num = _draft([1, 2, 3, 4, 5, 6], 6)
    assert num == 0


def test_drafter_needs_continuation_before_pending():
    # (2, 3) "matches" only as the trailing n-gram itself — the trivial
    # self-match proposes nothing
    _, num = _draft([1, 2, 3], 3)
    assert num == 0


def test_drafter_clamps_at_context_boundary():
    # drafted KV writes reach position length-1+num, which must stay
    # inside the cache: at length 14 of width 16 only 2 drafts fit
    history = [5, 1, 2, 9, 9, 9, 9, 9, 9, 9, 9, 9, 5, 1]
    drafts, num = _draft(history, 14, k=3, width=16)
    assert num == 2
    assert drafts[:2] == [2, 9]


def test_drafter_inactive_row_drafts_zero():
    _, num = _draft([2, 3, 4, 2, 3], 5, active=False)
    assert num == 0


# ---------------------------------------------------------------------- #
# greedy parity: spec on == spec off, token for token
# ---------------------------------------------------------------------- #
def _run_pair(spec_engine, oracle, coro_factory):
    spec_engine.start()
    oracle.start()
    try:
        return (
            asyncio.run(coro_factory(spec_engine)),
            asyncio.run(coro_factory(oracle)),
        )
    finally:
        spec_engine.stop()
        oracle.stop()


@pytest.mark.parametrize(
    "paged,kv_quant",
    [
        # tier-1 representatives: one per layout axis and one per pool
        # axis (bf16-dense, int8-paged); the remaining diagonal legs
        # run in the slow tier — each engine pair here costs ~10s
        pytest.param(False, None, id="bf16-dense"),
        pytest.param(True, "int8", id="int8-paged"),
        pytest.param(
            True, None, id="bf16-paged", marks=pytest.mark.slow
        ),
        pytest.param(
            False, "int8", id="int8-dense", marks=pytest.mark.slow
        ),
    ],
)
def test_greedy_parity_with_warm_session(paged, kv_quant):
    """spec-decode: ngram emits the exact oracle token stream — cold
    prefill, decode, and a warm continuation (paged prefix-hit / dense
    prefix-copy admission) all included. The spec leg must also have
    actually speculated, or the parity is vacuous."""
    from langstream_tpu.providers.jax_local.engine import SamplingParams

    async def run(engine):
        first = await engine.generate(
            _repetitive(30), SamplingParams(max_new_tokens=12)
        )
        # shares a long prefix with the first prompt → warm admission
        second = await engine.generate(
            _repetitive(24) + [99, 98], SamplingParams(max_new_tokens=12)
        )
        return first.tokens, second.tokens

    spec_tokens, oracle_tokens = _run_pair(
        _engine("ngram", paged=paged, kv_quant=kv_quant),
        _engine("off", paged=paged, kv_quant=kv_quant),
        run,
    )
    assert spec_tokens == oracle_tokens


def test_greedy_parity_and_fewer_dispatches_high_repetition():
    """The acceptance instrument: on a high-repetition workload the spec
    leg emits the identical stream from FEWER decode scan steps, with
    the drafted/accepted ledger populated."""
    from langstream_tpu.providers.jax_local.engine import SamplingParams

    async def run(engine):
        result = await engine.generate(
            _repetitive(30), SamplingParams(max_new_tokens=32)
        )
        return result.tokens

    spec = _engine("ngram", max_seq_len=256, decode_chunk=4)
    oracle = _engine("off", max_seq_len=256, decode_chunk=4)
    spec_tokens, oracle_tokens = _run_pair(spec, oracle, run)
    assert spec_tokens == oracle_tokens
    assert spec.stats["tokens_drafted"] > 0
    assert spec.stats["tokens_draft_accepted"] > 0
    # fewer forwards per generated token — the whole point
    assert spec.stats["decode_steps"] < oracle.stats["decode_steps"]
    # the ledger decomposes exactly: every accepted draft came out of a
    # drafted candidate, the rest were rejected (wasted)
    rejected = spec.stats["tokens_wasted"].get("draft_rejected", 0)
    assert (
        spec.stats["tokens_draft_accepted"] + rejected
        == spec.stats["tokens_drafted"]
    )
    # per-accepted-token normalizer grew slower than plain step count
    assert spec.stats["decode_token_steps"] > spec.stats["decode_steps"]


def test_greedy_parity_mid_chunk_stop():
    """A stop token landing mid-chunk (and, on the spec leg, potentially
    mid-verify-block) truncates identically: surplus accepted tokens are
    discarded and the length pointer stops at the stop."""
    from langstream_tpu.providers.jax_local.engine import SamplingParams

    # learn the oracle stream first, then stop on a token mid-stream
    async def plain(engine):
        result = await engine.generate(
            _repetitive(30), SamplingParams(max_new_tokens=16)
        )
        return result.tokens

    probe = _engine("off")
    probe.start()
    try:
        stream = asyncio.run(plain(probe))
    finally:
        probe.stop()
    stop = stream[len(stream) // 2]

    async def run(engine):
        result = await engine.generate(
            _repetitive(30),
            SamplingParams(max_new_tokens=16),
            stop_tokens={stop},
        )
        return result.tokens, result.finish_reason

    spec_out, oracle_out = _run_pair(_engine("ngram"), _engine("off"), run)
    assert spec_out == oracle_out
    assert oracle_out[1] == "stop"
    assert stop not in oracle_out[0]


def test_no_draft_stochastic_is_bitwise_oracle():
    """A slot with no draftable repetition reproduces the plain step
    BITWISE — including seeded stochastic sampling (same keys, same
    cond tiering), not just greedily."""
    from langstream_tpu.providers.jax_local.engine import SamplingParams

    async def run(engine):
        result = await engine.generate(
            list(range(1, 31)),
            SamplingParams(
                temperature=0.8, top_k=20, top_p=0.9,
                max_new_tokens=8, seed=1234,
            ),
        )
        return result.tokens

    spec = _engine("ngram")
    oracle = _engine("off")
    spec_tokens, oracle_tokens = _run_pair(spec, oracle, run)
    assert spec_tokens == oracle_tokens


# ---------------------------------------------------------------------- #
# rejection sampling preserves the sampling distribution
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("top_k,top_p", [(0, 0.0), (4, 0.0), (0, 0.85)])
def test_rejection_sampling_preserves_distribution(top_k, top_p):
    """accept-w.p.-p(draft) + residual resampling emits tokens
    distributed exactly as the oracle's truncated/temperature-scaled
    distribution, regardless of what the drafter proposed. Empirical
    check over many seeds at fixed logits (TV distance tolerance)."""
    import jax
    import jax.numpy as jnp

    from langstream_tpu.providers.jax_local import engine as engine_lib
    from langstream_tpu.providers.jax_local.spec_decode import (
        _accept_or_fallback,
    )

    vocab, rows, temp = 8, 8192, 0.7
    logits = jnp.asarray(
        [2.0, 1.5, 1.0, 0.6, 0.3, 0.0, -0.5, -1.0], jnp.float32
    )
    batch = jnp.tile(logits[None, :], (rows, 1))
    temperature = jnp.full((rows,), temp, jnp.float32)
    top_k_arr = jnp.full((rows,), top_k, jnp.int32)
    top_p_arr = jnp.full((rows,), top_p, jnp.float32)
    keys = engine_lib._sampling_keys(
        jnp.arange(rows, dtype=jnp.uint32), jnp.full((rows,), 5, jnp.int32)
    )
    # the draft: token 1 (inside every truncation set used here)
    candidate = jnp.full((rows,), 1, jnp.int32)
    have = jnp.ones((rows,), bool)
    accepted, fallback = _accept_or_fallback(
        batch, temperature, top_k_arr, top_p_arr, keys, candidate, have
    )
    emitted = np.asarray(jnp.where(accepted, candidate, fallback))

    target = engine_lib._truncation_mask(
        batch[:1], top_k_arr[:1], top_p_arr[:1]
    )[0] / temp
    probs = np.asarray(jax.nn.softmax(target))
    counts = np.bincount(emitted, minlength=vocab) / rows
    assert 0.05 < float(np.mean(np.asarray(accepted))) < 1.0
    # total variation distance between empirical and target
    assert 0.5 * np.abs(counts - probs).sum() < 0.03


def test_draft_outside_truncation_always_rejected():
    """A drafted token the truncation set excludes has p=0 and must
    never be emitted as an acceptance."""
    import jax.numpy as jnp

    from langstream_tpu.providers.jax_local import engine as engine_lib
    from langstream_tpu.providers.jax_local.spec_decode import (
        _accept_or_fallback,
    )

    rows = 512
    logits = jnp.asarray(
        [3.0, 2.5, 2.0, 1.5, -2.0, -3.0, -4.0, -5.0], jnp.float32
    )
    batch = jnp.tile(logits[None, :], (rows, 1))
    keys = engine_lib._sampling_keys(
        jnp.arange(rows, dtype=jnp.uint32), jnp.full((rows,), 3, jnp.int32)
    )
    accepted, _ = _accept_or_fallback(
        batch,
        jnp.full((rows,), 0.9, jnp.float32),
        jnp.full((rows,), 4, jnp.int32),   # top-4 keeps tokens 0..3
        jnp.zeros((rows,), jnp.float32),
        keys,
        jnp.full((rows,), 6, jnp.int32),   # drafted token outside top-4
        jnp.ones((rows,), bool),
    )
    assert not bool(np.asarray(accepted).any())


# ---------------------------------------------------------------------- #
# paged rollback: length rewind only, at a block boundary
# ---------------------------------------------------------------------- #
def test_paged_length_rewind_at_block_boundary():
    """Rejected drafts whose KV rows spilled across a block boundary
    roll back by NOT advancing the length pointer: the garbage rows in
    the next (already reserved) block are causally invisible and the
    following verify overwrites them in order. Control = a cache that
    never saw the drafts."""
    import jax.numpy as jnp

    from langstream_tpu.providers.jax_local import model as model_lib

    config = _config(max_seq_len=64, interpret=True)
    params = model_lib.init_params(config)
    freqs = model_lib.model_freqs(config)
    block_size = 8
    tables = jnp.asarray([[1, 2, 3, 4]], jnp.int32)

    def fresh():
        return model_lib.init_paged_cache(config, 8, block_size)

    prompt = jnp.asarray([[3, 1, 4, 1, 5, 9, 2]], jnp.int32)  # 7 tokens
    spec_cache, _, _ = model_lib.paged_prefill(
        config, params, fresh(), prompt, jnp.asarray([7]), tables, freqs,
    )
    control_cache, _, _ = model_lib.paged_prefill(
        config, params, fresh(), prompt, jnp.asarray([7]), tables, freqs,
    )

    # pending token t0 at position 7 = the LAST row of block 1; drafts
    # d1..d3 land at positions 8..10 — the first rows of block 2
    lengths = jnp.asarray([8], jnp.int32)
    block = jnp.asarray([[6, 11, 12, 13]], jnp.int32)
    spec_cache, spec_logits, _ = model_lib.paged_verify_step(
        config, params, spec_cache, block, lengths,
        jnp.asarray([4], jnp.int32), tables, freqs,
    )
    # control: the same step WITHOUT drafts (plain decode of t0)
    control_cache, control_logits, _ = model_lib.paged_decode_step(
        config, params, control_cache, jnp.asarray([6], jnp.int32),
        lengths, tables, freqs,
    )
    np.testing.assert_allclose(
        np.asarray(spec_logits)[:, 0], np.asarray(control_logits),
        rtol=2e-5, atol=2e-5,
    )

    # every draft rejected → lengths advance by ONE only; the next
    # verify (new pending token 7) must see identical state despite the
    # garbage rows at 8..10 — it overwrites position 8 and attends only
    # up to its own block
    lengths = jnp.asarray([9], jnp.int32)
    next_block = jnp.asarray([[7, 21, 22, 23]], jnp.int32)
    _, spec_next, _ = model_lib.paged_verify_step(
        config, params, spec_cache, next_block, lengths,
        jnp.asarray([4], jnp.int32), tables, freqs,
    )
    _, control_next, _ = model_lib.paged_verify_step(
        config, params, control_cache, next_block, lengths,
        jnp.asarray([4], jnp.int32), tables, freqs,
    )
    np.testing.assert_allclose(
        np.asarray(spec_next), np.asarray(control_next),
        rtol=2e-5, atol=2e-5,
    )


# ---------------------------------------------------------------------- #
# watchdog: per-accepted-token normalization
# ---------------------------------------------------------------------- #
def test_watchdog_spec_step_slowdown_does_not_trip():
    """Regression for the ISSUE 7 watchdog fix: a k=4 speculative step
    at 2× the step wall time yields ~4 tokens — per-ACCEPTED-TOKEN
    latency improved, so the degradation detector must not trip (and
    conversely a real 4× per-token regression still must)."""
    from langstream_tpu.runtime.watchdog import EngineWatchdog

    engine = types.SimpleNamespace(
        stats={
            "decode_chunks": 0, "decode_steps": 0,
            "decode_token_steps": 0.0, "decode_time": 0.0,
            "prefill_calls": 0, "warm_prefill_calls": 0,
        },
        _pending=[], _queue=queue.Queue(), slots=[],
        kv_manager=None, num_blocks=0, _crashed=None,
    )
    watchdog = EngineWatchdog(
        engine, min_baseline_chunks=4, degrade_factor=3.0,
        capture_profile=False,
    )
    now = 0.0
    # baseline: plain decode, 8 steps/chunk at 10 ms/step (= 10 ms/token)
    for _ in range(6):
        engine.stats["decode_chunks"] += 1
        engine.stats["decode_steps"] += 8
        engine.stats["decode_token_steps"] += 8.0
        engine.stats["decode_time"] += 8 * 0.010
        now += 5.0
        assert watchdog.check(now=now) is None
    assert watchdog.baseline_step_s == pytest.approx(0.010)
    # speculation enabled: each step takes 2× (20 ms) but accepts the
    # k=4 block → 4 tokens/step = 5 ms/token. NOT a degradation.
    for _ in range(4):
        engine.stats["decode_chunks"] += 1
        engine.stats["decode_steps"] += 8
        engine.stats["decode_token_steps"] += 8 * 4.0
        engine.stats["decode_time"] += 8 * 0.020
        now += 5.0
        assert watchdog.check(now=now) is None
    # a REAL regression in per-token terms still trips
    engine.stats["decode_chunks"] += 1
    engine.stats["decode_steps"] += 8
    engine.stats["decode_token_steps"] += 8.0
    engine.stats["decode_time"] += 8 * 0.050
    assert watchdog.check(now=now + 5.0) == "decode_degraded"


# ---------------------------------------------------------------------- #
# telemetry: flight records + /metrics gauges
# ---------------------------------------------------------------------- #
@pytest.fixture
def flight_recorder(tmp_path):
    from langstream_tpu.runtime import flight

    saved = flight.RECORDER.path
    flight.RECORDER.path = None
    flight.RECORDER._pending.clear()
    path = flight.configure(str(tmp_path / "flight"))
    yield flight, path
    flight.RECORDER.flush()
    flight.RECORDER.path = saved


def test_flight_and_metrics_acceptance_evidence(flight_recorder):
    """The ISSUE 7 acceptance evidence chain: a high-repetition workload
    leaves drafted/accepted gain fields on flight decode_chunk records,
    the acceptance-rate gauge + draft_rejected wasted label on
    engines_snapshot, and both render through the shared Prometheus
    text path every /metrics surface serves."""
    from langstream_tpu.api.metrics import (
        parse_prometheus_text,
        prometheus_text,
    )
    from langstream_tpu.providers.jax_local.engine import (
        SamplingParams,
        engines_snapshot,
    )

    flight, path = flight_recorder
    # the spec gauges sum over every engine still in _LIVE_ENGINES (a
    # WeakSet): cycle-pinned engines from EARLIER tests linger until a
    # gc pass and inflate the absolute totals, so collect first and
    # assert the DELTA this engine contributed (full-suite runs saw
    # exactly that flake at ~700 tests of gc pressure)
    import gc

    gc.collect()
    before = engines_snapshot()
    engine = _engine("ngram", max_seq_len=256, decode_chunk=4)
    engine.start()
    try:
        async def run():
            await engine.generate(
                _repetitive(30), SamplingParams(max_new_tokens=32)
            )

        asyncio.run(run())
        gauges = engines_snapshot()
    finally:
        engine.stop()
    flight.RECORDER.flush()

    drafted = engine.stats["tokens_drafted"]
    accepted = engine.stats["tokens_draft_accepted"]
    assert drafted > 0 and accepted > 0
    total_drafted = gauges["spec_tokens_drafted_total"]
    total_accepted = gauges["spec_tokens_accepted_total"]
    assert total_drafted - before.get(
        "spec_tokens_drafted_total", 0.0
    ) == float(drafted)
    assert total_accepted - before.get(
        "spec_tokens_accepted_total", 0.0
    ) == float(accepted)
    assert gauges["spec_acceptance_rate"] == pytest.approx(
        total_accepted / total_drafted, abs=1e-4
    )
    rendered = prometheus_text({}, gauges)
    parsed = parse_prometheus_text(rendered)
    assert parsed["spec_acceptance_rate"][0][1] > 0
    wasted = dict(
        (labels["reason"], value)
        for labels, value in parsed["jax_engine_tokens_wasted_total"]
    )
    before_rejected = before.get(
        'jax_engine_tokens_wasted_total{reason="draft_rejected"}', 0.0
    )
    assert wasted["draft_rejected"] - before_rejected == drafted - accepted

    chunks = [
        e for e in flight.read_artifact(path)
        if e.get("kind") == "decode_chunk"
    ]
    assert chunks
    assert sum(c.get("drafted", 0) for c in chunks) == drafted
    assert sum(c.get("accepted", 0) for c in chunks) == accepted
    # fewer decode dispatches per generated token than one-per-token
    steps = sum(c["steps"] for c in chunks)
    assert steps < engine.stats["tokens_generated"]


# ---------------------------------------------------------------------- #
# plumbing
# ---------------------------------------------------------------------- #
def test_engine_rejects_unknown_spec_mode():
    with pytest.raises(ValueError, match="spec decode"):
        _engine("turbo")


def test_provider_plumbs_spec_decode():
    """engine: {spec-decode: ...} flows compiler globals → provider →
    engine (string-coerced like every other engine knob)."""
    from langstream_tpu.providers.jax_local.provider import (
        JaxCompletionsService,
    )

    service = JaxCompletionsService({
        "model": {"preset": "tiny"},
        "engine": {
            "max-slots": "2", "max-seq-len": "64",
            "spec-decode": "ngram", "spec-k": "3", "spec-ngram": "3",
        },
    })
    try:
        assert service.engine.spec_decode == "ngram"
        assert service.engine.spec
        assert service.engine.spec_k == 3
        assert service.engine.spec_ngram == 3
        assert service.engine.spec_block == 4
    finally:
        service.engine.stop()


def test_mirror_rejects_spec_decode():
    engine = _engine("ngram")
    engine.mirror = object()
    try:
        with pytest.raises(NotImplementedError, match="spec_decode"):
            engine._check_mirror_layout()
    finally:
        engine.mirror = None
        engine.stop()
