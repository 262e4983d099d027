import asyncio

import numpy as np
import pytest

from langstream_tpu.providers.jax_local.engine import (
    DecodeEngine,
    GenerationRequest,
    SamplingParams,
)
from langstream_tpu.providers.jax_local.model import LlamaConfig, init_params
from langstream_tpu.providers.jax_local.tokenizer import ByteTokenizer


@pytest.fixture(scope="module")
def engine():
    config = LlamaConfig.tiny(max_seq_len=128)
    params = init_params(config)
    engine = DecodeEngine(
        config, params, max_slots=4, max_seq_len=128, prefill_buckets=[16, 32, 64]
    )
    engine.start()
    yield engine
    engine.stop()


def test_generate_deterministic(engine):
    async def main():
        prompt = [1, 2, 3, 4, 5]
        r1 = await engine.generate(prompt, SamplingParams(max_new_tokens=8))
        r2 = await engine.generate(prompt, SamplingParams(max_new_tokens=8))
        assert len(r1.tokens) == 8
        assert r1.tokens == r2.tokens  # greedy => deterministic
        assert r1.prompt_tokens == 5

    asyncio.run(main())


def test_streaming_callbacks(engine):
    async def main():
        seen = []

        def on_token(token, last):
            seen.append((token, last))

        result = await engine.generate(
            [9, 8, 7], SamplingParams(max_new_tokens=5), on_token=on_token
        )
        await asyncio.sleep(0.05)  # let callbacks drain
        assert [t for t, _ in seen] == result.tokens
        assert seen[-1][1] is True

    asyncio.run(main())


def test_concurrent_requests_continuous_batching(engine):
    async def main():
        prompts = [[i + 1, i + 2, i + 3] for i in range(6)]  # > max_slots
        results = await asyncio.gather(
            *[
                engine.generate(p, SamplingParams(max_new_tokens=6))
                for p in prompts
            ]
        )
        assert all(len(r.tokens) == 6 for r in results)
        # each prompt decodes independently & deterministically
        again = await engine.generate(prompts[0], SamplingParams(max_new_tokens=6))
        assert again.tokens == results[0].tokens

    asyncio.run(main())


def test_concurrent_same_as_solo(engine):
    """Continuous batching must not change any request's output."""

    async def main():
        prompts = [[5, 6, 7], [11, 12, 13], [21, 22, 23]]
        solo = []
        for p in prompts:
            r = await engine.generate(p, SamplingParams(max_new_tokens=5))
            solo.append(r.tokens)
        batched = await asyncio.gather(
            *[engine.generate(p, SamplingParams(max_new_tokens=5)) for p in prompts]
        )
        assert [r.tokens for r in batched] == solo

    asyncio.run(main())


def test_stop_tokens(engine):
    async def main():
        # find what greedy generates, then stop on its 2nd token
        free = await engine.generate([1, 2], SamplingParams(max_new_tokens=6))
        stop = free.tokens[2]
        result = await engine.generate(
            [1, 2], SamplingParams(max_new_tokens=6), stop_tokens={stop}
        )
        assert result.tokens == free.tokens[:2]
        assert result.finish_reason == "stop"

    asyncio.run(main())


def test_session_kv_reuse(engine):
    async def main():
        base_prefills = engine.stats["prefill_calls"]
        prompt1 = [1, 2, 3, 4]
        r1 = await engine.generate(
            prompt1, SamplingParams(max_new_tokens=4), session_id="sess-A"
        )
        assert engine.stats["prefill_calls"] == base_prefills + 1
        # follow-up extends (prompt1 + answer) — warm cache, no prefill call
        prompt2 = prompt1 + r1.tokens + [40, 41]
        hits = engine.stats["session_hits"]
        r2 = await engine.generate(
            prompt2, SamplingParams(max_new_tokens=4), session_id="sess-A"
        )
        assert engine.stats["session_hits"] == hits + 1
        assert engine.stats["prefill_calls"] == base_prefills + 1  # no new prefill
        assert len(r2.tokens) == 4
        # correctness: same prompt cold must give identical tokens
        r3 = await engine.generate(prompt2, SamplingParams(max_new_tokens=4))
        assert r3.tokens == r2.tokens

    asyncio.run(main())


def test_prompt_too_long_rejected(engine):
    async def main():
        with pytest.raises(ValueError, match="exceeds"):
            await engine.generate(
                list(range(200)), SamplingParams(max_new_tokens=1)
            )

    asyncio.run(main())


def test_long_prompt_chunked_prefill_matches_single_window():
    """A prompt longer than the largest bucket prefills in bucket-sized
    windows (overlap-shifted tail); greedy output must be identical to an
    engine whose bucket swallows the prompt whole."""
    config = LlamaConfig.tiny(max_seq_len=256)
    params = init_params(config)
    prompt = [(13 * i) % 250 + 1 for i in range(90)]
    sampling = SamplingParams(max_new_tokens=10)

    async def run(buckets):
        engine = DecodeEngine(
            config, params, max_slots=2, max_seq_len=256,
            prefill_buckets=buckets,
        )
        engine.start()
        try:
            return (await engine.generate(prompt, sampling)).tokens
        finally:
            engine.stop()

    chunked = asyncio.run(run([32]))       # 90 tokens -> 2 full + tail
    whole = asyncio.run(run([128]))
    assert len(chunked) == 10
    assert chunked == whole


def test_long_warm_suffix_chunked_and_reused():
    """A session follow-up whose suffix exceeds the largest bucket still
    reuses the pinned prefix (session hit) and decodes the same tokens as
    a cold engine fed the full prompt."""
    config = LlamaConfig.tiny(max_seq_len=256)
    params = init_params(config)
    first = [(7 * i) % 250 + 1 for i in range(24)]
    sampling = SamplingParams(max_new_tokens=6)

    async def main():
        engine = DecodeEngine(
            config, params, max_slots=2, max_seq_len=256,
            prefill_buckets=[32],
        )
        engine.start()
        try:
            r1 = await engine.generate(first, sampling, session_id="s")
            follow = first + list(r1.tokens) + [
                (11 * i) % 250 + 1 for i in range(70)
            ]
            r2 = await engine.generate(follow, sampling, session_id="s")
            assert engine.stats["session_hits"] == 1
            cold_engine = DecodeEngine(
                config, params, max_slots=2, max_seq_len=256,
                prefill_buckets=[128],
            )
            cold_engine.start()
            try:
                cold = await cold_engine.generate(follow, sampling)
            finally:
                cold_engine.stop()
            assert r2.tokens == cold.tokens
        finally:
            engine.stop()

    asyncio.run(main())


def test_pipelined_decode_with_staggered_arrivals_matches_serial():
    """pipeline_decode + prefill overlap + requests joining mid-stream:
    every request's greedy tokens must match a plain serial engine's."""
    config = LlamaConfig.tiny(max_seq_len=128)
    params = init_params(config)
    sampling = SamplingParams(max_new_tokens=10)

    def prompt(i):
        return [(9 * i + j) % 250 + 1 for j in range(8 + i % 5)]

    async def staggered(engine):
        async def late(i):
            await asyncio.sleep(0.002 * i)
            return await engine.generate(prompt(i), sampling)

        return await asyncio.gather(*[late(i) for i in range(10)])

    async def main():
        pipelined = DecodeEngine(
            config, params, max_slots=3, max_seq_len=128,
            prefill_buckets=[16], decode_chunk=4, pipeline_decode=True,
        )
        pipelined.start()
        try:
            results = await staggered(pipelined)
        finally:
            pipelined.stop()
        serial = DecodeEngine(
            config, params, max_slots=3, max_seq_len=128,
            prefill_buckets=[16], decode_chunk=4,
        )
        serial.start()
        try:
            for i in range(10):
                expected = await serial.generate(prompt(i), sampling)
                assert results[i].tokens == expected.tokens, f"request {i}"
        finally:
            serial.stop()

    asyncio.run(main())


def test_session_reuse_races_cold_admissions_under_pressure():
    """VERDICT r2 weak #5: more live sessions than slots, follow-ups
    racing cold admissions. Whatever mix of warm hits and LRU evictions
    the scheduler lands on, every result must equal the cold-engine
    answer, and the hottest sessions must actually get reuse."""
    config = LlamaConfig.tiny(max_seq_len=256)
    params = init_params(config)
    sampling = SamplingParams(max_new_tokens=6)

    def prompt(i):
        return [(5 * i + j) % 250 + 1 for j in range(20)]

    async def main():
        engine = DecodeEngine(
            config, params, max_slots=4, max_seq_len=256,
            prefill_buckets=[32, 64],
        )
        engine.start()
        try:
            firsts = await asyncio.gather(*[
                engine.generate(prompt(i), sampling, session_id=f"c{i}")
                for i in range(8)
            ])
            follows = [
                prompt(i) + list(firsts[i].tokens) + prompt(i + 50)
                for i in range(8)
            ]
            # follow-ups for all 8 sessions at once: 4 pinned slots max,
            # so warm hits and cold (re)admissions race for slots
            seconds = await asyncio.gather(*[
                engine.generate(follows[i], sampling, session_id=f"c{i}")
                for i in range(8)
            ])
            reference = DecodeEngine(
                config, params, max_slots=4, max_seq_len=256,
                prefill_buckets=[64],
            )
            reference.start()
            try:
                for i in range(8):
                    cold = await reference.generate(follows[i], sampling)
                    assert seconds[i].tokens == cold.tokens, f"session c{i}"
            finally:
                reference.stop()
            # at most 4 pins could survive round 1; some must get reuse
            assert 0 < engine.stats["session_hits"] <= 4
        finally:
            engine.stop()

    asyncio.run(main())


def test_sampling_tiers_match_full_path():
    """The lax.cond tiers in _sample are an optimization, not a
    semantics change: for any given key, the cheap tiers must produce
    EXACTLY the token the full truncated path would (greedy == argmax;
    k=0/p=0 masking is the identity, so plain categorical == truncated
    categorical on the same scaled logits)."""
    import jax
    import jax.numpy as jnp

    from langstream_tpu.providers.jax_local.engine import (
        _sample,
        _sampling_keys,
    )

    key = jax.random.PRNGKey(7)
    logits = jax.random.normal(key, (5, 64), dtype=jnp.float32) * 3.0

    def keys_for(seed_base):
        return _sampling_keys(
            jnp.arange(seed_base, seed_base + 5, dtype=jnp.uint32),
            jnp.full((5,), 9, jnp.int32),
        )

    def run(temperature, top_k, top_p, keys):
        return _sample(
            logits,
            jnp.full((5,), temperature, jnp.float32),
            jnp.full((5,), top_k, jnp.int32),
            keys,
            jnp.full((5,), top_p, jnp.float32),
        )

    # greedy tier == argmax
    sample_keys = keys_for(11)
    assert (run(0.0, 0, 0.0, sample_keys) == jnp.argmax(logits, -1)).all()
    # plain tier (no truncation) == truncated path with identity masks:
    # force the truncated branch by setting top_k to the full vocab
    # (keeps >= 64th largest = everything, i.e. no truncation)
    plain = run(0.9, 0, 0.0, sample_keys)
    truncated_identity = run(0.9, 64, 0.0, sample_keys)
    assert (plain == truncated_identity).all()
    # top-p = 1.0 keeps the whole nucleus: also identical to plain
    assert (plain == run(0.9, 0, 1.0, sample_keys)).all()
    # a tight top-k must restrict samples to the k best tokens
    top2 = jnp.argsort(logits, axis=-1)[:, -2:]
    for seed in range(5):
        picks = run(1.3, 2, 0.0, keys_for(seed * 100))
        assert all(
            int(picks[row]) in set(top2[row].tolist()) for row in range(5)
        )


def test_temperature_sampling_varies(engine):
    async def main():
        results = set()
        for seed in range(4):
            r = await engine.generate(
                [3, 1, 4], SamplingParams(temperature=1.5, max_new_tokens=6)
            )
            results.add(tuple(r.tokens))
        assert len(results) > 1  # hot sampling is not constant

    asyncio.run(main())


def test_provider_end_to_end():
    async def main():
        from langstream_tpu.providers.jax_local.provider import (
            JaxCompletionsService,
            JaxEmbeddingsService,
        )
        from langstream_tpu.api.service import ChatMessage

        service = JaxCompletionsService(
            {
                "model": {"preset": "tiny", "max_seq_len": 128},
                "engine": {"max-slots": 2, "max-seq-len": 128},
            }
        )
        chunks = []

        class Consumer:
            def consume_chunk(self, answer_id, index, chunk, last):
                chunks.append((chunk.content, last))

        result = await service.get_chat_completions(
            [ChatMessage("user", "hi")],
            {"max-tokens": 6},
            Consumer(),
        )
        await asyncio.sleep(0.05)
        assert result.completion_tokens <= 6
        assert chunks and chunks[-1][1] is True
        streamed = "".join(c for c, _ in chunks)
        assert streamed == result.content
        await service.close()

        embeddings = JaxEmbeddingsService({}, None)
        vectors = await embeddings.compute_embeddings(["hello", "world"])
        assert len(vectors) == 2
        norms = [sum(v * v for v in vec) for vec in vectors]
        assert all(abs(n - 1.0) < 1e-3 for n in norms)

    asyncio.run(main())


@pytest.mark.slow
@pytest.mark.parametrize("topk", [0, 2])
def test_engine_fuzz_interleavings(topk):
    """Soak the whole loop at once: pipelined dispatch, staggered
    arrivals, session reuse under slot pressure, long prompts through
    chunked prefill, random sampling params, and cancellations racing
    admission — with and without logprobs_topk (whose extra jit
    outputs must survive every path). Every future must resolve; every
    uncancelled result must be non-empty and within budget; the engine
    must stay serviceable."""
    import random

    config = LlamaConfig.tiny(max_seq_len=192)
    params = init_params(config)
    rng = random.Random(20260730)

    async def main():
        engine = DecodeEngine(
            config, params, max_slots=3, max_seq_len=192,
            prefill_buckets=[16, 32], decode_chunk=4,
            pipeline_decode=True, logprobs_topk=topk,
        )
        engine.start()

        # a few shared templates so the cross-slot prefix cache (copies,
        # salvage, same-round duplicates) races sessions/cancellations
        templates = [
            [(t * 31 + j) % 250 + 1 for j in range(24)] for t in range(3)
        ]

        async def one(i):
            length = rng.choice([3, 9, 20, 40, 90])  # 40/90 > bucket 32
            prompt = [(i * 13 + j) % 250 + 1 for j in range(length)]
            if rng.random() < 0.4:
                prompt = templates[i % 3] + prompt[: max(length - 24, 2)]
            sampling = SamplingParams(
                temperature=rng.choice([0.0, 0.0, 0.9]),
                top_k=rng.choice([0, 5]),
                top_p=rng.choice([0.0, 0.9]),
                max_new_tokens=rng.choice([1, 4, 11]),
                seed=rng.choice([None, 7]),
                frequency_penalty=rng.choice([0.0, 2.0]),
                logit_bias=rng.choice([None, {17: 5.0}]),
            )
            session = rng.choice([None, f"s{i % 4}"])
            handle: list = []
            await asyncio.sleep(rng.random() * 0.05)
            task = asyncio.ensure_future(engine.generate(
                prompt, sampling, session_id=session, handle=handle
            ))
            if rng.random() < 0.25:
                await asyncio.sleep(rng.random() * 0.1)
                if handle:
                    handle[0].cancel()
            result = await asyncio.wait_for(task, timeout=120)
            if result.finish_reason != "cancelled":
                assert 0 < len(result.tokens) <= sampling.max_new_tokens
                assert len(result.logprobs) == len(result.tokens)
                if topk:
                    assert len(result.top_logprobs) == len(result.tokens)
                    assert all(
                        len(ids) == topk and len(lps) == topk
                        for ids, lps in result.top_logprobs
                    )
                else:
                    assert result.top_logprobs is None
            return result

        try:
            results = await asyncio.gather(*[one(i) for i in range(40)])
            assert len(results) == 40
            # the engine is still healthy afterwards
            final = await asyncio.wait_for(
                engine.generate([1, 2, 3], SamplingParams(max_new_tokens=3)),
                timeout=60,
            )
            assert len(final.tokens) == 3
            assert not engine._prefill_inflight
            assert all(not s.active for s in engine.slots)
        finally:
            engine.stop()

    asyncio.run(main())


def test_logit_bias_forces_and_bans_tokens():
    """OpenAI logit_bias: +100 forces a token under greedy decoding
    (including the prefill-sampled first token), -100 bans it; an empty
    bias is an exact identity."""
    config = LlamaConfig.tiny(max_seq_len=128)
    params = init_params(config)
    prompt = [3, 5, 7]

    async def main():
        engine = DecodeEngine(
            config, params, max_slots=2, max_seq_len=128,
            prefill_buckets=[16], decode_chunk=4,
        )
        engine.start()
        try:
            base = await engine.generate(
                prompt, SamplingParams(max_new_tokens=8)
            )
            same = await engine.generate(
                prompt, SamplingParams(max_new_tokens=8, logit_bias={})
            )
            assert same.tokens == base.tokens  # empty bias is identity
            forced = await engine.generate(
                prompt,
                SamplingParams(max_new_tokens=8, logit_bias={42: 1000.0}),
            )
            assert forced.tokens == [42] * 8
            banned_id = base.tokens[0]
            banned = await engine.generate(
                prompt,
                SamplingParams(
                    max_new_tokens=8, logit_bias={banned_id: -1000.0}
                ),
            )
            assert banned_id not in banned.tokens
        finally:
            engine.stop()

    asyncio.run(main())


def test_seeded_sampling_reproducible_across_batches():
    """A seeded request reproduces its sampled tokens EXACTLY no matter
    what shares the batch (per-slot keys derive from seed + position);
    different seeds diverge."""
    config = LlamaConfig.tiny(max_seq_len=128)
    params = init_params(config)
    prompt = [11, 22, 33]

    async def main():
        engine = DecodeEngine(
            config, params, max_slots=4, max_seq_len=128,
            prefill_buckets=[16], decode_chunk=4,
        )
        engine.start()
        try:
            seeded = SamplingParams(
                temperature=1.0, max_new_tokens=12, seed=1234
            )
            alone = await engine.generate(prompt, seeded)
            # same seed, but now racing three other hot requests
            crowded, *_ = await asyncio.gather(
                engine.generate(prompt, seeded),
                *[
                    engine.generate(
                        [7 * i, 9, 9, 9],
                        SamplingParams(temperature=1.5, max_new_tokens=12),
                    )
                    for i in range(3)
                ],
            )
            assert crowded.tokens == alone.tokens
            other = await engine.generate(
                prompt,
                SamplingParams(temperature=1.0, max_new_tokens=12, seed=99),
            )
            assert other.tokens != alone.tokens
        finally:
            engine.stop()

    asyncio.run(main())


def test_frequency_penalty_suppresses_repeats():
    """A strong frequency penalty must cap per-token repeats in greedy
    decoding (each use lowers that token's logit), while zero penalties
    leave the distribution untouched (exact float identity)."""
    config = LlamaConfig.tiny(max_seq_len=256)
    params = init_params(config)

    async def main():
        engine = DecodeEngine(
            config, params, max_slots=2, max_seq_len=256,
            prefill_buckets=[16], decode_chunk=8,
        )
        engine.start()
        try:
            prompt = [5, 6, 7]
            base = await engine.generate(
                prompt, SamplingParams(max_new_tokens=40)
            )
            zeroed = await engine.generate(
                prompt,
                SamplingParams(
                    max_new_tokens=40,
                    presence_penalty=0.0, frequency_penalty=0.0,
                ),
            )
            assert zeroed.tokens == base.tokens  # 0-penalty is identity
            penalized = await engine.generate(
                prompt,
                SamplingParams(max_new_tokens=40, frequency_penalty=100.0),
            )
            from collections import Counter

            worst = max(Counter(penalized.tokens).values())
            # a 100-logit hit per use forces a new argmax every time
            assert worst <= 2, Counter(penalized.tokens).most_common(3)
            assert penalized.tokens != base.tokens
        finally:
            engine.stop()

    asyncio.run(main())


def test_cancel_frees_slot_and_resolves():
    """cancel() ends generation at the next token boundary (reason
    'cancelled'); a request cancelled before admission resolves without
    ever taking a slot; the engine keeps serving afterwards."""
    config = LlamaConfig.tiny(max_seq_len=128)
    params = init_params(config)

    async def main():
        engine = DecodeEngine(
            config, params, max_slots=1, max_seq_len=128,
            prefill_buckets=[16], decode_chunk=4,
        )
        engine.start()
        try:
            long = SamplingParams(max_new_tokens=100)
            running_handle: list = []
            queued_handle: list = []
            running = asyncio.ensure_future(engine.generate(
                [1, 2, 3], long, handle=running_handle
            ))
            # single slot: the second request has to queue
            queued = asyncio.ensure_future(engine.generate(
                [4, 5, 6], long, handle=queued_handle
            ))
            await asyncio.sleep(0.3)
            queued_handle[0].cancel()   # cancelled BEFORE admission
            running_handle[0].cancel()  # cancelled mid-decode
            first = await asyncio.wait_for(running, timeout=30)
            second = await asyncio.wait_for(queued, timeout=30)
            assert first.finish_reason == "cancelled"
            assert 0 < len(first.tokens) < 100
            assert second.finish_reason == "cancelled"
            # the engine still serves normally afterwards
            ok = await engine.generate(
                [7, 8, 9], SamplingParams(max_new_tokens=5)
            )
            assert len(ok.tokens) == 5
        finally:
            engine.stop()

    asyncio.run(main())


def test_stop_strings_trim_and_cancel():
    """The `stop` option ends the answer at the first stop-string match:
    content is trimmed at the match, finish_reason is 'stop', and the
    engine stops decoding early instead of running to max-tokens."""

    async def main():
        from langstream_tpu.providers.jax_local.provider import (
            JaxCompletionsService,
        )
        from langstream_tpu.api.service import ChatMessage

        service = JaxCompletionsService(
            {
                "model": {"preset": "tiny", "max_seq_len": 256},
                "engine": {"max-slots": 2, "max-seq-len": 256},
            }
        )
        messages = [ChatMessage("user", "tell me everything")]
        full = await service.get_chat_completions(
            messages, {"max-tokens": 48}
        )
        assert len(full.content) > 8
        # pick a substring from the middle of the deterministic greedy
        # answer as the stop string
        middle = len(full.content) // 2
        stop = full.content[middle:middle + 3]
        prefix = full.content[: full.content.find(stop)]
        stopped = await service.get_chat_completions(
            messages, {"max-tokens": 48, "stop": [stop]}
        )
        assert stopped.content == prefix
        assert stopped.finish_reason == "stop"
        # streaming path: streamed text matches the trimmed content
        chunks = []

        class Consumer:
            def consume_chunk(self, answer_id, index, chunk, last):
                chunks.append((chunk.content, last))

        streamed = await service.get_chat_completions(
            messages, {"max-tokens": 48, "stop": [stop]}, Consumer()
        )
        await asyncio.sleep(0.05)
        assert streamed.content == prefix
        assert "".join(c for c, _ in chunks) == prefix
        assert chunks[-1][1] is True
        await service.close()

    asyncio.run(main())


def test_engine_tensor_parallel_matches_single_device():
    """tp=2 sharded engine must produce identical greedy tokens."""
    from langstream_tpu.parallel.mesh import MeshConfig

    async def main():
        config = LlamaConfig.tiny(max_seq_len=64)
        params = init_params(config)
        solo = DecodeEngine(config, params, max_slots=2, max_seq_len=64,
                            prefill_buckets=[16])
        solo.start()
        r1 = await solo.generate([1, 2, 3], SamplingParams(max_new_tokens=5))
        solo.stop()

        sharded = DecodeEngine(
            config, params, max_slots=2, max_seq_len=64,
            prefill_buckets=[16], mesh_config=MeshConfig(tp=2),
        )
        assert dict(sharded.mesh.shape)["tp"] == 2
        sharded.start()
        r2 = await sharded.generate([1, 2, 3], SamplingParams(max_new_tokens=5))
        sharded.stop()
        assert r1.tokens == r2.tokens

    asyncio.run(main())


def test_engine_tp_rejects_indivisible_heads():
    config = LlamaConfig.tiny()
    params = init_params(config)
    from langstream_tpu.parallel.mesh import MeshConfig

    with pytest.raises(ValueError, match="must divide"):
        DecodeEngine(config, params, mesh_config=MeshConfig(tp=8))


def test_logprobs_surfaced(engine):
    """Every generated token carries a real logprob (≤ 0, aligned 1:1)."""

    async def main():
        r = await engine.generate([2, 4, 6], SamplingParams(max_new_tokens=5))
        assert len(r.logprobs) == len(r.tokens)
        assert all(isinstance(lp, float) and lp <= 0.0 for lp in r.logprobs)
        # greedy tokens should be the argmax => logprob is the max one,
        # which for a softmax over V classes is > -log(V) only when the
        # distribution is peaked; just sanity-check finiteness here
        assert all(np.isfinite(lp) for lp in r.logprobs)

    asyncio.run(main())


def test_warm_followup_single_dispatch():
    """A warm-session follow-up with a LONG suffix must cost exactly one
    chunked prefill-at-offset dispatch (no per-token forcing), and match
    the cold path token-for-token."""
    config = LlamaConfig.tiny(max_seq_len=256)
    engine = DecodeEngine(
        config, init_params(config), max_slots=2, max_seq_len=256,
        prefill_buckets=[16, 64, 128],
    )
    engine.start()

    async def main():
        prompt1 = [1, 2, 3, 4]
        r1 = await engine.generate(
            prompt1, SamplingParams(max_new_tokens=4), session_id="s"
        )
        warm_before = engine.stats["warm_prefill_calls"]
        prefills_before = engine.stats["prefill_calls"]
        decode_before = engine.stats["decode_steps"]
        suffix = [(i % 50) + 1 for i in range(60)]  # long suffix
        prompt2 = prompt1 + r1.tokens + suffix
        r2 = await engine.generate(
            prompt2, SamplingParams(max_new_tokens=4), session_id="s"
        )
        assert engine.stats["warm_prefill_calls"] == warm_before + 1
        assert engine.stats["prefill_calls"] == prefills_before
        # decode steps only for the 4 new tokens (chunked), NOT ~60 forcing
        assert engine.stats["decode_steps"] - decode_before <= 8
        cold = await engine.generate(prompt2, SamplingParams(max_new_tokens=4))
        assert cold.tokens == r2.tokens

    asyncio.run(main())
    engine.stop()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_crash_fails_all_waiters_fast():
    """A crashed engine must fail every caller promptly — queued, pending,
    in-flight, and future submissions — never hang them."""
    import concurrent.futures

    config = LlamaConfig.tiny(max_seq_len=64)
    engine = DecodeEngine(
        config, init_params(config), max_slots=2, max_seq_len=64,
        prefill_buckets=[16],
    )
    # sabotage the device path: every prefill raises inside the loop
    def boom(*a, **k):
        raise RuntimeError("injected device failure")

    engine._get_prefill = boom  # type: ignore[method-assign]

    async def main():
        with pytest.raises(RuntimeError):
            await asyncio.wait_for(
                engine.generate([1, 2, 3], SamplingParams(max_new_tokens=4)),
                timeout=30,
            )

    asyncio.run(main())
    # engine is now crashed: direct submission must raise immediately
    from langstream_tpu.providers.jax_local.engine import GenerationRequest

    with pytest.raises(RuntimeError, match="crashed"):
        engine.submit(
            GenerationRequest(
                prompt_tokens=[1], sampling=SamplingParams(max_new_tokens=1),
                future=concurrent.futures.Future(),
            )
        )
    with pytest.raises(RuntimeError, match="crashed"):
        engine.start()


@pytest.mark.slow
def test_engine_tp4_flash_matches_single_device():
    """tp=4 engine with the Pallas flash prefill active (interpret mode)
    must produce the same greedy tokens as the unsharded engine — the
    serving path for BASELINE config #5 (70B TP), VERDICT r2 weak #2."""
    import dataclasses

    from langstream_tpu.parallel.mesh import MeshConfig

    async def main():
        config = dataclasses.replace(
            LlamaConfig.tiny(max_seq_len=64),
            num_kv_heads=4, use_flash=True, flash_interpret=True,
        )
        params = init_params(config)
        solo = DecodeEngine(config, params, max_slots=2, max_seq_len=64,
                            prefill_buckets=[16])
        solo.start()
        r1 = await solo.generate(
            [1, 2, 3, 4, 5], SamplingParams(max_new_tokens=6)
        )
        solo.stop()

        sharded = DecodeEngine(
            config, params, max_slots=2, max_seq_len=64,
            prefill_buckets=[16], mesh_config=MeshConfig(tp=4),
        )
        assert sharded.config.use_flash  # not silently disabled anymore
        sharded.start()
        r2 = await sharded.generate(
            [1, 2, 3, 4, 5], SamplingParams(max_new_tokens=6)
        )
        sharded.stop()
        assert r1.tokens == r2.tokens

    asyncio.run(main())


def test_session_lru_eviction_under_pressure():
    """Slot pressure must evict the least-recently USED pinned session,
    not an arbitrary one (VERDICT r2 weak #5): a freshly-touched session
    survives a cold admission; the stale one pays."""

    async def main():
        config = LlamaConfig.tiny(max_seq_len=64)
        params = init_params(config)
        engine = DecodeEngine(
            config, params, max_slots=3, max_seq_len=64, prefill_buckets=[16]
        )
        engine.start()
        try:
            sampling = SamplingParams(max_new_tokens=2)
            r = {}
            for name, prompt in (("A", [1, 2]), ("B", [3, 4]), ("C", [5, 6])):
                r[name] = await engine.generate(
                    prompt, sampling, session_id=name
                )
            # touch A: warm follow-up — A becomes most recently used
            hits = engine.stats["session_hits"]
            await engine.generate(
                [1, 2] + r["A"].tokens + [9], sampling, session_id="A"
            )
            assert engine.stats["session_hits"] == hits + 1

            # cold admission with all slots pinned: B (stalest) is evicted
            await engine.generate([7, 8], sampling)
            sessions = {s.session_id for s in engine.slots}
            assert "A" in sessions and "C" in sessions
            assert "B" not in sessions

            # A is still warm: another follow-up is a session hit...
            hits = engine.stats["session_hits"]
            a_history = next(
                s.history for s in engine.slots if s.session_id == "A"
            )
            await engine.generate(
                list(a_history) + [10], sampling, session_id="A"
            )
            assert engine.stats["session_hits"] == hits + 1
            # ...while B went cold: its follow-up re-prefills
            prefills = engine.stats["prefill_calls"]
            await engine.generate(
                [3, 4] + r["B"].tokens + [11], sampling, session_id="B"
            )
            assert engine.stats["prefill_calls"] == prefills + 1
        finally:
            engine.stop()

    asyncio.run(main())


def test_pipeline_decode_matches_serial():
    """Pipelined dispatch (chunk N+1 chained off chunk N's device carry)
    must be token-identical to serial dispatch — including stop tokens
    finishing mid-chunk, session reuse, and slot recycling under
    concurrent load."""

    async def run_engine(pipeline: bool):
        config = LlamaConfig.tiny(max_seq_len=128)
        params = init_params(config)
        engine = DecodeEngine(
            config, params, max_slots=2, max_seq_len=128,
            prefill_buckets=[16, 32], decode_chunk=4,
            pipeline_decode=pipeline,
        )
        engine.start()
        try:
            sampling = SamplingParams(max_new_tokens=17)
            # concurrent burst: more requests than slots → recycling
            results = await asyncio.gather(*[
                engine.generate(
                    [1 + i, 2, 3], sampling,
                    stop_tokens={7} if i % 2 else set(),
                    session_id=f"s{i}" if i < 2 else None,
                )
                for i in range(5)
            ])
            # warm follow-up on a pinned session
            follow = await engine.generate(
                [1, 2, 3] + results[0].tokens + [9],
                SamplingParams(max_new_tokens=5), session_id="s0",
            )
            return (
                [r.tokens for r in results],
                [r.finish_reason for r in results],
                follow.tokens,
                engine.stats["session_hits"],
            )
        finally:
            engine.stop()

    serial = asyncio.run(run_engine(False))
    pipelined = asyncio.run(run_engine(True))
    assert serial[0] == pipelined[0]
    assert serial[1] == pipelined[1]
    assert serial[2] == pipelined[2]
    assert serial[3] == pipelined[3]


@pytest.mark.slow
def test_engine_tp8_matches_single_device():
    """tp=8 (the BASELINE #5 mesh width) must be token-identical to the
    unsharded engine on the full 8-device CPU mesh."""
    import dataclasses

    from langstream_tpu.parallel.mesh import MeshConfig

    async def main():
        config = dataclasses.replace(
            LlamaConfig.tiny(max_seq_len=64),
            num_heads=8, num_kv_heads=8, intermediate_size=256,
        )
        params = init_params(config)
        solo = DecodeEngine(config, params, max_slots=2, max_seq_len=64,
                            prefill_buckets=[16])
        solo.start()
        r1 = await solo.generate(
            [1, 2, 3, 4], SamplingParams(max_new_tokens=6)
        )
        solo.stop()

        sharded = DecodeEngine(
            config, params, max_slots=2, max_seq_len=64,
            prefill_buckets=[16], mesh_config=MeshConfig(tp=8),
        )
        assert dict(sharded.mesh.shape)["tp"] == 8
        sharded.start()
        r2 = await sharded.generate(
            [1, 2, 3, 4], SamplingParams(max_new_tokens=6)
        )
        sharded.stop()
        assert r1.tokens == r2.tokens

    asyncio.run(main())


def test_warm_followups_batch_into_one_dispatch():
    """Several sessions' follow-ups arriving together must share ONE
    prefill-at-offset dispatch (BASELINE #5: bursts of session turns)."""

    async def main():
        config = LlamaConfig.tiny(max_seq_len=128)
        params = init_params(config)
        engine = DecodeEngine(
            config, params, max_slots=4, max_seq_len=128,
            prefill_buckets=[16, 32],
        )
        engine.start()
        try:
            sampling = SamplingParams(max_new_tokens=3)
            first = await asyncio.gather(*[
                engine.generate([i + 1, 2, 3], sampling, session_id=f"s{i}")
                for i in range(4)
            ])
            engine.reset_stats()
            # submit all four follow-ups while the engine thread is
            # stopped, then restart: one admission sees the whole burst
            # (deterministic — no reliance on the 3ms admission linger)
            engine.stop()
            import concurrent.futures

            futures = []
            for i in range(4):
                future: "concurrent.futures.Future" = (
                    concurrent.futures.Future()
                )
                engine.submit(GenerationRequest(
                    prompt_tokens=[i + 1, 2, 3] + first[i].tokens + [9],
                    sampling=sampling,
                    session_id=f"s{i}",
                    future=future,
                ))
                futures.append(future)
            engine.start()
            follow = [
                await asyncio.get_running_loop().run_in_executor(
                    None, future.result, 60
                )
                for future in futures
            ]
            assert all(len(r.tokens) == 3 for r in follow)
            assert engine.stats["session_hits"] == 4
            assert engine.stats["prefill_calls"] == 0  # all warm
            # 4 same-bucket suffixes -> one batched dispatch
            assert engine.stats["warm_prefill_calls"] == 1, engine.stats
        finally:
            engine.stop()

    asyncio.run(main())


def test_pipeline_decode_matches_serial_sampled():
    """Sampled decoding (temperature/top-k/top-p) must also be identical
    under pipelined dispatch: chaining changes WHEN chunks dispatch, not
    the rng key sequence or chunk shapes."""

    async def run_engine(pipeline: bool):
        config = LlamaConfig.tiny(max_seq_len=128)
        params = init_params(config)
        engine = DecodeEngine(
            config, params, max_slots=2, max_seq_len=128,
            prefill_buckets=[16], decode_chunk=4, seed=7,
            pipeline_decode=pipeline,
        )
        engine.start()
        try:
            results = await asyncio.gather(*[
                engine.generate(
                    [1 + i, 2, 3],
                    SamplingParams(
                        temperature=0.9, top_k=8, top_p=0.95,
                        max_new_tokens=13,
                    ),
                )
                for i in range(3)
            ])
            return [r.tokens for r in results]
        finally:
            engine.stop()

    assert asyncio.run(run_engine(False)) == asyncio.run(run_engine(True))


def test_partial_prefix_session_reuse_matches_cold():
    """A session follow-up that DIVERGES mid-prompt (chat-template role
    markers) reuses the common prefix and must produce exactly the
    tokens a cold run of the same prompt produces."""

    async def main():
        config = LlamaConfig.tiny(max_seq_len=128)
        params = init_params(config)
        engine = DecodeEngine(
            config, params, max_slots=2, max_seq_len=128,
            prefill_buckets=[16, 32, 64],
        )
        engine.start()
        try:
            sampling = SamplingParams(max_new_tokens=5)
            shared = list(range(1, 25))          # 24-token shared prefix
            first = await engine.generate(
                shared + [30, 31], sampling, session_id="s"
            )
            # follow-up: same 24-token prefix, then different tokens
            divergent = shared + [40, 41, 42]
            hits = engine.stats["session_hits"]
            warm = await engine.generate(
                divergent, sampling, session_id="s"
            )
            assert engine.stats["session_hits"] == hits + 1  # partial warm

            cold_engine = DecodeEngine(
                config, params, max_slots=2, max_seq_len=128,
                prefill_buckets=[16, 32, 64],
            )
            cold_engine.start()
            cold = await cold_engine.generate(divergent, sampling)
            cold_engine.stop()
            assert warm.tokens == cold.tokens
            assert first.tokens  # sanity
        finally:
            engine.stop()

    asyncio.run(main())


def test_cross_slot_prefix_copy_from_pinned_session():
    """A sessionless request whose prompt shares a long prefix with a
    DIFFERENT slot's pinned session copies the KV rows on-device instead
    of re-prefilling; greedy tokens must match a prefix-cache-disabled
    engine."""
    config = LlamaConfig.tiny(max_seq_len=256)
    params = init_params(config)
    shared = [(5 * i) % 250 + 1 for i in range(40)]
    first = shared + [7, 8]
    second = shared + [9, 10, 11]  # diverges after the shared prefix
    sampling = SamplingParams(max_new_tokens=6)

    async def run(prefix_cache):
        engine = DecodeEngine(
            config, params, max_slots=4, max_seq_len=256,
            prefill_buckets=[16, 32, 64], prefix_cache=prefix_cache,
        )
        engine.start()
        try:
            r1 = await engine.generate(first, sampling, session_id="pin")
            r2 = await engine.generate(second, sampling)
            return (r1.tokens, r2.tokens), dict(engine.stats)
        finally:
            engine.stop()

    cold_out, cold_stats = asyncio.run(run(False))
    out, stats = asyncio.run(run(True))
    assert out == cold_out
    assert cold_stats["prefix_hits"] == 0
    # the pinned session sits in another slot -> real cross-slot copy
    assert stats["prefix_hits"] == 1
    assert stats["prefix_tokens_reused"] >= 40
    assert stats["prefill_calls"] == cold_stats["prefill_calls"] - 1


def test_prefix_salvage_from_finished_sessionless_slot():
    """Sessionless slots retain their trimmed history at finish; a later
    request with the same template prefix salvages those rows (same-slot,
    no copy) or copies them, instead of a cold prefill."""
    config = LlamaConfig.tiny(max_seq_len=256)
    params = init_params(config)
    shared = [(3 * i) % 250 + 1 for i in range(32)]
    sampling = SamplingParams(max_new_tokens=5)

    async def main():
        engine = DecodeEngine(
            config, params, max_slots=2, max_seq_len=256,
            prefill_buckets=[16, 32, 64],
        )
        engine.start()
        try:
            r1 = await engine.generate(shared + [1, 2], sampling)
            r2 = await engine.generate(shared + [3, 4, 5], sampling)
            assert engine.stats["prefix_hits"] == 1
            assert engine.stats["prefix_tokens_reused"] >= 32
            cold_engine = DecodeEngine(
                config, params, max_slots=2, max_seq_len=256,
                prefill_buckets=[16, 32, 64], prefix_cache=False,
            )
            cold_engine.start()
            try:
                c1 = await cold_engine.generate(shared + [1, 2], sampling)
                c2 = await cold_engine.generate(shared + [3, 4, 5], sampling)
            finally:
                cold_engine.stop()
            assert r1.tokens == c1.tokens
            assert r2.tokens == c2.tokens
        finally:
            engine.stop()

    asyncio.run(main())


def test_same_batch_duplicate_prompts_share_one_prefill():
    """k identical prompts submitted together (the n>1 choices shape):
    one cold prefill, the rest reuse its rows via same-round cross-slot
    copies — and every choice still decodes the cold-engine tokens."""
    config = LlamaConfig.tiny(max_seq_len=256)
    params = init_params(config)
    prompt = [(7 * i) % 250 + 1 for i in range(24)]
    sampling = SamplingParams(max_new_tokens=6)

    async def run(prefix_cache):
        engine = DecodeEngine(
            config, params, max_slots=4, max_seq_len=256,
            prefill_buckets=[16, 32, 64], prefix_cache=prefix_cache,
        )
        engine.start()
        try:
            results = await asyncio.gather(
                *[engine.generate(prompt, sampling) for _ in range(3)]
            )
            return [r.tokens for r in results], dict(engine.stats)
        finally:
            engine.stop()

    cold_out, _ = asyncio.run(run(False))
    out, stats = asyncio.run(run(True))
    assert out == cold_out
    # at least the followers admitted after the first dispatch reuse it;
    # same-round batching may catch all three in one admission round
    assert stats["prefix_hits"] >= 2
    assert stats["prefill_calls"] + stats["warm_prefill_calls"] <= 3


def test_cross_slot_long_suffix_inline_copy():
    """Cross-slot reuse where the divergent suffix exceeds the largest
    bucket: the copy dispatches inline and the suffix takes the chunked
    prefill-at-offset path; tokens match the disabled-cache engine."""
    config = LlamaConfig.tiny(max_seq_len=512)
    params = init_params(config)
    shared = [(11 * i) % 250 + 1 for i in range(100)]
    long_tail = [(13 * i) % 250 + 1 for i in range(80)]  # > largest bucket
    sampling = SamplingParams(max_new_tokens=5)

    async def run(prefix_cache):
        engine = DecodeEngine(
            config, params, max_slots=4, max_seq_len=512,
            prefill_buckets=[16, 32, 64], prefix_cache=prefix_cache,
        )
        engine.start()
        try:
            r1 = await engine.generate(shared, sampling, session_id="pin")
            r2 = await engine.generate(shared[:90] + long_tail, sampling)
            return (r1.tokens, r2.tokens), dict(engine.stats)
        finally:
            engine.stop()

    cold_out, _ = asyncio.run(run(False))
    out, stats = asyncio.run(run(True))
    assert out == cold_out
    assert stats["prefix_hits"] == 1
    assert stats["prefix_tokens_reused"] >= 90


def test_prefix_reuse_stress_parity():
    """Sessionless template-sharing requests racing session follow-ups
    (including chunked long suffixes on slots other requests are copying
    from): every greedy result must equal a solo run on a
    prefix-cache-disabled engine. Guards the copy/warm dispatch-ordering
    invariant (a copy must never read rows a same-round warm prefill
    overwrites)."""
    config = LlamaConfig.tiny(max_seq_len=256)
    params = init_params(config)
    template = [(17 * j) % 250 + 1 for j in range(30)]
    sampling = SamplingParams(max_new_tokens=6)

    def prompt(i):
        if i % 2 == 0:  # sessionless template sharer (copier)
            return template + [(i * 7 + j) % 250 + 1 for j in range(4)]
        # session traffic; every other one gets a long divergent suffix
        tail = 70 if i % 4 == 3 else 6
        return template[:20] + [(i * 11 + j) % 250 + 1 for j in range(tail)]

    def session(i):
        return None if i % 2 == 0 else f"sess-{i % 5}"

    async def main():
        engine = DecodeEngine(
            config, params, max_slots=3, max_seq_len=256,
            prefill_buckets=[16, 32, 64], decode_chunk=4,
            pipeline_decode=True,
        )
        engine.start()

        async def late(i):
            await asyncio.sleep(0.003 * (i % 7))
            return await engine.generate(prompt(i), sampling,
                                         session_id=session(i))

        try:
            results = await asyncio.gather(*[late(i) for i in range(20)])
            assert engine.stats["prefix_hits"] >= 1  # the path actually ran
        finally:
            engine.stop()
        solo = DecodeEngine(
            config, params, max_slots=3, max_seq_len=256,
            prefill_buckets=[16, 32, 64], decode_chunk=4,
            prefix_cache=False,
        )
        solo.start()
        try:
            for i in range(20):
                expected = await solo.generate(prompt(i), sampling)
                assert results[i].tokens == expected.tokens, f"request {i}"
        finally:
            solo.stop()

    asyncio.run(main())


def test_prefix_copy_from_actively_decoding_slot():
    """A stateless continuation that resends a decoding slot's
    prompt+partial answer: the copy must cap at the slot's written rows
    (the newest history token's KV row is only written by the NEXT
    decode dispatch). Greedy parity against a prefix-cache-disabled
    engine catches any unwritten-row copy."""
    config = LlamaConfig.tiny(max_seq_len=256)
    params = init_params(config)
    prompt_a = [(19 * j) % 250 + 1 for j in range(24)]
    kwargs = dict(
        max_slots=4, max_seq_len=256, prefill_buckets=[16, 32, 64],
        decode_chunk=1,
    )

    async def main():
        solo = DecodeEngine(config, params, prefix_cache=False, **kwargs)
        solo.start()
        try:
            a_ref = await solo.generate(
                prompt_a, SamplingParams(max_new_tokens=24)
            )
            prompt_b = prompt_a + a_ref.tokens  # extends A's full history
            b_ref = await solo.generate(
                prompt_b, SamplingParams(max_new_tokens=6)
            )
        finally:
            solo.stop()

        engine = DecodeEngine(config, params, **kwargs)
        engine.start()
        try:
            streamed = asyncio.Event()
            seen = 0

            def on_token(token, last):
                nonlocal seen
                seen += 1
                if seen >= 4:
                    streamed.set()

            a_task = asyncio.ensure_future(engine.generate(
                prompt_a, SamplingParams(max_new_tokens=24),
                on_token=on_token,
            ))
            await asyncio.wait_for(streamed.wait(), timeout=60)
            # B admits while A is still decoding; its prompt extends A's
            # history past the written rows
            b = await engine.generate(
                prompt_b, SamplingParams(max_new_tokens=6)
            )
            a = await a_task
            assert a.tokens == a_ref.tokens
            assert b.tokens == b_ref.tokens
            assert engine.stats["prefix_hits"] >= 1
        finally:
            engine.stop()

    asyncio.run(main())


def test_top_logprobs_greedy():
    """logprobs_topk=K returns K ranked alternatives per generated token
    (prefill first token AND decode steps); under greedy sampling the
    emitted token must be rank 1 with its logprob matching, and an
    engine without the knob returns None (and unchanged jit arity)."""

    async def main():
        config = LlamaConfig.tiny(max_seq_len=64)
        params = init_params(config, seed=11)
        engine = DecodeEngine(
            config, params, max_slots=2, max_seq_len=64,
            prefill_buckets=[16], decode_chunk=4, logprobs_topk=3,
        )
        engine.start()
        try:
            result = await engine.generate(
                [1, 2, 3, 4, 5], SamplingParams(
                    temperature=0.0, max_new_tokens=6
                ),
            )
        finally:
            engine.stop()
        assert result.top_logprobs is not None
        assert len(result.top_logprobs) == len(result.tokens)
        for token, logprob, (ids, lps) in zip(
            result.tokens, result.logprobs, result.top_logprobs
        ):
            assert len(ids) == 3 and len(lps) == 3
            assert ids[0] == token          # greedy -> rank 1
            assert abs(lps[0] - logprob) < 1e-4
            assert lps[0] >= lps[1] >= lps[2]

        plain = DecodeEngine(
            config, params, max_slots=2, max_seq_len=64,
            prefill_buckets=[16], decode_chunk=4,
        )
        plain.start()
        try:
            result2 = await plain.generate(
                [1, 2, 3, 4, 5], SamplingParams(
                    temperature=0.0, max_new_tokens=6
                ),
            )
        finally:
            plain.stop()
        assert result2.top_logprobs is None
        assert result2.tokens == result.tokens  # knob is observability-only

    asyncio.run(main())


def test_prefill_groups_stay_inside_the_token_budget(monkeypatch):
    """A prefill dispatch carries at most MAX_PREFILL_TOKENS (rows x
    bucket): groups split, the variant list never names a larger one, and
    the split groups still answer like solo runs."""
    monkeypatch.setattr(DecodeEngine, "MAX_PREFILL_TOKENS", 64)
    config = LlamaConfig.tiny(max_seq_len=128)
    engine = DecodeEngine(
        config, init_params(config), max_slots=8, max_seq_len=128,
        prefill_buckets=[16, 32],
    )
    assert [len(g) for g in engine._pow2_groups(list(range(7)), 16)] == [4, 2, 1]
    assert [len(g) for g in engine._pow2_groups(list(range(7)), 32)] == [2, 2, 2, 1]
    for fn, args in engine._variant_jobs():
        if fn in (engine._get_prefill(16), engine._get_prefill(32)):
            rows, bucket = args[2].shape
            assert rows * bucket <= 64
    engine.start()
    try:
        async def main():
            prompts = [[i + 1] * 20 for i in range(8)]  # bucket 32, 8 rows
            sampling = SamplingParams(max_new_tokens=4)
            together = await asyncio.gather(
                *[engine.generate(p, sampling) for p in prompts]
            )
            solo = [await engine.generate(p, sampling) for p in prompts]
            assert [r.tokens for r in together] == [r.tokens for r in solo]
            assert max(
                entry["prefill_tokens"] for entry in engine.dispatch_log
            ) <= 64

        asyncio.run(main())
    finally:
        engine.stop()
