"""int8 KV cache (`engine: {kv-quant: int8}`): per-(position, head)
scales fold into the attention contractions so the MXU streams the bare
int8 cache (docs/perf.md "Round-4 step-time lever"). Cold prefill
attends against the dequantized-quantized values, so every reuse path
(warm session, cross-slot copy, chunked long prefill) is token-IDENTICAL
to a cold run on the same quantized engine; accuracy vs the bf16 cache
is a tolerance statement."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.providers.jax_local.engine import (
    DecodeEngine,
    SamplingParams,
)
from langstream_tpu.providers.jax_local.model import (
    LlamaConfig,
    decode_step,
    init_cache,
    init_params,
    prefill,
)
from langstream_tpu.ops.rope import rope_frequencies


def _kwargs():
    return dict(
        max_slots=3, max_seq_len=256, prefill_buckets=[16, 32, 64],
        decode_chunk=4,
    )


def test_cache_layout_and_bytes():
    config = LlamaConfig.tiny(max_seq_len=64)
    cache = init_cache(config, 2, 64, kv_quant=True)
    assert cache["k"].dtype == jnp.int8
    assert cache["k_scale"].dtype == jnp.float32
    assert cache["k_scale"].shape == cache["k"].shape[:-1]
    plain = init_cache(config, 2, 64)
    quant_bytes = sum(
        a.size * a.dtype.itemsize for a in cache.values()
    )
    plain_bytes = sum(a.size * a.dtype.itemsize for a in plain.values())
    assert quant_bytes < plain_bytes  # int8 + scales < bf16


def test_model_level_logits_close_to_bf16():
    """Prefill + a few decode steps: quantized-cache logits must track
    the bf16-cache logits closely (same argmax for a random tiny model
    on most steps; bounded absolute error everywhere)."""
    config = LlamaConfig.tiny(max_seq_len=64)
    params = init_params(config)
    freqs = rope_frequencies(
        config.dims_per_head, config.max_seq_len, config.rope_theta
    )
    tokens = jnp.asarray([[(7 * i) % 250 + 1 for i in range(12)]])
    lengths = jnp.asarray([12])
    slots = jnp.asarray([0])

    outs = {}
    for name, quant in (("bf16", False), ("int8", True)):
        cache = init_cache(config, 1, 64, kv_quant=quant)
        cache, logits, _ = prefill(
            config, params, cache, tokens, lengths, slots, freqs
        )
        steps = [logits]
        step_lengths = lengths
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        for _ in range(4):
            step_lengths = step_lengths + 1
            cache, logits, _ = decode_step(
                config, params, cache, token, step_lengths, freqs
            )
            steps.append(logits)
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        outs[name] = np.stack([np.asarray(s) for s in steps])

    reference, quantized = outs["bf16"], outs["int8"]
    scale = np.abs(reference).max()
    assert np.abs(reference - quantized).max() < 0.05 * scale
    agree = (reference.argmax(-1) == quantized.argmax(-1)).mean()
    assert agree >= 0.8, f"greedy agreement only {agree:.2f}"


def test_quantized_engine_reuse_paths_token_identical():
    """Within the SAME quantized engine: session warm follow-ups and
    cross-slot prefix copies decode exactly the cold tokens — the
    invariant that makes the cache safe to reuse."""
    config = LlamaConfig.tiny(max_seq_len=256)
    params = init_params(config)
    sampling = SamplingParams(max_new_tokens=6)
    shared = [(5 * i) % 250 + 1 for i in range(40)]

    async def main():
        engine = DecodeEngine(config, params, kv_quant="int8", **_kwargs())
        engine.start()
        try:
            r1 = await engine.generate(
                shared + [7, 8], sampling, session_id="pin"
            )
            follow = shared + [7, 8] + r1.tokens + [30, 31]
            warm = await engine.generate(follow, sampling, session_id="pin")
            assert engine.stats["session_hits"] >= 1
            copied = await engine.generate(shared + [9, 9, 9], sampling)
            assert engine.stats["prefix_hits"] >= 1

            cold = DecodeEngine(config, params, kv_quant="int8",
                                prefix_cache=False, **_kwargs())
            cold.start()
            try:
                cold_warm = await cold.generate(follow, sampling)
                cold_copied = await cold.generate(
                    shared + [9, 9, 9], sampling
                )
            finally:
                cold.stop()
            assert warm.tokens == cold_warm.tokens
            assert copied.tokens == cold_copied.tokens
        finally:
            engine.stop()

    asyncio.run(main())


def test_quantized_long_prompt_chunked_matches_whole():
    config = LlamaConfig.tiny(max_seq_len=256)
    params = init_params(config)
    prompt = [(13 * i) % 250 + 1 for i in range(90)]
    sampling = SamplingParams(max_new_tokens=8)

    async def run(buckets):
        engine = DecodeEngine(
            config, params, kv_quant="int8", max_slots=2, max_seq_len=256,
            prefill_buckets=buckets,
        )
        engine.start()
        try:
            return (await engine.generate(prompt, sampling)).tokens
        finally:
            engine.stop()

    chunked = asyncio.run(run([32]))
    whole = asyncio.run(run([128]))
    assert len(chunked) == 8
    assert chunked == whole


def test_unknown_kv_quant_rejected():
    config = LlamaConfig.tiny(max_seq_len=64)
    params = init_params(config)
    with pytest.raises(ValueError, match="kv cache quantization"):
        DecodeEngine(config, params, kv_quant="fp4", max_slots=2,
                     max_seq_len=64)


def test_quantized_prefill_flash_kernel_matches_xla():
    """Cold quantized prefill through the int8 flash kernel (interpret
    mode) writes the same cache rows and near-identical logits as the
    XLA scale-folded path — kv-quant no longer forfeits flash."""
    import dataclasses

    config = LlamaConfig.tiny(max_seq_len=64)
    params = init_params(config)
    freqs = rope_frequencies(
        config.dims_per_head, config.max_seq_len, config.rope_theta
    )
    tokens = jnp.asarray([[(11 * i) % 250 + 1 for i in range(24)]])
    lengths = jnp.asarray([24])
    slots = jnp.asarray([0])

    def run(flash: bool):
        cfg = dataclasses.replace(
            config,
            use_flash=flash,
            flash_interpret=flash,
            # the tiny head dim is not MXU-aligned; interpret mode
            # exercises the kernel math anyway
        )
        cache = init_cache(cfg, 1, 64, kv_quant=True)
        return prefill(cfg, params, cache, tokens, lengths, slots, freqs)

    cache_xla, logits_xla, _ = run(False)
    cache_flash, logits_flash, _ = run(True)
    # cache rows come from quantize_kv on the SAME k/v activations of
    # each layer; layer>0 activations pass through the attention impl,
    # so int8 rows may differ by ±1 quantum at most
    np.testing.assert_allclose(
        np.asarray(cache_flash["k"], dtype=np.int32),
        np.asarray(cache_xla["k"], dtype=np.int32),
        atol=1,
    )
    np.testing.assert_allclose(
        np.asarray(logits_flash), np.asarray(logits_xla),
        rtol=5e-2, atol=5e-2,
    )
