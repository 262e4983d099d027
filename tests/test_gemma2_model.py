"""Gemma-2 family: HF-logits parity (GeGLU, sandwich norms, zero-centered
RMSNorm, logit softcapping, alternating sliding window, scaled embeddings,
tied head) plus decode/prefill consistency and an engine smoke."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from langstream_tpu.ops.rope import rope_frequencies
from langstream_tpu.providers.jax_local.model import (
    LlamaConfig,
    decode_step,
    forward,
    init_cache,
    init_params,
    layer_windows,
    load_hf_checkpoint,
    prefill,
)


def _hf_gemma2():
    import torch
    from transformers import Gemma2Config, Gemma2ForCausalLM

    hf_config = Gemma2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=128, rms_norm_eps=1e-6,
        rope_theta=10000.0, attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0, query_pre_attn_scalar=16,
        sliding_window=8, tie_word_embeddings=True,
        hidden_activation="gelu_pytorch_tanh", attention_bias=False,
        attn_implementation="eager",  # sdpa drops softcapping
    )
    torch.manual_seed(0)
    return Gemma2ForCausalLM(hf_config).eval()


def test_forward_matches_hf_gemma2():
    """Full-sequence forward vs transformers' Gemma2ForCausalLM — the
    prompt is LONGER than the sliding window so the alternating window
    mask actually bites on layer 0."""
    import torch

    hf_model = _hf_gemma2()
    config, params = load_hf_checkpoint(hf_model, dtype=jnp.float32)
    assert config.post_norms and config.norm_plus_one
    assert config.attn_logit_softcap == 50.0
    assert config.sliding_window == 8

    prompt = [3, 17, 9, 40, 2, 77, 101, 5, 63, 8, 21, 90, 11, 55, 7, 33]
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor([prompt])).logits[0].numpy()
    logits = forward(
        config, params, jnp.array([prompt], dtype=jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(logits)[0], hf_logits, rtol=2e-3, atol=2e-3
    )


def test_gemma2_decode_matches_prefill():
    """Token-by-token decode must equal one-shot prefill across a
    sliding-window boundary (prompt 12 + decode past position 8)."""
    config = LlamaConfig.tiny_gemma2()
    params = init_params(config, seed=1)
    freqs = rope_frequencies(
        config.dims_per_head, config.max_seq_len, config.rope_theta
    )
    prompt = [5, 9, 13, 2, 7, 30, 44, 12, 3, 8, 19, 27]

    cache = init_cache(config, batch=1, max_len=32)
    cache, logits_full, _ = prefill(
        config, params, cache, jnp.array([prompt], dtype=jnp.int32),
        jnp.array([len(prompt)], dtype=jnp.int32),
        jnp.array([0], dtype=jnp.int32), freqs,
    )

    cache2 = init_cache(config, batch=1, max_len=32)
    cache2, logits_step, _ = prefill(
        config, params, cache2, jnp.array([prompt[:1]], dtype=jnp.int32),
        jnp.array([1], dtype=jnp.int32),
        jnp.array([0], dtype=jnp.int32), freqs,
    )
    for position, token in enumerate(prompt[1:], start=2):
        cache2, logits_step, _ = decode_step(
            config, params, cache2,
            jnp.array([token], dtype=jnp.int32),
            jnp.array([position], dtype=jnp.int32), freqs,
        )
    np.testing.assert_allclose(
        np.asarray(logits_step), np.asarray(logits_full),
        rtol=2e-4, atol=2e-4,
    )


def test_layer_windows_pattern():
    config = LlamaConfig.tiny_gemma2()
    wins = np.asarray(layer_windows(config))
    assert wins.tolist() == [8, 0]
    assert layer_windows(LlamaConfig.tiny()) is None


def test_gemma2_engine_generates():
    """tiny-gemma2 through the continuous-batching engine end to end."""
    import asyncio

    from langstream_tpu.providers.jax_local.engine import (
        DecodeEngine,
        SamplingParams,
    )

    config = LlamaConfig.tiny_gemma2()
    params = init_params(config, seed=2)
    engine = DecodeEngine(
        config, params, max_slots=2, max_seq_len=64,
        prefill_buckets=[16], decode_chunk=4,
    )
    try:
        engine.start()

        async def run():
            sampling = SamplingParams(temperature=0.0, max_new_tokens=8)
            results = await asyncio.gather(
                engine.generate([1, 2, 3, 4, 5], sampling),
                engine.generate([9, 8, 7], sampling),
            )
            return results

        results = asyncio.run(run())
        assert all(len(r.tokens) == 8 for r in results)
    finally:
        engine.stop()


def test_gemma2_safetensors_roundtrip(tmp_path):
    """The safetensors loader must map the four-norm sandwich layout —
    it used to map post_attention_layernorm to the pre-MLP norm (the
    Llama layout), silently mis-normalizing every block."""
    import torch

    from langstream_tpu.providers.jax_local.weights import (
        load_safetensors_checkpoint,
    )

    hf_model = _hf_gemma2()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)
    config, params = load_safetensors_checkpoint(
        str(tmp_path), dtype=jnp.float32
    )
    assert config.post_norms and "post_attn_norm" in params

    prompt = [3, 17, 9, 40, 2, 77, 101, 5, 63, 8, 21, 90]
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor([prompt])).logits[0].numpy()
    logits = forward(config, params, jnp.array([prompt], dtype=jnp.int32))
    np.testing.assert_allclose(
        np.asarray(logits)[0], hf_logits, rtol=2e-3, atol=2e-3
    )


def test_gemma2_engine_tp4_matches_single_device():
    """Gemma-2 under tensor parallelism: the family's extra params
    (sandwich norms) shard replicated, the window/softcap paths ride
    the sharded jits — tokens must match the unsharded engine."""
    import asyncio
    import dataclasses

    from langstream_tpu.parallel.mesh import MeshConfig
    from langstream_tpu.providers.jax_local.engine import (
        DecodeEngine,
        SamplingParams,
    )

    async def main():
        config = dataclasses.replace(
            LlamaConfig.tiny_gemma2(max_seq_len=64),
            num_heads=4, num_kv_heads=4,
        )
        params = init_params(config, seed=5)
        solo = DecodeEngine(config, params, max_slots=2, max_seq_len=64,
                            prefill_buckets=[16])
        solo.start()
        r1 = await solo.generate(
            [1, 2, 3, 4], SamplingParams(max_new_tokens=6)
        )
        solo.stop()

        sharded = DecodeEngine(
            config, params, max_slots=2, max_seq_len=64,
            prefill_buckets=[16], mesh_config=MeshConfig(tp=4),
        )
        sharded.start()
        r2 = await sharded.generate(
            [1, 2, 3, 4], SamplingParams(max_new_tokens=6)
        )
        sharded.stop()
        assert r1.tokens == r2.tokens

    asyncio.run(main())
