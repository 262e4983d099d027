"""Qwen-2 family (Llama architecture + q/k/v projection biases):
HF-logits parity and decode/prefill consistency."""

import numpy as np

import jax.numpy as jnp

from langstream_tpu.ops.rope import rope_frequencies
from langstream_tpu.providers.jax_local.model import (
    LlamaConfig,
    decode_step,
    forward,
    init_cache,
    init_params,
    load_hf_checkpoint,
    prefill,
)


def _hf_qwen2():
    import torch
    from transformers import Qwen2Config, Qwen2ForCausalLM

    hf_config = Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-6, rope_theta=10000.0,
        tie_word_embeddings=False, attn_implementation="eager",
    )
    torch.manual_seed(1)
    model = Qwen2ForCausalLM(hf_config).eval()
    # random-normal biases so the bias path actually shows in the logits
    with torch.no_grad():
        for layer in model.model.layers:
            for proj in ("q_proj", "k_proj", "v_proj"):
                getattr(layer.self_attn, proj).bias.normal_(std=0.5)
    return model


def test_forward_matches_hf_qwen2():
    import torch

    hf_model = _hf_qwen2()
    config, params = load_hf_checkpoint(hf_model, dtype=jnp.float32)
    assert config.qkv_bias and "bq" in params

    prompt = [3, 17, 9, 40, 2, 77, 101, 5]
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor([prompt])).logits[0].numpy()
    logits = forward(config, params, jnp.array([prompt], dtype=jnp.int32))
    np.testing.assert_allclose(
        np.asarray(logits)[0], hf_logits, rtol=2e-3, atol=2e-3
    )


def test_qwen2_decode_matches_prefill():
    config = LlamaConfig.tiny_qwen2()
    params = init_params(config, seed=2)
    # zero-init biases would make this test blind to the bias plumbing
    params = dict(
        params,
        bq=params["bq"] + 0.3,
        bk=params["bk"] - 0.2,
        bv=params["bv"] + 0.1,
    )
    freqs = rope_frequencies(
        config.dims_per_head, config.max_seq_len, config.rope_theta
    )
    prompt = [5, 9, 13, 2, 7, 30]

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


def test_qwen2_safetensors_roundtrip(tmp_path):
    """The serving engine's primary loader (safetensors) must carry the
    q/k/v biases — it silently dropped them once (review finding), and
    validate_family_params now fails fast on that class of bug."""
    import torch

    from langstream_tpu.providers.jax_local.weights import (
        load_safetensors_checkpoint,
    )

    hf_model = _hf_qwen2()
    hf_model.save_pretrained(tmp_path, safe_serialization=True)
    config, params = load_safetensors_checkpoint(
        str(tmp_path), dtype=jnp.float32
    )
    assert config.qkv_bias and "bq" in params

    prompt = [4, 11, 7, 99, 23]
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor([prompt])).logits[0].numpy()
    logits = forward(config, params, jnp.array([prompt], dtype=jnp.int32))
    np.testing.assert_allclose(
        np.asarray(logits)[0], hf_logits, rtol=2e-3, atol=2e-3
    )


def test_missing_family_params_fail_fast():
    import pytest as _pytest

    config = LlamaConfig.tiny_qwen2()
    params = init_params(config, seed=0)
    del params["bq"]
    freqs = rope_frequencies(
        config.dims_per_head, config.max_seq_len, config.rope_theta
    )
    with _pytest.raises(ValueError, match="bq"):
        forward(config, params, jnp.zeros((1, 4), dtype=jnp.int32),
                freqs=freqs)


def test_qwen2_engine_tp2_matches_single_device():
    """Qwen-2 under tensor parallelism: the q/k/v biases shard over the
    head axis in lockstep with their projections."""
    import asyncio

    from langstream_tpu.parallel.mesh import MeshConfig
    from langstream_tpu.providers.jax_local.engine import (
        DecodeEngine,
        SamplingParams,
    )

    async def main():
        config = LlamaConfig.tiny_qwen2(max_seq_len=64)
        params = init_params(config, seed=6)
        params = dict(params, bq=params["bq"] + 0.2, bk=params["bk"] - 0.1)
        solo = DecodeEngine(config, params, max_slots=2, max_seq_len=64,
                            prefill_buckets=[16])
        solo.start()
        r1 = await solo.generate(
            [1, 2, 3, 4], SamplingParams(max_new_tokens=6)
        )
        solo.stop()

        sharded = DecodeEngine(
            config, params, max_slots=2, max_seq_len=64,
            prefill_buckets=[16], mesh_config=MeshConfig(tp=2),
        )
        sharded.start()
        r2 = await sharded.generate(
            [1, 2, 3, 4], SamplingParams(max_new_tokens=6)
        )
        sharded.stop()
        assert r1.tokens == r2.tokens

    asyncio.run(main())
