import numpy as np
import pytest

import jax
import jax.numpy as jnp

from langstream_tpu.providers.jax_local.model import (
    LlamaConfig,
    decode_step,
    init_cache,
    init_params,
    load_hf_checkpoint,
    logical_axes,
    prefill,
)
from langstream_tpu.ops.rope import rope_frequencies


def test_prefill_and_decode_shapes():
    config = LlamaConfig.tiny()
    params = init_params(config)
    freqs = rope_frequencies(config.dims_per_head, config.max_seq_len, config.rope_theta)
    cache = init_cache(config, batch=4, max_len=64)
    tokens = jnp.array([[1, 2, 3, 0], [4, 5, 0, 0]], dtype=jnp.int32)
    lengths = jnp.array([3, 2], dtype=jnp.int32)
    slots = jnp.array([0, 2], dtype=jnp.int32)
    cache, logits, _ = prefill(config, params, cache, tokens, lengths, slots, freqs)
    assert logits.shape == (2, config.vocab_size)
    # decode one token for every slot
    new_tokens = jnp.zeros((4,), dtype=jnp.int32)
    slot_lengths = jnp.array([4, 1, 3, 1], dtype=jnp.int32)
    cache2, logits2, _ = decode_step(config, params, cache, new_tokens, slot_lengths, freqs)
    assert logits2.shape == (4, config.vocab_size)
    assert cache2["k"].shape == cache["k"].shape


@pytest.mark.parametrize(
    "program", ["prefill", "prefill_at_offset", "decode_step"]
)
@pytest.mark.parametrize("preset", ["tiny", "tiny-deepseek-v2"])
def test_every_dense_program_returns_cache_logits_and_counters(preset, program):
    """One set of programs serves both families: each returns (cache,
    logits, expert counters), the counters None exactly where the config
    has no routed experts, and the engine's program of that kind hands
    them on as its last result with nothing in between."""
    from langstream_tpu.providers.jax_local import model as model_lib
    from langstream_tpu.providers.jax_local.engine import DecodeEngine

    config = LlamaConfig.from_dict({"preset": preset, "max_seq_len": 32})
    params = init_params(config)
    freqs = model_lib.model_freqs(config)
    cache = init_cache(config, batch=2, max_len=32)
    tokens = jnp.array([[1, 2, 3, 0], [4, 5, 0, 0]], dtype=jnp.int32)
    two = jnp.array([3, 2], dtype=jnp.int32)
    slots = jnp.array([0, 1], dtype=jnp.int32)
    run = {
        "prefill": lambda: prefill(
            config, params, cache, tokens, two, slots, freqs
        ),
        "prefill_at_offset": lambda: model_lib.prefill_at_offset(
            config, params, cache, tokens, two, two, slots, freqs
        ),
        "decode_step": lambda: decode_step(
            config, params, cache, tokens[:, 0], two, freqs
        ),
    }[program]
    new_cache, logits, counters = jax.eval_shape(run)
    assert set(new_cache) == set(cache)
    assert logits.shape == (2, config.vocab_size)
    routed = config.experts is not None
    if routed:
        assert counters.shape == (3 + config.experts.held,)
        assert counters.dtype == jnp.int32
    else:
        assert counters is None
    engine = DecodeEngine(
        config, params, max_slots=2, max_seq_len=32, prefill_buckets=[16],
        decode_chunk=2,
    )
    name = {
        "prefill": "prefill_dense", "prefill_at_offset": "prefill_offset_dense",
        "decode_step": "decode_chunk_dense",
    }[program]
    fn, avals = next(
        job for job in engine._variant_jobs() if job[0].__name__ == name
    )
    with engine.mesh:
        results = jax.eval_shape(fn, *engine._variant_args(avals))
    assert (results[-1] is not None) == routed
    assert not hasattr(model_lib, "step_results")


def test_prefill_padding_invariance():
    """Padded prompt positions must not affect the last-token logits."""
    config = LlamaConfig.tiny()
    params = init_params(config)
    freqs = rope_frequencies(config.dims_per_head, config.max_seq_len, config.rope_theta)
    prompt = [5, 9, 13]
    for pad in (0, 3, 9):
        cache = init_cache(config, batch=1, max_len=32)
        tokens = jnp.array([prompt + [0] * pad], dtype=jnp.int32)
        _, logits, _ = prefill(
            config, params, cache, tokens,
            jnp.array([3], dtype=jnp.int32), jnp.array([0], dtype=jnp.int32),
            freqs,
        )
        if pad == 0:
            base = logits
        else:
            np.testing.assert_allclose(
                np.asarray(logits), np.asarray(base), rtol=2e-4, atol=2e-4
            )


def test_decode_matches_prefill():
    """Decoding token-by-token must equal prefilling the whole prompt."""
    config = LlamaConfig.tiny()
    params = init_params(config)
    freqs = rope_frequencies(config.dims_per_head, config.max_seq_len, config.rope_theta)
    prompt = [3, 7, 11, 19]

    cache = init_cache(config, batch=1, max_len=32)
    cache, logits_prefill, _ = prefill(
        config, params, cache, jnp.array([prompt], dtype=jnp.int32),
        jnp.array([len(prompt)], dtype=jnp.int32),
        jnp.array([0], dtype=jnp.int32), freqs,
    )

    # now: prefill only the first token, decode the rest one by one
    cache2 = init_cache(config, batch=1, max_len=32)
    cache2, logits_step, _ = prefill(
        config, params, cache2, jnp.array([prompt[:1]], dtype=jnp.int32),
        jnp.array([1], dtype=jnp.int32), jnp.array([0], dtype=jnp.int32), freqs,
    )
    for i, token in enumerate(prompt[1:], start=2):
        cache2, logits_step, _ = decode_step(
            config, params, cache2,
            jnp.array([token], dtype=jnp.int32),
            jnp.array([i], dtype=jnp.int32), freqs,
        )
    np.testing.assert_allclose(
        np.asarray(logits_step), np.asarray(logits_prefill), rtol=2e-3, atol=2e-3
    )


def test_parity_with_huggingface_llama():
    """Our forward must match transformers' LlamaForCausalLM logits."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFLlamaConfig
    from transformers import LlamaForCausalLM

    hf_config = HFLlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf_model = LlamaForCausalLM(hf_config).eval()

    config, params = load_hf_checkpoint(hf_model, dtype=jnp.float32)
    freqs = rope_frequencies(config.dims_per_head, config.max_seq_len, config.rope_theta)

    prompt = [1, 5, 9, 42, 17]
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor([prompt])).logits[0, -1].numpy()

    cache = init_cache(config, batch=1, max_len=32)
    _, logits, _ = prefill(
        config, params, cache, jnp.array([prompt], dtype=jnp.int32),
        jnp.array([len(prompt)], dtype=jnp.int32),
        jnp.array([0], dtype=jnp.int32), freqs,
    )
    np.testing.assert_allclose(
        np.asarray(logits)[0], hf_logits, rtol=1e-3, atol=1e-3
    )


def test_sharded_params_on_mesh():
    """Params shard over a tp mesh and prefill runs under jit."""
    from langstream_tpu.parallel import MeshConfig, build_mesh, shard_params

    config = LlamaConfig.tiny()
    params = init_params(config)
    mesh = build_mesh(MeshConfig(tp=4), devices=jax.devices()[:4])
    sharded = shard_params(params, logical_axes(config), mesh)
    # heads axis of wq sharded over tp
    spec = sharded["wq"].sharding.spec
    assert spec == jax.sharding.PartitionSpec(None, None, "tp")
    freqs = rope_frequencies(config.dims_per_head, config.max_seq_len, config.rope_theta)
    cache = init_cache(config, batch=2, max_len=32)
    tokens = jnp.array([[1, 2], [3, 4]], dtype=jnp.int32)
    cache, logits, _ = jax.jit(
        lambda p, c, t: prefill(
            config, p, c, t,
            jnp.array([2, 2], dtype=jnp.int32),
            jnp.array([0, 1], dtype=jnp.int32), freqs,
        )
    )(sharded, cache, tokens)
    assert logits.shape == (2, config.vocab_size)


def test_num_params_estimate():
    config = LlamaConfig.llama3_8b()
    assert 7.5e9 < config.num_params() < 8.5e9


def test_rope_scaling_matches_hf_llama31():
    """Llama-3.1-style rope_scaling (NTK-by-parts) must match
    transformers exactly — positions BEYOND original_max stress the
    stretched low-frequency band."""
    import torch
    from transformers import LlamaConfig as HFLlamaConfig
    from transformers import LlamaForCausalLM

    hf_config = HFLlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-6, rope_theta=10000.0,
        rope_scaling={
            "rope_type": "llama3", "factor": 8.0,
            "low_freq_factor": 1.0, "high_freq_factor": 4.0,
            "original_max_position_embeddings": 16,
        },
        attn_implementation="eager", tie_word_embeddings=False,
    )
    torch.manual_seed(4)
    hf_model = LlamaForCausalLM(hf_config).eval()
    config, params = load_hf_checkpoint(hf_model, dtype=jnp.float32)
    assert config.rope_scaling == ("llama3", 8.0, 1.0, 4.0, 16.0)

    prompt = list(range(3, 43))  # 40 tokens >> original_max 16
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor([prompt])).logits[0].numpy()
    from langstream_tpu.providers.jax_local.model import forward

    logits = forward(config, params, jnp.array([prompt], dtype=jnp.int32))
    np.testing.assert_allclose(
        np.asarray(logits)[0], hf_logits, rtol=2e-3, atol=2e-3
    )
