"""MoE op + Mixtral-family model tests (virtual 8-device CPU mesh)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from langstream_tpu.ops.moe import moe_capacity, moe_mlp, moe_routing
from langstream_tpu.providers.jax_local import model as model_lib
from langstream_tpu.providers.jax_local.model import (
    LlamaConfig,
    decode_step,
    forward,
    init_cache,
    init_params,
    logical_axes,
    prefill,
)
from langstream_tpu.ops.rope import rope_frequencies


def test_capacity():
    assert moe_capacity(64, 4, 2, 2.0) == 64
    assert moe_capacity(1, 8, 2, 1.0) == 1
    # None = dropless bound S * k; factor clamps to it
    assert moe_capacity(64, 4, 2, None) == 128
    assert moe_capacity(64, 4, 2, 100.0) == 128


def test_routing_valid_mask_frees_capacity():
    """Padding tokens must not evict real tokens from expert capacity."""
    # tokens 0-2 are padding, 3-4 real; all prefer expert 0; capacity 2
    logits = jnp.full((5, 2), 0.0).at[:, 0].set(9.0)
    valid = jnp.array([False, False, False, True, True])
    dispatch, combine, _ = moe_routing(logits, 1, capacity=2, valid=valid)
    # both real tokens fit; no padding token is dispatched at all
    assert float(dispatch[3].sum()) == 1.0
    assert float(dispatch[4].sum()) == 1.0
    assert float(dispatch[:3].sum()) == 0.0
    assert float(combine[:3].sum()) == 0.0


def test_moe_dense_matches_routed_with_ample_capacity():
    """The exact dense path and the capacity-routed path agree when no
    token overflows capacity (the regimes differ only via dropping)."""
    key = jax.random.PRNGKey(0)
    h, f, e, t = 8, 16, 4, 32
    x = jax.random.normal(key, (t, h), dtype=jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(1), (h, e))
    w_g = jax.random.normal(jax.random.PRNGKey(2), (e, h, f)) * 0.1
    w_u = jax.random.normal(jax.random.PRNGKey(3), (e, h, f)) * 0.1
    w_d = jax.random.normal(jax.random.PRNGKey(4), (e, f, h)) * 0.1
    y_dense, _ = moe_mlp(x, router, w_g, w_u, w_d, capacity_factor=None)
    y_routed, _ = moe_mlp(x, router, w_g, w_u, w_d, capacity_factor=float(e))
    np.testing.assert_allclose(
        np.asarray(y_dense), np.asarray(y_routed), rtol=1e-4, atol=1e-5
    )


def test_moe_grouped_routing_bounds_capacity():
    """Long inputs route in fixed-size groups: dispatch stays linear."""
    key = jax.random.PRNGKey(0)
    h, f, e, t = 8, 16, 4, 300  # t >> group_size, not a multiple of it
    x = jax.random.normal(key, (t, h), dtype=jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(1), (h, e))
    w = jax.random.normal(jax.random.PRNGKey(2), (e, h, f)) * 0.1
    wd = jax.random.normal(jax.random.PRNGKey(3), (e, f, h)) * 0.1
    y, aux = moe_mlp(x, router, w, w, wd, capacity_factor=None, group_size=64)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()


def test_moe_prefill_padding_invariance():
    """Dropless serving + valid mask: padded prompt positions must not
    change the last-token logits of an MoE prefill."""
    config = LlamaConfig.tiny_moe()
    params = init_params(config)
    freqs = rope_frequencies(
        config.dims_per_head, config.max_seq_len, config.rope_theta
    )
    prompt = [5, 9, 13]
    base = None
    for pad in (0, 5, 13):
        cache = init_cache(config, batch=1, max_len=32)
        tokens = jnp.array([prompt + [0] * pad], dtype=jnp.int32)
        _, logits, _ = prefill(
            config, params, cache, tokens,
            jnp.array([3], dtype=jnp.int32), jnp.array([0], dtype=jnp.int32),
            freqs,
        )
        if base is None:
            base = logits
        else:
            np.testing.assert_allclose(
                np.asarray(logits), np.asarray(base), rtol=2e-4, atol=2e-4
            )


def test_routing_top1_assigns_argmax():
    logits = jnp.array(
        [[5.0, 0.0, 0.0], [0.0, 5.0, 0.0], [0.0, 0.0, 5.0]], dtype=jnp.float32
    )
    dispatch, combine, aux = moe_routing(logits, 1, capacity=2)
    # each token goes to its argmax expert, weight ~1 after renorm
    for t in range(3):
        expert = int(jnp.argmax(logits[t]))
        assert float(dispatch[t, expert].sum()) == 1.0
        np.testing.assert_allclose(float(combine[t, expert].sum()), 1.0, rtol=1e-5)
    assert np.isfinite(float(aux))


def test_routing_respects_capacity():
    # all tokens prefer expert 0; with capacity 2 only 2 rows fit
    logits = jnp.full((5, 2), 0.0).at[:, 0].set(9.0)
    dispatch, combine, _ = moe_routing(logits, 1, capacity=2)
    assert float(dispatch[:, 0].sum()) == 2.0  # 2 tokens kept
    # overflowed tokens are dropped (no combine weight anywhere)
    kept = combine.sum(axis=(1, 2))
    assert float((kept > 0).sum()) == 2


def test_moe_identical_experts_matches_dense():
    """With every expert identical and ample capacity, MoE output equals
    the dense SwiGLU MLP (combine weights sum to 1 per token)."""
    key = jax.random.PRNGKey(0)
    h, f, e, t = 16, 32, 4, 12
    x = jax.random.normal(key, (t, h), dtype=jnp.float32)
    w_gate1 = jax.random.normal(jax.random.PRNGKey(1), (h, f)) * 0.1
    w_up1 = jax.random.normal(jax.random.PRNGKey(2), (h, f)) * 0.1
    w_down1 = jax.random.normal(jax.random.PRNGKey(3), (f, h)) * 0.1
    router = jax.random.normal(jax.random.PRNGKey(4), (h, e))
    tile = lambda w: jnp.tile(w[None], (e, 1, 1))
    y, aux = moe_mlp(
        x, router, tile(w_gate1), tile(w_up1), tile(w_down1),
        num_selected=2, capacity_factor=4.0,
    )
    dense = jnp.einsum(
        "tf,fh->th",
        jax.nn.silu(x @ w_gate1) * (x @ w_up1),
        w_down1,
    )
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), rtol=1e-4, atol=1e-5)


def test_moe_model_shapes_and_finite():
    config = LlamaConfig.tiny_moe()
    params = init_params(config)
    assert params["w_gate"].shape == (2, 4, 64, 128)
    assert params["router"].shape == (2, 64, 4)
    tokens = jnp.ones((2, 8), dtype=jnp.int32)
    logits, aux = forward(config, params, tokens, with_aux=True)
    assert logits.shape == (2, 8, config.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    assert float(aux) > 0


def test_moe_decode_matches_prefill():
    """Token-by-token decode equals whole-prompt prefill for MoE too."""
    config = LlamaConfig.tiny_moe()
    params = init_params(config)
    freqs = rope_frequencies(
        config.dims_per_head, config.max_seq_len, config.rope_theta
    )
    prompt = [3, 7, 11, 19]
    cache = init_cache(config, batch=1, max_len=32)
    cache, logits_pre, _ = prefill(
        config, params, cache,
        jnp.array([prompt], dtype=jnp.int32),
        jnp.array([len(prompt)], dtype=jnp.int32),
        jnp.array([0], dtype=jnp.int32), freqs,
    )
    cache2 = init_cache(config, batch=1, max_len=32)
    logits_dec = None
    for i, token in enumerate(prompt):
        cache2, logits_dec, _ = decode_step(
            config, params, cache2,
            jnp.array([token], dtype=jnp.int32),
            jnp.array([i + 1], dtype=jnp.int32), freqs,
        )
    np.testing.assert_allclose(
        np.asarray(logits_pre), np.asarray(logits_dec), rtol=2e-3, atol=2e-3
    )


def test_moe_ep_sharded_matches_single_device():
    """ep-sharded MoE model forward == unsharded forward."""
    from langstream_tpu.parallel.mesh import (
        MeshConfig, build_mesh, shard_params,
    )

    config = LlamaConfig.tiny_moe()
    params = init_params(config)
    tokens = jnp.arange(16, dtype=jnp.int32).reshape(2, 8) % config.vocab_size
    expected = forward(config, params, tokens)

    mesh = build_mesh(MeshConfig(dp=2, ep=4), devices=jax.devices()[:8])
    axes = logical_axes(config)
    with mesh:
        sharded = shard_params(params, axes, mesh)
        got = jax.jit(lambda p, t: forward(config, p, t))(sharded, tokens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=2e-3, atol=2e-3
    )


def test_moe_trainer_step():
    from langstream_tpu.parallel.mesh import MeshConfig
    from langstream_tpu.training.trainer import TrainConfig, Trainer

    config = LlamaConfig.tiny_moe()
    trainer = Trainer(
        config, init_params(config),
        mesh_config=MeshConfig(dp=2, ep=4),
        train_config=TrainConfig(learning_rate=1e-3, remat=True),
    )
    tokens = np.random.randint(1, config.vocab_size, size=(4, 16)).astype(np.int32)
    mask = np.ones((4, 16), dtype=bool)
    loss1 = trainer.train_step(tokens, mask)
    for _ in range(3):
        loss2 = trainer.train_step(tokens, mask)
    assert np.isfinite(loss1) and np.isfinite(loss2)
    assert loss2 < loss1
