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


# --------------------------------------------------------------------- #
# the routing rules of a chip's share (moe_mlp_held)
# --------------------------------------------------------------------- #
def test_sigmoid_bias_routing_is_a_plain_top_k_over_score_plus_bias():
    """Selection by ``s + b``, weights from ``s`` alone, renormalised over
    the chosen: against a top-k written out here. The bias is large enough
    to change most tokens' chosen sets, so that weights read off ``s + b``,
    or a selection without ``b``, would differ."""
    from langstream_tpu.ops.moe import sigmoid_bias_routing

    rng = np.random.default_rng(0)
    tokens, experts, k = 40, 16, 4
    logits = rng.normal(size=(tokens, experts)).astype(np.float32)
    bias = (rng.normal(size=experts) * 0.1).astype(np.float32)
    weights, chosen = sigmoid_bias_routing(
        jnp.asarray(logits), jnp.asarray(bias), num_selected=k,
        scaling_factor=2.5, renormalise=True,
    )
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    moved = 0
    for token in range(tokens):
        order = np.argsort(-(scores[token] + bias))[:k]
        assert sorted(chosen[token].tolist()) == sorted(order.tolist())
        picked = scores[token][np.asarray(chosen[token])]
        want = 2.5 * picked / (picked.sum() + 1e-6)
        np.testing.assert_allclose(weights[token], want, rtol=1e-5)
        moved += set(order) != set(np.argsort(-scores[token])[:k])
    assert moved > tokens // 4  # the bias changes the chosen set
    plain, _ = sigmoid_bias_routing(
        jnp.asarray(logits), jnp.asarray(bias), num_selected=k,
        scaling_factor=1.0, renormalise=False,
    )
    np.testing.assert_allclose(
        plain, np.take_along_axis(scores, np.asarray(chosen), -1), rtol=1e-5
    )


@pytest.mark.parametrize("rule", ["group_limited", "sigmoid_bias"])
def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer(rule):
    """Four chips hold 2 of 8 experts each (``held`` < ``routed``): the
    parts ``moe_mlp_held`` computes for the four held ranges add up to
    what it gives with all 8 held, and to a loop over every token's
    experts written out here; under either routing rule."""
    import functools

    from langstream_tpu.ops.moe import (
        group_limited_routing,
        moe_mlp_held,
        sigmoid_bias_routing,
    )

    rng = np.random.default_rng(3)
    tokens, hidden, inter, experts, k = 37, 64, 32, 8, 3
    x = jnp.asarray(rng.normal(size=(tokens, hidden)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(hidden, experts)) * hidden ** -0.5, jnp.float32)
    w_gate, w_up = (
        jnp.asarray(rng.normal(size=(experts, hidden, inter)) * hidden ** -0.5, jnp.float32)
        for _ in range(2)
    )
    w_down = jnp.asarray(rng.normal(size=(experts, inter, hidden)) * inter ** -0.5, jnp.float32)
    if rule == "group_limited":
        route = functools.partial(
            group_limited_routing, groups=4, groups_kept=2, num_selected=k,
            scaling_factor=4.0,
        )
    else:
        route = functools.partial(
            sigmoid_bias_routing,
            bias=jnp.asarray(rng.normal(size=experts) * 0.05, jnp.float32),
            num_selected=k, scaling_factor=1.0, renormalise=True,
        )

    def share(first, held):
        part, counters = moe_mlp_held(
            x, router, w_gate[None, first:first + held],
            w_up[None, first:first + held], w_down[None, first:first + held],
            held_first=first, route=route,
        )
        return np.asarray(part), counters

    with jax.default_matmul_precision("highest"):
        parts = [share(first, 2) for first in (0, 2, 4, 6)]
        uncut, counters = share(0, experts)
        weights, chosen = route(x @ router)
        want = np.zeros((tokens, hidden), np.float64)
        for token in range(tokens):
            for weight, expert in zip(np.asarray(weights[token]), np.asarray(chosen[token])):
                hidden_row = jax.nn.silu(x[token] @ w_gate[expert]) * (x[token] @ w_up[expert])
                want[token] += float(weight) * np.asarray(hidden_row @ w_down[expert])
    np.testing.assert_allclose(sum(part for part, _ in parts), uncut, atol=2e-5)
    np.testing.assert_allclose(uncut, want, atol=2e-5)
    assert float(np.abs(uncut).max()) > 0.1
    # every assignment meets exactly one share
    assert sum(int(c[1]) for _, c in parts) == int(counters[1]) == tokens * k
    assert all(0 < int(c[1]) < tokens * k for _, c in parts)
