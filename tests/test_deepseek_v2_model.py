"""The latent-attention, routed-experts family (DeepSeek-V2's shape) at a
test size, float32 on the CPU: hidden 64, 4 heads of 16 + 8 / 16, latent
32, 8 experts in 4 groups, 2 groups and 3 experts a token, 1 dense + 2
expert layers (the ``tiny-deepseek-v2`` preset).

The three dense-layout programs (``latent_moe.py``) are held to the plain
reference the benchmark compares with (``benchmark/reference/
deepseek_v2.py``: one full forward pass, no cache, no absorption, no
kernels, every expert by a plain loop) on seeded weights whose norm scales
lie away from 1; the absorbed attention to the expanded form; YaRN and the
router to numbers worked out by hand; a chip's SHARE of the experts to the
uncut layer; the grouped expert path to a loop over experts; and every
switch the family cannot take to its refusal.
"""

import asyncio
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v2 as reference
from langstream_tpu.ops import mla_attention
from langstream_tpu.ops.moe import (
    group_limited_routing,
    grouped_matmul,
    moe_mlp_held,
    sigmoid_bias_routing,
    routed_tile,
)
from langstream_tpu.ops.rope import rope_frequencies, yarn_softmax_scale
from langstream_tpu.providers.jax_local import latent_moe
from langstream_tpu.providers.jax_local import model as model_lib
from langstream_tpu.providers.jax_local.engine import (
    DecodeEngine,
    GenerationRequest,
    SamplingParams,
)

SEED = 7
SLOTS, MAX_LEN = 4, 64
# the whole model, and one chip's share of it: experts 2-5 of 8 (a strict
# part that does not start at 0) and 300 of 512 rows of the vocabulary
SHARES = {
    "whole": {},
    "share": {"experts-held-first": 2, "experts-held": 4, "vocab-size": 300},
}


def tiny(**model_keys):
    return model_lib.LlamaConfig.from_dict({"preset": "tiny-deepseek-v2", **model_keys})


def file_of(config):
    """The configuration's file the reference reads, for a program config."""
    mla, experts = config.mla, config.experts
    _, factor, fast, slow, mscale, all_dim, original = config.rope_scaling
    return {
        "vocab_size": config.vocab_size, "hidden_size": config.hidden_size,
        "intermediate_size": config.intermediate_size,
        "moe_intermediate_size": experts.intermediate_size,
        "num_hidden_layers": config.num_layers,
        "first_k_dense_replace": experts.leading_dense,
        "num_attention_heads": config.num_heads,
        "q_lora_rank": mla.q_lora_rank, "kv_lora_rank": mla.kv_lora_rank,
        "qk_nope_head_dim": mla.qk_nope_head_dim,
        "qk_rope_head_dim": mla.qk_rope_head_dim, "v_head_dim": mla.v_head_dim,
        "n_routed_experts": experts.held, "experts_held_first": experts.held_first,
        "n_routed_experts_published": experts.routed,
        "n_shared_experts": experts.shared, "n_group": experts.groups,
        "topk_group": experts.groups_kept,
        "num_experts_per_tok": experts.per_token,
        "routed_scaling_factor": experts.scaling_factor,
        "rope_theta": config.rope_theta, "rms_norm_eps": config.norm_eps,
        "weights": "f32-normal", "norm_topk_prob": False,
        "scoring_func": "softmax", "topk_method": "group_limited_greedy",
        "moe_layer_freq": 1, "tie_word_embeddings": False,
        "attention_bias": False,
        "rope_scaling": {
            "type": "yarn", "factor": factor, "beta_fast": fast,
            "beta_slow": slow, "mscale": mscale, "mscale_all_dim": all_dim,
            "original_max_position_embeddings": int(original),
        },
    }


@pytest.fixture(scope="module", params=list(SHARES), ids=list(SHARES))
def family(request):
    """(config, params, the reference's sizes and weights) of one share."""
    config = tiny(**SHARES[request.param])
    sizes = reference.Sizes(file_of(config))
    return (
        config, model_lib.init_params(config, seed=SEED), sizes,
        reference.make_weights(sizes, SEED),
    )


def _gap(logits, want):
    return float(np.abs(np.asarray(logits) - want).max())


def _prefilled(config, params, prompts, slot_ids):
    tokens = np.zeros((len(prompts), 48), np.int32)
    for row, prompt in enumerate(prompts):
        tokens[row, : len(prompt)] = prompt
    lengths = np.array([len(p) for p in prompts], np.int32)
    freqs = model_lib.model_freqs(config)
    cache = model_lib.init_cache(config, SLOTS, MAX_LEN)
    cache, logits, counters = jax.jit(
        lambda c, t, n, s: model_lib.prefill(config, params, c, t, n, s, freqs)
    )(cache, tokens, lengths, np.asarray(slot_ids, np.int32))
    return cache, logits, counters, freqs


def _prompts(config, lengths=(40, 29)):
    rng = np.random.default_rng(0)
    return [list(rng.integers(0, config.vocab_size, size=n)) for n in lengths]


# --------------------------------------------------------------------- #
# the three programs against the reference's full forward pass
# --------------------------------------------------------------------- #
def test_the_recipe_draws_the_weights_the_reference_draws(family):
    config, params, sizes, weights = family
    mla = config.mla
    expert_layer = weights["layers"][1]
    np.testing.assert_array_equal(expert_layer["expert_gate"][0], params["moe.w_gate"][0])
    np.testing.assert_array_equal(expert_layer["expert_down"][0], params["moe.w_down"][0])
    np.testing.assert_array_equal(expert_layer["router"], params["moe.router"][0])
    np.testing.assert_array_equal(weights["layers"][0]["down"][0], params["dense.w_down"][0])
    np.testing.assert_array_equal(weights["lm_head"][0], params["lm_head"])
    np.testing.assert_array_equal(weights["embedding"], params["embedding"])
    # a token's own row is drawn as large as what the layers add to it
    spread = float(np.std(np.asarray(params["embedding"], np.float32)))
    assert 0.9 < spread / latent_moe.EMBEDDING_STD < 1.1
    # W_qb lies as its nope and rotary column groups, each [out, in];
    # W_kvb as W_UK and W_UV, heads first
    wq_b = np.asarray(expert_layer["wq_b"][0]).reshape(
        mla.q_lora_rank, config.num_heads, -1
    )
    np.testing.assert_array_equal(
        wq_b[..., : mla.qk_nope_head_dim].reshape(mla.q_lora_rank, -1).T,
        params["moe.wq_nope"][0],
    )
    np.testing.assert_array_equal(
        wq_b[..., mla.qk_nope_head_dim:].reshape(mla.q_lora_rank, -1).T,
        params["moe.wq_pe"][0],
    )
    wkv_b = np.asarray(expert_layer["wkv_b"][0]).reshape(
        mla.kv_lora_rank, config.num_heads, -1
    )
    np.testing.assert_array_equal(
        wkv_b[..., mla.qk_nope_head_dim:].transpose(1, 0, 2), params["moe.wv_b"][0]
    )
    # norm scales lie away from 1: a dropped RMS cannot stay correct
    for name in ("moe.q_norm", "moe.kv_norm", "dense.attn_norm", "final_norm"):
        assert float(np.abs(np.asarray(params[name]) - 1.0).mean()) > 0.1, name
    assert config.num_params() == sum(int(np.prod(p.shape)) for p in params.values())


def test_prefill_matches_the_reference(family):
    config, params, sizes, weights = family
    prompts = _prompts(config)
    _, logits, counters, _ = _prefilled(config, params, prompts, [1, 3])
    want = reference.logits_at(
        sizes, weights, prompts, [(len(p) - 1, len(p)) for p in prompts], MAX_LEN
    )
    for row in range(2):
        assert _gap(logits[row], want[row][0]) < 2e-5
        assert np.abs(want[row]).max() > 1.0  # logits of a size worth comparing
    # the counters: every real token's assignments, those that met a held
    # expert, the rows computed (whole tiles), tokens by held expert
    experts = config.experts
    routed, held, rows = (int(n) for n in counters[:3])
    assert routed == sum(len(p) for p in prompts) * experts.per_token * 2  # 2 layers
    assert int(counters[3:].sum()) == held and held <= routed
    assert (held == routed) == (experts.held == experts.routed)
    assert rows >= held and rows % 16 == 0


def test_decode_through_the_cache_matches_the_reference(family):
    config, params, sizes, weights = family
    rows = _prompts(config)
    slot_ids = [1, 3]
    cache, logits, _, freqs = _prefilled(config, params, rows, slot_ids)
    step = jax.jit(
        lambda c, t, n, w: model_lib.decode_step(config, params, c, t, n, freqs, w)
    )
    tokens = np.zeros(SLOTS, np.int32)
    lengths = np.zeros(SLOTS, np.int32)
    active = np.array([False, True, False, True])
    picked = np.asarray(jnp.argmax(logits, -1))
    for _ in range(4):
        for row, slot in enumerate(slot_ids):
            rows[row] = rows[row] + [int(picked[row])]
            tokens[slot], lengths[slot] = picked[row], len(rows[row])
        cache, logits, counters = step(cache, tokens, lengths, active)
        # slots that ride along are routed nowhere
        assert int(counters[0]) == 2 * config.experts.per_token * 2
        got = np.asarray(logits)[slot_ids]
        want = reference.logits_at(
            sizes, weights, rows, [(len(r) - 1, len(r)) for r in rows], MAX_LEN
        )
        for row in range(2):
            assert _gap(got[row], want[row][0]) < 2e-5
        picked = got.argmax(-1)


def test_prefill_at_offset_matches_the_reference(family):
    config, params, sizes, weights = family
    rows = _prompts(config, lengths=(30, 21))
    slot_ids = np.array([2, 0], np.int32)
    cache, _, _, freqs = _prefilled(config, params, rows, slot_ids)
    rng = np.random.default_rng(1)
    suffixes = [list(rng.integers(0, config.vocab_size, size=n)) for n in (10, 16)]
    tokens = np.zeros((2, 16), np.int32)
    for row, suffix in enumerate(suffixes):
        tokens[row, : len(suffix)] = suffix
    cache, logits, _ = jax.jit(
        lambda c, t, n, o, s: model_lib.prefill_at_offset(
            config, params, c, t, n, o, s, freqs
        )
    )(
        cache, tokens, np.array([10, 16], np.int32),
        np.array([30, 21], np.int32), slot_ids,
    )
    full = [row + suffix for row, suffix in zip(rows, suffixes)]
    want = reference.logits_at(
        sizes, weights, full, [(len(r) - 1, len(r)) for r in full], MAX_LEN
    )
    for row in range(2):
        assert _gap(logits[row], want[row][0]) < 2e-5


def test_no_other_program_computes_the_family():
    """Every loop over the uniform layer stack refuses a config whose
    layers are not alike: nothing falls through to a GQA path."""
    config = tiny()
    params = model_lib.init_params(config, seed=0)
    with pytest.raises(NotImplementedError, match="latent"):
        model_lib._stack_layer_params(params, config)
    with pytest.raises(ValueError, match="no int8 form"):
        model_lib.init_cache(config, 2, 32, kv_quant=True)
    with pytest.raises(ValueError, match="come together"):
        model_lib.LlamaConfig.from_dict({"preset": "tiny-deepseek-v2", "experts": None})
    with pytest.raises(ValueError, match="routed experts"):
        model_lib.LlamaConfig.from_dict({"preset": "tiny", "experts-held": 2})
    with pytest.raises(ValueError, match="inconsistent"):
        tiny(**{"experts-held-first": 6, "experts-held": 4})


# --------------------------------------------------------------------- #
# the absorbed attention against the expanded form
# --------------------------------------------------------------------- #
def _attention_case(heads=4, nope=16, rope=8, latent=128, v_dim=16, slots=3, keys=64):
    rng = np.random.default_rng(3)
    row = -(-(latent + rope) // 128) * 128

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    rows = jnp.concatenate([
        normal(slots, keys, latent + rope),
        jnp.zeros((slots, keys, row - latent - rope), jnp.float32),
    ], axis=-1)
    return {
        "q_nope": normal(slots, 1, heads, nope), "q_pe": normal(slots, 1, heads, rope),
        "rows": rows, "wk_b": normal(heads, latent, nope) * latent ** -0.5,
        "wv_b": normal(heads, latent, v_dim) * latent ** -0.5,
        "lengths": jnp.asarray([keys, 17, 1], jnp.int32), "latent": latent,
        "rope": rope, "scale": 0.21,
    }


def _expanded(case):
    """Keys and values expanded through W_UK / W_UV, plain softmax."""
    latent, rope = case["latent"], case["rope"]
    c_kv, k_pe = case["rows"][..., :latent], case["rows"][..., latent:latent + rope]
    k_nope = jnp.einsum("stc,hcd->sthd", c_kv, case["wk_b"])
    v = jnp.einsum("stc,hcd->sthd", c_kv, case["wv_b"])
    scores = (
        jnp.einsum("sqhd,sthd->shqt", case["q_nope"], k_nope)
        + jnp.einsum("sqhd,std->shqt", case["q_pe"], k_pe)
    ) * case["scale"]
    seen = jnp.arange(case["rows"].shape[1])[None, :] < case["lengths"][:, None]
    scores = jnp.where(seen[:, None, None, :], scores, -jnp.inf)
    return jnp.einsum("shqt,sthd->sqhd", jax.nn.softmax(scores, -1), v)


def test_absorbed_attention_is_the_expanded_form():
    case = _attention_case()
    with jax.default_matmul_precision("highest"):
        got = mla_attention.absorbed_attention(
            case["q_nope"], case["q_pe"], case["rows"], case["lengths"][:, None],
            case["wk_b"], case["wv_b"], scale=case["scale"],
        )
        want = _expanded(case)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_absorbed_attention_in_blocks_of_queries(monkeypatch):
    """Many queries a row, each with its own count of visible keys, in
    blocks (with a last block that is padded)."""
    monkeypatch.setattr(mla_attention, "_query_block", lambda *_: 4)
    case = _attention_case(slots=2)
    rng = np.random.default_rng(5)
    queries = 10
    q_nope = jnp.asarray(rng.normal(size=(2, queries, 4, 16)), jnp.float32)
    q_pe = jnp.asarray(rng.normal(size=(2, queries, 4, 8)), jnp.float32)
    visible = jnp.asarray(rng.integers(1, 64, size=(2, queries)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = mla_attention.absorbed_attention(
            q_nope, q_pe, case["rows"], visible, case["wk_b"], case["wv_b"],
            scale=case["scale"],
        )
        for query in range(queries):
            one = dict(case, q_nope=q_nope[:, query:query + 1],
                       q_pe=q_pe[:, query:query + 1], lengths=visible[:, query])
            np.testing.assert_allclose(
                got[:, query:query + 1], _expanded(one), atol=2e-5
            )


def test_the_mla_decode_kernel_is_the_absorbed_form():
    """The Pallas kernel (interpret mode) over the STACKED cache and a
    layer scalar, against the expanded form on that layer's slab."""
    case = _attention_case()
    layers, layer = 3, 2
    rng = np.random.default_rng(9)
    stack = jnp.asarray(rng.normal(size=(layers,) + case["rows"].shape), jnp.float32)
    stack = stack.at[layer].set(case["rows"])
    q_lat = jnp.einsum("sqhd,hcd->sqhc", case["q_nope"], case["wk_b"])
    query = mla_attention.latent_query(q_lat, case["q_pe"], stack.shape[-1])[:, 0]
    assert mla_attention.mla_decode_shapes_ok(64, case["latent"], stack.shape[-1])
    with jax.default_matmul_precision("highest"):
        o_lat = mla_attention.mla_decode_attention(
            query, stack, case["lengths"], jnp.int32(layer),
            latent=case["latent"], scale=case["scale"], block_k=16, interpret=True,
        )
        got = jnp.einsum("shc,hcd->shd", o_lat, case["wv_b"])
        want = _expanded(case)[:, 0]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_flash_prefill_takes_values_narrower_than_keys():
    """Latent attention's expanded heads: keys nope + rope wide, values
    narrower; the kernel (interpret mode) against XLA attention."""
    from langstream_tpu.ops.attention import prefill_attention
    from langstream_tpu.ops.flash_attention import flash_prefill_attention

    rng = np.random.default_rng(11)
    q, k = (jnp.asarray(rng.normal(size=(2, 256, 2, 192)), jnp.float32) for _ in "qk")
    v = jnp.asarray(rng.normal(size=(2, 256, 2, 128)), jnp.float32)
    mask = jnp.arange(256)[None, :] < jnp.asarray([256, 100])[:, None]
    with jax.default_matmul_precision("highest"):
        got = flash_prefill_attention(q, k, v, mask=mask, scale=0.11, interpret=True)
        want = prefill_attention(q, k, v, mask=mask, scale=0.11)
    assert got.shape == (2, 256, 2, 128)
    np.testing.assert_allclose(got[0], want[0], atol=2e-4)
    np.testing.assert_allclose(got[1, :100], want[1, :100], atol=2e-4)


# --------------------------------------------------------------------- #
# YaRN, by hand
# --------------------------------------------------------------------- #
YARN = ("yarn", 40.0, 32.0, 1.0, 0.707, 0.707, 4096.0)


def test_yarn_frequencies_and_softmax_scale_by_hand():
    """rope dim 64, theta 10,000, factor 40 over 4,096 (DeepSeek-V2's):
    the correction dims are floor(64 ln(4096 / (32 * 2 pi)) / (2 ln 10000))
    = floor(10.47) = 10 and ceil(64 ln(4096 / (2 pi)) / (2 ln 10000)) =
    ceil(22.51) = 23, so pairs 0-10 keep theta^(-2i/64), pairs 23-31 are
    divided by 40, and pair 16 blends 6/13 of the divided with 7/13."""
    table = np.asarray(rope_frequencies(64, 8, 10000.0, scaling=YARN))
    angle_at_1 = np.arctan2(table[1, 1], table[0, 1])  # position 1: inv_freq
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(angle_at_1[:11], plain[:11], rtol=1e-5)
    np.testing.assert_allclose(angle_at_1[23:], plain[23:] / 40.0, rtol=1e-4)
    assert angle_at_1[16] == pytest.approx(0.01 * 7 / 13 + 0.00025 * 6 / 13, rel=1e-5)
    assert angle_at_1[16] == pytest.approx(0.005500, rel=1e-3)
    # cos and sin carry mscale(40, 0.707) / mscale(40, 0.707) = 1
    np.testing.assert_allclose(table[0] ** 2 + table[1] ** 2, 1.0, rtol=1e-5)
    # m = 0.1 * 0.707 * ln 40 + 1 = 1.26080; s = 192^(-1/2) * m^2
    m = 0.1 * 0.707 * math.log(40.0) + 1.0
    assert m == pytest.approx(1.26080, abs=1e-5)
    assert yarn_softmax_scale(YARN) == pytest.approx(1.58963, abs=1e-5)
    config = model_lib.LlamaConfig.deepseek_v2()
    assert latent_moe.softmax_scale(config) == pytest.approx(0.114722, abs=1e-6)
    assert yarn_softmax_scale(None) == 1.0
    # and the reference computes the same frequencies on its own
    inv_freq, on_cos_sin = reference.yarn_inv_freq(64, 10000.0, YARN[1:])
    np.testing.assert_allclose(inv_freq, angle_at_1, rtol=1e-5)
    assert on_cos_sin == 1.0
    # a mscale that differs from mscale_all_dim scales cos and sin
    scaled = rope_frequencies(64, 4, 10000.0, scaling=("yarn", 40.0, 32.0, 1.0, 1.0, 0.707, 4096.0))
    ratio = (0.1 * math.log(40.0) + 1.0) / m
    np.testing.assert_allclose(scaled[0, 0], ratio, rtol=1e-6)


def test_yarn_from_a_published_rope_scaling_dict():
    published = {
        "type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
        "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 4096,
    }
    assert model_lib.normalize_rope_scaling(published) == YARN
    with pytest.raises(ValueError, match="missing"):
        model_lib.normalize_rope_scaling({"type": "yarn", "factor": 40})
    assert model_lib.LlamaConfig.deepseek_v2().rope_scaling == YARN


# --------------------------------------------------------------------- #
# the router, by hand
# --------------------------------------------------------------------- #
def test_group_limited_routing_by_hand():
    """8 experts in 4 groups of 2, the 2 best groups kept, 3 experts a
    token, weights x 4 and not renormalised. Probabilities (of logits
    ln p): group 0 holds .30 and .02, group 1 .20 and .18, group 2 .25 and
    .01, group 3 .03 and .01. The groups' scores are their largest: .30,
    .20, .25, .03, so groups 0 and 2 stay; the token's experts are 0 (.30),
    4 (.25) and 1 (.02): expert 2 (.20) and 3 (.18) are larger than .02
    but their group is masked."""
    probs = np.array([[.30, .02, .20, .18, .25, .01, .03, .01]])
    weights, chosen = group_limited_routing(
        jnp.log(jnp.asarray(probs, jnp.float32)), groups=4, groups_kept=2,
        num_selected=3, scaling_factor=4.0,
    )
    assert chosen.tolist() == [[0, 4, 1]]
    np.testing.assert_allclose(weights, [[1.20, 1.00, 0.08]], rtol=1e-5)
    # not renormalised: the weights sum to 4 x .57, not to 4
    assert float(weights.sum()) == pytest.approx(4 * 0.57, rel=1e-5)
    # the reference's router, on its own, agrees
    sizes = reference.Sizes(file_of(tiny()))
    dense = reference.route(
        sizes, jnp.log(jnp.asarray(probs, jnp.float32)), jnp.eye(8, dtype=jnp.float32)
    )
    np.testing.assert_allclose(
        dense, [[1.20, 0.08, 0, 0, 1.00, 0, 0, 0]], rtol=1e-5, atol=1e-7
    )


# --------------------------------------------------------------------- #
# the share: what 4 chips give adds up to the uncut layer
# --------------------------------------------------------------------- #
def test_the_four_shares_and_one_shared_expert_are_the_uncut_layer():
    """Each of 4 chips holds 2 of the 8 experts (one routing group each).
    The parts the PROGRAM computes for the four held ranges, with the
    shared expert, which every chip computes alike, counted once, add up to
    what the REFERENCE gives for the whole layer with all 8 held."""
    whole = tiny()
    sizes = reference.Sizes(file_of(whole))
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(1, 37, whole.hidden_size)), jnp.float32)
    layer = 2  # the model's second expert layer
    parts, shared = [], None
    with jax.default_matmul_precision("highest"):
        for first in (0, 2, 4, 6):
            config = tiny(**{"experts-held-first": first, "experts-held": 2})
            params = model_lib.init_params(config, seed=SEED)
            weights = tuple(
                params[f"moe.{name}"][layer - 1] for name in latent_moe.EXPERT_MLP
            )
            stacks = tuple(params[f"moe.{name}"] for name in latent_moe.EXPERT_STACKS)
            normed = model_lib._norm(config, x, weights[0])
            delta, counters = model_lib._expert_block(
                config, normed, weights[1:], stacks, layer - 1, None
            )
            out = x + delta
            shared, _ = model_lib._mlp_block(config, normed, weights[2:])
            parts.append(out - x - shared)  # this chip's routed experts' part
            assert int(counters[3:].sum()) == int(counters[1]) < int(counters[0])
            # the reference, given the same share, gives the same part
            want = reference.reference_layer(sizes, SEED, layer, x[0], first, 2, shared=False)
            np.testing.assert_allclose(parts[-1][0], want, atol=1e-5)
        uncut = reference.reference_layer(sizes, SEED, layer, x[0], 0, 8, shared=True)
    np.testing.assert_allclose((sum(parts) + shared)[0], uncut, atol=2e-5)
    assert float(np.abs(uncut).max()) > 0.1


# --------------------------------------------------------------------- #
# the grouped path against a loop over experts
# --------------------------------------------------------------------- #
def _loop_over_experts(x, router, w_gate, w_up, w_down, held_first, valid, route):
    weights, chosen = route(x @ router)
    out = np.zeros_like(x)
    met = 0
    by_expert = np.zeros(w_gate.shape[0], np.int64)
    for token in range(x.shape[0]):
        if not valid[token]:
            continue
        for weight, expert in zip(np.asarray(weights[token]), np.asarray(chosen[token])):
            local = int(expert) - held_first
            if 0 <= local < w_gate.shape[0]:
                hidden = jax.nn.silu(x[token] @ w_gate[local]) * (x[token] @ w_up[local])
                out[token] += float(weight) * np.asarray(hidden @ w_down[local])
                met += 1
                by_expert[local] += 1
    return out, met, by_expert


@pytest.mark.parametrize("rule", ["group_limited", "sigmoid_bias"])
@pytest.mark.parametrize("kernel", [False, True], ids=["ragged_dot", "pallas-interpret"])
def test_the_grouped_path_drops_no_token_under_a_skewed_router(kernel, rule):
    """A router that sends almost every token to the same two experts: the
    busiest held expert takes many times the mean, nothing is dropped (the
    layout has room for the worst case), and padding tokens take no row;
    under either routing rule (``moe_mlp_held`` takes the rule)."""
    rng = np.random.default_rng(4)
    tokens, hidden, inter, experts, held_first, held = 50, 64, 32, 8, 2, 4
    x = jnp.asarray(rng.normal(size=(tokens, hidden)), jnp.float32)
    router = rng.normal(size=(hidden, experts)) * 0.02
    router[:, 3] += 0.5 * np.sign(rng.normal(size=hidden))  # expert 3 is loud
    router = jnp.asarray(router, jnp.float32)
    x = x + 2.0 * jnp.sign(router[:, 3])[None, :]  # and nearly every token hears it
    w_gate, w_up = (
        jnp.asarray(rng.normal(size=(held, hidden, inter)) * hidden ** -0.5, jnp.float32)
        for _ in range(2)
    )
    w_down = jnp.asarray(rng.normal(size=(held, inter, hidden)) * inter ** -0.5, jnp.float32)
    valid = np.arange(tokens) < 45
    if rule == "group_limited":
        route = functools.partial(
            group_limited_routing, groups=4, groups_kept=2, num_selected=3,
            scaling_factor=4.0,
        )
    else:
        route = functools.partial(
            sigmoid_bias_routing,
            bias=jnp.asarray(rng.normal(size=experts) * 0.05, jnp.float32),
            num_selected=3, scaling_factor=1.0, renormalise=True,
        )
    with jax.default_matmul_precision("highest"):
        got, counters = moe_mlp_held(
            x, router, w_gate[None], w_up[None], w_down[None],
            held_first=held_first, route=route, valid=jnp.asarray(valid),
            interpret=kernel,
        )
        want, met, by_expert = _loop_over_experts(
            np.asarray(x), router, w_gate, w_up, w_down, held_first, valid, route
        )
    np.testing.assert_allclose(np.asarray(got)[valid], want[valid], atol=2e-5)
    routed, held_met, rows = (int(n) for n in counters[:3])
    assert routed == 45 * 3 and held_met == met
    assert counters[3:].tolist() == by_expert.tolist()
    assert by_expert.max() > 2 * by_expert.mean()  # skewed indeed
    tile = routed_tile(tokens, 3, experts)
    assert rows == sum(-(-int(n) // tile) * tile for n in by_expert)


def test_the_grouped_matmul_reads_a_layer_of_the_stack():
    """Rows sorted by expert, every expert's rows from a tile boundary;
    the weights taken from layer ``layer`` of the layers' stack where they
    lie (a prefetched scalar, as the decode kernel takes its layer)."""
    rng = np.random.default_rng(6)
    layers, groups, k, n, tile = 3, 4, 64, 128, 16
    w = jnp.asarray(rng.normal(size=(layers, groups, k, n)), jnp.float32)
    sizes = jnp.asarray([32, 0, 16, 16], jnp.int32)   # whole tiles
    x = jnp.asarray(rng.normal(size=(96, k)), jnp.float32)  # 2 tiles to spare
    tile_group = jnp.asarray([0, 0, 2, 3, 3, 3], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = grouped_matmul(
            x, w, jnp.int32(1), tile_group, jnp.int32(4), sizes, tile=tile,
            interpret=True,
        )
        want = jax.lax.ragged_dot(x, w[1], sizes)
    np.testing.assert_allclose(got[:64], want[:64], atol=1e-4)
    assert routed_tile(64, 6, 160) == 16 and routed_tile(4096, 6, 160) == 128


# --------------------------------------------------------------------- #
# the engine: served through the normal path, refused by the switch's name
# --------------------------------------------------------------------- #
def test_the_engine_serves_the_share_and_counts_its_experts():
    config = tiny(**SHARES["share"])
    params = model_lib.init_params(config, seed=SEED)
    engine = DecodeEngine(
        config, params, max_slots=4, max_seq_len=128, prefill_buckets=[32, 64],
        decode_chunk=4,
    )
    engine.start()
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(0, 256, size=n)) for n in (20, 50, 33)]

    async def main():
        return await asyncio.gather(*[
            engine.generate(p, SamplingParams(max_new_tokens=6, temperature=0.0))
            for p in prompts
        ])

    try:
        results = asyncio.run(main())
    finally:
        engine.stop()
    sizes = reference.Sizes(file_of(config))
    weights = reference.make_weights(sizes, SEED)
    for prompt, result in zip(prompts, results):
        assert len(result.tokens) == 6
        full = prompt + result.tokens
        want = reference.logits_at(
            sizes, weights, [full], [(len(prompt) - 1, len(full) - 1)], 128
        )[0]
        assert want.argmax(-1).tolist() == result.tokens  # greedy, float32
    stats = engine.stats
    assert stats["moe_assignments"] > stats["moe_assignments_held"] > 0
    assert stats["moe_rows_computed"] >= stats["moe_assignments_held"]
    assert sum(stats["moe_tokens_by_expert"]) == stats["moe_assignments_held"]
    assert len(stats["moe_tokens_by_expert"]) == config.experts.held


REFUSED = {
    "kv-layout": dict(kv_layout="paged"),
    "prefill-mode": dict(kv_layout="paged", prefill_mode="mixed"),
    "kv-host-blocks": dict(kv_layout="paged", kv_host_blocks=8),
    "kv-quant": dict(kv_quant="int8"),
    "quantization": dict(quantize="int8"),
    "spec-decode": dict(spec_decode="ngram"),
}


@pytest.mark.parametrize("switch", list(REFUSED))
def test_every_switch_the_family_cannot_take_is_refused_by_name(switch):
    config = tiny()
    params = model_lib.init_params(config, seed=0)
    with pytest.raises(ValueError, match=switch):
        DecodeEngine(config, params, max_slots=2, max_seq_len=64, **REFUSED[switch])


def test_a_mesh_of_more_than_one_chip_is_refused():
    from langstream_tpu.parallel.mesh import MeshConfig

    config = tiny()
    params = model_lib.init_params(config, seed=0)
    with pytest.raises(ValueError, match="mesh"):
        DecodeEngine(
            config, params, max_slots=2, max_seq_len=64, mesh_config=MeshConfig(tp=2)
        )


def test_int8_weights_handed_in_are_refused():
    from langstream_tpu.providers.jax_local.quant import quantize

    config = tiny()
    params = dict(model_lib.init_params(config, seed=0))
    params["lm_head"] = quantize(params["lm_head"])
    with pytest.raises(ValueError, match="quantization"):
        DecodeEngine(config, params, max_slots=2, max_seq_len=64)


def test_a_request_the_family_cannot_take_is_refused_at_submit():
    config = tiny()
    params = model_lib.init_params(config, seed=0)
    engine = DecodeEngine(
        config, params, max_slots=2, max_seq_len=128, prefill_buckets=[16, 32]
    )

    def request(**fields):
        return GenerationRequest(
            prompt_tokens=fields.pop("prompt", [1, 2, 3]),
            sampling=SamplingParams(max_new_tokens=2), **fields,
        )

    with pytest.raises(ValueError, match="largest prefill bucket"):
        engine.submit(request(prompt=list(range(40))))
    with pytest.raises(ValueError, match="handoff"):
        engine.submit(request(export_handoff=True))
    with pytest.raises(ValueError, match="handoff"):
        engine.submit(request(kv_import={"rows": []}))
