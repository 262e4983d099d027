"""Plain reference for the DeepSeek-V2 family (``model_type``
``deepseek_v2``): latent attention (MLA) and a mixture of routed and
shared experts after leading dense layers. Weights from the seed, one
full forward pass in float32 under ``jax.default_matmul_precision
("highest")``: no cache, no absorbed projections, no kernels, every held
expert applied to every token by a plain loop and weighted by the router.

Imports nothing of the program and nothing of another family. The sizes
come from the configuration's file, the published ``config.json`` keys
(https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json).

**A layer**, as published (``h`` hidden, RMS with ``rms_norm_eps``, no
biases): ``x^ = RMS(x)``; ``c_q = RMS(x^ W_qa)`` (``q_lora_rank``);
``q = c_q W_qb`` -> heads x (``q_nope`` ``qk_nope_head_dim`` |
``q_pe`` ``qk_rope_head_dim``); ``x^ W_kva`` -> ``c_kv``
(``kv_lora_rank``) | ``k_pe`` (one for all heads); ``c_kv = RMS(c_kv)``;
``k_pe`` and ``q_pe`` rotated; ``c_kv W_kvb`` -> heads x (``k_nope`` |
``v`` ``v_head_dim``); ``score = (q_nope.k_nope + q_pe.k_pe) * s``,
causal softmax in float32, ``o = softmax . v``, out ``concat(o) W_o``.
YaRN: ``inv_freq`` blends ``theta^(-2i/d)`` and the same over ``factor``
by the linear ramp between the correction dims of ``beta_fast`` and
``beta_slow`` at ``original_max_position_embeddings``; cos and sin are
scaled by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``;
``s = (nope + rope)^(-1/2) * m^2`` with ``m = 0.1 * mscale_all_dim *
ln(factor) + 1``. The first ``first_k_dense_replace`` layers' feed-forward
is a SwiGLU of ``intermediate_size``. The others': ``p = softmax(x^ W_g)``
over ALL routed experts in float32; a group's score is the largest ``p``
among its experts; the ``topk_group`` best of ``n_group`` groups stay, the
others' ``p`` count as 0; the ``num_experts_per_tok`` largest remaining
are the token's experts with weights ``routed_scaling_factor * p_e``, not
renormalised (``norm_topk_prob`` false); ``y = sum_e w_e SwiGLU_e(x^) +
SwiGLU_shared(x^)``, the shared width ``n_shared_experts *
moe_intermediate_size``. Final RMS, untied head.

**Departure from the published code**: it rotates interleaved pairs
(``x[2i], x[2i+1]``); this reference, like the program, rotates halves
(``x[i], x[i + d/2]``). With weights from a seed the two differ by a fixed
permutation of the rotary columns of ``W_qb`` and ``W_kva``; a loader of
published checkpoints has to apply it (ROADMAP).

**The share** (``model-configs`` guide, section 4): the file's
``n_routed_experts`` counts the experts HELD here, ``[experts_held_first,
experts_held_first + n_routed_experts)`` of the ``n_routed_experts_
published`` the router scores. The router keeps its published width, its
groups and its experts per token; the sum runs over the held experts only
and the shared expert is added here. What the absent experts would add is
left out. ``vocab_size`` is the slice held, a smaller vocabulary.
``reference_layer`` gives one layer's feed-forward for any held range, so
that a test can add the shares up.

**Weights** (``weights`` in the file): ``bf16-normal`` (``f32-normal``):
float32 normal times 1/sqrt(fan-in), rounded to bf16 (kept float32); ``wo``
and every ``down`` further divided by sqrt(2 * layers); the embedding
times ``EMBEDDING_STD`` whatever the width, so that a token's own row is
as large in its state as what the layers add to it (see the constant); every
norm scale drawn uniformly from [0.5, 1.5), away from 1, so that a dropped
RMS cannot stay correct. Keys: ``split(PRNGKey(seed), 4)`` gives embedding,
head, final norm and the layers' root; layer ``l`` (its number in the
model) draws from ``split(fold_in(root, l), 14)`` in the order attn_norm,
wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b, wo, mlp_norm, then (dense)
gate, up, down or (experts) router, shared gate, up, down, and the
routed experts' root; expert ``e`` (its number among ALL the router's
outputs) draws gate, up, down from ``split(fold_in(root, e), 3)``: an
expert's weights do not depend on which range is held.

``lower`` re-states every matmul weight, the experts' among them, in the
nearest precision below the configuration's (int8 or fp8 under bf16): the
control the comparison has to fail. Router and norms stay as they are.

**The work counts.** ``prompt_flops`` and ``output_token_flops`` count what
a token really meets: the attention's projections, the router at its full
width, the shared expert, and of the routed experts the EXPECTATION of
those it meets among the held ones (``num_experts_per_tok * held /
published``: 1.5 of 40 for a quarter of 160; by symmetry of the groups.
The records a run keeps hold no routing, so this is the expectation, not a
count), the head over the slice. Attention is counted in its plain form
(keys ``nope + rope`` wide, values ``v_head_dim`` wide): absorbing
``W_UK``/``W_UV`` into the query is the program's choice, and its extra
flops are not model work. ``kernel_work`` answers for ``flash_prefill`` at
those two widths (padding a tile shows as a lower share), for
``mla_decode``, which reads the latent once a layer a step: 2 x (latent +
rope + latent) x heads flops a cached token against (latent + rope) x 2
bytes (a cache row padded to whole lanes shows as a lower share), and for
``moe_grouped_matmul`` in the prefills: the expected routed rows through
an expert's three matmuls, against the rows in and out and every held
expert's weights once a layer.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Sizes:
    """The published sizes, read from the configuration's file."""

    def __init__(self, config: Dict[str, Any]) -> None:
        self.vocab = int(config["vocab_size"])
        self.hidden = int(config["hidden_size"])
        self.dense_inter = int(config["intermediate_size"])
        self.expert_inter = int(config["moe_intermediate_size"])
        self.layers = int(config["num_hidden_layers"])
        self.dense_layers = int(config["first_k_dense_replace"])
        self.heads = int(config["num_attention_heads"])
        self.q_rank = int(config["q_lora_rank"])
        self.kv_rank = int(config["kv_lora_rank"])
        self.nope = int(config["qk_nope_head_dim"])
        self.rope = int(config["qk_rope_head_dim"])
        self.v_dim = int(config["v_head_dim"])
        self.held = int(config["n_routed_experts"])
        self.held_first = int(config["experts_held_first"])
        self.router = int(config["n_routed_experts_published"])
        self.shared = int(config["n_shared_experts"])
        self.groups = int(config["n_group"])
        self.groups_kept = int(config["topk_group"])
        self.per_token = int(config["num_experts_per_tok"])
        self.factor = float(config["routed_scaling_factor"])
        self.theta = float(config["rope_theta"])
        self.eps = float(config["rms_norm_eps"])
        self.recipe = str(config["weights"])
        scaling = config["rope_scaling"]
        self.yarn = (
            float(scaling["factor"]), float(scaling["beta_fast"]),
            float(scaling["beta_slow"]), float(scaling["mscale"]),
            float(scaling["mscale_all_dim"]),
            int(scaling["original_max_position_embeddings"]),
        )
        if (
            scaling["type"] != "yarn" or config["norm_topk_prob"]
            or config["scoring_func"] != "softmax"
            or config["topk_method"] != "group_limited_greedy"
            or int(config["moe_layer_freq"]) != 1
            or config["tie_word_embeddings"] or config["attention_bias"]
            or self.held_first + self.held > self.router
        ):
            raise ValueError("a deepseek_v2 configuration this reference does not compute")

    def _key(self):
        return tuple(sorted(vars(self).items()))

    def __hash__(self) -> int:  # a static argument of the jitted passes
        return hash(self._key())

    def __eq__(self, other) -> bool:
        return isinstance(other, Sizes) and self._key() == other._key()


def size_check(engine_config) -> Dict[str, Any]:
    """Key of the configuration's file -> what the program's config holds
    for it: every size the reference reads, the experts HELD here against
    the program's count of held experts (and the router's width and the
    range's start beside it), the vocabulary as the slice it is."""
    mla, experts = engine_config.mla, engine_config.experts
    kind, factor, fast, slow, mscale, all_dim, original = engine_config.rope_scaling
    return {
        "vocab_size": engine_config.vocab_size,
        "hidden_size": engine_config.hidden_size,
        "intermediate_size": engine_config.intermediate_size,
        "num_hidden_layers": engine_config.num_layers,
        "num_attention_heads": engine_config.num_heads,
        "q_lora_rank": mla.q_lora_rank,
        "kv_lora_rank": mla.kv_lora_rank,
        "qk_nope_head_dim": mla.qk_nope_head_dim,
        "qk_rope_head_dim": mla.qk_rope_head_dim,
        "v_head_dim": mla.v_head_dim,
        "moe_intermediate_size": experts.intermediate_size,
        "n_routed_experts": experts.held,
        "experts_held_first": experts.held_first,
        "n_routed_experts_published": experts.routed,
        "n_shared_experts": experts.shared,
        "first_k_dense_replace": experts.leading_dense,
        "n_group": experts.groups,
        "topk_group": experts.groups_kept,
        "num_experts_per_tok": experts.per_token,
        "routed_scaling_factor": experts.scaling_factor,
        "rope_theta": engine_config.rope_theta,
        "rope_scaling": {
            "type": kind, "factor": factor, "beta_fast": fast, "beta_slow": slow,
            "mscale": mscale, "mscale_all_dim": all_dim,
            "original_max_position_embeddings": int(original),
        },
        "rms_norm_eps": engine_config.norm_eps,
        "tie_word_embeddings": engine_config.tie_embeddings,
        "attention_bias": engine_config.qkv_bias,
    }


# --------------------------------------------------------------------- #
# the work counts
# --------------------------------------------------------------------- #
def attention_params(sizes: Sizes) -> int:
    qk = sizes.nope + sizes.rope
    return (
        sizes.hidden * sizes.q_rank + sizes.q_rank * sizes.heads * qk
        + sizes.hidden * (sizes.kv_rank + sizes.rope)
        + sizes.kv_rank * sizes.heads * (sizes.nope + sizes.v_dim)
        + sizes.heads * sizes.v_dim * sizes.hidden
    )


def experts_met(sizes: Sizes) -> float:
    """Routed experts a token meets among the held ones, in expectation."""
    return sizes.per_token * sizes.held / sizes.router


def body_matmul_params(sizes: Sizes) -> float:
    """Matmul weights one token meets in all layers, the head apart."""
    expert = 3 * sizes.hidden * sizes.expert_inter
    dense_layer = attention_params(sizes) + 3 * sizes.hidden * sizes.dense_inter
    expert_layer = (
        attention_params(sizes) + sizes.hidden * sizes.router
        + sizes.shared * expert + experts_met(sizes) * expert
    )
    return (
        sizes.dense_layers * dense_layer
        + (sizes.layers - sizes.dense_layers) * expert_layer
    )


def attention_flops(sizes: Sizes, context: int) -> int:
    """QK^T (nope + rope wide) and PV (v wide) of one query token over
    ``context`` keys, all heads, all layers, in the plain form."""
    return 2 * sizes.heads * (sizes.nope + sizes.rope + sizes.v_dim) * context * sizes.layers


def prompt_flops(sizes: Sizes, prompt_tokens: int) -> int:
    body = 2 * body_matmul_params(sizes) * prompt_tokens
    attn = attention_flops(sizes, 1) * prompt_tokens * (prompt_tokens + 1) // 2
    return int(body + attn + 2 * sizes.hidden * sizes.vocab)


def output_token_flops(sizes: Sizes, context: int) -> int:
    body = 2 * (body_matmul_params(sizes) + sizes.hidden * sizes.vocab)
    return int(body + attention_flops(sizes, context))


def kernel_work(sizes: Sizes, kernel: str, served: Dict[str, Any]):
    """(flops, bytes) an ideal kernel named ``kernel`` needs for what the
    traced window served; None for a name this family does not count."""
    if kernel == "flash_prefill" and served["prompts"]:
        pairs = sum(n * (n + 1) // 2 for n in served["prompts"])
        tokens = sum(served["prompts"])
        qk = sizes.nope + sizes.rope
        flops = 2 * sizes.heads * (qk + sizes.v_dim) * pairs * sizes.layers
        moved = tokens * sizes.heads * (2 * qk + 2 * sizes.v_dim) * 2 * sizes.layers
        return flops, moved
    if kernel == "moe_grouped_matmul" and served["prompts"]:
        # the prefills' routed rows (in expectation: the records hold no
        # routing) through gate, up and down; every held expert's weights
        # at least once a layer, however the engine groups its dispatches
        expert_layers = sizes.layers - sizes.dense_layers
        rows = sum(served["prompts"]) * experts_met(sizes)
        expert = 3 * sizes.hidden * sizes.expert_inter
        flops = 2 * expert * rows * expert_layers
        moved = (rows * 2 * sizes.hidden + sizes.held * expert) * 2 * expert_layers
        return int(flops), int(moved)
    if kernel == "mla_decode" and served["decode_queries"]:
        keys, queries = served["decode_keys"], served["decode_queries"]
        latent = sizes.kv_rank + sizes.rope
        flops = 2 * sizes.heads * (latent + sizes.kv_rank) * keys * sizes.layers
        moved = (keys * latent + queries * sizes.heads * (latent + sizes.kv_rank)) * 2
        return flops, moved * sizes.layers
    return None


# --------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------- #
def _dtype(sizes: Sizes):
    if sizes.recipe not in ("bf16-normal", "f32-normal"):
        raise ValueError(f"unknown weights recipe {sizes.recipe!r}")
    return jnp.bfloat16 if sizes.recipe == "bf16-normal" else jnp.float32


@partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


@partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _normal_each(keys, shape, scale, dtype):
    """One draw a key, stacked: what each key draws does not depend on
    the others."""
    return jax.vmap(
        lambda key: (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)
    )(keys)


def _norm_scale(key, width: int):
    return jax.random.uniform(key, (width,), jnp.float32, 0.5, 1.5)


def make_layer(sizes: Sizes, root, layer: int, first: int, count: int) -> Dict[str, Any]:
    """Layer ``layer``'s weights with the routed experts ``[first, first +
    count)``. Matmul leaves are ``(values, None)``."""
    dtype = _dtype(sizes)
    keys = jax.random.split(jax.random.fold_in(root, layer), 14)
    h, heads = sizes.hidden, sizes.heads
    down = 1.0 / math.sqrt(2 * sizes.layers)
    out: Dict[str, Any] = {
        "attn_norm": _norm_scale(keys[0], h),
        "wq_a": (_normal(keys[1], (h, sizes.q_rank), h ** -0.5, dtype), None),
        "q_norm": _norm_scale(keys[2], sizes.q_rank),
        "wq_b": (_normal(
            keys[3], (sizes.q_rank, heads * (sizes.nope + sizes.rope)),
            sizes.q_rank ** -0.5, dtype), None),
        "wkv_a": (_normal(keys[4], (h, sizes.kv_rank + sizes.rope), h ** -0.5, dtype), None),
        "kv_norm": _norm_scale(keys[5], sizes.kv_rank),
        "wkv_b": (_normal(
            keys[6], (sizes.kv_rank, heads * (sizes.nope + sizes.v_dim)),
            sizes.kv_rank ** -0.5, dtype), None),
        "wo": (_normal(
            keys[7], (heads * sizes.v_dim, h),
            (heads * sizes.v_dim) ** -0.5 * down, dtype), None),
        "mlp_norm": _norm_scale(keys[8], h),
    }

    def swiglu(prefix, gate, up, dn, width, draw=_normal):
        out[prefix + "gate"] = (draw(gate, (h, width), h ** -0.5, dtype), None)
        out[prefix + "up"] = (draw(up, (h, width), h ** -0.5, dtype), None)
        out[prefix + "down"] = (draw(dn, (width, h), width ** -0.5 * down, dtype), None)

    if layer < sizes.dense_layers:
        swiglu("", keys[9], keys[10], keys[11], sizes.dense_inter)
        return out
    out["router"] = _normal(keys[9], (h, sizes.router), h ** -0.5, dtype)
    swiglu("shared_", keys[10], keys[11], keys[12], sizes.shared * sizes.expert_inter)
    each = jnp.stack([
        jax.random.split(jax.random.fold_in(keys[13], expert), 3)
        for expert in range(first, first + count)
    ])  # [count, 3, key]
    swiglu("expert_", each[:, 0], each[:, 1], each[:, 2], sizes.expert_inter, _normal_each)
    return out


# What a layer adds to a state is 0.2-0.5 a value at any width (unit-scale
# projections, ``wo`` and ``down`` over sqrt(2 * layers)). A row of
# 1/sqrt(hidden) a value, 0.014 here, is a fifteenth of that: every state
# is then its context's average, the router sees all the slots of a decode
# step nearly alike, and how many held experts a step meets (what its
# expert matmuls cost) follows the seed: 95-112 tiles a step over eight
# seeds at a tenth of these widths, 118-123 with rows of this size
# (``tools/decode_tiles.py``; PERF.md section 6, PR 29). Much larger, and
# the logits are the row's own: the lower-precision control then reads as
# the program does.
EMBEDDING_STD = 0.22


def make_weights(sizes: Sizes, seed: int) -> Dict[str, Any]:
    """The configuration's weights from the seed, on the default device,
    held as stored (bf16 for ``bf16-normal``)."""
    dtype = _dtype(sizes)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    h = sizes.hidden
    return {
        "embedding": _normal(keys[0], (sizes.vocab, h), EMBEDDING_STD, dtype),
        "lm_head": (_normal(keys[1], (h, sizes.vocab), h ** -0.5, dtype), None),
        "final_norm": _norm_scale(keys[2], h),
        "layers": [
            make_layer(sizes, keys[3], layer, sizes.held_first, sizes.held)
            for layer in range(sizes.layers)
        ],
    }


# --------------------------------------------------------------------- #
# the forward pass
# --------------------------------------------------------------------- #
def _dense(leaf: Tuple[Any, Any], lower: Optional[str]) -> jnp.ndarray:
    """One matmul weight ``[..., in, out]`` as float32, optionally restated
    in the lower precision ``lower`` on a symmetric grid with one scale
    for every output channel."""
    w32 = leaf[0].astype(jnp.float32)
    if lower is None:
        return w32
    absmax = jnp.maximum(jnp.max(jnp.abs(w32), axis=-2, keepdims=True), 1e-12)
    if lower == "int8":
        scale = absmax / 127.0
        return jnp.clip(jnp.round(w32 / scale), -127, 127) * scale
    if lower == "fp8":
        scale = absmax / 448.0
        return (w32 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown lower precision {lower!r}")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> Tuple[np.ndarray, float]:
    """(inv_freq [dim / 2], the factor on cos and sin)."""
    factor, fast, slow, mscale, all_dim, original = yarn
    exponents = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra, inter = 1.0 / theta ** exponents, 1.0 / (factor * theta ** exponents)

    def correction(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(fast)), 0)
    high = min(math.ceil(correction(slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    inv = inter * ramp + extra * (1.0 - ramp)
    return inv.astype(np.float32), _mscale(factor, mscale) / _mscale(factor, all_dim)


def softmax_scale(sizes: Sizes) -> float:
    m = _mscale(sizes.yarn[0], sizes.yarn[4])
    return (sizes.nope + sizes.rope) ** -0.5 * m * m


def _rotate(x, inv_freq, on_cos_sin):
    """x [T, heads, dim]; halves are rotated, position = row."""
    seq, _, dim = x.shape
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(angles) * on_cos_sin)[:, None, :]
    sin = (jnp.sin(angles) * on_cos_sin)[:, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(sizes: Sizes, x, layer, lower, block: int):
    """x [T, hidden] float32 -> the attention's output [T, hidden]."""
    seq = x.shape[0]
    heads, nope, rope, v_dim = sizes.heads, sizes.nope, sizes.rope, sizes.v_dim
    inv_freq, on_cos_sin = yarn_inv_freq(rope, sizes.theta, sizes.yarn)
    normed = _rms(x, layer["attn_norm"], sizes.eps)
    c_q = _rms(normed @ _dense(layer["wq_a"], lower), layer["q_norm"], sizes.eps)
    q = (c_q @ _dense(layer["wq_b"], lower)).reshape(seq, heads, nope + rope)
    q_nope, q_pe = q[..., :nope], _rotate(q[..., nope:], inv_freq, on_cos_sin)
    kv_a = normed @ _dense(layer["wkv_a"], lower)
    c_kv = _rms(kv_a[:, : sizes.kv_rank], layer["kv_norm"], sizes.eps)
    k_pe = _rotate(kv_a[:, None, sizes.kv_rank:], inv_freq, on_cos_sin)[:, 0]
    kv = (c_kv @ _dense(layer["wkv_b"], lower)).reshape(seq, heads, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = softmax_scale(sizes)
    keys_at = jnp.arange(seq)

    def rows(start):
        """Queries [start, start + block) against every key."""
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, block, 0)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, start, block, 0)
        scores = (
            jnp.einsum("thd,shd->hts", qn, k_nope) + jnp.einsum("thd,sd->hts", qp, k_pe)
        ) * scale
        causal = keys_at[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block))
    return out.reshape(seq, heads * v_dim) @ _dense(layer["wo"], lower)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(sizes: Sizes, normed, router) -> jnp.ndarray:
    """[T, router outputs] float32: ``routed_scaling_factor * p_e`` at the
    token's experts, 0 elsewhere."""
    probs = jax.nn.softmax(normed @ router.astype(jnp.float32), axis=-1)
    tokens = probs.shape[0]
    by_group = probs.reshape(tokens, sizes.groups, -1)
    _, best = jax.lax.top_k(by_group.max(axis=-1), sizes.groups_kept)
    keep = jnp.zeros((tokens, sizes.groups), bool).at[
        jnp.arange(tokens)[:, None], best].set(True)
    masked = jnp.where(keep[:, :, None], by_group, 0.0).reshape(tokens, -1)
    weights, chosen = jax.lax.top_k(masked, sizes.per_token)
    return jnp.zeros_like(probs).at[
        jnp.arange(tokens)[:, None], chosen].add(weights * sizes.factor)


def _feed_forward(sizes: Sizes, x, layer, lower, first: int, shared: bool = True):
    """The feed-forward's output on x [T, hidden] (the residual apart):
    dense SwiGLU, or the held experts ``[first, ...)`` weighted by the
    router plus (``shared``) the shared expert."""
    normed = _rms(x, layer["mlp_norm"], sizes.eps)
    if "router" not in layer:
        return _swiglu(normed, *(_dense(layer[n], lower) for n in ("gate", "up", "down")))
    weights = route(sizes, normed, layer["router"])
    held = layer["expert_gate"][0].shape[0]
    weights = weights[:, first:first + held]

    def one(mixed, leaves):
        """One expert at a time, so that one is float32 at a time."""
        weight, gate, up, down = leaves
        out = _swiglu(normed, *(_dense((w, None), lower) for w in (gate, up, down)))
        return mixed + weight[:, None] * out, None

    mixed, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        weights.T, layer["expert_gate"][0], layer["expert_up"][0], layer["expert_down"][0],
    ))
    if shared:
        mixed = mixed + _swiglu(normed, *(
            _dense(layer[n], lower) for n in ("shared_gate", "shared_up", "shared_down")
        ))
    return mixed


@partial(jax.jit, static_argnames=("sizes", "lower", "block", "first"))
def _layer(x, layer, sizes, lower, block, first):
    x = x + _attention(sizes, x, layer, lower, block)
    return x + _feed_forward(sizes, x, layer, lower, first)


_feed_forward_jit = jax.jit(
    _feed_forward, static_argnames=("sizes", "lower", "first", "shared")
)


def reference_layer(sizes: Sizes, seed: int, layer: int, x, first: int, count: int,
                    shared: bool = True):
    """Layer ``layer``'s feed-forward output on x [T, hidden] with the
    routed experts ``[first, first + count)`` held, with or without the
    shared expert: what one share of a deployment adds."""
    root = jax.random.split(jax.random.PRNGKey(seed), 4)[3]
    weights = make_layer(sizes, root, layer, first, count)
    with jax.default_matmul_precision("highest"):
        return _feed_forward_jit(
            sizes, jnp.asarray(x, jnp.float32), weights, None, first, shared
        )


@partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, scale, head, eps, lower):
    return _rms(x, scale, eps) @ _dense(head, lower)


def logits_at(
    sizes: Sizes,
    weights: Dict[str, Any],
    rows: Sequence[Sequence[int]],
    spans: Sequence[Tuple[int, int]],
    pad_to: int,
    lower: Optional[str] = None,
) -> List[np.ndarray]:
    """Full forward pass over each row of token ids (padded on the right
    to ``pad_to``, which causal attention never looks at and a token's own
    experts never mix in), returning the float32 logits at positions
    ``[start, stop)`` of each row. Attention runs in blocks of queries."""
    block = next(b for b in (256, 128, 64, 32, 16, 8, 4, 2, 1) if pad_to % b == 0)
    embedding = weights["embedding"]
    states = []
    for row in rows:
        ids = np.zeros((pad_to,), dtype=np.int32)
        ids[: len(row)] = np.asarray(row, dtype=np.int32)
        states.append(embedding[jnp.asarray(ids)].astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        for layer in weights["layers"]:
            states = [
                _layer(x, layer, sizes, lower, block, sizes.held_first) for x in states
            ]
        width = max(stop - start for start, stop in spans)
        out = []
        for x, (start, stop) in zip(states, spans):
            index = np.minimum(np.arange(start, start + width), pad_to - 1)
            logits = _head(
                x[jnp.asarray(index)], weights["final_norm"], weights["lm_head"],
                sizes.eps, lower,
            )
            out.append(np.asarray(logits)[: stop - start])
    return out
