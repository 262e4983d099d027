"""Plain reference for the Mixtral family: a GQA decoder whose MLP is a
sparse mixture of experts. Weights from the seed, one forward pass in
float32, no kernels, no cache, no capacity, no dispatch tensors.

Imports nothing of the program and nothing of another family. The sizes
come from the configuration's file (the published ``config.json`` keys,
among them ``num_local_experts`` and ``num_experts_per_tok``). Every
expert of every layer is held here (a chip's share of the experts would be
``num_local_experts`` held against the program's count the same way, with
the published count stated beside it in the file).

The block, as published: RMSNorm, grouped-query attention with rotary
embeddings and no biases, residual; RMSNorm, then for every token the
router's logits over all experts, softmax over ALL of them, the
``num_experts_per_tok`` largest kept and renormalised to sum to 1, each
kept expert a SwiGLU MLP, their outputs mixed by those weights; residual.
No token is ever dropped. The reference loops over the experts and masks
by the weights, which is the same sum.

Weights (``weights`` in the file): ``f32-normal`` (``bf16-normal``):
float32 normal * 1/sqrt(hidden) (rounded to bf16), ``wo`` and ``w_down``
scaled down by sqrt(2 * layers), norms 1, the output head tied to the
embedding or drawn the same way. Leaf ``i`` is drawn from
``jax.random.split(PRNGKey(seed), 10)[i]`` in the order embedding, wq, wk,
wv, wo, w_gate, w_up, w_down, lm_head, router; the expert leaves are
``[layers, experts, in, out]``.

``lower`` re-states the matmul weights, the experts' among them, in the
nearest precision below the configuration's (int8 or fp8 under float32 or
bf16): the control that the comparison has to fail. The router stays as
it is, as the norms do.

This file is the whole family as the harness sees it (README, "A
family"): ``Sizes``, ``make_weights``, ``logits_at``, ``size_check`` and
the work counts.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


class Sizes:
    """The published sizes, read from the configuration's file."""

    def __init__(self, config: Dict[str, Any]) -> None:
        self.vocab = int(config["vocab_size"])
        self.hidden = int(config["hidden_size"])
        self.inter = int(config["intermediate_size"])
        self.layers = int(config["num_hidden_layers"])
        self.heads = int(config["num_attention_heads"])
        self.kv_heads = int(config["num_key_value_heads"])
        self.head_dim = int(config.get("head_dim") or self.hidden // self.heads)
        self.experts = int(config["num_local_experts"])
        self.experts_per_token = int(config["num_experts_per_tok"])
        self.theta = float(config["rope_theta"])
        self.eps = float(config["rms_norm_eps"])
        self.tied = bool(config["tie_word_embeddings"])
        self.recipe = str(config["weights"])


def size_check(engine_config) -> Dict[str, Any]:
    """Key of the configuration's file -> what the program's config holds
    for it; the harness refuses a run where any pair differs."""
    return {
        "vocab_size": engine_config.vocab_size,
        "hidden_size": engine_config.hidden_size,
        "intermediate_size": engine_config.intermediate_size,
        "num_hidden_layers": engine_config.num_layers,
        "num_attention_heads": engine_config.num_heads,
        "num_key_value_heads": engine_config.num_kv_heads,
        "head_dim": engine_config.dims_per_head,
        "num_local_experts": engine_config.num_experts,
        "num_experts_per_tok": engine_config.num_experts_per_tok,
        "rope_theta": engine_config.rope_theta,
        "rms_norm_eps": engine_config.norm_eps,
        "tie_word_embeddings": engine_config.tie_embeddings,
        "attention_bias": engine_config.qkv_bias,
    }


# --------------------------------------------------------------------- #
# the work counts
# --------------------------------------------------------------------- #
def layer_matmul_params(sizes: Sizes) -> int:
    """Weights one token meets in a layer: the attention's projections,
    the router over all experts, and the MLPs of the experts it is routed
    to, not of those the layer holds."""
    attn = sizes.hidden * sizes.head_dim * (2 * sizes.heads + 2 * sizes.kv_heads)
    router = sizes.hidden * sizes.experts
    return attn + router + sizes.experts_per_token * 3 * sizes.hidden * sizes.inter


def attention_flops(sizes: Sizes, context: int) -> int:
    """QK^T and PV of one query token over ``context`` keys, all layers."""
    return 4 * sizes.heads * sizes.head_dim * context * sizes.layers


def prompt_flops(sizes: Sizes, prompt_tokens: int) -> int:
    """A prompt's forward pass: every token through every layer's matmuls
    (its own experts'), causal attention (token p sees p + 1 keys), and
    the output head once, for the last token."""
    body = 2 * layer_matmul_params(sizes) * sizes.layers * prompt_tokens
    attn = attention_flops(sizes, 1) * prompt_tokens * (prompt_tokens + 1) // 2
    return body + attn + 2 * sizes.hidden * sizes.vocab


def output_token_flops(sizes: Sizes, context: int) -> int:
    """One decoded token whose query sees ``context`` keys."""
    body = 2 * (layer_matmul_params(sizes) * sizes.layers + sizes.hidden * sizes.vocab)
    return body + attention_flops(sizes, context)


def kernel_work(sizes: Sizes, kernel: str, served: Dict[str, Any]):
    """(flops, bytes) of the work of the kernel named ``kernel`` for what
    the traced window served; None for a kernel this family's models do
    not run. Its decode attention is grouped-query attention over a bf16
    cache: the K and V rows that hold the keys once each, the queries and
    the outputs. (The program has no expert-matmul kernel to count.)"""
    if kernel == "flash_decode" and served["decode_queries"]:
        keys, queries = served["decode_keys"], served["decode_queries"]
        flops = attention_flops(sizes, 1) * keys
        kv = 2 * sizes.kv_heads * sizes.head_dim * 2 * keys
        q_and_out = 2 * queries * sizes.heads * sizes.head_dim * 2
        return flops, (kv + q_and_out) * sizes.layers
    return None


# --------------------------------------------------------------------- #
# weights and the forward pass
# --------------------------------------------------------------------- #
def make_weights(sizes: Sizes, seed: int) -> Dict[str, Any]:
    """The configuration's weights from the seed, on the default device.
    Matmul leaves come back as ``(values, scale)`` with scale None."""
    if sizes.recipe not in ("bf16-normal", "f32-normal"):
        raise ValueError(f"unknown weights recipe {sizes.recipe!r}")
    dtype = jnp.bfloat16 if sizes.recipe == "bf16-normal" else jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(seed), 10)
    h, f, v, n, e = sizes.hidden, sizes.inter, sizes.vocab, sizes.layers, sizes.experts
    q_out = sizes.heads * sizes.head_dim
    kv_out = sizes.kv_heads * sizes.head_dim
    base = 1.0 / math.sqrt(h)
    down = base / math.sqrt(2 * n)

    def normal(index, shape, scale):
        drawn = jax.random.normal(keys[index], shape, dtype=jnp.float32)
        return (drawn * scale).astype(dtype)

    out: Dict[str, Any] = {
        "embedding": normal(0, (v, h), base),
        "wq": (normal(1, (n, h, q_out), base), None),
        "wk": (normal(2, (n, h, kv_out), base), None),
        "wv": (normal(3, (n, h, kv_out), base), None),
        "wo": (normal(4, (n, q_out, h), down), None),
        "w_gate": (normal(5, (n, e, h, f), base), None),
        "w_up": (normal(6, (n, e, h, f), base), None),
        "w_down": (normal(7, (n, e, f, h), down), None),
        "router": normal(9, (n, h, e), base),
        "attn_norm": jnp.ones((n, h), jnp.float32),
        "mlp_norm": jnp.ones((n, h), jnp.float32),
        "final_norm": jnp.ones((h,), jnp.float32),
    }
    if not sizes.tied:
        out["lm_head"] = (normal(8, (h, v), base), None)
    return out


def _dense(leaf: Tuple[Any, Any], lower: Optional[str]) -> jnp.ndarray:
    """One matmul weight ``[..., in, out]`` as float32, optionally restated
    in the lower precision ``lower`` (``int8`` or ``fp8``) on a symmetric
    grid with one scale for every output channel."""
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


def _rope(x, theta):
    """x [T, heads, dim]; rotate-half convention, position = row."""
    seq, _, dim = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("dims", "lower"))
def _layer(x, leaves, plain, dims, lower):
    """One decoder layer on one row: x [T, hidden] float32."""
    heads, kv_heads, head_dim, per_token, theta, eps = dims
    wq, wk, wv, wo, w_gate, w_up, w_down = (_dense(leaf, lower) for leaf in leaves)
    attn_norm, mlp_norm, router = plain
    seq = x.shape[0]
    normed = _rms(x, attn_norm, eps)
    q = _rope((normed @ wq).reshape(seq, heads, head_dim), theta)
    k = _rope((normed @ wk).reshape(seq, kv_heads, head_dim), theta)
    v = (normed @ wv).reshape(seq, kv_heads, head_dim)
    q = q.reshape(seq, kv_heads, heads // kv_heads, head_dim)
    scores = jnp.einsum("tkgd,skd->kgts", q, k) / math.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((seq, seq), dtype=bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(scores, -1), v)
    x = x + attn.reshape(seq, heads * head_dim) @ wo
    normed = _rms(x, mlp_norm, eps)
    # the router: softmax over all experts, the largest kept, renormalised
    probs = jax.nn.softmax(normed @ router.astype(jnp.float32), axis=-1)
    kept, chosen = jax.lax.top_k(probs, per_token)
    kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
    mixed = jnp.zeros_like(x)
    for expert in range(router.shape[-1]):
        weight = jnp.sum(jnp.where(chosen == expert, kept, 0.0), axis=-1)
        out = (jax.nn.silu(normed @ w_gate[expert]) * (normed @ w_up[expert])) @ w_down[expert]
        mixed = mixed + weight[:, None] * out
    return x + mixed


@partial(jax.jit, static_argnames=("eps", "lower", "tied"))
def _head(x, scale, head, eps, lower, tied):
    normed = _rms(x, scale, eps)
    if tied:
        return normed @ head.astype(jnp.float32).T
    return normed @ _dense(head, lower)


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
    ``[start, stop)`` of each row."""
    dims = (
        sizes.heads, sizes.kv_heads, sizes.head_dim, sizes.experts_per_token,
        sizes.theta, sizes.eps,
    )
    embedding = weights["embedding"]
    states = []
    for row in rows:
        ids = np.zeros((pad_to,), dtype=np.int32)
        ids[: len(row)] = np.asarray(row, dtype=np.int32)
        states.append(embedding[jnp.asarray(ids)].astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        for layer in range(sizes.layers):
            leaves = tuple((weights[name][0][layer], None) for name in MATMULS)
            plain = (
                weights["attn_norm"][layer], weights["mlp_norm"][layer],
                weights["router"][layer],
            )
            states = [_layer(x, leaves, plain, dims, lower) for x in states]
        head = weights["embedding"] if sizes.tied else weights["lm_head"]
        width = max(stop - start for start, stop in spans)
        out = []
        for x, (start, stop) in zip(states, spans):
            index = np.minimum(np.arange(start, start + width), pad_to - 1)
            logits = _head(
                x[jnp.asarray(index)], weights["final_norm"], head,
                sizes.eps, lower, sizes.tied,
            )
            out.append(np.asarray(logits)[: stop - start])
    return out
