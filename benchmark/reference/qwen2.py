"""Plain reference for the Qwen-2 family: weights from the seed, one
forward pass in float32, no kernels, no cache, no batching tricks.

Imports nothing of the program. The sizes come from the configuration's
file (the published ``config.json`` keys); the weights are made here from
the seed by the recipe the configuration states (``weights`` in the file):

- ``int8-uniform``: every matmul weight is int8 drawn uniformly from
  [-127, 127] with one scale 1/sqrt(hidden)/127 for every output channel,
  the embedding bf16 normal / sqrt(hidden), norms 1, qkv biases 0, an
  untied int8 output head;
- ``bf16-normal`` (``f32-normal``): float32 normal * 1/sqrt(hidden)
  rounded to bf16 (kept float32), ``wo`` and ``w_down`` scaled down by
  sqrt(2 * layers), norms 1, qkv biases 0, the output head tied to the
  embedding or drawn the same way, as the file says.

Both draw leaf ``i`` from ``jax.random.split(PRNGKey(seed), 10)[i]`` in the
order embedding, wq, wk, wv, wo, w_gate, w_up, w_down, lm_head. The norm
scales and the qkv biases are leaves like the others and the forward pass
applies them; both recipes make them 1 and 0 because the program's random
initialisation does, so a comparison on these weights cannot see a norm
scale or a bias that the program drops (PERF.md, Open questions).

``lower`` re-states the weights in the nearest precision below the
configuration's (int4 for int8, int8 or fp8 for bf16): the control that
the comparison has to fail.

This file is the whole family as the harness sees it (README, "A
family"): ``Sizes``, ``make_weights`` and ``logits_at`` for the
comparison, ``size_check`` for the program's sizes against the file, and
the work counts (``prompt_flops``, ``output_token_flops``, ``kernel_work``)
for ``mfu.*`` and the kernels' rooflines. The counts are ``flops.py``'s,
the count of a GQA decoder, which nothing else reaches.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops

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
        self.head_dim = int(
            config.get("head_dim") or self.hidden // self.heads
        )
        self.theta = float(config["rope_theta"])
        self.eps = float(config["rms_norm_eps"])
        self.tied = bool(config["tie_word_embeddings"])
        self.qkv_bias = bool(config["attention_bias"])
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
        "rope_theta": engine_config.rope_theta,
        "rms_norm_eps": engine_config.norm_eps,
        "tie_word_embeddings": engine_config.tie_embeddings,
        "attention_bias": engine_config.qkv_bias,
    }


prompt_flops = flops.prompt_flops
output_token_flops = flops.output_token_flops


def kernel_work(sizes: Sizes, kernel: str, served: Dict[str, Any]):
    """(flops, bytes) of the work of the kernel named ``kernel`` for what
    the traced window served (``measure.served_between``); None for a
    kernel this family's models do not run or that had nothing to do."""
    if kernel == "flash_decode" and served["decode_queries"]:
        return flops.decode_attention(
            sizes, served["decode_keys"], served["decode_queries"]
        )
    return None


def make_weights(sizes: Sizes, seed: int) -> Dict[str, Any]:
    """The configuration's weights from the seed, on the default device.
    Matmul leaves come back as ``(values, scale)``: int8 values with a
    float32 per-output-channel scale, or bf16 values with scale None."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 10)
    h, f, v, n = sizes.hidden, sizes.inter, sizes.vocab, sizes.layers
    q_out = sizes.heads * sizes.head_dim
    kv_out = sizes.kv_heads * sizes.head_dim
    shapes = {
        "wq": (1, (n, h, q_out)), "wk": (2, (n, h, kv_out)),
        "wv": (3, (n, h, kv_out)), "wo": (4, (n, q_out, h)),
        "w_gate": (5, (n, h, f)), "w_up": (6, (n, h, f)),
        "w_down": (7, (n, f, h)),
    }
    base = 1.0 / math.sqrt(h)
    out: Dict[str, Any] = {
        "attn_norm": jnp.ones((n, h), jnp.float32),
        "mlp_norm": jnp.ones((n, h), jnp.float32),
        "final_norm": jnp.ones((h,), jnp.float32),
    }
    if sizes.qkv_bias:
        out["bq"] = jnp.zeros((n, q_out), jnp.float32)
        out["bk"] = jnp.zeros((n, kv_out), jnp.float32)
        out["bv"] = jnp.zeros((n, kv_out), jnp.float32)
    if sizes.recipe == "int8-uniform":
        out["embedding"] = (
            jax.random.normal(keys[0], (v, h), dtype=jnp.bfloat16) * base
        )
        for name, (index, shape) in shapes.items():
            values = jax.random.randint(
                keys[index], shape, -127, 128, dtype=jnp.int8
            )
            out[name] = (values, jnp.float32(base / 127.0))
        if not sizes.tied:
            values = jax.random.randint(
                keys[8], (h, v), -127, 128, dtype=jnp.int8
            )
            out["lm_head"] = (values, jnp.float32(base / 127.0))
    elif sizes.recipe in ("bf16-normal", "f32-normal"):
        dtype = jnp.bfloat16 if sizes.recipe == "bf16-normal" else jnp.float32

        def normal(index, shape, scale):
            drawn = jax.random.normal(keys[index], shape, dtype=jnp.float32)
            return (drawn * scale).astype(dtype)

        out["embedding"] = normal(0, (v, h), base)
        down = base / math.sqrt(2 * n)
        for name, (index, shape) in shapes.items():
            scale = down if name in ("wo", "w_down") else base
            out[name] = (normal(index, shape, scale), None)
        if not sizes.tied:
            out["lm_head"] = (normal(8, (h, v), base), None)
    else:
        raise ValueError(f"unknown weights recipe {sizes.recipe!r}")
    return out


def _fake_quant(w32: jnp.ndarray, levels: int) -> jnp.ndarray:
    """Symmetric per-output-channel integer grid of +-``levels``."""
    absmax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / levels
    return jnp.clip(jnp.round(w32 / scale), -levels, levels) * scale


def _dense(leaf: Tuple[Any, Any], lower: Optional[str]) -> jnp.ndarray:
    """One matmul weight as float32, optionally restated in the lower
    precision ``lower`` (``int4``, ``int8`` or ``fp8``)."""
    values, scale = leaf
    w32 = values.astype(jnp.float32)
    if scale is not None:
        w32 = w32 * scale
    if lower is None:
        return w32
    if lower == "int4":
        return _fake_quant(w32, 7)
    if lower == "int8":
        return _fake_quant(w32, 127)
    if lower == "fp8":
        absmax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
        scale8 = jnp.maximum(absmax, 1e-12) / 448.0
        return (w32 / scale8).astype(jnp.float8_e4m3fn).astype(
            jnp.float32
        ) * scale8
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
    """One decoder layer on one row: x [T, hidden] float32. ``plain``
    holds the layer's norm scales and its qkv biases (None without)."""
    heads, kv_heads, head_dim, theta, eps = dims
    wq, wk, wv, wo, w_gate, w_up, w_down = (
        _dense(leaf, lower) for leaf in leaves
    )
    attn_norm, mlp_norm, biases = plain
    bq, bk, bv = biases if biases is not None else (0.0, 0.0, 0.0)
    seq = x.shape[0]
    normed = _rms(x, attn_norm, eps)
    q = _rope((normed @ wq + bq).reshape(seq, heads, head_dim), theta)
    k = _rope((normed @ wk + bk).reshape(seq, kv_heads, head_dim), theta)
    v = (normed @ wv + bv).reshape(seq, kv_heads, head_dim)
    group = heads // kv_heads
    q = q.reshape(seq, kv_heads, group, head_dim)
    scores = jnp.einsum("tkgd,skd->kgts", q, k) / math.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((seq, seq), dtype=bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(scores, -1), v)
    x = x + attn.reshape(seq, heads * head_dim) @ wo
    normed = _rms(x, mlp_norm, eps)
    return x + (jax.nn.silu(normed @ w_gate) * (normed @ w_up)) @ w_down


@partial(jax.jit, static_argnames=("eps", "lower", "tied"))
def _head(x, scale, head, eps, lower, tied):
    normed = _rms(x, scale, eps)
    if tied:
        # the tied head is the embedding itself: never restated lower,
        # as the program's int8 path leaves the embedding alone too
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
    to ``pad_to``, which causal attention never looks at), returning the
    float32 logits at positions ``[start, stop)`` of each row: the
    distribution of the token after each of those positions."""
    dims = (sizes.heads, sizes.kv_heads, sizes.head_dim, sizes.theta, sizes.eps)
    embedding = weights["embedding"]
    states = []
    for row in rows:
        ids = np.zeros((pad_to,), dtype=np.int32)
        ids[: len(row)] = np.asarray(row, dtype=np.int32)
        states.append(embedding[jnp.asarray(ids)].astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        for layer in range(sizes.layers):
            leaves = tuple(
                (weights[name][0][layer], weights[name][1])
                for name in MATMULS
            )
            plain = (
                weights["attn_norm"][layer], weights["mlp_norm"][layer],
                tuple(weights[b][layer] for b in ("bq", "bk", "bv"))
                if sizes.qkv_bias else None,
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
