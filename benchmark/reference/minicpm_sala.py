"""Plain reference for the MiniCPM-SALA family (``model_type``
``minicpm_sala``): lightning linear-attention layers beside block-sparse
GQA layers, in the order ``mixer_types`` gives. Weights from the seed, one
full forward pass in float32 under ``jax.default_matmul_precision
("highest")``: no cache, no state carried, no kernels, no chunks; the
linear attention in its exact quadratic form, the selection by ``top_k``
over block scores. Imports nothing of the program and selects its own
blocks. Sizes from the configuration's file, the published ``config.json``
keys (https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json).

**The model** (``h`` hidden, RMS with ``rms_norm_eps``, no biases):
``x = scale_emb * E[token]``; every layer, with ``r = scale_depth /
sqrt(num_hidden_layers)``: ``x += r * Mixer(RMS(x)) W_o``, then ``x += r *
SwiGLU(RMS(x))``; ``logits = (RMS(x) / (hidden_size / dim_model_base))
W_head``. ``mup_denominator`` enters no forward equation.

- ``lightning-attn`` (``lightning_nh`` heads of ``lightning_head_dim``):
  ``q, k, v = x^ W_q, x^ W_k, x^ W_v``; ``qk_norm``: RMS over the head
  dim of q and of k, a learned scale ``[d]`` each; ``lightning_use_rope``:
  q and k rotated at their positions (halves, ``rope_theta``); per head
  ``j``: ``o_t = sum_{s <= t} lam_j^(t-s) (q_t . k_s / sqrt(d)) v_s``,
  which is ``S_t = lam_j S_{t-1} + k_t^T v_t``, ``o_t = (q_t / sqrt(d))
  S_t`` summed out; ``use_output_norm``: RMS of the concatenated ``o``
  with a learned scale; ``use_output_gate``: ``o * sigmoid(x^ W_g)``.
  *Assumed*: ``lam_j = exp(-2^(-8 j / heads))``, ``j = 1..heads``, on
  every lightning layer; the norm over all ``heads x d`` values; the gate
  before ``W_o``.
- ``minicpm4`` (``num_attention_heads`` over ``num_key_value_heads``,
  ``head_dim``): the same q/k norm; ``attn_use_rope`` false: no rotation;
  scores ``q . k / sqrt(d)``, causal; ``attn_use_output_gate`` as above. A
  query at position ``t`` (context ``t + 1``) attends every key at or
  before it if ``t + 1 <= dense_len``. Past that (InfLLM-V2, over the
  layer's own K): compressed keys ``K~_i = mean(K[stride * i : stride * i
  + kernel])`` for the windows that end at or before ``t``; ``p_{h,i} =
  softmax_i(q_h . K~_i / sqrt(d))`` for each query head of a kv head's
  group, ``P_i = sum_h p_{h,i}``; a block of ``block_size`` positions
  scores the max of ``P_i`` over the windows that overlap it; kept always:
  blocks ``< init_blocks`` and every block from the one that holds
  position ``t + 1 - window_size`` on; of the other blocks that start at or
  before ``t`` the ``topk`` best; the output is causal softmax attention
  of every head of the group over the kept blocks' positions. The sizes
  are ``sparse_config`` in the file (*assumed*: MiniCPM4's; the published
  config carries none).

**Weights** (``weights`` in the file): ``int8-uniform``: every matmul
weight ``[in, out]`` int8 uniform in [-127, 127] with the scale ``1 /
sqrt(in) / 127`` for every output channel; the embedding bf16 normal /
sqrt(hidden) (``int8-uniform-f32``: float32, for a CPU self-test); every
norm scale uniform in [0.5, 1.5), away from 1, so that a dropped output
norm cannot stay correct; the q and k norms' uniform in [1.5, 2.5): a
sparse layer's scores then spread by about 4 and a dozen keys of 10,000
carry a query's attention, where at scales near 1 some 4,000 share it,
the layer adds next to nothing and the ``no-selection`` control reads as
the model does (PERF.md, section 6, PR 33). Keys: ``split(PRNGKey(seed), 4)``
gives embedding, head, final norm and the layers' root; layer ``l`` draws
from ``split(fold_in(root, l), 13)`` in the order attn_norm, wq, wk, wv,
wg, wo, q_norm, k_norm, out_norm, mlp_norm, w_gate, w_up, w_down
(``out_norm``'s key is skipped by a sparse layer).

``lower``: ``int4`` re-states every matmul weight on a grid of +-7 (the
precision below the configuration's; the control the comparison has to
fail); ``no-selection`` computes the sparse layers densely at every
context (the family's own control: a comparison that cannot tell it from
the model cannot see the selection).

**The work counts** count what a token meets: the kept blocks' keys, not
the context (``kept_keys``); the compressed keys it scores; a lightning
layer's state update and read-out (``4 d^2`` a head: the recurrence's,
whatever form a kernel computes). ``kernel_work`` answers, by name, for
``lightning_prefill`` and ``sparse_block_prefill`` (the prompts the window
prefilled), ``lightning_decode`` and ``sparse_block_decode`` (its decode
queries; a query past ``dense_len`` keeps a count of blocks that does not
grow with its context).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}
SPARSE_KEYS = (
    "kernel_size", "kernel_stride", "block_size", "topk", "init_blocks",
    "window_size", "dense_len",
)
LAYER_KEYS = (
    "attn_norm", "wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm",
    "out_norm", "mlp_norm", "w_gate", "w_up", "w_down",
)
MATMULS = ("wq", "wk", "wv", "wg", "wo", "w_gate", "w_up", "w_down")
SHARP = ("q_norm", "k_norm")


class Sizes:
    """The published sizes, read from the configuration's file."""

    def __init__(self, config: Dict[str, Any]) -> None:
        self.vocab = int(config["vocab_size"])
        self.hidden = int(config["hidden_size"])
        self.inter = int(config["intermediate_size"])
        self.layers = int(config["num_hidden_layers"])
        self.heads = int(config["num_attention_heads"])
        self.kv_heads = int(config["num_key_value_heads"])
        self.head_dim = int(config["head_dim"])
        self.l_heads = int(config["lightning_nh"])
        self.l_dim = int(config["lightning_head_dim"])
        self.mixers = tuple(KINDS[name] for name in config["mixer_types"])
        self.theta = float(config["rope_theta"])
        self.eps = float(config["rms_norm_eps"])
        self.scale_emb = float(config["scale_emb"])
        self.scale_depth = float(config["scale_depth"])
        self.base = int(config["dim_model_base"])
        self.recipe = str(config["weights"])
        sparse = config["sparse_config"]
        (self.kernel, self.stride, self.block, self.topk, self.init,
         self.window, self.dense_len) = (int(sparse[k]) for k in SPARSE_KEYS)
        if (
            len(self.mixers) != self.layers
            or int(config["lightning_nkv"]) != self.l_heads
            or not config["qk_norm"] or config["attn_use_rope"]
            or not config["lightning_use_rope"] or not config["use_output_gate"]
            or not config["use_output_norm"] or not config["attn_use_output_gate"]
            or config["lightning_scale"] != "1/sqrt(d)"
            or config["tie_word_embeddings"] or config["attention_bias"]
            or config["hidden_act"] != "silu"
            or self.kernel != 2 * self.stride or self.block % self.stride
        ):
            raise ValueError("a minicpm_sala configuration this reference does not compute")

    def _key(self):
        return tuple(sorted(vars(self).items()))

    def __hash__(self) -> int:  # a static argument of the jitted passes
        return hash(self._key())

    def __eq__(self, other) -> bool:
        return isinstance(other, Sizes) and self._key() == other._key()


def size_check(engine_config) -> Dict[str, Any]:
    """Key of the configuration's file -> what the program's config holds
    for it: every size the reference reads, the order of the layers'
    kinds and the selection's sizes among them."""
    names = {kind: name for name, kind in KINDS.items()}
    hybrid = engine_config.hybrid
    selection = hybrid.selection
    layers = engine_config.num_layers
    return {
        "vocab_size": engine_config.vocab_size,
        "hidden_size": engine_config.hidden_size,
        "intermediate_size": engine_config.intermediate_size,
        "num_hidden_layers": layers,
        "num_attention_heads": engine_config.num_heads,
        "num_key_value_heads": engine_config.num_kv_heads,
        "head_dim": engine_config.dims_per_head,
        "lightning_nh": hybrid.lightning_heads,
        "lightning_nkv": hybrid.lightning_heads,
        "lightning_head_dim": hybrid.lightning_head_dim,
        "mixer_types": [names[kind] for kind in engine_config.mixers],
        "sparse_config": {key: getattr(selection, key) for key in SPARSE_KEYS},
        "rope_theta": engine_config.rope_theta,
        "rms_norm_eps": engine_config.norm_eps,
        "scale_emb": engine_config.embedding_scale,
        "scale_depth": round(engine_config.residual_scale * math.sqrt(layers), 9),
        "dim_model_base": engine_config.hidden_size / engine_config.logit_divisor,
        "tie_word_embeddings": engine_config.tie_embeddings,
        "attention_bias": engine_config.qkv_bias,
    }


# --------------------------------------------------------------------- #
# the work counts
# --------------------------------------------------------------------- #
def layer_matmul_params(sizes: Sizes, kind: str) -> int:
    if kind == "lightning":
        mixer = 5 * sizes.hidden * sizes.l_heads * sizes.l_dim
    else:
        mixer = sizes.hidden * sizes.head_dim * (3 * sizes.heads + 2 * sizes.kv_heads)
    return mixer + 3 * sizes.hidden * sizes.inter


def body_matmul_params(sizes: Sizes) -> int:
    """Matmul weights one token meets in all layers, the head apart."""
    return sum(layer_matmul_params(sizes, kind) for kind in sizes.mixers)


def kept_keys(sizes: Sizes, context):
    """Keys a query of ``context`` (its position + 1; an int or an array)
    attends in a sparse layer: all of them up to ``dense_len``; past it
    the context less the far blocks the selection drops."""
    context = np.asarray(context, dtype=np.int64)
    first_local = np.maximum(context - sizes.window, 0) // sizes.block
    far = np.maximum(first_local - sizes.init, 0)
    dropped = np.maximum(far - sizes.topk, 0) * sizes.block
    return np.where(context <= sizes.dense_len, context, context - dropped)


def scored_windows(sizes: Sizes, context):
    """Compressed keys a query past ``dense_len`` scores."""
    context = np.asarray(context, dtype=np.int64)
    windows = np.maximum(context - sizes.kernel, -sizes.stride) // sizes.stride + 1
    return np.where(context <= sizes.dense_len, 0, windows)


def mixer_flops(sizes: Sizes, context):
    """The mixers' own work for one token of ``context``, all layers:
    QK^T and PV over the kept keys and the scores over the compressed
    keys in the sparse layers; the state's update and read-out in the
    lightning layers."""
    sparse = sum(1 for kind in sizes.mixers if kind == "sparse")
    lightning = len(sizes.mixers) - sparse
    attend = 4 * sizes.heads * sizes.head_dim * kept_keys(sizes, context)
    score = 2 * sizes.heads * sizes.head_dim * scored_windows(sizes, context)
    state = 4 * sizes.l_heads * sizes.l_dim * sizes.l_dim
    return sparse * (attend + score) + lightning * state


def prompt_flops(sizes: Sizes, prompt_tokens: int) -> int:
    body = 2 * body_matmul_params(sizes) * prompt_tokens
    mixers = int(mixer_flops(sizes, np.arange(1, prompt_tokens + 1)).sum())
    return int(body + mixers + 2 * sizes.hidden * sizes.vocab)


def output_token_flops(sizes: Sizes, context: int) -> int:
    body = 2 * (body_matmul_params(sizes) + sizes.hidden * sizes.vocab)
    return int(body + mixer_flops(sizes, context))


def kernel_work(sizes: Sizes, kernel: str, served: Dict[str, Any]):
    """(flops, bytes) an ideal kernel named ``kernel`` needs for what the
    traced window served; None for a name this family does not count."""
    sparse = sum(1 for kind in sizes.mixers if kind == "sparse")
    lightning = len(sizes.mixers) - sparse
    width = sizes.l_heads * sizes.l_dim
    state = sizes.l_heads * sizes.l_dim * sizes.l_dim
    prompts, queries = served["prompts"], served["decode_queries"]
    if kernel == "lightning_prefill" and prompts:
        tokens = sum(prompts)
        # q, k, v in and o out (bf16), the recurrence's flops
        return 4 * state * tokens * lightning, 4 * width * 2 * tokens * lightning
    if kernel == "lightning_decode" and queries:
        # the float32 state read once and written once a token
        return 4 * state * queries * lightning, 2 * state * 4 * queries * lightning
    if kernel == "sparse_block_prefill" and prompts:
        keys = sum(int(kept_keys(sizes, np.arange(1, n + 1)).sum()) for n in prompts)
        tokens = sum(prompts)
        rows = (2 * sizes.heads + 2 * sizes.kv_heads) * sizes.head_dim * 2
        return 4 * sizes.heads * sizes.head_dim * keys * sparse, rows * tokens * sparse
    if kernel == "sparse_block_decode" and queries:
        # a query keeps what it would at its mean context: past dense_len
        # the kept blocks' keys, a count that does not grow
        keys = int(kept_keys(sizes, served["decode_keys"] // queries)) * queries
        flops = 4 * sizes.heads * sizes.head_dim * keys
        moved = 2 * sizes.kv_heads * sizes.head_dim * 2 * keys
        q_and_out = 2 * queries * sizes.heads * sizes.head_dim * 2
        return flops * sparse, (moved + q_and_out) * sparse
    return None


# --------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------- #
def _shapes(sizes: Sizes, kind: str) -> Dict[str, Tuple[int, ...]]:
    h, f = sizes.hidden, sizes.inter
    if kind == "lightning":
        q_out = kv_out = sizes.l_heads * sizes.l_dim
        dim = sizes.l_dim
    else:
        q_out, kv_out = sizes.heads * sizes.head_dim, sizes.kv_heads * sizes.head_dim
        dim = sizes.head_dim
    shapes = {
        "attn_norm": (h,), "wq": (h, q_out), "wk": (h, kv_out), "wv": (h, kv_out),
        "wg": (h, q_out), "wo": (q_out, h), "q_norm": (dim,), "k_norm": (dim,),
        "mlp_norm": (h,), "w_gate": (h, f), "w_up": (h, f), "w_down": (f, h),
    }
    if kind == "lightning":
        shapes["out_norm"] = (q_out,)
    return shapes


@partial(jax.jit, static_argnames=("shape",))
def _int8(key, shape):
    return jax.random.randint(key, shape, -127, 128, dtype=jnp.int8)


def _matmul(key, shape):
    """(int8 values, the one scale of every output channel)."""
    return _int8(key, shape), jnp.float32(1.0 / math.sqrt(shape[0]) / 127.0)


def _norm_scale(key, width: int, low: float = 0.5):
    return jax.random.uniform(key, (width,), jnp.float32, low, low + 1.0)


def make_weights(sizes: Sizes, seed: int) -> Dict[str, Any]:
    """The configuration's weights from the seed, on the default device,
    held as stored: int8 matmuls, a layer at a time."""
    if sizes.recipe not in ("int8-uniform", "int8-uniform-f32"):
        raise ValueError(f"unknown weights recipe {sizes.recipe!r}")
    dtype = jnp.bfloat16 if sizes.recipe == "int8-uniform" else jnp.float32
    top = jax.random.split(jax.random.PRNGKey(seed), 4)
    layers = []
    for index, kind in enumerate(sizes.mixers):
        keys = dict(zip(LAYER_KEYS, jax.random.split(jax.random.fold_in(top[3], index), 13)))
        layers.append({
            name: _matmul(keys[name], shape) if name in MATMULS
            else _norm_scale(keys[name], shape[0], 1.5 if name in SHARP else 0.5)
            for name, shape in _shapes(sizes, kind).items()
        })
    h = sizes.hidden
    return {
        "embedding": (
            jax.random.normal(top[0], (sizes.vocab, h), dtype=dtype) * (1.0 / math.sqrt(h))
        ).astype(dtype),
        "lm_head": _matmul(top[1], (h, sizes.vocab)),
        "final_norm": _norm_scale(top[2], h),
        "layers": layers,
    }


# --------------------------------------------------------------------- #
# the forward pass
# --------------------------------------------------------------------- #
def _dense(leaf, lower: Optional[str]) -> jnp.ndarray:
    """One matmul weight as float32, optionally restated on the grid of
    the precision below (``int4``: +-7 an output channel)."""
    values, scale = leaf
    w32 = values.astype(jnp.float32) * scale
    if lower != "int4":
        return w32
    absmax = jnp.maximum(jnp.max(jnp.abs(w32), axis=-2, keepdims=True), 1e-12)
    return jnp.clip(jnp.round(w32 / (absmax / 7.0)), -7, 7) * (absmax / 7.0)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [T, heads, dim]; halves are rotated, position = row."""
    seq, _, dim = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _lightning(sizes: Sizes, normed, layer, lower, block: int):
    """The lightning mixer on normed x [T, hidden] -> [T, heads * d], in
    the exact quadratic form of its recurrence, a block of queries at a
    time."""
    seq, heads, dim = normed.shape[0], sizes.l_heads, sizes.l_dim
    q, k, v = (
        (normed @ _dense(layer[name], lower)).reshape(seq, heads, dim)
        for name in ("wq", "wk", "wv")
    )
    q = _rope(_rms(q, layer["q_norm"], sizes.eps), sizes.theta) / math.sqrt(dim)
    k = _rope(_rms(k, layer["k_norm"], sizes.eps), sizes.theta)
    slopes = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32) / heads)
    keys_at = jnp.arange(seq)

    def rows(start):
        gap = (start + jnp.arange(block))[:, None] - keys_at[None, :]   # [t, s]
        decay = jnp.where(
            gap >= 0, jnp.exp(-slopes[:, None, None] * jnp.maximum(gap, 0)), 0.0
        )
        scores = jnp.einsum(
            "thd,shd->hts", jax.lax.dynamic_slice_in_dim(q, start, block, 0), k
        )
        return jnp.einsum("hts,shd->thd", scores * decay, v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(seq, heads * dim)
    return _rms(out, layer["out_norm"], sizes.eps)


def kept_blocks(sizes: Sizes, q, k, start, select: bool = True):
    """The blocks the queries ``q [t, heads, d]`` at positions ``start +
    arange(t)`` keep over the keys ``k [T, kv_heads, d]``: ``[kv_heads, t,
    blocks]`` bool."""
    seq, kv_heads, dim = k.shape
    count = q.shape[0]
    blocks = -(-seq // sizes.block)
    context = start + jnp.arange(count) + 1                          # [t]
    block_at = jnp.arange(blocks)
    begins = block_at[None, :] * sizes.block < context[:, None]      # [t, b]
    if not select:
        return jnp.broadcast_to(begins[None], (kv_heads, count, blocks))
    windows = (seq - sizes.kernel) // sizes.stride + 1
    starts = jnp.arange(windows) * sizes.stride
    compressed = jax.vmap(
        lambda at: jax.lax.dynamic_slice_in_dim(k, at, sizes.kernel, 0).mean(axis=0)
    )(starts)                                                        # [w, kv, d]
    whole = starts[None, :] + sizes.kernel <= context[:, None]       # [t, w]
    scores = jnp.einsum(
        "tkgd,wkd->kgtw", q.reshape(count, kv_heads, -1, dim), compressed
    ) / math.sqrt(dim)
    scores = jnp.where(whole[None, None], scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    top = jnp.where(jnp.isfinite(top), top, 0.0)  # a query no window precedes
    weights = jnp.where(whole[None, None], jnp.exp(scores - top), 0.0)
    total = jnp.sum(weights, axis=-1, keepdims=True)
    mass = (weights / jnp.where(total == 0.0, 1.0, total)).sum(axis=1)  # [kv,t,w]
    overlap = (
        (starts[None, :] < (block_at[:, None] + 1) * sizes.block)
        & (starts[None, :] + sizes.kernel > block_at[:, None] * sizes.block)
    )                                                                # [b, w]
    by_block = jnp.max(
        jnp.where(overlap[None, None], mass[:, :, None, :], 0.0), axis=-1
    )                                                                # [kv, t, b]
    first_local = jnp.maximum(context - sizes.window, 0) // sizes.block
    always = (block_at[None, :] < sizes.init) | (block_at[None, :] >= first_local[:, None])
    far = begins & ~always
    ranked = jnp.where(far[None], by_block, -1.0)
    best, which = jax.lax.top_k(ranked, min(sizes.topk, blocks))
    chosen = jnp.zeros(ranked.shape, jnp.int32).at[
        jnp.arange(kv_heads)[:, None, None], jnp.arange(count)[None, :, None], which
    ].max((best >= 0.0).astype(jnp.int32)) > 0
    kept = (always[None] | chosen) & begins[None]
    dense = (context <= sizes.dense_len)[None, :, None]
    return jnp.where(dense, begins[None], kept)


def _sparse(sizes: Sizes, normed, layer, lower, block: int):
    """The block-sparse GQA mixer on normed x [T, hidden] -> [T, heads *
    d], a block of queries at a time."""
    seq, heads, kv_heads, dim = normed.shape[0], sizes.heads, sizes.kv_heads, sizes.head_dim
    q = (normed @ _dense(layer["wq"], lower)).reshape(seq, heads, dim)
    k = (normed @ _dense(layer["wk"], lower)).reshape(seq, kv_heads, dim)
    v = (normed @ _dense(layer["wv"], lower)).reshape(seq, kv_heads, dim)
    q = _rms(q, layer["q_norm"], sizes.eps)
    k = _rms(k, layer["k_norm"], sizes.eps)
    keys_at = jnp.arange(seq)

    def rows(start):
        q_rows = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        kept = kept_blocks(sizes, q_rows, k, start, select=lower != "no-selection")
        per_key = jnp.repeat(kept, sizes.block, axis=-1)[..., :seq]  # [kv, t, s]
        causal = keys_at[None, :] <= (start + jnp.arange(block))[:, None]
        mask = (per_key & causal[None])[:, None]                     # [kv,1,t,s]
        scores = jnp.einsum(
            "tkgd,skd->kgts", q_rows.reshape(block, kv_heads, -1, dim), k
        ) / math.sqrt(dim)
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block))
    return out.reshape(seq, heads * dim)


@partial(jax.jit, static_argnames=("sizes", "kind", "lower", "block"))
def _layer(x, layer, sizes, kind, lower, block):
    r = sizes.scale_depth / math.sqrt(sizes.layers)
    normed = _rms(x, layer["attn_norm"], sizes.eps)
    mixer = _lightning if kind == "lightning" else _sparse
    out = mixer(sizes, normed, layer, lower, block)
    out = out * jax.nn.sigmoid(normed @ _dense(layer["wg"], lower))
    x = x + r * (out @ _dense(layer["wo"], lower))
    normed = _rms(x, layer["mlp_norm"], sizes.eps)
    gate, up, down = (_dense(layer[n], lower) for n in ("w_gate", "w_up", "w_down"))
    # rows at a time (each row is its own): a long row's activations at the
    # SwiGLU's width would not fit beside the weights
    rows = 4 * block if normed.shape[0] % (4 * block) == 0 else normed.shape[0]
    out = jax.lax.map(
        lambda part: (jax.nn.silu(part @ gate) * (part @ up)) @ down,
        normed.reshape(-1, rows, normed.shape[1]),
    )
    return x + r * out.reshape(x.shape)


@partial(jax.jit, static_argnames=("sizes", "lower"))
def _head(x, scale, head, sizes, lower):
    normed = _rms(x, scale, sizes.eps) / (sizes.hidden / sizes.base)
    return normed @ _dense(head, lower)


def logits_at(
    sizes: Sizes,
    weights: Dict[str, Any],
    rows: Sequence[Sequence[int]],
    spans: Sequence[Tuple[int, int]],
    pad_to: int,
    lower: Optional[str] = None,
) -> List[np.ndarray]:
    """Full forward pass over each row of token ids, returning the
    float32 logits at positions ``[start, stop)`` of each row. A row is
    padded on the right (which nothing causal looks at) to its own length
    rounded up to a whole number of query blocks, at most ``pad_to``: the
    mixers run a block of queries at a time."""
    out = []
    with jax.default_matmul_precision("highest"):
        for row, (start, stop) in zip(rows, spans):
            block = 256 if len(row) > 1024 else 32
            padded = min(-(-len(row) // (4 * block)) * 4 * block, max(pad_to, len(row)))
            block = next(b for b in (block, 16, 8, 4, 2, 1) if padded % b == 0)
            ids = np.zeros((padded,), dtype=np.int32)
            ids[: len(row)] = np.asarray(row, dtype=np.int32)
            x = weights["embedding"][jnp.asarray(ids)].astype(jnp.float32) * sizes.scale_emb
            for kind, layer in zip(sizes.mixers, weights["layers"]):
                x = _layer(x, layer, sizes, kind, lower, block)
            logits = _head(
                x[start:stop], weights["final_norm"], weights["lm_head"], sizes, lower
            )
            out.append(np.asarray(logits))
    return out
