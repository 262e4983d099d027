"""Plain reference for the LFM2-MoE family (``model_type`` ``lfm2_moe``):
gated short-convolution layers beside GQA layers whose q and k heads are
normed, in the order ``layer_types`` gives; a dense SwiGLU behind the
first ``num_dense_layers`` mixers and a sigmoid router with a selection
bias over ``num_experts`` experts behind the others. Weights from the
seed, one full forward pass in float32 under ``jax.default_matmul_
precision("highest")``: no cache, no state carried, no kernels; the conv a
plain loop over its taps, every expert applied by a plain loop and
weighted by the router (nought where the token did not choose it).

Imports nothing of the program and nothing of another family. The sizes
come from the configuration's file, the published ``config.json`` keys
(https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json).

**The model** (``h`` hidden, RMS is ``x / sqrt(mean(x^2) + norm_eps) * w``
with a plain ``w``, no bias anywhere): ``x = E[token]``; every layer ``x +=
Mixer(RMS_op(x))``, then ``x += FF(RMS_ffn(x))``; ``logits = E . RMS(x)``
(*assumed*: the head is tied to the embedding, the family's convention;
the catalogued keys carry no flag).

- ``conv``: ``[B, C, X] = u W_in`` (three chunks of ``h``, in that order);
  ``g = B * X``; ``c_t = sum_j f[j] * g_{t - (L - 1) + j}``, ``j = 0 .. L -
  1``, ``L = conv_L_cache`` (a causal depthwise filter, ``f[L - 1]`` on the
  current token, ``g`` nought before the sequence's first token); ``y = C *
  c``; out ``= y W_out``.
- ``full_attention``: ``q, k, v = u W_q, u W_k, u W_v`` (``num_attention_
  heads`` and ``num_key_value_heads`` heads of ``hidden / heads``); RMS over
  the head dim of every q head and every k head with learned scales ``[d]``
  (one for q, one for k) BEFORE the rotation; q and k rotated over the
  whole head dim at their positions (``rope_theta``, default type); causal
  softmax GQA, scale ``1 / sqrt(d)``; out ``= o W_o``.
- feed-forward: layers below ``num_dense_layers``: ``(silu(x W_1) * x W_3)
  W_2`` at ``intermediate_size``. The others: ``s = sigmoid(x W_r)`` over
  all experts in float32; the token's experts are the ``num_experts_per_
  tok`` largest of ``s + b`` (``use_expert_bias``: ``b`` a float32 vector,
  no gradient-trained weight); their weights are ``s_e`` WITHOUT ``b``,
  divided (``norm_topk_prob``) by ``sum of the chosen s_e + 1e-6``, times
  ``routed_scaling_factor``; ``y = sum_e w_e (silu(x W_1e) * x W_3e) W_2e``
  at ``moe_intermediate_size``; no shared expert. *Assumed*: the ``1e-6``
  (the family's published code, from memory); router, sigmoid and top-k in
  float32 (the published code runs them in the model's dtype).

**Departure from the published code**: this reference, like the program,
rotates halves (``x[i], x[i + d/2]``), which is also what the published
family does for the default rotary type; a checkpoint stored for
interleaved pairs would differ by a fixed permutation of W_q's and W_k's
columns within a head (ROADMAP).

**Weights** (``weights`` in the file): ``bf16-normal`` (``f32-normal``, for
a CPU self-test): float32 normal times 1/sqrt(fan-in), rounded to bf16
(kept float32); ``W_out``, ``W_o`` and every ``W_2`` further divided by
sqrt(2 * layers); the embedding at 1/sqrt(hidden) (the head is its
transpose: a larger row would make a token's own id its next token);
every norm scale uniform in [0.5, 1.5), away from 1, so that a dropped RMS
cannot stay correct, and the q and k norms' in [1.5, 2.5), so that the
scores spread by about 4 and attention is peaked; the filter float32
normal at 1/sqrt(L) EVERY tap, so that a filter applied a tap off
computes another layer; the bias float32 normal at ``BIAS_STD``, the size
of the gaps between neighbouring scores near the cut, so that it changes
the chosen set for a sizeable share of tokens and weights taken from ``s +
b``, or a bias left out, compute another mixture. Keys: ``split(PRNGKey(
seed), 3)`` gives embedding, final norm and the layers' root; layer ``l``
draws from ``split(fold_in(root, l), 12)`` in the order op_norm, in_proj |
wq, filter | wk, out_proj | wv, wo, q_norm, k_norm, ffn_norm, gate |
router, up | bias, down, the experts' root; expert ``e`` draws gate, up,
down from ``split(fold_in(the experts' root, e), 3)``.

``lower`` re-states every matmul weight, the experts' among them, in the
nearest precision below the configuration's (int8 or fp8 under bf16): the
control the comparison has to fail. Router, bias, filter and norms stay.

**The work counts** count what a token meets: its mixer's projections (a
conv layer's 4 h^2, an attention layer's q, k, v, o), the router at its
full width, its own ``num_experts_per_tok`` experts (every expert is held:
no share is cut), attention over its context in the attention layers
ALONE, the head. ``kernel_work`` answers for ``flash_decode`` (the K and V
rows of the attention layers that the decode queries saw) and for
``moe_grouped_matmul`` **in the decode programs**: the routed rows' flops;
the bytes are, for every step and expert layer, once, the weights of the
experts a step of ``max-slots`` rows is expected to touch under even
routing (``E (1 - (1 - k/E)^slots)``: 63 of 64 at 64 slots), the steps
taken as ``decode_queries / slots``. ``served`` carries no step count: a
closed loop holds the slots full, and where they are not the true steps
are more, so the steps err low and the share reads low. The experts
touched are EXPECTED, not counted (the program's counters come back a
chunk, summed over its steps and layers, not a step): a router more skewed
than even touches fewer, the true bytes are then fewer and the share reads
HIGHER than the kernel earns; ``expert_load_peak.gen`` (busiest expert
over the mean) says how far from even a run's routing was.
``measure.kernel_roofline`` hands a family one name and
one count, so this family's count for that name is decode's and its metric
reads ``within='decode'``; the prefills' calls of the kernel get no
roofline.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"conv": "conv", "full_attention": "attention"}
LAYER_KEYS = (
    "op_norm", "in_proj|wq", "filter|wk", "out_proj|wv", "wo", "q_norm",
    "k_norm", "ffn_norm", "gate|router", "up|bias", "down", "experts",
)
BIAS_STD = 0.03


class Sizes:
    """The published sizes, read from the configuration's file."""

    def __init__(self, config: Dict[str, Any]) -> None:
        self.vocab = int(config["vocab_size"])
        self.hidden = int(config["hidden_size"])
        self.dense_inter = int(config["intermediate_size"])
        self.expert_inter = int(config["moe_intermediate_size"])
        self.layers = int(config["num_hidden_layers"])
        self.mixers = tuple(KINDS[name] for name in config["layer_types"])
        self.dense_layers = int(config["num_dense_layers"])
        self.heads = int(config["num_attention_heads"])
        self.kv_heads = int(config["num_key_value_heads"])
        self.head_dim = self.hidden // self.heads
        self.taps = int(config["conv_L_cache"])
        self.experts = int(config["num_experts"])
        self.per_token = int(config["num_experts_per_tok"])
        self.renormalise = bool(config["norm_topk_prob"])
        self.factor = float(config["routed_scaling_factor"])
        self.theta = float(config["rope_parameters"]["rope_theta"])
        self.eps = float(config["norm_eps"])
        self.recipe = str(config["weights"])
        # the slots a decode step holds: what ``moe_grouped_matmul``'s
        # bytes are counted over
        self.slots = int(config["globals"]["max-slots"])
        if (
            len(self.mixers) != self.layers or config["conv_bias"]
            or not config["use_expert_bias"]
            or config["rope_parameters"]["rope_type"] != "default"
            or int(config["experts_held_first"]) != 0
            or int(config["experts_held"]) != self.experts
            or not 0 < self.dense_layers < self.layers
        ):
            raise ValueError("an lfm2_moe configuration this reference does not compute")

    def _key(self):
        return tuple(sorted(vars(self).items()))

    def __hash__(self) -> int:  # a static argument of the jitted passes
        return hash(self._key())

    def __eq__(self, other) -> bool:
        return isinstance(other, Sizes) and self._key() == other._key()


def size_check(engine_config) -> Dict[str, Any]:
    """Key of the configuration's file -> what the program's config holds
    for it: every size the reference reads, the layers' kinds, the experts
    held here (all of them), the routing rule's three flags."""
    experts, conv = engine_config.experts, engine_config.short_conv
    names = {kind: name for name, kind in KINDS.items()}
    return {
        "vocab_size": engine_config.vocab_size,
        "hidden_size": engine_config.hidden_size,
        "intermediate_size": engine_config.intermediate_size,
        "num_hidden_layers": engine_config.num_layers,
        "layer_types": [names[kind] for kind in engine_config.mixers],
        "num_attention_heads": engine_config.num_heads,
        "num_key_value_heads": engine_config.num_kv_heads,
        "conv_L_cache": conv.taps,
        "conv_bias": False,
        "moe_intermediate_size": experts.intermediate_size,
        "num_experts": experts.routed,
        "experts_held_first": experts.held_first,
        "experts_held": experts.held,
        "num_experts_per_tok": experts.per_token,
        "num_dense_layers": experts.leading_dense,
        "norm_topk_prob": experts.renormalise,
        "routed_scaling_factor": experts.scaling_factor,
        "use_expert_bias": experts.routing == "sigmoid_bias",
        "norm_eps": engine_config.norm_eps,
        "rope_parameters": {
            "rope_theta": engine_config.rope_theta,
            "rope_type": "default" if engine_config.rope_scaling is None else "scaled",
        },
        "tie_word_embeddings": engine_config.tie_embeddings,
    }


# --------------------------------------------------------------------- #
# the work counts
# --------------------------------------------------------------------- #
def attention_layers(sizes: Sizes) -> int:
    return sum(1 for mixer in sizes.mixers if mixer == "attention")


def mixer_params(sizes: Sizes, mixer: str) -> int:
    h, d = sizes.hidden, sizes.head_dim
    if mixer == "conv":
        return h * 3 * h + h * h
    return h * d * (2 * sizes.heads + 2 * sizes.kv_heads)


def expert_params(sizes: Sizes) -> int:
    return 3 * sizes.hidden * sizes.expert_inter


def body_matmul_params(sizes: Sizes) -> int:
    """Matmul weights one token meets in all layers, the head apart: its
    mixers, the dense feed-forwards, the router and its OWN experts."""
    total = 0
    for layer, mixer in enumerate(sizes.mixers):
        total += mixer_params(sizes, mixer)
        if layer < sizes.dense_layers:
            total += 3 * sizes.hidden * sizes.dense_inter
        else:
            total += sizes.hidden * sizes.experts + sizes.per_token * expert_params(sizes)
    return total


def attention_flops(sizes: Sizes, context: int) -> int:
    """QK^T and PV of one query token over ``context`` keys, all heads, the
    attention layers alone."""
    return 4 * sizes.heads * sizes.head_dim * context * attention_layers(sizes)


def prompt_flops(sizes: Sizes, prompt_tokens: int) -> int:
    body = 2 * body_matmul_params(sizes) * prompt_tokens
    attn = attention_flops(sizes, 1) * prompt_tokens * (prompt_tokens + 1) // 2
    return int(body + attn + 2 * sizes.hidden * sizes.vocab)


def output_token_flops(sizes: Sizes, context: int) -> int:
    body = 2 * (body_matmul_params(sizes) + sizes.hidden * sizes.vocab)
    return int(body + attention_flops(sizes, context))


def experts_touched(sizes: Sizes) -> float:
    """Experts a decode step of ``slots`` rows is expected to touch, a
    layer, under even routing."""
    missed = (1.0 - sizes.per_token / sizes.experts) ** sizes.slots
    return sizes.experts * (1.0 - missed)


def kernel_work(sizes: Sizes, kernel: str, served: Dict[str, Any]):
    """(flops, bytes) an ideal kernel named ``kernel`` needs for what the
    traced window served; None for a name this family does not count."""
    if kernel == "flash_decode" and served["decode_queries"]:
        keys, queries, layers = (
            served["decode_keys"], served["decode_queries"], attention_layers(sizes)
        )
        flops = 4 * sizes.heads * sizes.head_dim * keys * layers
        rows = 2 * sizes.kv_heads * sizes.head_dim * 2 * keys
        q_and_out = 2 * queries * sizes.heads * sizes.head_dim * 2
        return flops, (rows + q_and_out) * layers
    if kernel == "moe_grouped_matmul" and served["decode_queries"]:
        # the DECODE programs' calls (the docstring says why): the routed
        # rows through gate, up and down; the touched experts' weights
        # once a step a layer, the rows in and out
        expert_layers = sizes.layers - sizes.dense_layers
        rows = served["decode_queries"] * sizes.per_token
        steps = served["decode_queries"] / sizes.slots
        flops = 2 * expert_params(sizes) * rows * expert_layers
        moved = (
            steps * experts_touched(sizes) * expert_params(sizes)
            + rows * 2 * sizes.hidden
        ) * 2 * expert_layers
        return int(flops), int(moved)
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


def _norm_scale(key, width: int, sharp: bool = False):
    low = 1.5 if sharp else 0.5
    return jax.random.uniform(key, (width,), jnp.float32, low, low + 1.0)


def make_layer(sizes: Sizes, root, layer: int) -> Dict[str, Any]:
    """Layer ``layer``'s weights. Matmul leaves are ``(values, None)``;
    the filter is ``[taps, hidden]`` (tap ``j`` is row ``j``)."""
    dtype = _dtype(sizes)
    keys = dict(zip(LAYER_KEYS, jax.random.split(jax.random.fold_in(root, layer), 12)))
    h, d = sizes.hidden, sizes.head_dim
    down = 1.0 / math.sqrt(2 * sizes.layers)

    def matmul(slot, shape, out=False, draw=_normal, key=None):
        scale = shape[0] ** -0.5 * (down if out else 1.0)
        return (draw(keys[slot] if key is None else key, shape, scale, dtype), None)

    made: Dict[str, Any] = {
        "op_norm": _norm_scale(keys["op_norm"], h),
        "ffn_norm": _norm_scale(keys["ffn_norm"], h),
    }
    if sizes.mixers[layer] == "conv":
        made["in_proj"] = matmul("in_proj|wq", (h, 3 * h))
        made["filter"] = _normal(
            keys["filter|wk"], (sizes.taps, h), sizes.taps ** -0.5, jnp.float32
        )
        made["out_proj"] = matmul("out_proj|wv", (h, h), out=True)
    else:
        made["wq"] = matmul("in_proj|wq", (h, sizes.heads * d))
        made["wk"] = matmul("filter|wk", (h, sizes.kv_heads * d))
        made["wv"] = matmul("out_proj|wv", (h, sizes.kv_heads * d))
        made["wo"] = matmul("wo", (sizes.heads * d, h), out=True)
        made["q_norm"] = _norm_scale(keys["q_norm"], d, sharp=True)
        made["k_norm"] = _norm_scale(keys["k_norm"], d, sharp=True)
    if layer < sizes.dense_layers:
        f = sizes.dense_inter
        made["gate"] = matmul("gate|router", (h, f))
        made["up"] = matmul("up|bias", (h, f))
        made["down"] = matmul("down", (f, h), out=True)
        return made
    made["router"] = _normal(keys["gate|router"], (h, sizes.experts), h ** -0.5, dtype)
    made["bias"] = _normal(keys["up|bias"], (sizes.experts,), BIAS_STD, jnp.float32)
    each = jnp.stack([
        jax.random.split(jax.random.fold_in(keys["experts"], expert), 3)
        for expert in range(sizes.experts)
    ])  # [experts, 3, key]
    f = sizes.expert_inter
    made["expert_gate"] = matmul("", (h, f), draw=_normal_each, key=each[:, 0])
    made["expert_up"] = matmul("", (h, f), draw=_normal_each, key=each[:, 1])
    made["expert_down"] = matmul("", (f, h), out=True, draw=_normal_each, key=each[:, 2])
    return made


def make_weights(sizes: Sizes, seed: int) -> Dict[str, Any]:
    """The configuration's weights from the seed, on the default device,
    held as stored (bf16 for ``bf16-normal``)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = sizes.hidden
    return {
        "embedding": (_normal(keys[0], (sizes.vocab, h), h ** -0.5, _dtype(sizes)), None),
        "final_norm": _norm_scale(keys[1], h),
        "layers": [make_layer(sizes, keys[2], layer) for layer in range(sizes.layers)],
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


def _rotate(x, theta: float):
    """x [T, heads, dim]; halves are rotated, position = row."""
    seq, _, dim = x.shape
    inv_freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq.astype(np.float32)[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(sizes: Sizes, normed, layer, lower):
    """The gated short convolution on normed [T, hidden]."""
    seq = normed.shape[0]
    b, c, x = jnp.split(normed @ _dense(layer["in_proj"], lower), 3, axis=-1)
    g = b * x
    # g_{t - (L - 1) + j}: g moved down by L - 1 - j rows, noughts above
    before = jnp.concatenate([jnp.zeros((sizes.taps - 1, g.shape[1]), g.dtype), g])
    mixed = jnp.zeros_like(g)
    for j in range(sizes.taps):
        mixed = mixed + layer["filter"][j] * before[j:j + seq]
    return (c * mixed) @ _dense(layer["out_proj"], lower)


def attention(sizes: Sizes, normed, layer, lower, block: int):
    """GQA with normed q and k heads on normed [T, hidden]."""
    seq = normed.shape[0]
    heads, kv_heads, d = sizes.heads, sizes.kv_heads, sizes.head_dim
    q = (normed @ _dense(layer["wq"], lower)).reshape(seq, heads, d)
    k = (normed @ _dense(layer["wk"], lower)).reshape(seq, kv_heads, d)
    v = (normed @ _dense(layer["wv"], lower)).reshape(seq, kv_heads, d)
    q = _rotate(_rms(q, layer["q_norm"], sizes.eps), sizes.theta)
    k = _rotate(_rms(k, layer["k_norm"], sizes.eps), sizes.theta)
    group = heads // kv_heads
    keys_at = jnp.arange(seq)

    def rows(start):
        """Queries [start, start + block) against every key."""
        part = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        part = part.reshape(block, kv_heads, group, d)
        scores = jnp.einsum("tkgd,skd->kgts", part, k) * d ** -0.5
        causal = keys_at[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        out = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(scores, -1), v)
        return out.reshape(block, heads * d)

    out = jax.lax.map(rows, jnp.arange(0, seq, block))
    return out.reshape(seq, heads * d) @ _dense(layer["wo"], lower)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(sizes: Sizes, normed, router, bias) -> jnp.ndarray:
    """[T, experts] float32: the token's weight at each of its experts
    (chosen by ``s + b``, weighted by ``s``), 0 elsewhere."""
    scores = jax.nn.sigmoid(normed @ router.astype(jnp.float32))
    _, chosen = jax.lax.top_k(scores + bias, sizes.per_token)
    at = jnp.arange(scores.shape[0])[:, None]
    weights = scores[at, chosen]
    if sizes.renormalise:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    return jnp.zeros_like(scores).at[at, chosen].add(weights * sizes.factor)


def feed_forward(sizes: Sizes, normed, layer, lower):
    """The feed-forward's output on normed [T, hidden]."""
    if "router" not in layer:
        return _swiglu(normed, *(_dense(layer[n], lower) for n in ("gate", "up", "down")))
    weights = route(sizes, normed, layer["router"], layer["bias"])

    def one(mixed, leaves):
        """One expert at a time, so that one is float32 at a time."""
        weight, gate, up, down = leaves
        out = _swiglu(normed, *(_dense((w, None), lower) for w in (gate, up, down)))
        return mixed + weight[:, None] * out, None

    mixed, _ = jax.lax.scan(one, jnp.zeros_like(normed), (
        weights.T, layer["expert_gate"][0], layer["expert_up"][0], layer["expert_down"][0],
    ))
    return mixed


@partial(jax.jit, static_argnames=("sizes", "lower", "block"))
def _layer(x, layer, sizes, lower, block):
    normed = _rms(x, layer["op_norm"], sizes.eps)
    if "in_proj" in layer:
        x = x + short_conv(sizes, normed, layer, lower)
    else:
        x = x + attention(sizes, normed, layer, lower, block)
    return x + feed_forward(sizes, _rms(x, layer["ffn_norm"], sizes.eps), layer, lower)


@partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, scale, embedding, eps, lower):
    return _rms(x, scale, eps) @ _dense(embedding, lower).T


def logits_at(
    sizes: Sizes,
    weights: Dict[str, Any],
    rows: Sequence[Sequence[int]],
    spans: Sequence[Tuple[int, int]],
    pad_to: int,
    lower: Optional[str] = None,
) -> List[np.ndarray]:
    """Full forward pass over each row of token ids, returning the float32
    logits at positions ``[start, stop)`` of each row. A row is padded on
    the right, which a causal filter, causal attention and a token's own
    experts never look at: so only as far as the longest row's whole
    block, not to ``pad_to`` (a sample of 2.5k-token rows in a 4,096
    context computes 2,560 positions a row). Attention runs in blocks of
    queries."""
    block = next(b for b in (256, 128, 64, 32, 16, 8, 4, 2, 1) if pad_to % b == 0)
    longest = max(len(row) for row in rows)
    width = min(pad_to, -(-longest // block) * block)
    embedding = weights["embedding"][0]
    states = []
    for row in rows:
        ids = np.zeros((width,), dtype=np.int32)
        ids[: len(row)] = np.asarray(row, dtype=np.int32)
        states.append(embedding[jnp.asarray(ids)].astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        for layer in weights["layers"]:
            states = [_layer(x, layer, sizes, lower, block) for x in states]
        span = max(stop - start for start, stop in spans)
        out = []
        for x, (start, stop) in zip(states, spans):
            index = np.minimum(np.arange(start, start + span), width - 1)
            logits = _head(
                x[jnp.asarray(index)], weights["final_norm"], weights["embedding"],
                sizes.eps, lower,
            )
            out.append(np.asarray(logits)[: stop - start])
    return out
