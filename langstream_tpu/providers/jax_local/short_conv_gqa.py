"""The short-convolution decoder family on the dense layout: layers whose
mixer is a gated short convolution with a per-slot conv state, beside
layers whose mixer is GQA with RMSNorm on every q and k head, in an order
the config gives layer by layer; behind the mixer a dense SwiGLU in the
leading layers and routed experts in the others.

A config with ``mixers`` (one kind a layer: ``"conv"`` or ``"attention"``)
and ``short_conv`` (:class:`model.ShortConv`) set; with ``experts``
(:class:`model.RoutedExperts`) the layers from ``leading_dense`` on route
(``ops/moe.py::moe_mlp_held``, any routing rule), without it every layer
is dense. The mixer kind and the feed-forward kind of a layer are
independent: :func:`runs_of` cuts the layers where EITHER changes.

- **conv**: ``[B, C, X] = W_in u`` (three chunks of ``h``); ``g = B * X``;
  ``c_t = sum_j f[j] * g_{t - (taps - 1) + j}`` (a causal depthwise filter
  of ``taps`` taps a channel, the last on the current token, ``g`` zero
  before a sequence's first token); ``y = C * c``; the block's ``wo`` is
  ``W_out``. Its state is ``conv: [conv layers, S, taps - 1, h]``, the
  last ``taps - 1`` columns of ``g`` a slot, in the activations' dtype,
  NOT addressed by position: a window at offset 0 starts from zeros
  whatever the slot held, a window at a later offset from what the one
  before it left, and a right-padded window hands on ``g`` at its last
  VALID positions.
- **attention**: GQA as ``model.py`` computes it, with RMSNorm over the
  head dim of q and of k (learned scales ``[d]``) before the rotation:
  the attends are GQA's own (``model._offset_attend``, ``model.
  _decode_attend``, handed in), over ``k`` and ``v`` stacks that hold the
  ATTENTION layers only, packed into 128-lane rows where ``flash_decode``
  reads them (``model.flash_decode_pack``).

An attend is given the layer's index in the MODEL (so that the block's
``index - leading_dense`` finds the layer's experts) and looks up where
its own kind's state lies (:func:`state_index`).

The parameters are one stack a run (``run<n>.*``, leaves ``[the run's
layers, ...]``), as the hybrid family's are, and the routed experts of all
expert layers in one stack a matrix (``moe.w_gate``, ``moe.w_up``,
``moe.w_down``: ``[expert layers, held, ...]``), which the grouped matmul
reads where it lies. The head is tied to the embedding.

Random initialisation is the recipe of ``benchmark/reference/lfm2_moe.py``
(its docstring, "Weights"): norm scales away from 1, the q and k norms'
around 2, the filter's taps of like size, a non-zero selection bias.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from langstream_tpu.parallel.mesh import L
# the other families' draws: a normal leaf, an expert stack a layer at a
# time, a norm's scale away from 1 (``sharp``: the q and k norms', around
# 2, so that the scores spread by about 4 and a dropped norm shows)
from langstream_tpu.providers.jax_local.hybrid_sparse_linear import _norm_scale
from langstream_tpu.providers.jax_local.latent_moe import _normal, _normal_experts
from langstream_tpu.providers.jax_local.quant import qeinsum

KINDS = ("conv", "attention")
# a layer's feed-forward leaves by kind, in the order the block takes them
FEED_FORWARD = {
    "dense": ("w_gate", "w_up", "w_down"),
    "experts": ("router", "expert_bias"),
}
EXPERT_STACKS = ("w_gate", "w_up", "w_down")
# the slots a layer's twelve keys are drawn for, whatever its kinds
_LAYER_KEYS = (
    "op_norm", "in_proj|wq", "filter|wk", "out_proj|wv", "wo", "q_norm",
    "k_norm", "ffn_norm", "w_gate|router", "w_up|expert_bias", "w_down",
    "experts",
)
# the selection bias: of the size of the gaps between neighbouring scores
# near the cut, so that it changes a sizeable share of the chosen sets
BIAS_STD = 0.03


def layers_of(config, kind: str) -> List[int]:
    """Model indices of the layers whose mixer is ``kind``."""
    return [i for i, mixer in enumerate(config.mixers) if mixer == kind]


def feed_forward_of(config, layer: int) -> str:
    experts = config.experts
    routed = experts is not None and layer >= experts.leading_dense
    return "experts" if routed else "dense"


def runs_of(config) -> List[Tuple[str, str, int, int]]:
    """The maximal runs of one mixer kind AND one feed-forward kind, in
    model order: (mixer, feed-forward, the model index of the run's first
    layer, its count of layers)."""
    runs: List[list] = []
    for index, mixer in enumerate(config.mixers):
        kinds = [mixer, feed_forward_of(config, index)]
        if runs and runs[-1][:2] == kinds:
            runs[-1][3] += 1
        else:
            runs.append(kinds + [index, 1])
    return [tuple(run) for run in runs]


def state_index(config) -> jnp.ndarray:
    """``[num_layers]``: where a layer's state lies in its own kind's
    stack (the conv state's, or K's and V's)."""
    seen = dict.fromkeys(KINDS, 0)
    index = []
    for mixer in config.mixers:
        index.append(seen[mixer])
        seen[mixer] += 1
    return jnp.asarray(index, jnp.int32)


def _shapes(config, mixer: str, feed_forward: str) -> Dict[str, Tuple[int, ...]]:
    h = config.hidden_size
    heads, kv_heads, dim = config.num_heads, config.num_kv_heads, config.dims_per_head
    shapes = {"op_norm": (h,), "ffn_norm": (h,)}
    if mixer == "conv":
        shapes.update(
            in_proj=(h, 3 * h), filter=(config.short_conv.taps, h),
            out_proj=(h, h),
        )
    else:
        shapes.update(
            wq=(h, heads * dim), wk=(h, kv_heads * dim), wv=(h, kv_heads * dim),
            wo=(heads * dim, h), q_norm=(dim,), k_norm=(dim,),
        )
    if feed_forward == "dense":
        f = config.intermediate_size
        shapes.update(w_gate=(h, f), w_up=(h, f), w_down=(f, h))
    else:
        routed = config.experts.routed
        shapes.update(router=(h, routed), expert_bias=(routed,))
    return shapes


def _expert_shapes(config) -> Dict[str, Tuple[int, ...]]:
    h, f = config.hidden_size, config.experts.intermediate_size
    return {"w_gate": (h, f), "w_up": (h, f), "w_down": (f, h)}


# --------------------------------------------------------------------- #
# parameters and cache
# --------------------------------------------------------------------- #
def _layer_leaf(config, name: str, shape, keys: Dict[str, Any]):
    """One layer's leaf ``name`` by the recipe: matmuls normal at
    ``1 / sqrt(fan-in)`` in the config's dtype, the output projections
    (``out_proj``, ``wo``, ``w_down``) further over ``sqrt(2 * layers)``;
    the filter float32 normal at ``1 / sqrt(taps)`` every tap; the bias
    float32 normal at ``BIAS_STD``; norm scales by :func:`_norm_scale`."""
    key = next(keys[slot] for slot in _LAYER_KEYS if name in slot.split("|"))
    if name.endswith("_norm"):
        return _norm_scale(key, shape[0], sharp=name in ("q_norm", "k_norm"))
    if name == "filter":
        return _normal(key, shape, shape[0] ** -0.5, jnp.float32)
    if name == "expert_bias":
        return _normal(key, shape, BIAS_STD, jnp.float32)
    down = 1.0 / math.sqrt(2 * config.num_layers)
    scale = shape[0] ** -0.5 * (down if name in ("out_proj", "wo", "w_down") else 1.0)
    return _normal(key, shape, scale, config.dtype)


def init_params(config, seed: int = 0) -> Dict[str, jnp.ndarray]:
    """Random parameters by the recipe ``benchmark/reference/lfm2_moe.py``
    states: ``split(PRNGKey(seed), 3)`` gives embedding, final norm and the
    layers' root; layer ``l`` (its number in the model) draws from
    ``split(fold_in(root, l), 12)`` in ``_LAYER_KEYS``' order; expert ``e``
    (its number among ALL the router's outputs) draws gate, up, down from
    ``split(fold_in(the layer's experts key, e), 3)``."""
    dtype = config.dtype
    h, layers = config.hidden_size, config.num_layers
    top = jax.random.split(jax.random.PRNGKey(seed), 3)
    per_layer = [
        dict(zip(_LAYER_KEYS, jax.random.split(jax.random.fold_in(top[2], layer), 12)))
        for layer in range(layers)
    ]
    params: Dict[str, jnp.ndarray] = {}
    experts = config.experts
    if experts is not None:
        # the expert stacks first, while the device is empty: their
        # float32 transients are the largest
        expert_keys = jnp.stack([
            jnp.stack([
                jax.random.split(jax.random.fold_in(keys["experts"], expert), 3)
                for expert in range(
                    experts.held_first, experts.held_first + experts.held
                )
            ])
            for keys in per_layer[experts.leading_dense:]
        ])  # [expert layers, held, 3, key]
        down = 1.0 / math.sqrt(2 * layers)
        for at, (name, shape) in enumerate(_expert_shapes(config).items()):
            scale = shape[0] ** -0.5 * (down if name == "w_down" else 1.0)
            params[f"moe.{name}"] = _normal_experts(
                expert_keys[:, :, at], shape, scale, dtype
            )
    for number, (mixer, feed_forward, start, count) in enumerate(runs_of(config)):
        for name, shape in _shapes(config, mixer, feed_forward).items():
            params[f"run{number}.{name}"] = jnp.stack([
                _layer_leaf(config, name, shape, per_layer[layer])
                for layer in range(start, start + count)
            ])
    params["embedding"] = _normal(top[0], (config.vocab_size, h), h ** -0.5, dtype)
    params["final_norm"] = _norm_scale(top[1], h)
    return params


def _leaf_names(config) -> List[str]:
    names = [
        f"run{number}.{name}"
        for number, (mixer, feed_forward, _, _) in enumerate(runs_of(config))
        for name in _shapes(config, mixer, feed_forward)
    ]
    if config.experts is not None:
        names += [f"moe.{name}" for name in EXPERT_STACKS]
    return names + ["embedding", "final_norm"]


def logical_axes(config) -> Dict[str, Any]:
    """Every leaf replicated: the family runs on one chip (the engine
    refuses a mesh)."""
    axes: Dict[str, Any] = {
        "embedding": L("vocab", "embed"), "final_norm": L(None),
    }
    for number, (mixer, feed_forward, _, _) in enumerate(runs_of(config)):
        for name, shape in _shapes(config, mixer, feed_forward).items():
            axes[f"run{number}.{name}"] = L("layers", *([None] * len(shape)))
    if config.experts is not None:
        for name in EXPERT_STACKS:
            axes[f"moe.{name}"] = L("layers", "expert", None, None)
    return axes


def num_params(config) -> int:
    total = config.vocab_size * config.hidden_size + config.hidden_size
    for mixer, feed_forward, _, count in runs_of(config):
        total += count * sum(
            math.prod(shape)
            for shape in _shapes(config, mixer, feed_forward).values()
        )
        if feed_forward == "experts":
            total += count * config.experts.held * sum(
                math.prod(shape) for shape in _expert_shapes(config).values()
            )
    return total


def init_state(config, batch: int) -> jnp.ndarray:
    """The conv state: the last ``taps - 1`` columns of ``g`` a slot a
    conv layer, zeros at a sequence's start."""
    return jnp.zeros(
        (
            len(layers_of(config, "conv")), batch, config.short_conv.taps - 1,
            config.hidden_size,
        ),
        config.dtype,
    )


def init_cache(config, batch: int, max_len: int) -> Dict[str, jnp.ndarray]:
    """The conv state beside K and V for the ATTENTION layers only, which
    lie as GQA's own do (packed rows where the decode kernel reads them
    packed)."""
    # model.py imports this file: its helper is fetched at the call
    from langstream_tpu.providers.jax_local.model import kv_leaves

    attention = len(layers_of(config, "attention"))
    return {
        "conv": init_state(config, batch),
        **kv_leaves(config, attention, batch, max_len),
    }


def cache_logical_axes() -> Dict[str, Any]:
    return {
        "conv": L("layers", "cache_batch", None, None),
        "k": L("layers", "cache_batch", "cache_sequence", None, None),
        "v": L("layers", "cache_batch", "cache_sequence", None, None),
    }


def validate_params(config, params: Dict[str, Any]) -> None:
    missing = [name for name in _leaf_names(config) if name not in params]
    if missing:
        raise ValueError(f"params missing {missing}, required by the model config")
    if config.experts is not None:
        held = params["moe.w_gate"].shape[1]
        if held != config.experts.held:
            raise ValueError(
                f"the expert stacks hold {held} experts, the config "
                f"{config.experts.held}"
            )


def layer_runs(config, params):
    """The layers as ``model._run_layers`` takes them: ``(mixer kind, the
    run's stacked layers, the MODEL index of its first layer, the routed
    experts' stacks or None)`` for every run. A layer is ``(op_norm, the
    mixer's weights, wo, None, ffn_norm, None, feed-forward weights)``;
    the attention's weights are GQA's ``(wq, wk, wv, no biases, q_norm,
    k_norm)``."""
    validate_params(config, params)
    stacks = (
        tuple(params[f"moe.{name}"] for name in EXPERT_STACKS)
        if config.experts is not None else None
    )

    def stack(number, mixer, feed_forward):
        leaf = lambda name: params[f"run{number}.{name}"]  # noqa: E731
        if mixer == "conv":
            weights, out = (leaf("in_proj"), leaf("filter")), leaf("out_proj")
        else:
            weights = (
                leaf("wq"), leaf("wk"), leaf("wv"), None, leaf("q_norm"),
                leaf("k_norm"),
            )
            out = leaf("wo")
        return (
            leaf("op_norm"), weights, out, None, leaf("ffn_norm"), None,
            tuple(leaf(name) for name in FEED_FORWARD[feed_forward]),
        )

    return [
        (
            mixer, stack(number, mixer, feed_forward), start,
            stacks if feed_forward == "experts" else None,
        )
        for number, (mixer, feed_forward, start, _) in enumerate(runs_of(config))
    ]


# --------------------------------------------------------------------- #
# the conv mixer, and GQA's attends over this family's state
# --------------------------------------------------------------------- #
def _gated(normed, weights):
    """The conv's input side on normed ``[..., h]``: (``g = B * X``, the
    output gate ``C``, the filter ``[taps, h]`` float32)."""
    in_proj, taps = weights
    b, c, x = jnp.split(qeinsum("...h,hd->...d", normed, in_proj), 3, axis=-1)
    return b * x, c, taps.astype(jnp.float32)


def _filtered(columns, taps, width: int):
    """``sum_j taps[j] * columns[..., j : j + width, :]`` in float32:
    ``columns [..., taps - 1 + width, h]`` holds the state, then ``g``."""
    count = taps.shape[0]
    return sum(
        taps[j] * jax.lax.slice_in_dim(
            columns, j, j + width, axis=columns.ndim - 2
        ).astype(jnp.float32)
        for j in range(count)
    )


def _over_kv(gqa, at):
    """GQA's attend over the ``k`` and ``v`` stacks of the carried
    ``(conv, k, v)``, its layer found in the attention layers' stack."""
    def attention(normed, weights, index, inputs, carried):
        conv, *kv = carried
        out, kv, _ = gqa(normed, weights, at[index], inputs, tuple(kv))
        return out, (conv, *kv), None

    return attention


def window_attends(config, lengths, offsets, slot_ids, gqa):
    """The attends of a window of tokens a row at ``offsets`` into slots
    ``slot_ids`` (a cold prefill is the window at offset 0; a chunked
    prefill is a sequence of them), on normed ``[B, T, h]`` with the
    carried ``(conv, k, v)``; ``gqa`` is GQA's attend for the same window
    (``model._offset_attend``). The conv starts from the slot's state
    (zeros at offset 0) and leaves ``g`` at the last ``taps - 1`` VALID
    positions, whatever padding follows."""
    at = state_index(config)
    keep = config.short_conv.taps - 1

    def conv(normed, weights, index, inputs, carried):
        state, *kv = carried
        g, gate, taps = _gated(normed, weights)
        # position 0 starts from zeros, whatever the slot held
        start = jnp.where(
            (offsets == 0)[:, None, None], 0, state[at[index], slot_ids]
        ).astype(g.dtype)
        columns = jnp.concatenate([start, g], axis=1)    # [B, keep + T, h]
        with jax.named_scope("attention"):
            out = gate * _filtered(columns, taps, g.shape[1]).astype(g.dtype)
        # g_t lies at column keep + t: the last valid ones are columns
        # lengths .. lengths + keep - 1 (the state's own where a row is
        # shorter than keep)
        last = lengths[:, None] + jnp.arange(keep)[None, :]
        moved = jnp.take_along_axis(columns, last[:, :, None], axis=1)
        with jax.named_scope("cache_write"):
            state = state.at[at[index], slot_ids].set(moved.astype(state.dtype))
        return out, (state, *kv), None

    return {"conv": conv, "attention": _over_kv(gqa, at)}


def decode_attends(config, write_mask, gqa):
    """The attends of one decode step for every slot, on normed ``[S,
    h]``: the conv state shifted by one column and written in place under
    ``write_mask`` (a slot that rides along keeps every bit); ``gqa`` is
    GQA's attend for the step (``model._decode_attend``)."""
    at = state_index(config)

    def conv(normed, weights, index, inputs, carried):
        state, *kv = carried
        g, gate, taps = _gated(normed, weights)
        held = state[at[index]]                          # [S, keep, h]
        columns = jnp.concatenate([held, g[:, None].astype(held.dtype)], axis=1)
        with jax.named_scope("attention"):
            out = gate * _filtered(columns, taps, 1)[:, 0].astype(g.dtype)
        moved = jnp.where(write_mask[:, None, None], columns[:, 1:], held)
        with jax.named_scope("cache_write"):
            state = state.at[at[index]].set(moved)
        return out, (state, *kv), None

    return {"conv": conv, "attention": _over_kv(gqa, at)}
