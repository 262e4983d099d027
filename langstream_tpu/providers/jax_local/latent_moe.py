"""The latent-attention, routed-experts decoder family on the dense layout.

A config with ``mla`` (:class:`model.LatentAttention`) and ``experts``
(:class:`model.RoutedExperts`) set: low-rank query and key/value
projections with their own RMSNorms, a rotary part shared by all heads, a
cache row of ``kv_lora_rank + qk_rope_head_dim`` values a token a layer;
``leading_dense`` layers with a plain SwiGLU, then layers whose
feed-forward is a group-limited router over ``routed`` experts of which
this chip holds ``[held_first, held_first + held)`` and computes only
where routed (``ops/moe.py::moe_mlp_held``), plus shared experts.

This file holds the family's attention kind, its parameters and its
cache; ``model.py`` holds the programs, the layer loop and the block body,
and takes from here the ``attend`` of each dense-layout program:

- :func:`prefill_attend`: cold, over expanded heads (keys ``nope + rope``
  wide, values ``v_head_dim`` wide) through the flash prefill kernel;
- :func:`offset_attend`: a suffix over cached latents, in the absorbed
  form (``ops/mla_attention.py::absorbed_attention``);
- :func:`decode_attend`: absorbed, the stacked cache carried through the
  layer loop and written in place.

The layer stack is not uniform, so the parameters are two stacks:
``dense.*`` leaves ``[leading_dense, ...]`` and ``moe.*`` leaves
``[num_layers - leading_dense, ...]`` (expert leaves ``[layers, held, h,
f]``, logical axis ``expert``). The cache is ONE stacked leaf over all
layers, ``latent: [L, S, T, row_width]`` (``kv_lora_rank +
qk_rope_head_dim`` values, then zeros up to whole lanes): the decode
kernel reads a row as key and value at once, so the row is written as
the reader wants it, where it lies.

Random initialisation follows the recipe of
``benchmark/reference/deepseek_v2.py`` (norm scales away from 1); the
rotary halves are rotated, not interleaved pairs, so a published
checkpoint needs its rotary columns permuted on loading (ROADMAP).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from langstream_tpu.ops.attention import prefill_attention
from langstream_tpu.ops.flash_attention import flash_prefill_attention, use_flash
from langstream_tpu.ops.mla_attention import (
    absorbed_attention,
    latent_query,
    mla_decode_attention,
    mla_decode_shapes_ok,
    use_mla_decode,
)
from langstream_tpu.ops.norms import rms_norm
from langstream_tpu.ops.rope import apply_rope, yarn_softmax_scale
from langstream_tpu.parallel.mesh import L

# ``W_qb`` lies as its two column groups, each [out, in]: the heads' nope
# columns and their rotary columns, so that no program slices a
# projection's output by head (XLA pushes such a slice into the weights and
# copies a layer's 75 MB of them every step). ``W_kvb`` likewise as ``W_UK``
# and ``W_UV``, heads first: the absorbed form contracts each alone, with
# the head as the batch dimension.
ATTENTION = (
    "attn_norm", "wq_a", "q_norm", "wq_nope", "wq_pe", "wkv_a", "kv_norm",
    "wk_b", "wv_b", "wo",
)
DENSE_MLP = ("mlp_norm", "w_gate", "w_up", "w_down")
EXPERT_MLP = ("mlp_norm", "router", "shared_gate", "shared_up", "shared_down")
# the routed experts' stacks [expert layers, held, ...] do not ride the
# layer scan: the grouped matmul reads a layer's experts where they lie
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def width(config) -> int:
    """Values a cached token holds a layer."""
    return config.mla.kv_lora_rank + config.mla.qk_rope_head_dim


def row_width(config) -> int:
    """A cache row as it lies: :func:`width` rounded up to whole lanes
    (576 -> 640), zeros past the values. A minor dimension that is not a
    multiple of 128 makes the TPU lay the stack out with the POSITIONS
    minor, and every program that takes it would transpose all of it on
    the way in and again on the way out."""
    return -(-width(config) // 128) * 128


def softmax_scale(config) -> float:
    mla = config.mla
    return (
        (mla.qk_nope_head_dim + mla.qk_rope_head_dim) ** -0.5
        * yarn_softmax_scale(config.rope_scaling)
    )


# --------------------------------------------------------------------- #
# parameters and cache
# --------------------------------------------------------------------- #
@partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


@partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _normal_experts(keys, shape, scale, dtype):
    """keys [layers, held, key] -> [layers, held, *shape], a layer at a
    time (one layer's float32 draws are the transient, not the stack's)."""
    def layer(layer_keys):
        return jax.vmap(
            lambda key: (
                jax.random.normal(key, shape, dtype=jnp.float32) * scale
            ).astype(dtype)
        )(layer_keys)

    return jax.lax.map(layer, keys)


# a token's own row as large in its state as what a layer adds to it
# (0.2-0.5 a value at any width): at 1/sqrt(hidden) every state is its
# context's average, a decode step's slots all route alike and the step's
# cost follows the seed (the reference's file has the readings)
EMBEDDING_STD = 0.22


def _norm_scale(key, size: int):
    return jax.random.uniform(key, (size,), jnp.float32, 0.5, 1.5)


def init_params(config, seed: int = 0) -> Dict[str, jnp.ndarray]:
    """Random parameters by the recipe ``benchmark/reference/
    deepseek_v2.py`` states (its docstring, "Weights"): per-layer keys,
    per-expert keys by the expert's number among all the router's
    outputs, norm scales uniform in [0.5, 1.5), the embedding at
    ``EMBEDDING_STD`` whatever the width."""
    mla, experts = config.mla, config.experts
    dtype = config.dtype
    h, heads, layers = config.hidden_size, config.num_heads, config.num_layers
    nope, rope, v_dim = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
    latent, q_rank = mla.kv_lora_rank, mla.q_lora_rank
    top = jax.random.split(jax.random.PRNGKey(seed), 4)
    down = 1.0 / math.sqrt(2 * layers)
    per_layer = [
        jax.random.split(jax.random.fold_in(top[3], layer), 14)
        for layer in range(layers)
    ]

    def attention(keys) -> Dict[str, jnp.ndarray]:
        wkv_b = _normal(
            keys[6], (latent, heads * (nope + v_dim)), latent ** -0.5, dtype
        ).reshape(latent, heads, nope + v_dim)
        wq_b = _normal(
            keys[3], (q_rank, heads * (nope + rope)), q_rank ** -0.5, dtype
        ).reshape(q_rank, heads, nope + rope)
        return {
            "attn_norm": _norm_scale(keys[0], h),
            "wq_a": _normal(keys[1], (h, q_rank), h ** -0.5, dtype),
            "q_norm": _norm_scale(keys[2], q_rank),
            "wq_nope": wq_b[..., :nope].reshape(q_rank, -1).T,
            "wq_pe": wq_b[..., nope:].reshape(q_rank, -1).T,
            "wkv_a": _normal(keys[4], (h, latent + rope), h ** -0.5, dtype),
            "kv_norm": _norm_scale(keys[5], latent),
            "wk_b": wkv_b[..., :nope].transpose(1, 0, 2),
            "wv_b": wkv_b[..., nope:].transpose(1, 0, 2),
            "wo": _normal(
                keys[7], (heads * v_dim, h), (heads * v_dim) ** -0.5 * down, dtype
            ),
            "mlp_norm": _norm_scale(keys[8], h),
        }

    def swiglu(gate, up, dn, inter, names):
        return {
            names[0]: _normal(gate, (h, inter), h ** -0.5, dtype),
            names[1]: _normal(up, (h, inter), h ** -0.5, dtype),
            names[2]: _normal(dn, (inter, h), inter ** -0.5 * down, dtype),
        }

    def stacked(group: str, rows) -> Dict[str, jnp.ndarray]:
        return {
            f"{group}.{name}": jnp.stack([row[name] for row in rows])
            for name in rows[0]
        }

    lead = experts.leading_dense
    params: Dict[str, jnp.ndarray] = {}
    # the expert stacks first, while the device is empty: their float32
    # transients are the largest
    inter = experts.intermediate_size
    expert_keys = jnp.stack([
        jnp.stack([
            jax.random.split(jax.random.fold_in(keys[13], expert), 3)
            for expert in range(
                experts.held_first, experts.held_first + experts.held
            )
        ])
        for keys in per_layer[lead:]
    ])  # [expert layers, held, 3, key]
    params["moe.w_gate"] = _normal_experts(
        expert_keys[:, :, 0], (h, inter), h ** -0.5, dtype
    )
    params["moe.w_up"] = _normal_experts(
        expert_keys[:, :, 1], (h, inter), h ** -0.5, dtype
    )
    params["moe.w_down"] = _normal_experts(
        expert_keys[:, :, 2], (inter, h), inter ** -0.5 * down, dtype
    )
    params.update(stacked("dense", [
        {**attention(keys), **swiglu(
            keys[9], keys[10], keys[11], config.intermediate_size, DENSE_MLP[1:]
        )}
        for keys in per_layer[:lead]
    ]))
    params.update(stacked("moe", [
        {
            **attention(keys),
            "router": _normal(keys[9], (h, experts.routed), h ** -0.5, dtype),
            **swiglu(
                keys[10], keys[11], keys[12], experts.shared * inter,
                ("shared_gate", "shared_up", "shared_down"),
            ),
        }
        for keys in per_layer[lead:]
    ]))
    params["embedding"] = _normal(
        top[0], (config.vocab_size, h), EMBEDDING_STD, dtype
    )
    params["lm_head"] = _normal(top[1], (h, config.vocab_size), h ** -0.5, dtype)
    params["final_norm"] = _norm_scale(top[2], h)
    return params


def logical_axes(config) -> Dict[str, Any]:
    attention = {
        "attn_norm": L("layers", None),
        "wq_a": L("layers", "embed", None),
        "q_norm": L("layers", None),
        "wq_nope": L("layers", "heads", None),
        "wq_pe": L("layers", "heads", None),
        "wkv_a": L("layers", "embed", None),
        "kv_norm": L("layers", None),
        "wk_b": L("layers", "heads", None, None),
        "wv_b": L("layers", "heads", None, None),
        "wo": L("layers", "heads", "embed"),
        "mlp_norm": L("layers", None),
    }
    axes: Dict[str, Any] = {
        "embedding": L("vocab", "embed"),
        "lm_head": L("embed", "vocab"),
        "final_norm": L(None),
    }
    for group in ("dense", "moe"):
        axes.update({f"{group}.{k}": v for k, v in attention.items()})
    axes.update({
        "dense.w_gate": L("layers", "embed", "mlp"),
        "dense.w_up": L("layers", "embed", "mlp"),
        "dense.w_down": L("layers", "mlp", "embed"),
        "moe.router": L("layers", "embed", None),
        "moe.shared_gate": L("layers", "embed", "mlp"),
        "moe.shared_up": L("layers", "embed", "mlp"),
        "moe.shared_down": L("layers", "mlp", "embed"),
        "moe.w_gate": L("layers", "expert", "embed", None),
        "moe.w_up": L("layers", "expert", "embed", None),
        "moe.w_down": L("layers", "expert", None, "embed"),
    })
    return axes


def num_params(config) -> int:
    mla, experts = config.mla, config.experts
    h, heads = config.hidden_size, config.num_heads
    attention = (
        h * mla.q_lora_rank
        + mla.q_lora_rank * heads * (mla.qk_nope_head_dim + mla.qk_rope_head_dim)
        + h * (mla.kv_lora_rank + mla.qk_rope_head_dim)
        + mla.kv_lora_rank * heads * (mla.qk_nope_head_dim + mla.v_head_dim)
        + heads * mla.v_head_dim * h
        + 2 * h + mla.q_lora_rank + mla.kv_lora_rank
    )
    expert = 3 * h * experts.intermediate_size
    dense_layer = attention + 3 * h * config.intermediate_size
    expert_layer = (
        attention + h * experts.routed + (experts.shared + experts.held) * expert
    )
    lead = experts.leading_dense
    return (
        lead * dense_layer + (config.num_layers - lead) * expert_layer
        + 2 * config.vocab_size * h + h
    )


def init_cache(config, batch: int, max_len: int) -> Dict[str, jnp.ndarray]:
    return {
        "latent": jnp.zeros(
            (config.num_layers, batch, max_len, row_width(config)), config.dtype
        )
    }


def cache_logical_axes() -> Dict[str, Any]:
    return {"latent": L("layers", "cache_batch", "cache_sequence", None)}


def validate_params(config, params: Dict[str, Any]) -> None:
    wanted = (
        [f"dense.{n}" for n in ATTENTION + DENSE_MLP]
        + [f"moe.{n}" for n in ATTENTION + EXPERT_MLP + EXPERT_STACKS]
        + ["embedding", "lm_head", "final_norm"]
    )
    missing = [name for name in wanted if name not in params]
    if missing:
        raise ValueError(f"params missing {missing}, required by the model config")
    held = params["moe.w_gate"].shape[1]
    if held != config.experts.held:
        raise ValueError(
            f"the expert stacks hold {held} experts, the config "
            f"{config.experts.held}"
        )


def layer_stacks(config, params):
    """The family's layers as ``model._run_layers`` takes them: (the
    leading dense layers, one tuple each; the expert layers' stacked tuple
    for the scan; the routed experts' stacks). A layer is ``(attn_norm,
    attention weights, wo, None, mlp_norm, None, feed-forward weights)``:
    the family has no post norms."""
    validate_params(config, params)

    def layers(group, mlp, at=None):
        def leaf(name):
            stack = params[f"{group}.{name}"]
            return stack if at is None else stack[at]

        return (
            leaf("attn_norm"), tuple(leaf(n) for n in ATTENTION[1:-1]),
            leaf("wo"), None, leaf(mlp[0]), None,
            tuple(leaf(n) for n in mlp[1:]),
        )

    lead = [
        layers("dense", DENSE_MLP, at=i)
        for i in range(config.experts.leading_dense)
    ]
    stacks = tuple(params[f"moe.{n}"] for n in EXPERT_STACKS)
    return lead, layers("moe", EXPERT_MLP), stacks


# --------------------------------------------------------------------- #
# the attention kind
# --------------------------------------------------------------------- #
def _norm(config, x, w):
    return rms_norm(x, w, config.norm_eps, plus_one=config.norm_plus_one)


def _project(config, normed, weights, freqs, positions):
    """The attention's input side on normed x [B, T, h]:
    (q_nope [B, T, H, nope], q_pe [B, T, H, rope] rotated, the token's
    cache row [B, T, row_width] = RMS(c_kv) | RoPE(k_pe) | zeros)."""
    mla = config.mla
    wq_a, q_norm, wq_nope, wq_pe, wkv_a, kv_norm = weights[:6]
    batch, seq = normed.shape[:2]
    heads = config.num_heads
    c_q = _norm(config, jnp.einsum("bth,hr->btr", normed, wq_a), q_norm)
    q_nope = jnp.einsum("btr,dr->btd", c_q, wq_nope).reshape(
        batch, seq, heads, mla.qk_nope_head_dim
    )
    q_pe = jnp.einsum("btr,dr->btd", c_q, wq_pe).reshape(
        batch, seq, heads, mla.qk_rope_head_dim
    )
    kv_a = jnp.einsum("bth,hc->btc", normed, wkv_a)
    c_kv = _norm(config, kv_a[..., : mla.kv_lora_rank], kv_norm)
    k_pe = apply_rope(kv_a[..., None, mla.kv_lora_rank:], freqs, positions)[:, :, 0]
    q_pe = apply_rope(q_pe, freqs, positions)
    # k_pe padded to whole lanes BEFORE it joins c_kv, so that the join is
    # lane-aligned: pieces of 512, 64 and 64 joined along the minor axis
    # make XLA put the positions minor in every buffer downstream, the
    # cache stack included (a transpose of all of it, twice a program)
    k_pe = jnp.pad(
        k_pe, ((0, 0), (0, 0), (0, row_width(config) - width(config)))
    )
    return q_nope, q_pe, jnp.concatenate([c_kv, k_pe], axis=-1)


@jax.named_scope("attention")
def _expanded_attention(config, q_nope, q_pe, rows, wk_b, wv_b, mask):
    """Cold prefill's self-attention over expanded heads: keys ``nope +
    rope`` wide (the rotary part one for all heads), values ``v`` wide.
    The flash kernel on TPU for long prompts (it takes the two widths
    apart), XLA otherwise."""
    latent = config.mla.kv_lora_rank
    c_kv, k_pe = rows[..., :latent], rows[..., latent:width(config)]
    k_nope = jnp.einsum("btc,hcd->bthd", c_kv, wk_b)
    v = jnp.einsum("btc,hcd->bthd", c_kv, wv_b)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], q_pe.shape)], axis=-1
    )
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    scale = softmax_scale(config)
    blocks = prefill_flash_blocks(config, q.shape[1])
    if blocks is not None:
        return flash_prefill_attention(
            q, k, v, mask=mask, scale=scale, block_q=blocks[0],
            block_k=blocks[1], interpret=config.flash_interpret,
        )
    return prefill_attention(q, k, v, mask=mask, scale=scale)


def prefill_flash_blocks(config, seq: int):
    """The flash kernel's (block_q, block_k) where cold prefill's
    attention over ``seq`` tokens runs through it, else None (XLA)."""
    if config.use_flash and (
        use_flash(seq, config.mla.v_head_dim) or config.flash_interpret
    ):
        # 512-row blocks: at 128 heads a 4,096-token call is 32,768 grid
        # steps of 256 x 256, and the steps' overhead is most of its time
        # (31.3 ms against 17.0 ms on a v5e: PERF.md section 6, PR 29)
        return 512, 512
    return None


def _absorb(q_nope, wk_b):
    """q~ = q_nope W_UK^T: a head's nope dims -> the latent."""
    return jnp.einsum("bthd,hcd->bthc", q_nope, wk_b)


def _expand(o_lat, wv_b):
    """o = o_lat W_UV: the latent -> a head's value dims."""
    return jnp.einsum("bthc,hcd->bthd", o_lat, wv_b)


def decode_kernel_ok(config, stack) -> bool:
    """The Pallas ``mla_decode`` kernel on TPU (and under the interpret
    test hook) where its shapes hold; the XLA absorbed form otherwise."""
    mla = config.mla
    shape = (stack.shape[2], mla.kv_lora_rank, stack.shape[3])
    return config.use_flash and (
        use_mla_decode(*shape)
        or (config.flash_interpret and mla_decode_shapes_ok(*shape))
    )


# --------------------------------------------------------------------- #
# the three attends: ``attend(normed [B, T, h], a layer's attention
# weights, the layer's index, None, the stacked latents or None) ->
# (attn [B, T, H, v], the latents, the layer's rows or None)``
# --------------------------------------------------------------------- #
def prefill_attend(config, freqs, positions, mask):
    """Cold prefill over expanded heads; the prompt's rows come back
    stacked over the layers (one stack a cache leaf) and the program
    writes them at its slots."""
    def attend(normed, weights, index, inputs, state):
        *_, wk_b, wv_b = weights
        q_nope, q_pe, rows = _project(config, normed, weights, freqs, positions)
        attn = _expanded_attention(
            config, q_nope, q_pe, rows, wk_b, wv_b, mask
        )
        return attn, state, (rows,)

    return attend


def offset_attend(config, freqs, seq, lengths, offsets, slot_ids):
    """A suffix into warm slots: its latents written at ``offset..``, its
    queries over prefix + suffix in the absorbed form (the cached latents
    are keys and values as they lie). Returns (attend, the suffix's valid
    mask [B, T])."""
    steps = jnp.arange(seq)[None, :]
    positions = offsets[:, None] + steps
    mask = steps < lengths[:, None]
    visible = positions + 1  # query i sees keys [0, offset + i]
    scale = softmax_scale(config)

    @jax.named_scope("cache_write")
    def write_rows(stack, index, rows):
        # a scatter of rows, as the decode step's: the positions are the
        # stack's second-minor (tiled) axis, and a dynamic_update_slice of
        # a window that starts anywhere on it makes XLA lay the whole
        # stack out positions-minor for this program (two transposes of
        # it). Padding rows past the suffix land past ``offset + length``,
        # where content is dead, or out of bounds, where they are dropped.
        return stack.at[index, slot_ids[:, None], positions].set(
            rows.astype(stack.dtype), mode="drop"
        )

    def attend(normed, weights, index, inputs, state):
        (stack,) = state
        *_, wk_b, wv_b = weights
        q_nope, q_pe, rows = _project(config, normed, weights, freqs, positions)
        stack = write_rows(stack, index, rows)
        with jax.named_scope("attention"):
            attn = absorbed_attention(
                q_nope, q_pe, stack[index, slot_ids], visible, wk_b, wv_b,
                scale=scale,
            )
        return attn, (stack,), None

    return attend, mask


def decode_attend(config, freqs, stack, lengths, positions, write_mask):
    """One decode step for every slot, absorbed, on normed [S, 1, h]: the
    new token's latent written into the stack at ``[layer, slot,
    position]`` in place, the layer's slab read once where it lies."""
    slots, max_len = stack.shape[1:3]
    # as the GQA step's: a masked slot and a position past the end go out
    # of bounds, where nothing is written
    write_pos = jnp.where(
        write_mask,
        jnp.where(positions < 0, positions + max_len, positions),
        max_len,
    )
    rows_at = jnp.arange(slots)
    kernel = decode_kernel_ok(config, stack)
    scale = softmax_scale(config)
    latent = config.mla.kv_lora_rank

    def attend(normed, weights, index, inputs, state):
        (stack,) = state
        *_, wk_b, wv_b = weights
        q_nope, q_pe, rows = _project(
            config, normed, weights, freqs, positions[:, None]
        )
        with jax.named_scope("cache_write"):
            stack = stack.at[index, rows_at, write_pos].set(
                rows[:, 0].astype(stack.dtype), mode="drop"
            )
        with jax.named_scope("attention"):
            if kernel:
                q_lat = _absorb(q_nope, wk_b)
                o_lat = mla_decode_attention(
                    latent_query(q_lat, q_pe, stack.shape[3])[:, 0], stack,
                    lengths, index, latent=latent, scale=scale,
                    interpret=config.flash_interpret,
                )[:, None]
                attn = _expand(o_lat, wv_b)
            else:
                attn = absorbed_attention(
                    q_nope, q_pe, stack[index], lengths[:, None], wk_b,
                    wv_b, scale=scale,
                )
        return attn, (stack,), None

    return attend
