"""Length-aware Pallas TPU decode attention (flash-decode).

The XLA decode attention (``ops/attention.py::decode_attention``) is an
einsum over the cache's FULL static buffer ``[S, T, KVH, D]``: masking
keeps invalid positions out of the softmax, but every decode step still
streams all ``T`` allocated rows per slot from HBM. At serving contexts
(T = 4-8k) with typical live lengths far below T, most of that traffic
is dead rows — and decode is the HBM-bound hot loop, so dead traffic is
lost tokens/sec.

This kernel makes decode-attention HBM traffic proportional to the
LIVE context instead of the allocated buffer:

- grid = (slot, T/block_k); the kv-block axis is innermost/sequential,
  so VMEM scratch carries the online-softmax state across a slot's
  blocks (same recurrence as ``ops/flash_attention.py``).
- the cache operands are the engine's STACKED leaves
  ``[L, S, T, KVH, D]``, seen as ``[L*S, T, KVH, D]`` (a bitcast), and
  the layer is a scalar-prefetch operand that the K/V (and scale)
  index maps turn into the row ``layer*S + slot``: the kernel streams
  its layer's slab where it lies. A custom call's operand is a
  materialised buffer, so handing it ``stack[layer]`` made XLA copy
  the slab out of the stack every layer of every step — and handing it
  a leaf in another layout than the one it lies in makes XLA re-lay-out
  the whole stack instead, which is why the scales go in position-last
  and a single kv head goes in squeezed (see the operands below).
- per-slot lengths ride as a scalar-prefetch operand: they are
  available to the BlockSpec index maps BEFORE the pipeline issues
  each block's DMA. Blocks past a slot's last live block clamp their
  index to that last block — Pallas elides the copy when the mapped
  block indices repeat, so skipped blocks cost neither HBM reads nor
  MXU time (their compute is ``pl.when``-gated off).
- GQA runs as one small MXU matmul per kv head against the block's
  ``[block_k, D]`` slab (a static python loop — KVH is a config
  constant); q is tiny ([H, D]) and loaded once per slot.
- heads narrower than a lane row (D = 64, 32, 16) are read PACKED: the
  cache then lies as ``[L, S, T, KVH / pack, 128]`` with ``pack = 128 //
  D`` kv heads side by side in one 128-lane row (``kv_pack`` has the
  rule; ``model.init_cache`` lays the leaf out so). The wrapper hands
  the kernel a query padded to 128 lanes, head ``h``'s D values in the
  lanes of its kv head and zeros elsewhere, so ``q_pad . k_row`` is
  head ``h``'s score exactly and ``p . v_row`` holds its output in the
  same lanes: the body above runs unchanged with ``KVH / pack`` kv
  heads and ``pack`` times the group, at ``pack`` times the MXU flops
  of a step that is bound by bytes.
- the int8-cache twin streams int8 k/v tiles (half the bytes — the
  kv-quant win compounds with block skipping) and folds the
  per-(position, head) scales exactly like the XLA quant path:
  k_scale AFTER q·kᵀ, v_scale into the probs BEFORE p·v.

Reference parity: none to port — the reference's decode loop lives
server-side behind provider HTTPS (SURVEY §2.4, `OpenAICompletionService
.java:52`); this is the TPU-native interior of the `jax-local` engine's
continuous-batching decode step (`providers/jax_local/engine.py`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # a vector register's minor axis: what a cache row must fill

# candidate kv-block sizes, largest first; the allocated cache length
# must divide evenly (no padding — padding would copy the cache)
_BLOCK_CANDIDATES = (512, 256, 128, 64, 32)


def pick_block_k(max_len: int) -> Optional[int]:
    for cand in _BLOCK_CANDIDATES:
        if max_len % cand == 0 and max_len >= cand:
            return cand
    return None


def _num_valid_blocks(length, block_k: int):
    """Blocks holding live rows (≥1 so empty slots still touch block 0 —
    their scores are fully masked and finalize emits zeros)."""
    return jnp.maximum(1, (length + block_k - 1) // block_k)


def _first_valid_block(length, window, block_k: int):
    """First block inside the sliding window (0 when window is off or
    wider than the live context): valid positions are
    ``length - window .. length - 1``."""
    return jnp.where(
        window > 0,
        jnp.maximum(0, (length - window) // block_k),
        0,
    )


def _decode_kernel_body(
    lens_ref,   # SMEM scalar-prefetch [S] int32
    win_ref,    # SMEM scalar-prefetch [1] int32 (0 = full attention)
    layer_ref,  # SMEM scalar-prefetch [1] int32 — index maps only
    q_ref,      # VMEM [1, H, D]
    k_ref,      # VMEM [1, block_k, KVH, D] (cache dtype, or int8);
                # [1, block_k, D] when KVH is 1
    v_ref,      # VMEM [1, block_k, KVH, D]
    ks_ref,     # VMEM [1, KVH, block_k] f32, or None (bf16 cache)
    vs_ref,     # VMEM [1, KVH, block_k] f32, or None
    out_ref,    # VMEM [1, H, D]
    m_scratch,  # VMEM [H, 128] f32 — running row max
    l_scratch,  # VMEM [H, 128] f32 — running row sum
    acc_scratch,  # VMEM [H, D] f32
    *,
    scale: float,
    block_k: int,
    kv_heads: int,
    group: int,
    softcap: Optional[float],
):
    """One online-softmax recurrence for both cache dtypes. The int8
    mode (``ks_ref``/``vs_ref`` present) streams int8 k/v from HBM (the
    bandwidth halving is the whole point) and folds the scales exactly
    like ``ops/attention.py::decode_attention_quant``: k_scale
    multiplies the scores after q·kᵀ, v_scale folds into the probs
    before p·v, and — matching the XLA quant path, which contracts
    f32 probs against f32 values — the p·v dot runs in f32 (no bf16
    round-trip on the scale-folded probs). The bf16 mode contracts
    bf16 probs with the bf16 cache, matching ``decode_attention``'s
    ``weights.astype(v_cache.dtype)``.

    A sliding window (Gemma-2) tightens the live block range from BOTH
    ends — blocks below the window skip compute exactly like dead
    blocks past the length (and their DMAs are clamp-elided by the
    index maps); ``softcap`` caps the scores before masking."""
    quantized = ks_ref is not None
    s_i = pl.program_id(0)
    j = pl.program_id(1)
    num_blocks = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    length = lens_ref[s_i]
    window = win_ref[0]
    first = _first_valid_block(length, window, block_k)

    @pl.when((j >= first) & (j < _num_valid_blocks(length, block_k)))
    def _compute():
        q = q_ref[0]  # [H, D]
        # int8 values are exactly representable in bf16, so the MXU
        # sees the same numbers the XLA quant path computes
        k = k_ref[0].astype(q.dtype) if quantized else k_ref[0]
        ks = ks_ref[0] if quantized else None  # [KVH, block_k] f32
        parts = []
        for h in range(kv_heads):
            q_h = q[h * group:(h + 1) * group]  # [G, D]
            k_h = k if k.ndim == 2 else k[:, h, :]  # [block_k, D]
            s_h = jax.lax.dot_general(
                q_h, k_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if quantized:
                s_h = s_h * ks[h:h + 1, :]
            parts.append(s_h)
        s = jnp.concatenate(parts, axis=0)  # [H, block_k]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        mask = cols < length
        mask = jnp.logical_and(
            mask, (window <= 0) | (cols > (length - 1) - window)
        )
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[:, :1]
        row_max = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, row_max)
        p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_scratch[:] = jnp.broadcast_to(
            l_scratch[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_scratch.shape,
        )

        if quantized:
            v = v_ref[0].astype(jnp.float32)  # f32 contraction, as XLA
            vs = vs_ref[0]                    # [KVH, block_k] f32
        else:
            v = v_ref[0]
        pv_parts = []
        for h in range(kv_heads):
            p_h = p[h * group:(h + 1) * group]  # [G, block_k] f32
            if quantized:
                p_h = p_h * vs[h:h + 1, :]
            else:
                p_h = p_h.astype(v.dtype)
            v_h = v if v.ndim == 2 else v[:, h, :]  # [block_k, D]
            pv_parts.append(
                jax.lax.dot_general(
                    p_h, v_h, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
        pv = jnp.concatenate(pv_parts, axis=0)  # [H, D]
        acc_scratch[:] = acc_scratch[:] * alpha + pv
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)

    @pl.when(j == num_blocks - 1)
    def _finalize():
        l = l_scratch[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = (acc_scratch[:] / l_safe).astype(out_ref.dtype)


def _decode_kernel(lens_ref, win_ref, layer_ref, q_ref, k_ref, v_ref,
                   out_ref, m_scratch, l_scratch, acc_scratch, **kw):
    _decode_kernel_body(
        lens_ref, win_ref, layer_ref, q_ref, k_ref, v_ref, None, None,
        out_ref, m_scratch, l_scratch, acc_scratch, **kw,
    )


def _decode_kernel_quant(lens_ref, win_ref, layer_ref, q_ref, k_ref, v_ref,
                         ks_ref, vs_ref, out_ref, m_scratch, l_scratch,
                         acc_scratch, **kw):
    _decode_kernel_body(
        lens_ref, win_ref, layer_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
        out_ref, m_scratch, l_scratch, acc_scratch, **kw,
    )


def flash_decode_attention(
    q: jnp.ndarray,        # [S, H, D] — one new token per slot
    k_cache: jnp.ndarray,  # [L, S, T, KVH, D] stacked (bf16; int8 with scales)
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,  # [S] valid rows incl. the new token
    layer: jnp.ndarray,    # scalar int32 — which slab of the stack
    *,
    k_scale: Optional[jnp.ndarray] = None,  # [L, S, T, KVH] — int8 mode
    v_scale: Optional[jnp.ndarray] = None,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,  # scalar; None/0 = full attn
    scale: Optional[float] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """:func:`langstream_tpu.ops.attention.decode_attention` (or
    ``decode_attention_quant`` when scales are given) over slab
    ``layer`` of the stacked cache, with HBM traffic ∝ live context and
    no copy of the slab: the layer is one more scalar in the index maps.
    Caller gates via :func:`use_flash_decode`; shapes must satisfy
    :func:`decode_shapes_ok`, and ``block_k`` must divide T
    (``pick_block_k``). A sliding ``window`` (Gemma-2) bounds the
    traffic by the window instead — blocks below it clamp-elide their
    DMA just like dead blocks past the length.

    A PACKED stack ``[L, S, T, KVH / pack, pack * D]`` (heads narrower
    than 128 lanes, :func:`kv_pack`) is told by its row being wider than
    q's heads: the kernel then runs on 128-lane rows with q zero-padded
    into its kv head's lanes, and each head's own D lanes come back."""
    slots, heads, head_dim = q.shape
    num_layers, max_len, kv_heads, dim = (
        k_cache.shape[0], k_cache.shape[2], k_cache.shape[3],
        k_cache.shape[4],
    )
    scale = head_dim ** -0.5 if scale is None else scale
    pack = dim // head_dim
    if pack > 1:
        # head h reads kv head h // (H / KVH), which lies in lanes
        # [lane * D, (lane + 1) * D) of packed row (h // group)
        lane = (np.arange(heads) // (heads // (kv_heads * pack))) % pack
        own = jnp.asarray(lane[:, None] == np.arange(pack))[:, :, None]
        q = jnp.where(own, q[:, :, None, :], 0).reshape(slots, heads, dim)
    group = heads // kv_heads
    block_k = block_k or pick_block_k(max_len)
    if block_k is None:
        raise ValueError(f"no kv block size divides max_len={max_len}")
    num_blocks = max_len // block_k
    quantized = k_scale is not None
    lengths = lengths.astype(jnp.int32)
    window_arr = jnp.reshape(
        jnp.asarray(0 if window is None else window, dtype=jnp.int32), (1,)
    )
    layer_arr = jnp.reshape(jnp.asarray(layer, dtype=jnp.int32), (1,))

    def block_index(s, j, lens, win):
        # clamp dead blocks (past the length OR below the sliding
        # window) into the live range: the mapped indices repeat, so
        # the pipeline skips their DMA entirely
        first = _first_valid_block(lens[s], win[0], block_k)
        last = _num_valid_blocks(lens[s], block_k) - 1
        return jnp.clip(j, first, last)

    # layer and slot merge into one leading axis: slab ``layer``'s slot
    # s is row ``layer * S + s``, and the pipeline moves the blocks it
    # moved when it was handed a slab. With ONE kv head (a tp shard of
    # a 4-kv-head model on four chips) the leaf lies with T and D as
    # its tiled axes, which is [rows, T, D]; asked for [rows, T, 1, D]
    # the operand would be the stack re-tiled over (1, D), a copy.
    kv_tail = (kv_heads, dim) if kv_heads > 1 else (dim,)

    def kv_index(s, j, lens, win, lyr):
        block = block_index(s, j, lens, win)
        return (lyr[0] * slots + s, block) + (0,) * len(kv_tail)

    def scale_index(s, j, lens, win, lyr):
        return (lyr[0] * slots + s, 0, block_index(s, j, lens, win))

    def q_index(s, j, lens, win, lyr):
        return (s, 0, 0)

    in_specs = [
        pl.BlockSpec((1, heads, dim), q_index),
        pl.BlockSpec((1, block_k) + kv_tail, kv_index),
        pl.BlockSpec((1, block_k) + kv_tail, kv_index),
    ]
    rows = num_layers * slots
    operands = [
        q,
        k_cache.reshape((rows, max_len) + kv_tail),
        v_cache.reshape((rows, max_len) + kv_tail),
    ]
    if quantized:
        kernel = functools.partial(
            _decode_kernel_quant, scale=scale, block_k=block_k,
            kv_heads=kv_heads, group=group, softcap=softcap,
        )
        in_specs += [
            pl.BlockSpec((1, kv_heads, block_k), scale_index),
            pl.BlockSpec((1, kv_heads, block_k), scale_index),
        ]
        # the scales go in with the position axis LAST: that is how the
        # f32[L, S, T, KVH] leaf lies on the chip (a kv-head axis 4 wide
        # is no lane axis, so the device's layout has T minor-most) and
        # the swap is a bitcast there. Handed [.., T, KVH] the kernel's
        # operand is the whole stack re-laid-out with KVH padded to 128
        # lanes, 32 times the leaf, copied every layer of every step.
        operands += [
            jnp.swapaxes(leaf.astype(jnp.float32), -1, -2).reshape(
                rows, kv_heads, max_len
            )
            for leaf in (k_scale, v_scale)
        ]
        stack_bytes = (
            k_cache.size + v_cache.size + (k_scale.size + v_scale.size) * 4
        )
    else:
        kernel = functools.partial(
            _decode_kernel, scale=scale, block_k=block_k,
            kv_heads=kv_heads, group=group, softcap=softcap,
        )
        stack_bytes = (k_cache.size + v_cache.size) * k_cache.dtype.itemsize

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots, num_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, heads, dim), q_index),
        scratch_shapes=[
            pltpu.VMEM((heads, 128), jnp.float32),
            pltpu.VMEM((heads, 128), jnp.float32),
            pltpu.VMEM((heads, dim), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="flash_decode_int8kv" if quantized else "flash_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, heads, dim), q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=4 * slots * heads * max_len * dim,
            # the operands are the whole stack but a call reads ONE
            # layer's slab, and of that the live context: the scheduler
            # should expect that traffic (estimate at half occupancy)
            bytes_accessed=(
                q.size * q.dtype.itemsize * 2 + stack_bytes // num_layers // 2
            ),
            transcendentals=slots * heads * max_len,
        ),
        interpret=interpret,
    )(lengths, window_arr, layer_arr, *operands)
    if pack > 1:
        out = jnp.where(
            own, out.reshape(slots, heads, pack, head_dim), 0
        ).sum(axis=2)
    return out


def flash_decode_attention_quant(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,   # [L, S, T, KVH, D] int8
    k_scale: jnp.ndarray,   # [L, S, T, KVH]
    v_cache: jnp.ndarray,
    v_scale: jnp.ndarray,
    lengths: jnp.ndarray,
    layer: jnp.ndarray,
    **kwargs,
) -> jnp.ndarray:
    """Argument-ordering twin of
    :func:`langstream_tpu.ops.attention.decode_attention_quant`."""
    return flash_decode_attention(
        q, k_cache, v_cache, lengths, layer,
        k_scale=k_scale, v_scale=v_scale, **kwargs,
    )


def flash_decode_attention_sharded(
    q: jnp.ndarray,        # [S, H, D] — H sharded over ``axis_name``
    k_cache: jnp.ndarray,  # [L, S, T, KVH, D] — KVH sharded
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    layer: jnp.ndarray,
    mesh,
    *,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    axis_name: str = "tp",
    interpret: bool = False,
) -> jnp.ndarray:
    """Flash decode under tensor parallelism: one independent kernel per
    head shard through ``shard_map`` (a Mosaic call has no SPMD
    partitioning rule). Attention never mixes heads, so no collective;
    query and kv heads shard by the same tp factor (``validate_mesh``
    enforces divisibility; a packed stack shards whole packed rows,
    which :func:`kv_pack` asks of ``tp``). The (traced) ``window`` and
    ``layer`` scalars ride as replicated operands."""
    from jax.sharding import PartitionSpec as P

    head_spec = P(None, axis_name, None)
    cache_spec = P(None, None, None, axis_name, None)
    scale_spec = P(None, None, None, axis_name)
    quantized = k_scale is not None
    window_arr = jnp.asarray(
        0 if window is None else window, dtype=jnp.int32
    )
    layer_arr = jnp.asarray(layer, dtype=jnp.int32)

    def local(q_l, k_l, v_l, lengths_l, layer_l, window_l, *scales):
        return flash_decode_attention(
            q_l, k_l, v_l, lengths_l, layer_l, interpret=interpret,
            softcap=softcap, window=window_l, scale=scale,
            **(
                {"k_scale": scales[0], "v_scale": scales[1]}
                if scales else {}
            ),
        )

    in_specs = [head_spec, cache_spec, cache_spec, P(None), P(), P()]
    operands = [q, k_cache, v_cache, lengths, layer_arr, window_arr]
    if quantized:
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    from langstream_tpu.ops.flash_attention import compat_shard_map

    return compat_shard_map(
        local, mesh, tuple(in_specs), head_spec
    )(*operands)


def kv_pack(
    dim: int, kv_heads: int, quantized: bool = False, tp: int = 1
) -> Optional[int]:
    """How many kv heads one row of the cache holds for the kernel: 1
    where a head is whole lanes wide (D % 128 == 0), ``128 // D`` where
    narrower heads fill a 128-lane row exactly, None where the kernel
    cannot read the cache. Packing wants a bf16 cache (an int8 row would
    hold ``pack`` heads with ``pack`` scales, which the per-head scale
    fold cannot express) and whole packed rows a tp shard."""
    if dim % LANES == 0:
        return 1
    pack = LANES // dim
    if LANES % dim == 0 and not quantized and kv_heads % (pack * tp) == 0:
        return pack
    return None


def decode_shapes_ok(
    max_len: int, dim: int, heads: int, kv_heads: int,
    quantized: bool = False, tp: int = 1,
) -> bool:
    """Hard shape requirements of the kernel (hold on ANY backend)."""
    return (
        kv_pack(dim, kv_heads, quantized, tp) is not None
        and heads % kv_heads == 0
        and pick_block_k(max_len) is not None
    )


def use_flash_decode(
    max_len: int, dim: int, heads: int, kv_heads: int,
    quantized: bool = False, tp: int = 1,
) -> bool:
    """The kernel pays once dead-block skipping can actually drop HBM
    traffic: a long allocated cache, a head dim that fills whole lanes
    (alone or packed), a block size that divides it, and a real TPU
    backend."""
    from langstream_tpu.ops.flash_attention import on_tpu

    if not decode_shapes_ok(max_len, dim, heads, kv_heads, quantized, tp):
        return False
    return on_tpu() and max_len >= 1024
