"""Length-aware Pallas TPU decode attention (flash-decode).

The XLA decode attention (``ops/attention.py::decode_attention``) is an
einsum over the cache's FULL static buffer ``[S, T, KVH, D]``: masking
keeps invalid positions out of the softmax, but every decode step still
streams all ``T`` allocated rows per slot from HBM. At serving contexts
(T = 4-8k) with typical live lengths far below T, most of that traffic
is dead rows — and decode is the HBM-bound hot loop, so dead traffic is
lost tokens/sec.

This kernel makes decode-attention HBM traffic, and its step count,
proportional to the LIVE context instead of the allocated buffer:

- grid = (slot groups,): a grid step takes ``G`` slots' queries and
  outputs as ``[G, H, D]`` blocks (``pick_slot_group``: every slot in
  one step wherever the blocks fit their VMEM budget), so the grid does
  not grow with ``T`` or with the kv blocks. A grid step has a fixed
  cost whatever it computes, and a grid of (slot, kv block) paid it for
  every allocated block, live or not.
- inside a step a loop walks the group's LIVE kv blocks only, slot after
  slot: a slot's ``first .. end - 1`` blocks of ``block_k`` rows, where
  ``end`` follows its length and ``first`` the sliding window
  (``_first_valid_block``). Each block is fetched by a manual DMA into
  one of two VMEM buffers, block n + 1's fetch issued before block n's
  compute, across slot boundaries too. The online-softmax state (same
  recurrence as ``ops/flash_attention.py``) resets at a slot's first
  block and its output is written at its last. A dead slot (length 0)
  costs no DMA and no compute and reads zeros.
- the cache operands are the engine's STACKED leaves
  ``[L, S, T, KVH, D]``, seen as ``[L*S, T, KVH, D]`` (a bitcast) and
  left in HBM (``pl.ANY``); the layer is a scalar-prefetch operand and
  a block's DMA reads row ``layer*S + slot`` of the stack: the kernel
  streams its layer's slab where it lies. A custom call's operand is a
  materialised buffer, so handing it ``stack[layer]`` made XLA copy the
  slab out of the stack every layer of every step — and handing it a
  leaf in another layout than the one it lies in makes XLA re-lay-out
  the whole stack instead, which is why the scales go in position-last
  and a single kv head goes in squeezed (see the operands below).
- per-slot lengths and the window ride as scalar-prefetch operands in
  SMEM, where the walk reads them.
- GQA: where the cache is bf16 and its kv heads a power of two, a
  block is read as ``[block_k * KVH, D]`` rows (the stack's own bytes:
  a position's kv heads follow each other) and all its heads go through
  one MXU matmul, each query row masked to its own kv head's rows
  (``_rows_of_heads``); the int8 twin runs one small matmul per kv head
  against the block's ``[block_k, D]`` slice. q is tiny ([H, D]) and
  read once per block.
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
- the int8-cache twin streams int8 k/v tiles (half the bytes) with
  their per-(position, head) scales and folds the scales exactly like
  the XLA quant path: k_scale AFTER q·kᵀ, v_scale into the probs
  BEFORE p·v.

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

# VMEM a grid step's query and output blocks may take, each block held
# twice by the pipeline; the kv blocks' two buffers and the softmax
# state come on top
_GROUP_VMEM_BYTES = 4 * 2 ** 20


def pick_block_k(max_len: int) -> Optional[int]:
    for cand in _BLOCK_CANDIDATES:
        if max_len % cand == 0 and max_len >= cand:
            return cand
    return None


def pick_slot_group(slots: int, heads: int, dim: int, itemsize: int) -> int:
    """Slots a grid step takes: the most that divide ``slots`` and whose
    query and output blocks (heads padded to a full sublane tile, two
    buffers each) fit ``_GROUP_VMEM_BYTES``. One group is one step a
    layer; every group more pays one more exposed first fetch."""
    per_slot = 4 * -(-heads // 16) * 16 * dim * itemsize
    fit = max(1, _GROUP_VMEM_BYTES // per_slot)
    return max(g for g in range(1, min(slots, fit) + 1) if slots % g == 0)


def _live_blocks(length, block_k: int):
    """Blocks holding live rows: 0 for an empty slot."""
    return (length + block_k - 1) // block_k


def _num_valid_blocks(length, block_k: int):
    """Blocks holding live rows, at least 1 (``ops/mla_attention.py``'s
    grid visits block 0 of an empty slot and emits zeros there)."""
    return jnp.maximum(1, _live_blocks(length, block_k))


def _first_valid_block(length, window, block_k: int):
    """First block inside the sliding window (0 when window is off or
    wider than the live context): valid positions are
    ``length - window .. length - 1``."""
    return jnp.where(
        window > 0,
        jnp.maximum(0, (length - window) // block_k),
        0,
    )


def _rows_of_heads(quantized: bool, kv_heads: int) -> bool:
    """Whether a block's kv heads are read as ROWS ``[block_k * KVH, D]``
    (row r is position r // KVH of kv head r % KVH): one matmul over all
    of a block's heads, each query row masked to its own kv head's rows,
    in place of one ``[block_k, D]`` slice per kv head, a
    sublane-strided gather of the block that cost twice the block's DMA
    at Qwen-2.5-7B's shapes on a TPU v5e. The rows are the stack's own
    bytes (a bitcast).
    Wants a bf16 cache (an int8 block's scales lie per kv head) and a
    power-of-two KVH (the row's head and position are a mask and a
    shift)."""
    return not quantized and (kv_heads & (kv_heads - 1)) == 0


def _attend_block(
    q, k, v, ks, vs, m_ref, l_ref, acc_ref, start, length, window, *,
    scale: float, kv_heads: int, group: int, softcap: Optional[float],
):
    """One kv block of one slot's online-softmax recurrence, for both
    cache dtypes: ``q`` [H, D], ``k``/``v`` the block whose first
    position is ``start`` — rows ``[block_k * KVH, D]`` where
    :func:`_rows_of_heads`, else ``[block_k, KVH, D]`` sliced per kv head
    — and ``ks``/``vs`` [KVH, block_k] f32 or None. The int8 mode
    streams int8 k/v from HBM (the bandwidth halving is the whole point)
    and folds the scales exactly like
    ``ops/attention.py::decode_attention_quant``: k_scale multiplies the
    scores after q·kᵀ, v_scale folds into the probs before p·v, and —
    matching the XLA quant path, which contracts f32 probs against f32
    values — the p·v dot runs in f32 (no bf16 round-trip on the
    scale-folded probs). The bf16 mode contracts bf16 probs with the
    bf16 cache, matching ``decode_attention``'s
    ``weights.astype(v_cache.dtype)``. ``softcap`` caps the scores
    before masking; the mask keeps positions under ``length`` and, with
    a sliding ``window`` (Gemma-2), inside it."""
    quantized = ks is not None
    rows = _rows_of_heads(quantized, kv_heads)
    if rows:
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, block_k * KVH]
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        head = (cols & (kv_heads - 1)) * group
        query = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        own = (query >= head) & (query < head + group)
        pos = start + (cols >> (kv_heads.bit_length() - 1))
    else:
        if quantized:
            # int8 values are exactly representable in bf16, so the MXU
            # sees the same numbers the XLA quant path computes
            k = k.astype(q.dtype)
        parts = []
        for h in range(kv_heads):
            s_h = jax.lax.dot_general(
                q[h * group:(h + 1) * group], k if k.ndim == 2 else k[:, h, :],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [G, block_k]
            if quantized:
                s_h = s_h * ks[h:h + 1, :]
            parts.append(s_h)
        s = jnp.concatenate(parts, axis=0)  # [H, block_k]
        own = True
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    mask = own & (pos < length) & ((window <= 0) | (pos > (length - 1) - window))
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]
    row_max = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, row_max)
    p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:] = jnp.broadcast_to(
        l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
        l_ref.shape,
    )

    if rows:
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    else:
        if quantized:
            v = v.astype(jnp.float32)  # f32 contraction, as XLA
        pv_parts = []
        for h in range(kv_heads):
            p_h = p[h * group:(h + 1) * group]  # [G, block_k] f32
            if quantized:
                p_h = p_h * vs[h:h + 1, :]
            else:
                p_h = p_h.astype(v.dtype)
            pv_parts.append(
                jax.lax.dot_general(
                    p_h, v if v.ndim == 2 else v[:, h, :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
        pv = jnp.concatenate(pv_parts, axis=0)  # [H, D]
    acc_ref[:] = acc_ref[:] * alpha + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)


def _live_span(length, window, block_k: int, num_blocks: int):
    """A slot's live blocks ``[first, end)``: past the sliding window's
    start and never past the cache (empty when the slot is)."""
    first = _first_valid_block(length, window, block_k)
    return first, jnp.minimum(_live_blocks(length, block_k), num_blocks)


def _decode_kernel(
    lens_ref,   # SMEM scalar-prefetch [S] int32
    win_ref,    # SMEM scalar-prefetch [1] int32 (0 = full attention)
    layer_ref,  # SMEM scalar-prefetch [1] int32
    order_ref,  # SMEM scalar-prefetch [S] int32: each group's live slots
                # first, in slot order
    live_ref,   # SMEM scalar-prefetch [S / G] int32: live slots a group
    q_ref,      # VMEM [G, H, D]
    k_hbm,      # HBM [L*S, T * KVH, D] rows (``_rows_of_heads``), else
                # [L*S, T, KVH, D] (cache dtype, or int8)
    v_hbm,
    *rest,      # (ks_hbm, vs_hbm: HBM [L*S, KVH, T] f32, int8 mode),
                # out_ref VMEM [G, H, D], then the scratch: k and v
                # buffers [2, block of k / v] (and ks, vs [2, KVH,
                # block_k]), DMA semaphores [streams, 2], m and l
                # [H, 128] f32, acc [H, D] f32
    quantized: bool,
    block_k: int,
    block_rows: int,
    num_blocks: int,
    slots: int,
    group_slots: int,
    **attend,
):
    if quantized:
        (ks_hbm, vs_hbm, out_ref, k_buf, v_buf, ks_buf, vs_buf, sems,
         m_ref, l_ref, acc_ref) = rest
        streams = ((k_hbm, k_buf), (v_hbm, v_buf), (ks_hbm, ks_buf),
                   (vs_hbm, vs_buf))
    else:
        out_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref = rest
        streams = ((k_hbm, k_buf), (v_hbm, v_buf))
    group = pl.program_id(0)
    base = group * group_slots
    live = live_ref[group]
    window = win_ref[0]

    def slot(n):
        # the group's n-th live slot (n may be ``live``: its value is
        # then read but never used)
        return order_ref[base + jnp.minimum(n, group_slots - 1)]

    def span(s):
        length = lens_ref[s]
        return (length,) + _live_span(length, window, block_k, num_blocks)

    def copies(s, j, buf):
        # block j of slot s into buffer ``buf``: for K and V its rows,
        # for the scales its columns
        row = layer_ref[0] * slots + s
        kv_rows = pl.ds(pl.multiple_of(j * block_rows, block_rows), block_rows)
        cols = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        return [
            pltpu.make_async_copy(
                src.at[(row, kv_rows) if n < 2 else (row, slice(None), cols)],
                dst.at[buf], sems.at[n, buf],
            )
            for n, (src, dst) in enumerate(streams)
        ]

    out_ref[...] = jnp.zeros_like(out_ref)  # dead slots read zeros

    def step(carry):
        n, j, buf = carry
        s = slot(n)
        length, first, end = span(s)
        last = j + 1 == end
        next_n = jnp.where(last, n + 1, n)
        next_j = jnp.where(last, span(slot(next_n))[1], j + 1)

        @pl.when(next_n < live)
        def _prefetch():
            for copy in copies(slot(next_n), next_j, 1 - buf):
                copy.start()

        for copy in copies(s, j, buf):
            copy.wait()

        @pl.when(j == first)
        def _reset():
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        _attend_block(
            q_ref[s - base], k_buf[buf], v_buf[buf],
            ks_buf[buf] if quantized else None,
            vs_buf[buf] if quantized else None,
            m_ref, l_ref, acc_ref, j * block_k, length, window, **attend,
        )

        @pl.when(last)
        def _finalize():
            l = l_ref[:, :1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            out_ref[s - base] = (acc_ref[:] / l_safe).astype(out_ref.dtype)

        return next_n, next_j, 1 - buf

    first0 = span(slot(0))[1]

    @pl.when(live > 0)
    def _first_fetch():
        for copy in copies(slot(0), first0, 0):
            copy.start()

    jax.lax.while_loop(
        lambda carry: carry[0] < live, step, (jnp.int32(0), first0, jnp.int32(0))
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
    ``layer`` of the stacked cache, with HBM traffic and work ∝ live
    context and no copy of the slab: the layer is one more scalar the
    kernel's DMAs read. Caller gates via :func:`use_flash_decode`;
    shapes must satisfy :func:`decode_shapes_ok`, and ``block_k`` must
    divide T (``pick_block_k``). A sliding ``window`` (Gemma-2) bounds
    the traffic by the window instead: blocks below it are never
    fetched, like dead blocks past the length. An empty slot's output
    is zeros.

    A PACKED stack ``[L, S, T, KVH / pack, pack * D]`` (heads narrower
    than 128 lanes, :func:`kv_pack`) is told by its row being wider than
    q's heads: the kernel then runs on 128-lane rows with q zero-padded
    into its kv head's lanes, and each head's own D lanes come back.
    ``interpret`` runs the kernel in the TPU interpreter (its DMAs and
    semaphores simulated), for CPU tests."""
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
    quantized = k_scale is not None
    group_slots = pick_slot_group(slots, heads, dim, q.dtype.itemsize)
    lengths = lengths.astype(jnp.int32)
    window_arr = jnp.reshape(
        jnp.asarray(0 if window is None else window, dtype=jnp.int32), (1,)
    )
    layer_arr = jnp.reshape(jnp.asarray(layer, dtype=jnp.int32), (1,))

    # each group's live slots first, in slot order, and how many: the
    # walk visits those alone. A compare against every position is one
    # small fusion where a sort or a scatter would be ops of their own,
    # once a layer (nothing hoists them out of the layer loop)
    first, end = _live_span(lengths, window_arr[0], block_k, max_len // block_k)
    is_live = (end > first).reshape(-1, group_slots).astype(jnp.int32)
    live = is_live.sum(axis=1)
    dest = (
        jnp.cumsum(is_live, axis=1) - 1
        + np.arange(0, slots, group_slots)[:, None]
    ).reshape(slots)
    order = jnp.sum(
        jnp.where(
            (dest[None, :] == np.arange(slots)[:, None])
            & (is_live.reshape(1, slots) > 0),
            np.arange(slots, dtype=np.int32)[None, :], 0,
        ),
        axis=1, dtype=jnp.int32,
    )

    # layer and slot merge into one leading axis: slab ``layer``'s slot
    # s is row ``layer * S + s``, a bitcast of the stack. Read as rows
    # (``_rows_of_heads``) a position's kv heads follow each other, which
    # is how the stack lies ([.., T, KVH, D] tiles KVH rows of D lanes).
    # Otherwise, with ONE kv head (a tp shard of a 4-kv-head model on
    # four chips, or two 64-wide heads packed) the leaf lies with T and D
    # as its tiled axes, which is [rows, T, D]; asked for [rows, T, 1, D]
    # the operand would be the stack re-tiled over (1, D), a copy.
    if _rows_of_heads(quantized, kv_heads):
        kv_tail, block_rows = (max_len * kv_heads, dim), block_k * kv_heads
        kv_block = (block_rows, dim)
    else:
        kv_tail = (max_len,) + ((kv_heads, dim) if kv_heads > 1 else (dim,))
        block_rows, kv_block = block_k, (block_k,) + kv_tail[1:]
    rows = num_layers * slots
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)

    def group_index(g, *prefetched):
        return (g, 0, 0)

    in_specs = [pl.BlockSpec((group_slots, heads, dim), group_index),
                in_hbm, in_hbm]
    operands = [
        q,
        k_cache.reshape((rows,) + kv_tail),
        v_cache.reshape((rows,) + kv_tail),
    ]
    buffers = [pltpu.VMEM((2,) + kv_block, k_cache.dtype)] * 2
    if quantized:
        in_specs += [in_hbm, in_hbm]
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
        buffers += [pltpu.VMEM((2, kv_heads, block_k), jnp.float32)] * 2
        stack_bytes = (
            k_cache.size + v_cache.size + (k_scale.size + v_scale.size) * 4
        )
    else:
        stack_bytes = (k_cache.size + v_cache.size) * k_cache.dtype.itemsize

    kernel = functools.partial(
        _decode_kernel, quantized=quantized, block_k=block_k,
        block_rows=block_rows, num_blocks=max_len // block_k, slots=slots,
        group_slots=group_slots, scale=scale, kv_heads=kv_heads,
        group=group, softcap=softcap,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(slots // group_slots,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((group_slots, heads, dim), group_index),
        scratch_shapes=buffers + [
            pltpu.SemaphoreType.DMA((len(buffers), 2)),
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
        interpret=pltpu.InterpretParams() if interpret else False,
    )(lengths, window_arr, layer_arr, order, live, *operands)
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
