"""Fused ragged paged attention (Pallas TPU) over the KV block pool.

The gather/scatter paged paths (``ops/attention.py::paged_*``) round-trip
the ENTIRE per-row KV view through HBM before attending: ``gather_blocks``
reads every live pool block, writes a contiguous ``[B, M*Bs, KVH, D]``
copy, and the XLA attention then re-reads that copy — 3× the KV traffic
of the dense layout on the path the PR-4 roofline says is MBU-bound.
This kernel is the Ragged Paged Attention shape (PAPERS.md, arxiv
2604.15464): the block table rides into the kernel as a scalar-prefetch
operand and the BlockSpec index maps address the pool DIRECTLY, so the
pipeline DMAs each table-addressed block HBM→VMEM exactly once and the
online-softmax recurrence consumes it in place — no materialized gather,
KV traffic ∝ live (block-padded) context.

One grid covers every ragged case the engine dispatches:

- grid = (row, Tq/block_q, M); the kv-block axis is innermost and
  sequential, so VMEM scratch carries the online-softmax state across a
  row's blocks (the ``flash_attention.py`` / ``decode_kernel.py``
  recurrence).
- each row carries ``start`` (global position of its first query token)
  and ``length`` (TOTAL live context = prefix + new tokens): decode is
  ``Tq=1, start=length-1``; warm prefill-at-offset is ``start=offset``;
  cold paged prefill is ``start=0``. Query token t of row b sits at
  global position ``starts[b] + t`` and attends causally at that
  position — the same masking formulas the XLA paged paths share.
- block tables / starts / lengths / window are scalar-prefetch operands:
  available to the index maps BEFORE each block's DMA is issued. Blocks
  outside a (row, q-block)'s live range — past the causal frontier, past
  the row's length, or below its sliding window — clamp their mapped
  pool index into the live range; Pallas elides the copy when mapped
  indices repeat, so skipped blocks cost neither HBM reads nor MXU time
  (their compute is ``pl.when``-gated off).
- GQA runs as one small MXU matmul per kv head (static python loop —
  KVH is a config constant) against the block's ``[Bs, D]`` slab, with
  the q tile flattened to ``[block_q·G, D]`` per kv head.
- the int8-pool twin streams bare int8 k/v blocks through the MXU (half
  the bytes) and folds the per-(position, kv-head) scales exactly as the
  ``ops/attention.py`` quant algebra prescribes: k_scale multiplies the
  scores AFTER q·kᵀ (the score layout), v_scale folds into the probs
  BEFORE p·v, and the p·v contraction runs in f32 like the XLA quant
  path.

The gather/scatter composition stays in ``ops/attention.py`` as the
reference oracle (``paged_kernel: reference``); ``interpret=True`` runs
this kernel on CPU so tier-1 parity stays CPU-verifiable. Under tensor
parallelism the kernel dispatches through
:func:`ragged_paged_attention_sharded` — one independent launch per
kv-head shard via ``shard_map`` (a bare Mosaic call has no SPMD
partitioning rule), tables/starts/lengths replicated, the pool split on
its kv-head axis — the same twin pattern ``flash_attention.py`` /
``decode_kernel.py`` use.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# Largest q tile (block_q · heads · head_dim elements) the v5e compiler
# accepts under its 16 MiB scoped-VMEM limit: the three f32 scratch
# buffers, the double-buffered q/out blocks and the body's f32
# temporaries all scale with it. H=28, D=128, block_q=128 compiles;
# H=32 at the same block_q asks for 17.5 MiB and is refused
# (tests/test_chip_compile.py pins both).
_MAX_Q_TILE_ELEMS = 128 * 28 * 128


def _max_block_q(heads: int, dim: int) -> int:
    """Largest power-of-two q tile ≤ 128 that stays inside
    ``_MAX_Q_TILE_ELEMS`` (power of two so the prefill buckets tile it
    without padding)."""
    block_q = 128
    while block_q > 1 and block_q * heads * dim > _MAX_Q_TILE_ELEMS:
        block_q //= 2
    return block_q


def _last_live_block(total, block_size: int):
    """Index of the last block holding live rows (≥0 so empty rows still
    map block 0 — fully masked, finalize emits zeros)."""
    return jnp.maximum(1, (total + block_size - 1) // block_size) - 1


def _block_bounds(start, total, window, qi, *, block_q: int, block_size: int):
    """[first, last] table-block range a q tile actually needs: causal
    frontier of the tile's LAST query caps the top, the row's length
    caps it again, and a sliding window (of the tile's FIRST query)
    floors the bottom. Everything outside clamps into this range, which
    elides the DMA and skips the compute."""
    last = jnp.minimum(
        _last_live_block(total, block_size),
        (start + (qi + 1) * block_q - 1) // block_size,
    )
    last = jnp.maximum(last, 0)
    first = jnp.where(
        window > 0,
        jnp.maximum(0, (start + qi * block_q - window + 1) // block_size),
        0,
    )
    return jnp.minimum(first, last), last


def _ragged_kernel_body(
    tables_ref,  # SMEM scalar-prefetch [B, M] int32
    starts_ref,  # SMEM scalar-prefetch [B] int32
    totals_ref,  # SMEM scalar-prefetch [B] int32
    win_ref,     # SMEM scalar-prefetch [1] int32 (0 = full attention)
    q_ref,       # VMEM [1, block_q, H, D]
    k_ref,       # VMEM [1, Bs, KVH, D] (pool dtype, or int8)
    v_ref,       # VMEM [1, Bs, KVH, D]
    ks_ref,      # VMEM [1, Bs, KVH] f32, or None (bf16 pool)
    vs_ref,      # VMEM [1, Bs, KVH] f32, or None
    out_ref,     # VMEM [1, block_q, H, D]
    m_scratch,   # VMEM [block_q*H, 128] f32 — running row max
    l_scratch,   # VMEM [block_q*H, 128] f32 — running row sum
    acc_scratch,  # VMEM [block_q*H, D] f32
    *,
    scale: float,
    block_q: int,
    block_size: int,
    kv_heads: int,
    group: int,
    softcap: Optional[float],
    ragged_q: bool = False,
):
    """One online-softmax recurrence for both pool dtypes. Rows of the
    score/accumulator tiles are kv-head-major: row ``h·(block_q·G) +
    t·G + g`` is query token ``t`` of query head ``h·G + g`` — the
    per-head q·kᵀ matmuls concatenate along axis 0 and the finalize
    un-permutes back to ``[block_q, H, D]``.

    ``ragged_q`` is the token-ragged q formulation (mixed prefill+decode
    dispatch): each row's live query count is ``total - start`` and may
    differ per row, so q tiles past a row's live count gate off their
    compute AND their finalize — their (clamped) output block belongs to
    the row's last live tile, which already wrote it."""
    quantized = ks_ref is not None
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)
    num_j = pl.num_programs(2)
    rows_per_head = block_q * group

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    start = starts_ref[b]
    total = totals_ref[b]
    window = win_ref[0]
    # live-tile gate for the ragged-q grid: a tile whose first query
    # index is past the row's live count is dead (its q/KV index maps
    # clamp into the live range, so its DMAs are elided; compute and
    # the live finalize are gated off, and the tile's OWN output block
    # — out tiles never clamp — is zeroed instead, so masked positions
    # are deterministic zeros rather than uninitialized VMEM: the
    # mixed dispatch's null-block writes derive from them, and the
    # mirror replays must be bitwise)
    live = (qi * block_q < total - start) if ragged_q else True
    if ragged_q:

        @pl.when((j == num_j - 1) & jnp.logical_not(live))
        def _zero_dead():
            out_ref[0] = jnp.zeros_like(out_ref[0])
    first, last = _block_bounds(
        start, total, window, qi, block_q=block_q, block_size=block_size
    )

    @pl.when((j >= first) & (j <= last) & live)
    def _compute():
        q = q_ref[0]  # [block_q, H, D]
        # int8 pool values are exactly representable in bf16/f32, so the
        # MXU sees the same numbers the XLA quant path computes
        k = k_ref[0].astype(q.dtype) if quantized else k_ref[0]
        ks = ks_ref[0] if quantized else None  # [Bs, KVH] f32
        parts = []
        for h in range(kv_heads):
            q_h = q[:, h * group:(h + 1) * group, :].reshape(
                rows_per_head, q.shape[-1]
            )
            k_h = k[:, h, :]  # [Bs, D]
            s_h = jax.lax.dot_general(
                q_h, k_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if quantized:
                s_h = s_h * ks[:, h][None, :]
            parts.append(s_h)
        s = jnp.concatenate(parts, axis=0)  # [block_q*H, Bs]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        # global position of each score row's query: rows are kv-head-
        # major, so token index = (row % rows_per_head) // group
        row_ids = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        q_pos = start + qi * block_q + (row_ids % rows_per_head) // group
        cols = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        mask = (cols <= q_pos) & (cols < total)
        mask = jnp.logical_and(
            mask, (window <= 0) | (cols > q_pos - window)
        )
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[:, :1]
        row_max = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, row_max)
        # p is zeroed (not just -inf shifted) so fully-masked rows stay 0
        p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_scratch[:] = jnp.broadcast_to(
            l_scratch[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_scratch.shape,
        )

        if quantized:
            v = v_ref[0].astype(jnp.float32)  # f32 contraction, as XLA
            vs = vs_ref[0]                    # [Bs, KVH] f32
        else:
            v = v_ref[0]
        pv_parts = []
        for h in range(kv_heads):
            p_h = p[h * rows_per_head:(h + 1) * rows_per_head]
            if quantized:
                p_h = p_h * vs[:, h][None, :]
            else:
                p_h = p_h.astype(v.dtype)
            v_h = v[:, h, :]  # [Bs, D]
            pv_parts.append(
                jax.lax.dot_general(
                    p_h, v_h, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
        pv = jnp.concatenate(pv_parts, axis=0)  # [block_q*H, D]
        acc_scratch[:] = acc_scratch[:] * alpha + pv
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)

    @pl.when((j == num_j - 1) & live)
    def _finalize():
        l = l_scratch[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = acc_scratch[:] / l_safe  # [block_q*H, D] kv-head-major
        dim = out.shape[-1]
        out = out.reshape(kv_heads, block_q, group, dim)
        out = out.transpose(1, 0, 2, 3).reshape(
            block_q, kv_heads * group, dim
        )
        out_ref[0] = out.astype(out_ref.dtype)


def _ragged_kernel(tables_ref, starts_ref, totals_ref, win_ref, q_ref,
                   k_ref, v_ref, out_ref, m_scratch, l_scratch,
                   acc_scratch, **kw):
    _ragged_kernel_body(
        tables_ref, starts_ref, totals_ref, win_ref, q_ref, k_ref, v_ref,
        None, None, out_ref, m_scratch, l_scratch, acc_scratch, **kw,
    )


def _ragged_kernel_quant(tables_ref, starts_ref, totals_ref, win_ref,
                         q_ref, k_ref, v_ref, ks_ref, vs_ref, out_ref,
                         m_scratch, l_scratch, acc_scratch, **kw):
    _ragged_kernel_body(
        tables_ref, starts_ref, totals_ref, win_ref, q_ref, k_ref, v_ref,
        ks_ref, vs_ref, out_ref, m_scratch, l_scratch, acc_scratch, **kw,
    )


def _ragged_q_kernel(tables_ref, starts_ref, totals_ref, qoff_ref, win_ref,
                     q_ref, k_ref, v_ref, out_ref, m_scratch, l_scratch,
                     acc_scratch, **kw):
    # qoff_ref is consumed by the index maps only (it addresses the
    # flattened q tile); the recurrence itself needs just starts/totals
    del qoff_ref
    _ragged_kernel_body(
        tables_ref, starts_ref, totals_ref, win_ref, q_ref, k_ref, v_ref,
        None, None, out_ref, m_scratch, l_scratch, acc_scratch,
        ragged_q=True, **kw,
    )


def _ragged_q_kernel_quant(tables_ref, starts_ref, totals_ref, qoff_ref,
                           win_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                           out_ref, m_scratch, l_scratch, acc_scratch, **kw):
    del qoff_ref
    _ragged_kernel_body(
        tables_ref, starts_ref, totals_ref, win_ref, q_ref, k_ref, v_ref,
        ks_ref, vs_ref, out_ref, m_scratch, l_scratch, acc_scratch,
        ragged_q=True, **kw,
    )


def ragged_paged_attention(
    q: jnp.ndarray,             # [B, Tq, H, D] (right-padded new tokens)
    k_pool: jnp.ndarray,        # [N, Bs, KVH, D] (bf16/f32; int8 w/ scales)
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, M] pool block per sequence block
    starts: jnp.ndarray,        # [B] global position of each row's query 0
    lengths: jnp.ndarray,       # [B] TOTAL live context (prefix + new)
    *,
    k_scale: Optional[jnp.ndarray] = None,  # [N, Bs, KVH] — int8 pools
    v_scale: Optional[jnp.ndarray] = None,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,   # scalar; None/0 = full attn
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """One fused launch over the block pool for decode (Tq=1,
    start=length-1), warm prefill-at-offset (start=offset), and cold
    paged prefill (start=0) — drop-in for the per-path
    :func:`langstream_tpu.ops.attention.paged_decode_attention` /
    ``paged_chunk_attention`` gathers (or their ``_quant`` twins when
    scales are given). Returns [B, Tq, H, D]; rows past a row's new-token
    count compute garbage exactly like the XLA paths (callers index by
    length). Caller gates via :func:`use_fused_paged`."""
    batch, seq, heads, dim = q.shape
    num_blocks_table = block_tables.shape[1]
    block_size, kv_heads = k_pool.shape[1], k_pool.shape[2]
    group = heads // kv_heads
    scale = dim ** -0.5 if scale is None else scale
    quantized = k_scale is not None
    block_q = min(block_q or _max_block_q(heads, dim), seq)
    padded = -(-seq // block_q) * block_q
    if padded != seq:
        q = jnp.pad(q, ((0, 0), (0, padded - seq), (0, 0), (0, 0)))
    num_q_blocks = padded // block_q

    tables = block_tables.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    totals = lengths.astype(jnp.int32)
    window_arr = jnp.reshape(
        jnp.asarray(0 if window is None else window, dtype=jnp.int32), (1,)
    )

    def kv_block(b, qi, j, tables, starts, totals, win):
        first, last = _block_bounds(
            starts[b], totals[b], win[0], qi,
            block_q=block_q, block_size=block_size,
        )
        # dead blocks clamp into the live range: the mapped pool indices
        # repeat, so the pipeline skips their DMA entirely
        return tables[b, jnp.clip(j, first, last)]

    def kv_index(b, qi, j, tables, starts, totals, win):
        return (kv_block(b, qi, j, tables, starts, totals, win), 0, 0, 0)

    def scale_index(b, qi, j, tables, starts, totals, win):
        return (kv_block(b, qi, j, tables, starts, totals, win), 0, 0)

    def q_index(b, qi, j, tables, starts, totals, win):
        return (b, qi, 0, 0)

    in_specs = [
        pl.BlockSpec((1, block_q, heads, dim), q_index),
        pl.BlockSpec((1, block_size, kv_heads, dim), kv_index),
        pl.BlockSpec((1, block_size, kv_heads, dim), kv_index),
    ]
    operands = [q, k_pool, v_pool]
    kernel_kw = dict(
        scale=scale, block_q=block_q, block_size=block_size,
        kv_heads=kv_heads, group=group, softcap=softcap,
    )
    if quantized:
        kernel = functools.partial(_ragged_kernel_quant, **kernel_kw)
        in_specs += [
            pl.BlockSpec((1, block_size, kv_heads), scale_index),
            pl.BlockSpec((1, block_size, kv_heads), scale_index),
        ]
        operands += [
            k_scale.astype(jnp.float32), v_scale.astype(jnp.float32),
        ]
        kv_bytes = k_pool.size + v_pool.size + (
            k_scale.size + v_scale.size
        ) * 4
    else:
        kernel = functools.partial(_ragged_kernel, **kernel_kw)
        kv_bytes = (k_pool.size + v_pool.size) * k_pool.dtype.itemsize

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(batch, num_q_blocks, num_blocks_table),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, heads, dim), q_index),
        scratch_shapes=[
            pltpu.VMEM((block_q * heads, 128), jnp.float32),
            pltpu.VMEM((block_q * heads, 128), jnp.float32),
            pltpu.VMEM((block_q * heads, dim), jnp.float32),
        ],
    )
    ctx = num_blocks_table * block_size
    out = pl.pallas_call(
        kernel,
        name=(
            "ragged_paged_attention_int8kv" if quantized
            else "ragged_paged_attention"
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, padded, heads, dim), q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=4 * batch * padded * heads * ctx * dim,
            # the whole point: the scheduler should expect table-
            # addressed block traffic, not a gathered copy (estimate at
            # half occupancy, like the flash-decode kernel)
            bytes_accessed=q.size * q.dtype.itemsize * 2 + kv_bytes // 2,
            transcendentals=batch * padded * heads * ctx,
        ),
        interpret=interpret,
    )(tables, starts, totals, window_arr, *operands)
    return out[:, :seq] if padded != seq else out


def ragged_paged_attention_quant(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,     # [N, Bs, KVH, D] int8
    k_scale: jnp.ndarray,    # [N, Bs, KVH] f32
    v_pool: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_tables: jnp.ndarray,
    starts: jnp.ndarray,
    lengths: jnp.ndarray,
    **kwargs,
) -> jnp.ndarray:
    """Argument-ordering twin of
    :func:`langstream_tpu.ops.attention.paged_chunk_attention_quant`."""
    return ragged_paged_attention(
        q, k_pool, v_pool, block_tables, starts, lengths,
        k_scale=k_scale, v_scale=v_scale, **kwargs,
    )


def ragged_q_paged_attention(
    q: jnp.ndarray,             # [Q, H, D] flattened new-token tile
    k_pool: jnp.ndarray,        # [N, Bs, KVH, D] (bf16/f32; int8 w/ scales)
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, M] pool block per sequence block
    starts: jnp.ndarray,        # [B] global position of each row's query 0
    lengths: jnp.ndarray,       # [B] TOTAL live context (prefix + new)
    q_offsets: jnp.ndarray,     # [B] row offsets into the flat q tile
    *,
    max_q_len: int,             # static per-row span capacity in q
    k_scale: Optional[jnp.ndarray] = None,  # [N, Bs, KVH] — int8 pools
    v_scale: Optional[jnp.ndarray] = None,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,   # scalar; None/0 = full attn
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Token-ragged q formulation: ONE grid serves rows with
    Tq ∈ {0..max_q_len} — the mixed prefill+decode dispatch shape
    (Sarathi/DeepServe chunked-prefill batching on the RPA schedule).

    Row ``b``'s live queries are ``lengths[b] - starts[b]`` tokens
    (decode rows carry 1, admitting rows carry a prefill window, idle
    rows 0) living at ``q_offsets[b] .. q_offsets[b]+live-1`` of the
    flattened ``q`` tile — cu_q_lens-style row offsets, carried as a
    scalar-prefetch operand next to the existing starts/lengths. Each
    row's span must be ``block_q``-aligned (``q_offsets`` multiples of
    ``block_q``, spans padded up to it); q tiles past a row's live
    count clamp their index maps into the row's LAST live tile — the
    repeated mapped indices elide the q/KV DMAs — and gate off both
    compute and finalize, so attention work is ∝ live tokens per row,
    not ∝ the padded span. Returns the flat [Q, H, D] outputs; padding
    positions within a live tile compute garbage exactly like the XLA
    paths (callers index by the row's live count)."""
    total_q, heads, dim = q.shape
    batch, num_blocks_table = block_tables.shape
    block_size, kv_heads = k_pool.shape[1], k_pool.shape[2]
    group = heads // kv_heads
    scale = dim ** -0.5 if scale is None else scale
    quantized = k_scale is not None
    block_q = min(block_q or min(8, _max_block_q(heads, dim)), max_q_len)
    if max_q_len % block_q or total_q % block_q:
        raise ValueError(
            f"ragged-q spans must tile by block_q={block_q} "
            f"(max_q_len={max_q_len}, flat q={total_q})"
        )
    num_q_tiles = max_q_len // block_q
    # 4-d view so the shared kernel body's [1, block_q, H, D] ref shape
    # (and the scratch layout) match the fixed-Tq kernel exactly
    q_tiles = q.reshape(total_q // block_q, block_q, heads, dim)

    tables = block_tables.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    totals = lengths.astype(jnp.int32)
    qoffs = q_offsets.astype(jnp.int32)
    window_arr = jnp.reshape(
        jnp.asarray(0 if window is None else window, dtype=jnp.int32), (1,)
    )

    def live_tile(b, qi, starts, totals):
        # last live q tile of row b (>=0 so fully-dead rows clamp to
        # tile 0 — gated off in the kernel)
        q_len = totals[b] - starts[b]
        tiles = jnp.maximum(1, (q_len + block_q - 1) // block_q)
        return jnp.minimum(qi, tiles - 1)

    def q_index(b, qi, j, tables, starts, totals, qoffs, win):
        return (
            qoffs[b] // block_q + live_tile(b, qi, starts, totals),
            0, 0, 0,
        )

    def out_index(b, qi, j, tables, starts, totals, qoffs, win):
        # out tiles do NOT clamp: a dead tile owns its span position and
        # writes zeros there (see the kernel's _zero_dead), so padding
        # positions are deterministic instead of uninitialized
        return (qoffs[b] // block_q + qi, 0, 0, 0)

    def kv_block(b, qi, j, tables, starts, totals, qoffs, win):
        qi_live = live_tile(b, qi, starts, totals)
        first, last = _block_bounds(
            starts[b], totals[b], win[0], qi_live,
            block_q=block_q, block_size=block_size,
        )
        # dead q tiles AND dead kv blocks clamp into the live range:
        # repeated mapped indices elide the DMA entirely
        return tables[b, jnp.clip(j, first, last)]

    def kv_index(b, qi, j, tables, starts, totals, qoffs, win):
        return (kv_block(b, qi, j, tables, starts, totals, qoffs, win),
                0, 0, 0)

    def scale_index(b, qi, j, tables, starts, totals, qoffs, win):
        return (kv_block(b, qi, j, tables, starts, totals, qoffs, win),
                0, 0)

    in_specs = [
        pl.BlockSpec((1, block_q, heads, dim), q_index),
        pl.BlockSpec((1, block_size, kv_heads, dim), kv_index),
        pl.BlockSpec((1, block_size, kv_heads, dim), kv_index),
    ]
    operands = [q_tiles, k_pool, v_pool]
    kernel_kw = dict(
        scale=scale, block_q=block_q, block_size=block_size,
        kv_heads=kv_heads, group=group, softcap=softcap,
    )
    if quantized:
        kernel = functools.partial(_ragged_q_kernel_quant, **kernel_kw)
        in_specs += [
            pl.BlockSpec((1, block_size, kv_heads), scale_index),
            pl.BlockSpec((1, block_size, kv_heads), scale_index),
        ]
        operands += [
            k_scale.astype(jnp.float32), v_scale.astype(jnp.float32),
        ]
        kv_bytes = k_pool.size + v_pool.size + (
            k_scale.size + v_scale.size
        ) * 4
    else:
        kernel = functools.partial(_ragged_q_kernel, **kernel_kw)
        kv_bytes = (k_pool.size + v_pool.size) * k_pool.dtype.itemsize

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(batch, num_q_tiles, num_blocks_table),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, heads, dim), out_index),
        scratch_shapes=[
            pltpu.VMEM((block_q * heads, 128), jnp.float32),
            pltpu.VMEM((block_q * heads, 128), jnp.float32),
            pltpu.VMEM((block_q * heads, dim), jnp.float32),
        ],
    )
    ctx = num_blocks_table * block_size
    out = pl.pallas_call(
        kernel,
        name=(
            "ragged_q_paged_attention_int8kv" if quantized
            else "ragged_q_paged_attention"
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (total_q // block_q, block_q, heads, dim), q.dtype
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * batch * max_q_len * heads * ctx * dim,
            bytes_accessed=q.size * q.dtype.itemsize * 2 + kv_bytes // 2,
            transcendentals=batch * max_q_len * heads * ctx,
        ),
        interpret=interpret,
    )(tables, starts, totals, qoffs, window_arr, *operands)
    return out.reshape(total_q, heads, dim)


def ragged_q_paged_attention_quant(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,     # [N, Bs, KVH, D] int8
    k_scale: jnp.ndarray,    # [N, Bs, KVH] f32
    v_pool: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_tables: jnp.ndarray,
    starts: jnp.ndarray,
    lengths: jnp.ndarray,
    q_offsets: jnp.ndarray,
    **kwargs,
) -> jnp.ndarray:
    """Int8-pool twin of :func:`ragged_q_paged_attention` (argument
    ordering matches the other ``*_quant`` wrappers)."""
    return ragged_q_paged_attention(
        q, k_pool, v_pool, block_tables, starts, lengths, q_offsets,
        k_scale=k_scale, v_scale=v_scale, **kwargs,
    )


def ragged_q_paged_attention_sharded(
    q: jnp.ndarray,             # [Q, H, D] — H sharded over ``axis_name``
    k_pool: jnp.ndarray,        # [N, Bs, KVH, D] — KVH sharded
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, M] (replicated host metadata)
    starts: jnp.ndarray,        # [B]
    lengths: jnp.ndarray,       # [B]
    q_offsets: jnp.ndarray,     # [B] (replicated)
    mesh,
    *,
    max_q_len: int,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    axis_name: str = "tp",
    interpret: bool = False,
) -> jnp.ndarray:
    """Token-ragged q kernel under tensor parallelism — the shard_map
    twin, exactly like :func:`ragged_paged_attention_sharded`: one
    independent launch per kv-head shard, tables/starts/lengths/
    q_offsets replicated scalar-prefetch, pool/q/out split on their
    kv-head/head axes (attention never mixes kv heads, so no
    collective)."""
    from jax.sharding import PartitionSpec as P

    head_spec = P(None, axis_name, None)         # flat q / out [Q, H, D]
    pool_spec = P(None, None, axis_name, None)   # [N, Bs, KVH, D]
    scale_spec = P(None, None, axis_name)        # [N, Bs, KVH]
    quantized = k_scale is not None
    window_arr = jnp.asarray(
        0 if window is None else window, dtype=jnp.int32
    )

    def local(q_l, k_l, v_l, tables_l, starts_l, totals_l, qoffs_l,
              window_l, *scales):
        return ragged_q_paged_attention(
            q_l, k_l, v_l, tables_l, starts_l, totals_l, qoffs_l,
            max_q_len=max_q_len, interpret=interpret, softcap=softcap,
            window=window_l, scale=scale, block_q=block_q,
            **(
                {"k_scale": scales[0], "v_scale": scales[1]}
                if scales else {}
            ),
        )

    in_specs = [
        head_spec, pool_spec, pool_spec,
        P(None, None), P(None), P(None), P(None), P(),
    ]
    operands = [
        q, k_pool, v_pool, block_tables, starts, lengths, q_offsets,
        window_arr,
    ]
    if quantized:
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    from langstream_tpu.ops.flash_attention import compat_shard_map

    return compat_shard_map(
        local, mesh, tuple(in_specs), head_spec
    )(*operands)


def ragged_paged_attention_sharded(
    q: jnp.ndarray,             # [B, Tq, H, D] — H sharded over ``axis_name``
    k_pool: jnp.ndarray,        # [N, Bs, KVH, D] — KVH sharded
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, M] (replicated host metadata)
    starts: jnp.ndarray,        # [B]
    lengths: jnp.ndarray,       # [B]
    mesh,
    *,
    k_scale: Optional[jnp.ndarray] = None,  # [N, Bs, KVH] — int8 pools
    v_scale: Optional[jnp.ndarray] = None,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    axis_name: str = "tp",
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused ragged paged attention under tensor parallelism — the paged
    twin of ``flash_prefill_attention_sharded`` /
    ``flash_decode_attention_sharded``.

    A Mosaic ``pallas_call`` has no SPMD partitioning rule, so the kernel
    cannot sit inside a tp-sharded jit directly; ``shard_map`` over the
    kv-head axis runs one independent launch per shard. Attention never
    mixes kv heads, so no collective is needed: each shard's kernel sees
    a contiguous local head slab of the pool (the layout
    ``model.paged_cache_logical_axes`` pins — kv_heads shard, pool blocks
    never do), the q/output head axis splits by the same tp factor
    (``validate_mesh`` enforces divisibility, so the GQA group size is
    shard-invariant and the per-kv-head MXU loop runs over the local
    shard only). Block tables, starts, lengths, and the (traced)
    ``window`` scalar are replicated operands — the same host metadata
    every shard prefetches in full. With ``k_scale``/``v_scale`` the
    int8-pool kernel runs per shard, scales sharded over their kv-head
    axis."""
    from jax.sharding import PartitionSpec as P

    head_spec = P(None, None, axis_name, None)   # q / out [B, Tq, H, D]
    pool_spec = P(None, None, axis_name, None)   # [N, Bs, KVH, D]
    scale_spec = P(None, None, axis_name)        # [N, Bs, KVH]
    quantized = k_scale is not None
    window_arr = jnp.asarray(
        0 if window is None else window, dtype=jnp.int32
    )

    def local(q_l, k_l, v_l, tables_l, starts_l, totals_l, window_l,
              *scales):
        return ragged_paged_attention(
            q_l, k_l, v_l, tables_l, starts_l, totals_l,
            interpret=interpret, softcap=softcap, window=window_l,
            scale=scale, block_q=block_q,
            **(
                {"k_scale": scales[0], "v_scale": scales[1]}
                if scales else {}
            ),
        )

    in_specs = [
        head_spec, pool_spec, pool_spec,
        P(None, None), P(None), P(None), P(),
    ]
    operands = [q, k_pool, v_pool, block_tables, starts, lengths, window_arr]
    if quantized:
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    from langstream_tpu.ops.flash_attention import compat_shard_map

    return compat_shard_map(
        local, mesh, tuple(in_specs), head_spec
    )(*operands)


def ragged_paged_attention_quant_sharded(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,     # [N, Bs, KVH, D] int8
    k_scale: jnp.ndarray,    # [N, Bs, KVH] f32
    v_pool: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_tables: jnp.ndarray,
    starts: jnp.ndarray,
    lengths: jnp.ndarray,
    mesh,
    **kwargs,
) -> jnp.ndarray:
    """Int8-pool twin of :func:`ragged_paged_attention_sharded` — thin
    argument-ordering wrapper."""
    return ragged_paged_attention_sharded(
        q, k_pool, v_pool, block_tables, starts, lengths, mesh,
        k_scale=k_scale, v_scale=v_scale, **kwargs,
    )


def fused_shapes_ok(heads: int, kv_heads: int) -> bool:
    """Structural requirement (holds on ANY backend): GQA folds into the
    per-kv-head matmul loop, so query heads must group evenly."""
    return kv_heads > 0 and heads % kv_heads == 0


def use_fused_paged(
    dim: int, heads: int, kv_heads: int, interpret: bool = False
) -> bool:
    """Kernel gate: structurally-valid GQA always; beyond that, a real
    TPU backend with an MXU-aligned head_dim — or interpret mode (the
    CPU test hook), where Mosaic's tiling constraints don't apply, so
    tiny test shapes exercise the exact kernel schedule tier-1 can
    verify."""
    if not fused_shapes_ok(heads, kv_heads):
        return False
    if interpret:
        return True
    from langstream_tpu.ops.flash_attention import on_tpu

    return on_tpu() and dim % 128 == 0
