"""Block-sparse GQA: a parameter-free selection of key blocks over
compressed keys, and attention over the kept blocks.

The selection (InfLLM-V2's, over the layer's own K): a compressed key is
the mean of ``kernel_size`` consecutive keys, one every ``kernel_stride``
positions; a query scores the compressed keys that lie wholly at or
before it (a softmax a query head, summed over the heads of a kv head's
group: one selection a kv head a query position); a block of
``block_size`` positions scores the max over the compressed windows that
overlap it; block(s) ``[0, init_blocks)`` and the blocks that hold the
last ``window_size`` positions are always kept, of the others the
``topk`` best. A query whose context (its position + 1) is at most
``dense_len`` keeps every block: dense causal attention. The result IS
attention over the kept blocks' positions, causal inside them.

- :func:`select_blocks` returns the kept blocks ``[B, KVH, Tq, NB]`` (one
  function for a prefill window's queries and a decode step's) with the
  counts a trace reads (blocks kept, blocks in context, queries);
- :func:`sparse_prefill_attention`: a window of queries at an offset over
  the slot's cached K and V with the selection as a per-key mask. On TPU
  the Pallas kernel ``sparse_block_prefill`` (flash attention, grid
  ``(row, head, query tile, key tile)``; key tiles past a query tile's
  causal frontier are neither fetched nor computed; K and V are read
  where they lie in the stacked cache); XLA's masked attention elsewhere;
- :func:`sparse_decode_attention`: one query a slot. On TPU the Pallas
  kernel ``sparse_block_decode``: a prefetched list of the key tiles that
  hold a kept block a slot a kv head (a tile is several blocks wide: one
  grid step a block costs more in step overhead than the blocks' bytes),
  the kept positions inside a tile by an additive bias.

The cache the two read: K and V ``[L, S, KVH, T, D]`` (a kv head's
positions contiguous, so a tile is one slab), compressed keys ``[L, S,
KVH, T / kernel_stride, D]``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_TILES = (2048, 1024, 512, 256, 128, 64, 32, 16, 8)
_Q_TILES = (512, 256, 128, 64, 32, 16, 8)  # a prefill's query and key tiles


@dataclasses.dataclass(frozen=True)
class Selection:
    """The selection's sizes (MiniCPM4's ``sparse_config`` names)."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def check(self) -> None:
        if (
            self.kernel_size != 2 * self.kernel_stride
            or self.block_size % self.kernel_stride
            or min(dataclasses.astuple(self)) < 1
        ):
            raise ValueError(f"unsupported block selection: {self}")


def compressed_count(max_len: int, sel: Selection) -> int:
    """Rows of the compressed-key cache: one a stride (the last, whose
    window would pass the end, is never valid)."""
    return max_len // sel.kernel_stride


def select_blocks(q, compressed, positions, valid, sel: Selection, *,
                  scale: float, num_blocks: int):
    """The kept blocks of every query: q ``[B, Tq, H, D]``, compressed
    ``[B, KVH, NC, D]`` (the row's slot), positions ``[B, Tq]`` absolute,
    valid ``[B, Tq]`` (padding and riding slots count nothing). Returns
    (kept ``[B, KVH, Tq, NB]`` bool, counts int32 ``[3]``: blocks kept and
    blocks in context, both summed over kv heads and valid queries, and
    the valid queries)."""
    batch, seq, heads, dim = q.shape
    kv_heads, windows = compressed.shape[1], compressed.shape[2]
    group = heads // kv_heads
    per_block = sel.block_size // sel.kernel_stride
    context = positions + 1                                        # [B, Tq]
    blocks = jnp.arange(num_blocks)
    visible = blocks[None, None, :] * sel.block_size < context[..., None]
    first_local = jnp.maximum(context - sel.window_size, 0) // sel.block_size
    always = (blocks[None, None, :] < sel.init_blocks) | (
        blocks[None, None, :] >= first_local[..., None]
    )

    def chosen():
        scores = jnp.einsum(
            "btkgd,bkcd->bkgtc",
            q.reshape(batch, seq, kv_heads, group, dim), compressed,
            preferred_element_type=jnp.float32,
        ) * scale
        whole = (
            jnp.arange(windows)[None, None, :] * sel.kernel_stride
            + sel.kernel_size <= context[..., None]
        )[:, None, None]                                           # [B,1,1,Tq,NC]
        scores = jnp.where(whole, scores, NEG_INF)
        top = jnp.max(scores, axis=-1, keepdims=True)
        weights = jnp.where(whole, jnp.exp(scores - top), 0.0)
        total = jnp.sum(weights, axis=-1, keepdims=True)
        mass = jnp.sum(
            weights / jnp.where(total == 0.0, 1.0, total), axis=2
        )                                                          # [B,KVH,Tq,NC]
        # block b meets windows 4b - 1 .. 4b + 3 (kernel 32, stride 16,
        # block 64): pad one window in front, take the max of the block's
        # own ``per_block`` and of the next block's first
        need = (num_blocks + 1) * per_block
        mass = jnp.pad(
            mass, ((0, 0), (0, 0), (0, 0), (1, max(0, need - windows - 1)))
        )[..., :need].reshape(batch, kv_heads, seq, num_blocks + 1, per_block)
        by_block = jnp.maximum(
            mass[..., :-1, :].max(axis=-1), mass[..., 1:, 0]
        )                                                          # [B,KVH,Tq,NB]
        far = (visible & ~always)[:, None]
        ranked = jnp.where(far, by_block, -1.0)
        count = min(sel.topk, num_blocks)
        best, which = jax.lax.top_k(ranked, count)
        hit = (which[..., None] == blocks) & (best[..., None] >= 0.0)
        return jnp.any(hit, axis=-2)

    sparse = context > sel.dense_len                               # [B, Tq]
    picked = jax.lax.cond(
        jnp.any(sparse & valid), chosen,
        lambda: jnp.zeros((batch, kv_heads, seq, num_blocks), bool),
    )
    kept = jnp.where(
        sparse[:, None, :, None], (always[:, None] | picked), True
    ) & visible[:, None]
    counted = valid[:, None, :, None]
    counts = jnp.stack([
        jnp.sum(kept & counted), jnp.sum(visible & valid[..., None]) * kv_heads,
        jnp.sum(valid),
    ]).astype(jnp.int32)
    return kept, counts


def key_mask(kept, positions, max_len: int, sel: Selection):
    """The kept blocks as kept KEYS ``[B, KVH, Tq, max_len]`` bool: a
    kept block's positions at or before the query."""
    per_key = jnp.repeat(kept, sel.block_size, axis=-1)[..., :max_len]
    causal = jnp.arange(max_len)[None, None, :] <= positions[..., None]
    return per_key & causal[:, None]


def _masked_attention(q, k, v, mask, scale):
    """XLA's form: q ``[B, Tq, H, D]``, k and v ``[B, KVH, T, D]``, mask
    ``[B, KVH, Tq, T]``. Returns ``[B, Tq, H, D]`` in q's dtype."""
    batch, seq, heads, dim = q.shape
    kv_heads = k.shape[1]
    scores = jnp.einsum(
        "btkgd,bksd->bkgts",
        q.reshape(batch, seq, kv_heads, heads // kv_heads, dim), k,
        preferred_element_type=jnp.float32,
    ) * scale
    scores = jnp.where(mask[:, :, None], scores, NEG_INF)
    top = jnp.max(scores, axis=-1, keepdims=True)
    weights = jnp.where(mask[:, :, None], jnp.exp(scores - top), 0.0)
    total = jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights / jnp.where(total == 0.0, 1.0, total)
    out = jnp.einsum(
        "bkgts,bksd->btkgd", weights.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(batch, seq, heads, dim).astype(q.dtype)


# --------------------------------------------------------------------- #
# prefill: a window of queries at an offset, the selection as a mask
# --------------------------------------------------------------------- #
def _softmax_step(s, mask, v, m_scratch, l_scratch, acc_scratch):
    """One online-softmax step over a key tile: s ``[R, tk]`` float32
    scores, mask ``[R, tk]`` bool, v ``[tk, D]``."""
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scratch[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
    alpha = jnp.exp(m_prev - m_new)
    l_scratch[:] = jnp.broadcast_to(
        l_scratch[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
        l_scratch.shape,
    )
    acc_scratch[:] = acc_scratch[:] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)


def _start(m_scratch, l_scratch, acc_scratch):
    m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
    l_scratch[:] = jnp.zeros_like(l_scratch)
    acc_scratch[:] = jnp.zeros_like(acc_scratch)


def _finish(out_ref, l_scratch, acc_scratch):
    l = l_scratch[:, :1]
    out_ref[0] = (
        acc_scratch[:] / jnp.where(l == 0.0, 1.0, l)
    ).astype(out_ref.dtype)


def _last_tile(offsets, totals, b, i, block_q: int, block_k: int):
    """The last key tile a query tile can see."""
    frontier = jnp.minimum(offsets[b] + (i + 1) * block_q, totals[b])
    return jnp.maximum(frontier - 1, 0) // block_k


def _prefill_kernel(layer_ref, slots_ref, offsets_ref, totals_ref, q_ref,
                    k_ref, v_ref, mask_ref, out_ref, m_scratch, l_scratch,
                    acc_scratch, *, scale, block_q, block_k):
    del layer_ref, slots_ref
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        _start(m_scratch, l_scratch, acc_scratch)

    @pl.when(j <= _last_tile(offsets_ref, totals_ref, b, i, block_q, block_k))
    def _():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        mask = mask_ref[0, 0].astype(jnp.float32) > 0.0
        _softmax_step(s, mask, v_ref[0], m_scratch, l_scratch, acc_scratch)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        _finish(out_ref, l_scratch, acc_scratch)


def _prefill_pallas(q, k_stack, v_stack, mask, layer, slot_ids, offsets,
                    totals, scale, interpret):
    batch, seq, width = q.shape
    layers, slots, kv_heads, max_len, dim = k_stack.shape
    heads = width // dim
    group = heads // kv_heads
    block_q = next(t for t in _Q_TILES if seq % t == 0)
    block_k = next(t for t in _Q_TILES if max_len % t == 0)

    def slab(b, h, j, lyr, sl):
        return (lyr[0] * slots + sl[b]) * kv_heads + h // group

    def kv_index(b, h, i, j, lyr, sl, off, tot):
        j = jnp.minimum(j, _last_tile(off, tot, b, i, block_q, block_k))
        return (slab(b, h, j, lyr, sl), j, 0)

    def mask_index(b, h, i, j, lyr, sl, off, tot):
        j = jnp.minimum(j, _last_tile(off, tot, b, i, block_q, block_k))
        return (b, h // group, i, j)

    column = pl.BlockSpec(
        (1, block_q, dim), lambda b, h, i, j, *_: (b, i, h)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(batch, heads, seq // block_q, max_len // block_k),
        in_specs=[
            column,
            pl.BlockSpec((1, block_k, dim), kv_index),
            pl.BlockSpec((1, block_k, dim), kv_index),
            pl.BlockSpec((1, 1, block_q, block_k), mask_index),
        ],
        out_specs=column,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, dim), jnp.float32),
        ],
    )
    flat = (layers * slots * kv_heads, max_len, dim)
    return pl.pallas_call(
        functools.partial(
            _prefill_kernel, scale=scale, block_q=block_q, block_k=block_k
        ),
        name="sparse_block_prefill",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=4 * batch * heads * seq * max_len * dim,
            bytes_accessed=(
                2 * q.size * q.dtype.itemsize + mask.size
                + 2 * batch * heads * (seq // block_q) * max_len * dim
                * k_stack.dtype.itemsize
            ),
            transcendentals=batch * heads * seq * max_len,
        ),
        interpret=interpret,
    )(
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
        slot_ids.astype(jnp.int32), offsets.astype(jnp.int32),
        totals.astype(jnp.int32), q, k_stack.reshape(flat),
        v_stack.reshape(flat), mask.astype(jnp.int8),
    )


def sparse_prefill_attention(q, k_stack, v_stack, mask, layer, slot_ids,
                             offsets, totals, *, scale, kernel: bool,
                             interpret: bool = False):
    """A window of queries over its slots' cached keys: q ``[B, Tq, H *
    D]`` (the flattened projection), K and V stacks ``[L, S, KVH, T, D]``
    (the window's own rows already written), mask ``[B, KVH, Tq, T]`` the
    kept keys of every query, offsets and totals ``[B]`` the window's
    first position and its end. Returns ``[B, Tq, H * D]``."""
    if kernel:
        return _prefill_pallas(
            q, k_stack, v_stack, mask, layer, slot_ids, offsets, totals,
            scale, interpret,
        )
    batch, seq, width = q.shape
    dim = k_stack.shape[-1]
    out = _masked_attention(
        q.reshape(batch, seq, width // dim, dim), k_stack[layer, slot_ids],
        v_stack[layer, slot_ids], mask, scale,
    )
    return out.reshape(batch, seq, width)


# --------------------------------------------------------------------- #
# decode: one query a slot, a prefetched list of key tiles
# --------------------------------------------------------------------- #
def _decode_kernel(layer_ref, tiles_ref, counts_ref, q_ref, k_ref, v_ref,
                   bias_ref, out_ref, m_scratch, l_scratch, acc_scratch, *,
                   scale, kv_heads):
    del layer_ref, tiles_ref
    s_i, g, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        _start(m_scratch, l_scratch, acc_scratch)

    @pl.when(j < counts_ref[s_i * kv_heads + g])
    def _():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale + bias_ref[0, 0]
        _softmax_step(
            s, s > 0.5 * NEG_INF, v_ref[0], m_scratch, l_scratch, acc_scratch
        )

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        _finish(out_ref, l_scratch, acc_scratch)


def decode_tile(max_len: int) -> Optional[int]:
    return next((t for t in _TILES if max_len % t == 0), None)


def _decode_pallas(q, k_stack, v_stack, mask, layer, scale, interpret):
    slots, heads, dim = q.shape
    layers, _, kv_heads, max_len, _ = k_stack.shape
    group = heads // kv_heads
    tile = decode_tile(max_len)
    tiles = max_len // tile
    # the tiles that hold a kept key, first in the list, and how many
    touched = mask.reshape(slots, kv_heads, tiles, tile).any(axis=-1)
    order = jnp.argsort(~touched, axis=-1, stable=True).astype(jnp.int32)
    counts = touched.sum(axis=-1).astype(jnp.int32)
    bias = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)[:, :, None]

    def tile_of(s, g, j, tls, cnt):
        pair = s * kv_heads + g
        return tls[pair * tiles + jnp.minimum(j, jnp.maximum(cnt[pair], 1) - 1)]

    def kv_index(s, g, j, lyr, tls, cnt):
        return ((lyr[0] * slots + s) * kv_heads + g, tile_of(s, g, j, tls, cnt), 0)

    rows = pl.BlockSpec((1, group, dim), lambda s, g, j, *_: (s, g, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots, kv_heads, tiles),
        in_specs=[
            rows,
            pl.BlockSpec((1, tile, dim), kv_index),
            pl.BlockSpec((1, tile, dim), kv_index),
            pl.BlockSpec(
                (1, 1, 1, tile),
                lambda s, g, j, lyr, tls, cnt: (s, g, 0, tile_of(s, g, j, tls, cnt)),
            ),
        ],
        out_specs=rows,
        scratch_shapes=[
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, dim), jnp.float32),
        ],
    )
    flat = (layers * slots * kv_heads, max_len, dim)
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, kv_heads=kv_heads),
        name="sparse_block_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=4 * slots * heads * max_len * dim,
            bytes_accessed=2 * slots * kv_heads * max_len * dim
            * k_stack.dtype.itemsize,
            transcendentals=slots * heads * max_len,
        ),
        interpret=interpret,
    )(
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), order.reshape(-1),
        counts.reshape(-1), q, k_stack.reshape(flat), v_stack.reshape(flat),
        bias,
    )


def sparse_decode_attention(q, k_stack, v_stack, mask, layer, *, scale,
                            kernel: bool, interpret: bool = False):
    """One query a slot over the slot's kept keys: q ``[S, H, D]``, K and
    V stacks ``[L, S, KVH, T, D]`` (the new row already written), mask
    ``[S, KVH, T]`` the kept keys. Returns ``[S, H, D]``; a slot that
    keeps nothing reads zeros."""
    if kernel:
        return _decode_pallas(q, k_stack, v_stack, mask, layer, scale, interpret)
    return _masked_attention(
        q[:, None], k_stack[layer], v_stack[layer], mask[:, :, None], scale
    )[:, 0]


def sparse_shapes_ok(max_len: int, dim: int, heads: int, kv_heads: int) -> bool:
    """What the two kernels need: whole 128-lane rows, a kv head's group
    a whole sublane tile, key tiles of whole lanes that divide the cache."""
    return (
        dim % 128 == 0 and heads % kv_heads == 0
        and (heads // kv_heads) % 8 == 0 and max_len % 512 == 0
    )
