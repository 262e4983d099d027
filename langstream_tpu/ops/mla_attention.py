"""Latent (MLA) attention over a cache of latents, in the absorbed form.

The cache row of a token and a layer is ``c_kv`` (after its norm) followed
by ``k_pe`` (after RoPE): ``latent + rope`` values, one "head" for all
query heads, padded with zeros to whole lanes (576 -> 640: see
``latent_moe.row_width``). With ``W_UK`` absorbed into the query and ``W_UV`` applied
after the sum, a query never needs the expanded keys and values::

    q~ = q_nope W_UK^T                   (a head's nope dims -> latent)
    score = (q~ . c_kv + q_pe . k_pe) * s
    o_lat = softmax(score) . c_kv        (latent wide)
    o     = o_lat W_UV                   (-> a head's value dims)

so the row is BOTH key (all of it) and value (its first ``latent``
values), and a reader moves it once. ``2 * (2 * latent + rope) * heads``
flops a cached token against ``(latent + rope) * 2`` bytes: 242 flop/B at
the DeepSeek-V2 sizes, a v5e's ridge.

- :func:`absorbed_attention` is the XLA form (the CPU path, and a warm
  prefill's suffix over a cached prefix, in blocks of queries so that the
  ``[heads, queries, keys]`` scores stay small).
- :func:`mla_decode_attention` is the Pallas TPU kernel ``mla_decode`` for
  one query a slot: a flash-decode schedule (grid = (slot, T /
  block_k), online softmax in VMEM scratch, per-slot
  lengths as scalar prefetch so that blocks past a slot's length are
  neither moved nor computed, the STACKED cache ``[L, S, T, latent +
  rope]`` with the layer as one more prefetched scalar so that no slab is
  copied out of the stack), with one difference: the K tile is also the V
  tile. XLA's einsums would write the ``[S, heads, T]`` float32 scores to
  HBM between the two contractions, several times the latent's bytes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from langstream_tpu.ops.decode_kernel import (
    NEG_INF,
    _num_valid_blocks,
    pick_block_k,
)


def latent_query(
    q_lat: jnp.ndarray, q_pe: jnp.ndarray, width: int
) -> jnp.ndarray:
    """``q~ | q_pe | zeros`` [..., H, width]: the query as wide as a cache
    row (which is padded to whole lanes), so that one contraction over
    the row gives ``q~ . c_kv + q_pe . k_pe``."""
    lanes = width - q_lat.shape[-1] - q_pe.shape[-1]
    parts = [q_lat, q_pe]
    if lanes:
        parts.append(jnp.zeros(q_lat.shape[:-1] + (lanes,), q_lat.dtype))
    return jnp.concatenate(parts, axis=-1)


def _query_block(batch: int, heads: int, queries: int, keys: int) -> int:
    """Queries a block of :func:`absorbed_attention` takes: the largest
    power of two whose float32 scores ``[B, H, block, T]`` stay under 128
    MiB (128 heads over 4,608 keys: 32), at most 256."""
    block = 256
    while block > 1 and batch * heads * block * keys * 4 > 2 ** 27:
        block //= 2
    return min(block, queries)


def absorbed_attention(
    q_nope: jnp.ndarray,   # [B, Tq, H, nope]
    q_pe: jnp.ndarray,     # [B, Tq, H, rope]
    rows: jnp.ndarray,     # [B, T, row] cached rows (c_kv | k_pe | zeros)
    visible: jnp.ndarray,  # [B, Tq] int32: query i sees keys [0, visible)
    wk_b: jnp.ndarray,     # [H, latent, nope] — W_UK
    wv_b: jnp.ndarray,     # [H, latent, v] — W_UV
    *,
    scale: float,
) -> jnp.ndarray:
    """Attention of every query over its visible cached rows in the
    absorbed form, ``o`` [B, Tq, H, v]. float32 scores and softmax. The
    queries go in blocks, and ``W_UK`` / ``W_UV`` are applied inside a
    block, so that neither the scores nor the latent-wide ``q~`` and
    ``o_lat`` of all queries are ever whole (at 4,096 queries of 128
    heads they would be 0.7 GB each)."""
    batch, queries, heads, _ = q_nope.shape
    keys, latent = rows.shape[1], wk_b.shape[1]
    block = _query_block(batch, heads, queries, keys)
    pad = (-queries) % block
    if pad:
        q_nope = jnp.pad(q_nope, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pe = jnp.pad(q_pe, ((0, 0), (0, pad), (0, 0), (0, 0)))
        visible = jnp.pad(visible, ((0, 0), (0, pad)))
    num_blocks = (queries + pad) // block
    key_at = jnp.arange(keys)

    def blocks(x):
        """[B, Tq, ...] -> [num_blocks, B, block, ...]"""
        x = x.reshape((batch, num_blocks, block) + x.shape[2:])
        return jnp.swapaxes(x, 0, 1)

    def one(args):
        nope, pe, seen = args  # [B, block, H, nope|rope], [B, block]
        q_lat = jnp.einsum("bqhd,hcd->bqhc", nope, wk_b)
        q = latent_query(q_lat, pe, rows.shape[-1])
        scores = jnp.einsum(
            "bqhd,btd->bhqt", q, rows, preferred_element_type=jnp.float32,
        ) * scale
        mask = (key_at[None, None, :] < seen[:, :, None])[:, None]
        scores = jnp.where(mask, scores, NEG_INF)  # mask [B, 1, block, T]
        weights = jax.nn.softmax(scores, axis=-1)
        weights = jnp.where(mask, weights, 0.0)
        o_lat = jnp.einsum(
            "bhqt,btc->bqhc", weights.astype(rows.dtype),
            rows[..., :latent], preferred_element_type=jnp.float32,
        ).astype(rows.dtype)
        return jnp.einsum("bqhc,hcd->bqhd", o_lat, wv_b)

    if num_blocks == 1:
        out = one((q_nope, q_pe, visible))
    else:
        out = jax.lax.map(one, (blocks(q_nope), blocks(q_pe), blocks(visible)))
        out = jnp.swapaxes(out, 0, 1).reshape(
            (batch, num_blocks * block) + out.shape[3:]
        )
    return out[:, :queries].astype(q_nope.dtype)


def _mla_decode_kernel(
    lens_ref,    # SMEM scalar-prefetch [S] int32
    layer_ref,   # SMEM scalar-prefetch [1] int32 — index maps only
    q_ref,       # VMEM [1, H, row]: q~ | q_pe | zeros
    kv_ref,      # VMEM [1, block_k, row]: key AND value tile
    out_ref,     # VMEM [1, H, latent]
    m_scratch,   # VMEM [H, 128] f32 — running row max
    l_scratch,   # VMEM [H, 128] f32 — running row sum
    acc_scratch,  # VMEM [H, latent] f32
    *,
    scale: float,
    block_k: int,
    latent: int,
):
    del layer_ref
    s_i = pl.program_id(0)
    j = pl.program_id(1)
    num_blocks = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    length = lens_ref[s_i]

    @pl.when(j < _num_valid_blocks(length, block_k))
    def _compute():
        q = q_ref[0]    # [H, latent + rope]
        kv = kv_ref[0]  # [block_k, latent + rope]
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale       # [H, block_k]
        cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cols < length
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scratch[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_scratch[:] = jnp.broadcast_to(
            l_scratch[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_scratch.shape,
        )
        pv = jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :latent], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )               # [H, latent]
        acc_scratch[:] = acc_scratch[:] * alpha + pv
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)

    @pl.when(j == num_blocks - 1)
    def _finalize():
        l = l_scratch[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = (acc_scratch[:] / l_safe).astype(out_ref.dtype)


def mla_decode_shapes_ok(max_len: int, latent: int, row: int) -> bool:
    """Static requirements of the kernel: a block size dividing the
    allocated length, a row of whole lanes, and a value slice that ends
    on a lane boundary."""
    return (
        pick_block_k(max_len) is not None
        and latent % 128 == 0 and row % 128 == 0
    )


def use_mla_decode(max_len: int, latent: int, row: int) -> bool:
    from langstream_tpu.ops.flash_attention import on_tpu

    return on_tpu() and mla_decode_shapes_ok(max_len, latent, row)


def mla_decode_attention(
    q: jnp.ndarray,        # [S, H, row]: ``latent_query``, one a slot
    cache: jnp.ndarray,    # [L, S, T, row] stacked latents
    lengths: jnp.ndarray,  # [S] valid rows incl. the new token
    layer: jnp.ndarray,    # scalar int32 — which slab of the stack
    *,
    latent: int,
    scale: float,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``o_lat`` [S, H, latent] over slab ``layer`` of the stacked cache,
    HBM traffic proportional to the live context, the latent read once."""
    slots, heads, width = q.shape
    num_layers, max_len = cache.shape[0], cache.shape[2]
    block_k = block_k or pick_block_k(max_len)
    if block_k is None:
        raise ValueError(f"no kv block size divides max_len={max_len}")
    num_blocks = max_len // block_k
    lengths = lengths.astype(jnp.int32)
    layer_arr = jnp.reshape(jnp.asarray(layer, dtype=jnp.int32), (1,))

    def kv_index(s, j, lens, lyr):
        # dead blocks clamp to the last live one: the mapped indices
        # repeat, so the pipeline skips their DMA
        last = _num_valid_blocks(lens[s], block_k) - 1
        return (lyr[0] * slots + s, jnp.minimum(j, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, num_blocks),
        in_specs=[
            pl.BlockSpec((1, heads, width), lambda s, j, lens, lyr: (s, 0, 0)),
            pl.BlockSpec((1, block_k, width), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, heads, latent), lambda s, j, lens, lyr: (s, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((heads, 128), jnp.float32),
            pltpu.VMEM((heads, 128), jnp.float32),
            pltpu.VMEM((heads, latent), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _mla_decode_kernel, scale=scale, block_k=block_k, latent=latent
        ),
        name="mla_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, heads, latent), q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * slots * heads * max_len * (width + latent),
            bytes_accessed=(
                slots * max_len * width * cache.dtype.itemsize
                + (q.size + slots * heads * latent) * q.dtype.itemsize
            ),
            transcendentals=slots * heads * max_len,
        ),
        interpret=interpret,
    )(
        lengths, layer_arr, q,
        # layer and slot merge into one leading axis (a bitcast): slab
        # ``layer``'s slot s is row ``layer * S + s``
        cache.reshape(num_layers * slots, max_len, width),
    )
