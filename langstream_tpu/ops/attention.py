"""Attention ops for prefill and decode (GQA), XLA-first.

Decode attention over a static-length KV cache and causal prefill
attention. Plain einsum formulations — on TPU, XLA fuses the
softmax chain into the two matmuls and keeps them on the MXU; the Pallas
flash kernel (``ops/flash_attention.py``) takes over for long-sequence
prefill where the O(T²) materialization would spill HBM.

Conventions: q/k/v are [batch, seq, heads, head_dim]; the KV cache is
[batch, max_len, kv_heads, head_dim]; GQA repeats kv heads on the fly
(a gather XLA folds into the matmul, not a materialized repeat).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


def _group_query(q: jnp.ndarray, kv_heads: int) -> jnp.ndarray:
    """Reshape [B, T, H, D] → [B, T, KVH, G, D] grouping queries by their
    kv head (G = H // KVH)."""
    batch, seq, heads, dim = q.shape
    groups = heads // kv_heads
    return q.reshape(batch, seq, kv_heads, groups, dim)


def _cap_scores(scores: jnp.ndarray, softcap: Optional[float]) -> jnp.ndarray:
    """Logit softcapping (Gemma-2): cap·tanh(s/cap), applied BEFORE
    masking — matches the HF formulation."""
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    return scores


def prefill_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    mask: Optional[jnp.ndarray] = None,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Causal self-attention over a full (padded) prompt.

    q: [B, T, H, D], k/v: [B, T, KVH, D] → [B, T, H, D].
    ``mask`` [B, T] marks valid tokens (padding excluded). ``softcap``
    applies Gemma-style logit capping, ``window`` (traced scalar; 0 =
    full) restricts each query to the last ``window`` positions, and
    ``scale`` overrides the default head_dim**-0.5 (Gemma's
    query_pre_attn_scalar).
    """
    batch, seq, heads, dim = q.shape
    kv_heads = k.shape[2]
    scale = dim ** -0.5 if scale is None else scale
    qg = _group_query(q, kv_heads)  # [B, T, KVH, G, D]
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale  # [B, KVH, G, Tq, Ts]
    scores = _cap_scores(scores, softcap)
    causal = jnp.tril(jnp.ones((seq, seq), dtype=bool))
    if window is not None:
        rows = jnp.arange(seq)[:, None]
        cols = jnp.arange(seq)[None, :]
        in_window = (window <= 0) | (cols > rows - window)
        causal = jnp.logical_and(causal, in_window)
    allowed = causal[None, None, None]
    if mask is not None:
        allowed = jnp.logical_and(allowed, mask[:, None, None, None, :])
    scores = jnp.where(allowed, scores, -1e30)
    weights = _softmax(scores)
    out = jnp.einsum("bkgqs,bskd->bqkgd", weights.astype(v.dtype), v)
    # as wide as the values (latent attention's are narrower than its keys)
    return out.reshape(batch, seq, heads, v.shape[-1])


def _decode_valid(
    max_len: int,
    lengths: jnp.ndarray,
    window: Optional[jnp.ndarray],
) -> jnp.ndarray:
    """[B, T] validity for one-token decode: live rows, optionally
    restricted to the query's sliding window (query pos = lengths-1)."""
    pos = jnp.arange(max_len)[None, :]
    valid = pos < lengths[:, None]
    if window is not None:
        in_window = (window <= 0) | (
            pos > (lengths[:, None] - 1) - window
        )
        valid = jnp.logical_and(valid, in_window)
    return valid


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """One-token decode attention against the cache.

    q: [B, H, D] (the new token's queries), k/v_cache: [B, T, KVH, D],
    lengths: [B] number of valid cache entries (including the new token,
    already written at position lengths-1). Returns [B, H, D].
    """
    batch, heads, dim = q.shape
    max_len = k_cache.shape[1]
    kv_heads = k_cache.shape[2]
    groups = heads // kv_heads
    scale = dim ** -0.5 if scale is None else scale
    qg = q.reshape(batch, kv_heads, groups, dim)
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", qg.astype(jnp.float32), k_cache.astype(jnp.float32)
    ) * scale  # [B, KVH, G, T]
    scores = _cap_scores(scores, softcap)
    valid = _decode_valid(max_len, lengths, window)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    weights = _softmax(scores)
    out = jnp.einsum("bkgs,bskd->bkgd", weights.astype(v_cache.dtype), v_cache)
    return out.reshape(batch, heads, dim)


def chunk_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    starts: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Chunked prefill-at-offset attention against the cache.

    q: [B, T, H, D] — T new tokens per row whose global positions are
    ``starts[b] + t``; k/v_cache: [B, S, KVH, D] with the new tokens' KV
    already written at ``starts[b]..starts[b]+n-1``; lengths: [B] total
    valid cache entries (starts + suffix length). Query t attends
    causally to cache positions ``<= starts[b] + t``. Returns
    [B, T, H, D]. This is what makes a warm-session follow-up one
    bucketed dispatch instead of one decode dispatch per suffix token.
    """
    batch, seq, heads, dim = q.shape
    max_len = k_cache.shape[1]
    kv_heads = k_cache.shape[2]
    scale = dim ** -0.5 if scale is None else scale
    qg = _group_query(q, kv_heads)  # [B, Tq, KVH, G, D]
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k_cache.astype(jnp.float32)
    ) * scale  # [B, KVH, G, Tq, S]
    scores = _cap_scores(scores, softcap)
    pos_q = starts[:, None] + jnp.arange(seq)[None, :]       # [B, Tq]
    pos_s = jnp.arange(max_len)[None, None, :]               # [1, 1, S]
    allowed = (pos_s <= pos_q[:, :, None]) & (
        pos_s < lengths[:, None, None]
    )  # [B, Tq, S]
    if window is not None:
        allowed = allowed & (
            (window <= 0) | (pos_s > pos_q[:, :, None] - window)
        )
    scores = jnp.where(allowed[:, None, None, :, :], scores, -1e30)
    weights = _softmax(scores)
    out = jnp.einsum("bkgqs,bskd->bqkgd", weights.astype(v_cache.dtype), v_cache)
    return out.reshape(batch, seq, heads, dim)


def _softmax(scores: jnp.ndarray) -> jnp.ndarray:
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    exp = jnp.exp(scores)
    return exp / jnp.sum(exp, axis=-1, keepdims=True)


# ---------------------------------------------------------------------- #
# paged KV cache (kv_layout: paged)
# ---------------------------------------------------------------------- #
# The cache is a global block pool [num_blocks, block_size, kv_heads,
# head_dim] addressed through per-slot block tables [B, M] (M =
# max_seq // block_size): token position p of row b lives in pool block
# ``table[b, p // block_size]`` at offset ``p % block_size``. Block 0 is
# the null block — tables route padding and masked writes there, and no
# live length mask ever lets attention read it. The paths below GATHER a
# row-contiguous view via the table and reuse the dense attention math,
# so dense and paged layouts share one set of masking/softcap/window
# formulas. They are the REFERENCE ORACLE (``paged_kernel: reference``)
# for the fused Pallas kernel in ``ops/paged_attention.py``, which reads
# the tables inside its index maps and streams pool blocks HBM→VMEM
# directly — same masking formulas, no materialized gather copy.


def gather_blocks(pool: jnp.ndarray, block_tables: jnp.ndarray) -> jnp.ndarray:
    """[N, Bs, ...] pool + [B, M] tables → [B, M*Bs, ...] contiguous
    per-row view (a copy — the read side of the paged layout)."""
    view = pool[block_tables]  # [B, M, Bs, ...]
    return view.reshape(
        view.shape[0], view.shape[1] * view.shape[2], *view.shape[3:]
    )


def paged_write_rows(
    pool: jnp.ndarray,          # [N, Bs, ...]
    new: jnp.ndarray,           # [B, T, ...]
    block_tables: jnp.ndarray,  # [B, M]
    offsets: jnp.ndarray,       # [B] global position of each row's token 0
    valid: jnp.ndarray,         # [B, T] bool; False routes to the null block
) -> jnp.ndarray:
    """Scatter per-token rows into their table-addressed pool blocks.
    Works for any trailing shape (bf16/int8 values AND their scale
    leaves). Invalid rows — padding, masked decode slots — land in the
    null block, whose content is never read. Positions past the table's
    capacity (``pos // block_size >= M``) are routed through the null
    block the same way: relying on the take_along_axis index clamp
    would silently land them in the row's LAST real block, overwriting
    live rows another chain may still reference."""
    seq = new.shape[1]
    block_size = pool.shape[1]
    capacity = block_tables.shape[1]                           # M
    pos = offsets[:, None] + jnp.arange(seq)[None, :]          # [B, T]
    seq_block = (pos // block_size).astype(jnp.int32)
    blocks = jnp.take_along_axis(
        block_tables, jnp.clip(seq_block, 0, capacity - 1), axis=1
    )
    in_table = (seq_block >= 0) & (seq_block < capacity)
    blocks = jnp.where(valid & in_table, blocks, 0)
    return pool.at[blocks, pos % block_size].set(new.astype(pool.dtype))


def paged_decode_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """:func:`decode_attention` over a block pool: gather each row's
    blocks into a contiguous [B, M*Bs, KVH, D] view, then the dense
    formula (lengths mask out the tail, incl. any null-block rows)."""
    k_cache = gather_blocks(k_pool, block_tables)
    v_cache = gather_blocks(v_pool, block_tables)
    return decode_attention(
        q, k_cache, v_cache, lengths,
        softcap=softcap, window=window, scale=scale,
    )


def paged_chunk_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    starts: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """:func:`chunk_attention` over a block pool (prefill-at-offset for
    paged slots — the path that reads a SHARED cached prefix written by
    some other request's prefill)."""
    k_cache = gather_blocks(k_pool, block_tables)
    v_cache = gather_blocks(v_pool, block_tables)
    return chunk_attention(
        q, k_cache, v_cache, starts, lengths,
        softcap=softcap, window=window, scale=scale,
    )


def paged_decode_attention_quant(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,     # [N, Bs, KVH, D] int8
    k_scale: jnp.ndarray,    # [N, Bs, KVH] f32
    v_pool: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Int8-pool twin of :func:`paged_decode_attention` (scale leaves
    gather through the same tables)."""
    return decode_attention_quant(
        q,
        gather_blocks(k_pool, block_tables),
        gather_blocks(k_scale, block_tables),
        gather_blocks(v_pool, block_tables),
        gather_blocks(v_scale, block_tables),
        lengths,
        softcap=softcap, window=window, scale=scale,
    )


def paged_chunk_attention_quant(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    k_scale: jnp.ndarray,
    v_pool: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_tables: jnp.ndarray,
    starts: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Int8-pool twin of :func:`paged_chunk_attention`."""
    return chunk_attention_quant(
        q,
        gather_blocks(k_pool, block_tables),
        gather_blocks(k_scale, block_tables),
        gather_blocks(v_pool, block_tables),
        gather_blocks(v_scale, block_tables),
        starts, lengths,
        softcap=softcap, window=window, scale=scale,
    )


# ---------------------------------------------------------------------- #
# int8 KV-cache variants
# ---------------------------------------------------------------------- #
# The cache stores int8 values with a per-(position, kv-head) scale.
# Per-row scales COMMUTE with both attention contractions, so the MXU
# streams the bare int8 cache and the scales touch only
# activation-sized arrays — the same algebra that fixed the weight
# dequant in round 3 (quant.qeinsum):
#   QK: q · (K_q * s)ᵀ  = (q · K_qᵀ) * s      (s indexes [pos, head] —
#                                              the score layout)
#   PV: p · (V_q * s)   = (p * s) · V_q       (s folds into the probs)


def quantize_kv(x: jnp.ndarray):
    """Per-row symmetric int8: x [..., D] → (int8 values, f32 scales
    [...]) with scale = amax/127 over the head dim."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    values = jnp.round(
        x.astype(jnp.float32) / scale[..., None]
    ).astype(jnp.int8)
    return values, scale


def decode_attention_quant(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,    # [B, S, KVH, D] int8
    k_scale: jnp.ndarray,    # [B, S, KVH] f32
    v_cache: jnp.ndarray,
    v_scale: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """:func:`decode_attention` over an int8 cache (see algebra above)."""
    batch, heads, dim = q.shape
    max_len = k_cache.shape[1]
    kv_heads = k_cache.shape[2]
    groups = heads // kv_heads
    scale = dim ** -0.5 if scale is None else scale
    qg = q.reshape(batch, kv_heads, groups, dim)
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", qg.astype(jnp.float32),
        k_cache.astype(jnp.float32),
    )
    scores = scores * k_scale.transpose(0, 2, 1)[:, :, None, :] * scale
    scores = _cap_scores(scores, softcap)
    valid = _decode_valid(max_len, lengths, window)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    weights = _softmax(scores)
    weights = weights * v_scale.transpose(0, 2, 1)[:, :, None, :]
    out = jnp.einsum(
        "bkgs,bskd->bkgd", weights, v_cache.astype(jnp.float32)
    )
    return out.reshape(batch, heads, dim).astype(q.dtype)


def chunk_attention_quant(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,    # [B, S, KVH, D] int8
    k_scale: jnp.ndarray,    # [B, S, KVH] f32
    v_cache: jnp.ndarray,
    v_scale: jnp.ndarray,
    starts: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """:func:`chunk_attention` over an int8 cache."""
    batch, seq, heads, dim = q.shape
    max_len = k_cache.shape[1]
    kv_heads = k_cache.shape[2]
    scale = dim ** -0.5 if scale is None else scale
    qg = _group_query(q, kv_heads)
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
        k_cache.astype(jnp.float32),
    )
    scores = scores * k_scale.transpose(0, 2, 1)[:, :, None, None, :] * scale
    scores = _cap_scores(scores, softcap)
    pos_q = starts[:, None] + jnp.arange(seq)[None, :]
    pos_s = jnp.arange(max_len)[None, None, :]
    allowed = (pos_s <= pos_q[:, :, None]) & (
        pos_s < lengths[:, None, None]
    )
    if window is not None:
        allowed = allowed & (
            (window <= 0) | (pos_s > pos_q[:, :, None] - window)
        )
    scores = jnp.where(allowed[:, None, None, :, :], scores, -1e30)
    weights = _softmax(scores)
    weights = weights * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", weights, v_cache.astype(jnp.float32)
    )
    return out.reshape(batch, seq, heads, dim).astype(q.dtype)
