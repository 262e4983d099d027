"""Pallas TPU flash attention for causal prefill.

The plain-XLA prefill attention (``ops/attention.py``) materializes the
O(Tq·Ts) score matrix in HBM. For long prompts that dominates HBM traffic,
so this kernel computes attention blockwise in VMEM with the online-softmax
recurrence: the score tile, the softmax statistics (running max / running
sum), and the output accumulator all live in VMEM scratch; HBM sees only
q/k/v tile reads and one output tile write per q block.

Kernel layout (the canonical TPU flash schedule):

- grid = (batch, q_heads, steps): a row's steps are its (q block, k
  block) pairs, read from a scalar-prefetched table (``_step_table``),
  a q block's k blocks consecutive; the last grid axis is innermost and
  sequential on TPU, so VMEM scratch carries the online softmax state
  across the k blocks of one q block. The axis is as long as a full
  row's causal pairs, the most any row needs.
- q/k/v tiles are MXU-shaped ([block, head_dim], 128-aligned); the two
  matmuls (q·kᵀ and p·v) run on the MXU in the input dtype with f32
  accumulation; masking and the softmax recurrence run on the VPU in f32.
- GQA is folded into the k/v BlockSpec index maps (query head h reads kv
  head h // group) — no materialized head repetition.
- per-batch valid lengths ride in SMEM (right-padding mask), and every
  step is classed from them, its position against the diagonal and the
  window (``_block_class``): empty (above the diagonal, outside every
  row's window, a q block at or past the row's length, or padding past
  the row's last pair) computes nothing, and its blocks are the ones
  the step before it held, so it fetches nothing new either; full
  (wholly below the diagonal, inside the length and the window) runs
  the body without a mask; partial (the diagonal's block, the length's,
  a window's edge) runs the masked body. The table gives a row only
  its live pairs, and one step to each q block past its length, which
  writes that block's zeros: rows at or past the length read zeros.

Reference parity: this replaces the HBM-bound attention inside what the
reference would run as a remote model call (it has no kernels of its own —
`langstream-agents/langstream-ai-agents/.../OpenAICompletionService.java:52`
delegates to a provider); the kernel is the TPU-native interior of the
`jax-local` completions service.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def compat_shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without replication checking — the one call
    every sharded kernel wrapper in ``ops/`` routes through."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _kv_span(q_start, length, window, block_q: int, block_k: int, xp=jnp):
    """The k blocks a q block starting at ``q_start`` reads, ``first`` to
    ``last`` inclusive: none past the diagonal or the row's ``length``,
    none before every row's window (``window`` <= 0: no window). A q block
    at or past the length reads none; its span is then the row's last k
    block alone, the one the step before it held, so that its one step
    fetches nothing new. ``xp`` is ``jnp`` in the kernel and ``np`` on
    the host."""
    last = xp.maximum(xp.minimum(q_start + block_q, length) - 1, 0) // block_k
    first = xp.where(
        window > 0, xp.maximum(q_start - window + 1, 0) // block_k, 0
    )
    return xp.where(q_start < length, first, last), last


def _block_class(length, window, q_start, kj, *, block_q: int, block_k: int):
    """What the step of q block ``q_start`` and k block ``kj`` needs, as
    ``(full, partial)``. Full: the block lies wholly below the diagonal,
    inside the length and inside every row's window, so its mask is all
    ones and the body runs unmasked. Partial: any other block of the span
    (the diagonal's, the length's, a window's edge), which runs the masked
    body. Neither: the step is empty and computes nothing."""
    first, last = _kv_span(q_start, length, window, block_q, block_k)
    live = (q_start < length) & (first <= kj) & (kj <= last)
    k_start = kj * block_k
    full = (
        live
        & (k_start + block_k - 1 <= q_start)
        & (k_start + block_k <= length)
        & ((window <= 0) | (k_start >= q_start + block_q - window))
    )
    return full, live & jnp.logical_not(full)


def _flash_body(
    lengths_ref,  # SMEM [B] — valid length per batch row (scalar prefetch)
    window_ref,   # SMEM [1] — sliding window (0 = full attention)
    steps_ref,    # SMEM [B, 3, P] — a step's q block, k block, and 1 if
                  # the step is the row's (0: padding past its last)
    q_ref,        # VMEM [1, 1, block_q, d]
    k_ref,        # VMEM [1, 1, block_k, d] (int8 with scales)
    v_ref,        # VMEM [1, 1, block_k, dv] (dv = d but for latent
                  # attention's expanded heads: keys 192, values 128)
    scale_refs,   # None, or VMEM [1, 1, 1, block_k] f32 k and v scales
    out_ref,      # VMEM [1, 1, block_q, dv]
    m_scratch,    # VMEM [block_q, 128] f32 — running row max
    l_scratch,    # VMEM [block_q, 128] f32 — running row sum
    acc_scratch,  # VMEM [block_q, dv] f32 — unnormalized output
    *,
    scale: float,
    block_q: int,
    block_k: int,
    softcap: Optional[float],
):
    # program ids are read at the kernel's top level: inside a pl.when
    # body the interpreter has no rule for them
    b = pl.program_id(0)
    step = pl.program_id(2)
    last_step = pl.num_programs(2) - 1
    qi = steps_ref[b, 0, step]
    kj = steps_ref[b, 1, step]
    # a q block's steps are consecutive: it starts where the step before
    # was another q block's, and ends where the step after is
    starts = (step == 0) | (steps_ref[b, 0, jnp.maximum(step - 1, 0)] != qi)
    ends = (step == last_step) | (
        steps_ref[b, 0, jnp.minimum(step + 1, last_step)] != qi
    )

    @pl.when(starts)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    q_start = qi * block_q
    length, window = lengths_ref[b], window_ref[0]
    full, partial = _block_class(
        length, window, q_start, kj, block_q=block_q, block_k=block_k
    )
    own = steps_ref[b, 2, step] > 0
    full, partial = full & own, partial & own

    def attend(masked: bool):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        if scale_refs is not None:
            k, v = k.astype(q.dtype), v.astype(q.dtype)  # int8: exact in bf16
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        if scale_refs is not None:
            s = s * (scale_refs[0][0, 0] * scale)  # fold k scales per row
        else:
            s = s * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        if masked:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            mask = jnp.logical_and(cols <= rows, cols < length)
            mask = jnp.logical_and(
                mask, (window <= 0) | (cols > rows - window)
            )
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[:, :1]                      # [block_q, 1]
        row_max = jnp.max(s, axis=-1, keepdims=True)   # [block_q, 1]
        m_new = jnp.maximum(m_prev, row_max)
        p = jnp.exp(s - m_new)
        if masked:
            # p is zeroed (not just -inf shifted) so fully-masked rows stay 0
            p = p * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)                # [block_q, 1]

        l_prev = l_scratch[:, :1]
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)

        if scale_refs is not None:
            p = p * scale_refs[1][0, 0]  # fold v scales into probs
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, dv]
        acc_scratch[:] = acc_scratch[:] * alpha + pv
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    pl.when(full)(lambda: attend(masked=False))
    pl.when(partial)(lambda: attend(masked=True))

    @pl.when(ends)
    def _finalize():
        l = l_scratch[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # rows at or past the length read zeros, whatever a straddling
        # block's softmax left in them
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, acc_scratch.shape, 0
        )
        out_ref[0, 0] = jnp.where(
            rows < length, acc_scratch[:] / l_safe, 0.0
        ).astype(out_ref.dtype)


def _flash_kernel(
    lengths_ref, window_ref, steps_ref, q_ref, k_ref, v_ref, *rest, **kw
):
    """The bf16 kernel: :func:`_flash_body` with no scales."""
    _flash_body(
        lengths_ref, window_ref, steps_ref, q_ref, k_ref, v_ref, None,
        *rest, **kw,
    )


def _flash_kernel_quant(
    lengths_ref, window_ref, steps_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
    *rest, **kw,
):
    """Int8-cache flash: k/v tiles stream from HBM as int8 (half the
    bandwidth of bf16 — the whole point), upcast in VMEM (int8 values
    are EXACTLY representable in bf16, so the MXU sees the same values
    the XLA quant path does), and the per-(position, head) scales fold
    the way ``ops/attention.py`` folds them: k_scale multiplies the
    score AFTER the q·kᵀ contraction, v_scale folds into the probs
    BEFORE p·v — neither contraction ever touches a dequantized
    cache-sized tensor."""
    _flash_body(
        lengths_ref, window_ref, steps_ref, q_ref, k_ref, v_ref,
        (ks_ref, vs_ref), *rest, **kw,
    )


def _step_table(lengths, window, seq: int, block_q: int, block_k: int):
    """[B, 3, P] int32: for each row the (q block, k block) pairs the
    kernel visits, in order. A q block's live span comes whole
    (``_kv_span``); a q block with none, at or past the length, takes
    one step on its span, in which it writes its zeros; the steps past
    the row's last repeat that last pair and are marked padding, so that
    they fetch nothing and compute nothing. ``P``, the grid's steps a
    (row, head), is a full row's count of causal pairs, the most any row
    needs."""
    num_q = seq // block_q
    _, full_last = _kv_span(
        np.arange(num_q) * block_q, seq, 0, block_q, block_k, xp=np
    )
    total = int(np.sum(full_last + 1))
    q_start = jnp.arange(num_q, dtype=jnp.int32)[None, :] * block_q
    first, last = _kv_span(
        q_start, lengths[:, None], window, block_q, block_k
    )  # [B, num_q]
    count = last - first + 1  # one step for a q block past the length
    ends = jnp.cumsum(count, axis=1)                       # [B, num_q]
    step = jnp.arange(total, dtype=jnp.int32)[None, :]     # [1, P]
    qi = jnp.minimum(
        jnp.sum(step[:, :, None] >= ends[:, None, :], axis=2), num_q - 1
    ).astype(jnp.int32)                                     # [B, P]
    take = functools.partial(jnp.take_along_axis, indices=qi, axis=1)
    kj = jnp.minimum(take(first) + step - take(ends - count), take(last))
    own = (step < ends[:, -1:]).astype(jnp.int32)
    return jnp.stack([qi, kj, own], axis=1)


def _pallas_flash(
    q: jnp.ndarray,        # [B, H, T, D]
    k: jnp.ndarray,        # [B, KVH, T, D] (bf16, or int8 with scales)
    v: jnp.ndarray,
    lengths: jnp.ndarray,  # [B] int32
    *,
    block_q: int,
    block_k: int,
    interpret: bool,
    k_scale: Optional[jnp.ndarray] = None,  # [B, KVH, T] f32
    v_scale: Optional[jnp.ndarray] = None,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,   # scalar; None/0 = full
    scale: Optional[float] = None,
) -> jnp.ndarray:
    batch, heads, seq, dim = q.shape
    kv_heads = k.shape[1]
    v_dim = v.shape[3]  # the values' width; the keys' is the queries'
    group = heads // kv_heads
    scale = dim ** -0.5 if scale is None else scale
    quantized = k_scale is not None

    # lengths/window/steps ride as scalar-prefetch operands (whole arrays
    # in SMEM): a (1, 1) SMEM block over [B, 1] is refused by the Mosaic
    # lowering for any B > 1.
    lengths = lengths.astype(jnp.int32)
    window_arr = jnp.reshape(
        jnp.asarray(0 if window is None else window, dtype=jnp.int32), (1,)
    )
    steps = _step_table(lengths, window_arr[0], seq, block_q, block_k)
    grid = (batch, heads, steps.shape[2])

    def out_index(b, h, step, lens, win, steps):
        return (b, h, steps[b, 0, step], 0)

    # a q block past the length reads the row's last live one, so that
    # its step fetches nothing new
    def q_index(b, h, step, lens, win, steps):
        last = jnp.maximum(lens[b] - 1, 0) // block_q
        return (b, h, jnp.minimum(steps[b, 0, step], last), 0)

    def kv_index(b, h, step, lens, win, steps):
        return (b, h // group, steps[b, 1, step], 0)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, dim), q_index),
        pl.BlockSpec((1, 1, block_k, dim), kv_index),
        pl.BlockSpec((1, 1, block_k, v_dim), kv_index),
    ]
    operands = [q, k, v]
    kernel = _flash_kernel_quant if quantized else _flash_kernel
    if quantized:
        # scales ride as [B, KVH, 1, T]: Mosaic wants the block's
        # second-to-last dim a multiple of 8 or the whole axis, and a
        # (1, block_k) tile over [KVH, T] is neither
        scale_spec = pl.BlockSpec(
            (1, 1, 1, block_k),
            lambda b, h, step, lens, win, steps: (
                b, h // group, 0, steps[b, 1, step]
            ),
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale[:, :, None, :], v_scale[:, :, None, :]]
        kv_bytes = k.size + v.size + k_scale.size * 4 + v_scale.size * 4
    else:
        kv_bytes = (k.size + v.size) * k.dtype.itemsize

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, v_dim), out_index),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, v_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            kernel, scale=scale, block_q=block_q, block_k=block_k,
            softcap=softcap,
        ),
        name="flash_prefill_int8kv" if quantized else "flash_prefill",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, heads, seq, v_dim), q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * batch * heads * seq * seq * (dim + v_dim),
            bytes_accessed=(
                (q.size + batch * heads * seq * v_dim) * q.dtype.itemsize
                + kv_bytes
            ),
            transcendentals=batch * heads * seq * seq,
        ),
        interpret=interpret,
    )(lengths, window_arr, steps, *operands)


def flash_prefill_attention(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,  # [B, T, KVH, D] (bf16; int8 when scales given)
    v: jnp.ndarray,
    *,
    mask: Optional[jnp.ndarray] = None,   # [B, T] right-padded valid mask
    lengths: Optional[jnp.ndarray] = None,  # [B] (alternative to mask)
    k_scale: Optional[jnp.ndarray] = None,  # [B, T, KVH] — int8-cache mode
    v_scale: Optional[jnp.ndarray] = None,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,  # scalar; None/0 = full attn
    scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal flash attention over right-padded prompts ([B, T, H, D] in
    and out; ``v`` may be narrower than ``q`` and ``k``, and the output
    is as wide as ``v``: latent attention's expanded heads; rows at or
    past a prompt's length come back as zeros, and no block of them is
    computed). ``mask`` must be CONTIGUOUS right-padding (True for the
    first ``lengths[b]`` positions, False after) — it is collapsed to
    per-row lengths for the kernel's SMEM masking, so a non-contiguous
    (packed / loss-style) mask would be silently misapplied; use
    :func:`langstream_tpu.ops.attention.prefill_attention` for those.

    With ``k_scale``/``v_scale`` the kernel runs the int8-cache variant
    (k/v int8, per-(position, kv-head) scales — see
    :func:`_flash_kernel_quant`). ``softcap``/``window``/``scale`` carry
    the Gemma-2 mechanisms: logit capping, a (traced, per-layer) sliding
    window — blocks fully outside a row's window skip their compute —
    and the query_pre_attn_scalar score scale."""
    batch, seq, heads, dim = q.shape
    if lengths is None:
        lengths = (
            jnp.sum(mask.astype(jnp.int32), axis=-1)
            if mask is not None
            else jnp.full((batch,), seq, dtype=jnp.int32)
        )

    block_q, block_k, padded = _block_sizes(seq, block_q, block_k)

    # [B, T, H, D] → [B, H, T, D]; pad T to a block multiple (the length
    # mask keeps padded keys out of the softmax).
    def to_kernel_layout(x):
        x = jnp.swapaxes(x, 1, 2)
        if padded != seq:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, padded - seq), (0, 0)))
        return x

    def scales_layout(s):
        if s is None:
            return None
        s = jnp.swapaxes(s, 1, 2)  # [B, KVH, T]
        if padded != seq:
            s = jnp.pad(s, ((0, 0), (0, 0), (0, padded - seq)))
        return s.astype(jnp.float32)

    out = _pallas_flash(
        to_kernel_layout(q), to_kernel_layout(k), to_kernel_layout(v),
        lengths,
        block_q=block_q, block_k=block_k, interpret=interpret,
        k_scale=scales_layout(k_scale), v_scale=scales_layout(v_scale),
        softcap=softcap, window=window, scale=scale,
    )
    out = jnp.swapaxes(out, 1, 2)
    return out[:, :seq] if padded != seq else out


def flash_prefill_attention_quant(
    q: jnp.ndarray,        # [B, T, H, D]
    k: jnp.ndarray,        # [B, T, KVH, D] int8
    k_scale: jnp.ndarray,  # [B, T, KVH] f32
    v: jnp.ndarray,        # [B, T, KVH, D] int8
    v_scale: jnp.ndarray,  # [B, T, KVH] f32
    **kwargs,
) -> jnp.ndarray:
    """Causal flash prefill over an int8-quantized window (the cold half
    of `engine: {kv-quant: int8}`): same scale-folded algebra as
    :func:`langstream_tpu.ops.attention.chunk_attention_quant` with
    ``starts=0``, but the k/v tiles stream from HBM as int8 — quantized
    cold prefill keeps the flash HBM profile instead of falling back to
    the O(T²)-score XLA path. Thin
    argument-ordering wrapper over :func:`flash_prefill_attention` —
    its mask caveat (contiguous right-padding only) applies."""
    return flash_prefill_attention(
        q, k, v, k_scale=k_scale, v_scale=v_scale, **kwargs
    )


def flash_prefill_attention_sharded(
    q: jnp.ndarray,  # [B, T, H, D] — H sharded over ``axis_name``
    k: jnp.ndarray,  # [B, T, KVH, D] — KVH sharded over ``axis_name``
    v: jnp.ndarray,
    mesh,
    *,
    mask: Optional[jnp.ndarray] = None,
    lengths: Optional[jnp.ndarray] = None,
    k_scale: Optional[jnp.ndarray] = None,  # [B, T, KVH] — int8 mode
    v_scale: Optional[jnp.ndarray] = None,
    softcap: Optional[float] = None,
    window: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    axis_name: str = "tp",
    interpret: bool = False,
) -> jnp.ndarray:
    """Flash prefill under tensor parallelism.

    A Mosaic ``pallas_call`` has no SPMD partitioning rule, so it cannot
    sit inside a tp-sharded jit directly; ``shard_map`` over the head
    axis runs one independent kernel per shard — attention never mixes
    heads, so no collective is needed (the same per-shard layout the tp
    attention einsums produce). GQA stays consistent because query and
    kv heads shard by the same factor (``validate_mesh`` enforces
    divisibility). With ``k_scale``/``v_scale`` the int8-cache kernel
    runs per shard, the scales sharded over their kv-head axis. The
    (traced) ``window`` scalar rides as a replicated operand.
    """
    from jax.sharding import PartitionSpec as P

    batch = q.shape[0]
    if lengths is None:
        lengths = (
            jnp.sum(mask.astype(jnp.int32), axis=-1)
            if mask is not None
            else jnp.full((batch,), q.shape[1], dtype=jnp.int32)
        )
    head_spec = P(None, None, axis_name, None)
    scale_spec = P(None, None, axis_name)
    quantized = k_scale is not None
    window_arr = jnp.asarray(
        0 if window is None else window, dtype=jnp.int32
    )

    def local(q_l, k_l, v_l, lengths_l, window_l, *scales):
        return flash_prefill_attention(
            q_l, k_l, v_l, lengths=lengths_l, interpret=interpret,
            softcap=softcap, window=window_l, scale=scale,
            **(
                {"k_scale": scales[0], "v_scale": scales[1]}
                if scales else {}
            ),
        )

    in_specs = [head_spec, head_spec, head_spec, P(None), P()]
    operands = [q, k, v, lengths, window_arr]
    if quantized:
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    return compat_shard_map(
        local, mesh, tuple(in_specs), head_spec
    )(*operands)


def flash_prefill_attention_quant_sharded(
    q: jnp.ndarray,        # [B, T, H, D] — H sharded over ``axis_name``
    k: jnp.ndarray,        # [B, T, KVH, D] int8
    k_scale: jnp.ndarray,  # [B, T, KVH]
    v: jnp.ndarray,
    v_scale: jnp.ndarray,
    mesh,
    **kwargs,
) -> jnp.ndarray:
    """Int8 flash prefill under tensor parallelism — thin argument-
    ordering wrapper over :func:`flash_prefill_attention_sharded`."""
    return flash_prefill_attention_sharded(
        q, k, v, mesh, k_scale=k_scale, v_scale=v_scale, **kwargs
    )


def _round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def _block_sizes(seq: int, block_q: int, block_k: int) -> Tuple[int, int, int]:
    """The blocks the kernel runs a ``seq``-token call with, no longer
    than the call, and the length it pads the call to."""
    block_q = min(block_q, _round_up(seq, 128))
    block_k = min(block_k, _round_up(seq, 128))
    return block_q, block_k, _round_up(seq, max(block_q, block_k))


def attention_blocks(
    lengths: Sequence[int],
    seq: int,
    block_q: int = 256,
    block_k: int = 256,
    window: int = 0,
) -> Tuple[int, int]:
    """How often the kernel's skip engages, counted on the host: the
    (q block, k block) steps :func:`flash_prefill_attention` computes for
    prompts of ``lengths`` in a ``seq``-token call, and the steps of the
    causal bucket (what it would compute were every row ``seq`` long),
    each summed over the rows, per head and per layer, under the same
    ``window`` (0: none)."""
    block_q, block_k, padded = _block_sizes(seq, block_q, block_k)
    q_start = np.arange(padded // block_q)[None, :] * block_q

    def computed(lens) -> int:
        lens = np.asarray(lens, dtype=np.int64)[:, None]
        first, last = _kv_span(q_start, lens, window, block_q, block_k, xp=np)
        return int(np.sum(np.where(q_start < lens, last - first + 1, 0)))

    return computed(lengths), computed([seq] * len(lengths))


def on_tpu() -> bool:
    """True when the devices JAX holds are TPUs, judged by their
    ``device_kind`` (a plug-in may register a TPU under another platform
    name, so ``default_backend()`` alone could silently disable the
    kernels)."""
    try:
        devices = jax.devices()
    except RuntimeError:  # pragma: no cover — backend init failed
        return False
    return any("TPU" in (d.device_kind or "") for d in devices)


def use_flash(seq: int, dim: int) -> bool:
    """Flash pays off once the score matrix dwarfs the tiles: long enough
    sequence, MXU-aligned head_dim, and a real TPU backend."""
    return on_tpu() and seq >= 1024 and dim % 128 == 0
