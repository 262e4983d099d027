"""Lightning (linear) attention: a decayed outer-product state a head.

Per head ``j`` with decay ``lam_j = exp(-s_j)``: ``S_t = lam_j S_{t-1} +
k_t^T v_t`` (``S`` is ``[D, D]`` float32, zero before the first token) and
``o_t = (q_t * scale) S_t``. No softmax, no normaliser: the state is the
whole memory of the past, so it is NOT addressed by position and a slot
that is reused has to start from zeros.

Three forms of the same recurrence:

- :func:`lightning_recurrence`: the recurrence written as one, a
  ``lax.scan`` over positions in float32 (the oracle of the tests);
- :func:`lightning_prefill_attention`: a window of ``T`` tokens that
  starts from a carried state, chunk-wise (``C`` tokens a chunk: the
  causal products inside a chunk weighted by ``lam^(i-j)``, plus the
  carried state's share ``lam^(i+1) q_i S``, then the state moved on by
  the chunk). On TPU the Pallas kernel ``lightning_prefill`` (grid
  ``(row, head, chunk)``, the chunk axis sequential with the state in
  VMEM scratch; q, k, v and the output are read and written as column
  blocks of the flattened ``[B, T, H * D]`` projections, so nothing is
  transposed); the same arithmetic in ``jax.numpy`` elsewhere. Rows are
  right-padded: positions at or past a row's length neither feed the
  state nor decay it.
- :func:`lightning_decode_attention`: one token a slot against the
  STACKED state ``[L, S, H, D, D]``, updated in place. On TPU the Pallas
  kernel ``lightning_decode`` (grid ``(slot, head group)``; the state
  block aliased in to out, read once and written once a step); an einsum
  elsewhere. A slot that only rides along keeps every bit of its state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_CHUNKS = (256, 128, 64, 32, 16, 8)
_HIGHEST = jax.lax.Precision.HIGHEST


def decay_slopes(heads: int) -> np.ndarray:
    """``s_j = 2^(-8 j / heads)``, ``j = 1..heads`` (Lightning
    Attention's ALiBi-style slopes); the decay is ``exp(-s_j)``."""
    return np.asarray(
        [2.0 ** (-8.0 * j / heads) for j in range(1, heads + 1)], np.float32
    )


def pick_chunk(seq: int):
    return next((c for c in _CHUNKS if seq % c == 0), None)


def lightning_recurrence(q, k, v, state, slopes, lengths, scale):
    """The recurrence, a position at a time, in float32: q, k, v ``[B, T,
    H, D]``, state ``[B, H, D, D]``, lengths ``[B]`` (positions past a
    row's length change nothing). Returns (o ``[B, T, H, D]`` float32,
    the state after each row's last valid token)."""
    lam = jnp.exp(-jnp.asarray(slopes, jnp.float32))[None, :, None, None]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))

    def step(state, inputs):
        t, q_t, k_t, v_t = inputs
        moved = lam * state + jnp.einsum("bhd,bhe->bhde", k_t, v_t)
        state = jnp.where((t < lengths)[:, None, None, None], moved, state)
        return state, jnp.einsum("bhd,bhde->bhe", q_t * scale, state)

    seq = q.shape[1]
    state, out = jax.lax.scan(
        step, state.astype(jnp.float32),
        (jnp.arange(seq), *(x.swapaxes(0, 1) for x in (q, k, v))),
    )
    return out.swapaxes(0, 1), state


# --------------------------------------------------------------------- #
# prefill: a window from a carried state, chunk-wise
# --------------------------------------------------------------------- #
def _chunk_terms(q, k, v, state, slope, valid, scale):
    """One chunk of one head, the arithmetic both forms share: q, k, v
    ``[C, D]``, state ``[D, D]`` float32, slope ``[1, 1]``, valid the
    chunk's count of real tokens (an int32 scalar). Returns (o ``[C, D]``
    float32, the state after the chunk's valid tokens)."""
    chunk = q.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    gap = (rows - cols).astype(jnp.float32)
    within = jnp.where(
        (rows >= cols) & (cols < valid), jnp.exp(-slope * gap), 0.0
    )
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    intra = jnp.dot(
        (scores * within).astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    at = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    carried = jnp.dot(
        q.astype(jnp.float32) * (jnp.exp(-slope * (at + 1)) * scale), state,
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )
    # the count as a vector: a kernel's scalar unit does no float work
    count = (jnp.zeros((1, 1), jnp.int32) + valid).astype(jnp.float32)
    left = jnp.where(at < valid, jnp.exp(-slope * (count - 1.0 - at)), 0.0)
    moved = jnp.exp(-slope * count) * state + jnp.dot(
        (k.astype(jnp.float32) * left).T, v.astype(jnp.float32),
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )
    return intra + carried, moved


def _prefill_kernel(lens_ref, slope_ref, q_ref, k_ref, v_ref, state_ref,
                    out_ref, final_ref, scratch, *, scale, chunk):
    b, c = pl.program_id(0), pl.program_id(2)

    @pl.when(c == 0)
    def _start():
        scratch[:] = state_ref[0, 0]

    valid = jnp.clip(lens_ref[b] - c * chunk, 0, chunk)
    out, moved = _chunk_terms(
        q_ref[0], k_ref[0], v_ref[0], scratch[:], slope_ref[0][:, :1],
        valid, scale,
    )
    out_ref[0] = out.astype(out_ref.dtype)
    scratch[:] = moved

    @pl.when(c == pl.num_programs(2) - 1)
    def _finish():
        final_ref[0, 0] = scratch[:]


def _prefill_pallas(q, k, v, state, slopes, lengths, scale, chunk, interpret):
    batch, seq, width = q.shape
    heads, dim = state.shape[1], state.shape[2]
    column = pl.BlockSpec((1, chunk, dim), lambda b, h, c, lens: (b, c, h))
    whole = pl.BlockSpec((1, 1, dim, dim), lambda b, h, c, lens: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, heads, seq // chunk),
        in_specs=[
            pl.BlockSpec((1, 1, 128), lambda b, h, c, lens: (h, 0, 0)),
            column, column, column, whole,
        ],
        out_specs=[column, whole],
        scratch_shapes=[pltpu.VMEM((dim, dim), jnp.float32)],
    )
    flops = batch * heads * seq * (4 * chunk * dim + 4 * dim * dim)
    return pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, chunk=chunk),
        name="lightning_prefill",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq, width), q.dtype),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=4 * q.size * q.dtype.itemsize + 8 * state.size,
            transcendentals=batch * heads * seq * chunk,
        ),
        interpret=interpret,
    )(
        # a slope a head as a row of lanes, where the kernel reads a vector
        lengths.astype(jnp.int32),
        jnp.broadcast_to(
            jnp.asarray(slopes, jnp.float32)[:, None, None], (heads, 1, 128)
        ),
        q, k, v, state,
    )


def _prefill_xla(q, k, v, state, slopes, lengths, scale, chunk):
    batch, seq, width = q.shape
    heads, dim = state.shape[1], state.shape[2]
    chunks = seq // chunk

    def split(x):  # [B, T, H * D] -> [chunks, B, H, C, D]
        return x.reshape(batch, chunks, chunk, heads, dim).transpose(1, 0, 3, 2, 4)

    terms = jax.vmap(jax.vmap(
        functools.partial(_chunk_terms, scale=scale),
        in_axes=(0, 0, 0, 0, 0, None),
    ))
    slope = jnp.broadcast_to(
        jnp.asarray(slopes, jnp.float32)[None, :, None, None],
        (batch, heads, 1, 1),
    )

    def step(state, inputs):
        index, q_c, k_c, v_c = inputs
        valid = jnp.clip(lengths - index * chunk, 0, chunk).astype(jnp.int32)
        out, state = terms(q_c, k_c, v_c, state, slope, valid)
        return state, out

    state, out = jax.lax.scan(
        step, state, (jnp.arange(chunks), split(q), split(k), split(v))
    )
    out = out.transpose(1, 0, 3, 2, 4).reshape(batch, seq, width)
    return out.astype(q.dtype), state


def lightning_prefill_attention(q, k, v, state, slopes, lengths, *, scale,
                                kernel: bool, interpret: bool = False):
    """A window from a carried state: q, k, v ``[B, T, H * D]`` (the
    flattened projections, heads side by side), state ``[B, H, D, D]``
    float32, lengths ``[B]`` real tokens a row. Returns (o ``[B, T, H *
    D]`` in q's dtype, the state after each row's last real token)."""
    chunk = pick_chunk(q.shape[1])
    if chunk is None:
        raise ValueError(f"no chunk size divides a window of {q.shape[1]}")
    if kernel:
        return _prefill_pallas(
            q, k, v, state, slopes, lengths, scale, chunk, interpret
        )
    return _prefill_xla(q, k, v, state, slopes, lengths, scale, chunk)


# --------------------------------------------------------------------- #
# decode: one token a slot against the stacked state, in place
# --------------------------------------------------------------------- #
def _decode_kernel(layer_ref, active_ref, lam_ref, qt_ref, kt_ref, v_ref,
                   state_ref, out_ref, moved_ref, *, group):
    del layer_ref
    live = active_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _step():
        for h in range(group):
            k_col = kt_ref[0, 0][:, h:h + 1]          # [D, 1]
            q_col = qt_ref[0, 0][:, h:h + 1]
            v_row = v_ref[0][h:h + 1, :]              # [1, D]
            moved = lam_ref[h:h + 1, :] * state_ref[0, h] + k_col * v_row
            moved_ref[0, h] = moved
            out_ref[0, h:h + 1, :] = jnp.sum(
                q_col * moved, axis=0, keepdims=True
            ).astype(out_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _ride():
        moved_ref[...] = state_ref[...]
        out_ref[...] = jnp.zeros_like(out_ref)


def _decode_pallas(q, k, v, stack, layer, active, slopes, scale, interpret):
    slots, heads, dim = q.shape
    layers = stack.shape[0]
    group = 8 if heads % 8 == 0 else heads
    lam = jnp.broadcast_to(
        jnp.exp(-jnp.asarray(slopes, jnp.float32))[:, None], (heads, dim)
    )
    # q and k go in transposed, ``[S, H / group, D, group]`` float32: a
    # head's values are then a lane slice that broadcasts along lanes, and
    # the outer product and the read-out are plain vector work
    def transposed(x):
        return x.astype(jnp.float32).reshape(
            slots, heads // group, group, dim
        ).transpose(0, 1, 3, 2)

    q_t, k_t = transposed(q) * scale, transposed(k)
    columns = pl.BlockSpec(
        (1, 1, dim, group), lambda s, g, lyr, act: (s, g, 0, 0)
    )
    rows = pl.BlockSpec((1, group, dim), lambda s, g, lyr, act: (s, g, 0))
    slab = pl.BlockSpec(
        (1, group, dim, dim),
        lambda s, g, lyr, act: (lyr[0] * slots + s, g, 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, heads // group),
        in_specs=[
            pl.BlockSpec((group, dim), lambda s, g, lyr, act: (g, 0)),
            columns, columns, rows, slab,
        ],
        out_specs=[rows, slab],
    )
    out, moved = pl.pallas_call(
        functools.partial(_decode_kernel, group=group),
        name="lightning_decode",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((slots, heads, dim), jnp.float32),
            jax.ShapeDtypeStruct((layers * slots, heads, dim, dim), jnp.float32),
        ],
        input_output_aliases={6: 1},
        cost_estimate=pl.CostEstimate(
            flops=4 * slots * heads * dim * dim,
            bytes_accessed=8 * slots * heads * dim * dim,
            transcendentals=0,
        ),
        interpret=interpret,
    )(
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
        active.astype(jnp.int32), lam, q_t, k_t, v.astype(jnp.float32),
        stack.reshape(layers * slots, heads, dim, dim),
    )
    return out, moved.reshape(stack.shape)


def lightning_decode_attention(q, k, v, stack, layer, active, slopes, *,
                               scale, kernel: bool, interpret: bool = False):
    """One token a slot: q, k, v ``[S, H, D]``, the stacked state ``[L, S,
    H, D, D]`` float32, ``layer`` the slab, ``active`` ``[S]`` bool (a
    slot that rides along keeps its state and reads zeros). Returns (o
    ``[S, H, D]`` float32, the stack with the slab moved on a token)."""
    if kernel:
        return _decode_pallas(
            q, k, v, stack, layer, active, slopes, scale, interpret
        )
    lam = jnp.exp(-jnp.asarray(slopes, jnp.float32))[None, :, None, None]
    state = stack[layer]
    moved = lam * state + jnp.einsum(
        "shd,she->shde", k.astype(jnp.float32), v.astype(jnp.float32)
    )
    moved = jnp.where(active[:, None, None, None], moved, state)
    out = jnp.einsum(
        "shd,shde->she", q.astype(jnp.float32) * scale, moved,
        precision=_HIGHEST,
    )
    out = jnp.where(active[:, None, None], out, 0.0)
    return out, stack.at[layer].set(moved)


def lightning_shapes_ok(dim: int) -> bool:
    """What the two kernels need of a head: whole 128-lane rows."""
    return dim % 128 == 0
