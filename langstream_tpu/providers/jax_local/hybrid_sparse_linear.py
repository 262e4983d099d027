"""The hybrid decoder family on the dense layout: layers whose mixer is
linear attention with a recurrent state, beside layers whose mixer is
block-sparse GQA, in an order the config gives layer by layer.

A config with ``mixers`` (one kind a layer: ``"lightning"`` or
``"sparse"``) and ``hybrid`` (:class:`model.HybridMixers`) set. Both kinds
share the block around them (``model._block``: pre-norm, mixer, ``wo``,
residual, pre-norm, SwiGLU, residual, with the config's residual scale)
and differ in weights and in state:

- **lightning** (``ops/lightning_attention.py``): q, k, v of ``heads x
  head_dim`` each; RMSNorm over the head dim of q and of k with a learned
  scale; rotary on q and k at absolute positions; per head ``S_t = lam
  S_{t-1} + k_t^T v_t``, ``o_t = (q_t / sqrt(d)) S_t``; RMSNorm of the
  concatenated ``o`` with a learned scale; ``o * sigmoid(W_g x)``.
  Its state is ``state: [lightning layers, S, heads, d, d]`` float32, NOT
  addressed by position: a prefill window at offset 0 starts from zeros
  whatever the slot held, a window at a later offset from what the window
  before it left.
- **sparse** (``ops/block_sparse_attention.py``): GQA without rotation,
  the same q/k norm, the same output gate; a query whose context is past
  ``dense_len`` attends the blocks a parameter-free selection over
  compressed keys keeps, any other densely. Its state is ``k``, ``v``:
  ``[sparse layers, S, kv_heads, T, d]`` and the compressed keys ``kc:
  [sparse layers, S, kv_heads, T / stride, d]``, all addressed by
  position and masked by it.

The parameters are one stack a RUN of consecutive layers of one kind
(``run<n>.*``, leaves ``[the run's layers, ...]``; :func:`runs_of`): the
layer loop scans a run's own leaves, and no program ever slices a larger
stack (a static slice of a weight stack is a copy of it, made once a
program and kept while it runs). One attend a kind serves the cold
prefill, the prefill at an offset (a cold prefill is one at offset 0) and
so a chunked prefill's windows (:func:`window_attends`); one a kind the
decode step (:func:`decode_attends`). The attends carry the cache's four
leaves and the selection's counters (blocks kept, blocks in context,
queries) as the loop's ``state``.

Random initialisation is the recipe ``int8-uniform`` of
``benchmark/reference/minicpm_sala.py``: int8 matmul weights with one
float32 scale an output channel, norm scales away from 1 (the q and k
norms' around 2, so that attention is peaked). The float form
(:func:`init_params`) is that, dequantised.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from langstream_tpu.ops import block_sparse_attention as sparse_ops
from langstream_tpu.ops import lightning_attention as lightning_ops
from langstream_tpu.ops.flash_attention import on_tpu
from langstream_tpu.ops.norms import rms_norm
from langstream_tpu.ops.rope import apply_rope
from langstream_tpu.parallel.mesh import L
from langstream_tpu.providers.jax_local.quant import QTensor, qeinsum

KINDS = ("sparse", "lightning")
CACHE = ("state", "k", "v", "kc")
# a layer's leaves by kind, in the order a layer's keys are drawn
# (``_LAYER_KEYS``); the matmuls are the int8 form's QTensors
MATMULS = ("wq", "wk", "wv", "wg", "wo", "w_gate", "w_up", "w_down")
_LAYER_KEYS = (
    "attn_norm", "wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm",
    "out_norm", "mlp_norm", "w_gate", "w_up", "w_down",
)


def layers_of(config, kind: str) -> List[int]:
    """Model indices of the layers of ``kind``."""
    return [i for i, mixer in enumerate(config.mixers) if mixer == kind]


def runs_of(config) -> List[Tuple[str, int, int, int]]:
    """The maximal runs of one kind, in model order: (kind, the model
    index of the run's first layer, its count of layers, the index of its
    first layer among the layers of its kind: where its state lies)."""
    runs, seen = [], dict.fromkeys(KINDS, 0)
    for index, kind in enumerate(config.mixers):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, index, 1, seen[kind]])
        seen[kind] += 1
    return [tuple(run) for run in runs]


def _shapes(config, kind: str) -> Dict[str, Tuple[int, ...]]:
    h, f = config.hidden_size, config.intermediate_size
    hybrid = config.hybrid
    if kind == "lightning":
        heads = kv_heads = hybrid.lightning_heads
        dim = hybrid.lightning_head_dim
    else:
        heads, kv_heads = config.num_heads, config.num_kv_heads
        dim = config.dims_per_head
    shapes = {
        "attn_norm": (h,), "wq": (h, heads * dim), "wk": (h, kv_heads * dim),
        "wv": (h, kv_heads * dim), "wg": (h, heads * dim),
        "wo": (heads * dim, h), "q_norm": (dim,), "k_norm": (dim,),
        "mlp_norm": (h,), "w_gate": (h, f), "w_up": (h, f), "w_down": (f, h),
    }
    if kind == "lightning":
        shapes["out_norm"] = (heads * dim,)
    return shapes


# --------------------------------------------------------------------- #
# parameters and cache
# --------------------------------------------------------------------- #
@partial(jax.jit, static_argnames=("shape",))
def _int8(key, shape):
    return jax.random.randint(key, shape, -127, 128, dtype=jnp.int8)


def _norm_scale(key, size: int, sharp: bool = False):
    """A norm's scale, away from 1: uniform in [0.5, 1.5); the q and k
    norms' (``sharp``) in [1.5, 2.5), so that a sparse layer's scores
    spread by about 4 and a dozen keys carry a query's attention. At 1
    some 4,000 of 10,000 keys share it, the layer's output is their
    average, next to nothing, and a comparison cannot tell the selection
    from dense attention (PERF.md section 6, PR 33)."""
    low = 1.5 if sharp else 0.5
    return jax.random.uniform(key, (size,), jnp.float32, low, low + 1.0)


def _matmul(key, shape, quantized: bool, dtype):
    """One matmul weight ``[in, out]``: int8 uniform in [-127, 127] with
    the scale ``1 / sqrt(in) / 127`` for every output channel."""
    values = _int8(key, shape)
    scale = 1.0 / math.sqrt(shape[0]) / 127.0
    if quantized:
        return QTensor(q=values, scale=jnp.full(shape[1:], scale, jnp.float32))
    return (values.astype(jnp.float32) * scale).astype(dtype)


def init_params(config, seed: int = 0, quantized: bool = False):
    """Random parameters by the recipe ``benchmark/reference/
    minicpm_sala.py`` states (its docstring, "Weights"): ``split(PRNGKey(
    seed), 4)`` gives embedding, head, final norm and the layers' root;
    layer ``l`` (its number in the model) draws its leaves from
    ``split(fold_in(root, l), 13)`` in ``_LAYER_KEYS``' order."""
    dtype = config.dtype
    h, v = config.hidden_size, config.vocab_size
    top = jax.random.split(jax.random.PRNGKey(seed), 4)
    params: Dict[str, Any] = {}
    for number, (kind, start, count, _) in enumerate(runs_of(config)):
        # a leaf at a time over the run's layers, so that what waits to
        # be stacked is one leaf's layers and never a whole layer's
        keys = [
            dict(zip(
                _LAYER_KEYS,
                jax.random.split(jax.random.fold_in(top[3], layer), 13),
            ))
            for layer in range(start, start + count)
        ]
        for name, shape in _shapes(config, kind).items():
            params[f"run{number}.{name}"] = jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves), *(
                    _matmul(layer[name], shape, quantized, dtype)
                    if name in MATMULS else _norm_scale(
                        layer[name], shape[0], sharp=name in ("q_norm", "k_norm")
                    )
                    for layer in keys
                )
            )
    params["embedding"] = (
        jax.random.normal(top[0], (v, h), dtype=dtype) * (1.0 / math.sqrt(h))
    ).astype(dtype)
    params["lm_head"] = _matmul(top[1], (h, v), quantized, dtype)
    params["final_norm"] = _norm_scale(top[2], h)
    return params


def quantize_params(params: Dict[str, Any], quantize) -> Dict[str, Any]:
    """The family's matmul leaves as int8 QTensors (idempotent)."""
    out = dict(params)
    for name, leaf in params.items():
        if (
            name.rpartition(".")[2] in MATMULS + ("lm_head",)
            and not isinstance(leaf, QTensor)
        ):
            out[name] = quantize(leaf)
    return out


def logical_axes(config) -> Dict[str, Any]:
    """Every leaf replicated: the family runs on one chip (the engine
    refuses a mesh)."""
    axes: Dict[str, Any] = {
        "embedding": L("vocab", "embed"), "lm_head": L("embed", "vocab"),
        "final_norm": L(None),
    }
    for number, (kind, *_) in enumerate(runs_of(config)):
        for name, shape in _shapes(config, kind).items():
            axes[f"run{number}.{name}"] = L("layers", *([None] * len(shape)))
    return axes


def num_params(config) -> int:
    total = 2 * config.vocab_size * config.hidden_size + config.hidden_size
    for kind in KINDS:
        total += len(layers_of(config, kind)) * sum(
            math.prod(shape) for shape in _shapes(config, kind).values()
        )
    return total


def init_cache(config, batch: int, max_len: int) -> Dict[str, jnp.ndarray]:
    hybrid = config.hybrid
    sparse, lightning = (len(layers_of(config, kind)) for kind in KINDS)
    rows = (sparse, batch, config.num_kv_heads)
    dim = config.dims_per_head
    return {
        "state": jnp.zeros(
            (lightning, batch, hybrid.lightning_heads,
             hybrid.lightning_head_dim, hybrid.lightning_head_dim),
            jnp.float32,
        ),
        "k": jnp.zeros(rows + (max_len, dim), config.dtype),
        "v": jnp.zeros(rows + (max_len, dim), config.dtype),
        "kc": jnp.zeros(
            rows + (sparse_ops.compressed_count(max_len, hybrid.selection), dim),
            config.dtype,
        ),
    }


def cache_logical_axes() -> Dict[str, Any]:
    return {
        "state": L("layers", "cache_batch", None, None, None),
        "k": L("layers", "cache_batch", None, "cache_sequence", None),
        "v": L("layers", "cache_batch", None, "cache_sequence", None),
        "kc": L("layers", "cache_batch", None, "cache_sequence", None),
    }


def zero_counters():
    """Blocks kept, blocks in context, queries: what a program's sparse
    layers add up and the engine puts on its spans."""
    return jnp.zeros((3,), jnp.int32)


def validate_params(config, params: Dict[str, Any]) -> None:
    wanted = [
        f"run{number}.{name}"
        for number, (kind, *_) in enumerate(runs_of(config))
        for name in _shapes(config, kind)
    ] + ["embedding", "lm_head", "final_norm"]
    missing = [name for name in wanted if name not in params]
    if missing:
        raise ValueError(f"params missing {missing}, required by the model config")


def layer_runs(config, params):
    """The layers as ``model._run_layers`` takes them: one ``(kind, the
    run's stacked layers, the index of its first layer in the kind's own
    stack of state, no routed experts)`` for every run. A layer is ``(attn_norm, the mixer's
    weights, wo, None, mlp_norm, None, SwiGLU weights)``."""
    validate_params(config, params)

    def stack(number, kind):
        mixer = ("wq", "wk", "wv", "wg", "q_norm", "k_norm") + (
            ("out_norm",) if kind == "lightning" else ()
        )
        leaf = lambda name: params[f"run{number}.{name}"]  # noqa: E731
        return (
            leaf("attn_norm"), tuple(leaf(n) for n in mixer), leaf("wo"),
            None, leaf("mlp_norm"), None,
            tuple(leaf(n) for n in ("w_gate", "w_up", "w_down")),
        )

    return [
        (kind, stack(number, kind), first, None)
        for number, (kind, _, _, first) in enumerate(runs_of(config))
    ]


# --------------------------------------------------------------------- #
# the two mixers
# --------------------------------------------------------------------- #
def _kernels(config, max_len: int) -> bool:
    """The Pallas kernels on TPU where their shapes hold (any shape under
    the interpret test hook); the same arithmetic in XLA otherwise."""
    return config.use_flash and (
        config.flash_interpret
        or on_tpu()
        and lightning_ops.lightning_shapes_ok(config.hybrid.lightning_head_dim)
        and sparse_ops.sparse_shapes_ok(
            max_len, config.dims_per_head, config.num_heads,
            config.num_kv_heads,
        )
    )


def _projected(config, normed, weights, heads, kv_heads, dim):
    """The mixers' shared input side on normed ``[B, T, h]``: q ``[B, T,
    heads, d]`` and k ``[B, T, kv_heads, d]`` normed over the head dim, v,
    and the output gate ``[B, T, heads * d]``."""
    wq, wk, wv, wg, q_norm, k_norm = weights[:6]
    lead = normed.shape[:2]
    q = qeinsum("bth,hd->btd", normed, wq).reshape(lead + (heads, dim))
    k = qeinsum("bth,hd->btd", normed, wk).reshape(lead + (kv_heads, dim))
    v = qeinsum("bth,hd->btd", normed, wv).reshape(lead + (kv_heads, dim))
    gate = jax.nn.sigmoid(qeinsum("bth,hd->btd", normed, wg))
    q = rms_norm(q, q_norm, config.norm_eps)
    k = rms_norm(k, k_norm, config.norm_eps)
    return q, k, v, gate


def _lightning_sides(config, normed, weights, freqs, positions):
    hybrid = config.hybrid
    heads, dim = hybrid.lightning_heads, hybrid.lightning_head_dim
    q, k, v, gate = _projected(config, normed, weights, heads, heads, dim)
    q = apply_rope(q, freqs, positions)
    k = apply_rope(k, freqs, positions)
    return q, k, v, gate


def _lightning_out(config, out, weights, gate):
    out = rms_norm(out.astype(gate.dtype), weights[6], config.norm_eps)
    return out * gate


def _drop_at(positions, valid, max_len: int):
    """Where a row is written: its position, or out of bounds (nothing is
    written) for padding, a riding slot or a position past the end."""
    return jnp.where(valid & (positions >= 0), positions, max_len)


def _rows_of(slot_ids, kv_heads: int):
    """``[B * kv_heads]``: where each (row's slot, kv head) lies on the
    merged ``slots x kv_heads`` axis of a stack seen as ``[L, S * KVH,
    ...]`` (a bitcast). Rows are then written and gathered with ONE index
    a row beside the position, as the GQA cache's are: nothing makes XLA
    take a layer's slab out of the stack."""
    return (slot_ids[:, None] * kv_heads + jnp.arange(kv_heads)[None, :]).reshape(-1)


def _merged(stack):
    return stack.reshape(stack.shape[0], -1, *stack.shape[3:])


def _write_rows(stack, layer, rows_at, positions, new):
    """``new [B, T, KVH, D]`` at ``positions [B, T]`` of each row's kv
    heads (``rows_at``: :func:`_rows_of`); a position past the end writes
    nothing."""
    batch, seq, kv_heads, dim = new.shape
    new = new.swapaxes(1, 2).reshape(batch * kv_heads, seq, dim)
    at = jnp.repeat(positions, kv_heads, axis=0)                # [B * KVH, T]
    return _merged(stack).at[layer, rows_at[:, None], at].set(
        new.astype(stack.dtype), mode="drop"
    ).reshape(stack.shape)


def _compress_into(kc, k_stack, layer, rows_at, first, count, until, sel):
    """Compressed keys ``first .. first + count`` (``first``, ``until``
    ``[B]``) of each row's slot, from the K rows as they now lie, into
    ``kc``; a window that does not end at or before ``until`` is not
    written."""
    kv_heads = kc.shape[2]
    index = first[:, None] + jnp.arange(count)[None, :]              # [B, n]
    whole = index * sel.kernel_stride + sel.kernel_size <= until[:, None]
    keys_at = jnp.minimum(
        index[:, :, None] * sel.kernel_stride
        + jnp.arange(sel.kernel_size)[None, None, :],
        k_stack.shape[3] - 1,
    )                                                                # [B, n, w]
    keys_at = jnp.repeat(keys_at, kv_heads, axis=0)
    taken = _merged(k_stack)[layer, rows_at[:, None, None], keys_at]
    rows = taken.astype(jnp.float32).mean(axis=2).astype(kc.dtype)   # [B*KVH,n,D]
    index = jnp.repeat(jnp.where(whole, index, kc.shape[3]), kv_heads, axis=0)
    return _merged(kc).at[layer, rows_at[:, None], index].set(
        rows, mode="drop"
    ).reshape(kc.shape)


def window_attends(config, freqs, seq, lengths, offsets, slot_ids, max_len):
    """The attends of a window of ``seq`` tokens a row at ``offsets``
    into slots ``slot_ids`` (a cold prefill is the window at offset 0; a
    chunked prefill is a sequence of them): by kind, ``attend(normed [B,
    T, h], the mixer's weights, the layer's index in its kind's stack,
    None, (state, k, v, kc, counters))``. Returns (attends, the valid
    mask ``[B, T]``)."""
    hybrid = config.hybrid
    sel = hybrid.selection
    steps = jnp.arange(seq)[None, :]
    positions = offsets[:, None] + steps
    valid = steps < lengths[:, None]
    totals = offsets + lengths
    kernels = _kernels(config, max_len)
    slopes = lightning_ops.decay_slopes(hybrid.lightning_heads)
    scale = config.dims_per_head ** -0.5
    at = _drop_at(positions, valid, max_len)
    rows_at = _rows_of(slot_ids, config.num_kv_heads)
    # compressed windows that end inside this window of tokens
    first = jnp.maximum(0, -((sel.kernel_size - 1 - offsets) // sel.kernel_stride))
    count = seq // sel.kernel_stride + 1

    def lightning(normed, weights, index, inputs, carried):
        state, *rest = carried
        q, k, v, gate = _lightning_sides(config, normed, weights, freqs, positions)
        flat = normed.shape[:2] + (-1,)
        # position 0 starts from zeros, whatever the slot held
        start = jnp.where(
            (offsets == 0)[:, None, None, None], 0.0, state[index, slot_ids]
        )
        with jax.named_scope("attention"):
            out, moved = lightning_ops.lightning_prefill_attention(
                q.reshape(flat), k.reshape(flat), v.reshape(flat), start,
                slopes, lengths, scale=hybrid.lightning_head_dim ** -0.5,
                kernel=kernels, interpret=config.flash_interpret,
            )
        state = state.at[index, slot_ids].set(moved)
        return _lightning_out(config, out, weights, gate), (state, *rest), None

    def sparse(normed, weights, index, inputs, carried):
        state, k_stack, v_stack, kc, counters = carried
        q, k, v, gate = _projected(
            config, normed, weights, config.num_heads, config.num_kv_heads,
            config.dims_per_head,
        )
        with jax.named_scope("cache_write"):
            k_stack = _write_rows(k_stack, index, rows_at, at, k)
            v_stack = _write_rows(v_stack, index, rows_at, at, v)
            kc = _compress_into(
                kc, k_stack, index, rows_at, first, count, totals, sel
            )
        with jax.named_scope("attention"):
            kept, counted = sparse_ops.select_blocks(
                q, kc[index, slot_ids], positions, valid, sel, scale=scale,
                num_blocks=-(-max_len // sel.block_size),
            )
            mask = sparse_ops.key_mask(kept, positions, max_len, sel)
            out = sparse_ops.sparse_prefill_attention(
                q.reshape(normed.shape[:2] + (-1,)), k_stack, v_stack,
                mask & valid[:, None, :, None], index, slot_ids, offsets,
                totals, scale=scale, kernel=kernels,
                interpret=config.flash_interpret,
            )
        carried = (state, k_stack, v_stack, kc, counters + counted)
        return out * gate, carried, None

    return {"lightning": lightning, "sparse": sparse}, valid


def decode_attends(config, freqs, lengths, positions, write_mask, max_len):
    """The attends of one decode step for every slot, on normed ``[S, 1,
    h]``: the lightning state moved on a token in place, the new K and V
    rows (and a compressed key, where the token completes a window)
    written at ``[layer, slot, :, position]``, the kept keys attended."""
    hybrid = config.hybrid
    sel = hybrid.selection
    slots = positions.shape[0]
    rows_at = _rows_of(jnp.arange(slots), config.num_kv_heads)
    kernels = _kernels(config, max_len)
    slopes = lightning_ops.decay_slopes(hybrid.lightning_heads)
    scale = config.dims_per_head ** -0.5
    at = _drop_at(positions, write_mask, max_len)[:, None]
    # the token at ``position`` ends compressed window ``first`` iff the
    # window starts on a stride: else ``until`` is 0 and nothing is written
    ends = positions + 1 - sel.kernel_size
    first = jnp.maximum(ends, 0) // sel.kernel_stride
    until = jnp.where(
        write_mask & (ends >= 0) & (ends % sel.kernel_stride == 0),
        positions + 1, 0,
    )

    def lightning(normed, weights, index, inputs, carried):
        state, *rest = carried
        q, k, v, gate = _lightning_sides(
            config, normed, weights, freqs, positions[:, None]
        )
        with jax.named_scope("attention"):
            out, state = lightning_ops.lightning_decode_attention(
                q[:, 0], k[:, 0], v[:, 0], state, index, write_mask, slopes,
                scale=hybrid.lightning_head_dim ** -0.5, kernel=kernels,
                interpret=config.flash_interpret,
            )
        out = out.reshape(slots, 1, -1)
        return _lightning_out(config, out, weights, gate), (state, *rest), None

    def sparse(normed, weights, index, inputs, carried):
        state, k_stack, v_stack, kc, counters = carried
        q, k, v, gate = _projected(
            config, normed, weights, config.num_heads, config.num_kv_heads,
            config.dims_per_head,
        )
        with jax.named_scope("cache_write"):
            k_stack = _write_rows(k_stack, index, rows_at, at, k)
            v_stack = _write_rows(v_stack, index, rows_at, at, v)
            kc = _compress_into(kc, k_stack, index, rows_at, first, 1, until, sel)
        with jax.named_scope("attention"):
            kept, counted = sparse_ops.select_blocks(
                q, kc[index], positions[:, None], write_mask[:, None], sel,
                scale=scale, num_blocks=-(-max_len // sel.block_size),
            )
            mask = sparse_ops.key_mask(kept, positions[:, None], max_len, sel)
            out = sparse_ops.sparse_decode_attention(
                q[:, 0], k_stack, v_stack, mask[:, :, 0], index, scale=scale,
                kernel=kernels, interpret=config.flash_interpret,
            )
        carried = (state, k_stack, v_stack, kc, counters + counted)
        return out.reshape(slots, 1, -1) * gate, carried, None

    return {"lightning": lightning, "sparse": sparse}


def decode_reader(config, cache) -> str:
    """The kernels that read the cache in the decode step, by their names
    on a device trace, or ``xla``."""
    if _kernels(config, cache["k"].shape[3]):
        return "sparse_block_decode+lightning_decode"
    return "xla"
