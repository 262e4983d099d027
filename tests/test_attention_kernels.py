"""Flash-attention kernel (interpret mode) and ring attention (virtual CPU
mesh) against the plain-XLA reference attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from langstream_tpu.ops.attention import prefill_attention
from langstream_tpu.ops.flash_attention import flash_prefill_attention
from langstream_tpu.parallel.ring import ring_attention_sharded


def _make_qkv(batch, seq, heads, kv_heads, dim, seed=0):
    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (batch, seq, heads, dim), dtype=jnp.float32)
    k = jax.random.normal(kk, (batch, seq, kv_heads, dim), dtype=jnp.float32)
    v = jax.random.normal(kv, (batch, seq, kv_heads, dim), dtype=jnp.float32)
    return q, k, v


@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (4, 2)])
def test_flash_matches_reference(heads, kv_heads):
    batch, seq, dim = 2, 256, 128
    q, k, v = _make_qkv(batch, seq, heads, kv_heads, dim)
    lengths = jnp.array([256, 190], dtype=jnp.int32)
    mask = jnp.arange(seq)[None, :] < lengths[:, None]

    ref = prefill_attention(q, k, v, mask=mask)
    out = flash_prefill_attention(
        q, k, v, mask=mask, block_q=128, block_k=128, interpret=True
    )
    # padded rows are garbage in both; compare valid rows only
    for b in range(batch):
        n = int(lengths[b])
        np.testing.assert_allclose(
            np.asarray(out[b, :n]), np.asarray(ref[b, :n]),
            rtol=2e-5, atol=2e-5,
        )


def test_flash_pads_non_multiple_seq():
    batch, seq, dim = 1, 200, 128
    q, k, v = _make_qkv(batch, seq, 2, 2, dim, seed=1)
    ref = prefill_attention(q, k, v)
    out = flash_prefill_attention(
        q, k, v, block_q=128, block_k=128, interpret=True
    )
    assert out.shape == ref.shape
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("sp", [2, 4])
def test_ring_attention_matches_reference(sp):
    batch, seq, heads, kv_heads, dim = 2, 64, 4, 2, 16
    q, k, v = _make_qkv(batch, seq, heads, kv_heads, dim, seed=2)
    lengths = jnp.array([64, 50], dtype=jnp.int32)
    mask = jnp.arange(seq)[None, :] < lengths[:, None]

    devices = np.asarray(jax.devices()[:sp]).reshape(sp)
    mesh = Mesh(devices, ("sp",))

    ref = prefill_attention(q, k, v, mask=mask)
    out = ring_attention_sharded(q, k, v, mesh, mask=mask)
    for b in range(batch):
        n = int(lengths[b])
        np.testing.assert_allclose(
            np.asarray(out[b, :n]), np.asarray(ref[b, :n]),
            rtol=1e-5, atol=1e-5,
        )


def test_ring_attention_non_causal():
    batch, seq, heads, kv_heads, dim = 1, 32, 2, 2, 8
    q, k, v = _make_qkv(batch, seq, heads, kv_heads, dim, seed=3)
    devices = np.asarray(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devices, ("sp",))

    # non-causal reference: softmax over all positions
    scale = dim ** -0.5
    s = jnp.einsum("bqhd,bshd->bhqs", q, k) * scale
    w = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhqs,bshd->bqhd", w, v)

    out = ring_attention_sharded(q, k, v, mesh, causal=False)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_ring_attention_under_jit():
    batch, seq, heads, kv_heads, dim = 1, 32, 2, 1, 8
    q, k, v = _make_qkv(batch, seq, heads, kv_heads, dim, seed=4)
    devices = np.asarray(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devices, ("sp",))

    ref = prefill_attention(q, k, v)
    out = jax.jit(
        lambda q, k, v: ring_attention_sharded(q, k, v, mesh)
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_flash_sharded_tp_matches_reference():
    """shard_map'd flash over the head axis on a tp=4 CPU mesh must match
    the XLA attention (the tp serving path, VERDICT r2 weak #2)."""
    from langstream_tpu.ops.flash_attention import (
        flash_prefill_attention_sharded,
    )

    batch, seq, heads, kv_heads, dim = 2, 256, 8, 4, 128
    q, k, v = _make_qkv(batch, seq, heads, kv_heads, dim, seed=3)
    lengths = jnp.array([256, 130], dtype=jnp.int32)
    mask = jnp.arange(seq)[None, :] < lengths[:, None]
    ref = prefill_attention(q, k, v, mask=mask)

    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
    out = jax.jit(
        lambda q, k, v: flash_prefill_attention_sharded(
            q, k, v, mesh, mask=mask, interpret=True
        )
    )(q, k, v)
    for b in range(batch):
        n = int(lengths[b])
        np.testing.assert_allclose(
            np.asarray(out[b, :n]), np.asarray(ref[b, :n]),
            rtol=2e-5, atol=2e-5,
        )


# --------------------------- int8 flash -------------------------------- #
@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (4, 2)])
def test_flash_quant_matches_xla_quant(heads, kv_heads):
    """Int8 flash (interpret) vs the XLA scale-folded reference
    (chunk_attention_quant at starts=0): same algebra, block-tiled."""
    from langstream_tpu.ops.attention import (
        chunk_attention_quant,
        quantize_kv,
    )
    from langstream_tpu.ops.flash_attention import (
        flash_prefill_attention_quant,
    )

    batch, seq, dim = 2, 256, 128
    q, k, v = _make_qkv(batch, seq, heads, kv_heads, dim)
    lengths = jnp.array([256, 190], dtype=jnp.int32)
    mask = jnp.arange(seq)[None, :] < lengths[:, None]
    k_q, k_s = quantize_kv(k)
    v_q, v_s = quantize_kv(v)

    ref = chunk_attention_quant(
        q, k_q, k_s, v_q, v_s, jnp.zeros_like(lengths), lengths
    )
    out = flash_prefill_attention_quant(
        q, k_q, k_s, v_q, v_s, mask=mask,
        block_q=128, block_k=128, interpret=True,
    )
    for b in range(batch):
        n = int(lengths[b])
        np.testing.assert_allclose(
            np.asarray(out[b, :n]), np.asarray(ref[b, :n]),
            rtol=2e-2, atol=2e-2,  # probs round through bf16 in-kernel
        )


def test_flash_quant_pads_non_multiple_seq():
    from langstream_tpu.ops.attention import (
        chunk_attention_quant,
        quantize_kv,
    )
    from langstream_tpu.ops.flash_attention import (
        flash_prefill_attention_quant,
    )

    batch, seq, dim = 1, 200, 128
    q, k, v = _make_qkv(batch, seq, 4, 2, dim, seed=3)
    lengths = jnp.array([200], dtype=jnp.int32)
    k_q, k_s = quantize_kv(k)
    v_q, v_s = quantize_kv(v)
    ref = chunk_attention_quant(
        q, k_q, k_s, v_q, v_s, jnp.zeros_like(lengths), lengths
    )
    out = flash_prefill_attention_quant(
        q, k_q, k_s, v_q, v_s, lengths=lengths,
        block_q=128, block_k=128, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(ref[0]), rtol=2e-2, atol=2e-2
    )


def test_flash_quant_sharded_tp_matches_reference():
    from langstream_tpu.ops.attention import (
        chunk_attention_quant,
        quantize_kv,
    )
    from langstream_tpu.ops.flash_attention import (
        flash_prefill_attention_quant_sharded,
    )

    batch, seq, dim = 1, 256, 128
    heads, kv_heads = 8, 4
    q, k, v = _make_qkv(batch, seq, heads, kv_heads, dim, seed=5)
    lengths = jnp.array([222], dtype=jnp.int32)
    mask = jnp.arange(seq)[None, :] < lengths[:, None]
    k_q, k_s = quantize_kv(k)
    v_q, v_s = quantize_kv(v)
    ref = chunk_attention_quant(
        q, k_q, k_s, v_q, v_s, jnp.zeros_like(lengths), lengths
    )
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("tp",))
    out = flash_prefill_attention_quant_sharded(
        q, k_q, k_s, v_q, v_s, mesh, mask=mask, interpret=True
    )
    n = int(lengths[0])
    np.testing.assert_allclose(
        np.asarray(out[0, :n]), np.asarray(ref[0, :n]),
        rtol=2e-2, atol=2e-2,
    )


# ------------------- quantized XLA paths vs bf16 refs ------------------ #
# The int8-cache attention folds per-row scales into the contractions
# (score-side for K, probs-side for V) instead of dequantizing the
# cache. These tests pin that algebra against the PLAIN attention run
# over an explicitly dequantized cache — same values, so the only
# tolerance needed is f32 reassociation — across GQA group sizes
# (MHA, 2x, 4x grouping) and softcap on/off.


def _dequant(values, scale):
    return values.astype(jnp.float32) * scale[..., None]


@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (4, 2), (8, 2)])
@pytest.mark.parametrize("softcap", [None, 30.0])
def test_decode_attention_quant_matches_bf16_reference(
    heads, kv_heads, softcap
):
    from langstream_tpu.ops.attention import (
        decode_attention,
        decode_attention_quant,
        quantize_kv,
    )

    batch, max_len, dim = 3, 64, 32
    key = jax.random.PRNGKey(11)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (batch, heads, dim), dtype=jnp.float32)
    k = jax.random.normal(kk, (batch, max_len, kv_heads, dim), jnp.float32)
    v = jax.random.normal(kv, (batch, max_len, kv_heads, dim), jnp.float32)
    lengths = jnp.array([64, 40, 1], dtype=jnp.int32)
    k_q, k_s = quantize_kv(k)
    v_q, v_s = quantize_kv(v)

    ref = decode_attention(
        q, _dequant(k_q, k_s), _dequant(v_q, v_s), lengths, softcap=softcap
    )
    out = decode_attention_quant(
        q, k_q, k_s, v_q, v_s, lengths, softcap=softcap
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (4, 2), (8, 2)])
@pytest.mark.parametrize("softcap", [None, 30.0])
def test_chunk_attention_quant_matches_bf16_reference(
    heads, kv_heads, softcap
):
    from langstream_tpu.ops.attention import (
        chunk_attention,
        chunk_attention_quant,
        quantize_kv,
    )

    batch, seq, max_len, dim = 2, 8, 64, 32
    key = jax.random.PRNGKey(12)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (batch, seq, heads, dim), dtype=jnp.float32)
    k = jax.random.normal(kk, (batch, max_len, kv_heads, dim), jnp.float32)
    v = jax.random.normal(kv, (batch, max_len, kv_heads, dim), jnp.float32)
    starts = jnp.array([16, 3], dtype=jnp.int32)
    lengths = starts + jnp.array([8, 5], dtype=jnp.int32)
    k_q, k_s = quantize_kv(k)
    v_q, v_s = quantize_kv(v)

    ref = chunk_attention(
        q, _dequant(k_q, k_s), _dequant(v_q, v_s), starts, lengths,
        softcap=softcap,
    )
    out = chunk_attention_quant(
        q, k_q, k_s, v_q, v_s, starts, lengths, softcap=softcap
    )
    # row 1's padding queries (suffix length 5 < seq 8) attend garbage in
    # both paths but may reassociate differently: compare valid rows
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(ref[0]), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(out[1, :5]), np.asarray(ref[1, :5]), rtol=2e-5, atol=2e-5
    )


# --------------------------- paged layout ------------------------------ #
def _paged_layout(k, v, block_size, seed=0):
    """Scatter dense [B, T, KVH, D] caches into a shuffled block pool +
    tables, so the paged paths are tested against NON-contiguous,
    non-identity block placement."""
    batch, max_len, kv_heads, dim = k.shape
    blocks_per_row = max_len // block_size
    total = batch * blocks_per_row
    rng = np.random.RandomState(seed)
    order = rng.permutation(total) + 1  # block 0 stays the null block
    tables = order.reshape(batch, blocks_per_row).astype(np.int32)
    k_pool = np.zeros((total + 1, block_size, kv_heads, dim), np.float32)
    v_pool = np.zeros_like(k_pool)
    for b in range(batch):
        for j in range(blocks_per_row):
            rows = slice(j * block_size, (j + 1) * block_size)
            k_pool[tables[b, j]] = np.asarray(k[b, rows])
            v_pool[tables[b, j]] = np.asarray(v[b, rows])
    return jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(tables)


def test_paged_decode_attention_matches_dense():
    from langstream_tpu.ops.attention import (
        decode_attention,
        paged_decode_attention,
    )

    batch, max_len, heads, kv_heads, dim = 2, 64, 4, 2, 32
    key = jax.random.PRNGKey(21)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (batch, heads, dim), dtype=jnp.float32)
    k = jax.random.normal(kk, (batch, max_len, kv_heads, dim), jnp.float32)
    v = jax.random.normal(kv, (batch, max_len, kv_heads, dim), jnp.float32)
    lengths = jnp.array([60, 17], dtype=jnp.int32)
    k_pool, v_pool, tables = _paged_layout(k, v, block_size=16)

    ref = decode_attention(q, k, v, lengths)
    out = paged_decode_attention(q, k_pool, v_pool, tables, lengths)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_paged_chunk_attention_matches_dense():
    from langstream_tpu.ops.attention import (
        chunk_attention,
        paged_chunk_attention,
    )

    batch, seq, max_len, heads, kv_heads, dim = 2, 8, 64, 4, 2, 32
    key = jax.random.PRNGKey(22)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (batch, seq, heads, dim), dtype=jnp.float32)
    k = jax.random.normal(kk, (batch, max_len, kv_heads, dim), jnp.float32)
    v = jax.random.normal(kv, (batch, max_len, kv_heads, dim), jnp.float32)
    starts = jnp.array([20, 5], dtype=jnp.int32)
    lengths = starts + jnp.array([8, 8], dtype=jnp.int32)
    k_pool, v_pool, tables = _paged_layout(k, v, block_size=16, seed=1)

    ref = chunk_attention(q, k, v, starts, lengths, window=jnp.int32(24))
    out = paged_chunk_attention(
        q, k_pool, v_pool, tables, starts, lengths, window=jnp.int32(24)
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_paged_write_rows_scatters_and_masks():
    from langstream_tpu.ops.attention import gather_blocks, paged_write_rows

    block_size, kv_heads, dim = 4, 2, 8
    pool = jnp.zeros((9, block_size, kv_heads, dim), jnp.float32)
    tables = jnp.asarray([[3, 1, 7, 0], [5, 2, 0, 0]], jnp.int32)
    new = jnp.arange(2 * 6 * kv_heads * dim, dtype=jnp.float32).reshape(
        2, 6, kv_heads, dim
    )
    offsets = jnp.asarray([2, 0], jnp.int32)       # row 0 writes mid-block
    valid = jnp.asarray(
        [[True] * 6, [True] * 3 + [False] * 3]     # row 1: 3 real tokens
    )
    pool = paged_write_rows(pool, new, tables, offsets, valid)
    view = gather_blocks(pool, tables)             # [2, 16, KVH, D]
    np.testing.assert_array_equal(
        np.asarray(view[0, 2:8]), np.asarray(new[0])
    )
    np.testing.assert_array_equal(
        np.asarray(view[1, :3]), np.asarray(new[1, :3])
    )
    # masked rows landed in the null block, not in the row's real blocks
    np.testing.assert_array_equal(np.asarray(view[1, 3:8]), 0.0)


def test_paged_write_rows_routes_past_capacity_to_null_block():
    """Regression (ISSUE 6 satellite): positions at/past the table's
    capacity (``pos // block_size >= M``) must route through the null
    block explicitly. The old code leaned on take_along_axis's
    out-of-bounds clamp, which resolved them to the row's LAST real
    block — silently overwriting live rows at the table-capacity
    boundary."""
    from langstream_tpu.ops.attention import gather_blocks, paged_write_rows

    block_size, kv_heads, dim = 4, 2, 8
    pool = jnp.zeros((9, block_size, kv_heads, dim), jnp.float32)
    tables = jnp.asarray([[3, 1]], jnp.int32)  # M = 2 → capacity 8 rows
    new = jnp.arange(1, 1 + 4 * kv_heads * dim, dtype=jnp.float32).reshape(
        1, 4, kv_heads, dim
    )
    # offset 6: positions 6..9 — the last two straddle the capacity
    # boundary and must vanish into the null block
    pool = paged_write_rows(
        pool, new, tables,
        jnp.asarray([6], jnp.int32), jnp.ones((1, 4), bool),
    )
    view = gather_blocks(pool, tables)  # [1, 8, KVH, D]
    np.testing.assert_array_equal(np.asarray(view[0, 6:8]), np.asarray(new[0, :2]))
    # in-capacity rows BEFORE the boundary are untouched (the clamp bug
    # wrote positions 8/9 into block ``tables[0, 1]`` rows 0/1)
    np.testing.assert_array_equal(np.asarray(view[0, 4:6]), 0.0)
    np.testing.assert_array_equal(np.asarray(view[0, :4]), 0.0)
    # overflow rows landed in the null block (content never read live)
    np.testing.assert_array_equal(np.asarray(pool[0, 0]), np.asarray(new[0, 2]))
    np.testing.assert_array_equal(np.asarray(pool[0, 1]), np.asarray(new[0, 3]))


def test_flash_prefill_window_softcap_matches_reference():
    """Gemma-2 mechanisms in the prefill kernel: sliding-window masking
    (+ out-of-window block compute skip), logit softcap, and the
    query_pre_attn_scalar scale against the XLA reference."""
    batch, seq, dim = 2, 256, 128
    q, k, v = _make_qkv(batch, seq, 4, 2, dim, seed=9)
    lengths = jnp.array([256, 170], dtype=jnp.int32)
    mask = jnp.arange(seq)[None, :] < lengths[:, None]
    window = jnp.asarray(48, dtype=jnp.int32)

    from langstream_tpu.ops.attention import prefill_attention as xla_prefill

    ref = xla_prefill(
        q, k, v, mask=mask, softcap=30.0, window=window, scale=0.2
    )
    out = flash_prefill_attention(
        q, k, v, mask=mask, softcap=30.0, window=window, scale=0.2,
        block_q=64, block_k=64, interpret=True,
    )
    for b in range(batch):
        n = int(lengths[b])
        np.testing.assert_allclose(
            np.asarray(out[b, :n]), np.asarray(ref[b, :n]),
            rtol=2e-5, atol=2e-5,
        )


# ---------------- flash prefill: the step classes ----------------------- #
EDGE_BLOCK = 64
# a row of one token, rows one short of, at and one past a block's edge,
# and a whole row, in one batch
EDGE_LENGTHS = (1, EDGE_BLOCK - 1, EDGE_BLOCK, EDGE_BLOCK + 1, 4 * EDGE_BLOCK)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize(
    "family",
    [{}, {"window": 128, "softcap": 30.0, "scale": 0.2}],
    ids=["full", "window_softcap"],
)
@pytest.mark.parametrize(
    "dims", [(4, 2, 128, 128), (2, 2, 192, 128)], ids=["gqa", "latent"]
)
def test_flash_classes_match_reference_and_zero_past_length(
    quantized, family, dims
):
    """Every step class (empty past the length or the window, full, and
    partial at the diagonal, the length or a window's edge) against the
    XLA attention on the valid rows, and rows at or past the length read
    exactly zero. The 128-token window leaves a full block inside it and
    a full row fewer steps than the grid has, so padding steps run."""
    from langstream_tpu.ops.attention import quantize_kv

    heads, kv_heads, dim, v_dim = dims
    seq = EDGE_LENGTHS[-1]
    batch = len(EDGE_LENGTHS)
    q, k, v = _make_qkv(batch, seq, heads, kv_heads, dim, seed=11)
    v = v[..., :v_dim]
    lengths = jnp.array(EDGE_LENGTHS, dtype=jnp.int32)
    mask = jnp.arange(seq)[None, :] < lengths[:, None]
    family = dict(family)
    if "window" in family:
        family["window"] = jnp.asarray(family["window"], dtype=jnp.int32)
    blocks = dict(block_q=EDGE_BLOCK, block_k=EDGE_BLOCK, interpret=True)
    if quantized:
        # the int8 twin against the plain attention over the dequantized
        # cache: the same values, the scales folded elsewhere
        k_q, k_s = quantize_kv(k)
        v_q, v_s = quantize_kv(v)
        ref = prefill_attention(
            q, _dequant(k_q, k_s), _dequant(v_q, v_s), mask=mask, **family
        )
        out = flash_prefill_attention(
            q, k_q, v_q, k_scale=k_s, v_scale=v_s, lengths=lengths,
            **family, **blocks,
        )
    else:
        ref = prefill_attention(q, k, v, mask=mask, **family)
        out = flash_prefill_attention(q, k, v, mask=mask, **family, **blocks)
    assert out.shape == (batch, seq, heads, v_dim)
    for b, n in enumerate(EDGE_LENGTHS):
        np.testing.assert_allclose(
            np.asarray(out[b, :n]), np.asarray(ref[b, :n]),
            rtol=2e-5, atol=2e-5,
        )
        np.testing.assert_array_equal(np.asarray(out[b, n:]), 0.0)


def _live_pairs(length, padded, block_q, block_k, window):
    """The (q block, k block) pairs of a ``padded`` call in which some
    (row, column) is causal, inside the row's window and inside the
    length, by brute force over every element."""
    rows = np.arange(padded)[:, None]
    cols = np.arange(padded)[None, :]
    visible = (
        (cols <= rows) & ((window <= 0) | (cols > rows - window))
        & (rows < length) & (cols < length)
    )
    blocks = visible.reshape(
        padded // block_q, block_q, padded // block_k, block_k
    ).any(axis=(1, 3))
    return set(zip(*np.nonzero(blocks)))


@pytest.mark.parametrize(
    "seq,block_q,block_k,window",
    [
        (256, 64, 64, 0),
        (256, 64, 64, 48),
        (256, 64, 64, 150),
        (512, 128, 64, 0),
        (512, 64, 128, 100),
        (384, 256, 256, 0),
    ],
)
def test_attention_blocks_counts_brute_force(seq, block_q, block_k, window):
    """The host counter against brute force, and the kernel's step table:
    every live pair once, in q order, and one step for each q block that
    has none."""
    from langstream_tpu.ops.flash_attention import (
        _step_table,
        attention_blocks,
    )

    lengths = [0, 1, block_q - 1, block_q, block_q + 1, seq - 1, seq]
    padded = -(-seq // max(block_q, block_k)) * max(block_q, block_k)
    live = [_live_pairs(n, padded, block_q, block_k, window) for n in lengths]
    full = _live_pairs(seq, padded, block_q, block_k, window)
    assert attention_blocks(lengths, seq, block_q, block_k, window) == (
        sum(map(len, live)), len(full) * len(lengths)
    )
    table = np.asarray(_step_table(
        jnp.asarray(lengths, jnp.int32), window, padded, block_q, block_k
    ))
    for (qi, kj, own), pairs in zip(table, live):
        assert np.all(np.diff(qi) >= 0)
        visited = [(q, k) for q, k, o in zip(qi, kj, own) if o]
        assert len(set(visited)) == len(visited)
        assert pairs <= set(visited)
        idle = set(range(padded // block_q)) - {q for q, _ in pairs}
        extra = set(visited) - pairs
        assert {q for q, _ in extra} == idle and len(extra) == len(idle)
