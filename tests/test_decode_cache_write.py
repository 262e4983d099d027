"""What one dense ``decode_step`` writes into the stacked cache, and what it
leaves alone. The stack rides the layer scan as carry and is written in
place (a scatter of rows where the Pallas kernel reads it, a select over
the layer's slab where XLA's einsums do), so the write's semantics are
checked for both attention paths and both cache dtypes: a masked slot keeps
every bit, a slot riding along with ``lengths`` 0 is untouched, a write at
``max_len - 1`` lands there and nowhere else, no layer writes outside its
own slab's row, and the logits agree with ``forward`` over the same tokens —
an oracle that shares neither the loop nor the cache."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.ops.attention import quantize_kv
from langstream_tpu.providers.jax_local import model as model_lib

MAX_LEN = 64
LAYERS = 3

PATHS = pytest.mark.parametrize("path", ["kernel", "xla"])
CACHES = pytest.mark.parametrize("kv_quant", [False, True], ids=["plain", "int8kv"])


def _config(path):
    # the smallest shape the kernel takes (head dim 128, a block size that
    # divides max_len), so interpret mode stays fast on the CPU
    config = model_lib.LlamaConfig(
        vocab_size=64, hidden_size=128, intermediate_size=96,
        num_layers=LAYERS, num_heads=2, num_kv_heads=2, head_dim=128,
        max_seq_len=MAX_LEN, dtype=jnp.float32, flash_interpret=True,
    )
    if path == "xla":
        config = dataclasses.replace(
            config, use_flash=False, flash_interpret=False
        )
    return config


def _setup(path, seed=3):
    config = _config(path)
    params = model_lib.init_params(config, seed=seed)
    freqs = model_lib.rope_frequencies(
        config.dims_per_head, config.max_seq_len, config.rope_theta
    )
    return config, params, freqs


def _random_cache(config, slots, kv_quant, seed=11):
    """A cache with no zero in it, so an untouched entry is told from a
    written one and a clobbered one from either."""
    cache = model_lib.init_cache(config, slots, kv_quant=kv_quant)
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    k = jax.random.normal(kk, cache["k"].shape, dtype=jnp.float32) + 3.0
    v = jax.random.normal(kv, cache["v"].shape, dtype=jnp.float32) - 3.0
    if kv_quant:
        k_q, k_s = quantize_kv(k)
        v_q, v_s = quantize_kv(v)
        return dict(cache, k=k_q, k_scale=k_s, v=v_q, v_scale=v_s)
    return dict(cache, k=k.astype(cache["k"].dtype), v=v.astype(cache["v"].dtype))


@PATHS
@CACHES
def test_write_lands_on_its_rows_and_nowhere_else(path, kv_quant):
    config, params, freqs = _setup(path)
    # slot 0: mid-sequence; 1: the last row of the buffer; 2: live context
    # but masked (a logits-only rerun); 3: riding along with lengths 0
    # (position -1), masked as the engine masks it; 4: its first token
    lengths = jnp.array([10, MAX_LEN, 7, 0, 1], dtype=jnp.int32)
    write_mask = jnp.array([True, True, False, False, True])
    tokens = jnp.array([5, 9, 11, 0, 13], dtype=jnp.int32)
    slots = lengths.shape[0]
    before = _random_cache(config, slots, kv_quant)
    kept = {name: np.array(leaf) for name, leaf in before.items()}

    after, logits, _ = jax.jit(
        lambda cache: model_lib.decode_step(
            config, params, cache, tokens, lengths, freqs, write_mask
        )
    )(before)

    assert logits.shape == (slots, config.vocab_size)
    # every layer writes its own slab's row of each unmasked slot; all else
    # keeps every bit: masked slots, the lengths-0 rider, and every other
    # row of a live slot
    expected = np.zeros((LAYERS, slots, MAX_LEN), dtype=bool)
    for slot, pos in ((0, 9), (1, MAX_LEN - 1), (4, 0)):
        expected[:, slot, pos] = True
    for name, old in kept.items():
        new = np.asarray(after[name])
        assert new.shape == old.shape and new.dtype == old.dtype, name
        changed = (new != old).reshape(LAYERS, slots, MAX_LEN, -1).any(-1)
        assert np.array_equal(changed, expected), (
            name, np.argwhere(changed != expected)[:8]
        )


@PATHS
@CACHES
def test_masked_step_changes_no_bit(path, kv_quant):
    """Every slot masked: the cache comes back identical in every leaf,
    while the logits are still computed (the engine's logits-only rerun)."""
    config, params, freqs = _setup(path)
    lengths = jnp.array([10, MAX_LEN, 0], dtype=jnp.int32)
    tokens = jnp.array([5, 9, 0], dtype=jnp.int32)
    before = _random_cache(config, 3, kv_quant)
    kept = {name: np.array(leaf) for name, leaf in before.items()}
    after, logits, _ = model_lib.decode_step(
        config, params, before, tokens, lengths, freqs,
        jnp.zeros((3,), dtype=bool),
    )
    for name, old in kept.items():
        assert np.array_equal(np.asarray(after[name]), old), name
    assert np.isfinite(np.asarray(logits[:2])).all()


@PATHS
@CACHES
def test_decode_logits_match_forward(path, kv_quant):
    """Token by token from an empty cache, each step's logits against the
    cache-free ``forward`` over the same tokens: the rows every layer wrote
    are the rows its later steps read. Slot 1 stops early and rides along
    masked; slot 2 never starts."""
    config, params, freqs = _setup(path)
    total = 9
    seq = np.array(
        [[(7 * i + 3) % 60 + 1 for i in range(total)],
         [(5 * i + 1) % 60 + 1 for i in range(total)],
         [0] * total], dtype=np.int32,
    )
    live = np.array([total, 5, 0])
    reference = np.asarray(
        model_lib.forward(config, params, jnp.asarray(seq[:2]), freqs=freqs)
    )
    cache = model_lib.init_cache(config, 3, kv_quant=kv_quant)
    step = jax.jit(
        lambda cache, tokens, lengths, mask: model_lib.decode_step(
            config, params, cache, tokens, lengths, freqs, mask
        )
    )
    scale = np.abs(reference).max()
    # int8 rows carry their rounding (tests/test_kv_quant.py holds the
    # quantized cache to the plain one within 5% of the logits' scale)
    atol = 0.05 * scale if kv_quant else 2e-4 * max(scale, 1.0)
    for t in range(total):
        active = live > t
        lengths = np.where(active, t + 1, np.minimum(live, t + 1))
        cache, logits, _ = step(
            cache, jnp.asarray(seq[:, t]), jnp.asarray(lengths, dtype=jnp.int32),
            jnp.asarray(active),
        )
        for slot in np.flatnonzero(active):
            np.testing.assert_allclose(
                np.asarray(logits[slot]), reference[slot, t], atol=atol,
                err_msg=f"slot {slot} step {t}",
            )
