"""A head-dim-64 model's dense cache lies PACKED where the decode kernel
reads it (two kv heads to a 128-lane row: ``model.flash_decode_pack``),
and every program that touches such a leaf goes through ``_pack_kv`` /
``_unpack_kv``. Each program here runs twice on one set of weights, once
with the kernel (Pallas interpret mode) over the packed leaf and once on
the XLA side over the plain one, and must give the same logits, the same
greedy tokens and the same rows."""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.providers.jax_local import model as model_lib
from langstream_tpu.providers.jax_local.engine import (
    DecodeEngine,
    SamplingParams,
    engines_snapshot,
)

MAX_LEN = 64
SLOTS = 3


def _config(path, **kw):
    # two 64-wide kv heads fill one row; the smallest shape the packed
    # reader takes, so interpret mode stays fast on the CPU
    config = model_lib.LlamaConfig(
        vocab_size=64, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=64, max_seq_len=MAX_LEN,
        dtype=jnp.float32, flash_interpret=True, **kw,
    )
    if path == "xla":
        config = dataclasses.replace(
            config, use_flash=False, flash_interpret=False
        )
    return config


@pytest.mark.parametrize(
    "path,kv_quant,tp,tail",
    [
        ("kernel", False, 1, (1, 128)),
        ("xla", False, 1, (2, 64)),      # no kernel: the leaf stays plain
        ("kernel", True, 1, (2, 64)),    # int8 rows carry a scale a head
        ("kernel", False, 2, (2, 64)),   # a shard would hold half a row
    ],
    ids=["packed", "xla-side", "int8kv", "tp2"],
)
def test_init_cache_packs_where_the_kernel_reads(path, kv_quant, tp, tail):
    config = _config(path)
    cache = model_lib.init_cache(config, SLOTS, kv_quant=kv_quant, tp=tp)
    assert cache["k"].shape == (2, SLOTS, MAX_LEN) + tail
    assert cache["v"].shape == cache["k"].shape
    if kv_quant:
        assert cache["k_scale"].shape == (2, SLOTS, MAX_LEN, 2)
    rows = jnp.arange(5 * 2 * 64, dtype=jnp.float32).reshape(5, 2, 64)
    packed = model_lib._pack_kv(rows, cache["k"])
    assert packed.shape == (5,) + tail
    # kv head g is lanes [g * 64, (g + 1) * 64) of its row
    np.testing.assert_array_equal(
        np.asarray(packed).reshape(5, -1), np.asarray(rows).reshape(5, -1)
    )
    np.testing.assert_array_equal(
        np.asarray(model_lib._unpack_kv(config, packed)), np.asarray(rows)
    )


def _programs(path, window=False):
    """Cold prefill -> a chunk of decode steps -> a suffix at an offset ->
    a verify block, every logit and the cache's rows (unpacked) back."""
    family = (
        dict(sliding_window=8, attn_logit_softcap=30.0) if window else {}
    )
    config = _config(path, **family)
    params = model_lib.init_params(config, seed=5)
    freqs = model_lib.rope_frequencies(
        config.dims_per_head, config.max_seq_len, config.rope_theta
    )
    cache = model_lib.init_cache(config, SLOTS)
    logits = []

    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, 64, (2, 16)), jnp.int32
    )
    lengths = jnp.array([16, 9], jnp.int32)
    slot_ids = jnp.array([0, 2], jnp.int32)      # slot 1 stays empty
    cache, out, _ = model_lib.prefill(
        config, params, cache, tokens, lengths, slot_ids, freqs
    )
    logits.append(out)

    held = jnp.array([16, 0, 9], jnp.int32)
    active = jnp.array([True, False, True])
    picked = jnp.zeros((SLOTS,), jnp.int32).at[slot_ids].set(
        jnp.argmax(out, axis=-1).astype(jnp.int32)
    )
    greedy = []
    for _ in range(4):
        held = jnp.where(active, held + 1, held)
        cache, out, _ = model_lib.decode_step(
            config, params, cache, picked, held, freqs, active
        )
        picked = jnp.where(active, jnp.argmax(out, axis=-1), 0).astype(jnp.int32)
        greedy.append(np.asarray(picked))
        logits.append(out[np.asarray(active)])

    suffix = jnp.asarray(
        np.random.default_rng(1).integers(1, 64, (1, 8)), jnp.int32
    )
    cache, out, _ = model_lib.prefill_at_offset(
        config, params, cache, suffix, jnp.array([6], jnp.int32),
        held[:1], jnp.array([0], jnp.int32), freqs,
    )
    logits.append(out)
    held = held.at[0].add(6)

    block = jnp.asarray(
        np.random.default_rng(2).integers(1, 64, (SLOTS, 4)), jnp.int32
    )
    cache, out, _ = model_lib.verify_step(
        config, params, cache, block, held + 1,
        jnp.array([4, 0, 3], jnp.int32), freqs, active,
    )
    logits.append(out[np.asarray(active)][:, :3])
    rows = {
        name: np.asarray(model_lib._unpack_kv(config, leaf))
        for name, leaf in cache.items()
    }
    return logits, np.stack(greedy), rows, cache


@pytest.mark.parametrize("window", [False, True], ids=["plain", "window-softcap"])
def test_packed_programs_match_the_xla_side(window):
    logits, greedy, rows, cache = _programs("kernel", window)
    assert cache["k"].shape == (2, SLOTS, MAX_LEN, 1, 128)
    ref_logits, ref_greedy, ref_rows, ref_cache = _programs("xla", window)
    assert ref_cache["k"].shape == (2, SLOTS, MAX_LEN, 2, 64)
    np.testing.assert_array_equal(greedy, ref_greedy)
    for got, want in zip(logits, ref_logits):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )
    for name in ref_rows:
        np.testing.assert_allclose(
            rows[name], ref_rows[name], rtol=1e-5, atol=1e-5
        )


def _serve(path, **engine_kw):
    """A pinned session and its follow-up (the warm prefill at an offset),
    then a sessionless prompt that shares the session's prefix from another
    slot (``copy_prefix``): greedy tokens, stats, the gauges."""
    config = _config(path)
    params = model_lib.init_params(config, seed=5)
    shared = [(5 * i) % 60 + 1 for i in range(20)]
    sampling = SamplingParams(max_new_tokens=6)

    async def main():
        engine = DecodeEngine(
            config, params, max_slots=SLOTS, max_seq_len=MAX_LEN,
            prefill_buckets=[8, 16, 32], decode_chunk=4, **engine_kw,
        )
        engine.start()
        try:
            first = await engine.generate(
                shared + [7, 8], sampling, session_id="pin"
            )
            follow = await engine.generate(
                shared + [7, 8] + list(first.tokens) + [9, 10, 11], sampling,
                session_id="pin",
            )
            other = await engine.generate(shared + [12, 13, 14], sampling)
            gauges = engines_snapshot()
            return (
                (first.tokens, follow.tokens, other.tokens),
                dict(engine.stats), gauges,
            )
        finally:
            engine.stop()

    return asyncio.run(main())


@pytest.mark.parametrize(
    "engine_kw", [{}, dict(spec_decode="ngram", spec_k=3, spec_ngram=2)],
    ids=["plain", "ngram-verify"],
)
def test_engine_on_packed_cache_serves_the_xla_sides_tokens(engine_kw):
    tokens, stats, gauges = _serve("kernel", **engine_kw)
    ref_tokens, ref_stats, ref_gauges = _serve("xla", **engine_kw)
    assert tokens == ref_tokens
    assert stats["session_hits"] == ref_stats["session_hits"] == 1
    assert stats["prefix_hits"] == ref_stats["prefix_hits"] == 1
    # the reader and how a leaf lies, said once at construction
    assert stats["decode_reader"] == "flash_decode"
    assert stats["cache_leaf_shape"] == (2, SLOTS, MAX_LEN, 1, 128)
    assert ref_stats["decode_reader"] == "xla"
    assert ref_stats["cache_leaf_shape"] == (2, SLOTS, MAX_LEN, 2, 64)
    key = 'jax_engine_decode_reader{reader="flash_decode",cache="2x3x64x1x128"}'
    assert gauges[key] >= 1.0
    assert 'jax_engine_decode_reader{reader="xla",cache="2x3x64x2x64"}' in ref_gauges


def test_engine_on_packed_cache_under_tp():
    """tp=2 over four 64-wide kv heads: a shard holds one whole packed
    row (``flash_decode_pack`` asks it of the mesh), the kernel runs a
    shard through shard_map, and the warm prefill unpacks a sharded leaf."""
    from langstream_tpu.parallel.mesh import MeshConfig

    sampling = SamplingParams(max_new_tokens=6)
    prompt = [(5 * i) % 60 + 1 for i in range(12)]

    def serve(path, **engine_kw):
        config = dataclasses.replace(
            _config(path), num_heads=8, num_kv_heads=4
        )
        params = model_lib.init_params(config, seed=5)

        async def main():
            engine = DecodeEngine(
                config, params, max_slots=2, max_seq_len=MAX_LEN,
                prefill_buckets=[8, 16], decode_chunk=4, **engine_kw,
            )
            engine.start()
            try:
                first = await engine.generate(prompt, sampling, session_id="s")
                follow = await engine.generate(
                    prompt + list(first.tokens) + [9, 10], sampling,
                    session_id="s",
                )
                return (first.tokens, follow.tokens), dict(engine.stats)
            finally:
                engine.stop()

        return asyncio.run(main())

    tokens, stats = serve("kernel", mesh_config=MeshConfig(tp=2))
    ref_tokens, _ = serve("xla")
    assert tokens == ref_tokens
    assert stats["decode_reader"] == "flash_decode"
    assert stats["cache_leaf_shape"] == (2, 2, MAX_LEN, 2, 128)
    assert stats["session_hits"] == 1
