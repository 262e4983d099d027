"""The hybrid family (linear attention with a recurrent state beside
block-sparse GQA, a kind a layer) at a test size, float32 on the CPU: the
``tiny-hybrid`` preset (5 layers, sparse at 0, 3 and 4; ``dense_len`` 32,
blocks of 8, the 2 best beside a window of 16), kernels in Pallas
interpret mode.

The dense layout's three programs (``hybrid_sparse_linear.py``) are held
to the plain reference the benchmark compares with (``benchmark/reference/
minicpm_sala.py``: one full forward pass, no cache, no state carried, the
linear attention in its quadratic form, its own selection) on seeded int8
weights whose norm scales lie away from 1, below and above ``dense_len``;
a chunked prefill to one cold prefill; a reused slot to a fresh engine;
the chunk-wise kernel to the plain recurrence; the selection to the
reference's; the int8 form to its dequantised float32; and every switch
the family cannot take to its refusal.
"""

import asyncio
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import minicpm_sala as reference
from langstream_tpu.ops import block_sparse_attention as sparse_ops
from langstream_tpu.ops import lightning_attention as lightning_ops
from langstream_tpu.providers.jax_local import hybrid_sparse_linear as hybrid
from langstream_tpu.providers.jax_local import model as model_lib
from langstream_tpu.providers.jax_local.engine import (
    DecodeEngine,
    GenerationRequest,
    SamplingParams,
)
from langstream_tpu.providers.jax_local.quant import QTensor

SEED = 11
SLOTS, MAX_LEN = 3, 128
NAMES = {"sparse": "minicpm4", "lightning": "lightning-attn"}


def tiny(**fields):
    config = model_lib.LlamaConfig.from_dict({"preset": "tiny-hybrid"})
    return dataclasses.replace(config, flash_interpret=True, **fields)


def file_of(config):
    """The configuration's file the reference reads, for a program config."""
    selection = config.hybrid.selection
    return {
        "vocab_size": config.vocab_size, "hidden_size": config.hidden_size,
        "intermediate_size": config.intermediate_size,
        "num_hidden_layers": config.num_layers,
        "num_attention_heads": config.num_heads,
        "num_key_value_heads": config.num_kv_heads,
        "head_dim": config.dims_per_head,
        "lightning_nh": config.hybrid.lightning_heads,
        "lightning_nkv": config.hybrid.lightning_heads,
        "lightning_head_dim": config.hybrid.lightning_head_dim,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "mixer_types": [NAMES[kind] for kind in config.mixers],
        "sparse_config": {
            key: getattr(selection, key) for key in reference.SPARSE_KEYS
        },
        "rope_theta": config.rope_theta, "rms_norm_eps": config.norm_eps,
        "scale_emb": config.embedding_scale,
        "scale_depth": config.residual_scale * math.sqrt(config.num_layers),
        "dim_model_base": config.hidden_size / config.logit_divisor,
        "qk_norm": True, "attn_use_rope": False, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True,
        "tie_word_embeddings": False, "attention_bias": False,
        "hidden_act": "silu", "weights": "int8-uniform-f32",
    }


@pytest.fixture(scope="module")
def family():
    config = tiny()
    params = hybrid.init_params(config, SEED, quantized=True)
    sizes = reference.Sizes(file_of(config))
    return config, params, sizes, reference.make_weights(sizes, SEED)


def _gap(logits, want):
    return float(np.abs(np.asarray(logits) - want).max())


def _prompts(config, lengths):
    rng = np.random.default_rng(0)
    return [list(rng.integers(0, config.vocab_size, size=n)) for n in lengths]


def _window(config, params, cache, rows, offsets, slot_ids, width):
    tokens = np.zeros((len(rows), width), np.int32)
    for row, piece in enumerate(rows):
        tokens[row, : len(piece)] = piece
    return jax.jit(
        lambda c, t, n, o, s: model_lib.prefill_at_offset(
            config, params, c, t, n, o, s, model_lib.model_freqs(config)
        )
    )(
        cache, tokens, np.array([len(r) for r in rows], np.int32),
        np.asarray(offsets, np.int32), np.asarray(slot_ids, np.int32),
    )


def _prefilled(config, params, prompts, slot_ids, cache=None, width=96):
    tokens = np.zeros((len(prompts), width), np.int32)
    for row, prompt in enumerate(prompts):
        tokens[row, : len(prompt)] = prompt
    lengths = np.array([len(p) for p in prompts], np.int32)
    freqs = model_lib.model_freqs(config)
    if cache is None:
        cache = model_lib.init_cache(config, SLOTS, MAX_LEN)
    cache, logits, counters = jax.jit(
        lambda c, t, n, s: model_lib.prefill(config, params, c, t, n, s, freqs)
    )(cache, tokens, lengths, np.asarray(slot_ids, np.int32))
    return cache, logits, counters, freqs


def _last(sizes, weights, rows):
    return [
        out[0] for out in reference.logits_at(
            sizes, weights, rows, [(len(r) - 1, len(r)) for r in rows], MAX_LEN
        )
    ]


# --------------------------------------------------------------------- #
# the three programs against the reference's full forward pass
# --------------------------------------------------------------------- #
def test_the_recipe_draws_the_weights_the_reference_draws(family):
    config, params, sizes, weights = family
    runs = hybrid.runs_of(config)
    assert runs == [("sparse", 0, 1, 0), ("lightning", 1, 2, 0), ("sparse", 3, 2, 1)]
    for number, (kind, start, count, _) in enumerate(runs):
        for name in ("wq", "wk", "wg", "wo", "w_down"):
            leaf = params[f"run{number}.{name}"]
            assert isinstance(leaf, QTensor) and leaf.q.dtype == jnp.int8
            assert leaf.q.shape[0] == count
            values, scale = weights["layers"][start + count - 1][name]
            np.testing.assert_array_equal(values, leaf.q[-1])
            np.testing.assert_allclose(leaf.scale[-1], scale)
    np.testing.assert_array_equal(weights["lm_head"][0], params["lm_head"].q)
    np.testing.assert_array_equal(weights["embedding"], params["embedding"])
    # norm scales lie away from 1: a dropped norm cannot stay correct
    for name in ("run0.q_norm", "run1.k_norm", "run1.out_norm", "final_norm"):
        assert float(np.abs(np.asarray(params[name]) - 1.0).mean()) > 0.1, name
    assert config.num_params() == sum(
        int(np.prod(leaf.shape)) for name, leaf in params.items()
        for leaf in ([leaf.q] if isinstance(leaf, QTensor) else [leaf])
    )


@pytest.mark.parametrize("lengths", [(30, 21), (90, 57)], ids=["dense", "selected"])
def test_prefill_then_decode_through_the_cache_matches_the_reference(family, lengths):
    """Below ``dense_len`` (32) every sparse layer attends densely; above
    it every later query selects: the counters say which ran."""
    config, params, sizes, weights = family
    rows = _prompts(config, lengths)
    slot_ids = [2, 0]
    cache, logits, counters, freqs = _prefilled(config, params, rows, slot_ids)
    want = _last(sizes, weights, rows)
    for row in range(2):
        assert _gap(logits[row], want[row]) < 2e-5
        assert np.abs(want[row]).max() > 0.3  # logits of a size worth comparing
    kept, visible, queries = (int(n) for n in counters)
    assert queries == sum(lengths) * 3  # three sparse layers
    assert (kept < visible) == (max(lengths) > 32)
    step = jax.jit(
        lambda c, t, n, w: model_lib.decode_step(config, params, c, t, n, freqs, w)
    )
    tokens, seen = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    active = np.array([True, False, True])
    picked = np.asarray(jnp.argmax(logits, -1))
    for _ in range(5):
        for row, slot in enumerate(slot_ids):
            rows[row] = rows[row] + [int(picked[row])]
            tokens[slot], seen[slot] = picked[row], len(rows[row])
        cache, logits, counters = step(cache, tokens, seen, active)
        assert int(counters[2]) == 2 * 3  # the riding slot counts nothing
        got = np.asarray(logits)[slot_ids]
        want = _last(sizes, weights, rows)
        for row in range(2):
            assert _gap(got[row], want[row]) < 2e-5
        picked = got.argmax(-1)


def test_a_chunked_prefill_of_three_windows_is_one_cold_prefill(family):
    """Window n + 1 starts from window n's state and KV; the last is
    right-padded and never re-teaches a position."""
    config, params, sizes, weights = family
    (prompt,) = _prompts(config, (83,))
    _, whole, _, _ = _prefilled(config, params, [prompt], [1])
    cache = model_lib.init_cache(config, SLOTS, MAX_LEN)
    for offset in (0, 32, 64):
        cache, logits, _ = _window(
            config, params, cache, [prompt[offset:offset + 32]], [offset], [1], 32
        )
    assert _gap(logits[0], np.asarray(whole[0])) < 2e-5
    assert _gap(logits[0], _last(sizes, weights, [prompt])[0]) < 2e-5


def test_a_window_at_position_nought_starts_from_zeros(family):
    """A slot that held another request: KV needs no reset (positions
    mask it), a state does, and the prefill at offset 0 gives it one."""
    config, params, _, _ = family
    first, second = _prompts(config, (70, 45))
    fresh, want, _, _ = _prefilled(config, params, [second], [1])
    used, _, _, _ = _prefilled(config, params, [first], [1])
    assert float(jnp.abs(used["state"][:, 1]).max()) > 0
    reused, got, _, _ = _prefilled(config, params, [second], [1], cache=used)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(reused["state"][:, 1]), np.asarray(fresh["state"][:, 1])
    )


def test_the_int8_form_is_its_dequantised_float32(family):
    config, quantized, _, _ = family
    plain = hybrid.init_params(config, SEED)
    assert not any(isinstance(leaf, QTensor) for leaf in plain.values())
    prompts = _prompts(config, (60,))
    _, got, _, _ = _prefilled(config, quantized, prompts, [0])
    _, want, _, _ = _prefilled(config, plain, prompts, [0])
    assert _gap(got, np.asarray(want)) < 2e-5


def test_the_int4_control_is_far_outside_the_tolerances(family):
    config, params, sizes, weights = family
    rows = _prompts(config, (90, 57))
    _, logits, _, _ = _prefilled(config, params, rows, [2, 0])
    spans = [(len(r) - 1, len(r)) for r in rows]
    for lower in ("int4", "no-selection"):
        control = reference.logits_at(sizes, weights, rows, spans, MAX_LEN, lower)
        assert min(_gap(logits[row], control[row][0]) for row in range(2)) > 2e-3, lower


def test_no_other_program_computes_the_family(family):
    config, params, _, _ = family
    with pytest.raises(NotImplementedError, match="hybrid"):
        model_lib.forward(config, params, jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="int8 form"):
        model_lib.init_cache(config, 2, 64, kv_quant=True)


# --------------------------------------------------------------------- #
# the kernels and the selection
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas-interpret"])
def test_the_chunk_wise_form_is_the_plain_recurrence(kernel):
    batch, seq, heads, dim = 2, 48, 4, 16
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v = (jax.random.normal(key, (batch, seq, heads * dim)) for key in keys[:3])
    state = jax.random.normal(keys[3], (batch, heads, dim, dim))
    slopes = lightning_ops.decay_slopes(heads)
    lengths = jnp.array([48, 29])
    heads_of = lambda x: x.reshape(batch, seq, heads, dim)  # noqa: E731
    want, moved = lightning_ops.lightning_recurrence(
        heads_of(q), heads_of(k), heads_of(v), state, slopes, lengths, dim ** -0.5
    )
    got, after = lightning_ops.lightning_prefill_attention(
        q, k, v, state, slopes, lengths, scale=dim ** -0.5, kernel=kernel,
        interpret=True,
    )
    real = (jnp.arange(seq)[None, :] < lengths[:, None])[:, :, None, None]
    assert float(jnp.abs((heads_of(got) - want) * real).max()) < 2e-5
    assert float(jnp.abs(after - moved).max()) < 2e-5


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas-interpret"])
def test_a_decode_step_moves_the_state_on_a_token(kernel):
    slots, heads, dim = 3, 4, 16
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k, v = (jax.random.normal(key, (slots, heads, dim)) for key in keys[:3])
    stack = jax.random.normal(keys[3], (2, slots, heads, dim, dim))
    slopes = lightning_ops.decay_slopes(heads)
    active = jnp.array([True, False, True])
    want, moved = lightning_ops.lightning_recurrence(
        q[:, None], k[:, None], v[:, None], stack[1], slopes,
        active.astype(jnp.int32), dim ** -0.5,
    )
    got, after = lightning_ops.lightning_decode_attention(
        q, k, v, stack, 1, active, slopes, scale=dim ** -0.5, kernel=kernel,
        interpret=True,
    )
    assert float(jnp.abs(got - want[:, 0])[active].max()) < 2e-5
    assert float(jnp.abs(after[1] - moved).max()) < 2e-5
    np.testing.assert_array_equal(np.asarray(after[0]), np.asarray(stack[0]))
    np.testing.assert_array_equal(np.asarray(after[1, 1]), np.asarray(stack[1, 1]))


def test_the_selected_blocks_are_the_references(family):
    """Random q and K without ties: the program's kept blocks (from its
    compressed-key cache) are the reference's (from K itself), for a
    window's queries and for a decode step's."""
    config, _, sizes, _ = family
    sel = config.hybrid.selection
    seq, heads, kv_heads, dim = 96, config.num_heads, config.num_kv_heads, 16
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    q = jax.random.normal(keys[0], (seq, heads, dim))
    k = jax.random.normal(keys[1], (seq, kv_heads, dim))
    want = reference.kept_blocks(sizes, q, k, 0)                 # [kv, t, b]
    assert bool((want.sum(-1)[:, 64:] < (jnp.arange(64, seq) // 8 + 1)).all())
    # the cache of compressed keys as the program keeps it: one a stride
    # (the last, whose window would pass the end, is never valid)
    windows = jnp.stack([
        jnp.pad(k, ((0, sel.kernel_size), (0, 0), (0, 0)))[at:at + sel.kernel_size].mean(0)
        for at in range(0, seq, sel.kernel_stride)
    ])                                                           # [w, kv, d]
    assert windows.shape[0] == sparse_ops.compressed_count(seq, sel)
    compressed = windows.swapaxes(0, 1)[None]
    positions = jnp.arange(seq)[None]
    got, counts = sparse_ops.select_blocks(
        q[None], compressed, positions, positions >= 0, sel, scale=dim ** -0.5,
        num_blocks=seq // sel.block_size,
    )
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want))
    assert int(counts[0]) == int(want.sum()) and int(counts[2]) == seq
    one, _ = sparse_ops.select_blocks(
        q[None, 77:78], compressed, positions[:, 77:78], jnp.ones((1, 1), bool),
        sel, scale=dim ** -0.5, num_blocks=seq // sel.block_size,
    )
    np.testing.assert_array_equal(np.asarray(one[0, :, 0]), np.asarray(want[:, 77]))


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_the_sparse_kernels_are_masked_attention(program):
    slots, heads, kv_heads, dim, max_len = 2, 16, 2, 16, 64
    keys = jax.random.split(jax.random.PRNGKey(13), 4)
    k_stack, v_stack = (
        jax.random.normal(key, (2, slots, kv_heads, max_len, dim)) for key in keys[:2]
    )
    width = 16 if program == "prefill" else 1
    q = jax.random.normal(keys[2], (slots, width, heads * dim))
    offsets = jnp.array([40, 8])
    positions = offsets[:, None] + jnp.arange(width)[None]
    mask = (
        jax.random.bernoulli(keys[3], 0.6, (slots, kv_heads, width, max_len))
        & (jnp.arange(max_len)[None, None, None] <= positions[:, None, :, None])
    )
    if program == "prefill":
        args = (q, k_stack, v_stack, mask, 1, jnp.arange(slots), offsets, offsets + width)
        run = sparse_ops.sparse_prefill_attention
    else:
        args = (q[:, 0].reshape(slots, heads, dim), k_stack, v_stack, mask[:, :, 0], 1)
        run = sparse_ops.sparse_decode_attention
    want = run(*args, scale=0.25, kernel=False)
    got = run(*args, scale=0.25, kernel=True, interpret=True)
    assert float(jnp.abs(got - want).max()) < 2e-5


# --------------------------------------------------------------------- #
# the engine: served through the normal path, refused by the switch's name
# --------------------------------------------------------------------- #
def _serve(engine, prompts, new_tokens=6):
    async def main():
        return await asyncio.gather(*[
            engine.generate(p, SamplingParams(max_new_tokens=new_tokens, temperature=0.0))
            for p in prompts
        ])

    return asyncio.run(main())


def test_the_engine_serves_chunked_prompts_and_a_reused_slot_is_a_fresh_engine(family):
    """Prompts past the largest bucket go through bucket-sized windows;
    one slot serves three prompts in turn (each over a state the one
    before left) and every answer is the reference's greedy one."""
    config, params, sizes, weights = family
    engine = DecodeEngine(
        config, params, max_slots=1, max_seq_len=MAX_LEN, prefill_buckets=[32],
        decode_chunk=4, quantize="int8",
    )
    assert engine.prefix_cache is False
    engine.start()
    prompts = _prompts(config, (75, 20, 97))
    try:
        results = [_serve(engine, [prompt])[0] for prompt in prompts]
    finally:
        engine.stop()
    for prompt, result in zip(prompts, results):
        full = prompt + result.tokens
        want = reference.logits_at(
            sizes, weights, [full], [(len(prompt) - 1, len(full) - 1)], MAX_LEN
        )[0]
        assert want.argmax(-1).tolist() == result.tokens  # greedy, float32
    stats = engine.stats
    assert stats["state_resets"] == 3 and stats["session_hits"] == 0
    assert 0 < stats["sparse_kept"] < stats["sparse_visible"]
    assert stats["sparse_queries"] > 3 * sum(len(p) for p in prompts)


REFUSED = {
    "kv-layout": dict(kv_layout="paged"),
    "prefill-mode": dict(kv_layout="paged", prefill_mode="mixed"),
    "kv-host-blocks": dict(kv_layout="paged", kv_host_blocks=8),
    "kv-quant": dict(kv_quant="int8"),
    "spec-decode": dict(spec_decode="ngram"),
    "mesh": None,
}


@pytest.mark.parametrize("switch", list(REFUSED))
def test_every_switch_the_family_cannot_take_is_refused_by_name(family, switch):
    from langstream_tpu.parallel.mesh import MeshConfig

    config, params, _, _ = family
    options = REFUSED[switch] or dict(mesh_config=MeshConfig(tp=2))
    with pytest.raises(ValueError, match=switch):
        DecodeEngine(config, params, max_slots=2, max_seq_len=64, **options)


def test_a_request_the_family_cannot_take_is_refused_at_submit(family):
    config, params, _, _ = family
    engine = DecodeEngine(
        config, params, max_slots=2, max_seq_len=MAX_LEN, prefill_buckets=[16, 32]
    )

    def request(**fields):
        return GenerationRequest(
            prompt_tokens=[1, 2, 3], sampling=SamplingParams(max_new_tokens=2),
            **fields,
        )

    with pytest.raises(ValueError, match="handoff"):
        engine.submit(request(export_handoff=True))
    with pytest.raises(ValueError, match="handoff"):
        engine.submit(request(kv_import={"rows": []}))


def test_a_follow_up_on_a_session_prefills_cold(family):
    """Session warm reuse copies no state: a follow-up on the same
    session id is a cold prefill, and its answer is the reference's."""
    config, params, sizes, weights = family
    engine = DecodeEngine(
        config, params, max_slots=2, max_seq_len=MAX_LEN, prefill_buckets=[32, 64],
        decode_chunk=4,
    )
    engine.start()
    (first,) = _prompts(config, (40,))

    async def turn(prompt):
        return await engine.generate(
            prompt, SamplingParams(max_new_tokens=4, temperature=0.0),
            session_id="s",
        )

    try:
        one = asyncio.run(turn(first))
        second = first + one.tokens + [5, 6, 7]
        two = asyncio.run(turn(second))
    finally:
        engine.stop()
    full = second + two.tokens
    want = reference.logits_at(
        sizes, weights, [full], [(len(second) - 1, len(full) - 1)], MAX_LEN
    )[0]
    assert want.argmax(-1).tolist() == two.tokens
    assert engine.stats["session_hits"] == 0 and engine.stats["state_resets"] == 2


def test_the_published_config_is_the_preset():
    """``config_from_hf`` on the published keys gives the preset, kind by
    kind; a switch the family does not compute is refused."""
    import types

    published = dict(
        model_type="minicpm_sala", vocab_size=73448, hidden_size=4096,
        intermediate_size=16384, num_hidden_layers=32, num_attention_heads=32,
        num_key_value_heads=2, head_dim=128, lightning_nh=32, lightning_nkv=32,
        lightning_head_dim=128, lightning_use_rope=True, attn_use_rope=False,
        qk_norm=True, use_output_gate=True, use_output_norm=True,
        attn_use_output_gate=True, rms_norm_eps=1e-6, rope_theta=10000,
        max_position_embeddings=524288, scale_emb=12, scale_depth=1.4,
        dim_model_base=256, tie_word_embeddings=False,
        mixer_types=[
            "minicpm4" if i in (0, 9, 16, 17, 22, 29, 30, 31) else "lightning-attn"
            for i in range(32)
        ],
    )
    config = model_lib.config_from_hf(types.SimpleNamespace(**published))
    preset = model_lib.LlamaConfig.minicpm_sala(max_seq_len=524288)
    assert config == preset
    # by hand: a SwiGLU 201,326,592; a sparse layer's mixer 52,428,800, a
    # lightning layer's 83,886,080; embedding and head 601,686,016; norms
    mlp, sparse, lightning = 3 * 4096 * 16384, 52_428_800, 5 * 4096 * 4096
    norms = 8 * (2 * 4096 + 256) + 24 * (3 * 4096 + 256) + 4096
    assert config.num_params() == (
        8 * (mlp + sparse) + 24 * (mlp + lightning) + 601_686_016 + norms
    ) == 9_477_206_016
    with pytest.raises(ValueError, match="attn_use_rope"):
        model_lib.config_from_hf(
            types.SimpleNamespace(**dict(published, attn_use_rope=True))
        )
