"""The short-convolution family (gated short-conv layers with a per-slot
conv state beside GQA layers with normed q and k heads; dense SwiGLU
behind the leading mixers, routed experts with a sigmoid router and a
selection bias behind the others) at a test size, float32 on the CPU: the
``tiny-conv-moe`` preset (conv conv | attention conv conv attention, 2
dense layers then 8 experts, 3 a token).

The dense layout's three programs (``short_conv_gqa.py`` beside GQA's
attends in ``model.py``) are held to the plain reference the benchmark
compares with (``benchmark/reference/lfm2_moe.py``: one full forward pass,
no cache, no state carried, the conv a loop over taps, the experts a loop)
on seeded weights whose norm scales, filter taps and selection bias are
drawn so that each fault of the family changes the logits: a cold prefill,
a chunked prefill with a right-padded tail, decode through the cache past
a chunk's end, a reused slot, the five faults, the packed decode kernel,
and every switch the family cannot take to its refusal.
"""

import asyncio
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as reference
from langstream_tpu.ops import moe as moe_ops
from langstream_tpu.providers.jax_local import model as model_lib
from langstream_tpu.providers.jax_local import short_conv_gqa as short_conv
from langstream_tpu.providers.jax_local.engine import (
    DecodeEngine,
    GenerationRequest,
    SamplingParams,
)

SEED = 11
SLOTS, MAX_LEN = 3, 128
NAMES = {"conv": "conv", "attention": "full_attention"}
TIGHT = 2e-5  # float32 program against float32 reference


def tiny(**fields):
    config = model_lib.LlamaConfig.from_dict({"preset": "tiny-conv-moe"})
    return dataclasses.replace(config, flash_interpret=True, **fields)


def file_of(config, slots=SLOTS):
    """The configuration's file the reference reads, for a program config."""
    experts = config.experts
    return {
        "vocab_size": config.vocab_size, "hidden_size": config.hidden_size,
        "intermediate_size": config.intermediate_size,
        "moe_intermediate_size": experts.intermediate_size,
        "num_hidden_layers": config.num_layers,
        "layer_types": [NAMES[kind] for kind in config.mixers],
        "num_dense_layers": experts.leading_dense,
        "num_attention_heads": config.num_heads,
        "num_key_value_heads": config.num_kv_heads,
        "conv_L_cache": config.short_conv.taps, "conv_bias": False,
        "num_experts": experts.routed, "experts_held_first": 0,
        "experts_held": experts.held,
        "num_experts_per_tok": experts.per_token,
        "norm_topk_prob": experts.renormalise,
        "routed_scaling_factor": experts.scaling_factor,
        "use_expert_bias": True, "norm_eps": config.norm_eps,
        "rope_parameters": {"rope_theta": config.rope_theta, "rope_type": "default"},
        "tie_word_embeddings": True, "weights": "f32-normal",
        "globals": {"max-slots": slots},
    }


@pytest.fixture(scope="module")
def family():
    config = tiny()
    params = model_lib.init_params(config, SEED)
    sizes = reference.Sizes(file_of(config))
    return config, params, sizes, reference.make_weights(sizes, SEED)


def _gap(logits, want):
    return float(np.abs(np.asarray(logits) - want).max())


def _prompts(config, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, config.vocab_size, size=n)) for n in lengths]


def _window(config, params, cache, rows, offsets, slot_ids, width):
    tokens = np.zeros((len(rows), width), np.int32)
    for row, piece in enumerate(rows):
        tokens[row, : len(piece)] = piece
    with jax.default_matmul_precision("highest"):
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
    with jax.default_matmul_precision("highest"):
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
    assert short_conv.runs_of(config) == [
        ("conv", "dense", 0, 2), ("attention", "experts", 2, 1),
        ("conv", "experts", 3, 2), ("attention", "experts", 5, 1),
    ]
    assert short_conv.state_index(config).tolist() == [0, 1, 0, 2, 3, 1]
    layers = weights["layers"]
    pairs = {
        "run0.in_proj": (1, "in_proj"), "run0.out_proj": (1, "out_proj"),
        "run0.w_down": (1, "down"), "run1.wq": (2, "wq"), "run1.wo": (2, "wo"),
        "run2.in_proj": (4, "in_proj"), "run3.wk": (5, "wk"),
    }
    for name, (layer, leaf) in pairs.items():
        np.testing.assert_array_equal(params[name][-1], layers[layer][leaf][0])
    for name, (layer, leaf) in {
        "run0.filter": (1, "filter"), "run1.q_norm": (2, "q_norm"),
        "run2.expert_bias": (4, "bias"), "run3.router": (5, "router"),
        "run2.ffn_norm": (4, "ffn_norm"),
    }.items():
        np.testing.assert_array_equal(params[name][-1], layers[layer][leaf])
    # the expert stacks hold the expert layers alone: layer 3 is their row 1
    np.testing.assert_array_equal(params["moe.w_up"][1], layers[3]["expert_up"][0])
    np.testing.assert_array_equal(params["embedding"], weights["embedding"][0])
    # what makes each fault show: norm scales away from 1, the q and k
    # norms' around 2, three taps of like size, a bias that is not nought
    for name in ("run0.op_norm", "run1.q_norm", "run3.k_norm", "final_norm"):
        assert float(np.abs(np.asarray(params[name]) - 1.0).mean()) > 0.1, name
    assert float(np.asarray(params["run1.q_norm"]).min()) >= 1.5
    taps = np.abs(np.asarray(params["run0.filter"])).mean(axis=(0, 2))
    assert taps.max() < 1.5 * taps.min()
    assert float(np.abs(np.asarray(params["run2.expert_bias"])).mean()) > 0.01
    assert config.num_params() == sum(int(np.prod(p.shape)) for p in params.values())


def test_prefill_then_decode_through_the_cache_matches_the_reference(family):
    """A cold prefill of two rows of different lengths into slots 2 and 0,
    then 2 x conv_L_cache + 1 decode steps with a slot riding along: every
    step's logits are the reference's at that position."""
    config, params, sizes, weights = family
    rows = _prompts(config, (40, 23))
    slot_ids = [2, 0]
    cache, logits, counters, freqs = _prefilled(config, params, rows, slot_ids)
    want = _last(sizes, weights, rows)
    for row in range(2):
        assert _gap(logits[row], want[row]) < TIGHT
        assert np.abs(want[row]).max() > 1.0  # logits of a size worth comparing
    # 4 expert layers, 3 experts a token, every expert held
    assert [int(n) for n in counters[:2]] == [63 * 3 * 4] * 2
    assert int(counters[3:].sum()) == 63 * 3 * 4
    step = jax.jit(
        lambda c, t, n, w: model_lib.decode_step(config, params, c, t, n, freqs, w)
    )
    tokens, seen = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    active = np.array([True, False, True])
    picked = np.asarray(jnp.argmax(logits, -1))
    untouched = np.asarray(cache["conv"][:, 1])
    for _ in range(2 * config.short_conv.taps + 1):
        for row, slot in enumerate(slot_ids):
            rows[row] = rows[row] + [int(picked[row])]
            tokens[slot], seen[slot] = picked[row], len(rows[row])
        with jax.default_matmul_precision("highest"):
            cache, logits, counters = step(cache, tokens, seen, active)
        assert int(counters[0]) == int(counters[1]) == 2 * 3 * 4  # the rider routes nowhere
        got = np.asarray(logits)[slot_ids]
        want = _last(sizes, weights, rows)
        for row in range(2):
            assert _gap(got[row], want[row]) < TIGHT
        picked = got.argmax(-1)
    np.testing.assert_array_equal(np.asarray(cache["conv"][:, 1]), untouched)


def test_a_chunked_prefill_with_a_padded_tail_is_one_cold_prefill(family):
    """Window n + 1 starts from window n's conv state and KV rows; the last
    is right-padded (19 of 32 rows valid), hands on the state at its last
    VALID position and teaches no position twice: the decode step behind
    it reads the reference's logits too."""
    config, params, sizes, weights = family
    (prompt,) = _prompts(config, (83,))
    _, whole, _, freqs = _prefilled(config, params, [prompt], [1])
    cache = model_lib.init_cache(config, SLOTS, MAX_LEN)
    for offset in (0, 32, 64):
        cache, logits, _ = _window(
            config, params, cache, [prompt[offset:offset + 32]], [offset], [1], 32
        )
    assert _gap(logits[0], np.asarray(whole[0])) < TIGHT
    assert _gap(logits[0], _last(sizes, weights, [prompt])[0]) < TIGHT
    row = prompt + [int(np.asarray(logits[0]).argmax())]
    tokens, seen = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tokens[1], seen[1] = row[-1], len(row)
    with jax.default_matmul_precision("highest"):
        _, after, _ = jax.jit(
            lambda c, t, n, w: model_lib.decode_step(config, params, c, t, n, freqs, w)
        )(cache, tokens, seen, np.array([False, True, False]))
    assert _gap(after[1], _last(sizes, weights, [row])[0]) < TIGHT
    # a window that would pass the cache's end drops the rows past it
    late = model_lib.init_cache(config, SLOTS, MAX_LEN)
    late, _, _ = _window(config, params, late, [prompt[:8]], [MAX_LEN - 8], [0], 32)
    assert float(jnp.abs(late["k"][:, 0, : MAX_LEN - 8]).max()) == 0.0
    assert float(jnp.abs(late["k"][:, 0, MAX_LEN - 8:]).max()) > 0.0


@pytest.mark.parametrize("cache_of", ["conv", "gqa", "gqa-int8-kv"])
def test_a_windows_attention_in_blocks_is_the_windows_attention(
    family, monkeypatch, cache_of
):
    """``_offset_attend`` attends a block of each row's queries at a time
    where the window's scores would pass ``SCORES_IN_FLIGHT_BYTES``: two
    rows of 32 queries in blocks of 8 read what they read whole, for this
    family, for plain GQA and over an int8 cache."""
    config, params = family[:2]
    if cache_of != "conv":
        config = dataclasses.replace(
            model_lib.LlamaConfig.tiny(max_seq_len=MAX_LEN), dtype=jnp.float32
        )
        params = model_lib.init_params(config, SEED)
    first, then = _prompts(config, (40, 51))

    def windows():
        cache = model_lib.init_cache(
            config, SLOTS, MAX_LEN, kv_quant=cache_of == "gqa-int8-kv"
        )
        cache, _, _ = _window(
            config, params, cache, [first[:32], then[:32]], [0, 0], [2, 0], 32
        )
        return _window(
            config, params, cache, [first[32:], then[32:]], [32, 32], [2, 0], 32
        )

    def loops():
        return jax.jit(
            lambda c: model_lib.prefill_at_offset(
                config, params, c, np.zeros((2, 32), np.int32),
                np.ones(2, np.int32), np.zeros(2, np.int32),
                np.arange(2, dtype=np.int32), model_lib.model_freqs(config),
            )
        ).lower(whole_cache).as_text().count("stablehlo.while")

    whole_cache, whole, _ = windows()
    layers_only = loops()
    monkeypatch.setattr(
        model_lib, "SCORES_IN_FLIGHT_BYTES", 16 * 4 * config.num_heads * MAX_LEN
    )
    assert loops() > layers_only  # the blocks' loop beside the layers' own
    cache, blocked, _ = windows()
    assert _gap(blocked, np.asarray(whole)) < TIGHT
    for name, leaf in whole_cache.items():  # a later layer's rows: rounding
        room = 1 if leaf.dtype == jnp.int8 else TIGHT
        assert _gap(cache[name].astype(jnp.float32), np.asarray(leaf, np.float32)) <= room


def test_a_window_at_position_nought_starts_from_zeros(family):
    """A slot that held another request: KV needs no reset (positions mask
    it), a conv state does, and the prefill at offset 0 gives it one."""
    config, params, _, _ = family
    first, second = _prompts(config, (70, 45))
    fresh, want, _, _ = _prefilled(config, params, [second], [1])
    used, _, _, _ = _prefilled(config, params, [first], [1])
    assert float(jnp.abs(used["conv"][:, 1]).max()) > 0
    reused, got, _, _ = _prefilled(config, params, [second], [1], cache=used)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(reused["conv"][:, 1]), np.asarray(fresh["conv"][:, 1])
    )


def _without_qk_norm(monkeypatch, config, params):
    sound = short_conv.layer_runs

    def dropped(config, params):
        return [
            (mixer, (layers[0], layers[1][:4], *layers[2:]), first, experts)
            if mixer == "attention" else (mixer, layers, first, experts)
            for mixer, layers, first, experts in sound(config, params)
        ]

    monkeypatch.setattr(short_conv, "layer_runs", dropped)
    return params


def _filter_a_tap_off(monkeypatch, config, params):
    return {
        name: jnp.roll(leaf, 1, axis=1) if name.endswith(".filter") else leaf
        for name, leaf in params.items()
    }


def _weights_from_biased_scores(monkeypatch, config, params):
    def faulty(logits, bias, *, num_selected, scaling_factor, renormalise):
        scores = jax.nn.sigmoid(logits) + bias
        weights, chosen = jax.lax.top_k(scores, num_selected)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
        return weights * scaling_factor, chosen

    monkeypatch.setattr(model_lib, "sigmoid_bias_routing", faulty)
    return params


def _bias_left_out(monkeypatch, config, params):
    return {
        name: jnp.zeros_like(leaf) if name.endswith(".expert_bias") else leaf
        for name, leaf in params.items()
    }


FAULTS = {
    "a dropped q/k norm": _without_qk_norm,
    "the filter a tap off": _filter_a_tap_off,
    "weights from s + b": _weights_from_biased_scores,
    "the bias left out": _bias_left_out,
    "the conv state not carried": None,
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_each_fault_of_the_family_fails_the_comparison(family, monkeypatch, fault):
    """What the recipe's draws are for: each way of computing another
    model moves the logits by hundreds of times the tolerance."""
    config, params, sizes, weights = family
    rows = _prompts(config, (64, 50), seed=5)
    want = _last(sizes, weights, rows)
    if FAULTS[fault] is None:
        # two windows of the first row, the state between them lost
        cache = model_lib.init_cache(config, SLOTS, MAX_LEN)
        cache, _, _ = _window(config, params, cache, [rows[0][:32]], [0], [0], 32)
        cache = dict(cache, conv=jnp.zeros_like(cache["conv"]))
        _, logits, _ = _window(config, params, cache, [rows[0][32:]], [32], [0], 32)
        assert _gap(logits[0], want[0]) > 100 * TIGHT
        return
    faulty = FAULTS[fault](monkeypatch, config, params)
    _, logits, _, _ = _prefilled(config, faulty, rows, [0, 1])
    assert min(_gap(logits[row], want[row]) for row in range(2)) > 100 * TIGHT


def test_the_int8_control_is_far_outside_the_tolerance(family):
    config, params, sizes, weights = family
    rows = _prompts(config, (64, 50), seed=5)
    _, logits, _, _ = _prefilled(config, params, rows, [0, 1])
    spans = [(len(r) - 1, len(r)) for r in rows]
    control = reference.logits_at(sizes, weights, rows, spans, MAX_LEN, "int8")
    assert min(_gap(logits[row], control[row][0]) for row in range(2)) > 100 * TIGHT


def test_the_packed_decode_kernel_reads_the_attention_layers_rows(monkeypatch):
    """Heads of 64 over 2 kv heads: K and V lie packed, two heads to a
    128-lane row, for the ATTENTION layers alone, and the decode step goes
    through ``flash_decode`` (interpret mode) to the reference's logits."""
    config = tiny(hidden_size=256, head_dim=64)
    params = model_lib.init_params(config, SEED)
    sizes = reference.Sizes(file_of(config))
    weights = reference.make_weights(sizes, SEED)
    cache = model_lib.init_cache(config, SLOTS, MAX_LEN)
    assert cache["k"].shape == (2, SLOTS, MAX_LEN, 1, 128)
    assert cache["conv"].shape == (4, SLOTS, 2, 256)
    assert model_lib.decode_reader(config, cache) == "flash_decode"
    rows = _prompts(config, (40, 23))
    cache, logits, _, freqs = _prefilled(config, params, rows, [2, 0], cache=cache)
    tokens, seen = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    picked = np.asarray(jnp.argmax(logits, -1))
    for _ in range(2):
        for row, slot in enumerate((2, 0)):
            rows[row] = rows[row] + [int(picked[row])]
            tokens[slot], seen[slot] = picked[row], len(rows[row])
        with jax.default_matmul_precision("highest"):
            cache, logits, _ = jax.jit(
                lambda c, t, n, w: model_lib.decode_step(
                    config, params, c, t, n, freqs, w
                )
            )(cache, tokens, seen, np.array([True, False, True]))
        got = np.asarray(logits)[[2, 0]]
        want = _last(sizes, weights, rows)
        for row in range(2):
            assert _gap(got[row], want[row]) < TIGHT
        picked = got.argmax(-1)


def test_no_other_program_computes_the_family(family):
    config, params, _, _ = family
    with pytest.raises(NotImplementedError, match="conv"):
        model_lib.forward(config, params, jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="int8 form"):
        model_lib.init_cache(config, 2, 64, kv_quant=True)
    with pytest.raises(ValueError, match="mixers with"):
        model_lib.LlamaConfig.from_dict(
            {"preset": "tiny-conv-moe", "short-conv": None, "experts": None}
        )
    with pytest.raises(ValueError, match="short_conv"):
        model_lib.LlamaConfig.from_dict({"preset": "tiny-moe", "experts": {
            "routed": 8, "held-first": 0, "held": 8, "intermediate-size": 32,
            "per-token": 2, "shared": 0, "leading-dense": 1, "groups": 1,
            "groups-kept": 1, "scaling-factor": 1.0,
        }})


# --------------------------------------------------------------------- #
# the engine: served through the normal path, refused by the switch's name
# --------------------------------------------------------------------- #
def _serve(engine, prompts, new_tokens=10):
    async def main():
        return await asyncio.gather(*[
            engine.generate(p, SamplingParams(max_new_tokens=new_tokens, temperature=0.0))
            for p in prompts
        ])

    return asyncio.run(main())


def test_the_engine_serves_chunked_prompts_and_a_reused_slot_is_a_fresh_engine(family):
    """Prompts past the largest bucket go through bucket-sized windows
    with a right-padded tail; one slot serves three prompts in turn (each
    over the state the one before left), 10 tokens each past two chunk
    boundaries, and every answer is the reference's greedy one."""
    config, params, sizes, weights = family
    engine = DecodeEngine(
        config, params, max_slots=1, max_seq_len=MAX_LEN, prefill_buckets=[32],
        decode_chunk=4,
    )
    assert engine.stateful and engine.prefix_cache is False
    assert engine.stats["decode_reader"] == "xla"
    assert engine.stats["cache_leaf_shape"] == (4, 1, 2, 64)  # the conv leaf
    engine.start()
    prompts = _prompts(config, (75, 20, 97))
    try:
        with jax.default_matmul_precision("highest"):
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
    routed = (sum(len(p) for p in prompts) + 3 * 9) * 3 * 4
    # a slot decodes to its chunk's end: up to 3 steps past each answer
    assert routed <= stats["moe_assignments"] <= routed + 3 * 3 * 3 * 4
    assert stats["moe_assignments_held"] == stats["moe_assignments"]
    assert sum(stats["moe_tokens_by_expert"]) == stats["moe_assignments"]
    assert stats["moe_rows_computed"] >= stats["moe_assignments"]
    assert stats["sparse_queries"] == 0
    # and the exposition carries them: the experts' counters by name and
    # by expert, the resets, the reader beside how the conv leaf lies
    from langstream_tpu.providers.jax_local.engine import engines_snapshot

    shown = engines_snapshot()
    assert shown["jax_engine_moe_assignments_total"] >= stats["moe_assignments"]
    assert shown["jax_engine_state_resets_total"] >= 3
    assert 'jax_engine_moe_tokens_by_expert_total{expert="7"}' in shown
    assert 'jax_engine_decode_reader{reader="xla",cache="4x1x2x64"}' in shown


REFUSED = {
    "kv-layout": dict(kv_layout="paged"),
    "prefill-mode": dict(kv_layout="paged", prefill_mode="mixed"),
    "kv-host-blocks": dict(kv_layout="paged", kv_host_blocks=8),
    "kv-quant": dict(kv_quant="int8"),
    "quantization": dict(quantize="int8"),
    "spec-decode": dict(spec_decode="ngram"),
    "mesh": None,
}


@pytest.mark.parametrize("switch", list(REFUSED))
def test_every_switch_the_family_cannot_take_is_refused_by_name(family, switch):
    """By what the config HAS: a carried state and routed experts."""
    from langstream_tpu.parallel.mesh import MeshConfig

    config, params, _, _ = family
    options = REFUSED[switch] or dict(mesh_config=MeshConfig(tp=2))
    with pytest.raises(ValueError, match=switch) as refused:
        DecodeEngine(config, params, max_slots=2, max_seq_len=64, **options)
    assert "a carried state, routed experts" in str(refused.value)
    assert "latent" not in str(refused.value)


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


def test_the_published_config_is_the_preset_and_a_cut_keeps_the_first_layers():
    """``config_from_hf`` on the published keys gives the preset; the
    ``num-layers`` override cuts the per-layer ``mixers`` with the depth; a
    switch the family does not compute is refused."""
    layer_types = [
        "full_attention" if i % 4 == 2 else "conv" for i in range(40)
    ]
    published = dict(
        model_type="lfm2_moe", vocab_size=65536, hidden_size=2048,
        intermediate_size=11776, moe_intermediate_size=1536,
        num_hidden_layers=40, num_attention_heads=32, num_key_value_heads=8,
        conv_L_cache=3, conv_bias=False, layer_types=layer_types,
        max_position_embeddings=128000, norm_eps=1e-5, norm_topk_prob=True,
        num_dense_layers=2, num_experts=64, num_experts_per_tok=4,
        routed_scaling_factor=1, use_expert_bias=True,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    )
    config = model_lib.config_from_hf(types.SimpleNamespace(**published))
    assert config == model_lib.LlamaConfig.lfm2_24b_a2b(max_seq_len=128000)
    # by hand: a conv mixer 16,777,216 + 6,144 of filter; an attention
    # mixer 10,485,760 + 128 of q/k norms; a dense feed-forward
    # 72,351,744; an expert layer's router and bias 131,136 and 64
    # experts of 9,437,184; two norms a layer; the embedding and its norm
    conv, attention = 4 * 2048 * 2048 + 3 * 2048, 10_485_760 + 128
    dense, routed = 3 * 2048 * 11776, 2048 * 64 + 64 + 64 * 9_437_184
    assert config.num_params() == (
        30 * conv + 10 * attention + 2 * dense + 38 * routed + 40 * 2 * 2048
        + 65536 * 2048 + 2048
    ) == 23_843_661_440
    cut = model_lib.LlamaConfig.from_dict({"preset": "lfm2-24b-a2b", "num-layers": "10"})
    assert cut.mixers == config.mixers[:10] and cut.num_layers == 10
    assert cut.mixers.count("attention") == 2
    assert cut.num_params() == (
        8 * conv + 2 * attention + 2 * dense + 8 * routed + 10 * 2 * 2048
        + 65536 * 2048 + 2048
    ) == 5_267_090_176
    assert [run[:2] + run[3:] for run in short_conv.runs_of(cut)] == [
        ("conv", "dense", 2), ("attention", "experts", 1), ("conv", "experts", 3),
        ("attention", "experts", 1), ("conv", "experts", 3),
    ]
    for switch in (dict(conv_bias=True), dict(use_expert_bias=False)):
        with pytest.raises(ValueError, match=next(iter(switch))):
            model_lib.config_from_hf(types.SimpleNamespace(**dict(published, **switch)))


def test_the_routing_rule_reaches_the_expert_block(family):
    """``_expert_block`` hands ``moe_mlp_held`` the config's rule: with the
    bias pushed far up on two experts every token chooses them."""
    config, params, _, _ = family
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 9, 64)), jnp.float32)
    bias = jnp.zeros((8,), jnp.float32).at[jnp.array([1, 6])].set(5.0)
    stacks = tuple(params[f"moe.{name}"] for name in short_conv.EXPERT_STACKS)
    _, counters = model_lib._expert_block(
        config, x, (params["run2.router"][0], bias), stacks, 1, None
    )
    by_expert = counters[3:].tolist()
    assert by_expert[1] == by_expert[6] == 9 and sum(by_expert) == 27
    assert moe_ops.routed_tile(9, 3, 8) == 16
