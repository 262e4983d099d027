"""The hybrid family (lightning linear attention beside block-sparse GQA)
through the self-test, as files alone (``reference/minicpm_sala.py`` and
``configs/tiny-hybrid-selftest.json``): served through the whole pipeline
on a CPU, every prompt chunked into 64-token windows and past the tiny
``dense_len``, and held to the family's plain reference; its two controls
(int4 weights, no selection) not correct; the size check's cases; the
work counts at the published widths by hand; the reference in blocks of
queries against one pass."""

import asyncio
import json
import os
import time

import numpy as np
import pytest

from benchmark import harness, report
from benchmark.tests.test_end_to_end import CPU, run, selftest_cell

CONFIG = "tiny-hybrid-selftest"


def test_a_sound_run_is_correct_and_every_prefill_is_chunked_and_cold(tmp_path):
    cell, raw, line = run("selftest-closed", 3_000_000_019, tmp_path, config=CONFIG)
    assert cell["family"].__name__.endswith(cell["config_file"]["model_type"])
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    for name in ("max_logit_gap", "mean_logit_gap"):
        assert line["compared"][name]["value"] <= line["compared"][name]["limit"]
    sent = [r for r in raw["records"] if "done" in r]
    assert sent and all(r.get("prompt_ids") and r.get("output_ids") for r in sent)
    # every prompt is longer than the one bucket and than dense_len
    assert min(len(r["prompt_ids"]) for r in sent) > 64
    close = raw["counters"]["close"]
    assert close["warm_prefill_calls"] == 0 and close["prefix_hits"] == 0


def test_a_rolled_head_is_not_correct(tmp_path, monkeypatch):
    import jax.numpy as jnp

    from langstream_tpu.providers.jax_local import model

    sound = model._logits
    monkeypatch.setattr(
        model, "_logits",
        lambda config, params, x: jnp.roll(sound(config, params, x), 1, axis=-1),
    )
    _, _, line = run("selftest-closed", 7, tmp_path, config=CONFIG)
    assert line["correct"] is False


@pytest.mark.parametrize("lower", ["int4", "no-selection"])
def test_the_controls_fail_the_comparison(lower, tmp_path):
    """The reference in the program's place in int4, and with the sparse
    layers computed densely: not correct, by each number at three times
    its limit or more; the program's own correct."""
    cell = selftest_cell(CONFIG, "selftest-closed")
    assert cell["config_file"]["lower_precision"] == "int4"
    raw = asyncio.run(harness.run_cell(
        cell, 3_000_000_023, 2.0, False, time.perf_counter(), CPU, str(tmp_path),
    ))
    got = report.compare_with_reference(cell, raw, 23, [lower])
    checks, correct = report.judge(cell, got["program"], 0, 0, 0)
    assert correct is True, checks
    checks, correct = report.judge(cell, got["control_" + lower], 0, 0, 0)
    assert correct is False
    for name in ("max_logit_gap", "mean_logit_gap"):
        assert checks[name]["value"] >= 3 * checks[name]["limit"]


def _engine_config(**fields):
    import dataclasses

    from langstream_tpu.providers.jax_local.model import LlamaConfig

    config = LlamaConfig.from_dict({"preset": "tiny-hybrid", "vocab-size": 512})
    return dataclasses.replace(config, **fields)


@pytest.mark.parametrize("fields,differs", [
    ({}, None),
    ({"mixers": ("sparse", "lightning", "sparse", "lightning", "sparse")}, "mixer_types"),
    ({"embedding_scale": 1.0}, "scale_emb"),
    ({"residual_scale": 1.0}, "scale_depth"),
    ({"logit_divisor": 1.0}, "dim_model_base"),
    ({"num_kv_heads": 4}, "num_key_value_heads"),
    ("topk", "sparse_config"),
])
def test_the_size_check_holds_the_program_to_the_file(fields, differs):
    import dataclasses

    cell = selftest_cell(CONFIG, "selftest-closed")
    if fields == "topk":
        config = _engine_config()
        selection = dataclasses.replace(config.hybrid.selection, topk=3)
        fields = {"hybrid": dataclasses.replace(config.hybrid, selection=selection)}
    engine_config = _engine_config(**fields)
    if differs is None:
        harness.check_sizes(cell["family"], engine_config, cell["config_file"])
        return
    with pytest.raises(SystemExit) as refused:
        harness.check_sizes(cell["family"], engine_config, cell["config_file"])
    assert differs in str(refused.value)


def test_the_size_check_covers_every_size_the_reference_reads():
    cell = selftest_cell(CONFIG, "selftest-closed")
    family = cell["family"]
    checked = family.size_check(_engine_config())
    read = []

    class Recording(dict):
        def __getitem__(self, key):
            read.append(key)
            return dict.__getitem__(self, key)

    family.Sizes(Recording(dict(cell["config_file"])))
    # what the reference reads and the program has no size for: the recipe
    # of the weights and the published switches, which Sizes itself refuses
    # when they are not the ones it computes
    free = {
        "weights", "qk_norm", "attn_use_rope", "lightning_use_rope",
        "use_output_gate", "use_output_norm", "attn_use_output_gate",
        "lightning_scale", "hidden_act",
    }
    assert set(read) - free <= set(checked)
    assert {"mixer_types", "sparse_config"} <= set(checked)


def _published():
    path = os.path.join(harness.ROOT, "benchmark", "configs", "minicpm-sala-int8.json")
    with open(path) as handle:
        file = json.load(handle)
    family = harness.load_module("reference", file["model_type"])
    return file, family, family.Sizes(file)


def test_the_published_file_is_the_published_config():
    """Every number of the catalog's row under its own key, the layers'
    kinds in the published order, nothing cut."""
    file, _, sizes = _published()
    sparse_at = [i for i, kind in enumerate(sizes.mixers) if kind == "sparse"]
    assert sparse_at == [0, 9, 16, 17, 22, 29, 30, 31] and sizes.layers == 32
    assert (file["hidden_size"], file["intermediate_size"], file["vocab_size"]) == (
        4096, 16384, 73448)
    assert (file["scale_emb"], file["scale_depth"], file["dim_model_base"],
            file["mup_denominator"]) == (12, 1.4, 256, 32)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as handle:
        entry = next(c for c in json.load(handle)["configs"] if c["name"] == file["name"])
    assert entry["reduced"] == [] and entry["source"] == file["source"]


# the published widths: goldens by hand. A SwiGLU 201,326,592; a sparse
# layer's mixer 52,428,800 (q, gate, o 16,777,216 each; k, v 1,048,576
# each); a lightning layer's 83,886,080 (five of 16,777,216)
def test_the_work_counts_at_the_published_widths():
    _, family, sizes = _published()
    mlp = 3 * 4096 * 16384
    body = 8 * (52_428_800 + mlp) + 24 * (83_886_080 + mlp)
    assert family.body_matmul_params(sizes) == body == 8_875_147_264
    head = 4096 * 73448
    # at or under dense_len a query keeps its whole context and scores no
    # compressed key; a lightning layer costs 4 x 32 x 128 x 128 a token
    state = 4 * 32 * 128 * 128
    assert int(family.kept_keys(sizes, 8192)) == 8192
    assert family.output_token_flops(sizes, 5000) == (
        2 * (body + head) + 8 * 4 * 32 * 128 * 5000 + 24 * state
    )
    # at 10,500: the local blocks start at (10500 - 2048) // 64 = 132, so
    # 131 far blocks, 64 kept, 67 dropped; 655 compressed keys end there
    assert int(family.kept_keys(sizes, 10500)) == 10500 - 67 * 64 == 6212
    assert int(family.scored_windows(sizes, 10500)) == (10500 - 32) // 16 + 1 == 655
    assert family.output_token_flops(sizes, 10500) == (
        2 * (body + head) + 8 * (4 * 32 * 128 * 6212 + 2 * 32 * 128 * 655) + 24 * state
    )
    # the kept keys stop growing: one more block of context, the same count
    assert int(family.kept_keys(sizes, 16000)) - int(family.kept_keys(sizes, 10500)) < 64
    # a prompt of 9,000: every token the body, one token the head, the
    # mixers token by token
    mixers = sum(int(family.mixer_flops(sizes, c)) for c in range(1, 9001))
    assert family.prompt_flops(sizes, 9000) == 2 * body * 9000 + mixers + 2 * head
    served = {"prompts": [9000], "decode_queries": 100, "decode_keys": 100 * 12000}
    flops, moved = family.kernel_work(sizes, "lightning_prefill", served)
    assert (flops, moved) == (state * 9000 * 24, 4 * 4096 * 2 * 9000 * 24)
    flops, moved = family.kernel_work(sizes, "lightning_decode", served)
    assert (flops, moved) == (state * 100 * 24, 2 * 32 * 128 * 128 * 4 * 100 * 24)
    kept = int(family.kept_keys(sizes, 12000))
    assert kept == 12000 - (154 - 64) * 64
    flops, moved = family.kernel_work(sizes, "sparse_block_decode", served)
    assert flops == 4 * 32 * 128 * kept * 100 * 8
    assert moved == (2 * 2 * 128 * 2 * kept * 100 + 2 * 100 * 32 * 128 * 2) * 8
    flops, moved = family.kernel_work(sizes, "sparse_block_prefill", served)
    keys = sum(int(family.kept_keys(sizes, c)) for c in range(1, 9001))
    assert flops == 4 * 32 * 128 * keys * 8
    assert moved == (2 * 32 + 2 * 2) * 128 * 2 * 9000 * 8
    assert family.kernel_work(sizes, "flash_decode", served) is None


def test_blocks_of_queries_agree_with_one_pass():
    """``logits_at`` runs the mixers a block of queries at a time; one
    block that holds the whole row is the plain pass."""
    import jax
    import jax.numpy as jnp

    cell = selftest_cell(CONFIG, "selftest-closed")
    family = cell["family"]
    sizes = family.Sizes(cell["config_file"])
    weights = family.make_weights(sizes, 5)
    row = list(np.random.default_rng(2).integers(0, sizes.vocab, size=100))
    blocked = family.logits_at(sizes, weights, [row], [(60, 100)], 256)[0]
    ids = np.zeros((128,), np.int32)
    ids[:100] = row
    with jax.default_matmul_precision("highest"):
        x = weights["embedding"][jnp.asarray(ids)].astype(jnp.float32) * sizes.scale_emb
        for kind, layer in zip(sizes.mixers, weights["layers"]):
            x = family._layer(x, layer, sizes, kind, None, 128)
        whole = family._head(x[60:100], weights["final_norm"], weights["lm_head"], sizes, None)
    assert float(np.abs(blocked - np.asarray(whole)).max()) < 2e-6
    assert float(np.abs(blocked).max()) > 0.3
