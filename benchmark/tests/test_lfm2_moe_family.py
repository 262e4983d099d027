"""The short-convolution, routed-experts family through the self-test, as
files alone (``reference/lfm2_moe.py`` and ``configs/tiny-convmoe-
selftest.json``): served through the whole pipeline on a CPU, every prompt
chunked into 64-token windows with a right-padded tail (the conv state
carried), decoded through the cache, and held to the family's plain
reference; the lower-precision control and a rolled head not correct; the
size check's cases; the work counts at the published widths by hand; the
cell's own readers on a context that holds nothing for them."""

import asyncio
import json
import os
import time

import pytest

from benchmark import harness, report
from benchmark.tests.test_end_to_end import CPU, run, selftest_cell

CONFIG = "tiny-convmoe-selftest"


def test_a_sound_run_is_correct_and_every_prefill_is_chunked_and_cold(tmp_path):
    cell, raw, line = run("selftest-closed", 3_000_000_019, tmp_path, config=CONFIG)
    assert cell["family"].__name__.endswith("lfm2_moe")
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    for name in ("max_logit_gap", "mean_logit_gap"):
        assert line["compared"][name]["value"] <= line["compared"][name]["limit"]
    sent = [r for r in raw["records"] if "done" in r]
    assert sent and all(r.get("prompt_ids") and r.get("output_ids") for r in sent)
    # every prompt is longer than the one bucket: windows, the last padded
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
    for name in ("max_logit_gap", "mean_logit_gap"):
        assert line["compared"][name]["value"] > 100 * line["compared"][name]["limit"]


def test_the_int8_control_fails_the_comparison(tmp_path):
    cell = selftest_cell(CONFIG, "selftest-closed")
    lower = cell["config_file"]["lower_precision"]
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


def _engine_config(**model_keys):
    from langstream_tpu.providers.jax_local.model import LlamaConfig

    keys = {
        "preset": "tiny-conv-moe", "num-layers": 6, "experts-held-first": 0,
        "experts-held": 8, "vocab-size": 512,
    }
    keys.update(model_keys)
    return LlamaConfig.from_dict(keys)


@pytest.mark.parametrize("model_keys,differs", [
    ({}, None),
    ({"num-layers": 5}, "layer_types"),                 # a deeper cut
    ({"experts-held": 4}, "experts_held"),              # a share of the experts
    ({"vocab-size": 320}, "vocab_size"),                # a sliced vocabulary
    ({"tie_embeddings": False}, "tie_word_embeddings"),
    ({"short_conv": {"taps": 4}}, "conv_L_cache"),
])
def test_the_size_check_holds_the_program_to_the_file(model_keys, differs):
    cell = selftest_cell(CONFIG, "selftest-closed")
    engine_config = _engine_config(**model_keys)
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

    family.Sizes(Recording(cell["config_file"]))
    # what the reference reads and the program has no size for: the
    # recipe of the weights, and the slots a step holds (the engine's)
    assert set(read) - {"weights", "globals"} <= set(checked)
    # a share of the experts is a configuration this reference refuses
    with pytest.raises(ValueError, match="does not compute"):
        family.Sizes(dict(cell["config_file"], experts_held=4))


# the published widths of the cell's configuration: goldens by hand.
# conv mixer 4 x 2,048^2 = 16,777,216; attention mixer 2,048 x 64 x (64 +
# 16) = 10,485,760; dense feed-forward 3 x 2,048 x 11,776 = 72,351,744; an
# expert 3 x 2,048 x 1,536 = 9,437,184; router 131,072
def test_the_work_counts_at_the_published_widths():
    with open(os.path.join(harness.ROOT, "benchmark", "configs", "lfm2-24b-a2b.json")) as handle:
        file = json.load(handle)
    family = harness.load_module("reference", file["model_type"])
    sizes = family.Sizes(file)
    assert family.attention_layers(sizes) == 2
    assert family.mixer_params(sizes, "conv") == 16_777_216
    assert family.mixer_params(sizes, "attention") == 10_485_760
    expert = 9_437_184
    body = (
        8 * 16_777_216 + 2 * 10_485_760 + 2 * 72_351_744
        + 8 * (131_072 + 4 * expert)
    )
    assert family.body_matmul_params(sizes) == body == 602_931_200
    head = 2048 * 65536
    # one output token at a context of 1,500: the body and the head twice,
    # and 32 heads x 64 x 1,500 x 2 attention layers, QK and PV
    assert family.output_token_flops(sizes, 1500) == (
        2 * (body + head) + 4 * 32 * 64 * 1500 * 2
    )
    pairs = 700 * 701 // 2
    assert family.prompt_flops(sizes, 700) == (
        2 * body * 700 + 4 * 32 * 64 * 2 * pairs + 2 * head
    )
    served = {"prompts": [700], "decode_queries": 64 * 100, "decode_keys": 64 * 100 * 1000}
    flops, moved = family.kernel_work(sizes, "flash_decode", served)
    assert flops == 4 * 32 * 64 * 6_400_000 * 2
    # K and V rows of 8 kv heads x 64 in bf16, q and out of 32 x 64
    assert moved == (2 * 8 * 64 * 2 * 6_400_000 + 2 * 6400 * 32 * 64 * 2) * 2
    # 64 slots, 4 of 64 experts a token: 64 (1 - (60/64)^64) touched a step
    touched = 64 * (1 - (60 / 64) ** 64)
    assert family.experts_touched(sizes) == pytest.approx(touched)
    assert 62.9 < touched < 63.0
    flops, moved = family.kernel_work(sizes, "moe_grouped_matmul", served)
    assert flops == 2 * expert * 6400 * 4 * 8
    assert moved == int((100 * touched * expert + 6400 * 4 * 2 * 2048) * 2 * 8)
    # a step's bytes are its bound: 9.5 GB of expert weights against 3.9 GFLOP
    assert moved / 819e9 > 50 * flops / 197e12
    assert family.kernel_work(sizes, "flash_prefill", served) is None
    assert family.kernel_work(sizes, "moe_grouped_matmul", {
        "prompts": [700], "decode_queries": 0, "decode_keys": 0,
    }) is None


def test_the_cells_readers_find_nothing_where_there_is_nothing_to_read():
    """A traced run of a program without this PR's spans or kernels (the
    parent commit), or a run without a trace: every reader of the cell
    returns None and raises nothing."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as handle:
        benchmark = json.load(handle)
    names = [
        m["name"] for m in benchmark["per_layer"]
        if m.get("workloads") == ["lfm2-24b-a2b.gen"]
    ]
    assert len(names) == 17
    cell = selftest_cell(CONFIG, "selftest-closed")
    counters = {"chunk_log_len": 0, "tokens_wasted": {}}
    raw = {
        "records": [], "window": {"opens": 0.0, "closes": 1.0}, "setup_s": 0.0,
        "counters": {"open": counters, "close": counters}, "chunk_log": [],
        "slots": 4, "decode_chunk": 4,
    }
    bare = report.context(cell, raw, CPU)
    empty_trace = dict(bare, trace={
        "programs": [], "phases": [], "flows": [], "gaps": [], "busy_s": 0.0,
        "kernel_s": 0.0, "window_s": 1.0, "lo": 0.0, "hi": 1e9, "chips": 1,
        "marked": True, "op_seconds": {}, "gap_total_s": 0.0,
        "begin": dict(counters, at=0.0), "end": dict(counters, at=1.0),
    })
    for name in names:
        reader = harness.load_module("metrics", name)
        assert reader.read(dict(bare)) is None, name
        assert reader.read(dict(empty_trace)) is None, name
