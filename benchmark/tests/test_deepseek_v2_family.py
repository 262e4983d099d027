"""The latent-attention, routed-experts family through the self-test, as
files alone (``reference/deepseek_v2.py``, ``configs/tiny-deepseek-
selftest.json`` and the share's app): a chip's SHARE of the model — the
held range a strict part of the router's experts, a sliced vocabulary —
served through the whole pipeline on a CPU and held to the family's plain
reference; the size check's cases for the share; goldens of the work
counts at the published widths."""

import asyncio
import json
import os
import time

import pytest

from benchmark import harness, report
from benchmark.tests.test_end_to_end import CPU, run, selftest_cell

CONFIG = "tiny-deepseek-selftest"


def test_a_sound_run_of_the_share_is_correct(tmp_path):
    cell, raw, line = run("selftest-closed", 3_000_000_019, tmp_path, config=CONFIG)
    assert cell["family"].__name__.endswith("deepseek_v2")
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    for name in ("max_logit_gap", "mean_logit_gap"):
        assert line["compared"][name]["value"] <= line["compared"][name]["limit"]
    sent = [r for r in raw["records"] if "done" in r]
    assert sent and all(r.get("prompt_ids") and r.get("output_ids") for r in sent)
    # ids are sampled from the slice
    held = cell["config_file"]["vocab_size"]
    assert all(0 <= t < held for r in sent for t in r["output_ids"])
    assert raw["counters"]["close"]["warm_prefill_calls"] == 0


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
    for seed in (21, 3_000_000_023):
        raw = asyncio.run(harness.run_cell(
            cell, seed, 2.0, False, time.perf_counter(), CPU, str(tmp_path),
        ))
        got = report.compare_with_reference(cell, raw, seed, [lower])
        checks, correct = report.judge(cell, got["program"], 0, 0, 0)
        assert correct is True, checks
        checks, correct = report.judge(cell, got["control_" + lower], 0, 0, 0)
        assert correct is False
        for name in ("max_logit_gap", "mean_logit_gap"):
            assert checks[name]["value"] >= 3 * checks[name]["limit"]


def _engine_config(**model_keys):
    from langstream_tpu.providers.jax_local.model import LlamaConfig

    keys = {
        "preset": "tiny-deepseek-v2", "num-layers": 3, "experts-held-first": 2,
        "experts-held": 4, "vocab-size": 320,
    }
    keys.update(model_keys)
    return LlamaConfig.from_dict(keys)


@pytest.mark.parametrize("model_keys,differs", [
    ({}, None),
    ({"experts-held": 2}, "n_routed_experts"),         # fewer experts held
    ({"experts-held-first": 0}, "experts_held_first"),  # another range
    ({"vocab-size": 512}, "vocab_size"),                # the uncut vocabulary
    ({"num-layers": 2}, "num_hidden_layers"),
])
def test_the_size_check_holds_the_program_to_the_share(model_keys, differs):
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
    file = dict(cell["config_file"])
    read = []

    class Recording(dict):
        def __getitem__(self, key):
            read.append(key)
            return dict.__getitem__(self, key)

    family.Sizes(Recording(file))
    # what the reference reads and the program has no size for: the recipe
    # of the weights and the routing's published switches, which Sizes
    # itself refuses when they are not the ones it computes
    free = {"weights", "norm_topk_prob", "scoring_func", "topk_method", "moe_layer_freq"}
    assert set(read) - free <= set(checked)


# the published widths of the cell's configuration: goldens by hand
# attention 149,159,936 a layer; an expert 23,592,960; shared 47,185,920;
# router 819,200; dense feed-forward 188,743,680
def test_the_work_counts_at_the_published_widths():
    with open(os.path.join(harness.ROOT, "benchmark", "configs", "deepseek-v2-ep4.json")) as handle:
        file = json.load(handle)
    family = harness.load_module("reference", file["model_type"])
    sizes = family.Sizes(file)
    attention = 7_864_320 + 37_748_736 + 2_949_120 + 16_777_216 + 83_886_080
    assert family.attention_params(sizes) == attention == 149_225_472
    assert family.experts_met(sizes) == 1.5
    expert = 3 * 5120 * 1536
    body = (
        attention + 3 * 5120 * 12288
        + 4 * (attention + 5120 * 160 + 2 * expert + 1.5 * expert)
    )
    assert family.body_matmul_params(sizes) == body
    # one output token at a context of 3,000: the body and the head's
    # slice twice, and 128 heads x (192 + 128) x 3,000 x 5 layers twice
    head = 5120 * 25600
    assert family.output_token_flops(sizes, 3000) == int(
        2 * (body + head) + 2 * 128 * 320 * 3000 * 5
    )
    # a prompt of 2,000: every token the body, one token the head, and the
    # causal pairs
    pairs = 2000 * 2001 // 2
    assert family.prompt_flops(sizes, 2000) == int(
        2 * body * 2000 + 2 * 128 * 320 * 5 * pairs + 2 * head
    )
    served = {"prompts": [2000, 4000], "decode_queries": 64, "decode_keys": 64 * 3000}
    pairs = 2000 * 2001 // 2 + 4000 * 4001 // 2
    flops, moved = family.kernel_work(sizes, "flash_prefill", served)
    assert flops == 2 * 128 * (192 + 128) * pairs * 5
    # q and k 192 wide, v and o 128 wide, bf16, every token once a layer
    assert moved == 6000 * 128 * (2 * 192 + 2 * 128) * 2 * 5
    flops, moved = family.kernel_work(sizes, "mla_decode", served)
    assert flops == 2 * 128 * (576 + 512) * 64 * 3000 * 5
    assert moved == (64 * 3000 * 576 + 64 * 128 * (576 + 512)) * 2 * 5
    assert family.kernel_work(sizes, "flash_decode", served) is None


def test_the_routing_tool_reads_a_share(monkeypatch, capsys):
    """``tools/routing.py`` on the self-test's share: every expert layer
    is read, int8 weights route more tokens otherwise than bf16
    activations do, and the control's mean gap is the larger."""
    import sys

    routing = harness.load_module("tools", "routing")
    cell = selftest_cell(CONFIG, "selftest-closed")
    monkeypatch.setattr(harness, "load_cell", lambda workload: cell)
    monkeypatch.setattr(sys, "argv", [
        "routing.py", "--workload", cell["name"], "--seeds", "3000000031",
        "--tokens", "48", "--tail", "16",
    ])
    assert routing.main() == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    sizes = cell["family"].Sizes(cell["config_file"])
    assert len(line["layers"]) == sizes.layers - sizes.dense_layers
    for layer in line["layers"]:
        assert 0 < layer["held_share"] < 1 and layer["peak"] >= 1
        assert 0 < layer["tail_experts"] <= sizes.held
        assert layer["flipped"]["int8"] >= layer["flipped"]["bf16"] >= 0
    assert line["int8"]["prompt"]["mean_logit_gap"] >= line["bf16"]["prompt"]["mean_logit_gap"]
