"""The harness end to end on two tiny configurations, of two families,
that no cell of BENCHMARK.json references and no harness file names: up
to the point where it would demand a TPU through the command line (which
it must refuse on a CPU), and past that point with the look for a chip
skipped, sound and with the timed path broken underneath. The second
(``tiny-moe-selftest``: a sparse mixture of experts) enters as files
alone: ``reference/mixtral.py`` and its configuration's file."""

import asyncio
import json
import os
import re
import subprocess
import sys
import time

import pytest

from benchmark import harness, report

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# one configuration a family: the file's ``model_type`` finds the family
FAMILIES = ["tiny-selftest", "tiny-moe-selftest"]


def selftest_cell(config, traffic):
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as handle:
        benchmark = json.load(handle)
    name = config + "." + traffic
    benchmark["workloads"].append({
        "name": name, "config": config, "traffic": traffic,
        "chips": 1, "why": "self-test",
    })
    for metric in benchmark["end_to_end"] + benchmark["per_layer"]:
        if "workloads" in metric:
            metric["workloads"].append(name)
    return harness.load_cell(name, benchmark)


def run(traffic, seed, tmp_path, trace=False, seconds=3.0, config="tiny-selftest"):
    cell = selftest_cell(config, traffic)
    raw = asyncio.run(harness.run_cell(
        cell, seed, seconds, trace, time.perf_counter(), CPU, str(tmp_path),
    ))
    return cell, raw, report.result_line(cell, raw, seed, trace, CPU)


def test_the_command_refuses_without_a_tpu():
    for workload in ("qwen25-7b-int8.sat", "qwen25-0.5b.chat"):
        done = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", workload,
             "--seed", "1", "--seconds", "1", "--trace", "0"],
            cwd=harness.ROOT, capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert done.returncode != 0
        assert "needs a TPU" in done.stderr
        assert done.stdout.strip() == ""


def test_the_command_refuses_an_unknown_cell():
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "nope",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert done.returncode != 0 and done.stdout.strip() == ""


@pytest.mark.parametrize("config,traffic", [
    ("tiny-selftest", "selftest-open"), ("tiny-selftest", "selftest-closed"),
    ("tiny-moe-selftest", "selftest-closed"),
])
def test_a_sound_run_is_correct_and_reports_its_metrics(config, traffic, tmp_path):
    cell, raw, line = run(traffic, 3_000_000_019, tmp_path, config=config)
    assert cell["family"].__name__.endswith(cell["config_file"]["model_type"])
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "compared"
    assert set(line["built_in_window"]) == {"programs", "seconds"}
    for name in ("max_logit_gap", "mean_logit_gap"):
        assert line["compared"][name]["value"] <= line["compared"][name]["limit"]
    wanted = {m["name"] for m in cell["end_to_end"]}
    assert set(line["metrics"]) == wanted
    assert all(v["value"] > 0 for v in line["metrics"].values())
    # every request went through the whole pipeline: the engine probe saw
    # each one the client sent
    sent = [r for r in raw["records"] if "done" in r]
    assert sent and all(r.get("prompt_ids") and r.get("output_ids") for r in sent)
    # what run.py keeps of a run for a later look is plain JSON
    kept = json.loads(json.dumps(report.run_summary(raw, line)))
    assert len(kept["requests"]) == sum(1 for r in raw["records"] if "frames" in r)
    assert kept["chunk_log"]
    assert sum(n for _, n in kept["tokens_per_half_second"]) == sum(
        r["tokens"] for r in kept["requests"]
    )
    # and no two prompts share more than the chat template's head: the
    # mix's frame puts the question first, so every prefill is cold
    assert raw["counters"]["close"]["prefix_hits"] == 0
    assert raw["counters"]["close"]["warm_prefill_calls"] == 0


def test_a_mix_of_sessions_is_data_alone(tmp_path):
    """Multi-turn conversations on one session id, the instruction first
    and shared by every prompt, a follow-up carrying its history: a traffic
    file and nothing else. The engine's prefix reuse sees it."""
    cell, raw, line = run("selftest-sessions", 13, tmp_path, seconds=4.0)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    done = [r for r in raw["records"] if "done" in r]
    assert {r["turn"] for r in done} == {0, 1, 2}
    by_session = {}
    for record in done:
        by_session.setdefault(record["session"], []).append(record)
    for turns in by_session.values():
        turns.sort(key=lambda r: r["turn"])
        for before, after in zip(turns, turns[1:]):
            # sent once the answer before had ended, and the probe's record
            # of the follow-up is the follow-up's own: its prompt holds the
            # earlier one's
            assert after["sent"] >= before["done"]
            shared = len(before["prompt_ids"]) - 20
            assert after["prompt_ids"][:shared] == before["prompt_ids"][:shared]
            assert len(after["prompt_ids"]) > len(before["prompt_ids"])
    close = raw["counters"]["close"]
    assert close["prefix_hits"] > 0 or close["warm_prefill_calls"] > 0


def test_a_traced_run_reports_per_layer_metrics_and_leaves_out_what_it_cannot_read(tmp_path):
    cell, raw, line = run("selftest-open", 5, tmp_path, trace=True)
    assert line["correct"] is True
    assert "pipeline_in_ms.p50" in line["metrics"]
    assert "engine_first_token_ms.p50" in line["metrics"]
    # no TPU plane in a CPU trace: the device metrics are left out, never 0
    for name in ("device_idle_share.chat", "mfu.chat"):
        assert name not in line["metrics"]


@pytest.mark.parametrize("config", FAMILIES)
def test_a_token_altered_where_it_is_produced_is_not_correct(config, tmp_path, monkeypatch):
    """The timed path broken underneath: the model's output head hands the
    sampler logits shifted by one id, so every served token is its
    neighbour's."""
    import jax.numpy as jnp

    from langstream_tpu.providers.jax_local import model

    sound = model._logits
    monkeypatch.setattr(
        model, "_logits",
        lambda config, params, x: jnp.roll(sound(config, params, x), 1, axis=-1),
    )
    _, _, line = run("selftest-closed", 7, tmp_path, config=config)
    assert line["correct"] is False
    for name in ("max_logit_gap", "mean_logit_gap"):
        assert line["compared"][name]["value"] > 100 * line["compared"][name]["limit"]


def test_an_answer_that_never_comes_is_not_correct(tmp_path, monkeypatch):
    from benchmark import client

    sound = client.Chat.ask

    async def lossy(self, record, due, lead):
        if record["index"] % 5 == 3:
            record["frames"] = []
            record["due"] = due if due is not None else time.perf_counter()
            record["error"] = "dropped by the test"
            record["failed_at"] = time.perf_counter()
            return
        await sound(self, record, due, lead)

    monkeypatch.setattr(client.Chat, "ask", lossy)
    _, _, line = run("selftest-open", 9, tmp_path)
    assert line["failed"] > 0 and line["correct"] is False


@pytest.mark.parametrize("config", FAMILIES)
def test_the_control_in_a_lower_precision_fails_the_comparison(config, tmp_path):
    """The reference put in the program's place, in the nearest precision
    below the configuration's, through the judgement a run's ``correct``
    comes from: not correct, by each number at three times its limit or
    more, and the program's own correct, on three seeds."""
    cell = selftest_cell(config, "selftest-closed")
    lower = cell["config_file"]["lower_precision"]
    for seed in (21, 22, 3_000_000_023):
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


def test_nothing_to_compare_is_not_correct():
    cell = selftest_cell("tiny-selftest", "selftest-closed")
    checks, correct = report.judge(cell, None, 0, 0, 0)
    assert correct is False and checks["requests_compared"]["value"] == 0


def test_no_harness_file_names_a_family():
    """A family is ``reference/<model_type>.py`` and is found through the
    configuration's file: no module of the harness, no metric's reader, no
    generator and no tool says its name, so a new one needs no edit."""
    import glob

    here = os.path.join(harness.ROOT, "benchmark")
    families = [
        os.path.basename(path)[:-3]
        for path in glob.glob(os.path.join(here, "reference", "*.py"))
        if os.path.basename(path) not in ("__init__.py", "compare.py")
    ]
    assert {"qwen2", "mixtral"} <= set(families)
    files = [os.path.join(here, "reference", "compare.py")]
    for folder in ("", "metrics", "generators", "tools"):
        files += glob.glob(os.path.join(here, folder, "*.py"))
    assert len(files) > 40
    for path in files:
        with open(path) as handle:
            text = handle.read()
        for family in families:
            assert family not in text, (path, family)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as handle:
        named = json.load(handle)
    assert "tiny-moe-selftest" not in json.dumps(named)
    # and the count of a GQA decoder is reached through its family alone
    for path in files:
        with open(path) as handle:
            assert not re.search(r"import .*\bflops\b|flops import", handle.read()), path
