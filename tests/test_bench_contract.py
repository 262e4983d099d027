"""The bench artifact contract its readers rely on: the LAST stdout
line is the result; provisional successes are never followed by zero
records; every record carries its phase timings. Regressions here zero
the scoreboard, so CI pins the state machine."""

from __future__ import annotations

import contextlib
import importlib.util
import io
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fresh_bench(monkeypatch, **env):
    """Import bench.py as a new module with a controlled environment."""
    for key in list(os.environ):
        if key.startswith("BENCH_"):
            monkeypatch.delenv(key, raising=False)
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    spec = importlib.util.spec_from_file_location(
        f"bench_contract_{id(env)}", os.path.join(REPO, "bench.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _lines(buffer: io.StringIO):
    return [
        json.loads(line)
        for line in buffer.getvalue().splitlines() if line.strip()
    ]


def test_provisional_then_final_last_line_wins(monkeypatch):
    bench = _fresh_bench(monkeypatch)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        bench.emit_provisional("prov_metric", 111.0, note="warmup")
        bench.emit_provisional("prov_metric", 222.0, note="mid-measure")
        bench.emit_success(333.0, {"k": "v"})
    records = _lines(out)
    assert [r["value"] for r in records] == [111.0, 222.0, 333.0]
    assert records[0]["provisional"] and records[1]["provisional"]
    assert "provisional" not in records[-1]
    assert records[-1]["value"] == 333.0  # the driver parses the LAST line


def test_failure_never_follows_provisional_success(monkeypatch):
    bench = _fresh_bench(monkeypatch)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        bench.emit_provisional("prov_metric", 50.0)
        suppressed = bench.emit_failure("engine died")
    assert suppressed is False
    records = _lines(out)
    assert records[-1]["value"] == 50.0  # provisional stands as last line
    # a provisional is not the final line: the once-only lock stays free
    assert not bench._EMITTED.locked()


def test_plain_failure_still_emits(monkeypatch):
    bench = _fresh_bench(monkeypatch)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert bench.emit_failure("backend down") is True
    record = _lines(out)[-1]
    assert record["value"] == 0.0 and record["error"] == "backend down"
    assert "timings_s" in record


def test_final_emit_is_once_only(monkeypatch):
    bench = _fresh_bench(monkeypatch)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        bench.emit_success(400.0, {})
        assert bench.emit("again", 1.0, 0.1) is False
        bench.emit_provisional("late_prov", 2.0)  # no-op after final
    records = _lines(out)
    assert len(records) == 1 and records[0]["value"] == 400.0


def test_metric_suffix_shared_by_all_builders(monkeypatch):
    bench = _fresh_bench(
        monkeypatch, BENCH_MODEL="llama-3-8b", BENCH_QUANT="int8"
    )
    assert bench.metric_suffix() == "llama_3_8b_int8"
    assert bench.metric_name().endswith(bench.metric_suffix())
