"""The observability plane (ISSUE 1): end-to-end trace-id propagation
(gateway -> topic -> runner -> engine), the engine flight recorder
(flush-on-crash evidence), and the unified Prometheus exposition served
by every scrape surface."""

import asyncio
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
APP = os.path.join(REPO, "examples", "applications", "jax-completions")
INSTANCE = os.path.join(REPO, "examples", "instances", "local-tiny.yaml")
GOLDEN = os.path.join(os.path.dirname(__file__), "golden")


# ---------------------------------------------------------------------- #
# unified Prometheus exposition
# ---------------------------------------------------------------------- #
def _sample_exposition() -> str:
    from langstream_tpu.api.metrics import Histogram, MetricsReporter, prometheus_text

    reporter = MetricsReporter(prefix="agent_demo")
    reporter.counter("records_in").count(7)
    reporter.counter("errors").count(1)
    histogram = reporter.histogram("latency_seconds", buckets=(0.01, 0.1, 1.0))
    for value in (0.005, 0.05, 0.05, 0.5, 2.0):
        histogram.observe(value)
    gauges = {
        "jax_engine_slot_occupancy": 0.75,
        "jax_engine_decode_ms_per_step": 12.5,
        # paged KV pool + prefix cache (engines_snapshot, kv_layout: paged)
        "kv_blocks_in_use": 42.0,
        "kv_blocks_total": 64.0,
        "prefix_cache_hit_tokens_total": 1024.0,
        "prefix_cache_evictions_total": 3.0,
        # efficiency accounting (ISSUE 4): roofline utilization, goodput
        # ledger (labeled wasted-token reasons), SLO burn rates, watchdog
        "jax_engine_mfu": 0.42,
        "jax_engine_mbu": 0.63,
        "jax_engine_goodput_ratio": 0.9375,
        "jax_engine_tokens_useful_total": 960.0,
        'jax_engine_tokens_wasted_total{reason="cancelled"}': 48.0,
        'jax_engine_tokens_wasted_total{reason="evicted_recompute"}': 16.0,
        # speculative decoding (ISSUE 7): drafted/accepted counters +
        # acceptance rate, and rejected drafts as a wasted reason
        'jax_engine_tokens_wasted_total{reason="draft_rejected"}': 24.0,
        # chunked mixed prefill (ISSUE 12): prompt-padding ghosts —
        # split-path bucket rounding vs the mixed path's width cap
        'jax_engine_tokens_wasted_total{reason="prefill_padding"}': 40.0,
        # mixed-step carry (ISSUE 14): speculatively chained steps,
        # per-reason chain-break counters, and the tokens a chained
        # step sampled for rows that had already stopped
        'jax_engine_tokens_wasted_total{reason="carry_invalidated"}': 2.0,
        "jax_engine_mixed_steps_chained_total": 57.0,
        'mixed_carry_invalidations_total{reason="admission"}': 4.0,
        'mixed_carry_invalidations_total{reason="stale_row"}': 1.0,
        "spec_tokens_drafted_total": 96.0,
        "spec_tokens_accepted_total": 72.0,
        "spec_acceptance_rate": 0.75,
        "jax_engine_slo_ttft_p95_target_ms": 200.0,
        "jax_engine_slo_ttft_burn_rate_5m": 0.8,
        "jax_engine_slo_ttft_burn_rate_1h": 0.4,
        "watchdog_trips_total": 1.0,
        # self-healing serving (ISSUE 9): supervisor recovery counters,
        # the degraded-mode gauge, crash-replay waste, load shedding
        'jax_engine_tokens_wasted_total{reason="crash_replay"}': 12.0,
        "engine_restarts_total": 1.0,
        "sessions_resurrected_total": 2.0,
        "engine_degraded": 0.0,
        'requests_shed_total{reason="queue_timeout"}': 3.0,
        # fleet layer (ISSUE 11): the admission backlog the router's
        # least-queue fallback and the autoscaler's pressure math read
        "jax_engine_queue_depth": 2.0,
        # the engine thread's seconds by phase (ISSUE 26), summed at the
        # phase spans' own boundaries
        'jax_engine_loop_seconds_total{phase="idle"}': 12.5,
        'jax_engine_loop_seconds_total{phase="admit"}': 0.75,
        'jax_engine_loop_seconds_total{phase="dispatch"}': 0.25,
        'jax_engine_loop_seconds_total{phase="emit"}': 3.5,
        # the engine thread's CPU seconds in the same phases (ISSUE 37):
        # wall minus CPU is the phase's time off the CPU
        'jax_engine_loop_cpu_seconds_total{phase="idle"}': 0.125,
        'jax_engine_loop_cpu_seconds_total{phase="admit"}': 0.5,
        'jax_engine_loop_cpu_seconds_total{phase="dispatch"}': 0.25,
        'jax_engine_loop_cpu_seconds_total{phase="emit"}': 3.25,
        # request-journey ledger (ISSUE 20): per-stage SLO blame —
        # violating requests counted by their dominant journey stage
        'jax_engine_slo_blame_total{kind="ttft",stage="queue"}': 2.0,
        'jax_engine_slo_blame_total{kind="tpot",stage="handoff_transit"}':
            1.0,
    }
    # request-journey ledger (ISSUE 20): per-stage latency histogram
    # families (jax_engine_journey_<stage>_seconds) — fresh Histograms
    # with the ledger's buckets, NOT the process-global STAGE_SECONDS
    # (other tests observe into those; the golden must be deterministic)
    from langstream_tpu.runtime.journey import _STAGE_BUCKETS

    histograms = reporter.histogram_snapshots()
    for stage, values in (
        ("queue", (0.004, 0.02, 0.02)),
        ("handoff_transit", (0.3, 4.0)),
    ):
        stage_histogram = Histogram(
            f"jax_engine_journey_{stage}_seconds",
            buckets=_STAGE_BUCKETS,
        )
        for value in values:
            stage_histogram.observe(value)
        histograms[stage_histogram.name] = stage_histogram.snapshot()
    return prometheus_text(
        reporter.snapshot(), gauges, histograms,
        help_texts={
            "jax_engine_slot_occupancy":
                "mean fraction of decode slots active",
            "kv_blocks_in_use":
                "paged KV pool blocks referenced by slots or prefix cache",
            "prefix_cache_hit_tokens_total":
                "prompt tokens served from cached prefix blocks",
            "prefix_cache_evictions_total":
                "prefix-cache blocks evicted under pool pressure",
            "jax_engine_mfu":
                "model FLOP utilization vs the per-chip peak (roofline)",
            "jax_engine_mbu":
                "HBM bandwidth utilization vs the per-chip peak",
            "jax_engine_goodput_ratio":
                "useful tokens / all generated tokens",
            "jax_engine_tokens_wasted_total":
                "tokens burned on cancelled requests, evicted-session"
                " recompute, rejected speculative drafts, or prefill"
                " bucket/width padding, by reason",
            "spec_tokens_drafted_total":
                "speculative-decode candidate tokens proposed by the"
                " prompt-lookup drafter",
            "jax_engine_mixed_steps_chained_total":
                "mixed steps dispatched off the previous step's"
                " device-resident carry (two-step window plan)",
            "mixed_carry_invalidations_total":
                "mixed-step chains broken or contradicted, by reason",
            "spec_acceptance_rate":
                "fraction of drafted tokens the verify step accepted",
            "jax_engine_slo_ttft_burn_rate_5m":
                "TTFT SLO burn rate over 5m (1.0 = consuming budget at"
                " the allowed rate)",
            "watchdog_trips_total":
                "decode-stall watchdog trips (degraded / no-progress /"
                " kv-pool livelock)",
            "engine_restarts_total":
                "supervisor engine rebuilds (crash or watchdog"
                " escalation)",
            "sessions_resurrected_total":
                "live sessions re-admitted bitwise onto a rebuilt engine",
            "engine_degraded":
                "1 while the supervisor is rebuilding (serving 503 +"
                " Retry-After) or terminally failed",
            "requests_shed_total":
                "pending requests failed fast at the admission deadline,"
                " by reason",
            "jax_engine_queue_depth":
                "requests waiting for a decode slot (submit queue +"
                " admission pending); the fleet routing/scaling signal",
            "jax_engine_loop_seconds_total":
                "engine thread seconds by loop phase (idle, admit,"
                " dispatch, emit), at the phase spans' boundaries",
            "jax_engine_loop_cpu_seconds_total":
                "engine thread CPU seconds by loop phase, at the same"
                " boundaries (wall minus CPU: off the CPU in the phase)",
            "jax_engine_slo_blame_total":
                "SLO-violating requests by kind (ttft/tpot) and the"
                " journey stage that dominated the violated window",
            "jax_engine_journey_queue_seconds":
                "request-journey stage latency: admission queue wait",
            "jax_engine_journey_handoff_transit_seconds":
                "request-journey stage latency: KV handoff fabric"
                " transit (export stamp to decode-side arrival)",
        },
    )


def test_prometheus_exposition_matches_golden():
    """The shared renderer's output is pinned byte-for-byte: runner
    pods, the OpenAI server, and the gateway all serve through it, so a
    format drift here is a format drift on every scrape endpoint."""
    text = _sample_exposition()
    golden_path = os.path.join(GOLDEN, "metrics_exposition.txt")
    with open(golden_path) as handle:
        assert text == handle.read()


def test_prometheus_exposition_parses_as_valid_format():
    from langstream_tpu.api.metrics import parse_prometheus_text

    text = _sample_exposition()
    parsed = parse_prometheus_text(text)  # raises on malformed lines
    assert parsed["agent_demo_records_in_total"] == [({}, 7.0)]
    assert parsed["jax_engine_slot_occupancy"] == [({}, 0.75)]
    # labeled gauge samples (goodput ledger reasons) parse into one
    # family with per-label samples, sharing a single HELP/TYPE header
    wasted = parsed["jax_engine_tokens_wasted_total"]
    assert ({"reason": "cancelled"}, 48.0) in wasted
    assert ({"reason": "evicted_recompute"}, 16.0) in wasted
    assert text.count("# TYPE jax_engine_tokens_wasted_total gauge") == 1
    buckets = parsed["agent_demo_latency_seconds_bucket"]
    assert ({"le": "+Inf"}, 5.0) in buckets
    # every family carries HELP + TYPE
    for line in text.splitlines():
        if line.startswith("# TYPE"):
            name = line.split()[2]
            assert f"# HELP {name} " in text
    with pytest.raises(ValueError):
        parse_prometheus_text("not { a metric line !!!")


def test_quantile_from_buckets():
    from langstream_tpu.api.metrics import quantile_from_buckets

    samples = [
        ({"le": "0.01"}, 1.0), ({"le": "0.1"}, 9.0), ({"le": "+Inf"}, 10.0),
    ]
    # linear interpolation inside the winning bucket (no stairstep at
    # bucket edges): rank 5 sits 50% into (0.01, 0.1] by count
    assert quantile_from_buckets(samples, 0.5) == pytest.approx(0.055)
    # the first bucket interpolates from 0
    assert quantile_from_buckets(samples, 0.05) == pytest.approx(0.005)
    # a rank exactly at a bucket's cumulative count lands on its bound
    assert quantile_from_buckets(samples, 0.9) == pytest.approx(0.1)
    # rank in the +Inf bucket caps at the highest finite bound
    # (histogram_quantile semantics), never returns inf
    assert quantile_from_buckets(samples, 0.99) == 0.1
    assert quantile_from_buckets([], 0.5) is None


def test_all_three_surfaces_share_the_renderer():
    """pod.prometheus_text IS api.metrics.prometheus_text (one code
    path), and the gateway + OpenAI server route through it too."""
    import inspect

    from langstream_tpu.api import metrics as api_metrics
    from langstream_tpu.runtime import pod

    assert pod.prometheus_text is api_metrics.prometheus_text
    gateway_src = inspect.getsource(
        sys.modules["langstream_tpu.gateway.server"]
        if "langstream_tpu.gateway.server" in sys.modules
        else __import__(
            "langstream_tpu.gateway.server", fromlist=["server"]
        )
    )
    assert "prometheus_text" in gateway_src
    openai_src = inspect.getsource(
        __import__(
            "langstream_tpu.serving.openai_api", fromlist=["openai_api"]
        )
    )
    assert "from langstream_tpu.api.metrics import prometheus_text" in openai_src


# ---------------------------------------------------------------------- #
# flight recorder
# ---------------------------------------------------------------------- #
@pytest.fixture
def flight_recorder(tmp_path):
    """A freshly-targeted global recorder, restored after the test so
    later engine constructions don't keep appending to tmp files."""
    from langstream_tpu.runtime import flight

    saved = (flight.RECORDER.path, flight.RECORDER._last_flush)
    flight.RECORDER.path = None
    flight.RECORDER._pending.clear()
    path = flight.configure(str(tmp_path / "flight"))
    yield flight, path
    flight.RECORDER.flush()
    flight.RECORDER.path = saved[0]


def test_flight_recorder_writes_jsonl(flight_recorder):
    flight, path = flight_recorder
    flight.record("phase", name="backend-init")
    flight.record("decode_chunk", steps=4, active=2, slots=4, step_ms=1.5)
    flight.flush()
    entries = flight.read_artifact(path)
    kinds = [e["kind"] for e in entries]
    assert kinds[0] == "meta"
    assert "phase" in kinds and "decode_chunk" in kinds
    assert all("ts" in e for e in entries)
    assert flight.latest_artifact(str(os.path.dirname(path))) == path


def test_flight_recorder_tolerates_torn_tail(flight_recorder):
    flight, path = flight_recorder
    flight.record("phase", name="measure")
    flight.flush()
    with open(path, "a") as handle:
        handle.write('{"ts": 1, "kind": "decode_ch')  # killed mid-write
    entries = flight.read_artifact(path)
    assert entries[-1]["kind"] == "phase"


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_flight_recorder_flush_on_engine_crash(flight_recorder):
    """A crashing engine loop must leave its artifact on disk BEFORE
    failing waiters — the whole point is evidence behind a dead run."""
    from langstream_tpu.providers.jax_local.engine import (
        DecodeEngine,
        SamplingParams,
    )
    from langstream_tpu.providers.jax_local.model import (
        LlamaConfig,
        init_params,
    )

    flight, path = flight_recorder
    config = LlamaConfig.tiny(max_seq_len=64)
    engine = DecodeEngine(
        config, init_params(config), max_slots=2, max_seq_len=64,
        prefill_buckets=[16],
    )

    def boom(*a, **k):
        raise RuntimeError("injected device failure")

    engine._get_prefill = boom  # type: ignore[method-assign]

    async def main():
        with pytest.raises(RuntimeError):
            await asyncio.wait_for(
                engine.generate([1, 2, 3], SamplingParams(max_new_tokens=4)),
                timeout=30,
            )

    asyncio.run(main())
    entries = flight.read_artifact(path)
    kinds = [e["kind"] for e in entries]
    assert "engine_start" in kinds
    crash = next(e for e in entries if e["kind"] == "engine_crash")
    assert "injected device failure" in crash["error"]


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_flight_recorder_decode_series_and_ab_analyze(flight_recorder):
    """A successful run's artifact carries decode step-time and
    slot-occupancy series, and tools/ab_analyze.py reads them."""
    from langstream_tpu.providers.jax_local.engine import (
        DecodeEngine,
        SamplingParams,
    )
    from langstream_tpu.providers.jax_local.model import (
        LlamaConfig,
        init_params,
    )

    flight, path = flight_recorder
    config = LlamaConfig.tiny(max_seq_len=64)
    engine = DecodeEngine(
        config, init_params(config), max_slots=2, max_seq_len=64,
        prefill_buckets=[16],
    )

    async def main():
        result = await engine.generate(
            [1, 2, 3], SamplingParams(max_new_tokens=6)
        )
        assert len(result.tokens) == 6

    asyncio.run(main())
    engine.stop()
    entries = flight.read_artifact(path)
    chunks = [e for e in entries if e["kind"] == "decode_chunk"]
    assert chunks, "no decode telemetry in the artifact"
    assert all(
        {"steps", "active", "slots", "step_ms", "queue_depth", "kv_frac"}
        <= set(c) for c in chunks
    )
    assert any(e["kind"] == "request" and e["ttft_ms"] >= 0 for e in entries)
    assert entries[-1]["kind"] == "engine_stop"

    # ab_analyze reads the artifact dir layout (<dir>/flight/*.jsonl)
    art_dir = os.path.dirname(os.path.dirname(path))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ab_analyze.py"),
         os.path.dirname(os.path.dirname(path))],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert "Flight recorder" in out.stdout
    assert "step p50" in out.stdout
    assert "occupancy" in out.stdout
    del art_dir


# ---------------------------------------------------------------------- #
# trace merging
# ---------------------------------------------------------------------- #
def _fake_dump(path, component, events):
    payload = [
        {
            "name": name, "cat": component, "ph": "X", "ts": ts,
            "dur": 10.0, "pid": 0, "tid": 1, "args": args,
        }
        for name, ts, args in events
    ]
    with open(path, "w") as handle:
        json.dump({"traceEvents": payload}, handle)


def test_merge_chrome_trace_files_and_filter(tmp_path):
    from langstream_tpu.runtime.tracing import (
        merge_chrome_trace_files,
        trace_summary,
    )

    _fake_dump(tmp_path / "trace_gateway_1.json", "gateway", [
        ("gateway.produce", 100.0, {"trace_id": "aaa"}),
        ("gateway.produce", 300.0, {"trace_id": "bbb"}),
    ])
    _fake_dump(tmp_path / "trace_engine_1.json", "engine", [
        ("engine.request", 200.0, {"trace_id": "aaa", "ttft_ms": 5.0}),
        ("engine.decode_chunk", 150.0, {"trace_ids": "aaa,bbb"}),
    ])
    # bare-array Chrome trace shape (other tools emit this) must merge too
    with open(tmp_path / "trace_extern_1.json", "w") as handle:
        json.dump([{
            "name": "extern.step", "cat": "extern", "ph": "X",
            "ts": 250.0, "dur": 1.0, "pid": 0, "tid": 1,
            "args": {"trace_id": "aaa"},
        }], handle)
    merged = merge_chrome_trace_files([str(tmp_path)])
    events = merged["traceEvents"]
    # one named pid lane per dump
    meta = [e for e in events if e.get("ph") == "M"]
    assert {e["args"]["name"] for e in meta} == {
        "trace_engine_1", "trace_extern_1", "trace_gateway_1",
    }
    assert {e["pid"] for e in events} == {1, 2, 3}
    # wall-clock sorted (metadata first)
    ts = [e["ts"] for e in events if e.get("ph") != "M"]
    assert ts == sorted(ts)

    only_a = merge_chrome_trace_files([str(tmp_path)], trace_id="aaa")
    names = [e["name"] for e in only_a["traceEvents"] if e.get("ph") != "M"]
    assert "engine.request" in names and "engine.decode_chunk" in names
    assert all(
        "bbb" not in (e.get("args", {}).get("trace_id") or "")
        for e in only_a["traceEvents"]
    )

    summary = trace_summary([str(tmp_path)])
    assert summary["aaa"]["components"] == ["engine", "extern", "gateway"]
    assert summary["bbb"]["spans"] == 2


def test_trace_merge_cli_tool(tmp_path):
    _fake_dump(tmp_path / "trace_runner_9.json", "runner", [
        ("sink.write", 50.0, {"trace_id": "ccc"}),
    ])
    out_path = tmp_path / "merged.json"
    result = subprocess.run(
        [sys.executable, "-m", "langstream_tpu", "trace",
         str(tmp_path), "-o", str(out_path)],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert result.returncode == 0, result.stderr
    with open(out_path) as handle:
        merged = json.load(handle)
    assert any(
        e.get("name") == "sink.write" for e in merged["traceEvents"]
    )
    listing = subprocess.run(
        [sys.executable, "-m", "langstream_tpu", "trace",
         str(tmp_path), "--list"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert "ccc" in listing.stdout and "runner" in listing.stdout


# ---------------------------------------------------------------------- #
# end-to-end: one trace id across gateway -> runner -> engine
# ---------------------------------------------------------------------- #
def test_trace_id_spans_gateway_runner_engine(tmp_path, monkeypatch):
    """A chat request driven through gateway -> two-agent pipeline ->
    jax-local engine leaves per-component dumps that merge into ONE
    timeline where a single trace_id spans >=3 components, with
    TTFT/TPOT attributes on the engine spans (ISSUE 1 acceptance)."""
    import aiohttp

    from langstream_tpu.runtime import tracing

    trace_dir = tmp_path / "traces"
    monkeypatch.setenv("LANGSTREAM_TRACE_DIR", str(trace_dir))
    # fresh per-test registry: other tests' NOOP lookups never register,
    # but an earlier traced test in the same process would
    saved_tracers = dict(tracing._TRACERS)
    tracing._TRACERS.clear()

    async def main():
        from langstream_tpu.gateway import GatewayServer
        from langstream_tpu.runtime.local import run_application

        runner = await run_application(APP, instance_file=INSTANCE)
        gateway = GatewayServer(port=0)
        gateway.register_local_runner(runner)
        await gateway.start()
        port = gateway._runner.addresses[0][1]  # noqa: SLF001
        app_id = runner.application.application_id
        try:
            async with aiohttp.ClientSession() as session:
                async with session.post(
                    f"http://127.0.0.1:{port}/api/gateways/produce/"
                    f"default/{app_id}/produce-input?param:sessionId=s1",
                    data=json.dumps(
                        {"key": "user-1", "value": "what is a TPU?"}
                    ),
                ) as response:
                    assert response.status == 200, await response.text()
                # the gateway's /metrics serves the shared exposition
                async with session.get(
                    f"http://127.0.0.1:{port}/metrics"
                ) as response:
                    from langstream_tpu.api.metrics import (
                        parse_prometheus_text,
                    )

                    metrics = parse_prometheus_text(await response.text())
                    assert metrics["gateway_records_produced_total"] == [
                        ({}, 1.0)
                    ]
            history = runner.reader("history-topic")
            out = []
            deadline = asyncio.get_event_loop().time() + 90
            while not out and asyncio.get_event_loop().time() < deadline:
                out.extend(await history.read(timeout=0.2))
            assert out, "pipeline produced no answer"
            trace_id = out[0].header(tracing.TRACE_ID_HEADER)
            assert trace_id, "answer record lost the trace header"
            # the id survived BOTH topic hops: streamed chunks carry it too
            chunks = await runner.reader("output-topic").read(timeout=1.0)
            assert chunks
            assert all(
                c.header(tracing.TRACE_ID_HEADER) == trace_id
                for c in chunks
            )
            return str(trace_id)
        finally:
            await gateway.stop()
            await runner.stop()

    try:
        trace_id = asyncio.run(main())
        paths = tracing.dump_all(str(trace_dir))
        components = {
            os.path.basename(p).split("_")[1] for p in paths
        }
        assert {"gateway", "runner", "engine"} <= components, paths
        summary = tracing.trace_summary(paths)
        assert {"gateway", "runner", "engine"} <= set(
            summary[trace_id]["components"]
        )
        merged = tracing.merge_chrome_trace_files(paths, trace_id=trace_id)
        by_name = {}
        for event in merged["traceEvents"]:
            if event.get("ph") != "M":
                by_name.setdefault(event["name"], event)
        # gateway entry + runner hops + engine request all in one timeline
        assert "gateway.produce" in by_name
        assert "sink.write" in by_name
        request_span = by_name["engine.request"]
        assert request_span["args"]["ttft_ms"] >= 0
        assert "tpot_ms" in request_span["args"]
        assert by_name["engine.prefill"]["args"]["ttft_ms"] >= 0
    finally:
        tracing._TRACERS.clear()
        tracing._TRACERS.update(saved_tracers)


# ---------------------------------------------------------------------- #
# `langstream-tpu top`
# ---------------------------------------------------------------------- #
def test_top_renders_engine_table(capsys):
    import argparse

    from aiohttp import web

    from langstream_tpu.api.metrics import prometheus_text
    from langstream_tpu.cli.main import _top_cmd

    async def main():
        async def metrics(request):
            return web.Response(text=prometheus_text({}, {
                "jax_engine_slot_occupancy": 0.5,
                "jax_engine_decode_ms_per_step": 3.25,
                "jax_engine_tokens_generated": 123.0,
                "jax_engine_decode_steps": 40.0,
            }), content_type="text/plain")

        app = web.Application()
        app.router.add_get("/metrics", metrics)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]  # noqa: SLF001
        try:
            await _top_cmd(argparse.Namespace(
                url=f"http://127.0.0.1:{port}/metrics",
                interval=0.01, count=2,
            ))
        finally:
            await runner.cleanup()

    asyncio.run(main())
    out = capsys.readouterr().out
    assert "slot occupancy" in out and "50.0%" in out
    assert "tokens generated" in out and "123" in out


# ---------------------------------------------------------------------- #
# satellites
# ---------------------------------------------------------------------- #
def test_camel_plan_error_not_double_prefixed(tmp_path):
    import textwrap

    from langstream_tpu.compiler import (
        build_application,
        build_execution_plan,
    )

    app_dir = tmp_path / "app"
    app_dir.mkdir()
    (app_dir / "pipeline.yaml").write_text(textwrap.dedent("""
        topics:
          - name: "out"
        pipeline:
          - name: "camel"
            type: "camel-source"
            output: "out"
            configuration:
              component-uri: "kafka:?brokers=b:9092"
    """))
    (app_dir / "instance.yaml").write_text(textwrap.dedent("""
        instance:
          streamingCluster: {type: memory}
          computeCluster: {type: local}
    """))
    app = build_application(str(app_dir))
    with pytest.raises(ValueError) as err:
        build_execution_plan(app)
    message = str(err.value)
    assert "kafka URI needs a topic name" in message
    assert "camel-source: camel-source:" not in message
    assert "camel-source:" in message


def test_weights_cache_key_separates_norm_conventions(tmp_path):
    """Shape-identical configs with different init conventions (e.g. a
    norm_plus_one flip) must not share a weights-cache entry."""
    import dataclasses

    from langstream_tpu.providers.jax_local.model import LlamaConfig
    from langstream_tpu.providers.jax_local.quant import (
        init_quantized_params_cached,
    )

    config = LlamaConfig.tiny(max_seq_len=64)
    flipped = dataclasses.replace(config, norm_plus_one=True)
    init_quantized_params_cached(config, cache_dir=str(tmp_path))
    init_quantized_params_cached(flipped, cache_dir=str(tmp_path))
    entries = [n for n in os.listdir(tmp_path) if n.endswith(".npz")]
    assert len(entries) == 2, entries
    # and a warm re-read returns the flipped config's own weights
    import numpy as np

    fresh = init_quantized_params_cached(flipped, cache_dir=str(tmp_path))
    std = init_quantized_params_cached(config, cache_dir=str(tmp_path))
    assert not np.array_equal(
        np.asarray(fresh["final_norm"]), np.asarray(std["final_norm"])
    )
