"""Self-healing serving (ISSUE 9): deterministic fault injection +
engine supervisor with bitwise session resurrection.

The acceptance arc: with ``LANGSTREAM_FAULTS=engine_thread_crash@step=N``
armed, a session killed mid-decode resumes on a rebuilt engine and its
FULL output is bitwise identical to the same request on an uncrashed
engine (greedy and seeded stochastic — penalties included — on dense and
paged layouts, spec-on too), no other in-flight session is failed (zero
500s; only bounded 503 + Retry-After during the rebuild), and the
recovery leaves evidence on every plane: ``engine_restarts_total`` /
``sessions_resurrected_total`` / ``engine_recovery_seconds`` in the
engine snapshot, ``engine_recovery`` flight events, an
``engine.recovery`` trace span, and ``tokens_wasted{crash_replay}`` in
the goodput ledger. Satellites: admission-deadline load shedding,
watchdog escalation, the paged-allocator and dispatch fault points, and
the OpenAI surface's sibling-cancellation error propagation."""

import asyncio
import gc
import logging
import time
import weakref

import pytest

from langstream_tpu.api import errors as api_errors
from langstream_tpu.providers.jax_local.engine import (
    DecodeEngine,
    SamplingParams,
    engines_histograms,
    engines_snapshot,
)
from langstream_tpu.providers.jax_local.model import LlamaConfig, init_params
from langstream_tpu.runtime import faults
from langstream_tpu.runtime.local import settle_collector
from langstream_tpu.runtime.supervisor import EngineSupervisor


@pytest.fixture(autouse=True)
def _clean_faults():
    """Every test starts and ends disarmed with zeroed arrival counters
    (the registry is process-global by design — a one-shot fault stays
    consumed across a supervisor rebuild)."""
    faults.reset()
    yield
    faults.reset()


@pytest.fixture()
def flight_recorder(tmp_path):
    from langstream_tpu.runtime import flight

    saved = flight.RECORDER.path
    flight.RECORDER.path = None
    flight.RECORDER._pending.clear()
    path = flight.configure(str(tmp_path / "flight"))
    yield flight, path
    flight.RECORDER.flush()
    flight.RECORDER.path = saved


# ---------------------------------------------------------------------- #
# fault registry (runtime/faults.py)
# ---------------------------------------------------------------------- #
def test_fault_spec_parsing_and_describe():
    specs = faults.parse_spec(
        "engine_thread_crash@step=40,dispatch_error@step=7:1.0,"
        "stuck_step@step=5;dur=45"
    )
    assert [s.point for s in specs] == [
        "engine_thread_crash", "dispatch_error", "stuck_step",
    ]
    assert specs[0].step == 40 and specs[0].prob is None
    assert specs[1].prob == 1.0
    assert specs[2].params == {"dur": "45"}
    assert specs[2].describe() == "stuck_step@step=5;dur=45"
    for bad in ("nope", "x@stop=3", "x@step=abc", "x@step=1:1.5"):
        with pytest.raises(ValueError):
            faults.parse_spec(bad)


def test_one_shot_fires_exactly_once():
    faults.configure("p@step=3")
    fired = [bool(faults.fire("p")) for _ in range(6)]
    assert fired == [False, False, True, False, False, False]
    # a rebuilt engine re-passing the point does NOT re-fire: arrival
    # counters are process-global for the registry's lifetime
    with pytest.raises(faults.InjectedFault):
        faults.configure("q@step=1")
        faults.check("q")
    faults.check("q")  # consumed


def test_probabilistic_faults_are_deterministic():
    faults.configure("p@step=2:0.5", seed=7)
    first = [bool(faults.fire("p")) for _ in range(64)]
    assert not first[0]  # armed only from step 2
    assert any(first) and not all(first)
    faults.reset()
    faults.configure("p@step=2:0.5", seed=7)
    assert [bool(faults.fire("p")) for _ in range(64)] == first
    faults.reset()
    faults.configure("p@step=2:1.0", seed=7)
    assert [bool(faults.fire("p")) for _ in range(4)] == [
        False, True, True, True,
    ]


def test_unarmed_registry_is_inert_and_cheap():
    assert not faults.armed()
    assert faults.fire("anything") is None
    faults.check("anything")  # no raise
    assert faults.maybe_sleep("anything") == 0.0


def test_stuck_step_sleeps_for_configured_duration():
    faults.configure("stuck_step@step=1;dur=0.05")
    started = time.perf_counter()
    slept = faults.maybe_sleep("stuck_step")
    assert slept == pytest.approx(0.05)
    assert time.perf_counter() - started >= 0.04


def test_pool_exhausted_fault_point():
    from langstream_tpu.providers.jax_local.paged import PagedKVManager

    manager = PagedKVManager(num_blocks=8, block_size=4)
    faults.configure("pool_exhausted@step=1")
    assert manager.allocate(2) is None  # injected exhaustion, no state
    fresh = manager.allocate(2)         # one-shot consumed
    assert fresh is not None and len(fresh) == 2


def test_env_arming(monkeypatch):
    monkeypatch.setenv("LANGSTREAM_FAULTS", "engine_thread_crash@step=9")
    faults.configure_from_env()
    assert faults.armed()
    assert "engine_thread_crash" in faults.REGISTRY.describe()


# ---------------------------------------------------------------------- #
# crash → rebuild → bitwise resurrection
# ---------------------------------------------------------------------- #
def _factory(config, params, **overrides):
    kwargs = dict(
        max_slots=4, max_seq_len=128, prefill_buckets=[16, 32],
        decode_chunk=4, seed=11,
    )
    kwargs.update(overrides)
    return lambda: DecodeEngine(config, params, **kwargs)


@pytest.fixture(scope="module")
def tiny():
    config = LlamaConfig.tiny(max_seq_len=512)
    return config, init_params(config)


GREEDY = dict(max_new_tokens=20)
SEEDED = dict(
    max_new_tokens=20, temperature=0.9, top_k=8, top_p=0.9, seed=1234,
    presence_penalty=0.4, frequency_penalty=0.25,
)


def _run(engine, prompt, sampling_kwargs, **kw):
    async def main():
        return await engine.generate(
            list(prompt), SamplingParams(**sampling_kwargs), **kw
        )

    return asyncio.run(main())


@pytest.mark.parametrize(
    "sampling",
    [
        # seeded (penalties + truncation + per-request seed) subsumes
        # greedy's resurrection machinery; the greedy leg rides the
        # slow tier (~7s — tier-1 wall-clock headroom, ISSUE 14)
        pytest.param(GREEDY, id="greedy", marks=pytest.mark.slow),
        pytest.param(SEEDED, id="seeded"),
    ],
)
def test_crash_mid_decode_resumes_bitwise_dense(tiny, sampling,
                                                flight_recorder):
    config, params = tiny
    factory = _factory(config, params)
    oracle = factory()
    oracle.start()
    expected = _run(oracle, [1, 2, 3, 4, 5], sampling)
    oracle.stop()
    assert len(expected.tokens) == sampling["max_new_tokens"]

    faults.configure("engine_thread_crash@step=2")
    supervisor = EngineSupervisor(factory)
    first_engine = supervisor.engine
    streamed = []
    result = _run(
        supervisor.engine, [1, 2, 3, 4, 5], sampling,
        on_token=lambda token, last: streamed.append(token),
    )
    assert supervisor.restarts == 1
    assert supervisor.state == "serving"
    assert supervisor.engine is not first_engine
    # THE acceptance assertion: the resumed session's full output is
    # bitwise identical to the uncrashed oracle's
    assert result.tokens == expected.tokens
    assert result.finish_reason == expected.finish_reason
    assert result.prompt_tokens == 5
    # the stream saw every token exactly once: the pre-crash prefix from
    # the dead engine, the continuation from the rebuilt one — replay
    # tokens are never re-emitted
    asyncio.run(asyncio.sleep(0))  # drain any queued callbacks
    assert streamed == expected.tokens
    # goodput: the replay prefill is billed as crash_replay recompute
    stats = supervisor.engine.stats
    assert stats["tokens_wasted"].get("crash_replay", 0) > 0
    supervisor.stop()


def test_crash_spares_no_session_and_seeds_survive_together(tiny):
    """Two concurrent sessions, one crash: BOTH resume bitwise — no
    in-flight session is failed (the zero-500s criterion)."""
    config, params = tiny
    factory = _factory(config, params)
    oracle = factory()
    oracle.start()

    async def pair(engine):
        return await asyncio.gather(
            engine.generate([1, 2, 3, 4, 5], SamplingParams(**GREEDY)),
            engine.generate([9, 8, 7], SamplingParams(**SEEDED)),
        )

    expected = asyncio.run(pair(oracle))
    oracle.stop()
    faults.configure("engine_thread_crash@step=2")
    supervisor = EngineSupervisor(factory)
    results = asyncio.run(pair(supervisor.engine))
    assert supervisor.restarts == 1
    for got, want in zip(results, expected):
        assert got.tokens == want.tokens
        assert got.finish_reason == want.finish_reason
    supervisor.stop()


def test_crash_resumes_bitwise_paged_across_block_boundary(tiny,
                                                           flight_recorder):
    """Paged layout: crash lands the replay mid-block (prompt + accepted
    tokens not block-aligned), the rebuilt pool re-teaches it through a
    normal cold prefill, and the continuation matches the oracle
    bitwise. Afterwards a prompt sharing a ≥256-token prefix with the
    resurrected session hits the NEW engine's prefix cache — the
    resurrected state is first-class cache content, not a special case."""
    config, params = tiny
    prompt = [(i * 7) % 250 + 1 for i in range(300)]
    factory = _factory(
        config, params, max_seq_len=512,
        prefill_buckets=[16, 32, 64, 128, 256],
        kv_layout="paged", kv_block_size=16,
    )
    oracle = factory()
    oracle.start()
    expected_g = _run(oracle, prompt, GREEDY)
    expected_s = _run(oracle, prompt, SEEDED)
    oracle.stop()

    for sampling, expected in ((GREEDY, expected_g), (SEEDED, expected_s)):
        faults.reset()
        # crash after chunk 2: 4+4 decode tokens + the prefill token =
        # 9 accepted → replay prefill length 300 + 9 - 1 = 308, which is
        # mid-block at block_size 16 (308 % 16 == 4)
        faults.configure("engine_thread_crash@step=2")
        supervisor = EngineSupervisor(factory)
        result = _run(supervisor.engine, prompt, sampling)
        assert supervisor.restarts == 1
        assert result.tokens == expected.tokens
        engine = supervisor.engine
        assert engine.stats["tokens_wasted"].get("crash_replay", 0) > 0
        if sampling is GREEDY:
            # ≥256-token prefix hit against the resurrected session's
            # published chain on the REBUILT engine
            before = engine.kv_manager.stats["hit_tokens"]
            follow = _run(engine, prompt + [33, 34], GREEDY)
            assert len(follow.tokens) == GREEDY["max_new_tokens"]
            assert engine.kv_manager.stats["hit_tokens"] - before >= 256
        supervisor.stop()


def test_crash_resumes_bitwise_with_spec_decode(tiny):
    """Speculative decoding on: accepted draft tokens are part of the
    replay state; the resumed spec engine continues bitwise."""
    config, params = tiny
    prompt = [5, 6, 7, 8] * 6  # repetition for the prompt-lookup drafter
    factory = _factory(
        config, params, spec_decode="ngram", spec_k=3, spec_ngram=2,
        decode_chunk=2,
    )
    oracle = factory()
    oracle.start()
    expected = _run(oracle, prompt, GREEDY)
    oracle.stop()
    faults.configure("engine_thread_crash@step=2")
    supervisor = EngineSupervisor(factory)
    result = _run(supervisor.engine, prompt, GREEDY)
    assert supervisor.restarts == 1
    assert result.tokens == expected.tokens
    supervisor.stop()


def test_recovery_evidence_metrics_flight_trace(tiny, flight_recorder):
    """Every observability plane carries the recovery: snapshot gauges,
    the recovery_seconds histogram, flight events, the trace span."""
    flight, path = flight_recorder
    config, params = tiny
    factory = _factory(config, params)
    faults.configure("engine_thread_crash@step=1")
    supervisor = EngineSupervisor(factory)

    class SpanRecorder:
        enabled = True
        events = []

        def event(self, name, duration_s, **kw):
            self.events.append((name, duration_s, kw))

    supervisor.tracer = SpanRecorder()
    result = _run(supervisor.engine, [1, 2, 3], GREEDY)
    assert len(result.tokens) == GREEDY["max_new_tokens"]
    assert supervisor.restarts == 1
    gauges = engines_snapshot()
    assert gauges["engine_restarts_total"] >= 1.0
    assert gauges["sessions_resurrected_total"] >= 1.0
    assert gauges["engine_degraded"] == 0.0
    assert 'jax_engine_tokens_wasted_total{reason="crash_replay"}' in gauges
    histograms = engines_histograms()
    assert histograms["engine_recovery_seconds"]["count"] >= 1
    spans = [e for e in SpanRecorder.events if e[0] == "engine.recovery"]
    assert spans and spans[0][2]["sessions"] == 1
    flight.flush()
    kinds = [e["kind"] for e in flight.read_artifact(path)]
    for kind in ("fault_injected", "engine_crash", "engine_recovery",
                 "session_resume"):
        assert kind in kinds, kinds
    phases = [
        e.get("phase") for e in flight.read_artifact(path)
        if e["kind"] == "engine_recovery"
    ]
    assert "begin" in phases and "complete" in phases
    supervisor.stop()


def test_degraded_mode_is_typed_503_not_500(tiny):
    """While rebuilding, submits raise the typed retryable error (the
    HTTP surfaces turn it into 503 + Retry-After), and a supervisor past
    its restart budget fails terminally instead of retrying forever."""
    config, params = tiny
    factory = _factory(config, params)
    supervisor = EngineSupervisor(factory)
    engine = supervisor.engine
    # freeze a rebuild window: a condemned engine with on_crash set
    supervisor.state = "rebuilding"
    engine._crashed = RuntimeError("boom")
    with pytest.raises(api_errors.EngineRebuildingError) as info:
        engine.submit(
            __import__(
                "langstream_tpu.providers.jax_local.engine",
                fromlist=["GenerationRequest"],
            ).GenerationRequest(prompt_tokens=[1], sampling=SamplingParams())
        )
    assert info.value.retry_after_s > 0
    assert engines_snapshot()["engine_degraded"] == 1.0
    engine._crashed = None
    supervisor.state = "serving"
    supervisor.stop()


def test_restart_budget_gives_up(tiny):
    config, params = tiny
    factory = _factory(config, params)
    # fire on EVERY chunk from step 1: the rebuilt engine crashes again
    # immediately → second restart exceeds max_restarts=1 → terminal
    faults.configure("engine_thread_crash@step=1:1.0")
    supervisor = EngineSupervisor(factory, max_restarts=1)
    with pytest.raises(RuntimeError, match="giving up"):
        _run(supervisor.engine, [1, 2, 3], GREEDY)
    assert supervisor.state == "failed"


# ---------------------------------------------------------------------- #
# admission deadlines / load shedding
# ---------------------------------------------------------------------- #
def test_queue_deadline_sheds_with_retry_after(tiny, flight_recorder):
    flight, path = flight_recorder
    config, params = tiny
    engine = DecodeEngine(
        config, params, max_slots=1, max_seq_len=128,
        prefill_buckets=[16], decode_chunk=2, queue_timeout_s=0.02,
    )
    engine.start()

    async def main():
        hog = asyncio.ensure_future(engine.generate(
            [1, 2, 3], SamplingParams(max_new_tokens=64)
        ))
        await asyncio.sleep(0.05)  # hog owns the only slot
        starved = asyncio.ensure_future(engine.generate(
            [4, 5, 6], SamplingParams(max_new_tokens=4)
        ))
        with pytest.raises(api_errors.QueueTimeoutError) as info:
            await starved
        assert info.value.retry_after_s >= 1.0
        await hog
        return info.value

    asyncio.run(main())
    assert engine.stats["requests_shed"] == {"queue_timeout": 1}
    gauges = engines_snapshot()
    assert gauges['requests_shed_total{reason="queue_timeout"}'] >= 1.0
    flight.flush()
    sheds = [
        e for e in flight.read_artifact(path) if e["kind"] == "request_shed"
    ]
    assert sheds and sheds[0]["reason"] == "queue_timeout"
    engine.stop()


# ---------------------------------------------------------------------- #
# watchdog escalation
# ---------------------------------------------------------------------- #
def test_watchdog_escalates_after_n_trips_within_window():
    from types import SimpleNamespace

    from langstream_tpu.runtime.watchdog import EngineWatchdog

    engine = SimpleNamespace(
        stats={
            "decode_chunks": 0, "decode_steps": 0, "decode_token_steps": 0.0,
            "decode_time": 0.0, "prefill_calls": 0, "warm_prefill_calls": 0,
        },
        slots=[SimpleNamespace(active=True)],
        _pending=[],
        kv_manager=None,
    )
    watchdog = EngineWatchdog(
        engine, no_progress_s=10.0, trip_cooldown_s=5.0,
        capture_profile=False, escalate_trips=3, escalate_window_s=100.0,
    )
    escalations = []
    watchdog.on_escalate = escalations.append
    now = 1000.0
    watchdog.check(now=now)  # anchors the stall
    # three no-progress trips, spaced past the cooldown
    for i in range(3):
        now += 15.0
        assert watchdog.check(now=now) == "no_progress"
    assert escalations == ["watchdog_escalation:no_progress"]
    # a fourth trip inside the same window does NOT re-escalate (the
    # restart is already underway)
    now += 15.0
    watchdog.check(now=now)
    assert len(escalations) == 1
    # existing behavior preserved: trips counted, cooldown respected
    assert watchdog.trips == 4


def test_escalation_restart_resurrects_live_session(tiny):
    """The supervisor's second detection arm: a restart REQUEST (the
    watchdog escalation path) on a live engine tears it down cleanly
    and resumes the in-flight session bitwise."""
    config, params = tiny
    factory = _factory(config, params)
    oracle = factory()
    oracle.start()
    expected = _run(oracle, [2, 4, 6, 8], GREEDY)
    oracle.stop()
    supervisor = EngineSupervisor(factory)
    first_engine = supervisor.engine

    async def main():
        task = asyncio.ensure_future(supervisor.engine.generate(
            [2, 4, 6, 8], SamplingParams(**GREEDY)
        ))
        while not first_engine.stats["tokens_generated"]:
            await asyncio.sleep(0.005)
        await asyncio.to_thread(
            supervisor.request_restart, "watchdog_escalation:test"
        )
        return await task

    result = asyncio.run(main())
    assert supervisor.restarts == 1
    assert supervisor.engine is not first_engine
    assert result.tokens == expected.tokens
    supervisor.stop()


@pytest.mark.parametrize("arm", ["crash", "escalation"])
def test_a_heal_in_a_frozen_process_frees_the_superseded_engine(tiny, arm):
    """A serving process freezes its start-up objects out of the
    collector's way once it is warm (``settle_collector``), the first
    engine among them, and nothing frozen is ever examined again. A heal
    must all the same leave nothing of the superseded engine: its cache
    is the device's again before the replacement is built, and the engine
    itself (it sits in cycles) goes with the collector's next pass."""
    config, params = tiny

    def factory():
        engine = _factory(config, params)()
        engine.precompile()
        return engine

    if arm == "crash":
        faults.configure("engine_thread_crash@step=2")
    supervisor = EngineSupervisor(factory)
    first = weakref.ref(supervisor.engine)
    settle_collector()
    # pytest keeps every log record of a test, and "engine loop crashed"
    # carries the traceback, whose frames hold the engine
    logging.disable(logging.ERROR)
    try:
        if arm == "crash":  # the heal runs on the dying engine's thread
            _run(supervisor.engine, [1, 2, 3, 4, 5], GREEDY)
        else:
            supervisor.request_restart("watchdog_escalation:test")
        assert supervisor.restarts == 1
        assert supervisor.engine is not first()
        assert supervisor.engine.cache is not None
        deadline = time.monotonic() + 10.0
        while first() is not None and time.monotonic() < deadline:
            assert first().cache is None  # not the collector's to free
            time.sleep(0.02)  # the dying thread still holds it
            gc.collect()
        assert first() is None
    finally:
        logging.disable(logging.NOTSET)
        gc.unfreeze()
        supervisor.stop()


# ---------------------------------------------------------------------- #
# OpenAI surface: 503/Retry-After + sibling-cancellation regression
# ---------------------------------------------------------------------- #
async def _post(port, path, payload):
    import aiohttp

    async with aiohttp.ClientSession() as session:
        async with session.post(
            f"http://127.0.0.1:{port}{path}", json=payload
        ) as response:
            try:
                body = await response.json(content_type=None)
            except ValueError:
                body = {"raw": await response.text()}
            return response.status, dict(response.headers), body


def test_api_answers_503_with_retry_after_while_rebuilding():
    from langstream_tpu.serving.openai_api import OpenAIApiServer

    class Rebuilding:
        def available(self):
            return 3.0

        async def get_chat_completions(self, *a, **k):  # pragma: no cover
            raise AssertionError("must be gated before the service")

    async def main():
        server = OpenAIApiServer(
            Rebuilding(), model="tiny", host="127.0.0.1", port=0,
        )
        await server.start()
        try:
            port = server.addresses[0][1]
            status, headers, body = await _post(
                port, "/v1/chat/completions",
                {"messages": [{"role": "user", "content": "hi"}],
                 "stream": False},
            )
            assert status == 503
            assert headers["Retry-After"] == "3"
            assert "rebuilding" in body["error"]["message"]
            # streaming requests are gated BEFORE the SSE response opens
            status, headers, _ = await _post(
                port, "/v1/chat/completions",
                {"messages": [{"role": "user", "content": "hi"}],
                 "stream": True},
            )
            assert status == 503 and "Retry-After" in headers
        finally:
            await server.stop()

    asyncio.run(main())


def test_sibling_cancel_race_propagates_first_real_error():
    """Regression (ISSUE 9 bugfix): with n>1, when the first exception
    gather surfaces is a CancelledError (a sibling's cancel racing its
    own completion), the ORIGINAL typed error from another sibling must
    reach the client — here as a 503 + Retry-After from a fault-injected
    dispatch error, not a swallowed cancellation."""
    from langstream_tpu.serving.openai_api import OpenAIApiServer

    faults.configure("dispatch_error@step=1")

    class Racy:
        calls = 0

        async def get_chat_completions(self, messages, options, consumer=None):
            Racy.calls += 1
            call = Racy.calls
            if call == 1:
                # completes "cancelled" first — the exception gather
                # surfaces, exactly the race the bugfix targets
                await asyncio.sleep(0.01)
                raise asyncio.CancelledError()
            await asyncio.sleep(0.05)
            try:
                faults.check("dispatch_error")  # first arrival → fires
            except faults.InjectedFault as fault:
                raise api_errors.QueueTimeoutError(
                    f"dispatch failed: {fault}", retry_after_s=2.0
                ) from fault
            raise AssertionError("fault should have fired")

    async def main():
        server = OpenAIApiServer(
            Racy(), model="tiny", host="127.0.0.1", port=0,
        )
        await server.start()
        try:
            port = server.addresses[0][1]
            status, headers, body = await _post(
                port, "/v1/chat/completions",
                {"messages": [{"role": "user", "content": "hi"}], "n": 2},
            )
            assert status == 503, body
            assert "Retry-After" in headers
            assert "dispatch failed" in body["error"]["message"]
        finally:
            await server.stop()

    asyncio.run(main())


def test_provider_surfaces_rebuild_as_typed_unavailable(tiny):
    """JaxCompletionsService.available() + the pre-generate gate: a
    rebuilding supervisor turns new work into the typed retryable error
    end to end (provider level — the HTTP mapping is covered above)."""
    from langstream_tpu.providers.jax_local.provider import (
        JaxCompletionsService,
    )

    service = JaxCompletionsService({
        "model": {"preset": "tiny", "max_seq_len": 128},
        "engine": {"max-slots": 2, "max-seq-len": 128,
                   "queue-timeout-s": 30},
    })
    try:
        assert service._supervisor is not None  # on by default
        assert service.available() is None
        service._supervisor.state = "rebuilding"
        assert service.available() == pytest.approx(
            service._supervisor.retry_after()
        )
        with pytest.raises(api_errors.EngineRebuildingError):
            asyncio.run(service.get_text_completions(
                ["hi"], {"max-tokens": 4}
            ))
        service._supervisor.state = "serving"
        assert service.engine.queue_timeout_s == 30.0
    finally:
        asyncio.run(service.close())


def test_ci_shard_learns_recovery():
    import tools.ci_shard as ci_shard

    assert ci_shard.assign("test_recovery.py") == "kernels-engine"
