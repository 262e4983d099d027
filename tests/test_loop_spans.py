"""The event loop's thread on the profiler's trace (ISSUE 37): one
``loop.deliver`` span a hand-over's delivery, the gateway's chat frames in
and out, and the engine thread's CPU time in every phase span. CPU
backend, tiny engine, as ``test_phase_spans.py``."""

import asyncio
import glob
import os
import time

import jax
import pytest

from langstream_tpu.api.metrics import prometheus_text
from langstream_tpu.providers.jax_local import engine as engine_lib
from langstream_tpu.providers.jax_local.engine import (
    DecodeEngine,
    SamplingParams,
)
from langstream_tpu.providers.jax_local.model import LlamaConfig, init_params
from langstream_tpu.runtime import tracing

PROMPTS = [[1, 2, 3, 4], [9, 8, 7], list(range(1, 20))]
# the four phases with a running sum, by their stat's name
SUMMED = ("idle", "admit", "dispatch", "emit")


def thread_clock_step_ms():
    """The thread CPU clock's step here, in ms: a host whose clock counts
    in ticks (the chip's host: 10 ms) charges a span a whole tick, so a
    span's ``cpu_ms`` may pass its wall time by one step."""
    last, steps = time.thread_time(), []
    while len(steps) < 3:
        now = time.thread_time()
        if now != last:
            steps.append(now - last)
            last = now
    return max(0.1, 1e3 * min(steps))


@pytest.fixture(scope="module")
def engine():
    config = LlamaConfig.tiny(max_seq_len=128)
    engine = DecodeEngine(
        config, init_params(config), max_seq_len=128, max_slots=4,
        prefill_buckets=[16, 32], decode_chunk=8,
    )
    engine.start()
    yield engine
    engine.stop()


def generate(engine, tokens):
    """Serve the prompts, each streaming to a callback: the engine hands
    a token over to the loop only for a caller that takes it."""
    streamed = []

    async def main():
        return await asyncio.gather(*[
            engine.generate(
                prompt, SamplingParams(max_new_tokens=tokens),
                on_token=lambda token, last: streamed.append(token),
                trace_id=f"loop-{index}",
            )
            for index, prompt in enumerate(PROMPTS)
        ])

    results = asyncio.run(main())
    assert len(streamed) == sum(len(result.tokens) for result in results)
    return results


def host_lines(log_dir):
    """The ``engine.*`` and ``loop.*`` host events of the trace under
    ``log_dir``, by line: (name, start, end, attributes), in order."""
    path = glob.glob(
        os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True
    )[0]
    data = jax.profiler.ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 {str(k): v for k, v in e.stats})
                for e in line.events
                if e.name.startswith(("engine.", "loop."))
            ]
            if events:
                lines.append(sorted(events, key=lambda e: (e[1], -e[2])))
    return lines


def assert_no_overlap(events):
    for before, after in zip(events, events[1:]):
        assert before[2] <= after[1], (before, after)


@pytest.fixture(scope="module")
def profiled(engine, tmp_path_factory):
    generate(engine, 8)  # compile every shape outside the session
    log_dir = str(tmp_path_factory.mktemp("loop"))
    with tracing.profile(log_dir):
        results = generate(engine, 24)
        # the engine thread leaves its last emit span after the answers
        # resolve: keep the session open until it has waited for work
        idle = engine.stats["idle_time"]
        deadline = time.monotonic() + 30
        while engine.stats["idle_time"] == idle:
            assert time.monotonic() < deadline
            time.sleep(0.01)
    return results, host_lines(log_dir)


def test_one_deliver_span_a_delivery_on_the_loops_thread(profiled):
    results, lines = profiled
    loop_lines = [
        [e for e in line if e[0].startswith("loop.")] for line in lines
    ]
    delivering = [
        (line, events) for line, events in zip(lines, loop_lines) if events
    ]
    # one thread runs them, and it is not the engine's
    assert len(delivering) == 1
    line, delivers = delivering[0]
    assert not any(e[0].startswith("engine.") for e in line)
    assert {e[0] for e in delivers} == {"loop.deliver"}
    assert_no_overlap(delivers)
    # their tokens are every token the engine handed over
    assert sum(int(e[3]["tokens"]) for e in delivers) == sum(
        len(result.tokens) for result in results
    )
    for index in range(len(PROMPTS)):
        mine = [e for e in delivers if e[3]["trace_id"] == f"loop-{index}"]
        assert sum(int(e[3]["first"]) for e in mine) == 1
        assert sum(int(e[3]["done"]) for e in mine) == 1
        # the first token comes before any other, the result last
        assert int(mine[0][3]["first"]) == 1 and int(mine[-1][3]["done"]) == 1
    room = thread_clock_step_ms()
    for name, start, end, attributes in delivers:
        assert 0.0 <= float(attributes["cpu_ms"]) <= (end - start) / 1e6 + room


def test_every_engine_span_carries_its_cpu_time(profiled):
    _, lines = profiled
    spans = [e for line in lines for e in line if e[0].startswith("engine.")]
    assert {e[0] for e in spans} >= {
        "engine.admit", "engine.dispatch_decode", "engine.emit",
        "engine.wait_for_work", "engine.wait_chunk",
    }
    room = thread_clock_step_ms()
    for name, start, end, attributes in spans:
        cpu_ms = float(attributes["cpu_ms"])
        assert 0.0 <= cpu_ms <= (end - start) / 1e6 + room, (name, cpu_ms)
    # a blocking wait is mostly off the CPU, which is what the two
    # clocks tell apart
    waits = [e for e in spans if e[0] == "engine.wait_for_work"]
    assert sum(float(e[3]["cpu_ms"]) for e in waits) < 0.5 * sum(
        (e[2] - e[1]) / 1e6 for e in waits
    )


def test_the_cpu_sums_sit_beside_the_wall_sums_on_metrics(engine):
    generate(engine, 4)
    stats = engine.stats
    for phase in SUMMED:
        assert 0.0 < stats[phase + "_cpu"] <= stats[phase + "_time"] + 1e-3
    text = prometheus_text({}, engine_lib.engines_snapshot())
    for phase in SUMMED:
        assert (
            f'jax_engine_loop_cpu_seconds_total{{phase="{phase}"}}' in text
        )
    assert text.count("# TYPE jax_engine_loop_cpu_seconds_total gauge") == 1


def test_a_delivery_without_tokens_is_not_a_first(engine):
    """A failure posted through the inbox carries no token: its span says
    ``done`` and not ``first``; a request's first delivery is its first
    only once."""
    request = engine_lib.GenerationRequest(
        prompt_tokens=[1], sampling=SamplingParams(max_new_tokens=1),
    )
    delivery = engine_lib._Delivery(request)
    delivery.calls += [(5, False), (6, False)]
    delivery.run()
    assert request.delivered == 2
    failed = engine_lib._Delivery(request, error=RuntimeError("x"))
    failed.run()
    assert request.delivered == 2


# ------------------------------------------------------------------ #
# the gateway's chat frames
# ------------------------------------------------------------------ #
def test_the_gateways_frames_are_spans_of_the_loops_thread(tmp_path):
    from test_gateway import start_app_and_gateway

    async def main():
        import aiohttp

        runner, gateway = await start_app_and_gateway(tmp_path, 18137)
        base = "http://127.0.0.1:18137"
        try:
            async with aiohttp.ClientSession() as session:
                async with session.ws_connect(
                    f"{base}/v1/chat/default/app/chat?param:session-id=s1"
                ) as chat_ws:
                    for question in ("ping", "pong"):
                        await chat_ws.send_json({"value": question})
                        message = await chat_ws.receive_json(timeout=5)
                        assert message["record"]["value"] == question
                    return message["record"]["headers"]
        finally:
            await gateway.stop()
            await runner.stop()

    log_dir = str(tmp_path / "trace")
    with tracing.profile(log_dir):
        headers = asyncio.run(main())
    events = [e for line in host_lines(log_dir) for e in line]
    frames_in = [e for e in events if e[0] == "loop.gateway_in"]
    frames_out = [e for e in events if e[0] == "loop.gateway_out"]
    assert len(frames_in) == len(frames_out) == 2
    # the id the gateway stamped on the way in is on the frame going out
    assert headers[tracing.TRACE_ID_HEADER] == frames_in[-1][3]["trace_id"]
    for frame_in, frame_out in zip(frames_in, frames_out):
        assert frame_in[3]["trace_id"] == frame_out[3]["trace_id"] != ""
        assert frame_in[2] <= frame_out[1]
        # an echo record has no stream index (an empty value is no stat)
        assert "index" not in frame_out[3]
    for line in host_lines(log_dir):
        assert_no_overlap([e for e in line if e[0].startswith("loop.")])
