"""A harvested chunk's tokens cross to their callers' event loop in ONE
hand-over, made when the chunk's bookkeeping is done (ISSUE 36): the
callers' contract (one ``on_token`` a token, in order, the future after
the last) holds for every kind of dispatch, and the engine thread makes
one cross-thread post a loop whatever the number of slots and steps.
CPU backend, tiny engine."""

import asyncio
import collections
import concurrent.futures
import sys
import threading
import time

import pytest

from langstream_tpu.providers.jax_local import engine as engine_lib
from langstream_tpu.providers.jax_local.engine import (
    DecodeEngine,
    GenerationRequest,
    SamplingParams,
)
from langstream_tpu.providers.jax_local.model import LlamaConfig, init_params
from langstream_tpu.runtime import tracing

CONFIG = LlamaConfig.tiny(max_seq_len=128)
PARAMS = init_params(CONFIG)
MODES = {
    "plain": dict(prefill_buckets=[16, 32]),
    "mixed": dict(
        prefill_buckets=[16, 32], kv_layout="paged", kv_block_size=8,
        paged_kernel="reference", prefill_mode="mixed", prefill_chunk=16,
    ),
    "speculative": dict(
        prefill_buckets=[32], spec_decode="ngram", spec_k=3, spec_ngram=2,
    ),
}
# the verify skill's shape: a repetitive prompt, so that drafts are
# accepted and a speculative step emits a varying number of tokens
REPETITIVE = (list(range(1, 9)) * 8)[:30]


def make_engine(mode="plain", chunk=8, **options):
    options = {**MODES[mode], **options}
    options.setdefault("max_slots", 4)
    engine = DecodeEngine(
        CONFIG, PARAMS, max_seq_len=128, decode_chunk=chunk, **options
    )
    engine.tracer = tracing.Tracer("emit-handover")
    engine.start()
    return engine


def prompt(seed, length=5):
    return [(7 * seed + 3 * j) % 250 + 1 for j in range(length)]


# ------------------------------------------------------------------ #
# (1) the callers' contract, on a real event loop
# ------------------------------------------------------------------ #
async def streamed(engine, tokens, new_tokens, stop_tokens=None):
    """One request through ``engine.generate``: its callbacks as
    ``(token, is_last, the loop's thread?, future already done?)`` and
    its result."""
    loop = asyncio.get_running_loop()
    calls = []
    handle = []

    def on_token(token, is_last):
        calls.append((
            token, is_last,
            asyncio.get_running_loop() is loop,
            handle[0].future.done(),
        ))

    result = await engine.generate(
        tokens, SamplingParams(max_new_tokens=new_tokens),
        stop_tokens=stop_tokens, on_token=on_token, handle=handle,
    )
    return calls, result


@pytest.mark.parametrize("chunk", [1, 4, 32])
@pytest.mark.parametrize("mode", sorted(MODES))
def test_callbacks_come_once_a_token_in_order_and_the_future_last(
    mode, chunk
):
    engine = make_engine(mode, chunk, max_slots=2)

    async def main():
        first = streamed(engine, REPETITIVE, 21)
        second = streamed(engine, prompt(5, 9), 13)
        return await asyncio.gather(first, second)

    try:
        (calls, result), (calls2, result2) = asyncio.run(main())
        for got, answer, count in (
            (calls, result, 21), (calls2, result2, 13)
        ):
            assert answer.finish_reason == "length"
            assert len(answer.tokens) == count
            assert [token for token, *_ in got] == answer.tokens
            # the last marker once, on the last; on the loop's thread;
            # the future not resolved before the last callback
            assert [last for _, last, *_ in got] == (
                [False] * (count - 1) + [True]
            )
            assert all(on_loop for *_, on_loop, _ in got)
            assert not any(done for *_, done in got)
        if mode == "speculative":
            assert engine.stats["tokens_draft_accepted"] > 0
        if mode == "mixed":
            assert engine.stats["mixed_steps"] > 0

        # a stop token ends the answer: no callback for it, so the last
        # marker never comes, and the result is what was called back
        stop = result.tokens[9]
        cut = result.tokens.index(stop)
        calls, stopped = asyncio.run(
            streamed(engine, REPETITIVE, 21, stop_tokens={stop})
        )
        assert stopped.finish_reason == "stop"
        assert stopped.tokens == result.tokens[:cut]
        assert [token for token, *_ in calls] == stopped.tokens
        assert not any(last for _, last, *_ in calls)
        assert not any(done for *_, done in calls)
    finally:
        engine.stop()


# ------------------------------------------------------------------ #
# (2) the posts, counted by a stand-in for the loop
# ------------------------------------------------------------------ #
class CountingLoop:
    """Stands in for the callers' event loop: counts the cross-thread
    posts and runs what is posted, and what that schedules, at once on
    the posting thread, one callback at a time as a loop does."""

    def __init__(self):
        self.posts = []  # (perf_counter_ns, callbacks the post led to)
        self.ready = collections.deque()

    def is_closed(self):
        return False

    def call_soon(self, fn, *args):
        self.ready.append((fn, args))

    def call_soon_threadsafe(self, fn, *args):
        stamp = time.perf_counter_ns()
        self.ready.append((fn, args))
        ran = 0
        while self.ready:
            fn, args = self.ready.popleft()
            fn(*args)
            ran += 1
        self.posts.append((stamp, ran))


class Stream:
    """One request submitted straight to the engine on a stand-in loop."""

    def __init__(self, loop, tokens, new_tokens, **fields):
        self.stamps = []
        self.future = concurrent.futures.Future()
        self.request = GenerationRequest(
            prompt_tokens=list(tokens),
            sampling=SamplingParams(max_new_tokens=new_tokens),
            on_token=self._on_token, future=self.future, loop=loop,
            **fields,
        )

    def _on_token(self, token, last):
        assert not self.future.done()
        self.stamps.append((time.perf_counter_ns(), token))

    @property
    def tokens(self):
        return [token for _, token in self.stamps]


def engine_spans(engine, name):
    spans = sorted(engine.tracer._spans, key=lambda span: span.start_ns)
    return [span for span in spans if span.name == name]


@pytest.fixture(scope="module")
def counted_engine():
    engine = make_engine("plain", 8)
    yield engine
    engine.stop()


@pytest.mark.parametrize(
    "requests,chunk_steps", [(1, 8), (4, 8), (4, 1)],
    ids=["1-slot-8-steps", "4-slots-8-steps", "4-slots-1-step"],
)
def test_one_cross_thread_post_a_loop_for_a_chunk(
    counted_engine, requests, chunk_steps
):
    engine = counted_engine
    engine.decode_chunk = chunk_steps
    engine.tracer.clear()
    before = engine.stats["emit_handovers"]
    loop = CountingLoop()
    new_tokens = 1 + 2 * chunk_steps
    streams = [
        Stream(loop, prompt(seed), new_tokens) for seed in range(requests)
    ]
    for stream in streams:
        engine.submit(stream.request)
    for stream in streams:
        result = stream.future.result(timeout=120)
        assert result.tokens == stream.tokens
        assert len(result.tokens) == new_tokens
    emits = engine_spans(engine, "engine.emit")
    harvests = engine_spans(engine, "engine.harvest_prefills")
    # one post a chunk however many slots rode it and steps it ran, one
    # a harvested prefill record, and nothing else crosses
    assert [span.attributes["handovers"] for span in emits] == (
        [1] * len(emits)
    )
    assert [span.attributes["handovers"] for span in harvests] == (
        [1] * len(harvests)
    )
    assert sum(span.attributes["tokens"] for span in emits) == (
        requests * 2 * chunk_steps
    )
    assert len(loop.posts) == len(emits) + len(harvests)
    assert engine.stats["emit_handovers"] - before == len(loop.posts)
    # on the loop's side a request's callbacks are one run, and a chunk's
    # runs are chained: no post holds more than its requests' runs
    assert max(ran for _, ran in loop.posts) <= 1 + requests


def test_two_loops_get_a_post_each(counted_engine):
    engine = counted_engine
    engine.decode_chunk = 8
    engine.tracer.clear()
    loops = [CountingLoop(), CountingLoop()]
    streams = [
        Stream(loops[seed % 2], prompt(20 + seed), 9) for seed in range(4)
    ]
    for stream in streams:
        engine.submit(stream.request)
    for stream in streams:
        assert stream.future.result(timeout=120).tokens == stream.tokens
    emits = engine_spans(engine, "engine.emit")
    assert {span.attributes["handovers"] for span in emits} == {2}


def test_a_first_token_is_posted_before_the_next_chunk_is_dispatched(
    counted_engine,
):
    engine = counted_engine
    engine.decode_chunk = 8
    engine.tracer.clear()
    loop = CountingLoop()
    stream = Stream(loop, prompt(31), 9)
    engine.submit(stream.request)
    stream.future.result(timeout=120)
    harvest = engine_spans(engine, "engine.harvest_prefills")[0]
    dispatch = engine_spans(engine, "engine.dispatch_decode")[0]
    first_post, first_callback = loop.posts[0][0], stream.stamps[0][0]
    # inside the harvest's span, before the chunk is even built
    assert harvest.start_ns < first_post < first_callback
    assert first_callback < harvest.start_ns + harvest.duration_ns
    assert first_callback < dispatch.start_ns
    assert all(
        stamp > dispatch.start_ns + dispatch.duration_ns
        for stamp, _ in stream.stamps[1:]
    )


def test_a_crash_in_the_bookkeeping_hands_over_what_reached_the_slots():
    engine = make_engine("plain", 8)
    seen = {}

    def on_crash(exc):
        # what a supervisor would find in the slots, and what the
        # callers had been given by then
        seen["generated"] = sorted(
            list(slot.generated) for slot in engine.slots if slot.active
        )
        seen["delivered"] = sorted(stream.tokens for stream in streams)

    emit_token = engine._emit_token
    calls = [0]

    def failing(index, token, *args, **kwargs):
        emit_token(index, token, *args, **kwargs)
        calls[0] += 1
        if calls[0] == 2 + 8 + 3:  # second slot, mid-chunk
            raise RuntimeError("injected bookkeeping failure")

    engine._emit_token = failing
    engine.on_crash = on_crash
    loop = CountingLoop()
    streams = [Stream(loop, prompt(40 + seed), 17) for seed in range(2)]
    try:
        for stream in streams:
            engine.submit(stream.request)
        deadline = time.monotonic() + 120
        while not seen and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sorted(len(tokens) for tokens in seen["generated"]) == [4, 9]
        assert seen["delivered"] == seen["generated"]
    finally:
        engine.stop()


def test_posts_from_many_threads_keep_each_requests_order():
    """The loop's inbox under contention: more posting threads than
    cores at a shortened switch interval, one real loop. Every request's
    callbacks arrive in the order posted and its future last, whichever
    thread's post made the inbox."""
    posters, rounds, burst = 16, 40, 5

    async def main():
        loop = asyncio.get_running_loop()
        got = {n: [] for n in range(posters)}
        requests = [
            GenerationRequest(
                prompt_tokens=[1], sampling=SamplingParams(),
                on_token=lambda token, last, n=n: got[n].append(
                    (token, last, requests[n].future.done())
                ),
                future=loop.create_future(), loop=loop,
            )
            for n in range(posters)
        ]

        def post(n):
            for round_ in range(rounds):
                delivery = engine_lib._Delivery(requests[n])
                first = round_ * burst
                delivery.calls = [
                    (first + j, False) for j in range(burst)
                ]
                if round_ == rounds - 1:
                    delivery.calls[-1] = (first + burst - 1, True)
                    delivery.result = n
                engine_lib._post_to_loop(loop, [delivery])

        threads = [
            threading.Thread(target=post, args=(n,)) for n in range(posters)
        ]
        for thread in threads:
            thread.start()
        results = await asyncio.wait_for(
            asyncio.gather(*(request.future for request in requests)),
            timeout=60,
        )
        for thread in threads:
            thread.join(timeout=10)
            assert not thread.is_alive()
        return loop, got, results

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        loop, got, results = asyncio.run(main())
    finally:
        sys.setswitchinterval(interval)
    assert results == list(range(posters))
    for n in range(posters):
        assert [token for token, *_ in got[n]] == list(range(rounds * burst))
        assert [last for _, last, _ in got[n]].count(True) == 1
        assert got[n][-1][1] is True
        assert not any(done for *_, done in got[n])
    # the closed loop's inbox goes when the next loop gets its own
    assert loop in engine_lib._INBOXES
    asyncio.run(main())
    assert loop not in engine_lib._INBOXES


# ------------------------------------------------------------------ #
# (3) a stop string whose match falls inside a chunk
# ------------------------------------------------------------------ #
def test_a_stop_string_matched_inside_a_chunk_trims_as_before():
    from langstream_tpu.api.service import ChatMessage
    from langstream_tpu.providers.jax_local.provider import (
        JaxCompletionsService,
    )

    async def main():
        service = JaxCompletionsService({
            "model": {"preset": "tiny", "max_seq_len": 256},
            "engine": {
                "max-slots": 2, "max-seq-len": 256, "decode-chunk": 16,
            },
        })
        served = []
        generate = service.engine.generate

        async def recording(*args, **kwargs):
            result = await generate(*args, **kwargs)
            served.append(list(result.tokens))
            return result

        service.engine.generate = recording
        try:
            messages = [ChatMessage("user", "tell me everything")]
            options = {"max-tokens": 48}
            full = await service.get_chat_completions(messages, options)
            assert full.completion_tokens == len(served[0]) == 48
            # the text as the stream decoder releases it, token by token
            walker = service.tokenizer.stream_decoder()
            ends = []
            for token in served[0]:
                ends.append((ends[-1] if ends else 0) + len(walker.push(token)))
            # a stop string that starts where a token of the middle of the
            # second chunk (tokens 17-32) starts
            for at in range(1 + 16 + 5, 1 + 16 + 12):
                start = ends[at - 1]
                stop = full.content[start:start + 3]
                if ends[at] > start and full.content.find(stop) == start:
                    break
            else:
                pytest.fail("no token of the chunk's middle starts a stop")
            prefix = full.content[:start]
            before = service.engine.stats["tokens_wasted"].get("cancelled", 0)
            chunks = []

            class Consumer:
                def consume_chunk(self, answer_id, index, chunk, last):
                    chunks.append((chunk.content, last))

            for consumer in (None, Consumer()):
                stopped = await service.get_chat_completions(
                    messages, {**options, "stop": [stop]}, consumer
                )
                assert stopped.content == prefix
                assert stopped.finish_reason == "stop"
                assert stopped.completion_tokens == at
                # the cancel lands behind the chunk that held the match:
                # the engine decoded no further than a chunk past it
                assert served[0][:at] == served[-1][:at]
                assert at < len(served[-1]) <= 1 + 16 + 16 + 1
            assert "".join(text for text, _ in chunks) == prefix
            assert [last for _, last in chunks].count(True) == 1
            assert chunks[-1][1] is True
            wasted = service.engine.stats["tokens_wasted"]["cancelled"]
            assert wasted - before == len(served[-1]) + len(served[-2])
        finally:
            await service.close()

    asyncio.run(main())
