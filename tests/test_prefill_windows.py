"""A cold prompt is prefilled in windows of the bucket that wastes fewest
rows (ISSUE 34): the plan (``engine._prefill_windows``, the only planner)
at every edge of the benchmark's three bucket lists, the same answer
through windows as through one bucket on every family's tiny preset, and
the admission of several windowed prompts in one cycle. CPU backend."""

import concurrent.futures
import dataclasses

import numpy as np
import pytest

from langstream_tpu.providers.jax_local import engine as engine_lib
from langstream_tpu.providers.jax_local import hybrid_sparse_linear as hybrid
from langstream_tpu.providers.jax_local.engine import (
    DecodeEngine,
    GenerationRequest,
    SamplingParams,
    _prefill_windows,
)
from langstream_tpu.providers.jax_local.model import LlamaConfig, init_params
from langstream_tpu.runtime import tracing

SAT = [256, 2048]       # qwen25-7b-int8.sat and qwen25-0.5b.chat
DOCS = [2048, 4096]     # deepseek-v2-ep4.docs
LONGDOCS = [2048]       # minicpm-sala-int8.longdocs (stateful)


def parent_plan(total, reused, buckets, stateful):
    """What the parent (PR 33) dispatched: one bucket for a cold prompt
    that fits one, else ``_prefill_long``'s windows as they were."""
    largest = buckets[-1]
    windows, position = [], reused
    while total - position > largest:
        windows.append((position, largest))
        position += largest
    tail = engine_lib._bucket(total - position, buckets)
    windows.append((position if stateful else max(0, total - tail), tail))
    return windows


def strided(count, width, total):
    return [(i * width, width) for i in range(count - 1)] + [(total - width, width)]


# ------------------------------------------------------------------ #
# (1) the plan
# ------------------------------------------------------------------ #
@pytest.mark.parametrize(
    "total, buckets, stateful, want",
    [
        # sat / chat: two windows from 257, three from 513, four to 1,024;
        # five windows (1,280 rows) are over half of 2,048
        (154, SAT, False, [(0, 256)]),
        (256, SAT, False, [(0, 256)]),
        (257, SAT, False, [(0, 256), (1, 256)]),
        (460, SAT, False, [(0, 256), (204, 256)]),
        (512, SAT, False, [(0, 256), (256, 256)]),
        (513, SAT, False, [(0, 256), (256, 256), (257, 256)]),
        (1024, SAT, False, strided(4, 256, 1024)),
        (1025, SAT, False, [(0, 2048)]),
        (1796, SAT, False, [(0, 2048)]),
        # a recurrent state is taught no position twice: no shifted tail
        (460, SAT, True, [(0, 256), (256, 256)]),
        # docs: two windows of 2,048 are not half of 4,096
        (1113, DOCS, False, [(0, 2048)]),
        (2048, DOCS, False, [(0, 2048)]),
        (2049, DOCS, False, [(0, 4096)]),
        (4013, DOCS, False, [(0, 4096)]),
        # longdocs: one bucket, every prompt past it, right-padded tail
        (8543, LONGDOCS, True, [(i * 2048, 2048) for i in range(5)]),
        (16043, LONGDOCS, True, [(i * 2048, 2048) for i in range(8)]),
        # past the largest bucket the rest is covered like a short prompt
        (2048 + 300, SAT, False, [(0, 2048), (2048, 256), (2092, 256)]),
        (70, [16, 32], False, [(0, 32), (32, 32), (54, 16)]),
        # a tie in rows goes to the larger bucket (fewer dispatches)
        (100, [32, 64, 512], False, [(0, 64), (36, 64)]),
        # the default doubling set never engages: rows >= length > half
        (513, [64, 128, 256, 512, 1024], False, [(0, 1024)]),
    ],
)
def test_the_plan_at_every_edge(total, buckets, stateful, want):
    assert _prefill_windows(total, 0, buckets, stateful) == want


@pytest.mark.parametrize(
    "lengths, buckets, stateful",
    [
        (range(1113, 4014), DOCS, False),
        (range(8543, 16044, 7), LONGDOCS, True),
        (range(1025, 1797), SAT, False),
        (range(1, 257), SAT, False),
    ],
    ids=["docs", "longdocs", "sat-past-1024", "sat-short"],
)
def test_cells_that_bypass_plan_what_the_parent_planned(lengths, buckets, stateful):
    """``docs`` and ``longdocs`` dispatch the parent's programs in the
    parent's order; so do ``sat``'s prompts outside 257-1,024."""
    for total in lengths:
        assert _prefill_windows(total, 0, buckets, stateful) == parent_plan(
            total, 0, buckets, stateful
        )


@pytest.mark.parametrize("stateful", [False, True])
def test_a_cover_teaches_every_token_once_or_identically(stateful):
    """Windows run left to right without a gap from ``reused`` to the
    last token, none writes past the prompt but a padded tail, and where
    the plan leaves one bucket its rows are at most half of it."""
    for buckets in (SAT, DOCS, [16, 32], [32, 64, 512], [8]):
        for reused in (0, 5, buckets[0]):
            for total in range(reused + 1, 3 * buckets[-1], max(1, buckets[0] // 7)):
                windows = _prefill_windows(total, reused, buckets, stateful)
                taught = reused
                for offset, bucket in windows:
                    assert bucket in buckets and 0 <= offset <= taught
                    taught = max(taught, min(total, offset + bucket))
                    if not stateful and len(windows) > 1:
                        assert offset + bucket <= total
                assert taught == total
                fits = engine_lib._bucket(total - reused, buckets)
                if total - reused <= buckets[-1] and len(windows) > 1:
                    rows = sum(bucket for _, bucket in windows)
                    assert rows <= engine_lib.WINDOWED_ROWS_SHARE * fits


# ------------------------------------------------------------------ #
# (2) the same answer through windows as through one bucket
# ------------------------------------------------------------------ #
def _tiny_dense():
    config = LlamaConfig.tiny(max_seq_len=128)
    return config, init_params(config), {}


def _tiny_int8_kv():
    config, params, _ = _tiny_dense()
    return config, params, {"kv_quant": "int8"}


def _tiny_deepseek_v2():
    config = LlamaConfig.from_dict({"preset": "tiny-deepseek-v2"})
    return config, init_params(config, seed=3), {}


def _tiny_hybrid():
    config = dataclasses.replace(
        LlamaConfig.from_dict({"preset": "tiny-hybrid"}), flash_interpret=True
    )
    return config, hybrid.init_params(config, 3, quantized=True), {"quantize": "int8"}


FAMILIES = {
    "dense-bf16": _tiny_dense, "int8-kv": _tiny_int8_kv,
    "tiny_deepseek_v2": _tiny_deepseek_v2, "tiny_hybrid": _tiny_hybrid,
}
PENALISED = dict(
    max_new_tokens=12, temperature=0.0, presence_penalty=0.4, frequency_penalty=0.6
)


def _answer(family, buckets, tokens):
    config, params, options = FAMILIES[family]()
    engine = DecodeEngine(
        config, params, max_slots=1, max_seq_len=128, prefill_buckets=buckets,
        decode_chunk=4, prefix_cache=False, **options,
    )
    engine.start()
    try:
        future = concurrent.futures.Future()
        engine.submit(GenerationRequest(
            prompt_tokens=list(tokens), sampling=SamplingParams(**PENALISED),
            future=future,
        ))
        result = future.result(timeout=300)
        return result, np.asarray(engine._counts)[0], dict(engine.stats)
    finally:
        engine.stop()


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_prompt_in_windows_answers_as_in_one_bucket(family):
    """45 tokens against buckets [16, 128]: three windows of 16 (48 rows)
    where one bucket computes 128. Greedy under penalties, so the tokens
    depend on the counts the prefill's last window left."""
    rng = np.random.default_rng(7)
    tokens = [int(t) for t in rng.integers(1, 250, size=45)]
    one, one_counts, one_stats = _answer(family, [128], tokens)
    got, counts, stats = _answer(family, [16, 128], tokens)
    assert one_stats["prompts_windowed"] == 0
    assert one_stats["tokens_wasted"]["prefill_padding"] == 128 - 45
    assert stats["prompts_windowed"] == 1
    assert stats["tokens_wasted"]["prefill_padding"] == 3 * 16 - 45
    assert stats["prefill_calls"] == 1 and stats["warm_prefill_calls"] == 0
    assert got.tokens == one.tokens
    assert len(got.tokens) == PENALISED["max_new_tokens"]
    np.testing.assert_allclose(got.logprobs, one.logprobs, atol=2e-2)
    np.testing.assert_array_equal(counts, one_counts)
    assert counts.sum() >= len(got.tokens) - 1


# ------------------------------------------------------------------ #
# (3) windowed prompts are not rationed to one a cycle
# ------------------------------------------------------------------ #
class Stream:
    def __init__(self, tokens, new_tokens, on_first=None):
        self.count = 0
        self.on_first = on_first
        self.future = concurrent.futures.Future()
        self.request = GenerationRequest(
            prompt_tokens=list(tokens),
            sampling=SamplingParams(max_new_tokens=new_tokens),
            on_token=self._on_token, future=self.future,
        )

    def _on_token(self, token, last):
        self.count += 1
        if self.count == 1 and self.on_first is not None:
            self.on_first()

    def wait(self):
        return self.future.result(timeout=300).tokens


def _prompt(seed, length):
    return [(7 * seed + 3 * j) % 250 + 1 for j in range(length)]


def test_windowed_prompts_freed_together_are_admitted_in_one_cycle():
    """Three prompts of 20, 27 and 32 tokens against buckets [16, 64] find
    three free slots beside a decoding stream: each is two windows of 16,
    all six are dispatched in the one cycle (the hold is for prompts past
    the largest bucket), and the counters and spans read what the plan
    says."""
    chunk = 8
    config = LlamaConfig.tiny(max_seq_len=128)
    engine = DecodeEngine(
        config, init_params(config), max_slots=4, max_seq_len=128,
        prefill_buckets=[16, 64], decode_chunk=chunk, prefix_cache=False,
    )
    engine.tracer = tracing.Tracer("prefill-windows")
    engine.start()
    try:
        lengths = (20, 27, 32)
        alone = []
        for seed, length in enumerate(lengths):
            stream = Stream(_prompt(seed, length), 1 + chunk)
            engine.submit(stream.request)
            alone.append(stream.wait())
        before = dict(engine.stats)
        padding = engine.stats["tokens_wasted"]["prefill_padding"]
        log = len(engine.dispatch_log)
        engine.tracer.clear()
        trio = [
            Stream(_prompt(seed, length), 1 + chunk)
            for seed, length in enumerate(lengths)
        ]

        def submit_all():
            for stream in trio:
                engine.submit(stream.request)

        runner = Stream(_prompt(93, 6), 1 + 6 * chunk, on_first=submit_all)
        engine.submit(runner.request)
        runner.wait()
        assert [stream.wait() for stream in trio] == alone
        kinds = "".join(e["kind"][0] for e in engine.dispatch_log[log:])
        runs = [len(run) for run in kinds.split("d") if run]
        assert runs == [1, 6]  # the runner's bucket, then 3 x 2 windows
        assert engine.stats["prompts_windowed"] - before["prompts_windowed"] == 3
        assert engine.stats["long_prompts_held"] == before["long_prompts_held"]
        assert engine.stats["prefill_calls"] - before["prefill_calls"] == 4
        assert engine.stats["tokens_wasted"]["prefill_padding"] - padding == (
            (16 - 6) + sum(2 * 16 - length for length in lengths)
        )
        spans = [
            span for span in engine.tracer._spans
            if span.name == "engine.prefill_dispatch"
        ]
        spans.sort(key=lambda span: span.start_ns)
        assert [span.attributes["kind"] for span in spans] == ["cold"] + ["long"] * 3
        for span, length in zip(spans[1:], lengths):
            plan = _prefill_windows(length, 0, [16, 64], False)
            assert span.attributes["windows"] == len(plan) == 2
            assert span.attributes["offset"] == 0
            assert span.attributes["bucket"] == 16 and span.attributes["rows"] == 1
        gauges = engine_lib.engines_snapshot()
        assert gauges["jax_engine_prompts_windowed_total"] >= (
            engine.stats["prompts_windowed"]
        )
    finally:
        engine.stop()
