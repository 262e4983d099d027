#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

Drives the main path once, the way ``apps run`` does (``cli/main.py``
``_apps_run``): YAML app -> local runner -> memory broker ->
``ai-chat-completions`` agent -> ``jax-local`` provider -> ``DecodeEngine``,
answering eight concurrent chat sessions over the gateway's WebSocket at
the full published widths and depth of Qwen-2.5-7B with int8 weights
(random, seed 0; byte tokenizer).

    python chip_smoke.py                 # one chip: phases dense, paged
    python chip_smoke.py --chips 4       # tp=4 against tp=1, dense only

One process, the only one that touches JAX. It needs a TPU: any other
platform exits non-zero before anything is built, and nothing here falls
back to the CPU, to interpret mode or to a reference kernel. Every phase
prints one JSON line; a phase that raises ends the run non-zero. The last
line of standard output is the device record the driver reads.

With random weights over a 152,064-entry vocabulary the byte tokenizer
decodes almost every sampled id to nothing, so an answer's size is
counted in tokens (each request must run to ``max-tokens`` or to a
sampled stop token), and completeness at the client (each session must see
its ``stream-last-message`` frame inside its own time limit — an engine
error under the pipeline's error policy reaches a client only as
silence).
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import logging
import os
import socket
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.abspath(__file__))
APP_DIR = os.path.join(REPO, "examples", "applications", "jax-completions")

# first-token logprob, tp=4 against tp=1: bf16 partial sums reorder under
# tensor parallelism, so values agree to bf16 rounding of O(10) logits,
# not bit for bit
TP_LOGPROB_TOLERANCE = 0.25


def require_tpu(chips: int) -> Dict[str, Any]:
    """The device assertion: a TPU with at least ``chips`` devices, or no
    run at all. Returns the device as JAX reports it."""
    import jax

    devices = jax.devices()
    record = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if record["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU; JAX reports {record['platform']!r}"
        )
    if record["count"] < chips:
        raise SystemExit(
            f"chip_smoke --chips {chips} needs {chips} devices; "
            f"JAX reports {record['count']}"
        )
    return record


def device_checks(engine) -> List[str]:
    """What only the chip can show: the kernel gates are open on this
    device and the engine's own compiled prefill and decode programs hold
    Mosaic kernels. Returns the kernels found."""
    from langstream_tpu.ops.decode_kernel import use_flash_decode
    from langstream_tpu.ops.flash_attention import use_flash

    config = engine.config
    dim, heads, kv_heads = (
        config.dims_per_head, config.num_heads, config.num_kv_heads
    )
    bucket = engine.prefill_buckets[-1]
    found = []
    if not use_flash(bucket, dim):
        raise AssertionError(f"use_flash({bucket}, {dim}) is off on this device")
    if not use_flash_decode(engine.max_seq_len, dim, heads, kv_heads):
        raise AssertionError("use_flash_decode is off on this device")
    if engine.paged:
        if engine.paged_kernel != "fused":
            raise AssertionError(
                f"paged kernel resolved to {engine.paged_kernel!r}, not 'fused'"
            )
        found.append("ragged_paged_attention")
    else:
        found += ["flash_prefill_attention", "flash_decode_attention"]
    wanted = {
        "prefill": engine._get_prefill(bucket),  # noqa: SLF001
        "decode": engine._get_decode(engine.decode_chunk),  # noqa: SLF001
    }
    for name, wanted_fn in wanted.items():
        # the first job of each kind is its smallest group: a program
        # precompile built and the sessions ran, so this finds it compiled
        fn, avals = next(
            job for job in engine._variant_jobs()  # noqa: SLF001
            if job[0] is wanted_fn
        )
        with engine.mesh:
            text = fn.lower(
                *engine._variant_args(avals)  # noqa: SLF001
            ).compile().as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError(
                f"compiled {name} variant holds no tpu_custom_call"
            )
    return found


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def _questions(short: int, long: int, short_chars: int, long_chars: int):
    """One distinct question per session: ``short`` of about 200 prompt
    tokens once the pipeline's template is around them, ``long`` past the
    first bucket so bucketed prefill runs at the largest one."""
    sizes = [short_chars] * short + [long_chars] * long
    words = "the quick brown fox jumps over the lazy dog "
    return [
        (f"session {index}: " + words * (size // len(words) + 1))[:size]
        for index, size in enumerate(sizes)
    ]


async def _chat(port: int, app_id: str, session: str, question: str,
                limit_s: float) -> Dict[str, Any]:
    """One chat session: send the question, read frames to the terminal
    one. Raises on a session that outlives ``limit_s``."""
    import websockets

    url = (
        f"ws://127.0.0.1:{port}/v1/chat/default/{app_id}/chat"
        f"?param:session-id={session}"
    )

    async def talk() -> Dict[str, Any]:
        started = time.perf_counter()
        frames, text = 0, []
        async with websockets.connect(url, max_size=None) as ws:
            await ws.send(json.dumps({"value": question}))
            async for frame in ws:
                frames += 1
                record = json.loads(frame).get("record", {})
                text.append(str(record.get("value") or ""))
                headers = record.get("headers", {})
                if headers.get("stream-last-message") == "true":
                    return {
                        "session": session,
                        "frames": frames,
                        "chars": len("".join(text)),
                        "seconds": time.perf_counter() - started,
                    }
        raise RuntimeError(f"{session}: socket closed before the last frame")

    try:
        return await asyncio.wait_for(talk(), limit_s)
    except asyncio.TimeoutError:
        raise RuntimeError(
            f"{session}: no complete answer within {limit_s:.0f}s"
        ) from None


def _record_results(completions) -> List[Any]:
    """Per-request results as the agent receives them: the streamed
    records carry text only, and the engine's counters are totals."""
    results: List[Any] = []
    answer = completions.get_chat_completions

    async def recording(messages, options, stream_consumer=None):
        result = await answer(messages, options, stream_consumer)
        results.append(result)
        return result

    completions.get_chat_completions = recording
    return results


async def _first_token_logprobs(completions, questions) -> List[float]:
    """Greedy first-token logprob of each question, asked of the engine
    directly (the example app does not surface logprobs) — what the
    ``--chips 4`` comparison holds tp=4 to."""
    from langstream_tpu.providers.jax_local.engine import SamplingParams

    out = []
    for question in questions:
        tokens = completions.tokenizer.apply_chat_template(
            [{"role": "user", "content": question}]
        )
        result = await completions.engine.generate(
            tokens, SamplingParams(temperature=0.0, max_new_tokens=1)
        )
        out.append(float(result.logprobs[0]))
    return out


async def run_phase(
    phase: str,
    *,
    model: str = "qwen-2.5-7b",
    quantization: str = "int8",
    tp: int = 1,
    max_slots: int = 32,
    max_seq_len: int = 2048,
    decode_chunk: int = 32,
    prefill_buckets=(256, 2048),
    kv_layout: str = "dense",
    short_sessions: int = 6,
    long_sessions: int = 2,
    short_chars: int = 60,
    long_chars: int = 1100,
    max_tokens: int = 64,
    session_limit_s: float = 300.0,
    logprobs: bool = False,
) -> Dict[str, Any]:
    """Start the app and the gateway, answer the sessions, check what came
    out, stop everything. Returns the phase's record; raises on anything
    short of a full pass."""
    import jax

    from langstream_tpu.gateway import GatewayServer
    from langstream_tpu.runtime.local import run_application

    instance = {"instance": {
        "streamingCluster": {"type": "memory"},
        "computeCluster": {"type": "local"},
        "globals": {
            "model": model,
            "quantization": quantization,
            "tp": tp,
            "max-slots": max_slots,
            "max-seq-len": max_seq_len,
            "max-tokens": max_tokens,
            "decode-chunk": decode_chunk,
            "prefill-buckets": list(prefill_buckets),
            "precompile": True,
            "kv-layout": kv_layout,
        },
    }}
    questions = _questions(
        short_sessions, long_sessions, short_chars, long_chars
    )
    devices = jax.devices()[:tp]
    phase_started = time.perf_counter()
    with tempfile.NamedTemporaryFile("w", suffix=".json") as handle:
        json.dump(instance, handle)
        handle.flush()
        runner = await run_application(APP_DIR, instance_file=handle.name)
    gateway = None
    try:
        started_s = time.perf_counter() - phase_started
        completions = runner._service_provider_registry.completions()  # noqa: SLF001
        engine = completions.engine
        # bytes on each device once weights and KV are placed
        resident = [
            (d.memory_stats() or {}).get("bytes_in_use") for d in devices
        ]
        results = _record_results(completions)
        gateway = GatewayServer(port=_free_port())
        gateway.register_local_runner(runner)
        await gateway.start()
        app_id = runner.application.application_id
        asked = time.perf_counter()
        answers = await asyncio.gather(*[
            _chat(gateway.port, app_id, f"{phase}-{index}", question,
                  session_limit_s)
            for index, question in enumerate(questions)
        ])
        answered_s = time.perf_counter() - asked
        if completions.engine is not engine:
            raise AssertionError("the supervisor replaced the engine mid-phase")
        supervisor = completions._supervisor  # noqa: SLF001
        if supervisor is not None and (
            supervisor.restarts or supervisor.state != "serving"
        ):
            raise AssertionError(
                f"supervisor: {supervisor.restarts} restarts, "
                f"state {supervisor.state!r}"
            )
        stats = dict(engine.stats)
        sessions = len(questions)
        if stats["requests"] != sessions:
            raise AssertionError(
                f"engine finished {stats['requests']} requests, not {sessions}"
            )
        # bucket padding is the only waste a healthy run books
        wasted = {
            reason: count for reason, count in stats["tokens_wasted"].items()
            if reason != "prefill_padding"
        }
        if stats["requests_shed"] or wasted:
            raise AssertionError(
                f"shed {stats['requests_shed']}, wasted {wasted}"
            )
        generated = [r.completion_tokens for r in results]
        for result in results:
            # greedy on random weights: an answer runs to max-tokens
            # unless the model happens to sample the stop token
            full = result.completion_tokens == max_tokens
            if result.finish_reason not in ("length", "stop") or not (
                full or (result.finish_reason == "stop"
                         and result.completion_tokens >= 1)
            ):
                raise AssertionError(
                    f"a request ended {result.finish_reason!r} with "
                    f"{result.completion_tokens} tokens"
                )
        if len(results) != sessions or stats["tokens_useful"] != sum(generated):
            raise AssertionError(
                f"{len(results)} results with {generated} tokens; engine "
                f"counted {stats['tokens_useful']} useful tokens"
            )
        agent_errors = {
            agent["agent-id"]: agent["stats"]["errors"]
            for agent in runner.info()["agents"] if "stats" in agent
        }
        if any(agent_errors.values()):
            raise AssertionError(f"agent errors: {agent_errors}")
        kernels = device_checks(engine)
        record = {
            "phase": phase,
            "model": model,
            "quantization": quantization,
            "tp": tp,
            "kv_layout": kv_layout,
            "requests": stats["requests"],
            "tokens_out": stats["tokens_useful"],
            "prompt_tokens": sorted(r.prompt_tokens for r in results),
            "answer_chars": sum(a["chars"] for a in answers),
            "variants": engine.precompile_stats["variants"],
            "init_s": round(started_s - engine.precompile_stats["seconds"], 1),
            "precompile_s": round(engine.precompile_stats["seconds"], 1),
            "precompile_compile_s": round(
                engine.precompile_stats["compile_seconds"], 1
            ),
            "first_answer_s": round(min(a["seconds"] for a in answers), 2),
            "all_answers_s": round(answered_s, 2),
            "kernels": kernels,
            "resident_bytes": resident,
        }
        if logprobs:
            record["first_token_logprobs"] = await _first_token_logprobs(
                completions, questions
            )
    finally:
        if gateway is not None:
            await gateway.stop()
        await runner.stop()
    record["phase_s"] = round(time.perf_counter() - phase_started, 1)
    record["peak_bytes_in_use"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
    ]
    return record


def _release(chips: int) -> None:
    """Between phases: the stopped engine's weights and cache must have
    left the device, or the next phase does not fit."""
    import jax

    gc.collect()
    for device in jax.devices()[:chips]:
        held = device.memory_stats()["bytes_in_use"]
        if held > 1 << 30:
            raise AssertionError(
                f"{device}: {held / 2**30:.2f} GiB still in use after the "
                "phase was stopped"
            )


def _run(phase: str, **kwargs) -> Dict[str, Any]:
    record = asyncio.run(run_phase(phase, **kwargs))
    print(json.dumps(record), flush=True)
    return record


def _compare_tp(sharded: Dict[str, Any], single: Dict[str, Any]) -> None:
    resident = sharded["resident_bytes"]
    total = sum(resident)
    # split four ways: no device holds much more than its quarter (the
    # replicated norms and penalty counts add a little to each)
    if max(resident) > 0.4 * total or resident[0] > 1.25 * min(resident):
        raise AssertionError(
            f"tp=4 is not split four ways: bytes in use {resident}"
        )
    gaps = [
        abs(a - b) for a, b in zip(
            sharded["first_token_logprobs"], single["first_token_logprobs"]
        )
    ]
    if max(gaps) > TP_LOGPROB_TOLERANCE:
        raise AssertionError(
            f"first-token logprobs, tp=4 against tp=1, differ by up to "
            f"{max(gaps):.3f} (tolerance {TP_LOGPROB_TOLERANCE}): {gaps}"
        )
    print(json.dumps({
        "phase": "compare-tp",
        "max_first_token_logprob_gap": max(gaps),
        "tolerance": TP_LOGPROB_TOLERANCE,
        "resident_bytes_tp4": resident,
    }), flush=True)


def main(argv: Optional[List[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)
    device = require_tpu(args.chips)
    # the engine's own start-up lines (weights, variants, compile
    # seconds) go to standard error, stamped
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s: %(message)s",
    )

    from langstream_tpu.runtime.compile_cache import configure_compile_cache

    configure_compile_cache()
    if args.chips == 4:
        sharded = _run("dense-tp4", tp=4, logprobs=True)
        _release(4)
        single = _run("dense-tp1", tp=1, logprobs=True)
        _compare_tp(sharded, single)
    else:
        _run("dense", kv_layout="dense")
        _release(1)
        _run("paged", kv_layout="paged")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
