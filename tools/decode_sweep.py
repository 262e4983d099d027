"""On-chip decode-chunk sweep: measure steady-state engine throughput at
several ``decode_chunk`` sizes to pick the dispatch granularity for the
serving config (bigger chunks amortize host round trips; smaller
chunks cut time-to-first-token and admission latency).

Run on the TPU: ``python tools/decode_sweep.py [preset] [quant]``.
Prints one line per chunk size. Uses the persistent compile cache, so a
re-run after the first is cheap.
"""
from __future__ import annotations

import asyncio
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PRESET = sys.argv[1] if len(sys.argv) > 1 else "llama-3-8b"
QUANT = (sys.argv[2] if len(sys.argv) > 2 else "int8") or None
SLOTS = int(os.environ.get("SWEEP_SLOTS", "32"))
PROMPT_LEN = int(os.environ.get("SWEEP_PROMPT", "128"))
NEW = int(os.environ.get("SWEEP_NEW", "128"))
CHUNKS = [int(c) for c in os.environ.get("SWEEP_CHUNKS", "16,32,64").split(",")]


def main() -> None:
    from langstream_tpu.providers.jax_local import model as model_lib
    from langstream_tpu.providers.jax_local.engine import (
        DecodeEngine,
        SamplingParams,
    )
    from langstream_tpu.runtime.compile_cache import configure_compile_cache

    configure_compile_cache()
    config = model_lib.LlamaConfig.from_dict({"preset": PRESET})
    config = dataclasses.replace(config, max_seq_len=PROMPT_LEN + NEW + 64)
    t0 = time.perf_counter()
    if QUANT == "int8":
        from langstream_tpu.providers.jax_local.quant import (
            init_quantized_params,
        )

        params = init_quantized_params(config, seed=0)
    else:
        params = model_lib.init_params(config, seed=0)
    print(f"params init: {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    sampling = SamplingParams(temperature=0.0, max_new_tokens=NEW)

    def prompt(i: int):
        return [(7 * i + j) % 250 + 1 for j in range(PROMPT_LEN)]

    for chunk in CHUNKS:
        engine = DecodeEngine(
            config, params, max_slots=SLOTS, max_seq_len=config.max_seq_len,
            prefill_buckets=[PROMPT_LEN], decode_chunk=chunk,
            quantize=QUANT, pipeline_decode=True,
        )

        async def run():
            engine.precompile()
            engine.start()
            await asyncio.gather(
                *[engine.generate(prompt(i), sampling) for i in range(SLOTS)]
            )
            engine.reset_stats()
            t0 = time.perf_counter()
            results = await asyncio.gather(
                *[engine.generate(prompt(i + 1), sampling)
                  for i in range(SLOTS)]
            )
            elapsed = time.perf_counter() - t0
            tokens = sum(len(r.tokens) for r in results)
            stats = engine.stats
            steps = max(stats["decode_steps"], 1)
            walls = sorted(w for _, _, w in engine.chunk_log)
            p50 = walls[len(walls) // 2] if walls else 0.0
            print(
                f"chunk={chunk:3d}: {tokens / elapsed:7.1f} tok/s  "
                f"({stats['decode_time'] / steps * 1e3:6.2f} ms/step, "
                f"chunk wall p50 {p50 * 1e3:6.0f} ms, "
                f"occupancy {stats['active_slot_steps'] / steps / SLOTS * 100:4.1f}%)",
                flush=True,
            )

        asyncio.run(run())
        engine.stop()
        del engine


if __name__ == "__main__":
    main()
