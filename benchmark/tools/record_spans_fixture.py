#!/usr/bin/env python3
"""Record the small trace ``tests/test_spans.py`` reads
(tests/spans_fixture.xplane.pb and spans_fixture.json): a tiny engine on
the TPU serving a few requests under the profiler, the harness's marker
first, then the ring of finished legs and the window's two instants.

    python benchmark/tools/record_spans_fixture.py <out dir>
"""

import asyncio
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


async def serve(engine, sampling_of, salt: int):
    """Six requests on four slots, a few milliseconds apart, two lengths
    of prompt (two buckets) and two lengths of answer. ``salt`` keeps a
    pass's prompts apart from another's, so that none finds a prefix in
    a slot and takes a path the first pass did not compile."""
    async def one(index: int):
        await asyncio.sleep(0.004 * index)
        prompt = [1 + (salt + index * 7 + j) % 200 for j in range(10 if index % 3 else 40)]
        return await engine.generate(
            prompt, sampling_of(6 if index % 2 else 12), trace_id=f"fixture-{index}"
        )

    return await asyncio.gather(*[one(i) for i in range(6)])


def main() -> int:
    import jax

    from benchmark import spans, trace_reduce
    from langstream_tpu.providers.jax_local.engine import DecodeEngine, SamplingParams
    from langstream_tpu.providers.jax_local.model import LlamaConfig, init_params
    from langstream_tpu.runtime import journey

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_spans_fixture needs a TPU")
    out = sys.argv[1]
    os.makedirs(out, exist_ok=True)
    config = LlamaConfig.tiny(max_seq_len=128)
    engine = DecodeEngine(
        config, init_params(config), max_slots=4, max_seq_len=128,
        prefill_buckets=[16, 64], decode_chunk=4,
    )
    engine.start()

    def sampling_of(tokens: int):
        return SamplingParams(max_new_tokens=tokens)

    asyncio.run(serve(engine, sampling_of, 0))  # every shape compiles here
    journey.LEGS.clear()
    scratch = os.path.join(out, "spans_fixture_trace")
    shutil.rmtree(scratch, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    jax.profiler.start_trace(scratch, profiler_options=options)
    with jax.profiler.TraceAnnotation(trace_reduce.MARK):
        begin = time.perf_counter()
    results = asyncio.run(serve(engine, sampling_of, 100))
    time.sleep(0.02)
    end = time.perf_counter()
    jax.profiler.stop_trace()
    engine.stop()
    path = trace_reduce.find_trace(scratch)
    shutil.copy(path, os.path.join(out, "spans_fixture.xplane.pb"))
    legs = journey.finished_legs()
    with open(os.path.join(out, "spans_fixture.json"), "w") as handle:
        json.dump({"begin": begin, "end": end, "legs": legs}, handle, indent=1)
    read = spans.read_trace(path, end - begin)
    print("bytes", os.path.getsize(path), "answers", [len(r.tokens) for r in results])
    if read is None:
        print("no device plane, marker or engine span in the trace")
        return 1
    parts = spans.first_token_parts(read, legs, begin)
    print("window_s", end - begin, "busy_s", read["busy_s"], "gap_total_s", read["gap_total_s"])
    print("idle_by_phase", spans.idle_by_phase(read))
    print("idle_shares", spans.idle_shares(read))
    print("programs", [(p["kind"], p["phase"] is not None) for p in read["programs"]])
    print("parts", len(parts), "of", len(legs), parts[:2])
    print("decode_step_ms", spans.decode_step_ms(read), "prefill_s", spans.prefill_seconds(read))
    return 0


if __name__ == "__main__":
    sys.exit(main())
