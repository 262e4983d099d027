#!/usr/bin/env python3
"""Record the small trace the self-tests reduce (tests/fixture.xplane.pb):
a few runs on the TPU of one jitted program that holds a matmul loop and a
Pallas kernel, with idle sleeps between them, the harness's marker first.

    python benchmark/tools/record_fixture.py <out dir>
"""

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from benchmark import trace_reduce

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_fixture needs a TPU")

    def add_one(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1.0

    @jax.jit
    def program(x):
        def body(_, carry):
            y = pl.pallas_call(
                add_one, out_shape=jax.ShapeDtypeStruct(carry.shape, carry.dtype)
            )(carry)
            return jnp.tanh(y @ y.T @ y) * 0.5

        return jax.lax.fori_loop(0, 6, body, x)

    x = jnp.ones((1024, 1024), jnp.float32)
    program(x).block_until_ready()
    out = sys.argv[1]
    scratch = os.path.join(out, "fixture_trace")
    shutil.rmtree(scratch, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    jax.profiler.start_trace(scratch, profiler_options=options)
    began = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_reduce.MARK):
        pass
    for _ in range(4):
        x = program(x)
        x.block_until_ready()
        time.sleep(0.002)
    span = time.perf_counter() - began
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(scratch, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, os.path.join(out, "fixture.xplane.pb"))
    reduced = trace_reduce.reduce_trace(path, span)
    print({k: v for k, v in reduced.items() if k not in ("op_seconds", "gaps", "flows")}, span)
    with open(os.path.join(out, "fixture_span.txt"), "w") as handle:
        handle.write(repr(span))
    return 0


if __name__ == "__main__":
    sys.exit(main())
