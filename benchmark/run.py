#!/usr/bin/env python3
"""python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the machine it is started on.
One process, the only one that touches JAX; it needs a TPU with the
cell's chips and exits non-zero without one. The last line of standard
output is the result the driver reads.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()  # set-up is counted from process start

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def place_compile_cache(workload: str) -> str:
    """Before JAX is imported: the persistent compile cache goes to a
    fixed directory inside the checkout (the path is part of the cache's
    key), and the machine's size cap (192 MiB, under one cell's programs)
    is lifted. The program sets no directory of its own where this
    variable is set (runtime/compile_cache.py)."""
    cache_dir = os.path.join(HERE, ".cache", workload)
    os.makedirs(cache_dir, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache_dir, "jax")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return cache_dir


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "langstream_tpu")):
        print("benchmark: the system under test (langstream_tpu/) is not in "
              "this checkout", file=sys.stderr)
        return 2
    # before anything imports JAX, which reads these variables once
    cache_dir = place_compile_cache(args.workload)
    sys.path.insert(0, ROOT)
    from benchmark import harness, report

    cell = harness.load_cell(args.workload)
    device = harness.require_tpu(cell["chips"])
    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(name)s: %(message)s",
    )
    raw = asyncio.run(harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), STARTED, device, cache_dir,
    ))
    line = report.result_line(cell, raw, args.seed, bool(args.trace), device)
    with open(os.path.join(cache_dir, "last_run.json"), "w") as handle:
        json.dump(report.run_summary(raw, line), handle)
    report.say_compared(device, line)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
