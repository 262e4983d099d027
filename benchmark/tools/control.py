#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, on the chip at
the cell's own size: for each seed, serve a short window of the cell's
traffic, then put through the harness's own judgement (``report.judge``,
the one a run's ``correct`` comes from) (a) what the program served and
(b) the reference in the program's place, computed in each precision below
the configuration's (``lower_precision`` in its file) on the same prompts
and tokens. (a) has to come out correct and every (b) not. The benchmark's
own runs never run this.

    python benchmark/tools/control.py --workload <name> --seeds 1,2,3 --seconds 8 --out <file.jsonl>

``--set quantization=int8`` serves the PROGRAM's own lower-precision path
instead (an instance global of the configuration's file overridden): then
(a) is the control and has to come out not correct. ``--samples 4,8``
also reads the numbers over the first 4 and 8 requests of the sample, for
choosing ``sample_requests``. ``--judge <file.jsonl>`` needs no chip: it
puts the numbers an earlier call read, kept in that file, through
``report.judge`` again with the limits the configuration's file has now.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402


def judge_again(workload: str, path: str) -> int:
    from benchmark import harness, report

    cell = harness.load_cell(workload)
    with open(path) as handle:
        lines = [json.loads(text) for text in handle if text.strip()]
    for line in lines:
        if line["workload"] != workload:
            continue
        for who, read in line.items():
            if isinstance(read, dict) and "numbers" in read:
                checks, correct = report.judge(cell, read["numbers"], 0, 0, 0)
                print(json.dumps({
                    "seed": line["seed"], "set": line["set"], "who": who,
                    "correct": correct, "compared": checks,
                }))
    return 0


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--seconds", type=float, default=8.0)
    parser.add_argument("--lower", default="")
    parser.add_argument("--samples", default="")
    parser.add_argument("--set", action="append", default=[], help="global=value")
    parser.add_argument("--out", default="")
    parser.add_argument("--judge", default="")
    args = parser.parse_args()
    if args.judge:
        return judge_again(args.workload, args.judge)
    overrides = dict(item.split("=", 1) for item in args.set)
    # before anything imports JAX (report imports the reference); another
    # program needs a cache of its own
    cache_dir = bench_run.place_compile_cache(
        args.workload + "".join(f"-{k}-{v}" for k, v in sorted(overrides.items()))
    )
    from benchmark import harness, report
    from benchmark.reference import compare

    cell = harness.load_cell(args.workload)  # a fresh dict, its files fresh from disk
    cell["config_file"]["globals"].update(overrides)
    device = harness.require_tpu(cell["chips"])
    lowers = [x for x in (args.lower or cell["config_file"]["lower_precision"]).split(",") if x]
    prefixes = [int(x) for x in args.samples.split(",") if x]
    for seed in (int(s) for s in args.seeds.split(",")):
        raw = asyncio.run(harness.run_cell(
            cell, seed, args.seconds, False, time.perf_counter(), device, cache_dir,
        ))
        got = report.compare_with_reference(cell, raw, seed, lowers)
        line = {"workload": args.workload, "seed": seed, "device": device,
                "set": overrides, "reference_s": got.pop("reference_s", None)}
        for who, numbers in got.items():
            checks, correct = report.judge(cell, numbers, 0, 0, 0)
            line[who] = {"correct": correct, "compared": checks}
            if numbers:
                line[who]["numbers"] = dict(
                    compare.summed_up(numbers["each_request"]),
                    prompt_lengths=numbers["prompt_lengths"],
                )
                for count in prefixes:
                    line[who][f"first_{count}"] = compare.summed_up(
                        numbers["each_request"][:count]
                    )
            harness.say(device, f"seed {seed} {who}: correct {correct} " + json.dumps(
                {k: v for k, v in line[who].items() if k != "compared"}
            ))
        if not args.out:
            print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as handle:
                handle.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
