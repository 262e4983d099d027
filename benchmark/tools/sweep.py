#!/usr/bin/env python3
"""Try one cell's traffic at several values of one parameter, on the chip,
in one process: the cell's app is built once, and every value (times every
seed) is an episode of warm-up, a window of ``--seconds`` and a drain.

This is how an open-loop cell's rate is found, once: for every rate it
prints how many requests failed, whether the engine's pending queue grew
through the window, and how full the slots were. The cell's rate is 0.8 of
the highest rate at which no request fails, the queue does not grow and the
slots are not continuously full. The benchmark never searches for a rate.

    python benchmark/tools/sweep.py --workload <name> --vary users.rate_per_s=8.5,10,11 \\
        --set window_opens.after_seconds=15 --set cooldown_seconds=0 --seconds 45 --out <file.jsonl>

A key with dots names a parameter inside a group of the traffic file.

Warm up for longer than a request's lifetime: slots fill that slowly, and a
shorter look reads a rate as sustained that is not.

``--series 1`` adds the tokens delivered in every half second of the
window, for a look at how evenly a mix delivers.
"""

from __future__ import annotations

import argparse
import asyncio
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402


def _value(text: str):
    try:
        return json.loads(text)
    except ValueError:
        return text


def _with(traffic, overrides):
    """A copy of the traffic file with ``{"a.b": value}`` set at a -> b."""
    out = copy.deepcopy(traffic)
    for dotted, value in overrides.items():
        *groups, key = dotted.split(".")
        spot = out
        for group in groups:
            spot = spot[group]
        spot[key] = value
    return out


async def _drained(engine, limit_s: float = 60.0) -> None:
    from benchmark import probes

    deadline = time.perf_counter() + limit_s
    while time.perf_counter() < deadline:
        now = probes.counters(engine)
        if not now["slots_active"] and not now["queue_depth"]:
            return
        await asyncio.sleep(0.25)


async def sweep(cell, episodes, seconds, device, cache_dir, out, series):
    from benchmark import harness, measure, probes

    async with harness.serving(cell, episodes[0][1], device, cache_dir) as served:
        generator = harness.load_module("generators", cell["traffic_file"]["kind"])
        slots = served.engine.max_slots
        for number, (overrides, seed) in enumerate(episodes):
            traffic = _with(cell["traffic_file"], overrides)
            # a number of its own for every request of the sweep
            plan = generator.plan(traffic, seed, seconds, slots, 10000 * number)
            drive = served.drive(plan, traffic, f"{seed}-{number}")
            began = time.perf_counter()
            await drive.open_window()
            samples = []
            while time.perf_counter() < drive.window["closes"]:
                samples.append(probes.counters(served.engine))
                await asyncio.sleep(0.25)
            await drive.close_window()
            ctx = {
                "counted_by": drive.counted_by, "requests": drive.records,
                "window": drive.window, "limit_s": drive.limit_s,
            }
            counted = measure.counted(ctx)
            half = len(samples) // 2
            queue = [s["queue_depth"] for s in samples]
            active = [s["slots_active"] for s in samples]
            line = {
                **overrides, "seed": seed, "device": device,
                "warmup_s": drive.window["opens"] - began,
                "attempted": len(counted),
                "failed": sum(1 for r in counted if "error" in r or "done" not in r),
                "queue_mean_first_half": sum(queue[:half]) / max(1, half),
                "queue_mean_second_half": sum(queue[half:]) / max(1, len(queue) - half),
                "queue_max": max(queue or [0]),
                "slots_active_mean": sum(active) / max(1, len(active)),
                "slots_full_share": sum(1 for a in active if a >= slots) / max(1, len(active)),
                "out_tok_s": measure.out_tok_s(ctx),
                "ttft_ms_p50": measure.percentile(measure.ttft_ms(ctx), 50),
                "ttft_ms_p95": measure.percentile(measure.ttft_ms(ctx), 95),
                "tpot_ms_p50": measure.percentile(measure.tpot_ms(ctx), 50),
            }
            if series:
                bins = [0] * (int(2 * seconds) + 1)
                for record in ctx["requests"]:
                    for at, tokens in record.get("frames", []):
                        spot = int(2 * (at - drive.window["opens"]))
                        if 0 <= spot < len(bins):
                            bins[spot] += tokens
                line["tokens_per_half_second"] = bins
            print(json.dumps(line), flush=True)
            if out:
                with open(out, "a") as handle:
                    handle.write(json.dumps(line) + "\n")
            await _drained(served.engine)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--vary", required=True, help="key=v1,v2,...")
    parser.add_argument("--set", action="append", default=[], help="key=value")
    parser.add_argument("--seeds", default="1")
    parser.add_argument("--seconds", type=float, default=18.0)
    parser.add_argument("--series", type=int, default=0)
    parser.add_argument("--out", default="")
    args = parser.parse_args()
    from benchmark import harness

    cache_dir = bench_run.place_compile_cache(args.workload)
    cell = harness.load_cell(args.workload)
    device = harness.require_tpu(cell["chips"])
    fixed = {k: _value(v) for k, v in (item.split("=", 1) for item in args.set)}
    key, _, values = args.vary.partition("=")
    episodes = [
        (dict(fixed, **{key: _value(value)}), int(seed))
        for value in values.split(",") for seed in args.seeds.split(",")
    ]
    asyncio.run(sweep(
        cell, episodes, args.seconds, device, cache_dir, args.out, bool(args.series),
    ))
    return 0


if __name__ == "__main__":
    sys.exit(main())
