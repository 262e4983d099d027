"""One run of one cell: build the app as ``apps run`` does, serve the
cell's traffic through the gateway's chat WebSocket, measure a window,
compare what the window served with the plain reference, print the line.

Nothing here names a cell, a configuration, a traffic mix or a metric:
``BENCHMARK.json`` names them, and each resolves to a file of its own
(``configs/<config>.json``, ``traffic/<traffic>.json``,
``generators/<kind>.py``, ``metrics/<metric>.py``, ``apps/<app>/``). Nor
does it name a model family: the configuration's file gives its
``model_type``, and ``reference/<model_type>.py`` holds the family's plain
reference, its size check and its work counts (README, "A family").
"""

from __future__ import annotations

import asyncio
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import socket
import sys
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# --------------------------------------------------------------------- #
# resolving names to files
# --------------------------------------------------------------------- #
def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as handle:
        return json.load(handle)


def load_module(folder: str, name: str):
    path = os.path.join(HERE, folder, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: no {folder}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{folder}.{name.replace('.', '_').replace('-', '_')}",
        path, submodule_search_locations=None,
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str, benchmark: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The cell named ``workload`` with its configuration, traffic, the
    configuration's family (``reference/<model_type>.py``) and the metrics
    it reports, each from the file its name resolves to."""
    if benchmark is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as handle:
            benchmark = json.load(handle)
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"benchmark: unknown workload {workload!r}; BENCHMARK.json has "
            f"{sorted(cells)}"
        )
    cell = dict(cells[workload])
    cell["config_file"] = load_json("configs", cell["config"] + ".json")
    cell["traffic_file"] = load_json("traffic", cell["traffic"] + ".json")
    cell["family"] = load_module("reference", cell["config_file"]["model_type"])

    def mine(metric: Dict[str, Any]) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    cell["end_to_end"] = [m for m in benchmark["end_to_end"] if mine(m)]
    reported = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [
        m for m in benchmark["per_layer"]
        if mine(m) and m["moves"] in reported
    ]
    return cell


def peaks_for(device_kind: str) -> Dict[str, float]:
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(
            f"benchmark: no peaks for device kind {device_kind!r} in "
            f"peaks.json (it has {sorted(table)})"
        )
    return table[device_kind]


# --------------------------------------------------------------------- #
# the device
# --------------------------------------------------------------------- #
def require_tpu(chips: int) -> Dict[str, Any]:
    """A TPU with the cell's chips, or no run at all."""
    import jax

    devices = jax.devices()
    record = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if record["platform"] != "tpu":
        raise SystemExit(
            f"benchmark: needs a TPU; JAX reports {record['platform']!r}"
        )
    if record["count"] < chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} chips; JAX reports "
            f"{record['count']}"
        )
    record["count"] = chips
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed and jax.config.jax_compilation_cache_dir != placed:
        raise SystemExit(
            "benchmark: JAX was imported before the compile cache was "
            f"placed (it keeps {jax.config.jax_compilation_cache_dir!r}, "
            f"not {placed!r})"
        )
    return record


def release() -> int:
    """Once the app is stopped and the event loop closed: collect what
    still refers to the engine, so that its weights and cache leave the
    device before the reference makes its own. Returns bytes still held."""
    import jax

    gc.collect()
    held = max(
        (d.memory_stats() or {}).get("bytes_in_use", 0) for d in jax.devices()
    )
    print(f"benchmark: {held / 2**30:.2f} GiB held on the device before the "
          "reference", file=sys.stderr, flush=True)
    return held


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def say(device: Dict[str, Any], message: str) -> None:
    """Every line the run prints names its device."""
    print(
        f"[{device['platform']} {device['kind']} x{device['count']}] {message}",
        file=sys.stderr, flush=True,
    )


# --------------------------------------------------------------------- #
# the program's sizes against the configuration's file
# --------------------------------------------------------------------- #
def check_sizes(family, engine_config, config_file: Dict[str, Any]) -> None:
    """The family says which keys of the configuration's file are held
    against which values of the program's config (``size_check``); any
    difference, or a key the file lacks, ends the run."""
    wrong = {
        key: (config_file.get(key), value)
        for key, value in family.size_check(engine_config).items()
        if key not in config_file or config_file[key] != value
    }
    if wrong:
        raise SystemExit(
            f"benchmark: the program's model differs from the "
            f"configuration's file (file, program): {wrong}"
        )


# --------------------------------------------------------------------- #
# driving the traffic
# --------------------------------------------------------------------- #
class Drive:
    """One run's traffic: the plan's users send through the client, and
    the window opens and closes around them. Which kind of mix it is the
    harness never asks: the generator drives, the traffic file says which
    requests count (``counted_by``), and the wait after the close follows
    from that."""

    def __init__(self, plan, url: Callable[[int], str], traffic, engine) -> None:
        self.plan = plan
        self.url = url
        self.engine = engine
        self.counted_by = traffic["counted_by"]
        self.limit_s = float(traffic["request_limit_seconds"])
        self.records: List[Dict[str, Any]] = plan.requests
        self.window: Dict[str, float] = {}

    def chat(self, session: int):
        from .client import Chat

        return Chat(self.url(session), self.limit_s)

    async def open_window(self) -> None:
        """Start the users, warm up as the mix says (still set-up), and
        return at the window's first instant."""
        from . import probes

        opens = await self.plan.start(self.chat)
        self.window = {"opens": opens, "closes": opens + self.plan.window_seconds}
        self.counters_open = probes.counters(self.engine)

    def _awaited(self) -> List[Dict[str, Any]]:
        """Past the close: every request that counts and has not ended (an
        answer that comes late is late, not lost), and every request the
        close cut between two frames: its next frame (the engine's next
        harvest) carries the tokens made up to the close, whatever the
        harvests' phase."""
        from . import measure

        closes = self.window["closes"]

        def open_still(record) -> bool:
            return "done" not in record and "error" not in record

        counted = measure.counted({
            "requests": self.records, "window": self.window, "counted_by": self.counted_by,
        })
        return [r for r in counted if open_still(r)] + [
            r for r in self.records
            if open_still(r) and r.get("frames") and r["frames"][-1][0] < closes
        ]

    async def close_window(self) -> None:
        """Sleep to the window's close, then wait for what is awaited (a
        minute past the close if need be) and stop the rest."""
        from . import probes

        await asyncio.sleep(max(0.0, self.window["closes"] - time.perf_counter()))
        self.counters_close = probes.counters(self.engine)
        self.plan.stop()
        deadline = self.window["closes"] + self.limit_s + 5
        while self._awaited() and time.perf_counter() < deadline:
            await asyncio.sleep(0.01)
        await self.plan.cancel()


async def wait_for_harvest(engine, limit_s: float = 5.0) -> None:
    """Return just after the engine's next decode harvest (its counter of
    chunks moves), so that counters read now line up with device work."""
    seen = engine.stats["decode_chunks"]
    deadline = time.perf_counter() + limit_s
    while engine.stats["decode_chunks"] == seen and time.perf_counter() < deadline:
        await asyncio.sleep(0.001)


async def traced(engine, trace_dir: str, seconds: float) -> Dict[str, Any]:
    """Trace ``seconds`` of the window with JAX's profiler, started and
    stopped just after a harvest; returns the counters at both ends."""
    import jax

    from . import probes

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the device's ops and our marker only
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    await asyncio.to_thread(
        jax.profiler.start_trace, trace_dir, profiler_options=options
    )
    await wait_for_harvest(engine)
    with jax.profiler.TraceAnnotation("benchmark.mark"):
        begin = probes.counters(engine)
    await asyncio.sleep(seconds)
    await wait_for_harvest(engine)
    end = probes.counters(engine)
    await asyncio.to_thread(jax.profiler.stop_trace)
    return {"dir": trace_dir, "begin": begin, "end": end}


@contextlib.contextmanager
def builds_watched():
    """Yields a list that gains (instant, seconds) whenever JAX builds a
    program or fetches one from its cache: none should inside a window."""
    import jax

    built: List[tuple] = []

    def on_build(event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            built.append((time.perf_counter(), seconds))

    jax.monitoring.register_event_duration_secs_listener(on_build)
    try:
        yield built
    finally:
        jax.monitoring.unregister_event_duration_listener(on_build)


# --------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------- #
class Served:
    """The cell's app, up and answering on the gateway."""

    def __init__(self, runner, completions, engine, base_url, probed, weights_seed):
        self.runner = runner
        self.completions = completions
        self.engine = engine
        self.base_url = base_url
        self.probed = probed
        self.weights_seed = weights_seed

    def drive(self, plan, traffic, tag) -> Drive:
        """``tag`` sets this drive's session ids apart from another's on
        the same app; the probe joins its records to the client's by them."""
        self.probed.clear()
        for record in plan.requests:
            record["session"] = f"s{tag}-{record['session']}"
        return Drive(
            plan, lambda session: f"{self.base_url}?param:session-id={session}",
            traffic, self.engine,
        )


@contextlib.asynccontextmanager
async def serving(cell: Dict[str, Any], seed: int, device, cache_dir: str):
    """Build the cell's app as ``apps run`` does, with weights from the
    seed, put the benchmark's probes around the engine, start a gateway;
    stop all of it on the way out."""
    from langstream_tpu.gateway import GatewayServer
    from langstream_tpu.runtime.local import run_application

    from . import probes
    from .tokenizer import VisibleTokenizer

    config_file = cell["config_file"]
    weights_seed = seed % (2 ** 31 - 1)
    instance = {"instance": {
        "streamingCluster": {"type": "memory"},
        "computeCluster": {"type": "local"},
        "globals": dict(config_file["globals"], seed=weights_seed),
    }}
    os.makedirs(cache_dir, exist_ok=True)
    instance_path = os.path.join(cache_dir, "instance.json")
    with open(instance_path, "w") as handle:
        json.dump(instance, handle)
    app_dir = os.path.join(HERE, "apps", config_file["app"])
    say(device, f"{cell['name']}: building {config_file['app']} (seed {seed})")
    runner = await run_application(app_dir, instance_file=instance_path)
    gateway = None
    try:
        completions = runner._service_provider_registry.completions()  # noqa: SLF001
        engine = completions.engine
        check_sizes(cell["family"], engine.config, config_file)
        completions.tokenizer = VisibleTokenizer()
        probed: Dict[tuple, Dict[str, Any]] = {}
        probes.wrap_engine(engine, probed)
        gateway = GatewayServer(port=_free_port())
        gateway.register_local_runner(runner)
        await gateway.start()
        app_id = runner.application.application_id
        yield Served(
            runner, completions, engine,
            f"ws://127.0.0.1:{gateway.port}/v1/chat/default/{app_id}/chat",
            probed, weights_seed,
        )
    finally:
        if gateway is not None:
            await gateway.stop()
        await runner.stop()


async def run_cell(
    cell: Dict[str, Any],
    seed: int,
    seconds: float,
    trace: bool,
    started: float,
    device: Dict[str, Any],
    cache_dir: str,
) -> Dict[str, Any]:
    """Set-up, warm-up, the window, the wait for what is due; returns the
    raw records once the program's state is stopped and freed."""
    import jax

    traffic = cell["traffic_file"]
    devices = jax.devices()[: cell["chips"]]
    with builds_watched() as built:
        async with serving(cell, seed, device, cache_dir) as served:
            engine = served.engine
            generator = load_module("generators", traffic["kind"])
            plan = generator.plan(traffic, seed, seconds, engine.max_slots)
            drive = served.drive(plan, traffic, seed)
            say(device, f"built in {time.perf_counter() - started:.1f}s (precompile "
                        f"{engine.precompile_stats.get('seconds', 0.0):.1f}s); warming up")
            await drive.open_window()
            setup_s = drive.window["opens"] - started
            say(device, f"window open after {setup_s:.1f}s of set-up")
            trace_info = None
            if trace:
                await asyncio.sleep(min(1.0, seconds / 8))
                trace_info = await traced(
                    engine, os.path.join(cache_dir, "trace"),
                    min(float(traffic["trace_seconds"]), seconds / 2),
                )
            await drive.close_window()
            if served.completions.engine is not engine:
                raise RuntimeError("the supervisor replaced the engine mid-run")
            peak = max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
            )
            for record in drive.records:
                record.update(served.probed.get((record["session"], record["turn"]), {}))
            out = {
                "records": drive.records,
                "window": drive.window,
                "setup_s": setup_s,
                "counters": {"open": drive.counters_open, "close": drive.counters_close},
                "chunk_log": list(engine.chunk_log),
                "trace": trace_info,
                "slots": engine.max_slots,
                "decode_chunk": engine.decode_chunk,
                "memory_peak_bytes": int(peak),
                "weights_seed": served.weights_seed,
                # nothing should compile, or come from the cache, inside the window
                "built_in_window": [
                    took for at, took in built
                    if drive.window["opens"] <= at < drive.window["closes"]
                ],
                "agent_errors": {
                    agent["agent-id"]: agent["stats"]["errors"]
                    for agent in served.runner.info()["agents"] if "stats" in agent
                },
            }
    del served, drive, engine
    gc.collect()
    return out
