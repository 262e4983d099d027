"""``langstream-tpu`` CLI.

Reference parity (``langstream-cli/src/main/java/ai/langstream/cli/commands/RootCmd.java:38``):

- ``apps run <dir>``     — the ``langstream docker run`` local path
  (``docker/LocalRunApplicationCmd.java:56``): run the whole app in-process
  with the in-memory broker + gateway.
- ``apps plan <dir>``    — print the compiled execution plan.
- ``gateway chat|produce|consume`` — WebSocket client commands
  (``gateway/ChatGatewayCmd.java:39``).
- ``docs``               — agent-type documentation listing.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import uuid
from typing import List, Optional


def _parse_params(values: List[str]) -> dict:
    out = {}
    for item in values or []:
        if "=" not in item:
            raise SystemExit(f"bad parameter {item!r}: expected name=value")
        name, _, value = item.partition("=")
        out[name] = value
    return out


# ---------------------------------------------------------------------- #
# apps
# ---------------------------------------------------------------------- #
async def _apps_run(args, ui: bool = False) -> None:
    from langstream_tpu.gateway import GatewayServer
    from langstream_tpu.runtime.local import run_application

    runner = await run_application(
        args.app_dir, instance_file=args.instance, secrets_file=args.secrets
    )
    print(f"application {runner.application.application_id} running:")
    for node in runner.plan.agents:
        print(
            f"  agent {node.id}: {node.input_topic or '(source)'} -> "
            f"{node.output_topic or '(sink)'}"
        )
    http = None
    if getattr(args, "http_port", -1) >= 0:
        from langstream_tpu.runtime.pod import AgentHttpServer

        def _engine_module():
            import sys

            return sys.modules.get(
                "langstream_tpu.providers.jax_local.engine"
            )

        http = AgentHttpServer(
            info=runner.info,
            metrics=runner.metrics,
            gauges=lambda: (
                _engine_module().engines_snapshot()
                if _engine_module() else {}
            ),
            histograms=lambda: (
                _engine_module().engines_histograms()
                if _engine_module() else {}
            ),
            port=args.http_port,
            host="127.0.0.1",
        )
        try:
            await http.start()
            http.ready = True
            print(f"metrics on http://127.0.0.1:{http.port}/metrics")
        except OSError as error:
            print(f"(metrics server disabled: {error})")
            http = None
    gateway = None
    if runner.application.gateways:
        gateway = GatewayServer(port=args.gateway_port)
        gateway.register_local_runner(runner, tenant=args.tenant)
        await gateway.start()
        print(f"gateway on ws://127.0.0.1:{args.gateway_port}/v1/...")
        ui_url = (
            f"http://127.0.0.1:{args.gateway_port}/ui/{args.tenant}/"
            f"{runner.application.application_id}"
        )
        print(f"ui: {ui_url}")
        if ui:
            import webbrowser

            try:
                webbrowser.open(ui_url)
            except Exception:  # noqa: BLE001 — headless is fine
                pass
    elif ui:
        print("no gateways declared; the UI needs at least one")
    try:
        await runner.join()
    except KeyboardInterrupt:
        pass
    finally:
        if gateway is not None:
            await gateway.stop()
        if http is not None:
            await http.stop()
        await runner.stop()


def _apps_plan(args) -> None:
    from langstream_tpu.compiler import build_application, build_execution_plan

    application = build_application(
        args.app_dir, instance_file=args.instance, secrets_file=args.secrets
    )
    plan = build_execution_plan(application)
    out = {
        "topics": {
            name: {"partitions": t.partitions, "implicit": t.implicit}
            for name, t in plan.topics.items()
        },
        "agents": [
            {
                "id": node.id,
                "input": node.input_topic,
                "output": node.output_topic,
                "source": node.source.agent_type if node.source else None,
                "processors": [p.agent_type for p in node.processors],
                "sink": node.sink.agent_type if node.sink else None,
                "service": node.service.agent_type if node.service else None,
                "parallelism": node.resources.parallelism,
            }
            for node in plan.agents
        ],
        "gateways": [g.id for g in application.gateways],
    }
    print(json.dumps(out, indent=2))


# ---------------------------------------------------------------------- #
# gateway client
# ---------------------------------------------------------------------- #
def _gateway_url(args, kind: str) -> str:
    base = args.url.rstrip("/")
    url = f"{base}/v1/{kind}/{args.tenant}/{args.application}/{args.gateway}"
    query = [f"param:{k}={v}" for k, v in _parse_params(args.param).items()]
    if args.credentials:
        query.append(f"credentials={args.credentials}")
    if query:
        url += "?" + "&".join(query)
    return url


async def _gateway_chat(args) -> None:
    import websockets

    session = args.session or uuid.uuid4().hex
    if not any(p.startswith("session-id=") for p in (args.param or [])):
        args.param = (args.param or []) + [f"session-id={session}"]
    url = _gateway_url(args, "chat")
    print(f"connected to {url}")
    async with websockets.connect(url) as ws:

        async def reader():
            async for frame in ws:
                message = json.loads(frame)
                record = message.get("record", {})
                value = record.get("value")
                headers = record.get("headers", {})
                if headers.get("stream-last-message") == "true":
                    print(f"\n< {value}" if value else "")
                elif headers.get("stream-index"):
                    print(value, end="", flush=True)
                else:
                    print(f"< {value}")

        reader_task = asyncio.ensure_future(reader())
        loop = asyncio.get_running_loop()
        try:
            while True:
                line = await loop.run_in_executor(None, sys.stdin.readline)
                if not line:
                    break
                await ws.send(json.dumps({"value": line.strip()}))
        finally:
            reader_task.cancel()


async def _gateway_produce(args) -> None:
    import websockets

    url = _gateway_url(args, "produce")
    async with websockets.connect(url) as ws:
        await ws.send(
            json.dumps({"key": args.key, "value": args.value, "headers": {}})
        )
        print(await ws.recv())


async def _gateway_consume(args) -> None:
    import websockets

    url = _gateway_url(args, "consume")
    if args.position:
        url += ("&" if "?" in url else "?") + f"option:position={args.position}"
    async with websockets.connect(url) as ws:
        async for frame in ws:
            print(frame)


# ---------------------------------------------------------------------- #
# control-plane commands (reference: RootCmd.java:38 apps/tenants/profiles)
# ---------------------------------------------------------------------- #
def _admin(args):
    from langstream_tpu.admin.client import client_from_profile

    return client_from_profile(
        getattr(args, "profile", None),
        url=getattr(args, "api_url", None),
        tenant=getattr(args, "cp_tenant", None),
        token=getattr(args, "token", None),
    )


def _print_json(doc) -> None:
    print(json.dumps(doc, indent=2))


async def _apps_deploy(args, update: bool) -> None:
    client = _admin(args)
    instance_yaml = secrets_yaml = None
    if args.instance:
        with open(args.instance) as handle:
            instance_yaml = handle.read()
    if args.secrets:
        with open(args.secrets) as handle:
            secrets_yaml = handle.read()
    result = await client.deploy_application_directory(
        args.app_id, args.app_dir,
        instance_yaml=instance_yaml, secrets_yaml=secrets_yaml,
        update=update, dry_run=args.dry_run,
    )
    _print_json(result)


async def _apps_get(args) -> None:
    _print_json(await _admin(args).get_application(args.app_id))


async def _apps_list(args) -> None:
    _print_json(await _admin(args).list_applications())


async def _apps_delete(args) -> None:
    _print_json(await _admin(args).delete_application(args.app_id))


async def _apps_logs(args) -> None:
    print(await _admin(args).get_logs(args.app_id), end="")


async def _apps_download(args) -> None:
    data = await _admin(args).download_code(args.app_id)
    target = args.output or f"{args.app_id}.zip"
    with open(target, "wb") as handle:
        handle.write(data)
    print(f"wrote {len(data)} bytes to {target}")


async def _archetypes_cmd(args) -> None:
    client = _admin(args)
    if args.archetypes_command == "list":
        _print_json(await client.list_archetypes())
    elif args.archetypes_command == "get":
        _print_json(await client.get_archetype(args.archetype_id))
    elif args.archetypes_command == "deploy":
        _print_json(await client.deploy_from_archetype(
            args.archetype_id, args.app_id, _parse_params(args.param)
        ))


async def _tenants_cmd(args) -> None:
    client = _admin(args)
    if args.tenants_command == "list":
        _print_json(await client.list_tenants())
    elif args.tenants_command == "get":
        _print_json(await client.get_tenant(args.name))
    elif args.tenants_command in ("put", "create"):
        _print_json(await client.put_tenant(args.name))
    elif args.tenants_command == "delete":
        _print_json(await client.delete_tenant(args.name))


def _profiles_cmd(args) -> None:
    from langstream_tpu.admin.client import load_profiles, save_profiles

    config = load_profiles()
    if args.profiles_command == "list":
        _print_json({
            "current": config.get("current"),
            "profiles": config.get("profiles", {}),
        })
    elif args.profiles_command == "create" or args.profiles_command == "update":
        # update merges: omitted flags keep their stored values
        existing = config.get("profiles", {}).get(args.name, {})
        profile = dict(existing) if args.profiles_command == "update" else {}
        if args.api_url:
            profile["webServiceUrl"] = args.api_url
        if args.cp_tenant:
            profile["tenant"] = args.cp_tenant
        elif "tenant" not in profile:
            profile["tenant"] = "default"
        if args.token:
            profile["token"] = args.token
        config.setdefault("profiles", {})[args.name] = profile
        if args.set_current or config.get("current") is None:
            config["current"] = args.name
        save_profiles(config)
        print(f"profile {args.name} saved")
    elif args.profiles_command == "get":
        profile = config.get("profiles", {}).get(args.name)
        if profile is None:
            raise SystemExit(f"unknown profile {args.name!r}")
        _print_json({args.name: profile})
    elif args.profiles_command == "delete":
        config.get("profiles", {}).pop(args.name, None)
        if config.get("current") == args.name:
            config["current"] = None
        save_profiles(config)
        print(f"profile {args.name} deleted")
    elif args.profiles_command == "set-current":
        if args.name not in config.get("profiles", {}):
            raise SystemExit(f"unknown profile {args.name!r}")
        config["current"] = args.name
        save_profiles(config)
        print(f"current profile: {args.name}")


# ---------------------------------------------------------------------- #
# broker
# ---------------------------------------------------------------------- #
async def _broker_serve(args) -> None:
    from langstream_tpu.topics.log.server import serve

    server = await serve(args.directory, host=args.host, port=args.port)
    print(f"tpulog broker serving {args.directory} on {server.address}")
    try:
        await asyncio.Event().wait()
    finally:
        await server.close()


# ---------------------------------------------------------------------- #
# observability: trace merge + live engine top
# ---------------------------------------------------------------------- #
def _trace_cmd(args) -> None:
    """Merge per-pod Chrome-trace dumps (LANGSTREAM_TRACE_DIR) into one
    Perfetto-loadable timeline, optionally filtered to one trace id."""
    from langstream_tpu.runtime.tracing import run_trace_merge

    for line in run_trace_merge(
        args.paths, output=args.output, trace_id=args.trace_id,
        list_ids=args.list,
    ):
        print(line)


def _journey_cmd(args) -> None:
    """Join fleet-wide flight-recorder artifacts by trace id into
    per-request journey waterfalls, per-stage percentiles, and SLO
    blame tables (docs/observability.md, "Request journeys")."""
    from langstream_tpu.runtime.journey import run_journey

    for line in run_journey(
        args.paths, trace_id=args.trace_id,
        slo_ttft_ms=args.slo_ttft_ms, slo_tpot_ms=args.slo_tpot_ms,
        as_json=args.json, waterfalls=args.waterfalls,
    ):
        print(line)


async def _profile_cmd(args) -> None:
    """Trigger an on-demand profiler capture on a serving process via
    its guarded ``/debug/profile`` endpoint (runner pod :8080, serve
    :8000) and print the artifact directory."""
    import aiohttp

    url = args.url.rstrip("/")
    if not url.endswith("/debug/profile"):
        url += "/debug/profile"
    timeout = aiohttp.ClientTimeout(total=args.seconds + 60)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        async with session.get(
            url, params={"seconds": args.seconds}
        ) as response:
            if response.status == 409:
                raise SystemExit(
                    "capture already in progress on the target "
                    "(one at a time); retry when it finishes"
                )
            if response.status != 200:
                # body may be anything (a proxy's HTML, an older
                # server's 404 text) — report it raw, don't parse it
                raise SystemExit(
                    f"capture failed ({response.status}): "
                    f"{(await response.text())[:300]}"
                )
            body = await response.json(content_type=None)
    print(f"profile ({args.seconds:.0f}s) -> {body['path']}")
    print("  inspect with TensorBoard's profile plugin or xprof; "
          "device_memory.json holds the HBM snapshot")


async def _top_cmd(args) -> None:
    """Poll a /metrics endpoint and render a live engine table
    (occupancy, step time, token throughput from poll deltas) plus an
    SLO panel (TTFT/TPOT percentiles vs targets, burn rates) when the
    target exports SLO gauges."""
    import time as _time

    import aiohttp

    from langstream_tpu.api.metrics import (
        parse_prometheus_text,
        quantile_from_buckets,
    )

    previous_tokens: Optional[float] = None
    previous_at: Optional[float] = None
    iteration = 0
    async with aiohttp.ClientSession(
        timeout=aiohttp.ClientTimeout(total=5)
    ) as session:
        while True:
            iteration += 1
            try:
                async with session.get(args.url) as response:
                    text = await response.text()
                metrics = parse_prometheus_text(text)
            except (
                aiohttp.ClientError, asyncio.TimeoutError, ValueError,
            ) as error:
                print(f"[{args.url}] scrape failed: {error}")
                metrics = None
            if metrics is not None:

                def gauge(name: str, default: float = 0.0) -> float:
                    samples = metrics.get(name)
                    return samples[0][1] if samples else default

                now = _time.monotonic()
                tokens = gauge("jax_engine_tokens_generated")
                tok_s = 0.0
                if previous_at is not None and now > previous_at:
                    tok_s = max(0.0, tokens - (previous_tokens or 0.0)) / (
                        now - previous_at
                    )
                previous_tokens, previous_at = tokens, now
                p50 = quantile_from_buckets(
                    metrics.get(
                        "jax_engine_decode_step_seconds_bucket", []
                    ),
                    0.5,
                )
                rows = [
                    ("slot occupancy",
                     f"{gauge('jax_engine_slot_occupancy'):7.1%}"),
                    ("decode ms/step (mean)",
                     f"{gauge('jax_engine_decode_ms_per_step'):9.2f}"),
                    ("decode ms/step (p50 interp)",
                     "      n/a" if p50 is None else f"{p50 * 1e3:9.2f}"),
                    ("output tok/s (poll delta)", f"{tok_s:9.1f}"),
                    ("tokens generated", f"{tokens:9.0f}"),
                    ("decode steps",
                     f"{gauge('jax_engine_decode_steps'):9.0f}"),
                    ("prefix KV rows reused",
                     f"{gauge('jax_engine_prefix_tokens_reused'):9.0f}"),
                    ("session hits",
                     f"{gauge('jax_engine_session_hits'):9.0f}"),
                ]
                if "jax_engine_mfu" in metrics:
                    rows.append(("MFU / MBU (roofline)",
                                 f"{gauge('jax_engine_mfu'):7.1%} / "
                                 f"{gauge('jax_engine_mbu'):5.1%}"))
                if "jax_engine_goodput_ratio" in metrics:
                    rows.append(("goodput (useful/total tokens)",
                                 f"{gauge('jax_engine_goodput_ratio'):7.1%}"))
                if "spec_tokens_drafted_total" in metrics:
                    # speculative decoding: drafted vs verify-accepted
                    # candidates — a collapsed rate means the workload
                    # has no self-repetition for the drafter to mine
                    rows.append((
                        "spec accept (drafted tokens)",
                        f"{gauge('spec_acceptance_rate'):7.1%} "
                        f"({gauge('spec_tokens_drafted_total'):.0f})",
                    ))
                stamp = _time.strftime("%H:%M:%S")
                print(f"-- langstream-tpu top  {args.url}  {stamp} --")
                if tokens or gauge("jax_engine_decode_steps"):
                    for label, value in rows:
                        print(f"  {label:28s} {value}")
                else:
                    print("  engine idle (no decode activity yet)")
                # SLO panel: measured percentiles (interpolated from the
                # exported buckets) against the configured targets, plus
                # the multi-window burn rates the engine derives from
                # the same histograms
                slo_rows = []
                for key, label in (("ttft", "TTFT"), ("tpot", "TPOT")):
                    target = metrics.get(
                        f"jax_engine_slo_{key}_p95_target_ms"
                    )
                    if not target:
                        continue
                    target_ms = target[0][1]
                    p95 = quantile_from_buckets(
                        metrics.get(
                            f"jax_engine_{key}_seconds_bucket", []
                        ),
                        0.95,
                    )
                    p95_ms = None if p95 is None else p95 * 1e3

                    def burn(window: str) -> str:
                        # absent gauge = no sample landed in the window
                        # yet — render n/a, NOT a perfect-looking 0.00x
                        sample = metrics.get(
                            f"jax_engine_slo_{key}_burn_rate_{window}"
                        )
                        return (
                            f"{sample[0][1]:5.2f}x" if sample
                            else "  n/a"
                        )

                    status = (
                        "  n/a" if p95_ms is None
                        else ("BREACH" if p95_ms > target_ms else "ok")
                    )
                    measured = (
                        "     n/a" if p95_ms is None else f"{p95_ms:8.1f}"
                    )
                    # honest labeling: the p95 (and its ok/BREACH) is
                    # computed from lifetime-cumulative buckets — a past
                    # breach lingers there; the burn rates are the
                    # windowed "is it breaching NOW" signal
                    slo_rows.append(
                        f"  {label} p95(life) {measured} ms  "
                        f"(target {target_ms:7.1f} ms)  "
                        f"burn 5m {burn('5m')} / 1h {burn('1h')}  "
                        f"[{status}]"
                    )
                if slo_rows:
                    print("  -- SLO --")
                    for row in slo_rows:
                        print(row)
                # journey stage panel: per-stage latency histograms
                # from the request-journey ledger — rendered only for
                # stages that have observed at least one sample
                stage_rows = []
                for stage in (
                    "route", "queue", "admit", "prefill",
                    "handoff_export", "handoff_transit",
                    "handoff_import", "decode", "finish",
                ):
                    base = f"jax_engine_journey_{stage}_seconds"
                    count_samples = metrics.get(f"{base}_count")
                    if not count_samples or not count_samples[0][1]:
                        continue
                    count = count_samples[0][1]
                    buckets = metrics.get(f"{base}_bucket", [])
                    p50s = quantile_from_buckets(buckets, 0.5)
                    p95s = quantile_from_buckets(buckets, 0.95)
                    sum_samples = metrics.get(f"{base}_sum")
                    total = sum_samples[0][1] if sum_samples else 0.0

                    def ms(value: Optional[float]) -> str:
                        return (
                            "     n/a" if value is None
                            else f"{value * 1e3:8.1f}"
                        )

                    stage_rows.append(
                        f"    {stage:16s} n={count:6.0f}  "
                        f"p50 {ms(p50s)} ms  p95 {ms(p95s)} ms  "
                        f"total {total:8.2f} s"
                    )
                if stage_rows:
                    print("  -- journey stages --")
                    for row in stage_rows:
                        print(row)
                # fleet panel: rendered when the target serves fleet
                # gauges (a gateway with a registered FleetRouter /
                # FleetController) — per-replica queue depth + state,
                # the affinity hit rate, and current/target replicas
                if "fleet_replicas_current" in metrics or (
                    "fleet_replica_queue_depth" in metrics
                ):
                    # a bare FleetRouter (no controller) exports only
                    # the known-replica count
                    current = gauge(
                        "fleet_replicas_current",
                        gauge("fleet_replicas_known"),
                    )
                    target_samples = metrics.get("fleet_replicas_target")
                    target = (
                        f"{target_samples[0][1]:.0f}" if target_samples
                        else "n/a"
                    )
                    print(
                        f"  -- fleet --  replicas {current:.0f} "
                        f"(target {target}, "
                        f"routable {gauge('fleet_replicas_routable'):.0f})"
                    )
                    if "fleet_affinity_hit_rate" in metrics:
                        routed = {
                            labels.get("policy", "?"): value
                            for labels, value in metrics.get(
                                "fleet_routed_total", []
                            )
                        }
                        routed_txt = " ".join(
                            f"{policy}={count:.0f}"
                            for policy, count in sorted(routed.items())
                            if count
                        )
                        print(
                            f"  affinity hit rate "
                            f"{gauge('fleet_affinity_hit_rate'):7.1%}  "
                            f"(prefix tokens matched "
                            f"{gauge('fleet_prefix_match_tokens_total'):.0f}"
                            f"; routed {routed_txt or '0'})"
                        )
                    states = {
                        labels.get("replica", "?"): labels.get("state", "?")
                        for labels, value in metrics.get(
                            "fleet_replica_state", []
                        )
                        if value
                    }
                    for labels, depth in sorted(
                        metrics.get("fleet_replica_queue_depth", []),
                        key=lambda s: s[0].get("replica", ""),
                    ):
                        replica = labels.get("replica", "?")
                        state = states.get(replica, "?")
                        print(
                            f"    {replica:20s} queue {depth:5.0f}  "
                            f"[{state}]"
                        )
            if args.count and iteration >= args.count:
                break
            await asyncio.sleep(args.interval)


# ---------------------------------------------------------------------- #
# docs
# ---------------------------------------------------------------------- #
def _docs(args) -> None:
    import json as _json

    from langstream_tpu.model.docs import all_docs, generate_docs_model, get_doc
    from langstream_tpu.runtime.registry import _ensure_builtin_loaded

    _ensure_builtin_loaded()
    agent_type = getattr(args, "agent_type", None)
    as_json = getattr(args, "json", False)
    if agent_type:
        doc = get_doc(agent_type)
        if doc is None:
            raise SystemExit(f"no documentation for agent type {agent_type!r}")
        if as_json:
            print(_json.dumps(doc.to_dict(), indent=2))
            return
        print(f"{doc.agent_type} ({doc.category})")
        print(f"  {doc.description}")
        for prop in doc.properties:
            req = " (required)" if prop.required else ""
            default = f" [default: {prop.default}]" if prop.default is not None else ""
            print(f"  - {prop.name}: {prop.type}{req}{default}")
            if prop.description:
                print(f"      {prop.description}")
            if prop.choices:
                print(f"      choices: {', '.join(prop.choices)}")
        return
    if as_json:
        print(_json.dumps(generate_docs_model(), indent=2))
        return
    print("agent types (docs <type> for details):")
    for name, doc in sorted(all_docs().items()):
        print(f"  {name:28s} {doc.category:10s} {doc.description}")


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog="langstream-tpu")
    sub = parser.add_subparsers(dest="command", required=True)

    def add_admin_flags(cmd) -> None:
        cmd.add_argument("--api-url", default=None,
                         help="control-plane URL (or LANGSTREAM_API_URL)")
        cmd.add_argument("--cp-tenant", default=None,
                         help="control-plane tenant (default from profile)")
        cmd.add_argument("--token", default=None)
        cmd.add_argument("--profile", default=None)

    apps = sub.add_parser("apps", help="application commands")
    apps_sub = apps.add_subparsers(dest="apps_command", required=True)
    for name in ("run", "plan", "ui"):
        cmd = apps_sub.add_parser(
            name,
            help="run the app locally and open the web UI"
            if name == "ui" else None,
        )
        cmd.add_argument("app_dir")
        cmd.add_argument("-i", "--instance", default=None)
        cmd.add_argument("-s", "--secrets", default=None)
        if name in ("run", "ui"):
            cmd.add_argument("--gateway-port", type=int, default=8091)
            cmd.add_argument("--tenant", default="default")
            cmd.add_argument(
                "--http-port", type=int, default=8080,
                help="/info + /metrics port (-1 disables)",
            )
    # control-plane application commands (reference: apps deploy/update/...)
    for name in ("deploy", "update"):
        cmd = apps_sub.add_parser(name, help=f"{name} via the control plane")
        cmd.add_argument("app_id")
        cmd.add_argument("app_dir")
        cmd.add_argument("-i", "--instance", default=None)
        cmd.add_argument("-s", "--secrets", default=None)
        cmd.add_argument("--dry-run", action="store_true")
        add_admin_flags(cmd)
    for name in ("get", "delete", "logs"):
        cmd = apps_sub.add_parser(name)
        cmd.add_argument("app_id")
        add_admin_flags(cmd)
    cmd = apps_sub.add_parser("list")
    add_admin_flags(cmd)
    cmd = apps_sub.add_parser("download", help="download the app's code zip")
    cmd.add_argument("app_id")
    cmd.add_argument("-o", "--output", default=None)
    add_admin_flags(cmd)

    archetypes = sub.add_parser("archetypes", help="application archetypes")
    archetypes_sub = archetypes.add_subparsers(
        dest="archetypes_command", required=True
    )
    cmd = archetypes_sub.add_parser("list")
    add_admin_flags(cmd)
    cmd = archetypes_sub.add_parser("get")
    cmd.add_argument("archetype_id")
    add_admin_flags(cmd)
    cmd = archetypes_sub.add_parser(
        "deploy", help="deploy an app from an archetype"
    )
    cmd.add_argument("archetype_id")
    cmd.add_argument("app_id")
    cmd.add_argument("-p", "--param", action="append", default=[],
                     help="archetype parameter name=value")
    add_admin_flags(cmd)

    tenants = sub.add_parser("tenants", help="tenant administration")
    tenants_sub = tenants.add_subparsers(dest="tenants_command", required=True)
    for name in ("list", "get", "put", "create", "delete"):
        cmd = tenants_sub.add_parser(name)
        if name != "list":
            cmd.add_argument("name")
        add_admin_flags(cmd)

    profiles = sub.add_parser("profiles", help="control-plane profiles")
    profiles_sub = profiles.add_subparsers(
        dest="profiles_command", required=True
    )
    for name in ("create", "update"):
        cmd = profiles_sub.add_parser(name)
        cmd.add_argument("name")
        cmd.add_argument("--api-url", required=name == "create")
        cmd.add_argument("--cp-tenant", default=None)
        cmd.add_argument("--token", default=None)
        cmd.add_argument("--set-current", action="store_true")
    for name in ("get", "delete", "set-current"):
        cmd = profiles_sub.add_parser(name)
        cmd.add_argument("name")
    profiles_sub.add_parser("list")

    gateway = sub.add_parser("gateway", help="gateway client commands")
    gateway_sub = gateway.add_subparsers(dest="gateway_command", required=True)
    for name in ("chat", "produce", "consume"):
        cmd = gateway_sub.add_parser(name)
        cmd.add_argument("-u", "--url", default="ws://127.0.0.1:8091")
        cmd.add_argument("-t", "--tenant", default="default")
        cmd.add_argument("-a", "--application", required=True)
        cmd.add_argument("-g", "--gateway", required=True)
        cmd.add_argument("-p", "--param", action="append", default=[])
        cmd.add_argument("--credentials", default=None)
        if name == "chat":
            cmd.add_argument("--session", default=None)
        if name == "produce":
            cmd.add_argument("-k", "--key", default=None)
            cmd.add_argument("-v", "--value", required=True)
        if name == "consume":
            cmd.add_argument("--position", default=None)

    broker = sub.add_parser("broker", help="serve a durable tpulog broker")
    broker.add_argument("directory", help="broker data directory")
    broker.add_argument("--host", default="127.0.0.1")
    broker.add_argument("--port", type=int, default=4551)

    docs = sub.add_parser("docs", help="agent-type documentation")
    docs.add_argument("agent_type", nargs="?", help="show one agent's docs")
    docs.add_argument("--json", action="store_true", help="emit the JSON doc model")

    trace = sub.add_parser(
        "trace",
        help="merge per-pod Chrome-trace dumps (LANGSTREAM_TRACE_DIR) "
             "into one Perfetto timeline",
    )
    trace.add_argument(
        "paths", nargs="+",
        help="trace dump files and/or directories of *.json dumps",
    )
    trace.add_argument("-o", "--output", default="merged_trace.json")
    trace.add_argument(
        "--trace-id", default=None,
        help="keep only spans of this request (langstream-trace-id)",
    )
    trace.add_argument(
        "--list", action="store_true",
        help="list trace ids and the components each one crossed",
    )

    journey = sub.add_parser(
        "journey",
        help="join fleet-wide flight artifacts (LANGSTREAM_FLIGHT_DIR) "
             "by trace id into per-request waterfalls, per-stage "
             "p50/p95, and SLO blame",
    )
    journey.add_argument(
        "paths", nargs="+",
        help="flight_*.jsonl artifacts and/or directories of them "
             "(pass every replica's artifact dir to join "
             "cross-replica journeys)",
    )
    journey.add_argument(
        "--trace-id", default=None,
        help="render the full stage waterfall of one request",
    )
    journey.add_argument(
        "--slo-ttft-ms", type=float, default=0.0,
        help="TTFT SLO for blame attribution (0 = no TTFT blame)",
    )
    journey.add_argument(
        "--slo-tpot-ms", type=float, default=0.0,
        help="per-token TPOT SLO for blame attribution "
             "(0 = no TPOT blame)",
    )
    journey.add_argument(
        "--waterfalls", type=int, default=3,
        help="how many slowest-request waterfalls to render",
    )
    journey.add_argument(
        "--json", action="store_true",
        help="emit the joined journeys as JSON instead of tables",
    )

    top = sub.add_parser(
        "top",
        help="poll a /metrics endpoint and render a live engine "
             "occupancy/step-time table",
    )
    top.add_argument(
        "url", nargs="?", default="http://127.0.0.1:8000/metrics",
        help="scrape URL (runner pod :8080, serve :8000, gateway :8091)",
    )
    top.add_argument("--interval", type=float, default=2.0)
    top.add_argument(
        "--count", type=int, default=0,
        help="stop after N polls (0 = until interrupted)",
    )

    check = sub.add_parser(
        "check",
        help="static analysis over the runtime: lock-discipline + "
             "jit-hazard AST passes and the compiled-HLO invariant "
             "matrix; non-zero exit on unsuppressed findings "
             "(docs/analysis.md)",
    )
    from langstream_tpu.analysis.check import build_parser as _check_parser

    _check_parser(check)

    profile = sub.add_parser(
        "profile",
        help="trigger an on-demand device-profiler capture on a serving "
             "process (guarded /debug/profile endpoint; one at a time)",
    )
    profile.add_argument(
        "url", nargs="?", default="http://127.0.0.1:8000",
        help="server base URL (runner pod :8080, serve :8000) or the "
             "full /debug/profile URL",
    )
    profile.add_argument(
        "--seconds", type=float, default=3.0,
        help="capture window (everything the devices run in it lands "
             "in the trace)",
    )

    # pod entry points (invoked by the deployer's generated manifests;
    # reference: AgentRunnerStarter.java:39, RuntimeDeployer.java:40,
    # ApplicationSetupRunner.java:40)
    runner = sub.add_parser(
        "agent-runner", help="run one plan node from a mounted pod config"
    )
    runner.add_argument("--config", required=True,
                        help="path to pod-configuration.json")
    runner.add_argument("--http-port", type=int, default=8080,
                        help="/info + /metrics port (0 = kernel-assigned)")

    download = sub.add_parser(
        "code-download", help="fetch the app code archive (init container)"
    )
    download.add_argument("--config", required=True)
    download.add_argument("--target", required=True)

    setup = sub.add_parser(
        "application-setup", help="create topics + assets (setup Job)"
    )
    setup.add_argument("--delete", action="store_true")

    deployer = sub.add_parser(
        "deployer", help="build the plan and write Agent CRs (deployer Job)"
    )
    deployer.add_argument("--delete", action="store_true")

    # long-running services (what the helm chart's Deployments invoke)
    cp = sub.add_parser("controlplane", help="run the REST control plane")
    cp.add_argument("--host", default="0.0.0.0")
    cp.add_argument("--port", type=int, default=8090)
    cp.add_argument("--storage-path", default="/var/lib/langstream")
    cp.add_argument("--code-storage", default=None,
                    help="code storage config JSON (default: local-disk)")
    cp.add_argument("--executor", choices=["kubernetes", "local", "none"],
                    default="kubernetes")
    cp.add_argument("--reconcile", action="store_true",
                    help="also run the operator loop in-process")
    cp.add_argument("--image", default="langstream-tpu/runtime:latest")
    cp.add_argument("--auth-token", default=None)
    cp.add_argument("--archetypes", default=None)

    op = sub.add_parser("operator", help="run the reconcile loop")
    op.add_argument("--interval", type=float, default=2.0)
    op.add_argument("--image", default="langstream-tpu/runtime:latest")
    op.add_argument("--code-storage", default=None)

    gws = sub.add_parser("gateway-server", help="serve application gateways")
    gws.add_argument("--host", default="0.0.0.0")
    gws.add_argument("--port", type=int, default=8091)
    gws.add_argument("--sync-interval", type=float, default=5.0)

    serve = sub.add_parser(
        "serve",
        help="OpenAI-compatible HTTP server over the TPU engine "
             "(/v1/chat/completions, /v1/completions, /v1/embeddings)",
    )
    serve.add_argument("--model", default="tiny", help="model preset or name")
    serve.add_argument("--checkpoint", default=None, help="HF/orbax dir")
    serve.add_argument("--tokenizer", default=None, help="HF tokenizer path")
    serve.add_argument("--quantization", default=None, choices=["int8"])
    serve.add_argument("--tp", type=int, default=1, help="tensor parallelism")
    serve.add_argument("--max-slots", type=int, default=8)
    serve.add_argument("--max-seq-len", type=int, default=2048)
    serve.add_argument("--decode-chunk", type=int, default=16)
    serve.add_argument("--precompile", action="store_true")
    # pipelined dispatch hides the host gap between decode chunks;
    # token-identical by test
    serve.add_argument(
        "--no-pipeline-decode", action="store_true",
        help="disable pipelined decode dispatch (on by default)",
    )
    serve.add_argument(
        "--no-prefix-cache", action="store_true",
        help="disable cross-slot prompt-prefix KV reuse (on by default)",
    )
    serve.add_argument(
        "--logprobs-top-k", type=int, default=0,
        help="enable OpenAI top_logprobs up to K alternatives per token "
             "(static — adds a top_k to the serving jits; 0 = off)",
    )
    serve.add_argument(
        "--kv-layout", default="dense", choices=["dense", "paged"],
        help="KV cache layout: dense per-slot regions, or a paged "
             "block pool with a persistent refcounted prefix cache "
             "(docs/perf.md 'KV layouts')",
    )
    serve.add_argument(
        "--kv-block-size", type=int, default=16,
        help="paged layout: tokens per pool block",
    )
    serve.add_argument(
        "--kv-blocks", type=int, default=0,
        help="paged layout: pool size in blocks (0 = the dense-"
             "equivalent worst case, slots x ceil(max_seq/block))",
    )
    serve.add_argument(
        "--kv-host-blocks", type=int, default=0,
        help="paged layout: host-DRAM demotion tier capacity in "
             "blocks (0 = off). Evicted chains demote to pinned host "
             "RAM and promote back on a prefix digest hit instead of "
             "recomputing (docs/perf.md 'KV tiers')",
    )
    serve.add_argument(
        "--paged-kernel", default="fused", choices=["fused", "reference"],
        help="paged attention kernel: fused ragged Pallas launch over "
             "the block tables (default) or the gather/scatter "
             "reference oracle (docs/perf.md 'Ragged paged attention')",
    )
    serve.add_argument(
        "--prefill-mode", default="split", choices=["split", "mixed"],
        help="paged prefill scheduling: split (dedicated bucketed "
             "prefill dispatches) or mixed (token-budget chunked "
             "prefill fused into the decode step — bounds every "
             "dispatch, docs/perf.md 'Chunked prefill & mixed "
             "dispatch')",
    )
    serve.add_argument(
        "--prefill-chunk", type=int, default=64,
        help="mixed prefill mode: max prompt tokens any single decode "
             "step carries",
    )
    serve.add_argument(
        "--mixed-carry", default="on", choices=["on", "off"],
        help="mixed prefill mode: pipeline consecutive mixed steps off "
             "the previous step's device-resident outputs (two-step "
             "window plan — hides the per-step host round trip; "
             "docs/perf.md 'Mixed-step carry')",
    )
    serve.add_argument(
        "--spec-decode", default="off", choices=["off", "ngram"],
        help="speculative decoding: self-drafting prompt-lookup drafts "
             "spec-k tokens per decode step, one batched forward "
             "verifies them (docs/perf.md 'Speculative decoding')",
    )
    serve.add_argument(
        "--spec-k", type=int, default=4,
        help="drafted tokens verified per decode step (spec-decode)",
    )
    serve.add_argument(
        "--spec-ngram", type=int, default=2,
        help="suffix n-gram length the prompt-lookup drafter matches",
    )
    serve.add_argument(
        "--slo-ttft-ms", type=float, default=0,
        help="TTFT p95 SLO target in ms: enables burn-rate gauges on "
             "/metrics and the `top` SLO panel (0 = off)",
    )
    serve.add_argument(
        "--slo-tpot-ms", type=float, default=0,
        help="TPOT p95 SLO target in ms (0 = off)",
    )
    serve.add_argument(
        "--no-watchdog", action="store_true",
        help="disable the decode-stall watchdog (on by default for "
             "serve: EWMA step-latency degradation, no-progress and "
             "KV-pool livelock detection with automatic evidence "
             "capture)",
    )
    serve.add_argument(
        "--no-supervisor", action="store_true",
        help="disable the engine supervisor (on by default: an engine "
             "crash or watchdog escalation snapshots every live "
             "session, rebuilds the engine, and resumes each stream "
             "bitwise — docs/robustness.md)",
    )
    serve.add_argument(
        "--max-restarts", type=int, default=3,
        help="supervisor: engine rebuilds allowed inside the restart "
             "window before giving up (crash-loop circuit breaker)",
    )
    serve.add_argument(
        "--queue-timeout-s", type=float, default=0,
        help="admission deadline: pending requests older than this are "
             "shed with 503 + Retry-After instead of waiting in the "
             "queue forever (0 = off)",
    )
    # fleet membership (langstream_tpu/fleet): role-aware heartbeat
    # gossip over the topic fabric — the router's liveness/affinity/
    # disaggregation view is built ENTIRELY from these beats
    serve.add_argument(
        "--fleet-role", default="unified",
        choices=["unified", "prefill", "decode"],
        help="disaggregation pool this replica serves (gossiped in "
             "every heartbeat; the FleetRouter sends cold prompts to "
             "the prefill pool and pinned handoff continuations to "
             "the decode pool — docs/fleet.md)",
    )
    serve.add_argument(
        "--fleet-gossip", default=None, metavar="JSON",
        help="streaming-cluster config for the heartbeat fabric, e.g. "
             '\'{"type":"kafka","configuration":{...}}\' — when set, '
             "this replica publishes build_heartbeat on a period "
             "(fleet/heartbeat.publish_loop) so routers see it without "
             "scraping",
    )
    serve.add_argument(
        "--fleet-replica-id", default=None,
        help="stable pod identity stamped on heartbeats (default: "
             "$HOSTNAME — the StatefulSet ordinal name on kube)",
    )
    serve.add_argument(
        "--fleet-heartbeat-s", type=float, default=2.0,
        help="heartbeat publish period in seconds",
    )
    serve.add_argument("--embeddings-checkpoint", default=None)
    serve.add_argument("--host", default="0.0.0.0")
    serve.add_argument("--port", type=int, default=8000)
    # multi-host SPMD serving (tp spanning hosts): host 0 serves HTTP
    # and mirrors every dispatch; followers replay the stream on their
    # shard of the global mesh. jax.distributed comes up first either
    # way (runtime/multihost.py plan, or LANGSTREAM_* env on pods).
    serve.add_argument(
        "--followers", type=int, default=0,
        help="leader: number of follower hosts to wait for",
    )
    serve.add_argument(
        "--mirror-port", type=int, default=8477,
        help="leader: port the dispatch mirror listens on",
    )
    serve.add_argument(
        "--follower-of", default=None, metavar="HOST:PORT",
        help="run as a follower replaying the leader's dispatch stream",
    )

    python_cmd = sub.add_parser(
        "python", help="application Python dependency tooling"
    )
    python_sub = python_cmd.add_subparsers(
        dest="python_command", required=True
    )
    deps = python_sub.add_parser(
        "load-deps",
        help="pip-install python/requirements.txt into python/lib "
             "(shipped with the code archive; reference: "
             "langstream python load-pip-requirements)",
    )
    deps.add_argument("app_dir")

    plugins = sub.add_parser("plugins", help="agent plugin packaging")
    plugins_sub = plugins.add_subparsers(dest="plugins_command", required=True)
    pkg = plugins_sub.add_parser(
        "package", help="zip a plugin dir (the NAR-build equivalent)"
    )
    pkg.add_argument("plugin_dir")
    pkg.add_argument("-o", "--output", default=None)
    plugins_sub.add_parser("list", help="show loaded plugins")
    return parser


def main(argv: Optional[List[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    if args.command == "apps" and args.apps_command in ("run", "ui"):
        asyncio.run(_apps_run(args, ui=args.apps_command == "ui"))
    elif args.command == "apps" and args.apps_command == "plan":
        _apps_plan(args)
    elif args.command == "apps" and args.apps_command in ("deploy", "update"):
        asyncio.run(_apps_deploy(args, update=args.apps_command == "update"))
    elif args.command == "apps" and args.apps_command == "get":
        asyncio.run(_apps_get(args))
    elif args.command == "apps" and args.apps_command == "list":
        asyncio.run(_apps_list(args))
    elif args.command == "apps" and args.apps_command == "delete":
        asyncio.run(_apps_delete(args))
    elif args.command == "apps" and args.apps_command == "logs":
        asyncio.run(_apps_logs(args))
    elif args.command == "apps" and args.apps_command == "download":
        asyncio.run(_apps_download(args))
    elif args.command == "archetypes":
        asyncio.run(_archetypes_cmd(args))
    elif args.command == "tenants":
        asyncio.run(_tenants_cmd(args))
    elif args.command == "profiles":
        _profiles_cmd(args)
    elif args.command == "gateway" and args.gateway_command == "chat":
        asyncio.run(_gateway_chat(args))
    elif args.command == "gateway" and args.gateway_command == "produce":
        asyncio.run(_gateway_produce(args))
    elif args.command == "gateway" and args.gateway_command == "consume":
        asyncio.run(_gateway_consume(args))
    elif args.command == "broker":
        asyncio.run(_broker_serve(args))
    elif args.command == "docs":
        _docs(args)
    elif args.command == "trace":
        _trace_cmd(args)
    elif args.command == "journey":
        _journey_cmd(args)
    elif args.command == "top":
        try:
            asyncio.run(_top_cmd(args))
        except KeyboardInterrupt:
            pass
    elif args.command == "check":
        from langstream_tpu.analysis.check import run_check

        raise SystemExit(run_check(args))
    elif args.command == "profile":
        asyncio.run(_profile_cmd(args))
    elif args.command == "agent-runner":
        from langstream_tpu.runtime.pod import agent_runner_main

        asyncio.run(
            agent_runner_main(args.config, http_port=args.http_port)
        )
    elif args.command == "code-download":
        from langstream_tpu.runtime.pod import code_download_main

        code_download_main(args.config, args.target)
    elif args.command == "application-setup":
        from langstream_tpu.runtime.pod import application_setup_main

        asyncio.run(application_setup_main(delete=args.delete))
    elif args.command == "deployer":
        from langstream_tpu.runtime.pod import deployer_main

        asyncio.run(deployer_main(delete=args.delete))
    elif args.command == "controlplane":
        from langstream_tpu.cli.services import controlplane_main

        asyncio.run(controlplane_main(args))
    elif args.command == "operator":
        from langstream_tpu.cli.services import operator_main

        asyncio.run(operator_main(args))
    elif args.command == "gateway-server":
        from langstream_tpu.cli.services import gateway_server_main

        asyncio.run(gateway_server_main(args))
    elif args.command == "serve":
        from langstream_tpu.cli.services import serve_main

        asyncio.run(serve_main(args))
    elif args.command == "python" and args.python_command == "load-deps":
        import os
        import subprocess

        requirements = os.path.join(
            args.app_dir, "python", "requirements.txt"
        )
        target = os.path.join(args.app_dir, "python", "lib")
        if not os.path.isfile(requirements):
            raise SystemExit(f"no {requirements}")
        os.makedirs(target, exist_ok=True)
        subprocess.run(
            [sys.executable, "-m", "pip", "install",
             "--target", target, "--upgrade",
             "-r", requirements],
            check=True,
        )
        print(f"installed {requirements} -> {target}")
    elif args.command == "plugins" and args.plugins_command == "package":
        import os
        import zipfile

        from langstream_tpu.runtime.plugins import load_plugin

        plugin_dir = args.plugin_dir.rstrip("/")
        # validate before packaging: a bad manifest fails at build time
        load_plugin(plugin_dir)
        output = args.output or f"{os.path.basename(plugin_dir)}.zip"
        with zipfile.ZipFile(output, "w", zipfile.ZIP_DEFLATED) as zf:
            for root, _dirs, files in os.walk(plugin_dir):
                for name in files:
                    if name.endswith(".pyc"):
                        continue
                    full = os.path.join(root, name)
                    zf.write(full, os.path.relpath(full, plugin_dir))
        print(f"packaged {plugin_dir} -> {output}")
    elif args.command == "plugins" and args.plugins_command == "list":
        from langstream_tpu.runtime.plugins import loaded_plugins

        _print_json(loaded_plugins())


if __name__ == "__main__":
    main()
