"""Long-running service entry points: control plane, operator, gateway.

The reference deploys these as separate images (langstream-webservice,
langstream-k8s-deployer operator, langstream-api-gateway); here they are
subcommands of the one runtime image, which is what the helm chart's
Deployments invoke:

- ``controlplane`` — REST webservice + (optionally) the reconcile loop,
  file-backed stores under ``--storage-path``.
- ``operator``     — standalone reconcile loop against the cluster's API
  server (Application/Agent CRs → StatefulSets).
- ``gateway-server`` — serves every deployed application's gateways,
  discovering apps from Application CRs and connecting to each app's
  own ``streamingCluster``.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import signal
from typing import Any, Dict

logger = logging.getLogger(__name__)


def _install_stop(loop, stop: asyncio.Event) -> None:
    def _signalled() -> None:
        # flush the flight recorder the moment the signal lands: a k8s
        # preStop SIGTERM gives a bounded grace period, and the async
        # teardown below it can be cut short by SIGKILL — the ring's
        # evidence must already be on disk by then (no-op when the
        # recorder is disabled)
        try:
            from langstream_tpu.runtime import flight

            flight.flush()
        except Exception:  # noqa: BLE001 — never block the shutdown path
            pass
        stop.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, _signalled)
        except (NotImplementedError, RuntimeError):
            pass


async def controlplane_main(args) -> None:
    from langstream_tpu.controlplane import (
        ApplicationService,
        FileSystemApplicationStore,
        GlobalMetadataStore,
        TenantService,
    )
    from langstream_tpu.controlplane.codestorage import create_code_storage
    from langstream_tpu.controlplane.webservice import ControlPlaneWebService

    storage = args.storage_path
    os.makedirs(storage, exist_ok=True)
    store = FileSystemApplicationStore(os.path.join(storage, "apps"))
    metadata = GlobalMetadataStore(os.path.join(storage, "metadata.json"))
    tenants = TenantService(metadata)
    if "default" not in {t.name for t in tenants.list()}:
        tenants.create("default")
    code_config = json.loads(args.code_storage) if args.code_storage else {
        "type": "local-disk", "path": os.path.join(storage, "code"),
    }
    code = create_code_storage(code_config)

    executor = None
    operator = None
    if args.executor == "kubernetes":
        from langstream_tpu.deployer.kubeclient import create_kube_api
        from langstream_tpu.deployer.operator import (
            KubernetesExecutor,
            Operator,
        )

        kube = create_kube_api()
        operator = Operator(
            kube, image=args.image, code_storage_config=code_config
        )
        executor = KubernetesExecutor(
            kube, operator if args.reconcile else None
        )
    elif args.executor == "local":
        from langstream_tpu.controlplane.service import LocalExecutor

        executor = LocalExecutor()

    service = ApplicationService(store, code, tenants, executor=executor)
    webservice = ControlPlaneWebService(
        service,
        auth_token=args.auth_token or os.environ.get("LANGSTREAM_AUTH_TOKEN"),
        archetypes_path=args.archetypes,
    )
    port = await webservice.start(args.host, args.port)
    logger.info("control plane on %s:%d (storage %s)", args.host, port, storage)
    print(f"control plane listening on http://{args.host}:{port}", flush=True)

    stop = asyncio.Event()
    _install_stop(asyncio.get_running_loop(), stop)
    tasks = []
    if operator is not None and args.reconcile:
        tasks.append(asyncio.get_running_loop().create_task(
            operator.run(stop=stop)
        ))
    try:
        await stop.wait()
    finally:
        for task in tasks:
            task.cancel()
        await webservice.stop()


async def operator_main(args) -> None:
    from langstream_tpu.deployer.kubeclient import create_kube_api
    from langstream_tpu.deployer.operator import Operator

    code_config = (
        json.loads(args.code_storage) if args.code_storage else {}
    )
    operator = Operator(
        create_kube_api(), image=args.image, code_storage_config=code_config
    )
    stop = asyncio.Event()
    _install_stop(asyncio.get_running_loop(), stop)
    logger.info("operator reconcile loop started (interval %ss)", args.interval)
    print("operator running", flush=True)
    await operator.run(interval=args.interval, stop=stop)


class GatewayAppWatcher:
    """Polls Application CRs and (de)registers them with the gateway,
    each with a topic runtime for its own streamingCluster (reference:
    the api-gateway reads apps through the k8s application store)."""

    def __init__(self, gateway, kube) -> None:
        self.gateway = gateway
        self.kube = kube
        self._registered: Dict[tuple, Any] = {}

    async def sync(self) -> None:
        from langstream_tpu.deployer.crds import ApplicationCustomResource
        from langstream_tpu.model.application import Application
        from langstream_tpu.topics import create_topic_runtime

        seen = set()
        for doc in self.kube.list("Application"):
            cr = ApplicationCustomResource.from_manifest(doc)
            key = (cr.namespace, cr.name)
            seen.add(key)
            if key in self._registered:
                continue
            try:
                application = Application.from_document(
                    cr.application, cr.instance
                )
                application.application_id = cr.name
                application.tenant = cr.namespace
                runtime = create_topic_runtime(
                    application.instance.streaming_cluster
                )
            except Exception:  # noqa: BLE001 — one bad app can't stop sync
                logger.exception("cannot register app %s", key)
                continue
            self.gateway.register(cr.namespace, application, runtime)
            self._registered[key] = runtime
            logger.info("gateway registered %s/%s", *key)
        for key in list(self._registered):
            if key not in seen:
                runtime = self._registered.pop(key)
                self.gateway._apps.pop(key, None)  # noqa: SLF001
                await runtime.close()
                logger.info("gateway unregistered %s/%s", *key)

    async def run(self, stop: asyncio.Event, interval: float = 5.0) -> None:
        while not stop.is_set():
            try:
                await self.sync()
            except Exception:  # noqa: BLE001
                logger.exception("gateway app sync failed")
            try:
                await asyncio.wait_for(stop.wait(), timeout=interval)
            except asyncio.TimeoutError:
                pass


async def gateway_server_main(args) -> None:
    from langstream_tpu.deployer.kubeclient import create_kube_api
    from langstream_tpu.gateway import GatewayServer

    gateway = GatewayServer(host=args.host, port=args.port)
    await gateway.start()
    print(f"gateway listening on ws://{args.host}:{args.port}", flush=True)
    stop = asyncio.Event()
    _install_stop(asyncio.get_running_loop(), stop)
    watcher = GatewayAppWatcher(gateway, create_kube_api())
    try:
        await watcher.run(stop, interval=args.sync_interval)
    finally:
        await gateway.stop()


def _mirror_fingerprint(config: Dict[str, Any]) -> bytes:
    """Leader/follower config digest over the keys that shape the jit
    programs. Observability-only knobs (SLO targets, watchdog) must not
    force flag parity across hosts — a follower has no HTTP surface to
    serve SLOs from."""
    from langstream_tpu.serving.mirror import config_fingerprint

    scrubbed = {k: v for k, v in config.items() if k != "slo"}
    scrubbed["engine"] = {
        k: v for k, v in config.get("engine", {}).items()
        if k != "watchdog"
    }
    return config_fingerprint(scrubbed)


async def serve_main(args) -> None:
    """`langstream-tpu serve`: OpenAI-compatible HTTP server straight
    over the jax-local engine (no pipeline needed) — existing OpenAI
    clients point their base URL at this process."""
    import os

    # flight recorder: ON for every serve run (override the dir with
    # LANGSTREAM_FLIGHT_DIR, disable with LANGSTREAM_FLIGHT_DIR="") — a
    # run that dies at backend init must still leave the init-phase
    # timeline on disk
    import langstream_tpu
    from langstream_tpu.runtime import flight

    # default next to the repo's other bench artifacts when running
    # from a checkout (where tools/ab_analyze.py looks by default);
    # CWD-relative otherwise — never inside an installed site-packages
    repo_root = os.path.dirname(
        os.path.dirname(os.path.abspath(langstream_tpu.__file__))
    )
    default_dir = (
        os.path.join(repo_root, "bench_artifacts", "flight")
        if os.path.isdir(os.path.join(repo_root, "bench_artifacts"))
        else os.path.join("bench_artifacts", "flight")
    )
    flight_dir = os.environ.get("LANGSTREAM_FLIGHT_DIR", default_dir)
    # stamp fleet identity before configure so it rides the artifact's
    # meta record — `langstream-tpu journey` joins per-replica artifacts
    # by trace id and labels each stage with this replica id
    import socket

    flight.set_identity(
        getattr(args, "fleet_replica_id", None)
        or os.environ.get("HOSTNAME")
        or socket.gethostname(),
        getattr(args, "fleet_role", "unified") or "unified",
    )
    if flight_dir:
        path = flight.configure(flight_dir, run_id=f"serve-{args.model}")
        print(f"flight recorder -> {path}", flush=True)
    flight.record("phase", name="backend-init", model=args.model)
    flight.flush()

    # multi-host slice: bring up jax.distributed from StatefulSet/env
    # identity before any device access, so the global mesh spans hosts
    from langstream_tpu.runtime.multihost import initialize_multihost

    initialize_multihost()

    from langstream_tpu.providers.jax_local.provider import (
        JaxCompletionsService,
        JaxEmbeddingsService,
    )
    from langstream_tpu.serving.openai_api import OpenAIApiServer

    config = {
        "model": {"preset": args.model, "max_seq_len": args.max_seq_len},
        "engine": {
            "max-slots": args.max_slots,
            "max-seq-len": args.max_seq_len,
            "decode-chunk": args.decode_chunk,
            "precompile": bool(args.precompile),
            "pipeline-decode": not getattr(args, "no_pipeline_decode", False),
            "prefix-cache": not getattr(args, "no_prefix_cache", False),
            "logprobs-top-k": getattr(args, "logprobs_top_k", 0),
            "kv-layout": getattr(args, "kv_layout", "dense"),
            "kv-block-size": getattr(args, "kv_block_size", 16),
            "kv-blocks": getattr(args, "kv_blocks", 0) or "",
            "kv-host-blocks": getattr(args, "kv_host_blocks", 0) or "",
            "paged-kernel": getattr(args, "paged_kernel", "fused"),
            "spec-decode": getattr(args, "spec_decode", "off"),
            "spec-k": getattr(args, "spec_k", 4),
            "spec-ngram": getattr(args, "spec_ngram", 2),
            "prefill-mode": getattr(args, "prefill_mode", "split"),
            "prefill-chunk": getattr(args, "prefill_chunk", 64),
            "mixed-carry": getattr(args, "mixed_carry", "on"),
            # decode-stall watchdog: on by default for serve (the
            # provider starts it; --no-watchdog disables)
            "watchdog": not getattr(args, "no_watchdog", False),
            # engine supervisor (self-healing serving): crash →
            # snapshot → rebuild → bitwise session resurrection; the
            # multi-host mirror path disables it below (a rebuilt
            # leader cannot resynchronize followers yet)
            "supervisor": not getattr(args, "no_supervisor", False),
            "max-restarts": getattr(args, "max_restarts", 3),
            # admission deadline / load shedding (0 = off)
            "queue-timeout-s": getattr(args, "queue_timeout_s", 0) or "",
        },
    }
    if getattr(args, "followers", 0) or getattr(args, "follower_of", None):
        # mirror serving: every leader dispatch must replay on the
        # followers in stream order — a supervisor rebuild would fork
        # the stream, so the heal arc is disabled rather than divergent
        config["engine"]["supervisor"] = False
    slo_targets = {
        "ttft-ms-p95": getattr(args, "slo_ttft_ms", 0) or 0,
        "tpot-ms-p95": getattr(args, "slo_tpot_ms", 0) or 0,
    }
    if any(slo_targets.values()):
        config["slo"] = {k: v for k, v in slo_targets.items() if v}
    from langstream_tpu.providers.jax_local.model import LlamaConfig

    try:
        LlamaConfig.from_dict({"preset": args.model})
        known_preset = True
    except KeyError:
        known_preset = False
    if args.checkpoint:
        config["checkpoint"] = args.checkpoint
        if not known_preset:
            # the checkpoint carries the real model config; --model is
            # then just the served model NAME, not a preset
            config["model"] = {}
    elif not known_preset:
        raise SystemExit(
            f"unknown model preset {args.model!r} and no --checkpoint "
            "given; pass a preset (tiny, llama-3-1b, llama-3-8b, "
            "llama-3-70b) or point --checkpoint at a model directory"
        )
    if args.tokenizer:
        config["tokenizer"] = {"type": "hf", "path": args.tokenizer}
    if args.quantization:
        config["quantization"] = args.quantization
    if args.tp and args.tp > 1:
        config["mesh"] = {"tp": args.tp}
    # --kv-layout paged composes with multi-host serving: paged
    # dispatch records carry their block-table rows and COW copies
    # publish block_copy records, so followers replay the identical
    # pool mutations on their shard (serving/mirror.py).
    if getattr(args, "spec_decode", "off") != "off" and (
        getattr(args, "followers", 0) or getattr(args, "follower_of", None)
    ):
        # configuration-time guard: the mirror replays fixed-width
        # dispatch records; spec dispatches carry the device
        # token-history operand and return variable-width outputs
        # (engine._check_mirror_layout backstops)
        raise SystemExit(
            "--spec-decode is not supported with multi-host serving "
            "(--followers/--follower-of) yet"
        )
    completions = JaxCompletionsService(config)
    if getattr(args, "follower_of", None):
        # follower host of a multi-host replica: no HTTP surface — just
        # replay the leader's dispatch stream on this process's shard
        from langstream_tpu.serving.mirror import FollowerExecutor

        completions.engine.stop()  # executor owns the dispatches
        leader_host, _, leader_port = args.follower_of.rpartition(":")
        executor = FollowerExecutor(completions.engine)
        executor.connect(
            leader_host or "127.0.0.1", int(leader_port),
            fingerprint=_mirror_fingerprint(config),
        )
        print(
            f"follower: replaying dispatch stream from {args.follower_of}",
            flush=True,
        )
        records = await asyncio.to_thread(executor.run)
        print(f"follower: stream ended after {records} records", flush=True)
        return
    mirror = None
    if getattr(args, "followers", 0):
        from langstream_tpu.serving.mirror import DispatchMirror

        mirror = DispatchMirror(
            host=args.host, port=args.mirror_port,
            fingerprint=_mirror_fingerprint(config),
        )
        print(
            f"mirror: waiting for {args.followers} follower(s) "
            f"on :{mirror.port}",
            flush=True,
        )
        await asyncio.to_thread(mirror.wait_for_followers, args.followers)
        completions.engine.mirror = mirror
    embeddings = None
    if args.embeddings_checkpoint:
        embeddings = JaxEmbeddingsService(
            {"embeddings-model": {"checkpoint": args.embeddings_checkpoint}},
            None,
        )
    from langstream_tpu.providers.jax_local.engine import (
        engines_histograms,
        engines_snapshot,
    )

    server = OpenAIApiServer(
        completions, embeddings,
        model=args.model, host=args.host, port=args.port,
        gauges=engines_snapshot, histograms=engines_histograms,
    )
    await server.start()
    from langstream_tpu.runtime.local import settle_collector

    settle_collector()
    port = server.addresses[0][1] if server.addresses else args.port
    flight.record("phase", name="serving", port=port)
    flight.flush()
    print(
        f"OpenAI-compatible API on http://{args.host}:{port}/v1 "
        f"(model {args.model})",
        flush=True,
    )
    stop = asyncio.Event()
    _install_stop(asyncio.get_running_loop(), stop)
    gossip_task, gossip_runtime = await _start_fleet_gossip(
        args, completions, port, stop
    )
    try:
        await stop.wait()
    finally:
        if gossip_task is not None:
            gossip_task.cancel()
            try:
                # wait the cancel out: a mid-write publish must not
                # race the runtime close below
                await gossip_task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        if gossip_runtime is not None:
            try:
                await gossip_runtime.close()
            except Exception:  # noqa: BLE001 — shutdown best-effort
                pass
        await server.stop()
        await completions.close()


async def _start_fleet_gossip(args, completions, port: int, stop):
    """``serve --fleet-gossip``: publish role-aware heartbeats on the
    topic fabric so fleet routers see this replica without scraping —
    the runner-pod wiring of ``fleet/heartbeat.publish_loop`` (ROADMAP
    item 4). Returns (task, topic_runtime), both None when gossip is
    not configured. A bad fabric config logs and disables gossip; it
    never takes the serving process down."""
    gossip = getattr(args, "fleet_gossip", None)
    if not gossip:
        return None, None
    import socket

    from langstream_tpu.fleet.heartbeat import (
        HEARTBEAT_TOPIC,
        build_heartbeat,
        publish_loop,
    )
    from langstream_tpu.topics import create_topic_runtime

    role = getattr(args, "fleet_role", "unified") or "unified"
    replica_id = (
        getattr(args, "fleet_replica_id", None)
        or os.environ.get("HOSTNAME")
        or f"{socket.gethostname()}:{port}"
    )
    runtime = None
    try:
        runtime = create_topic_runtime(json.loads(gossip))
        producer = runtime.create_producer(
            f"fleet-gossip-{replica_id}", {"topic": HEARTBEAT_TOPIC}
        )
        await producer.start()
    except Exception:  # noqa: BLE001 — gossip must not kill serving
        logger.exception("fleet gossip disabled: bad --fleet-gossip")
        if runtime is not None:
            # the runtime came up before the producer failed: close it
            # or its client connections/threads outlive the feature
            try:
                await runtime.close()
            except Exception:  # noqa: BLE001
                pass
        return None, None
    seq = {"n": 0}

    def beat():
        seq["n"] += 1
        # the CURRENT engine: the supervisor swaps it on rebuild, and
        # the degraded/rebuilding state rides the beat so routers
        # drain this replica instead of 503-discovering it
        return build_heartbeat(
            replica_id,
            seq["n"],
            engine=completions.engine,
            supervisor=getattr(completions, "_supervisor", None),
            role=role,
        )

    task = asyncio.get_running_loop().create_task(
        publish_loop(
            producer, beat,
            interval_s=getattr(args, "fleet_heartbeat_s", 2.0),
            stop=stop,
        )
    )
    print(
        f"fleet gossip: {replica_id} role={role} -> "
        f"{HEARTBEAT_TOPIC} every {getattr(args, 'fleet_heartbeat_s', 2.0)}s",
        flush=True,
    )
    return task, runtime
