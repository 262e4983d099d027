"""Pod entry points — the in-container mains the deployer's manifests run.

Reference parity:

- ``agent-runner``       — ``AgentRunnerStarter.java:39`` reads the mounted
  ``RuntimePodConfiguration`` and runs the agent's main loop, with the
  agent HTTP endpoints (``/info``, ``/metrics``) on :8080
  (``AgentRunner.java:99-113`` Jetty + Prometheus ``DefaultExports``).
- ``code-download``      — ``AgentCodeDownloaderStarter`` /
  ``DownloadAgentCodeCommand``: fetch the app's code archive from code
  storage into the shared emptyDir before the runner starts.
- ``application-setup``  — ``ApplicationSetupRunner.java:40``: create
  topics and deploy assets for the application.
- ``deployer``           — ``RuntimeDeployer.java:40``: build the execution
  plan and write one Agent CR per plan node (the operator turns those into
  StatefulSets).

TPU-native notes: the runner is the same asyncio
:class:`~langstream_tpu.runtime.local.LocalApplicationRunner` used by
``apps run`` — a pod is simply a one-node plan whose replicas come from
the StatefulSet, not from in-process parallelism. The broker is whatever
``streamingCluster`` names (tpulog served broker across pods, Kafka, or
memory for single-pod tests).
"""

from __future__ import annotations

import asyncio
import dataclasses
import io
import json
import logging
import os
import signal
import zipfile
from typing import Any, Dict, Optional

from langstream_tpu.api.errors import ErrorsSpec
from langstream_tpu.compiler.planner import AgentNode, AgentSpec, ExecutionPlan
from langstream_tpu.model.application import (
    Application,
    Instance,
    ResourcesSpec,
)

logger = logging.getLogger(__name__)

AGENT_HTTP_PORT = 8080


# ---------------------------------------------------------------------- #
# pod configuration (the mounted Secret)
# ---------------------------------------------------------------------- #
def load_pod_configuration(path: str) -> Dict[str, Any]:
    with open(path, "r", encoding="utf-8") as handle:
        return json.load(handle)


def node_from_document(doc: Dict[str, Any]) -> AgentNode:
    """Rebuild an :class:`AgentNode` from its ``dataclasses.asdict`` form
    (the ``agentNode`` field the operator serializes into Agent CRs and
    pod Secrets)."""

    def spec(value: Optional[Dict[str, Any]]) -> Optional[AgentSpec]:
        if not value:
            return None
        return AgentSpec(
            agent_id=value["agent_id"],
            agent_type=value["agent_type"],
            configuration=value.get("configuration", {}) or {},
        )

    return AgentNode(
        id=doc["id"],
        pipeline=doc.get("pipeline", ""),
        module=doc.get("module", ""),
        source=spec(doc.get("source")),
        processors=[s for s in map(spec, doc.get("processors", [])) if s],
        sink=spec(doc.get("sink")),
        service=spec(doc.get("service")),
        input_topic=doc.get("input_topic"),
        output_topic=doc.get("output_topic"),
        errors=ErrorsSpec(**(doc.get("errors") or {})),
        resources=ResourcesSpec(**(doc.get("resources") or {})),
    )


def _application_for_pod(config: Dict[str, Any]) -> Application:
    """A minimal Application carrying what agents need at runtime:
    AI-provider resources, the streaming cluster, and resolved secrets
    (the pipeline/module structure stays behind in the control plane)."""
    app = Application(
        application_id=config.get("applicationId", "application"),
        tenant=config.get("tenant", "default"),
        resources=config.get("resources", {}) or {},
    )
    app.instance = Instance(
        streaming_cluster=config.get("streamingCluster") or {"type": "memory"},
        compute_cluster={"type": "local"},
        globals_=config.get("globals", {}) or {},
    )
    code_dir = os.environ.get("LANGSTREAM_CODE_DIR")
    if code_dir:
        python_dir = os.path.join(code_dir, "python")
        if os.path.isdir(python_dir):
            app.python_path = python_dir
        elif os.path.isdir(code_dir):
            app.python_path = code_dir
    return app


# ---------------------------------------------------------------------- #
# /metrics + /info HTTP (reference AgentRunner.java:99-113)
# ---------------------------------------------------------------------- #
# the one registry→exposition renderer lives in api.metrics; re-exported
# here because this module is where runner pods (and older call sites)
# import it from
from langstream_tpu.api.metrics import prometheus_text  # noqa: F401,E402


class AgentHttpServer:
    """The per-runner HTTP surface: ``/info`` (JSON), ``/metrics``
    (Prometheus text), ``/ready`` + ``/ok`` (probes)."""

    def __init__(
        self,
        *,
        info: Any,            # () -> dict
        metrics: Any = None,  # MetricsReporter
        gauges: Any = None,   # () -> dict of name -> float
        histograms: Any = None,  # () -> dict of name -> le-snapshot
        port: int = AGENT_HTTP_PORT,
        host: str = "0.0.0.0",
    ) -> None:
        self._info = info
        self._metrics = metrics
        self._gauges = gauges
        self._histograms = histograms
        self.port = port
        self.host = host
        self._runner = None
        self.ready = False

    async def start(self) -> None:
        from aiohttp import web

        app = web.Application()
        app.router.add_get("/info", self._handle_info)
        app.router.add_get("/metrics", self._handle_metrics)
        app.router.add_get("/ready", self._handle_ready)
        app.router.add_get("/ok", self._handle_ready)
        app.router.add_get("/debug/profile", self._handle_profile)
        runner = web.AppRunner(app, access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, self.host, self.port)
        await site.start()
        self._runner = runner
        # port 0 → kernel-assigned; expose the real one for tests
        server = site._server  # noqa: SLF001 — aiohttp has no accessor
        if server and server.sockets:
            self.port = server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    async def _handle_info(self, request):
        from aiohttp import web

        return web.json_response(self._info())

    async def _handle_metrics(self, request):
        from aiohttp import web

        counters = self._metrics.snapshot() if self._metrics else {}
        gauges = self._gauges() if self._gauges else {}
        histograms: Dict[str, Any] = {}
        if self._metrics is not None and hasattr(
            self._metrics, "histogram_snapshots"
        ):
            histograms.update(self._metrics.histogram_snapshots())
        if self._histograms is not None:
            histograms.update(self._histograms())
        return web.Response(
            text=prometheus_text(counters, gauges, histograms),
            content_type="text/plain",
        )

    async def _handle_ready(self, request):
        from aiohttp import web

        return web.Response(text="OK" if self.ready else "STARTING",
                            status=200 if self.ready else 503)

    async def _handle_profile(self, request):
        """On-demand profiler capture (``?seconds=N``) on runner pods —
        same contract as the OpenAI server's ``/debug/profile``: one
        capture at a time, 409 on a concurrent request."""
        import asyncio as _asyncio

        from aiohttp import web

        from langstream_tpu.runtime import profiling

        try:
            seconds = float(request.query.get("seconds", 3))
        except (TypeError, ValueError):
            return web.json_response(
                {"error": "seconds must be a number"}, status=400
            )
        try:
            # capture() validates the range itself (one source of truth)
            path = await _asyncio.to_thread(profiling.capture, seconds)
        except ValueError as error:
            return web.json_response({"error": str(error)}, status=400)
        except profiling.ProfileBusyError as error:
            return web.json_response({"error": str(error)}, status=409)
        return web.json_response({"path": path, "seconds": seconds})


# ---------------------------------------------------------------------- #
# agent-runner
# ---------------------------------------------------------------------- #
async def agent_runner_main(
    config_path: str,
    *,
    http_port: int = AGENT_HTTP_PORT,
    stop_event: Optional[asyncio.Event] = None,
) -> None:
    """Run one execution-plan node until SIGTERM, serving /info+/metrics.

    Reference: ``AgentRunnerStarter.java:39`` → ``AgentRunner.run``.
    """
    from langstream_tpu.runtime.local import (
        LocalApplicationRunner,
        settle_collector,
    )

    # pods can override the port via env without changing the manifest
    # command line (tests use this to avoid :8080 collisions)
    http_port = int(os.environ.get("LANGSTREAM_HTTP_PORT", http_port))
    plugins_dir = os.environ.get("LANGSTREAM_PLUGINS_DIR")
    if plugins_dir:
        from langstream_tpu.runtime.plugins import load_plugins

        load_plugins(plugins_dir)
    # observability: pods opt into the flight recorder via
    # LANGSTREAM_FLIGHT_DIR (trace dumps likewise via
    # LANGSTREAM_TRACE_DIR, handled by the tracer registry)
    from langstream_tpu.runtime import flight

    flight.configure_from_env()
    flight.record("phase", name="pod-start", config=config_path)
    # multi-host slice: all pods of this replica enter one pjit program
    # (SURVEY §7 hard part (e)); a no-op for single-host replicas
    from langstream_tpu.runtime.multihost import initialize_multihost

    initialize_multihost()
    config = load_pod_configuration(config_path)
    node = node_from_document(config["agentNode"])
    # one pod = one replica; data parallelism is the StatefulSet's
    # replica count (all replicas share one consumer group)
    node = dataclasses.replace(
        node, resources=dataclasses.replace(node.resources, parallelism=1)
    )
    application = _application_for_pod(config)
    plan = ExecutionPlan(application=application, topics={}, agents=[node])
    state_dir = os.environ.get("LANGSTREAM_STATE_DIR")
    if state_dir:
        os.makedirs(state_dir, exist_ok=True)
    runner = LocalApplicationRunner(plan, state_directory=state_dir or None)

    def _engine_module():
        import sys

        return sys.modules.get("langstream_tpu.providers.jax_local.engine")

    def gauges() -> Dict[str, float]:
        # TPU engine internals, when this pod hosts a jax-local engine
        module = _engine_module()
        return module.engines_snapshot() if module else {}

    def histograms() -> Dict[str, Any]:
        module = _engine_module()
        return module.engines_histograms() if module else {}

    http = AgentHttpServer(
        info=runner.info, metrics=runner.metrics, gauges=gauges,
        histograms=histograms, port=http_port,
    )
    await http.start()
    logger.info(
        "agent-runner %s serving /info,/metrics on :%d", node.id, http.port
    )

    stop = stop_event or asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, stop.set)
        except (NotImplementedError, RuntimeError):  # non-main thread
            pass
    try:
        await runner.start()
        settle_collector()
        http.ready = True
        join = asyncio.ensure_future(runner.join())
        stop_task = asyncio.ensure_future(stop.wait())
        await asyncio.wait(
            [join, stop_task], return_when=asyncio.FIRST_COMPLETED
        )
        for task in (join, stop_task):
            if not task.done():
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass
        if join.done() and not join.cancelled():
            join.result()  # propagate a crashed runner
    finally:
        http.ready = False
        await runner.stop()
        await http.stop()


# ---------------------------------------------------------------------- #
# code-download
# ---------------------------------------------------------------------- #
def code_download_main(config_path: str, target: str) -> None:
    """Fetch + unpack the application's code archive (init container).

    Reference: ``AgentCodeDownloaderStarter`` — the runner pod's code
    volume is populated before the main container starts.
    """
    from langstream_tpu.controlplane.codestorage import create_code_storage

    config = load_pod_configuration(config_path)
    code_id = config.get("codeArchiveId")
    tenant = config.get("tenant", "default")
    os.makedirs(target, exist_ok=True)
    if not code_id:
        logger.info("no code archive for this application; nothing to do")
        return
    storage_config = json.loads(
        os.environ.get("LANGSTREAM_CODE_STORAGE") or "{}"
    )
    storage = create_code_storage(storage_config)
    archive = storage.download(tenant, code_id)
    with zipfile.ZipFile(io.BytesIO(archive)) as zf:
        for member in zf.namelist():
            # refuse path traversal out of the target dir
            path = os.path.realpath(os.path.join(target, member))
            if not path.startswith(os.path.realpath(target) + os.sep):
                raise ValueError(f"archive member escapes target: {member}")
        zf.extractall(target)
    logger.info("downloaded code archive %s into %s", code_id, target)


# ---------------------------------------------------------------------- #
# application-setup
# ---------------------------------------------------------------------- #
def _application_from_env() -> Application:
    """Parse the Application CR spec the Jobs receive via
    ``LANGSTREAM_APPLICATION`` (see ``deployer/resources.py:_job``)."""
    raw = os.environ.get("LANGSTREAM_APPLICATION")
    if not raw:
        raise SystemExit("LANGSTREAM_APPLICATION env var is required")
    spec = json.loads(raw)
    definition = spec.get("application")
    instance = spec.get("instance")
    if isinstance(definition, str):
        definition = json.loads(definition or "{}")
    if isinstance(instance, str):
        instance = json.loads(instance or "{}")
    application = Application.from_document(definition or {}, instance or {})
    if spec.get("applicationId"):
        application.application_id = spec["applicationId"]
    if spec.get("tenant"):
        application.tenant = spec["tenant"]
    return application


async def application_setup_main(*, delete: bool = False) -> None:
    """Create (or clean up) topics and assets for the application.

    Reference: ``ApplicationSetupRunner.java:40`` (runApplicationSetup:
    topics + assets; cleanup path on delete).
    """
    from langstream_tpu.api.assets import deploy_assets
    from langstream_tpu.compiler.planner import build_execution_plan
    from langstream_tpu.topics import create_topic_runtime

    application = _application_from_env()
    plan = build_execution_plan(application)
    runtime = create_topic_runtime(application.instance.streaming_cluster)
    admin = runtime.create_admin()
    try:
        for spec in plan.topics.values():
            if delete:
                if spec.deletion_mode == "delete":
                    await admin.delete_topic(spec.name)
            elif spec.creation_mode == "create-if-not-exists":
                await admin.create_topic(spec)
                logger.info("topic %s ready", spec.name)
    finally:
        await admin.close()
        await runtime.close()
    if plan.assets and not delete:
        await deploy_assets(plan.assets, application.resources)
        logger.info("deployed %d assets", len(plan.assets))


# ---------------------------------------------------------------------- #
# deployer
# ---------------------------------------------------------------------- #
async def deployer_main(*, delete: bool = False) -> None:
    """Build the execution plan and write Agent CRs (the operator turns
    them into StatefulSets). Reference: ``RuntimeDeployer.java:40``.
    """
    from langstream_tpu.deployer.crds import AgentCustomResource
    from langstream_tpu.deployer.kubeclient import create_kube_api
    from langstream_tpu.compiler.planner import build_execution_plan

    raw = os.environ.get("LANGSTREAM_APPLICATION")
    spec = json.loads(raw) if raw else {}
    application = _application_from_env()
    namespace = application.tenant or "default"
    kube = create_kube_api()
    label = {"langstream.tpu/application": application.application_id}
    if delete:
        for doc in kube.list("Agent", namespace, label):
            kube.delete("Agent", namespace, doc["metadata"]["name"])
        return
    plan = build_execution_plan(application)
    desired = set()
    for node in plan.agents:
        name = f"{application.application_id}-{node.id}"
        desired.add(name)
        cr = AgentCustomResource(
            name=name,
            namespace=namespace,
            application_id=application.application_id,
            agent_node=dataclasses.asdict(node),
            streaming_cluster=application.instance.streaming_cluster,
            resources=application.resources,
            parallelism=node.resources.parallelism,
            size=node.resources.size,
            disk=node.resources.disk,
            code_archive_id=spec.get("codeArchiveId"),
            checksum=spec.get("checksum"),
        )
        kube.apply(cr.to_manifest())
        logger.info("applied Agent CR %s", name)
    for doc in kube.list("Agent", namespace, label):
        if doc["metadata"]["name"] not in desired:
            kube.delete("Agent", namespace, doc["metadata"]["name"])
