"""The gateway server: WS produce/consume/chat + HTTP produce/service.

Endpoint and wire parity with the reference gateway:

- WS ``/v1/{produce|consume|chat}/{tenant}/{application}/{gateway}``
  (``websocket/WebSocketConfig.java:46-48``); query args use the
  reference's conventions (``GatewayRequestHandler.java:105-116``):
  ``param:<name>=...`` for declared gateway parameters,
  ``option:<name>=...`` for options (e.g. ``option:position=earliest``),
  ``credentials=...`` / ``test-credentials=...`` for auth.
- Produce frames are ``{"key", "value", "headers"}``
  (``api/ProduceRequest.java:20``); consume pushes are
  ``{"record": {...}, "offset": "..."}`` (``api/ConsumePushMessage.java:20``).
- HTTP ``POST /api/gateways/produce/{tenant}/{app}/{gateway}`` and the
  ``service`` gateway ``/api/gateways/service/...`` topic round-trip
  correlated by ``langstream-service-request-id``
  (``http/GatewayResource.java:74-96,156-190``).
- Gateway lifecycle events (ClientConnected/Disconnected) go to the
  configured events-topic (``events/EventRecord.java:13-29``).
"""

from __future__ import annotations

import asyncio
import json
import logging
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

from aiohttp import WSMsgType, web

from langstream_tpu.api.metrics import MetricsReporter, prometheus_text
from langstream_tpu.api.records import Record, now_millis
from langstream_tpu.api.topics import OffsetPosition
from langstream_tpu.gateway.auth import (
    AuthenticationFailed,
    Principal,
    create_auth_provider,
)
from langstream_tpu.model.application import Application, Gateway
from langstream_tpu.runtime import tracing
from langstream_tpu.runtime.tracing import (
    TRACE_ID_HEADER,
    get_tracer,
    new_trace_id,
    wall,
)

logger = logging.getLogger(__name__)


class GatewayError(Exception):
    def __init__(self, status: int, message: str) -> None:
        super().__init__(message)
        self.status = status


class _RegisteredApp:
    def __init__(self, application: Application, topic_runtime) -> None:
        self.application = application
        self.topic_runtime = topic_runtime
        self.producers: Dict[str, Any] = {}

    async def producer(self, topic: str):
        producer = self.producers.get(topic)
        if producer is None:
            producer = self.topic_runtime.create_producer("gateway", {"topic": topic})
            await producer.start()
            self.producers[topic] = producer
        return producer


class GatewayServer:
    """Serves every registered application's gateways on one port."""

    def __init__(self, host: str = "127.0.0.1", port: int = 8091) -> None:
        self.host = host
        self.port = port
        self._apps: Dict[Tuple[str, str], _RegisteredApp] = {}
        self._runner: Optional[web.AppRunner] = None
        self._auth_cache: Dict[int, Any] = {}
        # observability: request-entry spans (NOOP unless tracing is on)
        # + counters served at /metrics through the shared exposition
        # renderer — same format as runner pods and the OpenAI server
        self.tracer = get_tracer("gateway")
        self.metrics = MetricsReporter(prefix="gateway")
        # fleet layer (langstream_tpu/fleet): when a router/controller
        # is registered, produce paths stamp a replica-affinity header
        # and /metrics serves the fleet gauges
        self._fleet = None

    # ------------------------------------------------------------------ #
    # registration / lifecycle
    # ------------------------------------------------------------------ #
    def register(self, tenant: str, application: Application, topic_runtime) -> None:
        self._apps[(tenant, application.application_id)] = _RegisteredApp(
            application, topic_runtime
        )

    def register_local_runner(self, local_runner, tenant: str = "default") -> None:
        self.register(tenant, local_runner.application, local_runner.topic_runtime)

    def register_fleet(self, controller) -> None:
        """Attach a fleet router/controller (``fleet.FleetRouter`` or
        ``fleet.FleetController``): produce paths consult it for a
        prefix-affinity replica and /metrics merges its gauges. The
        gateway stays fully functional without one — routing is an
        overlay, not a dependency."""
        self._fleet = controller

    async def start(self) -> None:
        app = web.Application()
        app.router.add_get("/v1/produce/{tenant}/{application}/{gateway}", self._ws_produce)
        app.router.add_get("/v1/consume/{tenant}/{application}/{gateway}", self._ws_consume)
        app.router.add_get("/v1/chat/{tenant}/{application}/{gateway}", self._ws_chat)
        app.router.add_post(
            "/api/gateways/produce/{tenant}/{application}/{gateway}", self._http_produce
        )
        app.router.add_post(
            "/api/gateways/service/{tenant}/{application}/{gateway}", self._http_service
        )
        app.router.add_get("/healthz", self._healthz)
        app.router.add_get("/metrics", self._metrics)
        # local UI (reference: `langstream apps ui`)
        app.router.add_get("/ui/{tenant}/{application}", self._ui_page)
        app.router.add_get("/ui/api/{tenant}/{application}", self._ui_api)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        logger.info("gateway listening on %s:%d", self.host, self.port)

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    async def _healthz(self, request) -> web.Response:
        return web.json_response({"status": "OK", "apps": len(self._apps)})

    async def _metrics(self, request) -> web.Response:
        gauges = {"gateway_registered_apps": float(len(self._apps))}
        histograms = dict(self.metrics.histogram_snapshots())
        # `apps run` hosts the gateway in the SAME process as the TPU
        # engine: surface the engine's efficiency gauges (MFU/MBU,
        # goodput, SLO burn rates, watchdog trips) here too, so every
        # scrape surface of the process tells the same story. Lazy via
        # sys.modules — a gateway-only process never imports the engine.
        import sys as _sys

        engine_module = _sys.modules.get(
            "langstream_tpu.providers.jax_local.engine"
        )
        if engine_module is not None:
            gauges.update(engine_module.engines_snapshot())
            histograms.update(engine_module.engines_histograms())
        else:
            # gateway-only process: the engine families are absent, but
            # the journey ledger's route stage is sampled HERE — its
            # per-stage histograms must still reach this surface
            from langstream_tpu.runtime.journey import stage_histograms

            histograms.update(stage_histograms())
        # fleet routing/autoscaling gauges (per-replica queue depth and
        # state, affinity hit rate, replica counts) — the `top` fleet
        # panel reads exactly these families
        if self._fleet is not None:
            gauges.update(self._fleet.gauges())
        return web.Response(
            text=prometheus_text(
                self.metrics.snapshot(),
                gauges,
                histograms,
            ),
            content_type="text/plain",
        )

    def _ui_app(self, request):
        key = (request.match_info["tenant"], request.match_info["application"])
        registered = self._apps.get(key)
        if registered is None:
            raise web.HTTPNotFound(text=f"no application {key}")
        return registered.application

    async def _ui_page(self, request) -> web.Response:
        from langstream_tpu.gateway.ui import render_page

        self._ui_app(request)  # 404 for unknown apps
        return web.Response(
            text=render_page(
                request.match_info["tenant"],
                request.match_info["application"],
            ),
            content_type="text/html",
        )

    async def _ui_api(self, request) -> web.Response:
        from langstream_tpu.gateway.ui import describe

        return web.json_response(describe(self._ui_app(request)))

    # ------------------------------------------------------------------ #
    # request validation (GatewayRequestHandler.validateRequest parity)
    # ------------------------------------------------------------------ #
    def _resolve(
        self, request, expected_type: str
    ) -> Tuple[_RegisteredApp, Gateway, Dict[str, str], Dict[str, str], Optional[str]]:
        tenant = request.match_info["tenant"]
        application_id = request.match_info["application"]
        gateway_id = request.match_info["gateway"]
        registered = self._apps.get((tenant, application_id))
        if registered is None:
            raise GatewayError(404, f"unknown application {tenant}/{application_id}")
        gateway = None
        for candidate in registered.application.gateways:
            if candidate.id == gateway_id:
                gateway = candidate
                break
        if gateway is None:
            raise GatewayError(404, f"unknown gateway {gateway_id!r}")
        if gateway.type != expected_type:
            raise GatewayError(
                400,
                f"gateway {gateway_id!r} is of type {gateway.type!r}, "
                f"expected {expected_type!r}",
            )
        options: Dict[str, str] = {}
        parameters: Dict[str, str] = {}
        credentials: Optional[str] = None
        for key, value in request.query.items():
            if key in ("credentials", "test-credentials"):
                credentials = value
            elif key.startswith("option:"):
                options[key[len("option:"):]] = value
            elif key.startswith("param:"):
                parameters[key[len("param:"):]] = value
            else:
                raise GatewayError(
                    400,
                    f"invalid query parameter {key!r}. To specify a gateway "
                    "parameter, use the format param:<parameter_name>. "
                    "To specify an option, use the format option:<option_name>.",
                )
        required = set(gateway.parameters) | self._referenced_parameters(gateway)
        for name in sorted(required):
            if not parameters.get(name):
                raise GatewayError(
                    400,
                    f"missing required parameter {name!r}. "
                    f"Required parameters: {sorted(required)}",
                )
        unknown = set(parameters) - required
        if unknown:
            raise GatewayError(400, f"unknown parameters: {sorted(unknown)}")
        return registered, gateway, parameters, options, credentials

    @staticmethod
    def _referenced_parameters(gateway: Gateway) -> set:
        names = set()
        for options in (
            gateway.produce_options,
            gateway.consume_options.get("filters", {}),
            gateway.chat_options,
        ):
            for header in options.get("headers", []) or []:
                name = header.get("value-from-parameters")
                if name:
                    names.add(name)
        return names

    async def _authenticate(
        self, gateway: Gateway, credentials: Optional[str]
    ) -> Optional[Principal]:
        if not gateway.authentication:
            return Principal(credentials or "anonymous") if credentials else None
        provider_key = id(gateway)
        provider = self._auth_cache.get(provider_key)
        if provider is None:
            provider = create_auth_provider(gateway.authentication)
            self._auth_cache[provider_key] = provider
        if credentials is None:
            raise GatewayError(401, "credentials required")
        try:
            return await provider.authenticate(credentials)
        except AuthenticationFailed as error:
            raise GatewayError(401, str(error)) from error

    @staticmethod
    def _resolve_headers(
        entries: List[Dict[str, Any]],
        parameters: Dict[str, str],
        principal: Optional[Principal],
    ) -> List[Tuple[str, str]]:
        """Resolve configured gateway headers: literal ``value``,
        ``value-from-parameters`` or ``value-from-authentication``. Entries
        without a ``key`` default to the client-session header (the shape
        used by chat-options in the reference examples)."""
        out = []
        for entry in entries or []:
            key = entry.get("key", "langstream-client-session-id")
            if "value" in entry:
                value = entry["value"]
            elif "value-from-parameters" in entry:
                value = parameters.get(entry["value-from-parameters"], "")
            elif "value-from-authentication" in entry:
                if principal is None:
                    raise GatewayError(401, "authentication required for header")
                value = principal.get(entry["value-from-authentication"])
            else:
                value = ""
            out.append((key, str(value) if value is not None else ""))
        return out

    async def _emit_event(
        self, registered: _RegisteredApp, gateway: Gateway, event_type: str,
        parameters: Dict[str, str],
    ) -> None:
        topic = gateway.events_topic
        if not topic:
            return
        producer = await registered.producer(topic)
        await producer.write(
            Record(
                value={
                    "type": event_type,
                    "timestamp": now_millis(),
                    "source": {"gateway": gateway.id, "type": gateway.type},
                    "data": {"user-parameters": parameters},
                }
            )
        )

    # ------------------------------------------------------------------ #
    # produce
    # ------------------------------------------------------------------ #
    @staticmethod
    def _parse_produce(payload: str) -> Tuple[Any, Any, List[Tuple[str, str]]]:
        try:
            body = json.loads(payload)
        except json.JSONDecodeError as error:
            raise GatewayError(400, f"invalid JSON: {error}") from error
        if not isinstance(body, dict):
            raise GatewayError(400, "produce payload must be a JSON object")
        headers = [
            (str(k), str(v)) for k, v in (body.get("headers") or {}).items()
        ]
        return body.get("key"), body.get("value"), headers

    @staticmethod
    def _stamp_trace(
        headers: Tuple[Tuple[str, str], ...]
    ) -> Tuple[Tuple[Tuple[str, str], ...], str]:
        """Ensure a ``langstream-trace-id`` header: keep a client-supplied
        one (cross-system traces), mint one otherwise. Every ingress path
        stamps here so one id follows the request through every topic
        hop, runner span, and engine span."""
        for key, value in headers:
            if key == TRACE_ID_HEADER and value:
                return headers, str(value)
        trace_id = new_trace_id()
        return headers + ((TRACE_ID_HEADER, trace_id),), trace_id

    def _route_decision(self, value: Any, user_headers=()):
        """The fleet router's verdict for one produce, or None (no
        fleet attached / unroutable). Split out of
        :meth:`_fleet_headers` so the journey ledger sees the decision
        itself — policy and matched-prefix class — not just the stamped
        header."""
        if self._fleet is None:
            return None
        from langstream_tpu.fleet.router import (
            REPLICA_HEADER,
            NoRoutableReplica,
        )

        tokens = None
        if isinstance(value, dict):
            raw = value.get("tokens")
            if isinstance(raw, list) and all(
                isinstance(t, int) for t in raw
            ):
                tokens = raw
        pin = next(
            (
                str(v) for k, v in user_headers
                if k == REPLICA_HEADER and v
            ),
            None,
        )
        try:
            decision = self._fleet.route(tokens, session_replica=pin)
        except NoRoutableReplica:
            self.metrics.counter("fleet_unroutable").count()
            return None
        if decision.policy == "sticky":
            self.metrics.counter("fleet_sticky").count()
        self.metrics.counter("fleet_routed").count()
        return decision

    def _fleet_headers(
        self,
        value: Any,
        user_headers: Tuple[Tuple[str, str], ...] = (),
    ) -> Tuple[Tuple[str, str], ...]:
        """Prefix-affinity routing at the front door: when a fleet
        router is registered, pick the replica whose resident chain set
        best matches the session's token prefix (``tokens`` in a dict
        payload; token-less payloads fall back least-queue-depth) and
        stamp it as the ``langstream-replica`` header, so downstream
        consumers — and keyed partitioners — can honor the decision.

        Session stickiness (ROADMAP item 4): a follow-up carrying the
        stamped ``langstream-replica`` header from a prior reply PINS
        its session's replica — the warm KV lives there NOW, before its
        chain digests have gossiped — and a stale/condemned pin falls
        back to digest scoring, re-stamping the new decision.

        Never fails the produce: an unroutable fleet degrades to the
        pre-fleet blind path."""
        decision = self._route_decision(value, user_headers)
        if decision is None:
            return ()
        from langstream_tpu.fleet.router import REPLICA_HEADER

        return ((REPLICA_HEADER, decision.replica_id),)

    def _record_route(
        self, trace_id: str, decision, started: float, dur_s: float
    ) -> None:
        """The journey ledger's ``route`` stage on the gateway: a
        histogram sample for this /metrics surface, a ``gateway.route``
        trace event, and a ``journey`` flight record when the recorder
        is armed — so fleet-wide joins see who decided and why, not
        just where the request landed."""
        from langstream_tpu.runtime import flight
        from langstream_tpu.runtime.journey import STAGE_SECONDS

        STAGE_SECONDS["route"].observe(max(0.0, dur_s))
        if decision is None:
            return
        attrs = {
            "policy": decision.policy,
            "replica": decision.replica_id,
            "prefix_class": (
                "host" if getattr(decision, "matched_host_blocks", 0)
                else "warm" if getattr(decision, "matched_blocks", 0)
                else "cold"
            ),
        }
        if self.tracer.enabled:
            self.tracer.event(
                "gateway.route",
                max(0.0, dur_s),
                trace_id=trace_id,
                start=started,
                **attrs,
            )
        if flight.RECORDER.enabled:
            start_wall = wall(started)
            flight.record(
                "journey",
                trace_id=trace_id,
                stages=[{
                    "stage": "route",
                    "start": start_wall,
                    "end": start_wall + max(0.0, dur_s),
                    **attrs,
                }],
            )

    async def _do_produce(
        self, registered, gateway, parameters, principal, payload: str
    ) -> None:
        key, value, user_headers = self._parse_produce(payload)
        gateway_headers = self._resolve_headers(
            gateway.produce_options.get("headers"), parameters, principal
        )
        route_t0 = time.perf_counter()
        decision = self._route_decision(value, tuple(user_headers))
        route_dur = time.perf_counter() - route_t0
        fleet_headers: Tuple[Tuple[str, str], ...] = ()
        if decision is not None:
            from langstream_tpu.fleet.router import REPLICA_HEADER

            fleet_headers = ((REPLICA_HEADER, decision.replica_id),)
        if self._fleet is not None:
            # the routing layer owns the replica header: drop any
            # client-supplied pin (honored pins re-stamp the same
            # value; stale pins must not ride beside the new decision
            # — and when the whole fleet is unroutable, forwarding the
            # client's echoed pin would steer the session to a replica
            # the router just refused)
            from langstream_tpu.fleet.router import REPLICA_HEADER

            user_headers = [
                h for h in user_headers if h[0] != REPLICA_HEADER
            ]
        headers, trace_id = self._stamp_trace(
            tuple(user_headers)
            + tuple(gateway_headers)
            + fleet_headers
        )
        if self._fleet is not None:
            self._record_route(trace_id, decision, route_t0, route_dur)
        with self.tracer.span(
            "gateway.produce", trace_id=trace_id,
            gateway=gateway.id, topic=gateway.topic,
        ):
            await (await registered.producer(gateway.topic)).write(
                Record(value=value, key=key, headers=headers)
            )
        self.metrics.counter("records_produced").count()

    async def _ws_produce(self, request) -> web.WebSocketResponse:
        try:
            registered, gateway, parameters, _options, credentials = self._resolve(
                request, "produce"
            )
            principal = await self._authenticate(gateway, credentials)
        except GatewayError as error:
            raise web.HTTPBadRequest(text=str(error)) if error.status == 400 else (
                web.HTTPNotFound(text=str(error)) if error.status == 404
                else web.HTTPUnauthorized(text=str(error))
            )
        ws = web.WebSocketResponse()
        await ws.prepare(request)
        await self._emit_event(registered, gateway, "ClientConnected", parameters)
        try:
            async for message in ws:
                if message.type != WSMsgType.TEXT:
                    continue
                try:
                    await self._do_produce(
                        registered, gateway, parameters, principal, message.data
                    )
                    await ws.send_json({"status": "OK"})
                except GatewayError as error:
                    await ws.send_json({"status": "BAD_REQUEST", "reason": str(error)})
        finally:
            await self._emit_event(registered, gateway, "ClientDisconnected", parameters)
        return ws

    async def _http_produce(self, request) -> web.Response:
        try:
            registered, gateway, parameters, _options, credentials = self._resolve(
                request, "produce"
            )
            principal = await self._authenticate(gateway, credentials)
            await self._do_produce(
                registered, gateway, parameters, principal, await request.text()
            )
        except GatewayError as error:
            return web.json_response(
                {"status": "ERROR", "reason": str(error)}, status=error.status
            )
        return web.json_response({"status": "OK"})

    # ------------------------------------------------------------------ #
    # consume
    # ------------------------------------------------------------------ #
    @staticmethod
    def _record_to_json(record: Record) -> Dict[str, Any]:
        value = record.value
        if isinstance(value, bytes):
            value = value.decode("utf-8", errors="replace")
        offset = ""
        partition = getattr(record, "partition", None)
        if partition is not None:
            offset = f"{partition}-{getattr(record, 'offset', '')}"
        return {
            "record": {
                "key": record.key,
                "value": value,
                "headers": {str(k): str(v) for k, v in record.headers},
            },
            "offset": offset,
        }

    @staticmethod
    def _matches(record: Record, filters: List[Tuple[str, str]]) -> bool:
        return all(str(record.header(k)) == v for k, v in filters)

    async def _consume_loop(
        self, ws, registered, topic: str, filters, position: OffsetPosition
    ) -> None:
        reader = registered.topic_runtime.create_reader(
            {"topic": topic}, position
        )
        await reader.start()
        try:
            while not ws.closed:
                batch = await reader.read(timeout=0.2)
                for record in batch:
                    if not self._matches(record, filters):
                        continue
                    # the frame's build on the loop's thread, up to the
                    # send's await (a span on that thread wraps none);
                    # every socket's reader sees every record, so the
                    # filter above stays outside: one span a frame
                    with tracing.phase(
                        "loop.gateway_out",
                        trace_id=str(record.header(TRACE_ID_HEADER) or ""),
                        index=str(record.header("stream-index", "")),
                    ):
                        frame = self._record_to_json(record)
                    await ws.send_json(frame)
        except (ConnectionResetError, asyncio.CancelledError):
            pass
        finally:
            await reader.close()

    def _consume_filters(self, gateway, parameters, principal):
        return self._resolve_headers(
            gateway.consume_options.get("filters", {}).get("headers"),
            parameters,
            principal,
        )

    async def _ws_consume(self, request) -> web.WebSocketResponse:
        try:
            registered, gateway, parameters, options, credentials = self._resolve(
                request, "consume"
            )
            principal = await self._authenticate(gateway, credentials)
        except GatewayError as error:
            raise web.HTTPBadRequest(text=str(error))
        position = OffsetPosition.LATEST
        if options.get("position") == "earliest":
            position = OffsetPosition.EARLIEST
        filters = self._consume_filters(gateway, parameters, principal)
        ws = web.WebSocketResponse()
        await ws.prepare(request)
        await self._emit_event(registered, gateway, "ClientConnected", parameters)
        consume_task = asyncio.ensure_future(
            self._consume_loop(ws, registered, gateway.topic, filters, position)
        )
        try:
            async for message in ws:
                # client offset acks are accepted and ignored (the reader is
                # positional; reconnect with option:position to replay)
                continue
        finally:
            consume_task.cancel()
            await self._emit_event(registered, gateway, "ClientDisconnected", parameters)
        return ws

    # ------------------------------------------------------------------ #
    # chat (produce + filtered consume on one socket; ChatHandler.java:42)
    # ------------------------------------------------------------------ #
    async def _ws_chat(self, request) -> web.WebSocketResponse:
        try:
            registered, gateway, parameters, _options, credentials = self._resolve(
                request, "chat"
            )
            principal = await self._authenticate(gateway, credentials)
        except GatewayError as error:
            raise web.HTTPBadRequest(text=str(error))
        chat = gateway.chat_options
        questions_topic = chat.get("questions-topic")
        answers_topic = chat.get("answers-topic")
        if not questions_topic or not answers_topic:
            raise web.HTTPBadRequest(
                text="chat gateway requires chat-options.questions-topic and answers-topic"
            )
        headers = self._resolve_headers(chat.get("headers"), parameters, principal)
        ws = web.WebSocketResponse()
        await ws.prepare(request)
        await self._emit_event(registered, gateway, "ClientConnected", parameters)
        consume_task = asyncio.ensure_future(
            self._consume_loop(
                ws, registered, answers_topic, headers, OffsetPosition.LATEST
            )
        )
        try:
            async for message in ws:
                if message.type != WSMsgType.TEXT:
                    continue
                try:
                    # the frame's synchronous part on the loop's thread,
                    # up to the produce's await (which it does not wrap)
                    with tracing.phase("loop.gateway_in") as span:
                        key, value, user_headers = self._parse_produce(
                            message.data
                        )
                        chat_headers, trace_id = self._stamp_trace(
                            tuple(user_headers) + tuple(headers)
                        )
                        record = Record(value=value, key=key, headers=chat_headers)
                        span.set(trace_id=trace_id)
                    with self.tracer.span(
                        "gateway.chat.produce", trace_id=trace_id,
                        gateway=gateway.id, topic=questions_topic,
                    ):
                        await (
                            await registered.producer(questions_topic)
                        ).write(record)
                    self.metrics.counter("records_produced").count()
                except GatewayError as error:
                    await ws.send_json({"status": "BAD_REQUEST", "reason": str(error)})
        finally:
            consume_task.cancel()
            await self._emit_event(registered, gateway, "ClientDisconnected", parameters)
        return ws

    # ------------------------------------------------------------------ #
    # service gateway (topic round-trip; GatewayResource.java:156-190)
    # ------------------------------------------------------------------ #
    async def _proxy_service(
        self, request, base_url: str, suffix: str = ""
    ) -> web.Response:
        """Forward the request to an agent service endpoint and relay the
        response verbatim (the reference's direct-proxy service mode);
        ``option:path`` selects the upstream path."""
        import aiohttp

        body = await request.read()
        target = base_url.rstrip("/") + (
            "/" + suffix.lstrip("/") if suffix else ""
        )
        headers = {}
        if request.content_type:
            headers["Content-Type"] = request.content_type
        try:
            async with aiohttp.ClientSession() as session:
                async with session.request(
                    request.method, target, data=body or None,
                    headers=headers,
                    timeout=aiohttp.ClientTimeout(total=60),
                ) as upstream:
                    payload = await upstream.read()
                    return web.Response(
                        body=payload,
                        status=upstream.status,
                        content_type=upstream.content_type,
                    )
        except aiohttp.ClientError as error:
            return web.json_response(
                {"status": "ERROR", "reason": f"service unreachable: {error}"},
                status=502,
            )

    async def _http_service(self, request) -> web.Response:
        try:
            registered, gateway, parameters, options, credentials = self._resolve(
                request, "service"
            )
            principal = await self._authenticate(gateway, credentials)
        except GatewayError as error:
            return web.json_response(
                {"status": "ERROR", "reason": str(error)}, status=error.status
            )
        service = gateway.service_options
        # direct proxy mode (reference: GatewayResource.java:234,331-345
        # getExecutorServiceURI): forward straight to the agent service
        # pod instead of a topic round trip
        proxy_url = service.get("service-url")
        if not proxy_url and service.get("agent-id"):
            name = (
                f"{registered.application.application_id}-"
                f"{service['agent-id']}"
            )
            tenant = request.match_info["tenant"]
            proxy_url = f"http://{name}.{tenant}.svc:8000"
        if proxy_url:
            return await self._proxy_service(
                request, proxy_url, options.get("path", "")
            )
        input_topic = service.get("input-topic")
        output_topic = service.get("output-topic")
        if not input_topic or not output_topic:
            return web.json_response(
                {"status": "ERROR", "reason": "service gateway needs input/output topics"},
                status=400,
            )
        request_id = uuid.uuid4().hex
        reader = registered.topic_runtime.create_reader(
            {"topic": output_topic}, OffsetPosition.LATEST
        )
        await reader.start()
        key, value, user_headers = self._parse_produce(await request.text())
        service_headers, trace_id = self._stamp_trace(
            tuple(user_headers)
            + (("langstream-service-request-id", request_id),)
        )
        with self.tracer.span(
            "gateway.service.produce", trace_id=trace_id,
            gateway=gateway.id, topic=input_topic,
        ):
            await (await registered.producer(input_topic)).write(
                Record(value=value, key=key, headers=service_headers)
            )
        self.metrics.counter("service_requests").count()
        timeout = float(service.get("timeout-seconds", 30))
        deadline = time.monotonic() + timeout
        try:
            while time.monotonic() < deadline:
                for record in await reader.read(timeout=0.2):
                    if record.header("langstream-service-request-id") == request_id:
                        return web.json_response(self._record_to_json(record))
        finally:
            await reader.close()
        return web.json_response(
            {"status": "ERROR", "reason": "timed out waiting for the response"},
            status=504,
        )
