"""The load generator's client: one conversation over the gateway's chat
WebSocket, on a socket and a session id of its own. The record every
question fills is what every client-side metric is read from; all instants
are ``time.perf_counter()`` of the one process.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import time
from typing import Any, Dict, Optional

import websockets


async def _sleep_until(instant: float) -> None:
    delay = instant - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)


class Chat:
    """``async with Chat(url, limit_s) as talk``: the socket opens with the
    first question and closes on the way out."""

    def __init__(self, url: str, limit_s: float) -> None:
        self.url = url
        self.limit_s = limit_s
        self.socket = None

    async def __aenter__(self) -> "Chat":
        return self

    async def __aexit__(self, *exc) -> None:
        await self._hang_up()

    async def _hang_up(self) -> None:
        socket, self.socket = self.socket, None
        if socket is not None:
            await socket.close()

    async def ask(self, record: Dict[str, Any], due: Optional[float], lead: float) -> None:
        """Send ``record['question']`` (at ``due``, if given; the socket is
        opened ``lead`` before it) and read frames to the terminal one.
        Fills ``record``: ``due``, ``sent``, ``frames`` (instant,
        characters), ``done`` or ``error`` with ``failed_at``. Never raises."""

        async def talk() -> None:
            if due is not None:
                await _sleep_until(due - lead)
            if self.socket is None:
                self.socket = await websockets.connect(self.url, max_size=None)
            if due is not None:
                await _sleep_until(due)
            record["sent"] = time.perf_counter()
            record["due"] = due if due is not None else record["sent"]
            await self.socket.send(json.dumps({"value": record["question"]}))
            async for frame in self.socket:
                now = time.perf_counter()
                body = json.loads(frame).get("record", {})
                record["frames"].append((now, len(str(body.get("value") or ""))))
                if body.get("headers", {}).get("stream-last-message") == "true":
                    record["done"] = now
                    return
            raise RuntimeError("socket closed before the last frame")

        record["frames"] = []
        try:
            budget = self.limit_s + (max(0.0, due - time.perf_counter()) if due else 0.0)
            await asyncio.wait_for(talk(), budget)
        except asyncio.TimeoutError:
            record["error"] = f"no complete answer within {self.limit_s:.0f}s"
        except Exception as error:  # noqa: BLE001 - a failed request is counted, not raised
            record["error"] = repr(error)[:200]
        if "error" in record:
            record["failed_at"] = time.perf_counter()
            record.setdefault("due", due if due is not None else record["failed_at"])
            with contextlib.suppress(Exception):
                await self._hang_up()  # the next question gets a new socket
