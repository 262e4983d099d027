"""The one generator: users who chat with the app, each over a socket of
its own. Everything about a mix is a parameter of its traffic file; this
file drives them all the same way, and the harness never asks which kind
of mix it is running.

A **user** waits for its start, then holds a conversation: ``turns``
questions on one session id over one socket, each sent once the answer to
the one before has ended and ``think_seconds`` have passed, each carrying
the conversation's earlier questions before its own (so a follow-up
shares its history with the engine's cache). Who the users are
(``users``):

- ``{"rate_per_s": r}`` - open loop, stratified: user i starts at
  (i + u_i) / r with u_i ~ U(0, 1) from the seed, one arrival in every
  interval of 1 / r, whether or not earlier ones are answered, and holds
  one conversation. The schedule runs ``window_opens.after_seconds`` of
  warm-up, the window, and ``cooldown_seconds`` after it (sent, not
  counted, so the window's last requests decode under load). Each of the
  three has a multiset of questions of its own, so every seed offers the
  window exactly the same work.
- ``{"per_slot": n, "start_interval_seconds": d}`` - closed loop: n users
  for every slot of the configuration, user i starts i x d after the
  first, and each begins its next conversation the instant the last one
  ends, until the window closes. The conversations are one sequence of
  ``sequence`` (more than a run can finish), taken in order by whichever
  user is free. Started all at once, users whose answers have the same
  number of tokens fill and empty the slots in one wave for the rest of
  the run, and a window cuts such lumps at random: hence the interval.

When the window opens (``window_opens``): ``{"after_seconds": s}`` of an
arrival schedule, or, for a population, ``{"after_finished_per_slot": k}``
once k requests for every slot have ended. Which requests a run counts (``counted_by``): ``due`` -
every request due in the window, answered or not - or ``ended`` - every
request that ended in it.

Also read from the file, every one required: ``turns``, ``think_seconds``,
``cooldown_seconds``, ``connect_lead_seconds`` (a scheduled user opens its
socket this long before it is due, so that the send falls on the due
instant), ``request_limit_seconds``, ``trace_seconds`` and ``prompts``
(``generators/prompts.py``).
"""

from __future__ import annotations

import asyncio
import random
import time
from typing import Any, Callable, Dict, List, Optional

from . import prompts


class Plan:
    """Everything a run will send, laid out from the seed, and the users
    that send it."""

    def __init__(self, traffic: Dict[str, Any], seed: int, seconds: float, slots: int,
                 first_index: int = 0) -> None:
        # a tool that plans twice on one app numbers the second plan's
        # requests after the first's, so that no two questions are alike
        self.first_index = first_index
        self.spec = traffic["prompts"]
        self.turns = int(traffic["turns"])
        self.think_s = float(traffic["think_seconds"])
        self.lead = float(traffic["connect_lead_seconds"])
        self.requests: List[Dict[str, Any]] = []
        # a user: its start in seconds after the first, and the pool it takes
        # its conversations from; a pool: conversations nobody has begun
        self.users: List[Dict[str, Any]] = []
        self.pools: Dict[str, List[List[Dict[str, Any]]]] = {}
        self.finished = 0
        self.stopped = False
        self.tasks: List[asyncio.Task] = []
        users, opens = traffic["users"], traffic["window_opens"]
        self.opens_after_s: Optional[float] = None
        self.opens_after_finished: Optional[int] = None
        if "rate_per_s" in users:
            rate = float(users["rate_per_s"])
            spans = (float(opens["after_seconds"]), seconds, float(traffic["cooldown_seconds"]))
            counts = [round(rate * span) for span in spans]
            rng = random.Random(f"{seed}:arrivals")
            for phase, count in zip(("warmup", "window", "cooldown"), counts):
                for conversation in self._lay_out(phase, count, seed):
                    number = len(self.users)
                    conversation[0]["due_s"] = (number + rng.random()) / rate
                    self.users.append({
                        "start_s": conversation[0]["due_s"], "pool": phase, "again": False,
                    })
            self.opens_after_s = counts[0] / rate
            self.window_seconds = counts[1] / rate
        else:
            self._lay_out("sequence", int(traffic["sequence"]), seed)
            interval = float(users["start_interval_seconds"])
            self.users = [
                {"start_s": i * interval, "pool": "sequence", "again": True}
                for i in range(int(users["per_slot"]) * slots)
            ]
            self.window_seconds = float(seconds)
            self.opens_after_finished = int(opens["after_finished_per_slot"]) * slots

    def _lay_out(self, pool: str, count: int, seed: int) -> List[List[Dict[str, Any]]]:
        """``count`` conversations of ``turns`` requests into ``pool``."""
        first = self.first_index + len(self.requests)
        indexes = [
            [first + c * self.turns + turn for turn in range(self.turns)]
            for c in range(count)
        ]
        conversations = []
        for c, asked in enumerate(prompts.conversations(self.spec, indexes, seed, pool)):
            conversations.append([
                {
                    "index": indexes[c][turn], "session": first // self.turns + c,
                    "turn": turn, "phase": pool,
                    "question": prompts.message(self.spec, asked[: turn + 1]),
                }
                for turn in range(self.turns)
            ])
            self.requests += conversations[-1]
        self.pools[pool] = list(conversations)
        return conversations

    async def _user(self, user, chat: Callable[[int], Any], t0: float) -> None:
        due: Optional[float] = t0 + user["start_s"]
        pool = self.pools[user["pool"]]
        while pool:
            conversation = pool.pop(0)
            if due is not None:
                conversation[0]["due"] = due  # counts from now on, sent or not
            async with chat(conversation[0]["session"]) as talk:
                for record in conversation:
                    await talk.ask(record, due, self.lead)
                    self.finished += 1
                    due = None  # a follow-up is due when it is sent
                    if self.think_s and record is not conversation[-1]:
                        await asyncio.sleep(self.think_s)
            if self.stopped or not user["again"]:
                return

    async def start(self, chat: Callable[[int], Any]) -> float:
        """Start every user (``chat(session)`` gives the client's socket for
        one conversation), warm up - still set-up - and return the window's
        first instant."""
        t0 = time.perf_counter() + 0.2
        self.tasks = [
            asyncio.ensure_future(self._user(user, chat, t0)) for user in self.users
        ]
        if self.opens_after_s is not None:
            opens = t0 + self.opens_after_s
            await asyncio.sleep(max(0.0, opens - time.perf_counter()))
            return opens
        while self.finished < self.opens_after_finished:
            if all(task.done() for task in self.tasks):
                raise RuntimeError("the users ended during warm-up")
            await asyncio.sleep(0.01)
        return time.perf_counter()

    def stop(self) -> None:
        """At the window's close: no user begins a further conversation
        (one whose start is scheduled after the close still holds its own:
        the cool-down)."""
        self.stopped = True

    async def cancel(self) -> None:
        for task in self.tasks:
            task.cancel()
        await asyncio.gather(*self.tasks, return_exceptions=True)


def plan(traffic: Dict[str, Any], seed: int, seconds: float, slots: int,
         first_index: int = 0) -> Plan:
    return Plan(traffic, seed, seconds, slots, first_index)
