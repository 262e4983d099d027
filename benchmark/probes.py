"""Spans and counters taken from the benchmark's own files, around the
calls into the engine (as ``chip_smoke._record_results`` does around the
completions service). Nothing here changes what the program computes.
"""

from __future__ import annotations

import time
from typing import Any, Dict


def wrap_engine(engine, by_turn: Dict[tuple, Dict[str, Any]]) -> None:
    """Record, per request: the instant the agent's call reached
    ``engine.generate`` (``engine_submit``), the instant its first token
    came back to the event loop (``engine_first``), its prompt ids and the
    ids it was served. Joined to the client's record by the session id the
    gateway hands down and the turn: a session's n-th call is its n-th
    question."""
    generate = engine.generate
    turns: Dict[Any, int] = {}

    async def recording(prompt_tokens, sampling, *, on_token=None, session_id=None, **kwargs):
        submitted = time.perf_counter()
        turn = turns.get(session_id, 0)
        turns[session_id] = turn + 1
        record = by_turn.setdefault((session_id, turn), {})
        record["engine_submit"] = submitted
        record["prompt_ids"] = list(prompt_tokens)

        def first_token(token_id: int, is_last: bool) -> None:
            record.setdefault("engine_first", time.perf_counter())
            if on_token is not None:
                on_token(token_id, is_last)

        result = await generate(
            prompt_tokens, sampling, on_token=first_token, session_id=session_id, **kwargs
        )
        record["output_ids"] = [int(t) for t in result.tokens]
        record["finish"] = result.finish_reason
        return result

    engine.generate = recording


def counters(engine) -> Dict[str, Any]:
    """A snapshot of the engine's own counters (plain numbers, copied)."""
    stats = engine.stats
    return {
        "at": time.perf_counter(),
        "requests": stats["requests"],
        "tokens_useful": stats["tokens_useful"],
        "tokens_wasted": dict(stats["tokens_wasted"]),
        "requests_shed": dict(stats["requests_shed"]),
        "prefill_calls": stats["prefill_calls"] + stats["warm_prefill_calls"],
        "warm_prefill_calls": stats["warm_prefill_calls"],
        "prefix_hits": stats["prefix_hits"] + stats["session_hits"],
        "decode_steps": stats["decode_steps"],
        "decode_chunks": stats["decode_chunks"],
        "active_slot_steps": stats["active_slot_steps"],
        "chunk_log_len": len(engine.chunk_log),
        "queue_depth": engine.queue_depth,
        "slots_active": sum(1 for s in engine.slots if s.active),
    }
