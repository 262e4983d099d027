"""Continuous-batching decode engine — the serving core of ``jax-local``.

Design (TPU-first, see SURVEY.md §7 phase 4/5):

- **Slot-based static batch**: the KV cache holds ``max_slots`` sequences
  of ``max_seq_len``; every decode step runs ALL slots through one jitted
  ``decode_step`` — static shapes, one compilation, MXU-friendly batched
  matmuls. Empty slots ride along masked (their tokens are ignored), so
  admission/retirement never recompiles.
- **Continuous batching**: requests join mid-flight. A joining request
  prefills into its slot (bucketed prompt lengths → few compilations) while
  other slots keep decoding; a finishing request frees its slot
  immediately. No batch barrier — exactly the property the runner's
  emit-as-you-complete contract preserves upstream.
- **Dedicated device thread**: the asyncio side enqueues requests
  (thread-safe) and receives per-token callbacks on its own loop. The
  engine thread records a harvested chunk's callbacks while it does the
  chunk's bookkeeping and hands them over with ONE
  ``loop.call_soon_threadsafe`` a loop when the bookkeeping is done
  (:meth:`DecodeEngine._hand_over`); the loop then runs one request's
  callbacks an iteration (:class:`_LoopInbox`). Device dispatch never
  blocks the event loop.
- **Session KV reuse** (BASELINE config #5): a finished request may pin its
  slot under a session id; a follow-up with the same session id whose
  prompt extends the pinned history skips re-prefilling the shared prefix
  (teacher-forced suffix only). Keyed by record key upstream, so broker
  partitioning gives replica affinity.
- **In-jit sampling**: greedy / temperature / top-k / top-p sampling,
  presence & frequency penalties, logit_bias, and per-request seeded
  keys all run on device inside the decode jit (tiered with ``lax.cond``
  so greedy traffic skips the sort); only the sampled token ids [S]
  cross to host per chunk.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import dataclasses
import logging
import os
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from langstream_tpu.parallel.mesh import (
    MeshConfig,
    build_mesh,
    param_shardings,
    shard_params,
    validate_mesh,
)
from langstream_tpu.api import errors as api_errors
from langstream_tpu.providers.jax_local import model as model_lib
from langstream_tpu.runtime import faults, flight, tracing
from langstream_tpu.runtime.tracing import get_tracer

logger = logging.getLogger(__name__)


def _program(kind: str, **jit_options):
    """``jax.jit`` under a stable name of the engine's own: the
    profiler's ``XLA Modules`` line, the compile log and the dispatch
    path's ``PjitFunction`` then read ``jit_<kind>`` where every closure
    used to be ``jit_run``. A name only: what is traced, donated and
    compiled is as before. ``PROGRAM_KINDS`` lists them."""

    def wrap(fn):
        fn.__name__ = fn.__qualname__ = kind
        return jax.jit(fn, **jit_options)

    return wrap


# every program the engine dispatches, by what it does and the cache
# layout it serves; a trace reader tells prefills from decode chunks by
# these prefixes (``jit_prefill_``, ``jit_decode_chunk_``)
PROGRAM_KINDS = (
    "init_cache_paged", "init_cache_dense",
    "prefill_paged", "prefill_dense",
    "prefill_offset_paged", "prefill_offset_dense",
    "decode_chunk_paged", "decode_chunk_dense",
    "spec_decode_chunk_paged", "spec_decode_chunk_dense",
    "mixed_step_paged", "copy_prefix_dense", "block_copy_paged",
    "handoff_export_paged", "handoff_import_paged", "counts_restore",
)

# live engines, for /metrics exposure (weak: a stopped engine's buffers
# must not be pinned by the metrics path)
import weakref

_LIVE_ENGINES: "weakref.WeakSet" = weakref.WeakSet()

# decode-step latency histogram across every engine in the process
# (observed once per chunk at wall/steps; buckets tuned to the ms range
# a decode step lives in)
from langstream_tpu.api.metrics import Histogram
from langstream_tpu.runtime import accounting
from langstream_tpu.runtime import journey as journey_ledger

DECODE_STEP_SECONDS = Histogram(
    "jax_engine_decode_step_seconds",
    buckets=(0.001, 0.002, 0.005, 0.01, 0.02, 0.035, 0.05, 0.075,
             0.1, 0.15, 0.25, 0.5, 1.0),
)
# per-request latency histograms: TTFT (submit → first token), TPOT
# (mean inter-token gap), end-to-end. Observed at _finish; the SLO
# burn-rate tracker reads timestamped snapshots of these same buckets
TTFT_SECONDS = Histogram(
    "jax_engine_ttft_seconds",
    buckets=(0.01, 0.025, 0.05, 0.1, 0.2, 0.35, 0.5, 0.75, 1.0,
             2.0, 5.0, 10.0, 30.0),
)
TPOT_SECONDS = Histogram(
    "jax_engine_tpot_seconds",
    buckets=(0.002, 0.005, 0.01, 0.02, 0.035, 0.05, 0.075, 0.1,
             0.15, 0.25, 0.5, 1.0),
)
REQUEST_SECONDS = Histogram("jax_engine_request_seconds")
# per-chunk roofline utilization (fractions of the per-chip peak):
# MFU = model FLOP utilization, MBU = HBM-bandwidth utilization
_UTIL_BUCKETS = (0.01, 0.02, 0.05, 0.1, 0.15, 0.2, 0.3, 0.4, 0.5,
                 0.6, 0.7, 0.8, 0.9, 1.0)
MFU_PER_CHUNK = Histogram("jax_engine_mfu_per_chunk", buckets=_UTIL_BUCKETS)
MBU_PER_CHUNK = Histogram("jax_engine_mbu_per_chunk", buckets=_UTIL_BUCKETS)


def _supervisor_module():
    """The supervisor module iff something in this process already
    imported it — the ONE gate that keeps unsupervised processes from
    ever paying for (or exporting) the self-healing metric families."""
    import sys as _sys

    return _sys.modules.get("langstream_tpu.runtime.supervisor")


def engines_histograms():
    out = {
        h.name: h.snapshot()
        for h in (
            DECODE_STEP_SECONDS, TTFT_SECONDS, TPOT_SECONDS,
            REQUEST_SECONDS, MFU_PER_CHUNK, MBU_PER_CHUNK,
        )
    }
    # per-stage journey histograms (ISSUE 20) and recovery_seconds ride
    # every surface the engine histograms reach (runner pods, the
    # OpenAI server, the gateway)
    out.update(journey_ledger.stage_histograms())
    supervisor_mod = _supervisor_module()
    if supervisor_mod is not None:
        out.update(supervisor_mod.supervisor_histograms())
    return out


def engines_snapshot() -> Dict[str, float]:
    """Prometheus-gauge view over every live engine in this process:
    decode-step latency, slot occupancy, token/prefill counters
    (reference: AgentRunner.java:99-113 exposes runtime internals the
    same way; here the runtime internal is the TPU engine)."""
    out: Dict[str, float] = {}
    tokens = handovers = steps = chunks = 0
    session_hits = prefix_hits = prefix_tokens = 0
    decode_time = prefill_time = 0.0
    prefill_rows = prefill_join_rows = 0
    prefill_join_wait = 0.0
    long_prompts_held = prompts_windowed = 0
    loop_seconds = {"idle": 0.0, "admit": 0.0, "dispatch": 0.0, "emit": 0.0}
    loop_cpu = dict.fromkeys(loop_seconds, 0.0)
    active_slot_steps = total_slot_steps = 0
    paged_engines = 0
    kv_blocks_in_use = kv_blocks_total = 0
    prefix_hit_tokens = prefix_evictions = 0
    handoff_exported_bytes = handoff_imported_bytes = 0
    handoff_exports = handoff_imports = handoff_imported_tokens = 0
    host_engines = 0
    kv_host_blocks_in_use = kv_host_blocks_total = 0
    host_demotions = host_promotions = host_evictions = 0
    host_demote_bytes = host_promote_bytes = 0
    kv_host_hit_tokens = host_promote_aborts = 0
    useful_tokens = 0
    wasted: Dict[str, int] = {
        reason: 0
        for reason in (
            "cancelled", "evicted_recompute", "draft_rejected",
            # supervisor resurrection: tokens re-prefilled to fast-
            # forward a crashed session back to its pre-crash state
            "crash_replay",
            # prompt-padding ghosts: split-path bucket rounding (up to
            # ~2x a prompt's FLOPs at the worst bucket edge) vs the
            # mixed path's ≤ width−1 per window — the padding win the
            # chunked-prefill A/B is judged on
            "prefill_padding",
            # mixed-step carry: tokens a speculatively chained step
            # sampled for rows whose request had already stopped or
            # been cancelled by the time the step was host-processed
            "carry_invalidated",
            # prefill/decode disaggregation: tokens whose KV handoff
            # aborted (pool pressure / torn payload / layout mismatch)
            # and had to be re-prefilled on the decode replica
            "handoff_aborted",
        )
    }
    shed_engines = 0
    shed: Dict[str, int] = {"queue_timeout": 0}
    spec_engines = 0
    spec_drafted = spec_accepted = 0
    mixed_engines = 0
    mixed_chained = 0
    # mixed-step carry: why speculative chains broke — pre-seeded so
    # every series exists before the first event (rate() alerts)
    carry_invalidations: Dict[str, int] = {
        reason: 0
        for reason in (
            "admission", "replay", "budget", "epoch", "condemned",
            "width", "drained", "stale_row",
        )
    }
    decode_flops = decode_bytes = prefill_flops = 0.0
    peaks: Optional[accounting.PeakSpecs] = None
    # snapshot-tolerant reads of engine-thread-owned state: a supervisor
    # rebuild registers the replacement engine FROM the dying engine
    # thread, and the engine thread inserts new wasted/shed reasons
    # lazily — iterating either container live from a scrape thread can
    # raise "changed size during iteration" (the build_heartbeat race
    # class, PR 10). stable_list/stable_items retry the snapshot.
    from langstream_tpu.utils.threadsafe import stable_items, stable_list

    live_engines = stable_list(_LIVE_ENGINES)
    for engine in live_engines:
        stats = engine.stats
        tokens += stats["tokens_generated"]
        handovers += stats["emit_handovers"]
        steps += stats["decode_steps"]
        chunks += stats["decode_chunks"]
        decode_time += stats["decode_time"]
        prefill_time += stats["prefill_time"]
        prefill_rows += stats["prefill_rows"]
        prefill_join_rows += stats["prefill_join_rows"]
        prefill_join_wait += stats["prefill_join_wait"]
        long_prompts_held += stats["long_prompts_held"]
        prompts_windowed += stats["prompts_windowed"]
        for phase_name in loop_seconds:
            loop_seconds[phase_name] += stats[phase_name + "_time"]
            loop_cpu[phase_name] += stats[phase_name + "_cpu"]
        active_slot_steps += stats["active_slot_steps"]
        total_slot_steps += stats["decode_steps"] * engine.max_slots
        session_hits += stats["session_hits"]
        prefix_hits += stats["prefix_hits"]
        prefix_tokens += stats["prefix_tokens_reused"]
        useful_tokens += stats["tokens_useful"]
        for reason, count in stable_items(stats["tokens_wasted"]):
            wasted[reason] = wasted.get(reason, 0) + count
        if engine.queue_timeout_s:
            shed_engines += 1
        for reason, count in stable_items(stats.get("requests_shed", {})):
            shed[reason] = shed.get(reason, 0) + count
        decode_flops += stats["decode_flops"]
        decode_bytes += stats["decode_bytes"]
        prefill_flops += stats["prefill_flops"]
        peaks = engine.peaks
        if engine.slo is not None:
            # SLO targets + multi-window burn rates: visible from the
            # first scrape (targets are config, not traffic)
            out.update(engine.slo.gauges())
        reader = getattr(engine, "decode_reader", None)
        if reader is not None:
            # engines by what reads the cache in their decode step and how
            # a cache leaf lies (settled at construction)
            key = (
                f'jax_engine_decode_reader{{reader="{reader}",cache="'
                + "x".join(map(str, engine.cache_leaf_shape)) + '"}'
            )
            out[key] = out.get(key, 0.0) + 1.0
        config = getattr(engine, "config", None)
        experts = getattr(config, "experts", None)
        if experts is not None:
            # routed experts held here: what the router assigned, what
            # met a held expert, what the expert matmuls computed
            first = experts.held_first
            counted = [
                (f"jax_engine_{name}_total", stats[name])
                for name in (
                    "moe_assignments", "moe_assignments_held",
                    "moe_rows_computed",
                )
            ] + [
                (
                    "jax_engine_moe_tokens_by_expert_total"
                    f'{{expert="{first + offset}"}}',
                    count,
                )
                for offset, count in enumerate(
                    list(stats["moe_tokens_by_expert"])
                )
            ]
            for key, count in counted:
                out[key] = out.get(key, 0.0) + float(count)
        if getattr(config, "mixers", None) is not None:
            # a carried state's resets (recurrent, conv) and, for the
            # hybrid family, its block selection
            selection = (
                ("sparse_kept", "sparse_visible", "sparse_queries")
                if config.hybrid is not None else ()
            )
            for name in selection + ("state_resets",):
                key = f"jax_engine_{name}_total"
                out[key] = out.get(key, 0.0) + float(stats[name])
        if getattr(engine, "spec", False):
            spec_engines += 1
            spec_drafted += stats["tokens_drafted"]
            spec_accepted += stats["tokens_draft_accepted"]
        if getattr(engine, "mixed", False):
            mixed_engines += 1
            mixed_chained += stats.get("mixed_steps_chained", 0)
            for reason, count in stable_items(
                stats.get("mixed_carry_invalidations", {})
            ):
                carry_invalidations[reason] = (
                    carry_invalidations.get(reason, 0) + count
                )
        if getattr(engine, "kv_manager", None) is not None:
            paged_engines += 1
            kv_blocks_in_use += engine.kv_manager.blocks_in_use
            kv_blocks_total += engine.num_blocks
            prefix_hit_tokens += engine.kv_manager.stats["hit_tokens"]
            prefix_evictions += engine.kv_manager.stats["evictions"]
            handoff_exports += stats.get("handoff_exports", 0)
            handoff_exported_bytes += stats.get("handoff_export_bytes", 0)
            handoff_imports += stats.get("handoff_imports", 0)
            handoff_imported_bytes += stats.get("handoff_import_bytes", 0)
            handoff_imported_tokens += stats.get(
                "handoff_import_tokens", 0
            )
            arena = getattr(engine, "kv_host_arena", None)
            if arena is not None:
                host_engines += 1
                arena_stats = arena.snapshot_stats()
                kv_host_blocks_in_use += arena_stats["blocks_in_use"]
                kv_host_blocks_total += arena.capacity_blocks
                host_evictions += arena_stats["evictions"]
                host_demotions += stats.get("host_demotions", 0)
                host_demote_bytes += stats.get("host_demote_bytes", 0)
                host_promotions += stats.get("host_promotions", 0)
                host_promote_bytes += stats.get("host_promote_bytes", 0)
                kv_host_hit_tokens += stats.get("kv_host_hit_tokens", 0)
                host_promote_aborts += stats.get("host_promote_aborts", 0)
    if live_engines:
        # watchdog trips ride the engine exposition so every scrape
        # surface sees them (0 included — the series must exist BEFORE
        # the first trip for rate() alerts to work); lazy import keeps
        # engine import free of the watchdog module at load time
        from langstream_tpu.runtime.watchdog import trips_total

        out["watchdog_trips_total"] = float(trips_total())
        # admission backlog: the fleet layer's routing/scaling signal
        # (fleet/router.py least-queue fallback, fleet/autoscaler.py
        # queue pressure) — exposed from construction so an idle
        # replica scrapes 0, not no-data
        out["jax_engine_queue_depth"] = float(
            sum(engine.queue_depth for engine in live_engines)
        )
    if paged_engines:
        # paged KV pool + persistent prefix cache (kv_layout: paged):
        # pool capacity/pressure are known from construction, so these
        # are exposed BEFORE the first token — an operator verifying a
        # freshly sized-down pool must not scrape no-data
        out["kv_blocks_in_use"] = float(kv_blocks_in_use)
        out["kv_blocks_total"] = float(kv_blocks_total)
        out["prefix_cache_hit_tokens_total"] = float(prefix_hit_tokens)
        out["prefix_cache_evictions_total"] = float(prefix_evictions)
        # paged-KV handoff (prefill/decode disaggregation): exposed
        # from construction on every paged engine so the disagg A/B
        # never scrapes no-data, and a decode replica importing nothing
        # (routing misconfigured) is visible as a flat zero
        out["kv_handoff_exports_total"] = float(handoff_exports)
        out["kv_handoff_exported_bytes_total"] = float(
            handoff_exported_bytes
        )
        out["kv_handoff_imports_total"] = float(handoff_imports)
        out["kv_handoff_imported_bytes_total"] = float(
            handoff_imported_bytes
        )
        out["kv_handoff_imported_tokens_total"] = float(
            handoff_imported_tokens
        )
    if host_engines:
        # tiered KV pool (kv-host-blocks > 0): host-arena capacity /
        # pressure and the demote/promote traffic each way — gated on
        # the tier being configured so an un-tiered deployment's
        # exposition is byte-identical to pre-tier builds. Exposed from
        # construction: a freshly sized host arena must scrape 0, not
        # no-data, and kv_host_hit_tokens_total is the goodput-ledger
        # companion (promotions that replaced eviction recompute)
        out["kv_host_blocks_in_use"] = float(kv_host_blocks_in_use)
        out["kv_host_blocks_total"] = float(kv_host_blocks_total)
        out["kv_host_demotions_total"] = float(host_demotions)
        out["kv_host_demoted_bytes_total"] = float(host_demote_bytes)
        out["kv_host_promotions_total"] = float(host_promotions)
        out["kv_host_promoted_bytes_total"] = float(host_promote_bytes)
        out["kv_host_hit_tokens_total"] = float(kv_host_hit_tokens)
        out["kv_host_promote_aborts_total"] = float(host_promote_aborts)
        out["kv_host_evictions_total"] = float(host_evictions)
    if spec_engines:
        # speculative decoding (spec-decode: ngram): drafted/accepted
        # counters + the acceptance rate — exposed from construction so
        # an operator A/B-ing the knob never scrapes no-data, and a
        # collapsed acceptance rate (workload without repetition) is
        # visible before anyone reads a flight artifact
        out["spec_tokens_drafted_total"] = float(spec_drafted)
        out["spec_tokens_accepted_total"] = float(spec_accepted)
        out["spec_acceptance_rate"] = round(
            spec_accepted / spec_drafted, 4
        ) if spec_drafted else 0.0
    if mixed_engines:
        # mixed-step carry (prefill_mode: mixed): chained-step counter +
        # per-reason chain-break counters — exposed from construction so
        # the carry A/B never scrapes no-data, and a chain rate stuck at
        # zero (carry off / constant invalidation) is visible without
        # reading a flight artifact. NOTE process-global gauges: tests
        # must assert DELTAS, not absolutes (other live engines count).
        out["jax_engine_mixed_steps_chained_total"] = float(mixed_chained)
        for reason, count in sorted(carry_invalidations.items()):
            out[
                f'mixed_carry_invalidations_total{{reason="{reason}"}}'
            ] = float(count)
    if shed_engines or any(shed.values()):
        # admission deadlines armed (or sheds already happened): the
        # series must exist BEFORE the first shed so rate() alerts work
        for reason, count in sorted(shed.items()):
            out[f'requests_shed_total{{reason="{reason}"}}'] = float(count)
    # self-healing plane (runtime/supervisor.py): restart/resurrection
    # counters + the degraded-mode gauge — exposed even with ZERO live
    # engines, because mid-rebuild (old engine retired, new one still
    # compiling) is exactly when an operator scrapes for it
    supervisor_mod = _supervisor_module()
    if supervisor_mod is not None:
        out.update(supervisor_mod.supervisor_gauges())
    if not (tokens or steps):
        return out
    out["jax_engine_session_hits"] = float(session_hits)
    out["jax_engine_prefix_hits"] = float(prefix_hits)
    out["jax_engine_prefix_tokens_reused"] = float(prefix_tokens)
    out["jax_engine_tokens_generated"] = float(tokens)
    # cross-thread posts that carried them to their callers' loops
    out["jax_engine_emit_handovers_total"] = float(handovers)
    out["jax_engine_decode_steps"] = float(steps)
    out["jax_engine_decode_chunks"] = float(chunks)
    out["jax_engine_decode_time_seconds"] = round(decode_time, 6)
    out["jax_engine_prefill_time_seconds"] = round(prefill_time, 6)
    # the engine thread's own seconds by phase, summed at the phase
    # spans' boundaries (docs/observability.md §1)
    for phase_name, seconds in loop_seconds.items():
        out[
            f'jax_engine_loop_seconds_total{{phase="{phase_name}"}}'
        ] = round(seconds, 6)
    # the thread's CPU seconds at the same boundaries: wall minus CPU is
    # what it spent off the CPU inside a phase (the GIL, a blocking call)
    for phase_name, seconds in loop_cpu.items():
        out[
            f'jax_engine_loop_cpu_seconds_total{{phase="{phase_name}"}}'
        ] = round(seconds, 6)
    # split prefills and how many rode the decode dispatch behind them
    # (docs/observability.md §1: the loop harvests before it dispatches)
    out["jax_engine_prefill_rows_total"] = float(prefill_rows)
    out["jax_engine_prefill_join_rows_total"] = float(prefill_join_rows)
    out["jax_engine_prefill_join_wait_seconds_total"] = round(
        prefill_join_wait, 6
    )
    # cycles in which a prompt past the largest bucket waited for the
    # decode chunk behind another's windows (_admit: one a cycle)
    out["jax_engine_long_prompts_held_total"] = float(long_prompts_held)
    # cold prompts that fit a bucket, taught in windows of a smaller one
    out["jax_engine_prompts_windowed_total"] = float(prompts_windowed)
    if steps:
        out["jax_engine_decode_ms_per_step"] = round(
            decode_time / steps * 1e3, 4
        )
    if total_slot_steps:
        out["jax_engine_slot_occupancy"] = round(
            active_slot_steps / total_slot_steps, 4
        )
    # goodput ledger: every generated token classified useful vs wasted
    # (labeled by reason); the ratio is the fleet's headline efficiency
    out["jax_engine_tokens_useful_total"] = float(useful_tokens)
    for reason, count in sorted(wasted.items()):
        out[
            f'jax_engine_tokens_wasted_total{{reason="{reason}"}}'
        ] = float(count)
    accounted = useful_tokens + sum(wasted.values())
    if accounted:
        out["jax_engine_goodput_ratio"] = round(
            useful_tokens / accounted, 4
        )
    # roofline utilization over all decode work so far: cumulative
    # modeled FLOPs/bytes divided by busy decode wall time and the
    # per-chip peak (per-chunk values feed the MFU/MBU histograms)
    if peaks is not None and decode_time > 0:
        out["jax_engine_mfu"] = round(
            accounting.CostModel.mfu(decode_flops, decode_time, peaks), 6
        )
        out["jax_engine_mbu"] = round(
            accounting.CostModel.mbu(decode_bytes, decode_time, peaks), 6
        )
    if peaks is not None and prefill_time > 0 and prefill_flops:
        # prefill is FLOPs-bound and runs in separate dispatches —
        # folding it into jax_engine_mfu would blur both numbers, so a
        # prefill-heavy workload gets its own utilization gauge
        out["jax_engine_prefill_mfu"] = round(
            accounting.CostModel.mfu(prefill_flops, prefill_time, peaks), 6
        )
    return out


@dataclasses.dataclass
class SamplingParams:
    temperature: float = 0.0  # 0 = greedy
    top_k: int = 0            # 0 = no top-k
    top_p: float = 0.0        # 0 = no nucleus truncation
    max_new_tokens: int = 256
    # OpenAI-style repetition penalties over the GENERATED tokens (the
    # engine keeps a per-slot token-count array on device):
    # presence subtracts a flat amount from every already-seen token's
    # logit; frequency subtracts count × the amount
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # per-request RNG seed (OpenAI `seed`): sampling keys derive from
    # (seed, cache position), so a seeded request reproduces its tokens
    # EXACTLY regardless of what else shares the batch. None = a fresh
    # auto-seed per request (still independent of batch composition).
    seed: Optional[int] = None
    # OpenAI `logit_bias`: token id → additive logit adjustment,
    # applied before sampling (±100 effectively forces/bans a token).
    # Capped at DecodeEngine.MAX_LOGIT_BIAS entries per request.
    logit_bias: Optional[Dict[int, float]] = None


@dataclasses.dataclass
class GenerationRequest:
    prompt_tokens: List[int]
    sampling: SamplingParams
    stop_tokens: Set[int] = dataclasses.field(default_factory=set)
    # called with (token_id, is_last): on ``loop``, a harvested chunk's
    # calls for this request in one callback (DecodeEngine._hand_over);
    # on the engine thread at the token's bookkeeping when loop is None
    on_token: Optional[Callable[[int, bool], None]] = None
    session_id: Optional[str] = None
    future: Optional[Any] = None  # asyncio.Future or concurrent future
    loop: Optional[Any] = None
    # set from ANY thread via cancel(); the engine finishes the request
    # with reason "cancelled" at the next token boundary (or drops it
    # from the queue before admission), freeing the slot for others
    cancelled: bool = False
    # end-to-end trace context (langstream-trace-id record header /
    # x-langstream-trace-id HTTP header): the engine tags its
    # admission/prefill/request spans with it so one id links the
    # gateway, the runner, and the device timeline
    trace_id: Optional[str] = None
    # session resurrection (runtime/supervisor.py): tokens the crashed
    # predecessor engine had already ACCEPTED for this request. The
    # supervisor rewrites ``prompt_tokens`` to prompt + replay[:-1]
    # (teacher-forced through a normal prefill — the paged prefix cache
    # makes it cheap) and the harvest path fast-forwards the slot
    # through them instead of emitting a fresh sample: sampling keys
    # derive from (seed, position) and penalty counts are restored
    # position-exactly, so the continuation is bitwise identical to the
    # uncrashed oracle. ``prompt_len`` preserves the ORIGINAL prompt
    # length across (repeated) resurrections for usage accounting.
    replay_tokens: Optional[List[int]] = None
    replay_logprobs: Optional[List[float]] = None
    replay_tops: Optional[List[Tuple[List[int], List[float]]]] = None
    prompt_len: Optional[int] = None
    # prefill/decode disaggregation (fleet/handoff.py): a prefill-leg
    # request asks the engine to export the session's published KV
    # chain at finish (rides GenerationResult.kv_handoff); a decode-leg
    # replay request carries the assembled handoff payload, imported
    # into the pool at admission so the replay prefill hits the prefix
    # cache for the full prompt instead of recomputing it
    export_handoff: bool = False
    kv_import: Optional[Dict[str, Any]] = None
    # journey ledger (ISSUE 20): the prefill replica's manifest export
    # stamp (wall seconds), threaded onto the decode-leg request so the
    # engine can emit a ``handoff_transit`` stage — fabric time between
    # the export and this replica's import — in its journey record
    handoff_export_ts: Optional[float] = None
    # tokens its ``on_token`` has been called with on ``loop``
    # (``_Delivery.run``): the delivery that starts at 0 carries the
    # first token (``loop.deliver``'s ``first``)
    delivered: int = 0

    def cancel(self) -> None:
        self.cancelled = True


@dataclasses.dataclass
class GenerationResult:
    tokens: List[int]
    prompt_tokens: int
    finish_reason: str = "stop"
    # dispatch-to-harvest age of this request's prefill: since prefill
    # overlaps decode, this is the first-token admission latency the
    # caller experienced, NOT pure device prefill compute time
    prefill_time: float = 0.0
    decode_time: float = 0.0
    # per-token log-probability under the untruncated distribution,
    # aligned 1:1 with ``tokens`` (consumed by the FLARE controller;
    # reference: FlareControllerAgent.java logprobs field)
    logprobs: List[float] = dataclasses.field(default_factory=list)
    # per-token top-K alternatives (OpenAI `top_logprobs`): one
    # (token_ids, logprobs) pair per generated token, or None when the
    # engine runs with logprobs_topk=0
    top_logprobs: Optional[List[Tuple[List[int], List[float]]]] = None
    # disaggregation prefill leg (request.export_handoff): the session's
    # published KV chain serialized for the topic fabric — tokens +
    # per-leaf pool rows (fleet/handoff.py chunks it into records)
    kv_handoff: Optional[Dict[str, Any]] = None


@dataclasses.dataclass
class _Slot:
    request: Optional[GenerationRequest] = None
    length: int = 0                 # valid cache length
    generated: Optional[List[int]] = None
    logprobs: Optional[List[float]] = None  # parallel to ``generated``
    tops: Optional[List[Tuple[List[int], List[float]]]] = None  # top-K
                                            # alternatives per token
    history: Optional[List[int]] = None  # full token history in cache
    blocks: Optional[List[int]] = None   # paged layout: this slot's pool
                                         # blocks, in sequence order
    session_id: Optional[str] = None     # pinned session (slot free but warm)
    last_used: float = 0.0               # monotonic; drives LRU eviction
    epoch: int = 0                       # bumps on assign/finish; guards
                                         # pipelined results for recycled slots
    prefilling: bool = False             # prefill dispatched, first token
                                         # not yet harvested
    # mixed dispatch (prefill_mode: mixed): next prompt index a mixed
    # step should teach (None = not admitting through the mixed path);
    # successive decode steps carry prefill_chunk-token windows until
    # the watermark reaches the prompt end
    prefill_pos: Optional[int] = None
    prefill_seq: int = 0                 # admission order (FIFO budget share)
    prefill_t0: float = 0.0              # admission ts (prefill_time anchor)
    prefill_reused: int = 0              # cache-served prefix at admission

    @property
    def active(self) -> bool:
        return self.request is not None

    @property
    def ready(self) -> bool:
        """Participating in decode chunks (prefill result harvested)."""
        return self.request is not None and not self.prefilling


class _Delivery:
    """One request's part of a hand-over to its loop: its ``on_token``
    arguments in order, then its result (or the error that fails it)."""

    __slots__ = ("request", "calls", "result", "error")

    def __init__(
        self, request: "GenerationRequest",
        error: Optional[BaseException] = None,
    ) -> None:
        self.request = request
        self.calls: List[Tuple[int, bool]] = []
        self.result: Optional[GenerationResult] = None
        self.error = error

    def run(self) -> None:
        """On the request's loop: one callback a token, then the future.
        A callback that raises loses its own token only, as when each
        was a callback of the loop's."""
        request = self.request
        request.delivered += len(self.calls)
        for token, done in self.calls:
            try:
                request.on_token(token, done)
            except Exception:  # noqa: BLE001
                logger.exception("on_token callback raised")
        future = request.future
        if future is None or future.done():
            return
        if self.error is not None:
            future.set_exception(self.error)
        elif self.result is not None:
            future.set_result(self.result)


class _LoopInbox:
    """The loop's end of every hand-over to it: deliveries in the order
    they were posted, ONE run an iteration of the loop. asyncio runs
    every callback that is ready when an iteration starts before it
    polls its sockets and timers again, so a chunk's deliveries posted
    as so many ``call_soon`` would hold the loop for the whole chunk
    (64 requests x 32 tokens); chained, the loop polls between two
    requests, and the select it makes there is where the engine thread
    gets the GIL back. Touched on the loop's thread only."""

    def __init__(self, loop) -> None:
        self.loop = loop
        self.deliveries: "collections.deque[_Delivery]" = collections.deque()
        self.pumping = False

    def accept(self, deliveries: List[_Delivery]) -> None:
        """The one callable a hand-over posts across threads."""
        self.deliveries.extend(deliveries)
        if not self.pumping:
            self.pumping = True
            self._pump()

    def _pump(self) -> None:
        """Run the oldest delivery under ONE ``loop.deliver`` span: the
        loop thread's share of a token's path (``on_token``, the
        provider's stream decoder and stop watch, the chunk batcher's
        emit), with the thread's CPU milliseconds in ``cpu_ms``. The span
        holds no ``await``, as no span on the loop's thread may: it is
        the thread's, and one held across an ``await`` would interleave
        with other tasks' spans on the same line."""
        try:
            delivery = self.deliveries.popleft()
            request = delivery.request
            finished = delivery.result is not None or delivery.error is not None
            with tracing.phase(
                "loop.deliver", tokens=len(delivery.calls),
                first=int(request.delivered == 0 and bool(delivery.calls)),
                done=int(finished), trace_id=request.trace_id or "",
            ) as span:
                cpu = time.thread_time()
                delivery.run()
                span.set(cpu_ms=(time.thread_time() - cpu) * 1e3)
        finally:
            if self.deliveries:
                self.loop.call_soon(self._pump)
            else:
                self.pumping = False


# loop -> its inbox, for every engine of the process: a request that a
# rebuilt engine resumes gets its tokens behind those the dead one posted
_INBOXES: Dict[Any, _LoopInbox] = {}


def _post_to_loop(loop, deliveries: List[_Delivery]) -> None:
    """Hand ``deliveries`` to ``loop`` from any thread with ONE
    ``call_soon_threadsafe``, behind everything posted to it before.
    Raises RuntimeError if the loop is closed."""
    inbox = _INBOXES.get(loop)
    if inbox is None:
        for known in list(_INBOXES):
            if known.is_closed():
                _INBOXES.pop(known, None)
        inbox = _INBOXES.setdefault(loop, _LoopInbox(loop))
    loop.call_soon_threadsafe(inbox.accept, deliveries)


def fail_request_future(
    request: "GenerationRequest", error: BaseException
) -> None:
    """Deliver ``error`` to a request's waiter from any thread — the ONE
    future-failing path shared by crash fail-fast, load shedding, the
    retired-queue straggler sweep, and the supervisor's give-up handling
    (a fix to the loop-closed race must land once, not four times). On a
    loop it goes through the loop's inbox, so it lands behind the tokens
    already handed over."""
    future = request.future
    if future is None:
        return
    if request.loop is not None:
        try:
            _post_to_loop(request.loop, [_Delivery(request, error=error)])
        except RuntimeError:
            # waiter's loop already closed (caller gave up) — must not
            # abort failing any REMAINING waiters
            pass
    elif not future.done():
        future.set_exception(error)


def _bucket(length: int, buckets: List[int]) -> int:
    for size in buckets:
        if length <= size:
            return size
    return buckets[-1]


# A stretch that fits a bucket is taught in windows of a smaller one only
# when the windows' rows are at most this share of the bucket's. What the
# rows saved have to pay for: every window streams the weights again
# (7.6 GB on Qwen-2.5-7B int8), attends over the slot's whole slab in XLA
# and not in the flash kernel, costs the host a dispatch of some 3 ms, and
# leaves the batched cold dispatch its neighbours share. At half, the
# default doubling bucket set never meets the rule (rows >= length > half
# the bucket), nor does a prompt that fills most of its bucket. A constant
# and not a setting; the chip's readings behind it are in PERF.md
# (section 6, PR 34).
WINDOWED_ROWS_SHARE = 0.5


def _prefill_windows(
    total: int, reused: int, buckets: List[int], stateful: bool
) -> List[Tuple[int, int]]:
    """The ``(offset, bucket)`` windows that teach ``total - reused``
    tokens from position ``reused``, left to right: the cover of a prompt
    by the buckets the engine has, and the only place that plans one.
    Whole windows of the largest bucket while more than it is left; what
    is left then fits a bucket and is one window of it, or, where that
    wastes at least half its rows (``WINDOWED_ROWS_SHARE``), windows of
    the smaller bucket that computes the fewest (the larger on a tie). The
    last window is shifted left to end at the prompt's last token:
    re-teaching a few positions (identical tokens, identical KV) is
    cheaper than a ragged-tail program, and it never writes past
    ``max_seq_len``. A recurrent state cannot be taught a position twice:
    a ``stateful`` family's last window starts where the one before it
    ended, right-padded (the write drops rows past ``max_seq_len``)."""
    largest = buckets[-1]
    windows: List[Tuple[int, int]] = []
    position = reused
    while total - position > largest:
        windows.append((position, largest))
        position += largest
    left = total - position
    fits = bucket = _bucket(left, buckets)
    count = 1
    rows = WINDOWED_ROWS_SHARE * fits  # the most a cover may compute
    for smaller in buckets:  # ascending: on a tie the larger wins
        covering = -(-left // smaller)
        if smaller < fits and covering * smaller <= rows:
            bucket, count, rows = smaller, covering, covering * smaller
    for _ in range(count - 1):
        windows.append((position, bucket))
        position += bucket
    windows.append(
        (position if stateful else max(0, total - bucket), bucket)
    )
    return windows


class DecodeEngine:
    """Runs one model on one mesh with continuous batching."""

    def __init__(
        self,
        config: model_lib.LlamaConfig,
        params: Dict[str, Any],
        *,
        mesh_config: Optional[MeshConfig] = None,
        max_slots: int = 8,
        max_seq_len: Optional[int] = None,
        prefill_buckets: Optional[List[int]] = None,
        decode_chunk: int = 8,
        seed: int = 0,
        quantize: Optional[str] = None,  # "int8" = weight-only int8
        kv_quant: Optional[str] = None,  # "int8" = int8 KV cache
        kv_layout: str = "dense",        # "dense" | "paged" (block pool)
        kv_block_size: int = 16,         # paged: tokens per pool block
        kv_blocks: Optional[int] = None,  # paged: pool size (None = the
                                          # dense-equivalent worst case)
        kv_host_blocks: int = 0,          # paged: host-DRAM demotion
                                          # tier capacity in blocks —
                                          # evicted chains demote there
                                          # and promote back on a
                                          # digest match (0 = off, the
                                          # single-tier behavior)
        paged_kernel: str = "fused",     # paged attention: "fused" (one
                                          # Pallas launch over the block
                                          # tables) | "reference" (the
                                          # gather/scatter oracle)
        spec_decode: str = "off",        # speculative decoding: "off" |
                                          # "ngram" (self-drafting
                                          # prompt-lookup, k drafted
                                          # tokens verified per step)
        spec_k: int = 4,                 # drafted tokens per decode step
        spec_ngram: int = 2,             # suffix n-gram the drafter matches
        prefill_mode: str = "split",     # paged prefill scheduling:
                                          # "split" (dedicated bucketed
                                          # prefill dispatches) | "mixed"
                                          # (token-budget chunked prefill
                                          # fused into the decode step)
        prefill_chunk: int = 64,         # mixed: max prefill tokens any
                                          # single step carries
        mixed_carry: bool = True,        # mixed: pipeline consecutive
                                          # mixed steps off the previous
                                          # step's device-resident
                                          # outputs (two-step window
                                          # plan); needs pipeline_decode
        pipeline_decode: bool = False,
        prefix_cache: bool = True,
        logprobs_topk: int = 0,
        slo: Optional[Dict[str, Any]] = None,  # {ttft_ms_p95, tpot_ms_p95}
        queue_timeout_s: Optional[float] = None,  # admission deadline:
                                          # pending requests older than
                                          # this are shed with a typed
                                          # QueueTimeoutError (None=off)
    ) -> None:
        self.config = config
        # a family whose mixers carry a state that no position addresses
        # (a recurrent state, a conv state) beside its KV rows cannot
        # reuse rows across slots or turns: a state has no snapshot at a
        # prefix's end
        self.stateful = config.mixers is not None
        # what the config's layers cannot take yet is refused here, by
        # the switch's name: nothing falls through to a GQA path
        self._refuse_unsupported(
            config, params, mesh_config, quantize=quantize,
            kv_quant=kv_quant, kv_layout=kv_layout,
            kv_host_blocks=kv_host_blocks, spec_decode=spec_decode,
            prefill_mode=prefill_mode,
        )
        if self.stateful:
            if prefix_cache:
                logger.info(
                    "prefix-cache and session warm reuse are off for a "
                    "model with a carried state: it has no snapshot at a "
                    "prefix's end (every prefill is cold)"
                )
            prefix_cache = False
        self.max_slots = max_slots
        self.decode_chunk = max(1, decode_chunk)
        # top-K alternative logprobs per generated token (OpenAI
        # `top_logprobs`). STATIC — it shapes the jit outputs, so 0
        # (off) keeps the serving graphs byte-identical to a build
        # without the feature; >0 adds a top_k over the logits per step
        self.logprobs_topk = max(0, int(logprobs_topk))
        # pipelined decode: dispatch chunk N+1 from chunk N's on-device
        # carry BEFORE host-processing N's tokens, hiding the host
        # round trip between chunks. Finished slots may burn up to one
        # surplus chunk; results are epoch-guarded so a recycled
        # slot never receives the old request's tokens.
        self.pipeline_decode = pipeline_decode
        # cross-slot prompt-prefix reuse: a cold request whose prompt
        # shares a prefix with another live slot's cache copies those KV
        # rows on-device (bandwidth-bound) instead of recomputing the
        # prefill (FLOPs-bound), then prefills only the divergent suffix.
        # Covers n>1 choices, shared chat templates, and repeated prompts.
        self.prefix_cache = prefix_cache
        self.max_seq_len = min(
            max_seq_len or config.max_seq_len, config.max_seq_len
        )
        self.prefill_buckets = prefill_buckets or self._default_buckets()
        if mesh_config is None:
            # default: single device. Sharding is opt-in via provider
            # config (mesh: {tp: N}) so small models never get axes that
            # don't divide their head counts.
            mesh_config = MeshConfig()
        validate_mesh(
            mesh_config,
            num_heads=config.num_heads,
            num_kv_heads=config.num_kv_heads,
            intermediate_size=config.intermediate_size,
            num_experts=config.num_experts,
            allow_pp=False,  # serving has no pipeline schedule
        )
        # flash under tp>1 runs through shard_map over the head axis (see
        # model._prefill_attn); no need to disable the kernel here
        self.mesh = build_mesh(
            mesh_config, devices=jax.devices()[: mesh_config.size]
        )
        axes = model_lib.logical_axes(config)
        from langstream_tpu.providers.jax_local.quant import QTensor

        pre_quantized = any(
            isinstance(v, QTensor) for v in params.values()
        )
        if quantize or pre_quantized:
            if quantize not in (None, "int8"):
                raise ValueError(f"unknown quantization {quantize!r}")
            from langstream_tpu.providers.jax_local.quant import (
                quantize_logical_axes,
                quantize_params,
            )

            params = quantize_params(params, config.num_experts)
            axes = quantize_logical_axes(axes, params)
        with self.mesh:
            self.params = shard_params(params, axes, self.mesh)
        self.freqs = model_lib.model_freqs(config)
        if kv_quant not in (None, "int8"):
            raise ValueError(f"unknown kv cache quantization {kv_quant!r}")
        self.kv_quant = kv_quant == "int8"
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv layout {kv_layout!r}")
        if paged_kernel not in ("fused", "reference"):
            raise ValueError(f"unknown paged kernel {paged_kernel!r}")
        self.kv_layout = kv_layout
        self.paged = kv_layout == "paged"
        # fused-vs-reference is the ROADMAP-item-1 A/B knob: "fused"
        # REQUESTS the ragged Pallas kernel; model._use_fused_paged falls
        # back to the reference composition off-TPU (sans the interpret
        # hook) / on non-MXU-aligned head dims, so the knob is safe to
        # leave at its default everywhere. tp>1 is NOT a downgrade: the
        # kernel runs per kv-head shard through its shard_map twin
        # (ragged_paged_attention_sharded), like the dense flash
        # kernels. That gate is static per engine (config shapes,
        # interpret hook, backend), so resolve it ONCE here and let
        # accounting, flight/artifact telemetry, and the dispatch
        # builders all see the kernel that actually runs — a silent
        # fused→reference fallback must not leave the byte model
        # charging fused bytes (MBU would read ~3x low).
        self.paged_kernel_requested = paged_kernel if self.paged else None
        self.paged_kernel = self.paged_kernel_requested
        # speculative decoding (ROADMAP item 2): a prompt-lookup drafter
        # proposes spec_k tokens per decode step and ONE verify forward
        # scores all of them — 1..spec_k+1 tokens per weight pass. The
        # non-speculative scan stays compiled as the oracle ("off").
        if spec_decode not in ("off", "ngram"):
            raise ValueError(f"unknown spec decode mode {spec_decode!r}")
        self.spec_decode = spec_decode
        self.spec = spec_decode == "ngram"
        self.spec_k = max(1, int(spec_k))
        self.spec_ngram = max(1, int(spec_ngram))
        # tokens a single scan step can emit (verify block width): the
        # context/budget arithmetic everywhere else keys off this
        self.spec_block = (self.spec_k + 1) if self.spec else 1
        # mixed prefill+decode dispatch (ROADMAP item 1 / ISSUE 12): on
        # the paged path, prefill stops being its own dispatch shape —
        # admitting slots park at a `prefill_pos` watermark and every
        # decode step carries up to `prefill_chunk` of their prompt
        # tokens alongside the Tq=1 decode rows in ONE fused ragged
        # launch (Sarathi-style stall-free batching), bounding any
        # single dispatch's duration and capping padding at the mixed
        # width instead of a power-of-two bucket. "split" keeps the
        # dedicated prefill dispatch + harvest machinery (the oracle
        # the mixed path is token-parity-tested against, and the
        # on-chip A/B's other leg).
        if prefill_mode not in ("split", "mixed"):
            raise ValueError(f"unknown prefill mode {prefill_mode!r}")
        if prefill_mode == "mixed" and kv_layout != "paged":
            raise ValueError(
                "prefill_mode 'mixed' requires kv_layout 'paged' — the "
                "dense cache has no per-row table indirection for the "
                "token-ragged mixed dispatch to address"
            )
        self.prefill_mode = prefill_mode
        self.mixed = prefill_mode == "mixed"
        self.prefill_chunk = max(1, int(prefill_chunk))
        # device-resident mixed-step carry (ROADMAP item 1 / ISSUE 14):
        # while admissions are chunking through mixed steps, the NEXT
        # step's window content is host-predictable from the watermark
        # bookkeeping advanced at plan time, so the engine speculatively
        # plans step N+1 and dispatches it off step N's device-resident
        # outputs (sampled tokens / cache / counts / tables / sampling
        # arrays stay on device; only the small prompt-window token
        # delta uploads) BEFORE host-processing N — hiding the host
        # round trip exactly like _dispatch_decode(carry=...). Chained
        # and unchained steps share ONE compiled program per width (the
        # fresh dispatch passes an all-False chain mask), so chaining
        # is bitwise-neutral by construction. Gated like decode
        # pipelining: both knobs must be on.
        self.mixed_carry = self.mixed and bool(mixed_carry)
        # mixed width ladder: power-of-two [S, W] dispatch widths up to
        # the (rounded-up) budget, so compilations stay logarithmic and
        # every width tiles evenly by the ragged kernel's q tile
        cap = 1
        while cap < self.prefill_chunk:
            cap *= 2
        widths = [min(8, cap)]
        while widths[-1] < cap:
            widths.append(widths[-1] * 2)
        self._mixed_widths = widths
        self._admit_seq = 0
        self._prefill_batches = 0  # numbers engine.prefill_dispatch spans
        if self.paged_kernel == "fused" and not model_lib._use_fused_paged(
            config, config.dims_per_head, config.num_heads,
            config.num_kv_heads, self.mesh,
        ):
            self.paged_kernel = "reference"
        self.kv_manager = None
        self.kv_host_blocks = 0
        self.kv_host_arena = None
        if self.paged:
            from langstream_tpu.providers.jax_local.paged import (
                PagedKVManager,
            )

            self.block_size = max(1, int(kv_block_size))
            # per-slot table width: enough blocks to address max_seq_len
            self.max_blocks = -(-self.max_seq_len // self.block_size)
            # default pool = the dense layout's worst case (+ null
            # block); real deployments size it DOWN — short requests
            # release blocks early and shared prefixes are stored once,
            # which is the whole HBM win
            self.num_blocks = int(
                kv_blocks or max_slots * self.max_blocks + 1
            )
            if self.num_blocks < self.max_blocks + 1:
                raise ValueError(
                    f"kv_blocks={self.num_blocks} cannot hold even one "
                    f"max-length sequence ({self.max_blocks} blocks of "
                    f"{self.block_size})"
                )
            self.kv_manager = PagedKVManager(self.num_blocks, self.block_size)
            # two-tier pool (ISSUE 18): a bounded pinned host-RAM arena
            # below the HBM pool — eviction demotes victim chains
            # through the jitted handoff gather (D2H) and admission
            # promotes digest matches back through the donated handoff
            # scatter (H2D) before falling back to cold prefill
            self.kv_host_blocks = max(0, int(kv_host_blocks or 0))
            if self.kv_host_blocks:
                from langstream_tpu.providers.jax_local.paged import (
                    HostKVArena,
                )

                self.kv_host_arena = HostKVArena(self.kv_host_blocks)
                self.kv_manager.attach_host(
                    self.kv_host_arena, self._demote_block_data
                )
            else:
                self.kv_host_arena = None
            # host-authoritative block tables [slots, max_blocks]; rows
            # are uploaded per dispatch (0 = the null block)
            self._block_tables = np.zeros(
                (max_slots, self.max_blocks), dtype=np.int32
            )
            cache_sharding = param_shardings(
                model_lib.paged_cache_logical_axes(self.kv_quant), self.mesh
            )
            with self.mesh:
                # device-thread state: rethreaded (donated) through
                # every dispatch on _run_loop
                # owned-by: _run_loop
                # built IN PLACE on every shard (jit + out_shardings):
                # built whole on device 0 and then sliced, a 3.5 GB
                # cache did not fit next to the loader's weights there
                # (tp=4 on four real chips)
                self.cache = _program(
                    "init_cache_paged", out_shardings=cache_sharding
                )(
                    lambda: model_lib.init_paged_cache(
                        config, self.num_blocks, self.block_size,
                        kv_quant=self.kv_quant,
                    )
                )()
            # the jitted COW block copy pins its outputs to this layout
            # so the SPMD partitioner can never resolve the dynamic
            # block index by all-gathering the pool (see _get_block_copy)
            self._cache_sharding = cache_sharding
        else:
            cache_sharding = param_shardings(
                model_lib.cache_logical_axes(self.kv_quant, config),
                self.mesh,
            )
            with self.mesh:
                # owned-by: _run_loop
                # built in place on every shard, as the paged pool is
                self.cache = _program(
                    "init_cache_dense", out_shardings=cache_sharding
                )(
                    lambda: model_lib.init_cache(
                        config, max_slots, self.max_seq_len,
                        kv_quant=self.kv_quant,
                        tp=dict(self.mesh.shape).get("tp", 1),
                    )
                )()
        # what reads the cache in the decode step, and how a leaf lies:
        # settled here, by the model's own gates, and said once (stats,
        # the jax_engine_decode_reader gauge, the start-up log line)
        if not self.paged:
            self.decode_reader = model_lib.decode_reader(
                config, self.cache, self.mesh
            )
        elif self.paged_kernel == "fused":
            self.decode_reader = "ragged_paged_attention" + (
                "_int8kv" if self.kv_quant else ""
            )
        else:
            self.decode_reader = "xla"
        self.cache_leaf_shape = tuple(
            jax.tree_util.tree_leaves(self.cache)[0].shape
        )
        self.slots = [_Slot() for _ in range(max_slots)]
        # efficiency accounting: analytical FLOPs/bytes per dispatch from
        # the model shape + quantization widths + KV layout, divided by
        # measured wall time and the per-chip peaks → per-chunk MFU/MBU
        self.peaks = accounting.PeakSpecs.from_env()
        self.cost_model = accounting.CostModel.from_model_config(
            config,
            weight_quant=(
                "int8" if (quantize == "int8" or pre_quantized) else None
            ),
            kv_quant=self.kv_quant,
            kv_block_size=self.block_size if self.paged else 1,
            paged_kernel=self.paged_kernel,
            # per-CHIP accounting under tensor parallelism: weights and
            # KV shard over tp, so a chip's share of the work divides —
            # billing whole-model FLOPs/bytes per chip would overstate
            # MFU/MBU by ~tp× on sharded engines
            tp=dict(self.mesh.shape).get("tp", 1),
        )
        # SLO burn-rate tracking over the process-wide TTFT/TPOT
        # histograms (targets come from serve/provider config)
        self.slo = (
            accounting.SLOTracker(
                slo, {"ttft": TTFT_SECONDS, "tpot": TPOT_SECONDS}
            )
            if slo else None
        )
        # goodput ledger support: sessions whose warm cache was evicted,
        # so a follow-up's re-prefill can be booked as wasted recompute
        # (value = cached history length at eviction; bounded FIFO)
        self._evicted_sessions: Dict[str, int] = {}
        self.base_seed = seed
        self._seed_sequence = 0
        # per-slot generated-token counts for presence/frequency
        # penalties; lives on device, threaded (donated) through every
        # prefill/decode dispatch like the KV cache. Explicitly
        # replicated over the mesh: on tp>1 an unplaced buffer would sit
        # on device 0 only, and lowering engine variants from live avals
        # (precompile, the StableHLO assertion tests) would see
        # incompatible device sets before the first dispatch resolves it
        from jax.sharding import NamedSharding, PartitionSpec

        with self.mesh:
            # device-thread state, like the cache  # owned-by: _run_loop
            self._counts = jax.device_put(
                jnp.zeros((max_slots, config.vocab_size), jnp.int32),
                NamedSharding(self.mesh, PartitionSpec()),
            )

        self._queue: "queue.Queue[Optional[GenerationRequest]]" = queue.Queue()
        # admission backlog, popped only by the device thread (submit()
        # hands off through the thread-safe queue; len() reads from
        # other threads are point-in-time snapshots)
        self._pending: List[GenerationRequest] = []  # owned-by: _run_loop
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self._crashed: Optional[BaseException] = None
        # supervised mode (runtime/supervisor.py): when set, a crashed
        # device thread hands its live sessions to this hook instead of
        # failing every waiter — crash → rebuild → resume, not crash →
        # mass 500. Unset (the default) keeps the fail-fast behavior.
        self.on_crash: Optional[Callable[[BaseException], None]] = None
        # admission deadline for load shedding (serve --queue-timeout-s)
        self.queue_timeout_s = (
            float(queue_timeout_s) if queue_timeout_s else None
        )
        # EWMA decode-step seconds: the Retry-After estimator for shed
        # requests (queue depth × step time ≈ when a slot frees up)
        self._step_ewma: Optional[float] = None
        self._counts_restore_fn: Optional[Any] = None
        # set once drain_for_recovery has swept the queue: a submit that
        # lands after the sweep must fail itself (nothing reads it)
        self._recovery_drained = False
        # set by precompile(): how many variants, the seconds it took to
        # compile them, and to compile and run each once (start-up
        # evidence)
        self.precompile_stats = {
            "variants": 0, "compile_seconds": 0.0, "seconds": 0.0,
        }
        self._compiled_prefill: Dict[int, Any] = {}
        self._prefill_offset_fns: Dict[int, Any] = {}
        self._decode_fns: Dict[int, Any] = {}
        self._spec_decode_fns: Dict[int, Any] = {}
        self._mixed_fns: Dict[int, Any] = {}
        self._copy_fns: Dict[int, Any] = {}
        self._block_copy_fn: Optional[Any] = None
        # KV-handoff gather/scatter jits, memoized per pow2-padded
        # block-chain width (same retrace budget as every builder)
        self._handoff_export_fns: Dict[int, Any] = {}
        self._handoff_import_fns: Dict[int, Any] = {}
        # prefill dispatches whose first tokens are not yet harvested
        # (FIFO — the device executes dispatches in order)
        self._prefill_inflight: List[Dict[str, Any]] = []  # owned-by: _run_loop
        # what the bookkeeping in progress owes each loop: loop ->
        # id(request) -> its delivery, posted by _hand_over
        self._outbox: Dict[Any, Dict[int, _Delivery]] = {}  # owned-by: _run_loop
        # decode dispatches so far: a prefill harvested at the count it
        # was launched at rides the first dispatch behind it
        # (stats["prefill_join_rows"])
        self._decode_seq = 0  # owned-by: _run_loop
        # end of the latest accounted decode interval (busy-time union)
        self._decode_busy_until = 0.0
        # end of the latest processed mixed step (host-gap evidence for
        # the mixed-step carry: unchained steps pay the gap, chained
        # steps collapse it)  # owned-by: _run_loop
        self._last_mixed_end = 0.0
        # counters mutated only on the device thread; cross-thread
        # readers (engines_snapshot, build_heartbeat, the watchdog)
        # take snapshot-tolerant reads — see _stable_items there
        self.stats = self._new_stats()  # owned-by: _run_loop
        # per-chunk dispatch log: (steps, active_slots, wall_seconds) —
        # the occupancy/step-time evidence the bench prints (bounded)
        self.chunk_log: List[Tuple[int, int, float]] = []  # owned-by: _run_loop
        # token-denominated twin of chunk_log covering EVERY device
        # dispatch (prefill windows included): the interference-bound
        # evidence — in mixed mode no entry's prefill_tokens may exceed
        # prefill_chunk, while a split-path cold prompt logs its whole
        # bucket in one entry (bounded like chunk_log)
        self.dispatch_log: List[Dict[str, Any]] = []  # owned-by: _run_loop
        # multi-host SPMD serving: when set (serving/mirror.py), every
        # device dispatch is also published as a compact record so
        # follower hosts replay the identical jit sequence on their
        # shards of the same global mesh
        self.mirror: Optional[Any] = None
        # observability plane: per-request spans (NOOP unless
        # LANGSTREAM_TRACE_DIR is set) + the crash-surviving flight
        # recorder (no-op unless configured / LANGSTREAM_FLIGHT_DIR)
        self.tracer = get_tracer("engine")
        flight.configure_from_env()
        # deterministic chaos (LANGSTREAM_FAULTS): zero-cost no-ops when
        # unarmed; arrival counters are process-global, so a one-shot
        # fault consumed here stays consumed across a supervisor rebuild
        faults.configure_from_env()
        flight.record(
            "engine_start",
            slots=max_slots,
            ctx=self.max_seq_len,
            mesh=dict(self.mesh.shape),
            decode_chunk=self.decode_chunk,
            kv_quant=bool(self.kv_quant),
            kv_layout=self.kv_layout,
            kv_blocks=self.num_blocks if self.paged else 0,
            kv_host_blocks=self.kv_host_blocks,
            paged_kernel=self.paged_kernel or "",
            paged_kernel_requested=self.paged_kernel_requested or "",
            spec_decode=self.spec_decode,
            spec_k=self.spec_k if self.spec else 0,
            prefill_mode=self.prefill_mode,
            prefill_chunk=self.prefill_chunk if self.mixed else 0,
        )
        _LIVE_ENGINES.add(self)

    @staticmethod
    def _refuse_unsupported(
        config, params, mesh_config, *, quantize, kv_quant, kv_layout,
        kv_host_blocks, spec_decode, prefill_mode,
    ) -> None:
        """Every switch the config's layers cannot take, refused by name
        when the engine is built, each with the reason of what the config
        HAS: latent attention (``mla``), a carried state (per-layer
        ``mixers``: the hybrid family's recurrent state, the
        short-convolution family's conv state), routed experts. Such a
        model runs the dense layout's three programs on one chip; the
        programs and the layer loop are every family's
        (``model._run_layers``), what is missing is an ``attend``, a
        cache format or a weight format (ROADMAP R-M1 .. R-M6 say what
        each needs). Prefix reuse is not a switch to refuse but a path to
        leave: the constructor turns it off for a carried state and
        :meth:`_session_warm` answers cold."""
        from langstream_tpu.providers.jax_local.quant import QTensor

        latent = config.mla is not None
        state = config.mixers is not None
        routed = config.experts is not None
        int8_weights = bool(quantize) or any(
            isinstance(v, QTensor) for v in params.values()
        )
        # switch -> (is it on, [(the config has this, so why not)])
        refused = {
            "kv-layout: paged": (kv_layout != "dense", [
                (latent, "no attend over a pool of latents yet"),
                (state, "the pool holds one kind of row; the model carries "
                        "a state beside K and V"),
            ]),
            "prefill-mode: mixed": (prefill_mode != "split", [
                (latent or state, "a paged dispatch"),
            ]),
            "kv-host-blocks": (bool(kv_host_blocks), [
                (latent or state, "the host tier holds paged GQA rows"),
            ]),
            "kv-quant": (bool(kv_quant), [
                (latent, "the latent cache has no int8 form"),
                (state, "no int8 form of the carried state or of what "
                        "lies beside K and V"),
            ]),
            "quantization": (int8_weights, [
                (routed, "quant.py has no int8 form of the expert stacks"),
                (latent, "nor of the low-rank projections"),
            ]),
            "spec-decode": (spec_decode != "off", [
                (latent, "no verify attend over latents yet"),
                (state, "a rejected draft cannot be rolled out of a "
                        "carried state"),
            ]),
            "mesh (tp / ep / any axis > 1)": (
                mesh_config is not None and mesh_config.size > 1, [
                    (latent, "the latent cache has one head"),
                    (state, "the carried state is not sharded"),
                    (routed, "the expert stacks hold this chip's share"),
                ],
            ),
        }
        named = [
            f"{switch} ({'; '.join(why for has, why in reasons if has)})"
            for switch, (on, reasons) in refused.items()
            if on and any(has for has, _ in reasons)
        ]
        if named:
            has = [
                name for name, there in (
                    ("latent attention", latent), ("a carried state", state),
                    ("routed experts", routed),
                ) if there
            ]
            raise ValueError(
                f"a model with {', '.join(has)} does not support: "
                + "; ".join(named)
            )

    def _new_stats(self) -> Dict[str, Any]:
        """Zeroed counters, beside what construction settled."""
        return dict(
            self._fresh_stats(),
            decode_reader=self.decode_reader,
            cache_leaf_shape=self.cache_leaf_shape,
        )

    @staticmethod
    def _fresh_stats() -> Dict[str, Any]:
        return {
            "tokens_generated": 0,
            # cross-thread posts that carried them (and the results) to
            # their loops: one a loop a harvested chunk or prefill record
            "emit_handovers": 0,
            "requests": 0,
            "prefill_calls": 0,
            "warm_prefill_calls": 0,
            "decode_steps": 0,
            "session_hits": 0,
            "prefix_hits": 0,            # cross-slot prefix-copy admissions
            "prefix_tokens_reused": 0,   # KV rows copied instead of recomputed
            "decode_chunks": 0,
            "decode_time": 0.0,      # wall secs inside decode dispatches
            "prefill_time": 0.0,     # wall secs inside prefill dispatches
            # split prefills: rows harvested, those of them active in the
            # first decode dispatch after their prefill's launch (all but
            # a request its first token ends, while the loop harvests
            # before it dispatches), and the host seconds blocked on
            # first tokens in that harvest (the device is busy meanwhile)
            "prefill_rows": 0,
            "prefill_join_rows": 0,
            "prefill_join_wait": 0.0,
            # cycles in which a cold prompt past the largest bucket had a
            # slot and waited all the same, for the decode chunk behind
            # another such prompt's windows (_admit)
            "long_prompts_held": 0,
            # cold prompts that fit a bucket and were taught in windows of
            # a smaller one, which computes at most half its rows
            # (_prefill_windows); over prefill_rows: the share of prompts
            # the cover engages on
            "prompts_windowed": 0,
            "active_slot_steps": 0,  # sum of active slots over decode steps
            # wall-clock breakdown of everything OUTSIDE device dispatches,
            # so "unaccounted" time has a name (VERDICT r2 weak #1)
            # (each sum is taken at its phase span's own boundaries —
            # tracing.phase in _run_loop — so /metrics and a trace agree)
            "idle_time": 0.0,        # engine thread blocked on empty queue
            "emit_time": 0.0,        # host token bookkeeping + hand-overs
            "admit_time": 0.0,       # admission: slots, batch builds, prefill dispatches
            "dispatch_time": 0.0,    # building and dispatching decode chunks
            # the engine thread's CPU seconds inside the same four spans
            # (time.thread_time() at the same boundaries)
            "idle_cpu": 0.0,
            "emit_cpu": 0.0,
            "admit_cpu": 0.0,
            "dispatch_cpu": 0.0,
            # goodput ledger: tokens that reached a live caller vs tokens
            # burned on cancelled requests / eviction-induced re-prefill
            "tokens_useful": 0,
            "tokens_wasted": {},     # reason -> tokens
            # load shedding: pending requests failed fast at their
            # admission deadline instead of starving in _pending
            "requests_shed": {},     # reason -> requests
            # roofline accumulators (modeled work per dispatch kind)
            "decode_flops": 0.0,
            "decode_bytes": 0.0,
            "prefill_flops": 0.0,
            # the flash prefill's (q block, k block) steps computed and
            # those of the causal buckets, per head per layer, summed over
            # the cold dispatches whose attention is the kernel
            "prefill_attn_blocks": 0.0,
            "prefill_attn_blocks_causal": 0.0,
            # speculative decoding: drafted candidates vs candidates the
            # verify pass accepted (rejected = the new wasted reason)
            "tokens_drafted": 0,
            "tokens_draft_accepted": 0,
            # decode wall-time normalizer for the watchdog: tokens an
            # AVERAGE active slot gained, summed over chunks — equals
            # decode_steps for plain decode, grows ~(1+accept·k) faster
            # under speculation, so per-token latency stays comparable
            "decode_token_steps": 0.0,
            # mixed-step carry (prefill_mode: mixed): total mixed steps,
            # how many were dispatched off the previous step's device
            # carry, and why chains broke (reason -> events) — the
            # chain-rate evidence the carry A/B is judged on
            "mixed_steps": 0,
            "mixed_steps_chained": 0,
            "mixed_carry_invalidations": {},
            # summed device idle between consecutive mixed steps (the
            # per-step host tax; ~0 while chains hold)
            "mixed_gap_time": 0.0,
            # paged-KV handoff (prefill/decode disaggregation): exports
            # serialized off this engine's pool, imports written into
            # it, and the device bytes each way — the transfer price
            # the disagg A/B reads next to its tail win
            "handoff_exports": 0,
            "handoff_export_bytes": 0,
            "handoff_imports": 0,
            "handoff_import_bytes": 0,
            "handoff_import_tokens": 0,
            # tiered KV pool (host-DRAM demotion tier): blocks moved
            # each way with their D2H/H2D bytes, prompt tokens served
            # by promotions instead of recompute, and promotions that
            # tore mid-scatter and fell back to cold prefill
            "host_demotions": 0,
            "host_demote_bytes": 0,
            "host_promotions": 0,
            "host_promote_bytes": 0,
            "kv_host_hit_tokens": 0,
            "host_promote_aborts": 0,
            # routed experts held here (model.RoutedExperts), from the
            # counters every prefill and decode chunk returns: (token,
            # expert) assignments the router made, those that met a held
            # expert, rows the expert matmuls computed (tile padding
            # included), and tokens by held expert
            "moe_assignments": 0,
            "moe_assignments_held": 0,
            "moe_rows_computed": 0,
            "moe_tokens_by_expert": [],
            # the hybrid family: key blocks its sparse layers attended
            # and had in context and the queries that chose (summed over
            # sparse layers; every prefill and decode chunk returns them);
            # any model with a carried state (recurrent, conv): the slots
            # whose state a prefill at position 0 started from zeros
            "sparse_kept": 0,
            "sparse_visible": 0,
            "sparse_queries": 0,
            "state_resets": 0,
        }

    def _note_counters(self, span, counters) -> None:
        """A harvested dispatch's counters (None for a family that
        returns none): the routed experts', or the block selection's,
        into ``stats`` and onto the phase span the host harvests them
        under."""
        if counters is None:
            return
        if isinstance(counters, list):  # a chunked prompt's windows
            counters = sum(np.asarray(each) for each in counters)
        counters = np.asarray(counters)
        if self.config.experts is None:  # the block selection's
            kept, visible, queries = (int(n) for n in counters)
            self.stats["sparse_kept"] += kept
            self.stats["sparse_visible"] += visible
            self.stats["sparse_queries"] += queries
            span.set(
                sparse_kept=kept, sparse_visible=visible,
                sparse_queries=queries,
            )
            return
        routed, held, rows = (int(n) for n in counters[:3])
        by_expert = [int(n) for n in counters[3:]]
        stats = self.stats
        stats["moe_assignments"] += routed
        stats["moe_assignments_held"] += held
        stats["moe_rows_computed"] += rows
        kept = stats["moe_tokens_by_expert"]
        stats["moe_tokens_by_expert"] = [
            a + b for a, b in zip(kept or [0] * len(by_expert), by_expert)
        ]
        span.set(
            moe_routed=routed, moe_held=held, moe_rows=rows,
            moe_by_expert=":".join(map(str, by_expert)),
        )

    # lint: allow(owned-by-violation) -- bench/warmup contract: callers
    #   reset counters only while the engine is idle (no dispatch in
    #   flight); a concurrent reset would at worst lose a sample, and
    #   the replacement dicts/lists are fully formed before publication
    def reset_stats(self) -> None:
        """Zero the counters (e.g. after warmup, before measurement)."""
        self.stats = self._new_stats()
        self.chunk_log = []
        self.dispatch_log = []

    def _default_buckets(self) -> List[int]:
        buckets, size = [], 64
        limit = self.max_seq_len if hasattr(self, "max_seq_len") else 4096
        while size < limit:
            buckets.append(size)
            size *= 2
        buckets.append(limit)
        return buckets

    # ------------------------------------------------------------------ #
    # jitted device functions
    # ------------------------------------------------------------------ #
    def _tp_mesh(self):
        """The mesh iff tensor parallelism is actually on — the one rule
        for whether model code routes Pallas kernels through their
        shard_map wrappers (a bare Mosaic call has no SPMD partitioning
        rule). Used by prefill AND decode jits; keep them in lockstep."""
        return self.mesh if dict(self.mesh.shape).get("tp", 1) > 1 else None

    def _pin_counts(self, counts: jnp.ndarray) -> jnp.ndarray:
        """Inside a jit on a mesh: hand the penalty counts back
        replicated, as they were placed. Left to the partitioner a decode
        step returns them vocab-sharded (it follows the logits), the
        next prefill returns them replicated, and every program then
        exists under two argument shardings — the second compiles in
        traffic, whatever precompile built (tp=4 on four chips: first
        answer after 102 s)."""
        if self.mesh.size == 1:
            return counts
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.lax.with_sharding_constraint(
            counts, NamedSharding(self.mesh, PartitionSpec())
        )

    def _get_prefill(self, bucket: int):
        """Prefill + first-token sampling in ONE jit: the engine never
        blocks on prefill — sampling on-device means harvesting is a pure
        D2H read of [B] tokens once the dispatch completes, so decode
        chunks for already-running slots keep flowing underneath."""
        fn = self._compiled_prefill.get(bucket)
        if fn is None:
            config, freqs = self.config, self.freqs
            mesh = self._tp_mesh()
            topk = self.logprobs_topk

            def sample_first(logits, slot_ids, counts, temperature, top_k,
                             top_p, seeds, lengths, bias_ids, bias_vals):
                keys = _sampling_keys(seeds, lengths)
                rows = jnp.arange(logits.shape[0])[:, None]
                adjusted = logits.at[rows, bias_ids].add(bias_vals)
                sampled = _sample(adjusted, temperature, top_k, keys, top_p)
                lp = _token_logprob(logits, sampled)
                tops = _top_logprobs(logits, topk) if topk else None
                # fresh request: reset the slot's penalty counts, then
                # count the first sampled token
                counts = counts.at[slot_ids].set(0)
                counts = counts.at[slot_ids, sampled].add(1)
                return counts, sampled, lp, tops

            if self.paged:
                paged_kernel = self.paged_kernel

                @_program("prefill_paged", donate_argnums=(1, 6))
                def run(params, cache, tokens, lengths, slot_ids, tables,
                        counts, temperature, top_k, top_p, seeds,
                        bias_ids, bias_vals):
                    cache, logits, _ = model_lib.paged_prefill(
                        config, params, cache, tokens, lengths, tables,
                        freqs, mesh=mesh, kernel=paged_kernel,
                    )
                    counts, sampled, lp, tops = sample_first(
                        logits, slot_ids, counts, temperature, top_k,
                        top_p, seeds, lengths, bias_ids, bias_vals,
                    )
                    return cache, counts, sampled, lp, tops, None

            else:

                @_program("prefill_dense", donate_argnums=(1, 5))
                def run(params, cache, tokens, lengths, slot_ids, counts,
                        temperature, top_k, top_p, seeds,
                        bias_ids, bias_vals):
                    cache, logits, moe = model_lib.prefill(
                        config, params, cache, tokens, lengths, slot_ids,
                        freqs, mesh=mesh,
                    )
                    counts, sampled, lp, tops = sample_first(
                        logits, slot_ids, counts, temperature, top_k,
                        top_p, seeds, lengths, bias_ids, bias_vals,
                    )
                    return cache, counts, sampled, lp, tops, moe

            fn = run
            self._compiled_prefill[bucket] = fn
        return fn

    def _get_prefill_offset(self, bucket: int):
        fn = self._prefill_offset_fns.get(bucket)
        if fn is None:
            config, freqs = self.config, self.freqs
            mesh = self._tp_mesh()
            topk = self.logprobs_topk

            def sample_first(logits, slot_ids, counts, temperature, top_k,
                             top_p, seeds, offsets, lengths,
                             bias_ids, bias_vals):
                # key position = the row's TOTAL cache length, so a warm
                # continuation samples exactly like a cold run of the
                # same full prompt
                keys = _sampling_keys(seeds, offsets + lengths)
                rows = jnp.arange(logits.shape[0])[:, None]
                adjusted = logits.at[rows, bias_ids].add(bias_vals)
                sampled = _sample(adjusted, temperature, top_k, keys, top_p)
                lp = _token_logprob(logits, sampled)
                tops = _top_logprobs(logits, topk) if topk else None
                counts = counts.at[slot_ids].set(0)
                counts = counts.at[slot_ids, sampled].add(1)
                return counts, sampled, lp, tops

            if self.paged:
                paged_kernel = self.paged_kernel

                @_program("prefill_offset_paged", donate_argnums=(1, 7))
                def run(params, cache, tokens, lengths, offsets, slot_ids,
                        tables, counts, temperature, top_k, top_p, seeds,
                        bias_ids, bias_vals):
                    cache, logits, _ = model_lib.paged_prefill_at_offset(
                        config, params, cache, tokens, lengths, offsets,
                        tables, freqs, mesh=mesh, kernel=paged_kernel,
                    )
                    counts, sampled, lp, tops = sample_first(
                        logits, slot_ids, counts, temperature, top_k,
                        top_p, seeds, offsets, lengths, bias_ids, bias_vals,
                    )
                    return cache, counts, sampled, lp, tops, None

            else:

                @_program("prefill_offset_dense", donate_argnums=(1, 6))
                def run(params, cache, tokens, lengths, offsets, slot_ids,
                        counts, temperature, top_k, top_p, seeds,
                        bias_ids, bias_vals):
                    cache, logits, moe = model_lib.prefill_at_offset(
                        config, params, cache, tokens, lengths, offsets,
                        slot_ids, freqs,
                    )
                    counts, sampled, lp, tops = sample_first(
                        logits, slot_ids, counts, temperature, top_k,
                        top_p, seeds, offsets, lengths, bias_ids, bias_vals,
                    )
                    return cache, counts, sampled, lp, tops, moe

            fn = run
            self._prefill_offset_fns[bucket] = fn
        return fn

    def _get_decode(self, steps: int = 1):
        """Jitted K-step decode: a ``lax.scan`` of decode+sample, so one
        host↔device dispatch yields K tokens per slot. Chunking amortizes
        dispatch latency (which dominates when the model is small);
        stop conditions are
        applied host-side afterwards, surplus steps for a finished slot
        are discarded and its length pointer rewound.

        With ``spec_decode: ngram`` every scan step is draft→verify→
        accept instead (:meth:`_get_spec_decode`) and yields 1..spec_k+1
        tokens per slot per step; this plain scan stays compiled as the
        non-speculative oracle."""
        if self.spec:
            return self._get_spec_decode(steps)
        fn = self._decode_fns.get(steps)
        if fn is None:
            config, freqs = self.config, self.freqs
            mesh = self._tp_mesh()
            topk = self.logprobs_topk
            paged = self.paged
            paged_kernel = self.paged_kernel

            def run_impl(params, cache, tokens, lengths, active, write_mask,
                         tables, counts, temperature, top_k, top_p,
                         presence, frequency, seeds, bias_ids, bias_vals):
                slots = tokens.shape[0]

                def body(carry, _):
                    cache, tokens, lengths, counts, moe = carry
                    if paged:
                        cache, logits, _ = model_lib.paged_decode_step(
                            config, params, cache, tokens, lengths,
                            tables, freqs, write_mask, mesh=mesh,
                            kernel=paged_kernel,
                        )
                    else:
                        # the step's expert counters, summed over the
                        # chunk (None, an empty pytree, for a family
                        # without routed experts)
                        cache, logits, step_moe = model_lib.decode_step(
                            config, params, cache, tokens, lengths, freqs,
                            write_mask, mesh=mesh,
                        )
                        if step_moe is not None:
                            moe = moe + step_moe
                    # presence/frequency penalties over generated tokens
                    # (identity when both are 0 — exact float math)
                    adjusted = (
                        logits
                        - presence[:, None] * (counts > 0)
                        - frequency[:, None] * counts
                    )
                    adjusted = adjusted.at[
                        jnp.arange(slots)[:, None], bias_ids
                    ].add(bias_vals)
                    # per-slot keys from (seed, position): sampling never
                    # depends on what else shares the batch
                    keys = _sampling_keys(seeds, lengths)
                    sampled = _sample(adjusted, temperature, top_k, keys, top_p)
                    # logprob under the RAW untruncated distribution (the
                    # model's own confidence — what FLARE consumes)
                    lp = _token_logprob(logits, sampled)
                    sampled = jnp.where(active, sampled, 0)
                    counts = counts.at[jnp.arange(slots), sampled].add(
                        active.astype(jnp.int32)
                    )
                    lengths = jnp.where(active, lengths + 1, lengths)
                    ys = (sampled, lp)
                    if topk:
                        ys = ys + _top_logprobs(logits, topk)
                    return (cache, sampled, lengths, counts, moe), ys

                moe = model_lib.zero_counters(config)
                (
                    (cache, final_tokens, final_lengths, counts, moe),
                    ys,
                ) = jax.lax.scan(
                    body, (cache, tokens, lengths, counts, moe), None,
                    length=steps,
                )
                out, lps = ys[0], ys[1]
                # [steps, S, K] -> [S, steps, K] to match out.T's layout
                tops = (
                    (ys[2].transpose(1, 0, 2), ys[3].transpose(1, 0, 2))
                    if topk else None
                )
                # final carry is returned ON DEVICE so a pipelined next
                # chunk can chain without a host round trip
                return (
                    cache, self._pin_counts(counts), out.T, lps.T, tops,
                    final_tokens, final_lengths, moe,
                )

            if paged:

                @_program("decode_chunk_paged", donate_argnums=(1, 7))
                def run(params, cache, tokens, lengths, active, write_mask,
                        tables, counts, temperature, top_k, top_p,
                        presence, frequency, seeds, bias_ids, bias_vals):
                    return run_impl(
                        params, cache, tokens, lengths, active, write_mask,
                        tables, counts, temperature, top_k, top_p,
                        presence, frequency, seeds, bias_ids, bias_vals,
                    )

            else:

                @_program("decode_chunk_dense", donate_argnums=(1, 6))
                def run(params, cache, tokens, lengths, active, write_mask,
                        counts, temperature, top_k, top_p,
                        presence, frequency, seeds, bias_ids, bias_vals):
                    return run_impl(
                        params, cache, tokens, lengths, active, write_mask,
                        None, counts, temperature, top_k, top_p,
                        presence, frequency, seeds, bias_ids, bias_vals,
                    )

            fn = run
            self._decode_fns[steps] = fn
        return fn

    def _get_spec_decode(self, steps: int):
        """Jitted K-step SPECULATIVE decode scan (``spec_decode: ngram``).
        Each scan step: (1) the prompt-lookup drafter proposes up to
        spec_k tokens from the slot's own device-resident token history,
        (2) ONE verify forward scores the [S, 1+spec_k] candidate block
        at every position (dense :func:`model.verify_step`; paged rides
        the fused kernel's existing Tq>1 formulation), (3) the
        acceptance pass emits 1..spec_k+1 tokens per slot with the exact
        sampling semantics of the oracle scan (greedy exact-match /
        rejection sampling, penalties, bias, seeded keys). Rejected
        suffixes roll back by NOT advancing lengths — rows past the
        accepted length are causally invisible and overwritten in order
        by later steps (paged blocks were reserved at admission, so no
        allocator churn). Emitted counts ride the scan outputs so the
        host sees a variable number of tokens per dispatch."""
        fn = self._spec_decode_fns.get(steps)
        if fn is None:
            from langstream_tpu.providers.jax_local import (
                spec_decode as spec_lib,
            )

            config, freqs = self.config, self.freqs
            mesh = self._tp_mesh()
            topk = self.logprobs_topk
            paged = self.paged
            paged_kernel = self.paged_kernel
            k = self.spec_k
            ngram = self.spec_ngram
            block_width = self.spec_block
            width = self.max_seq_len  # history array width

            def run_impl(params, cache, tokens, lengths, active, write_mask,
                         history, tables, counts, temperature, top_k, top_p,
                         presence, frequency, seeds, bias_ids, bias_vals):
                slots = tokens.shape[0]

                def body(carry, _):
                    cache, tokens, lengths, counts, history = carry
                    drafts, num = spec_lib.draft_ngram(
                        history, lengths, active, ngram=ngram, k=k,
                    )
                    block = jnp.concatenate(
                        [tokens[:, None], drafts], axis=1
                    )  # [S, 1+k]
                    valid_lens = jnp.where(active, 1 + num, 0)
                    if paged:
                        cache, logits, _ = model_lib.paged_verify_step(
                            config, params, cache, block, lengths,
                            valid_lens, tables, freqs,
                            write_mask=write_mask, mesh=mesh,
                            kernel=paged_kernel,
                        )
                    else:
                        cache, logits, _ = model_lib.verify_step(
                            config, params, cache, block, lengths,
                            valid_lens, freqs, write_mask=write_mask,
                            mesh=mesh,
                        )
                    emitted, lps, valid, counts, tops = (
                        spec_lib.accept_block(
                            logits, block, num, counts, active,
                            temperature, top_k, top_p, seeds, lengths,
                            presence, frequency, bias_ids, bias_vals, topk,
                        )
                    )
                    m = valid.sum(axis=1).astype(jnp.int32)  # [S] emitted
                    # append the emitted tokens to the device history
                    # (positions lengths..lengths+m-1; invalid → dropped)
                    pos = lengths[:, None] + jnp.arange(block_width)[None, :]
                    pos = jnp.where(valid, pos, width)
                    history = history.at[
                        jnp.arange(slots)[:, None], pos
                    ].set(emitted, mode="drop")
                    last = jnp.take_along_axis(
                        emitted,
                        jnp.clip(m - 1, 0, block_width - 1)[:, None],
                        axis=1,
                    )[:, 0]
                    tokens = jnp.where(active & (m > 0), last, tokens)
                    lengths = lengths + jnp.where(active, m, 0)
                    ys = (emitted, lps, valid, num)
                    if topk:
                        ys = ys + tops
                    return (cache, tokens, lengths, counts, history), ys

                (
                    (cache, final_tokens, final_lengths, counts,
                     final_history),
                    ys,
                ) = jax.lax.scan(
                    body, (cache, tokens, lengths, counts, history),
                    None, length=steps,
                )
                # [steps, S, B] -> [S, steps, B]
                out = ys[0].transpose(1, 0, 2)
                lps = ys[1].transpose(1, 0, 2)
                valid = ys[2].transpose(1, 0, 2)
                drafted = ys[3].transpose(1, 0)  # [S, steps]
                tops = (
                    (ys[4].transpose(1, 0, 2, 3), ys[5].transpose(1, 0, 2, 3))
                    if topk else None
                )
                return (
                    cache, self._pin_counts(counts), out, lps, valid,
                    drafted, tops,
                    final_tokens, final_lengths, final_history,
                )

            if paged:

                @_program("spec_decode_chunk_paged", donate_argnums=(1, 6, 8))
                def run(params, cache, tokens, lengths, active, write_mask,
                        history, tables, counts, temperature, top_k, top_p,
                        presence, frequency, seeds, bias_ids, bias_vals):
                    return run_impl(
                        params, cache, tokens, lengths, active, write_mask,
                        history, tables, counts, temperature, top_k, top_p,
                        presence, frequency, seeds, bias_ids, bias_vals,
                    )

            else:

                @_program("spec_decode_chunk_dense", donate_argnums=(1, 6, 7))
                def run(params, cache, tokens, lengths, active, write_mask,
                        history, counts, temperature, top_k, top_p,
                        presence, frequency, seeds, bias_ids, bias_vals):
                    return run_impl(
                        params, cache, tokens, lengths, active, write_mask,
                        history, None, counts, temperature, top_k, top_p,
                        presence, frequency, seeds, bias_ids, bias_vals,
                    )

            fn = run
            self._spec_decode_fns[steps] = fn
        return fn

    def _get_mixed(self, width: int):
        """Jitted mixed prefill+decode step (``prefill_mode: mixed``):
        ONE fused dispatch where every ready slot rides as a Tq=1
        decode row and admitting slots carry ``width``-capped prefill
        windows — :func:`model.paged_mixed_step` plus in-jit sampling
        with the split paths' EXACT semantics, so mixed and split legs
        are token-parity comparable:

        - decode rows sample like the decode scan body: penalties over
          the slot's count row, logit bias, keys from (seed, post-write
          length);
        - a window that COMPLETES its prompt samples like the prefill
          paths' ``sample_first``: counts reset then the first token
          counted, NO penalties (fresh request), keys from (seed,
          total prompt length);
        - mid-prefill and idle rows discard their sample and leave the
          count row untouched.

        Mixed-step carry: the program additionally takes the PREVIOUS
        step's device-resident sampled tokens plus a host ``chain_mask``
        and splices them into column 0 of chained rows — a fresh
        dispatch passes zeros + an all-False mask (integer identity), so
        chained and unchained steps run the SAME compiled program per
        width and chaining is bitwise-neutral by construction (the
        decode carry's contract). The returned ``sampled`` array is the
        next chain's device-resident token operand."""
        fn = self._mixed_fns.get(width)
        if fn is None:
            config, freqs = self.config, self.freqs
            mesh = self._tp_mesh()
            topk = self.logprobs_topk
            paged_kernel = self.paged_kernel

            @_program("mixed_step_paged", donate_argnums=(1, 9))
            def run(params, cache, tokens, offsets, num_tokens,
                    write_mask, decode_mask, completes, tables, counts,
                    prev_sampled, chain_mask,
                    temperature, top_k, top_p, presence, frequency,
                    seeds, bias_ids, bias_vals):
                # chained rows ride the previous mixed step's on-device
                # sample as their pending token (host never saw it yet)
                tokens = tokens.at[:, 0].set(
                    jnp.where(chain_mask, prev_sampled, tokens[:, 0])
                )
                cache, logits, _ = model_lib.paged_mixed_step(
                    config, params, cache, tokens, offsets, num_tokens,
                    tables, freqs, write_mask=write_mask, mesh=mesh,
                    kernel=paged_kernel,
                )
                slots = tokens.shape[0]
                rows = jnp.arange(slots)
                sample_mask = decode_mask | completes
                # completing rows reset their penalty counts FIRST
                # (sample_first semantics — order is irrelevant for the
                # sample itself since penalties don't apply to them)
                counts = jnp.where(completes[:, None], 0, counts)
                penalized = (
                    logits
                    - presence[:, None] * (counts > 0)
                    - frequency[:, None] * counts
                )
                adjusted = jnp.where(
                    decode_mask[:, None], penalized, logits
                )
                adjusted = adjusted.at[rows[:, None], bias_ids].add(
                    bias_vals
                )
                # key position = the row's TOTAL cache length after this
                # step: decode rows match the scan body's `lengths`,
                # completing windows match sample_first's prompt length
                keys = _sampling_keys(seeds, offsets + num_tokens)
                sampled = _sample(adjusted, temperature, top_k, keys,
                                  top_p)
                lp = _token_logprob(logits, sampled)
                tops = _top_logprobs(logits, topk) if topk else None
                sampled = jnp.where(sample_mask, sampled, 0)
                counts = counts.at[rows, sampled].add(
                    sample_mask.astype(jnp.int32)
                )
                return cache, self._pin_counts(counts), sampled, lp, tops

            fn = run
            self._mixed_fns[width] = fn
        return fn

    def _get_copy_prefix(self, bucket: int):
        """Jitted cross-slot KV copy: move ``bucket`` cache rows starting
        at ``offset`` from slot ``src`` to slot ``dst``. Pure device-side
        data movement — for a B-token prefix this reads+writes
        ``B * layers * kv_heads * head_dim * 2`` elements (a few MB),
        orders of magnitude cheaper than recomputing the prefill.
        ``params`` is unused; it keeps the (params, cache, ...) argument
        shape every other engine dispatch has, so :meth:`precompile` can
        drive all variants uniformly."""
        fn = self._copy_fns.get(bucket)
        if fn is None:

            @_program("copy_prefix_dense", donate_argnums=(1,))
            def run(params, cache, src, dst, offset):
                del params

                def move(c):
                    # rank-agnostic: value leaves are 5-d, int8-KV scale
                    # leaves 4-d — both are [layers, slot, seq, ...]
                    tail = (0,) * (c.ndim - 3)
                    chunk = jax.lax.dynamic_slice(
                        c, (0, src, offset) + tail,
                        (c.shape[0], 1, bucket) + c.shape[3:],
                    )
                    return jax.lax.dynamic_update_slice(
                        c, chunk, (0, dst, offset) + tail
                    )

                return (jax.tree_util.tree_map(move, cache),)

            fn = run
            self._copy_fns[bucket] = fn
        return fn

    def _get_block_copy(self):
        """Jitted pool-block copy (paged layout): duplicate block ``src``
        into ``dst`` across every layer and cache leaf. This is the
        copy-on-write primitive — a session follow-up that diverges
        mid-block gets a private copy of the boundary block before its
        suffix prefill overwrites rows a published chain still needs.
        ``params`` is unused; it keeps the uniform (params, cache, ...)
        dispatch shape (see :meth:`_get_copy_prefix`). Outputs carry the
        pool's sharding constraint: the copied block index is dynamic
        and the block axis replicated, so without the pin the SPMD
        partitioner may resolve the slice by all-gathering the
        kv-head-sharded pool under tp>1."""
        fn = self._block_copy_fn
        if fn is None:
            sharding = self._cache_sharding

            @_program("block_copy_paged", donate_argnums=(1,))
            def run(params, cache, src, dst):
                del params

                def move(c, s):
                    # [layers, num_blocks, block_size, ...] — value AND
                    # scale leaves share the leading three axes
                    tail = (0,) * (c.ndim - 2)
                    chunk = jax.lax.dynamic_slice(
                        c, (0, src) + tail,
                        (c.shape[0], 1) + c.shape[2:],
                    )
                    return jax.lax.with_sharding_constraint(
                        jax.lax.dynamic_update_slice(
                            c, chunk, (0, dst) + tail
                        ),
                        s,
                    )

                return (jax.tree_util.tree_map(move, cache, sharding),)

            fn = run
            self._block_copy_fn = fn
        return fn

    def _dispatch_block_copy(self, src: int, dst: int) -> None:
        if self.mirror is not None:
            # COW is a device dispatch: followers must duplicate the
            # same pool block on their shard, in stream order, or every
            # later read of the private copy diverges
            self._check_mirror_layout()
            self.mirror.publish(
                "block_copy", {}, [np.int32(src), np.int32(dst)]
            )
        run = self._get_block_copy()
        (self.cache,) = run(
            self.params, self.cache, np.int32(src), np.int32(dst)
        )
        self.kv_manager.stats["cow_copies"] += 1

    # ------------------------------------------------------------------ #
    # paged-KV handoff (prefill/decode disaggregation, fleet/handoff.py)
    # ------------------------------------------------------------------ #
    @staticmethod
    def _handoff_pad(n: int) -> int:
        """Pow2-padded block-chain width: bounds the export/import jits
        to one lowering per width bucket instead of one per chain
        length (the retrace-budget rule, analysis/retrace.py)."""
        return 1 << max(0, int(n - 1).bit_length())

    def _get_handoff_export(self, width: int):
        """Jitted pool gather for a handoff export: every cache leaf's
        rows for ``width`` table blocks, ``[layers, width, …]`` per
        leaf. No donation — the pool stays live (the exported chain is
        still published and serving). Dynamic block ids index a
        replicated axis, so no sharding constraint is needed: each
        kv-head shard gathers its own rows and the host concatenation
        is the unsharded view."""
        fn = self._handoff_export_fns.get(width)
        if fn is None:

            @_program("handoff_export_paged")
            def run(cache, blocks):
                return jax.tree_util.tree_map(
                    lambda c: jnp.take(c, blocks, axis=1), cache
                )

            fn = run
            self._handoff_export_fns[width] = fn
        return fn

    def _get_handoff_import(self, width: int):
        """Jitted pool scatter for a handoff import: write ``width``
        blocks of per-leaf rows into their freshly reserved pool slots.
        Donates the cache like every mutating dispatch; padded entries
        target the null block (their zero rows are never read through a
        live length mask). Outputs carry the pool's sharding constraint
        for the same reason the block copy does — the scattered block
        axis is replicated, and without the pin the partitioner may
        materialize the kv-head-sharded pool whole under tp>1."""
        fn = self._handoff_import_fns.get(width)
        if fn is None:
            sharding = self._cache_sharding

            @_program("handoff_import_paged", donate_argnums=(1,))
            def run(params, cache, blocks, data):
                del params

                def put(c, d, s):
                    return jax.lax.with_sharding_constraint(
                        c.at[:, blocks].set(d.astype(c.dtype)), s
                    )

                return (
                    jax.tree_util.tree_map(put, cache, data, sharding),
                )

            fn = run
            self._handoff_import_fns[width] = fn
        return fn

    def _export_handoff(
        self, slot: _Slot, request: Optional[GenerationRequest] = None
    ) -> Optional[Dict[str, Any]]:
        """Serialize the finishing slot's published chain for the topic
        fabric: full blocks of ``history[:length]`` (exactly what
        :meth:`PagedKVManager.publish` made matchable — the final
        sampled token is never in the cache, so it rides the manifest's
        teacher-forced replay instead). Returns the payload
        ``fleet.handoff.handoff_records`` chunks, or None when nothing
        is exportable (no full block yet). ``request`` (the finishing
        request — ``slot.request`` is already cleared by ``_finish``)
        labels the trace span; the payload's ``export_ts`` lets the
        serving layer stamp the chunk-0 manifest so the decode side can
        compute ``handoff_transit``."""
        full = slot.length // self.block_size
        if full <= 0 or not slot.blocks:
            return None
        export_t0 = time.perf_counter()
        tokens = slot.history[: full * self.block_size]
        blocks = slot.blocks[:full]
        width = self._handoff_pad(full)
        padded = np.zeros((width,), dtype=np.int32)
        padded[:full] = blocks
        run = self._get_handoff_export(width)
        gathered = run(self.cache, padded)
        arrays = {
            leaf: np.asarray(value)[:, :full]
            for leaf, value in gathered.items()
        }
        # lazy: the canonical byte accounting lives with the wire
        # schema (one definition for gauges, assembler, and sim)
        from langstream_tpu.fleet.handoff import payload_nbytes

        payload = {
            "tokens": list(tokens),
            "arrays": arrays,
            "block_size": self.block_size,
            "kv_quant": bool(self.kv_quant),
            # the transit anchor: rides the chunk-0 manifest
            # (manifest_for_request) so the decode leg can subtract.
            # Stamped AFTER the arrays are materialized — transit
            # measures the fabric, not this replica's serialization
            "export_ts": tracing.wall(time.perf_counter()),
        }
        nbytes = payload_nbytes(payload)
        self.stats["handoff_exports"] += 1
        self.stats["handoff_export_bytes"] += nbytes
        flight.record(
            "kv_handoff_export",
            tokens=len(tokens),
            blocks=full,
            nbytes=nbytes,
        )
        if self.tracer.enabled:
            self.tracer.event(
                "engine.handoff_export",
                time.perf_counter() - export_t0,
                trace_id=(request.trace_id or "") if request else "",
                start=export_t0,
                tokens=len(tokens),
                blocks=full,
                bytes=nbytes,
                aborted=False,
                replica=flight.get_identity().get("replica", ""),
            )
        return payload

    def _import_pending_handoffs(self) -> None:
        """Import every pending request's handoff payload BEFORE the
        admission scan, on the engine thread (the manager's owner): the
        written chain publishes under the normal ``(parent_block,
        chunk)`` keys, so the request's own admission — and any
        concurrent same-prefix admission — then hits the prefix cache
        instead of re-prefilling. A failed import (pool pressure, shape
        mismatch, torn payload) bills ``handoff_aborted`` and degrades
        to recompute — never a caller-visible error."""
        if not self.paged or not self.prefix_cache:
            return
        for request in self._pending:
            if request.kv_import is None:
                continue
            payload, request.kv_import = request.kv_import, None
            import_start = time.perf_counter()
            ok = self._import_handoff(
                payload, trace_id=request.trace_id or ""
            )
            if ok:
                # journey ledger: the decode leg's handoff_import stage
                # window + admission class (the later prefix-cache hit
                # this import manufactured must not book as "hbm-hit")
                request._jt_import = (  # type: ignore[attr-defined]
                    import_start, time.perf_counter()
                )
                request._jt_admit_class = (  # type: ignore[attr-defined]
                    "handoff-import"
                )

    def _import_handoff(
        self, payload: Dict[str, Any], trace_id: str = ""
    ) -> bool:
        manager = self.kv_manager
        tokens = list(payload.get("tokens") or [])
        arrays = payload.get("arrays") or {}
        size = int(payload.get("block_size", 0) or 0)
        full = len(tokens) // size if size else 0
        import_t0 = time.perf_counter()

        def aborted(reason: str) -> bool:
            self._waste("handoff_aborted", len(tokens))
            flight.record(
                "kv_handoff_import_aborted",
                reason=reason, tokens=len(tokens),
            )
            if self.tracer.enabled:
                self.tracer.event(
                    "engine.handoff_import",
                    time.perf_counter() - import_t0,
                    trace_id=trace_id,
                    start=import_t0,
                    tokens=len(tokens),
                    aborted=True,
                    reason=reason,
                )
            return False

        if self.mirror is not None:
            # followers replay dispatch records, and the import scatter
            # carries host-built arrays no record schema ships yet —
            # refuse rather than fork the mirrored pools
            return aborted("mirror")
        if (
            full <= 0
            or size != self.block_size
            or bool(payload.get("kv_quant", False)) != bool(self.kv_quant)
            or set(arrays) != set(self.cache)
        ):
            return aborted("layout_mismatch")
        for leaf, expect in self.cache.items():
            shape = tuple(np.asarray(arrays[leaf]).shape)
            if shape != (expect.shape[0], full, *expect.shape[2:]):
                return aborted("shape_mismatch")
        reserved = manager.import_session(tokens)
        if reserved is None:
            return aborted("pool_exhausted")
        chain, fresh = reserved
        try:
            if fresh:
                # only the blocks the local cache does NOT already hold
                # are written; a (partially) resident prefix keeps its
                # local rows — they are bitwise the same content
                start = len(chain)
                width = self._handoff_pad(len(fresh))
                padded = np.zeros((width,), dtype=np.int32)
                padded[: len(fresh)] = fresh
                data = {}
                for leaf, array in arrays.items():
                    piece = np.ascontiguousarray(
                        np.asarray(array)[:, start:full]
                    )
                    if width > len(fresh):
                        pad = [(0, 0)] * piece.ndim
                        pad[1] = (0, width - len(fresh))
                        piece = np.pad(piece, pad)
                    data[leaf] = piece
                run = self._get_handoff_import(width)
                (self.cache,) = run(self.params, self.cache, padded, data)
        except Exception:  # noqa: BLE001 — unwind before ids recycle
            manager.abort_import(chain + fresh)
            raise
        manager.commit_import(tokens, chain + fresh)
        nbytes = payload.get("nbytes")
        if not isinstance(nbytes, (int, float)):
            from langstream_tpu.fleet.handoff import payload_nbytes

            nbytes = payload_nbytes(payload)
        self.stats["handoff_imports"] += 1
        self.stats["handoff_import_bytes"] += int(nbytes)
        self.stats["handoff_import_tokens"] += len(tokens)
        flight.record(
            "kv_handoff_import",
            tokens=len(tokens),
            blocks_written=len(fresh),
            blocks_local=len(chain),
            nbytes=int(nbytes),
        )
        if self.tracer.enabled:
            self.tracer.event(
                "engine.handoff_import",
                time.perf_counter() - import_t0,
                trace_id=trace_id,
                start=import_t0,
                tokens=len(tokens),
                blocks=len(chain) + len(fresh),
                bytes=int(nbytes),
                aborted=False,
                replica=flight.get_identity().get("replica", ""),
            )
        return True

    # ------------------------------------------------------------------ #
    # tiered KV pool: host-DRAM demotion / promotion (ISSUE 18)
    # ------------------------------------------------------------------ #
    # lint: allow(owned-by-violation) -- engine-thread by contract: the
    #   manager stores this as its demote hook (attach_host) and calls
    #   it only inside the eviction pass of allocate(), which runs on
    #   _run_loop()'s admission scan; the AST reachability pass cannot
    #   follow the stored-callback indirection
    def _demote_block_data(
        self, block: int
    ) -> Optional[Tuple[Dict[str, Any], int]]:
        """Data-plane hook the manager calls while demoting one victim
        block: gather the block's pool rows D2H through the memoized
        handoff-export jit (width 1 — demotion happens block-by-block
        inside the eviction pass, before the id returns to the free
        list, so the gather dispatch always precedes any new owner's
        write in stream order). Returns ``(leaf tree, nbytes)`` —
        ``np.asarray`` preserves bf16 and int8+scales bitwise — or
        None when demotion must be skipped (mirrored engines replay
        dispatch records that carry no host-tier schema)."""
        if self.mirror is not None:
            return None
        run = self._get_handoff_export(1)
        gathered = run(self.cache, np.asarray([block], dtype=np.int32))
        data = {
            leaf: np.asarray(value)[:, 0]
            for leaf, value in gathered.items()
        }
        nbytes = sum(a.nbytes for a in data.values())
        self.stats["host_demotions"] += 1
        self.stats["host_demote_bytes"] += nbytes
        flight.record("kv_host_demote", block=block, nbytes=nbytes)
        return data, nbytes

    def _host_probe(
        self, prompt: Sequence[int], match: Optional[Tuple[List[int], int]]
    ) -> List[Any]:
        """Host-tier continuation of the HBM prefix scan: the demoted
        entries that extend ``match``'s chain, truncated at the first
        entry without captured rows (an accounting-only entry cannot
        be promoted)."""
        if (
            not self.paged
            or not self.prefix_cache
            or self.kv_manager.host is None
            or self.mirror is not None
        ):
            return []
        start = len(match[0]) if match is not None else 0
        entries = self.kv_manager.host_match(prompt, start)
        out: List[Any] = []
        for entry in entries:
            if entry.data is None:
                break
            out.append(entry)
        return out

    def _promote_host_chain(
        self,
        prompt: Sequence[int],
        matched: List[int],
        matched_tokens: int,
        entries: List[Any],
        fresh: List[int],
    ) -> int:
        """Scatter ``entries`` (host-tier continuation of the matched
        HBM chain) into the first ``len(entries)`` freshly reserved
        blocks through the donated, sharding-pinned handoff-import jit,
        then publish the promoted chain — publish-at-commit: the rows'
        writes are dispatched HERE, so any reader (same-round warm
        suffix, later mixed window) is ordered after them on the
        stream. Any failure aborts BEFORE anything publishes: the fresh
        blocks stay private, the admission proceeds as a cold prefill,
        and the caller never sees an error. Returns promoted blocks
        (0 = aborted)."""
        count = len(entries)
        target = fresh[:count]
        size = self.block_size
        try:
            if faults.fire("host_promote_torn") is not None:
                raise RuntimeError("chaos: torn host promotion")
            width = self._handoff_pad(count)
            padded = np.zeros((width,), dtype=np.int32)
            padded[:count] = target
            data: Dict[str, Any] = {}
            for leaf, expect in self.cache.items():
                rows = np.stack(
                    [np.asarray(entry.data[leaf]) for entry in entries],
                    axis=1,
                )
                if rows.shape != (
                    expect.shape[0], count, *expect.shape[2:]
                ):
                    raise ValueError(
                        f"host entry shape {rows.shape} does not fit "
                        f"pool leaf {leaf}"
                    )
                if width > count:
                    pad = [(0, 0)] * rows.ndim
                    pad[1] = (0, width - count)
                    rows = np.pad(rows, pad)
                data[leaf] = rows
            run = self._get_handoff_import(width)
            (self.cache,) = run(self.params, self.cache, padded, data)
        except Exception:  # noqa: BLE001 — abort-before-recycle
            self.stats["host_promote_aborts"] += 1
            flight.record(
                "kv_host_promote_aborted",
                blocks=count, tokens=count * size,
            )
            return 0
        end = matched_tokens + count * size
        self.kv_manager.publish(list(prompt[:end]), matched + target)
        nbytes = sum(entry.nbytes for entry in entries)
        self.stats["host_promotions"] += count
        self.stats["host_promote_bytes"] += nbytes
        self.stats["kv_host_hit_tokens"] += count * size
        arena = self.kv_manager.host
        if arena is not None:
            arena.note_promoted(count)
        flight.record(
            "kv_host_promote",
            blocks=count, tokens=count * size, nbytes=nbytes,
        )
        return count

    def _dispatch_prefix_copy(self, src: int, dst: int, length: int) -> None:
        """Copy cache rows [0:length) of ``src`` into ``dst`` in
        bucket-sized windows. Windows may overshoot the exact length:
        rows past the shared prefix are either overwritten by the
        suffix prefill or masked by the slot's length, and decode writes
        a row before ever attending to it — so no masking is needed."""
        largest = self.prefill_buckets[-1]
        position = 0
        while position < length:
            remaining = length - position
            bucket = (
                largest if remaining > largest
                else _bucket(remaining, self.prefill_buckets)
            )
            if self.mirror is not None:
                self.mirror.publish("copy", {"bucket": bucket}, [
                    np.int32(src), np.int32(dst), np.int32(position),
                ])
            run = self._get_copy_prefix(bucket)
            (self.cache,) = run(
                self.params,
                self.cache,
                np.int32(src),
                np.int32(dst),
                np.int32(position),
            )
            position += bucket
        self.stats["prefix_hits"] += 1
        self.stats["prefix_tokens_reused"] += length

    def _variant_jobs(self) -> List[Tuple[Any, Tuple[Any, ...]]]:
        """One (jit fn, arg avals) entry per prefill/decode variant the
        engine can ever dispatch — the single source both precompile
        phases drive from, so they cannot drift. Args 0/1 are always
        params/cache avals; every other arg is a plain data array
        (zeros are valid stand-ins for all of them)."""

        def aval(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

        params_aval = jax.tree_util.tree_map(aval, self.params)
        cache_aval = jax.tree_util.tree_map(aval, self.cache)
        counts_aval = aval(self._counts)

        def vec(n, dtype):
            return jax.ShapeDtypeStruct((n,), dtype)

        def tables(n):
            # paged: per-row block tables ride every dispatch
            return (
                (jax.ShapeDtypeStruct((n, self.max_blocks), jnp.int32),)
                if self.paged else ()
            )

        jobs: List[Tuple[Any, Tuple[Any, ...]]] = []
        size = 1
        # mixed mode retires the bucketed prefill dispatches entirely:
        # prompts enter through the mixed decode-step windows below, so
        # compiling the (bucket × group-size) prefill lattice would be
        # pure waste (and followers never receive those records either)
        while not self.mixed and size <= self.max_slots:
            for bucket in self.prefill_buckets:
                if size > self._max_prefill_rows(bucket):
                    continue
                sampling = (
                    vec(size, jnp.float32), vec(size, jnp.int32),
                    vec(size, jnp.float32), vec(size, jnp.uint32),
                    jax.ShapeDtypeStruct(
                        (size, self.MAX_LOGIT_BIAS), jnp.int32
                    ),
                    jax.ShapeDtypeStruct(
                        (size, self.MAX_LOGIT_BIAS), jnp.float32
                    ),
                )
                tokens = jax.ShapeDtypeStruct((size, bucket), jnp.int32)
                jobs.append((self._get_prefill(bucket), (
                    params_aval, cache_aval, tokens,
                    vec(size, jnp.int32), vec(size, jnp.int32),
                    *tables(size), counts_aval, *sampling,
                )))
                jobs.append((self._get_prefill_offset(bucket), (
                    params_aval, cache_aval, tokens,
                    vec(size, jnp.int32), vec(size, jnp.int32),
                    vec(size, jnp.int32), *tables(size),
                    counts_aval, *sampling,
                )))
            size *= 2
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        if self.paged:
            jobs.append((self._get_block_copy(), (
                params_aval, cache_aval, scalar, scalar,
            )))
        elif self.prefix_cache:
            for bucket in self.prefill_buckets:
                jobs.append((self._get_copy_prefix(bucket), (
                    params_aval, cache_aval, scalar, scalar, scalar,
                )))
        slots = self.max_slots
        step_variants = {self.decode_chunk, 1}
        # spec decode threads the per-slot token history (drafting
        # source) through the scan carry as one extra [S, max_seq] array
        history = (
            (jax.ShapeDtypeStruct(
                (slots, self.max_seq_len), jnp.int32
            ),)
            if self.spec else ()
        )
        for steps in step_variants:
            jobs.append((self._get_decode(steps), (
                params_aval, cache_aval,
                vec(slots, jnp.int32), vec(slots, jnp.int32),
                vec(slots, jnp.bool_), vec(slots, jnp.bool_),
                *history, *tables(slots), counts_aval,
                vec(slots, jnp.float32), vec(slots, jnp.int32),
                vec(slots, jnp.float32), vec(slots, jnp.float32),
                vec(slots, jnp.float32), vec(slots, jnp.uint32),
                jax.ShapeDtypeStruct(
                    (slots, self.MAX_LOGIT_BIAS), jnp.int32
                ),
                jax.ShapeDtypeStruct(
                    (slots, self.MAX_LOGIT_BIAS), jnp.float32
                ),
            )))
        if self.mixed:
            for width in self._mixed_widths:
                jobs.append((self._get_mixed(width), (
                    params_aval, cache_aval,
                    jax.ShapeDtypeStruct((slots, width), jnp.int32),
                    vec(slots, jnp.int32), vec(slots, jnp.int32),
                    vec(slots, jnp.bool_), vec(slots, jnp.bool_),
                    vec(slots, jnp.bool_),
                    jax.ShapeDtypeStruct(
                        (slots, self.max_blocks), jnp.int32
                    ),
                    counts_aval,
                    # mixed-step carry operands: the previous step's
                    # sampled tokens + the chain mask (zeros/False on a
                    # fresh dispatch — one program serves both)
                    vec(slots, jnp.int32), vec(slots, jnp.bool_),
                    vec(slots, jnp.float32), vec(slots, jnp.int32),
                    vec(slots, jnp.float32), vec(slots, jnp.float32),
                    vec(slots, jnp.float32), vec(slots, jnp.uint32),
                    jax.ShapeDtypeStruct(
                        (slots, self.MAX_LOGIT_BIAS), jnp.int32
                    ),
                    jax.ShapeDtypeStruct(
                        (slots, self.MAX_LOGIT_BIAS), jnp.float32
                    ),
                )))
        return jobs

    def _variant_args(self, avals: Tuple[Any, ...]) -> List[Any]:
        """Callable arguments for a :meth:`_variant_jobs` entry, placed
        as a live dispatch places them — a program's compile-cache key
        follows its arguments' shardings, so anything else builds a
        program traffic never runs: real params, the live cache and
        penalty counts (the one data aval that carries a sharding; all
        three donated and rethreaded by whoever calls), zeros for every
        other data arg (incl. seeds — values are ignored). Zero decode
        `active`/`write_mask` masks mean no cache row is written;
        prefill windows write garbage into slot 0's rows (and reset its
        counts, as every admission does), which is why a call must come
        before traffic."""
        return [self.params, self.cache] + [
            self._counts if spec.sharding is not None
            else np.zeros(spec.shape, spec.dtype)
            for spec in avals[2:]
        ]

    # lint: allow(owned-by-violation) -- pre-traffic by contract (see
    #   docstring): must run before the engine thread serves requests,
    #   while the device thread is idle or not yet started
    def precompile(self) -> None:
        """Compile-and-execute every (bucket, pow2-group-size) prefill
        variant and the decode chunks BEFORE serving traffic. Group sizes
        are timing-dependent (admission batching), so relying on warmup
        traffic to cover them is racy — a variant first seen under load
        stalls every active request for the whole compile. Dummy rows
        target slot 0, so this must run before real requests occupy the
        cache (call right after construction; ``start()`` is fine too
        since the engine thread is idle until the first submit).

        Two phases over the SAME job list (:meth:`_variant_jobs`) and
        the SAME arguments (:meth:`_variant_args`): (1) every variant is
        lowered + compiled concurrently in a thread pool — on a big
        model a cold cache means tens of XLA compiles, and they
        parallelize well; (2) each variant executes once sequentially
        (its executable is already in memory), which warms the jit call
        caches, so traffic never compiles."""
        from concurrent.futures import ThreadPoolExecutor

        # later processes find the executables in the persistent cache
        from langstream_tpu.runtime.compile_cache import (
            configure_compile_cache,
        )

        configure_compile_cache()
        jobs = self._variant_jobs()
        # one XLA compile keeps about one core busy: use the host's. On
        # a mesh, one at a time: concurrent SPMD-partitioned compiles
        # overflow the TPU compiler's stack (SIGSEGV in xla::spmd's sort
        # resharding at tp=4, on the chips and on a described mesh alike:
        # always at four or more at once, one run in four at two)
        workers = (
            1 if self.mesh.size > 1
            else max(1, min(len(jobs), (os.cpu_count() or 4) - 1))
        )

        def build(job):
            # lowered from the SAME arguments phase 2 calls with, so
            # phase 2 finds the executable in memory: a second program
            # per variant compiles one after another (measured on the
            # chip: 280 of 350 s)
            fn, avals = job
            with self.mesh:
                fn.lower(*self._variant_args(avals)).compile()

        started = time.perf_counter()
        with ThreadPoolExecutor(max_workers=workers) as pool:
            list(pool.map(build, jobs))
        logger.info(
            "precompiled %d variants in %.1fs",
            len(jobs), time.perf_counter() - started,
        )
        self.precompile_stats = {
            "variants": len(jobs),
            "compile_seconds": time.perf_counter() - started,
            "seconds": 0.0,
        }
        with self.mesh:
            for fn, avals in jobs:
                outputs = fn(*self._variant_args(avals))
                self.cache = outputs[0]
                if len(outputs) > 1:
                    self._counts = outputs[1]
            jax.block_until_ready(self.cache)
        self.precompile_stats["seconds"] = time.perf_counter() - started

    # ------------------------------------------------------------------ #
    # public API (thread-safe)
    # ------------------------------------------------------------------ #
    def start(self) -> None:
        if self._crashed is not None:
            if self.on_crash is not None:
                raise api_errors.EngineRebuildingError(
                    "engine is rebuilding after a crash; retry shortly",
                    retry_after_s=2.0,
                )
            raise RuntimeError("decode engine crashed") from self._crashed
        if self._thread is not None:
            return
        # monotone bool handshake with the loop: start/stop own the
        # True/False transitions, the loop only reads it (and clears it
        # on crash exit); a stale read costs one idle-poll iteration
        # lint: allow(cross-thread-mutation) -- single-word flag store;
        #   readers tolerate one-iteration staleness by design
        self._running = True
        self._thread = threading.Thread(
            target=self._run_loop, name="jax-local-engine", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._running = False
        self._queue.put(None)
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        flight.record(
            "engine_stop",
            tokens=self.stats["tokens_generated"],
            requests=self.stats["requests"],
            decode_steps=self.stats["decode_steps"],
        )
        flight.flush()
        if self.mirror is not None:
            try:
                self.mirror.publish("stop", {}, [])
            except Exception:
                # writer already dead (follower dropped) — still close
                logger.warning("mirror: stop record not delivered")
            self.mirror.close()

    @property
    def queue_depth(self) -> int:
        """Requests waiting for a slot: the submit queue plus the
        admission-pending list. Read from any thread (both reads are
        atomic snapshots); the fleet layer's routing/scaling signal and
        the ``jax_engine_queue_depth`` gauge."""
        return self._queue.qsize() + len(self._pending)

    def submit(self, request: GenerationRequest) -> None:
        if self._crashed is not None:
            if self.on_crash is not None:
                # supervised: the crash window is a bounded rebuild, not
                # a terminal state — callers get a typed retryable error
                # (503 + Retry-After on the HTTP surfaces), never a 500
                raise api_errors.EngineRebuildingError(
                    "engine is rebuilding after a crash; retry shortly",
                    retry_after_s=2.0,
                )
            raise RuntimeError("decode engine crashed") from self._crashed
        if request.replay_tokens and self.mirror is not None:
            # replay admission restores penalty counts with a dispatch
            # the follower replay protocol does not speak
            raise NotImplementedError(
                "session resurrection over the multi-host mirror is not "
                "supported"
            )
        bias = request.sampling.logit_bias
        if bias and len(bias) > self.MAX_LOGIT_BIAS:
            raise ValueError(
                f"logit_bias has {len(bias)} entries; this engine supports "
                f"at most {self.MAX_LOGIT_BIAS}"
            )
        if self.stateful and (
            request.export_handoff or request.kv_import is not None
        ):
            raise ValueError(
                "a model with a carried state has no handoff rows "
                "(export_handoff / kv_import: the payload holds paged GQA "
                "rows and no state)"
            )
        if self.config.mla is not None:
            # what a REQUEST can ask of the latent family that it cannot
            # take (the engine's switches were refused when it was built)
            if request.export_handoff or request.kv_import is not None:
                raise ValueError(
                    "the latent-attention family has no handoff rows "
                    "(export_handoff / kv_import: the payload holds paged "
                    "GQA rows)"
                )
            largest = self.prefill_buckets[-1]
            if len(request.prompt_tokens) > largest:
                raise ValueError(
                    f"prompt of {len(request.prompt_tokens)} tokens exceeds "
                    f"the largest prefill bucket ({largest}): the latent "
                    "family has no chunked prefill (a window over a latent "
                    "prefix needs a flash kernel with a key offset); raise "
                    "prefill-buckets"
                )
        # prompts longer than the largest bucket prefill in bucket-sized
        # windows (chunked prefill), so context length is the only limit
        limit = self.max_seq_len - 1
        if len(request.prompt_tokens) > limit:
            raise ValueError(
                f"prompt of {len(request.prompt_tokens)} tokens exceeds the "
                f"context limit of {limit} (max_seq_len {self.max_seq_len})"
            )
        # paged: no per-request block check needed — the constructor
        # guarantees the pool covers at least one max_seq_len sequence,
        # which bounds any single reservation
        # span/TTFT anchor on the process's one clock (wall time, where
        # a dump or the cross-replica ledger needs it, is tracing.wall)
        request._submit_ts = time.perf_counter()  # type: ignore[attr-defined]
        self._queue.put(request)
        if self._crashed is not None:
            # crashed between the check above and the put: the loop will
            # never drain the queue again
            if self.on_crash is None:
                self._fail_all_pending()
            elif self._recovery_drained:
                # supervised AND the recovery drain already swept this
                # queue: nothing will ever read it again — fail any
                # strays (incl. this request, unless the drain captured
                # it, in which case its future rides the resurrection)
                # with the typed retryable error so no caller hangs
                self._fail_stragglers()

    async def generate(
        self,
        prompt_tokens: List[int],
        sampling: SamplingParams,
        *,
        stop_tokens: Optional[Set[int]] = None,
        on_token: Optional[Callable[[int, bool], None]] = None,
        session_id: Optional[str] = None,
        handle: Optional[List[GenerationRequest]] = None,
        trace_id: Optional[str] = None,
        request_fields: Optional[Dict[str, Any]] = None,
    ) -> GenerationResult:
        """Asyncio entry: submit and await the result. Pass ``handle``
        (an empty list) to receive the live request — its ``cancel()``
        ends generation at the next token boundary (used by the service
        layer for stop-string matches and disconnected clients).
        ``request_fields`` sets extra :class:`GenerationRequest` fields
        before submit — the disaggregation seam (``export_handoff`` on
        the prefill leg; ``kv_import``/``replay_tokens``/``prompt_len``
        on the decode leg's warm admission)."""
        loop = asyncio.get_running_loop()
        future: "asyncio.Future[GenerationResult]" = loop.create_future()
        request = GenerationRequest(
            prompt_tokens=list(prompt_tokens),
            sampling=sampling,
            stop_tokens=stop_tokens or set(),
            on_token=on_token,
            session_id=session_id,
            future=future,
            loop=loop,
            trace_id=trace_id,
        )
        if request_fields:
            for key, value in request_fields.items():
                setattr(request, key, value)
        if handle is not None:
            handle.append(request)
        self.start()
        self.submit(request)
        try:
            return await future
        except asyncio.CancelledError:
            # caller gave up (client disconnect, task cancelled): free
            # the slot at the next token boundary instead of decoding a
            # full answer nobody reads
            request.cancel()
            raise

    # ------------------------------------------------------------------ #
    # engine thread
    # ------------------------------------------------------------------ #
    def _run_loop(self) -> None:
        logger.info(
            "engine started: %d slots × %d ctx, mesh %s, decode reads the "
            "cache %s through %s",
            self.max_slots, self.max_seq_len, dict(self.mesh.shape),
            list(self.cache_leaf_shape), self.decode_reader,
        )
        try:
            with self.mesh:
                inflight = None
                while self._running:
                    self._drain_queue(
                        block=not self._any_active()
                        and not self._pending
                        and inflight is None
                        and not self._any_admitting()
                    )
                    if not self._running:
                        break
                    if (
                        self._pending
                        and inflight is None
                        and any(not s.active for s in self.slots)
                    ):
                        # admission linger: give a burst of submissions a
                        # beat to land so prefill batches fill up and decode
                        # waves stay aligned (amortizes dispatch latency).
                        # Skipped while a chunk is in flight — lingering
                        # then would add 3 ms to THAT chunk's harvest
                        # latency, taxing every running stream's TPOT for
                        # a batching benefit the next dispatch gets anyway
                        with self._phase("engine.linger"):
                            time.sleep(0.003)
                            self._drain_queue(block=False)
                    # dispatch prefills WITHOUT blocking: they queue behind
                    # the in-flight decode chunk (if any); their slots join
                    # the next fresh chunk, which waits for their first
                    # tokens below. (mixed mode: admission only parks the
                    # slot at its watermark — the windows ride the decode
                    # steps below)
                    with self._phase(
                        "engine.admit", "admit",
                        pending=len(self._pending),
                    ):
                        self._admit()
                    if inflight is not None:
                        # overlap: chain the next chunk off the device-side
                        # carry BEFORE blocking on this one's tokens
                        chained = None
                        if inflight.get("mixed"):
                            # mixed-step carry: the next window's content
                            # is host-predictable from the watermark
                            # bookkeeping advanced at dispatch, so plan
                            # step N+1 and dispatch it off N's device
                            # outputs; any contradiction falls back to
                            # the host-built dispatch (and is counted)
                            plan_next = self._plan_mixed_chain(inflight)
                            if isinstance(plan_next, dict):
                                chained = self._dispatch_chunk(
                                    carry=inflight, plan_next=plan_next
                                )
                            else:
                                self._note_carry_invalidation(plan_next)
                        elif self.pipeline_decode and self._can_chain(
                            inflight
                        ):
                            chained = self._dispatch_chunk(carry=inflight)
                        self._process_decode(inflight)
                        inflight = chained
                    if inflight is not None:
                        # a chained chunk is in flight, which happens only
                        # while no prefill is (_can_chain)
                        continue
                    # a fresh chunk is built from what the host knows
                    # (slot.ready), and the device runs the prefills
                    # launched above BEFORE it: wait them out first, so
                    # their slots ride THIS chunk instead of sitting a
                    # whole chunk out
                    self._harvest_prefills()
                    if self._any_ready() or self._any_admitting():
                        inflight = self._dispatch_chunk()
                        if not self.pipeline_decode or (
                            inflight.get("mixed") and not self.mixed_carry
                        ):
                            # unpipelined engines (and mixed engines with
                            # the carry off) process immediately: the
                            # next window's content then depends on THIS
                            # step's completion bookkeeping
                            self._process_decode(inflight)
                            inflight = None
        except BaseException as exc:  # noqa: BLE001
            logger.exception("engine loop crashed")
            # flip the crash flag BEFORE failing waiters so a racing
            # submit() either lands in the drained queue below or raises
            self._crashed = exc
            self._running = False
            # the flight artifact is the crash's on-disk evidence —
            # flush BEFORE failing waiters (their callbacks may tear the
            # process down)
            flight.record("engine_crash", error=repr(exc)[:512])
            flight.flush()
            if self.on_crash is not None:
                # supervised: live sessions stay parked in the queue /
                # _pending / slots for the supervisor to resurrect onto
                # a rebuilt engine — the hook runs the whole detect →
                # heal arc on this (already dead) thread, then the
                # thread exits quietly (the crash is already logged,
                # flight-recorded, and handled; re-raising would only
                # spam threading's excepthook mid-recovery)
                self.on_crash(exc)
                return
            self._fail_all_pending()
            raise

    @contextlib.contextmanager
    def _phase(self, name: str, stat: Optional[str] = None, **attributes):
        """One phase of the loop: its span (``tracing.phase``) with the
        thread's CPU milliseconds inside it (``cpu_ms``) and, at the same
        two boundaries, the running sums ``<stat>_time`` (wall) and
        ``<stat>_cpu`` in ``self.stats``."""
        started, cpu = time.perf_counter(), time.thread_time()
        try:
            with tracing.phase(name, self.tracer, **attributes) as span:
                try:
                    yield span
                finally:
                    cpu = time.thread_time() - cpu
                    span.set(cpu_ms=cpu * 1e3)
        finally:
            if stat is not None:
                self.stats[stat + "_time"] += time.perf_counter() - started
                self.stats[stat + "_cpu"] += cpu

    def _any_active(self) -> bool:
        return any(slot.active for slot in self.slots)

    def _any_ready(self) -> bool:
        return any(slot.ready for slot in self.slots)

    def _any_admitting(self) -> bool:
        """Mixed mode: slots parked at a prefill watermark, waiting for
        decode steps to carry their prompt windows."""
        return self.mixed and any(
            slot.prefill_pos is not None and slot.request is not None
            for slot in self.slots
        )

    def _drain_queue(self, block: bool) -> None:
        try:
            if block:
                # idle: the engine is between busy phases — the next
                # mixed step's inter-dispatch gap would measure idle
                # time, not the per-step host tax (see _process_mixed)
                self._last_mixed_end = 0.0
                with self._phase("engine.wait_for_work", "idle"):
                    item = self._queue.get(timeout=0.05)
            else:
                item = self._queue.get_nowait()
            if item is not None:
                self._pending.append(item)
        except queue.Empty:
            return
        while True:
            try:
                item = self._queue.get_nowait()
                if item is not None:
                    self._pending.append(item)
            except queue.Empty:
                return

    def _find_warm_slot(self, request: GenerationRequest) -> Optional[int]:
        if request.session_id is None:
            return None
        for i, slot in enumerate(self.slots):
            if (
                not slot.active
                and slot.session_id == request.session_id
                and slot.history is not None
            ):
                return i
        return None

    def _find_slot(
        self, request: GenerationRequest, exclude: frozenset = frozenset()
    ) -> Optional[int]:
        """``exclude`` protects slots serving as cross-slot prefix-copy
        sources this admission round: their rows must stay intact until
        the copies dispatch (after the cold batch), so they cannot be
        handed out or evicted in the same round."""
        # session hit first
        warm = self._find_warm_slot(request)
        if warm is not None:
            return warm
        for i, slot in enumerate(self.slots):
            if (
                not slot.active
                and slot.session_id is None
                and i not in exclude
            ):
                return i
        # evict the least-recently USED pinned session (a hot session's
        # warm cache survives slot pressure; the stalest one pays)
        victim: Optional[int] = None
        for i, slot in enumerate(self.slots):
            if not slot.active and i not in exclude and (
                victim is None
                or slot.last_used < self.slots[victim].last_used
            ):
                victim = i
        return victim

    # a PARTIAL prefix match must cover at least this many tokens to be
    # worth a warm admission (below it, warm ≈ cold anyway); full
    # extensions of the pinned history always qualify
    WARM_MIN_PREFIX = 16
    # warm-first admission fairness: after this many jump-aheads the
    # queue head is admitted regardless, so warm traffic can't starve it
    MAX_HEAD_SKIPS = 4
    # sparse per-request logit_bias entries threaded to the device as
    # [batch, MAX_LOGIT_BIAS] (id, value) pairs; padding = (0, 0.0),
    # a harmless +0 on token 0
    MAX_LOGIT_BIAS = 64

    def _session_warm(self, index: int, request: GenerationRequest):
        """Return the reusable prefix length for a warm admission, or
        None for cold.

        Longest-common-prefix reuse (the block-prefix-cache idea): chat
        templates re-render earlier turns with role markers the raw
        generated tokens don't carry, so a follow-up prompt usually
        EXTENDS only part of the pinned history before diverging. The
        shared prefix stays in the KV cache; prefill resumes from the
        divergence point and overwrites the stale rows beyond it."""
        slot = self.slots[index]
        prompt = request.prompt_tokens
        if self.stateful or not (
            request.session_id is not None
            and slot.session_id == request.session_id
            and slot.history
        ):
            # (a carried state has no snapshot at the shared prefix's
            # end: such a model's follow-ups prefill cold)
            return None
        lcp = self._lcp(prompt, slot.history)
        if lcp == len(prompt):
            # the prompt is entirely inside the cache: re-prefill the
            # last token so fresh logits exist for the first sample
            lcp = len(prompt) - 1
        if lcp <= 0:
            return None
        full_extension = lcp == len(slot.history)
        if not full_extension and lcp < self.WARM_MIN_PREFIX:
            return None
        return lcp

    @staticmethod
    def _lcp(a: List[int], b: List[int]) -> int:
        """Longest common prefix of two token lists (chunked slice
        compares so the common case runs at C speed)."""
        limit = min(len(a), len(b))
        lcp = 0
        while lcp < limit:
            n = min(64, limit - lcp)
            if a[lcp:lcp + n] == b[lcp:lcp + n]:
                lcp += n
                continue
            while lcp < limit and a[lcp] == b[lcp]:
                lcp += 1
            break
        return lcp

    def _find_prefix_source(
        self,
        request: GenerationRequest,
        cold_reserved: frozenset,
        warm_reserved: frozenset,
    ) -> Optional[Tuple[int, int, bool]]:
        """Best cross-slot prefix source for a sessionless-cold request:
        the slot whose cache holds the longest common prefix with the
        prompt. Returns (source slot, lcp, in_round) or None.

        Eligible sources, by dispatch-ordering safety:
        - this round's cold reservations (``in_round=True``) — their
          prefill batch dispatches BEFORE the copies, and their
          prompt is known from the reserved request (this is what makes
          n>1 choices submitted together share one prefill);
        - slots with ``history`` set and no undispatched reservation:
          decoding slots (decode writes only at positions ≥ length),
          prefilling slots (their prefill is already dispatched), and
          idle pinned sessions (protected from same-round eviction via
          ``_find_slot``'s exclude set).
        Warm reservations are skipped: their cache is mid-transition."""
        prompt = request.prompt_tokens
        # the best any source can reach: the full prompt minus the
        # last token (which is always re-prefilled for fresh logits)
        full = len(prompt) - 1
        best: Optional[Tuple[int, int, bool]] = None
        for i, slot in enumerate(self.slots):
            if i in cold_reserved:
                history = slot.request.prompt_tokens if slot.request else None
                in_round = True
            elif i in warm_reserved:
                continue
            else:
                history = slot.history
                in_round = False
                if slot.length < self.WARM_MIN_PREFIX:
                    # copyable rows are capped at slot.length, so this
                    # slot can never clear the reuse threshold — skip
                    # the O(prompt_len) LCP entirely
                    continue
            if not history:
                continue
            lcp = self._lcp(prompt, history)
            if not in_round:
                # an ACTIVE slot's newest history token has no KV row
                # yet — it is written by the NEXT decode dispatch (the
                # finish path trims history[:length] for the same
                # reason); only rows [0:length) are copyable
                lcp = min(lcp, slot.length)
            if lcp == len(prompt):
                # re-prefill the last token so fresh logits exist for
                # the first sample (same rule as the session-warm path)
                lcp = len(prompt) - 1
            if lcp < self.WARM_MIN_PREFIX:
                continue
            if best is None or lcp > best[1]:
                best = (i, lcp, in_round)
                if lcp >= full:
                    # full-prefix match: nothing can beat it — stop
                    # rescanning the remaining slots (the old scan was
                    # O(slots × prompt_len) per cold admission)
                    break
        return best

    def _drop_cancelled(self) -> None:
        """Resolve cancelled-before-admission requests without ever
        spending a slot or a prefill on them."""
        if any(r.cancelled for r in self._pending):
            keep: List[GenerationRequest] = []
            for queued in self._pending:
                if queued.cancelled:
                    self._resolve_cancelled(queued)
                else:
                    keep.append(queued)
            self._pending = keep
            self._hand_over()

    def _shed_expired(self) -> None:
        """Admission deadlines (serve ``--queue-timeout-s``): a pending
        request older than the deadline fails FAST with a typed
        :class:`~langstream_tpu.api.errors.QueueTimeoutError` instead of
        starving in ``_pending`` while its caller times out anyway —
        load shedding under sustained overload."""
        timeout = self.queue_timeout_s
        if not timeout or not self._pending:
            return
        now = time.perf_counter()
        keep: List[GenerationRequest] = []
        for request in self._pending:
            waited = now - getattr(request, "_submit_ts", now)
            if waited < timeout:
                keep.append(request)
            else:
                self._shed(request, waited)
        self._pending = keep

    def _shed(self, request: GenerationRequest, waited: float) -> None:
        shed = self.stats["requests_shed"]
        shed["queue_timeout"] = shed.get("queue_timeout", 0) + 1
        self.stats["requests"] += 1
        # Retry-After ≈ when a slot plausibly frees: the backlog this
        # request would wait behind × the EWMA decode-step time (a
        # coarse lower bound — better than a constant, cheap to compute)
        step_s = self._step_ewma if self._step_ewma else 0.05
        retry_after = max(1.0, len(self._pending) * step_s)
        flight.record(
            "request_shed",
            reason="queue_timeout",
            waited_s=round(waited, 3),
            queue_depth=len(self._pending),
            retry_after_s=round(retry_after, 3),
            trace_id=request.trace_id or "",
        )
        fail_request_future(request, api_errors.QueueTimeoutError(
            f"request waited {waited:.2f}s in the admission queue "
            f"(queue timeout {self.queue_timeout_s}s); shed before "
            "admission — retry later",
            retry_after_s=retry_after,
        ))

    def _admit(self) -> None:
        """Move pending requests into slots. Cold requests sharing a prompt
        bucket are prefilled in ONE batched device call, and warm-session
        follow-ups sharing a suffix bucket likewise batch into one
        prefill-at-offset dispatch (batches split into power-of-two group
        sizes so compilations stay bounded). A cold prompt past the
        largest bucket takes a slot alone, and while some slot decodes
        only one of them a call."""
        if self.mixed:
            return self._admit_mixed()
        if self.paged:
            return self._admit_paged()
        self._shed_expired()
        self._drop_cancelled()
        # whether this call has admitted a cold prompt past the largest
        # bucket, and whether the next one waits for the cycle's chunk
        long_cold = held = False
        while self._pending and not held:
            cold: List[Tuple[int, GenerationRequest]] = []
            cold_bucket: Optional[int] = None
            # suffix bucket -> [(slot index, request, reused prefix len)]
            warm: Dict[int, List[Tuple[int, GenerationRequest, int]]] = {}
            # cross-slot prefix copies this round: (src, dst, lcp).
            # When copies exist, round-end dispatch order is cold batch
            # -> copies -> long-warm -> warm suffix prefills, so a copy
            # always reads rows whose writes are already dispatched and
            # never rows a warm prefill is about to overwrite. Without
            # copies the old warm-first order is kept (better warm TTFT).
            copies: List[Tuple[int, int, int]] = []
            # session follow-ups with chunked (long) suffixes; deferred
            # to round end for the same reason — an inline dispatch
            # could overwrite a source's rows before a queued copy reads
            # them
            long_warm: List[Tuple[int, GenerationRequest, int]] = []
            sources: set = set()        # slots protected from eviction
            cold_reserved: set = set()  # this round's cold slot indices
            warm_reserved: set = set()  # this round's warm slot indices
            progressed = False
            while self._pending:
                # admit warm-eligible requests FIRST: a strictly-FIFO
                # admission lets a burst of cold requests evict pinned
                # sessions whose follow-ups sit right behind them in the
                # same queue (measured: zero reuse at 2× slot pressure).
                # Bounded both ways: the scan looks at most 2×slots deep
                # (deeper entries are nowhere near admission), and a head
                # request skipped MAX_HEAD_SKIPS times is force-admitted
                # so sustained warm traffic cannot starve cold arrivals.
                position, index, reused = 0, None, None
                head = self._pending[0]
                if getattr(head, "_skipped", 0) < self.MAX_HEAD_SKIPS:
                    depth = max(2 * self.max_slots, 8)
                    for p, queued in enumerate(self._pending[:depth]):
                        warm_index = self._find_warm_slot(queued)
                        if warm_index is None:
                            continue
                        lcp = self._session_warm(warm_index, queued)
                        if lcp is not None:
                            position, index, reused = p, warm_index, lcp
                            break
                request = self._pending[position]
                if index is None:
                    index = self._find_slot(request, frozenset(sources))
                    if index is not None:
                        reused = self._session_warm(index, request)
                if index is None:
                    break
                if position > 0:
                    head._skipped = getattr(head, "_skipped", 0) + 1
                largest = self.prefill_buckets[-1]
                if reused is not None:
                    slot = self.slots[index]
                    suffix = len(request.prompt_tokens) - reused
                    suffix_bucket = _bucket(suffix, self.prefill_buckets)
                    self._pending.pop(position)
                    slot.request = request  # reserve the slot
                    self.stats["session_hits"] += 1
                    warm_reserved.add(index)
                    if (
                        suffix > largest
                        or reused + suffix_bucket > self.max_seq_len
                    ):
                        # too big for one batched window, or a window at
                        # the reused offset would clamp past max_seq_len
                        # — the chunked path's overlap-shifted tail
                        # handles both (dispatched at round end)
                        long_warm.append((index, request, reused))
                        continue
                    warm.setdefault(suffix_bucket, []).append(
                        (index, request, reused)
                    )
                    continue
                prompt_len = len(request.prompt_tokens)
                if self.prefix_cache:
                    found = self._find_prefix_source(
                        request,
                        frozenset(cold_reserved),
                        frozenset(warm_reserved),
                    )
                else:
                    found = None
                if found is not None:
                    src, lcp, in_round = found
                    suffix = prompt_len - lcp
                    suffix_bucket = _bucket(suffix, self.prefill_buckets)
                    needs_long = (
                        suffix > largest
                        or lcp + suffix_bucket > self.max_seq_len
                    )
                    if src == index:
                        # the chosen slot itself holds the prefix (e.g.
                        # an evicted session's cache salvaged by a new
                        # request with the same template): rows already
                        # in place, no copy
                        self._pending.pop(position)
                        self.slots[index].request = request
                        self.stats["prefix_hits"] += 1
                        self.stats["prefix_tokens_reused"] += lcp
                        if needs_long:
                            self._prefill_long(index, request, lcp)
                            progressed = True
                        else:
                            warm.setdefault(suffix_bucket, []).append(
                                (index, request, lcp)
                            )
                            warm_reserved.add(index)
                        continue
                    if needs_long and not in_round:
                        # chunked suffix dispatches inline, so the copy
                        # must too (the source's rows are all from
                        # already-dispatched work — safe to read now)
                        self._pending.pop(position)
                        self.slots[index].request = request
                        self._dispatch_prefix_copy(src, index, lcp)
                        self._prefill_long(index, request, lcp)
                        progressed = True
                        continue
                    if not needs_long:
                        self._pending.pop(position)
                        self.slots[index].request = request
                        copies.append((src, index, lcp))
                        sources.add(src)
                        warm.setdefault(suffix_bucket, []).append(
                            (index, request, lcp)
                        )
                        warm_reserved.add(index)
                        continue
                    # needs_long with an in-round source: the source's
                    # prefill hasn't dispatched yet — fall through cold
                windows = _prefill_windows(
                    prompt_len, 0, self.prefill_buckets, self.stateful
                )
                if len(windows) > 1:
                    if prompt_len <= largest:
                        # it fits a bucket and would waste most of it:
                        # windows of a smaller one, and not rationed as
                        # below (a few short windows are no stall)
                        self.stats["prompts_windowed"] += 1
                    elif long_cold and self._any_ready():
                        # a chunked prompt holds the device for all its
                        # windows, and every decoding slot waits them
                        # out: while slots decode, ONE such prompt a
                        # cycle, so a stall is one prompt long however
                        # many slots came free together (admitted all at
                        # once, the slots that were freed together stay
                        # together, and their answers come in waves: a
                        # stall of several prompts, then nothing)
                        held = True
                        self.stats["long_prompts_held"] += 1
                        break
                    else:
                        long_cold = True
                    self._pending.pop(position)
                    self.slots[index].request = request  # reserve the slot
                    self._prefill_long(index, request, 0)
                    progressed = True
                    continue
                bucket = windows[0][1]
                if cold_bucket is None:
                    cold_bucket = bucket
                elif bucket != cold_bucket:
                    break  # different bucket: next outer round
                self._pending.pop(position)
                self.slots[index].request = request  # reserve the slot
                cold.append((index, request))
                cold_reserved.add(index)
                # batch caps at the largest power of two ≤ max_slots
                if len(cold) >= self.max_slots:
                    break
            if copies:
                # cold batch FIRST so same-round copies can source from
                # it, then the copies, then every warm suffix prefill
                # (which overwrites rows past each slot's reused point —
                # including, for long_warm, rows a copy may have read)
                if cold:
                    self._prefill_batch(cold, cold_bucket)
                    progressed = True
                for src, dst, lcp in copies:
                    self._dispatch_prefix_copy(src, dst, lcp)
                for index, request, reused in long_warm:
                    self._prefill_long(index, request, reused)
                    progressed = True
                for suffix_bucket, batch in warm.items():
                    self._prefill_warm_batch(batch, suffix_bucket)
                    progressed = True
            else:
                # no ordering constraint: keep warm-first (lower warm
                # TTFT — a warm suffix is much cheaper than a cold batch)
                for index, request, reused in long_warm:
                    self._prefill_long(index, request, reused)
                    progressed = True
                for suffix_bucket, batch in warm.items():
                    self._prefill_warm_batch(batch, suffix_bucket)
                    progressed = True
                if cold:
                    self._prefill_batch(cold, cold_bucket)
                    progressed = True
            if not progressed:
                return

    def _admit_paged(self) -> None:
        """Paged-layout admission. Block-granular matching against the
        persistent prefix cache replaces the dense path's slot-resident
        LCP scan (and its copy-ordering machinery — shared blocks are
        REFERENCED through the table, never copied), so a shared RAG or
        system prefix survives any slot turnover. Every request reserves
        its worst case (prompt + max_new, capped at max_seq_len) up
        front, so the decode path never allocates and cannot stall on
        pool pressure mid-flight; when the pool (after LRU eviction)
        cannot cover a reservation, the request simply stays pending
        until running requests release blocks.

        Round dispatch order is cold batch → long prefills → warm
        suffixes: a suffix admitted onto blocks published this round
        always reads rows whose writes are already dispatched."""
        self._shed_expired()
        self._drop_cancelled()
        self._import_pending_handoffs()
        largest = self.prefill_buckets[-1]
        while self._pending:
            cold: List[Tuple[int, GenerationRequest]] = []
            cold_bucket: Optional[int] = None
            # suffix bucket -> [(slot, request, resume offset)]
            warm: Dict[int, List[Tuple[int, GenerationRequest, int]]] = {}
            long_entries: List[Tuple[int, GenerationRequest, int]] = []
            progressed = False
            while self._pending:
                # warm-first session scan (shared with _admit_mixed)
                position, index, session_lcp = self._scan_admission()
                request = self._pending[position]
                if index is None:
                    break
                # probe the resume offset WITHOUT committing, so the
                # cold-bucket grouping check can end the round before
                # any blocks move (match() only touches LRU ticks); the
                # probe's match is handed to _paged_reserve so the
                # O(prompt_len) chain walk runs once per admission
                prompt_len = len(request.prompt_tokens)
                probe_match = None
                host_probe: List[Any] = []
                if session_lcp is not None:
                    probe = session_lcp
                elif self.prefix_cache:
                    probe_match = self.kv_manager.match(
                        request.prompt_tokens
                    )
                    # host-tier continuation after the HBM prefix scan:
                    # demoted chain entries extend the probe exactly as
                    # resident blocks would (reserve promotes them)
                    host_probe = self._host_probe(
                        request.prompt_tokens, probe_match
                    )
                    probe = (
                        probe_match[1] + len(host_probe) * self.block_size
                    )
                    while probe >= prompt_len:
                        if host_probe:
                            host_probe.pop()
                        probe -= self.block_size
                else:
                    probe = 0
                suffix = prompt_len - probe
                needs_long = suffix > largest or (
                    probe > 0
                    and probe + _bucket(suffix, self.prefill_buckets)
                    > self.max_seq_len
                )
                if probe == 0 and not needs_long:
                    bucket = _bucket(prompt_len, self.prefill_buckets)
                    if cold_bucket is None:
                        cold_bucket = bucket
                    elif bucket != cold_bucket:
                        break  # different bucket: next outer round
                resume = self._paged_reserve(
                    index, request, session_lcp, probe_match,
                    host_entries=host_probe,
                )
                if resume is None:
                    # pool exhausted even after eviction: every block is
                    # referenced by running work — wait for releases
                    break
                if position > 0:
                    head = self._pending[0]
                    head._skipped = getattr(head, "_skipped", 0) + 1
                self._pending.pop(position)
                self.slots[index].request = request  # reserve the slot
                if session_lcp is not None:
                    self.stats["session_hits"] += 1
                if resume < probe:
                    # a torn promotion fell back toward cold: the
                    # probe-based cold/warm grouping above no longer
                    # holds, so route through the long path — it
                    # handles ANY resume offset without disturbing the
                    # round's cold-bucket invariant
                    long_entries.append((index, request, resume))
                elif needs_long:
                    long_entries.append((index, request, resume))
                elif resume == 0:
                    cold.append((index, request))
                    if len(cold) >= self.max_slots:
                        break
                else:
                    warm.setdefault(
                        _bucket(prompt_len - resume, self.prefill_buckets),
                        [],
                    ).append((index, request, resume))
            if cold:
                self._prefill_batch(cold, cold_bucket)
                progressed = True
            for index, request, resume in long_entries:
                self._prefill_long(index, request, resume)
                progressed = True
            for suffix_bucket, batch in warm.items():
                self._prefill_warm_batch(batch, suffix_bucket)
                progressed = True
            if not progressed:
                return

    def _scan_admission(self):
        """Warm-first admission selection shared by the paged admission
        paths: prefer a pending request with a warm session slot (scan
        bounded to 2×slots deep; a head skipped MAX_HEAD_SKIPS times is
        force-admitted so warm traffic can't starve cold arrivals),
        else the queue head into any free/evictable slot. Returns
        (position in _pending, slot index or None, session lcp or
        None) — ONE policy, so mixed- and split-mode admission
        ordering can never diverge under identical traffic (the A/B's
        equal-traffic premise)."""
        position, index, session_lcp = 0, None, None
        head = self._pending[0]
        if getattr(head, "_skipped", 0) < self.MAX_HEAD_SKIPS:
            depth = max(2 * self.max_slots, 8)
            for p, queued in enumerate(self._pending[:depth]):
                warm_index = self._find_warm_slot(queued)
                if warm_index is None:
                    continue
                lcp = self._session_warm(warm_index, queued)
                if lcp is not None:
                    position, index, session_lcp = p, warm_index, lcp
                    break
        request = self._pending[position]
        if index is None:
            index = self._find_slot(request)
            if index is not None:
                session_lcp = self._session_warm(index, request)
        return position, index, session_lcp

    def _admit_mixed(self) -> None:
        """Token-budget admission (``prefill_mode: mixed``): a request
        claims a slot and its worst-case block reservation exactly like
        the split paged path, but NO prefill dispatch happens here —
        the slot parks as ADMITTING (``prefill_pos`` watermark) and
        successive mixed decode steps carry ``prefill_chunk``-token
        windows of its prompt alongside the decoding rows
        (:meth:`_dispatch_mixed`), so no stream ever stalls behind a
        monolithic bucket-sized prefill. Cold prompts are NOT published
        at admission: their blocks fill across several dispatches, and
        a duplicate matching the chain early would attend over rows not
        yet written (the split path's cold-batch-before-warm-suffix
        dispatch ordering does not exist here) — they publish at finish
        like every partially-matched prompt."""
        self._shed_expired()
        self._drop_cancelled()
        self._import_pending_handoffs()
        while self._pending:
            position, index, session_lcp = self._scan_admission()
            request = self._pending[position]
            if index is None:
                return
            probe_match = None
            host_probe: List[Any] = []
            if session_lcp is None and self.prefix_cache:
                probe_match = self.kv_manager.match(request.prompt_tokens)
                host_probe = self._host_probe(
                    request.prompt_tokens, probe_match
                )
            resume = self._paged_reserve(
                index, request, session_lcp, probe_match,
                publish_cold=False, host_entries=host_probe,
            )
            if resume is None:
                # pool exhausted even after eviction: every block is
                # referenced by running work — wait for releases
                return
            if position > 0:
                head = self._pending[0]
                head._skipped = getattr(head, "_skipped", 0) + 1
            self._pending.pop(position)
            slot = self.slots[index]
            slot.request = request
            if session_lcp is not None:
                self.stats["session_hits"] += 1
            self._assign_slot(index, request, reused=resume)
            slot.prefilling = True
            slot.prefill_pos = resume
            slot.prefill_reused = resume
            self._admit_seq += 1
            slot.prefill_seq = self._admit_seq
            slot.prefill_t0 = time.perf_counter()
            flight.record(
                "mixed_admit",
                slot=index,
                prompt_tokens=len(request.prompt_tokens),
                reused_tokens=resume,
                queue_depth=len(self._pending),
            )

    def _paged_reserve(
        self,
        index: int,
        request: GenerationRequest,
        session_lcp: Optional[int],
        match: Optional[Tuple[List[int], int]] = None,
        publish_cold: bool = True,
        host_entries: Optional[List[Any]] = None,
    ) -> Optional[int]:
        """Commit pool blocks for a request before it is admitted.
        Returns the resume offset — tokens already resident for this
        slot (session continuation, prefix-cache hit, or host-tier
        promotion) — or None when the pool cannot cover the
        reservation.

        ``host_entries`` is the host-tier continuation of ``match``
        (``_host_probe``): after the worst-case fresh allocation, those
        entries are scattered H2D into the first fresh blocks and
        published (publish-at-commit); a torn promotion aborts before
        anything publishes and the admission degrades to cold prefill.

        Copy-on-write happens here: a session follow-up that diverges
        mid-block gets a private copy of the boundary block, and shared
        blocks in the overwrite region are swapped for fresh ones (a
        full overwrite needs no copy) — published chains are never
        written after publication."""
        slot = self.slots[index]
        manager = self.kv_manager
        size = self.block_size
        prompt = request.prompt_tokens
        need_tokens = min(
            len(prompt) + request.sampling.max_new_tokens, self.max_seq_len
        )
        need_blocks = -(-need_tokens // size)
        if session_lcp is not None and slot.blocks:
            blocks = list(slot.blocks)
            keep_full, partial = divmod(session_lcp, size)
            replace: List[int] = []
            cow: Optional[int] = None
            if (
                partial
                and keep_full < len(blocks)
                and manager.is_shared(blocks[keep_full])
            ):
                cow = keep_full
                replace.append(keep_full)
            start_full = keep_full + (1 if partial else 0)
            for j in range(start_full, min(len(blocks), need_blocks)):
                if manager.is_shared(blocks[j]):
                    replace.append(j)
            extend = max(0, need_blocks - len(blocks))
            fresh = manager.allocate(len(replace) + extend)
            if fresh is None:
                return None
            for j, new in zip(replace, fresh):
                if j == cow:
                    self._dispatch_block_copy(blocks[j], new)
                manager.unref(blocks[j])
                blocks[j] = new
            blocks.extend(fresh[len(replace):])
            for extra in blocks[need_blocks:]:
                manager.unref(extra)  # shrink vs the previous reservation
            slot.blocks = blocks[:need_blocks]
            resume = session_lcp
        else:
            if slot.blocks:
                # evicting a pinned session (or leftover) for a new owner
                if slot.session_id is not None:
                    self._note_eviction(slot.session_id, slot.length)
                manager.release(slot.blocks)
                slot.blocks = None
                slot.session_id = None
                slot.history = None
                slot.length = 0
            matched: List[int] = []
            matched_tokens = 0
            if self.prefix_cache:
                # the admission loop's probe already walked the chain;
                # nothing can change it between probe and commit (same
                # engine-thread iteration, no allocation in between)
                matched, matched_tokens = (
                    (list(match[0]), match[1]) if match is not None
                    else manager.match(prompt)
                )
            promote = list(host_entries or [])
            # re-prefill at least the last prompt token so fresh logits
            # exist for the first sample (same rule as the dense paths).
            # Host-tier entries trim first: they continue the HBM chain,
            # so they are the chain's tail
            total = matched_tokens + size * len(promote)
            while promote and total >= len(prompt):
                promote.pop()
                total -= size
            while matched and matched_tokens >= len(prompt):
                matched.pop()
                matched_tokens -= size
            manager.ref(matched)
            fresh = manager.allocate(need_blocks - len(matched))
            if fresh is None:
                manager.release(matched)
                return None
            promoted = 0
            if promote:
                # worst-case-reserved promotion: the fresh allocation
                # above already covers every non-matched block, so the
                # H2D scatter targets the first len(promote) of them —
                # an abort leaves them private cold blocks (no client
                # error, no publish, no id recycled mid-chain)
                promoted = self._promote_host_chain(
                    prompt, matched, matched_tokens, promote, fresh
                )
            slot.blocks = matched + fresh
            if matched_tokens:
                self.stats["prefix_hits"] += 1
                self.stats["prefix_tokens_reused"] += matched_tokens
                manager.stats["hit_tokens"] += matched_tokens
            if promoted:
                self.stats["prefix_tokens_reused"] += promoted * size
                # journey admit class: the host tier (not cold prefill,
                # not a pure HBM hit) is what served this admission
                if getattr(request, "_jt_admit_class", None) is None:
                    request._jt_admit_class = (  # type: ignore[attr-defined]
                        "host-promote"
                    )
            if (
                self.prefix_cache and publish_cold
                and not matched_tokens and not promoted
            ):
                # publish a fully-cold prompt's blocks NOW so same-round
                # duplicates share them — safe because the cold batch
                # (which writes every one of these blocks) dispatches
                # before any warm suffix this round. Partially-matched
                # prompts publish their divergent tail at finish instead
                # (their suffix prefill dispatches in the warm wave).
                # Mixed admission passes publish_cold=False: its blocks
                # fill across several dispatches, so early publication
                # would let a duplicate read unwritten rows. (A promoted
                # admission already published its promoted chain —
                # publishing the unwritten tail here would expose it.)
                manager.publish(prompt, slot.blocks)
            resume = matched_tokens + promoted * size
        table = self._block_tables[index]
        table[:] = 0
        table[: len(slot.blocks)] = slot.blocks
        return resume

    # Tokens one prefill dispatch may carry (rows × bucket). Activations
    # and the warm path's [rows, heads, bucket, ctx] f32 scores scale
    # with it: next to 11 GB of Qwen-2.5-7B int8 weights + a 32 × 2048
    # KV cache, the v5e compiler refuses a 4 × 2048 warm prefill for
    # HBM and crashes outright on 16 × 2048.
    MAX_PREFILL_TOKENS = 4096

    def _max_prefill_rows(self, bucket: int) -> int:
        """Largest power-of-two group one dispatch at ``bucket`` takes."""
        rows = max(1, self.MAX_PREFILL_TOKENS // bucket)
        return 1 << (rows.bit_length() - 1)

    def _pow2_groups(self, batch: List[Any], bucket: int) -> List[List[Any]]:
        """Split into power-of-two group sizes (no padding rows — a
        padding row would have to scatter somewhere in the cache) so the
        per-(bucket, batch-size) compilation count stays logarithmic;
        no group exceeds :meth:`_max_prefill_rows`."""
        groups: List[List[Any]] = []
        remaining = batch
        limit = self._max_prefill_rows(bucket)
        while remaining:
            size = 1
            while size * 2 <= min(len(remaining), limit):
                size *= 2
            groups.append(remaining[:size])
            remaining = remaining[size:]
        return groups

    MAX_EVICTED_SESSIONS = 512

    def _note_eviction(self, session_id: str, cached_tokens: int) -> None:
        """Remember a pinned session whose warm cache was evicted, so a
        later follow-up's re-prefill is booked as eviction-induced
        recompute in the goodput ledger (bounded FIFO)."""
        if cached_tokens <= 0:
            return
        evicted = self._evicted_sessions
        evicted.pop(session_id, None)
        while len(evicted) >= self.MAX_EVICTED_SESSIONS:
            evicted.pop(next(iter(evicted)))
        evicted[session_id] = cached_tokens

    def _waste(self, reason: str, tokens: int) -> None:
        if tokens > 0:
            wasted = self.stats["tokens_wasted"]
            wasted[reason] = wasted.get(reason, 0) + tokens

    def _assign_slot(
        self, index: int, request: GenerationRequest, reused: int = 0
    ) -> None:
        """Reset a slot's bookkeeping for a newly admitted request.
        ``reused`` = cache tokens this admission did NOT re-prefill
        (session continuation / prefix copy / paged prefix hit)."""
        # journey ledger anchors: the single admission point for every
        # path (cold, mixed, session, handoff) stamps the queue→prefill
        # boundary and the admission class (unless an earlier stage —
        # handoff import, host promotion — already classified it)
        request._assigned_ts = (  # type: ignore[attr-defined]
            time.perf_counter()
        )
        if getattr(request, "_jt_admit_class", None) is None:
            request._jt_admit_class = (  # type: ignore[attr-defined]
                "hbm-hit" if reused > 0 else "cold"
            )
        slot = self.slots[index]
        if (
            slot.session_id is not None
            and slot.session_id != request.session_id
            and slot.history
        ):
            # a new owner is evicting this pinned session's warm cache
            self._note_eviction(slot.session_id, slot.length)
        if request.session_id is not None:
            cached = self._evicted_sessions.pop(request.session_id, None)
            if cached:
                # tokens the follow-up must re-prefill that its evicted
                # warm cache (or a prefix hit standing in for it) would
                # have served — upper-bounded by the stored history
                self._waste(
                    "evicted_recompute",
                    min(cached, len(request.prompt_tokens)) - reused,
                )
        if self.stateful and not reused:
            # the prefill at position 0 starts the slot's carried state
            # (recurrent, conv) from zeros (the family's window_attends)
            self.stats["state_resets"] += 1
        slot.generated = []
        slot.logprobs = []
        slot.tops = [] if self.logprobs_topk else None
        slot.history = list(request.prompt_tokens)
        slot.session_id = None
        slot.length = len(request.prompt_tokens)
        slot.last_used = time.monotonic()
        slot.epoch += 1
        slot.prefill_pos = None   # mixed admission re-parks it after this
        slot.prefill_reused = 0

    def _request_seed(self, request: GenerationRequest) -> int:
        """The request's sampling seed: explicit (OpenAI `seed`) or a
        fresh auto-seed, fixed for the request's whole lifetime."""
        if request.sampling.seed is not None:
            return request.sampling.seed & 0xFFFFFFFF
        assigned = getattr(request, "_auto_seed", None)
        if assigned is None:
            self._seed_sequence += 1
            assigned = (self.base_seed * 1_000_003 + self._seed_sequence) \
                & 0xFFFFFFFF
            request._auto_seed = assigned  # type: ignore[attr-defined]
        return assigned

    def _sampling_arrays(self, requests: List[GenerationRequest]):
        # numpy on purpose: jit dispatch converts implicitly, and the
        # multi-host mirror can serialize the arrays without a D2H sync
        return (
            np.asarray(
                [r.sampling.temperature for r in requests], dtype=np.float32
            ),
            np.asarray([r.sampling.top_k for r in requests], dtype=np.int32),
            np.asarray(
                [r.sampling.top_p for r in requests], dtype=np.float32
            ),
            np.asarray(
                [self._request_seed(r) for r in requests], dtype=np.uint32
            ),
        )

    def _penalty_arrays(self, slots: List[_Slot]):
        presence = np.zeros((self.max_slots,), dtype=np.float32)
        frequency = np.zeros((self.max_slots,), dtype=np.float32)
        for i, slot in enumerate(slots):
            if slot.active:
                presence[i] = slot.request.sampling.presence_penalty
                frequency[i] = slot.request.sampling.frequency_penalty
        return presence, frequency

    def _bias_rows(self, requests: List[Optional[GenerationRequest]]):
        """[len(requests), MAX_LOGIT_BIAS] (ids, values) for logit_bias;
        rows for None/bias-less requests are all (0, 0.0) — a +0 on
        token 0."""
        k = self.MAX_LOGIT_BIAS
        ids = np.zeros((len(requests), k), dtype=np.int32)
        values = np.zeros((len(requests), k), dtype=np.float32)
        vocab = self.config.vocab_size
        for row, request in enumerate(requests):
            bias = request.sampling.logit_bias if request else None
            if not bias:
                continue
            valid = [
                (int(token), float(value)) for token, value in bias.items()
                if 0 <= int(token) < vocab
            ]
            for column, (token, value) in enumerate(valid[:k]):
                ids[row, column] = token
                values[row, column] = value
        return ids, values

    def _prefill_batch(
        self, batch: List[Tuple[int, GenerationRequest]], bucket: int
    ) -> None:
        """Dispatch cold prefills (first token sampled in-jit) WITHOUT
        blocking — the result is picked up by :meth:`_harvest_prefills`
        while decode chunks for already-running slots continue."""
        faults.check("dispatch_error")
        for group in self._pow2_groups(batch, bucket):
            with self._prefill_phase(
                "cold", bucket, [index for index, _ in group],
                **self._attention_blocks(
                    [len(request.prompt_tokens) for _, request in group],
                    bucket,
                ),
            ) as batch_id:
                started = time.perf_counter()
                size = len(group)
                tokens = np.zeros((size, bucket), dtype=np.int32)
                lengths = np.zeros((size,), dtype=np.int32)
                slot_ids = np.zeros((size,), dtype=np.int32)
                for row, (index, request) in enumerate(group):
                    prompt = request.prompt_tokens
                    tokens[row, : len(prompt)] = prompt
                    lengths[row] = len(prompt)
                    slot_ids[row] = index
                    self._assign_slot(index, request)
                    self.slots[index].prefilling = True
                run = self._get_prefill(bucket)
                temperature, top_k, top_p, seeds = self._sampling_arrays(
                    [request for _, request in group]
                )
                bias_ids, bias_vals = self._bias_rows(
                    [request for _, request in group]
                )
                # ONE host-args list feeds both the mirror record and the
                # dispatch, so the replayed argument order cannot drift
                host_args = [
                    tokens, lengths, slot_ids,
                    temperature, top_k, top_p, seeds, bias_ids, bias_vals,
                ]
                paged_args = (
                    (self._block_tables[slot_ids],) if self.paged else ()
                )
                if self.mirror is not None:
                    self._check_mirror_layout()
                    # paged dispatches ship their block-table rows in
                    # dispatch-arg position (small int32 host metadata — no
                    # D2H of pool data); the follower's replay rebuilds the
                    # exact argument tuple from engine.paged
                    self.mirror.publish(
                        "prefill", {"bucket": bucket},
                        [*host_args[:3], *paged_args, *host_args[3:]],
                    )
                self._stamp_dispatch(
                    [request for _, request in group], batch_id, bucket
                )
                self.cache, self._counts, sampled, lps, tops, moe = run(
                    self.params, self.cache, *host_args[:3], *paged_args,
                    self._counts, *host_args[3:],
                )
                self.stats["prefill_calls"] += 1
                self.stats["prefill_time"] += time.perf_counter() - started
                # modeled prefill work (cumulative prefill MFU denominator
                # is prefill_time, which also absorbs the harvest wait)
                dispatch_flops = sum(
                    self.cost_model.prefill_flops(len(r.prompt_tokens))
                    for _, r in group
                )
                self.stats["prefill_flops"] += dispatch_flops
                # goodput ledger: bucket-rounding ghosts — positions the
                # padded [size, bucket] dispatch computes past each prompt's
                # end (up to ~2x a prompt's FLOPs at the worst bucket edge;
                # the mixed path caps the same waste at width−1 per window)
                live = sum(len(r.prompt_tokens) for _, r in group)
                self._waste("prefill_padding", size * bucket - live)
                self._log_dispatch(
                    "prefill", tokens=live, rows=size, wall=0.0,
                    prefill_tokens=live,
                )
                flight.record(
                    "prefill",
                    bucket=bucket,
                    batch=size,
                    warm=False,
                    reused_tokens=0,
                    wall_ms=round((time.perf_counter() - started) * 1e3, 3),
                    queue_depth=len(self._pending),
                    flops=dispatch_flops,
                )
                self._launched(
                    [(index, request) for index, request in group],
                    (sampled, lps, tops, moe), {}, started, batch_id,
                )

    def _prefill_warm_batch(
        self,
        batch: List[Tuple[int, GenerationRequest, int]],
        bucket: int,
    ) -> None:
        """Warm-session admissions sharing a suffix bucket: the cache
        already holds each slot's shared prefix; ONE bucketed
        prefill-at-offset dispatch writes every suffix (chunked prefill —
        no per-token forcing, no per-request dispatch). Groups split to
        power-of-two sizes to bound compilations, like cold prefill.
        Non-blocking, like :meth:`_prefill_batch`."""
        faults.check("dispatch_error")
        for group in self._pow2_groups(batch, bucket):
            with self._prefill_phase(
                "warm", bucket, [index for index, _, _ in group]
            ) as batch_id:
                started = time.perf_counter()
                size = len(group)
                tokens = np.zeros((size, bucket), dtype=np.int32)
                lengths = np.zeros((size,), dtype=np.int32)
                offsets = np.zeros((size,), dtype=np.int32)
                slot_ids = np.zeros((size,), dtype=np.int32)
                for row, (index, request, reused) in enumerate(group):
                    suffix = request.prompt_tokens[reused:]
                    tokens[row, : len(suffix)] = suffix
                    lengths[row] = len(suffix)
                    offsets[row] = reused
                    slot_ids[row] = index
                    self._assign_slot(index, request, reused)
                    self.slots[index].prefilling = True
                run = self._get_prefill_offset(bucket)
                temperature, top_k, top_p, seeds = self._sampling_arrays(
                    [request for _, request, _ in group]
                )
                bias_ids, bias_vals = self._bias_rows(
                    [request for _, request, _ in group]
                )
                host_args = [
                    tokens, lengths, offsets, slot_ids,
                    temperature, top_k, top_p, seeds, bias_ids, bias_vals,
                ]
                paged_args = (
                    (self._block_tables[slot_ids],) if self.paged else ()
                )
                if self.mirror is not None:
                    self._check_mirror_layout()
                    self.mirror.publish(
                        "prefill_offset", {"bucket": bucket},
                        [*host_args[:4], *paged_args, *host_args[4:]],
                    )
                self._stamp_dispatch(
                    [request for _, request, _ in group], batch_id, bucket
                )
                self.cache, self._counts, sampled, lps, tops, moe = run(
                    self.params, self.cache, *host_args[:4], *paged_args,
                    self._counts, *host_args[4:],
                )
                self.stats["warm_prefill_calls"] += 1
                self.stats["prefill_time"] += time.perf_counter() - started
                dispatch_flops = sum(
                    self.cost_model.prefill_flops(
                        len(r.prompt_tokens) - reused, offset=reused
                    )
                    for _, r, reused in group
                )
                self.stats["prefill_flops"] += dispatch_flops
                live = sum(
                    len(r.prompt_tokens) - reused for _, r, reused in group
                )
                self._waste("prefill_padding", size * bucket - live)
                self._log_dispatch(
                    "prefill", tokens=live, rows=size, wall=0.0,
                    prefill_tokens=live,
                )
                flight.record(
                    "prefill",
                    bucket=bucket,
                    batch=size,
                    warm=True,
                    reused_tokens=int(sum(r for _, _, r in group)),
                    wall_ms=round((time.perf_counter() - started) * 1e3, 3),
                    queue_depth=len(self._pending),
                    flops=dispatch_flops,
                )
                self._launched(
                    [(index, request) for index, request, _ in group],
                    (sampled, lps, tops, moe),
                    {index: reused for index, _, reused in group},
                    started, batch_id,
                )

    def _prefill_long(
        self, index: int, request: GenerationRequest, reused: int
    ) -> None:
        """A prompt (or warm-session suffix) taught in windows, left to
        right, each one a prefill-at-offset dispatch (non-blocking, like
        the batched paths): one longer than the largest bucket, which is
        what lets long-context prompts (ring/Ulysses scale) enter the slot
        cache without a giant single-dispatch bucket, and a cold one that
        would waste most of the bucket it fits. :func:`_prefill_windows`
        says which windows."""
        faults.check("dispatch_error")
        self._assign_slot(index, request, reused)
        self.slots[index].prefilling = True
        windows = _prefill_windows(
            len(request.prompt_tokens), reused, self.prefill_buckets,
            self.stateful,
        )
        with self._prefill_phase(
            "long", windows[-1][1], [index],
            offset=reused, windows=len(windows),
        ) as batch_id:
            self._dispatch_long(
                index, request, reused, windows, batch_id
            )

    def _dispatch_long(
        self,
        index: int,
        request: GenerationRequest,
        reused: int,
        windows: List[Tuple[int, int]],
        batch_id: int,
    ) -> None:
        prompt = request.prompt_tokens
        total = len(prompt)
        started = time.perf_counter()
        temperature, top_k, top_p, seeds = self._sampling_arrays([request])
        bias_ids, bias_vals = self._bias_rows([request])
        self._stamp_dispatch(
            [request], batch_id, windows[-1][1]
        )
        counted = []  # every window's counters; the host sums them
        for step, (offset, bucket) in enumerate(windows):
            chunk = prompt[offset:offset + bucket]
            tokens = np.zeros((1, bucket), dtype=np.int32)
            tokens[0, : len(chunk)] = chunk
            lengths = np.asarray([len(chunk)], dtype=np.int32)
            offsets = np.asarray([offset], dtype=np.int32)
            slot_ids = np.asarray([index], dtype=np.int32)
            run = self._get_prefill_offset(bucket)
            host_args = [
                tokens, lengths, offsets, slot_ids,
                temperature, top_k, top_p, seeds, bias_ids, bias_vals,
            ]
            paged_args = (
                (self._block_tables[slot_ids],) if self.paged else ()
            )
            if self.mirror is not None:
                self._check_mirror_layout()
                self.mirror.publish(
                    "prefill_offset", {"bucket": bucket},
                    [*host_args[:4], *paged_args, *host_args[4:]],
                )
            self.cache, self._counts, sampled, lps, tops, moe = run(
                self.params, self.cache, *host_args[:4], *paged_args,
                self._counts, *host_args[4:],
            )
            if moe is not None:
                counted.append(moe)
            if step == len(windows) - 1:
                # only the final window's sampled token is the real first
                # token; intermediate windows' samples are discarded
                self._launched(
                    [(index, request)], (sampled, lps, tops, counted or None),
                    {index: reused} if reused else {}, started, batch_id,
                )
        self.stats["warm_prefill_calls" if reused else "prefill_calls"] += 1
        self.stats["prefill_time"] += time.perf_counter() - started
        # chunked windows re-teach overlapped tail positions; modeling
        # each window at its own offset keeps the count exact anyway
        self.stats["prefill_flops"] += sum(
            self.cost_model.prefill_flops(
                min(bucket, total - offset), offset=offset
            )
            for offset, bucket in windows
        )
        # goodput: window positions beyond the new suffix — the shifted
        # tail's re-taught overlap (identical KV, wasted FLOPs)
        self._waste(
            "prefill_padding",
            sum(bucket for _, bucket in windows) - (total - reused),
        )
        for offset, bucket in windows:
            taught = min(bucket, total - offset)
            self._log_dispatch(
                "prefill", tokens=taught, rows=1,
                wall=0.0, prefill_tokens=taught,
            )

    def _launched(
        self,
        group: List[Tuple[int, GenerationRequest]],
        outputs: Tuple[Any, Any, Any, Any],
        reused: Dict[int, int],
        started: float,
        batch_id: int,
    ) -> None:
        """A prefill dispatch whose first tokens are still on the device:
        queued for :meth:`_harvest_prefills`, with the count of decode
        dispatches at its launch (a row joins if none came between)."""
        sampled, lps, tops, moe = outputs
        self._prefill_inflight.append({
            "group": group,
            "sampled": sampled,
            "lps": lps,
            "tops": tops,
            "moe": moe,
            "reused": reused,
            "started": started,
            "batch": batch_id,
            "decode_seq": self._decode_seq,
        })

    @contextlib.contextmanager
    def _prefill_phase(self, kind: str, bucket: int, slot_ids: List[int],
                       **attributes):
        """One prefill dispatch (batch build and jit call) as a child
        span of ``engine.admit``; yields the batch's number, which its
        requests' ring records carry too (``runtime/journey.py``). A
        chunked prompt's span also says where it starts (``offset``) and
        in how many ``windows`` it is taught; a cold one whose attention
        is the flash kernel, how many blocks the kernel computes
        (``attn_blocks``, ``attn_blocks_causal``: :meth:`_attention_blocks`)."""
        self._prefill_batches += 1
        with self._phase(
            "engine.prefill_dispatch",
            kind=kind, bucket=bucket, rows=len(slot_ids),
            batch=self._prefill_batches,
            slots=":".join(map(str, slot_ids)), **attributes,
        ):
            yield self._prefill_batches

    def _attention_blocks(
        self, lengths: List[int], bucket: int
    ) -> Dict[str, float]:
        """A cold prefill's ``attn_blocks`` and ``attn_blocks_causal``
        (``model.prefill_attention_blocks``: the flash kernel's steps
        computed and those of the causal bucket), added to the stats;
        none where its attention is not the flash kernel."""
        counts = model_lib.prefill_attention_blocks(
            self.config, lengths, bucket,
            self.paged_kernel if self.paged else None,
        )
        if counts is None:
            return {}
        self.stats["prefill_attn_blocks"] += counts[0]
        self.stats["prefill_attn_blocks_causal"] += counts[1]
        return {"attn_blocks": counts[0], "attn_blocks_causal": counts[1]}

    @staticmethod
    def _stamp_dispatch(
        requests: List[GenerationRequest], batch_id: int, bucket: int
    ) -> None:
        """The instant just before a prefill's jit call, on every
        request it carries (a finished leg's ``dispatched``)."""
        now = time.perf_counter()
        for request in requests:
            request._dispatched = (  # type: ignore[attr-defined]
                now, batch_id, bucket
            )

    def _check_mirror_layout(self) -> None:
        """Engine features the follower replay protocol cannot speak
        yet. Paged IS spoken: dispatch records carry the block-table
        rows (host-local int32 metadata) and COW block copies publish
        their own ``block_copy`` records, so a follower replays the
        identical device-side pool mutations without running the block
        allocator itself. Fail loudly on the rest instead of silently
        diverging shards."""
        if self.spec:
            # spec dispatches carry the device token-history operand and
            # return variable-width outputs the follower replay protocol
            # does not speak yet
            raise NotImplementedError(
                "multi-host mirror does not support spec_decode yet"
            )

    def _harvest_prefills(self) -> None:
        """Wait for every prefill dispatch in flight and emit its first
        tokens, oldest first (the device runs dispatches in order, so
        while the host hands out one record's tokens the device is
        already in the next). Runs just before a fresh decode chunk is
        built: the device runs these prefills ahead of that chunk
        anyway, so waiting costs the running streams nothing and the
        new slots ride the chunk."""
        while self._prefill_inflight:
            record = self._prefill_inflight[0]
            with self._phase(
                "engine.harvest_prefills",
                rows=len(record["group"]), batch=record["batch"],
            ) as span:
                try:
                    self._harvest_record(record)
                finally:
                    # the first tokens go now, not with the chunk's
                    span.set(handovers=self._hand_over())
                self._note_counters(span, record.get("moe"))
                # rows still live after their first token, with no decode
                # dispatch since their launch: they ride the next one
                joined = sum(
                    1 for index, request in record["group"]
                    if record["decode_seq"] == self._decode_seq
                    and self.slots[index].request is request
                )
                self.stats["prefill_join_rows"] += joined
                span.set(joined=joined)
            self._prefill_inflight.pop(0)

    def _harvest_record(self, record: Dict[str, Any]) -> None:
        """The oldest prefill dispatch: wait for its first tokens, then
        hand each to its slot."""
        wait_started = time.perf_counter()
        firsts = np.asarray(record["sampled"])
        lps = np.asarray(record["lps"])
        tops = record.get("tops")
        if tops is not None:
            tops = (np.asarray(tops[0]), np.asarray(tops[1]))
        waited = time.perf_counter() - wait_started
        self.stats["prefill_time"] += waited
        self.stats["prefill_join_wait"] += waited
        self.stats["prefill_rows"] += len(record["group"])
        age = time.perf_counter() - record["started"]
        if self.tracer.enabled:
            now_pc = time.perf_counter()
            for index, request in record["group"]:
                submit_ts = getattr(
                    request, "_submit_ts", record["started"]
                )
                tid = request.trace_id or ""
                self.tracer.event(
                    "engine.admission",
                    max(0.0, record["started"] - submit_ts),
                    trace_id=tid,
                    start=submit_ts,
                    slot=index,
                )
                reused = record.get("reused", {}).get(index, 0)
                self.tracer.event(
                    "engine.prefill",
                    max(0.0, now_pc - record["started"]),
                    trace_id=tid,
                    start=record["started"],
                    slot=index,
                    prompt_tokens=len(request.prompt_tokens),
                    # cache-served prefix vs actually-prefilled span:
                    # the acceptance evidence that a prefix-cache hit
                    # shrank this request's prefill work
                    reused_tokens=reused,
                    prefill_tokens=len(request.prompt_tokens) - reused,
                    ttft_ms=round((now_pc - submit_ts) * 1e3, 3),
                )
        for row, (index, request) in enumerate(record["group"]):
            self.slots[index].prefilling = False
            if request.replay_tokens:
                # resurrected session: fast-forward through the
                # accepted history instead of emitting the prefill's
                # own sample (see _resume_replay)
                self._resume_replay(
                    index, request,
                    reused=record.get("reused", {}).get(index, 0),
                )
            else:
                self._emit_token(
                    index, int(firsts[row]), float(lps[row]),
                    top=(
                        (tops[0][row].tolist(), tops[1][row].tolist())
                        if tops is not None else None
                    ),
                )
            request._prefill_time = age  # type: ignore[attr-defined]

    def _resume_replay(
        self, index: int, request: GenerationRequest, reused: int = 0
    ) -> None:
        """Fast-forward a resurrected session (supervisor rebuild).

        The prefill that just harvested taught the cache
        ``prompt + replay[:-1]``; this seeds the slot's bookkeeping with
        the accepted tokens and teacher-forces ``replay[-1]`` as the
        pending token — its KV row is written by the next decode step,
        exactly like a freshly sampled first token, so the continuation
        samples at cache position ``len(prompt) + len(replay)`` with the
        key the uncrashed oracle would have used. The prefill's OWN
        sampled token is discarded: its logits were computed without the
        restored penalty state, and the caller already holds the real
        token for that position. Penalty counts are restored
        position-exactly (:meth:`_restore_counts`), so greedy AND seeded
        stochastic continuations — penalties included — are bitwise
        identical to an uncrashed run. Replayed tokens are NOT re-emitted
        (the caller's stream already has them); they re-enter the final
        result through ``slot.generated``."""
        slot = self.slots[index]
        replay = list(request.replay_tokens)
        slot.generated = replay
        lps = list(request.replay_logprobs or [])
        slot.logprobs = lps + [0.0] * (len(replay) - len(lps))
        if slot.tops is not None:
            tops = list(request.replay_tops or [])
            slot.tops = tops + [([], [])] * (len(replay) - len(tops))
        slot.history.append(replay[-1])
        self._restore_counts(index, replay)
        # TTFT anchor for the resumed span: the next emitted token is
        # the first the NEW engine produces for this request
        request._first_token_ts = (  # type: ignore[attr-defined]
            time.perf_counter()
        )
        # goodput ledger: every token this admission re-prefilled is
        # crash-replay recompute the uncrashed oracle never paid for
        # (the paged prefix cache shrinks it via `reused`)
        self._waste(
            "crash_replay", len(request.prompt_tokens) - reused
        )
        flight.record(
            "session_resume",
            slot=index,
            replayed=len(replay),
            reused_tokens=reused,
            trace_id=request.trace_id or "",
        )
        if request.cancelled:
            self._finish(index, "cancelled")
        elif (
            len(replay) >= request.sampling.max_new_tokens
            or slot.length + 1 >= self.max_seq_len
        ):
            # the crash raced the finish: the session was already at its
            # budget/context boundary — close it out like the oracle did
            self._finish(index, "length")

    def _get_counts_restore(self):
        """Jitted single-row overwrite of the penalty-count array: the
        replay prefill reset the slot's row and counted its (discarded)
        sample; this puts back the exact multiset of tokens the crashed
        engine had accumulated, so the first resumed sample sees the
        same penalty adjustments the oracle's would."""
        fn = self._counts_restore_fn
        if fn is None:

            @_program("counts_restore")
            def run(counts, index, row):
                return (
                    jax.lax.dynamic_update_slice(
                        counts, row[None, :], (index, jnp.int32(0))
                    ),
                )

            fn = run
            self._counts_restore_fn = fn
        return fn

    def _restore_counts(self, index: int, tokens: List[int]) -> None:
        row = np.zeros((self.config.vocab_size,), dtype=np.int32)
        for token in tokens:
            if 0 <= token < self.config.vocab_size:
                row[token] += 1
        run = self._get_counts_restore()
        (self._counts,) = run(self._counts, np.int32(index), row)

    def _can_chain(self, inflight: Dict[str, Any]) -> bool:
        """A chunk may be pre-dispatched off the in-flight carry only when
        no admission is waiting and every active slot has ≥2 chunks of
        budget and context left (so the blind chunk can't overrun)."""
        if self._pending or self._prefill_inflight or self._any_admitting():
            # harvested prefill slots should join the NEXT chunk, not wait
            # out a blind pre-dispatched one (and mixed admitting slots
            # need every next dispatch to be a fresh mixed step)
            return False
        # worst-case tokens a chunk can emit per slot: each spec step
        # may accept every draft plus the bonus token
        budget = inflight["steps"] * self.spec_block
        for i, slot in enumerate(self.slots):
            if not inflight["active"][i]:
                continue
            if not slot.active or slot.epoch != inflight["epochs"][i]:
                return False
            request = slot.request
            if len(slot.generated) + 2 * budget > request.sampling.max_new_tokens:
                return False
            if slot.length + 1 + 2 * budget >= self.max_seq_len:
                return False
        return True

    def _dispatch_chunk(
        self,
        carry: Optional[Dict[str, Any]] = None,
        plan_next: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        """The run loop's one way to a decode dispatch (a chunk, or with
        ``plan_next`` a chained mixed step), under its phase span."""
        with self._phase("engine.dispatch_decode", "dispatch") as span:
            if plan_next is not None:
                record = self._dispatch_mixed(
                    carry=carry, plan_next=plan_next
                )
            else:
                record = self._dispatch_decode(carry=carry)
            span.set(
                steps=record["steps"],
                active=(
                    record["n_decode"] if record.get("mixed")
                    else int(record["active"].sum())
                ),
                chained=int(carry is not None),
            )
        self._decode_seq += 1
        return record

    def _dispatch_decode(
        self, carry: Optional[Dict[str, Any]] = None
    ) -> Dict[str, Any]:
        """Dispatch one decode chunk. With ``carry`` (a previous chunk's
        record), tokens/lengths chain on-device — no host round trip.
        In mixed mode, while any slot is admitting, the dispatch is a
        single mixed step instead (:meth:`_dispatch_mixed`)."""
        if carry is None and self._any_admitting():
            return self._dispatch_mixed()
        faults.check("dispatch_error")
        # chaos: a dispatch that WEDGES instead of erroring (stuck_step
        # sleeps `dur` seconds here) — the watchdog/escalation test shape
        faults.maybe_sleep("stuck_step")
        started = time.perf_counter()
        # summed (block-padded, for paged) context length of the chunk's
        # riders at dispatch — the roofline's attention/KV-read term
        kv_tokens = 0
        if carry is not None:
            steps = carry["steps"]
            active = carry["active"]
            # approximation: the carry chunk advanced every rider by its
            # step count. Unpadded for paged (block crossings unknown
            # without slot state, slight undercount), a rider that
            # hit a stop token mid-carry still counts (slight overcount),
            # and under spec decode a step advances 1..spec_block tokens
            # (reading the accepted counts here would sync on the carry
            # and defeat pipelining — steps is the guaranteed floor)
            # — _can_chain rules out budget/context finishes, so chains
            # stay rare-error-bounded; fresh dispatches are exact.
            kv_tokens = carry["kv_tokens"] + int(active.sum()) * steps
            (
                temperature, top_k, top_p, presence, frequency, seeds,
                bias_ids, bias_vals,
            ) = carry["sampling_arrays"]
            tokens_arg = carry["final_tokens"]
            lengths_arg = carry["final_lengths"]
            active_arg = carry["active_dev"]
            tables_arg = carry["tables_dev"]
            history_arg = carry["final_history"]
            epochs = carry["epochs"]
            if self.mirror is not None:
                # followers chain from their OWN previous decode output
                # (identical values — SPMD determinism), so the record
                # carries no arrays
                self.mirror.publish("decode_chained", {"steps": steps}, [])
        else:
            tokens = np.zeros((self.max_slots,), dtype=np.int32)
            lengths = np.zeros((self.max_slots,), dtype=np.int32)
            active = np.zeros((self.max_slots,), dtype=bool)
            temperature = np.zeros((self.max_slots,), dtype=np.float32)
            top_k = np.zeros((self.max_slots,), dtype=np.int32)
            top_p = np.zeros((self.max_slots,), dtype=np.float32)
            seeds_host = np.zeros((self.max_slots,), dtype=np.uint32)
            epochs = [0] * self.max_slots
            steps = self.decode_chunk
            history = (
                np.zeros((self.max_slots, self.max_seq_len), dtype=np.int32)
                if self.spec else None
            )
            for i, slot in enumerate(self.slots):
                lengths[i] = slot.length
                epochs[i] = slot.epoch
                if slot.ready:
                    active[i] = True
                    tokens[i] = slot.history[-1]
                    lengths[i] = slot.length + 1
                    kv_tokens += self.cost_model.kv_read_tokens(
                        slot.length + 1
                    )
                    temperature[i] = slot.request.sampling.temperature
                    top_k[i] = slot.request.sampling.top_k
                    top_p[i] = slot.request.sampling.top_p
                    seeds_host[i] = self._request_seed(slot.request)
                    if history is not None:
                        # drafting source: the slot's full token history
                        # (prompt + generated incl. the pending token —
                        # h[t] = token at cache position t)
                        history[i, : len(slot.history)] = slot.history
                    # a chunk writes cache positions up to
                    # length + steps·block − 1 (block = 1 + spec_k when
                    # speculating); drop to single-step near the context
                    # boundary — the in-jit draft clamp keeps even a
                    # single spec step inside the cache
                    if (
                        self.max_seq_len - slot.length - 1
                        < steps * self.spec_block
                    ):
                        steps = 1
            bias_ids, bias_vals = self._bias_rows(
                [slot.request if slot.ready else None for slot in self.slots]
            )
            presence, frequency = self._penalty_arrays(self.slots)
            if self.mirror is not None:
                self._check_mirror_layout()
                # paged: the full [S, M] tables ride the record (they
                # are the dispatch's 7th argument); chained chunks carry
                # nothing — followers reuse the tables from their carry,
                # exactly like the leader's device-resident carry
                table_args = (
                    (self._block_tables,) if self.paged else ()
                )
                self.mirror.publish("decode", {"steps": steps}, [
                    tokens, lengths, active, *table_args,
                    temperature, top_k, top_p, presence, frequency,
                    seeds_host, bias_ids, bias_vals,
                ])
            # device-resident args: chained chunks reuse the carry's
            # arrays with ZERO host->device transfers — re-uploading
            # per chunk serializes the engine thread on the transfer
            seeds = jnp.asarray(seeds_host)
            temperature = jnp.asarray(temperature)
            top_k = jnp.asarray(top_k)
            top_p = jnp.asarray(top_p)
            presence = jnp.asarray(presence)
            frequency = jnp.asarray(frequency)
            bias_ids = jnp.asarray(bias_ids)
            bias_vals = jnp.asarray(bias_vals)
            tokens_arg = jnp.asarray(tokens)
            lengths_arg = jnp.asarray(lengths)
            active_arg = jnp.asarray(active)
            history_arg = jnp.asarray(history) if self.spec else None
            # block tables are device-resident in the carry like every
            # other chained operand (tables of active riders cannot
            # change while _can_chain holds)
            tables_arg = (
                jnp.asarray(self._block_tables) if self.paged else None
            )
        # telemetry snapshot AT DISPATCH: by processing time a rider may
        # have finished and its slot been recycled to a new request, so
        # live-slot reads would mis-attribute the chunk. Chained chunks
        # inherit the carry's snapshot — _can_chain guarantees the rider
        # set is unchanged
        trace_ids, queue_depth, kv_frac = "", 0, 0.0
        kv_blocks, prefix_hit_tokens = 0, 0
        if carry is not None:
            trace_ids = carry["trace_ids"]
            queue_depth = carry["queue_depth"]
            kv_frac = carry["kv_frac"]
            kv_blocks = carry["kv_blocks"]
            prefix_hit_tokens = carry["prefix_hit_tokens"]
        elif self.tracer.enabled or flight.RECORDER.enabled:
            if self.paged:
                kv_blocks = self.kv_manager.blocks_in_use
                prefix_hit_tokens = self.kv_manager.stats["hit_tokens"]
            trace_ids = ",".join(
                slot.request.trace_id
                for i, slot in enumerate(self.slots)
                if active[i] and slot.active and slot.request.trace_id
            )
            queue_depth = len(self._pending)
            if self.paged:
                kv_frac = round(
                    self.kv_manager.blocks_in_use / float(self.num_blocks), 4
                )
            else:
                kv_frac = round(
                    sum(slot.length for slot in self.slots if slot.active)
                    / float(self.max_slots * self.max_seq_len),
                    4,
                )
        run = self._get_decode(steps)
        paged_args = (tables_arg,) if self.paged else ()
        out_valid = out_drafted = final_history = out_moe = None
        if self.spec:
            (
                self.cache, self._counts, out_tokens, out_lps, out_valid,
                out_drafted, out_tops, final_tokens, final_lengths,
                final_history,
            ) = run(
                self.params, self.cache, tokens_arg, lengths_arg,
                active_arg, active_arg, history_arg, *paged_args,
                self._counts, temperature, top_k, top_p, presence,
                frequency, seeds, bias_ids, bias_vals,
            )
        else:
            (
                self.cache, self._counts, out_tokens, out_lps, out_tops,
                final_tokens, final_lengths, out_moe,
            ) = run(
                self.params, self.cache, tokens_arg, lengths_arg,
                active_arg, active_arg, *paged_args, self._counts,
                temperature, top_k, top_p, presence, frequency, seeds,
                bias_ids, bias_vals,
            )  # arg order mirrored by FollowerExecutor._decode — keep in sync
        return {
            "out_tokens": out_tokens,
            "out_lps": out_lps,
            "out_tops": out_tops,
            "out_valid": out_valid,
            "out_drafted": out_drafted,
            "out_moe": out_moe,
            "final_tokens": final_tokens,
            "final_lengths": final_lengths,
            "final_history": final_history,
            "active": active,
            "active_dev": active_arg,
            "tables_dev": tables_arg,
            "sampling_arrays": (
                temperature, top_k, top_p, presence, frequency, seeds,
                bias_ids, bias_vals,
            ),
            "epochs": list(epochs),
            "steps": steps,
            "started": started,
            "kv_tokens": kv_tokens,
            "trace_ids": trace_ids,
            "queue_depth": queue_depth,
            "kv_frac": kv_frac,
            "kv_blocks": kv_blocks,
            "prefix_hit_tokens": prefix_hit_tokens,
        }

    def _log_dispatch(
        self, kind: str, *, tokens: int, rows: int, wall: float,
        steps: int = 0, prefill_tokens: int = 0,
    ) -> None:
        """Token-denominated dispatch log (every device dispatch, prefill
        included): the interference-bound evidence the mixed A/B and the
        regression test read — ``prefill_tokens`` is the prompt work a
        single dispatch serializes in front of every running stream.
        ``wall`` is the dispatch-to-harvest time for SYNCHRONOUS entries
        (decode chunks, mixed steps) and 0.0 for the split path's
        non-blocking prefill dispatches (their device time overlaps
        decode and is unobservable at dispatch) — token counts, not
        walls, are the cross-kind comparison this log exists for."""
        if len(self.dispatch_log) < 65536:
            self.dispatch_log.append({
                "kind": kind,
                "tokens": int(tokens),
                "rows": int(rows),
                "steps": int(steps),
                "prefill_tokens": int(prefill_tokens),
                "wall": wall,
            })

    def _plan_mixed_chain(self, inflight: Dict[str, Any]):
        """Two-step window plan (mixed-step carry): decide whether the
        NEXT mixed step is host-predictable from the in-flight one and,
        if so, name its rows. Window content for step N+1 is derivable
        at plan time — watermarks advanced deterministically when N was
        dispatched and ``completes`` is part of N's plan — so the only
        host-unknown input is N's sampled tokens, which stay on device
        (:meth:`_get_mixed`'s ``prev_sampled`` operand). Returns a plan
        dict (``riders`` = rows chained off N's device sample,
        ``windows`` = prompt windows, ``width``) or the invalidation
        reason that forces the next dispatch back to host-built:

        - ``admission``: queued/admitted work N's carried sampling
          arrays don't cover;
        - ``replay``: a resurrected session completes at N — its next
          token is teacher-forced, not N's speculated sample;
        - ``budget``: a rider could finish by length during N;
        - ``width``: the window ladder changes width at N+1;
        - ``condemned``: the supervisor condemned this engine;
        - ``drained``: no windows remain — the mixed phase is over and
          plain (decode-carry-chainable) chunks take back over;
        - ``epoch``: a carried row's slot was recycled (paranoia guard).
        """
        if not self._running or self._crashed is not None:
            return "condemned"
        if self._pending or self._prefill_inflight:
            return "admission"
        prev_plan = inflight["plan"]
        prev_completes = inflight["completes"]
        prev_decode = inflight["decode_mask"]
        prev_offsets = inflight["offsets"]
        prev_num = inflight["num_tokens"]
        epochs = inflight["epochs"]
        riders: List[int] = []
        admitting: List[int] = []
        for i, slot in enumerate(self.slots):
            carried = prev_decode[i] or (i in prev_plan)
            if slot.request is None:
                if carried:
                    return "epoch"
                continue
            if slot.epoch != epochs[i]:
                # the slot acquired a request AFTER the in-flight step
                # was planned — its sampling params are not in the
                # carried device arrays
                return "admission" if not carried else "epoch"
            if prev_decode[i] or (i in prev_plan and prev_completes[i]):
                if i in prev_plan and slot.request.replay_tokens:
                    return "replay"
                riders.append(i)
            elif slot.prefill_pos is not None:
                admitting.append(i)
        for i in riders:
            slot = self.slots[i]
            # the speculated step emits one more token per rider on top
            # of the in-flight one: require room for both, so a rider
            # can only ever finish mid-chain by a (host-unpredictable)
            # stop/cancel — never by length (the _can_chain rule)
            generated = len(slot.generated) if slot.generated else 0
            if generated + 2 > slot.request.sampling.max_new_tokens:
                return "budget"
            if int(prev_offsets[i]) + int(prev_num[i]) + 2 >= self.max_seq_len:
                return "budget"
        windows, width = self._plan_windows(admitting)
        if not windows:
            return "drained"
        if width != inflight["width"]:
            # chain only across equal-width steps: the speculative
            # dispatch reuses the in-flight step's exact compiled
            # variant, and a ladder transition costs one host round
            # trip instead of a mid-stream variant swap
            return "width"
        return {"riders": riders, "windows": windows, "width": width}

    def _plan_windows(
        self, admitting: List[int]
    ) -> Tuple[Dict[int, Tuple[int, int]], int]:
        """FIFO token-budget window plan over admitting slot indices:
        ``{slot: (pos, n)}`` plus the pow2 dispatch width. ONE
        implementation serves the fresh dispatch AND the two-step chain
        plan — the chained ≡ unchained bitwise contract depends on the
        two schedules never diverging, so there must be nothing to keep
        in lockstep."""
        budget = self.prefill_chunk
        windows: Dict[int, Tuple[int, int]] = {}
        max_n = 1
        for i in sorted(admitting, key=lambda i: self.slots[i].prefill_seq):
            if budget <= 0:
                break
            slot = self.slots[i]
            remaining = len(slot.request.prompt_tokens) - slot.prefill_pos
            n = min(remaining, budget)
            if n <= 0:
                continue
            windows[i] = (slot.prefill_pos, n)
            budget -= n
            max_n = max(max_n, n)
        width = next(w for w in self._mixed_widths if w >= max_n)
        return windows, width

    def _note_carry_invalidation(self, reason: str, events: int = 1) -> None:
        invalidations = self.stats["mixed_carry_invalidations"]
        invalidations[reason] = invalidations.get(reason, 0) + events

    def _dispatch_mixed(
        self,
        carry: Optional[Dict[str, Any]] = None,
        plan_next: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        """Dispatch ONE mixed step: every ready slot rides as a Tq=1
        decode row, and up to ``prefill_chunk`` prompt tokens from
        admitting slots ride alongside as prefill windows — one fused
        token-ragged launch, one weight pass, one bounded dispatch. The
        budget is shared FIFO by admission order, so an early prompt is
        never starved by a later burst; a window that reaches its
        prompt's end samples the request's first token in the same
        dispatch (no separate harvest).

        With ``carry`` (the in-flight previous step's record) and
        ``plan_next`` (from :meth:`_plan_mixed_chain`), the step chains
        on-device: riders take the previous step's device-resident
        sample as their pending token, tables and sampling arrays are
        reused from the carry, and only the small prompt-window token
        delta uploads — no host round trip between consecutive mixed
        steps, exactly like ``_dispatch_decode(carry=...)``."""
        faults.check("dispatch_error")
        faults.maybe_sleep("stuck_step")
        started = time.perf_counter()
        slots_n = self.max_slots
        chained = carry is not None
        if chained:
            plan = plan_next["windows"]
            riders = plan_next["riders"]
            width = plan_next["width"]
        else:
            plan, width = self._plan_windows([
                i for i, s in enumerate(self.slots)
                if s.prefill_pos is not None and s.request is not None
            ])
            riders = [i for i, s in enumerate(self.slots) if s.ready]

        tokens = np.zeros((slots_n, width), dtype=np.int32)
        offsets = np.zeros((slots_n,), dtype=np.int32)
        num_tokens = np.zeros((slots_n,), dtype=np.int32)
        write_mask = np.zeros((slots_n,), dtype=bool)
        decode_mask = np.zeros((slots_n,), dtype=bool)
        completes = np.zeros((slots_n,), dtype=bool)
        chain_mask = np.zeros((slots_n,), dtype=bool)
        epochs = [slot.epoch for slot in self.slots]
        kv_tokens = 0          # decode rows' (block-padded) context reads
        prefill_kv_tokens = 0  # windows' prefix+window reads
        prefill_tokens = 0
        padding = 0
        for i, (pos, n) in plan.items():
            slot = self.slots[i]
            prompt = slot.request.prompt_tokens
            tokens[i, :n] = prompt[pos:pos + n]
            offsets[i] = pos
            num_tokens[i] = n
            write_mask[i] = True
            completes[i] = pos + n == len(prompt)
            prefill_tokens += n
            padding += width - n
            prefill_kv_tokens += self.cost_model.kv_read_tokens(pos + n)
        for i in riders:
            slot = self.slots[i]
            if chained:
                # pending token = the in-flight step's device-resident
                # sample (spliced in-jit via prev_sampled); next cache
                # position = the in-flight row's offset + its count
                chain_mask[i] = True
                offsets[i] = int(carry["offsets"][i]) + int(
                    carry["num_tokens"][i]
                )
            else:
                tokens[i, 0] = slot.history[-1]
                offsets[i] = slot.length
            num_tokens[i] = 1
            write_mask[i] = True
            decode_mask[i] = True
            kv_tokens += self.cost_model.kv_read_tokens(int(offsets[i]) + 1)
        # advance the taught watermarks NOW: the window content is final
        # once dispatched, and the NEXT step's plan (chained or fresh)
        # derives from the advanced bookkeeping
        for i, (pos, n) in plan.items():
            self.slots[i].prefill_pos = pos + n
        # telemetry snapshot AT DISPATCH (the decode-path rule): with
        # the carry, this step is processed only after the previous
        # one's processing may have finished a rider and recycled its
        # slot — live-slot reads at processing time would attribute the
        # step to a request whose tokens were never in it
        trace_ids = ""
        if self.tracer.enabled or flight.RECORDER.enabled:
            trace_ids = ",".join(
                self.slots[i].request.trace_id
                for i in riders
                if self.slots[i].request is not None
                and self.slots[i].request.trace_id
            )
        # goodput: ghost positions the padded [S, W] grid computes for a
        # short window — the mixed analogue of bucket padding, capped at
        # width−1 per admitting row per step (vs up to ~bucket/2 − 1 per
        # PROMPT on the split path)
        self._waste("prefill_padding", padding)
        if chained:
            # device-resident carry: tables, sampling arrays, and the
            # previous sample never leave the device — only the window
            # token delta above uploads
            tables_dev = carry["tables_dev"]
            sampling_dev = carry["sampling_dev"]
            prev_sampled = carry["sampled"]
        else:
            # sampling params are per-request constants, filled for
            # EVERY live row (planned or not) so a chained step can
            # reuse these device arrays verbatim even when the FIFO
            # budget reaches a row this step skipped
            temperature = np.zeros((slots_n,), dtype=np.float32)
            top_k = np.zeros((slots_n,), dtype=np.int32)
            top_p = np.zeros((slots_n,), dtype=np.float32)
            seeds = np.zeros((slots_n,), dtype=np.uint32)
            requests: List[Optional[GenerationRequest]] = [None] * slots_n
            for i, slot in enumerate(self.slots):
                request = slot.request
                if request is None:
                    continue
                requests[i] = request
                temperature[i] = request.sampling.temperature
                top_k[i] = request.sampling.top_k
                top_p[i] = request.sampling.top_p
                seeds[i] = self._request_seed(request)
            presence, frequency = self._penalty_arrays(self.slots)
            bias_ids, bias_vals = self._bias_rows(requests)
            sampling_dev = tuple(
                jnp.asarray(a) for a in (
                    temperature, top_k, top_p, presence, frequency,
                    seeds, bias_ids, bias_vals,
                )
            )
            tables_dev = jnp.asarray(self._block_tables)
            prev_sampled = np.zeros((slots_n,), dtype=np.int32)
        host_args = [
            tokens, offsets, num_tokens, write_mask, decode_mask,
            completes,
        ]
        if self.mirror is not None:
            self._check_mirror_layout()
            if chained:
                # chained records carry ONLY the window-delta metadata:
                # followers reuse tables/sampling/the previous sample
                # from their own carry — same contract as chained
                # decode, whose records carry nothing at all
                self.mirror.publish(
                    "mixed_chained", {"width": width},
                    [*host_args, chain_mask],
                )
            else:
                # mixed records carry per-row token counts (offsets /
                # num_tokens / the mask trio) in dispatch-arg position —
                # small int32/bool host metadata, like the table rows
                self.mirror.publish(
                    "mixed", {"width": width},
                    [
                        *host_args, self._block_tables, prev_sampled,
                        chain_mask,
                        *(np.asarray(a) for a in sampling_dev),
                    ],
                )
        run = self._get_mixed(width)
        self.cache, self._counts, sampled, lps, tops = run(
            self.params, self.cache, *host_args, tables_dev,
            self._counts, prev_sampled, chain_mask, *sampling_dev,
        )
        return {
            "mixed": True,
            "chained": chained,
            "width": width,
            "plan": plan,
            "sampled": sampled,
            "lps": lps,
            "out_tops": tops,
            "decode_mask": decode_mask,
            "completes": completes,
            "offsets": offsets,
            "num_tokens": num_tokens,
            "sampling_dev": sampling_dev,
            "tables_dev": tables_dev,
            "epochs": epochs,
            "steps": 1,
            "started": started,
            "kv_tokens": kv_tokens,
            "prefill_kv_tokens": prefill_kv_tokens,
            "prefill_tokens": prefill_tokens,
            "n_decode": int(decode_mask.sum()),
            "queue_depth": len(self._pending),
            "trace_ids": trace_ids,
        }

    def _process_mixed(self, inflight: Dict[str, Any]) -> None:
        with self._phase("engine.wait_chunk", steps=1):
            sampled = np.asarray(inflight["sampled"])
            lps = np.asarray(inflight["lps"])
            tops = inflight.get("out_tops")
            if tops is not None:
                tops = (np.asarray(tops[0]), np.asarray(tops[1]))
        with self._emit_span():
            self._account_mixed(
                inflight, sampled, lps, tops, time.perf_counter()
            )
        # chaos: deterministic engine-thread death AFTER this step's
        # tokens reached their callers (same point as _process_decode)
        faults.check("engine_thread_crash")

    @contextlib.contextmanager
    def _emit_span(self):
        """A harvested chunk's bookkeeping, then its tokens' hand-over,
        as ONE ``engine.emit`` span (``tokens`` and the ``handovers``
        that carried them as attributes, no span a token), timed into
        ``emit_time`` at the same boundaries. The hand-over is made
        whatever the bookkeeping raised: a token that reached
        ``slot.generated`` has reached its caller's loop by the time
        anyone else can see the slot."""
        with self._phase("engine.emit", "emit") as span:
            before = self.stats["tokens_generated"]
            try:
                yield span
            finally:
                span.set(
                    tokens=self.stats["tokens_generated"] - before,
                    handovers=self._hand_over(),
                )

    def _account_mixed(
        self, inflight: Dict[str, Any], sampled, lps, tops, ended: float
    ) -> None:
        """A harvested mixed step's bookkeeping (the body of its
        ``engine.emit`` span, which hands its tokens over at its end)."""
        wall = ended - inflight["started"]
        decode_mask = inflight["decode_mask"]
        completes = inflight["completes"]
        plan = inflight["plan"]
        n_decode = inflight["n_decode"]
        prefill_toks = inflight["prefill_tokens"]
        # the mixed step IS a decode step for its riders; its whole wall
        # is decode time — there is no separate prefill dispatch or
        # harvest stall to bill, which is the point of the fusion
        self.stats["decode_steps"] += 1
        self.stats["decode_chunks"] += 1
        self.stats["decode_token_steps"] += 1.0
        self.stats["mixed_steps"] += 1
        if inflight.get("chained"):
            self.stats["mixed_steps_chained"] += 1
        # host-gap evidence: device idle between the previous mixed
        # step's host processing and this step's dispatch — ~0 for
        # chained steps (dispatched before the previous harvest), the
        # per-step host tax for unchained ones (what the carry hides)
        gap_ms = (
            max(0.0, inflight["started"] - self._last_mixed_end) * 1e3
            if self._last_mixed_end else 0.0
        )
        self._last_mixed_end = ended
        self.stats["mixed_gap_time"] += gap_ms / 1e3
        self.stats["active_slot_steps"] += n_decode
        self.stats["decode_time"] += max(
            0.0, ended - max(inflight["started"], self._decode_busy_until)
        )
        self._decode_busy_until = max(self._decode_busy_until, ended)
        if len(self.chunk_log) < 65536:
            self.chunk_log.append((1, n_decode, wall))
        self._log_dispatch(
            "mixed", tokens=n_decode + prefill_toks,
            rows=n_decode + len(plan), wall=wall, steps=1,
            prefill_tokens=prefill_toks,
        )
        self._step_ewma = (
            wall if self._step_ewma is None
            else 0.8 * self._step_ewma + 0.2 * wall
        )
        DECODE_STEP_SECONDS.observe(wall)
        windows = list(plan.values())
        chunk_flops = self.cost_model.mixed_step_flops(
            n_decode, inflight["kv_tokens"], windows
        )
        chunk_bytes = self.cost_model.mixed_step_bytes(
            inflight["kv_tokens"] + inflight["prefill_kv_tokens"],
            n_decode + prefill_toks,
        )
        self.stats["decode_flops"] += chunk_flops
        self.stats["decode_bytes"] += chunk_bytes
        mfu = accounting.CostModel.mfu(chunk_flops, wall, self.peaks)
        mbu = accounting.CostModel.mbu(chunk_bytes, wall, self.peaks)
        if n_decode or plan:
            MFU_PER_CHUNK.observe(mfu)
            MBU_PER_CHUNK.observe(mbu)
        if self.tracer.enabled or flight.RECORDER.enabled:
            self.tracer.event(
                "engine.decode_chunk",
                wall,
                start=inflight["started"],
                trace_ids=inflight["trace_ids"],
                steps=1,
                active=n_decode,
                step_ms=round(wall * 1e3, 3),
                mfu=round(mfu, 6),
                mbu=round(mbu, 6),
            )
            flight.record(
                "decode_chunk",
                steps=1,
                active=n_decode,
                slots=self.max_slots,
                step_ms=round(wall * 1e3, 3),
                queue_depth=inflight["queue_depth"],
                kv_frac=round(
                    self.kv_manager.blocks_in_use / float(self.num_blocks),
                    4,
                ),
                tokens=self.stats["tokens_generated"],
                mfu=round(mfu, 6),
                mbu=round(mbu, 6),
                tokens_useful=self.stats["tokens_useful"],
                tokens_wasted=sum(self.stats["tokens_wasted"].values()),
                kv_blocks_in_use=self.kv_manager.blocks_in_use,
                kv_blocks_total=self.num_blocks,
                prefix_hit_tokens=self.kv_manager.stats["hit_tokens"],
                # mixed-dispatch series: how much prompt work rode this
                # step (ab_analyze reads these next to step_ms — the
                # stall-free-batching evidence); `chained`/`gap_ms` are
                # the carry's pipelining proof (chained steps overlap
                # the previous harvest, so their gap collapses to ~0)
                mixed=1,
                width=inflight["width"],
                prefill_rows=len(plan),
                prefill_tokens=prefill_toks,
                chained=1 if inflight.get("chained") else 0,
                gap_ms=round(gap_ms, 3),
            )
        stale_rows = 0
        for i, slot in enumerate(self.slots):
            if slot.epoch != inflight["epochs"][i] or not slot.active:
                if inflight.get("chained") and (
                    decode_mask[i] or (i in plan and completes[i])
                ):
                    # the speculated step sampled for a row whose
                    # request stopped/was cancelled while it was in
                    # flight — bill the discarded work to the ledger
                    stale_rows += 1
                continue
            top = (
                (tops[0][i].tolist(), tops[1][i].tolist())
                if tops is not None else None
            )
            if decode_mask[i]:
                slot.length += 1
                self._emit_token(i, int(sampled[i]), float(lps[i]), top=top)
            elif i in plan and completes[i]:
                request = slot.request
                slot.prefilling = False
                slot.prefill_pos = None
                request._prefill_time = (  # type: ignore[attr-defined]
                    ended - slot.prefill_t0
                )
                self.stats[
                    "warm_prefill_calls" if slot.prefill_reused
                    else "prefill_calls"
                ] += 1
                if self.tracer.enabled:
                    submit_ts = getattr(
                        request, "_submit_ts", slot.prefill_t0
                    )
                    self.tracer.event(
                        "engine.prefill",
                        max(0.0, ended - slot.prefill_t0),
                        trace_id=request.trace_id or "",
                        start=slot.prefill_t0,
                        slot=i,
                        prompt_tokens=len(request.prompt_tokens),
                        reused_tokens=slot.prefill_reused,
                        prefill_tokens=(
                            len(request.prompt_tokens)
                            - slot.prefill_reused
                        ),
                        ttft_ms=round((ended - submit_ts) * 1e3, 3),
                    )
                if request.replay_tokens:
                    # resurrected session: fast-forward through the
                    # accepted history instead of emitting the window's
                    # own sample (see _resume_replay)
                    self._resume_replay(
                        i, request, reused=slot.prefill_reused
                    )
                else:
                    self._emit_token(
                        i, int(sampled[i]), float(lps[i]), top=top
                    )
        if stale_rows:
            self._waste("carry_invalidated", stale_rows)
            self._note_carry_invalidation("stale_row", stale_rows)

    def _process_decode(self, inflight: Dict[str, Any]) -> None:
        if inflight.get("mixed"):
            return self._process_mixed(inflight)
        # a plain chunk ends any contiguous mixed phase: the next mixed
        # step's gap should not span the decode chunks in between
        self._last_mixed_end = 0.0
        steps = inflight["steps"]
        # plain: [S, steps]; spec: [S, steps, B] with a True-prefix
        # valid mask per (slot, step) — 1..B tokens per step
        with self._phase("engine.wait_chunk", steps=steps):
            out_host = np.asarray(inflight["out_tokens"])
            lps_host = np.asarray(inflight["out_lps"])
            tops = inflight.get("out_tops")
            if tops is not None:  # ([S, steps, K] ids, [S, steps, K] lps)
                tops = (np.asarray(tops[0]), np.asarray(tops[1]))
        with self._emit_span() as span:
            self._note_counters(span, inflight.get("out_moe"))
            self._account_decode(
                inflight, out_host, lps_host, tops, time.perf_counter()
            )
        # chaos: deterministic engine-thread death AFTER this chunk's
        # tokens reached their callers — the supervisor must resurrect
        # every live session from exactly this point, and the resumed
        # continuation must match the uncrashed oracle bitwise
        faults.check("engine_thread_crash")

    def _account_decode(
        self, inflight: Dict[str, Any], out_host, lps_host, tops,
        ended: float,
    ) -> None:
        """A harvested chunk's bookkeeping and the per-token loop, which
        records each token's callback for the hand-over and makes none
        (the body of its ``engine.emit`` span: ONE span a chunk, none a
        token)."""
        steps = inflight["steps"]
        active = inflight["active"]
        spec = self.spec
        wall = ended - inflight["started"]
        n_active = int(active.sum())
        drafted_total = accepted_total = 0
        if spec:
            valid_host = np.asarray(inflight["out_valid"])      # [S, steps, B]
            drafted_host = np.asarray(inflight["out_drafted"])  # [S, steps]
            emitted_total = int(valid_host[active].sum())
            drafted_total = int(drafted_host[active].sum())
            # per (slot, step) the block emits 1 + (leading accepted
            # drafts) tokens — the +1 is the bonus/fallback token the
            # verify logits fund either way
            accepted_total = emitted_total - n_active * steps
            self.stats["tokens_drafted"] += drafted_total
            self.stats["tokens_draft_accepted"] += accepted_total
            # rejected drafts burned verify FLOPs/bandwidth for tokens
            # nobody receives: a first-class wasted reason in the
            # goodput ledger, NOT silently folded into useful work
            self._waste("draft_rejected", drafted_total - accepted_total)
            token_steps = emitted_total / n_active if n_active else float(steps)
        else:
            token_steps = float(steps)
        # per-accepted-token wall-time normalizer (watchdog baseline):
        # equals `steps` for plain decode; under speculation a step
        # legitimately takes longer but yields 1..spec_k+1 tokens
        self.stats["decode_token_steps"] += token_steps
        self.stats["decode_steps"] += steps
        self.stats["decode_chunks"] += 1
        # pipelined chunks overlap in wall time (chunk N+1 is dispatched
        # before N is processed): account the UNION of busy intervals, or
        # decode_time would double-count overlap and the derived raw
        # capability (tokens / decode_time) would mismeasure
        self.stats["decode_time"] += max(
            0.0,
            ended - max(inflight["started"], self._decode_busy_until),
        )
        self._decode_busy_until = max(self._decode_busy_until, ended)
        self.stats["active_slot_steps"] += n_active * steps
        if len(self.chunk_log) < 65536:
            self.chunk_log.append((steps, n_active, wall))
        self._log_dispatch(
            "decode",
            tokens=(
                emitted_total if spec else steps * n_active
            ),
            rows=n_active, wall=wall, steps=steps,
        )
        step_s = wall / max(steps, 1)
        # EWMA step time: the Retry-After estimator for shed requests
        # and degraded-mode 503s (coarse but self-calibrating)
        self._step_ewma = (
            step_s if self._step_ewma is None
            else 0.8 * self._step_ewma + 0.2 * step_s
        )
        DECODE_STEP_SECONDS.observe(step_s)
        # per-chunk roofline: modeled FLOPs/HBM bytes over measured wall
        # → MFU/MBU vs the per-chip peak. A chunk overlapped by
        # pipelining shares wall time with its neighbour, so per-chunk
        # values can read slightly high; the cumulative gauges divide by
        # the busy-time union and stay honest.
        chunk_flops = self.cost_model.decode_chunk_flops(
            steps, n_active, inflight["kv_tokens"], block=self.spec_block
        )
        chunk_bytes = self.cost_model.decode_chunk_bytes(
            steps, n_active, inflight["kv_tokens"], block=self.spec_block
        )
        self.stats["decode_flops"] += chunk_flops
        self.stats["decode_bytes"] += chunk_bytes
        mfu = accounting.CostModel.mfu(chunk_flops, wall, self.peaks)
        mbu = accounting.CostModel.mbu(chunk_bytes, wall, self.peaks)
        if n_active:
            MFU_PER_CHUNK.observe(mfu)
            MBU_PER_CHUNK.observe(mbu)
        if self.tracer.enabled or flight.RECORDER.enabled:
            step_ms = round(wall / max(steps, 1) * 1e3, 3)
            # one span per chunk, tagged with every rider's trace id so
            # the merge tool can pull a request's device chunks into its
            # timeline without per-slot span spam; rider ids / queue
            # depth / KV pressure were snapshotted at DISPATCH (a slot
            # may have been recycled to a new request since)
            self.tracer.event(
                "engine.decode_chunk",
                wall,
                start=inflight["started"],
                trace_ids=inflight["trace_ids"],
                steps=steps,
                active=n_active,
                step_ms=step_ms,
                mfu=round(mfu, 6),
                mbu=round(mbu, 6),
            )
            kv_fields = {}
            if self.paged:
                # A/B-able pool pressure series (tools/ab_analyze.py):
                # blocks resident vs total, cumulative prefix-hit tokens
                kv_fields = dict(
                    kv_blocks_in_use=inflight["kv_blocks"],
                    kv_blocks_total=self.num_blocks,
                    prefix_hit_tokens=inflight["prefix_hit_tokens"],
                )
            if spec:
                # speculation gain series: drafted vs verify-accepted
                # candidates this chunk — ab_analyze digests the
                # acceptance rate and dispatches-per-token from these
                kv_fields.update(
                    drafted=drafted_total, accepted=accepted_total,
                )
            flight.record(
                "decode_chunk",
                steps=steps,
                active=n_active,
                slots=self.max_slots,
                step_ms=step_ms,
                queue_depth=inflight["queue_depth"],
                kv_frac=inflight["kv_frac"],
                tokens=self.stats["tokens_generated"],
                # efficiency series: per-chunk roofline utilization +
                # cumulative goodput ledger (ab_analyze digests these
                # into per-leg efficiency columns)
                mfu=round(mfu, 6),
                mbu=round(mbu, 6),
                tokens_useful=self.stats["tokens_useful"],
                tokens_wasted=sum(
                    self.stats["tokens_wasted"].values()
                ),
                **kv_fields,
            )
        for i, slot in enumerate(self.slots):
            if not active[i]:
                continue
            if slot.epoch != inflight["epochs"][i]:
                # the slot was recycled while this chunk was in flight —
                # its sampled tokens belong to the finished request
                continue
            for j in range(steps):
                if not slot.active:
                    # finished mid-chunk: surplus sampled tokens discarded;
                    # the length pointer stopped where the stop hit, so the
                    # garbage cache rows beyond it are dead
                    break
                if spec:
                    # variable tokens per step: the valid mask is a
                    # True-prefix over the block; a stop landing
                    # mid-block discards the accepted suffix the same
                    # way a mid-chunk stop discards surplus steps
                    # (length rewind — rows past the stop are dead)
                    for b in range(self.spec_block):
                        if not valid_host[i, j, b] or not slot.active:
                            break
                        slot.length += 1
                        self._emit_token(
                            i, int(out_host[i, j, b]),
                            float(lps_host[i, j, b]),
                            top=(
                                (
                                    tops[0][i, j, b].tolist(),
                                    tops[1][i, j, b].tolist(),
                                )
                                if tops is not None else None
                            ),
                        )
                    continue
                slot.length += 1
                self._emit_token(
                    i, int(out_host[i, j]), float(lps_host[i, j]),
                    top=(
                        (tops[0][i, j].tolist(), tops[1][i, j].tolist())
                        if tops is not None else None
                    ),
                )

    def _emit_token(
        self, index: int, token: int, logprob: float = 0.0, top=None
    ) -> None:
        """Record a newly generated token for a slot; finish if stopping."""
        slot = self.slots[index]
        request = slot.request
        if not slot.generated:
            # first token: TTFT anchor for the request span, the flight
            # log and the journey's prefill→decode boundary
            request._first_token_ts = (  # type: ignore[attr-defined]
                time.perf_counter()
            )
        slot.generated.append(token)
        slot.logprobs.append(logprob)
        if slot.tops is not None:
            slot.tops.append(top if top is not None else ([], []))
        hit_stop = token in request.stop_tokens
        if not hit_stop:
            # stop tokens stay out of the history so a session follow-up
            # prompt (which re-renders the answer without the stop marker)
            # still prefix-matches the warm cache
            slot.history.append(token)
        self.stats["tokens_generated"] += 1
        done = (
            hit_stop
            or request.cancelled
            or len(slot.generated) >= request.sampling.max_new_tokens
            or slot.length + 1 >= self.max_seq_len
        )
        if request.on_token is not None and not hit_stop:
            self._post_token(request, token, done)
        if done:
            if hit_stop:
                reason = "stop"
            elif request.cancelled:
                reason = "cancelled"
            else:
                reason = "length"
            self._finish(index, reason)

    def _finish(self, index: int, reason: str) -> None:
        slot = self.slots[index]
        request = slot.request
        generated = list(slot.generated)
        logprobs = list(slot.logprobs)
        tops = list(slot.tops) if slot.tops is not None else None
        if generated and generated[-1] in request.stop_tokens:
            generated = generated[:-1]
            logprobs = logprobs[:-1]
            if tops is not None:
                tops = tops[:-1]
        # resurrected sessions carry prompt + replay[:-1] in
        # prompt_tokens; usage accounting must report the ORIGINAL
        # prompt length, not the teacher-forced replay prefill's
        prompt_tokens = (
            request.prompt_len if request.prompt_len is not None
            else len(request.prompt_tokens)
        )
        result = GenerationResult(
            tokens=generated,
            prompt_tokens=prompt_tokens,
            finish_reason=reason,
            prefill_time=getattr(request, "_prefill_time", 0.0),
            logprobs=logprobs,
            top_logprobs=tops,
        )
        self.stats["requests"] += 1
        # goodput ledger: a cancelled request's tokens were decoded for
        # a caller that stopped listening (client disconnect / stop
        # string landed); everything else reached a live consumer
        if reason == "cancelled":
            self._waste("cancelled", len(generated))
        else:
            self.stats["tokens_useful"] += len(generated)
        # per-request latency attribution: TTFT (submit → first token) +
        # TPOT (mean inter-token gap after the first). Always computed —
        # the SLO histograms/burn rates must not depend on tracing being
        # enabled (one subtraction + histogram insert per request)
        now_pc = time.perf_counter()
        submit_ts = getattr(request, "_submit_ts", now_pc)
        first_ts = getattr(request, "_first_token_ts", now_pc)
        ttft_ms = round((first_ts - submit_ts) * 1e3, 3)
        tpot_ms = (
            round((now_pc - first_ts) / (len(generated) - 1) * 1e3, 3)
            if len(generated) > 1 else 0.0
        )
        TTFT_SECONDS.observe(max(0.0, ttft_ms / 1e3))
        if len(generated) > 1:
            TPOT_SECONDS.observe(max(0.0, tpot_ms / 1e3))
        REQUEST_SECONDS.observe(max(0.0, now_pc - submit_ts))
        if self.slo is not None:
            self.slo.tick()
        if self.tracer.enabled or flight.RECORDER.enabled:
            tid = request.trace_id or ""
            self.tracer.event(
                "engine.request",
                max(0.0, now_pc - submit_ts),
                trace_id=tid,
                start=submit_ts,
                slot=index,
                prompt_tokens=len(request.prompt_tokens),
                tokens=len(generated),
                finish_reason=reason,
                ttft_ms=ttft_ms,
                tpot_ms=tpot_ms,
            )
            flight.record(
                "request",
                trace_id=tid,
                prompt_tokens=len(request.prompt_tokens),
                tokens=len(generated),
                finish_reason=reason,
                ttft_ms=ttft_ms,
                tpot_ms=tpot_ms,
            )
        # pin the slot for session reuse; otherwise free it fully
        slot.request = None
        slot.epoch += 1
        slot.generated = None
        slot.logprobs = None
        slot.tops = None
        if self.paged and slot.blocks is not None:
            if self.prefix_cache:
                # publish the completed prefix (prompt + generated) —
                # only rows actually IN the cache (the final sampled
                # token is never written before finish), full blocks
                # only. This is what makes the prefix persistent: the
                # chain outlives the slot, refcounted by the map.
                self.kv_manager.publish(
                    slot.history[: slot.length], slot.blocks
                )
                if request.export_handoff and reason != "cancelled":
                    # disaggregation prefill leg: serialize the chain
                    # just published, while the slot's refs still pin
                    # it (no eviction race inside this finish)
                    export_start = time.perf_counter()
                    result.kv_handoff = self._export_handoff(
                        slot, request
                    )
                    if result.kv_handoff is not None:
                        request._jt_export = (  # type: ignore[attr-defined]
                            export_start, time.perf_counter()
                        )
            if request.session_id is not None:
                slot.session_id = request.session_id
                slot.last_used = time.monotonic()
                slot.history = slot.history[: slot.length]
                # trim the worst-case reservation down to what the
                # session actually holds: an idle pinned session must
                # not sit on never-written tail blocks the allocator
                # can neither use nor evict (refcount pins them)
                keep = -(-slot.length // self.block_size)
                for extra in slot.blocks[keep:]:
                    self.kv_manager.unref(extra)
                slot.blocks = slot.blocks[:keep]
                self._block_tables[index, keep:] = 0
            else:
                # sessionless: drop the slot's references — uncached
                # blocks free immediately, published ones stay matchable
                # until LRU eviction needs them
                self.kv_manager.release(slot.blocks)
                slot.blocks = None
                slot.session_id = None
                slot.history = None
                slot.length = 0
                self._block_tables[index, :] = 0
        elif request.session_id is not None:
            slot.session_id = request.session_id
            slot.last_used = time.monotonic()
            # keep only the history that is actually IN the cache (the
            # final sampled token is never written before finish)
            slot.history = slot.history[: slot.length]
        elif self.prefix_cache:
            # sessionless: the slot is fully free, but keep the (trimmed)
            # token history so later traffic sharing a template prefix
            # can cross-slot copy the rows instead of re-prefilling
            slot.session_id = None
            slot.history = slot.history[: slot.length]
        else:
            slot.session_id = None
            slot.history = None
            slot.length = 0
        self._emit_journey(
            index, request, reason, len(generated), ttft_ms, now_pc
        )
        if request.future is not None:
            self._post_future(request, result)

    def _emit_journey(
        self,
        index: int,
        request: GenerationRequest,
        reason: str,
        tokens: int,
        ttft_ms: float,
        now: float,
    ) -> None:
        """Assemble this leg's journey from the request's instants (all
        on ``time.perf_counter()``; ``now`` is the finish): keep them in
        the process-wide ring, tile the stages (wall time, since legs of
        other replicas join them; StageBuilder clamps), feed the
        per-stage histograms and SLO blame — always — and emit the
        ``journey`` flight record + per-stage trace events when those
        sinks are enabled."""
        submit = getattr(request, "_submit_ts", now)
        assigned = getattr(request, "_assigned_ts", submit)
        dispatched, batch, bucket = getattr(
            request, "_dispatched", (None, None, None)
        )
        first = getattr(request, "_first_token_ts", None)
        import_window = getattr(request, "_jt_import", None)
        export_window = getattr(request, "_jt_export", None)
        admit_class = getattr(request, "_jt_admit_class", None) or "cold"
        journey_ledger.record_leg(
            trace_id=request.trace_id or "",
            session_id=request.session_id or "",
            slot=index,
            submit=submit,
            assigned=assigned,
            dispatched=dispatched,
            batch=batch,
            bucket=bucket,
            first_token=first,
            finish=now,
            admit_class=admit_class,
            prompt_tokens=len(request.prompt_tokens),
            tokens=tokens,
            finish_reason=reason,
        )
        wall = tracing.wall
        submit_wall, admit_wall = wall(submit), wall(assigned)
        first_wall = wall(first) if first is not None else None
        now_wall = wall(now)
        builder = journey_ledger.StageBuilder()
        if request.handoff_export_ts is not None:
            # decode leg of a disaggregated request: the prefill
            # replica's export stamp (off the chunk-0 manifest) anchors
            # transit — fabric + assembly time until our submit
            builder.add(
                "handoff_transit", request.handoff_export_ts, submit_wall
            )
        builder.add(
            "queue",
            submit_wall,
            wall(import_window[0]) if import_window else admit_wall,
        )
        if import_window:
            builder.add(
                "handoff_import",
                wall(import_window[0]), wall(import_window[1]),
            )
        builder.add(
            "admit", admit_wall, admit_wall, admit_class=admit_class
        )
        builder.add(
            "prefill",
            admit_wall,
            first_wall if first_wall is not None else admit_wall,
        )
        decode_end = wall(export_window[0]) if export_window else now_wall
        builder.add(
            "decode",
            first_wall if first_wall is not None else admit_wall,
            decode_end,
        )
        if export_window:
            builder.add(
                "handoff_export",
                wall(export_window[0]), wall(export_window[1]),
            )
        builder.add(
            "finish",
            wall(export_window[1]) if export_window else decode_end,
            now_wall,
            finish_reason=reason,
        )
        stages = builder.stages
        journey_ledger.observe_stages(stages)
        if self.slo is not None and self.slo.targets_s:
            ttft_target = self.slo.targets_s.get("ttft")
            if (
                ttft_target is not None
                and ttft_ms / 1e3 > ttft_target
            ):
                self.slo.attribute(
                    "ttft",
                    journey_ledger.blame_stage(stages, first_wall, "ttft"),
                )
            tpot_target = self.slo.targets_s.get("tpot")
            if (
                tpot_target is not None
                and tokens > 1
                and first_wall is not None
                and (decode_end - first_wall) / (tokens - 1) > tpot_target
            ):
                self.slo.attribute(
                    "tpot",
                    journey_ledger.blame_stage(stages, first_wall, "tpot"),
                )
        if not (self.tracer.enabled or flight.RECORDER.enabled):
            return
        tid = request.trace_id or ""
        flight.record(
            "journey",
            trace_id=tid,
            session_id=request.session_id or "",
            slot=index,
            finish_reason=reason,
            tokens=tokens,
            admit_class=admit_class,
            first_token=first_wall,
            ttft_ms=ttft_ms,
            e2e_ms=round((now_wall - stages[0]["start"]) * 1e3, 3),
            stages=stages,
        )
        if self.tracer.enabled and tid:
            replica = flight.get_identity().get("replica", "")
            for stage in stages:
                self.tracer.event(
                    f"engine.journey.{stage['stage']}",
                    stage["end"] - stage["start"],
                    trace_id=tid,
                    start=stage["start"] - tracing.CLOCK_OFFSET,
                    slot=index,
                    replica=replica,
                )

    def _resolve_cancelled(self, request: GenerationRequest) -> None:
        """Resolve a request cancelled before it ever reached a slot."""
        self.stats["requests"] += 1
        if request.future is not None:
            self._post_future(
                request,
                GenerationResult(
                    # a resurrected request cancelled before re-admission
                    # still owes its caller the already-delivered tokens
                    tokens=list(request.replay_tokens or []),
                    prompt_tokens=(
                        request.prompt_len
                        if request.prompt_len is not None
                        else len(request.prompt_tokens)
                    ),
                    finish_reason="cancelled",
                    logprobs=list(request.replay_logprobs or []),
                ),
            )

    def _delivery(self, request: GenerationRequest) -> _Delivery:
        """The request's entry in what the bookkeeping in progress owes
        its loop."""
        owed = self._outbox.get(request.loop)
        if owed is None:
            owed = self._outbox[request.loop] = {}
        delivery = owed.get(id(request))
        if delivery is None:
            delivery = owed[id(request)] = _Delivery(request)
        return delivery

    def _post_token(
        self, request: GenerationRequest, token: int, done: bool
    ) -> None:
        """A token's callback: recorded for the hand-over that ends the
        bookkeeping in progress, or made here where the request has no
        loop."""
        if request.loop is not None:
            self._delivery(request).calls.append((token, done))
        else:
            request.on_token(token, done)

    def _post_future(self, request: GenerationRequest, result) -> None:
        """A finished request's result, behind its last token's callback
        in the same delivery."""
        if request.loop is not None:
            self._delivery(request).result = result
        else:
            request.future.set_result(result)

    def _hand_over(self) -> int:
        """Post everything the bookkeeping recorded since the last
        hand-over: ONE ``call_soon_threadsafe`` for each loop that is
        owed something, whatever the number of requests and tokens.
        Returns the posts made (``stats["emit_handovers"]``)."""
        if not self._outbox:
            return 0
        outbox, self._outbox = self._outbox, {}
        posts = 0
        for loop, owed in outbox.items():
            try:
                _post_to_loop(loop, list(owed.values()))
            except RuntimeError:
                # the callers' loop is closed: nobody is left to tell,
                # and the other loops are still owed theirs
                logger.warning(
                    "dropped a hand-over to a closed loop (%d requests)",
                    len(owed),
                )
                continue
            posts += 1
        self.stats["emit_handovers"] += posts
        return posts

    def _fail_all_pending(self) -> None:
        """Fail EVERY waiter promptly: queued, pending, and in-flight.
        A crashed engine must never leave a caller hanging (the future is
        the contract streaming callers await on — see
        JaxCompletionsService.get_chat_completions)."""
        error = RuntimeError("decode engine crashed; see logs")

        def fail(request: GenerationRequest) -> None:
            fail_request_future(request, error)

        # drain anything submitted but not yet picked up by the loop
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                self._pending.append(item)
        for request in self._pending:
            fail(request)
        self._pending = []
        self._prefill_inflight = []
        for slot in self.slots:
            if slot.active:
                fail(slot.request)
                slot.request = None
                slot.prefilling = False

    def _fail_stragglers(self) -> None:
        """Fail (with the typed retryable error) any request sitting in
        this retired engine's queue: the recovery drain already swept it
        once, so nothing will ever read these again. Futures the drain
        DID capture are untouched — they ride the resurrection."""
        error = api_errors.EngineRebuildingError(
            "engine is rebuilding after a crash; retry shortly",
            retry_after_s=2.0,
        )
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                return
            if item is not None:
                fail_request_future(item, error)

    # ------------------------------------------------------------------ #
    # supervisor takeover (runtime/supervisor.py)
    # ------------------------------------------------------------------ #
    # lint: allow(owned-by-violation) -- supervisor heal arc: runs only
    #   after the device thread has exited (crash hook fires on the
    #   dying thread itself) or was condemned + joined (request_restart);
    #   slot neutralization here fences any wedged zombie that survives
    #   the join timeout
    def drain_for_recovery(self) -> List[GenerationRequest]:
        """Turn every live session of this (dead or condemned) engine
        into a request the supervisor can resubmit to a rebuilt one.

        Active slots become REPLAY requests: ``prompt_tokens`` is
        rewritten to ``prompt + generated[:-1]`` (a normal prefill
        teaches it back into the cache — block-granular prefix hits make
        it cheap on paged engines) and the accepted tokens ride
        ``replay_tokens`` so :meth:`_resume_replay` fast-forwards the
        slot bitwise. Queued / pending / still-prefilling requests (no
        token ever reached their caller) resubmit untouched. Slots are
        neutralized FIRST, so a wedged engine thread that wakes up after
        an escalation takeover can never emit into a resurrected
        caller's stream."""
        requests: List[GenerationRequest] = []
        # flag FIRST, then sweep: any submit whose put lands after this
        # point either gets collected below or fails itself in submit()
        # (_fail_stragglers) — no interleaving leaves a caller hanging
        self._recovery_drained = True
        # drain anything submitted but never picked up by the dead loop
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                self._pending.append(item)
        for slot in self.slots:
            if not slot.active:
                continue
            request = slot.request
            generated = list(slot.generated or [])
            logprobs = list(slot.logprobs or [])
            tops = list(slot.tops) if slot.tops is not None else None
            # neutralize before snapshotting anything else: a zombie
            # thread finds the slot inactive and skips emission
            slot.request = None
            slot.prefilling = False
            slot.prefill_pos = None
            slot.epoch += 1
            original = (
                request.prompt_len if request.prompt_len is not None
                else len(request.prompt_tokens)
            )
            if generated:
                prompt = request.prompt_tokens[:original]
                request.prompt_len = original
                request.prompt_tokens = prompt + generated[:-1]
                request.replay_tokens = generated
                request.replay_logprobs = logprobs
                request.replay_tops = tops
            requests.append(request)
        requests.extend(self._pending)
        self._pending = []
        self._prefill_inflight = []
        return requests

    # lint: allow(owned-by-violation) -- supervisor heal arc: runs on the
    #   dying engine thread itself (the crash hook) or once the condemned
    #   thread was joined; drain_for_recovery has fenced a wedged survivor
    #   off its slots, and one that wakes finds no cache and ends
    def retire(self) -> None:
        """Superseded (the supervisor's heal arc, after the drain): leave
        the /metrics aggregation at once (a superseded engine must not
        double-count against its replacement) and hand the device its
        memory back BEFORE the replacement asks for its own. The engine
        itself sits in cycles (its programs' closures, the crash hook)
        and goes whenever the collector next looks; its cache, a tenth
        of the chip or more, must not wait for that. The weights are the
        replacement's too and stay."""
        _LIVE_ENGINES.discard(self)
        self.cache = None
        self._counts = None

    # lint: allow(owned-by-violation) -- supervisor heal arc: runs on
    #   the rebuilt engine BEFORE start(), so its device thread does not
    #   exist yet (no concurrent mutator)
    def absorb_stats(self, previous: Dict[str, Any]) -> None:
        """Carry a crashed predecessor's cumulative counters into this
        engine so every /metrics series stays monotonic across a
        supervisor rebuild (a token counter dropping to zero reads as a
        counter reset mid-incident — exactly when dashboards matter)."""
        for key, value in previous.items():
            if isinstance(value, dict):
                mine = self.stats.setdefault(key, {})
                for reason, count in value.items():
                    mine[reason] = mine.get(reason, 0) + count
            elif isinstance(value, (int, float)):
                self.stats[key] = self.stats.get(key, 0) + value


def _sampling_keys(
    seeds: jnp.ndarray,       # [S] uint32 per-request seeds
    positions: jnp.ndarray,   # [S] cache positions (monotonic per step)
) -> jnp.ndarray:
    """One PRNG key per slot, derived from (seed, position) — sampling
    is a pure function of the request, never of its batch neighbours."""
    def derive(seed, position):
        return jax.random.fold_in(jax.random.PRNGKey(seed), position)

    return jax.vmap(derive)(seeds, positions)


def _rowwise_categorical(keys: jnp.ndarray, scaled: jnp.ndarray) -> jnp.ndarray:
    return jax.vmap(
        lambda key, row: jax.random.categorical(key, row)
    )(keys, scaled)


def _sample(
    logits: jnp.ndarray,      # [S, V] f32
    temperature: jnp.ndarray, # [S]
    top_k: jnp.ndarray,       # [S] (0 = disabled)
    keys: jnp.ndarray,        # [S] per-slot PRNG keys (_sampling_keys)
    top_p: Optional[jnp.ndarray] = None,  # [S] (0 = disabled)
    *,
    masked: Optional[jnp.ndarray] = None,  # precomputed _truncation_mask
) -> jnp.ndarray:
    """Per-slot sampling on device: greedy when temperature==0, else
    temperature softmax with optional top-k and/or top-p truncation.

    Tiered via ``lax.cond`` so the expensive paths only execute when a
    slot actually asks for them — the full [S, V] descending sort costs
    a large share of a decode step's wall time at a 128k vocab, and
    greedy/plain-categorical traffic (the common case) doesn't need it.
    A caller that already holds the truncation mask for these logits
    (the speculative acceptance pass needs it for its probabilities)
    passes it as ``masked`` so the truncated tier skips the re-sort."""
    slots, vocab = logits.shape
    greedy = jnp.argmax(logits, axis=-1)

    def plain(_):
        # temperature softmax, no truncation: categorical needs no sort
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        return _rowwise_categorical(keys, scaled)

    def truncated(_):
        m = _truncation_mask(logits, top_k, top_p) if masked is None else masked
        scaled = m / jnp.maximum(temperature, 1e-6)[:, None]
        return _rowwise_categorical(keys, scaled)

    any_truncation = jnp.any(top_k > 0)
    if top_p is not None:
        any_truncation = any_truncation | jnp.any(top_p > 0)

    def stochastic(_):
        return jax.lax.cond(any_truncation, truncated, plain, None)

    sampled = jax.lax.cond(
        jnp.any(temperature > 0),
        stochastic,
        lambda _: greedy,
        None,
    )
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


def _truncation_mask(
    logits: jnp.ndarray,      # [S, V]
    top_k: jnp.ndarray,       # [S] (0 = disabled)
    top_p: Optional[jnp.ndarray],  # [S] (0 = disabled)
) -> jnp.ndarray:
    """Top-k/top-p truncation as a -inf mask over the logits — the sort-
    based masking ``_sample``'s truncated tier applies before scaling.
    Shared with the speculative acceptance pass
    (``spec_decode._accept_or_fallback``), which needs the truncated
    distribution's probabilities rather than a sample, so the two paths
    cannot drift."""
    vocab = logits.shape[-1]
    sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]  # descending
    # top-k mask: keep logits >= k-th largest (k clamped to [1, V])
    k = jnp.clip(top_k, 0, vocab)
    kth_index = jnp.clip(k - 1, 0, vocab - 1)
    kth_value = jnp.take_along_axis(
        sorted_logits, kth_index[:, None], axis=1
    )
    masked = jnp.where(
        (k[:, None] > 0) & (logits < kth_value), -jnp.inf, logits
    )
    if top_p is not None:
        # nucleus: keep the smallest set of tokens whose mass >= p
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cumulative = jnp.cumsum(probs, axis=-1)
        # threshold = smallest sorted logit still inside the nucleus
        inside = cumulative - probs < top_p[:, None]
        cut = jnp.where(inside, sorted_logits, jnp.inf).min(axis=-1)
        masked = jnp.where(
            (top_p[:, None] > 0) & (masked < cut[:, None]),
            -jnp.inf, masked,
        )
    return masked


def _sample_with_logprob(
    logits: jnp.ndarray,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    keys: jnp.ndarray,
    top_p: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sample and also return each sampled token's log-probability under
    the UNTRUNCATED distribution (the model's own confidence — what the
    FLARE controller consumes; reference: OpenAI-style logprobs)."""
    token = _sample(logits, temperature, top_k, keys, top_p)
    return token, _token_logprob(logits, token)


def _token_logprob(logits: jnp.ndarray, token: jnp.ndarray) -> jnp.ndarray:
    """lp = logits[token] - logsumexp(logits): same value as a full
    log_softmax gather without materializing a second [S, V] array."""
    logits32 = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits32, token[:, None], axis=-1)[:, 0]
    return picked - jax.scipy.special.logsumexp(logits32, axis=-1)


def _top_logprobs(logits: jnp.ndarray, k: int):
    """Top-k alternative tokens + logprobs under the RAW untruncated
    distribution (OpenAI ``top_logprobs``): top_k commutes with the
    monotonic log_softmax, so rank on logits and normalize the k
    winners only."""
    logits32 = logits.astype(jnp.float32)
    vals, ids = jax.lax.top_k(logits32, k)
    lse = jax.scipy.special.logsumexp(logits32, axis=-1, keepdims=True)
    return ids.astype(jnp.int32), vals - lse
