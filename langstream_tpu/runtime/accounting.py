"""Efficiency accounting: roofline cost model + SLO burn-rate math.

The observability plane (PR 1) answers "where did a request's time go";
this module answers "how close to the hardware ceiling is the engine
running, and is the fleet meeting its SLOs" — the control signals every
perf PR is judged against (AIBrix / DeepServe treat MFU-style utilization
and SLO attainment as first-class scheduler inputs).

Three pieces, all analytical and dependency-free so they run identically
on a laptop and on-chip:

- :class:`PeakSpecs` — per-chip peak FLOP/s and HBM bandwidth
  (v5e-1 defaults; ``LANGSTREAM_PEAK_TFLOPS`` / ``LANGSTREAM_PEAK_HBM_GBS``
  override for other chip generations without a code change).
- :class:`CostModel` — FLOPs and HBM bytes per prefill token and per
  decode step, derived purely from the model config (layers, heads /
  kv_heads, head_dim, hidden, vocab, weight/KV quantization widths,
  dense vs paged KV layout). The engine multiplies these by measured
  chunk wall time to produce per-chunk **MFU** (model FLOP utilization)
  and **MBU** (memory-bandwidth utilization).
- :class:`SLOTracker` — multi-window (5m/1h) SLO burn rates computed
  from timestamped snapshots of the TTFT/TPOT latency histograms: the
  same ``le``-bucketed data every /metrics surface exposes, so the burn
  math is auditable from a scrape alone (:func:`violation_fraction`).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, Mapping, Optional, Tuple

# v5e-1 per-chip peaks (bf16 MXU; weight-only int8 halves weight BYTES
# but the matmuls still run in bf16 — qeinsum dequantizes into the
# contraction — so the FLOPs ceiling stays the bf16 one)
DEFAULT_PEAK_FLOPS = 197e12
DEFAULT_PEAK_HBM_GBS = 819.0

ENV_PEAK_TFLOPS = "LANGSTREAM_PEAK_TFLOPS"
ENV_PEAK_HBM_GBS = "LANGSTREAM_PEAK_HBM_GBS"


@dataclasses.dataclass(frozen=True)
class PeakSpecs:
    """Per-chip hardware ceilings the roofline divides by."""

    flops: float = DEFAULT_PEAK_FLOPS
    hbm_bytes_per_s: float = DEFAULT_PEAK_HBM_GBS * 1e9

    @classmethod
    def from_env(cls) -> "PeakSpecs":
        tflops = os.environ.get(ENV_PEAK_TFLOPS, "")
        gbs = os.environ.get(ENV_PEAK_HBM_GBS, "")
        return cls(
            flops=float(tflops) * 1e12 if tflops else DEFAULT_PEAK_FLOPS,
            hbm_bytes_per_s=(
                float(gbs) * 1e9 if gbs else DEFAULT_PEAK_HBM_GBS * 1e9
            ),
        )


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Analytical FLOPs/bytes per unit of engine work.

    Derived once from the model config at engine construction; every
    accessor is a handful of integer multiplies, cheap enough to run on
    the engine thread per dispatch.

    Conventions (all counts are per CHIP — utilization against the
    single-chip peak is what the bench reports and what A/B legs
    compare; under tensor parallelism ``tp_shards`` divides the sharded
    work so a tp=2 engine is not billed whole-model FLOPs/bytes per
    chip, which would overstate MFU/MBU by ~tp×):

    - matmul FLOPs: ``2 * params`` per token (multiply+add), the
      standard serving approximation (embedding lookups excluded).
    - attention FLOPs: QK^T + AV are each ``2 * ctx * num_heads *
      head_dim`` per token per layer → ``4 * ctx * heads * head_dim *
      layers`` total. GQA shrinks the KV *bytes* (kv_heads), not the
      query-side FLOPs.
    - decode-step HBM bytes: the full weight working set streams once
      per step (batched slots share it — that is the whole point of
      batching) plus each active slot's KV history read + 1 row written.
    - paged layout: KV reads round each slot's context up to the block
      size (any block-granular access touches whole blocks), and the
      byte model is KERNEL-aware (``paged_kernel``): the fused ragged
      Pallas kernel streams each table-addressed pool block once plus
      the table/metadata words themselves, while the gather/scatter
      reference composition reads the pool, WRITES a contiguous copy,
      and re-reads that copy in the attention einsum — 3× the KV-read
      traffic. Charging both legs the same bytes would make the slower
      leg's MBU read dishonestly high (:meth:`kv_read_bytes`).
    - weight-only int8 halves weight bytes (per-channel scales are
      <1% and excluded); int8 KV stores int8 values + one f32 scale per
      (layer, position, kv_head) for each of k and v.
    - tensor parallelism (``tp_shards`` > 1): weights shard over tp
      (heads/mlp/vocab rules — the whole parameter set to the serving
      approximation), the KV cache shards on its kv-head axis, and the
      query-head FLOPs split the same way, so weight bytes, KV
      row bytes, and every FLOPs accessor divide by ``tp_shards``.
      Block tables do NOT divide: they are replicated scalar-prefetch
      operands — every shard's kernel reads the full table — so the
      per-chip table words stay whole. Activations are replicated per
      chip (and excluded from the byte model like in the dense case).
    """

    params: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    weight_bytes: int      # per chip (already divided by tp_shards)
    kv_row_bytes: int      # per chip, per token of KV history, all layers
    kv_block_size: int = 1  # paged read granularity (1 = dense)
    # paged attention kernel the engine dispatches: "fused" | "reference"
    # (None = dense layout — no table indirection to charge for)
    paged_kernel: Optional[str] = None
    # tensor-parallel shard count: FLOPs accessors divide by this
    # (weight/KV BYTES are divided once at construction)
    tp_shards: int = 1

    @classmethod
    def from_model_config(
        cls,
        config: Any,
        *,
        weight_quant: Optional[str] = None,
        kv_quant: bool = False,
        kv_block_size: int = 1,
        paged_kernel: Optional[str] = None,
        tp: int = 1,
    ) -> "CostModel":
        params = held = config.num_params()
        head_dim = config.dims_per_head
        tp = max(1, int(tp))
        experts = getattr(config, "experts", None)
        if experts is not None:
            # routed experts: a token meets the experts it is sent to among
            # the held ones (in expectation), not every expert the chip
            # holds; a step's bytes are still all the weights held (a batch
            # of slots touches nearly every expert)
            idle = experts.held - experts.per_token * experts.held / experts.routed
            params -= int(
                (config.num_layers - experts.leading_dense) * idle
                * 3 * config.hidden_size * experts.intermediate_size
            )
        if getattr(config, "mla", None) is not None:
            # latent attention: one row of latents a token a layer
            return cls(
                params=params,
                num_layers=config.num_layers,
                num_heads=config.num_heads,
                num_kv_heads=1,
                head_dim=config.mla.kv_lora_rank + config.mla.qk_rope_head_dim,
                weight_bytes=held * 2,
                kv_row_bytes=config.num_layers * 2 * (
                    config.mla.kv_lora_rank + config.mla.qk_rope_head_dim
                ),
                kv_block_size=1,
                paged_kernel=None,
                tp_shards=1,
            )
        layers = config.num_layers
        if getattr(config, "mixers", None) is not None:
            # per-layer mixers: KV rows in the attention layers alone (a
            # linear-attention or conv layer's state does not grow with a
            # token)
            layers = sum(
                1 for mixer in config.mixers if mixer in ("sparse", "attention")
            )
        if kv_quant:
            # int8 values + one f32 scale per (layer, pos, kv_head) for
            # each of k and v
            kv_row_bytes = 2 * layers * config.num_kv_heads * (
                head_dim + 4
            )
        else:
            kv_row_bytes = (
                2 * layers * config.num_kv_heads * head_dim * 2
            )  # k+v, bf16
        return cls(
            params=params,
            num_layers=layers,
            num_heads=config.num_heads,
            num_kv_heads=config.num_kv_heads,
            head_dim=head_dim,
            weight_bytes=held * (1 if weight_quant == "int8" else 2) // tp,
            kv_row_bytes=kv_row_bytes // tp,
            kv_block_size=max(1, int(kv_block_size)),
            paged_kernel=paged_kernel,
            tp_shards=tp,
        )

    # ------------------------------------------------------------------ #
    # decode
    # ------------------------------------------------------------------ #
    def kv_read_tokens(self, ctx: int) -> int:
        """KV history rows a decode step actually reads for one slot at
        context ``ctx`` (paged gathers touch whole blocks)."""
        block = self.kv_block_size
        return -(-ctx // block) * block if block > 1 else ctx

    def kv_read_bytes(self, kv_tokens: float) -> float:
        """HBM bytes to get ``kv_tokens`` rows of (block-padded) KV
        history in front of the compute units, per the dispatched
        kernel:

        - dense: rows stream once.
        - paged fused: pool blocks stream once through the table-
          addressed index maps, plus the table/metadata words the
          kernel prefetches (one int32 per touched block per layer —
          the pallas_call runs once per layer inside the scan).
        - paged reference: ``gather_blocks`` reads the pool AND writes
          a contiguous copy, then attention re-reads the copy — 3× the
          row bytes — plus the same table reads for the gather indices.
        """
        base = float(self.kv_row_bytes) * kv_tokens
        if self.paged_kernel is None:
            return base
        table_bytes = 4.0 * self.num_layers * (
            -(-kv_tokens // self.kv_block_size)
        )
        if self.paged_kernel == "fused":
            return base + table_bytes
        return 3.0 * base + table_bytes

    def decode_chunk_flops(
        self, steps: int, active: int, kv_tokens: int, block: int = 1
    ) -> float:
        """FLOPs for one K-step decode chunk. ``kv_tokens`` is the sum of
        active slots' context lengths at dispatch (attention cost is
        linear in the summed context, so only the sum is needed).

        ``block`` is the verify width of a speculative step (1 + spec_k;
        1 = plain decode): every matmul processes ``block`` positions per
        slot per step, and each position attends over the slot's context
        plus its own in-block causal prefix — this is exactly the
        conversion speculation sells (k× the useful FLOPs for ~1× the
        weight bytes), so MFU must bill it."""
        in_block = active * block * (block - 1) / 2.0  # causal intra-block
        per_step = (
            2.0 * self.params * active * block
            + 4.0 * (kv_tokens * block + in_block)
            * self.num_heads * self.head_dim * self.num_layers
        )
        # per-chip under tp: matmul params and query heads both shard,
        # so the whole per-step FLOPs count divides by the shard count
        return per_step * steps / self.tp_shards

    def decode_chunk_bytes(
        self, steps: int, active: int, kv_tokens: int, block: int = 1
    ) -> float:
        """HBM bytes for one K-step decode chunk: weights once per step
        + each active slot's kernel-aware KV read (:meth:`kv_read_bytes`)
        + ``block`` rows written per slot per step. ``kv_tokens`` should
        already be block-padded for the paged layout
        (:meth:`kv_read_tokens` per slot, summed).

        ``block`` > 1 (speculative verify) does NOT multiply the weight
        or KV-read streams — the whole point of verifying k drafts in
        one forward is that they share the step's weight pass — only the
        KV rows written scale with the verify width. Billing k tokens at
        1-token bytes would overstate MBU by ~k×."""
        per_step = (
            float(self.weight_bytes)
            + self.kv_read_bytes(kv_tokens)
            + float(self.kv_row_bytes) * active * block
        )
        return per_step * steps

    def kv_handoff_bytes(self, tokens: int) -> float:
        """Bytes a paged-KV handoff (prefill/decode disaggregation)
        moves for ``tokens`` rows of history: whole-model rows —
        ``kv_row_bytes`` is per CHIP under tp, and an export
        concatenates every shard's kv-heads — block-padded like any
        pool access (the handoff ships whole blocks). This is the
        transfer price the disagg A/B reads next to its tail win, and
        what the engine's ``kv_handoff_*_bytes_total`` gauges should
        roughly integrate to."""
        return (
            float(self.kv_row_bytes) * self.tp_shards
            * self.kv_read_tokens(int(tokens))
        )

    def kv_demote_bytes(self, tokens: int) -> float:
        """D2H bytes to demote ``tokens`` rows of KV history into the
        host-DRAM tier (ISSUE 18). Same whole-model, block-padded row
        accounting as :meth:`kv_handoff_bytes` — the demote gather IS
        the handoff export jit pointed at PCIe instead of the fabric —
        so the on-chip handoff-bandwidth window doubles as this leg's
        calibration. Integrates to ``kv_host_demoted_bytes_total``."""
        return self.kv_handoff_bytes(tokens)

    def kv_promote_bytes(self, tokens: int) -> float:
        """H2D bytes to promote ``tokens`` rows back into the HBM pool
        through the donated import scatter. Symmetric with
        :meth:`kv_demote_bytes` (same rows, opposite direction); the
        price a promotion pays instead of the recompute FLOPs a cold
        re-teach would burn. Integrates to
        ``kv_host_promoted_bytes_total``."""
        return self.kv_handoff_bytes(tokens)

    # ------------------------------------------------------------------ #
    # prefill
    # ------------------------------------------------------------------ #
    def prefill_flops(self, new_tokens: int, offset: int = 0) -> float:
        """FLOPs to prefill ``new_tokens`` starting at cache position
        ``offset`` (warm continuation / chunked window): matmul
        ``2·P`` per token plus causal attention over each token's own
        prefix (position p costs ``4·p·heads·head_dim`` per layer)."""
        positions_sum = (
            new_tokens * offset + new_tokens * (new_tokens - 1) // 2
        )
        return (
            2.0 * self.params * new_tokens
            + 4.0 * positions_sum * self.num_heads * self.head_dim
            * self.num_layers
        ) / self.tp_shards  # per chip: params and heads shard over tp

    def prefill_bytes(self, new_tokens: int, offset: int = 0) -> float:
        """HBM bytes for a prefill dispatch: weights once + kernel-aware
        KV prefix read + the new rows written. Prefill is FLOPs-bound at
        any real length; this exists so prefill MBU is also reportable."""
        return (
            float(self.weight_bytes)
            + self.kv_read_bytes(self.kv_read_tokens(offset))
            + float(self.kv_row_bytes) * new_tokens
        )

    # ------------------------------------------------------------------ #
    # mixed prefill+decode dispatch (prefill_mode: mixed)
    # ------------------------------------------------------------------ #
    def mixed_step_flops(
        self,
        decode_rows: int,
        decode_kv_tokens: int,
        prefill_windows,  # [(offset, new_tokens), ...]
    ) -> float:
        """FLOPs for one mixed step: the decode riders' single-step
        chunk plus each admitting row's prefill window at its offset.
        Only LIVE tokens are billed (like every other accessor) — the
        padded [S, W] grid's ghost positions burn real device FLOPs but
        modeled-useful-work-over-wall is what MFU means, so padding
        shows up as lower MFU (and in the ``prefill_padding`` goodput
        reason), never as inflated utilization."""
        flops = self.decode_chunk_flops(1, decode_rows, decode_kv_tokens)
        for offset, new_tokens in prefill_windows:
            flops += self.prefill_flops(new_tokens, offset=offset)
        return flops

    def mixed_step_bytes(
        self, kv_tokens: float, rows_written: int
    ) -> float:
        """HBM bytes for one mixed step: ONE weight pass serves every
        row — decode riders AND prefill windows share it, which is the
        fusion's whole point (the split path streams the weights once
        for the prefill dispatch and again for the decode step) — plus
        the kernel-aware KV reads (decode contexts + window prefixes,
        block-padded, summed into ``kv_tokens``) and the new rows
        written (decode tokens + prefill window tokens)."""
        return (
            float(self.weight_bytes)
            + self.kv_read_bytes(kv_tokens)
            + float(self.kv_row_bytes) * rows_written
        )

    # ------------------------------------------------------------------ #
    # utilization
    # ------------------------------------------------------------------ #
    @staticmethod
    def mfu(flops: float, wall_s: float, peaks: PeakSpecs) -> float:
        return flops / wall_s / peaks.flops if wall_s > 0 else 0.0

    @staticmethod
    def mbu(hbm_bytes: float, wall_s: float, peaks: PeakSpecs) -> float:
        return (
            hbm_bytes / wall_s / peaks.hbm_bytes_per_s if wall_s > 0 else 0.0
        )


# ---------------------------------------------------------------------- #
# SLO burn rates from histogram snapshots
# ---------------------------------------------------------------------- #
def count_le(snapshot: Mapping[str, float], target: float) -> float:
    """Observations ≤ ``target`` in a cumulative ``le``-keyed histogram
    snapshot (:meth:`api.metrics.Histogram.snapshot` shape), linearly
    interpolated inside the bucket containing ``target``. Observations
    in the +Inf bucket never count as ≤ any finite target."""
    entries = sorted(
        (float("inf") if le == "+Inf" else float(le), value)
        for le, value in snapshot.items()
        if le not in ("sum", "count")
    )
    prev_upper, prev_cum = 0.0, 0.0
    for upper, cumulative in entries:
        if target <= upper:
            if upper == float("inf"):
                # target beyond the last finite bound: everything in the
                # +Inf bucket is (conservatively) a violation
                return prev_cum
            if upper == prev_upper:
                return cumulative
            fraction = (target - prev_upper) / (upper - prev_upper)
            return prev_cum + (cumulative - prev_cum) * max(
                0.0, min(1.0, fraction)
            )
        prev_upper, prev_cum = upper, cumulative
    return prev_cum


def violation_fraction(
    now: Mapping[str, float],
    then: Optional[Mapping[str, float]],
    target: float,
) -> Optional[float]:
    """Fraction of observations ABOVE ``target`` between two snapshots
    of the same histogram (``then`` = None means since the beginning).
    Returns None when no observations landed in the interval."""
    total = now.get("count", 0) - (then.get("count", 0) if then else 0)
    if total <= 0:
        return None
    ok = count_le(now, target) - (count_le(then, target) if then else 0.0)
    return max(0.0, min(1.0, (total - ok) / total))


class SLOTracker:
    """Multi-window SLO burn rates for TTFT/TPOT targets.

    Burn rate = (violation fraction in the window) / (error budget),
    the standard SRE multi-window shape: burn 1.0 means the service is
    consuming its budget exactly as fast as the SLO allows; >1 predicts
    a breach. Computed from timestamped snapshots of the engine's
    latency histograms, so the numbers agree with what a Prometheus
    scrape of the same buckets would show.

    Targets are p95 objectives (``objective=0.95`` → 5% budget):
    ``{"ttft_ms_p95": 200, "tpot_ms_p95": 30}`` — either key optional.
    """

    WINDOWS: Tuple[Tuple[str, float], ...] = (("5m", 300.0), ("1h", 3600.0))

    def __init__(
        self,
        targets: Mapping[str, Any],
        histograms: Mapping[str, Any],  # {"ttft": Histogram, "tpot": ...}
        *,
        objective: float = 0.95,
        snapshot_interval: float = 15.0,
    ) -> None:
        self.objective = float(objective)
        self.snapshot_interval = float(snapshot_interval)
        self.histograms = dict(histograms)
        self.targets_s: Dict[str, float] = {}
        for key in ("ttft", "tpot"):
            raw = targets.get(f"{key}_ms_p95")
            if raw and key in self.histograms:
                self.targets_s[key] = float(raw) / 1e3
        self._ring: Deque[Tuple[float, Dict[str, Dict[str, float]]]] = (
            deque()
        )
        # per-stage SLO blame (ISSUE 20): violating requests counted by
        # the journey stage that dominated the violated window, keyed
        # (kind, stage)  # guarded-by: _lock
        self._blame: Dict[Tuple[str, str], int] = {}
        self._lock = threading.Lock()

    def tick(self, now: Optional[float] = None) -> None:
        """Record a timestamped snapshot (at most one per
        ``snapshot_interval``); called per finished request and from
        :meth:`gauges`, so scraping alone keeps the windows honest."""
        now = time.monotonic() if now is None else now
        with self._lock:
            if self._ring and now - self._ring[-1][0] < self.snapshot_interval:
                return
            self._ring.append((
                now,
                {
                    key: self.histograms[key].snapshot()
                    for key in self.targets_s
                },
            ))
            horizon = now - self.WINDOWS[-1][1] - self.snapshot_interval
            while len(self._ring) > 1 and self._ring[1][0] <= horizon:
                self._ring.popleft()

    def attribute(self, kind: str, stage: Optional[str]) -> None:
        """Book one violating request against its dominant journey
        stage (``runtime/journey.blame_stage``) — the per-stage blame
        the burn rates alone cannot give: a burning TTFT budget with
        blame on ``queue`` is a capacity problem, on ``handoff_transit``
        a fabric problem, on ``prefill`` a scheduling one."""
        if not stage or kind not in ("ttft", "tpot"):
            return
        with self._lock:
            key = (kind, str(stage))
            self._blame[key] = self._blame.get(key, 0) + 1

    def _snapshot_before(
        self, key: str, cutoff: float
    ) -> Optional[Dict[str, float]]:
        """Newest ring snapshot taken at or before ``cutoff`` (None =
        tracker younger than the window → burn over the whole history)."""
        best = None
        for ts, snaps in self._ring:
            if ts <= cutoff:
                best = snaps.get(key)
            else:
                break
        return best

    def gauges(self, now: Optional[float] = None) -> Dict[str, float]:
        now = time.monotonic() if now is None else now
        self.tick(now)
        out: Dict[str, float] = {}
        budget = max(1e-9, 1.0 - self.objective)
        with self._lock:
            for key, target_s in self.targets_s.items():
                out[f"jax_engine_slo_{key}_p95_target_ms"] = round(
                    target_s * 1e3, 3
                )
                snap_now = self.histograms[key].snapshot()
                for label, window in self.WINDOWS:
                    then = self._snapshot_before(key, now - window)
                    fraction = violation_fraction(snap_now, then, target_s)
                    if fraction is not None:
                        out[f"jax_engine_slo_{key}_burn_rate_{label}"] = (
                            round(fraction / budget, 4)
                        )
            for (kind, stage), count in sorted(self._blame.items()):
                out[
                    "jax_engine_slo_blame_total"
                    f'{{kind="{kind}",stage="{stage}"}}'
                ] = float(count)
        return out
