#!/usr/bin/env python
"""Summarize the heal watcher's bench A/B artifacts and recommend
default flips.

Each A/B leg is one ``bench.py`` run whose last stdout line is saved as:
``bench_artifacts/bench_heal.json`` (main e2e run) plus ``_kvq`` (int8
KV cache), ``_flashdec0/1`` (flash-decode off/on at 2048 ctx),
``_admis`` (admission-chunk), and ``_warm``/``_trace``. This tool reads
whatever subset exists — including provisional (partial-window) records
— and prints a comparison table plus the default-flip recommendations
VERDICT r4 #2 asks for ("run the queued on-chip A/Bs and flip defaults
on wins"), so a result landing after the build session still turns
into action mechanically next round:

    python tools/ab_analyze.py [artifacts_dir]
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, Optional

LEGS = {
    "bench_heal.json": "main (bf16 KV, auto kernel)",
    "bench_heal_kvq.json": "int8 KV cache",
    "bench_heal_flashdec0.json": "flash-decode OFF @2048ctx/16slots",
    "bench_heal_flashdec1.json": "flash-decode ON @2048ctx/16slots",
    "bench_heal_admis.json": "admission-chunk 8",
    "bench_heal_paged.json": "paged KV, fused ragged kernel (--kv-layout paged)",
    "bench_heal_paged_ref.json": "paged KV, gather reference (--paged-kernel reference)",
    "bench_heal_spec.json": "speculative decoding (--spec-decode ngram)",
    "bench_heal_mixed.json":
        "paged KV, mixed prefill+decode dispatch (--prefill-mode mixed)",
    "bench_heal_mixed_carry.json":
        "mixed dispatch, device carry OFF control (--mixed-carry off)",
    "bench_heal_kv_tiers.json":
        "paged KV + host-DRAM demotion tier (--kv-host-blocks)",
    "bench_heal_paged_tp2.json": "paged KV, fused kernel, tp=2 mesh (--tp 2)",
    "bench_heal_paged_ref_tp2.json": "paged KV, gather reference, tp=2 mesh",
    "bench_heal_chaos.json":
        "chaos: mid-run engine crash + supervisor recovery (--chaos)",
    # fleet A/B (langstream_tpu/fleet/sim.py): same synthetic
    # shared-prefix traffic through the prefix-affinity router vs
    # blind round-robin — CPU legs, so they exist on every machine
    "bench_fleet_routed.json": "fleet: prefix-affinity routing (sim)",
    "bench_fleet_rr.json": "fleet: round-robin baseline (sim)",
    # prefill/decode disaggregation A/B (fleet/sim.py --disagg): role
    # pools + paged-KV handoff over the topic fabric vs the same
    # capacity unified, identical traffic — judged on the decode-side
    # tail (max TPOT excursion, p95 TTFT) at roughly equal tok/s
    "bench_fleet_disagg.json":
        "fleet: prefill/decode disaggregation + KV handoff (sim)",
    "bench_fleet_unified.json":
        "fleet: unified control for --disagg (sim)",
    # tiered KV pool A/B (fleet/sim.py --tiers): host-DRAM demotion
    # arenas + tier-tagged gossip vs the HBM-only pool on identical
    # pool-pressure traffic — judged on the eviction-recompute cut at
    # roughly equal tok/s
    "bench_fleet_tiered.json":
        "fleet: tiered KV pool, host-DRAM demotion arenas (sim)",
    "bench_fleet_untiered.json":
        "fleet: HBM-only control for --tiers (sim)",
}


def last_json_line(path: str) -> Optional[Dict[str, Any]]:
    """The bench contract: the LAST stdout line is the result."""
    record = None
    try:
        with open(path) as handle:
            for line in handle:
                line = line.strip()
                if not line:
                    continue
                try:
                    record = json.loads(line)
                except ValueError:
                    continue
    except OSError:
        return None
    return record


def describe(record: Dict[str, Any]) -> str:
    if record.get("error"):
        return f"FAILED @{record.get('phase')}: {record['error'][:60]}"
    if record.get("metric") == "fleet_sim":
        # fleet sim legs measure cache economics, not tok/s
        bits = [
            f"{record.get('prefix_hit_tokens', 0):.0f} prefix-hit tokens",
            f"shed {record.get('requests_shed', 0)}",
            f"reroutes {record.get('reroutes', 0)}",
            f"500s {record.get('client_errors', 0)}",
        ]
        if record.get("ttft_p50_s") is not None:
            bits.append(f"TTFT p50 {record['ttft_p50_s']:.2f}s")
        # disagg tail columns (ISSUE 15): what the disagg-vs-unified
        # pair is judged on — the worst same-replica inter-token gap,
        # p95 TTFT, and the equal-throughput premise (sim tok/s)
        if record.get("ttft_p95_s") is not None:
            bits.append(f"p95 {record['ttft_p95_s']:.2f}s")
        if record.get("max_tpot_excursion_s") is not None:
            bits.append(
                f"max TPOT exc {record['max_tpot_excursion_s']:.2f}s"
            )
        if record.get("tok_s"):
            bits.append(f"{record['tok_s']:.1f} sim tok/s")
        if record.get("roles"):
            roles = record["roles"]
            bits.append(
                f"pools P{roles.get('prefill', 0)}/D{roles.get('decode', 0)}"
            )
            bits.append(
                f"handoffs {record.get('handoff_imported', 0)}"
                f"/{record.get('handoff_exported', 0)}"
                f" (aborted {record.get('handoff_aborted', 0)},"
                f" orphaned {record.get('handoffs_orphaned', 0)})"
            )
        # tiered-pool columns (ISSUE 18): the --tiers pair's verdict —
        # re-teach work eviction burned vs hits the host tier absorbed
        if record.get("evicted_recompute_tokens") is not None:
            bits.append(
                f"evict recompute "
                f"{record['evicted_recompute_tokens']} tok"
            )
        if record.get("kv_host_hit_tokens") is not None:
            bits.append(f"host hits {record['kv_host_hit_tokens']} tok")
            bits.append(
                f"demoted/promoted {record.get('host_demoted_blocks', 0)}"
                f"/{record.get('host_promoted_blocks', 0)} blocks"
            )
        if record.get("streams_exact") is False:
            bits.append("STREAMS DIVERGED")
        return " ".join(bits)
    bits = [f"{record.get('value', 0):.0f} tok/s"]
    if record.get("provisional"):
        bits.append("(provisional)")
    # kernel-leg column: which paged attention kernel produced the leg
    # (fused ragged Pallas launch vs the gather/scatter reference) —
    # the ROADMAP-item-1 paged-vs-dense gap is read off this pair
    if record.get("kv_layout") == "paged" and record.get("paged_kernel"):
        bits.append(f"kernel={record['paged_kernel']}")
    # tp column: chips in the leg's tensor-parallel mesh — sharded legs
    # report per-CHIP tok/s and per-chip MFU/MBU (the cost model divides
    # sharded work by tp), so a tp=2 leg must never be compared against
    # a tp=1 leg as if they ran the same hardware
    if record.get("tp") and int(record["tp"]) > 1:
        bits.append(f"tp={record['tp']}")
    # spec-decode column: which leg ran speculative decoding, plus its
    # own acceptance evidence (the on-vs-off delta only means anything
    # read next to the rate — a collapsed rate explains a flat delta)
    if record.get("spec_decode") and record["spec_decode"] != "off":
        bits.append(f"spec={record['spec_decode']}")
        if record.get("spec_acceptance") is not None:
            bits.append(f"accept {record['spec_acceptance'] * 100:.0f}%")
    # prefill-mode column: which prefill scheduling produced the leg
    # (mixed = chunked prefill fused into the decode step) — read next
    # to the tail columns below, which are what the pair is judged on
    if record.get("prefill_mode") and record["prefill_mode"] != "split":
        bits.append(f"prefill={record['prefill_mode']}")
        # carry column: whether consecutive mixed steps chained off the
        # previous step's device outputs, plus the leg's own chain-rate
        # and host-gap evidence (a carry-on leg with a collapsed chain
        # rate explains a flat delta — read the invalidation counters)
        if record.get("mixed_carry"):
            bits.append(f"carry={record['mixed_carry']}")
        if record.get("mixed_chain_rate") is not None:
            bits.append(f"chain {record['mixed_chain_rate'] * 100:.0f}%")
        if record.get("mixed_host_gap_ms_mean") is not None:
            bits.append(
                f"host gap {record['mixed_host_gap_ms_mean']:.1f} ms/step"
            )
    # tiered-pool columns (ISSUE 18): arena size, what the host tier
    # absorbed (promoted hits) vs what eviction still re-taught — the
    # pair's verdict is the recompute cut, read next to tok/s
    if record.get("kv_host_blocks"):
        bits.append(f"host-blocks={record['kv_host_blocks']}")
        if record.get("kv_host_hit_tokens") is not None:
            bits.append(f"host hits {record['kv_host_hit_tokens']} tok")
        if record.get("host_promote_aborts"):
            bits.append(f"promote aborts {record['host_promote_aborts']}")
    if record.get("evicted_recompute_tokens") is not None:
        bits.append(
            f"evict recompute {record['evicted_recompute_tokens']} tok"
        )
    # chaos column: which leg ran with the fault registry armed — a
    # recovery-under-load number must never read as a clean regression
    if record.get("chaos"):
        bits.append(f"chaos={record['chaos']}")
    if record.get("raw_engine_tok_s"):
        bits.append(f"raw {record['raw_engine_tok_s']:.0f}")
    if record.get("decode_ms_per_step"):
        bits.append(f"{record['decode_ms_per_step']:.1f} ms/step")
    # per-leg roofline columns (bench stamps these from its own
    # decode roofline; flight artifacts carry the per-chunk series)
    if record.get("mfu") is not None:
        bits.append(f"MFU {record['mfu'] * 100:.1f}%")
    if record.get("hbm_bw_pct") is not None:
        bits.append(f"MBU {record['hbm_bw_pct'] * 100:.1f}%")
    if record.get("p50_rtt_ms"):
        bits.append(f"p50 RTT {record['p50_rtt_ms']:.0f} ms")
    if record.get("p50_ttft_ms"):
        bits.append(f"TTFT {record['p50_ttft_ms']:.0f} ms")
    # tail columns (ISSUE 12): p95 TTFT + the worst inter-token gap any
    # closed-loop client saw — the numbers the mixed-vs-split prefill
    # pair is actually judged on (interference hides in the tail, not
    # the mean)
    if record.get("p95_ttft_ms"):
        bits.append(f"TTFT p95 {record['p95_ttft_ms']:.0f} ms")
    if record.get("max_tpot_excursion_ms"):
        bits.append(
            f"max TPOT exc {record['max_tpot_excursion_ms']:.0f} ms"
        )
    if record.get("attempt"):
        bits.append(f"attempt {record['attempt']}")
    return " ".join(bits)


def usable(record: Optional[Dict[str, Any]]) -> bool:
    """A record that can enter an e2e A/B comparison: nonzero AND the
    e2e gateway metric — a leg whose window died after warmup leaves a
    raw_engine_decode_* provisional as its last line, and comparing raw
    decode against e2e would fabricate a huge spurious win."""
    return (
        bool(record)
        and record.get("value", 0) > 0
        and str(record.get("metric", "")).startswith("e2e_gateway")
    )


def caveat(*records: Optional[Dict[str, Any]]) -> str:
    """Flag recommendations built on partial-window estimates."""
    if any(r and r.get("provisional") for r in records):
        return " [PROVISIONAL inputs - confirm with a full window]"
    return ""


def _percentile(values, fraction: float) -> float:
    ordered = sorted(values)
    return ordered[
        min(len(ordered) - 1, int(fraction * (len(ordered) - 1)))
    ]


def flight_summary(art_dir: str) -> Optional[str]:
    """One-paragraph digest of the newest flight-recorder artifact
    (``<art_dir>/flight/flight_*.jsonl``): phase timeline + decode
    step-time/occupancy series — the on-chip evidence VERDICT r5 found
    missing. Tolerates absence (returns None) and torn tails."""
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    try:
        from langstream_tpu.runtime import flight
    except Exception:  # noqa: BLE001 — analyzer must not need the package
        return None
    path = flight.latest_artifact(os.path.join(art_dir, "flight"))
    if path is None:
        return None
    entries = flight.read_artifact(path)
    phases = [e for e in entries if e.get("kind") == "phase"]
    chunks = [e for e in entries if e.get("kind") == "decode_chunk"]
    crashes = [
        e for e in entries
        if e.get("kind") in ("engine_crash", "bench_failure")
    ]
    lines = [f"# Flight recorder ({os.path.basename(path)})\n"]
    # fleet identity rides the meta record(s) — supplementary meta
    # (post-configure set_identity) comes later, so the last one wins
    identity: Dict[str, Any] = {}
    for entry in entries:
        if entry.get("kind") == "meta":
            identity.update({
                key: entry[key]
                for key in ("replica", "fleet_role")
                if entry.get(key)
            })
    if identity:
        lines.append(
            f"  replica: {identity.get('replica', '?')} "
            f"[{identity.get('fleet_role', 'unified')}]"
        )
    if phases:
        lines.append(
            "  phases: " + " -> ".join(str(p.get("name")) for p in phases)
        )
    for crash in crashes:
        lines.append(
            f"  {crash['kind']}: "
            f"{crash.get('reason') or crash.get('error', '')}"
        )
    if chunks:
        steps = [c["step_ms"] for c in chunks if c.get("step_ms")]
        occ = [
            c["active"] / c["slots"] for c in chunks if c.get("slots")
        ]
        if steps:
            lines.append(
                f"  decode: {len(chunks)} chunks, step p50 "
                f"{_percentile(steps, 0.5):.2f} ms / p95 "
                f"{_percentile(steps, 0.95):.2f} ms"
            )
        if occ:
            lines.append(
                f"  occupancy: mean {sum(occ) / len(occ):.1%} over "
                f"{len(occ)} chunks"
            )
        # roofline series: per-chunk MFU/MBU stamped by the engine's
        # efficiency accounting (fractions of the per-chip peak)
        mfus = [c["mfu"] for c in chunks if c.get("mfu") is not None]
        mbus = [c["mbu"] for c in chunks if c.get("mbu") is not None]
        if mfus:
            lines.append(
                f"  roofline: MFU p50 {_percentile(mfus, 0.5):.1%} / "
                f"peak {max(mfus):.1%}; MBU p50 "
                f"{_percentile(mbus, 0.5):.1%} / peak {max(mbus):.1%}"
                if mbus else
                f"  roofline: MFU p50 {_percentile(mfus, 0.5):.1%}"
            )
        # goodput ledger: cumulative useful/wasted counters ride each
        # decode_chunk record — the last one is the run's total
        tail = chunks[-1]
        useful = tail.get("tokens_useful")
        wasted = tail.get("tokens_wasted")
        if useful is not None and (useful or wasted):
            total = useful + (wasted or 0)
            lines.append(
                f"  goodput: {useful}/{total} tokens useful "
                f"({useful / total:.1%}); wasted {wasted or 0}"
            )
        # speculative decoding series: per-chunk drafted vs accepted
        # candidates -> run acceptance rate + dispatches per generated
        # token (the "fewer forwards per token" acceptance evidence)
        drafted = sum(c.get("drafted", 0) for c in chunks)
        if drafted:
            accepted = sum(c.get("accepted", 0) for c in chunks)
            # `tokens` is the engine-lifetime cumulative gauge, so a
            # recording that starts mid-run (on-demand profiling) would
            # understate dispatches-per-token if divided directly —
            # align the windows instead: steps AFTER the first record
            # over the token delta across the recorded span
            if len(chunks) > 1:
                total_steps = sum(c.get("steps", 0) for c in chunks[1:])
                tokens = chunks[-1].get("tokens", 0) - chunks[0].get(
                    "tokens", 0
                )
            else:
                total_steps = sum(c.get("steps", 0) for c in chunks)
                tokens = max((c.get("tokens", 0) for c in chunks), default=0)
            line = (
                f"  spec decode: {accepted}/{drafted} drafts accepted "
                f"({accepted / drafted:.1%})"
            )
            if tokens and total_steps:
                line += (
                    f"; {total_steps / tokens:.2f} decode dispatches "
                    "per generated token"
                )
            lines.append(line)
        # mixed prefill+decode series (prefill_mode: mixed): how much
        # prompt work rode each decode step — read next to step_ms for
        # the stall-free-batching verdict (a flat excursion with large
        # per-step prefill_tokens means the budget exceeds the decode
        # step's headroom: lower --prefill-chunk)
        mixed_chunks = [c for c in chunks if c.get("mixed")]
        if mixed_chunks:
            loads = [c.get("prefill_tokens", 0) for c in mixed_chunks]
            lines.append(
                f"  mixed dispatch: {len(mixed_chunks)}/{len(chunks)} "
                f"steps carried prefill windows, prefill tokens/step "
                f"p50 {_percentile(loads, 0.5)} / max {max(loads)}"
            )
            # mixed-step carry series: chained steps overlap the
            # previous harvest, so their inter-dispatch host gap
            # collapses to ~0 — the gap split between chained and
            # unchained steps IS the per-step host tax the carry hides
            chained = [c for c in mixed_chunks if c.get("chained")]
            gaps = [
                c["gap_ms"] for c in mixed_chunks
                if c.get("gap_ms") is not None
            ]
            if chained or gaps:
                line = (
                    f"  mixed carry: {len(chained)}/{len(mixed_chunks)} "
                    "steps chained"
                )
                chained_gaps = [
                    c["gap_ms"] for c in chained
                    if c.get("gap_ms") is not None
                ]
                fresh_gaps = [
                    c["gap_ms"] for c in mixed_chunks
                    if c.get("gap_ms") is not None and not c.get("chained")
                ]
                if chained_gaps:
                    line += (
                        f"; host gap p50 chained "
                        f"{_percentile(chained_gaps, 0.5):.2f} ms"
                    )
                if fresh_gaps:
                    line += (
                        f" vs unchained "
                        f"{_percentile(fresh_gaps, 0.5):.2f} ms"
                    )
                lines.append(line)
        # paged-KV series (kv_layout: paged): pool pressure + cumulative
        # prefix-cache hit tokens ride each decode_chunk record
        pool = [
            (c["kv_blocks_in_use"], c.get("kv_blocks_total", 0))
            for c in chunks if c.get("kv_blocks_in_use") is not None
        ]
        if pool:
            in_use = [p[0] for p in pool]
            total = max(p[1] for p in pool) or 1
            hit_tokens = max(
                (c.get("prefix_hit_tokens", 0) for c in chunks), default=0
            )
            lines.append(
                f"  kv pool: blocks in use p50 "
                f"{_percentile(in_use, 0.5)}/{total} "
                f"(peak {max(in_use)}, {max(in_use) / total:.0%}); "
                f"prefix-cache hit tokens {hit_tokens}"
            )
    elif not crashes:
        lines.append("  no decode samples (run died before serving?)")
    # self-healing digest (chaos legs / organic crashes): injected
    # faults, supervisor recoveries with their rebuild times and
    # resurrected-session counts, shed requests, and the replay-token
    # overhead the goodput ledger billed to crash_replay — the evidence
    # that a crash healed instead of 500ing
    injected = [e for e in entries if e.get("kind") == "fault_injected"]
    recoveries = [
        e for e in entries
        if e.get("kind") == "engine_recovery"
        and e.get("phase") == "complete"
    ]
    gave_up = [
        e for e in entries
        if e.get("kind") == "engine_recovery"
        and e.get("phase") in ("gave_up", "rebuild_failed")
    ]
    resumes = [e for e in entries if e.get("kind") == "session_resume"]
    sheds = [e for e in entries if e.get("kind") == "request_shed"]
    if injected:
        lines.append(
            "  chaos: " + ", ".join(
                str(e.get("spec", e.get("point"))) for e in injected[:6]
            )
            + (f" (+{len(injected) - 6} more)" if len(injected) > 6 else "")
        )
    if recoveries:
        times = [
            e["recovery_s"] for e in recoveries
            if e.get("recovery_s") is not None
        ]
        sessions = sum(e.get("sessions", 0) for e in recoveries)
        replay_tokens = sum(e.get("replayed", 0) for e in resumes)
        line = (
            f"  recovery: {len(recoveries)} engine rebuild(s), "
            f"{sessions} session(s) resurrected"
        )
        if times:
            line += (
                f", recovery_seconds p50 {_percentile(times, 0.5):.2f}s"
                f" / max {max(times):.2f}s"
            )
        if replay_tokens:
            line += f"; {replay_tokens} tokens replayed (crash_replay)"
        lines.append(line)
    if gave_up:
        lines.append(
            f"  RECOVERY GAVE UP: {len(gave_up)} terminal failure(s) — "
            "the restart budget tripped; this leg's number is not a "
            "healthy-path measurement"
        )
    if sheds:
        lines.append(
            f"  load shedding: {len(sheds)} request(s) shed at the "
            "admission deadline"
        )
    return "\n".join(lines)


def journey_summary(art_dir: str) -> Optional[str]:
    """Per-stage journey digest over every flight artifact under
    ``<art_dir>/flight`` — stage p50/p95, cross-replica journey count,
    and the dominant stage (``langstream-tpu journey`` renders the full
    waterfalls). None when no journey records exist (pre-ledger
    artifacts) or the package is unimportable."""
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    try:
        from langstream_tpu.runtime.journey import journey_digest
    except Exception:  # noqa: BLE001 — analyzer must not need the package
        return None
    try:
        lines = journey_digest(os.path.join(art_dir, "flight"))
    except Exception:  # noqa: BLE001 — torn artifacts must not kill the report
        return None
    if not lines:
        return None
    return "\n".join(["# Request journeys\n"] + lines)


def main() -> None:
    art_dir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_artifacts",
    )
    if not os.path.isdir(art_dir):
        # an empty comparison table would read as "every leg absent" —
        # a wrong path must fail loudly instead
        raise SystemExit(
            f"ab_analyze: artifacts directory {art_dir!r} does not exist "
            "(pass the bench_artifacts dir the legs wrote into)"
        )
    records: Dict[str, Optional[Dict[str, Any]]] = {}
    print(f"# A/B artifacts in {art_dir}\n")
    for name, label in LEGS.items():
        record = last_json_line(os.path.join(art_dir, name))
        records[name] = record
        status = describe(record) if record else "absent"
        print(f"  {label:40s} {status}")
    print()
    flight_digest = flight_summary(art_dir)
    if flight_digest:
        print(flight_digest)
        print()
        journey_digest_text = journey_summary(art_dir)
        if journey_digest_text:
            print(journey_digest_text)
            print()
    else:
        # distinguish "legs ran without evidence" from a clean run: the
        # efficiency columns (MFU/MBU, goodput) come FROM the flight
        # artifact, so its absence must be called out, not left as an
        # empty section
        print(
            "# Flight recorder\n\n"
            f"  MISSING: no flight artifacts under "
            f"{os.path.join(art_dir, 'flight')} — per-chunk MFU/MBU and "
            "goodput columns unavailable. Run the legs with "
            "LANGSTREAM_FLIGHT_DIR set (bench.py and `serve` enable it "
            "by default).\n"
        )

    main_rec = records["bench_heal.json"]
    recommendations = []
    kvq = records["bench_heal_kvq.json"]
    if usable(main_rec) and usable(kvq):
        delta = kvq["value"] / main_rec["value"] - 1
        note = caveat(main_rec, kvq)
        if delta > 0.03:
            recommendations.append(
                f"FLIP kv-quant default to int8: {delta:+.1%} e2e "
                f"({main_rec['value']:.0f} -> {kvq['value']:.0f} tok/s); "
                "set engine kv-quant default + jax-completions globals"
                + note
            )
        else:
            recommendations.append(
                f"keep bf16 KV cache default ({delta:+.1%} not a win)"
                + note
            )
    fd0, fd1 = records["bench_heal_flashdec0.json"], records[
        "bench_heal_flashdec1.json"
    ]
    if usable(fd0) and usable(fd1):
        delta = fd1["value"] / fd0["value"] - 1
        note = caveat(fd0, fd1)
        if delta > 0.03:
            recommendations.append(
                f"KEEP flash-decode auto-gate (ON wins {delta:+.1%} at "
                "2048 ctx); consider lowering the T>=1024 gate" + note
            )
        else:
            recommendations.append(
                f"flash-decode not a win at 2048 ctx ({delta:+.1%}); "
                "keep the XLA path default, re-test at 4096+" + note
            )
    paged = records["bench_heal_paged.json"]
    if usable(main_rec) and usable(paged):
        delta = paged["value"] / main_rec["value"] - 1
        note = caveat(main_rec, paged)
        kernel = paged.get("paged_kernel") or "fused"
        if delta > 0.03:
            recommendations.append(
                f"FLIP kv-layout default to paged ({kernel} kernel): "
                f"{delta:+.1%} e2e "
                f"({main_rec['value']:.0f} -> {paged['value']:.0f} tok/s); "
                "set engine kv-layout default + jax-completions globals"
                + note
            )
        else:
            recommendations.append(
                f"keep dense KV layout default ({delta:+.1%} with the "
                f"{kernel} kernel; paged still wins HBM headroom for "
                "long-context / shared-prefix traffic)" + note
            )
    paged_ref = records["bench_heal_paged_ref.json"]
    if usable(paged) and usable(paged_ref):
        # fused-vs-reference kernel pair at equal layout: read step time
        # and the kernel-aware MFU/MBU columns (per-chunk series in the
        # flight digest above) — the ROADMAP item 1 instrument
        delta = paged["value"] / paged_ref["value"] - 1
        note = caveat(paged, paged_ref)
        if delta > 0.03:
            recommendations.append(
                f"KEEP paged-kernel fused default: {delta:+.1%} over the "
                f"gather reference ({paged_ref['value']:.0f} -> "
                f"{paged['value']:.0f} tok/s)" + note
            )
        else:
            recommendations.append(
                f"fused paged kernel not yet a win ({delta:+.1%} vs "
                "gather reference) — check per-chunk MBU in the flight "
                "digest: the fused leg models ~1/3 the KV bytes, so "
                "equal step time at lower MBU means the launch is "
                "compute/grid-bound (raise kv-block-size)" + note
            )
    paged_tp2 = records["bench_heal_paged_tp2.json"]
    paged_ref_tp2 = records["bench_heal_paged_ref_tp2.json"]
    if usable(paged_tp2) and usable(paged_ref_tp2):
        # fused-vs-reference under tensor parallelism (ROADMAP item 3):
        # the shard_map'd fused kernel vs the gather/scatter reference
        # on the same tp=2 mesh. This is the pair that decides whether
        # multi-chip paged serving keeps the fused default — before the
        # shard_map twin existed, tp>1 silently downgraded to reference
        # and paid 3x KV traffic the moment a model outgrew one chip.
        delta = paged_tp2["value"] / paged_ref_tp2["value"] - 1
        note = caveat(paged_tp2, paged_ref_tp2)
        if delta > 0.03:
            recommendations.append(
                f"KEEP paged-kernel fused default under tp: {delta:+.1%} "
                f"over the gather reference on the tp=2 mesh "
                f"({paged_ref_tp2['value']:.0f} -> "
                f"{paged_tp2['value']:.0f} tok/s/chip)" + note
            )
        else:
            recommendations.append(
                f"fused paged kernel not a win under tp=2 ({delta:+.1%} "
                "vs gather reference) — check per-chunk MBU: per-shard "
                "launches see 1/tp of the heads, so small models may be "
                "grid-bound; re-test on the real slice before flipping"
                + note
            )
    if usable(paged) and usable(paged_tp2):
        # scaling sanity: per-chip throughput under tp=2 vs one chip.
        # Perfect weak scaling holds per-chip tok/s flat; a deep drop
        # means the all-reduces (not the paged kernel) own the step.
        delta = paged_tp2["value"] / paged["value"] - 1
        recommendations.append(
            f"tp=2 paged per-chip throughput {delta:+.1%} vs single chip "
            f"({paged['value']:.0f} -> {paged_tp2['value']:.0f} "
            "tok/s/chip) — collective overhead, not a kernel verdict"
            + caveat(paged, paged_tp2)
        )
    spec = records["bench_heal_spec.json"]
    if usable(main_rec) and usable(spec):
        # spec-on-vs-off pair at equal sampling semantics (greedy parity
        # is test-enforced): the delta is throughput; the acceptance
        # rate says whether a flat delta is a drafter miss (low rate —
        # workload has no self-repetition) or verify overhead
        delta = spec["value"] / main_rec["value"] - 1
        note = caveat(main_rec, spec)
        rate = spec.get("spec_acceptance")
        rate_note = (
            f" at {rate:.0%} draft acceptance" if rate is not None else ""
        )
        if delta > 0.03:
            recommendations.append(
                f"FLIP spec-decode default to ngram: {delta:+.1%} e2e "
                f"({main_rec['value']:.0f} -> {spec['value']:.0f} tok/s)"
                f"{rate_note}; set engine spec-decode default + "
                "jax-completions globals" + note
            )
        elif rate is not None and rate < 0.2:
            recommendations.append(
                f"keep spec-decode off ({delta:+.1%}): acceptance "
                f"collapsed to {rate:.0%} — this workload has no "
                "self-repetition for the prompt-lookup drafter; re-test "
                "on RAG/code traffic before judging the verify path"
                + note
            )
        else:
            recommendations.append(
                f"keep spec-decode off ({delta:+.1%} not a win"
                f"{rate_note}; verify-step overhead is not being "
                "repaid — try a smaller --spec-k)" + note
            )
    mixed = records["bench_heal_mixed.json"]
    if usable(paged) and usable(mixed):
        # mixed-vs-split prefill at equal (paged) layout: the verdict is
        # the TAIL — p95 TTFT and the max TPOT excursion (a monolithic
        # prefill stalls every running stream for its whole dispatch;
        # the mixed path bounds each dispatch at the token budget) —
        # read at roughly equal throughput. A throughput win alone is
        # not the claim; a tail win at flat throughput is.
        tput = mixed["value"] / paged["value"] - 1
        note = caveat(paged, mixed)
        exc_split = paged.get("max_tpot_excursion_ms")
        exc_mixed = mixed.get("max_tpot_excursion_ms")
        p95_split = paged.get("p95_ttft_ms")
        p95_mixed = mixed.get("p95_ttft_ms")
        if not exc_split or not exc_mixed:
            recommendations.append(
                "mixed prefill: excursion columns missing on one leg "
                f"(throughput {tput:+.1%}); re-run both legs on a bench "
                "with max_tpot_excursion_ms (ISSUE 12) for the tail "
                "verdict" + note
            )
        else:
            exc_cut = (exc_split - exc_mixed) / exc_split
            ttft_note = ""
            if p95_split and p95_mixed:
                ttft_note = (
                    f", p95 TTFT {p95_split:.0f} -> {p95_mixed:.0f} ms"
                )
            if exc_cut > 0.15 and tput > -0.03:
                recommendations.append(
                    f"FLIP prefill-mode default to mixed (paged): max "
                    f"TPOT excursion cut {exc_cut:.1%} ({exc_split:.0f} "
                    f"-> {exc_mixed:.0f} ms){ttft_note} for {tput:+.1%} "
                    "throughput; set engine prefill-mode default + "
                    "jax-completions globals" + note
                )
            else:
                recommendations.append(
                    f"keep prefill-mode split (excursion cut {exc_cut:.1%}"
                    f"{ttft_note}, throughput {tput:+.1%}) — if the "
                    "excursion is flat, check prefill_tokens in the "
                    "flight decode_chunk records: a budget larger than "
                    "the decode step's headroom just moves the stall "
                    "inside the mixed step (lower --prefill-chunk)" + note
                )
    carry_off = records["bench_heal_mixed_carry.json"]
    if usable(mixed) and usable(carry_off):
        # carry-on-vs-off at equal mixed scheduling: the carry is
        # bitwise-neutral, so this is a pure step-time/tail pair — the
        # verdict is throughput + host-gap collapse, sanity-checked
        # against the on-leg's own chain rate (a collapsed chain rate
        # means constant invalidation, not a broken carry: read the
        # mixed_carry_invalidations counters on /metrics)
        tput = mixed["value"] / carry_off["value"] - 1
        note = caveat(carry_off, mixed)
        rate = mixed.get("mixed_chain_rate")
        gap_on = mixed.get("mixed_host_gap_ms_mean")
        gap_off = carry_off.get("mixed_host_gap_ms_mean")
        gap_note = ""
        if gap_on is not None and gap_off is not None:
            gap_note = f", host gap {gap_off:.1f} -> {gap_on:.1f} ms/step"
        if rate is not None and rate < 0.2:
            recommendations.append(
                f"mixed carry: chain rate collapsed ({rate:.1%}) — the "
                f"two-step plan is constantly invalidated (throughput "
                f"{tput:+.1%}{gap_note}); read "
                "mixed_carry_invalidations_total by reason before "
                "judging the carry" + note
            )
        elif tput > 0.03:
            recommendations.append(
                f"KEEP mixed-carry on (engine default): {tput:+.1%} "
                f"tok/s over the carry-off control"
                + (f", chain rate {rate:.1%}" if rate is not None else "")
                + gap_note + note
            )
        else:
            recommendations.append(
                f"mixed carry is NOT paying ({tput:+.1%} vs off"
                + (f", chain rate {rate:.1%}" if rate is not None else "")
                + f"{gap_note}): on a local chip the host gap may "
                "already be negligible — keep the default only if a "
                "slower host confirms it" + note
            )
    chaos = records["bench_heal_chaos.json"]
    if usable(main_rec) and usable(chaos):
        # chaos-vs-clean pair: the delta prices one crash/rebuild/resume
        # cycle under full load — read next to the recovery digest above
        # (recovery_seconds, sessions resurrected, crash_replay tokens).
        # This is a robustness price tag, never a perf verdict.
        delta = chaos["value"] / main_rec["value"] - 1
        note = caveat(main_rec, chaos)
        if delta > -0.10:
            # noise can put the chaos leg ABOVE clean — report "within
            # noise", never a nonsensical negative cost
            cost = (
                f"costs {-delta:.1%} of clean throughput" if delta < 0
                else "is within run-to-run noise of the clean leg"
            )
            recommendations.append(
                f"recovery is CHEAP: one mid-run engine crash {cost} "
                f"({main_rec['value']:.0f} -> {chaos['value']:.0f} tok/s) "
                "with zero failed streams — the supervisor arc holds "
                "under load" + note
            )
        else:
            recommendations.append(
                f"recovery is EXPENSIVE ({delta:+.1%} vs clean): check "
                "recovery_seconds in the flight digest — a rebuild "
                "dominated by jit compiles means the persistent compile "
                "cache is cold or mis-keyed; precompile + cache dir are "
                "the levers" + note
            )
    admis = records["bench_heal_admis.json"]
    if usable(main_rec) and usable(admis):
        tput = admis["value"] / main_rec["value"] - 1
        ttft_main = main_rec.get("p50_ttft_ms")
        ttft_admis = admis.get("p50_ttft_ms")
        note = caveat(main_rec, admis)
        if not ttft_main or not ttft_admis:
            # a provisional/partial record carries no TTFT — a missing
            # field is not a 100% cut
            recommendations.append(
                "admission-chunk: TTFT missing on one leg "
                f"(throughput {tput:+.1%}); need a full-window pair"
                + note
            )
        elif (ttft_main - ttft_admis) / ttft_main > 0.15 and tput > -0.03:
            cut = (ttft_main - ttft_admis) / ttft_main
            recommendations.append(
                f"FLIP admission-chunk default to 8: TTFT cut {cut:.1%} "
                f"for {tput:+.1%} throughput" + note
            )
        else:
            cut = (ttft_main - ttft_admis) / ttft_main
            recommendations.append(
                f"keep admission-chunk off (TTFT cut {cut:.1%}, "
                f"throughput {tput:+.1%})" + note
            )

    routed = records["bench_fleet_routed.json"]
    rr = records["bench_fleet_rr.json"]
    if (
        routed and rr
        and routed.get("metric") == "fleet_sim"
        and rr.get("metric") == "fleet_sim"
        and routed.get("sessions") == rr.get("sessions")
    ):
        # affinity-vs-round-robin at identical traffic: the affinity
        # verdict is the FLEET-WIDE prefix-hit-token delta (tokens the
        # routed fleet never re-prefilled) read next to the shed delta
        # (backlog the saved prefill work prevented)
        base_hits = max(1, int(rr.get("prefix_hit_tokens", 0)))
        hit_delta = routed.get("prefix_hit_tokens", 0) / base_hits - 1
        shed_routed = int(routed.get("requests_shed", 0))
        shed_rr = int(rr.get("requests_shed", 0))
        if hit_delta > 0.03 and shed_routed <= shed_rr:
            recommendations.append(
                f"ENABLE prefix-affinity routing: {hit_delta:+.1%} "
                f"fleet prefix-hit tokens "
                f"({rr.get('prefix_hit_tokens', 0):.0f} -> "
                f"{routed.get('prefix_hit_tokens', 0):.0f}), sheds "
                f"{shed_rr} -> {shed_routed}; register a FleetRouter "
                "on the gateway (docs/fleet.md)"
            )
        else:
            recommendations.append(
                f"keep round-robin routing ({hit_delta:+.1%} prefix-hit "
                f"tokens, sheds {shed_rr} -> {shed_routed}): traffic "
                "has too little prefix sharing for affinity to pay"
            )

    disagg = records["bench_fleet_disagg.json"]
    unified = records["bench_fleet_unified.json"]
    if (
        disagg and unified
        and disagg.get("metric") == "fleet_sim"
        and unified.get("metric") == "fleet_sim"
        and disagg.get("sessions") == unified.get("sessions")
    ):
        # disagg-vs-unified at identical traffic and equal capacity:
        # the verdict is the decode-side TAIL — a decode replica that
        # never runs a monolithic prefill has structurally bounded TPOT
        # excursions — read at roughly equal tok/s, and only with the
        # bitwise stream contract and zero client errors intact (a tail
        # win bought with diverged or failed streams is not a win)
        exc_u = unified.get("max_tpot_excursion_s")
        exc_d = disagg.get("max_tpot_excursion_s")
        tok_u = unified.get("tok_s") or 0
        tok_d = disagg.get("tok_s") or 0
        tput = tok_d / tok_u - 1 if tok_u else 0.0
        p95_u = unified.get("ttft_p95_s")
        p95_d = disagg.get("ttft_p95_s")
        ttft_note = (
            f", p95 TTFT {p95_u:.2f} -> {p95_d:.2f}s"
            if p95_u is not None and p95_d is not None else ""
        )
        safe = (
            disagg.get("client_errors", 0) == 0
            and disagg.get("streams_exact", False)
        )
        if exc_u is None or exc_d is None or not exc_u:
            recommendations.append(
                "disaggregation: excursion columns missing on one leg "
                f"(throughput {tput:+.1%}); re-run fleet.sim --disagg "
                "for the tail verdict"
            )
        elif not safe:
            recommendations.append(
                "disaggregation BROKE the stream contract "
                f"({disagg.get('client_errors', 0)} client errors, "
                f"streams_exact={disagg.get('streams_exact')}) — fix "
                "the handoff path before reading any tail numbers"
            )
        else:
            cut = (exc_u - exc_d) / exc_u
            aborted = disagg.get("handoff_aborted", 0)
            if cut > 0.3 and tput > -0.15:
                recommendations.append(
                    f"ENABLE prefill/decode disaggregation: max TPOT "
                    f"excursion cut {cut:.1%} ({exc_u:.2f} -> "
                    f"{exc_d:.2f}s){ttft_note} at {tput:+.1%} tok/s, "
                    f"{disagg.get('handoff_imported', 0)} handoffs "
                    f"({aborted} aborted), zero client errors — run "
                    "serve --fleet-role pools behind the role-aware "
                    "router (docs/fleet.md)"
                )
            else:
                recommendations.append(
                    f"keep the fleet unified (excursion cut {cut:.1%}"
                    f"{ttft_note}, tok/s {tput:+.1%}): the handoff tax "
                    "is not being repaid — check handoff_bytes vs the "
                    "prefill work saved, and the pool split (prefill-"
                    "bound traffic wants a bigger prefill pool)"
                )

    kv_tiers = records["bench_heal_kv_tiers.json"]
    if usable(paged) and usable(kv_tiers):
        # tiered-vs-untiered pool at equal (paged) layout: the verdict
        # is the eviction-recompute cut — tokens the HBM-only pool
        # re-prefilled that the host tier answered with a promotion —
        # at roughly equal tok/s (the H2D scatter must not eat the
        # saved FLOPs). Read host hits next to the cut: hits without a
        # cut mean the traffic was never pool-pressured and the pair
        # proves nothing.
        tput = kv_tiers["value"] / paged["value"] - 1
        note = caveat(paged, kv_tiers)
        rec_base = paged.get("evicted_recompute_tokens")
        rec_tier = kv_tiers.get("evicted_recompute_tokens")
        hits = kv_tiers.get("kv_host_hit_tokens", 0)
        if rec_base is None or rec_tier is None:
            recommendations.append(
                "kv tiers: eviction-recompute columns missing on one "
                f"leg (throughput {tput:+.1%}); re-run both legs with "
                "a pool-pressure bench (small --kv-blocks) for the "
                "verdict" + note
            )
        elif not rec_base and not hits:
            recommendations.append(
                f"kv tiers: no pool pressure on either leg (0 recompute, "
                f"0 host hits, throughput {tput:+.1%}) — shrink "
                "--kv-blocks or widen the prompt set before judging the "
                "tier" + note
            )
        else:
            cut = (
                (rec_base - rec_tier) / rec_base if rec_base else 0.0
            )
            if cut > 0.3 and tput > -0.10:
                recommendations.append(
                    f"ENABLE the host KV tier: eviction recompute cut "
                    f"{cut:.1%} ({rec_base} -> {rec_tier} tokens, "
                    f"{hits} host-hit tokens) at {tput:+.1%} tok/s; "
                    f"set serve --kv-host-blocks "
                    f"{kv_tiers.get('kv_host_blocks', 0)} (docs/perf.md "
                    "'KV tiers')" + note
                )
            else:
                recommendations.append(
                    f"keep the pool HBM-only (recompute cut {cut:.1%}, "
                    f"{hits} host-hit tokens, tok/s {tput:+.1%}): the "
                    "promote/demote traffic is not repaying the saved "
                    "prefill — check host_promote_aborts and the D2H "
                    "window in the flight digest" + note
                )

    tiered = records["bench_fleet_tiered.json"]
    untiered = records["bench_fleet_untiered.json"]
    if (
        tiered and untiered
        and tiered.get("metric") == "fleet_sim"
        and untiered.get("metric") == "fleet_sim"
        and tiered.get("sessions") == untiered.get("sessions")
    ):
        # fleet-level tiered pair on identical pool-pressure traffic:
        # same verdict shape as the engine pair, plus the stream
        # contract (a recompute cut bought with diverged streams is
        # not a win)
        rec_base = int(untiered.get("evicted_recompute_tokens", 0))
        rec_tier = int(tiered.get("evicted_recompute_tokens", 0))
        hits = int(tiered.get("kv_host_hit_tokens", 0))
        tok_u = untiered.get("tok_s") or 0
        tok_t = tiered.get("tok_s") or 0
        tput = tok_t / tok_u - 1 if tok_u else 0.0
        safe = (
            tiered.get("client_errors", 0) == 0
            and tiered.get("streams_exact", False)
        )
        cut = (rec_base - rec_tier) / rec_base if rec_base else 0.0
        if not safe:
            recommendations.append(
                "kv tiers (fleet sim) BROKE the stream contract "
                f"({tiered.get('client_errors', 0)} client errors, "
                f"streams_exact={tiered.get('streams_exact')}) — fix "
                "the promotion path before reading the recompute cut"
            )
        elif rec_base and cut > 0.3 and tput > -0.10:
            recommendations.append(
                f"ENABLE host KV tiers fleet-wide: eviction recompute "
                f"cut {cut:.1%} ({rec_base} -> {rec_tier} tokens, "
                f"{hits} host-hit tokens, "
                f"{tiered.get('host_promoted_blocks', 0)} blocks "
                f"promoted) at {tput:+.1%} tok/s with tier-tagged "
                "routing — serve --kv-host-blocks on every replica"
            )
        else:
            recommendations.append(
                f"keep fleet pools HBM-only (recompute cut {cut:.1%}, "
                f"{hits} host-hit tokens, tok/s {tput:+.1%}): traffic "
                "has too little re-arrival under pressure for the tier "
                "to pay"
            )

    print("# Recommendations\n")
    if recommendations:
        for recommendation in recommendations:
            print(f"  - {recommendation}")
    else:
        print("  - no complete A/B pair yet; leave defaults as-is")
    if usable(main_rec):
        target = main_rec["value"] / 800.0
        print(
            f"\n  headline: {main_rec['value']:.0f} tok/s = {target:.2f}x "
            f"the 800 tok/s target"
        )


if __name__ == "__main__":
    main()
