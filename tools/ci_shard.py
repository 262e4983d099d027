#!/usr/bin/env python
"""CI test sharding: one place that maps shard names to test files.

The reference splits its 17-minute suite across a CI matrix
(`/root/reference/.github/workflows/ci.yml:28-91` — Runtime / Deployer /
Api Gateway / Control plane / Other); this is the analogue for the
pytest suite. `.github/workflows/ci.yml` runs one job per shard with
``python tools/ci_shard.py <shard> | xargs python -m pytest``, and
tests/test_ci_shards.py asserts the partition is total and disjoint —
a new test file that matches no shard fails CI wiring at test time, not
by silently never running.

Assignment is by filename prefix list (explicit beats glob-clever):
the first shard whose prefix matches claims the file.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List

# ordered: first match wins
SHARDS: Dict[str, List[str]] = {
    # models, kernels, engine, parallelism — the JAX-heavy, compile-bound
    # shard
    "kernels-engine": [
        "test_engine",
        # efficiency accounting (roofline/MFU/MBU, goodput, watchdog,
        # SLO burn rates) constructs DecodeEngines — JAX-heavy shard
        "test_efficiency",
        "test_attention_kernels",
        # speculative decoding (drafter/acceptance units + engine
        # parity A/Bs) constructs DecodeEngines — JAX-heavy shard
        "test_spec_decode",
        "test_paged_kernel",
        "test_paged_kv",
        # tiered KV pool (host-DRAM demotion tier): demote/promote
        # bitwise-parity A/Bs construct DecodeEngines — JAX-heavy; the
        # pure-CPU arena/router/sim legs ride along with the story
        "test_kv_tiers",
        # unified mixed prefill+decode dispatch (token-ragged kernel +
        # engine scheduler A/Bs) constructs DecodeEngines — JAX-heavy
        "test_mixed_dispatch",
        # multi-chip paged serving (shard_map'd fused kernel, tp=2
        # engine A/Bs, compiled-HLO collective assertions) — JAX-heavy
        "test_multichip_paged",
        # self-healing serving (fault injection, supervisor rebuilds,
        # bitwise session resurrection) constructs DecodeEngines —
        # JAX-heavy shard
        "test_recovery",
        "test_decode_kernel",
        # the main path's kernels compiled for a described v5e at real
        # widths, and chip_smoke.py's phase function at a tiny size
        "test_chip_compile",
        "test_chip_smoke",
        "test_kv_quant",
        "test_quant",
        "test_llama_model",
        "test_gemma2_model",
        "test_qwen2_model",
        "test_moe",
        "test_pipeline",
        "test_multihost",
        "test_mirror",
        "test_checkpoint",
        "test_openai_api",
        "test_e2e_jax",
    ],
    # control plane, deployer, k8s storage, gateway, auth, CLI
    "k8s-gateway": [
        "test_controlplane",
        "test_deployer",
        "test_kube_app_store",
        "test_helm_chart",
        "test_k8s_schema_validation",
        "test_e2e_tier",
        "test_s3_codestorage",
        "test_cli_admin",
        "test_gateway",
        "test_jwt_auth",
        "test_auth_identity_providers",
        "test_service_commands",
        "test_mini_langstream",
    ],
    # agents and topic runtimes
    "agents-topics": [
        "test_agents",
        "test_new_agents",
        "test_genai",
        "test_external_stores",
        "test_external_providers",
        "test_kafka",
        "test_pulsar",
        "test_pravega",
        "test_avro",
        "test_el",
        "test_topic_contract",
        "test_memory_broker",
        "test_log_broker",
        "test_tpulog_app",
        "test_azure_blob",
        "test_isolation",
        "test_plugins",
    ],
    # fleet layer: prefix-affinity routing, SLO autoscaling, simulated
    # fleet — pure-CPU (no JAX), so its own shard keeps the JAX-heavy
    # shards' wall time flat as the fleet suite grows
    "fleet": [
        "test_fleet",
        # prefill/decode disaggregation: the sim A/B + handoff
        # machinery are pure-CPU; the real-engine bitwise-parity legs
        # are JAX-heavy but belong with the fleet story they verify
        "test_disagg",
        # request-journey ledger: stage tiling, cross-replica joins,
        # SLO blame — mostly pure-CPU sim legs plus one real-engine
        # tiling leg, verifying fleet-wide observability
        "test_journey",
    ],
    # static analysis (`langstream-tpu check`): lock-discipline +
    # jit-hazard AST fixtures, the HLO rule library, and the repo-wide
    # clean-run gate — mostly AST-light with two tiny engine builds
    "analysis": [
        "test_analysis",
    ],
    # compiler, runner, examples, docs — everything else lands here via
    # the catch-all marker (must stay LAST)
    "core-runner": ["*"],
}


def test_files(tests_dir: str) -> List[str]:
    return sorted(
        name for name in os.listdir(tests_dir)
        if name.startswith("test_") and name.endswith(".py")
    )


def assign(name: str) -> str:
    """Shard for a test filename (first prefix match; '*' catches all)."""
    stem = name[: -len(".py")] if name.endswith(".py") else name
    for shard, prefixes in SHARDS.items():
        for prefix in prefixes:
            if prefix == "*" or stem == prefix or stem.startswith(prefix + "_"):
                return shard
    raise LookupError(f"no shard matches {name}")


def files_for(shard: str, tests_dir: str) -> List[str]:
    if shard not in SHARDS:
        raise SystemExit(
            f"unknown shard {shard!r}; known: {', '.join(SHARDS)}"
        )
    return [
        os.path.join(tests_dir, name)
        for name in test_files(tests_dir)
        if assign(name) == shard
    ]


def main() -> None:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tests_dir = os.path.join(repo, "tests")
    if len(sys.argv) != 2:
        raise SystemExit("usage: ci_shard.py <shard>|--list")
    if sys.argv[1] == "--list":
        for shard in SHARDS:
            print(shard)
        return
    for path in files_for(sys.argv[1], tests_dir):
        print(path)


if __name__ == "__main__":
    main()
