#!/usr/bin/env python3
"""How many expert tiles a decode step computes, seed by seed: the reading
behind the recipe's ``EMBEDDING_STD`` (``reference/deepseek_v2.py``; PERF.md
section 6, PR 29). The program's own prefill and greedy decode on the CPU,
in float32, at a tenth of the DeepSeek-V2 widths with its routing whole
(hidden 512, 16 heads, latent 128, 160 experts of width 128 in 8 groups,
3 groups and 6 experts a token, experts 0-39 held, 1 dense + 4 expert
layers, 25,600 ids): ``--slots`` prompts of the ``docs`` mix's text, then
``--steps`` steps. A step's tiles (16 rows each, summed over the expert
layers) are the held experts its tokens meet, which is what its expert
matmuls read; where they follow the seed, so does the cell's speed.
Counts only, no chip, under a minute a seed.

    python benchmark/tools/decode_tiles.py --seeds 1,2,3,4,5,6,7,8 [--embedding-gain 0.0625]

``--embedding-gain`` multiplies the embedding the recipe drew (0.0625 gives
a row of 1/sqrt(hidden) a value at the DeepSeek-V2 width, the recipe
before PR 29's review round).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--embedding-gain", type=float, default=1.0)
    parser.add_argument("--slots", type=int, default=64)
    parser.add_argument("--prompt", type=int, default=256)
    parser.add_argument("--steps", type=int, default=48)
    args = parser.parse_args()
    from benchmark import harness
    from benchmark.generators import prompts
    from langstream_tpu.providers.jax_local import model

    config = dataclasses.replace(
        model.LlamaConfig.tiny_deepseek_v2(args.prompt + args.steps + 8),
        vocab_size=25600, hidden_size=512, intermediate_size=1024, num_layers=5,
        num_heads=16, num_kv_heads=16, head_dim=48,
        rope_scaling=("yarn", 40.0, 32.0, 1.0, 0.707, 0.707, 4096.0),
        mla=model.LatentAttention(
            q_lora_rank=192, kv_lora_rank=128, qk_nope_head_dim=32,
            qk_rope_head_dim=16, v_head_dim=32,
        ),
        experts=model.RoutedExperts(
            routed=160, held_first=0, held=40, intermediate_size=128, per_token=6,
            shared=2, leading_dense=1, groups=8, groups_kept=3, scaling_factor=16.0,
        ),
    )
    spec = harness.load_json("traffic", "docs.json")["prompts"]
    freqs = model.model_freqs(config)
    prefill = jax.jit(lambda p, c, t, n, s: model.prefill(config, p, c, t, n, s, freqs))
    step = jax.jit(
        lambda p, c, t, n: model.decode_step(config, p, c, t, n, freqs, None),
        donate_argnums=(1,),
    )
    for seed in (int(s) for s in args.seeds.split(",")):
        params = model.init_params(config, seed=seed)
        params["embedding"] = params["embedding"] * args.embedding_gain
        rng = random.Random(f"{seed}:decode-tiles")
        tokens = np.zeros((args.slots, args.prompt), np.int32)
        lengths = np.zeros((args.slots,), np.int32)
        for slot in range(args.slots):
            size = args.prompt - rng.randrange(0, args.prompt // 4)
            text = prompts.message(spec, [prompts.question(slot, size, rng)])[:size]
            tokens[slot, :size] = np.frombuffer(text.encode(), dtype=np.uint8)
            lengths[slot] = size
        cache = model.init_cache(config, args.slots, config.max_seq_len)
        cache, logits, _ = prefill(
            params, cache, tokens, lengths, np.arange(args.slots, dtype=np.int32)
        )
        tiles, held, in_flight = [], [], []
        for _ in range(args.steps):
            picked = np.asarray(jnp.argmax(logits, -1)).astype(np.int32)
            lengths = lengths + 1
            cache, logits, counters = step(params, cache, picked, lengths)
            counters = np.asarray(counters)
            held.append(int(counters[1]))
            tiles.append(int(counters[2]) // 16)
            in_flight.append(len(set(picked.tolist())))
        print(json.dumps({
            "seed": seed, "embedding_gain": args.embedding_gain,
            "tiles_a_step": float(np.mean(tiles)), "held_rows_a_step": float(np.mean(held)),
            "distinct_tokens_in_flight": float(np.mean(in_flight)),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
