#!/usr/bin/env python3
"""What a seed's random router does with the cell's prompts, layer by
layer, at the configuration's published widths: the diagnosis behind the
limits of the DeepSeek-V2 family's comparison and behind the spread of its
cell's speed (PERF.md section 6, PR 29). It needs no chip (an hour of CPU
for a handful of seeds) and counts only: nothing here is a time.

One prompt of ``--tokens`` characters as the mix's generator writes them,
then ``--tail`` ids drawn from the whole vocabulary (a decode step's tokens
are the model's own, from all of it), through the family's reference
three times: in float32, with every activation rounded to bf16 (what the
program's arithmetic does to it; weights as stored), and with the weights
in the configuration's lower precision (the control). For every expert
layer it prints

- ``held_share``: assignments that meet a held expert over all (the
  expectation is held / routed, 0.25 for a quarter of the experts);
- ``peak``: the busiest held expert's tokens over the held experts' mean;
- ``common``: the norm of the mean of the router's inputs over the norm of
  what is left of one without it (a router sees every token alike as this
  grows; 25 letters route 25 ways whatever it is);
- ``tail_experts``: held experts the ``--tail`` tokens meet (a decode step
  reads that many experts' weights);
- ``flipped.bf16`` / ``flipped.<lower>``: the share of tokens whose experts
  are not the float32 pass's;

and for the two rounded passes ``mean_logit_gap`` / ``max_logit_gap`` of
the token each puts first, as ``reference/compare.py`` reads them, over the
prompt and over the tail.

    python benchmark/tools/routing.py --workload deepseek-v2-ep4.docs --seeds 1,2 --tokens 960 --tail 64

``--embedding-gain g`` multiplies the embedding the recipe drew, for a look
at how the readings follow the one scale that sets a token's own share of
its state (PERF.md section 7).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--tokens", type=int, default=960)
    parser.add_argument("--tail", type=int, default=64)
    parser.add_argument("--embedding-gain", type=float, default=1.0)
    parser.add_argument("--out", default="")
    args = parser.parse_args()
    from benchmark import harness
    from benchmark.generators import prompts

    cell = harness.load_cell(args.workload)
    ref = cell["family"]
    sizes = ref.Sizes(cell["config_file"])
    lower = cell["config_file"]["lower_precision"].split(",")[0]
    total = args.tokens + args.tail
    block = next(b for b in (256, 128, 64, 32, 16, 8, 4, 2, 1) if total % b == 0)

    def rounded(x, on):
        return x.astype(jnp.bfloat16).astype(jnp.float32) if on else x

    def attention(x, layer, low, bf16):
        """``ref._attention`` with a rounding after every operation."""
        r = partial(rounded, on=bf16)
        heads, nope, rope, v_dim = sizes.heads, sizes.nope, sizes.rope, sizes.v_dim
        inv_freq, on_cos_sin = ref.yarn_inv_freq(rope, sizes.theta, sizes.yarn)
        w = lambda name: ref._dense(layer[name], low)  # noqa: E731
        normed = r(ref._rms(x, layer["attn_norm"], sizes.eps))
        c_q = r(ref._rms(r(normed @ w("wq_a")), layer["q_norm"], sizes.eps))
        q = r(c_q @ w("wq_b")).reshape(total, heads, nope + rope)
        q_nope, q_pe = q[..., :nope], r(ref._rotate(q[..., nope:], inv_freq, on_cos_sin))
        kv_a = r(normed @ w("wkv_a"))
        c_kv = r(ref._rms(kv_a[:, : sizes.kv_rank], layer["kv_norm"], sizes.eps))
        k_pe = r(ref._rotate(kv_a[:, None, sizes.kv_rank:], inv_freq, on_cos_sin)[:, 0])
        kv = r(c_kv @ w("wkv_b")).reshape(total, heads, nope + v_dim)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        keys_at = jnp.arange(total)

        def rows(start):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, start, block, 0)
            qp = jax.lax.dynamic_slice_in_dim(q_pe, start, block, 0)
            scores = (
                jnp.einsum("thd,shd->hts", qn, k_nope) + jnp.einsum("thd,sd->hts", qp, k_pe)
            ) * ref.softmax_scale(sizes)
            causal = keys_at[None, :] <= (start + jnp.arange(block))[:, None]
            scores = jnp.where(causal[None], scores, -jnp.inf)
            return jnp.einsum("hts,shd->thd", r(jax.nn.softmax(scores, -1)), v)

        out = r(jax.lax.map(rows, jnp.arange(0, total, block)))
        return r(out.reshape(total, heads * v_dim) @ w("wo"))

    def swiglu(x, gate, up, down, bf16):
        r = partial(rounded, on=bf16)
        return r(r(jax.nn.silu(r(x @ gate)) * r(x @ up)) @ down)

    @partial(jax.jit, static_argnames=("low", "bf16"))
    def one_layer(x, layer, low, bf16):
        r = partial(rounded, on=bf16)
        x = r(x + attention(x, layer, low, bf16))
        normed = r(ref._rms(x, layer["mlp_norm"], sizes.eps))
        if "router" not in layer:
            names = ("gate", "up", "down")
            return r(x + swiglu(normed, *(ref._dense(layer[n], low) for n in names), bf16)), None
        weights = ref.route(sizes, normed, layer["router"])
        held = weights[:, sizes.held_first: sizes.held_first + sizes.held]

        def one(mixed, leaves):
            weight, gate, up, down = leaves
            out = swiglu(normed, *(ref._dense((w, None), low) for w in (gate, up, down)), bf16)
            return mixed + weight[:, None] * out, None

        mixed, _ = jax.lax.scan(one, jnp.zeros_like(x), (
            held.T, layer["expert_gate"][0], layer["expert_up"][0], layer["expert_down"][0],
        ))
        names = ("shared_gate", "shared_up", "shared_down")
        mixed = r(mixed) + swiglu(normed, *(ref._dense(layer[n], low) for n in names), bf16)
        mean = normed.mean(0)
        common = jnp.sqrt((mean ** 2).sum() / ((normed - mean) ** 2).sum(-1).mean())
        return r(x + mixed), (weights > 0, common)

    def forward(weights, ids, low, bf16):
        x = weights["embedding"][jnp.asarray(ids)].astype(jnp.float32) * args.embedding_gain
        x, routed = rounded(x, bf16), []
        for layer in weights["layers"]:
            x, seen = one_layer(x, layer, low, bf16)
            if seen is not None:
                routed.append((np.asarray(seen[0]), float(seen[1])))
        logits = ref._head(x, weights["final_norm"], weights["lm_head"], sizes.eps, low)
        return np.asarray(logits), routed

    spec = cell["traffic_file"]["prompts"]
    first, last = sizes.held_first, sizes.held_first + sizes.held
    for seed in (int(s) for s in args.seeds.split(",")):
        weights = ref.make_weights(sizes, seed)
        rng = random.Random(f"{seed}:routing")
        text = prompts.message(spec, [prompts.question(0, args.tokens, rng)])[: args.tokens]
        ids = np.concatenate([
            np.frombuffer(text.encode(), dtype=np.uint8).astype(np.int32),
            np.random.default_rng(seed).integers(0, sizes.vocab, args.tail).astype(np.int32),
        ])
        base, routed = forward(weights, ids, None, False)
        line = {"workload": args.workload, "seed": seed, "tokens": args.tokens, "tail": args.tail,
                "embedding_gain": args.embedding_gain, "layers": []}
        for chosen, common in routed:
            held = chosen.sum(0)[first:last]
            tail = chosen[args.tokens:].sum(0)[first:last]
            line["layers"].append({
                "held_share": float(held.sum() / chosen.sum()),
                "peak": float(held.max() / max(held.mean(), 1e-9)),
                "common": common, "tail_experts": int((tail > 0).sum()), "flipped": {},
            })
        best = base.max(-1)
        for name, low, bf16 in (("bf16", None, True), (lower, lower, False)):
            other, routed_other = forward(weights, ids, low, bf16)
            gap = best - base[np.arange(total), other.argmax(-1)]
            parts = {"prompt": gap[: args.tokens], "tail": gap[args.tokens:]}
            line[name] = {
                part: {"mean_logit_gap": float(g.mean()), "max_logit_gap": float(g.max()),
                       "not_best": int((g > 0).sum())}
                for part, g in parts.items() if len(g)
            }
            for entry, (a, _), (b, _) in zip(line["layers"], routed, routed_other):
                entry["flipped"][name] = float((a != b).any(-1).mean())
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as handle:
                handle.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
