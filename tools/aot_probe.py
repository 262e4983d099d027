"""AOT-compile the engine's real decode/prefill jits for a v5e topology
(no TPU hardware needed — libtpu compiles against a topology descriptor)
and print XLA's own memory/cost analysis.

This is the blind-perf-debugging tool for when the chip is unreachable:
temp memory ≈ materialized intermediates (a dequantized bf16 weight copy
would show up as ~14 GB of temp for an 8B model); bytes-accessed versus
the int8 weight footprint shows whether decode is at its weights-bound
roofline. For the decode chunk the cache is no part of temp: decode_step
carries the stacked cache through its layer loop and writes it in place,
so the chunk's temp is a few MiB of activations (3.6 MiB at Qwen-2.5-7B,
32 x 2,048). A temp of one layer's slab or more there means a copy of the
cache is back (tests/test_chip_compile.py holds the two benchmark shapes
to that).

Usage: python tools/aot_probe.py [preset] [slots] [chunk] [seq]
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp

jax.config.update("jax_platforms", "cpu")

from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec  # noqa: E402

from langstream_tpu.ops.rope import rope_frequencies  # noqa: E402
from langstream_tpu.providers.jax_local import model as model_lib  # noqa: E402
from langstream_tpu.providers.jax_local.engine import (  # noqa: E402
    _sample_with_logprob,
)
from langstream_tpu.providers.jax_local.quant import (  # noqa: E402
    init_quantized_params,
)


def main() -> None:
    preset = sys.argv[1] if len(sys.argv) > 1 else "llama-3-8b"
    slots = int(sys.argv[2]) if len(sys.argv) > 2 else 32
    chunk = int(sys.argv[3]) if len(sys.argv) > 3 else 32
    seq = int(sys.argv[4]) if len(sys.argv) > 4 else 320

    config = model_lib.LlamaConfig.from_dict({"preset": preset})
    import dataclasses

    config = dataclasses.replace(config, max_seq_len=seq)
    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    mesh = Mesh(topo.devices[:1], ("d",))
    sharding = NamedSharding(mesh, PartitionSpec())

    def shapes_of(tree):
        return jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=sharding
            ),
            tree,
        )

    params = shapes_of(
        jax.eval_shape(lambda: init_quantized_params(config, seed=0))
    )
    cache = shapes_of(
        jax.eval_shape(lambda: model_lib.init_cache(config, slots, seq))
    )
    freqs = rope_frequencies(
        config.dims_per_head, config.max_seq_len, config.rope_theta
    )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    # -- the engine's decode chunk (engine._get_decode) ----------------- #
    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode_run(params, cache, tokens, lengths, active, write_mask,
                   temperature, top_k, top_p, rng):
        def body(carry, key):
            cache, tokens, lengths = carry
            cache, logits, _ = model_lib.decode_step(
                config, params, cache, tokens, lengths, freqs, write_mask
            )
            sampled, lp = _sample_with_logprob(
                logits, temperature, top_k,
                jax.random.split(key, tokens.shape[0]), top_p
            )
            sampled = jnp.where(active, sampled, 0)
            lengths = jnp.where(active, lengths + 1, lengths)
            return (cache, sampled, lengths), (sampled, lp)

        keys = jax.random.split(rng, chunk)
        (cache, _, _), (out, lps) = jax.lax.scan(
            body, (cache, tokens, lengths), keys
        )
        return cache, out.T, lps.T

    lowered = decode_run.lower(
        params, cache,
        arg((slots,), jnp.int32), arg((slots,), jnp.int32),
        arg((slots,), jnp.bool_), arg((slots,), jnp.bool_),
        arg((slots,), jnp.float32), arg((slots,), jnp.int32),
        arg((slots,), jnp.float32),
        arg((2,), jnp.uint32),
    )
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    gb = 2 ** 30
    weight_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(params)
    )
    cache_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(cache)
    )
    print(f"== decode chunk ({preset}, {slots} slots x {chunk} steps, seq {seq}) ==")
    print(f"weights: {weight_bytes / gb:.2f} GB  kv cache: {cache_bytes / gb:.2f} GB")
    print(f"temp:    {mem.temp_size_in_bytes / gb:.3f} GB")
    print(f"args:    {mem.argument_size_in_bytes / gb:.2f} GB  "
          f"output: {mem.output_size_in_bytes / gb:.2f} GB  "
          f"(donation aliases the cache; it is carried in place, so a "
          f"temp of a slab or more is a cache copy)")
    if cost:
        bytes_accessed = cost.get("bytes accessed", 0.0)
        flops = cost.get("flops", 0.0)
        per_step = bytes_accessed / chunk
        ideal = weight_bytes + cache_bytes
        print(f"bytes accessed: {bytes_accessed / gb:.1f} GB total, "
              f"{per_step / gb:.2f} GB/step "
              f"(weights+cache roofline {ideal / gb:.2f} GB/step, "
              f"ratio {per_step / ideal:.2f}x)")
        print(f"flops: {flops / 1e12:.2f} TF total")
        print(f"roofline step time at 819 GB/s: {per_step / (819 * 2**30) * 1e3:.1f} ms")


if __name__ == "__main__" and "--prefill" not in sys.argv and "--tp8-70b" not in sys.argv:
    main()


def probe_prefill(preset="llama-3-8b", batch=32, bucket=128, slots=32,
                  seq=320) -> None:
    """Same memory/cost analysis for the batched prefill jit."""
    import dataclasses

    config = model_lib.LlamaConfig.from_dict({"preset": preset})
    config = dataclasses.replace(config, max_seq_len=seq)
    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(topo.devices[:1], ("d",))
    sharding = NamedSharding(mesh, PartitionSpec())

    def shapes_of(tree):
        return jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=sharding
            ),
            tree,
        )

    params = shapes_of(
        jax.eval_shape(lambda: init_quantized_params(config, seed=0))
    )
    cache = shapes_of(
        jax.eval_shape(lambda: model_lib.init_cache(config, slots, seq))
    )
    freqs = rope_frequencies(
        config.dims_per_head, config.max_seq_len, config.rope_theta
    )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_run(params, cache, tokens, lengths, slot_ids):
        return model_lib.prefill(
            config, params, cache, tokens, lengths, slot_ids, freqs
        )

    compiled = prefill_run.lower(
        params, cache,
        arg((batch, bucket), jnp.int32),
        arg((batch,), jnp.int32),
        arg((batch,), jnp.int32),
    ).compile()
    mem = compiled.memory_analysis()
    gb = 2 ** 30
    print(f"== prefill ({preset}, batch {batch} x bucket {bucket}) ==")
    print(f"temp: {mem.temp_size_in_bytes / gb:.3f} GB  "
          f"args: {mem.argument_size_in_bytes / gb:.2f} GB")


if __name__ == "__main__" and "--prefill" in sys.argv:
    probe_prefill()


def probe_tp8_70b(slots=8, chunk=16, seq=512) -> None:
    """BASELINE config #5: compile the 70B int8 decode chunk tp=8-sharded
    for an 8-device v5e topology and report per-chip memory — proves the
    sharded program builds and fits HBM without the hardware."""
    import dataclasses

    from langstream_tpu.parallel.mesh import (
        MeshConfig,
        build_mesh,
        param_shardings,
        shard_params,  # noqa: F401 (sharding rules live beside it)
    )
    from langstream_tpu.parallel import mesh as mesh_lib

    config = model_lib.LlamaConfig.from_dict({"preset": "llama-3-70b"})
    config = dataclasses.replace(config, max_seq_len=seq)
    topo = topologies.get_topology_desc("v5e:2x4", "tpu")
    mesh = build_mesh(MeshConfig(tp=8), devices=list(topo.devices)[:8])

    from langstream_tpu.providers.jax_local.quant import (
        quantize_logical_axes,
    )

    axes = model_lib.logical_axes(config)
    param_shapes = jax.eval_shape(lambda: init_quantized_params(config, 0))
    axes = quantize_logical_axes(axes, param_shapes)
    shardings = param_shardings(axes, mesh)

    def with_sharding(shape_tree, sharding_tree):
        return jax.tree_util.tree_map(
            lambda leaf, s: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                                 sharding=s),
            shape_tree, sharding_tree,
        )

    params = with_sharding(param_shapes, shardings)
    cache_shapes = jax.eval_shape(
        lambda: model_lib.init_cache(config, slots, seq)
    )
    cache_shardings = param_shardings(model_lib.cache_logical_axes(), mesh)
    cache = with_sharding(cache_shapes, cache_shardings)
    freqs = rope_frequencies(
        config.dims_per_head, config.max_seq_len, config.rope_theta
    )
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=replicated)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode_run(params, cache, tokens, lengths, active, write_mask,
                   temperature, top_k, top_p, rng):
        def body(carry, key):
            cache, tokens, lengths = carry
            cache, logits, _ = model_lib.decode_step(
                config, params, cache, tokens, lengths, freqs, write_mask
            )
            sampled, lp = _sample_with_logprob(
                logits, temperature, top_k,
                jax.random.split(key, tokens.shape[0]), top_p
            )
            lengths = jnp.where(active, lengths + 1, lengths)
            return (cache, sampled, lengths), (sampled, lp)

        keys = jax.random.split(rng, chunk)
        (cache, _, _), (out, lps) = jax.lax.scan(
            body, (cache, tokens, lengths), keys
        )
        return cache, out.T, lps.T

    with mesh:
        compiled = decode_run.lower(
            params, cache,
            arg((slots,), jnp.int32), arg((slots,), jnp.int32),
            arg((slots,), jnp.bool_), arg((slots,), jnp.bool_),
            arg((slots,), jnp.float32), arg((slots,), jnp.int32),
            arg((slots,), jnp.float32), arg((2,), jnp.uint32),
        ).compile()
    mem = compiled.memory_analysis()
    gb = 2 ** 30
    weight_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(param_shapes)
    )
    print(f"== 70B int8 decode, tp=8 on v5e:2x4 "
          f"({slots} slots x {chunk} steps, seq {seq}) ==")
    print(f"total weights: {weight_bytes / gb:.1f} GB "
          f"(~{weight_bytes / 8 / gb:.2f} GB/chip sharded)")
    print(f"per-chip: args {mem.argument_size_in_bytes / gb:.2f} GB, "
          f"temp {mem.temp_size_in_bytes / gb:.3f} GB, "
          f"output {mem.output_size_in_bytes / gb:.2f} GB")
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 * gb, (
        "does not fit a 16 GB v5e chip"
    )
    print("fits one v5e chip's HBM per shard: OK")


if __name__ == "__main__" and "--tp8-70b" in sys.argv:
    probe_tp8_70b()
