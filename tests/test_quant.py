"""Weight-only int8 quantization tests."""

import concurrent.futures
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from langstream_tpu.parallel.mesh import MeshConfig
from langstream_tpu.providers.jax_local import model as model_lib
from langstream_tpu.providers.jax_local.quant import (
    QTensor,
    dq,
    quantize,
    quantize_logical_axes,
    quantize_params,
)


def test_quantize_roundtrip_error_bounded():
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (4, 32, 64), dtype=jnp.float32)
    qt = quantize(w)
    assert qt.q.dtype == jnp.int8
    assert qt.scale.shape == (4, 64)
    back = dq(qt, jnp.float32)
    # per-channel symmetric int8: error < scale/2 per element
    max_err = float(jnp.abs(back - w).max())
    max_scale = float(qt.scale.max())
    assert max_err <= max_scale * 0.51


def test_quantized_forward_close_to_fp():
    config = model_lib.LlamaConfig.tiny()
    params = model_lib.init_params(config, seed=0)
    qparams = quantize_params(params)
    assert isinstance(qparams["wq"], QTensor)
    assert isinstance(qparams["embedding"], jnp.ndarray)  # not quantized
    tokens = jnp.arange(16, dtype=jnp.int32).reshape(2, 8) % config.vocab_size
    fp = model_lib.forward(config, params, tokens)
    q = model_lib.forward(config, qparams, tokens)
    # logits track closely; rank-1 agreement on most positions
    fp_top = np.argmax(np.asarray(fp), -1)
    q_top = np.argmax(np.asarray(q), -1)
    assert (fp_top == q_top).mean() > 0.9
    err = np.abs(np.asarray(fp) - np.asarray(q))
    assert err.mean() < 0.05 * np.abs(np.asarray(fp)).mean() + 0.05


def test_moe_params_keep_expert_weights_fp():
    config = model_lib.LlamaConfig.tiny_moe()
    params = model_lib.init_params(config, seed=0)
    qparams = quantize_params(params, config.num_experts)
    assert isinstance(qparams["w_gate"], jnp.ndarray)
    assert isinstance(qparams["router"], jnp.ndarray)
    assert isinstance(qparams["wq"], QTensor)


def test_quantized_engine_decode_and_tp_sharding():
    from langstream_tpu.providers.jax_local.engine import (
        DecodeEngine,
        GenerationRequest,
        SamplingParams,
    )

    config = model_lib.LlamaConfig.tiny()
    params = model_lib.init_params(config, seed=0)
    engine = DecodeEngine(
        config, params, mesh_config=MeshConfig(tp=2),
        max_slots=2, max_seq_len=64, prefill_buckets=[16],
        quantize="int8",
    )
    engine.start()
    fut = concurrent.futures.Future()
    engine.submit(GenerationRequest(
        prompt_tokens=[1, 2, 3],
        sampling=SamplingParams(max_new_tokens=6),
        future=fut,
    ))
    result = fut.result(timeout=300)
    engine.stop()
    assert len(result.tokens) == 6

    # greedy tokens match the fp engine (tiny model, small drift ok but
    # greedy argmax should be stable on random weights)
    engine_fp = DecodeEngine(
        config, params, max_slots=2, max_seq_len=64, prefill_buckets=[16],
    )
    engine_fp.start()
    fut2 = concurrent.futures.Future()
    engine_fp.submit(GenerationRequest(
        prompt_tokens=[1, 2, 3],
        sampling=SamplingParams(max_new_tokens=6),
        future=fut2,
    ))
    result_fp = fut2.result(timeout=300)
    engine_fp.stop()
    agree = sum(
        a == b for a, b in zip(result.tokens, result_fp.tokens)
    ) / len(result.tokens)
    assert agree >= 0.5, (result.tokens, result_fp.tokens)


def test_direct_int8_init_serves():
    """The direct int8 init (bench path for big models) produces a
    servable param tree without ever materializing bf16 weights."""
    from langstream_tpu.providers.jax_local.quant import init_quantized_params

    config = model_lib.LlamaConfig.tiny()
    params = init_quantized_params(config, seed=0, direct=True)
    assert isinstance(params["wq"], QTensor)
    assert params["wq"].q.dtype == jnp.int8
    tokens = jnp.arange(16, dtype=jnp.int32).reshape(2, 8) % config.vocab_size
    logits = model_lib.forward(config, params, tokens)
    assert np.isfinite(np.asarray(logits)).all()


def test_engine_rejects_unknown_quantization():
    config = model_lib.LlamaConfig.tiny()
    params = model_lib.init_params(config)
    from langstream_tpu.providers.jax_local.engine import DecodeEngine

    with pytest.raises(ValueError, match="unknown quantization"):
        DecodeEngine(config, params, quantize="fp4")


def test_quantize_logical_axes_structure():
    config = model_lib.LlamaConfig.tiny()
    params = quantize_params(model_lib.init_params(config))
    axes = quantize_logical_axes(model_lib.logical_axes(config), params)
    assert isinstance(axes["wq"], QTensor)
    assert axes["wq"].q.names == ("layers", "embed", "heads")
    assert axes["wq"].scale.names == ("layers", "heads")
    # shard_params descends in lockstep on a tp mesh
    from langstream_tpu.parallel.mesh import build_mesh, shard_params

    mesh = build_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    with mesh:
        placed = shard_params(params, axes, mesh)
    spec = placed["wq"].q.sharding.spec
    assert spec == (None, None, "tp") or tuple(spec) == (None, None, "tp")


def test_weights_cache_roundtrip(tmp_path):
    """Opt-in on-disk weights cache (LS_WEIGHTS_CACHE_DIR): exact
    round-trip incl. bf16-as-uint16 leaves, and a corrupt entry is
    pruned + re-initialized instead of failing the load."""
    from langstream_tpu.providers.jax_local.quant import (
        init_quantized_params_cached,
    )

    config = model_lib.LlamaConfig.tiny()
    first = init_quantized_params_cached(config, seed=3, cache_dir=str(tmp_path))
    files = list(tmp_path.iterdir())
    assert len(files) == 1 and files[0].suffix == ".npz"
    second = init_quantized_params_cached(config, seed=3, cache_dir=str(tmp_path))
    for a, b in zip(
        jax.tree_util.tree_leaves(first), jax.tree_util.tree_leaves(second)
    ):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        )
    # truncated/corrupt entry: recover by re-init, file replaced
    files[0].write_bytes(b"garbage")
    third = init_quantized_params_cached(config, seed=3, cache_dir=str(tmp_path))
    assert len(jax.tree_util.tree_leaves(third)) == len(
        jax.tree_util.tree_leaves(first)
    )
    # a DIFFERENT seed must not hit the seed-3 entry
    other = init_quantized_params_cached(config, seed=4, cache_dir=str(tmp_path))
    assert len(list(tmp_path.iterdir())) == 2
    changed = any(
        not np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(
            jax.tree_util.tree_leaves(third), jax.tree_util.tree_leaves(other)
        )
    )
    assert changed


def test_compile_cache_placed_from_outside(monkeypatch, request):
    """One rule: with JAX_COMPILATION_CACHE_DIR set the code sets
    nothing; unset, the only value ever set is the checkout's."""
    from langstream_tpu.runtime import compile_cache

    # an earlier test of this process (an engine's precompile) may have
    # placed the checkout's cache already: start from none placed
    was, update = jax.config.jax_compilation_cache_dir, jax.config.update
    update("jax_compilation_cache_dir", None)
    request.addfinalizer(lambda: update("jax_compilation_cache_dir", was))
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.configure_compile_cache() == "/somewhere/else"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    expected = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_compile_cache",
    )
    assert compile_cache.configure_compile_cache() == expected
    assert calls == [("jax_compilation_cache_dir", expected)]
