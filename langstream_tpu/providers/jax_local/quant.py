"""Weight-only int8 quantization for serving.

TPU decode is weights-bound: every step re-reads all parameters from HBM
while the MXU sits mostly idle. Storing matmul weights as int8 with
per-output-channel scales halves the bytes read per step (vs bf16),
which translates almost directly into decode throughput — and lets an
8B-parameter model fit a single 16 GB v5e chip.

Dequantization happens *inside* the consuming matmul: ``dq()`` emits
``q.astype(dtype) * scale``, which XLA fuses into the einsum so int8 is
what crosses HBM and the multiply-add runs in bf16 on the MXU. No custom
kernels needed; this is the standard JAX serving recipe.

``QTensor`` is a NamedTuple, hence automatically a pytree node: scans
slice the leading layer axis of both ``q`` and ``scale``, and
``shard_params`` descends into it when given a matching QTensor of
logical axes (see :func:`quantize_logical_axes`).

Reference parity: none — the reference's models live behind provider
HTTPS APIs (SURVEY §2.4); quantization is net-new for the in-process
backend, analogous to what its external providers do server-side.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from langstream_tpu.parallel.mesh import L, LogicalAxes


class QTensor(NamedTuple):
    q: jnp.ndarray      # int8, original weight shape
    scale: jnp.ndarray  # f32, weight shape minus the contraction axis


def quantize(w: jnp.ndarray, contract_axis: int = -2) -> QTensor:
    """Symmetric per-channel int8: scales taken over the contraction
    (input) axis so each output channel dequantizes independently.

    For stacked weights [L, in, out] the default ``contract_axis=-2``
    is the ``in`` axis → scale [L, out].
    """
    w32 = jnp.asarray(w, dtype=jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=contract_axis, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QTensor(q=q, scale=jnp.squeeze(scale, axis=contract_axis))


def dq(w: Any, dtype: Any) -> jnp.ndarray:
    """Dequantize-or-cast: QTensor → bf16 weight (fused into the consumer
    matmul by XLA), plain array → cast. Model code calls this on every
    matmul weight so quantized and full-precision params are
    interchangeable."""
    if isinstance(w, QTensor):
        scale = jnp.expand_dims(w.scale, axis=-2)
        return (w.q.astype(dtype) * scale.astype(dtype))
    return w.astype(dtype) if w.dtype != dtype else w


def qeinsum(spec: str, x: jnp.ndarray, w: Any) -> jnp.ndarray:
    """Einsum against an optionally-quantized weight, scale applied to
    the OUTPUT.

    The decode path is weights-bound, so what matters is that int8 is the
    only thing crossing HBM. ``dq()``'s operand-side expression
    ``convert(int8)*broadcast(scale)`` is not reliably fused into the dot
    by XLA:TPU — when it isn't, every step materializes the bf16 weight
    (3× the traffic int8 was meant to save). Per-output-channel scales
    commute with the contraction, so we contract against the bare
    ``convert(int8)`` (which XLA does fuse into the MXU operand stream)
    and multiply the [*, out] result by the scale — an elementwise op on
    activations, not weights.

    Requires ``spec`` to contract the weight's second-to-last axis and
    end with its last axis (true of every matmul in the model).
    """
    if isinstance(w, QTensor):
        out = jnp.einsum(spec, x, w.q.astype(x.dtype))
        return out * w.scale.astype(x.dtype)
    return jnp.einsum(spec, x, w.astype(x.dtype) if w.dtype != x.dtype else w)


# parameter names quantized for the dense Llama family; MoE expert
# weights keep bf16 for now (expert matmuls are already batched small)
QUANTIZED_PARAMS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head")


def quantize_params(
    params: Dict[str, Any], num_experts: int = 0
) -> Dict[str, Any]:
    """Quantize the large matmul weights of a stacked-params pytree.
    Embedding and norms stay full precision (lookups/elementwise).
    Idempotent: already-quantized leaves pass through."""
    if "run0.wq" in params:
        # the hybrid family's leaves lie in a stack a run of layers
        from langstream_tpu.providers.jax_local import hybrid_sparse_linear

        return hybrid_sparse_linear.quantize_params(params, quantize)
    out = dict(params)
    moe_names = {"w_gate", "w_up", "w_down"} if num_experts else set()
    for name in QUANTIZED_PARAMS:
        if (
            name in out
            and name not in moe_names
            and not isinstance(out[name], QTensor)
        ):
            out[name] = quantize(out[name])
    return out


def init_quantized_params(
    config, seed: int = 0, direct: Optional[bool] = None
) -> Dict[str, Any]:
    """Random-init directly in int8 (benchmarking): never materializes
    the bf16 weights, so an 8B model inits in ~9 GB instead of peaking
    at 24 GB (bf16 + int8) — the difference between fitting one v5e
    chip and not. ``direct=None`` picks by size (small models go
    through the exact init + quantize path)."""
    import math

    from langstream_tpu.providers.jax_local import model as model_lib

    if config.hybrid is not None:
        # the hybrid family draws its int8 form itself, layer by layer
        from langstream_tpu.providers.jax_local import hybrid_sparse_linear

        return hybrid_sparse_linear.init_params(config, seed, quantized=True)
    key = jax.random.PRNGKey(seed)
    h = config.hidden_size
    scale = 1.0 / math.sqrt(h) / 127.0

    def q_init(k, shape):
        q = jax.random.randint(k, shape, -127, 128, dtype=jnp.int8)
        return QTensor(
            q=q, scale=jnp.full(shape[:-2] + shape[-1:], scale, jnp.float32)
        )

    if direct is None:
        direct = config.num_params() >= 5e8 and not config.num_experts
    if not direct or config.num_experts:
        # MoE always goes through exact init + quantize: the direct path
        # below emits dense-shaped MLP weights with no router
        return quantize_params(
            model_lib.init_params(config, seed=seed), config.num_experts
        )

    nh, nkv, hd = config.num_heads, config.num_kv_heads, config.dims_per_head
    f, v, layers = config.intermediate_size, config.vocab_size, config.num_layers
    keys = jax.random.split(key, 10)
    dtype = config.dtype
    # zero-centered norm convention (Gemma): identity weight is 0
    norm_fill = 0.0 if config.norm_plus_one else 1.0
    out: Dict[str, Any] = {
        "embedding": (
            jax.random.normal(keys[0], (v, h), dtype=dtype) * (1.0 / math.sqrt(h))
        ),
        "wq": q_init(keys[1], (layers, h, nh * hd)),
        "wk": q_init(keys[2], (layers, h, nkv * hd)),
        "wv": q_init(keys[3], (layers, h, nkv * hd)),
        "wo": q_init(keys[4], (layers, nh * hd, h)),
        "w_gate": q_init(keys[5], (layers, h, f)),
        "w_up": q_init(keys[6], (layers, h, f)),
        "w_down": q_init(keys[7], (layers, f, h)),
        "attn_norm": jnp.full((layers, h), norm_fill, dtype=jnp.float32),
        "mlp_norm": jnp.full((layers, h), norm_fill, dtype=jnp.float32),
        "final_norm": jnp.full((h,), norm_fill, dtype=jnp.float32),
    }
    if config.post_norms:
        out["post_attn_norm"] = jnp.full(
            (layers, h), norm_fill, dtype=jnp.float32
        )
        out["post_mlp_norm"] = jnp.full(
            (layers, h), norm_fill, dtype=jnp.float32
        )
    if config.qkv_bias:
        out["bq"] = jnp.zeros((layers, nh * hd), dtype=jnp.float32)
        out["bk"] = jnp.zeros((layers, nkv * hd), dtype=jnp.float32)
        out["bv"] = jnp.zeros((layers, nkv * hd), dtype=jnp.float32)
    if not config.tie_embeddings:
        out["lm_head"] = q_init(keys[8], (h, v))
    return out


def init_quantized_params_cached(
    config, seed: int = 0, cache_dir: Optional[str] = None
) -> Dict[str, Any]:
    """``init_quantized_params`` with an opt-in on-disk cache
    (``LS_WEIGHTS_CACHE_DIR``), so a repeated run can skip random-init
    + quantize entirely.

    Default OFF: on-device random init runs ~10 small jits that live in
    the persistent compile cache, so a warm run's init is seconds of
    on-chip compute — while loading the cache means pushing ~9 GB of
    host bytes to the device (`jax.device_put`). Use when init itself
    is the bottleneck (the bench's per-phase ``timings_s`` shows which).

    bf16 leaves ride as uint16 views (numpy can't serialize ml_dtypes
    reliably); dtype strings travel in a manifest entry. Writes are
    atomic (tmp + rename) so a killed attempt can't leave a truncated
    cache that poisons the next one."""
    import json
    import logging
    import os
    import time

    import numpy as np

    cache_dir = cache_dir or os.environ.get("LS_WEIGHTS_CACHE_DIR", "")
    if not cache_dir:
        return init_quantized_params(config, seed=seed)
    os.makedirs(cache_dir, exist_ok=True)
    # sweep orphaned tmp files from killed attempts (a mid-savez kill
    # leaves a multi-GB partial that nothing else deletes); only ones
    # older than 5 min, so a concurrent writer's live tmp survives
    now = time.time()
    for name in os.listdir(cache_dir):
        if ".tmp" in name:
            stale = os.path.join(cache_dir, name)
            try:
                if now - os.path.getmtime(stale) > 300:
                    os.unlink(stale)
            except OSError:
                pass
    # the key must separate every config whose INIT VALUES differ, not
    # just shape-identical ones: a norm-convention flip (norm_plus_one
    # fills norms with 0 instead of 1), sandwich norms, qkv biases, or a
    # tied head all change the pytree contents while num_params() can
    # stay equal — loading another preset's cache silently serves wrong
    # weights (ADVICE r5). Readable dims stay up front; the digest folds
    # in the full weight-relevant field set (runtime-only knobs like
    # use_flash are excluded so kernel A/Bs share one cache entry).
    import dataclasses
    import hashlib

    sig_fields = (
        "vocab_size", "hidden_size", "intermediate_size", "num_layers",
        "num_heads", "num_kv_heads", "head_dim", "num_experts",
        "num_experts_per_tok", "tie_embeddings", "post_norms",
        "qkv_bias", "norm_plus_one", "scale_embedding", "act", "dtype",
    )
    known = {f.name for f in dataclasses.fields(type(config))}
    signature = "|".join(
        f"{name}={getattr(config, name)!r}"
        for name in sig_fields if name in known
    )
    convention = "".join(
        tag for tag, on in (
            ("z1", config.norm_plus_one), ("pn", config.post_norms),
            ("qb", config.qkv_bias), ("te", config.tie_embeddings),
        ) if on
    ) or "std"
    digest = hashlib.sha1(signature.encode()).hexdigest()[:10]
    key = (
        f"int8_{config.num_layers}L_{config.hidden_size}h_"
        f"{config.num_params()}p_{convention}_{digest}_s{seed}"
    )
    path = os.path.join(cache_dir, key + ".npz")
    spec = jax.eval_shape(lambda: init_quantized_params(config, seed=seed))
    spec_leaves, treedef = jax.tree_util.tree_flatten(spec)

    def storable(arr):
        # uint16 view for 2-byte custom dtypes; wider types are native
        return (
            np.asarray(arr).view(np.uint16)
            if arr.dtype.itemsize == 2 and arr.dtype.kind == "V"
            or str(arr.dtype) == "bfloat16"
            else np.asarray(arr)
        )

    if os.path.exists(path):
        try:
            with np.load(path, allow_pickle=False) as data:
                dtypes = json.loads(bytes(data["manifest"]).decode())
                if len(dtypes) != len(spec_leaves):
                    raise ValueError("leaf count mismatch")
                leaves = []
                for i, (s, dt) in enumerate(zip(spec_leaves, dtypes)):
                    raw = data[f"a{i}"]
                    arr = raw.view(jnp.bfloat16) if dt == "bfloat16" else raw
                    if arr.shape != s.shape or str(arr.dtype) != str(s.dtype):
                        raise ValueError(f"leaf {i} mismatch")
                    leaves.append(jax.device_put(arr))
            return jax.tree_util.tree_unflatten(treedef, leaves)
        except Exception as error:  # noqa: BLE001 — stale/corrupt: re-init
            try:
                os.unlink(path)
            except OSError:
                pass
            logging.getLogger(__name__).warning(
                "weights cache %s unusable (%r); re-initializing", path, error
            )
    params = init_quantized_params(config, seed=seed)
    leaves = jax.tree_util.tree_leaves(params)
    arrays = {f"a{i}": storable(leaf) for i, leaf in enumerate(leaves)}
    arrays["manifest"] = np.frombuffer(
        json.dumps([str(leaf.dtype) for leaf in leaves]).encode(), np.uint8
    ).copy()
    tmp = path + f".tmp{os.getpid()}"
    np.savez(tmp, **arrays)
    # np.savez appends .npz to names lacking it
    os.replace(tmp if os.path.exists(tmp) else tmp + ".npz", path)
    return params


def quantize_logical_axes(
    axes: Dict[str, Any], params: Dict[str, Any]
) -> Dict[str, Any]:
    """Mirror a logical-axes pytree onto quantized params: quantized
    leaves become QTensor(q=original axes, scale=axes minus the
    contraction axis) so ``shard_params`` descends in lockstep."""
    out = dict(axes)
    for name, value in params.items():
        if isinstance(value, QTensor) and name in out:
            names = out[name].names
            scale_names = names[:-2] + (names[-1],)
            out[name] = QTensor(
                q=L(*names), scale=L(*scale_names)
            )
    return out
