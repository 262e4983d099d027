"""Flash-decode kernel (interpret mode / virtual CPU mesh) against the
plain-XLA decode attention, incl. the int8-cache twin and the tp-sharded
wrapper. Lengths cover full, partial-block, single-token, and empty
slots — the walk over live blocks must stay numerically invisible, and
an empty slot reads zeros.
The kernel takes the STACKED cache and a layer index (it reads its slab
where it lies); every case hands it a three-layer stack and asks for the
middle slab, the reference gets that slab sliced out."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from langstream_tpu.ops.attention import (
    decode_attention,
    decode_attention_quant,
    quantize_kv,
)
from langstream_tpu.ops.decode_kernel import (
    decode_shapes_ok,
    flash_decode_attention,
    flash_decode_attention_quant,
    flash_decode_attention_sharded,
    kv_pack,
    pick_block_k,
    use_flash_decode,
)


LAYERS = 3
LAYER = jnp.asarray(1, dtype=jnp.int32)  # the slab every case reads


def _make_inputs(slots, max_len, heads, kv_heads, dim, seed=0):
    """q [S, H, D] and the stacked k, v [LAYERS, S, T, KVH, D]."""
    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    stack = (LAYERS, slots, max_len, kv_heads, dim)
    q = jax.random.normal(kq, (slots, heads, dim), dtype=jnp.float32)
    k = jax.random.normal(kk, stack, dtype=jnp.float32)
    v = jax.random.normal(kv, stack, dtype=jnp.float32)
    return q, k, v


def _packed(stack, pack):
    """[.., KVH, D] as the cache holds narrow heads: [.., KVH / pack,
    pack * D], ``pack`` kv heads side by side in one 128-lane row."""
    return stack.reshape(
        stack.shape[:3] + (stack.shape[3] // pack, stack.shape[4] * pack)
    )


# (heads, kv heads, head dim, mode): ``plain``, ``window`` (Gemma-2's
# window + softcap + scale) or ``int8kv`` (the quantized cache and its
# scales). Head dims under 128 go in PACKED, the reference reads the heads
# apart. The first cases hand four slots: a full one, a ragged one, a
# single token and an empty one.
FIRST_LENGTHS = (256, 100, 1, 0)
MODES = {
    "bf16": (8, 4, 128, "plain"),
    "packed": (14, 2, 64, "plain"),   # Qwen-2.5-0.5B: one row
    "int8kv": (8, 4, 128, "int8kv"),
    "window-softcap": (8, 4, 128, "window"),
}
# lengths the walk over live blocks must keep invisible (``block_k`` 64,
# ``max_len`` 256); ``groups`` cuts the slots into grid steps of two
# (the VMEM budget of ``pick_slot_group`` lowered to fit two slots)
LENGTH_PATTERNS = {
    "dead-among-live": dict(lengths=(0, 200, 0, 0, 77, 0)),
    "all-dead": dict(lengths=(0, 0, 0)),
    "block-edges": dict(lengths=(64, 65, 256, 128)),
    "one-live-in-a-group": dict(lengths=(0, 0, 131, 0, 0, 0), groups=True),
    # window 40: the first live block is 3 and 2 for the first two slots
    "window-past-block-0": dict(lengths=(256, 190, 100, 45), window=40),
}


@pytest.mark.parametrize(
    "heads,kv_heads,dim,mode,pattern",
    [
        pytest.param(
            heads, kv_heads, dim, mode, "first",
            id=f"{heads}-{kv_heads}-{dim}-{mode == 'window'}",
        )
        for heads, kv_heads, dim, mode in (
            (8, 8, 128, "plain"), (8, 4, 128, "plain"), (8, 2, 128, "plain"),
            (8, 1, 128, "plain"),
            (14, 2, 64, "plain"), (14, 2, 64, "window"),  # Qwen-2.5-0.5B: one row
            (32, 8, 64, "plain"), (32, 8, 64, "window"),  # Llama-3.2-1B: four rows
            (8, 4, 32, "plain"),                          # four heads to a row
            (6, 3, 128, "plain"),                         # sliced per kv head
        )
    ] + [
        pytest.param(*MODES[mode], pattern, id=f"{pattern}-{mode}")
        for pattern in LENGTH_PATTERNS for mode in MODES
    ],
)
def test_flash_decode_matches_reference(
    monkeypatch, heads, kv_heads, dim, mode, pattern
):
    import langstream_tpu.ops.decode_kernel as decode_kernel

    case = LENGTH_PATTERNS.get(pattern, dict(lengths=FIRST_LENGTHS))
    lengths = jnp.array(case["lengths"], dtype=jnp.int32)
    slots, max_len = len(case["lengths"]), 256
    if case.get("groups"):
        per_slot = 4 * 16 * dim * 4  # two f32 q and out blocks of 16 rows
        monkeypatch.setattr(decode_kernel, "_GROUP_VMEM_BYTES", 2 * per_slot)
        assert decode_kernel.pick_slot_group(slots, heads, dim, 4) == 2
    q, k, v = _make_inputs(slots, max_len, heads, kv_heads, dim)
    kw = (
        dict(softcap=30.0, window=jnp.asarray(40, jnp.int32), scale=0.17)
        if mode == "window" else {}
    )
    if "window" in case:
        kw["window"] = jnp.asarray(case["window"], jnp.int32)

    if mode == "int8kv":
        k_q, k_s = quantize_kv(k)
        v_q, v_s = quantize_kv(v)
        ref = decode_attention_quant(
            q, k_q[LAYER], k_s[LAYER], v_q[LAYER], v_s[LAYER], lengths, **kw
        )
        out = flash_decode_attention_quant(
            q, k_q, k_s, v_q, v_s, lengths, LAYER, block_k=64,
            interpret=True, **kw,
        )
        tol = 2e-4
    else:
        ref = decode_attention(q, k[LAYER], v[LAYER], lengths, **kw)
        pack = kv_pack(dim, kv_heads)
        out = flash_decode_attention(
            q, _packed(k, pack), _packed(v, pack), lengths, LAYER,
            block_k=64, interpret=True, **kw,
        )
        tol = 2e-5
    assert out.shape == q.shape
    for s in range(slots):
        if int(lengths[s]) == 0:
            # an empty slot is neither fetched nor computed: it reads zeros
            np.testing.assert_array_equal(np.asarray(out[s]), 0.0)
            continue
        np.testing.assert_allclose(
            np.asarray(out[s]), np.asarray(ref[s]), rtol=tol, atol=tol
        )


def _pallas_grid(fn, *shapes):
    """The grid of the one ``pallas_call`` that ``fn`` traces to."""
    jaxpr = jax.make_jaxpr(fn)(*shapes)
    grids = [
        eqn.params["grid_mapping"].grid for eqn in jaxpr.jaxpr.eqns
        if eqn.primitive.name == "pallas_call"
    ]
    assert len(grids) == 1, grids
    return grids[0]


@pytest.mark.parametrize(
    "slots,heads,kv_heads,dim,quantized",
    [(32, 28, 4, 128, False), (32, 28, 4, 128, True),
     (128, 14, 1, 128, False), (256, 32, 8, 128, False)],
    ids=["7b", "7b-int8kv", "0.5b-packed", "256-slots"],
)
def test_the_grid_does_not_grow_with_the_cache(
    slots, heads, kv_heads, dim, quantized
):
    """The kernel's grid runs over groups of slots, never over kv blocks:
    the same at 2,048 and 4,096 allocated rows (a grid of (slot, block)
    would double), and one step a layer wherever the group's query and
    output blocks fit their VMEM budget (two steps for 256 slots of 32
    heads)."""
    grids = []
    for max_len in (2048, 4096):
        stack = jax.ShapeDtypeStruct(
            (LAYERS, slots, max_len, kv_heads, dim),
            jnp.int8 if quantized else jnp.bfloat16,
        )
        shapes = [
            jax.ShapeDtypeStruct((slots, heads, dim), jnp.bfloat16),
            stack, stack, jax.ShapeDtypeStruct((slots,), jnp.int32),
        ]
        if quantized:
            scales = jax.ShapeDtypeStruct(stack.shape[:-1], jnp.float32)
            shapes += [scales, scales]

        def fn(q, k, v, lengths, *sc):
            extra = {"k_scale": sc[0], "v_scale": sc[1]} if sc else {}
            return flash_decode_attention(q, k, v, lengths, LAYER, **extra)

        grids.append(_pallas_grid(fn, *shapes))
    assert grids[0] == grids[1]
    assert grids[0] == ((2,) if slots == 256 else (1,))


@pytest.mark.parametrize(
    "dim,heads,kv_heads,quantized,tp,pack",
    [
        (128, 28, 4, False, 1, 1), (128, 28, 4, True, 4, 1),
        (256, 8, 4, False, 1, 1),
        (64, 14, 2, False, 1, 2),      # two heads fill a row
        (64, 14, 1, False, 1, None),   # half a row
        (64, 14, 2, True, 1, None),    # two heads, two scales a row
        (64, 14, 2, False, 2, None),   # a shard would hold half a row
        (64, 32, 8, False, 2, 2), (64, 32, 8, False, 4, 2),
        (64, 32, 8, False, 8, None),
        (96, 8, 4, False, 1, None), (32, 8, 4, False, 1, 4),
        (16, 4, 2, False, 1, None),
    ],
)
def test_gate_truth_table(dim, heads, kv_heads, quantized, tp, pack):
    """What the kernel can read, and how many kv heads to a cache row."""
    assert kv_pack(dim, kv_heads, quantized, tp) == pack
    assert decode_shapes_ok(2048, dim, heads, kv_heads, quantized, tp) == (
        pack is not None
    )
    # no block size divides a 7-row cache, whatever the heads
    assert not decode_shapes_ok(7, dim, heads, kv_heads, quantized, tp)


def test_flash_decode_sharded_packed_matches_reference():
    """tp=2 over four 64-wide kv heads: a shard holds one packed row and
    the four heads that read it; no head's lanes cross a shard."""
    slots, max_len, heads, kv_heads, dim = 2, 128, 8, 4, 64
    q, k, v = _make_inputs(slots, max_len, heads, kv_heads, dim, seed=8)
    lengths = jnp.array([128, 60], dtype=jnp.int32)
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("tp",))
    ref = decode_attention(q, k[LAYER], v[LAYER], lengths)
    out = flash_decode_attention_sharded(
        q, _packed(k, 2), _packed(v, 2), lengths, LAYER, mesh, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_flash_decode_quant_matches_reference():
    slots, max_len, heads, kv_heads, dim = 3, 256, 8, 4, 128
    q, k, v = _make_inputs(slots, max_len, heads, kv_heads, dim, seed=1)
    k_q, k_s = quantize_kv(k)
    v_q, v_s = quantize_kv(v)
    lengths = jnp.array([256, 130, 7], dtype=jnp.int32)

    ref = decode_attention_quant(
        q, k_q[LAYER], k_s[LAYER], v_q[LAYER], v_s[LAYER], lengths
    )
    out = flash_decode_attention_quant(
        q, k_q, k_s, v_q, v_s, lengths, LAYER, block_k=64, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["two-a-shard", "one-a-shard"])
def test_flash_decode_sharded_matches_reference(kv_heads):
    # one kv head a shard is what tp=4 leaves of a 4-kv-head model: the
    # kernel is then handed the leaf with its head axis squeezed
    slots, max_len, heads, dim = 2, 128, 8, 128
    q, k, v = _make_inputs(slots, max_len, heads, kv_heads, dim, seed=2)
    lengths = jnp.array([128, 60], dtype=jnp.int32)

    devices = np.asarray(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devices, ("tp",))
    ref = decode_attention(q, k[LAYER], v[LAYER], lengths)
    out = flash_decode_attention_sharded(
        q, k, v, lengths, LAYER, mesh, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_block_pick_and_gate():
    assert pick_block_k(8192) == 512
    assert pick_block_k(320) == 64
    assert pick_block_k(7) is None
    # CPU backend → gate must stay closed regardless of shape
    assert not use_flash_decode(8192, 128, 32, 8)


def _tiny128_config():
    from langstream_tpu.providers.jax_local.model import LlamaConfig

    # smallest shape satisfying the kernel's requirements (D % 128,
    # block divides max_len) so interpret mode stays fast on CPU
    return LlamaConfig(
        vocab_size=64, hidden_size=128, intermediate_size=96,
        num_layers=2, num_heads=2, num_kv_heads=2, head_dim=128,
        max_seq_len=64, dtype=jnp.float32, flash_interpret=True,
    )


@pytest.mark.parametrize("kv_quant", [False, True])
def test_decode_step_flash_wiring(kv_quant):
    """decode_step through the kernel (flash_interpret) must match the
    XLA path bit-for-bit in shapes and closely in values — covers the
    cache write ordering, GQA grouping, and lengths-include-new-token
    semantics end to end."""
    import dataclasses

    from langstream_tpu.providers.jax_local import model as model_lib

    config = _tiny128_config()
    params = model_lib.init_params(config, seed=3)
    freqs = model_lib.rope_frequencies(
        config.dims_per_head, config.max_seq_len, config.rope_theta
    )
    slots = 3
    key = jax.random.PRNGKey(7)

    def run(cfg):
        cache = model_lib.init_cache(cfg, slots, kv_quant=kv_quant)
        # warm two slots with random prefix KV rows, leave one cold
        prefix = jax.random.normal(
            key, cache["k"].shape, dtype=jnp.float32
        )
        if kv_quant:
            k_q, k_s = quantize_kv(prefix)
            cache = dict(
                cache, k=k_q, k_scale=k_s,
                v=jnp.roll(k_q, 1, axis=2),
                v_scale=jnp.roll(k_s, 1, axis=2),
            )
        else:
            cache = dict(
                cache,
                k=prefix.astype(cache["k"].dtype),
                v=jnp.roll(prefix, 1, axis=2).astype(cache["v"].dtype),
            )
        tokens = jnp.array([5, 9, 11], dtype=jnp.int32)
        lengths = jnp.array([40, 13, 1], dtype=jnp.int32)
        return model_lib.decode_step(
            cfg, params, cache, tokens, lengths, freqs
        )

    cache_ref, logits_ref, _ = run(
        dataclasses.replace(config, use_flash=False, flash_interpret=False)
    )
    cache_out, logits_out, _ = run(config)
    np.testing.assert_allclose(
        np.asarray(logits_out), np.asarray(logits_ref), rtol=2e-4, atol=2e-4
    )
    for name in cache_ref:
        np.testing.assert_allclose(
            np.asarray(cache_out[name]), np.asarray(cache_ref[name]),
            rtol=1e-5, atol=1e-5,
        )


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["two-a-shard", "one-a-shard"])
def test_flash_decode_sharded_quant_matches_reference(kv_heads):
    """The tp>1 + kv-quant branch of _decode_attn_quant: sharded kernel
    with int8 cache + scales must match the XLA quant path."""
    slots, max_len, heads, dim = 2, 128, 8, 128
    q, k, v = _make_inputs(slots, max_len, heads, kv_heads, dim, seed=4)
    k_q, k_s = quantize_kv(k)
    v_q, v_s = quantize_kv(v)
    lengths = jnp.array([128, 45], dtype=jnp.int32)

    devices = np.asarray(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devices, ("tp",))
    ref = decode_attention_quant(
        q, k_q[LAYER], k_s[LAYER], v_q[LAYER], v_s[LAYER], lengths
    )
    out = flash_decode_attention_sharded(
        q, k_q, v_q, lengths, LAYER, mesh, k_scale=k_s, v_scale=v_s,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_flash_decode_quant_bf16_matches_reference():
    """bf16 activations (the production dtype): the quant kernel keeps
    the scale-folded probs·values contraction in f32 exactly like the
    XLA quant path — a bf16 round-trip there would drift greedy decode
    between kernel-on and kernel-off (review finding, round 4)."""
    slots, max_len, heads, kv_heads, dim = 2, 128, 8, 4, 128
    q, k, v = _make_inputs(slots, max_len, heads, kv_heads, dim, seed=5)
    q = q.astype(jnp.bfloat16)
    k_q, k_s = quantize_kv(k)
    v_q, v_s = quantize_kv(v)
    lengths = jnp.array([128, 77], dtype=jnp.int32)

    ref = decode_attention_quant(
        q, k_q[LAYER], k_s[LAYER], v_q[LAYER], v_s[LAYER], lengths
    )
    out = flash_decode_attention_quant(
        q, k_q, k_s, v_q, v_s, lengths, LAYER, block_k=64, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_flash_decode_window_softcap_matches_reference():
    """Gemma-2 mechanisms in the kernel: sliding window (block skipping
    from BOTH ends) + logit softcap + query_pre_attn_scalar scale must
    match the XLA decode path bit-for-bit in masking semantics."""
    slots, max_len, heads, kv_heads, dim = 3, 256, 8, 4, 128
    q, k, v = _make_inputs(slots, max_len, heads, kv_heads, dim, seed=6)
    lengths = jnp.array([256, 150, 9], dtype=jnp.int32)
    window = jnp.asarray(40, dtype=jnp.int32)

    ref = decode_attention(
        q, k[LAYER], v[LAYER], lengths, softcap=30.0, window=window, scale=0.17
    )
    out = flash_decode_attention(
        q, k, v, lengths, LAYER, softcap=30.0, window=window, scale=0.17,
        block_k=64, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    # window wider than the context ≡ full attention
    ref_full = decode_attention(q, k[LAYER], v[LAYER], lengths)
    out_wide = flash_decode_attention(
        q, k, v, lengths, LAYER, window=jnp.asarray(4096, dtype=jnp.int32),
        block_k=64, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out_wide), np.asarray(ref_full), rtol=2e-5, atol=2e-5
    )


def test_flash_decode_window_quant_matches_reference():
    slots, max_len, heads, kv_heads, dim = 2, 128, 8, 4, 128
    q, k, v = _make_inputs(slots, max_len, heads, kv_heads, dim, seed=7)
    k_q, k_s = quantize_kv(k)
    v_q, v_s = quantize_kv(v)
    lengths = jnp.array([128, 70], dtype=jnp.int32)
    window = jnp.asarray(24, dtype=jnp.int32)

    ref = decode_attention_quant(
        q, k_q[LAYER], k_s[LAYER], v_q[LAYER], v_s[LAYER], lengths,
        softcap=50.0, window=window,
    )
    out = flash_decode_attention_quant(
        q, k_q, k_s, v_q, v_s, lengths, LAYER, softcap=50.0, window=window,
        block_k=32, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )
