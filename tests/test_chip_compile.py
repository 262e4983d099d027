"""Main-path kernels compiled for a DESCRIBED TPU v5e at real widths.

Interpret mode never sees Mosaic's tiling rules or the 16 MiB scoped-VMEM
limit, so kernels that pass every CPU parity test can still be refused by
the chip's compiler. The TPU compiler is installed without a chip: these
cases lower and compile each kernel for a ``v5e:2x2`` topology that is
described, not attached (nothing runs — a compile is not a chip run).

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu, and every xdist worker imports
every test file. Compiles happen in the test's own process with the
persistent compile cache off (a described-device entry cannot be read
back without a chip). All cases live in this one file so one worker owns
the library.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from langstream_tpu.ops.decode_kernel import (
    flash_decode_attention,
    flash_decode_attention_sharded,
)
from langstream_tpu.ops.flash_attention import flash_prefill_attention
from langstream_tpu.ops.paged_attention import (
    ragged_paged_attention,
    ragged_paged_attention_sharded,
    ragged_q_paged_attention,
)

D = 128
QWEN = (28, 4)    # Qwen-2.5-7B heads / kv heads
LLAMA = (32, 8)   # Llama-3-8B
BLOCK = 16        # engine default kv-block-size
POOL = 4096       # pool blocks (32 slots × 2048 / 16)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp_mesh(topo):
    return Mesh(np.array(topo.devices[:4]), ("tp",))


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("batch,seq", [(4, 1024), (2, 2048)])
def test_flash_prefill_compiles(one_chip, batch, seq, quant):
    heads, kv_heads = QWEN
    s = functools.partial(_spec, sharding=one_chip)
    kv_dtype = jnp.int8 if quant else jnp.bfloat16
    shapes = [
        s((batch, seq, heads, D), jnp.bfloat16),
        s((batch, seq, kv_heads, D), kv_dtype),
        s((batch, seq, kv_heads, D), kv_dtype),
        s((batch,), jnp.int32),
    ]
    if quant:
        shapes += [s((batch, seq, kv_heads), jnp.float32)] * 2

    def fn(q, k, v, lengths, *scales):
        kw = {"k_scale": scales[0], "v_scale": scales[1]} if scales else {}
        return flash_prefill_attention(q, k, v, lengths=lengths, **kw)

    assert "tpu_custom_call" in _compiled_text(fn, *shapes)


def _decode_shapes(s, heads, kv_heads, quant, slots=32, max_len=2048):
    kv_dtype = jnp.int8 if quant else jnp.bfloat16
    shapes = [
        s((slots, heads, D), jnp.bfloat16),
        s((slots, max_len, kv_heads, D), kv_dtype),
        s((slots, max_len, kv_heads, D), kv_dtype),
        s((slots,), jnp.int32),
    ]
    if quant:
        shapes += [s((slots, max_len, kv_heads), jnp.float32)] * 2
    return shapes


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
def test_flash_decode_compiles(one_chip, quant):
    heads, kv_heads = QWEN
    shapes = _decode_shapes(
        functools.partial(_spec, sharding=one_chip), heads, kv_heads, quant
    )

    def fn(q, k, v, lengths, *scales):
        kw = {"k_scale": scales[0], "v_scale": scales[1]} if scales else {}
        return flash_decode_attention(q, k, v, lengths, **kw)

    assert "tpu_custom_call" in _compiled_text(fn, *shapes)


def _paged_shapes(s, batch, tq, heads, kv_heads, quant):
    kv_dtype = jnp.int8 if quant else jnp.bfloat16
    shapes = [
        s((batch, tq, heads, D), jnp.bfloat16),
        s((POOL, BLOCK, kv_heads, D), kv_dtype),
        s((POOL, BLOCK, kv_heads, D), kv_dtype),
        s((batch, 2048 // BLOCK), jnp.int32),
        s((batch,), jnp.int32),
        s((batch,), jnp.int32),
    ]
    if quant:
        shapes += [s((POOL, BLOCK, kv_heads), jnp.float32)] * 2
    return shapes


@pytest.mark.parametrize(
    "tq,model,quant",
    [
        (1, QWEN, False),
        (64, QWEN, False),
        (256, QWEN, False),
        (1, QWEN, True),
        # the scoped-VMEM case: a fixed block_q=128 needs 17.5 MiB at H=32
        (256, LLAMA, False),
        (256, LLAMA, True),
    ],
    ids=[
        "tq1-h28", "tq64-h28", "tq256-h28", "tq1-h28-int8kv", "tq256-h32",
        "tq256-h32-int8kv",
    ],
)
def test_ragged_paged_compiles(one_chip, tq, model, quant):
    heads, kv_heads = model
    batch = 32 if tq == 1 else 4
    shapes = _paged_shapes(
        functools.partial(_spec, sharding=one_chip),
        batch, tq, heads, kv_heads, quant,
    )

    def fn(q, kp, vp, tables, starts, lengths, *scales):
        kw = {"k_scale": scales[0], "v_scale": scales[1]} if scales else {}
        return ragged_paged_attention(q, kp, vp, tables, starts, lengths, **kw)

    assert "tpu_custom_call" in _compiled_text(fn, *shapes)


def test_ragged_q_paged_compiles(one_chip):
    """The mixed prefill+decode dispatch shape at its default block_q."""
    heads, kv_heads = QWEN
    batch, max_q_len = 32, 64
    s = functools.partial(_spec, sharding=one_chip)
    shapes = [
        s((batch * max_q_len, heads, D), jnp.bfloat16),
        s((POOL, BLOCK, kv_heads, D), jnp.bfloat16),
        s((POOL, BLOCK, kv_heads, D), jnp.bfloat16),
        s((batch, 2048 // BLOCK), jnp.int32),
        s((batch,), jnp.int32),
        s((batch,), jnp.int32),
        s((batch,), jnp.int32),
    ]

    def fn(q, kp, vp, tables, starts, lengths, qoffs):
        return ragged_q_paged_attention(
            q, kp, vp, tables, starts, lengths, qoffs, max_q_len=max_q_len
        )

    assert "tpu_custom_call" in _compiled_text(fn, *shapes)


def test_flash_decode_sharded_compiles(tp_mesh):
    heads, kv_heads = QWEN

    def s(shape, dtype):
        # head axes shard over tp; lengths replicate
        spec = {3: P(None, "tp", None), 4: P(None, None, "tp", None)}.get(
            len(shape), P()
        )
        return _spec(shape, dtype, NamedSharding(tp_mesh, spec))

    shapes = _decode_shapes(s, heads, kv_heads, quant=False)

    def fn(q, k, v, lengths):
        return flash_decode_attention_sharded(q, k, v, lengths, tp_mesh)

    assert "tpu_custom_call" in _compiled_text(fn, *shapes)


def test_ragged_paged_sharded_compiles(tp_mesh):
    heads, kv_heads = QWEN

    def s(shape, dtype):
        spec = P(None, None, "tp", None) if len(shape) == 4 else P()
        return _spec(shape, dtype, NamedSharding(tp_mesh, spec))

    shapes = _paged_shapes(s, 4, 256, heads, kv_heads, quant=False)

    def fn(q, kp, vp, tables, starts, lengths):
        return ragged_paged_attention_sharded(
            q, kp, vp, tables, starts, lengths, tp_mesh
        )

    assert "tpu_custom_call" in _compiled_text(fn, *shapes)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
def test_every_pallas_call_carries_its_name(one_chip, quant):
    """A kernel's ``name`` reaches the lowered text (``kernel_name``) and
    with it the HLO instruction the device trace shows, so a reduction can
    find a kernel by name (ISSUE 26). Lowered for the described chip: the
    CPU cannot lower a Mosaic kernel."""
    heads, kv_heads = QWEN
    s = functools.partial(_spec, sharding=one_chip)
    suffix = "_int8kv" if quant else ""
    kv_dtype = jnp.int8 if quant else jnp.bfloat16

    def scaled(fn, n):
        def call(*args):
            extra = args[n:]
            kw = {"k_scale": extra[0], "v_scale": extra[1]} if extra else {}
            return fn(*args[:n], **kw)
        return call

    prefill = [
        s((2, 1024, heads, D), jnp.bfloat16),
        s((2, 1024, kv_heads, D), kv_dtype),
        s((2, 1024, kv_heads, D), kv_dtype),
        s((2,), jnp.int32),
    ] + ([s((2, 1024, kv_heads), jnp.float32)] * 2 if quant else [])
    cases = {
        "flash_prefill": (
            lambda q, k, v, lengths, *sc: flash_prefill_attention(
                q, k, v, lengths=lengths,
                **({"k_scale": sc[0], "v_scale": sc[1]} if sc else {}),
            ),
            prefill,
        ),
        "flash_decode": (
            scaled(flash_decode_attention, 4),
            _decode_shapes(s, heads, kv_heads, quant),
        ),
        "ragged_paged_attention": (
            scaled(ragged_paged_attention, 6),
            _paged_shapes(s, 4, 64, heads, kv_heads, quant),
        ),
    }
    if not quant:
        cases["ragged_q_paged_attention"] = (
            lambda q, kp, vp, tables, starts, lengths, qoffs:
                ragged_q_paged_attention(
                    q, kp, vp, tables, starts, lengths, qoffs, max_q_len=64
                ),
            [
                s((32 * 64, heads, D), jnp.bfloat16),
                s((POOL, BLOCK, kv_heads, D), jnp.bfloat16),
                s((POOL, BLOCK, kv_heads, D), jnp.bfloat16),
                s((32, 2048 // BLOCK), jnp.int32),
                s((32,), jnp.int32), s((32,), jnp.int32), s((32,), jnp.int32),
            ],
        )
    for name, (fn, shapes) in cases.items():
        text = jax.jit(fn).lower(*shapes).as_text()
        assert f'kernel_name = "{name}{suffix}"' in text, name
