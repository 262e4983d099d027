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
import re

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


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
def test_flash_prefill_compiles_at_latent_widths(one_chip, quant):
    """The latent family's prefill call: 128 expanded heads, keys 192 and
    values 128 wide, 512 x 512 blocks over a 4,096-token bucket."""
    heads, seq = 128, 4096
    s = functools.partial(_spec, sharding=one_chip)
    kv_dtype = jnp.int8 if quant else jnp.bfloat16
    shapes = [
        s((1, seq, heads, 192), jnp.bfloat16),
        s((1, seq, heads, 192), kv_dtype),
        s((1, seq, heads, 128), kv_dtype),
        s((1,), jnp.int32),
    ]
    if quant:
        shapes += [s((1, seq, heads), jnp.float32)] * 2

    def fn(q, k, v, lengths, *scales):
        kw = {"k_scale": scales[0], "v_scale": scales[1]} if scales else {}
        return flash_prefill_attention(
            q, k, v, lengths=lengths, block_q=512, block_k=512, **kw
        )

    assert "tpu_custom_call" in _compiled_text(fn, *shapes)


STACK = 3         # layers in the stacked cache the decode kernel is handed


def _decode_shapes(s, heads, kv_heads, quant, slots=32, max_len=2048):
    """q, the STACKED k and v, lengths, the layer (and the stacked
    scales): the kernel reads its layer's slab out of the stack."""
    kv_dtype = jnp.int8 if quant else jnp.bfloat16
    shapes = [
        s((slots, heads, D), jnp.bfloat16),
        s((STACK, slots, max_len, kv_heads, D), kv_dtype),
        s((STACK, slots, max_len, kv_heads, D), kv_dtype),
        s((slots,), jnp.int32),
        s((), jnp.int32),
    ]
    if quant:
        shapes += [s((STACK, slots, max_len, kv_heads), jnp.float32)] * 2
    return shapes


@pytest.mark.parametrize(
    "quant,packed", [(False, False), (True, False), (False, True)],
    ids=["bf16", "int8kv", "0.5b-packed"],
)
def test_flash_decode_compiles(one_chip, quant, packed):
    heads, kv_heads = QWEN
    s = functools.partial(_spec, sharding=one_chip)
    shapes = _decode_shapes(s, heads, kv_heads, quant)
    if packed:
        # Qwen-2.5-0.5B at the chat cell's size: 14 heads of 64 over a
        # stack whose row is both kv heads, [24, 128, 2048, 1, 128]
        stack = s((24, 128, 2048, 1, D), jnp.bfloat16)
        shapes = [
            s((128, 14, D // 2), jnp.bfloat16), stack, stack,
            s((128,), jnp.int32), s((), jnp.int32),
        ]

    def fn(q, k, v, lengths, layer, *scales):
        kw = {"k_scale": scales[0], "v_scale": scales[1]} if scales else {}
        return flash_decode_attention(q, k, v, lengths, layer, **kw)

    text = _compiled_text(fn, *shapes)
    assert "tpu_custom_call" in text
    # the kernel's operand is the stack itself: nothing slices a slab out
    assert "dynamic-slice" not in text


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
        # head axes shard over tp; lengths and the layer replicate
        spec = {
            3: P(None, "tp", None), 5: P(None, None, None, "tp", None),
        }.get(len(shape), P())
        return _spec(shape, dtype, NamedSharding(tp_mesh, spec))

    shapes = _decode_shapes(s, heads, kv_heads, quant=False)

    def fn(q, k, v, lengths, layer):
        return flash_decode_attention_sharded(q, k, v, lengths, layer, tp_mesh)

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
            scaled(flash_decode_attention, 5),
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


# ---------------------------------------------------------------------- #
# The dense decode chunk: no copy of a cache slab (ISSUE 27)
# ---------------------------------------------------------------------- #
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%(?P<name>[\w.\-]+) = (?P<type>\(.*?\)|\S+) "
    r"(?P<op>[a-z][a-z0-9\-]*)\((?P<rest>.*)$"
)
_CALLED = re.compile(r"(?:body|condition|to_apply)=%([\w.\-]+)")
# results that name or move a buffer without making one
_NO_BUFFER = ("parameter", "get-tuple-element", "bitcast", "tuple")
# control flow: its results are its body's, looked at there
_CONTROL = ("while", "call")


def _computations(text):
    """{computation: [(name, result type, opcode, rest of the line)]}."""
    found, current = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            current = line.split()[1 if line.startswith("ENTRY") else 0]
            current = current.lstrip("%")
            found[current] = []
            continue
        match = _INSTRUCTION.match(line)
        if match and current is not None:
            found[current].append(match.group("name", "type", "op", "rest"))
    return found


def _loop_instructions(computations):
    """Every instruction that MATERIALISES a result inside the program's
    loops: those of the while bodies and of what they call, nested loops
    included — not the insides of a fusion, which live in registers and
    VMEM and whose only buffer is the fusion's own result."""
    todo = [
        called
        for body in computations.values()
        for _, _, op, rest in body if op == "while"
        for called in _CALLED.findall(rest)
    ]
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in computations:
            continue
        seen.add(name)
        for _, _, op, rest in computations[name]:
            if op in _CONTROL:
                todo += _CALLED.findall(rest)
    return [ins for name in seen for ins in computations[name]]


def _dims(result_type):
    """The dims of every array in a result type (a tuple has several)."""
    return [
        tuple(int(d) for d in dims.split(",") if d)
        for dims in re.findall(r"[a-z]\w*\[([\d,]*)\]", result_type)
    ]


def _compile_decode_chunk(*args, **kwargs):
    """:func:`_lower_decode_chunk`, compiled for the described chip.
    Returns (compiled, the cache's shapes)."""
    lowered, cache = _lower_decode_chunk(*args, **kwargs)
    return lowered.compile(), cache


def _lower_decode_chunk(
    monkeypatch, preset, slots, kv_quant, place, mesh=None, int8_weights=False
):
    """The engine's dense chunk at a preset's published widths and full
    depth, cut to what touches the cache (a 4-step scan of decode_step +
    greedy pick, the cache donated: engine._get_decode), lowered for the
    described chip. ``place(tree, axes)`` gives shapes their shardings.
    Returns (lowered, the cache's shapes)."""
    import langstream_tpu.ops.flash_attention as flash_attention
    from langstream_tpu.ops.rope import rope_frequencies
    from langstream_tpu.providers.jax_local import model as model_lib
    from langstream_tpu.providers.jax_local.quant import init_quantized_params

    # the kernel's gate asks jax.devices(), which is the CPU here: steer it
    # in the test, to what the chip would answer
    monkeypatch.setattr(flash_attention, "on_tpu", lambda: True)
    max_len = 2048
    config = getattr(model_lib.LlamaConfig, preset)(max_len)
    freqs = rope_frequencies(
        config.dims_per_head, config.max_seq_len, config.rope_theta
    )
    init = init_quantized_params if int8_weights else model_lib.init_params
    params = place(
        jax.eval_shape(lambda: init(config, seed=0)),
        None if int8_weights else model_lib.logical_axes(config),
    )
    cache = place(
        jax.eval_shape(
            lambda: model_lib.init_cache(config, slots, max_len, kv_quant=kv_quant)
        ),
        model_lib.cache_logical_axes(kv_quant),
    )

    @functools.partial(jax.jit, donate_argnums=(1,))
    def chunk(params, cache, tokens, lengths, active):
        def body(carry, _):
            cache, tokens, lengths = carry
            cache, logits, _ = model_lib.decode_step(
                config, params, cache, tokens, lengths, freqs, active,
                mesh=mesh,
            )
            picked = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            picked = jnp.where(active, picked, 0)
            lengths = jnp.where(active, lengths + 1, lengths)
            return (cache, picked, lengths), picked

        (cache, _, _), out = jax.lax.scan(
            body, (cache, tokens, lengths), None, length=4
        )
        return cache, out.T

    per_slot = [
        place(jax.ShapeDtypeStruct((slots,), dtype), None)
        for dtype in (jnp.int32, jnp.int32, jnp.bool_)
    ]
    return chunk.lower(params, cache, *per_slot), cache


# sha256 of the lowered text (a kernel's serialised body, which carries
# its call stack's line numbers, blanked) of the two cells' decode chunks
# as this jax lowers them since the decode kernel walks live blocks (its
# operands: the live slots' order and count beside the lengths). A change
# that means to change these programs replaces the hash; one that does
# not has left them alone.
GQA_CHUNKS = {
    "qwen25_7b": (32, True, "80e1edd4be85fca9c31e8fac59f8295e7386062133bdbd63219bf7cbfe5483d2"),
    "qwen25_0_5b": (128, False, "07b892b015dc7548cb8044ff5fc2af51e865138f9e8f03a0a9898c5b78dc3638"),
}


@pytest.mark.parametrize("preset", list(GQA_CHUNKS))
def test_the_gqa_decode_chunks_lower_as_they_did(one_chip, monkeypatch, preset):
    """The flagship's and the 0.5B's decode chunks are byte for byte what
    they were before the layer loop learned to run layers of different
    kinds (debug locations inside a kernel's body aside)."""
    import hashlib

    slots, int8_weights, golden = GQA_CHUNKS[preset]
    if jax.__version__ != "0.9.0":
        pytest.skip("the hashes are of the text jax 0.9.0 lowers")

    def place(tree, axes):
        del axes
        return jax.tree_util.tree_map(
            lambda leaf: _spec(leaf.shape, leaf.dtype, one_chip), tree
        )

    lowered, _ = _lower_decode_chunk(
        monkeypatch, preset, slots, False, place, int8_weights=int8_weights
    )
    text = re.sub(
        r'backend_config = "(?:[^"\\]|\\.)*"', 'backend_config = "<kernel>"',
        lowered.as_text(),
    )
    assert 'kernel_name = "flash_decode"' in text
    assert hashlib.sha256(text.encode()).hexdigest() == golden


HYBRID_SLOTS, HYBRID_LEN = 8, 16384


def _compile_hybrid(monkeypatch, one_chip, program):
    """MiniCPM-SALA whole, in int8, as ``minicpm-sala-int8.longdocs`` runs
    it (32 layers, 8 slots x 16,384): the decode chunk (a 4-step scan of
    decode_step + greedy pick) or a 2,048-token prefill window at an
    offset, the cache donated, for the described chip. Returns (lowered,
    compiled, the cache's shapes)."""
    import langstream_tpu.ops.flash_attention as flash_attention
    from langstream_tpu.providers.jax_local import hybrid_sparse_linear
    from langstream_tpu.providers.jax_local import model as model_lib
    from langstream_tpu.providers.jax_local.quant import init_quantized_params

    monkeypatch.setattr(flash_attention, "on_tpu", lambda: True)
    monkeypatch.setattr(hybrid_sparse_linear, "on_tpu", lambda: True)
    config = model_lib.LlamaConfig.minicpm_sala(HYBRID_LEN)
    freqs = model_lib.model_freqs(config)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda leaf: _spec(leaf.shape, leaf.dtype, one_chip), tree
        )

    params = place(jax.eval_shape(lambda: init_quantized_params(config, seed=0)))
    cache = place(jax.eval_shape(
        lambda: model_lib.init_cache(config, HYBRID_SLOTS, HYBRID_LEN)
    ))
    if program == "decode_chunk":

        @functools.partial(jax.jit, donate_argnums=(1,))
        def run(params, cache, tokens, lengths, active):
            def body(carry, _):
                cache, tokens, lengths, counted = carry
                cache, logits, step = model_lib.decode_step(
                    config, params, cache, tokens, lengths, freqs, active
                )
                picked = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                picked = jnp.where(active, picked, 0)
                lengths = jnp.where(active, lengths + 1, lengths)
                return (cache, picked, lengths, counted + step), picked

            (cache, _, _, counted), out = jax.lax.scan(
                body, (cache, tokens, lengths, model_lib.zero_counters(config)),
                None, length=4,
            )
            return cache, out.T, counted

        args = [
            place(jax.ShapeDtypeStruct((HYBRID_SLOTS,), dtype))
            for dtype in (jnp.int32, jnp.int32, jnp.bool_)
        ]
    else:

        @functools.partial(jax.jit, donate_argnums=(1,))
        def run(params, cache, tokens, lengths, offsets, slot_ids):
            return model_lib.prefill_at_offset(
                config, params, cache, tokens, lengths, offsets, slot_ids, freqs
            )

        args = [
            place(jax.ShapeDtypeStruct(shape, jnp.int32))
            for shape in ((1, 2048), (1,), (1,), (1,))
        ]
    lowered = run.lower(params, cache, *args)
    return lowered, lowered.compile(), cache


@pytest.mark.parametrize("program,kernels", [
    ("decode_chunk", ("lightning_decode", "sparse_block_decode")),
    ("prefill_window", ("lightning_prefill", "sparse_block_prefill")),
])
def test_hybrid_family_compiles_at_the_cells_shapes(
    one_chip, monkeypatch, program, kernels
):
    """``minicpm-sala-int8.longdocs``' two hot programs pass the chip's
    compiler at published widths and full depth and fit beside 9.8 GB of
    int8 weights: the whole hybrid cache (recurrent state, K, V,
    compressed keys) is an aliased output, the program's temp stays under
    1.5 GB (no weight stack sliced into a copy, no layer's K or V slab
    taken out of the stack), and the kernels reach the lowered text under
    the names the trace reduction looks for: one call a run of layers (4
    lightning runs, 5 sparse)."""
    lowered, compiled, cache = _compile_hybrid(monkeypatch, one_chip, program)
    text = lowered.as_text()
    for kernel in kernels:
        assert f'kernel_name = "{kernel}"' in text, kernel
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 9
    memory = compiled.memory_analysis()
    cache_bytes = sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in cache.values()
    )
    assert cache["state"].dtype == jnp.float32
    assert cache["state"].shape == (24, HYBRID_SLOTS, 32, 128, 128)
    assert memory.alias_size_in_bytes >= cache_bytes, memory
    assert memory.temp_size_in_bytes < 1.5 * 2 ** 30, memory
    assert memory.argument_size_in_bytes < 11.5 * 2 ** 30, memory


@pytest.mark.parametrize(
    "preset,slots,kv_quant",
    [("qwen25_7b", 32, False), ("qwen25_7b", 32, True),
     ("qwen25_0_5b", 128, False)],
    ids=["7b-bf16kv", "7b-int8kv", "0.5b-packed"],
)
def test_dense_decode_chunk_copies_no_cache_slab(
    one_chip, monkeypatch, preset, slots, kv_quant
):
    """The two cells' decode chunks, 32 x 2,048 and 128 x 2,048 (and the 7B
    with an int8 cache). Inside the step's loops nothing may produce a
    result of a slab's or the stack's shape but the in-place writes that
    alias the carry: neither half of the copy a scanned xs -> ys cache makes
    (a dynamic-slice of the stack into a slab, a dynamic-update-slice of a
    slab into the stack), nor a re-layout of either. The kernel must be
    handed the stack (one Pallas call a layer) and the program's temp stays
    under one slab: on the 7B, and on the 0.5B, whose two 64-wide kv heads
    lie packed in one 128-lane row (``[24, 128, 2048, 1, 128]``) so that
    the same kernel reads them."""

    def place(tree, _axes):
        return jax.tree_util.tree_map(
            lambda leaf: _spec(leaf.shape, leaf.dtype, one_chip), tree
        )

    compiled, cache = _compile_decode_chunk(
        monkeypatch, preset, slots, kv_quant, place,
        int8_weights=preset == "qwen25_7b",  # as the cells run them
    )
    text = compiled.as_text()

    stack = tuple(cache["k"].shape)           # [L, S, T, KVH, D]
    if preset == "qwen25_0_5b":
        assert stack == (24, 128, 2048, 1, 128)
    guarded = {stack, stack[1:], (1,) + stack[1:]}
    if kv_quant:                              # the scale leaves too
        guarded |= {stack[:-1], stack[1:-1], (1,) + stack[1:-1]}
    stacks = {stack, stack[:-1]}
    computations = _computations(text)
    loop = _loop_instructions(computations)
    assert any(op == "fusion" for _, _, op, _ in loop), "no loop was read"
    types = {
        name: result_type
        for body in computations.values()
        for name, result_type, _, _ in body
    }
    offenders = []
    for name, result_type, op, rest in loop:
        if op in _NO_BUFFER + _CONTROL:
            continue
        made = [dims for dims in _dims(result_type) if dims in guarded]
        if not made:
            continue
        in_place = all(dims in stacks for dims in made) and (
            op in ("scatter", "dynamic-update-slice")
            or (op == "fusion" and '"aliasing_operands":{"lists":[{' in rest)
        )
        # an in-place write takes rows, not a slab: the other half of the
        # copy is a dynamic-update-slice of a slab into the stack
        operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
        fed_a_slab = any(
            dims in guarded - stacks
            for operand in operands for dims in _dims(types.get(operand, ""))
        )
        if not in_place or fed_a_slab:
            offenders.append(f"{name} = {result_type} {op}")
    assert not offenders, offenders

    assert text.count('custom_call_target="tpu_custom_call"') == 1
    slab_bytes = int(np.prod(stack[1:])) * cache["k"].dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < slab_bytes, (temp, slab_bytes)


@pytest.mark.parametrize(
    "preset,slots,kv_quant",
    [("qwen25_7b", 32, False), ("qwen25_7b", 32, True),
     ("qwen25_0_5b", 128, False)],
    ids=["7b-bf16kv", "7b-int8kv", "0.5b-packed"],
)
def test_a_prefill_window_copies_no_cache_stack(
    one_chip, monkeypatch, preset, slots, kv_quant
):
    """One 256-token window of ``prefill_at_offset`` into one slot, as a
    cold prompt between the two cells' buckets is taught since PR 34: the
    rows the attention reads are pinned as the stack lies, so the program
    makes no copy of a K or V stack (unpinned, XLA's einsum laid the 7B's
    whole stacks out position-minor at entry and back at exit: four
    copies of 1.9 GB, a window of 47 ms on the chip where it takes 25)
    and its temp stays under one layer's slab."""
    import langstream_tpu.ops.flash_attention as flash_attention
    from langstream_tpu.providers.jax_local import model as model_lib
    from langstream_tpu.providers.jax_local.quant import init_quantized_params

    monkeypatch.setattr(flash_attention, "on_tpu", lambda: True)
    config = getattr(model_lib.LlamaConfig, preset)(2048)
    freqs = model_lib.model_freqs(config)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda leaf: _spec(leaf.shape, leaf.dtype, one_chip), tree
        )

    init = init_quantized_params if preset == "qwen25_7b" else model_lib.init_params
    params = place(jax.eval_shape(lambda: init(config, seed=0)))
    cache = place(jax.eval_shape(
        lambda: model_lib.init_cache(config, slots, 2048, kv_quant=kv_quant)
    ))

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(params, cache, tokens, lengths, offsets, slot_ids):
        return model_lib.prefill_at_offset(
            config, params, cache, tokens, lengths, offsets, slot_ids, freqs
        )

    args = [
        place(jax.ShapeDtypeStruct(shape, jnp.int32))
        for shape in ((1, 256), (1,), (1,), (1,))
    ]
    compiled = run.lower(params, cache, *args).compile()
    stack = ",".join(map(str, cache["k"].shape))
    copies = re.findall(
        rf"= \w+\[{stack}\]\{{[^}}]*\}} (?:copy|transpose)\(", compiled.as_text()
    )
    assert not copies, copies
    slab_bytes = int(np.prod(cache["k"].shape[1:])) * cache["k"].dtype.itemsize
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < slab_bytes, memory
    cache_bytes = sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in cache.values()
    )
    assert memory.alias_size_in_bytes >= cache_bytes, memory


def test_dense_decode_chunk_tp4_copies_no_cache_slab(tp_mesh, monkeypatch):
    """The same chunk under tp=4 (chip_smoke.py --chips 4): a shard holds
    ONE kv head, and the leaf then lies with T and D as its tiled axes. The
    kernel must be handed that ([rows, T, D]); asked for [rows, T, 1, D] its
    operand is the whole local stack re-tiled, every layer of every step.
    Any materialised copy is a temp, so the program's temp (0.4 MiB) is held
    under one local slab (16 MiB)."""
    from langstream_tpu.parallel.mesh import param_shardings

    def place(tree, axes):
        if axes is None:  # per-slot vectors replicate
            return _spec(tree.shape, tree.dtype, NamedSharding(tp_mesh, P()))
        return jax.tree_util.tree_map(
            lambda leaf, sharding: _spec(leaf.shape, leaf.dtype, sharding),
            tree, param_shardings(axes, tp_mesh),
        )

    compiled, cache = _compile_decode_chunk(
        monkeypatch, "qwen25_7b", 32, False, place, mesh=tp_mesh
    )
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    local = cache["k"].sharding.shard_shape(cache["k"].shape)
    local_slab = int(np.prod(local[1:])) * cache["k"].dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < local_slab, (temp, local_slab)


# ---------------------------------------------------------------------- #
# The latent-attention, routed-experts share at published widths (ISSUE 29)
# ---------------------------------------------------------------------- #
LATENT_SHARE = {
    "preset": "deepseek-v2", "num-layers": 5, "experts-held-first": 0,
    "experts-held": 40, "vocab-size": 25600, "max_seq_len": 4608,
}
LATENT_SLOTS = 64


def _compile_latent(monkeypatch, one_chip, program):
    """One chip's share of DeepSeek-V2 as ``deepseek-v2-ep4.docs`` runs it
    (5 layers, 40 of 160 experts, a quarter of the vocabulary, 64 slots x
    4,608): the decode chunk (a 4-step scan of decode_step + greedy pick)
    or the cold 4,096-token prefill, the cache donated, compiled for the
    described chip. Returns (compiled, the cache's shapes)."""
    import langstream_tpu.ops.flash_attention as flash_attention
    from langstream_tpu.providers.jax_local import model as model_lib

    monkeypatch.setattr(flash_attention, "on_tpu", lambda: True)
    config = model_lib.LlamaConfig.from_dict(dict(LATENT_SHARE))
    freqs = model_lib.model_freqs(config)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda leaf: _spec(leaf.shape, leaf.dtype, one_chip), tree
        )

    params = place(jax.eval_shape(lambda: model_lib.init_params(config, 0)))
    cache = place(jax.eval_shape(
        lambda: model_lib.init_cache(config, LATENT_SLOTS, config.max_seq_len)
    ))

    if program == "decode_chunk":

        @functools.partial(jax.jit, donate_argnums=(1,))
        def run(params, cache, tokens, lengths, active):
            def body(carry, _):
                cache, tokens, lengths, moe = carry
                cache, logits, step_moe = model_lib.decode_step(
                    config, params, cache, tokens, lengths, freqs, active
                )
                picked = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                picked = jnp.where(active, picked, 0)
                lengths = jnp.where(active, lengths + 1, lengths)
                return (cache, picked, lengths, moe + step_moe), picked

            moe = jnp.zeros((3 + config.experts.held,), jnp.int32)
            (cache, _, _, moe), out = jax.lax.scan(
                body, (cache, tokens, lengths, moe), None, length=4
            )
            return cache, out.T, moe

        args = [
            place(jax.ShapeDtypeStruct((LATENT_SLOTS,), dtype))
            for dtype in (jnp.int32, jnp.int32, jnp.bool_)
        ]
    else:

        @functools.partial(jax.jit, donate_argnums=(1,))
        def run(params, cache, tokens, lengths, slot_ids):
            return model_lib.prefill(
                config, params, cache, tokens, lengths, slot_ids, freqs
            )

        args = [
            place(jax.ShapeDtypeStruct(shape, jnp.int32))
            for shape in ((1, 4096), (1,), (1,))
        ]
    return run.lower(params, cache, *args).compile(), cache


@pytest.mark.parametrize("kernel", ["mla_decode", "moe_grouped_matmul"])
def test_latent_family_kernels_carry_their_names(one_chip, monkeypatch, kernel):
    """The two kernels the family adds reach the lowered text under the
    names the trace reduction looks for, at the cell's shapes."""
    import langstream_tpu.ops.flash_attention as flash_attention
    from langstream_tpu.ops.mla_attention import mla_decode_attention
    from langstream_tpu.ops.moe import grouped_matmul

    monkeypatch.setattr(flash_attention, "on_tpu", lambda: True)
    s = functools.partial(_spec, sharding=one_chip)
    if kernel == "mla_decode":
        def fn(q, stack, lengths, layer):
            return mla_decode_attention(
                q, stack, lengths, layer, latent=512, scale=0.1
            )

        shapes = [
            s((LATENT_SLOTS, 128, 640), jnp.bfloat16),
            s((5, LATENT_SLOTS, 4608, 640), jnp.bfloat16),
            s((LATENT_SLOTS,), jnp.int32), s((), jnp.int32),
        ]
    else:
        def fn(x, w, layer, tile_group, num_active, sizes):
            return grouped_matmul(
                x, w, layer, tile_group, num_active, sizes, tile=128
            )

        rows = 4096 * 6 + 40 * 128
        shapes = [
            s((rows, 5120), jnp.bfloat16),
            s((4, 40, 5120, 1536), jnp.bfloat16), s((), jnp.int32),
            s((rows // 128,), jnp.int32), s((), jnp.int32),
            s((40,), jnp.int32),
        ]
    text = jax.jit(fn).lower(*shapes).as_text()
    assert f'kernel_name = "{kernel}"' in text


@pytest.mark.parametrize("program", ["decode_chunk", "prefill_4096"])
def test_latent_share_compiles_at_published_widths(
    one_chip, monkeypatch, program
):
    """``deepseek-v2-ep4.docs``' two hot programs fit the chip and move
    what they should. Both: the program's temp stays under 2 GB beside
    12.2 GB of weights and latents, and the latents' stack is an aliased
    output (the cache is written where it lies). The decode chunk besides:
    inside the step's loops nothing makes a result of the stack's or a
    slab's shape but the row scatter that aliases the carry (no slab
    sliced out for the kernel, no re-layout), no ``[slots, heads, keys]``
    float32 scores exist anywhere (the kernel keeps them in VMEM), and the
    kernels are the two this family names: ``mla_decode`` once for the
    unrolled dense layer and once in the scan's body, the grouped matmul
    three times (gate, up, down)."""
    compiled, cache = _compile_latent(monkeypatch, one_chip, program)
    text = compiled.as_text()
    memory = compiled.memory_analysis()
    stack = tuple(cache["latent"].shape)       # [L, S, T, row]
    stack_bytes = int(np.prod(stack)) * 2
    assert memory.temp_size_in_bytes < 2 * 2 ** 30, memory
    assert memory.alias_size_in_bytes >= stack_bytes, memory
    kernels = text.count('custom_call_target="tpu_custom_call"')
    if program == "prefill_4096":
        # flash_prefill for the dense layer and in the scan, gate/up/down
        assert kernels == 5
        return
    assert kernels == 5
    assert memory.temp_size_in_bytes < stack_bytes // stack[0], memory
    guarded = {stack, stack[1:], (1,) + stack[1:]}
    computations = _computations(text)
    loop = _loop_instructions(computations)
    assert any(op == "fusion" for _, _, op, _ in loop), "no loop was read"
    offenders = []
    for name, result_type, op, rest in loop:
        if op in _NO_BUFFER + _CONTROL:
            continue
        made = [dims for dims in _dims(result_type) if dims in guarded]
        if not made:
            continue
        in_place = all(dims == stack for dims in made) and (
            op in ("scatter", "dynamic-update-slice")
            or (op == "fusion" and '"aliasing_operands":{"lists":[{' in rest)
        )
        if not in_place:
            offenders.append(f"{name} = {result_type} {op}")
    assert not offenders, offenders
    heads, keys = 128, stack[2]
    scores = [
        f"{name} = {result_type}"
        for body in computations.values()
        for name, result_type, _, _ in body
        if result_type.startswith("f32") and any(
            LATENT_SLOTS in dims and heads in dims and keys in dims
            for dims in _dims(result_type)
        )
    ]
    assert not scores, scores


# ---------------------------------------------------------------------- #
# The short-convolution, routed-experts cut at published widths (ISSUE 35)
# ---------------------------------------------------------------------- #
CONV_CUT = {"preset": "lfm2-24b-a2b", "num-layers": 10, "max_seq_len": 4096}
CONV_SLOTS = 64


def _compile_conv(monkeypatch, one_chip, program):
    """The first 10 layers of LFM2-24B-A2B as ``lfm2-24b-a2b.gen`` runs
    them (64 experts held, the whole vocabulary, 64 slots x 4,096): the
    decode chunk (a 4-step scan of decode_step + greedy pick) or a cold
    prefill of ``program`` = (rows, bucket), the cache donated, for the
    described chip. Returns (lowered, compiled, params' and cache's
    shapes)."""
    import langstream_tpu.ops.flash_attention as flash_attention
    from langstream_tpu.providers.jax_local import model as model_lib

    monkeypatch.setattr(flash_attention, "on_tpu", lambda: True)
    config = model_lib.LlamaConfig.from_dict(dict(CONV_CUT))
    freqs = model_lib.model_freqs(config)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda leaf: _spec(leaf.shape, leaf.dtype, one_chip), tree
        )

    params = place(jax.eval_shape(lambda: model_lib.init_params(config, 0)))
    cache = place(jax.eval_shape(
        lambda: model_lib.init_cache(config, CONV_SLOTS, config.max_seq_len)
    ))
    if program == "decode_chunk":

        @functools.partial(jax.jit, donate_argnums=(1,))
        def run(params, cache, tokens, lengths, active):
            def body(carry, _):
                cache, tokens, lengths, moe = carry
                cache, logits, step_moe = model_lib.decode_step(
                    config, params, cache, tokens, lengths, freqs, active
                )
                picked = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                picked = jnp.where(active, picked, 0)
                lengths = jnp.where(active, lengths + 1, lengths)
                return (cache, picked, lengths, moe + step_moe), picked

            (cache, _, _, moe), out = jax.lax.scan(
                body, (cache, tokens, lengths, model_lib.zero_counters(config)),
                None, length=4,
            )
            return cache, out.T, moe

        args = [
            place(jax.ShapeDtypeStruct((CONV_SLOTS,), dtype))
            for dtype in (jnp.int32, jnp.int32, jnp.bool_)
        ]
    else:
        rows, bucket = program

        @functools.partial(jax.jit, donate_argnums=(1,))
        def run(params, cache, tokens, lengths, slot_ids):
            return model_lib.prefill(
                config, params, cache, tokens, lengths, slot_ids, freqs
            )

        args = [
            place(jax.ShapeDtypeStruct(shape, jnp.int32))
            for shape in ((rows, bucket), (rows,), (rows,))
        ]
    lowered = run.lower(params, cache, *args)
    return lowered, lowered.compile(), params, cache


@pytest.mark.parametrize(
    "program", ["decode_chunk", (16, 256), (2, 2048)],
    ids=["decode_chunk", "prefill_16x256", "prefill_2x2048"],
)
def test_conv_family_compiles_at_published_widths(one_chip, monkeypatch, program):
    """``lfm2-24b-a2b.gen``'s hot programs pass the chip's compiler at
    published widths, 10 layers, 64 x 4,096, and fit beside 10.53 GB of
    weights and 1.08 GB of cache (conv state, packed K and V of the two
    attention layers): arguments under 11.7 GB, the whole cache an aliased
    output, temp under 1 GB (the widest prefills' attention runs in blocks
    of queries). The grouped matmul is there three times an expert run
    (gate, up, down: 4 runs), and in the decode chunk ``flash_decode``
    once an attention layer over rows packed two heads to a lane row; no
    copy of an expert stack, nor of K's or V's, is made anywhere."""
    lowered, compiled, params, cache = _compile_conv(monkeypatch, one_chip, program)
    text, built = lowered.as_text(), compiled.as_text()
    decode = program == "decode_chunk"
    assert text.count('kernel_name = "moe_grouped_matmul"') == 12
    assert text.count('kernel_name = "flash_decode"') == (2 if decode else 0)
    assert built.count('custom_call_target="tpu_custom_call"') == (14 if decode else 12)
    assert cache["k"].shape == (2, CONV_SLOTS, 4096, 4, 128)
    assert cache["conv"].shape == (8, CONV_SLOTS, 2, 2048)
    memory = compiled.memory_analysis()
    cache_bytes = sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in cache.values()
    )
    assert memory.alias_size_in_bytes >= cache_bytes, memory
    assert memory.argument_size_in_bytes < 11.7e9, memory
    assert memory.temp_size_in_bytes < (64e6 if decode else 1e9), memory
    for leaf in (params["moe.w_gate"], params["moe.w_down"], cache["k"]):
        stack = ",".join(map(str, leaf.shape))
        copies = re.findall(
            rf"= \w+\[{stack}\]\{{[^}}]*\}} (?:copy|transpose)\(", built
        )
        assert not copies, copies
