"""Llama-family decoder in functional JAX (stacked layers, lax.scan).

Pure-pytree formulation (no flax module state): parameters are a dict of
stacked per-layer arrays so the layer loop is one ``lax.scan`` — one
compilation for 8 or 80 layers, and the scan carry keeps activations in
registers/VMEM instead of re-reading HBM per layer.

Architecture: pre-norm transformer with RMSNorm, RoPE, GQA attention, and
SwiGLU MLP — Llama 2/3 family (config covers TinyLlama through 70B).
Weights import from a local HuggingFace checkpoint (torch state dict →
stacked jax arrays), or random-init for benchmarks.

Every program of every family runs the same layer loop
(:func:`_run_layers`, the only scan over layers) around the same block
body (:func:`_block`). What a program brings is its prelude (positions
and masks), its head, and its ``attend``: the attention, which owns the
cache (projects, ropes, writes, attends). An attention kind is a set of
attends and a cache layout: GQA's are in this file, the latent kind's in
``latent_moe.py``, the two kinds a hybrid config mixes layer by layer
(``config.mixers``: linear attention with a recurrent state, block-sparse
GQA) in ``hybrid_sparse_linear.py``, the gated short convolution that a
config with ``short_conv`` mixes with GQA layers (q and k normed a head)
in ``short_conv_gqa.py``; :func:`_kinds` says which a config has and
:func:`_layers_of` cuts its layers into runs of one mixer kind and one
feed-forward kind (dense, or routed experts: the two are independent).
Every serving program returns ``(cache, logits, counters)``: the routed
experts', the block selection's, or None (an empty pytree).

Logical sharding axes per parameter feed the mesh rules in
``langstream_tpu.parallel.mesh`` (tp shards heads/mlp, fsdp shards embed).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint
import numpy as np

from langstream_tpu.ops.attention import (
    chunk_attention,
    chunk_attention_quant,
    decode_attention,
    decode_attention_quant,
    paged_chunk_attention,
    paged_chunk_attention_quant,
    paged_decode_attention,
    paged_decode_attention_quant,
    paged_write_rows,
    prefill_attention,
    quantize_kv,
)
from langstream_tpu.ops.flash_attention import (
    attention_blocks,
    flash_prefill_attention,
    use_flash,
)
from langstream_tpu.ops.moe import (
    group_limited_routing,
    moe_mlp,
    moe_mlp_held,
    sigmoid_bias_routing,
)
from langstream_tpu.ops.norms import rms_norm
from langstream_tpu.ops.rope import apply_rope, rope_frequencies
from langstream_tpu.parallel.mesh import L
from langstream_tpu.ops.block_sparse_attention import Selection
from langstream_tpu.providers.jax_local import hybrid_sparse_linear as hybrid
from langstream_tpu.providers.jax_local import latent_moe
from langstream_tpu.providers.jax_local import short_conv_gqa as short_conv
from langstream_tpu.providers.jax_local.quant import qeinsum


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """Latent (MLA) attention: low-rank query and key/value projections
    with their own RMSNorms, a rotary part shared by all heads; the cache
    holds ``kv_lora_rank + qk_rope_head_dim`` values a token a layer."""
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int


@dataclasses.dataclass(frozen=True)
class RoutedExperts:
    """A router over ``routed`` experts of which THIS chip holds
    ``[held_first, held_first + held)`` and computes only where routed,
    ``shared`` experts every token meets (0: none), after ``leading_dense``
    layers with a plain SwiGLU of the config's ``intermediate_size``.
    ``routing`` names the rule (``ops/moe.py``): ``group_limited`` (softmax
    scores, the ``groups_kept`` best of ``groups`` groups, weights not
    renormalised) or ``sigmoid_bias`` (sigmoid scores, the selection over
    score + a bias vector, weights from the unbiased scores, renormalised
    where ``renormalise``)."""
    routed: int
    held_first: int
    held: int
    intermediate_size: int
    per_token: int
    shared: int
    leading_dense: int
    groups: int
    groups_kept: int
    scaling_factor: float
    routing: str = "group_limited"
    renormalise: bool = False


@dataclasses.dataclass(frozen=True)
class HybridMixers:
    """What the mixers of a config with per-layer ``mixers`` need beside
    the GQA sizes (which its sparse layers take): the linear-attention
    layers' heads, and the block selection of the sparse ones
    (hybrid_sparse_linear.py)."""
    lightning_heads: int
    lightning_head_dim: int
    selection: Selection = Selection()


@dataclasses.dataclass(frozen=True)
class ShortConv:
    """The gated short convolution of a config whose per-layer ``mixers``
    are ``conv`` and ``attention`` (short_conv_gqa.py): a causal depthwise
    filter of ``taps`` taps a channel, so ``taps - 1`` columns of state a
    slot a conv layer."""
    taps: int = 3


def zero_counters(config: "LlamaConfig"):
    """The counters a program returns with its outputs, as a scan over a
    chunk's steps starts them, by what the layers hold: the routed
    experts' (int32 ``[3 + held]``), the block selection's (int32
    ``[3]``), or None, an empty pytree, for a config with neither."""
    if config.experts is not None:
        return jnp.zeros((3 + config.experts.held,), jnp.int32)
    if config.hybrid is not None:
        return hybrid.zero_counters()
    return None


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 22
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: Optional[int] = None
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    tie_embeddings: bool = False
    # Mixture-of-experts (Mixtral family). 0 = dense SwiGLU MLP.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    capacity_factor: float = 2.0
    # Gemma-2 family extensions — every default is the Llama behavior.
    attn_logit_softcap: Optional[float] = None   # cap·tanh(s/cap) on scores
    final_logit_softcap: Optional[float] = None  # same on output logits
    query_pre_attn_scalar: Optional[float] = None  # attn scale base (None → head_dim)
    sliding_window: int = 0       # 0 = full attention on every layer;
                                  # >0 = Gemma-2 alternating pattern
                                  # (even layers slide, odd layers full)
    norm_plus_one: bool = False   # RMSNorm applies (1 + w) (zero-centered w)
    post_norms: bool = False      # sandwich norms after attn + mlp blocks
    scale_embedding: bool = False  # x *= sqrt(hidden) after the lookup
    act: str = "silu"             # MLP gate activation: silu | gelu_tanh
    qkv_bias: bool = False        # q/k/v projection biases (Qwen-2 family)
    # RoPE frequency scaling as a HASHABLE tuple ("llama3", factor,
    # low_freq_factor, high_freq_factor, original_max_positions) — the
    # Llama-3.1/3.2 long-context recipe (ops/rope.py). None = plain.
    rope_scaling: Optional[Tuple] = None
    # Latent attention (latent_moe.py) comes with routed experts; routed
    # experts come behind latent attention or behind the mixers of the
    # short-convolution family.
    mla: Optional[LatentAttention] = None
    experts: Optional[RoutedExperts] = None
    # The mixer of every layer by kind, with what the kinds need: "sparse"
    # | "lightning" with ``hybrid`` (hybrid_sparse_linear.py), "conv" |
    # "attention" with ``short_conv`` (short_conv_gqa.py); all None where
    # every layer is GQA, or latent with ``mla``.
    mixers: Optional[Tuple[str, ...]] = None
    hybrid: Optional[HybridMixers] = None
    short_conv: Optional[ShortConv] = None
    # muP scalings (MiniCPM): x *= embedding_scale after the lookup, both
    # residual branches times residual_scale, the final hidden state over
    # logit_divisor before the head. None leaves the program as it is.
    embedding_scale: Optional[float] = None
    residual_scale: Optional[float] = None
    logit_divisor: Optional[float] = None
    dtype: Any = jnp.bfloat16
    # Pallas flash prefill (TPU only; tp-sharded meshes route it through
    # shard_map over the head axis — see _prefill_attn).
    use_flash: bool = True
    # test hook: force the kernel in Pallas interpret mode (CPU parity
    # tests of the flash path; never set in production configs)
    flash_interpret: bool = False

    @property
    def dims_per_head(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @classmethod
    def llama3_8b(cls, max_seq_len: int = 8192) -> "LlamaConfig":
        return cls(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_heads=32, num_kv_heads=8,
            rope_theta=500000.0, max_seq_len=max_seq_len,
        )

    @classmethod
    def llama3_70b(cls, max_seq_len: int = 8192) -> "LlamaConfig":
        return cls(
            vocab_size=128256, hidden_size=8192, intermediate_size=28672,
            num_layers=80, num_heads=64, num_kv_heads=8,
            rope_theta=500000.0, max_seq_len=max_seq_len,
        )

    @classmethod
    def llama3_1b(cls, max_seq_len: int = 8192) -> "LlamaConfig":
        # Llama-3.2-1B shape (incl. its 32x llama3 rope scaling)
        return cls(
            vocab_size=128256, hidden_size=2048, intermediate_size=8192,
            num_layers=16, num_heads=32, num_kv_heads=8, head_dim=64,
            rope_theta=500000.0, max_seq_len=max_seq_len, tie_embeddings=True,
            rope_scaling=("llama3", 32.0, 1.0, 4.0, 8192.0),
        )

    @classmethod
    def llama31_8b(cls, max_seq_len: int = 8192) -> "LlamaConfig":
        """Llama-3.1-8B: the 3.0 shape + llama3 rope scaling (the
        128k-context recipe)."""
        return dataclasses.replace(
            cls.llama3_8b(max_seq_len),
            rope_scaling=("llama3", 8.0, 1.0, 4.0, 8192.0),
        )

    @classmethod
    def mixtral_8x7b(cls, max_seq_len: int = 8192) -> "LlamaConfig":
        return cls(
            vocab_size=32000, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_heads=32, num_kv_heads=8,
            rope_theta=1e6, max_seq_len=max_seq_len,
            num_experts=8, num_experts_per_tok=2,
        )

    @classmethod
    def gemma2_2b(cls, max_seq_len: int = 8192) -> "LlamaConfig":
        """Gemma-2-2B (HF google/gemma-2-2b): GeGLU, sandwich norms,
        zero-centered RMSNorm, logit softcapping, alternating sliding
        window, scaled embeddings, tied head."""
        return cls(
            vocab_size=256000, hidden_size=2304, intermediate_size=9216,
            num_layers=26, num_heads=8, num_kv_heads=4, head_dim=256,
            rope_theta=10000.0, max_seq_len=max_seq_len, norm_eps=1e-6,
            tie_embeddings=True, attn_logit_softcap=50.0,
            final_logit_softcap=30.0, query_pre_attn_scalar=256.0,
            sliding_window=4096, norm_plus_one=True, post_norms=True,
            scale_embedding=True, act="gelu_tanh",
        )

    @classmethod
    def gemma2_9b(cls, max_seq_len: int = 8192) -> "LlamaConfig":
        return dataclasses.replace(
            cls.gemma2_2b(max_seq_len), hidden_size=3584,
            intermediate_size=14336, num_layers=42, num_heads=16,
            num_kv_heads=8, head_dim=256,
        )

    @classmethod
    def tiny_gemma2(cls, max_seq_len: int = 256) -> "LlamaConfig":
        """Test-size Gemma-2 shape: every family mechanism on, window
        smaller than typical test prompts so sliding layers actually
        mask."""
        return cls(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            rope_theta=10000.0, max_seq_len=max_seq_len, norm_eps=1e-6,
            tie_embeddings=True, attn_logit_softcap=50.0,
            final_logit_softcap=30.0, query_pre_attn_scalar=16.0,
            sliding_window=8, norm_plus_one=True, post_norms=True,
            scale_embedding=True, act="gelu_tanh", dtype=jnp.float32,
        )

    @classmethod
    def qwen25_7b(cls, max_seq_len: int = 8192) -> "LlamaConfig":
        """Qwen-2.5-7B (HF Qwen/Qwen2.5-7B): Llama architecture plus
        q/k/v projection biases."""
        return cls(
            vocab_size=152064, hidden_size=3584, intermediate_size=18944,
            num_layers=28, num_heads=28, num_kv_heads=4, head_dim=128,
            rope_theta=1e6, max_seq_len=max_seq_len, norm_eps=1e-6,
            qkv_bias=True,
        )

    @classmethod
    def qwen25_0_5b(cls, max_seq_len: int = 8192) -> "LlamaConfig":
        return cls(
            vocab_size=151936, hidden_size=896, intermediate_size=4864,
            num_layers=24, num_heads=14, num_kv_heads=2, head_dim=64,
            rope_theta=1e6, max_seq_len=max_seq_len, norm_eps=1e-6,
            qkv_bias=True, tie_embeddings=True,
        )

    @classmethod
    def deepseek_v2(cls, max_seq_len: int = 8192) -> "LlamaConfig":
        """DeepSeek-V2 (HF deepseek-ai/DeepSeek-V2): latent attention,
        160 routed experts in 8 groups (3 groups and 6 experts a token,
        weights x16, not renormalised), 2 shared experts, one leading
        dense layer, YaRN x40 over 4,096. All 160 experts held: a chip's
        share is set beside the preset (``experts-held-first``,
        ``experts-held``, ``num-layers``, ``vocab-size``)."""
        return cls(
            vocab_size=102400, hidden_size=5120, intermediate_size=12288,
            num_layers=60, num_heads=128, num_kv_heads=128, head_dim=192,
            rope_theta=10000.0, max_seq_len=max_seq_len, norm_eps=1e-6,
            rope_scaling=("yarn", 40.0, 32.0, 1.0, 0.707, 0.707, 4096.0),
            mla=LatentAttention(
                q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                qk_rope_head_dim=64, v_head_dim=128,
            ),
            experts=RoutedExperts(
                routed=160, held_first=0, held=160, intermediate_size=1536,
                per_token=6, shared=2, leading_dense=1, groups=8,
                groups_kept=3, scaling_factor=16.0,
            ),
        )

    @classmethod
    def tiny_deepseek_v2(cls, max_seq_len: int = 256) -> "LlamaConfig":
        """Test-size shape of the latent/routed family: 4 heads of 16 + 8
        / 16, latent 32, 8 experts in 4 groups (2 groups and 3 experts a
        token), 1 dense + 2 expert layers."""
        return cls(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=3, num_heads=4, num_kv_heads=4, head_dim=24,
            rope_theta=10000.0, max_seq_len=max_seq_len, norm_eps=1e-6,
            rope_scaling=("yarn", 40.0, 32.0, 1.0, 0.707, 0.707, 64.0),
            mla=LatentAttention(
                q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16,
            ),
            experts=RoutedExperts(
                routed=8, held_first=0, held=8, intermediate_size=32,
                per_token=3, shared=2, leading_dense=1, groups=4,
                groups_kept=2, scaling_factor=4.0,
            ),
            dtype=jnp.float32,
        )

    @classmethod
    def minicpm_sala(cls, max_seq_len: int = 16384) -> "LlamaConfig":
        """MiniCPM-SALA (HF openbmb/MiniCPM-SALA): 24 lightning
        linear-attention layers beside 8 block-sparse GQA layers (at 0,
        9, 16, 17, 22, 29, 30, 31), q/k norms, output gates, no rotation
        in the sparse layers, MiniCPM's muP scalings. The selection's
        sizes are MiniCPM4's ``sparse_config`` (the config carries none)."""
        sparse_at = (0, 9, 16, 17, 22, 29, 30, 31)
        return cls(
            vocab_size=73448, hidden_size=4096, intermediate_size=16384,
            num_layers=32, num_heads=32, num_kv_heads=2, head_dim=128,
            rope_theta=10000.0, max_seq_len=max_seq_len, norm_eps=1e-6,
            mixers=tuple(
                "sparse" if i in sparse_at else "lightning" for i in range(32)
            ),
            hybrid=HybridMixers(lightning_heads=32, lightning_head_dim=128),
            embedding_scale=12.0, residual_scale=1.4 / math.sqrt(32),
            logit_divisor=4096 / 256,
        )

    @classmethod
    def tiny_hybrid(cls, max_seq_len: int = 256) -> "LlamaConfig":
        """Test-size shape of the hybrid family: 5 layers (sparse at 0, 3
        and 4), 4 heads of 16 over 2 kv heads, 4 lightning heads; the
        selection at ``dense_len`` 32 with blocks of 8, the 2 best beside
        a window of 16."""
        return cls(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=5, num_heads=4, num_kv_heads=2, head_dim=16,
            rope_theta=10000.0, max_seq_len=max_seq_len, norm_eps=1e-6,
            mixers=("sparse", "lightning", "lightning", "sparse", "sparse"),
            hybrid=HybridMixers(
                lightning_heads=4, lightning_head_dim=16,
                selection=Selection(
                    kernel_size=4, kernel_stride=2, block_size=8, topk=2,
                    init_blocks=1, window_size=16, dense_len=32,
                ),
            ),
            embedding_scale=12.0, residual_scale=1.4 / math.sqrt(5),
            logit_divisor=4.0, dtype=jnp.float32,
        )

    @classmethod
    def lfm2_24b_a2b(cls, max_seq_len: int = 4096) -> "LlamaConfig":
        """LFM2-24B-A2B (HF LiquidAI/LFM2-24B-A2B): 30 gated
        short-convolution layers (3 taps) beside 10 GQA layers (32/8 heads
        of 64, q and k normed a head) at 2, 6, ..., 38; two leading dense
        layers of width 11,776, then 64 routed experts of width 1,536
        (sigmoid scores, a selection bias, 4 a token, renormalised), none
        shared; tied head. All 40 layers: a cut is set beside the preset
        (``num-layers`` cuts ``mixers`` with the depth)."""
        return cls(
            vocab_size=65536, hidden_size=2048, intermediate_size=11776,
            num_layers=40, num_heads=32, num_kv_heads=8, head_dim=64,
            rope_theta=1e6, max_seq_len=max_seq_len, norm_eps=1e-5,
            tie_embeddings=True,
            mixers=tuple(
                "attention" if i % 4 == 2 else "conv" for i in range(40)
            ),
            short_conv=ShortConv(taps=3),
            experts=RoutedExperts(
                routed=64, held_first=0, held=64, intermediate_size=1536,
                per_token=4, shared=0, leading_dense=2, groups=1,
                groups_kept=1, scaling_factor=1.0, routing="sigmoid_bias",
                renormalise=True,
            ),
        )

    @classmethod
    def tiny_conv_moe(cls, max_seq_len: int = 256) -> "LlamaConfig":
        """Test-size shape of the short-convolution family: 6 layers (conv
        conv | attention conv conv attention), 4 heads of 16 over 2 kv
        heads, 2 leading dense layers, then 8 routed experts (3 a token):
        all three combinations of mixer and feed-forward."""
        return cls(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=6, num_heads=4, num_kv_heads=2, head_dim=16,
            rope_theta=10000.0, max_seq_len=max_seq_len, norm_eps=1e-5,
            tie_embeddings=True,
            mixers=("conv", "conv", "attention", "conv", "conv", "attention"),
            short_conv=ShortConv(taps=3),
            experts=RoutedExperts(
                routed=8, held_first=0, held=8, intermediate_size=32,
                per_token=3, shared=0, leading_dense=2, groups=1,
                groups_kept=1, scaling_factor=1.0, routing="sigmoid_bias",
                renormalise=True,
            ),
            dtype=jnp.float32,
        )

    @classmethod
    def tiny_qwen2(cls, max_seq_len: int = 256) -> "LlamaConfig":
        """Test-size Qwen-2 shape (qkv biases on)."""
        return dataclasses.replace(cls.tiny(max_seq_len), qkv_bias=True)

    @classmethod
    def tiny(cls, max_seq_len: int = 256) -> "LlamaConfig":
        """Test-size config for CPU runs."""
        return cls(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2,
            max_seq_len=max_seq_len, dtype=jnp.float32,
        )

    @classmethod
    def tiny_moe(cls, max_seq_len: int = 256) -> "LlamaConfig":
        """Test-size MoE config for CPU runs."""
        return dataclasses.replace(
            cls.tiny(max_seq_len), num_experts=4, num_experts_per_tok=2
        )

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "LlamaConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        clean = {k.replace("-", "_"): v for k, v in config.items()}
        if isinstance(clean.get("dtype"), str):
            # checkpoints serialize the dtype by name ("bfloat16")
            clean["dtype"] = jnp.dtype(clean["dtype"])
        if clean.get("rope_scaling") is not None:
            clean["rope_scaling"] = normalize_rope_scaling(
                clean["rope_scaling"]
            )
        presets = {
            "llama-3-8b": cls.llama3_8b, "llama-3-70b": cls.llama3_70b,
            "llama-3.1-8b": cls.llama31_8b,
            "llama-3-1b": cls.llama3_1b, "tiny": cls.tiny,
            "mixtral-8x7b": cls.mixtral_8x7b, "tiny-moe": cls.tiny_moe,
            "gemma-2-2b": cls.gemma2_2b, "gemma-2-9b": cls.gemma2_9b,
            "tiny-gemma2": cls.tiny_gemma2,
            "qwen-2.5-7b": cls.qwen25_7b, "qwen-2.5-0.5b": cls.qwen25_0_5b,
            "tiny-qwen2": cls.tiny_qwen2,
            "deepseek-v2": cls.deepseek_v2,
            "tiny-deepseek-v2": cls.tiny_deepseek_v2,
            "minicpm-sala": cls.minicpm_sala, "tiny-hybrid": cls.tiny_hybrid,
            "lfm2-24b-a2b": cls.lfm2_24b_a2b,
            "tiny-conv-moe": cls.tiny_conv_moe,
        }
        preset = clean.pop("preset", None)
        # a chip's share of the routed experts, beside the preset
        share = {
            name: int(clean.pop("experts_" + name))
            for name in ("held_first", "held")
            if clean.get("experts_" + name) not in (None, "")
        }
        for name in ("num_layers", "vocab_size"):
            # placeholders (``${globals.num-layers}``) arrive as strings
            if isinstance(clean.get(name), str):
                clean[name] = int(clean[name])
        for name, record in (("mla", LatentAttention), ("experts", RoutedExperts)):
            if isinstance(clean.get(name), dict):
                clean[name] = record(**{
                    k.replace("-", "_"): v for k, v in clean[name].items()
                })
        if isinstance(clean.get("hybrid"), dict):
            sizes = {
                k.replace("-", "_"): v for k, v in clean["hybrid"].items()
            }
            if isinstance(sizes.get("selection"), dict):
                sizes["selection"] = Selection(**{
                    k.replace("-", "_"): v
                    for k, v in sizes["selection"].items()
                })
            clean["hybrid"] = HybridMixers(**sizes)
        if isinstance(clean.get("short_conv"), dict):
            clean["short_conv"] = ShortConv(**{
                k.replace("-", "_"): v for k, v in clean["short_conv"].items()
            })
        if clean.get("mixers") is not None:
            clean["mixers"] = tuple(clean["mixers"])
        if preset:
            config = presets[preset]()
            if (
                config.mixers is not None and "mixers" not in clean
                and clean.get("num_layers", config.num_layers) < config.num_layers
            ):
                # a cut in depth keeps the first layers' kinds
                clean["mixers"] = config.mixers[: clean["num_layers"]]
            config = dataclasses.replace(
                config, **{k: v for k, v in clean.items() if k in known},
            )
        else:
            config = cls(**{k: v for k, v in clean.items() if k in known})
        if share:
            if config.experts is None:
                raise ValueError(
                    "experts-held-first / experts-held need a model with "
                    "routed experts"
                )
            config = dataclasses.replace(
                config, experts=dataclasses.replace(config.experts, **share)
            )
        if (config.mla is not None and config.experts is None) or (
            config.experts is not None
            and config.mla is None and config.short_conv is None
        ):
            raise ValueError(
                "latent attention and routed experts come together "
                "(latent_moe.py): set both `mla` and `experts`, or neither; "
                "routed experts without it come behind per-layer `mixers` "
                "with `short_conv` (short_conv_gqa.py)"
            )
        sizes = [
            name for name in ("hybrid", "short_conv")
            if getattr(config, name) is not None
        ]
        if len(sizes) != (config.mixers is not None):
            raise ValueError(
                "per-layer mixers come with their sizes: set `mixers` with "
                "`hybrid` (hybrid_sparse_linear.py) or with `short_conv` "
                f"(short_conv_gqa.py), or none of them (got mixers with {sizes})"
            )
        if config.mixers is not None:
            kinds = hybrid.KINDS if config.hybrid is not None else short_conv.KINDS
            if (
                len(config.mixers) != config.num_layers
                or set(config.mixers) - set(kinds)
                or config.mla is not None
                or config.hybrid is not None
                and config.hybrid.lightning_head_dim != config.dims_per_head
            ):
                raise ValueError(
                    f"inconsistent mixers for {config.num_layers} layers: "
                    f"{config.mixers} (kinds: {kinds}; no latent "
                    "attention; one head dim for both kinds)"
                )
            if config.hybrid is not None:
                config.hybrid.selection.check()
        experts = config.experts
        if experts is not None and not (
            0 <= experts.held_first
            and 0 < experts.held
            and experts.held_first + experts.held <= experts.routed
            and experts.routed % experts.groups == 0
            and 0 < experts.leading_dense < config.num_layers
            and experts.routing in ("group_limited", "sigmoid_bias")
        ):
            raise ValueError(f"inconsistent routed experts: {experts}")
        return config

    def num_params(self) -> int:
        if _family_of(self) is not None:
            return _family_of(self).num_params(self)
        head_dim = self.dims_per_head
        attn = self.hidden_size * head_dim * (2 * self.num_heads + 2 * self.num_kv_heads)
        mlp = 3 * self.hidden_size * self.intermediate_size
        if self.num_experts:
            mlp = mlp * self.num_experts + self.hidden_size * self.num_experts
        per_layer = attn + mlp + 2 * self.hidden_size
        emb = self.vocab_size * self.hidden_size * (1 if self.tie_embeddings else 2)
        return self.num_layers * per_layer + emb + self.hidden_size


def init_params(config: LlamaConfig, seed: int = 0) -> Dict[str, jnp.ndarray]:
    """Random-init (scaled normal) parameter pytree with stacked layers."""
    if _family_of(config) is not None:
        return _family_of(config).init_params(config, seed)
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, 10)
    h, f, v = config.hidden_size, config.intermediate_size, config.vocab_size
    nh, nkv, hd = config.num_heads, config.num_kv_heads, config.dims_per_head
    layers = config.num_layers
    dtype = config.dtype

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)

    scale = 1.0 / math.sqrt(h)
    if config.num_experts:
        e = config.num_experts
        mlp_params = {
            "w_gate": normal(keys[5], (layers, e, h, f), scale),
            "w_up": normal(keys[6], (layers, e, h, f), scale),
            "w_down": normal(keys[7], (layers, e, f, h), scale / math.sqrt(2 * layers)),
            "router": normal(keys[9], (layers, h, e), scale),
        }
    else:
        mlp_params = {
            "w_gate": normal(keys[5], (layers, h, f), scale),
            "w_up": normal(keys[6], (layers, h, f), scale),
            "w_down": normal(keys[7], (layers, f, h), scale / math.sqrt(2 * layers)),
        }
    # zero-centered convention (norm applies 1 + w): identity weight is 0
    norm_fill = 0.0 if config.norm_plus_one else 1.0

    def norm_init(shape):
        return jnp.full(shape, norm_fill, dtype=jnp.float32)

    params = {
        "embedding": normal(keys[0], (v, h), 1.0 / math.sqrt(h)),
        "wq": normal(keys[1], (layers, h, nh * hd), scale),
        "wk": normal(keys[2], (layers, h, nkv * hd), scale),
        "wv": normal(keys[3], (layers, h, nkv * hd), scale),
        "wo": normal(keys[4], (layers, nh * hd, h), scale / math.sqrt(2 * layers)),
        **mlp_params,
        "attn_norm": norm_init((layers, h)),
        "mlp_norm": norm_init((layers, h)),
        "final_norm": norm_init((h,)),
    }
    if config.post_norms:
        params["post_attn_norm"] = norm_init((layers, h))
        params["post_mlp_norm"] = norm_init((layers, h))
    if config.qkv_bias:
        params["bq"] = jnp.zeros((layers, nh * hd), dtype=jnp.float32)
        params["bk"] = jnp.zeros((layers, nkv * hd), dtype=jnp.float32)
        params["bv"] = jnp.zeros((layers, nkv * hd), dtype=jnp.float32)
    if not config.tie_embeddings:
        params["lm_head"] = normal(keys[8], (h, v), scale)
    return params


def logical_axes(config: LlamaConfig) -> Dict[str, Any]:
    """Logical sharding axes per parameter (fed to parallel.mesh rules)."""
    if _family_of(config) is not None:
        return _family_of(config).logical_axes(config)
    if config.num_experts:
        mlp_axes = {
            "w_gate": L("layers", "expert", "embed", "mlp"),
            "w_up": L("layers", "expert", "embed", "mlp"),
            "w_down": L("layers", "expert", "mlp", "embed"),
            "router": L("layers", "embed", None),
        }
    else:
        mlp_axes = {
            "w_gate": L("layers", "embed", "mlp"),
            "w_up": L("layers", "embed", "mlp"),
            "w_down": L("layers", "mlp", "embed"),
        }
    axes = {
        "embedding": L("vocab", "embed"),
        "wq": L("layers", "embed", "heads"),
        "wk": L("layers", "embed", "heads"),
        "wv": L("layers", "embed", "heads"),
        "wo": L("layers", "heads", "embed"),
        **mlp_axes,
        "attn_norm": L("layers", None),
        "mlp_norm": L("layers", None),
        "final_norm": L(None),
    }
    if config.post_norms:
        axes["post_attn_norm"] = L("layers", None)
        axes["post_mlp_norm"] = L("layers", None)
    if config.qkv_bias:
        axes["bq"] = L("layers", "heads")
        axes["bk"] = L("layers", "heads")
        axes["bv"] = L("layers", "heads")
    if not config.tie_embeddings:
        axes["lm_head"] = L("embed", "vocab")
    return axes


def flash_decode_pack(
    config: LlamaConfig, max_len: int, kv_quant: bool = False, tp: int = 1
) -> Optional[int]:
    """Does the ``flash_decode`` kernel read this config's dense cache,
    and how: the kv heads one cache row holds (1: a row is a head; ``128
    // head_dim`` where narrower heads are PACKED into 128-lane rows), or
    None where XLA's attention reads it. The one answer ``init_cache``
    lays the value leaves out by and the decode step's write and read
    follow (:func:`_decode_flash_path`): a leaf that lies otherwise than
    its reader wants is re-laid-out whole at every chunk's entry. Shape
    requirements bind even under the ``flash_interpret`` test hook; the
    backend/length policy only applies outside it."""
    from langstream_tpu.ops.decode_kernel import (
        decode_shapes_ok,
        kv_pack,
        use_flash_decode,
    )

    dim, kv_heads = config.dims_per_head, config.num_kv_heads
    shape = (max_len, dim, config.num_heads, kv_heads, kv_quant, tp)
    if config.use_flash and (
        use_flash_decode(*shape)
        or (config.flash_interpret and decode_shapes_ok(*shape))
    ):
        return kv_pack(dim, kv_heads, kv_quant, tp)
    return None


def init_cache(
    config: LlamaConfig,
    batch: int,
    max_len: Optional[int] = None,
    kv_quant: bool = False,
    tp: int = 1,
) -> Dict[str, jnp.ndarray]:
    """KV cache: [layers, batch, max_len, kv_heads, head_dim]; where the
    decode kernel reads narrow heads packed (:func:`flash_decode_pack`,
    which ``tp``, the mesh's, is for) the value leaves are ``[layers,
    batch, max_len, kv_heads / pack, pack * head_dim]``: the same bytes,
    lying as 128-lane rows. Programs move rows in and out of such a leaf
    through :func:`_pack_kv` and :func:`_unpack_kv`.

    ``kv_quant`` stores int8 values plus per-(position, kv-head) f32
    scales — halves the cache's HBM bytes on the weights+cache-bound
    decode path (scales are 1/32 of the int8 bytes at head_dim 128).
    The forward paths detect quantization by the ``k_scale`` key.

    The latent family's cache is one leaf of latents instead
    (``latent_moe.init_cache``), the hybrid family's a recurrent state
    beside K, V and compressed keys (``hybrid_sparse_linear.init_cache``);
    the short-convolution family's is K and V as here (:func:`kv_leaves`)
    for its ATTENTION layers only, beside the conv state
    (``short_conv_gqa.init_cache``)."""
    max_len = max_len or config.max_seq_len
    family = _family_of(config)
    if family is not None:
        if kv_quant:
            raise ValueError(
                f"the {_kinds(config).attention} cache has no int8 form"
            )
        return family.init_cache(config, batch, max_len)
    return kv_leaves(config, config.num_layers, batch, max_len, kv_quant, tp)


def kv_leaves(
    config: LlamaConfig, layers: int, batch: int, max_len: int,
    kv_quant: bool = False, tp: int = 1,
) -> Dict[str, jnp.ndarray]:
    """K and V (and their scales) of ``layers`` GQA layers, as
    :func:`init_cache` describes them: every family whose attention
    layers are GQA's lays them so."""
    pack = flash_decode_pack(config, max_len, kv_quant, tp) or 1
    shape = (
        layers, batch, max_len, config.num_kv_heads // pack,
        config.dims_per_head * pack,
    )
    if kv_quant:
        return {
            "k": jnp.zeros(shape, dtype=jnp.int8),
            "v": jnp.zeros(shape, dtype=jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], dtype=jnp.float32),
            "v_scale": jnp.zeros(shape[:-1], dtype=jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, dtype=config.dtype),
        "v": jnp.zeros(shape, dtype=config.dtype),
    }


def _pack_kv(rows: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    """K or V rows ``[..., kv_heads, head_dim]`` in the row form of the
    dense cache leaf (or slab) they go into: ``[..., kv_heads / pack,
    pack * head_dim]`` where :func:`init_cache` packed it, a reshape of
    the last two axes (row-major: kv head g is lanes ``[g % pack *
    head_dim, ...)`` of packed row ``g // pack``); else as they are."""
    if rows.shape[-2:] == leaf.shape[-2:]:
        return rows
    return rows.reshape(rows.shape[:-2] + leaf.shape[-2:])


def _unpack_kv(config: LlamaConfig, rows: jnp.ndarray) -> jnp.ndarray:
    """Rows read out of a dense value leaf (a slab, a slot's rows) as
    heads ``[..., kv_heads, head_dim]``: :func:`_pack_kv` the other way,
    for XLA's attention. Packed rows are pinned row-major first, as the
    leaf lies: XLA's einsums want the position axis minor-most, and
    unpinned that wish runs back through the slice to the stack, which
    is then copied whole at the program's entry and exit (1.5 GB of temp
    in the 0.5B's warm prefill); pinned, the rows that were read are
    transposed and the stack stays put."""
    heads = (config.num_kv_heads, config.dims_per_head)
    if rows.shape[-2:] == heads:
        return rows
    return _row_major(rows).reshape(rows.shape[:-2] + heads)


def _row_major(rows: jnp.ndarray) -> jnp.ndarray:
    """``rows`` pinned to lie as their shape reads, the last axis minor."""
    return with_layout_constraint(
        rows, Layout(major_to_minor=tuple(range(rows.ndim)))
    )


def cache_logical_axes(
    kv_quant: bool = False, config: Optional[LlamaConfig] = None
) -> Dict[str, Any]:
    if config is not None and _family_of(config) is not None:
        return _family_of(config).cache_logical_axes()
    # a packed leaf's fourth axis is still kv heads, ``pack`` to a row:
    # ``flash_decode_pack`` packs only where whole rows fall to a shard
    axes: Dict[str, Any] = {
        "k": L("layers", "cache_batch", "cache_sequence", "kv_heads", None),
        "v": L("layers", "cache_batch", "cache_sequence", "kv_heads", None),
    }
    if kv_quant:
        axes["k_scale"] = L(
            "layers", "cache_batch", "cache_sequence", "kv_heads"
        )
        axes["v_scale"] = L(
            "layers", "cache_batch", "cache_sequence", "kv_heads"
        )
    return axes


def init_paged_cache(
    config: LlamaConfig,
    num_blocks: int,
    block_size: int,
    kv_quant: bool = False,
) -> Dict[str, jnp.ndarray]:
    """Paged KV cache (``kv_layout: paged``): a GLOBAL block pool
    [layers, num_blocks, block_size, kv_heads, head_dim] shared by every
    slot, addressed through per-slot block tables. Unlike
    :func:`init_cache` there is no per-slot max_len region — HBM scales
    with the tokens actually resident, short requests release their
    blocks early, and published prefix chains survive slot turnover
    (engine/paged.py owns the block accounting). Block 0 is the null
    block (padding / masked writes; never read live).

    ``kv_quant`` mirrors the dense layout: int8 values plus
    per-(block, position, kv-head) f32 scales."""
    shape = (
        config.num_layers, num_blocks, block_size,
        config.num_kv_heads, config.dims_per_head,
    )
    if kv_quant:
        return {
            "k": jnp.zeros(shape, dtype=jnp.int8),
            "v": jnp.zeros(shape, dtype=jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], dtype=jnp.float32),
            "v_scale": jnp.zeros(shape[:-1], dtype=jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, dtype=config.dtype),
        "v": jnp.zeros(shape, dtype=config.dtype),
    }


def paged_cache_logical_axes(kv_quant: bool = False) -> Dict[str, Any]:
    """Pool blocks are never sharded (any block may serve any request);
    kv_heads shard under tp like the dense cache."""
    axes: Dict[str, Any] = {
        "k": L("layers", None, None, "kv_heads", None),
        "v": L("layers", None, None, "kv_heads", None),
    }
    if kv_quant:
        axes["k_scale"] = L("layers", None, None, "kv_heads")
        axes["v_scale"] = L("layers", None, None, "kv_heads")
    return axes


def normalize_rope_scaling(value: Any) -> Optional[Tuple]:
    """HF configs carry rope scaling as a dict; the config field is a
    hashable tuple ("llama3", factor, low, high, original_max) or
    ("yarn", factor, beta_fast, beta_slow, mscale, mscale_all_dim,
    original_max). Accepts either spelling; any other type raises rather
    than silently degrading."""
    if value is None or isinstance(value, tuple):
        return value
    if isinstance(value, (list,)):
        return tuple(value)
    # YAML configs spell keys with dashes; HF JSON with underscores
    value = {k.replace("-", "_"): v for k, v in value.items()}
    kind = value.get("rope_type") or value.get("type")
    if kind == "default":
        return None
    if kind == "yarn":
        wanted = (
            "factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim",
            "original_max_position_embeddings",
        )
        missing = [key for key in wanted if key not in value]
        if missing:
            raise ValueError(f"yarn rope_scaling missing {missing}")
        return ("yarn",) + tuple(float(value[key]) for key in wanted)
    if kind != "llama3":
        raise ValueError(f"unsupported rope scaling type: {kind!r}")
    # all four parameters are REQUIRED (as in HF's validation): assumed
    # defaults would silently build wrong long-context RoPE angles
    missing = [
        key
        for key in (
            "factor", "low_freq_factor", "high_freq_factor",
            "original_max_position_embeddings",
        )
        if key not in value
    ]
    if missing:
        raise ValueError(f"llama3 rope_scaling missing {missing}")
    return (
        "llama3",
        float(value["factor"]),
        float(value["low_freq_factor"]),
        float(value["high_freq_factor"]),
        float(value["original_max_position_embeddings"]),
    )


def model_freqs(config: LlamaConfig, dtype=jnp.float32) -> jnp.ndarray:
    """The ONE way to build this config's RoPE table — theta AND the
    rope-scaling recipe (engine, trainer, forward, and the graft entry
    all route through here so a scaled checkpoint can never silently
    get plain frequencies)."""
    # latent attention rotates only the part of a head set aside for it
    rotated = (
        config.mla.qk_rope_head_dim if _kinds(config).attention == "latent"
        else config.dims_per_head
    )
    return rope_frequencies(
        rotated, config.max_seq_len, config.rope_theta,
        dtype=dtype, scaling=config.rope_scaling,
    )


def validate_family_params(
    config: LlamaConfig, params: Dict[str, Any]
) -> None:
    """Fail fast when a checkpoint/loader dropped family-specific
    tensors: the layer stack's None fallbacks (post norms, qkv biases)
    would otherwise run a qkv_bias/post_norms config silently WITHOUT
    them — wrong logits, no error."""
    required = []
    if config.qkv_bias:
        required += ["bq", "bk", "bv"]
    if config.post_norms:
        required += ["post_attn_norm", "post_mlp_norm"]
    if not config.tie_embeddings:
        required += ["lm_head"]
    if config.num_experts:
        required += ["router"]
    missing = [name for name in required if name not in params]
    if missing:
        raise ValueError(
            f"params missing {missing}, required by the model config — "
            "the checkpoint or loader dropped family-specific tensors"
        )


class Kinds(NamedTuple):
    attention: str           # "gqa" | "latent" (latent_moe.py) | a kind a
                             # layer (``config.mixers``): "hybrid"
                             # (hybrid_sparse_linear.py) | "conv"
                             # (short_conv_gqa.py)
    cache: Tuple[str, ...]   # the cache's leaves, as every program carries them


class Run(NamedTuple):
    """Consecutive layers of one kind, as :func:`_run_layers` takes them."""
    layers: Any           # scanned: one tuple of stacked leaves ``[count,
                          # ...]``; unrolled: a list of per-layer tuples
    first: int = 0        # the index ``attend`` is given for the first
    kind: Optional[str] = None   # which of a program's attends, where it
                                 # brings one a kind
    unroll: bool = False
    experts: Any = None   # the routed experts' stacks of the run's
                          # feed-forward (None: :func:`_mlp_block`'s); the
                          # run's ``first`` is then its first layer's
                          # index in the MODEL


def _kinds(
    config: LlamaConfig, cache: Optional[Dict[str, jnp.ndarray]] = None
) -> Kinds:
    """What a config's layers are made of, asked here and nowhere else:
    the attention kind and, with it, the cache's leaves (an int8 GQA
    cache is told by its ``k_scale`` leaf). A config with per-layer
    ``mixers`` answers by the sizes beside them, ``hybrid`` or ``conv``:
    its layers' kinds are that list, and :func:`_layers_of` cuts it into
    runs. The feed-forward kinds follow the runs of :func:`_layers_of`."""
    if config.short_conv is not None:
        return Kinds("conv", ("conv", "k", "v"))
    if config.mixers is not None:
        return Kinds("hybrid", hybrid.CACHE)
    if config.mla is not None:
        return Kinds("latent", ("latent",))
    if cache is not None and "k_scale" in cache:
        return Kinds("gqa", ("k", "v", "k_scale", "v_scale"))
    return Kinds("gqa", ("k", "v"))


def _family_of(config: LlamaConfig):
    """The file that holds a family's parameters, cache and attends where
    it is not this one: ``latent_moe``, ``hybrid_sparse_linear``,
    ``short_conv_gqa``, or None for the uniform GQA stack."""
    return {
        "latent": latent_moe, "hybrid": hybrid, "conv": short_conv,
    }.get(_kinds(config).attention)


def _stack_layer_params(params: Dict[str, jnp.ndarray], config=None):
    """The uniform (GQA) stack's layers for the layer loop: ``(attn_norm,
    (wq, wk, wv, biases), wo, post_attn_norm, mlp_norm, post_mlp_norm,
    feed-forward weights)``, every leaf ``[layers, ...]``. Post norms
    (Gemma-2 sandwich) and qkv biases (Qwen-2) are None for families
    without them — None is an empty pytree, so scan passes it through
    untouched. With ``config`` given, validates the family tensors are
    actually present first (see :func:`validate_family_params`)."""
    if config is not None and _kinds(config).attention != "gqa":
        # the programs that come through here have no attend for
        # another family's cache yet (the dense layout's three do,
        # through :func:`_layers_of`)
        raise NotImplementedError(
            f"this program has no {_kinds(config).attention}-attention "
            "form: the family runs the dense layout's prefill, "
            "prefill_at_offset and decode_step only (latent_moe.py, "
            "hybrid_sparse_linear.py and short_conv_gqa.py hold their "
            "attends)"
        )
    if config is not None:
        validate_family_params(config, params)
    mlp = (params["w_gate"], params["w_up"], params["w_down"])
    if "router" in params:
        mlp = mlp + (params["router"],)
    biases = (
        (params["bq"], params["bk"], params["bv"])
        if "bq" in params else None
    )
    return (
        params["attn_norm"],
        (params["wq"], params["wk"], params["wv"], biases),
        params["wo"], params.get("post_attn_norm"),
        params["mlp_norm"], params.get("post_mlp_norm"), mlp,
    )


def _layers_of(config: LlamaConfig, params) -> Tuple[Run, ...]:
    """A config's layers as :func:`_run_layers` takes them, a :class:`Run`
    for every stretch of one kind: the uniform stack is one; the latent
    family's leading dense layers (unrolled) and its expert layers are
    two; a family with per-layer ``mixers`` cuts where the mixer's kind
    changes and, with routed experts behind some layers, where the
    feed-forward's does (conv then dense, attention then experts, conv
    then experts): each run a stack of its own (a lone layer is
    unrolled), a run of expert layers with the experts' stacks."""
    kind = _kinds(config).attention
    if kind == "latent":
        lead, layers, experts = latent_moe.layer_stacks(config, params)
        return (
            Run(list(lead), unroll=True),
            Run(layers, first=len(lead), experts=experts),
        )
    if kind in ("hybrid", "conv"):
        runs = []
        for mixer, layers, first, experts in _family_of(config).layer_runs(
            config, params
        ):
            # a lone layer is its stack's one row (a bitcast), unrolled
            lone = jax.tree_util.tree_leaves(layers)[0].shape[0] == 1
            if lone:
                layers = [jax.tree_util.tree_map(lambda x: x[0], layers)]
            runs.append(Run(layers, first, mixer, unroll=lone, experts=experts))
        return tuple(runs)
    return (Run(_stack_layer_params(params, config)),)


def _project_qkv(normed, wq, wk, wv, biases):
    """q/k/v projections with optional biases (Qwen-2); returns flat
    [..., H*D] / [..., KVH*D] arrays — callers reshape to heads."""
    q = qeinsum("...h,hd->...d", normed, wq)
    k = qeinsum("...h,hd->...d", normed, wk)
    v = qeinsum("...h,hd->...d", normed, wv)
    if biases is not None:
        bq, bk, bv = biases
        q = q + bq.astype(q.dtype)
        k = k + bk.astype(k.dtype)
        v = v + bv.astype(v.dtype)
    return q, k, v


def _norm(config: LlamaConfig, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    return rms_norm(x, w, config.norm_eps, plus_one=config.norm_plus_one)


def _attn_scale(config: LlamaConfig) -> float:
    """Gemma scales scores by query_pre_attn_scalar**-0.5 instead of
    head_dim**-0.5; None keeps the Llama default."""
    return (config.query_pre_attn_scalar or config.dims_per_head) ** -0.5


def layer_windows(config: LlamaConfig) -> Optional[jnp.ndarray]:
    """Per-layer sliding-window sizes [L] (0 = full attention): Gemma-2
    alternates sliding/full starting with sliding at layer 0 (HF
    ``layer_types``). None when the family has no sliding window — the
    attention ops skip the window masking entirely."""
    if not config.sliding_window:
        return None
    return jnp.array(_window_sizes(config), dtype=jnp.int32)


def _window_sizes(config: LlamaConfig) -> Tuple[int, ...]:
    """:func:`layer_windows` on the host: one size a layer, 0 on every
    layer when the family has no sliding window."""
    return tuple(
        config.sliding_window if i % 2 == 0 else 0
        for i in range(config.num_layers)
    )


def _embed(config: LlamaConfig, params, tokens: jnp.ndarray) -> jnp.ndarray:
    x = params["embedding"][tokens].astype(config.dtype)
    if config.scale_embedding:
        x = x * jnp.asarray(math.sqrt(config.hidden_size), dtype=x.dtype)
    if config.embedding_scale is not None:
        x = x * jnp.asarray(config.embedding_scale, dtype=x.dtype)
    return x


@jax.named_scope("mlp")
def _mlp_block(
    config: LlamaConfig,
    normed: jnp.ndarray,
    mlp_weights,
    valid=None,
    dropless: bool = False,
):
    """SwiGLU MLP (dense or MoE) on normed activations [..., H].

    Returns (residual delta, MoE load-balance aux loss — 0 for dense).
    ``valid`` masks padding out of MoE capacity; ``dropless`` selects the
    serving capacity regime (no token ever dropped — required for
    checkpoints trained dropless, e.g. Mixtral)."""
    if config.num_experts:
        w_gate, w_up, w_down, router = mlp_weights
        return moe_mlp(
            normed, router, w_gate, w_up, w_down,
            num_selected=config.num_experts_per_tok,
            capacity_factor=None if dropless else config.capacity_factor,
            valid=valid,
        )
    w_gate, w_up, w_down = mlp_weights
    gate = qeinsum("...h,hf->...f", normed, w_gate)
    up = qeinsum("...h,hf->...f", normed, w_up)
    if config.act == "gelu_tanh":  # GeGLU (Gemma): tanh-approx gelu gate
        activated = jax.nn.gelu(gate, approximate=True)
    else:
        activated = jax.nn.silu(gate)
    out = qeinsum("...f,fh->...h", activated * up, w_down)
    return out, jnp.zeros((), dtype=jnp.float32)


@jax.named_scope("head")
def _logits(config: LlamaConfig, params, x):
    if config.logit_divisor is not None:
        x = x * jnp.asarray(1.0 / config.logit_divisor, dtype=x.dtype)
    if config.tie_embeddings:
        head = params["embedding"].T.astype(x.dtype)
        logits = jnp.einsum("...h,hv->...v", x, head).astype(jnp.float32)
    else:
        logits = qeinsum(
            "...h,hv->...v", x, params["lm_head"]
        ).astype(jnp.float32)
    cap = config.final_logit_softcap
    if cap is not None:
        logits = cap * jnp.tanh(logits / cap)
    return logits


def _flash_path(config, q, mesh):
    """Shared gate for the bf16/int8 prefill twins: (use the flash
    kernel?, dispatch through the tp shard_map wrapper?). One place for
    the MXU-alignment heuristic and the SPMD rule so the two paths
    cannot diverge. Softcap / sliding window (Gemma-2) ride INTO the
    kernels as a static cap and a traced per-layer window scalar."""
    tp_sharded = mesh is not None and dict(mesh.shape).get("tp", 1) > 1
    return _flash_ok(config, q.shape[1]), tp_sharded


def _flash_ok(config: LlamaConfig, seq: int) -> bool:
    """Whether GQA's cold prefill over ``seq`` tokens attends through the
    flash kernel."""
    return config.use_flash and (
        use_flash(seq, config.dims_per_head) or config.flash_interpret
    )


def prefill_attention_blocks(
    config: LlamaConfig,
    lengths,
    bucket: int,
    paged_kernel: Optional[str] = None,
) -> Optional[Tuple[float, float]]:
    """How often the flash kernel's skip engages in a cold prefill of
    prompts ``lengths`` long in a ``bucket``: the (q block, k block)
    steps it computes and those of the causal bucket
    (``flash_attention.attention_blocks``), summed over the rows, per
    head and per layer (a mean over the layers, whose windows may
    differ). None where that prefill's attention is not the flash
    kernel. ``paged_kernel`` is a paged engine's attention kernel, None
    for the dense cache."""
    kind = _kinds(config).attention
    if kind == "latent":
        blocks = latent_moe.prefill_flash_blocks(config, bucket)
    elif kind == "gqa" and _flash_ok(config, bucket) and not (
        paged_kernel == "fused" and _use_fused_paged(
            config, config.dims_per_head, config.num_heads,
            config.num_kv_heads, None,
        )
    ):
        blocks = ()  # the kernel's default blocks, as _prefill_attn runs it
    else:  # a window at offset 0 (hybrid, conv), or the fused paged kernel
        blocks = None
    if blocks is None:
        return None
    counts = [
        (layers, attention_blocks(lengths, bucket, *blocks, window=window))
        for window, layers in collections.Counter(_window_sizes(config)).items()
    ]
    return tuple(
        sum(layers * count[i] for layers, count in counts)
        / config.num_layers
        for i in (0, 1)
    )


@jax.named_scope("attention")
def _prefill_attn(config, q, k, v, mask, mesh=None, window=None):
    """Flash kernel on TPU for long MXU-aligned prompts, XLA einsum path
    otherwise (CPU tests, short prompts, odd head dims, softcap/window
    families — see :func:`_flash_path`). Under tensor
    parallelism (``mesh`` with tp>1) the kernel runs through shard_map
    over the head axis — a bare Mosaic call has no SPMD partitioning
    rule (``flash_prefill_attention_sharded``). Only called from the
    serving prefill path: the kernel has no VJP, so the differentiable
    :func:`forward` keeps the XLA formulation. Masks here are always
    right-padded (built from lengths), which is what the kernel's
    lengths-based masking assumes."""
    flash_ok, tp_sharded = _flash_path(config, q, mesh)
    family = dict(
        softcap=config.attn_logit_softcap, window=window,
        scale=_attn_scale(config),
    )
    if flash_ok:
        from langstream_tpu.ops.flash_attention import (
            flash_prefill_attention_sharded,
        )

        if tp_sharded:
            return flash_prefill_attention_sharded(
                q, k, v, mesh, mask=mask, interpret=config.flash_interpret,
                **family,
            )
        return flash_prefill_attention(
            q, k, v, mask=mask, interpret=config.flash_interpret, **family
        )
    return prefill_attention(q, k, v, mask=mask, **family)


def _decode_flash_path(config, kc, mesh):
    """Gate + dispatch mode for the flash-decode kernel — the decode
    twin of :func:`_flash_path`, same contract: returns (use the
    kernel?, tp shard_map?). ``kc`` is the stacked leaf ``[L, S, T,
    KVH / pack, pack * D]``: the kernel reads it where
    :func:`flash_decode_pack` says so AND the leaf lies as that answer
    lays it (``init_cache`` asked the same question; a leaf made for
    another mesh falls to the XLA side, through ``_unpack_kv``)."""
    tp = 1 if mesh is None else dict(mesh.shape).get("tp", 1)
    pack = flash_decode_pack(config, kc.shape[2], kc.dtype == jnp.int8, tp)
    flash_ok = (
        pack is not None
        and kc.shape[3:] == (
            config.num_kv_heads // pack, config.dims_per_head * pack
        )
    )
    return flash_ok, tp > 1


def decode_reader(config, cache, mesh=None) -> str:
    """The attention that reads the DENSE cache in this engine's decode
    step, by the kernel's name as a device trace shows it
    (``flash_decode``, ``flash_decode_int8kv``, ``mla_decode``), or
    ``xla``: what the gates above answer for the cache as it lies."""
    if _kinds(config).attention == "latent":
        on_kernel = latent_moe.decode_kernel_ok(config, cache["latent"])
        return "mla_decode" if on_kernel else "xla"
    if _kinds(config).attention == "hybrid":
        return hybrid.decode_reader(config, cache)
    if not _decode_flash_path(config, cache["k"], mesh)[0]:
        return "xla"
    return "flash_decode_int8kv" if "k_scale" in cache else "flash_decode"


@jax.named_scope("attention")
def _decode_attn(config, q, kc, vc, lengths, layer, mesh=None, window=None):
    """Decode attention over slab ``layer`` of the STACKED cache leaves
    ``[L, S, T, KVH, D]`` (narrow heads packed ``pack`` to a 128-lane
    row where the kernel reads them: ``init_cache``): length-aware Pallas
    kernel on TPU for long allocated caches (HBM traffic ∝ live context
    — the XLA einsum streams the full static buffer), XLA path
    otherwise. The kernel takes the stack and the layer (a custom call's
    operand is a materialised buffer: a slab handed to it is a slab
    copied); the XLA path reads ``kc[layer]``, which XLA fuses into the
    einsums. Under
    tp the kernel runs per head shard through shard_map
    (``flash_decode_attention_sharded``). ``window`` is this layer's
    sliding-window size (Gemma-2) and rides into the flash-decode
    kernel as a traced scalar, like softcap and scale — the kernel
    handles windowed layers itself; only non-shape-compatible configs
    gate off to XLA (see ``_decode_flash_path``, whose answer
    ``_decode_attend``'s cache write follows too)."""
    flash_ok, tp_sharded = _decode_flash_path(config, kc, mesh)
    family = dict(
        softcap=config.attn_logit_softcap, window=window,
        scale=_attn_scale(config),
    )
    if flash_ok:
        from langstream_tpu.ops.decode_kernel import (
            flash_decode_attention,
            flash_decode_attention_sharded,
        )

        if tp_sharded:
            return flash_decode_attention_sharded(
                q, kc, vc, lengths, layer, mesh,
                interpret=config.flash_interpret, **family,
            )
        return flash_decode_attention(
            q, kc, vc, lengths, layer, interpret=config.flash_interpret,
            **family,
        )
    return decode_attention(
        q, _unpack_kv(config, kc[layer]), _unpack_kv(config, vc[layer]),
        lengths, **family,
    )


@jax.named_scope("attention")
def _decode_attn_quant(config, q, kc, ks, vc, vs, lengths, layer, mesh=None,
                       window=None):
    """Int8-cache twin of :func:`_decode_attn`."""
    flash_ok, tp_sharded = _decode_flash_path(config, kc, mesh)
    family = dict(
        softcap=config.attn_logit_softcap, window=window,
        scale=_attn_scale(config),
    )
    if flash_ok:
        from langstream_tpu.ops.decode_kernel import (
            flash_decode_attention_quant,
            flash_decode_attention_sharded,
        )

        if tp_sharded:
            return flash_decode_attention_sharded(
                q, kc, vc, lengths, layer, mesh, k_scale=ks, v_scale=vs,
                interpret=config.flash_interpret, **family,
            )
        return flash_decode_attention_quant(
            q, kc, ks, vc, vs, lengths, layer,
            interpret=config.flash_interpret, **family,
        )
    return decode_attention_quant(
        q, kc[layer], ks[layer], vc[layer], vs[layer], lengths, **family
    )


@jax.named_scope("attention")
def _prefill_attn_quant(config, q, k_q, k_s, v_q, v_s, lengths, mesh=None,
                        window=None):
    """Quantized-cold-prefill twin of :func:`_prefill_attn`: int8 flash
    kernel on TPU for long MXU-aligned prompts (same scale-folded
    algebra, int8 HBM loads), XLA ``chunk_attention_quant`` otherwise."""
    flash_ok, tp_sharded = _flash_path(config, q, mesh)
    family = dict(
        softcap=config.attn_logit_softcap, window=window,
        scale=_attn_scale(config),
    )
    if flash_ok:
        from langstream_tpu.ops.flash_attention import (
            flash_prefill_attention_quant,
            flash_prefill_attention_quant_sharded,
        )

        if tp_sharded:
            return flash_prefill_attention_quant_sharded(
                q, k_q, k_s, v_q, v_s, mesh, lengths=lengths,
                interpret=config.flash_interpret, **family,
            )
        return flash_prefill_attention_quant(
            q, k_q, k_s, v_q, v_s, lengths=lengths,
            interpret=config.flash_interpret, **family,
        )
    return chunk_attention_quant(
        q, k_q, k_s, v_q, v_s, jnp.zeros_like(lengths), lengths, **family
    )


def _use_fused_paged(config, dim, heads, kv_heads, mesh):
    """Gate for the fused ragged paged-attention kernel
    (``ops/paged_attention.py``) — the paged twin of
    :func:`_flash_path` / :func:`_decode_flash_path`. Under tensor
    parallelism the kernel dispatches through its shard_map twin
    (``ragged_paged_attention_sharded`` — one launch per kv-head shard,
    exactly like the dense flash kernels), so the gate is mesh-blind:
    only shapes (GQA divisibility, MXU head_dim alignment) and backend
    (TPU, or the interpret test hook) decide. ``mesh`` stays a
    parameter so the gate signature keeps matching the dispatch seams
    that pass it."""
    del mesh  # tp no longer downgrades — the sharded twin handles it
    from langstream_tpu.ops.paged_attention import use_fused_paged

    return config.use_flash and use_fused_paged(
        dim, heads, kv_heads, interpret=config.flash_interpret
    )


def _constrain_kv_shard(pool, mesh, *, scale: bool = False):
    """Pin a (possibly layer-stacked) KV pool leaf to its kv-head shard
    under tensor parallelism. Every jitted paged WRITE
    (``paged_write_rows`` scatter) routes its result through here: the
    scatter indexes the replicated block axis, and without an explicit
    constraint the SPMD partitioner is free to resolve it by
    all-gathering the pool — which would silently turn the paged layout
    into tp× HBM. The kv-head axis sits last on scale leaves
    ([..., N, Bs, KVH]) and second-to-last on value leaves
    ([..., N, Bs, KVH, D]). No-op off-mesh and at tp=1 (matching
    ``paged_cache_logical_axes``, whose tp-sized rule this mirrors)."""
    if mesh is None or dict(mesh.shape).get("tp", 1) <= 1:
        return pool
    from jax.sharding import NamedSharding, PartitionSpec

    axes = [None] * pool.ndim
    axes[pool.ndim - (1 if scale else 2)] = "tp"
    return jax.lax.with_sharding_constraint(
        pool, NamedSharding(mesh, PartitionSpec(*axes))
    )


def _mixed_block_q(width: int) -> int:
    """q-tile granularity for the token-ragged mixed dispatch: spans
    are ``width`` tokens per row, so the tile must divide the span —
    power-of-two widths take 8-row tiles, anything smaller (or odd)
    collapses to one tile per row."""
    return 8 if width % 8 == 0 else width


@jax.named_scope("attention")
def _paged_attn(config, q, k_pool, v_pool, tables, starts, totals, *,
                window, kernel, mesh=None, q_lens=None):
    """Paged attention dispatch, ONE seam for all the ragged cases:
    decode (q [S, H, D], starts = lengths-1), prefill-at-offset and cold
    paged prefill (q [B, T, H, D]), and — with ``q_lens`` — the MIXED
    prefill+decode dispatch, where every row carries its own new-token
    count (decode rows 1, admitting rows a prefill window, idle rows 0)
    and the fused path runs the token-ragged q formulation
    (:func:`langstream_tpu.ops.paged_attention.ragged_q_paged_attention`
    — flattened q tile + cu_q_lens-style row offsets, dead q tiles
    skipped). ``kernel == "fused"`` (and shapes / backend permitting —
    see :func:`_use_fused_paged`) runs the single fused Pallas launch
    that streams table-addressed pool blocks; under tp>1 that launch
    runs per kv-head shard through the shard_map twin (a bare Mosaic
    call has no SPMD partitioning rule). The gather/scatter composition
    in ``ops/attention.py`` stays as the reference oracle (it already
    speaks per-row starts/totals, so mixed rows need no new reference
    path — positions past a row's count compute discarded garbage)."""
    family = dict(
        softcap=config.attn_logit_softcap, window=window,
        scale=_attn_scale(config),
    )
    decode = q.ndim == 3
    heads, dim = q.shape[-2], q.shape[-1]
    kv_heads = k_pool.shape[2]
    if kernel == "fused" and _use_fused_paged(
        config, dim, heads, kv_heads, mesh
    ):
        from langstream_tpu.ops.paged_attention import (
            ragged_paged_attention,
            ragged_paged_attention_sharded,
            ragged_q_paged_attention,
            ragged_q_paged_attention_sharded,
        )

        tp_sharded = mesh is not None and dict(mesh.shape).get("tp", 1) > 1
        if q_lens is not None and not decode:
            # token-ragged q: rows at uniform stride in the flattened
            # tile (q_offsets = b·W — the cu_q_lens special case the
            # engine's static [S, W] dispatch shape produces)
            batch, width = q.shape[:2]
            q_flat = q.reshape(batch * width, heads, dim)
            qoffs = jnp.arange(batch, dtype=jnp.int32) * width
            block_q = _mixed_block_q(width)
            if tp_sharded:
                out = ragged_q_paged_attention_sharded(
                    q_flat, k_pool, v_pool, tables, starts, totals,
                    qoffs, mesh, max_q_len=width, block_q=block_q,
                    interpret=config.flash_interpret, **family,
                )
            else:
                out = ragged_q_paged_attention(
                    q_flat, k_pool, v_pool, tables, starts, totals,
                    qoffs, max_q_len=width, block_q=block_q,
                    interpret=config.flash_interpret, **family,
                )
            return out.reshape(batch, width, heads, dim)
        q_in = q[:, None] if decode else q
        if tp_sharded:
            out = ragged_paged_attention_sharded(
                q_in, k_pool, v_pool, tables, starts, totals, mesh,
                interpret=config.flash_interpret, **family,
            )
        else:
            out = ragged_paged_attention(
                q_in, k_pool, v_pool, tables, starts, totals,
                interpret=config.flash_interpret, **family,
            )
        return out[:, 0] if decode else out
    if decode:
        return paged_decode_attention(
            q, k_pool, v_pool, tables, totals, **family
        )
    return paged_chunk_attention(
        q, k_pool, v_pool, tables, starts, totals, **family
    )


@jax.named_scope("attention")
def _paged_attn_quant(config, q, k_pool, k_scale, v_pool, v_scale, tables,
                      starts, totals, *, window, kernel, mesh=None,
                      q_lens=None):
    """Int8-pool twin of :func:`_paged_attn` (scales stream through the
    same table-addressed index maps; ``q_lens`` selects the token-ragged
    mixed formulation exactly like the bf16 seam)."""
    family = dict(
        softcap=config.attn_logit_softcap, window=window,
        scale=_attn_scale(config),
    )
    decode = q.ndim == 3
    heads, dim = q.shape[-2], q.shape[-1]
    kv_heads = k_pool.shape[2]
    if kernel == "fused" and _use_fused_paged(
        config, dim, heads, kv_heads, mesh
    ):
        from langstream_tpu.ops.paged_attention import (
            ragged_paged_attention_quant,
            ragged_paged_attention_quant_sharded,
            ragged_q_paged_attention_quant,
            ragged_q_paged_attention_sharded,
        )

        tp_sharded = mesh is not None and dict(mesh.shape).get("tp", 1) > 1
        if q_lens is not None and not decode:
            batch, width = q.shape[:2]
            q_flat = q.reshape(batch * width, heads, dim)
            qoffs = jnp.arange(batch, dtype=jnp.int32) * width
            block_q = _mixed_block_q(width)
            if tp_sharded:
                out = ragged_q_paged_attention_sharded(
                    q_flat, k_pool, v_pool, tables, starts, totals,
                    qoffs, mesh, max_q_len=width, block_q=block_q,
                    k_scale=k_scale, v_scale=v_scale,
                    interpret=config.flash_interpret, **family,
                )
            else:
                out = ragged_q_paged_attention_quant(
                    q_flat, k_pool, k_scale, v_pool, v_scale,
                    tables, starts, totals, qoffs,
                    max_q_len=width, block_q=block_q,
                    interpret=config.flash_interpret, **family,
                )
            return out.reshape(batch, width, heads, dim)
        q_in = q[:, None] if decode else q
        if tp_sharded:
            out = ragged_paged_attention_quant_sharded(
                q_in, k_pool, k_scale, v_pool, v_scale,
                tables, starts, totals, mesh,
                interpret=config.flash_interpret, **family,
            )
        else:
            out = ragged_paged_attention_quant(
                q_in, k_pool, k_scale, v_pool, v_scale,
                tables, starts, totals, interpret=config.flash_interpret,
                **family,
            )
        return out[:, 0] if decode else out
    if decode:
        return paged_decode_attention_quant(
            q, k_pool, k_scale, v_pool, v_scale, tables, totals, **family
        )
    return paged_chunk_attention_quant(
        q, k_pool, k_scale, v_pool, v_scale, tables, starts, totals,
        **family,
    )


@jax.named_scope("mlp")
def _expert_block(config, normed, weights, stacks, layer, valid):
    """The feed-forward of an expert layer on normed [..., h]: the routed
    experts held here (``stacks[..][layer]``), where the config's routing
    rule sends a token, plus the shared experts where the config has any.
    ``weights`` is the router, the selection bias (the ``sigmoid_bias``
    rule's), then the shared experts' SwiGLU. Returns (delta, counters)."""
    experts = config.experts
    router, *rest = weights
    sizes = dict(
        num_selected=experts.per_token, scaling_factor=experts.scaling_factor
    )
    if experts.routing == "sigmoid_bias":
        bias, *rest = rest
        route = functools.partial(
            sigmoid_bias_routing, bias=bias, renormalise=experts.renormalise,
            **sizes,
        )
    else:
        route = functools.partial(
            group_limited_routing, groups=experts.groups,
            groups_kept=experts.groups_kept, **sizes,
        )
    shape = normed.shape
    routed, counters = moe_mlp_held(
        normed.reshape(-1, shape[-1]), router, *stacks, layer=layer,
        held_first=experts.held_first, route=route,
        valid=None if valid is None else valid.reshape(-1),
        interpret=config.flash_interpret,
    )
    if not experts.shared:
        return routed.reshape(shape), counters
    shared, _ = _mlp_block(config, normed, tuple(rest))
    return routed.reshape(shape) + shared, counters


def _block(config, x, layer, attend, index, inputs, state, *, valid,
           dropless, experts=None):
    """The decoder block, written once for every program of every family,
    on x ``[..., H]`` (a GQA decode step's ``[S, H]``, a prefill's
    ``[B, T, H]``): pre-norm, the program's ``attend``, ``wo``, residual,
    pre-norm, the feed-forward by the layer's kind (the routed experts of
    ``experts`` where given, else :func:`_mlp_block`), residual; Gemma-2's
    post norms where the layer has them. Returns (x, state, the attend's
    per-layer output, what the feed-forward counted: expert counters or
    the MoE aux loss)."""
    attn_norm, attention, wo, post_attn, mlp_norm, post_mlp, mlp = layer
    attn, state, out = attend(
        _norm(config, x, attn_norm), attention, index, inputs, state
    )
    attn = qeinsum(
        "sd,dh->sh" if x.ndim == 2 else "btd,dh->bth",
        attn.reshape(x.shape[:-1] + (-1,)), wo,
    )
    if post_attn is not None:
        attn = _norm(config, attn, post_attn)
    if config.residual_scale is not None:
        attn = attn * jnp.asarray(config.residual_scale, dtype=attn.dtype)
    x = x + attn
    if experts is not None:
        # the routed experts' stacks hold the expert layers alone
        held_layer = index - config.experts.leading_dense
    normed = _norm(config, x, mlp_norm)
    if experts is None:
        delta, counted = _mlp_block(
            config, normed, mlp, valid=valid, dropless=dropless
        )
    else:
        delta, counted = _expert_block(
            config, normed, mlp, experts, held_layer, valid
        )
    if post_mlp is not None:
        delta = _norm(config, delta, post_mlp)
    if config.residual_scale is not None:
        delta = delta * jnp.asarray(config.residual_scale, dtype=delta.dtype)
    return x + delta, state, out, counted


def _run_layers(config, runs, x, attend, *, state=None, per_layer=None,
                valid=None, dropless=True, total=None):
    """The layer loop of every program: the only ``lax.scan`` over layers,
    one for every scanned :class:`Run` of ``runs`` (:func:`_layers_of`).

    ``attend(normed, attention weights, index, inputs, state) -> (attn,
    state, out)`` is the program's attention and owns the cache: it
    projects, ropes, writes and attends; a program whose layers differ in
    kind passes one a kind, ``{kind: attend}``, and a run names its own.
    ``index`` counts from the run's ``first``. ``state`` rides the loop as
    carry (a cache written in place), ``per_layer`` is scanned beside a
    lone run's layers (each layer's ``inputs``: its window, a cache slab
    handed over as xs; an unrolled layer gets None), and ``out`` comes
    back stacked over the layers (a cold prefill's rows, a slab handed
    back as ys). ``valid`` and ``dropless`` are the feed-forward's.
    ``total`` starts the sum of what the scanned layers' feed-forwards
    count: the expert counters by default (None, an empty pytree, without
    routed experts), a scalar for the MoE aux loss.
    Returns (x, state, outs, total)."""
    if per_layer is not None and len(runs) > 1:
        raise ValueError("per-layer inputs are scanned beside ONE run's layers")
    if total is None and config.experts is not None:
        total = zero_counters(config)
    outs = []
    for run in runs:
        attend_run = attend[run.kind] if run.kind is not None else attend
        if run.unroll:
            each = []
            for index, layer in enumerate(run.layers, run.first):
                x, state, out, counted = _block(
                    config, x, layer, attend_run, jnp.int32(index), None,
                    state, valid=valid, dropless=dropless,
                    experts=run.experts,
                )
                if run.experts is not None:
                    total = total + counted
                each.append(out)
            outs.append(
                jax.tree_util.tree_map(lambda *parts: jnp.stack(parts), *each)
            )
            continue

        def layer_fn(carry, scanned, run=run, attend_run=attend_run):
            x, state, total = carry
            layer, inputs, index = scanned
            x, state, out, counted = _block(
                config, x, layer, attend_run, index, inputs, state,
                valid=valid, dropless=dropless, experts=run.experts,
            )
            # the routed experts' counters come from the expert layers
            # alone (a dense run beside them counts nothing)
            if total is not None and (
                run.experts is not None or config.experts is None
            ):
                total = total + counted
            return (x, state, total), out

        count = jax.tree_util.tree_leaves(run.layers)[0].shape[0]
        (x, state, total), scanned = jax.lax.scan(
            layer_fn, (x, state, total),
            (run.layers, per_layer, jnp.arange(run.first, run.first + count)),
        )
        outs.append(scanned)
    if len(outs) > 1:
        outs = [jax.tree_util.tree_map(
            lambda *parts: jnp.concatenate(parts), *outs
        )]
    return x, state, outs[0], total


def _rotated_heads(config, normed, weights, freqs, positions):
    """GQA's input side on normed ``[..., H]``: q ``[..., heads, D]`` and
    k, v ``[..., kv_heads, D]``, q and k rotated at ``positions`` (a
    decode step's ``[S]``, one a slot; else ``[B, T]``). ``weights`` is
    ``(wq, wk, wv, biases)`` and, for a family that norms every q and k
    head before the rotation, the two norms' scales ``[D]`` behind them."""
    hd = config.dims_per_head
    wq, wk, wv, biases, *norms = weights
    q, k, v = _project_qkv(normed, wq, wk, wv, biases)
    q = q.reshape(normed.shape[:-1] + (config.num_heads, hd))
    k = k.reshape(normed.shape[:-1] + (config.num_kv_heads, hd))
    v = v.reshape(normed.shape[:-1] + (config.num_kv_heads, hd))
    if len(norms):
        q = rms_norm(q, norms[0], config.norm_eps)
        k = rms_norm(k, norms[1], config.norm_eps)
    if normed.ndim == 2:
        q = apply_rope(q[:, None], freqs, positions[:, None])[:, 0]
        k = apply_rope(k[:, None], freqs, positions[:, None])[:, 0]
    else:
        q = apply_rope(q, freqs, positions)
        k = apply_rope(k, freqs, positions)
    return q, k, v


def _prefill_attend(config, freqs, positions, mask, lengths, mesh,
                    quantized: bool):
    """GQA's cold-prefill attend: self-attention that never reads the
    cache; the prompt's K and V (quantized for an int8 cache) come back
    stacked over the layers and the program writes them."""
    def attend(normed, weights, index, win, state):
        q, k, v = _rotated_heads(config, normed, weights, freqs, positions)
        if not quantized:
            attn = _prefill_attn(config, q, k, v, mask, mesh=mesh, window=win)
            return attn, state, (k, v)
        # quantize ONCE and run the prompt's self-attention through
        # the SAME f32 scale-folded math the warm/decode dispatches
        # use (the just-written rows as the "cache", starts=0):
        # identical formulas over identical row contents keep
        # cold/warm/prefix-copy paths token-identical. Long
        # MXU-aligned prompts take the int8 flash kernel — identical
        # scale-folded algebra, int8 HBM tile loads — so kv-quant
        # keeps the flash HBM profile on cold prefill; block
        # boundaries reassociate f32 sums exactly like the bf16
        # flash path does.
        k_q, k_s = quantize_kv(k)
        v_q, v_s = quantize_kv(v)
        attn = _prefill_attn_quant(
            config, q, k_q, k_s, v_q, v_s, lengths, mesh=mesh, window=win,
        )
        # grouped (k, v, k_scale, v_scale) — the ordering every
        # quantized program in this module uses
        return attn, state, (k_q, v_q, k_s, v_s)

    return attend


def _prefill_scan(
    config: LlamaConfig,
    params: Dict[str, jnp.ndarray],
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,     # [B, T] int32 (right-padded)
    lengths: jnp.ndarray,    # [B] true prompt lengths
    freqs: jnp.ndarray,
    mesh,
):
    """The cold prefill's pass over the layers, shared by the dense and
    paged cache layouts (cold prefill's self-attention never reads the
    cache, so only the WRITE differs between them). Returns (activations
    [B, T, H] after the final layer, the rows to write by cache leaf,
    each ``[L, B, T, ...]``, the expert counters)."""
    kinds = _kinds(config, cache)
    batch, seq = tokens.shape
    positions = jnp.arange(seq)[None, :].repeat(batch, 0)
    mask = positions < lengths[:, None]
    x = _embed(config, params, tokens)  # [B, T, H]
    if kinds.attention == "latent":
        attend = latent_moe.prefill_attend(config, freqs, positions, mask)
    else:
        attend = _prefill_attend(
            config, freqs, positions, mask, lengths, mesh,
            "k_scale" in kinds.cache,
        )
    x, _, rows, counters = _run_layers(
        config, _layers_of(config, params), x, attend,
        per_layer=layer_windows(config), valid=mask,
    )
    return x, dict(zip(kinds.cache, rows)), counters


def _last_token_logits(
    config: LlamaConfig,
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,          # [B, T, H]
    lengths: jnp.ndarray,    # [B]
) -> jnp.ndarray:
    x = _norm(config, x, params["final_norm"])
    batch = x.shape[0]
    last = x[jnp.arange(batch), (lengths - 1).astype(jnp.int32)]  # [B, H]
    return _logits(config, params, last)


def _scales_first(new):
    """The order the cold prefills write an int8 cache's leaves in."""
    names = tuple(new)
    return names[2:] + names[:2]


# jit: device-context — runs inside the engine's jitted dispatches
def prefill(
    config: LlamaConfig,
    params: Dict[str, jnp.ndarray],
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,     # [B, T] int32 (right-padded)
    lengths: jnp.ndarray,    # [B] true prompt lengths
    slot_ids: jnp.ndarray,   # [B] cache slots to write
    freqs: jnp.ndarray,
    mesh=None,               # tp mesh for the sharded flash path
):
    """Run the prompt through the model, write the KV cache at the given
    slots, return logits of each prompt's last real token [B, V]."""
    if _kinds(config).attention in ("hybrid", "conv"):
        # a family with a carried state: its cold prefill is its window at
        # offset 0, where the state starts from zeros
        return prefill_at_offset(
            config, params, cache, tokens, lengths, jnp.zeros_like(lengths),
            slot_ids, freqs,
        )
    x, new, counters = _prefill_scan(
        config, params, cache, tokens, lengths, freqs, mesh
    )

    @jax.named_scope("cache_write")
    def write(name):
        leaf, rows = cache[name], new[name]
        if rows.shape[3:] != leaf.shape[3:]:  # a packed value leaf
            rows = _pack_kv(rows, leaf)
        pad = leaf.shape[2] - rows.shape[2]
        if pad > 0:
            rows = jnp.pad(
                rows, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (rows.ndim - 3)
            )
        return leaf.at[:, slot_ids].set(rows.astype(leaf.dtype))

    out = dict(cache)
    for name in _scales_first(new):
        out[name] = write(name)
    return out, _last_token_logits(config, params, x, lengths), counters


# float32 scores a window's attention holds at a time (heads x queries x
# keys x 4 B): 32 heads x 2 rows x 2,048 queries over 4,096 keys are 2.1 GB
# whole, 4.3 GB of temp on the described v5e
SCORES_IN_FLIGHT_BYTES = 1 << 28


def _offset_attend(config, freqs, seq: int, lengths, offsets, slot_ids, *,
                   drop: bool = False):
    """GQA's attend for a suffix into warm dense slots: new KV written at
    ``offset..offset+len-1``, attention over prefix + suffix, a block of
    each row's queries at a time where the whole window's scores pass
    ``SCORES_IN_FLIGHT_BYTES``. ``drop`` writes the rows by position and
    drops those past the cache's end (a right-padded window that passes
    it, which a family with a carried state sends; else the caller keeps
    the window inside). Returns (attend, the suffix's valid mask
    [B, T])."""
    positions = offsets[:, None] + jnp.arange(seq)[None, :]  # [B, T] global
    mask = jnp.arange(seq)[None, :] < lengths[:, None]       # [B, T] valid
    totals = offsets + lengths                               # [B]
    family = dict(
        softcap=config.attn_logit_softcap, scale=_attn_scale(config)
    )

    # The stacked cache rides the layer scan as CARRY, indexed by layer —
    # not as scanned xs → ys. A while-loop carry is updated in place;
    # scanned outputs get a fresh stacked buffer, i.e. a second copy of
    # the whole cache (3.5 GB of temp at 32 slots × 2048 on Qwen-2.5-7B:
    # the v5e compiler refuses the program for HBM).
    @jax.named_scope("cache_write")
    def write_rows(stacked, layer_index, new):
        # stacked: [L, S, max_len, ...]; new: [B, T, ...] — write each
        # row's suffix window at its offset (rank-agnostic: value leaves
        # carry a head_dim axis, scale leaves don't). Padding positions
        # beyond the suffix length land past ``totals`` where content is
        # dead. One dynamic_update_slice per row: in place on the carry
        # whatever the stack's layout (a scatter wants it row-major: in
        # place where it lies so, the packed and the 128-wide rows the
        # decode kernel reads; else XLA re-lays-out, i.e. copies, the
        # whole cache). A slice that passes the end is clamped onto live
        # rows, a scattered row there is dropped.
        if drop:
            return stacked.at[layer_index, slot_ids[:, None], positions].set(
                new.astype(stacked.dtype), mode="drop"
            )

        def body(stacked, args):
            row_new, off, slot = args
            start = (layer_index, slot, off) + (0,) * (stacked.ndim - 3)
            return jax.lax.dynamic_update_slice(
                stacked, row_new.astype(stacked.dtype)[None, None], start
            ), None

        stacked, _ = jax.lax.scan(body, stacked, (new, offsets, slot_ids))
        return stacked

    def over_blocks(q, keys: int, attention):
        """``attention(queries [B, block, H, D], their offsets [B])`` over
        the window's queries ``q``, a block of each row's at a time where
        the scores of all of them over ``keys`` pass the bytes in flight."""
        rows = SCORES_IN_FLIGHT_BYTES // (4 * q.shape[2] * keys)
        block_q = seq
        while block_q * q.shape[0] > rows and block_q % 2 == 0 and block_q > 8:
            block_q //= 2
        if block_q == seq:
            return attention(q, offsets)

        def block(start):
            part = jax.lax.dynamic_slice_in_dim(q, start, block_q, axis=1)
            return attention(part, offsets + start)

        blocks = jax.lax.map(block, jnp.arange(0, seq, block_q))
        return blocks.swapaxes(0, 1).reshape(q.shape)

    # The rows the attention reads are pinned as the stack lies
    # (_row_major), packed or not, int8 or not: unpinned, XLA's einsum
    # asks for them position-minor, the wish runs back through the slice
    # to the carried stack, and the whole K and V stacks are copied at
    # the program's entry and back at its exit (Qwen-2.5-7B, 32 x 2,048:
    # four copies of 1.9 GB; a 256-token window took 47 ms on the chip
    # where it takes 25).
    def attend(normed, weights, index, win, kv):
        q, k, v = _rotated_heads(config, normed, weights, freqs, positions)
        if len(kv) == 4:
            kc, vc, ks, vs = kv
            k_q, k_s = quantize_kv(k)
            v_q, v_s = quantize_kv(v)
            kc = write_rows(kc, index, k_q)
            ks = write_rows(ks, index, k_s)
            vc = write_rows(vc, index, v_q)
            vs = write_rows(vs, index, v_s)
            k_rows, v_rows = (
                _row_major(stack[index, slot_ids]) for stack in (kc, vc)
            )
            k_scale, v_scale = ks[index, slot_ids], vs[index, slot_ids]
            with jax.named_scope("attention"):
                attn = over_blocks(q, k_rows.shape[1], lambda part, at: (
                    chunk_attention_quant(
                        part, k_rows, k_scale, v_rows, v_scale, at, totals,
                        window=win, **family,
                    )
                ))
            return attn, (kc, vc, ks, vs), None
        kc, vc = kv
        kc = write_rows(kc, index, _pack_kv(k, kc))
        vc = write_rows(vc, index, _pack_kv(v, vc))
        k_rows, v_rows = (
            _unpack_kv(config, _row_major(stack[index, slot_ids]))
            for stack in (kc, vc)
        )
        with jax.named_scope("attention"):
            attn = over_blocks(q, k_rows.shape[1], lambda part, at: (
                chunk_attention(
                    part, k_rows, v_rows, at, totals, window=win, **family
                )
            ))
        return attn, (kc, vc), None

    return attend, mask


def _carried(config, cache):
    """The cache's leaves as the layer loop carries them; the hybrid
    family's attends carry the selection's counters behind them."""
    kinds = _kinds(config, cache)
    leaves = tuple(cache[name] for name in kinds.cache)
    if kinds.attention == "hybrid":
        leaves += (zero_counters(config),)
    return leaves


def _uncarried(config, cache, carried, counters):
    """(the cache with the leaves the loop carried, the program's
    counters: the loop's own, or what the hybrid family's attends
    carried)."""
    kinds = _kinds(config, cache)
    if kinds.attention == "hybrid":
        *carried, counters = carried
    out = dict(cache)
    out.update(zip(kinds.cache, carried))
    return out, counters


# jit: device-context — runs inside the engine's jitted dispatches
def prefill_at_offset(
    config: LlamaConfig,
    params: Dict[str, jnp.ndarray],
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,     # [B, T] int32 suffix tokens (right-padded)
    lengths: jnp.ndarray,    # [B] true suffix lengths
    offsets: jnp.ndarray,    # [B] existing valid cache length per row
    slot_ids: jnp.ndarray,   # [B] cache slots to extend
    freqs: jnp.ndarray,
):
    """Chunked prefill of a *suffix* into warm cache slots: positions are
    offset by the already-cached prefix, new KV is written at
    ``offset..offset+len-1``, and attention runs over prefix + suffix.
    One dispatch replaces the old per-token teacher-forcing path for
    warm-session follow-ups (KV session reuse, BASELINE config #5).
    Caller must guarantee ``offset + T <= cache max_len`` (the engine's
    warm check enforces it — a clamped dynamic_update_slice would
    silently overwrite live prefix rows otherwise).
    Returns (cache, logits of each row's last real suffix token [B, V],
    the expert counters)."""
    kinds = _kinds(config, cache)
    window = (config, freqs, tokens.shape[1], lengths, offsets, slot_ids)
    if kinds.attention == "hybrid":
        attend, mask = hybrid.window_attends(*window, cache["k"].shape[3])
    elif kinds.attention == "conv":
        gqa, mask = _offset_attend(*window, drop=True)
        attend = short_conv.window_attends(
            config, lengths, offsets, slot_ids, gqa
        )
    elif kinds.attention == "latent":
        attend, mask = latent_moe.offset_attend(*window)
    else:
        attend, mask = _offset_attend(*window)
    x = _embed(config, params, tokens)                       # [B, T, H]
    x, stacked, _, counters = _run_layers(
        config, _layers_of(config, params), x, attend,
        state=_carried(config, cache),
        per_layer=layer_windows(config), valid=mask,
    )
    out, counters = _uncarried(config, cache, stacked, counters)
    return out, _last_token_logits(config, params, x, lengths), counters


# jit: device-context — runs inside the engine's jitted dispatches
def paged_prefill(
    config: LlamaConfig,
    params: Dict[str, jnp.ndarray],
    cache: Dict[str, jnp.ndarray],   # paged pool (init_paged_cache)
    tokens: jnp.ndarray,             # [B, T] int32 (right-padded)
    lengths: jnp.ndarray,            # [B] true prompt lengths
    block_tables: jnp.ndarray,       # [B, M] pool block per seq block
    freqs: jnp.ndarray,
    mesh=None,                       # tp mesh for the sharded flash path
    kernel: str = "fused",           # paged attention: fused | reference
):
    """Cold prefill into the paged block pool.

    Fused path (``kernel="fused"`` and the gate passes): cold prefill is
    prefill-at-offset with every offset 0 — the SAME fused ragged launch
    the warm and decode paths use, reading the just-written blocks
    through the tables (identical formulas over identical row contents,
    the same trick the quantized cold path has always used). Reference
    path: the dense layout's cold pass (and flash kernel gating) of
    :func:`prefill` — cold self-attention never reads the cache — with
    the KV write scattered through the block tables."""
    batch, seq = tokens.shape
    if kernel == "fused" and _use_fused_paged(
        config, config.dims_per_head, config.num_heads, config.num_kv_heads,
        mesh,
    ):
        return paged_prefill_at_offset(
            config, params, cache, tokens, lengths,
            jnp.zeros_like(lengths), block_tables, freqs,
            mesh=mesh, kernel=kernel,
        )
    x, new, counters = _prefill_scan(
        config, params, cache, tokens, lengths, freqs, mesh
    )
    valid = jnp.arange(seq)[None, :] < lengths[:, None]
    zeros = jnp.zeros((batch,), jnp.int32)

    @jax.named_scope("cache_write")
    def write(name):
        return _constrain_kv_shard(
            jax.vmap(
                lambda p, n: paged_write_rows(p, n, block_tables, zeros, valid)
            )(cache[name], new[name]),
            mesh, scale=name.endswith("_scale"),
        )

    out = dict(cache)
    for name in _scales_first(new):
        out[name] = write(name)
    return out, _last_token_logits(config, params, x, lengths), counters


def _paged_attend(config, freqs, positions, block_tables, starts, totals,
                  write_mask, mesh, kernel, q_lens=None):
    """GQA's attend over the paged block pool, for every paged program:
    the layer's pool slabs arrive scanned (``inputs = (slabs, window)``:
    (k, v) or, int8, (k, v, k_scale, v_scale)) and go back as the layer's
    ``out``. New KV scatters through the block tables from ``starts``
    (masked positions route to the null block), attention reads the live
    context through the SAME tables (:func:`_paged_attn`)."""
    decode = positions.ndim == 1

    @jax.named_scope("cache_write")
    def write(pool, new, scale=False):
        mask = write_mask
        if decode:  # one position a slot
            new, mask = new[:, None], mask[:, None]
        return _constrain_kv_shard(
            paged_write_rows(pool, new, block_tables, starts, mask),
            mesh, scale=scale,
        )

    def attend(normed, weights, index, inputs, state):
        slabs, win = inputs
        q, k, v = _rotated_heads(config, normed, weights, freqs, positions)
        mode = dict(window=win, kernel=kernel, mesh=mesh, q_lens=q_lens)
        if len(slabs) == 4:
            kp, vp, ks, vs = slabs
            k_q, k_s = quantize_kv(k)
            v_q, v_s = quantize_kv(v)
            kp = write(kp, k_q)
            ks = write(ks, k_s, scale=True)
            vp = write(vp, v_q)
            vs = write(vs, v_s, scale=True)
            attn = _paged_attn_quant(
                config, q, kp, ks, vp, vs, block_tables, starts, totals,
                **mode,
            )
            return attn, state, (kp, vp, ks, vs)
        kp, vp = slabs
        kp = write(kp, k)
        vp = write(vp, v)
        attn = _paged_attn(
            config, q, kp, vp, block_tables, starts, totals, **mode
        )
        return attn, state, (kp, vp)

    return attend


def _run_over_slabs(config, params, cache, x, attend, valid):
    """The layer loop for the programs that hand the cache to the scan as
    xs and take it back as ys (the paged pool's, and the dense verify
    step's). Returns (cache, x)."""
    names = _kinds(config, cache).cache
    x, _, slabs, _ = _run_layers(
        config, (Run(_stack_layer_params(params, config)),), x, attend,
        per_layer=(
            tuple(cache[name] for name in names), layer_windows(config)
        ),
        valid=valid,
    )
    out = dict(cache)
    out.update(zip(names, slabs))
    return out, x


# jit: device-context — runs inside the engine's jitted dispatches
def paged_prefill_at_offset(
    config: LlamaConfig,
    params: Dict[str, jnp.ndarray],
    cache: Dict[str, jnp.ndarray],   # paged pool
    tokens: jnp.ndarray,             # [B, T] suffix tokens (right-padded)
    lengths: jnp.ndarray,            # [B] true suffix lengths
    offsets: jnp.ndarray,            # [B] existing valid length per row
    block_tables: jnp.ndarray,       # [B, M]
    freqs: jnp.ndarray,
    mesh=None,                       # tp mesh (fused kernel runs per
                                     # kv-head shard via shard_map)
    kernel: str = "fused",           # paged attention: fused | reference
):
    """Paged twin of :func:`prefill_at_offset`: suffix KV scatters into
    table-addressed blocks, attention reads prefix + suffix through
    the SAME tables — which is how a request admitted onto a cached
    prefix chain (prefix-cache hit) attends over blocks some other
    request's prefill wrote. Shared blocks are never written here: the
    engine admits suffixes at block-aligned boundaries into private
    blocks (COW for mid-block session divergence happens before the
    dispatch). Attention dispatches through :func:`_paged_attn` — one
    fused table-addressed launch by default, gather/scatter reference
    otherwise."""
    seq = tokens.shape[1]
    positions = offsets[:, None] + jnp.arange(seq)[None, :]  # [B, T] global
    mask = jnp.arange(seq)[None, :] < lengths[:, None]       # [B, T] valid
    totals = offsets + lengths                               # [B]
    x = _embed(config, params, tokens)                       # [B, T, H]
    attend = _paged_attend(
        config, freqs, positions, block_tables, offsets, totals, mask, mesh,
        kernel,
    )
    out, x = _run_over_slabs(config, params, cache, x, attend, mask)
    return out, _last_token_logits(config, params, x, lengths), None


# jit: device-context — runs inside the engine's jitted dispatches
def paged_decode_step(
    config: LlamaConfig,
    params: Dict[str, jnp.ndarray],
    cache: Dict[str, jnp.ndarray],   # paged pool
    tokens: jnp.ndarray,             # [S] int32 — one new token per slot
    lengths: jnp.ndarray,            # [S] length INCLUDING the new token
    block_tables: jnp.ndarray,       # [S, M]
    freqs: jnp.ndarray,
    write_mask: Optional[jnp.ndarray] = None,  # [S] bool
    mesh=None,                       # tp mesh (fused kernel runs per
                                     # kv-head shard via shard_map)
    kernel: str = "fused",           # paged attention: fused | reference
):
    """Paged twin of :func:`decode_step`: the new token's KV scatters
    into its slot's current block (masked slots route to the null
    block), attention reads the live context through the tables — the
    decode (Tq=1, start=length-1) case of the :func:`_paged_attn`
    dispatch, so a mixed prefill+decode paged batch runs the same fused
    launch path end to end. Decode never allocates — the engine reserves
    each request's worst case (prompt + max_new_tokens) at admission, so
    this path cannot fail on pool pressure mid-flight."""
    slots = tokens.shape[0]
    positions = (lengths - 1).astype(jnp.int32)  # [S]
    if write_mask is None:
        write_mask = jnp.ones((slots,), dtype=bool)
    x = _embed(config, params, tokens)  # [S, H]
    attend = _paged_attend(
        config, freqs, positions, block_tables, positions, lengths,
        write_mask, mesh, kernel,
    )
    out, x = _run_over_slabs(config, params, cache, x, attend, None)
    x = _norm(config, x, params["final_norm"])
    return out, _logits(config, params, x), None


def _decode_attend(config, freqs, stacked, lengths, positions, write_mask,
                   mesh):
    """GQA's attend for one decode step over the dense layout, on normed
    [S, H]: the new row written into the stacked leaves at ``[layer, slot,
    position]`` in place, the layer's slab read where it lies
    (:func:`_decode_attn`)."""
    slots, max_len = stacked[0].shape[1:3]
    rows = jnp.arange(slots)
    # where the row goes: a negative position wraps as an index would, a
    # masked slot (riding along with lengths 0, or a logits-only rerun)
    # and a position past the end go out of bounds, where nothing is
    # written — the slot's rows keep every bit and nothing is read back
    write_pos = jnp.where(
        write_mask,
        jnp.where(positions < 0, positions + max_len, positions),
        max_len,
    )
    flash = _decode_flash_path(config, stacked[0], mesh)
    hit = jnp.arange(max_len)[None, :] == write_pos[:, None]  # [S, T]

    @jax.named_scope("cache_write")
    def write(stacked, layer, new):
        """stacked [L, S, max_len, ...], new [S, ...] (value leaves carry
        kv-head and head_dim axes, as the leaf packs them; scale leaves
        the kv-head axis), in place on the carry. The write follows the
        attention's reader. The kernel streams rows of a row-major
        stack, and there one scatter of S rows is in place: every head
        dim that fills 128-lane rows, alone or packed, on the chip. What
        is left to XLA's einsums (an int8 cache of narrow heads, a head
        dim that divides no lane row, a short cache, the CPU) wants the
        position axis minor-most, which is also how such a leaf lies on
        the chip; a scatter (or a row loop of dynamic_update_slice)
        makes XLA carry the stack row-major instead: the whole cache
        re-laid-out at the chunk's entry and exit behind two cache-sized
        temps, and every layer's slab sliced out and re-laid-out for the
        einsum. An elementwise select folded into the slab's
        dynamic_update_slice leaves the layout alone and runs in place:
        one pass over the slab the einsums read anyway."""
        new = new.astype(stacked.dtype)
        if flash[0]:
            return stacked.at[layer, rows, write_pos].set(new, mode="drop")
        slab = jax.lax.dynamic_index_in_dim(stacked, layer, 0, keepdims=False)
        mask = hit.reshape(hit.shape + (1,) * (slab.ndim - 2))
        slab = jnp.where(mask, new[:, None], slab)
        return jax.lax.dynamic_update_index_in_dim(stacked, slab, layer, 0)

    def attend(normed, weights, index, win, kv):
        q, k, v = _rotated_heads(config, normed, weights, freqs, positions)
        if len(kv) == 4:
            kc, vc, ks, vs = kv
            k_q, k_s = quantize_kv(k)
            v_q, v_s = quantize_kv(v)
            kc, ks = write(kc, index, k_q), write(ks, index, k_s)
            vc, vs = write(vc, index, v_q), write(vs, index, v_s)
            attn = _decode_attn_quant(
                config, q, kc, ks, vc, vs, lengths, index, mesh=mesh,
                window=win,
            )
            return attn, (kc, vc, ks, vs), None
        kc, vc = kv
        kc = write(kc, index, _pack_kv(k, kc))
        vc = write(vc, index, _pack_kv(v, vc))
        attn = _decode_attn(
            config, q, kc, vc, lengths, index, mesh=mesh, window=win
        )
        return attn, (kc, vc), None

    return attend


# jit: device-context — runs inside the engine's jitted dispatches
def decode_step(
    config: LlamaConfig,
    params: Dict[str, jnp.ndarray],
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,     # [S] int32 — one new token per slot
    lengths: jnp.ndarray,    # [S] current length INCLUDING the new token
    freqs: jnp.ndarray,
    write_mask: Optional[jnp.ndarray] = None,  # [S] bool; False = don't
                                               # touch this slot's cache
    mesh=None,                                 # tp mesh for the sharded
                                               # flash-decode kernel
):
    """One decode step for every slot: write the new token's KV, attend
    over the cache, return next-token logits [S, V]. ``write_mask``
    protects slots that are merely riding along (inactive, or
    logits-only reruns) from having their cache row clobbered.

    No copy of a cache slab is ever materialised: the stacked leaves
    ride the layer scan as CARRY (a while-loop carry is updated in
    place, where a scanned output is a fresh stacked buffer), the new
    row is written into the stack at ``[layer, slot, position]``, and
    the attention reads its layer's slab where it lies. The caller's jit
    donates the cache (the engine's chunk does, and carries it across its
    steps), so the returned leaves are the argument's buffers."""
    slots = tokens.shape[0]
    positions = (lengths - 1).astype(jnp.int32)  # [S]
    if write_mask is None:
        write_mask = jnp.ones((slots,), dtype=bool)
    kinds = _kinds(config, cache)
    stacked = _carried(config, cache)
    x = _embed(config, params, tokens)  # [S, H]
    windows = layer_windows(config)
    if kinds.attention == "hybrid":
        attend = hybrid.decode_attends(
            config, freqs, lengths, positions, write_mask, cache["k"].shape[3]
        )
        x, valid = x[:, None], None
    elif kinds.attention == "latent":
        attend = latent_moe.decode_attend(
            config, freqs, stacked[0], lengths, positions, write_mask
        )
        # the family's attends are written over [B, T, h]: a step is T = 1;
        # slots that ride along (inactive, lengths 0) are routed nowhere
        x, valid = x[:, None], write_mask[:, None]
    elif kinds.attention == "conv":
        attend = short_conv.decode_attends(
            config, write_mask, _decode_attend(
                config, freqs, stacked[1:], lengths, positions, write_mask,
                mesh,
            ),
        )
        valid = write_mask  # a riding slot is routed to no expert
    else:
        attend = _decode_attend(
            config, freqs, stacked, lengths, positions, write_mask, mesh
        )
        # decode groups are tiny (S = slots) so dropless capacity is cheap;
        # inactive slots can't evict anyone, so no valid mask is needed
        valid = None
    x, stacked, _, counters = _run_layers(
        config, _layers_of(config, params), x, attend, state=stacked,
        per_layer=windows, valid=valid,
    )
    out, counters = _uncarried(config, cache, stacked, counters)
    x = _norm(config, x.reshape(slots, -1), params["final_norm"])
    return out, _logits(config, params, x), counters


# jit: device-context — runs inside the engine's jitted dispatches
def verify_step(
    config: LlamaConfig,
    params: Dict[str, jnp.ndarray],
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,      # [S, B] int32 — last token + drafted block
    lengths: jnp.ndarray,     # [S] cache length INCLUDING tokens[:, 0]
    valid_lens: jnp.ndarray,  # [S] real tokens in the block (1 + drafted;
                              # 0 = inactive row)
    freqs: jnp.ndarray,
    write_mask: Optional[jnp.ndarray] = None,  # [S] bool
    mesh=None,
):
    """Speculative verify: :func:`decode_step` generalized to a [S, B]
    token block per slot. Teacher-forces the block at each slot's
    current position (tokens[:, 0] is the pending token whose KV row a
    plain decode step would write, tokens[:, 1:] are drafted
    candidates), writes KV for every real block position, attends
    causally over prefix + block, and returns logits for EVERY position
    [S, B, V] — the acceptance pass needs the distribution at each
    candidate, not just the last one (which is why this is not
    :func:`prefill_at_offset`). Writes are per-position masked scatters
    (OOB dropped), so rejected-suffix rollback is a pure length rewind:
    positions past the accepted length hold garbage that is causally
    invisible until a later step overwrites them in order."""
    slots, seq = tokens.shape
    offsets = (lengths - 1).astype(jnp.int32)                # [S]
    positions = offsets[:, None] + jnp.arange(seq)[None, :]  # [S, B] global
    mask = jnp.arange(seq)[None, :] < valid_lens[:, None]    # [S, B] valid
    totals = offsets + valid_lens                            # [S]
    if write_mask is None:
        write_mask = jnp.ones((slots,), dtype=bool)
    wmask = mask & write_mask[:, None]
    x = _embed(config, params, tokens)                       # [S, B, H]
    max_len = cache["k"].shape[2]
    rows = jnp.arange(slots)[:, None]
    family = dict(
        softcap=config.attn_logit_softcap, scale=_attn_scale(config)
    )
    # masked rows (inactive slot, padding beyond the drafted count, or a
    # carry that ran past max_seq_len) route out of bounds and drop —
    # a clamped dynamic_update_slice would silently overwrite live rows
    write_pos = jnp.where(wmask, positions, max_len)

    @jax.named_scope("cache_write")
    def write_rows(kc, new):
        return kc.at[rows, write_pos].set(
            new.astype(kc.dtype), mode="drop"
        )

    def attend(normed, weights, index, inputs, state):
        slabs, win = inputs
        q, k, v = _rotated_heads(config, normed, weights, freqs, positions)
        if len(slabs) == 4:
            kc, vc, ks, vs = slabs
            k_q, k_s = quantize_kv(k)
            v_q, v_s = quantize_kv(v)
            kc = write_rows(kc, k_q)
            ks = write_rows(ks, k_s)
            vc = write_rows(vc, v_q)
            vs = write_rows(vs, v_s)
            with jax.named_scope("attention"):
                attn = chunk_attention_quant(
                    q, kc, ks, vc, vs, offsets, totals, window=win, **family
                )
            return attn, state, (kc, vc, ks, vs)
        kc, vc = slabs
        kc = write_rows(kc, _pack_kv(k, kc))
        vc = write_rows(vc, _pack_kv(v, vc))
        with jax.named_scope("attention"):
            attn = chunk_attention(
                q, _unpack_kv(config, kc), _unpack_kv(config, vc), offsets,
                totals, window=win, **family,
            )
        return attn, state, (kc, vc)

    out, x = _run_over_slabs(config, params, cache, x, attend, mask)
    x = _norm(config, x, params["final_norm"])
    return out, _logits(config, params, x), None  # [S, B, V]


# jit: device-context — runs inside the engine's jitted dispatches
def paged_verify_step(
    config: LlamaConfig,
    params: Dict[str, jnp.ndarray],
    cache: Dict[str, jnp.ndarray],   # paged pool
    tokens: jnp.ndarray,             # [S, B] int32 block per slot
    lengths: jnp.ndarray,            # [S] length INCLUDING tokens[:, 0]
    valid_lens: jnp.ndarray,         # [S] real tokens (0 = inactive)
    block_tables: jnp.ndarray,       # [S, M]
    freqs: jnp.ndarray,
    write_mask: Optional[jnp.ndarray] = None,  # [S] bool
    mesh=None,
    kernel: str = "fused",
):
    """Paged twin of :func:`verify_step`: the candidate block's KV
    scatters into table-addressed blocks (masked/overflow rows route to
    the null block) and attention is the fused kernel's existing Tq>1
    prefill-at-offset formulation — no new kernel. Blocks were reserved
    worst-case at admission, so verify never allocates and rollback is
    a length-pointer rewind only."""
    slots, seq = tokens.shape
    offsets = (lengths - 1).astype(jnp.int32)
    positions = offsets[:, None] + jnp.arange(seq)[None, :]  # [S, B] global
    mask = jnp.arange(seq)[None, :] < valid_lens[:, None]
    totals = offsets + valid_lens
    if write_mask is None:
        write_mask = jnp.ones((slots,), dtype=bool)
    wmask = mask & write_mask[:, None]
    x = _embed(config, params, tokens)
    attend = _paged_attend(
        config, freqs, positions, block_tables, offsets, totals, wmask, mesh,
        kernel,
    )
    out, x = _run_over_slabs(config, params, cache, x, attend, mask)
    x = _norm(config, x, params["final_norm"])
    return out, _logits(config, params, x), None  # [S, B, V]


# jit: device-context — runs inside the engine's jitted dispatches
def paged_mixed_step(
    config: LlamaConfig,
    params: Dict[str, jnp.ndarray],
    cache: Dict[str, jnp.ndarray],   # paged pool
    tokens: jnp.ndarray,             # [S, W] int32 per-row new tokens
    offsets: jnp.ndarray,            # [S] existing valid rows per slot
    num_tokens: jnp.ndarray,         # [S] live new tokens (0 = idle row)
    block_tables: jnp.ndarray,       # [S, M]
    freqs: jnp.ndarray,
    write_mask: Optional[jnp.ndarray] = None,  # [S] bool
    mesh=None,
    kernel: str = "fused",
):
    """Unified mixed prefill+decode dispatch — ``decode_step`` and
    ``prefill_at_offset`` as ONE seam over per-row token counts
    (Sarathi-style chunked-prefill batching): a decode row carries its
    pending token (``offsets = length, num_tokens = 1``), an admitting
    row carries a ``prefill_chunk``-token window of its prompt
    (``offsets = taught-so-far``), an idle row carries nothing
    (``num_tokens = 0``). KV scatters through the block tables with
    per-position masking (padding/idle rows route to the null block —
    the :func:`paged_verify_step` machinery, which already proved this
    formulation token-exact against the split paths), attention runs
    the token-ragged fused launch (or the gather reference) through
    :func:`_paged_attn`, and ONE weight pass serves every row — the
    whole point: admitting a prompt costs decode riders a bounded
    mixed step, never a monolithic bucket-sized prefill dispatch.

    Returns (cache, logits [S, V], None) of each row's LAST live token —
    the only position the engine samples (decode rows sample their next
    token; an admitting row's sample is meaningful only on the window
    that completes its prompt; idle/mid-prefill rows are discarded)."""
    slots, width = tokens.shape
    positions = offsets[:, None] + jnp.arange(width)[None, :]  # [S, W]
    mask = jnp.arange(width)[None, :] < num_tokens[:, None]    # [S, W]
    totals = offsets + num_tokens                              # [S]
    if write_mask is None:
        write_mask = jnp.ones((slots,), dtype=bool)
    wmask = mask & write_mask[:, None]
    x = _embed(config, params, tokens)                         # [S, W, H]
    attend = _paged_attend(
        config, freqs, positions, block_tables, offsets, totals, wmask, mesh,
        kernel, q_lens=num_tokens,
    )
    out, x = _run_over_slabs(config, params, cache, x, attend, mask)
    x = _norm(config, x, params["final_norm"])
    last = x[
        jnp.arange(slots),
        jnp.clip(num_tokens - 1, 0, width - 1).astype(jnp.int32),
    ]  # [S, H] — each row's last live token
    return out, _logits(config, params, last), None  # [S, V]


# jit: device-context — runs inside the engine's jitted dispatches
def apply_layers(
    config: LlamaConfig,
    layer_inputs,          # stacked layer params (from _stack_layer_params),
                           # possibly a contiguous slice of the layers
    x: jnp.ndarray,        # [B, T, H] activations
    mask: Optional[jnp.ndarray],   # [B, T] valid-token mask or None
    freqs: jnp.ndarray,
    dropless: bool = False,
    layer_offset: int = 0,  # global index of layer_inputs[0] — keeps the
                            # sliding-window parity right for static
                            # layer slices
    windows: Optional[jnp.ndarray] = None,  # per-layer window sizes for
                            # THESE layers (overrides the config-derived
                            # slice — pipeline stages pass their pp-shard
                            # of layer_windows(), since a static offset
                            # cannot vary across SPMD stages)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The layer loop with the cache-free attend, over activations →
    (x, moe aux sum).

    Factored out of :func:`forward` so pipeline parallelism
    (``parallel.pipeline``) can run a *slice* of the layer stack as one
    pipeline stage."""
    batch, seq = x.shape[:2]
    positions = jnp.arange(seq)[None, :].repeat(batch, 0)
    if windows is None:
        windows = layer_windows(config)
        if windows is not None:
            n = jax.tree_util.tree_leaves(layer_inputs)[0].shape[0]
            windows = windows[layer_offset:layer_offset + n]

    def attend(normed, weights, index, win, state):
        q, k, v = _rotated_heads(config, normed, weights, freqs, positions)
        attn = prefill_attention(
            q, k, v, mask=mask,
            softcap=config.attn_logit_softcap, window=win,
            scale=_attn_scale(config),
        )
        return attn, state, None

    x, _, _, aux = _run_layers(
        config, (Run(layer_inputs),), x, attend, per_layer=windows,
        valid=mask, dropless=dropless, total=jnp.zeros((), dtype=jnp.float32),
    )
    return x, aux


# jit: device-context — runs inside the engine's jitted dispatches
def forward(
    config: LlamaConfig,
    params: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,   # [B, T]
    mask: Optional[jnp.ndarray] = None,  # [B, T] valid-token mask
    freqs: Optional[jnp.ndarray] = None,
    with_aux: bool = False,
    dropless: bool = False,
) -> jnp.ndarray:
    """Cache-free full-sequence forward → logits [B, T, V] (training /
    scoring path; serving uses :func:`prefill`/:func:`decode_step`).
    With ``with_aux`` also returns the mean MoE load-balancing loss.
    ``dropless=True`` selects the exact MoE regime (no token dropping) —
    use it when scoring a dropless-trained checkpoint; training keeps the
    capacity regime so the router feels the balance pressure."""
    if freqs is None:
        freqs = model_freqs(config)
    x = _embed(config, params, tokens)
    layer_inputs = _stack_layer_params(params, config)
    x, aux = apply_layers(config, layer_inputs, x, mask, freqs, dropless)
    x = _norm(config, x, params["final_norm"])
    logits = _logits(config, params, x)
    if with_aux:
        return logits, aux / max(config.num_layers, 1)
    return logits


# ---------------------------------------------------------------------- #
# HuggingFace checkpoint import
# ---------------------------------------------------------------------- #
def _hybrid_from_hf(hf_config) -> Dict[str, Any]:
    """The hybrid family's fields from a published ``minicpm_sala``
    config: the layers' kinds from ``mixer_types``, the muP scalings, the
    selection's sizes from ``sparse_config`` where the config carries one
    (MiniCPM4's otherwise). A switch the family's mixers do not compute
    is refused by its name."""
    wanted = dict(
        qk_norm=True, attn_use_rope=False, lightning_use_rope=True,
        use_output_gate=True, use_output_norm=True, attn_use_output_gate=True,
    )
    wrong = [
        f"{name}={getattr(hf_config, name, None)!r}"
        for name, value in wanted.items()
        if getattr(hf_config, name, value) != value
    ]
    if hf_config.lightning_nkv != hf_config.lightning_nh:
        wrong.append(f"lightning_nkv={hf_config.lightning_nkv!r}")
    if wrong:
        raise ValueError(
            "unsupported minicpm_sala switches: " + ", ".join(wrong)
        )
    kinds = {"minicpm4": "sparse", "lightning-attn": "lightning"}
    selection = getattr(hf_config, "sparse_config", None) or {}
    return dict(
        mixers=tuple(kinds[name] for name in hf_config.mixer_types),
        hybrid=HybridMixers(
            lightning_heads=hf_config.lightning_nh,
            lightning_head_dim=hf_config.lightning_head_dim,
            selection=Selection(**{
                key: int(selection[key])
                for key in Selection.__dataclass_fields__ if key in selection
            }),
        ),
        embedding_scale=float(hf_config.scale_emb),
        residual_scale=hf_config.scale_depth / math.sqrt(
            hf_config.num_hidden_layers
        ),
        logit_divisor=hf_config.hidden_size / hf_config.dim_model_base,
    )


def _short_conv_from_hf(hf_config) -> "LlamaConfig":
    """The short-convolution family's config from a published ``lfm2_moe``
    config: the layers' kinds from ``layer_types``, the filter's taps from
    ``conv_L_cache``, the sigmoid router with its selection bias. A switch
    the family does not compute is refused by its name. The head is tied
    (the family's convention) unless the config says otherwise."""
    wanted = dict(conv_bias=False, use_expert_bias=True)
    wrong = [
        f"{name}={getattr(hf_config, name, None)!r}"
        for name, value in wanted.items()
        if getattr(hf_config, name, value) != value
    ]
    kinds = {"conv": "conv", "full_attention": "attention"}
    wrong += [
        f"layer_types has {name!r}"
        for name in sorted(set(hf_config.layer_types) - set(kinds))
    ]
    if wrong:
        raise ValueError("unsupported lfm2_moe switches: " + ", ".join(wrong))
    rope = getattr(hf_config, "rope_parameters", None) or {}
    return LlamaConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        head_dim=hf_config.hidden_size // hf_config.num_attention_heads,
        rope_theta=float(
            rope.get("rope_theta", getattr(hf_config, "rope_theta", 1e6))
        ),
        norm_eps=hf_config.norm_eps,
        max_seq_len=hf_config.max_position_embeddings,
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", True),
        mixers=tuple(kinds[name] for name in hf_config.layer_types),
        short_conv=ShortConv(taps=hf_config.conv_L_cache),
        experts=RoutedExperts(
            routed=hf_config.num_experts, held_first=0,
            held=hf_config.num_experts,
            intermediate_size=hf_config.moe_intermediate_size,
            per_token=hf_config.num_experts_per_tok, shared=0,
            leading_dense=hf_config.num_dense_layers, groups=1, groups_kept=1,
            scaling_factor=float(hf_config.routed_scaling_factor),
            routing="sigmoid_bias",
            renormalise=bool(hf_config.norm_topk_prob),
        ),
    )


def config_from_hf(hf_config) -> LlamaConfig:
    if getattr(hf_config, "model_type", "") == "lfm2_moe":
        return _short_conv_from_hf(hf_config)
    rope_scaling = normalize_rope_scaling(
        getattr(hf_config, "rope_scaling", None)
    )
    gemma2 = getattr(hf_config, "model_type", "") == "gemma2"
    if gemma2:
        # Gemma-2 alternates sliding/full starting at layer 0; verify
        # the checkpoint follows that pattern before baking it in
        layer_types = getattr(hf_config, "layer_types", None)
        if layer_types is not None:
            expected = [
                "sliding_attention" if i % 2 == 0 else "full_attention"
                for i in range(hf_config.num_hidden_layers)
            ]
            if list(layer_types) != expected:
                raise ValueError(
                    f"unsupported gemma2 layer_types pattern: {layer_types}"
                )
    family = {}
    if getattr(hf_config, "model_type", "") == "minicpm_sala":
        family = _hybrid_from_hf(hf_config)
    if getattr(hf_config, "model_type", "") == "qwen2":
        family = dict(qkv_bias=True)
    if gemma2:
        family = dict(
            attn_logit_softcap=getattr(
                hf_config, "attn_logit_softcapping", None
            ),
            final_logit_softcap=getattr(
                hf_config, "final_logit_softcapping", None
            ),
            query_pre_attn_scalar=float(
                getattr(hf_config, "query_pre_attn_scalar", 0) or 0
            ) or None,
            sliding_window=getattr(hf_config, "sliding_window", 0) or 0,
            norm_plus_one=True,
            post_norms=True,
            scale_embedding=True,
            act="gelu_tanh",
        )
    return LlamaConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=getattr(hf_config, "num_key_value_heads", hf_config.num_attention_heads),
        head_dim=getattr(hf_config, "head_dim", None),
        rope_theta=getattr(hf_config, "rope_theta", 10000.0),
        norm_eps=hf_config.rms_norm_eps,
        max_seq_len=hf_config.max_position_embeddings,
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        num_experts=getattr(hf_config, "num_local_experts", 0) or 0,
        num_experts_per_tok=getattr(hf_config, "num_experts_per_tok", 2),
        rope_scaling=rope_scaling,
        **family,
    )


def load_hf_checkpoint(path_or_model, dtype=jnp.bfloat16):
    """Convert a HuggingFace Llama checkpoint (local path or loaded torch
    model) into (LlamaConfig, stacked-params pytree).

    The per-layer torch tensors are stacked along a leading layer axis to
    match the lax.scan layout. Linear weights transpose (torch stores
    [out, in]; we use [in, out] so forward is x @ W).
    """
    import torch

    if isinstance(path_or_model, str):
        from transformers import AutoModelForCausalLM

        model = AutoModelForCausalLM.from_pretrained(
            path_or_model, torch_dtype=torch.float32, local_files_only=True
        )
    else:
        model = path_or_model
    config = config_from_hf(model.config)
    config = dataclasses.replace(config, dtype=dtype)
    state = model.state_dict()

    def get(name):
        return jnp.asarray(state[name].to(torch.float32).numpy(), dtype=dtype)

    def stack(pattern, transpose=True):
        # cast each layer to the target dtype BEFORE stacking so transient
        # host memory is one float32 layer, not the whole float32 stack
        arrays = []
        for layer in range(config.num_layers):
            tensor = state[pattern.format(layer)].to(torch.float32).numpy()
            arrays.append(jnp.asarray(tensor.T if transpose else tensor, dtype=dtype))
        return jnp.stack(arrays)

    if config.num_experts:
        # Mixtral layout: block_sparse_moe.experts.{e}.w1/w3/w2 + gate
        def stack_experts(weight):
            # per-expert dtype cast before stacking: transient host memory
            # is one float32 expert matrix, not layers × experts of them
            arrays = []
            for layer in range(config.num_layers):
                per_expert = [
                    jnp.asarray(
                        state[
                            f"model.layers.{layer}.block_sparse_moe"
                            f".experts.{e}.{weight}.weight"
                        ].to(torch.float32).numpy().T,
                        dtype=dtype,
                    )
                    for e in range(config.num_experts)
                ]
                arrays.append(jnp.stack(per_expert))
            return jnp.stack(arrays)

        mlp_weights = {
            "w_gate": stack_experts("w1"),
            "w_up": stack_experts("w3"),
            "w_down": stack_experts("w2"),
            "router": stack("model.layers.{}.block_sparse_moe.gate.weight"),
        }
    else:
        mlp_weights = {
            "w_gate": stack("model.layers.{}.mlp.gate_proj.weight"),
            "w_up": stack("model.layers.{}.mlp.up_proj.weight"),
            "w_down": stack("model.layers.{}.mlp.down_proj.weight"),
        }
    def stack_norm(pattern):
        return jnp.asarray(
            np.stack([
                state[pattern.format(i)].to(torch.float32).numpy()
                for i in range(config.num_layers)
            ]), dtype=jnp.float32,
        )

    if config.post_norms:
        # Gemma-2 sandwich norms: input_layernorm is the pre-attn norm,
        # post_attention_layernorm the POST-attn one (applied to the
        # block output before the residual add), and the feedforward
        # pair wraps the MLP the same way
        norms = {
            "attn_norm": stack_norm("model.layers.{}.input_layernorm.weight"),
            "post_attn_norm": stack_norm(
                "model.layers.{}.post_attention_layernorm.weight"
            ),
            "mlp_norm": stack_norm(
                "model.layers.{}.pre_feedforward_layernorm.weight"
            ),
            "post_mlp_norm": stack_norm(
                "model.layers.{}.post_feedforward_layernorm.weight"
            ),
        }
    else:
        norms = {
            "attn_norm": stack_norm("model.layers.{}.input_layernorm.weight"),
            "mlp_norm": stack_norm(
                "model.layers.{}.post_attention_layernorm.weight"
            ),
        }
    params = {
        "embedding": get("model.embed_tokens.weight"),
        "wq": stack("model.layers.{}.self_attn.q_proj.weight"),
        "wk": stack("model.layers.{}.self_attn.k_proj.weight"),
        "wv": stack("model.layers.{}.self_attn.v_proj.weight"),
        "wo": stack("model.layers.{}.self_attn.o_proj.weight"),
        **mlp_weights,
        **norms,
        **(
            {
                "bq": stack_norm("model.layers.{}.self_attn.q_proj.bias"),
                "bk": stack_norm("model.layers.{}.self_attn.k_proj.bias"),
                "bv": stack_norm("model.layers.{}.self_attn.v_proj.bias"),
            }
            if config.qkv_bias else {}
        ),
        "final_norm": jnp.asarray(
            state["model.norm.weight"].to(torch.float32).numpy(),
            dtype=jnp.float32,
        ),
    }
    if not config.tie_embeddings:
        params["lm_head"] = get("lm_head.weight").T
    return config, params
