"""Rotary position embeddings (RoPE), Llama-3 style."""

from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp


def _llama3_scale_inv_freq(
    inv_freq: jnp.ndarray,
    factor: float,
    low_freq_factor: float,
    high_freq_factor: float,
    original_max_positions: float,
) -> jnp.ndarray:
    """Llama-3.1 NTK-by-parts frequency scaling (HF
    ``_compute_llama3_parameters``): high-frequency components keep
    their wavelength, low-frequency ones stretch by ``factor``, and the
    band between interpolates smoothly."""
    low_wavelen = original_max_positions / low_freq_factor
    high_wavelen = original_max_positions / high_freq_factor
    wavelen = 2.0 * math.pi / inv_freq
    scaled = inv_freq / factor
    smooth = (
        original_max_positions / wavelen - low_freq_factor
    ) / (high_freq_factor - low_freq_factor)
    smoothed = (1.0 - smooth) * scaled + smooth * inv_freq
    return jnp.where(
        wavelen < high_wavelen,
        inv_freq,
        jnp.where(wavelen > low_wavelen, scaled, smoothed),
    )


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature term: ``0.1 * mscale * ln(factor) + 1``
    for a factor above 1, else 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_softmax_scale(scaling: Optional[tuple]) -> float:
    """What YaRN multiplies the softmax scale by: ``mscale(factor,
    mscale_all_dim) ** 2`` (the DeepSeek-V2 recipe); 1 without YaRN."""
    if scaling is None or scaling[0] != "yarn":
        return 1.0
    return yarn_mscale(scaling[1], scaling[5]) ** 2


def _yarn_scale_inv_freq(
    head_dim: int,
    theta: float,
    factor: float,
    beta_fast: float,
    beta_slow: float,
    original_max_positions: float,
) -> jnp.ndarray:
    """YaRN (HF ``DeepseekV2YarnRotaryEmbedding``): dimensions that turn
    more than ``beta_fast`` times over the original context keep their
    frequency, those that turn fewer than ``beta_slow`` times are
    interpolated (divided by ``factor``), and a linear ramp over the
    dimension index blends the two between the correction dims."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    extrapolated = 1.0 / (theta ** exponents)
    interpolated = 1.0 / (factor * theta ** exponents)

    def correction_dim(rotations: float) -> float:
        return (
            head_dim
            * math.log(original_max_positions / (rotations * 2 * math.pi))
            / (2 * math.log(theta))
        )

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001  # the published guard against a zero-width ramp
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low),
        0.0, 1.0,
    )
    return interpolated * ramp + extrapolated * (1.0 - ramp)


def rope_frequencies(
    head_dim: int,
    max_positions: int,
    theta: float = 500000.0,
    dtype=jnp.float32,
    scaling: Optional[tuple] = None,
) -> jnp.ndarray:
    """Precomputed [max_positions, head_dim//2] complex angles as (cos, sin)
    stacked on a leading axis of size 2.

    ``scaling`` is the config's hashable rope-scaling tuple
    ``("llama3", factor, low_freq_factor, high_freq_factor,
    original_max_position_embeddings)`` — the Llama-3.1/3.2 long-context
    recipe — or ``("yarn", factor, beta_fast, beta_slow, mscale,
    mscale_all_dim, original_max_position_embeddings)``, which also
    scales cos and sin by ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``. None = plain RoPE."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    on_cos_sin = 1.0
    if scaling is not None:
        kind = scaling[0]
        if kind == "yarn":
            factor, beta_fast, beta_slow, mscale, all_dim, original = scaling[1:]
            inv_freq = _yarn_scale_inv_freq(
                head_dim, theta, factor, beta_fast, beta_slow, original
            )
            on_cos_sin = yarn_mscale(factor, mscale) / yarn_mscale(
                factor, all_dim
            )
        elif kind == "llama3":
            inv_freq = _llama3_scale_inv_freq(inv_freq, *scaling[1:])
        else:
            raise ValueError(f"unsupported rope scaling type: {kind!r}")
    positions = jnp.arange(max_positions, dtype=jnp.float32)
    angles = jnp.outer(positions, inv_freq)
    table = jnp.stack([jnp.cos(angles), jnp.sin(angles)])
    if on_cos_sin != 1.0:
        table = table * on_cos_sin
    return table.astype(dtype)


def apply_rope(
    x: jnp.ndarray,
    freqs: jnp.ndarray,
    positions: jnp.ndarray,
) -> jnp.ndarray:
    """Rotate ``x`` of shape [..., seq, heads, head_dim] by the angles at
    ``positions`` [..., seq]. Interleaved-pair convention (HF Llama's
    rotate_half layout: first half / second half)."""
    cos = freqs[0][positions]  # [..., seq, head_dim//2]
    sin = freqs[1][positions]
    cos = jnp.expand_dims(cos, axis=-2)  # broadcast over heads
    sin = jnp.expand_dims(sin, axis=-2)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)
