"""Rotary position embeddings: half-split ("rotate-half") pairs with the
Llama-3 long-context remap or YaRN frequencies with cos and sin scaled
(models/stacks.py's full layers), and interleaved pairs with YaRN
frequencies (models/mla.py).

Angles are computed in float32 from integer positions (not accumulated), so
decode steps at large positions stay exact. Cos/sin are computed on the fly —
they are cheap VPU work that XLA fuses into the surrounding ops, which beats
materializing a [max_seq, head_dim] table in HBM.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def rope_cos_sin(
    positions: jnp.ndarray,
    head_dim: int,
    theta: float,
    scaling: tuple | None = None,
):
    """Cos/sin for rotary embedding.

    positions: int array [...]. Returns (cos, sin) of shape [..., head_dim//2]
    in float32. `scaling` is the llama3 long-context frequency remap as a
    tuple (factor, low_freq_factor, high_freq_factor,
    original_max_position_embeddings) — the convention Llama 3.1/3.2
    checkpoints ship in config.json rope_scaling; None = plain RoPE.
    """
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if scaling is not None:
        inv_freq = _llama3_scaled_inv_freq(inv_freq, *scaling)
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def _llama3_scaled_inv_freq(
    inv_freq: jnp.ndarray,
    factor: float,
    low_freq_factor: float,
    high_freq_factor: float,
    original_max_position: float,
):
    """Llama-3.1 'llama3' rope_type: long wavelengths (relative to the
    original training context) are slowed by `factor`, short ones kept, and
    the band between low/high_freq_factor blends smoothly."""
    wavelen = 2.0 * jnp.pi / inv_freq
    low_wavelen = original_max_position / low_freq_factor
    high_wavelen = original_max_position / high_freq_factor
    # smooth ramp: 0 at low boundary → 1 at high boundary
    smooth = (original_max_position / wavelen - low_freq_factor) / (
        high_freq_factor - low_freq_factor
    )
    smooth = jnp.clip(smooth, 0.0, 1.0)
    blended = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
    return jnp.where(
        wavelen > low_wavelen,
        inv_freq / factor,
        jnp.where(wavelen < high_wavelen, inv_freq, blended),
    )


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Apply rotary embedding.

    x: [..., H, head_dim]; cos/sin: [..., head_dim//2] (broadcast over H).
    """
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max_position: float, beta_fast: float,
                  beta_slow: float) -> jnp.ndarray:
    """YaRN frequencies [head_dim // 2] in float32: a pair that turns
    more than ``beta_fast`` times over the original context keeps its
    frequency, one that turns fewer than ``beta_slow`` times is slowed by
    ``factor`` (interpolated), and a linear ramp over the pair index
    joins the two."""

    def pair_that_turns(rotations: float) -> float:
        return (head_dim * math.log(original_max_position / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_that_turns(beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0
    )
    return inv_freq / factor * ramp + inv_freq * (1.0 - ramp)


def yarn_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float, yarn: tuple):
    """Cos/sin [..., head_dim // 2] in float32 under YaRN; ``yarn`` is
    ``ModelConfig.rope_yarn``. Both are scaled by mscale's ratio, the
    convention of the DeepSeek-V3 code the ``mscale_all_dim`` key comes
    from (1 when the two are equal)."""
    factor, original_max, beta_fast, beta_slow, mscale, mscale_all_dim = yarn
    inv_freq = yarn_inv_freq(head_dim, theta, factor, original_max, beta_fast, beta_slow)
    ratio = _yarn_mscale(factor, mscale) / _yarn_mscale(factor, mscale_all_dim)
    return _scaled_cos_sin(positions, inv_freq, ratio)


def _scaled_cos_sin(positions, inv_freq, scale: float):
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def yarn_scaled_cos_sin(positions: jnp.ndarray, head_dim: int, theta: float, yarn: tuple):
    """Cos/sin [..., head_dim // 2] in float32 under YaRN as transformers
    applies it (``rope_type`` "yarn"); ``yarn`` is
    ``ModelConfig.rope_full_yarn``: (factor, original_max_position_embeddings,
    beta_fast, beta_slow, attention_factor). Both are times
    ``attention_factor``, so q.k of two rows rotated by them carries its
    square and no softmax scale knows of it."""
    factor, original_max, beta_fast, beta_slow, attention_factor = yarn
    inv_freq = yarn_inv_freq(head_dim, theta, factor, original_max, beta_fast, beta_slow)
    return _scaled_cos_sin(positions, inv_freq, attention_factor)


def yarn_softmax_scale(qk_head_dim: int, yarn: tuple | None) -> float:
    """The factor on q·k: ``qk_head_dim ** -0.5``, times YaRN's
    ``mscale_all_dim`` magnitude squared (once for q, once for k)."""
    scale = qk_head_dim ** -0.5
    if yarn is not None and yarn[5]:
        scale *= _yarn_mscale(yarn[0], yarn[5]) ** 2
    return scale


def apply_rope_interleaved(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotary embedding over the pairs (2i, 2i+1) of the last axis, in
    place: x [..., H, head_dim]; cos/sin [..., head_dim//2] (broadcast
    over H)."""
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)
