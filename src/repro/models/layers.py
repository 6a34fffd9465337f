"""Shared neural-net layers: norms, rotary/sinusoidal positions, gated MLPs."""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5,
             plus_one: bool = False) -> jnp.ndarray:
    """RMSNorm in f32 (gemma-style ``(1 + w)`` scaling when plus_one)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    w = 1.0 + w if plus_one else w
    return (normed * w).astype(x.dtype)


def gated_rms_norm(x: jnp.ndarray, gate: jnp.ndarray, weight: jnp.ndarray,
                   eps: float = 1e-5) -> jnp.ndarray:
    """Mamba-2's norm: RMSNorm(x * silu(gate)) fused before out_proj."""
    return rms_norm(x * jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype),
                    weight, eps)


# --- positions -----------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_range(head_dim: int, theta: float, yarn) -> Tuple[int, int]:
    """(low, high): the frequency indices between which YaRN's ramp runs
    from the original frequencies to the ``factor``-interpolated ones."""
    def dim(rotations):
        return (head_dim * math.log(yarn.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim(yarn.beta_fast)), 0)
    high = min(math.ceil(dim(yarn.beta_slow)), head_dim - 1)
    return low, high


def yarn_freqs(head_dim: int, theta: float, yarn) -> jnp.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies (``DeepseekV2YarnRotary
    Embedding``): extrapolated below ``low``, interpolated by ``factor``
    above ``high``, a linear ramp between."""
    extra = rope_freqs(head_dim, theta)
    low, high = yarn_range(head_dim, theta, yarn)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / yarn.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               yarn=None) -> jnp.ndarray:
    """NeoX-style half-rotation.  x: (..., S, D_head); positions: (..., S).
    ``yarn``: a ``YarnConfig``, for YaRN's frequencies and cos/sin scale."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta) if yarn is None else yarn_freqs(d, theta, yarn)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    """MusicGen-style sinusoidal embeddings.  positions: (..., S) -> (..., S, D)."""
    half = d_model // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)


# --- MLPs ------------------------------------------------------------------------


def gated_mlp(x: jnp.ndarray, wi: jnp.ndarray, wg: jnp.ndarray, wo: jnp.ndarray,
              act: str = "silu") -> jnp.ndarray:
    """SwiGLU (silu) / GeGLU (gelu): wo( act(x·wg) * (x·wi) )."""
    h = jnp.einsum("...d,df->...f", x, wi.astype(x.dtype))
    g = jnp.einsum("...d,df->...f", x, wg.astype(x.dtype))
    g = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g, approximate=True)
    return jnp.einsum("...f,fd->...d", g * h, wo.astype(x.dtype))


# --- init -------------------------------------------------------------------------


def dense_init(key, shape, in_axis: int = -2, dtype=jnp.float32) -> jnp.ndarray:
    """Truncated-normal fan-in init (the framework's only initializer)."""
    fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
    std = (1.0 / max(1, fan_in)) ** 0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def softcap(logits: jnp.ndarray, cap: float) -> jnp.ndarray:
    if cap <= 0.0:
        return logits
    return cap * jnp.tanh(logits / cap)
