"""Shared building blocks: RMSNorm, RoPE (plain and YaRN), SwiGLU,
initializers."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, gain: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * gain.astype(jnp.float32)).astype(dt)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature, ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, theta: float, yarn: tuple) -> jax.Array:
    """DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding`` (arXiv:2309.00071):
    frequencies that turn more than ``beta_fast`` times over the original
    context keep their value, those that turn fewer than ``beta_slow`` times
    are divided by ``factor``, and a linear ramp joins the two."""
    factor, original_max, beta_fast, beta_slow = yarn[:4]

    def correction_dim(rotations):
        return (head_dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = rope_freqs(head_dim, theta)
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn: Optional[tuple] = None) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq). ``yarn`` is
    ``ModelConfig.yarn``; None is plain rope."""
    dt = x.dtype
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta) if yarn is None \
        else yarn_freqs(hd, theta, yarn)                   # (hd/2,)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if yarn is not None:
        factor, mscale, mscale_all_dim = yarn[0], yarn[4], yarn[5]
        m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dt)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


def dense_init(key: jax.Array, shape, dtype, scale: float | None = None) -> jax.Array:
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / (fan_in ** 0.5)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
