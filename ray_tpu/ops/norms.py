"""Normalization ops."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm in fp32 accumulation regardless of input dtype.

    The variance is computed in float32 (bf16 squares underflow), the scale
    applied in the input dtype so the op fuses into the adjacent matmul.
    """
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms).astype(dtype) * weight


def layernorm(x: jax.Array, weight: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    """LayerNorm with weight and bias: mean and variance in float32 as
    ``rmsnorm``'s mean square is, the scale and the shift applied in the
    input dtype."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv).astype(dtype) * weight + bias
