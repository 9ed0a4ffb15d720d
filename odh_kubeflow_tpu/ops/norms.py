"""Normalisation ops.

Computed in float32 regardless of activation dtype — RMS statistics in
bfloat16 lose enough precision to visibly hurt long-sequence training,
and XLA fuses the upcast into the surrounding elementwise graph anyway
(no extra HBM traffic).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """Mean-subtracting LayerNorm with a scale and no bias (Cohere's)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(dtype)
