"""RMS norm, and the norm that subtracts the mean.

Matches the reference's two-op split semantics (OP_INV_RMS computes
1/sqrt(mean(x^2)+eps) per row in f32, OP_RMS_NORM multiplies by the weight;
src/nn/nn-cpu-ops.cpp:105-180) as a single fused op — XLA fuses the reduction
and the scale into one VPU pass anyway.
"""

from __future__ import annotations

import jax.numpy as jnp
import jax


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """x: [..., dim]; weight: [dim]. Reduction in float32 regardless of x dtype."""
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jnp.ndarray, gain: jnp.ndarray, bias: jnp.ndarray | None = None,
               eps: float = 1e-5) -> jnp.ndarray:
    """``gain * (x - mean) / sqrt(var + eps) [+ bias]`` over the last axis,
    float32 whatever x is: the indexer's key norm (gain and bias,
    models/deepseek.py) and a block whose norms subtract the mean
    (``NormKind.LAYER``: no bias, models/hybrid.py)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * gain
    return (y if bias is None else y + bias).astype(x.dtype)
