"""Rotary position embeddings, interleaved-pair convention.

The `.m` format stores Q/K weights pre-permuted to the interleaved-rotary
layout (converter/convert-hf.py:11-14), and the reference rotates adjacent
pairs (x[2i], x[2i+1]) per head using a precomputed cos/sin cache
(src/nn/nn-cpu-ops.cpp:1091-1120, cache built in src/nn/nn-core.cpp:323-340).
This module reproduces that exactly, including Llama-3.1 frequency scaling
(src/nn/nn-core.cpp:307-321).

The cache covers the full head dim (TP slicing is expressed through sharding
annotations instead of the reference's per-node qShift windows).
"""

from __future__ import annotations

import math

import numpy as np
import jax.numpy as jnp


def _scale_frequency_llama3(
    freq: float,
    scaling_factor: float,
    low_freq_factor: float,
    high_freq_factor: float,
    orig_max_seq_len: int,
) -> float:
    # src/nn/nn-core.cpp:307-321
    wave_len = 2.0 * math.pi / freq
    high_freq_wavelen = orig_max_seq_len / high_freq_factor
    if wave_len < high_freq_wavelen:
        return freq
    low_freq_wavelen = orig_max_seq_len / low_freq_factor
    if wave_len > low_freq_wavelen:
        return freq / scaling_factor
    smooth = (orig_max_seq_len / wave_len - low_freq_factor) / (high_freq_factor - low_freq_factor)
    return (1 - smooth) * freq / scaling_factor + smooth * freq


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor for a context stretched ``factor`` times."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(head_size: int, rope_theta: float, factor: float,
                     beta_fast: float, beta_slow: float, orig_max_seq_len: int) -> np.ndarray:
    """YaRN's frequencies of the ``head_size // 2`` pairs: the plain ones
    ``theta^(-2p/head)`` where a pair turns more than ``beta_fast`` times over
    the original context, those over ``factor`` where it turns fewer than
    ``beta_slow`` times, a linear blend over the pairs between (the
    "correction range", floor and ceiling of the pair at which a turn count
    is reached, clamped to the head)."""
    half = head_size // 2
    base = 1.0 / (rope_theta ** (2.0 * np.arange(half, dtype=np.float64) / head_size))

    def pair_at(turns: float) -> float:
        return head_size * math.log(orig_max_seq_len / (turns * 2.0 * math.pi)) / (
            2.0 * math.log(rope_theta))

    low = max(math.floor(pair_at(beta_fast)), 0)
    high = min(math.ceil(pair_at(beta_slow)), head_size - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return base * (1.0 - ramp) + (base / factor) * ramp


def build_rope_cache(
    seq_len: int,
    head_size: int,
    rope_theta: float = 10000.0,
    scaling_factor: float = 1.0,
    low_freq_factor: float = 0.0,
    high_freq_factor: float = 0.0,
    orig_max_seq_len: int = 0,
    dtype=np.float32,
    yarn: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Returns (cos, sin), each [seq_len, head_size // 2], float32.

    Frequencies follow the reference: pair p (elements 2p, 2p+1 of a head)
    uses theta^(-2p/head_size) (src/nn/nn-core.cpp:328-333).
    """
    if yarn:
        # the four scaling numbers are YaRN's factor, beta_slow, beta_fast and
        # original context; the rotation itself is not scaled
        freqs = yarn_frequencies(head_size, rope_theta, scaling_factor,
                                 high_freq_factor, low_freq_factor, orig_max_seq_len)
        t = np.arange(seq_len, dtype=np.float64)[:, None] * freqs[None, :]
        return np.cos(t).astype(dtype), np.sin(t).astype(dtype)
    half = head_size // 2
    freqs = np.empty(half, dtype=np.float64)
    apply_scaling = scaling_factor != 1.0
    for p in range(half):
        freq = 1.0 / (rope_theta ** ((2 * p) / head_size))
        if apply_scaling:
            freq = _scale_frequency_llama3(
                freq, scaling_factor, low_freq_factor, high_freq_factor, orig_max_seq_len
            )
        freqs[p] = freq
    t = np.arange(seq_len, dtype=np.float64)[:, None] * freqs[None, :]
    return np.cos(t).astype(dtype), np.sin(t).astype(dtype)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """Rotate interleaved pairs.

    x: [B, T, n_heads, head_size]; cos/sin: [seq_len, head_size//2];
    positions: [B, T] int32. Returns same shape/dtype as x.
    """
    b, t, h, d = x.shape
    xf = x.astype(jnp.float32).reshape(b, t, h, d // 2, 2)
    x0 = xf[..., 0]
    x1 = xf[..., 1]
    c = cos[positions][:, :, None, :]  # [B, T, 1, d/2]
    s = sin[positions][:, :, None, :]
    r0 = x0 * c - x1 * s
    r1 = x0 * s + x1 * c
    out = jnp.stack([r0, r1], axis=-1).reshape(b, t, h, d)
    return out.astype(x.dtype)


def apply_rope_first(x: jnp.ndarray, n: int, cos: jnp.ndarray, sin: jnp.ndarray,
                     positions: jnp.ndarray) -> jnp.ndarray:
    """``apply_rope`` on the first ``n`` numbers of every head of ``x``
    ``[B, T, H, D]`` (``cos`` / ``sin`` ``[seq_len, n // 2]``), the rest as
    they are; the whole head where ``n`` is its width."""
    if n >= x.shape[-1]:
        return apply_rope(x, cos, sin, positions)
    return jnp.concatenate([apply_rope(x[..., :n], cos, sin, positions), x[..., n:]], axis=-1)
