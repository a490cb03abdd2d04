"""The plain reference: a Llama-family forward pass in jax.numpy and float32.

No kernels, no cache, no serving batch: a whole sequence goes through every
layer with full causal attention, matrix products at
``default_matmul_precision("highest")``. It follows the published description
of the Mistral / Qwen2 decoder block:

    h  = x + Wo . attention(rope(Wq n1 + bq), rope(Wk n1 + bk), Wv n1 + bv)
    x' = h + W2 . (silu(W1 n2) * (W3 n2)),   n = rms_norm(., g, eps)
    logits = Wcls . rms_norm(x_last, g_final, eps)

with grouped-query attention (each key/value head serves heads/kv_heads query
heads) and scores scaled by 1/sqrt(head size). Departures from the published
form: (1) the rotary embedding rotates adjacent pairs (2p, 2p+1) of a head, the
convention of the .m format, which equals the published half-split form under
the converter's permutation of Wq's and Wk's rows; on random weights the two
are the same distribution. (2) Weights are Q40: 32-input blocks of 4-bit
values with a float16 scale, value = (nibble - 8) * scale, dequantized here
one layer at a time so that a 7B model fits beside the engine.

It takes the benchmark's own seeded arrays (weights.py) and builds its own
RoPE tables. It imports nothing from the program: not models/llama.py, not
models/oracle.py, not ops/, not quants/.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q40_BLOCK = 32


def dequant_q40(packed, scales):
    """uint8 ``[d_in/2, d_out]`` + float16 ``[d_in/32, d_out]`` -> float32
    ``[d_in, d_out]``. Packed row r = 16 b + j holds input 32 b + j in its low
    nibble and input 32 b + j + 16 in its high nibble."""
    half = Q40_BLOCK // 2
    n_blk, d_out = scales.shape
    p = packed.astype(jnp.int32).reshape(n_blk, half, d_out)
    lo = (p & 0x0F) - 8
    hi = (p >> 4) - 8
    v = jnp.concatenate([lo, hi], axis=1).astype(jnp.float32)  # [n_blk, 32, d_out]
    w = v * scales.astype(jnp.float32)[:, None, :]
    return w.reshape(n_blk * Q40_BLOCK, d_out)


def rope_tables(n_pos: int, head_size: int, theta: float):
    """cos, sin ``[n_pos, head_size/2]``: pair p turns by pos * theta^(-2p/head)."""
    p = np.arange(head_size // 2, dtype=np.float64)
    freq = 1.0 / (float(theta) ** (2.0 * p / head_size))
    ang = np.arange(n_pos, dtype=np.float64)[:, None] * freq[None, :]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _rope(x, cos, sin):
    """x ``[B, T, H, D]``; rotate adjacent pairs by the position's angle."""
    b, t, h, d = x.shape
    xp = x.reshape(b, t, h, d // 2, 2)
    x0, x1 = xp[..., 0], xp[..., 1]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.stack([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1).reshape(b, t, h, d)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rounder(dtype_name):
    """Identity for the reference; for its control, every value a block hands
    on is rounded to the named type (and back to float32)."""
    if dtype_name is None:
        return lambda x: x
    dt = jnp.dtype(dtype_name)
    return lambda x: x.astype(dt).astype(jnp.float32)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps", "lossy"))
def _layer(x, lw, cos, sin, *, n_heads, n_kv, eps, lossy=None):
    """One decoder block over whole sequences. x ``[B, T, d]`` float32; ``lw``
    the layer's planes, norm weights and (Qwen2) biases. ``lossy`` names the
    type the control rounds to; the reference passes None."""
    r = _rounder(lossy)
    b, t, d = x.shape
    hd = d // n_heads
    w = {k: dequant_q40(*lw[k]) for k in ("wq", "wk", "wv", "wo", "w1", "w2", "w3")}
    n1 = r(_rms_norm(x, lw["rms_att"], eps))
    q, k, v = n1 @ w["wq"], n1 @ w["wk"], n1 @ w["wv"]
    if "bq" in lw:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q, k, v = r(q), r(k), r(v)
    q = _rope(q.reshape(b, t, n_heads, hd), cos, sin)
    k = _rope(k.reshape(b, t, n_kv, hd), cos, sin)
    v = v.reshape(b, t, n_kv, hd)
    g = n_heads // n_kv
    q = q.reshape(b, t, n_kv, g, hd)
    scores = jnp.einsum("btkgh,bskh->bkgts", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
    att = jnp.einsum("bkgts,bskh->btkgh", jax.nn.softmax(scores, axis=-1), v)
    h = r(x + r(att.reshape(b, t, d)) @ w["wo"])
    n2 = r(_rms_norm(h, lw["rms_ffn"], eps))
    return r(h + r(jax.nn.silu(n2 @ w["w1"]) * (n2 @ w["w3"])) @ w["w2"])


@jax.jit
def _head_chunk(y, packed, scales):
    return y @ dequant_q40(packed, scales)


def reference_logits(cfg: dict, t: dict, tokens, row_positions, chunk: int = 16384,
                     lossy: str | None = None):
    """Logits at ``row_positions`` ``[B, R]`` of each sequence.

    cfg: the configuration file's keys; t: the arrays of weights.py;
    tokens: int ``[B, T]``, shorter sequences padded at the end (attention is
    causal, so what follows a position cannot reach it). Returns float32 numpy
    ``[B, R, vocab]``. ``lossy`` (the control only) rounds what each block
    hands on to the named type, e.g. "float8_e4m3fn"."""
    tokens = jnp.asarray(tokens, jnp.int32)
    row_positions = jnp.asarray(row_positions, jnp.int32)
    n_heads, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = float(cfg["rms_norm_eps"])
    hd = cfg["hidden_size"] // n_heads
    cos, sin = rope_tables(tokens.shape[1], hd, cfg["rope_theta"])
    cos, sin = jnp.asarray(cos), jnp.asarray(sin)
    per_layer = ("rms_att", "rms_ffn", "bq", "bk", "bv")
    with jax.default_matmul_precision("highest"):
        x = t["embedding"][tokens].astype(jnp.float32)
        for layer in range(cfg["num_hidden_layers"]):
            lw = {
                k: (t[k].packed[layer], t[k].scales[layer])
                for k in ("wq", "wk", "wv", "wo", "w1", "w2", "w3")
            }
            lw.update({k: t[k][layer] for k in per_layer if k in t})
            x = _layer(x, lw, cos, sin, n_heads=n_heads, n_kv=n_kv, eps=eps, lossy=lossy)
        x = jnp.take_along_axis(x, row_positions[:, :, None], axis=1)
        y = _rounder(lossy)(_rms_norm(x, t["rms_final"], eps))
        packed, scales = t["wcls"].packed, t["wcls"].scales
        outs = []
        for lo in range(0, packed.shape[-1], chunk):
            outs.append(np.asarray(
                _head_chunk(y, packed[:, lo:lo + chunk], scales[:, lo:lo + chunk])
            ))
    return np.concatenate(outs, axis=-1)[..., : cfg["vocab_size"]]
