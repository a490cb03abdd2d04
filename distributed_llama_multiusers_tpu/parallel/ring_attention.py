"""Sequence-parallel attention: blockwise LSE-combine and ring attention.

The reference has NO sequence parallelism — its KV cache is sharded only via
TP (kvDim) and attention is a serial per-head loop over 0..pos
(src/nn/nn-cpu-ops.cpp:749-784, SURVEY.md §5.7). Long context is therefore a
capability this framework adds, designed TPU-first:

- ``sp_attention``: the KV cache stays sharded along S over the ``sp`` mesh
  axis. Every device computes flash-style partial softmax stats (running
  max m, normalizer l, weighted value sum o) over ITS sequence block, then
  one tiny psum over sp combines the stats — no all-gather of the cache,
  communication is O(heads * head_size), independent of S. Works for decode
  (T=1) and for prefill with queries replicated over sp.

- ``ring_attention``: for sequence-sharded QUERIES (long-prompt prefill /
  training), KV blocks rotate around the sp ring via lax.ppermute while
  each device accumulates flash stats for its query block — classic ring
  attention (Liu et al. 2023), causal-masked. Communication overlaps with
  block compute; peak memory is O(S/sp) per device.

Both are shard_map programs over the (dp, tp, sp) mesh of parallel/mesh.py;
the dp and tp axes are embarrassingly parallel here (lanes, kv-head groups)
and carry no collectives.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _block_stats(q, k, v, mask):
    """Flash-attention partial stats for one KV block.

    q: [B, T, K, G, H] f32; k/v: [B, S_blk, K, H] f32; mask: [B, T, S_blk].
    Returns (o [B,T,K,G,H], l [B,T,K,G], m [B,T,K,G]) with the convention
    m = -inf and o = l = 0 for fully-masked query rows."""
    scores = jnp.einsum("btkgh,bskh->btkgs", q, k)
    scores = jnp.where(mask[:, :, None, None, :], scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)  # [B,T,K,G], -inf when all masked
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - m_safe[..., None])  # exp(-inf) = 0 on masked slots
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("btkgs,bskh->btkgh", p, v)
    return o, l, m


def _merge_stats(o1, l1, m1, o2, l2, m2):
    """Combine two flash partial-stat triples (order-invariant)."""
    m = jnp.maximum(m1, m2)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    w1 = jnp.where(l1 > 0, jnp.exp(jnp.where(jnp.isfinite(m1), m1, 0.0) - m_safe), 0.0)
    w2 = jnp.where(l2 > 0, jnp.exp(jnp.where(jnp.isfinite(m2), m2, 0.0) - m_safe), 0.0)
    o = o1 * w1[..., None] + o2 * w2[..., None]
    l = l1 * w1 + l2 * w2
    return o, l, m


def _finalize(o, l):
    return o / jnp.maximum(l, 1e-30)[..., None]


def sp_attention(
    q: jnp.ndarray,  # [B, T, n_kv, group, hd] (pre-scaled by caller or scale=)
    k_cache: jnp.ndarray,  # [B, S, n_kv, hd]
    v_cache: jnp.ndarray,  # [B, S, n_kv, hd]
    positions: jnp.ndarray,  # [B, T] int32 (query positions; mask is s <= pos)
    mesh: Mesh,
    scale: float,
) -> jnp.ndarray:
    """GQA attention over an S-sharded KV cache. Returns [B, T, n_kv, group, hd]
    f32, replicated over sp. One psum of flash stats crosses the sp axis."""
    n_sp = mesh.shape["sp"]
    s_total = k_cache.shape[1]
    s_blk = s_total // n_sp

    def inner(q, k, v, pos):
        # local S block: [B, s_blk, K/tp, H]; q replicated over sp
        start = jax.lax.axis_index("sp") * s_blk
        s_idx = start + jax.lax.broadcasted_iota(jnp.int32, (1, 1, s_blk), 2)
        mask = s_idx <= pos[:, :, None]  # [B, T, s_blk]
        o, l, m = _block_stats(q * scale, k, v, mask)

        # combine across sp: numerically exact psum of rescaled stats
        m_glob = jax.lax.pmax(m, "sp")
        m_safe = jnp.where(jnp.isfinite(m_glob), m_glob, 0.0)
        w = jnp.where(
            l > 0, jnp.exp(jnp.where(jnp.isfinite(m), m, 0.0) - m_safe), 0.0
        )
        o = jax.lax.psum(o * w[..., None], "sp")
        l = jax.lax.psum(l * w, "sp")
        return _finalize(o, l)

    return shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            P("dp", None, "tp", None, None),  # q
            P("dp", "sp", "tp", None),  # k
            P("dp", "sp", "tp", None),  # v
            P("dp", None),  # positions
        ),
        out_specs=P("dp", None, "tp", None, None),
        check_vma=False,
    )(q.astype(jnp.float32), k_cache.astype(jnp.float32), v_cache.astype(jnp.float32), positions)


def ring_attention(
    q: jnp.ndarray,  # [B, T, n_kv, group, hd] — T sharded over sp
    k: jnp.ndarray,  # [B, T, n_kv, hd]       — T sharded over sp
    v: jnp.ndarray,  # [B, T, n_kv, hd]
    mesh: Mesh,
    scale: float,
) -> jnp.ndarray:
    """Causal self-attention with sequence-sharded queries AND keys: KV blocks
    rotate around the sp ring (lax.ppermute) for n_sp steps while each device
    folds flash stats for its query block. Returns [B, T, n_kv, group, hd]
    f32 with the same sp sharding as q."""
    n_sp = mesh.shape["sp"]
    t_total = q.shape[1]
    t_blk = t_total // n_sp

    def inner(q, k, v):
        my = jax.lax.axis_index("sp")
        q_start = my * t_blk
        q_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, (1, t_blk, 1), 1)
        qf = q * scale
        perm = [(i, (i + 1) % n_sp) for i in range(n_sp)]

        def fold(o, l, m, kr, vr, r):
            # kr/vr originated on device (my - r) % n_sp
            src = (my - r) % n_sp
            k_idx = src * t_blk + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, t_blk), 2
            )
            mask = k_idx <= q_idx  # causal: key pos <= query pos
            o2, l2, m2 = _block_stats(qf, kr, vr, mask)
            return _merge_stats(o, l, m, o2, l2, m2)

        def step(carry, r):
            o, l, m, kr, vr = carry
            o, l, m = fold(o, l, m, kr, vr, r)
            kr = jax.lax.ppermute(kr, "sp", perm)
            vr = jax.lax.ppermute(vr, "sp", perm)
            return (o, l, m, kr, vr), None

        b, _, n_kv, g, hd = q.shape
        o0 = jnp.zeros((b, t_blk, n_kv, g, hd), jnp.float32)
        l0 = jnp.zeros((b, t_blk, n_kv, g), jnp.float32)
        m0 = jnp.full((b, t_blk, n_kv, g), -jnp.inf, jnp.float32)
        # n_sp - 1 fold+rotate steps, then fold the last received block with
        # no trailing rotation (its result would be discarded)
        (o, l, m, kr, vr), _ = jax.lax.scan(
            step, (o0, l0, m0, k, v), jnp.arange(n_sp - 1)
        )
        o, l, m = fold(o, l, m, kr, vr, jnp.int32(n_sp - 1))
        return _finalize(o, l)

    return shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            P("dp", "sp", "tp", None, None),
            P("dp", "sp", "tp", None),
            P("dp", "sp", "tp", None),
        ),
        out_specs=P("dp", "sp", "tp", None, None),
        check_vma=False,
    )(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
