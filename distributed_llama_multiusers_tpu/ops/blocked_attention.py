"""Attention at more than one row a lane against a long plane or a ring, a
block of keys at a time.

``models/llama.py`` ``_dense_attention`` materialises ``[B, T, heads, S]``
float32 scores: for one 512-row chunk of one lane, 128 heads and 32768 keys
that is 8.6 GB. Here the keys are read ``BLOCK_KEYS`` rows at a time out of the
stacked cache as the layer loop carries it, with a running maximum, sum and
value accumulator in float32 (the online softmax of ops/pallas_attention.py,
in ``jax.numpy``), over THE BLOCKS THE MASK ADMITS ONLY: the loop's bounds are
computed from the positions, so a chunk at start ``p`` against a 32768-row
plane visits the blocks up to ``p + T`` and nothing after them. No tensor has
both a ``T`` and an ``S`` axis.

One rule says which position a row holds, for a plane and a ring alike (the
ring's rule, models/hybrid.py's header): the cache has ``R`` rows a lane,
position ``p`` lives in row ``p mod R``, and a reader at position ``t`` takes
row ``r`` to hold the largest ``p <= t`` congruent to ``r``; it reads the row
where ``p >= 0`` and, with a window ``W``, where ``t - W < p``. A full-context
layer's plane is the case ``R = seq_len``, no window: ``p = r`` for ``r <= t``
and negative after it, which is the causal mask.

It engages by shape (``engages``): where the dense scores would exceed
``DENSE_SCORE_BYTES``. Every 2048-position configuration stays under it: a
plain plane's chunk there takes the same walk as a Pallas kernel over the
in-place stack (ops/pallas_attention.py ``prefill_attention``, where
``llama.prefill_attention_engages``), else ``_dense_attention``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_KEYS = 256
# float32 scores [B, T, heads, rows] above this take the blocked path. The
# largest dense tensor of the 2048-position configurations is 268 MB (a
# 1024-row chunk, 32 heads); a 256-row chunk against a 4608-row ring at 128
# heads is 604 MB
DENSE_SCORE_BYTES = 512 << 20


# A chunk's attention over a plane grows with its start and its matmuls do
# not, and every decoding lane waits a fused step through: on a v5e, 128 heads
# of 128, a 512-row fused step reads 93 ms at a start of 0, 106 at 8192 and 118
# at 28160, a 256-row one 74 to 80 past 8192. A chunk whose first row stands at
# or past this many positions of a plane read by key blocks takes the bucket
# under the largest (``taper_start``; runtime/engine.py ``max_chunk``): the
# longest gaps between a lane's tokens are then those of the chunks that start
# just under it, which every long prompt has, and not those of the one longest
# prompt's last chunks (PERF.md section 6, PR 47)
TAPER_KEYS = 8192


def engages(b: int, t: int, n_heads: int, rows: int) -> bool:
    """Whether attention of ``t`` query rows a lane against ``rows`` cache rows
    is computed a key block at a time: more than one row a lane, and dense
    scores over the stated size."""
    return t > 1 and 4 * b * t * n_heads * rows > DENSE_SCORE_BYTES


def taper_start(buckets, n_heads: int, plane_rows: int) -> int | None:
    """The start from which a prompt chunk takes the second-largest bucket:
    ``TAPER_KEYS`` where the largest bucket's attention over a plane of
    ``plane_rows`` is computed by key blocks and the plane is longer than
    that; None where every chunk takes the largest (a ladder of one rung, a
    plane whose scores are dense: every 2048-position configuration)."""
    if len(buckets) < 2 or plane_rows <= TAPER_KEYS:
        return None
    return TAPER_KEYS if engages(1, buckets[-1], n_heads, plane_rows) else None


def held_position(t, rows, ring: int):
    """The position a reader at ``t`` takes row ``r`` to hold: the largest
    ``p <= t`` congruent to ``r`` modulo ``ring`` (negative: none yet).
    ``t`` ``[..., 1]`` against ``rows`` ``[n]`` (broadcast)."""
    return t - jnp.mod(t - rows, ring)


def ring_mask(positions, ring: int, window: int = 0):
    """``[B, T, ring]``: the rows a query at ``positions`` reads, by
    arithmetic alone (module header). ``window`` 0: every ``p <= t``."""
    t = positions[:, :, None]
    p = held_position(t, jnp.arange(ring, dtype=positions.dtype), ring)
    ok = p >= 0
    return ok & (p > t - window) if window else ok


def chunk_blocks(first: int, last: int, ring: int, window: int = 0,
                 block: int = BLOCK_KEYS) -> tuple[int, int]:
    """``[j0, j1)``: the key blocks a chunk whose real rows stand at positions
    ``first .. last`` visits. Up to the block that holds ``last`` (every block
    once the positions have wrapped), from the block that holds the oldest
    position the first row reads. Traced scalars (``chunk_block_counts`` says
    the same in plain integers, for the host)."""
    n_blocks = -(-ring // block)
    wrapped = last >= ring
    lo = jnp.maximum(first - window + 1, 0) if window else 0
    j0 = jnp.where(wrapped, 0, lo // block)
    j1 = jnp.where(wrapped, n_blocks, last // block + 1)
    return j0, j1


def blocked_attention(q, k_all, v_all, layer, positions, n_valid, n_kv: int,
                      scale: float, window: int = 0, block: int = BLOCK_KEYS,
                      chosen=None, chosen_block: int = 0, sink=None):
    """GQA attention of ``q`` ``[B, T, n_heads, hd]`` over layer ``layer`` of
    the stacks ``k_all`` / ``v_all`` ``[L, B, R, n_kv * hd]`` (a plane, ``R =
    seq_len``, or a ring), read AFTER the chunk's rows were written.
    ``positions`` ``[B, T]``; ``n_valid`` ``[B]``: a lane's leading real rows
    (the others compute nothing anyone reads and bound no loop). ``chosen``
    ``[B, T, n_kv, S / chosen_block]`` (a block-sparse layer, ops/block_sparse.py):
    the blocks of ``chosen_block`` positions a row's kv head reads, beside the
    causal mask; a key block holds whole such blocks. The value stack may hold
    heads of another width ``vd`` (``[L, B, R, n_kv * vd]``). ``sink``
    ``[n_heads]`` float32 (None: none): a logit a head that joins the softmax
    as one more column and gives no value; the running maximum starts at it
    and the sum at 1. Returns ``[B, T, n_heads, vd]`` float32."""
    b, t, n_heads, hd = q.shape
    ring, kv_dim, v_dim = k_all.shape[2], k_all.shape[3], v_all.shape[3]
    vd = v_dim // n_kv
    group = n_heads // n_kv
    block = min(block, ring)
    q5 = q.astype(k_all.dtype).reshape(b, t, n_kv, group, hd)
    real = n_valid > 0
    first = jnp.min(jnp.where(real, positions[:, 0], jnp.iinfo(jnp.int32).max))
    last = jnp.max(jnp.where(
        real, jnp.take_along_axis(positions, jnp.maximum(n_valid - 1, 0)[:, None], axis=1)[:, 0], 0))
    j0, j1 = chunk_blocks(first, last, ring, window, block)
    j1 = jnp.where(jnp.any(real), j1, j0)  # no real row anywhere: nothing to visit
    t3 = positions[:, :, None]  # [B, T, 1]
    stat = (b, t, n_kv, group)

    def visit(j, carry):
        m, l, acc = carry
        # the last block of a count that is no whole blocks starts early, and
        # the rows it shares with the block before it are left out
        start = jnp.minimum(j * block, ring - block)
        kb = jax.lax.dynamic_slice(k_all, (layer, 0, start, 0), (1, b, block, kv_dim))
        vb = jax.lax.dynamic_slice(v_all, (layer, 0, start, 0), (1, b, block, v_dim))
        kb = kb.reshape(b, block, n_kv, hd)
        vb = vb.reshape(b, block, n_kv, vd)
        s = jnp.einsum("btkgh,bskh->btkgs", q5, kb,
                       preferred_element_type=jnp.float32) * scale
        rows = start + jnp.arange(block, dtype=jnp.int32)
        p = held_position(t3, rows, ring)  # [B, T, block]
        ok = (p >= 0) & (rows >= j * block)
        if window:
            ok = ok & (p > t3 - window)
        ok = ok[:, :, None, None, :]
        if chosen is not None:
            per = block // chosen_block
            mine = jax.lax.dynamic_slice_in_dim(chosen, start // chosen_block, per, axis=3)
            ok = ok & jnp.repeat(mine, chosen_block, axis=3)[:, :, :, None, :]
        s = jnp.where(ok, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)  # a row that has met no key yet
        alpha = jnp.exp(m - m_safe)
        pr = jnp.exp(s - m_safe[..., None])
        l = alpha * l + jnp.sum(pr, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "btkgs,bskh->btkgh", pr.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    if sink is None:
        m0, l0 = jnp.full(stat, -jnp.inf, jnp.float32), jnp.zeros(stat, jnp.float32)
    else:  # the sink's column, met first
        m0 = jnp.broadcast_to(sink.astype(jnp.float32).reshape(n_kv, group), stat)
        l0 = jnp.ones(stat, jnp.float32)
    init = (m0, l0, jnp.zeros((*stat, vd), jnp.float32))
    _, l, acc = jax.lax.fori_loop(j0, j1, visit, init)
    out = jnp.where(l[..., None] > 0.0, acc / jnp.maximum(l, 1e-30)[..., None], 0.0)
    return out.reshape(b, t, n_heads, vd)


def chunk_block_counts(start: int, n_rows: int, bucket: int, ring: int, window: int = 0,
                       block: int = BLOCK_KEYS) -> tuple[int, int]:
    """Host side, for the engine's counters, of one layer: ``(visited,
    causal)`` in (query row, key block) pairs. ``visited``: the bucket's rows
    times the blocks the loop runs for a chunk of ``n_rows`` real rows at
    ``start``; ``causal``: for every real row, the blocks that hold a position
    it reads, which is the least any schedule computes."""
    block = min(block, ring)
    last = start + n_rows - 1
    if last >= ring:  # chunk_blocks, in plain integers
        j0, j1 = 0, -(-ring // block)
    else:
        j0, j1 = (max(start - window + 1, 0) if window else 0) // block, last // block + 1
    t = np.arange(start, start + n_rows, dtype=np.int64)
    lo = np.maximum(t - window + 1, 0) if window else np.zeros_like(t)
    # the blocks of the ring that hold rows lo % ring .. t % ring, wrapped
    n_blocks = -(-ring // block)
    a, c = (t % ring) // block, (lo % ring) // block
    held = np.where(lo % ring <= t % ring, a - c + 1, np.minimum(n_blocks - c + a + 1, n_blocks))
    held = np.where(t - lo + 1 >= ring, n_blocks, held)
    return int(bucket * (j1 - j0)), int(held.sum())
