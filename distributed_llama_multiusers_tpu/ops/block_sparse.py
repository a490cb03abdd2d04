"""Which blocks of its keys a row of a block-sparse GQA layer attends
(InfLLM-v2, ``LayerKind.SPARSE``): the compressed keys, the score pass over
them, the pooling to blocks, and the exact choice.

Beside a layer's K and V planes a lane keeps one COMPRESSED key a kv head
every ``stride`` positions: ``ck_j`` is the mean of the cached keys over
positions ``[stride * j, stride * j + size)``, written once its last row
exists (``append_compressed``), across chunk boundaries: a step recomputes
every kernel that ENDS among its real rows from the planes as they stand
after the step's own append, so a kernel whose rows arrived in two chunks is
the same as one whose rows arrived in one. The stack is ``[sparse layers,
lanes, S / stride, n_kv * head]`` in the cache's type; like the planes it is
kept by position and never cleared: a reader takes kernel ``j`` to exist
where it ends at or before the reader's own position, by arithmetic.

A query row at position ``t`` (``block_scores``): per query head the softmax,
over the kernels that end at or before ``t``, of ``q . ck_j / sqrt(head)``;
summed over the heads of a kv head's group; a block's score the largest over
the kernels that overlap it. ``choose``: the first ``init_blocks`` blocks and
the blocks of the newest ``window`` positions (the row's own block the last
of them) score ``+inf``; the ``topk`` highest are the row's set, equal scores
to the lower block (a count of the blocks that rank before: exact, never
``approx_max_k``), a set a kv head; a row under ``dense_len`` takes every block it holds. The set is a
mask ``[B, T, n_kv, blocks]``; at one row a lane ``chosen_list`` turns it into
the decode kernel's work list (ops/pallas_attention.py
``sparse_decode_attention``): the chosen blocks in rising order, the row's
own block last.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# query rows whose scores over every compressed key are alive at once
SCORE_ROWS = 128


class SparseSizes(NamedTuple):
    """The sizes of a block-sparse layer, in positions but ``topk`` and
    ``init_blocks`` (blocks)."""

    kernel_size: int
    kernel_stride: int
    block_size: int
    topk: int
    window: int
    init_blocks: int
    dense_len: int

    @staticmethod
    def of(cfg) -> "SparseSizes":
        return SparseSizes(
            cfg.sparse_kernel_size, cfg.sparse_kernel_stride, cfg.sparse_block_size,
            cfg.sparse_topk, cfg.sparse_window, cfg.sparse_init_blocks, cfg.sparse_dense_len)

    def list_blocks(self, seq_len: int) -> int:
        """The most blocks a row attends: ``topk``, or every block under
        ``dense_len``; never more than the context has."""
        return min(max(self.topk, self.dense_len // self.block_size),
                   seq_len // self.block_size)


def blocks_attended(pos, sizes: SparseSizes):
    """(blocks a row at position ``pos`` attends, blocks it holds): host
    integers (numpy: a position or an array of them), for the engine's
    counters."""
    held = np.asarray(pos) // sizes.block_size + 1
    return np.where(np.asarray(pos) < sizes.dense_len, held, np.minimum(held, sizes.topk)), held


def append_compressed(ck_all, k_all, pi, ai, positions, n_valid, sizes: SparseSizes):
    """The compressed keys of sparse layer ``pi`` that end among this step's
    real rows, from layer ``ai`` of the key planes ``k_all`` ``[A, lanes, S,
    width]`` AFTER the step's append; ``positions`` ``[B, T]``, ``n_valid``
    ``[B]``. Returns the stack ``ck_all`` ``[P, lanes, S / stride, width]``."""
    size, stride = sizes.kernel_size, sizes.kernel_stride
    b, t = positions.shape
    seq, width = k_all.shape[2], k_all.shape[3]
    n_ck = ck_all.shape[2]
    first = positions[:, 0]
    last = first + n_valid - 1  # the lane's last real position
    # the first kernel that ends at or after the lane's first row
    j_lo = jnp.maximum((first - size + stride) // stride, 0)
    j = j_lo[:, None] + jnp.arange(t // stride + 1, dtype=jnp.int32)[None, :]  # [B, nj]
    end = j * stride + size - 1
    ok = (n_valid[:, None] > 0) & (end <= last[:, None]) & (end < seq)
    rows = j[:, :, None] * stride + jnp.arange(size, dtype=jnp.int32)  # [B, nj, size]
    lane = jnp.arange(b, dtype=jnp.int32)[:, None, None]
    # a plane's row in the stack seen as rows (layer, lane, position): a view
    at = (ai * k_all.shape[1] + lane) * seq + jnp.minimum(rows, seq - 1)
    kernel = k_all.reshape(-1, width)[at].astype(jnp.float32).mean(axis=2)  # [B, nj, width]
    return ck_all.at[pi, lane[:, :, 0], jnp.where(ok, j, n_ck)].set(
        kernel.astype(ck_all.dtype), mode="drop")


def _pool_to_blocks(p, sizes: SparseSizes, n_blocks: int):
    """``[..., kernels]`` -> ``[..., blocks]``: the largest over the kernels
    that overlap a block (kernel ``j`` holds ``[stride j, stride j + size)``)."""
    per_block = sizes.block_size // sizes.kernel_stride
    reach = sizes.kernel_size // sizes.kernel_stride - 1  # kernels that start before a block and reach it
    pad = [(0, 0)] * (p.ndim - 1) + [(reach, per_block)]
    padded = jnp.pad(p, pad)
    out = None
    for i in range(per_block + reach):
        part = jax.lax.slice_in_dim(padded, i, i + per_block * n_blocks, per_block, axis=p.ndim - 1)
        out = part if out is None else jnp.maximum(out, part)
    return out


def block_scores(q, ck_all, pi, positions, n_kv: int, sizes: SparseSizes, scale: float):
    """``r`` ``[B, T, n_kv, blocks]`` float32 of the module header, before the
    forced blocks: q ``[B, T, n_heads, hd]`` against sparse layer ``pi`` of
    the compressed keys. A block no complete kernel overlaps scores 0."""
    b, t, n_heads, hd = q.shape
    n_ck = ck_all.shape[2]
    n_blocks = n_ck * sizes.kernel_stride // sizes.block_size
    ck = jax.lax.dynamic_index_in_dim(ck_all, pi, 0, keepdims=False)  # [B, n_ck, width]
    group = n_heads // n_kv
    # block-diagonal queries, as the decode kernel's: a head's values in its
    # kv head's columns of a merged row, zeros in the others, so that the keys
    # are read as they lie (a product a kv head wants them kv-head-major, and
    # XLA copied the whole stack into that layout every layer)
    own = (jnp.arange(n_heads)[:, None] // group == jnp.arange(n_kv)[None, :])[:, :, None]
    q_wide = jnp.where(own, q.astype(ck.dtype)[:, :, :, None, :], 0).reshape(
        b, t, n_heads, n_kv * hd)
    ends = jnp.arange(n_ck, dtype=jnp.int32) * sizes.kernel_stride + sizes.kernel_size - 1

    def rows(q_rows, pos_rows):
        s = jnp.einsum("bthw,bjw->bthj", q_rows, ck,
                       preferred_element_type=jnp.float32) * scale
        ok = (ends[None, None, :] <= pos_rows[:, :, None])[:, :, None, :]
        s = jnp.where(ok, s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(ok, jnp.exp(s - jnp.where(m == -jnp.inf, 0.0, m)), 0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        p = p.reshape(b, -1, n_kv, group, n_ck).sum(axis=3)
        return _pool_to_blocks(p, sizes, n_blocks)  # [B, rows, n_kv, blocks]

    qb = min(SCORE_ROWS, t)
    if t == qb or t % qb:
        return rows(q_wide, positions)
    cut = lambda a, i: jax.lax.dynamic_slice_in_dim(a, i, qb, axis=1)
    r = jax.lax.map(lambda i: rows(cut(q_wide, i), cut(positions, i)), jnp.arange(0, t, qb))
    return jnp.moveaxis(r, 0, 1).reshape(b, t, n_kv, n_blocks)


def held_blocks(positions, n_blocks: int, sizes: SparseSizes):
    """``[B, T, 1, blocks]``: the blocks that hold a position a row may read."""
    blk = jnp.arange(n_blocks, dtype=jnp.int32)
    return (blk[None, None, :] <= (positions // sizes.block_size)[:, :, None])[:, :, None, :]


def choose(r, positions, sizes: SparseSizes):
    """The set of the module header as a mask ``[B, T, n_kv, blocks]``: r from
    ``block_scores``."""
    n_blocks = r.shape[-1]
    blk = jnp.arange(n_blocks, dtype=jnp.int32)
    own = (positions // sizes.block_size)[:, :, None, None]  # the row's own block
    held = held_blocks(positions, n_blocks, sizes)
    forced = (blk < sizes.init_blocks) | (blk > own - sizes.window // sizes.block_size)
    r = jnp.where(held, jnp.where(forced, jnp.inf, r), -jnp.inf)
    # a block's rank among its row's: the blocks that score higher, and the
    # equal ones before it. Exact, equal scores to the lower block, and one
    # fused compare-and-count: ``jax.lax.top_k`` and the scatter of its indices
    # into a mask took 19 of a 144 ms fused step at 512 rows on a v5e (PERF.md
    # section 6, PR 50)
    mine, other = r[..., :, None], r[..., None, :]
    before = (other > mine) | ((other == mine) & (blk[None, :] < blk[:, None]))
    picked = jnp.sum(before, axis=-1, dtype=jnp.int32) < sizes.topk
    dense = (positions < sizes.dense_len)[:, :, None, None]
    return held & (picked | dense)


def chosen_list(chosen, positions, seq_len: int, sizes: SparseSizes):
    """The decode kernel's work list from the mask of a step of one row a
    lane (``chosen`` ``[B, 1, n_kv, blocks]``): ``(count [B * n_kv], blocks
    [B * n_kv, list_blocks], pos [B])``: a (lane, kv head)'s blocks in rising
    order, so that the row's own block is the last; nothing for a parked
    lane (position past the context)."""
    b, _, n_kv, n_blocks = chosen.shape
    pos = positions.reshape(b).astype(jnp.int32)
    live = (pos >= 0) & (pos < seq_len)
    mask = chosen[:, 0] & live[:, None, None]  # [B, n_kv, blocks]
    order = jnp.argsort(~mask, axis=-1, stable=True)[..., : sizes.list_blocks(seq_len)]
    count = jnp.sum(mask, axis=-1).astype(jnp.int32)
    return count.reshape(b * n_kv), order.reshape(b * n_kv, -1).astype(jnp.int32), pos
