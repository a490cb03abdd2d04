"""Attention over the stacked contiguous KV cache, read in place: at one query
row a lane (``decode_attention``, below), and at a prefill bucket's rows a lane
(``prefill_attention``: "More than one row a lane", at the end).

At one query row a lane (``t == 1``) attention is a read of the lane's keys
and values and little else. The dense path (``models/llama.py``
``_dense_attention``) slices layer ``l``'s whole K and V planes out of the
stacked cache, ``lanes x seq_len`` rows each whatever the lanes hold, and
masks afterwards. This kernel is handed the stack itself, the layer index and
the lanes' positions as scalar-prefetch operands, and fetches for lane ``b``
only the row blocks ``[0, ceil((pos_b + 1) / BLOCK_ROWS))`` of layer ``l``.

How the stack goes in. Two forms of K / V stack, told apart by rank, and
neither is moved (a third, a latent cache, is "Latent rows" below):

- ``[L, lanes, S, n_kv, hd]`` with ``hd == 128`` (models/llama.py), ``(n_kv,
  hd)`` tiled. Merging ``(S, n_kv)`` into one axis of ``S * n_kv`` rows of
  ``hd`` leaves every byte where it is (a bitcast for XLA, checked in
  tests/test_chip_compile_attention.py), and gives the kernel a plain ``[rows, hd]``
  matrix a block: row ``s * n_kv + h`` is key head ``h`` of position ``s``,
  ``n_kv`` rows a position.
- ``[A, lanes, S, n_kv * hd]`` (models/hybrid.py: heads narrower than a
  128-lane tile, kept merged so that the last axis is whole tiles) goes in as
  it sits: one row a POSITION, ``n_kv * hd`` wide, every kv head's values side
  by side. A reshape to ``[S * n_kv, hd]`` rows is no bitcast under the chip's
  tiling, and the kernel does not need it.

What a block computes. All query heads of the lane against all rows of the
block in one product, ``[heads, width] x [rows, width]^T``, and each head
meets its own kv head only:

- 128-wide heads: a row belongs to ONE kv head, so the columns of the other
  heads' rows are masked out by a constant bias (``-inf`` where ``col % n_kv
  != head // group``).
- merged rows: a row belongs to EVERY head, and the QUERIES are block
  diagonal: head ``h``'s ``hd`` values sit in the columns of its kv head,
  ``[(h // group) * hd, (h // group + 1) * hd)``, zeros elsewhere, so the
  other heads' columns add exact zeros to the f32 sum. The value product gives
  every head all ``n_kv * hd`` columns, of which it keeps its kv head's
  (``_own_columns``: a select, no product); the softmax runs over ``[heads,
  rows]`` scores, one column a position.

The MXU has the width to spare, and no head's rows or columns have to be
picked out of the tiles. Online softmax over the lane's blocks (running
maximum, sum and value accumulator in f32 scratch); both products take the
operands the dense path's einsums give the MXU at the TPU's default precision
(bf16, accumulated in f32), with the scale applied to the f32 scores instead
of to the queries, which stay exact.

What is fetched. The grid is one axis over a work list built from the
positions (``lane_blocks``): one item a block a lane holds, the lanes in
order, and as many steps as there are items. A grid over lanes x the most
blocks a lane can have would spend a step on every block a lane does NOT
have: 128 steps a call at 16 lanes where the chat mixes' lanes hold 37 blocks,
0.8 ms of a 2.7 ms step on a v5e (PERF.md section 6, PR 32). The K and V block
index maps read the item's lane and block from the list, so the pipeline
fetches the next item's block while this one is computed, across lanes. A
parked lane (position ``>= seq_len``: idle, or admitting through the fused
step's prefill half) is one item that computes nothing and whose index stays
on the block the pipeline already holds (the lane before it, or the first
block the next live lane needs): no fetch is issued for an index that did not
change, so it reads nothing, and it writes zeros.

A ring. A window layer (models/hybrid.py) keeps ``R < seq_len`` rows a lane,
position ``p`` in row ``p mod R``, and reads the ``W`` newest positions. Its
work list (``ring_blocks``) is the at most ``W / BLOCK_ROWS + 1`` blocks that
hold ``(pos - W, pos]``, in the order of their positions and wrapped into the
ring; the first and the last are masked BY POSITION (the first holds positions
at or under ``pos - W``, the last holds rows above ``pos`` that still carry
what was written ``R`` positions ago), so two more rows ride the plan: the
position of an item's first row and the oldest position the lane reads. The
kernel is the same; which list it was handed is a static fact of the plan's
shape, and a full-context layer's program is what it was.

A sink, and values of another width. A window layer may have a learned logit
a query head that joins its softmax as one more column and gives no value
(``model_type: mimo_v2_flash``): the lane's running maximum then starts at the
sink's logit and its sum at 1 where they otherwise start at ``-inf`` and 0, and
nothing else changes: the column is met first, exactly. A merged value stack
may hold heads narrower than the keys' (192-wide keys beside 128-wide values):
the K and V blocks, the output and the accumulator take their own widths, and
a key head that straddles 128-lane tiles costs nothing here, since the
block-diagonal product runs over the whole row.

Latent rows. A latent cache (models/deepseek.py) keeps ONE row a position
that every query head meets whole, in two leaves: the normed latent ``c'``
(``[L, lanes, S, rank]``) and its rotated rope part (``[L, lanes, S, rope
leaf]``, zero past the rope width), each whole 128-lane tiles. In the absorbed
form the key row is ``[c' ; k_pe]`` and the value row is ``c'`` itself, so a
block is

    s   = (q_abs . c_blk^T + q_pe . r_blk^T) * scale      [heads, rows]
    acc = online-softmax(s) . c_blk                       [heads, rank]

the plain product: no head bias, no block-diagonal queries, no select
afterwards. The queries go in side by side (``[q_abs ; q_pe]``, ``rank + rope
leaf`` wide), the two leaves as the K and the V operand, and the latent block,
fetched once, is keys and values at once: 1280 bytes a position a layer at
rank 512, not twice the latent. It is ``decode_attention`` itself under a
static ``latent`` and not a sibling: the work list, the parked-lane rule, the
mask by position, the zeroed stale rows and the online softmax are the
kernel's body, and the form changes three lines of it (two score products and
which block the probabilities meet). A rank-4 leaf does not say whether its
row is merged heads or a latent, so the caller says (``supports``). What the
caller keeps: ``q_abs = q_nope . wuk`` before and ``o = o~ . wuv`` after are
XLA's, a head at a time over weights, not over the cache.

Chosen blocks. A block-sparse layer (models/hybrid.py, ops/block_sparse.py)
reads, for every (lane, kv head), the blocks of ``block_size`` positions (64
as published) that the step chose from its compressed keys' scores: a third
work list, computed by the step and different for each kv head. A grid step a
chosen block would be 2048 steps a layer at 16 lanes, each fetching 16 KB.
``sparse_decode_attention`` is a kernel of its own instead: one grid step a
(lane, kv head), the planes left in HBM, the step's blocks (64 rows of the kv
head's 128 columns each) copied into one VMEM buffer by as many DMAs, started
a grid step ahead into the other half of a double buffer, and ONE softmax over
the buffer's rows. The list is in rising order, so the row's own block is the
last and the rows past the lane's position are the buffer's tail.

More than one row a lane. A prefill chunk (``T`` rows of one lane, whole blocks
of ``QUERY_ROWS``: the buckets 64 / 256 / 512 / 1024) is attended by
``prefill_attention`` over the same stacks, neither moved. The dense path
forms ``[T, heads, S]`` float32 scores over the lane's whole context, 268 MB a
layer for 1024 rows of 32 heads against 2048 positions, written to HBM and
read back three times: a quarter of a 7B prefill half (PERF.md section 6, PR
51). Here a grid step is one key block of ``BLOCK_ROWS`` positions against one
block of query rows for the kv heads of one UNIT, scores and probabilities a
``[rows x group, BLOCK_ROWS]`` tile in VMEM, with the decode kernel's online
softmax in f32 scratch across a query block's key blocks.

- The work list (``chunk_blocks``, built once a forward from the rows'
  positions and each lane's count of real rows): for every block of query
  rows the key blocks ``[0, hi // BLOCK_ROWS]``, ``hi`` the highest position
  of its real rows. A key block past the chunk's last real row, or wholly
  above a query block's rows, is no item; a query block of padded rows alone
  is one item that computes nothing, keeps the index the pipeline holds (the
  parked-lane rule) and writes zeros. Padded rows inside a live query block
  compute finite values nobody reads (the next layer's K/V rows are made of
  them, and a NaN there would reach a later dense read through ``0 x NaN``).
- GQA: a kv head's ``group`` query heads ride one product as more rows
  (``[rows x group, 128] x [BLOCK_ROWS, 128]^T``), so a K or V block is
  fetched once a unit and the MXU's latched tile meets ``group`` times the
  rows.
- The mask is by position (``key <= row's position``, the positions handed in
  lane-replicated as the running maximum is kept) and only in the blocks a
  query block's rows end in (``LAST``); every other block is ``FULL``. In a
  ``LAST`` block the value rows above ``hi`` are zeroed.
- Which heads a unit is. Merged rows: the kv heads of one 128-lane column
  tile (one of 128, two of 64), the block ``[BLOCK_ROWS, 128]`` as it sits;
  with heads under 128 wide the QUERIES are zero outside their kv head's
  columns of the tile (the decode kernel's block-diagonal queries, a tile
  wide) and a head keeps its columns of the value product. 128-wide heads on
  their own axis: with ``(S, n_kv)`` merged into rows, a position's kv heads
  are ``n_kv`` consecutive rows, and the chip packs bf16 rows ``2i`` and ``2i
  + 1`` into the two halves of one 32-bit sublane. Read as 32-bit words
  (``ref.bitcast``, no data moves) every ``n_kv / 2``-th word row from ``u``
  holds kv heads ``2u`` (low half) and ``2u + 1`` (high half) of every
  position of the block: one strided load, two shifts, and both heads'
  ``[BLOCK_ROWS, 128]`` matrices are there. Hence a unit of two, and an even
  ``n_kv`` (``supports_prefill``).
- The row sum stays a sum a LANE (whole-tile adds) until the query block's
  last item, where one reduction across lanes finishes it; only the running
  maximum is reduced across lanes every step. Reducing both every step was
  0.263 against 0.154 ms a layer for a 1024-row chunk on a v5e (PERF.md
  section 6, PR 51).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# cache rows (positions) a block; chosen on a v5e from the two 7B head shapes
# (n_kv 8 / group 4 at 16 lanes, n_kv 4 / group 7 at 32): PERF.md section 6
BLOCK_ROWS = 256
# ... of a latent cache, whose position is 1280 bytes at rank 512 where K and V
# rows of heads are 2 to 4 KB: an item of the work list costs 0.55 us and its
# rows the HBM's rate, so the taller block wins under the chat mix's positions
# (1.03 against 1.22 ms for 24 layers of 32 lanes on a v5e, and 1.55 at 1024
# rows: scripts/latent_attention_lab.py, PERF.md section 6, PR 59)
LATENT_BLOCK_ROWS = 512
HEAD_SIZE = 128  # one lane tile: the scratch statistics are [heads, 128]
# the widest merged row taken: 8 kv heads of 192 a position, a K block of 768
# KB (compiled for a v5e and run there, PR 54; 1024 before: the 128-wide
# form's largest block)
MAX_ROW_WIDTH = 1536
# an item's code in the work list (``lane_blocks``)
FULL, LAST, FIRST, FINAL = 1, 2, 4, 8


def block_rows(latent: bool = False) -> int:
    """Cache rows a block of ``decode_attention`` fetches, by the form of the
    cache: what a work list is built by (``lane_blocks``) and the scheduler's
    counter counts by (``rows_read``)."""
    return LATENT_BLOCK_ROWS if latent else BLOCK_ROWS


def supports(k_all, n_heads: int, n_kv: int | None = None, v_all=None,
             latent: bool = False) -> bool:
    """Whether the kernel takes this cache: a bf16 stack whose context is
    whole blocks, in one of the module header's three forms. A rank-4 leaf
    does not say what its row holds, so the caller says: ``n_kv``, the heads
    of a merged row, or ``latent``, one latent row and its rope part a
    position that every query head meets whole.

    - ``[L, lanes, S, n_kv, HEAD_SIZE]``: query heads a multiple of kv heads.
    - ``[A, lanes, S, n_kv * hd]``: rows of whole 128-lane tiles that the
      caller's ``n_kv`` heads divide, at most ``MAX_ROW_WIDTH`` wide.
      ``v_all``: the value stack where it may differ from the keys' (None:
      of ``k_all``'s shape): merged rows of another width (a value head
      narrower than a key head) are taken, under the same rule; anything
      else unlike the keys is not.
    - ``latent``: ``k_all`` ``[L, lanes, S, rank]`` and ``v_all`` ``[L, lanes,
      S, rope leaf]``, each whole 128-lane tiles, a block of the two together
      (``LATENT_BLOCK_ROWS`` positions) within the widest merged K block's
      bytes; no heads to divide it."""
    if k_all.dtype != jnp.bfloat16 or k_all.ndim not in (4, 5):
        return False
    if v_all is not None and v_all.shape != k_all.shape and (
            k_all.ndim != 4 or v_all.dtype != k_all.dtype or v_all.shape[:3] != k_all.shape[:3]):
        return False
    widths = {k_all.shape[-1], k_all.shape[-1] if v_all is None else v_all.shape[-1]}
    if latent:
        n_kv = 1  # every query head reads the one row
        tiled = (k_all.ndim == 4 and v_all is not None
                 and all(width % HEAD_SIZE == 0 for width in widths)
                 and (k_all.shape[3] + v_all.shape[3]) * LATENT_BLOCK_ROWS
                 <= MAX_ROW_WIDTH * BLOCK_ROWS)
    elif k_all.ndim == 5:
        n_kv = k_all.shape[3]
        tiled = k_all.shape[4] == HEAD_SIZE
    else:
        tiled = bool(n_kv) and all(
            width % HEAD_SIZE == 0 and width % n_kv == 0 and width <= MAX_ROW_WIDTH
            for width in widths)
    return tiled and k_all.shape[2] % block_rows(latent) == 0 and n_heads % n_kv == 0


def rows_read(positions, seq_len: int, block: int = BLOCK_ROWS) -> int:
    """Cache rows a layer's K (or V) fetch brings in for these lane positions:
    whole blocks up to each live lane's row, nothing for a parked lane. Host
    side (numpy), for the scheduler's counter."""
    pos = np.asarray(positions, np.int64)
    live = pos[(pos >= 0) & (pos < seq_len)]
    return int((block * (live // block + 1)).sum())


def lane_blocks(positions: jnp.ndarray, seq_len: int, block: int = BLOCK_ROWS):
    """The work list of a step, layer invariant and built once outside the
    layer scan: ``(n_items, plan)``. Item ``w < n_items`` is one block (of
    ``block`` positions: ``block_rows`` of the cache's form) of one
    lane, the lanes in order and each lane's blocks in order; a parked lane is
    one item that computes nothing. ``plan`` is ``int32 [5, lanes * blocks]``:
    the item's lane, the lane and block it fetches, the lane's position, and
    a code (``FULL`` or ``LAST`` block, ``FIRST`` / ``FINAL`` item of its
    lane). The grid has ``n_items`` steps: nothing is spent on the blocks a
    lane does not have."""
    pos = positions.reshape(-1).astype(jnp.int32)
    n_lanes = pos.shape[0]
    lanes = jnp.arange(n_lanes, dtype=jnp.int32)
    n = jnp.where((pos >= 0) & (pos < seq_len), pos // block + 1, 0)
    live = n > 0
    items = jnp.maximum(n, 1)
    end = jnp.cumsum(items)
    w = jnp.arange(n_lanes * (seq_len // block), dtype=jnp.int32)
    lane = jnp.minimum(jnp.searchsorted(end, w, side="right", method="compare_all"), n_lanes - 1)
    lane = lane.astype(jnp.int32)
    j = w - (end - items)[lane]
    # a parked lane stays on what the pipeline holds: the last block of the
    # nearest live lane before it, else the first block of the first live lane
    prev = jax.lax.cummax(jnp.where(live, lanes, -1))
    first = jnp.argmax(live).astype(jnp.int32)  # 0 when every lane is parked
    held_lane = jnp.where(prev >= 0, prev, first)
    held_block = jnp.where(prev >= 0, jnp.maximum(n[held_lane] - 1, 0), 0)
    src = jnp.where(live, lanes, held_lane)[lane]
    block = jnp.where(live[lane], j, held_block[lane])
    code = (
        jnp.where(live[lane], jnp.where(j == n[lane] - 1, LAST, FULL), 0)
        + jnp.where(j == 0, FIRST, 0)
        + jnp.where(j == items[lane] - 1, FINAL, 0)
    )
    return end[-1], jnp.stack([lane, src, block, pos[lane], code])


def ring_rows_read(positions, seq_len: int, window: int, block: int = BLOCK_ROWS) -> int:
    """``rows_read`` for a window layer's ring: whole blocks that hold
    ``(pos - window, pos]`` of each live lane. Host side (numpy)."""
    pos = np.asarray(positions, np.int64)
    live = pos[(pos >= 0) & (pos < seq_len)]
    lo = np.maximum(live - window + 1, 0)
    return int((block * (live // block - lo // block + 1)).sum())


def ring_blocks(positions: jnp.ndarray, seq_len: int, window: int, ring: int):
    """``lane_blocks`` for a window layer's ring of ``ring`` rows (whole
    blocks, at least ``window + BLOCK_ROWS``): a live lane's items are the
    blocks that hold positions ``(pos - window, pos]``, oldest first, each
    fetched at its place in the ring. ``plan`` is ``int32 [7, lanes *
    (window / BLOCK_ROWS + 2)]``: ``lane_blocks``' five rows (the block is the
    one FETCHED; the first and last item of a lane carry ``LAST``: masked),
    then the position of the item's first row and the oldest position the
    lane reads."""
    pos = positions.reshape(-1).astype(jnp.int32)
    n_lanes = pos.shape[0]
    ring_blocks_ = ring // BLOCK_ROWS
    lanes = jnp.arange(n_lanes, dtype=jnp.int32)
    is_live = (pos >= 0) & (pos < seq_len)
    lo = jnp.maximum(pos - window + 1, 0)
    first_block = lo // BLOCK_ROWS  # in the numbering of positions
    n = jnp.where(is_live, pos // BLOCK_ROWS - first_block + 1, 0)
    live = n > 0
    items = jnp.maximum(n, 1)
    end = jnp.cumsum(items)
    w = jnp.arange(n_lanes * (-(-window // BLOCK_ROWS) + 1), dtype=jnp.int32)
    lane = jnp.minimum(jnp.searchsorted(end, w, side="right", method="compare_all"), n_lanes - 1)
    lane = lane.astype(jnp.int32)
    j = w - (end - items)[lane]
    # a parked lane stays on what the pipeline holds (lane_blocks)
    prev = jax.lax.cummax(jnp.where(live, lanes, -1))
    first = jnp.argmax(live).astype(jnp.int32)
    held_lane = jnp.where(prev >= 0, prev, first)
    last_of = (first_block + jnp.maximum(n - 1, 0)) % ring_blocks_
    held_block = jnp.where(prev >= 0, last_of[held_lane], (first_block % ring_blocks_)[first])
    src = jnp.where(live, lanes, held_lane)[lane]
    at = first_block[lane] + j  # the item's block, in the numbering of positions
    block = jnp.where(live[lane], at % ring_blocks_, held_block[lane])
    edge = (j == 0) | (j == n[lane] - 1)
    code = (
        jnp.where(live[lane], jnp.where(edge, LAST, FULL), 0)
        + jnp.where(j == 0, FIRST, 0)
        + jnp.where(j == items[lane] - 1, FINAL, 0)
    )
    return end[-1], jnp.stack(
        [lane, src, block, pos[lane], code, at * BLOCK_ROWS, lo[lane]])


def _head_bias(n_heads: int, heads_pad: int, n_kv: int, rows: int) -> np.ndarray:
    """0 where a block's row (``col % n_kv`` is its kv head) belongs to the
    query head's group, ``-inf`` elsewhere and on the padding heads."""
    head = np.arange(heads_pad)[:, None]
    col = np.arange(rows)[None, :]
    own = (col % n_kv == head // (n_heads // n_kv)) & (head < n_heads)
    return np.where(own, 0.0, -np.inf).astype(np.float32)


def _own_columns(n_heads: int, heads_pad: int, n_kv: int) -> np.ndarray:
    """``[heads_pad, n_kv, 1]``: whether kv head ``j``'s columns of a merged
    row are query head ``h``'s; nowhere on the padding heads."""
    head = np.arange(heads_pad)[:, None]
    own = (np.arange(n_kv)[None, :] == head // (n_heads // n_kv)) & (head < n_heads)
    return own[:, :, None]


def _decode_attention_kernel(layer_ref, plan_ref, q_ref, *refs, scale, rows_per_pos,
                             height, biased, sunk, latent):
    del layer_ref  # spent in the index maps
    # the head bias goes in with 128-wide heads only (module header); the
    # sink with a layer that has one ("A sink")
    extra = list(refs[:-6])
    bias_ref = extra.pop(0) if biased else None
    sink_ref = extra.pop(0) if sunk else None
    k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs[-6:]
    w = pl.program_id(0)
    block_index, pos, code = plan_ref[2, w], plan_ref[3, w], plan_ref[4, w]
    ring = plan_ref.shape[0] == 7  # a window layer's list (module header, "A ring")

    def across(stat):
        """A ``[heads, 128]`` statistic beside the ``[heads, width]`` values."""
        return stat if stat.shape == acc_ref.shape else stat[:, :1]

    @pl.when(code & FIRST != 0)
    def _():
        if sink_ref is None:
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
        else:
            # the sink is one more column of the softmax, met first: the
            # running maximum starts at its logit and the sum at exp(0); it
            # gives no value (a padding head's is -inf and sums nothing)
            sink = sink_ref[...]
            m_ref[...] = sink
            l_ref[...] = jnp.where(sink > -jnp.inf, 1.0, 0.0)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(last: bool):
        k, v = k_ref[...], v_ref[...]  # [rows, key width], [rows, value width]
        scores = partial(jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
        if latent:
            # the one key row a position is [c' ; k_pe], in two blocks: the
            # queries' two parts against them, and the value block IS the first
            rank = k.shape[1]
            s = (scores(q_ref[:, :rank], k) + scores(q_ref[:, rank:], v)) * scale
            v = k
        else:
            s = scores(q_ref[...], k) * scale  # [heads, rows]
        if bias_ref is not None:
            s = s + bias_ref[...]
        if last and ring:
            # the rows that hold positions [oldest, pos], by where the block
            # sits among the positions and not by what it contains
            base, oldest = plan_ref[5, w], plan_ref[6, w]
            limit = (pos - base + 1) * rows_per_pos
            floor = (oldest - base) * rows_per_pos
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where((col < limit) & (col >= floor), s, -jnp.inf)
            row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where((row < limit) & (row >= floor), v, jnp.zeros_like(v))
        elif last:
            # rows above the lane's position: out of the scores, and out of
            # the values (0 x NaN is NaN: a stale row must not reach the sum)
            limit = (pos - block_index * height + 1) * rows_per_pos
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col < limit, s, -jnp.inf)
            row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(row < limit, v, jnp.zeros_like(v))
        m_prev = m_ref[...]  # [heads, 128], every lane of a row the same
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a padding head has no row of its own in any block: exp(-inf - 0)
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe[:, :1])
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = across(alpha) * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    pl.when(code & FULL != 0)(partial(block, False))
    pl.when(code & LAST != 0)(partial(block, True))

    @pl.when(code & FINAL != 0)
    def _():
        l = across(l_ref[...])
        # a parked lane summed nothing: zeros, and no division by its sum (a
        # sink's lane summed the sink alone: zeros over one)
        o_ref[...] = jnp.where(l > 0.0, acc_ref[...] / l, 0.0)


def decode_attention(q, k_all, v_all, layer, work, scale: float,
                     interpret: bool = False, sink=None, latent: bool = False) -> jnp.ndarray:
    """One query row a lane against layer ``layer`` of the stacked cache.

    q ``[lanes, n_heads, hd]`` (head ``h * group + g`` reads kv head ``h``);
    ``k_all`` / ``v_all`` ``[L, lanes, S, n_kv, hd]`` or ``[A, lanes, S, n_kv *
    hd]`` (``supports``) as the layer loop carries them, the lanes' fresh rows
    already appended; a merged value stack may hold heads of another width
    ``vd`` (``[A, lanes, S, n_kv * vd]``); ``work`` from ``lane_blocks``, or
    from ``ring_blocks`` where the stack is a window layer's ring. ``sink``
    ``[n_heads]`` float32 (None: none): a logit a head that joins the softmax
    as one more column and gives no value. Returns ``[lanes, n_heads, vd]``
    float32; a lane's result depends on that lane's rows ``[0, pos]`` alone
    (a ring: on the rows that hold ``(pos - window, pos]``).

    ``latent`` (module header, "Latent rows"): ``k_all`` ``[L, lanes, S,
    rank]`` the latent rows and ``v_all`` ``[L, lanes, S, rope leaf]`` their
    rope parts, q ``[lanes, n_heads, rank + rope leaf]`` the absorbed queries
    beside the rotated ones, ``work`` from ``lane_blocks`` at
    ``block_rows(latent=True)``; returns ``[lanes, n_heads, rank]``: the
    probabilities over the latent rows themselves."""
    n_heads, hd = q.shape[1:]
    n_layers, lanes, seq_len = k_all.shape[:3]
    heads_axis = k_all.ndim == 5  # 128-wide heads, n_kv rows a position
    merged = not heads_axis and not latent  # one row a position, every kv head in it
    width, v_width = k_all.shape[-1], v_all.shape[-1]
    rows_per_pos = k_all.shape[3] if heads_axis else 1
    # the queries' columns and the result's: a latent row is keys over both
    # leaves' widths and values over the first's
    q_width, o_width = (width + v_width, width) if latent else (width, v_width)
    n_items, plan = work
    heads_pad = -(-n_heads // 16) * 16  # whole bf16 sublane tiles
    height = block_rows(latent)
    rows = height * rows_per_pos
    q = jnp.pad(q.astype(k_all.dtype), ((0, 0), (0, heads_pad - n_heads), (0, 0)))
    if merged:
        # block-diagonal queries: a head's values in its kv head's columns
        n_kv = width // hd
        own = _own_columns(n_heads, heads_pad, n_kv)
        q = jnp.where(own, q[:, :, None, :], 0).reshape(lanes, heads_pad, width)

    def stack_spec(w_):  # a block of a stack whose rows are w_ wide
        return pl.BlockSpec(
            (None, None, rows, w_),
            lambda w, layer_ref, plan_ref: (layer_ref[0], plan_ref[1, w], plan_ref[2, w], 0))

    def lane_spec(w_):
        return pl.BlockSpec(
            (None, heads_pad, w_), lambda w, layer_ref, plan_ref: (plan_ref[0, w], 0, 0))

    extra, extra_spec = [], []
    if heads_axis:
        extra.append(_head_bias(n_heads, heads_pad, rows_per_pos, rows))
        extra_spec.append(pl.BlockSpec((heads_pad, rows), lambda w, *_: (0, 0)))
    if sink is not None:
        # a lane tile wide, as the running maximum it starts is kept
        sunk = jnp.pad(sink.astype(jnp.float32), (0, heads_pad - n_heads),
                       constant_values=-jnp.inf)
        extra.append(jnp.broadcast_to(sunk[:, None], (heads_pad, HEAD_SIZE)))
        extra_spec.append(pl.BlockSpec((heads_pad, HEAD_SIZE), lambda w, *_: (0, 0)))
    out = pl.pallas_call(
        partial(_decode_attention_kernel, scale=scale, rows_per_pos=rows_per_pos,
                height=height, biased=heads_axis, sunk=sink is not None, latent=latent),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # the layer index and the work list
            grid=(n_items,),
            in_specs=[lane_spec(q_width), *extra_spec, stack_spec(width), stack_spec(v_width)],
            out_specs=lane_spec(o_width),
            scratch_shapes=[pltpu.VMEM((heads_pad, HEAD_SIZE), jnp.float32)] * 2
            + [pltpu.VMEM((heads_pad, o_width), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((lanes, heads_pad, o_width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        name="decode_attention",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), plan, q, *extra,
      # (S, n_kv) of 128-wide heads merged: a bitcast; a merged stack as it is
      k_all.reshape(n_layers, lanes, seq_len * rows_per_pos, width),
      v_all.reshape(n_layers, lanes, seq_len * rows_per_pos, v_width))
    if merged:
        # a head keeps its kv head's columns of the value product: a select
        # and a sum with exact zeros, no product
        out = jnp.where(own, out.reshape(lanes, heads_pad, n_kv, -1), 0.0).sum(axis=2)
    return out[:, :n_heads]


# query rows a block of ``prefill_attention``: the largest of these that
# divides the chunk (PERF.md section 6, PR 51: the sizes tried on a v5e)
QUERY_ROWS = (256, 128, 64)
# what the prefill kernel's bodies traced so far are (``TRACE_STATS`` of
# ops/pallas_q40.py): read by the engine's ``path_facts`` and printed by
# ``warmup_engine``, so a run whose chunks still form dense scores is not silent
TRACE_STATS = {"prefill_kernel_traces": 0}


def query_rows(t: int) -> int | None:
    """Query rows a block of ``prefill_attention`` for a chunk of ``t`` rows a
    lane; None where ``t`` is no whole blocks (a verify step's ``K + 1``)."""
    return next((r for r in QUERY_ROWS if t % r == 0), None)


def supports_prefill(k_all, n_heads: int, n_kv: int | None = None) -> bool:
    """Whether ``prefill_attention`` takes this cache: one ``supports`` takes,
    and, of 128-wide heads, an even number of kv heads (they leave a block in
    the pairs the chip packs them in; module header, "More than one row"), of
    a merged row, heads that fill or evenly share a 128-lane tile."""
    if not supports(k_all, n_heads, n_kv):
        return False
    if k_all.ndim == 5:
        return k_all.shape[3] % 2 == 0
    # heads do not straddle a column tile (a 192-wide key does: such a chunk
    # is read by ops/blocked_attention.py or dense)
    return HEAD_SIZE % (k_all.shape[3] // n_kv) == 0


def chunk_blocks(positions: jnp.ndarray, n_valid: jnp.ndarray, seq_len: int, rows: int):
    """``lane_blocks`` for chunks of more than one row a lane: ``(n_items,
    plan, row_positions)``, layer invariant. ``positions`` ``[B, T]``,
    ``n_valid`` ``[B]`` (a lane's leading real rows), ``rows`` from
    ``query_rows(T)``. ``row_positions`` ``int32 [B, T, 128]``: every row's
    position across a lane tile, as the kernel keeps its running maximum (its
    mask compares whole tiles and broadcasts no column). Item ``w <
    n_items`` is one key block of one block of ``rows`` query rows: the lanes
    in order, a lane's query blocks in order, and of a query block the key
    blocks ``[0, hi // BLOCK_ROWS]`` where ``hi`` is the highest position of
    its real rows. A query block with no real row is one item that computes
    nothing, fetches nothing (its index stays on what the pipeline holds) and
    writes zeros. ``plan`` is ``int32 [6, B * T / rows * S / BLOCK_ROWS]``:
    the item's lane and query block, the lane and key block it fetches, a code
    (``FULL``: every row reads every key of the block; ``LAST``: masked by
    position; ``FIRST`` / ``FINAL`` item of its query block), and ``hi``."""
    b, t = positions.shape
    nq = t // rows
    pos = positions.astype(jnp.int32).reshape(b * nq, rows)
    row = jnp.arange(t, dtype=jnp.int32).reshape(1, nq, rows)
    real = (row < n_valid.astype(jnp.int32)[:, None, None]).reshape(b * nq, rows)
    real = real & (pos >= 0) & (pos < seq_len)
    hi = jnp.max(jnp.where(real, pos, -1), axis=1)
    lo = jnp.min(jnp.where(real, pos, seq_len), axis=1)
    live = hi >= 0
    n = jnp.where(live, hi // BLOCK_ROWS + 1, 0)
    items = jnp.maximum(n, 1)
    end = jnp.cumsum(items)
    w = jnp.arange(b * nq * (seq_len // BLOCK_ROWS), dtype=jnp.int32)
    seg = jnp.minimum(jnp.searchsorted(end, w, side="right", method="compare_all"), b * nq - 1)
    seg = seg.astype(jnp.int32)
    j = w - (end - items)[seg]
    # an item that computes nothing stays on what the pipeline holds: the
    # block of the nearest live item before it, else of the first live item
    prev = jax.lax.cummax(jnp.where(live[seg], w, -1))
    held = jnp.where(prev >= 0, prev, jnp.argmax(live[seg]).astype(jnp.int32))
    code = (
        jnp.where(live[seg],
                  jnp.where((j + 1) * BLOCK_ROWS - 1 <= lo[seg], FULL, LAST), 0)
        + jnp.where(j == 0, FIRST, 0)
        + jnp.where(j == items[seg] - 1, FINAL, 0)
    )
    plan = jnp.stack([seg // nq, seg % nq, (seg // nq)[held], j[held], code, hi[seg]])
    return end[-1], plan, jnp.broadcast_to(
        positions.astype(jnp.int32)[:, :, None], (b, t, HEAD_SIZE))


def _prefill_attention_kernel(layer_ref, plan_ref, q_ref, qpos_ref, k_ref, v_ref, o_ref,
                              m_ref, l_ref, acc_ref, *, scale, group, pair_stride):
    del layer_ref  # spent in the index maps
    unit, w = pl.program_id(0), pl.program_id(1)
    block_index, code, hi = plan_ref[3, w], plan_ref[4, w], plan_ref[5, w]
    per = m_ref.shape[0]  # kv heads a grid step
    rows = q_ref.shape[0]
    lane_tiles = BLOCK_ROWS // HEAD_SIZE

    @pl.when(code & FIRST != 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def heads_of(ref):
        """The block's ``[BLOCK_ROWS, 128]`` matrix of each kv head of this
        grid step (module header, "More than one row")."""
        if pair_stride is None:  # a merged row's column tile, as it is
            return [ref[...]] * per
        # rows (position, kv head): the chip packs rows 2i and 2i + 1 into one
        # 32-bit sublane, so kv heads 2u and 2u + 1 of every position are the
        # low and high halves of every pair_stride-th word row from u
        words = ref.bitcast(jnp.uint32)[pl.ds(unit, BLOCK_ROWS, stride=pair_stride), :]
        halves = (words << 16, words & jnp.uint32(0xFFFF0000))
        return [jax.lax.bitcast_convert_type(h, jnp.float32).astype(ref.dtype) for h in halves]

    def block(masked: bool):
        ks, vs = heads_of(k_ref), heads_of(v_ref)
        if masked:
            # by position, inside a block the chunk's rows end in: a row reads
            # the keys up to its own, and a row above the highest real one
            # (stale: 0 x NaN is NaN) reaches neither scores nor values
            key_pos = block_index * BLOCK_ROWS + jax.lax.broadcasted_iota(
                jnp.int32, (rows * group, BLOCK_ROWS), 1)
            seen = key_pos <= jnp.concatenate([qpos_ref[...][:, :1]] * group, axis=0)
            row_pos = block_index * BLOCK_ROWS + jax.lax.broadcasted_iota(
                jnp.int32, (BLOCK_ROWS, HEAD_SIZE), 0)
            vs = [jnp.where(row_pos <= hi, v, jnp.zeros_like(v)) for v in vs]
        for i in range(per):
            # the kv head's `group` query heads ride one product as more rows
            q = jnp.concatenate(
                [q_ref[:, (i * group + g) * HEAD_SIZE:(i * group + g + 1) * HEAD_SIZE]
                 for g in range(group)], axis=0)  # [group * rows, 128]
            s = jax.lax.dot_general(
                q, ks[i], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            ) * scale  # [group * rows, BLOCK_ROWS]
            if masked:
                s = jnp.where(seen, s, -jnp.inf)
            m_prev = m_ref[i]  # [group * rows, 128], every lane of a row the same
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)  # a row that has met no key
            alpha = jnp.exp(m_prev - m_safe)
            # the maximum as it is kept, a lane tile beside a lane tile: no
            # column is broadcast
            p = jnp.exp(s - jnp.concatenate([m_safe] * lane_tiles, axis=1))
            # the sum stays a sum a LANE until the query block's last item:
            # adds of whole tiles here, one reduction across lanes there (the
            # reductions across lanes are what a step waits for; PERF.md
            # section 6, PR 51)
            tiles = [p[:, j * HEAD_SIZE:(j + 1) * HEAD_SIZE] for j in range(lane_tiles)]
            l_ref[i] = alpha * l_ref[i] + sum(tiles[1:], tiles[0])
            acc_ref[i] = alpha * acc_ref[i] + jnp.dot(
                p.astype(vs[i].dtype), vs[i], preferred_element_type=jnp.float32)
            m_ref[i] = m_new

    pl.when(code & FULL != 0)(partial(block, False))
    pl.when(code & LAST != 0)(partial(block, True))

    @pl.when(code & FINAL != 0)
    def _():
        for i in range(per):
            l = jnp.sum(l_ref[i], axis=1, keepdims=True)
            # a block with no real row summed nothing: zeros, no division
            out = jnp.where(l > 0.0, acc_ref[i] / l, 0.0)
            for g in range(group):
                at = (i * group + g) * HEAD_SIZE
                o_ref[:, at:at + HEAD_SIZE] = out[g * rows:(g + 1) * rows].astype(o_ref.dtype)


def prefill_attention(q, k_all, v_all, layer, work, scale: float,
                      interpret: bool = False) -> jnp.ndarray:
    """``T`` query rows a lane (whole blocks: ``query_rows``) against layer
    ``layer`` of the stacked cache, a key block at a time (module header, "More
    than one row a lane").

    q ``[B, T, n_heads, hd]`` (head ``h * group + g`` reads kv head ``h``);
    ``k_all`` / ``v_all`` as ``decode_attention`` takes them
    (``supports_prefill``), the chunk's rows already appended; ``work`` from
    ``chunk_blocks``.
    Returns ``[B, T, n_heads, hd]`` in q's type; a real row's result depends on
    its lane's rows ``[0, position]`` alone, a row at or past ``n_valid`` holds
    finite values nobody reads."""
    TRACE_STATS["prefill_kernel_traces"] += 1
    b, t, n_heads, hd = q.shape
    n_layers, lanes, seq_len = k_all.shape[:3]
    merged = k_all.ndim == 4
    n_kv = k_all.shape[3] // hd if merged else k_all.shape[3]
    group = n_heads // n_kv
    # kv heads a grid step: the two a packed word row holds, or those of one
    # 128-lane column tile of a merged row
    per = HEAD_SIZE // hd if merged else 2
    rows = query_rows(t)
    n_items, plan, qpos = work
    qk = q.astype(k_all.dtype)
    if hd < HEAD_SIZE:
        # a head's values in its kv head's columns of the tile, zeros in the
        # others' (``decode_attention``'s block-diagonal queries, a tile wide)
        own = (np.arange(per)[None, :] == (np.arange(n_heads) // group % per)[:, None])[:, :, None]
        qk = jnp.where(own, qk[:, :, :, None, :], 0).reshape(b, t, n_heads, HEAD_SIZE)
    qk = qk.reshape(b, t, n_heads * HEAD_SIZE)
    width = per * group * HEAD_SIZE  # a grid step's query columns
    if merged:
        kv_shape = k_all.shape
        kv_spec = pl.BlockSpec(
            (None, None, BLOCK_ROWS, HEAD_SIZE),
            lambda u, w, layer_ref, plan_ref: (layer_ref[0], plan_ref[2, w], plan_ref[3, w], u))
    else:
        # every axis down to n_kv merged: a bitcast (``decode_attention``), and
        # a block is a plain matrix, which the view as 32-bit words needs
        kv_shape = (n_layers * lanes * seq_len * n_kv, HEAD_SIZE)
        n_blocks = seq_len // BLOCK_ROWS
        kv_spec = pl.BlockSpec(
            (BLOCK_ROWS * n_kv, HEAD_SIZE),
            lambda u, w, layer_ref, plan_ref: (
                (layer_ref[0] * lanes + plan_ref[2, w]) * n_blocks + plan_ref[3, w], 0))
    q_spec = pl.BlockSpec(
        (None, rows, width), lambda u, w, layer_ref, plan_ref: (plan_ref[0, w], plan_ref[1, w], u))
    qpos_spec = pl.BlockSpec(
        (None, rows, HEAD_SIZE), lambda u, w, layer_ref, plan_ref: (plan_ref[0, w], plan_ref[1, w], 0))
    stat = pltpu.VMEM((per, group * rows, HEAD_SIZE), jnp.float32)
    out = pl.pallas_call(
        partial(_prefill_attention_kernel, scale=scale, group=group,
                pair_stride=None if merged else n_kv // 2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # the layer index and the work list
            grid=(n_kv // per, n_items),
            in_specs=[q_spec, qpos_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[stat] * 3,
        ),
        out_shape=jax.ShapeDtypeStruct(qk.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=64 * 2**20),
        name="prefill_attention",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), plan, qk, qpos,
      k_all.reshape(kv_shape), v_all.reshape(kv_shape))
    out = out.reshape(b, t, n_heads, HEAD_SIZE)
    if hd < HEAD_SIZE:
        # a head keeps its kv head's columns of the value product: a select
        # and a sum with exact zeros
        out = jnp.where(own, out.reshape(b, t, n_heads, per, hd), 0).sum(axis=3)
    return out


def supports_sparse(k_all, n_heads: int, n_kv: int, block_size: int) -> bool:
    """Whether ``sparse_decode_attention`` takes this cache: a merged bf16
    stack ``[A, lanes, S, n_kv * HEAD_SIZE]`` and blocks of whole bf16 sublane
    tiles."""
    return (
        k_all.dtype == jnp.bfloat16 and k_all.ndim == 4 and n_kv > 0
        and k_all.shape[3] == n_kv * HEAD_SIZE and n_heads % n_kv == 0
        and block_size % 16 == 0 and k_all.shape[2] % block_size == 0
    )


def _sparse_decode_kernel(layer_ref, count_ref, blocks_ref, pos_ref, q_ref, k_hbm, v_hbm,
                          o_ref, kbuf, vbuf, sem, *, scale, block_size, n_kv, hd):
    g, n_items = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]

    def copies(item, slot, i):
        lane, head = item // n_kv, item % n_kv
        at = blocks_ref[item, i] * block_size
        src = lambda ref: ref.at[layer, lane, pl.ds(at, block_size), pl.ds(head * hd, hd)]
        dst = lambda buf: buf.at[slot, pl.ds(i * block_size, block_size), :]
        return (pltpu.make_async_copy(src(k_hbm), dst(kbuf), sem.at[slot, 0]),
                pltpu.make_async_copy(src(v_hbm), dst(vbuf), sem.at[slot, 1]))

    def fetch(item, slot):
        def one(i, carry):
            for c in copies(item, slot, i):
                c.start()
            return carry
        jax.lax.fori_loop(0, count_ref[item], one, 0)

    slot = g % 2

    @pl.when(g == 0)
    def _():
        fetch(0, 0)

    @pl.when(g + 1 < n_items)
    def _():
        fetch(g + 1, 1 - slot)

    n = count_ref[g]

    def arrived(i, carry):
        for c in copies(g, slot, i):
            c.wait()
        return carry
    jax.lax.fori_loop(0, n, arrived, 0)

    # the list is in rising order: the last block is the row's own, and the
    # buffer's rows from `limit` on hold later positions or nothing
    limit = (n - 1) * block_size + pos_ref[g // n_kv] % block_size + 1
    k, v = kbuf[slot], vbuf[slot]  # [list rows, hd]
    s = jax.lax.dot_general(
        q_ref[...], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    s = jnp.where(jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < limit, s, -jnp.inf)
    # 0 x NaN is NaN: a row nothing was copied into must not reach the sum
    v = jnp.where(jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) < limit, v, jnp.zeros_like(v))
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - jnp.where(m == -jnp.inf, 0.0, m))
    l = jnp.sum(p, axis=1, keepdims=True)
    o = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    # a parked lane chose nothing: zeros, and no division by its sum
    o_ref[...] = jnp.where(l > 0.0, o / jnp.where(l > 0.0, l, 1.0), 0.0)


def sparse_decode_attention(q, k_all, v_all, layer, work, scale: float, block_size: int,
                            interpret: bool = False) -> jnp.ndarray:
    """One query row a lane against the blocks each (lane, kv head) chose of
    layer ``layer`` of the merged stacks ``[A, lanes, S, n_kv * hd]``
    (``supports_sparse``), the lanes' fresh rows already appended. ``work``
    from ``block_sparse.chosen_list``: ``(count [lanes * n_kv], blocks [lanes *
    n_kv, list], pos [lanes])``. q ``[lanes, n_heads, hd]``; returns ``[lanes,
    n_heads, hd]`` float32; a head's result depends on its kv head's chosen
    blocks alone, up to the lane's position."""
    lanes, n_heads, hd = q.shape
    n_kv = k_all.shape[3] // hd
    group = n_heads // n_kv
    count, blocks, pos = work
    group_pad = -(-group // 16) * 16  # whole bf16 sublane tiles
    rows = blocks.shape[1] * block_size
    qg = q.astype(k_all.dtype).reshape(lanes * n_kv, group, hd)
    qg = jnp.pad(qg, ((0, 0), (0, group_pad - group), (0, 0)))
    item = pl.BlockSpec((None, group_pad, hd), lambda g, *_: (g, 0, 0))
    out = pl.pallas_call(
        partial(_sparse_decode_kernel, scale=scale, block_size=block_size, n_kv=n_kv, hd=hd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # the layer index, and the work list's three parts
            grid=(lanes * n_kv,),
            in_specs=[item, pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=item,
            scratch_shapes=[pltpu.VMEM((2, rows, hd), k_all.dtype)] * 2
            + [pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((lanes * n_kv, group_pad, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 * 2**20),
        name="sparse_decode_attention",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), count, blocks, pos, qg, k_all, v_all)
    return out[:, :group].reshape(lanes, n_heads, hd)
