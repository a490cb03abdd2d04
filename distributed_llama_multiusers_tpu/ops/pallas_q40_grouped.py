"""Grouped Q40 matmul for a routed FFN: every row by ITS expert's weights,
the slabs read in place by layer index and expert id.

A routed layer multiplies each token's row by the few experts its router
chose. The weights of all experts of all routed layers are one stack
``[L, E, d_in/2, d_out]`` a matrix (``quants.packed.Q40Experts``). Slicing a
layer out for a kernel call copies every expert's planes, chosen or not (a
Pallas call gets its operands materialised: PERF.md section 6, PR 30), and a
loop over experts reads them all. Here the (token, expert) assignments are
sorted by expert and laid out in tiles of ``tm`` rows, each group starting on
a tile (``route_plan``); the grid is one axis over tiles, and the weight
blocks' index maps read the layer and the tile's expert id from scalar
prefetch: block ``(l, e, 0, 0)`` of the stack, a whole slab, where a slab is
one block of ``_plan_blocks`` (0.75 MiB at 2048 x 768). Tiles of one expert
follow each other, and the pipeline issues no fetch for an index that did not
change, so such a slab is read once whatever its row count, and a slab no row
chose is never addressed. Tiles past the last used one repeat its expert
(nothing is fetched) and compute nothing.

A wider slab (1.5 MiB at 2048 x 1536) is walked in the reduction blocks
``_plan_blocks`` gives: a second, inner grid axis ``k`` over them, the weight
block ``(l, e, k, 0)``, the partial products summed in an f32 scratch
(``_acc_epilogue``). A tile then fetches its slab once, block by block; a
second tile of the same expert fetches it again, and the unused tiles stay on
the last block fetched. Fetched or not, every tile dequantises its slab: on the
chip a second tile of an expert costs what the first does, on either kind of
slab, so ``tile_rows`` makes tiles tall enough that few groups fill two.

Shapes are static: ``n_tiles`` is the most tiles any routing of ``n_assign``
assignments over ``n_experts`` experts can need, so no token is ever dropped.
A parked row (an idle lane) is given the expert id ``n_experts``: it sorts
past every group, takes no tile row and fetches nothing.

The dequant chain is ops/pallas_q40.py's ``v4`` (two dots on the nibble
halves, the -8 offset folded into a correction dot against per-block sums of
``x``); the per-block sums go in untransposed, ``[tm, n_blk]``, since a tile of
8 rows cannot be a lane dimension.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..quants.packed import Q40Experts, unpack_q40_slabs
from .pallas_q40 import (
    VMEM_LIMIT_BYTES,
    _acc_epilogue,
    _f16_bits_to_f32,
    _final_writeback,
    _plan_blocks,
    _resolve_w_dtype,
    _sub_tiles,
)

HEIGHTS = (8, 16, 32, 64, 128)  # rows a tile: multiples of the 8 f32 sublanes

# Weights of a slab whose dequantisation costs a call what one more padded row
# costs it. Measured on a v5e (PERF.md section 6, PR 48): a used tile costs the
# kernel 1.6 us at 8 rows and 2.6 at 128 on a 2048 x 768 slab (3.8 and 6.6 at
# 2048 x 1536, 22 and 41 at 4096 x 4096): its slab's dequantisation, whatever
# rows it holds, a second tile of an expert as much as the first; and every
# padded row, in a used tile or not, costs 0.06-0.2 us in the operations
# around the kernel (the gather into padded order, the float32 halves, the
# activation, the gather back) and in the tile's own copies. The routed halves
# of the four benchmark configurations are cheapest at one height for any
# value from 50,000 to 80,000.
WEIGHTS_A_ROW = 1 << 16


def _tiles_a_group(group: float, tm: int) -> float:
    """Tiles of ``tm`` rows an expert's group fills, in expectation, where
    rows choose their experts independently: ``E[ceil(G / tm)]`` for ``G``
    Poisson with mean ``group``."""
    spread = 8 * math.sqrt(group) + 8
    return sum(
        -(-k // tm) * math.exp(k * math.log(group) - group - math.lgamma(k + 1))
        for k in range(max(1, int(group - spread)), int(group + spread) + 1)
    )


def tile_rows(n_assign: int, n_experts: int, slab_weights: int) -> int:
    """Rows a tile, chosen at trace time from the call's static shapes:
    ``n_assign`` (row, expert) pairs routed over ``n_experts`` experts, each a
    slab of ``slab_weights`` weights. An expert's likely group is ``n_assign /
    n_experts`` rows. A short tile makes a group fill several, each
    dequantising the slab again; a tall one pads every group to its height,
    and the padded rows are paid for around the kernel. The height is the
    cheapest of ``HEIGHTS`` by that count, in rows: the tiles the groups are
    likely to fill, each worth ``slab_weights / WEIGHTS_A_ROW`` rows, and the
    rows of the static plan (``max_tiles``). On the lab's table (PR 48) that
    is the smallest height at or above the group, give or take one step: a
    step taller where the slab is large and half the groups would spill over,
    a step shorter where it is small and many experts would be padded; 8 at
    every decode width.

    The expectation is a UNIFORM router's (the benchmark's weights are
    random: no trained checkpoint is in the repository, and a trained
    router's hot experts get taller groups), and the constant was measured
    on slabs of 1.5M to 16.7M weights: measure before trusting it outside."""
    group = n_assign / n_experts

    def rows_worth(tm: int) -> float:
        filled = n_experts * _tiles_a_group(group, tm)
        return filled * slab_weights / WEIGHTS_A_ROW + max_tiles(n_assign, n_experts, tm) * tm

    return min(HEIGHTS, key=rows_worth)


def max_tiles(n_assign: int, n_experts: int, tm: int) -> int:
    """The most tiles a routing can need: every group ends in at most one
    partial tile, and a tile holds at least one assignment."""
    return min(n_assign, n_assign // tm + min(n_experts, n_assign))


class RoutePlan(NamedTuple):
    """Where every (token, expert) assignment of a step goes. ``P`` padded
    rows in ``n_tiles`` tiles of ``tm`` (``P = n_tiles * tm``)."""

    tile_expert: jnp.ndarray  # [n_tiles] int32: the expert a tile multiplies by
    n_used: jnp.ndarray  # [] int32: tiles that hold rows; the rest compute nothing
    src: jnp.ndarray  # [P] int32: the token row a padded row takes; n for none
    pos: jnp.ndarray  # [n, k] int32: the padded row of each assignment; P if parked
    slabs: jnp.ndarray  # [] int32: distinct experts chosen (slabs a matrix read)
    assignments: jnp.ndarray  # [] int32: live rows x k
    tiled_rows: jnp.ndarray  # [] int32: n_used * tm, the rows a call multiplies


def route_plan(topi: jnp.ndarray, live: jnp.ndarray, n_experts: int, tm: int) -> RoutePlan:
    """Sort a step's assignments by expert into tiles of ``tm`` rows (one of
    ``HEIGHTS``; ``tile_rows`` is the rule). ``topi``: ``[n, k]`` expert ids,
    an id of ``n_experts`` one that is not held here; ``live``: ``[n]`` bool,
    False for a parked row, which routes nowhere."""
    n, k = topi.shape
    a = n * k
    n_tiles = max_tiles(a, n_experts, tm)
    p = n_tiles * tm
    flat = jnp.where(live[:, None], topi, n_experts).reshape(a).astype(jnp.int32)
    sizes = jnp.zeros((n_experts + 1,), jnp.int32).at[flat].add(1)
    g = sizes[:n_experts]
    tiles_e = (g + tm - 1) // tm
    tile_end = jnp.cumsum(tiles_e)
    n_used = tile_end[-1]
    # rank of an assignment inside its group: stable sort by expert
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    group_start = jnp.cumsum(sizes) - sizes  # [E + 1], the parked group last
    rank = jnp.arange(a, dtype=jnp.int32) - group_start[sorted_e]
    tile_start = jnp.concatenate([tile_end - tiles_e, jnp.zeros((1,), jnp.int32)])
    pos_sorted = jnp.where(sorted_e < n_experts, tile_start[sorted_e] * tm + rank, p)
    pos = jnp.zeros((a,), jnp.int32).at[order].set(pos_sorted)
    token = jnp.arange(a, dtype=jnp.int32) // k
    src = jnp.full((p + 1,), n, jnp.int32).at[pos].set(token)[:p]
    # tile i belongs to the first expert whose tiles end past i; tiles past
    # the last used one repeat its expert, so their index does not change
    tile_idx = jnp.arange(n_tiles, dtype=jnp.int32)
    owner = jnp.searchsorted(tile_end, tile_idx, side="right").astype(jnp.int32)
    last = jnp.max(jnp.where(g > 0, jnp.arange(n_experts, dtype=jnp.int32), 0))
    tile_expert = jnp.where(tile_idx < n_used, jnp.minimum(owner, n_experts - 1), last)
    return RoutePlan(
        tile_expert=tile_expert, n_used=n_used, src=src, pos=pos.reshape(n, k),
        slabs=jnp.sum(g > 0).astype(jnp.int32),
        assignments=jnp.sum(live).astype(jnp.int32) * k,
        tiled_rows=n_used * tm,
    )


def slab_blocks(d_in: int, d_out: int) -> int | None:
    """Reduction blocks the kernel walks a ``d_in x d_out`` slab in (1: the
    slab is one block and one fetch); None where it does not tile the shape:
    a block spans the whole output width."""
    plan = _plan_blocks(d_in, d_out)
    if plan is None or plan[0] != d_out or (d_in // 2) % plan[1]:
        return None
    return (d_in // 2) // plan[1]


def grouped_supports(w) -> bool:
    """Whether the kernel takes this stack: ``Q40Experts`` whose slab is whole
    blocks of ``_plan_blocks`` over the reduction, each the whole output
    width."""
    if not isinstance(w, Q40Experts) or w.packed.ndim != 4:
        return False
    return slab_blocks(w.d_in, w.d_out) is not None


def _grouped_kernel(meta_ref, x_lo_ref, x_hi_ref, bsum_ref, packed_ref,
                    scales_ref, out_ref, *acc_ref, w_dtype, sub_tiles, n_k):
    """One tile by one reduction block: ``[tm, rows * 2]`` of the rows (as
    nibble halves) by ``rows`` packed rows of one expert's slab (the whole
    slab where ``n_k`` is 1). meta: ``[layer, n_used, tile_expert...]``;
    layer and expert are spent in the index maps."""
    rows = packed_ref.shape[0]
    n_blk = rows // 16
    k = pl.program_id(1) if n_k > 1 else 0
    acc = acc_ref[0] if acc_ref else None

    @pl.when(pl.program_id(0) < meta_ref[1])
    def _():
        x_lo = x_lo_ref[...].astype(w_dtype)
        x_hi = x_hi_ref[...].astype(w_dtype)
        bsum = bsum_ref[...]
        off = 0
        for t in sub_tiles:
            s = _f16_bits_to_f32(scales_ref[:, off:off + t])  # [n_blk, t]
            p = packed_ref[:, off:off + t].astype(jnp.int32)
            s3 = s[:, None, :]
            w_lo = ((p & 0x0F).astype(jnp.float32).reshape(n_blk, 16, t) * s3)
            w_hi = ((p >> 4).astype(jnp.float32).reshape(n_blk, 16, t) * s3)
            w_lo = w_lo.reshape(rows, t).astype(w_dtype)
            w_hi = w_hi.reshape(rows, t).astype(w_dtype)
            # folded -8 offset, as in ops/pallas_q40.py's slab kernel
            corr = jnp.dot(bsum, s, preferred_element_type=jnp.float32)
            part = (
                jnp.dot(x_lo, w_lo, preferred_element_type=jnp.float32)
                + jnp.dot(x_hi, w_hi, preferred_element_type=jnp.float32)
                - 8.0 * corr
            )
            _acc_epilogue(part, off, t, k, n_k, out_ref, acc)
            off += t
        _final_writeback(k, n_k, out_ref, acc)


def tile_block_index(i, *k_meta, n_k: int = 1):
    """The weight block of tile ``i`` (and reduction block ``k`` where a slab
    has more than one): ``(layer, expert, k, 0)`` of the stack. An unused tile
    stays on the last block, so nothing is fetched for it. The tests read
    which slabs a plan addresses through this."""
    meta = k_meta[-1]
    k = jnp.where(i < meta[1], k_meta[0], n_k - 1) if len(k_meta) == 2 else 0
    return (meta[0], meta[2 + i], k, 0)


@partial(jax.jit, static_argnames=("interpret", "w_dtype"))
def _grouped_impl(x_rows, w: Q40Experts, layer, tile_expert, n_used,
                  interpret, w_dtype):
    p_rows, d_in = x_rows.shape
    n_tiles = tile_expert.shape[0]
    tm = p_rows // n_tiles
    half, n_blk, d_out = d_in // 2, d_in // 32, w.d_out
    n_k = slab_blocks(d_in, d_out)
    rows, k_blk = half // n_k, n_blk // n_k  # packed rows, quant blocks a block
    xb = x_rows.astype(jnp.float32).reshape(p_rows, n_blk, 2, 16)
    x_lo = xb[:, :, 0, :].reshape(p_rows, half)
    x_hi = xb[:, :, 1, :].reshape(p_rows, half)
    bsum = xb.sum(axis=(2, 3))  # [P, n_blk], exact f32
    meta = jnp.concatenate([
        jnp.stack([jnp.asarray(layer, jnp.int32), jnp.asarray(n_used, jnp.int32)]),
        tile_expert.astype(jnp.int32),
    ])
    if n_k == 1:
        grid, scratch = (n_tiles,), []
        row_spec = pl.BlockSpec((tm, half), lambda i, m: (i, 0))
        bsum_spec = pl.BlockSpec((tm, n_blk), lambda i, m: (i, 0))
        out_spec = pl.BlockSpec((tm, d_out), lambda i, m: (i, 0))
        w_index = tile_block_index
    else:
        grid, scratch = (n_tiles, n_k), [pltpu.VMEM((tm, d_out), jnp.float32)]
        row_spec = pl.BlockSpec((tm, rows), lambda i, k, m: (i, k))
        # a block of the sums is narrower than a tile of lanes: laid out
        # [n_k, P, k_blk], so that a block's last axis is the array's
        bsum = jnp.transpose(bsum.reshape(p_rows, n_k, k_blk), (1, 0, 2))
        bsum_spec = pl.BlockSpec((None, tm, k_blk), lambda i, k, m: (k, i, 0))
        out_spec = pl.BlockSpec((tm, d_out), lambda i, k, m: (i, 0))
        w_index = partial(tile_block_index, n_k=n_k)
    return pl.pallas_call(
        partial(_grouped_kernel, w_dtype=w_dtype, sub_tiles=_sub_tiles(d_out), n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                row_spec,
                row_spec,
                bsum_spec,
                # a block of the tile's expert's slab, addressed inside the
                # stack; the two leading block dimensions are squeezed
                pl.BlockSpec((None, None, rows, d_out), w_index),
                pl.BlockSpec((None, None, k_blk, d_out), w_index),
            ],
            out_specs=out_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((p_rows, d_out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="q40_grouped_matmul",
    )(meta, x_lo, x_hi, bsum, w.packed, w.scale_bits)


def q40_grouped_pallas(x_rows, w: Q40Experts, layer, plan: RoutePlan,
                       interpret: bool = False, w_dtype=None) -> jnp.ndarray:
    """``[P, d_in]`` rows in the plan's padded order -> ``[P, d_out]`` f32,
    row ``r`` by the slab ``(layer, plan.tile_expert[r // tm])``. Rows of
    unused tiles are not written."""
    return _grouped_impl(
        x_rows, w, layer, plan.tile_expert, plan.n_used,
        interpret, _resolve_w_dtype(w_dtype, interpret),
    )


def grouped_matmul_xla(x_rows, w, layer, plan: RoutePlan) -> jnp.ndarray:
    """The same product without the kernel (the CPU; what the kernel is held
    to): the slabs the tiles name are gathered and dequantized, no others.
    ``w``: ``Q40Experts`` and a layer index, or one layer's dense
    ``[E, d_in, d_out]`` (``layer`` unused)."""
    n_tiles = plan.tile_expert.shape[0]
    xt = x_rows.reshape(n_tiles, -1, x_rows.shape[-1])
    if isinstance(w, Q40Experts):
        slabs = unpack_q40_slabs(w, layer, plan.tile_expert, jnp.float32)
    else:
        slabs = w[plan.tile_expert].astype(jnp.float32)
    y = jnp.einsum("ptd,pdo->pto", xt.astype(jnp.float32), slabs)
    return y.reshape(x_rows.shape[0], -1)
