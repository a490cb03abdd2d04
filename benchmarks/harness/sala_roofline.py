"""Bytes and operations that a linear-attention layer's matrix state and a
block-sparse layer's attention MUST move, whatever implements them, from what
was advanced and attended (`EngineStats` `linear_state_bytes_moved`,
`linear_rows_computed`, `attn_blocks_read`, `attn_blocks_held`: host counts).
Beside `harness/ssm_roofline.py`, whose choice of step and of share this
module takes.

The matrix state at one row a lane: ``heads x d x d`` float32 numbers read
once and written once a live lane a layer (the program's counter is that, in
bytes), and the row's q, k, v in and o out. Through the chunk form a row's
published work is the recurrence's own, a head: the decay of the matrix (``d
x d`` products), ``k^T v`` into it (``2 d x d``) and ``q S`` out of it (``2 d
x d``): ``5 d^2`` operations a head a row, against the matrix units' peak; and
its bytes are the row's q, k, v in and o out at the activations' width (the
matrix's own round trip, once a chunk a layer, is left out: the counter does
not say how many chunks, so the bound is a little under what must move). The
share is the larger of the two bounds over the scope's time.

A block-sparse layer's decode attention: every block a (live lane, kv head)
attended, ``block_size`` rows of ``head_dim`` keys and as many values at the
cache's width, a layer. The counter counts ONE layer's, ONE kv head's blocks.
No share can pass 100 %: each is a least bound.
"""

from __future__ import annotations

# the choice of step and the share of a peak are `ssm_roofline`'s; this
# family's readers take them from here
from harness.ssm_roofline import decode_scopes_ms, hbm_share, prefill_scope_ms_per_step  # noqa: F401


def _linear_dim(config) -> int:
    return int(getattr(config, "linear_n_heads", 0)) * int(getattr(config, "linear_head_dim", 0))


def linear_row_bytes(config, act_bytes: int = 2) -> int:
    """A row's q, k, v in and o out, one layer."""
    return 4 * _linear_dim(config) * act_bytes


def linear_row_ops(config) -> int:
    """The recurrence's operations for one row of one layer: ``5 d^2`` a head."""
    return 5 * _linear_dim(config) * int(getattr(config, "linear_head_dim", 0))


def decode_state_bytes(config, state_bytes_a_step: float, lane_layers: float,
                       act_bytes: int = 2) -> float:
    """HBM bytes of one decode step's state updates: the state's bytes in and
    out (the program's counter, a step) and the rows of ``lane_layers`` (live
    lane, layer) pairs."""
    return state_bytes_a_step + lane_layers * linear_row_bytes(config, act_bytes)


def chunk_share(ctx, rows: float, ms: float | None) -> float | None:
    """Percent: the larger of the least times the chip's memory and its matrix
    units need for ``rows`` (row, layer) pairs through the recurrence, over
    ``ms`` of device time."""
    if ctx.peaks is None or not ms or not rows:
        return None
    least_s = max(rows * linear_row_bytes(ctx.config) / ctx.peaks["hbm_bytes_per_s"],
                  rows * linear_row_ops(ctx.config) / ctx.peaks["flops_per_s"])
    return 100.0 * least_s / (ms / 1e3)


def sparse_decode_bytes(config, blocks_a_step: float, cache_bytes: int = 2) -> float:
    """HBM bytes of one decode step's reads of the chosen blocks: keys and
    values, every kv head, every sparse layer (``blocks_a_step``: ONE layer's,
    ONE kv head's blocks, the counter's unit)."""
    rows = blocks_a_step * int(getattr(config, "sparse_block_size", 0))
    return (2 * rows * config.head_size * cache_bytes * config.n_kv_heads
            * int(getattr(config, "n_sparse_layers", 0)))
