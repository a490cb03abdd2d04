"""Bytes and operations that a gated delta-rule layer's matrix state MUST move
and make, whatever implements it, counted from the configuration FILE's
published keys (``ctx.cfg``: ``linear_attn_config`` ``num_heads`` and
``head_dim``) and from what the program
counted (`EngineStats` `delta_state_bytes_moved`, `delta_rows_computed`: host
counts). Beside `harness/sala_roofline.py`, whose choice of step and of share
this module takes.

The matrix state at one row a lane: ``heads x d x d`` float32 numbers read once
and written once a live lane a layer (the program's counter is that, in
bytes), and the row's q, k, v, g (a log decay a key channel, float32) and b in
and o out. Through the chunk form a row's published work is the recurrence's
own, a head: the decay of the matrix (``d^2`` products), ``S^T k`` (``2
d^2``), the rank-one update (``2 d^2``) and ``S^T q`` (``2 d^2``): ``7 d^2``
operations a head a row, against the matrix units' peak. What an
implementation adds to reach a chunk form (the decayed scores, the triangular
solve) is its own and is NOT counted. The bytes through the chunk form are the
row's operands in and its output out (the matrix's own round trip, once a
chunk a layer, is left out: the counter does not say how many chunks, so the
bound is a little under what must move). The share is the larger of the two
bounds over the scope's time. No share can pass 100 %: each is a least bound.
"""

from __future__ import annotations

# the choice of step and the share of a peak are `ssm_roofline`'s; this
# family's readers take them from here
from harness.ssm_roofline import decode_scopes_ms, hbm_share, prefill_scope_ms_per_step  # noqa: F401

STATE_BYTES = 4  # the matrix state and the log decay are float32 whatever the cache's type


def heads_and_width(cfg: dict) -> tuple[int, int]:
    lin = cfg.get("linear_attn_config") or {}
    return int(lin.get("num_heads", 0)), int(lin.get("head_dim", 0))


def row_bytes(cfg: dict, act_bytes: int = 2) -> int:
    """A row's q, k, v in and o out at the activations' width, its log decay a
    channel and its step a head in float32, one layer."""
    heads, d = heads_and_width(cfg)
    return 4 * heads * d * act_bytes + (heads * d + heads) * STATE_BYTES


def row_ops(cfg: dict) -> int:
    """The recurrence's operations for one row of one layer: ``7 d^2`` a head."""
    heads, d = heads_and_width(cfg)
    return 7 * heads * d * d


def lane_state_bytes(cfg: dict) -> int:
    """A lane's matrices in one layer, read once and written once."""
    heads, d = heads_and_width(cfg)
    return 2 * heads * d * d * STATE_BYTES


def decode_state_bytes(cfg: dict, state_bytes_a_step: float, act_bytes: int = 2) -> float:
    """HBM bytes of one decode step's state updates: the state's bytes in and
    out (the program's counter, a step) and the rows of the (live lane, layer)
    pairs that moved them."""
    lane_layers = state_bytes_a_step / lane_state_bytes(cfg)
    return state_bytes_a_step + lane_layers * row_bytes(cfg, act_bytes)


def chunk_share(ctx, rows: float, ms: float | None) -> float | None:
    """Percent: the larger of the least times the chip's memory and its matrix
    units need for ``rows`` (row, layer) pairs through the recurrence, over
    ``ms`` of device time."""
    if ctx.peaks is None or not ms or not rows:
        return None
    least_s = max(rows * row_bytes(ctx.cfg) / ctx.peaks["hbm_bytes_per_s"],
                  rows * row_ops(ctx.cfg) / ctx.peaks["flops_per_s"])
    return 100.0 * least_s / (ms / 1e3)
