"""Bytes a window layer's decode read MUST move and multiply-adds the prefill
attention's mask admits, whatever implements them, from what the program
counted (`EngineStats` `attn_window_rows_read`, `prefill_attn_blocks_causal`:
host counts). Beside `harness/roofline.py`, `harness/moe_roofline.py`,
`harness/sparse_roofline.py` and `harness/ssm_roofline.py`.

**The window layers' read at decode width.** A live lane at position ``pos``
needs the ``min(pos + 1, W)`` newest rows of K and of V in every window layer,
``n_kv x head`` numbers each at the cache's width. The program counts whole
blocks FETCHED (`attn_window_rows_read`, in rows of one layer); a lane past
the window fetches ``W + BLOCK`` rows most steps (17 blocks of 256 for 4096
positions that straddle block edges), so the rows NEEDED are taken as the
fetched ones times ``W / (W + BLOCK)``: exact for a lane past the window,
which seven requests in eight of the long-context mix are from their first
step, and under what a shorter lane needs of its one or two blocks by at most
the factor itself. No operation is counted against the matrix units: the share
is a least-bytes bound.

**Prefill attention a key block at a time.** The program counts, for every
real row of a chunk and every layer, the key blocks that hold a position the
row reads (`prefill_attn_blocks_causal`, in (row, block) pairs): a pair is
``BLOCK`` keys against all query heads, once for the scores and once for the
values, ``2 x heads x head x BLOCK`` multiply-adds. A block that the mask
admits in part is counted whole: over what the mask admits by at most one
block in a row's seventeen (a window layer) or in its tens (a full-context
layer), and still the least a blocked schedule computes.
"""

from __future__ import annotations

BLOCK = 256  # ops/pallas_attention.py BLOCK_ROWS and ops/blocked_attention.py BLOCK_KEYS


def kv_row_bytes(config, kv_bytes: int = 2) -> int:
    """A position's keys and values in one layer."""
    return 2 * config.n_kv_heads * config.head_size * kv_bytes


def window_rows_needed(config, rows_fetched: float) -> float:
    """Rows inside the lanes' windows, from the whole blocks fetched."""
    w = config.sliding_window
    return rows_fetched * w / (w + BLOCK)


def window_decode_bytes(config, rows_fetched: float, kv_bytes: int = 2) -> float:
    """HBM bytes a decode step's window layers must read: ``rows_fetched``
    rows of ONE layer's ring as the program counted them, every window layer."""
    return (window_rows_needed(config, rows_fetched) * config.n_window_layers
            * kv_row_bytes(config, kv_bytes))


def prefill_attention_flops(config, causal_pairs: float) -> float:
    """Floating-point operations of ``causal_pairs`` (query row, key block)
    pairs: scores and values, a multiply and an add each."""
    return causal_pairs * BLOCK * config.n_heads * config.head_size * 2 * 2


def prefill_attention_ms(ctx) -> float | None:
    """Device time under `dl.attention` in the prefill half of a fused step
    (the stretch's mean): a full-context layer's operations, and a window
    layer's, which sit one scope deeper under `dl.window_attention`."""
    from harness.ssm_roofline import prefill_scope_ms_per_step

    parts = [prefill_scope_ms_per_step(ctx, s) for s in ("dl.attention", "dl.window_attention")]
    found = [p for p in parts if p is not None]
    return sum(found) if found else None


def mxu_share(ctx, flops: float, ms: float | None) -> float | None:
    """Percent: the least time the chip's matrix units need for ``flops`` over
    ``ms`` of device time."""
    if ctx.peaks is None or not ms or not flops:
        return None
    return 100.0 * (flops / ctx.peaks["flops_per_s"]) / (ms / 1e3)
