"""Bytes a selective state-space layer's running sum MUST move, whatever
implements it, from what was advanced (`EngineStats` `ssm_lane_steps`,
`ssm_rows_computed`: host counts, summed over the state-space layers). Beside
`harness/roofline.py`, `harness/moe_roofline.py` and
`harness/sparse_roofline.py`.

Only what the recurrence itself reads and writes is counted, the work under
the program's `dl.ssm_scan` scope: the state of ``N x E`` float32 numbers read
once and written once a live lane a layer, and a row's step size, input and
output (``E`` numbers each, at the activations' width) and its two maps (``N``
numbers each). The conv's window, the gate and the projections lie outside
that scope and outside this count. No operation is counted against the matrix
units, which this work does not use: the share is a least-bytes bound and
cannot pass 100 %.
"""

from __future__ import annotations

STATE_BYTES = 4  # the running sum is float32 whatever the cache's type


def row_bytes(config, act_bytes: int = 2) -> int:
    """A row's step size, input and output and its two maps, one layer."""
    return (3 * config.ssm_d_inner + 2 * config.ssm_d_state) * act_bytes


def state_bytes(config) -> int:
    """A lane's running sum in one layer, read once and written once."""
    return 2 * config.ssm_d_state * config.ssm_d_inner * STATE_BYTES


def decode_update_bytes(config, lane_steps: float, act_bytes: int = 2) -> float:
    """HBM bytes of advancing ``lane_steps`` (live lane, layer) pairs by one
    row each: every pair's state in and out, and its row."""
    return lane_steps * (state_bytes(config) + row_bytes(config, act_bytes))


def chunk_scan_bytes(config, rows: float, act_bytes: int = 2) -> float:
    """HBM bytes of ``rows`` (row, layer) pairs through the chunked scan: each
    row's operands in and its output out. The state's own round trip, once a
    chunk a layer, is left out (the counter does not say how many chunks), so
    the bound is a little under what must move."""
    return rows * row_bytes(config, act_bytes)


def decode_scopes_ms(ctx, scopes) -> float | None:
    """Device time under ``scopes`` (each operation under its deepest) in the
    decode batch of ONE step, by `sparse_roofline.decode_scope_ms`'s choice of
    step. None where the program has no such scope."""
    from harness.sparse_roofline import decode_scope_ms

    parts = [decode_scope_ms(ctx, s) for s in scopes]
    found = [p for p in parts if p is not None]
    return sum(found) if found else None


def prefill_scope_ms_per_step(ctx, scope: str) -> float | None:
    """Device time under ``scope`` in the prefill half of a fused step, in ms:
    the mean over the stretch's fused executions (a class's median times its
    executions, summed over the classes). None where the stretch holds no
    fused step or the program has no such scope."""
    from harness import stepclass

    total = execs = 0.0
    seen = False
    for cls, d in (stepclass.for_ctx(ctx) or {}).get("classes", {}).items():
        if not cls.startswith(stepclass.FUSED):
            continue
        execs += d["executions"]
        if (stepclass.PREFILL, scope) in d["pair_ms"]:
            seen = True
            total += d["executions"] * d["pair_ms"][stepclass.PREFILL, scope]
    return total / execs if seen and execs else None


def hbm_share(ctx, nbytes: float, ms: float | None) -> float | None:
    """Percent: the least time the chip's memory needs for ``nbytes`` over
    ``ms`` of device time."""
    if ctx.peaks is None or not ms or not nbytes:
        return None
    return 100.0 * (nbytes / ctx.peaks["hbm_bytes_per_s"]) / (ms / 1e3)
