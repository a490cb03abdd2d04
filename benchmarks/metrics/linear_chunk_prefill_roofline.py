"""Linear-attention mixer: the larger of the least times the chip's memory and
its matrix units need for the rows a fused step's chunk form computes (each
row's q, k, v and o a layer, and the recurrence's 5 d^2 operations a head:
harness/sala_roofline.py) over the device time under `dl.linear_state` in the
prefill half of a fused step (the stretch's mean), in percent. The rows a step
computes are the window's mean by the program's counter
(`linear_rows_computed`, padded rows with the real ones, over `fused_steps`).
Absent where the program has no such scope or counter."""
from harness.sala_roofline import chunk_share, prefill_scope_ms_per_step


def read(ctx):
    rows, steps = ctx.counters.get("linear_rows_computed"), ctx.counters.get("fused_steps")
    if not rows or not steps:
        return None
    return chunk_share(ctx, rows / steps, prefill_scope_ms_per_step(ctx, "dl.linear_state"))
