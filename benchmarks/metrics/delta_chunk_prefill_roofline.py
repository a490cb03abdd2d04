"""Delta-rule mixer: the larger of the least times the chip's memory and its
matrix units need for the rows a fused step's chunk form computes (each row's
q, k, v, g, b and o a layer, and the recurrence's own 7 d^2 operations a head:
harness/delta_roofline.py; the decayed scores and the solve are the
implementation's and are not counted) over the device time under
`dl.delta_state` in the prefill half of a fused step (the stretch's mean), in
percent. The rows a step computes are the window's mean by the program's
counter (`delta_rows_computed`, padded rows with the real ones, over
`fused_steps`). Absent where the program has no such scope or counter."""
from harness.delta_roofline import chunk_share, heads_and_width, prefill_scope_ms_per_step


def read(ctx):
    rows, steps = ctx.counters.get("delta_rows_computed"), ctx.counters.get("fused_steps")
    if not rows or not steps or not heads_and_width(ctx.cfg)[0]:
        return None
    return chunk_share(ctx, rows / steps, prefill_scope_ms_per_step(ctx, "dl.delta_state"))
