"""State-space mixer: the least time the chip's memory needs for the rows a
fused step's chunked scan computes (each row's step size, input, output and
maps a layer: harness/ssm_roofline.py) over the device time under
`dl.ssm_scan` in the prefill half of a fused step (the stretch's mean), in
percent. The rows a step computes are the window's mean by the program's
counter (`ssm_rows_computed`, padded rows with the real ones, over
`fused_steps`). Absent where the program has no such scope or counter."""
from harness.ssm_roofline import chunk_scan_bytes, hbm_share, prefill_scope_ms_per_step


def read(ctx):
    rows, steps = ctx.counters.get("ssm_rows_computed"), ctx.counters.get("fused_steps")
    if not rows or not steps:
        return None
    return hbm_share(ctx, chunk_scan_bytes(ctx.config, rows / steps),
                     prefill_scope_ms_per_step(ctx, "dl.ssm_scan"))
