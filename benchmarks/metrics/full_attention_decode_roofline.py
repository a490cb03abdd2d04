"""Full-context attention with its own kv heads: the least time the chip's
memory needs for the rows the live lanes' positions reach (keys and values at
their published widths, every full-context layer:
harness/mixed_head_roofline.py) over the device time whose deepest scope is
`dl.attention` in the decode batch of one step, in percent. The rows a step
needs are the window's mean by the program's counter (`attn_full_rows_needed`
over `decode_steps`). Absent where the program has no such scope or counter."""
from harness.mixed_head_roofline import FULL, decode_read_bytes, kv_bytes_of
from harness.ssm_roofline import decode_scopes_ms, hbm_share


def read(ctx):
    rows, steps = ctx.counters.get("attn_full_rows_needed"), ctx.counters.get("decode_steps")
    if not rows or not steps:
        return None
    return hbm_share(ctx, decode_read_bytes(ctx.cfg, FULL, rows / steps, kv_bytes_of(ctx)),
                     decode_scopes_ms(ctx, ("dl.attention",)))
