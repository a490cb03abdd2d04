"""Window attention: the least time the chip's memory needs for the rows
inside the live lanes' windows (keys and values, every window layer:
harness/window_roofline.py) over the device time under `dl.window_attention`
in the decode batch of one step, in percent. The rows a step fetched are the
window's mean by the program's counter (`attn_window_rows_read` over
`decode_steps`). Absent where the program has no such scope or counter."""
from harness.ssm_roofline import decode_scopes_ms, hbm_share
from harness.window_roofline import window_decode_bytes


def read(ctx):
    rows, steps = ctx.counters.get("attn_window_rows_read"), ctx.counters.get("decode_steps")
    if not rows or not steps:
        return None
    kv_bytes = {"bfloat16": 2, "float32": 4}.get(ctx.kv_dtype, 2)
    return hbm_share(ctx, window_decode_bytes(ctx.config, rows / steps, kv_bytes),
                     decode_scopes_ms(ctx, ("dl.window_attention",)))
