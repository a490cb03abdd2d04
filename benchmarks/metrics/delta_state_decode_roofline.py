"""Delta-rule mixer: the least time the chip's memory needs for a decode step's
state update (every LIVE lane's float32 matrices read and written once a
layer, and its row: harness/delta_roofline.py) over the device time under
`dl.delta_state` in the decode batch of one step, in percent. The bytes a step
moved are the window's mean by the program's counter (`delta_state_bytes_moved`
over `decode_steps`); a parked lane's state is not counted, whatever the
program does with it. Absent where the program has no such scope or counter."""
from harness.delta_roofline import decode_scopes_ms, decode_state_bytes, hbm_share, heads_and_width


def read(ctx):
    moved, steps = ctx.counters.get("delta_state_bytes_moved"), ctx.counters.get("decode_steps")
    if not moved or not steps or not heads_and_width(ctx.cfg)[0]:
        return None
    return hbm_share(ctx, decode_state_bytes(ctx.cfg, moved / steps),
                     decode_scopes_ms(ctx, ("dl.delta_state",)))
