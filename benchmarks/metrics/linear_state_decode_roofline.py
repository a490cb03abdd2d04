"""Linear-attention mixer: the least time the chip's memory needs for a decode
step's state update (every LIVE lane's float32 matrices read and written once
a layer, and its row: harness/sala_roofline.py) over the device time under
`dl.linear_state` in the decode batch of one step, in percent. The bytes a
step moved are the window's mean by the program's counter
(`linear_state_bytes_moved` over `decode_steps`); a parked lane's state is not
counted, whatever the program does with it. Absent where the program has no
such scope or counter."""
from harness.sala_roofline import decode_scopes_ms, decode_state_bytes, hbm_share


def read(ctx):
    moved, steps = ctx.counters.get("linear_state_bytes_moved"), ctx.counters.get("decode_steps")
    heads, d = getattr(ctx.config, "linear_n_heads", 0), getattr(ctx.config, "linear_head_dim", 0)
    if not moved or not steps or not heads:
        return None
    lane_layers = moved / steps / (2 * 4 * heads * d * d)
    return hbm_share(ctx, decode_state_bytes(ctx.config, moved / steps, lane_layers),
                     decode_scopes_ms(ctx, ("dl.linear_state",)))
