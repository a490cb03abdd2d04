"""State-space mixer: the least time the chip's memory needs for a decode
step's state update (every LIVE lane's running sum read and written once a
layer, and its row: harness/ssm_roofline.py) over the device time under
`dl.ssm_scan` in the decode batch of one step, in percent. The (lane, layer)
pairs a step advanced are the window's mean by the program's counter
(`ssm_lane_steps` over `decode_steps`); a parked lane's sum is not counted,
whatever the program does with it. Absent where the program has no such scope
or counter."""
from harness.ssm_roofline import decode_scopes_ms, decode_update_bytes, hbm_share


def read(ctx):
    pairs, steps = ctx.counters.get("ssm_lane_steps"), ctx.counters.get("decode_steps")
    if not pairs or not steps:
        return None
    return hbm_share(ctx, decode_update_bytes(ctx.config, pairs / steps),
                     decode_scopes_ms(ctx, ("dl.ssm_scan",)))
