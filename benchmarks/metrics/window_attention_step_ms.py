"""Window attention: device time under `dl.window_attention` (a window layer's
read of its ring: the decode kernel over the blocks that hold the window) in
the decode batch of one step (`sparse_roofline.decode_scope_ms`'s choice of
step: the pipelined decode step's, else a fused step's decode half). Absent
where the program has no such scope."""
from harness.ssm_roofline import decode_scopes_ms


def read(ctx):
    return decode_scopes_ms(ctx, ("dl.window_attention",))
