"""Delta-rule mixer: device time under `dl.delta_state` (the matrix state's
read, the one-row recurrence and its commit) in the decode batch of one step
(`sparse_roofline.decode_scope_ms`'s choice of step: the pipelined decode
step's, else a fused step's decode half). Absent where the program has no such
scope."""
from harness.ssm_roofline import decode_scopes_ms


def read(ctx):
    return decode_scopes_ms(ctx, ("dl.delta_state",))
