"""Linear-attention mixer: device time under `dl.linear_attention` (the norm,
the projections, the head norms, the rotation, the gate, the out-projection)
and `dl.linear_state` inside it (the matrix state's read, the recurrence and
its commit) in the decode batch of one step (`sparse_roofline.decode_scope_ms`'s
choice of step: the pipelined decode step's, else a fused step's decode
half). Absent where the program has no such scope."""
from harness.sala_roofline import decode_scopes_ms

SCOPES = ("dl.linear_attention", "dl.linear_state")


def read(ctx):
    return decode_scopes_ms(ctx, SCOPES)
