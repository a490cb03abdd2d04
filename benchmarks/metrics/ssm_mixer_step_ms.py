"""State-space mixer: device time under `dl.ssm` (the norm, the projections,
the conv, the gates, the out-projection) and `dl.ssm_scan` inside it (the
running sum's read, the recurrence and its commit) in the decode batch of one
step (`sparse_roofline.decode_scope_ms`'s choice of step: the pipelined decode
step's, else a fused step's decode half). Absent where the program has no
such scope."""
from harness.ssm_roofline import decode_scopes_ms

SCOPES = ("dl.ssm", "dl.ssm_scan")


def read(ctx):
    return decode_scopes_ms(ctx, SCOPES)
