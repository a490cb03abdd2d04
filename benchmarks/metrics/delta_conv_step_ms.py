"""Delta-rule mixer: device time under `dl.delta_conv` (the three short convs
of q, k and v and their windows' read and commit) in the decode batch of one
step (`sparse_roofline.decode_scope_ms`'s choice of step). Absent where the
program has no such scope."""
from harness.ssm_roofline import decode_scopes_ms


def read(ctx):
    return decode_scopes_ms(ctx, ("dl.delta_conv",))
