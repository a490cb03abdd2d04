"""Sparse attention: device time under `dl.sparse_select` (the exact top-k of
the index scores and the gather of the chosen latent rows) in the decode batch
of one step: the pipelined decode program's where the traced stretch holds
one, else the decode half of a fused step (harness/sparse_roofline.py
`decode_scope_ms`). Absent where the program has no such scope."""
from harness.sparse_roofline import decode_scope_ms


def read(ctx):
    return decode_scope_ms(ctx, "dl.sparse_select")
