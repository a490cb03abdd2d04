"""Block-sparse attention: device time under `dl.block_scores` (the compressed
keys' append, the score pass over them, the pooling to blocks) in the decode
batch of one step: the pipelined decode program's where the traced stretch
holds one, else the decode half of a fused step (harness/sparse_roofline.py
`decode_scope_ms`). Absent where the program has no such scope."""
from harness.sparse_roofline import decode_scope_ms


def read(ctx):
    return decode_scope_ms(ctx, "dl.block_scores")
