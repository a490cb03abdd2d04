"""Block-sparse attention: blocks the decode steps' rows attended as a share
of the blocks they held (`EngineStats` `attn_blocks_read` over
`attn_blocks_held`, kept by the scheduler from the lanes' positions): 100 %
while every lane is under `dense_len`, and `topk` over the context's blocks
after that. Absent where the program keeps no such counters."""


def read(ctx):
    held = ctx.counters.get("attn_blocks_held")
    if not held:
        return None
    return 100.0 * ctx.counters["attn_blocks_read"] / held
