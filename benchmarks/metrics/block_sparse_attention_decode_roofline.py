"""Block-sparse attention: the least time the chip's memory needs for a decode
step's reads of the blocks its rows chose (keys and values of every chosen
block, every kv head, every sparse layer: harness/sala_roofline.py) over the
device time under `dl.attention` in the decode batch of one step, in percent.
The blocks a step attended are the window's mean by the program's counter
(`attn_blocks_read`, one layer's and one kv head's, over `decode_steps`).
Absent where the program has no such counter."""
from harness.sala_roofline import hbm_share, sparse_decode_bytes
from harness.sparse_roofline import decode_scope_ms


def read(ctx):
    blocks, steps = ctx.counters.get("attn_blocks_read"), ctx.counters.get("decode_steps")
    if not blocks or not steps:
        return None
    cache_bytes = 1 if "float8" in str(ctx.kv_dtype) else 2 if "16" in str(ctx.kv_dtype) else 4
    return hbm_share(ctx, sparse_decode_bytes(ctx.config, blocks / steps, cache_bytes),
                     decode_scope_ms(ctx, "dl.attention"))
