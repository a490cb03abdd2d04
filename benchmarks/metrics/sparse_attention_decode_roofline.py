"""Sparse attention: the least time the chip needs for a decode step's
attention over the rows the indexer chose (their latent and rope bytes read
once, the absorbed form's operations over them, the two halves of kv_b:
harness/sparse_roofline.py, whichever peak bounds), over the device time
under `dl.attention` the decode batch of a step (the pipelined decode
step's, else a fused step's decode half), in percent. The rows chosen a
step are the window's mean by the program's counter. Absent where the program
has no indexer."""
from harness.sparse_roofline import roofline_share, sparse_attention_step_work


def read(ctx):
    chosen = ctx.counters.get("sparse_rows_selected")
    if not chosen:
        return None
    return roofline_share(
        ctx, "dl.attention",
        lambda steps: sparse_attention_step_work(ctx.config, chosen / steps, ctx.lanes))
