"""Sparse attention: the least time the chip needs for a decode step's
indexers (the projections' Q40 bytes, the index keys the live lanes hold read
once, a multiply-add a number a head: harness/sparse_roofline.py, whichever
of HBM and the matrix units bounds), over the device time under `dl.indexer`
the decode batch of a step (the pipelined decode step's, else a fused step's
decode half), in percent. The rows scored a step are the window's mean by the
program's counter. Absent where the program has no such scope or counter."""
from harness.sparse_roofline import indexer_step_work, roofline_share


def read(ctx):
    scored = ctx.counters.get("indexer_rows_scored")
    if not scored:
        return None
    return roofline_share(
        ctx, "dl.indexer", lambda steps: indexer_step_work(ctx.config, scored / steps, ctx.lanes))
