"""99th percentile of the gaps between consecutive deltas of one stream."""
from harness.stats import percentile
from harness.window import itl_gaps_ms


def read(ctx):
    return percentile(itl_gaps_ms(ctx), 99)
