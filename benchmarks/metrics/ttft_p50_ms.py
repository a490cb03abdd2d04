"""Median of first delta minus due time, over the requests due in the window."""
from harness.stats import percentile
from harness.window import ttfts_ms


def read(ctx):
    return percentile(ttfts_ms(ctx), 50)
