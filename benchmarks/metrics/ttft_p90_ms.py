"""90th percentile of first delta minus due time (a window holds some tens of
requests, so few samples lie beyond it: per-layer until its spread is known)."""
from harness.stats import percentile
from harness.window import ttfts_ms


def read(ctx):
    return percentile(ttfts_ms(ctx), 90)
