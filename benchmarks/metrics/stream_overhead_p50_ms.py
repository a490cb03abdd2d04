"""Streaming: from `engine.pipeline_consume` returning a step's tokens to the
host, to the `on_delta` callbacks of that step, median over the window's
deltas. Read from the benchmark's own span around the call (traced runs)."""
import bisect

from harness.stats import percentile
from harness.window import in_window


def read(ctx):
    returns = ctx.consume_returns
    if not returns:
        return None
    out = []
    for s in ctx.streams:
        for t in s.delta_t:
            if in_window(ctx, t):
                i = bisect.bisect_right(returns, t) - 1
                if i >= 0:
                    out.append((t - returns[i]) * 1e3)
    return percentile(out, 50)
