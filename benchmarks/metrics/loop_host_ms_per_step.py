"""Step scheduling: the batching loop's own work a step — the seconds of its
`dl.loop.admit`, `dl.loop.dispatch` and `dl.loop.stream` spans in the traced
stretch over the count of `dl.loop.wait` spans there. What a step could be cut
to before the host becomes the bound."""
from harness import progtrace


def read(ctx):
    return progtrace.loop_host_ms_per_step(progtrace.for_ctx(ctx))
