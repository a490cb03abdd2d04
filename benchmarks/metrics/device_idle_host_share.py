"""Step scheduling: percent of the traced window the device stood idle in gaps
of 0.1 ms or more that began while the batching loop was admitting,
dispatching or streaming (not while it waited for the device)."""
from harness import progtrace


def read(ctx):
    return progtrace.idle_host_share(progtrace.for_ctx(ctx))
