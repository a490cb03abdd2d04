"""Attention: device time under `dl.attention` (the cache plane's convert,
scores, softmax, values) per execution of the pipelined decode program."""
from harness import progtrace


def read(ctx):
    red = progtrace.for_ctx(ctx)
    return progtrace.scope_ms_per_execution(red, "_decode_pl", ("dl.attention",))
