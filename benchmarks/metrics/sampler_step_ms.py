"""Sampler: device time under `dl.sampler` (grammar mask, argmax, the
full-vocabulary sort and draw) per execution of the pipelined decode program."""
from harness import progtrace


def read(ctx):
    red = progtrace.for_ctx(ctx)
    return progtrace.scope_ms_per_execution(red, "_decode_pl", ("dl.sampler",))
