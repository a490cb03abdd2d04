"""Step programs: median device duration of the pipelined decode program
(`_decode_pl`) in the traced stretch."""
from harness.xplane import program_median_ms


def read(ctx):
    if ctx.trace is None:
        return None
    return program_median_ms(ctx.trace, ("_decode_pl",))
