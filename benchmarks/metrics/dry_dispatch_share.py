"""Step scheduling: percent of the window's pipelined dispatches that the
batching loop made when the device had already finished everything in flight
(`EngineStats` `pipeline_dry_dispatches` over `pipeline_dispatches`): the
host was late. Counted in every run, traced or not."""
from harness import steplog


def read(ctx):
    return steplog.counter_share(ctx, "pipeline_dry_dispatches",
                                 ctx.counters.get("pipeline_dispatches"))
