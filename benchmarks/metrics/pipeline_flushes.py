"""Step scheduling: chains of the pipelined loop cut short inside the window
(`EngineStats.pipeline_flushes`); should read 0."""


def read(ctx):
    return ctx.counters.get("pipeline_flushes")
