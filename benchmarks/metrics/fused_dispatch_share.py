"""Step scheduling: share of the window's pipelined dispatches that carried a
prompt chunk (`EngineStats` `fused_steps` over `pipeline_dispatches`): where
it passes 50 %, the median gap between tokens is a fused step's and not a
decode step's."""


def read(ctx):
    steps = ctx.counters.get("pipeline_dispatches")
    if not steps:
        return None
    return 100.0 * ctx.counters.get("fused_steps", 0) / steps
