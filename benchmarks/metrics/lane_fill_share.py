"""Step scheduling: how full the decode batch ran: live lanes summed over
the window's pipelined dispatches (`EngineStats` `live_lane_steps`) over
dispatches x lanes, in percent."""
from harness import steplog


def read(ctx):
    return steplog.counter_share(
        ctx, "live_lane_steps",
        ctx.counters.get("pipeline_dispatches", 0) * ctx.lanes)
