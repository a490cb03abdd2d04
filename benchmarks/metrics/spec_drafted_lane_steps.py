"""Speculation: (drafted lane, verify step) pairs inside the window
(`EngineStats.spec_lane_steps`). The scheduler drafts for greedy lanes only, so
a sampled mix reads 0 here: the cell bypasses speculation, and this says so."""


def read(ctx):
    return ctx.counters.get("spec_lane_steps")
