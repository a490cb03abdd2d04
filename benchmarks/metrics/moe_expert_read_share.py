"""Routed experts: distinct (layer, expert) slabs the decode steps fetched, as
a share of what a sweep of every expert of every routed layer fetches
(`EngineStats` `moe_slabs_read` over `moe_slabs_whole`, counted on the device
from the routed ids and brought back with each step's tokens). Absent where
the program keeps no such counters."""


def read(ctx):
    whole = ctx.counters.get("moe_slabs_whole")
    if not whole:
        return None
    return 100.0 * ctx.counters["moe_slabs_read"] / whole
