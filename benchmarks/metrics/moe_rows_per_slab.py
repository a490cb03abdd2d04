"""Routed experts: (row, expert) assignments the decode steps routed over the
distinct (layer, expert) slabs they fetched (`EngineStats` `moe_assignments`
over `moe_slabs_read`): how many rows a fetched slab multiplies, which is how
full the grouped kernel's eight-row tiles are. Absent where the program keeps
no such counters."""


def read(ctx):
    slabs = ctx.counters.get("moe_slabs_read")
    if not slabs:
        return None
    return ctx.counters.get("moe_assignments", 0) / slabs
