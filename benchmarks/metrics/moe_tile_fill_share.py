"""Routed experts: the (row, expert) pairs that took a row of the grouped
kernel's tiles over the rows of those tiles (`EngineStats` `moe_tile_pairs`
over `moe_tile_rows`: used tiles x the height the program chose for the call),
summed over the decode steps and the prompt chunks that rode them: the share
of the rows the kernel multiplied that held a row. Absent where the program
keeps no such counters."""


def read(ctx):
    rows = ctx.counters.get("moe_tile_rows")
    if not rows:
        return None
    return 100.0 * ctx.counters.get("moe_tile_pairs", 0) / rows
