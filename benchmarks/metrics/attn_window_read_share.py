"""Window attention: cache rows the decode steps' window layers fetched of
their rings, as a share of what they would have fetched had they kept planes
read as the full-context layers read theirs (`EngineStats`
`attn_window_rows_read` over `attn_window_rows_plane`, kept by the scheduler
from the lanes' positions): 100 % while every lane is under the window, and
the window over the context after that. Absent where the program keeps no
such counters."""


def read(ctx):
    plane = ctx.counters.get("attn_window_rows_plane")
    if not plane:
        return None
    return 100.0 * ctx.counters["attn_window_rows_read"] / plane
