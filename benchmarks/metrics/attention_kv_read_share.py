"""Attention: cache rows the decode steps' attention fetched, as a share of
what reading every lane's whole K and V plane fetches (`EngineStats`
`attn_kv_rows_read` over `attn_kv_rows_whole`, kept by the scheduler from the
lanes' positions): how much of the read is left once the kernel stops at each
lane's row. Absent where the program keeps no such counters."""


def read(ctx):
    whole = ctx.counters.get("attn_kv_rows_whole")
    if not whole:
        return None
    return 100.0 * ctx.counters["attn_kv_rows_read"] / whole
