"""Step scheduling: share of the rows the window's prefill halves computed
that no prompt token filled: a chunk rides the smallest bucket that holds it
and the program computes every row of the bucket (`EngineStats`
`prefill_tokens` over `prefill_bucket_rows`). Absent where no chunk was
dispatched, or the program keeps no such counter."""


def read(ctx):
    rows = ctx.counters.get("prefill_bucket_rows")
    if not rows:
        return None
    return 100.0 * (1.0 - ctx.counters["prefill_tokens"] / rows)
