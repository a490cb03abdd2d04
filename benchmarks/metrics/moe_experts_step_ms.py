"""Routed experts: device time under `dl.experts` (the sort by expert, the
grouped kernel's three products, the combine) per execution of the pipelined
decode program. Absent where the program has no such scope."""
from harness import progtrace


def read(ctx):
    red = progtrace.for_ctx(ctx)
    fam = ((red or {}).get("scopes") or {}).get("_decode_pl")
    if not fam or not any("dl.experts" in ex for ex in fam["per_execution"]):
        return None
    return progtrace.scope_ms_per_execution(red, "_decode_pl", ("dl.experts",))
