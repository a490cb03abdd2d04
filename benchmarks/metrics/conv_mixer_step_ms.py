"""Short conv mixer: device time under `dl.conv` (the norm, the in-projection,
the gates, the taps, the out-projection) and `dl.conv_state` inside it (the
lane state's read and its commit) per execution of the pipelined decode
program. Absent where the program has no such scope."""
from harness import progtrace

SCOPES = ("dl.conv", "dl.conv_state")


def read(ctx):
    red = progtrace.for_ctx(ctx)
    fam = ((red or {}).get("scopes") or {}).get("_decode_pl")
    if not fam or not any(s in ex for ex in fam["per_execution"] for s in SCOPES):
        return None
    return progtrace.scope_ms_per_execution(red, "_decode_pl", SCOPES)
