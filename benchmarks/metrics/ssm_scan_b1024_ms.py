"""State-space mixer: device time under `dl.ssm_scan` (the chunked scan over
the chunk's rows, every state-space layer) in the prefill half of one
execution of class `dlstep.fused.b1024`, the median over the traced stretch.
Absent where the stretch holds no such execution or the program has no such
scope."""
from harness import stepclass


def read(ctx):
    d = (stepclass.for_ctx(ctx) or {}).get("classes", {}).get("dlstep.fused.b1024")
    return d["pair_ms"].get((stepclass.PREFILL, "dl.ssm_scan")) if d else None
