"""KV residency: per execution of the pipelined decode program (`_decode_pl`),
the device time under none of the program's leaf scopes: the self time of
`dl.layers` (what runs inside the layer scan beside the model's own
arithmetic) plus the operations that carry no scope at all. That is what XLA
adds around the model: whole-cache carry copies, per-layer slices and updates
of the stacked cache and of the stacked weight planes."""
from harness import progtrace


def read(ctx):
    return progtrace.overhead_ms_per_execution(progtrace.for_ctx(ctx), "_decode_pl")
