"""Step programs: share of the device's busy time in the traced stretch spent
in operations under `dlhalf.prefill`, whatever the class of step program: the
admitted chunks' part of the device, padding rows included."""
from harness import stepclass


def read(ctx):
    return stepclass.half_share(stepclass.for_ctx(ctx), stepclass.PREFILL)
