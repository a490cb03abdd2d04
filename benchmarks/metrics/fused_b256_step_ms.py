"""Step programs: median device duration of the whole executions of class
`dlstep.fused.b256` in the traced stretch: the fused prefill + decode step
whose chunk rode the 256-row bucket (harness/stepclass.py). None where the
stretch holds no such execution."""
from harness import stepclass


def read(ctx):
    return stepclass.class_median_ms(stepclass.for_ctx(ctx), "dlstep.fused.b256")
