"""Step programs: median device duration of the whole executions of class
`dlstep.fused.b1024` in the traced stretch: the fused prefill + decode step
whose chunk rode the 1024-row bucket, told from the other buckets by the class
its operations carry (harness/stepclass.py), so the figure cannot move because
the mix of buckets moved. None where the stretch holds no such execution."""
from harness import stepclass


def read(ctx):
    return stepclass.class_median_ms(stepclass.for_ctx(ctx), "dlstep.fused.b1024")
