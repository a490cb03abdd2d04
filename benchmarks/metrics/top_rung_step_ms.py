"""Step programs: median, over the WHOLE window, of the interval between two
readbacks (`StepRecord.interval_s`: what every live lane waited for its token)
of the steps whose prompt chunk rode the largest rung of the cell's prefill
ladder, from the records the batching loop leaves on `req.tel.chunks`
(harness/steplog.py). The step `itl_p99_ms` sits on, from every such step of
the 40 s where `fused_b1024_step_ms` has the traced 3 s. None where the window
holds no such step, or the program keeps no record."""
from harness import steplog


def read(ctx):
    return steplog.top_rung_step_ms(ctx)
