"""Step programs: median, over the whole executions of EVERY fused class in
the traced stretch, of the device time under `dlhalf.decode`: what the decode
batch's half of a fused step takes. It does not depend on the chunk's bucket,
so it cannot flip with the mix; beside `decode_step_device_ms` it says what
the decode batch pays for riding with a chunk."""
from harness import stepclass


def read(ctx):
    return stepclass.fused_half_ms(stepclass.for_ctx(ctx), stepclass.DECODE)
