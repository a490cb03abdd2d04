"""Step scheduling: percent of the window's fused steps whose interval
between readbacks passes by a quarter the median of their like (the same
class of step program within a band of 4096 start positions), from
`req.tel.chunks` (harness/steplog.py); the `[steplog]` table says whether the
wait for the device or the host's own turn grew. 0.0 where none was late."""
from harness import steplog


def read(ctx):
    return steplog.late_share(ctx)
