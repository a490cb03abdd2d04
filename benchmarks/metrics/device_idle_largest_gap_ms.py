"""Step scheduling: the longest single stretch of the traced window in which
no operation ran on the device, in ms (one gap of 137 ms and 1400 of 0.1 ms
are the same idle share); the `[steplog]` table names it by the steps on
either side and the loop span open when it began (harness/steplog.py)."""
from harness import steplog


def read(ctx):
    return steplog.largest_gap_ms(ctx)
