"""Admission: `Request.admitted_at - submitted_at` (both stamped by the
scheduler), 90th percentile over the requests admitted inside the window."""
from harness.stats import percentile
from harness.window import in_window


def read(ctx):
    waits = [
        (s.req.admitted_at - s.req.submitted_at) * 1e3
        for s in ctx.streams
        if s.req.admitted_at is not None and in_window(ctx, s.req.admitted_at)
    ]
    return percentile(waits, 90)
