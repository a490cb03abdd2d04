"""Admission: from a request's admission to its first prompt chunk handed to
the engine (`admitted_at` to `first_dispatch_at`, the program's stamps on
`req.tel`), median over the requests the window owes a first token."""
from harness import progtrace


def read(ctx):
    return progtrace.ttft_part_p50_ms(ctx, "dispatch_wait")
