"""Step programs: from a request's first prompt chunk handed to the engine to
the readback of the step that carried its final chunk (`first_dispatch_at` to
`prefill_done_at`, the program's stamps), median over the requests the window
owes a first token."""
from harness import progtrace


def read(ctx):
    return progtrace.ttft_part_p50_ms(ctx, "prefill")
