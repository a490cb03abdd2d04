"""Streaming: from the readback that gave the host a request's first token to
the consume of the next step, which emits it (`prefill_done_at` to
`first_token_at`, the program's stamps), median over the requests the window
owes a first token."""
from harness import progtrace


def read(ctx):
    return progtrace.ttft_part_p50_ms(ctx, "hold")
