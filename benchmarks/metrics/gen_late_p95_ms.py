"""How late the load generator ran: submit time minus due time, 95th
percentile over the open-loop requests due inside the window."""
from harness.stats import percentile
from harness.window import owed_streams


def read(ctx):
    late = [(s.submit_t - s.due_t) * 1e3 for s in owed_streams(ctx) if s.due_t is not None]
    return percentile(late, 95)
