"""Device: share of the device's busy time in the traced stretch spent in
operations under no `dl.*` scope at all (copies and converts XLA inserts with
no `op_name`, programs outside the step families). Near 100 % means the
executables came from a compile cache older than the scopes."""
from harness import progtrace


def read(ctx):
    red = progtrace.for_ctx(ctx)
    if not red or red["unscoped_s"] is None or not red["busy_s"]:
        return None
    return 100.0 * red["unscoped_s"] / red["busy_s"]
