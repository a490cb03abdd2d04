"""Step scheduling: percent of the window's seconds that lie between a
readback's return and the return of a dispatch the loop made dry
(`EngineStats` `pipeline_dry_s` over the window): the most the device can
have stood idle for want of a dispatch, on the host's clock."""
from harness import steplog


def read(ctx):
    return steplog.counter_share(ctx, "pipeline_dry_s", ctx.seconds)
