"""Routed experts, by scope: the least time the chip's HBM needs for the bytes
a decode step's routed products must move (the window's mean slabs and
assignments a step by the program's counters, with their scales, activations
in and results out: harness/moe_roofline.py `routed_step_bytes`) over the
device time under `dl.experts` in the decode batch of one step (a pipelined
decode step's where the stretch holds one, else a fused step's decode half,
which `moe_decode_roofline`'s `_decode_pl` key cannot give), in percent.
Absent where the program has no `attn_full_rows_needed` counter (a program
from before heads differed by kind), no routed counters, or was not traced."""
from harness.mixed_head_roofline import routed_shape, routed_steps_counted
from harness.moe_roofline import routed_step_bytes
from harness.ssm_roofline import decode_scopes_ms, hbm_share


def read(ctx):
    whole = ctx.counters.get("moe_slabs_whole")
    if not whole or "attn_full_rows_needed" not in ctx.counters:
        return None
    steps = routed_steps_counted(ctx.cfg, whole)
    step_bytes = routed_step_bytes(
        routed_shape(ctx.cfg), ctx.counters["moe_slabs_read"] / steps,
        ctx.counters["moe_assignments"] / steps)
    return hbm_share(ctx, step_bytes, decode_scopes_ms(ctx, ("dl.experts",)))
