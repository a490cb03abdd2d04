"""Prefill attention over keys wider than values: the least time the chip's
matrix units need for the multiply-adds the mask admits for the rows a fused
step's prefill half computed (harness/mixed_head_roofline.py, from the
program's counter `prefill_attn_blocks_causal` over `fused_steps`: the layers
whose attention runs a key block at a time, here the full-context ones) over
the device time whose deepest scope is `dl.attention` in the prefill half of a
fused step (the stretch's mean), in percent. Absent where the program has no
`attn_full_rows_needed` counter (a program from before heads differed by
kind), computes no prefill attention a key block at a time, or was not traced."""
from harness.mixed_head_roofline import prefill_attention_flops
from harness.ssm_roofline import prefill_scope_ms_per_step
from harness.window_roofline import mxu_share


def read(ctx):
    pairs, steps = ctx.counters.get("prefill_attn_blocks_causal"), ctx.counters.get("fused_steps")
    if not pairs or not steps or "attn_full_rows_needed" not in ctx.counters:
        return None
    return mxu_share(ctx, prefill_attention_flops(ctx.cfg, pairs / steps),
                     prefill_scope_ms_per_step(ctx, "dl.attention"))
