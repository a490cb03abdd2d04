"""Prefill attention: the least time the chip's matrix units need for the
multiply-adds the mask admits for the rows a fused step's prefill half
computed (harness/window_roofline.py, from the program's counter
`prefill_attn_blocks_causal` over `fused_steps`) over the device time under
`dl.attention` in the prefill half of a fused step (the stretch's mean), in
percent. Absent where the program computes no prefill attention a key block
at a time, or has no such counter."""
from harness.window_roofline import mxu_share, prefill_attention_flops, prefill_attention_ms


def read(ctx):
    pairs, steps = ctx.counters.get("prefill_attn_blocks_causal"), ctx.counters.get("fused_steps")
    if not pairs or not steps:
        return None
    return mxu_share(ctx, prefill_attention_flops(ctx.config, pairs / steps),
                     prefill_attention_ms(ctx))
