"""Routed experts: the least time the chip's HBM needs for the bytes the decode
steps' routed products must move (the slabs the program's counters say were
read, with their scales, plus activations in and results out:
harness/moe_roofline.py), over the summed device time of the grouped Q40
kernel's calls inside executions of the pipelined decode program
(`_decode_pl`) in the traced stretch, in percent. The slabs a step read are
the window's mean (the counters cover the window, the trace a stretch of it);
the steps traced are the kernel's calls over the three a routed layer makes.
Absent where the program has no such kernel or counters."""
from harness.moe_roofline import routed_matmuls_per_step, routed_step_bytes

KERNEL = "q40_grouped"


def read(ctx):
    tr = ctx.trace
    whole = ctx.counters.get("moe_slabs_whole")
    if tr is None or ctx.peaks is None or not whole:
        return None
    calls = secs = 0.0
    for (name, _shape), (s, n) in tr["program_ops"].get("_decode_pl", {}).items():
        if KERNEL in name:
            secs += s
            calls += n
    if not secs:
        return None
    c = ctx.config
    counted_steps = whole / ((c.n_layers - c.n_dense_layers) * c.n_experts)
    step_bytes = routed_step_bytes(
        c, ctx.counters["moe_slabs_read"] / counted_steps,
        ctx.counters["moe_assignments"] / counted_steps)
    need_s = (calls / routed_matmuls_per_step(c)) * step_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / secs
