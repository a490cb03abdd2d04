"""Q40 kernel: the least time the chip's HBM needs for the bytes the decode
step's Q40 matmuls must move (planes + scales + activations in and results
out, from shapes: harness/roofline.py), over the summed device time of the
kernel's calls inside executions of the pipelined decode program
(`_decode_pl`) in the traced stretch, in percent. That program makes seven
such calls a layer and one for the output head, all at m = lanes, so the calls
counted, over that number, are the steps whose bytes are owed. The fused steps
are left out: their kernel calls mix prefill widths with the decode batch.
Memory-bound: two operations a weight at m = 16 or 32 is far under the chip's
FLOP/s per byte."""
from harness.roofline import decode_step_q40_bytes, decode_step_q40_calls


def read(ctx):
    tr = ctx.trace
    if tr is None or ctx.peaks is None:
        return None
    calls = secs = 0.0
    for (name, _shape), (s, n) in tr["program_ops"].get("_decode_pl", {}).items():
        if "q40_matmul" in name:
            secs += s
            calls += n
    if not secs:
        return None
    per_step = sum(n for _di, _do, n in decode_step_q40_calls(ctx.config, ctx.padded_vocab))
    need_s = (calls / per_step) * decode_step_q40_bytes(
        ctx.config, ctx.padded_vocab, ctx.lanes
    ) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / secs
