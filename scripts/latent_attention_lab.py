"""PR 59's lab: decode attention over a latent cache, alone on the chip, at the
block heights the in-place kernel could fetch by and by the plane read it
replaces.

One program a form: a ``lax.scan`` over the layers of a stacked latent cache
(Kanana's cell: 24 layers, 32 lanes, 2048 positions, rank 512 beside a rope
leaf of 128, 32 heads, bf16), one query row a lane, the lanes at positions
drawn from the chat mix (``benchmarks/traffic/chat_saturated.json``: a request
weighed by the steps it decodes for, caught at a uniform point of its answer),
what is layer invariant (the work list, the mask) built once outside the scan
as the forward builds it:

  kernel_<rows>  ``ops/pallas_attention.py`` ``decode_attention(latent=True)``
                 with ``LATENT_BLOCK_ROWS`` moved to ``<rows>`` for the trace
                 (lab only; the tree keeps the one that wins, no switch is
                 shipped)
  dense          ``models/deepseek.py`` ``latent_plane_attention``: each
                 layer's planes sliced out of the stack, ``[lanes, heads, S]``
                 float32 scores (what the cell ran before PR 59)

Both take the absorbed queries and give ``o~``; ``wuk`` / ``wuv`` are XLA's on
either path and are not timed. Times are device durations of each executed
program in a profiler trace (median of REPS executions), so the host's
dispatch is not in them; PERF.md section 6 (PR 59) has the table.

On the chip:  chiprun -- python3 scripts/latent_attention_lab.py
Here (CPU, interpret mode, two layers, host clock only; never a device
number):      python3 scripts/latent_attention_lab.py --rehearse
Options: --blocks 256,512 (128 and 1024 compile too)  --draws 4  --lanes 32
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks"), os.path.join(ROOT, "scripts")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_llama_multiusers_tpu.models import deepseek  # noqa: E402
from distributed_llama_multiusers_tpu.ops import pallas_attention as pa  # noqa: E402
from prefill_attention_lab import program_times  # noqa: E402

HEADS, RANK, ROPE_LEAF, ROPE = 32, 512, 128, 64
SEQ, LAYERS = 2048, 24
SCALE = (128 + ROPE) ** -0.5
REPS = 10


@contextlib.contextmanager
def block_rows_as(rows):
    """The module's latent block height moved for a TRACE (lab only), and put
    back."""
    was = pa.LATENT_BLOCK_ROWS
    pa.LATENT_BLOCK_ROWS = rows
    try:
        yield
    finally:
        pa.LATENT_BLOCK_ROWS = was


def chat_positions(lanes: int, draw: int) -> np.ndarray:
    """A decode step's lane positions under the chat mix: a request of the
    traffic file's list a lane, drawn by the steps it decodes for, at a
    uniform point of its answer."""
    from harness.traffic import Traffic

    with open(os.path.join(ROOT, "benchmarks", "traffic", "chat_saturated.json")) as f:
        traffic = Traffic(json.load(f), lanes)
    specs = [traffic.spec(traffic.in_flight + k) for k in range(traffic.n)]
    steps = np.array([s.full_max_tokens for s in specs], np.float64)
    rng = np.random.default_rng([2059, draw])
    chosen = rng.choice(len(specs), size=lanes, p=steps / steps.sum())
    at = [specs[i].prompt_tokens + int(rng.integers(specs[i].full_max_tokens)) for i in chosen]
    return np.minimum(np.asarray(at, np.int32), SEQ - 1)


def make_form(rows, interpret):
    """A jitted ``f(q, c_all, r_all, positions)``: every layer's ``o~``
    summed, so that no layer's call is dead."""
    def call(q, c_all, r_all, positions):
        if rows:
            work = pa.lane_blocks(positions, SEQ, rows)
        else:
            mask = jnp.arange(SEQ)[None, None, :] <= positions[:, None, None]

        def layer(acc, l):
            if rows:
                with block_rows_as(rows):
                    o = pa.decode_attention(q, c_all, r_all, l, work, SCALE,
                                            interpret=interpret, latent=True)
            else:
                plane = lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False)
                o = deepseek.latent_plane_attention(
                    q[:, None, :, :RANK], q[:, None, :, RANK:], plane(c_all), plane(r_all),
                    mask, SCALE)[:, 0]
            return acc + o, None

        zero = jnp.zeros((q.shape[0], HEADS, RANK), jnp.float32)
        return jax.lax.scan(layer, zero, jnp.arange(c_all.shape[0], dtype=jnp.int32))[0]
    return jax.jit(call)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--blocks", default="256,512")
    ap.add_argument("--draws", type=int, default=4)
    ap.add_argument("--lanes", type=int, default=32)
    args = ap.parse_args()
    interpret = args.rehearse
    if not interpret and jax.devices()[0].platform != "tpu":
        sys.exit(f"the lab times a TPU; this is {jax.devices()[0].platform} "
                 "(--rehearse walks the control flow here)")
    blocks = tuple(int(b) for b in args.blocks.split(","))
    lanes, layers = args.lanes, 2 if interpret else LAYERS
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_f = open(os.path.join(out_dir, "latent_attention_lab.jsonl"), "a")

    def say(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out_f.write(line + "\n")
        out_f.flush()

    dev = jax.devices()[0]
    say({"lab": "latent_attention", "platform": dev.platform, "device_kind": dev.device_kind,
         "reps": REPS, "block_rows_in_tree": pa.LATENT_BLOCK_ROWS, "lanes": lanes, "heads": HEADS,
         "layers": layers, "seq": SEQ, "rank": RANK, "rope_leaf": ROPE_LEAF,
         "rehearsal": interpret})
    rng = np.random.default_rng(59)
    c_all = jnp.asarray(rng.standard_normal((layers, lanes, SEQ, RANK)), jnp.bfloat16)
    r_all = jnp.asarray(rng.standard_normal((layers, lanes, SEQ, ROPE_LEAF)), jnp.bfloat16)
    r_all = r_all.at[..., ROPE:].set(0)
    q = jnp.asarray(rng.standard_normal((lanes, HEADS, RANK + ROPE_LEAF)) * 0.3, jnp.bfloat16)
    q = q.at[..., RANK + ROPE:].set(0)
    forms = {f"kernel_{b}": make_form(b, interpret) for b in blocks}
    forms["dense"] = make_form(0, interpret)
    calls = []  # (record, thunk)
    for draw in range(args.draws):
        positions = chat_positions(lanes, draw)
        at = jnp.asarray(positions)
        outs = {}
        for name, f in forms.items():
            rows = int(name.split("_")[1]) if name != "dense" else 0
            t0 = time.perf_counter()
            outs[name] = np.asarray(f(q, c_all, r_all, at))
            rec = {"form": name, "draw": draw, "mean_position": float(positions.mean()),
                   "compile_s": round(time.perf_counter() - t0, 2)}
            if rows:
                rec.update(items_a_layer=int((positions // rows + 1).sum()),
                           read_share=pa.rows_read(positions, SEQ, rows) / (lanes * SEQ))
            calls.append((rec, partial(f, q, c_all, r_all, at)))
        want = outs["dense"]
        say({"check": True, "draw": draw, "max_abs_reference": float(np.abs(want).max()),
             "max_abs_error": {n: float(np.abs(o - want).max())
                               for n, o in outs.items() if n != "dense"}})
    trace_dir = os.path.join(ROOT, ".bench_out", "trace", "latent_attention_lab")
    shutil.rmtree(trace_dir, ignore_errors=True)
    if not interpret:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    host = []
    for _, thunk in calls:
        ts = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            thunk().block_until_ready()
            ts.append(time.perf_counter() - t0)
        host.append(1e3 * float(np.median(ts)))
    times = None
    if not interpret:
        jax.profiler.stop_trace()
        times = program_times(trace_dir, REPS * len(calls))
    for i, (rec, _) in enumerate(calls):
        rec["host_ms"] = round(host[i], 4)
        if times is not None:
            durs = times[i * REPS:(i + 1) * REPS]
            rec["device_ms"] = round(float(np.median(durs)), 5)
            rec["device_ms_min"] = round(min(durs), 5)
        say(rec)


if __name__ == "__main__":
    main()
