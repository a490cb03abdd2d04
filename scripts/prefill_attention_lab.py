"""PR 51's lab: attention of one prefill chunk against a 2048-position lane of
the stacked cache, alone on the chip, by the three paths the forwards have.

Three forms of one call (queries of a chunk of ``rows`` at ``start``, one lane,
layer 1 of a stack of two, bf16, as a fused step's prefill half calls it):

  kernel   ``ops/pallas_attention.py`` ``prefill_attention``: the stack in
           place, a key block at a time, the blocks up to the chunk's last row
  dense    ``models/llama.py`` ``dense_plane_attention``: the layer's planes
           converted to float32 and ``[T, heads, S]`` scores (what every
           2048-position configuration ran before PR 51)
  blocked  ``ops/blocked_attention.py``: the same walk over key blocks as an
           XLA loop (what the long-context configurations run; the bar the
           kernel has to clear, since routing the dense cells to it would have
           been the cheap version of PR 51)

What is layer invariant (the kernel's work list, the dense path's mask) is
built outside the timed program, as the forwards build it outside their layer
loops. ``--blocks`` times the kernel at other (query rows, key rows) a block,
reached by moving the module's two constants for the trace; no switch is
shipped. Times are device durations of each executed program in a profiler
trace (median of REPS executions), so the host's dispatch is not in them;
PERF.md section 6 (PR 51) has the table.

On the chip:  chiprun --timeout 1800 -- python3 scripts/prefill_attention_lab.py
Here (CPU, interpret mode, small rows, host clock only; never a device
number):      python3 scripts/prefill_attention_lab.py --rehearse
Options: --heads 32x8x128,28x4x128  --rows 256,1024  --starts 0,512
         --forms kernel,dense  --blocks 256x256,128x256,512x256,256x512
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import shutil
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_llama_multiusers_tpu.models import llama  # noqa: E402
from distributed_llama_multiusers_tpu.ops import blocked_attention  # noqa: E402
from distributed_llama_multiusers_tpu.ops import pallas_attention as pa  # noqa: E402

# (n_heads, n_kv, head): Mistral-7B, Qwen2.5-7B, and LFM2's merged 64-wide rows
HEADS = ((32, 8, 128), (28, 4, 128), (32, 8, 64))
ROWS = (64, 256, 512, 1024)
STARTS = (0, 512)
FORMS = ("kernel", "dense", "blocked")
SEQ = 2048
LAYERS, LAYER = 2, 1
REPS = 10


@contextlib.contextmanager
def blocks_as(blocks):
    """The module's (query rows, key rows) a block moved for a TRACE (lab
    only), and put back."""
    was = pa.QUERY_ROWS, pa.BLOCK_ROWS
    if blocks:
        pa.QUERY_ROWS, pa.BLOCK_ROWS = (blocks[0],), blocks[1]
    try:
        yield
    finally:
        pa.QUERY_ROWS, pa.BLOCK_ROWS = was


def make_form(form, n_kv, scale, interpret, blocks=None):
    """A jitted call of one form: ``f(q, k_all, v_all, positions, n_valid,
    aux)``, ``aux`` what the form builds outside its layer loop."""
    if form == "dense":
        def call(q, k_all, v_all, positions, n_valid, mask):
            return llama.dense_plane_attention(q, k_all, v_all, LAYER, mask, scale, n_kv)
    elif form == "blocked":
        def call(q, k_all, v_all, positions, n_valid, merged):
            # the stacks as that path keeps them, rows of n_kv * head, merged
            # outside the timed program (inside it the reshape is a copy)
            return blocked_attention.blocked_attention(
                q, *merged, LAYER, positions, n_valid, n_kv, scale)
    else:
        def call(q, k_all, v_all, positions, n_valid, work):
            with blocks_as(blocks):
                return pa.prefill_attention(
                    q, k_all, v_all, LAYER, work, scale, interpret=interpret)
    return jax.jit(call)


def aux_of(form, stacks, positions, n_valid, blocks=None):
    if form == "dense":
        return jnp.arange(SEQ)[None, None, :] <= positions[:, :, None]
    if form == "blocked":
        return tuple(jnp.asarray(k.reshape(*k.shape[:3], -1)) for k in stacks)
    with blocks_as(blocks):
        return pa.chunk_blocks(positions, n_valid, SEQ, pa.query_rows(positions.shape[1]))


def program_times(trace_dir, n_programs):
    """ms of each executed program of the newest trace under ``trace_dir``, in
    order of start, or None where the trace does not hold ``n_programs``."""
    from harness import xplane

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    chip = xplane.read_xplane(files[-1])["device"][0]
    programs = sorted(chip["modules"], key=lambda e: e["start"])
    if len(programs) != n_programs:
        return None
    return [p["dur"] / 1e6 for p in programs]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--heads", default="")
    ap.add_argument("--rows", default="")
    ap.add_argument("--starts", default="")
    ap.add_argument("--forms", default="")
    ap.add_argument("--blocks", default="")
    args = ap.parse_args()
    interpret = args.rehearse
    if not interpret and jax.devices()[0].platform != "tpu":
        sys.exit(f"the lab times a TPU; this is {jax.devices()[0].platform} "
                 "(--rehearse walks the control flow here)")
    ints = lambda text, sep: tuple(int(x) for x in text.split(sep))  # noqa: E731
    heads = tuple(ints(h, "x") for h in args.heads.split(",")) if args.heads else HEADS
    rows_list = ints(args.rows, ",") if args.rows else ((64, 256) if interpret else ROWS)
    starts = ints(args.starts, ",") if args.starts else STARTS
    forms = tuple(args.forms.split(",")) if args.forms else FORMS
    variants = [(f, None) for f in forms]
    if args.blocks:
        variants += [("kernel", ints(b, "x")) for b in args.blocks.split(",")]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_f = open(os.path.join(out_dir, "prefill_attention_lab.jsonl"), "a")

    def say(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out_f.write(line + "\n")
        out_f.flush()

    dev = jax.devices()[0]
    say({"lab": "prefill_attention", "platform": dev.platform, "device_kind": dev.device_kind,
         "reps": REPS, "blocks_in_tree": [pa.QUERY_ROWS, pa.BLOCK_ROWS], "seq": SEQ,
         "rehearsal": interpret})
    for n_heads, n_kv, hd in heads:
        scale = hd ** -0.5
        rng = np.random.default_rng(n_heads)
        shape = (LAYERS, 1, SEQ) + ((n_kv, hd) if hd == pa.HEAD_SIZE else (n_kv * hd,))
        k_all = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        v_all = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        calls = []  # (record, thunk)
        for rows in rows_list:
            q = jnp.asarray(rng.standard_normal((1, rows, n_heads, hd)), jnp.bfloat16)
            for start in starts:
                positions = (start + jnp.arange(rows, dtype=jnp.int32))[None, :]
                n_valid = jnp.asarray([rows], jnp.int32)
                outs = {}
                for form, blocks in variants:
                    if blocks and (rows % blocks[0] or SEQ % blocks[1]):
                        continue
                    f = make_form(form, n_kv, scale, interpret, blocks)
                    a = (q, k_all, v_all, positions, n_valid, aux_of(form, (k_all, v_all), positions, n_valid, blocks))
                    t0 = time.perf_counter()
                    out = np.asarray(f(*a).astype(jnp.float32)).reshape(rows, n_heads, hd)
                    name = form + ("_%dx%d" % blocks if blocks else "")
                    outs[name] = out
                    calls.append(({"n_heads": n_heads, "n_kv": n_kv, "head": hd, "rows": rows,
                                   "start": start, "form": name,
                                   "compile_s": round(time.perf_counter() - t0, 2)},
                                  partial(f, *a)))
                if "dense" in outs:
                    want = outs["dense"]
                    say({"check": True, "n_heads": n_heads, "n_kv": n_kv, "head": hd,
                         "rows": rows, "start": start,
                         "max_abs_reference": float(np.abs(want).max()),
                         "max_abs_error": {f: float(np.abs(o - want).max())
                                           for f, o in outs.items() if f != "dense"}})
        trace_dir = os.path.join(ROOT, ".bench_out", "trace", "prefill_attention_lab")
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
        del k_all, v_all


if __name__ == "__main__":
    main()
