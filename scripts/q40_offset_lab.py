"""PR 49's lab: what the Q40 slab kernel's -8 costs by where it is applied,
the kernel alone on the chip, by rows, on the shapes the benchmark's cells run.

Three forms of one call (``ops/pallas_q40.py`` ``_q40_matmul_core``, the default
chain, bf16 rows against a layer of a stack under a traced index, as the cells
call it):

  fold   the -8 folded into a correction dot a sub-tile a k chunk (what every
         call ran before PR 49, and what a block under SUBTRACT_MIN_ROWS runs)
  sub    the -8 taken off the nibbles in the dequant chain, no correction dot
         (what a block of SUBTRACT_MIN_ROWS rows and more runs)
  hoist  LAB ONLY, the fallback ISSUE 49 named and the lab rejected: the block
         sums accumulated over the k chunks in a scratch ``[m, d_in / 32]``
         and ONE correction dot a sub-tile at the last chunk, against the
         tile's whole scale plane (a block spec that ignores k). Planes of one
         chunk have nothing to hoist and are skipped.

fold and sub are the product's own bodies, reached by moving the module's
threshold for the trace; no switch is shipped. Times are the device durations
of the kernel's events in a profiler trace (median of REPS executions), so the
host's dispatch is not in them; PERF.md section 6 (PR 49) has the table and
the threshold read off it.

On the chip:  chiprun --timeout 2400 -- python3 scripts/q40_offset_lab.py
Here (CPU, interpret mode, tiny planes, host clock only; never a device
number):      python3 scripts/q40_offset_lab.py --rehearse
Options: --shapes 4096x14336,14336x4096  --rows 256,1024  --forms fold,sub
"""

from __future__ import annotations

import argparse
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
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from distributed_llama_multiusers_tpu.ops import pallas_q40 as pq  # noqa: E402
from distributed_llama_multiusers_tpu.quants.packed import (  # noqa: E402
    PackedQ40,
    q40_matmul_xla,
)

ROWS = (16, 32, 64, 128, 256, 512, 1024)
FORMS = ("fold", "sub", "hoist")
REPS = 10
LAYERS = 2
# (configuration file, parameter) of the planes timed: the dense cells' FFNs
# and projections, Jamba's MLP and mixer projections, Command A+'s 128-head q
# and o projections, and one head
PLANES = [
    ("mistral-7b-v0.3", ".layers.w1"), ("mistral-7b-v0.3", ".layers.w2"),
    ("qwen2.5-7b", ".layers.w1"), ("qwen2.5-7b", ".layers.w2"),
    ("mistral-7b-v0.3", ".layers.wq"), ("mistral-7b-v0.3", ".layers.wk"),
    ("qwen2.5-7b", ".layers.wq"),
    ("jamba2-3b", ".dense.w1"), ("jamba2-3b", ".dense.w2"),
    ("jamba2-3b", ".ssm.w_in"), ("jamba2-3b", ".ssm.w_out"),
    ("jamba2-3b", ".ssm.w_x"),
    ("command-a-plus-05-2026", ".attn.wq"), ("command-a-plus-05-2026", ".attn.wo"),
    ("mistral-7b-v0.3", ".wcls"),
]
REHEARSAL_SHAPES = [("tiny", "k_chunks", 2048, 1152), ("tiny", "one_slab", 64, 256)]


def config_shapes():
    """[(configuration, parameter, d_in, d_out)] of PLANES, read from the
    parameter trees the benchmark's families build from
    ``benchmarks/configs/*.json`` (shapes only: nothing is generated)."""
    from harness import cells

    bench = cells.load_benchmark()
    found = {}
    for name in sorted({c for c, _ in PLANES}):
        cfg = cells.load_config_file(bench, name)
        family = cells.load_family(cfg)
        config = family.program_config(cfg)
        tensors = jax.eval_shape(
            lambda: family.device_weights(config, 0, jnp.bfloat16))
        params = jax.eval_shape(
            lambda t: family.assemble_params(config, t), tensors)
        flat, _ = jax.tree_util.tree_flatten_with_path(
            params, is_leaf=lambda n: isinstance(n, PackedQ40))
        for path, w in flat:
            if isinstance(w, PackedQ40):
                found[name, jax.tree_util.keystr(path)] = (
                    w.packed.shape[-2] * 2, w.packed.shape[-1])
    return [(c, p, *found[c, p]) for c, p in PLANES]


def _hoist_kernel(x_ref, packed_ref, scales_ref, plane_ref, out_ref, acc_ref,
                  bs_ref, *, w_dtype, sub_tiles, n_k):
    """The default chain with the correction hoisted over the k chunks: chunk
    k's block sums land in columns ``[k * n_blk, (k + 1) * n_blk)`` of the
    scratch (the 0/1 matrix is built at that offset, so no lane is sliced at
    a traced position), and the last chunk subtracts 8 * (sums @ all scales)
    from the accumulator, a sub-tile at a time."""
    rows, _ = packed_ref.shape
    n_blk = rows // 16
    k = pl.program_id(2)
    x = x_ref[...].astype(w_dtype)
    shape = (2 * rows, n_k * n_blk)
    col_blk = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) >> 5) + k * n_blk
    ind = (col_blk == jax.lax.broadcasted_iota(jnp.int32, shape, 1)).astype(x.dtype)
    exact = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    b = jnp.dot(x, ind, preferred_element_type=jnp.float32, precision=exact)

    @pl.when(k == 0)
    def _():
        bs_ref[...] = b

    @pl.when(k > 0)
    def _():
        bs_ref[...] = bs_ref[...] + b

    off = 0
    for t in sub_tiles:
        s = pq._f16_bits_to_f32(scales_ref[:, off:off + t])
        p = packed_ref[:, off:off + t].astype(jnp.int32)
        nib = pq._natural_order(p & 0x0F, p >> 4, n_blk, t)
        w = (nib.astype(jnp.float32) * s[:, None, :]).reshape(
            2 * rows, t).astype(w_dtype)
        part = jnp.dot(x, w, preferred_element_type=jnp.float32)
        pq._acc_epilogue(part, off, t, k, n_k, out_ref, acc_ref)
        off += t

    @pl.when(k == n_k - 1)
    def _():
        bs = bs_ref[...]
        off = 0
        for t in sub_tiles:
            s_all = pq._f16_bits_to_f32(plane_ref[:, off:off + t])
            corr = jnp.dot(bs, s_all, preferred_element_type=jnp.float32)
            out_ref[:, off:off + t] = (
                acc_ref[:, off:off + t] - 8.0 * corr).astype(out_ref.dtype)
            off += t


def hoist_call(x, w, layer, interpret):
    """``_q40_matmul_core``'s call with the hoisted correction: the same plan,
    blocks and grid, one more input (the tile's whole scale plane) and one
    more scratch. x: [m, d_in] bf16, m whole tiles; w a stack."""
    d_in, d_out = w.d_in, w.d_out
    w_tile, rows = pq._plan_blocks(d_in, d_out)
    n_k = (d_in // 2) // rows
    assert n_k > 1, "one chunk: nothing to hoist"
    m_pad = x.shape[0]
    assert pq._m_geometry(m_pad, x.dtype)[0] == m_pad
    m_block, w_tile = pq._row_plan(m_pad, w_tile, rows, n_k, x.dtype.itemsize)
    sub = pq._sub_tiles(w_tile)
    scales = jax.lax.dynamic_index_in_dim(w.scales, layer, 0, keepdims=False)
    bits = jax.lax.bitcast_convert_type(scales, jnp.int16)
    need = (pq._block_bytes(m_block, w_tile, rows, n_k, x.dtype.itemsize)
            + 2 * (d_in // 32) * w_tile * 2 + m_block * (d_in // 32) * 4)
    kernel = partial(_hoist_kernel, w_dtype=jnp.float32 if interpret else jnp.bfloat16,
                     sub_tiles=sub, n_k=n_k)
    return pl.pallas_call(
        partial(pq._q40_matmul_kernel, body=kernel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m_pad // m_block, d_out // w_tile, n_k),
            in_specs=[
                pl.BlockSpec((m_block, 2 * rows), lambda i, j, k, l: (i, k)),
                pl.BlockSpec((None, rows, w_tile), lambda i, j, k, l: (l[0], k, j)),
                pl.BlockSpec((rows // 16, w_tile), lambda i, j, k, l: (k, j)),
                pl.BlockSpec((d_in // 32, w_tile), lambda i, j, k, l: (0, j)),
            ],
            out_specs=pl.BlockSpec((m_block, w_tile), lambda i, j, k, l: (i, j)),
            scratch_shapes=[pltpu.VMEM((m_block, w_tile), jnp.float32),
                            pltpu.VMEM((m_block, d_in // 32), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, d_out), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=pq._vmem_limit(need)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), x, w.packed, bits, bits)


def make_form(form, interpret):
    """A jitted call of one form. fold and sub are the product's bodies: the
    threshold is moved for the TRACE (a fresh function a form, so JAX traces
    each), and put back."""
    w_dtype = jnp.float32 if interpret else jnp.bfloat16

    def call(x, packed, scales, layer):
        w = PackedQ40(packed, scales)
        if form == "hoist":
            return hoist_call(x, w, layer, interpret)
        was = pq.SUBTRACT_MIN_ROWS
        pq.SUBTRACT_MIN_ROWS = 0 if form == "sub" else 1 << 30
        try:
            return pq._q40_matmul_core(x, w, interpret, w_dtype, "v4", layer)
        finally:
            pq.SUBTRACT_MIN_ROWS = was

    return jax.jit(call)


def kernel_times(trace_dir, n_programs):
    """ms of the kernel in each executed program of the newest trace under
    ``trace_dir``, in order of start, or None where the trace does not hold
    ``n_programs`` programs: the longest custom call among the device's
    operations inside each program's span (the others are XLA's own: a
    stack's scale slice converted, a pad)."""
    from harness import xplane

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    chip = xplane.read_xplane(files[-1])["device"][0]
    programs = sorted(chip["modules"], key=lambda e: e["start"])
    if len(programs) != n_programs:
        return None
    calls = sorted((e for e in chip["ops"] if e["opcode"] == "custom-call"),
                   key=lambda e: e["start"])
    out, i = [], 0
    for prog in programs:
        end = prog["start"] + prog["dur"]
        while i < len(calls) and calls[i]["start"] < prog["start"]:
            i += 1
        inside = []
        while i < len(calls) and calls[i]["start"] < end:
            inside.append(calls[i]["dur"])
            i += 1
        out.append(max(inside) / 1e6 if inside else float("nan"))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--shapes", default="")
    ap.add_argument("--rows", default="")
    ap.add_argument("--forms", default="")
    args = ap.parse_args()
    interpret = args.rehearse
    if not interpret and jax.devices()[0].platform != "tpu":
        sys.exit(f"the lab times a TPU; this is {jax.devices()[0].platform} "
                 "(--rehearse walks the control flow here)")
    shapes = REHEARSAL_SHAPES if interpret else config_shapes()
    if args.shapes:
        shapes = [s for s in shapes if f"{s[2]}x{s[3]}" in args.shapes.split(",")]
    rows_list = tuple(int(r) for r in args.rows.split(",")) if args.rows else ROWS
    forms = tuple(args.forms.split(",")) if args.forms else FORMS
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_f = open(os.path.join(out_dir, "q40_offset_lab.jsonl"), "a")

    def say(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out_f.write(line + "\n")
        out_f.flush()

    dev = jax.devices()[0]
    say({"lab": "q40_offset", "platform": dev.platform, "device_kind": dev.device_kind,
         "reps": REPS, "threshold_in_tree": pq.SUBTRACT_MIN_ROWS, "rehearsal": interpret})
    x_dtype = jnp.float32 if interpret else jnp.bfloat16
    seen = set()
    for config, param, d_in, d_out in shapes:
        if (d_in, d_out) in seen:
            continue
        seen.add((d_in, d_out))
        w_tile, k_rows = pq._plan_blocks(d_in, d_out)
        n_k = (d_in // 2) // k_rows
        packed = jax.random.bits(jax.random.PRNGKey(d_in), (LAYERS, d_in // 2, d_out), jnp.uint8)
        scales = (jax.random.uniform(jax.random.PRNGKey(d_out), (LAYERS, d_in // 32, d_out))
                  * 0.01 + 0.001).astype(jnp.float16)
        layer = jnp.int32(1)
        calls = []  # (rows, form, thunk)
        for rows in rows_list:
            x = jax.random.normal(jax.random.PRNGKey(rows), (rows, d_in), jnp.float32).astype(x_dtype)
            outs = {}
            for form in forms:
                if form == "hoist" and n_k == 1:
                    continue
                f = make_form(form, interpret)
                t0 = time.perf_counter()
                outs[form] = np.asarray(f(x, packed, scales, layer).astype(jnp.float32))
                compile_s = time.perf_counter() - t0
                calls.append((rows, form, partial(f, x, packed, scales, layer), compile_s))
            want = np.asarray(q40_matmul_xla(
                x.astype(jnp.float32), PackedQ40(packed[1], scales[1])))
            say({"check": True, "d_in": d_in, "d_out": d_out, "rows": rows,
                 "max_abs_reference": float(np.abs(want).max()),
                 "max_abs_error": {f: float(np.abs(o - want).max()) for f, o in outs.items()}})
        trace_dir = os.path.join(ROOT, ".bench_out", "trace", "q40_offset_lab")
        shutil.rmtree(trace_dir, ignore_errors=True)
        if not interpret:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        host = []
        for _, _, thunk, _ in calls:
            ts = []
            for _ in range(REPS):
                t0 = time.perf_counter()
                thunk().block_until_ready()
                ts.append(time.perf_counter() - t0)
            host.append(1e3 * float(np.median(ts)))
        times = None
        if not interpret:
            jax.profiler.stop_trace()
            times = kernel_times(trace_dir, REPS * len(calls))
        for i, (rows, form, _, compile_s) in enumerate(calls):
            rec = {"config": config, "param": param, "d_in": d_in, "d_out": d_out,
                   "w_tile": w_tile, "n_k": n_k, "rows": rows, "form": form,
                   "host_ms": round(host[i], 4), "compile_s": round(compile_s, 2)}
            if times is not None:
                durs = times[i * REPS:(i + 1) * REPS]
                rec["kernel_ms"] = round(float(np.median(durs)), 5)
                rec["kernel_ms_min"] = round(min(durs), 5)
            say(rec)
        del packed, scales


if __name__ == "__main__":
    main()
