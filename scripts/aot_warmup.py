"""Compile the server's whole warm-up for a DESCRIBED TPU v5e — no chip.

    JAX_PLATFORMS=cpu python scripts/aot_warmup.py --model m.m            # one chip
    JAX_PLATFORMS=cpu python scripts/aot_warmup.py --model m.m --tp 4     # 2x2 mesh

The third rehearsal of the on-chip-measurement guide (section 2) for the
serving path: the engine is built as `load_stack` builds it, `jax.jit` is
replaced for its lifetime by a proxy that lowers and compiles each program
for described `v5e:2x2` devices instead of running it (and hands back zeros
of the right shapes), and `warmup_engine` is driven unchanged. Every program
the server would warm is thereby put through the chip's own compiler: what
Mosaic or XLA:TPU refuses, refuses here, at no chip time. One line per
program: compile seconds, bytes from `memory_analysis()`, kernel calls
(`tpu_custom_call`) and collectives in the optimized HLO.

PR 21 found with it that no dequant mode compiled at prefill widths (scoped
VMEM) and that libtpu has no custom-call partitioner. A compile that passes
is not a chip run; nothing here is a time or a rate of the device. Write the
model with formats/synthetic (`chip_smoke.py --phase prepare` shows how); the
1B at 8 lanes takes about 10 minutes of compiling. Only one process can hold
libtpu: tests/test_chip_compile_*.py skip while this runs.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding  # noqa: E402

from distributed_llama_multiusers_tpu.formats import load_model_header  # noqa: E402
from distributed_llama_multiusers_tpu.models.llama import KVCache, LlamaParams  # noqa: E402
from distributed_llama_multiusers_tpu.models.loader import (  # noqa: E402
    load_params_from_m_quantized,
)
from distributed_llama_multiusers_tpu.ops import linear, pallas_q40  # noqa: E402
from distributed_llama_multiusers_tpu.parallel import MeshPlan, make_mesh  # noqa: E402
from distributed_llama_multiusers_tpu.parallel.sharding import (  # noqa: E402
    cache_shardings,
    param_shardings,
)
from distributed_llama_multiusers_tpu.runtime import engine as engine_mod  # noqa: E402

COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", required=True, help="a Q40 .m file")
    ap.add_argument("--tp", type=int, default=1, choices=(1, 2, 4))
    ap.add_argument("--lanes", type=int, default=8)
    args = ap.parse_args()

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = (make_mesh(MeshPlan(tp=args.tp), devices=list(topo.devices))
            if args.tp > 1 else None)
    default = (NamedSharding(mesh, P()) if mesh is not None
               else SingleDeviceSharding(topo.devices[0]))

    # ops/linear.py asks jax.devices(), which is the CPU here: steer it onto
    # its TPU branch (in this script, not through an option of the program)
    linear._pallas_q40_matmul.cache_clear()
    linear._pallas_q40_matmul = lambda: pallas_q40.q40_matmul_pallas

    def sds(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    def abstract(arg):
        """The argument as shapes on the described devices, placed the way
        load_stack / InferenceEngine place the real thing."""
        if mesh is not None and isinstance(arg, LlamaParams):
            return jax.tree.map(sds, arg, param_shardings(mesh, arg))
        if mesh is not None and isinstance(arg, KVCache):
            return jax.tree.map(sds, arg, cache_shardings(mesh))
        return jax.tree.map(
            lambda leaf: sds(leaf, default) if hasattr(leaf, "shape") else leaf,
            arg,
        )

    real_jit = jax.jit
    zeros: dict = {}
    report: list[dict] = []

    class AotJit:
        def __init__(self, fn, **kw):
            self.jitted = real_jit(fn, **kw)
            self.name = getattr(fn, "__name__", type(fn).__name__)
            self.seen: dict = {}

        def __call__(self, *call_args):
            if any(isinstance(leaf, jax.core.Tracer)
                   for leaf in jax.tree.leaves(call_args)):
                return self.jitted(*call_args)  # nested under an outer trace
            a_args = tuple(abstract(a) for a in call_args)
            key = str(jax.tree.map(
                lambda l: (getattr(l, "shape", l), str(getattr(l, "dtype", ""))),
                a_args,
            ))
            if key not in self.seen:
                t0 = time.perf_counter()
                compiled = self.jitted.lower(*a_args).compile()
                mem, hlo = compiled.memory_analysis(), compiled.as_text()
                rec = {
                    "program": self.name,
                    "compile_s": round(time.perf_counter() - t0, 1),
                    "args_gb": round(mem.argument_size_in_bytes / 2**30, 3),
                    "temp_gb": round(mem.temp_size_in_bytes / 2**30, 3),
                    "peak_gb": round((mem.argument_size_in_bytes
                                      + mem.output_size_in_bytes
                                      - mem.alias_size_in_bytes
                                      + mem.temp_size_in_bytes) / 2**30, 3),
                    "kernel_calls": hlo.count("tpu_custom_call"),
                    "collectives": {
                        c: len(re.findall(rf"\b{c}(-start)?\(", hlo))
                        for c in COLLECTIVES
                    } if mesh is not None else None,
                }
                report.append(rec)
                print("AOT " + json.dumps(rec), flush=True)
                self.seen[key] = jax.eval_shape(self.jitted, *a_args)
            return jax.tree.map(
                lambda s: zeros.setdefault(
                    (s.shape, str(s.dtype)), jnp.zeros(s.shape, s.dtype)),
                self.seen[key],
            )

        def lower(self, *a, **k):
            return self.jitted.lower(*a, **k)

    def aot_jit(fn=None, **kw):
        return AotJit(fn, **kw) if fn is not None else (lambda f: AotJit(f, **kw))

    header = load_model_header(args.model, max_seq_len=0)
    config, params = load_params_from_m_quantized(
        args.model, header, dtype=jnp.bfloat16)
    jax.jit = engine_mod.jax.jit = aot_jit
    try:
        eng = engine_mod.InferenceEngine(
            config, params, n_lanes=args.lanes, cache_dtype=jnp.bfloat16,
            mesh=mesh)
        eng._g_sharding = None  # the slab would be placed on described devices
        t0 = time.perf_counter()
        engine_mod.warmup_engine(eng, spec=True, multi_step=8)
    finally:
        jax.jit = engine_mod.jax.jit = real_jit
    steps = [r for r in report if r["kernel_calls"]]
    print(f"{len(report)} programs compiled for "
          f"{'tp=%d on ' % args.tp if mesh is not None else ''}a described v5e "
          f"in {time.perf_counter() - t0:.0f}s; {len(steps)} contain the "
          f"kernel; largest footprint {max(r['peak_gb'] for r in report)} GB "
          f"per device of 16")


if __name__ == "__main__":
    main()
