#!/usr/bin/env python3
"""The readings a limit of `correct` is set from, made on the chip.

    python3 benchmarks/control.py --config <name> [--seeds 12] [--control-seeds 3]

One process, no timed window. The engine as the configuration states it is
compared with the plain reference on a dozen seeds: the largest number it gives
is the lower end. Then the controls, on a few seeds: the program's own
lower-precision paths switched on, an f8 (e4m3) key/value cache and
Q80-emulated activations, the steps below bfloat16 that would tempt a later
PR, and the plain reference itself computed in f8. The smallest number a
control gives is the upper end. For the route check (pipelined and fused
programs against the synchronous ones) the control is the engine as configured
with the check's two fused admissions swapped, as a splice into the wrong lane.
The benchmark's runs never run this; `benchmarks/tests/test_control.py` keeps
it as a test at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH_DIR)

from harness import cells, correct  # noqa: E402

VARIANTS = {
    "as_configured": {},
    "f8_kv_cache": {"cache_dtype": "float8_e4m3fn"},
    "q80_activations": {"emulate_q80_activations": True},
    # no engine: the plain reference with every value a block hands on
    # rounded to f8 (e4m3), against itself in float32
    "reference_in_f8": {"reference": "float8_e4m3fn"},
    # the engine as configured, the route check's two fused admissions
    # swapped: what a splice into the wrong lane would read
    "admits_swapped": {"fault": "swap_admits"},
}
# what `correct` compares with a limit, and so what a reading is given for
COMPARED = ("prefill_rel_err", "decode_rel_err", "route_greedy_gap",
            "route_nucleus_excess", "route_kv_rel_err")


def readings(family, cfg: dict, variant: str, seeds, log=print) -> list[dict]:
    import jax.numpy as jnp

    from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

    serving = cfg["serving"]
    config = family.program_config(cfg)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
              "float8_e4m3fn": jnp.float8_e4m3fn}
    kw = dict(VARIANTS[variant])
    # what stands in the program's place: an engine, or the rounded reference
    subject = kw.pop("reference", None)
    fault = kw.pop("fault", None)
    cache_dtype = dtypes[kw.pop("cache_dtype", serving["kv_dtype"])]
    out = []
    for seed in seeds:
        t0 = time.monotonic()
        if isinstance(subject, InferenceEngine):
            subject.params = None  # free last seed's weights before the next
        gc.collect()
        tensors = family.device_weights(config, seed, dtypes[serving["activations"]])
        if subject is None:
            subject = InferenceEngine(
                config, family.assemble_params(config, tensors),
                n_lanes=int(serving["lanes"]), cache_dtype=cache_dtype, **kw,
            )
        elif isinstance(subject, InferenceEngine):
            # the weights are an operand of every program: nothing recompiles
            subject.params = family.assemble_params(config, tensors)
        r = correct.compare(family, cfg, tensors, subject, seed, fault=fault, keep_rows=True)
        r.update(seed=seed, variant=variant, seconds=round(time.monotonic() - t0, 1))
        log(json.dumps({k: v for k, v in r.items() if k != "row_errors"}))
        out.append(r)
        del tensors
    del subject
    gc.collect()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--out", default=None)
    ap.add_argument("--variants", default=",".join(VARIANTS))
    args = ap.parse_args()

    import jax

    from run import setup_compile_cache

    setup_compile_cache()
    dev = jax.devices()[0]
    bench = cells.load_benchmark()
    cfg = cells.load_config_file(bench, args.config)
    family = cells.load_family(cfg, bench.get("families_dir"))
    result = {"config": args.config, "device": dev.device_kind, "platform": dev.platform}
    chosen = args.variants.split(",")
    for variant in chosen:
        n = args.seeds if variant == "as_configured" else args.control_seeds
        seeds = [args.first_seed + 7919 * i for i in range(n)]
        result[variant] = readings(family, cfg, variant, seeds)
    summary = {"config": args.config, "device": dev.device_kind}
    for key in COMPARED:
        summary[key] = {
            v: {"smallest": min(vals), "largest": max(vals)}
            for v in chosen
            if (vals := [r[key] for r in result[v] if r.get(key) is not None])
        }
    result["summary"] = summary
    print(json.dumps(summary))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
