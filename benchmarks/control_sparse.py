#!/usr/bin/env python3
"""The two controls of a learned sparse attention, beside `control.py`'s.

    python3 benchmarks/control_sparse.py --config deepseek-v3.2 [--seeds 2]

A selection that is wrong still attends real rows, so it has to be shown that
`correct` tells it apart. As `control.py` puts the plain reference rounded to
f8 in the program's place, this puts the plain reference with a FAULT IN THE
SELECTION there, and compares it with the plain reference as written, by
`correct.compare`'s own numbers: ``recent`` chooses the ``index_topk`` most recent
positions whatever the indexer says (a sliding window), ``all`` chooses every
position (dense attention: the selection left out). Both have to read over
the configuration's limits; the readings go into its ``limits_from``. No
engine is built.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]

from harness import cells, correct  # noqa: E402

FAULTS = ("recent", "all")


def readings(family, cfg: dict, faults, seeds, dtype, log=print) -> list[dict]:
    """One reading a seed a fault: `correct.compare`'s two numbers of the
    logits (root mean square of the rows' relative errors, prefill rows and
    decode rows) beside the configuration's limits, the faulty reference in
    the program's place. The reference as written is computed once a seed."""
    config = family.program_config(cfg)
    limits = cfg["correctness"]["limits"]
    out = []
    for seed in seeds:
        tensors = family.device_weights(config, seed, dtype)
        prompts, forced = correct.sample_sequences(cfg, seed)
        prefixes = [correct.prefix_lengths(cfg, len(p)) for p in prompts]
        n_pre = len(prefixes[0]) + 1
        want = correct.plain_logits(family, cfg, tensors, prompts, forced, prefixes)
        for fault in faults:
            got = correct.plain_logits(family, cfg, tensors, prompts, forced, prefixes,
                                       lossy="select:" + fault)
            err = correct.relative_errors(got, want)
            r = {"variant": "selection_" + fault, "seed": seed,
                 "prefill_rel_err": correct._rms(err[:, :n_pre]),
                 "decode_rel_err": correct._rms(err[:, n_pre:]),
                 "largest_row": float(err.max())}
            r["ok"] = all(r[k] <= float(limits[k]) for k in ("prefill_rel_err", "decode_rel_err"))
            log(json.dumps(r))
            out.append(r)
        del tensors
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    args = ap.parse_args()

    import jax.numpy as jnp

    from run import setup_compile_cache

    setup_compile_cache()
    bench = cells.load_benchmark()
    cfg = cells.load_config_file(bench, args.config)
    family = cells.load_family(cfg, bench.get("families_dir"))
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["serving"]["activations"]]
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    got = readings(family, cfg, FAULTS, seeds, dtype)
    summary = {"config": args.config}
    for fault in FAULTS:
        mine = [r for r in got if r["variant"] == "selection_" + fault]
        summary["selection_" + fault] = {
            key: {"smallest": min(r[key] for r in mine), "largest": max(r[key] for r in mine)}
            for key in ("prefill_rel_err", "decode_rel_err")}
        summary["selection_" + fault]["ok"] = [r["ok"] for r in mine]
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
