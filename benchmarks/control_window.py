#!/usr/bin/env python3
"""The two controls of window attention beside full-context attention without
rotation, beside `control.py`'s.

    python3 benchmarks/control_window.py --config command-a-plus-05-2026 [--seeds 2]

A window layer that attends its whole context, or a full-context layer that
rotates where the family does not, still computes a sound attention over real
rows, so it has to be shown that `correct` tells each apart. As `control.py`
puts the plain reference rounded to f8 in the program's place, this puts the
plain reference with A FAULT IN THE LAYER there (`families/cohere2_moe.py`
``FAULTS``: ``no_window``, the window left out; ``rotate_full``, the rotation
applied in the full-context layers) and compares it with the plain reference as
written, by `correct.compare`'s own numbers; ``--f8`` adds the reference in f8
(`control.py`'s ``reference_in_f8``, here without an engine beside it). Each
has to read over the configuration's limits; the readings go into its
``limits_from``. No engine is built.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]

from harness import cells, correct  # noqa: E402

F8 = "float8_e4m3fn"


def readings(family, cfg: dict, variants, seeds, dtype, log=print) -> list[dict]:
    """One reading a seed a variant (a fault's name, or a type the reference
    is rounded to): `correct.compare`'s two numbers of the logits beside the
    configuration's limits, the faulty reference in the program's place. The
    reference as written is computed once a seed."""
    config = family.program_config(cfg)
    limits = cfg["correctness"]["limits"]
    out = []
    for seed in seeds:
        tensors = family.device_weights(config, seed, dtype)
        prompts, forced = correct.sample_sequences(cfg, seed)
        prefixes = [correct.prefix_lengths(cfg, len(p)) for p in prompts]
        n_pre = len(prefixes[0]) + 1
        want = correct.plain_logits(family, cfg, tensors, prompts, forced, prefixes)
        for variant in variants:
            lossy = "fault:" + variant if variant in family.FAULTS else variant
            got = correct.plain_logits(family, cfg, tensors, prompts, forced, prefixes, lossy=lossy)
            err = correct.relative_errors(got, want)
            r = {"variant": variant, "seed": seed,
                 "prefill_rel_err": correct._rms(err[:, :n_pre]),
                 "decode_rel_err": correct._rms(err[:, n_pre:]),
                 "largest_row": float(err.max()),
                 # by sample sequence: a fault that only longer contexts can show
                 "by_sequence": [correct._rms(row) for row in err]}
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
    ap.add_argument("--f8", action="store_true", help="also the reference rounded to f8")
    args = ap.parse_args()

    import jax.numpy as jnp

    from run import setup_compile_cache

    setup_compile_cache()
    bench = cells.load_benchmark()
    cfg = cells.load_config_file(bench, args.config)
    family = cells.load_family(cfg, bench.get("families_dir"))
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["serving"]["activations"]]
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    variants = list(family.FAULTS) + ([F8] if args.f8 else [])
    got = readings(family, cfg, variants, seeds, dtype)
    summary = {"config": args.config}
    for variant in variants:
        mine = [r for r in got if r["variant"] == variant]
        summary[variant] = {
            key: {"smallest": min(r[key] for r in mine), "largest": max(r[key] for r in mine)}
            for key in ("prefill_rel_err", "decode_rel_err")}
        summary[variant]["ok"] = [r["ok"] for r in mine]
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
