#!/usr/bin/env python3
"""How often rounding changes a routed model's choice of experts, on the chip.

    python3 benchmarks/route_share.py --config <name> [--seeds 2] [--lossy bfloat16]

Routing is discontinuous: a pass that rounds more can choose another expert
at the last place, and from there on the two passes are different networks.
For a configuration whose family reports its chosen sets
(``reference_forward(..., routes=)`` and ``route_difference_share``), the
plain reference is run twice over `correct`'s sample sequences, in float32
and with every value a block hands on rounded to ``--lossy``, and the share
of (row, routed layer) pairs whose chosen sets differ is printed, by layer
and over all. It is the reference against itself, not the engine: the engine
does not report its routes. The benchmark's runs never run this; the number
goes under ``limits_from`` in the configuration's file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH_DIR)

from harness import cells, correct  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--lossy", default="bfloat16")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from run import setup_compile_cache

    setup_compile_cache()
    bench = cells.load_benchmark()
    cfg = cells.load_config_file(bench, args.config)
    family = cells.load_family(cfg, bench.get("families_dir"))
    if not hasattr(family, "route_difference_share"):
        raise SystemExit(f"family of {args.config!r} reports no routes")
    config = family.program_config(cfg)
    out = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        tensors = family.device_weights(config, seed, jnp.bfloat16)
        prompts, forced = correct.sample_sequences(cfg, seed)
        by_layer, rows = None, 0
        for lo in range(0, len(prompts), correct.REFERENCE_BATCH):
            hi = lo + correct.REFERENCE_BATCH
            group = [p + f for p, f in zip(prompts[lo:hi], forced[lo:hi])]
            tokens = np.zeros((len(group), max(map(len, group))), np.int32)
            live = np.zeros(tokens.shape, bool)
            for r, seq in enumerate(group):
                tokens[r, : len(seq)], live[r, : len(seq)] = seq, True
            exact, rounded = [], []
            with jax.default_matmul_precision("highest"):
                family.reference_forward(cfg, tensors, tokens, routes=exact)
                family.reference_forward(cfg, tensors, tokens, lossy=args.lossy, routes=rounded)
            differ = np.stack([np.any(x != y, axis=-1)[live] for x, y in zip(exact, rounded)])
            by_layer = differ.sum(axis=1) if by_layer is None else by_layer + differ.sum(axis=1)
            rows += int(live.sum())
        share = [float(x) / rows for x in by_layer]
        rec = {"seed": seed, "lossy": args.lossy, "rows": rows,
               "share": float(np.mean(share)), "first_layer": share[0], "last_layer": share[-1]}
        print(json.dumps(rec), flush=True)
        out.append(rec)
        del tensors
    print(json.dumps({"config": args.config, "lossy": args.lossy,
                      "share_smallest": min(r["share"] for r in out),
                      "share_largest": max(r["share"] for r in out)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
