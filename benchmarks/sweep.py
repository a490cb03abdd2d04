#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the builder's sweep, run once on the chip.

    python3 benchmarks/sweep.py --workload <name> --rates 1.2,1.5,1.8 --seconds 40

One process and one warm engine; for each rate the cell's own traffic file is
run at that rate for ``--seconds`` after its pre-roll, and what decides whether
the rate is sustained is printed: the backlog (requests due but not yet
admitted) at the end of the window and at its middle, the queue wait in the
window's two halves, and the time to first token. The knee is the highest rate
at which the backlog does not grow. The cell then runs at four fifths of it,
written into its traffic file as ``rate_rps``. PERF.md records the sweep.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH_DIR)

from harness import cells  # noqa: E402
from harness.stats import percentile  # noqa: E402


def one_rate(sched, traffic_params, cfg, rate, seed, seconds, vocab, prompts):
    from harness.load import LoadGenerator
    from harness.traffic import Traffic

    traffic = Traffic(dict(traffic_params, rate_rps=rate), lanes=int(cfg["serving"]["lanes"]))
    gen = LoadGenerator(sched, traffic, seed, vocab, prompts)
    t0 = gen.start()
    t_open = t0 + traffic.preroll_s
    t_mid, t_close = t_open + seconds / 2, t_open + seconds

    def backlog(now):
        return sum(1 for s in list(gen.streams)
                   if s.submit_t is not None and s.submit_t <= now
                   and (s.req.admitted_at is None or s.req.admitted_at > now))

    time.sleep(max(0.0, t_mid - time.monotonic()))
    mid = backlog(time.monotonic())
    time.sleep(max(0.0, t_close - time.monotonic()))
    end = backlog(time.monotonic())
    gen.halt()
    gen.cancel_outstanding()
    streams = gen.streams

    def waits(a, b):
        return [(s.req.admitted_at - s.req.submitted_at) * 1e3 for s in streams
                if s.req.admitted_at is not None and a <= s.req.admitted_at < b]

    ttft = [(s.delta_t[0] - s.start_t) * 1e3 for s in streams
            if t_open <= s.start_t < t_close and s.delta_t]
    tokens = sum(1 for s in streams for t in s.delta_t if t_open <= t < t_close)
    return {
        "rate_rps": rate, "due_in_window": sum(1 for s in streams if t_open <= s.start_t < t_close),
        "backlog_mid": mid, "backlog_end": end,
        "queue_wait_p50_ms_first_half": percentile(waits(t_open, t_mid), 50),
        "queue_wait_p50_ms_second_half": percentile(waits(t_mid, t_close), 50),
        "ttft_p50_ms": percentile(ttft, 50), "ttft_p90_ms": percentile(ttft, 90),
        "tokens_per_s": tokens / seconds,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=2_300_000_011)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true", help="tests only: allow the CPU")
    ap.add_argument("--benchmark-file", default=None, help="tests only")
    args = ap.parse_args()

    import run

    if args.benchmark_file:
        with open(args.benchmark_file) as f:
            bench = json.load(f)
    else:
        bench = cells.load_benchmark()
    cell = cells.find_cell(bench, args.workload)
    cfg = cells.load_config_file(bench, cell["config"])
    traffic_params = cells.load_traffic_file(cell["traffic"], bench.get("traffic_dir"))
    family = cells.load_family(cfg, bench.get("families_dir"))
    run.setup_compile_cache()
    run.check_devices(cell, rehearse=args.rehearse)
    from harness.traffic import Traffic

    traffic = Traffic(traffic_params, lanes=int(cfg["serving"]["lanes"]))
    config, _tensors, _engine, sched, prompts = run.build_stack(family, cfg, args.seed, traffic)
    sched.start()
    rows = []
    try:
        for rate in [float(x) for x in args.rates.split(",")]:
            row = one_rate(sched, traffic_params, cfg, rate, args.seed,
                           args.seconds, config.vocab_size, prompts)
            print(json.dumps(row), flush=True)
            rows.append(row)
            time.sleep(1.0)
    finally:
        sched.stop()
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
