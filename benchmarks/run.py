#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time: it makes the weights on the device from ``--seed``,
builds the engine and the scheduler in-process as ``dllama-api`` builds them,
warms up the programs the cell's traffic uses, brings the load to its steady
state, measures for ``--seconds``, compares the engine with the plain
reference, and prints one JSON object as the last line of its output. With no
accelerator, or fewer chips than the cell asks for, it fails and prints no
result; ``--rehearse`` (tests only) drives the same control flow on the CPU at
a tiny size and reports counts, never device numbers.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH_DIR)

from harness import cells  # noqa: E402

TRACE_START_S = 1.0   # into the window, when the traced stretch begins
TRACE_SECONDS = 3.0   # and how long it lasts (never more than half the window)
FIRST_TOKEN_GRACE_S = 30.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: allow the CPU, report counts only")
    ap.add_argument("--benchmark-file", default=None,
                    help="tests only: a BENCHMARK.json other than the root's")
    return ap.parse_args(argv)


def setup_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), every program kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CacheCounts:
    """Hits and misses of the persistent cache, from jax.monitoring."""

    EVENTS = {
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def __init__(self):
        import jax

        self.counts = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        key = self.EVENTS.get(event)
        if key:
            self.counts[key] += 1


def check_devices(cell: dict, rehearse: bool):
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform == "cpu" and not rehearse:
        log("no accelerator: JAX reports the CPU; a cell is never measured there")
        raise SystemExit(3)
    if len(devs) < cell["chips"] and not rehearse:
        log(f"the cell needs {cell['chips']} chips, JAX reports {len(devs)}")
        raise SystemExit(3)
    return devs


def build_stack(family, cfg: dict, seed: int, traffic):
    """Weights, engine, tokenizer and scheduler as dllama-api's load_stack and
    make_scheduler put them together, apart from where the weights come from:
    the configuration's family module (`cells.load_family`) makes them."""
    import jax.numpy as jnp

    from distributed_llama_multiusers_tpu.runtime.engine import (
        DEFAULT_PREFILL_BUCKETS,
        InferenceEngine,
        warmup_engine,
    )
    from distributed_llama_multiusers_tpu.runtime.scheduler import (
        ContinuousBatchingScheduler,
    )
    from distributed_llama_multiusers_tpu.serving import QosQueue
    from harness.tokenizer import BenchTokenizer

    serving = cfg["serving"]
    config = family.program_config(cfg)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    t0 = time.monotonic()
    tensors = family.device_weights(config, seed, dtypes[serving["activations"]])
    params = family.assemble_params(config, tensors)
    t1 = time.monotonic()
    buckets = serving.get("prefill_buckets", "default")
    engine = InferenceEngine(
        config, params, n_lanes=int(serving["lanes"]),
        prefill_buckets=(DEFAULT_PREFILL_BUCKETS if buckets == "default"
                         else tuple(buckets)),
        cache_dtype=dtypes[serving["kv_dtype"]],
    )
    prompts: dict = {}
    tokenizer = BenchTokenizer(config.vocab_size, prompts)
    # the program logs one JSON line a request from its batching loop, to
    # stderr by default. Kept in memory here and written out after the run
    # (.bench_out/requests.log): a server's stderr is a pipe, and a disk that
    # stalls must not stall the loop
    from distributed_llama_multiusers_tpu.telemetry import Telemetry
    from distributed_llama_multiusers_tpu.telemetry.logs import JsonLogger

    sched = ContinuousBatchingScheduler(
        engine, tokenizer, queue_=QosQueue(capacity=0),
        telemetry=Telemetry(logger=JsonLogger(stream=io.StringIO())),
    )
    # the programs this cell's traffic can reach: the verify programs only
    # where a request is greedy (the scheduler drafts for no other lane), the
    # multi-step programs never (the pipelined loop takes their place)
    spec = traffic.temperature == 0.0
    warmup_engine(engine, spec=spec, multi_step=0)
    t2 = time.monotonic()
    log(f"weights {t1 - t0:.1f}s, engine + warm-up {t2 - t1:.1f}s "
        f"(speculative programs {'warmed' if spec else 'not needed'})")
    return config, tensors, engine, sched, prompts


def load_metric(name: str):
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    return cells.load_module(path, "bench_metric_" + name).read


class StallWatch(threading.Thread):
    """Says what every thread was doing when the engine dispatched no step for
    over half a second (the longest step is under 0.3 s) while the load was
    running: a run that reads far off is then a run with a cause. It wakes
    every tenth of a second and also records when it woke late itself: a
    thread that only sleeps wakes late when the whole process was not
    running, which tells a host that paused from a device that stalled."""

    LIMIT_S = 0.5
    TICK_S = 0.1

    def __init__(self, stats, has_work):
        super().__init__(name="bench-stall-watch", daemon=True)
        self.stats, self.has_work = stats, has_work
        self.stalls, self.late_wakes, self._halt = [], [], threading.Event()

    def _steps(self) -> int:
        s = self.stats
        return s.decode_steps + s.pipeline_dispatches + s.fused_steps + s.prefill_tokens

    def run(self) -> None:
        last, since, dumped = self._steps(), time.monotonic(), False
        asleep = time.monotonic()
        while not self._halt.wait(self.TICK_S):
            now, steps = time.monotonic(), self._steps()
            if now - asleep > 2 * self.TICK_S + 0.05:
                self.late_wakes.append({"at": asleep, "seconds": now - asleep - self.TICK_S})
            if steps != last or not self.has_work():
                if dumped:
                    self.stalls.append({"at": since, "seconds": now - since})
                last, since, dumped = steps, now, False
            elif now - since > self.LIMIT_S and not dumped:
                log(f"no step dispatched for {now - since:.2f} s; every thread's stack:")
                faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
                dumped = True
            asleep = time.monotonic()

    def stop(self) -> list:
        self._halt.set()
        self.join(timeout=5)
        return self.stalls


def traced_stretch(seconds: float, out_dir: str, annotate):
    """Profile a few seconds inside the window; returns the .xplane.pb path."""
    import glob

    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # no per-call Python events: they slow the host
    opts.host_tracer_level = 2
    span = min(TRACE_SECONDS, seconds / 2.0)
    time.sleep(min(TRACE_START_S, seconds / 4.0))
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with annotate("bench.traced_window"):
        time.sleep(span)
    jax.profiler.stop_trace()
    files = sorted(glob.glob(os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def main(argv=None) -> int:
    args = parse_args(argv)
    bench_file = args.benchmark_file
    if bench_file:
        with open(bench_file) as f:
            bench = json.load(f)
    else:
        bench = cells.load_benchmark()
    cell = cells.find_cell(bench, args.workload)
    cfg = cells.load_config_file(bench, cell["config"])
    traffic_params = cells.load_traffic_file(cell["traffic"], bench.get("traffic_dir"))
    family = cells.load_family(cfg, bench.get("families_dir"))

    import jax

    cache_dir = setup_compile_cache()
    cache_counts = CacheCounts()
    devs = check_devices(cell, args.rehearse)
    dev = devs[0]
    log(f"{args.workload} seed {args.seed} on {len(devs)} x {dev.device_kind} "
        f"({dev.platform}); compile cache {cache_dir}")

    from harness import correct, xplane
    from harness.load import LoadGenerator
    from harness.peaks import chip_peaks
    from harness.traffic import Traffic

    peaks = None if dev.platform == "cpu" else chip_peaks(dev.device_kind)
    traffic = Traffic(traffic_params, lanes=int(cfg["serving"]["lanes"]))
    config, tensors, engine, sched, prompts = build_stack(family, cfg, args.seed, traffic)

    annotate = None
    consume_returns: list[float] = []
    if args.trace:
        annotate = jax.profiler.TraceAnnotation
        real_consume = engine.pipeline_consume

        def consume(*a, **k):  # when the step's tokens reach the host
            with annotate("bench.consume"):
                out = real_consume(*a, **k)
            consume_returns.append(time.monotonic())
            return out

        engine.pipeline_consume = consume

    gen = LoadGenerator(sched, traffic, args.seed, config.vocab_size, prompts, annotate)
    sched.start()
    trace_file = None
    watch = StallWatch(
        engine.stats, lambda: any(not s.req.future.done() for s in list(gen.streams)))
    try:
        t0 = gen.start()
        watch.start()
        # the window is nominal: it opens preroll_s after the load starts and
        # lasts --seconds, however late this thread wakes
        t_open = t0 + traffic.preroll_s
        time.sleep(max(0.0, t_open - time.monotonic()))
        stats_open = engine.stats.snapshot()
        if args.trace:
            trace_file = traced_stretch(
                args.seconds, os.path.join(ROOT, ".bench_out", "trace", args.workload),
                annotate,
            )
        t_close = t_open + args.seconds
        time.sleep(max(0.0, t_close - time.monotonic()))
        stats_close = engine.stats.snapshot()
        stalls = [{"at_s": s["at"] - t_open, "seconds": s["seconds"]} for s in watch.stop()]
        late_wakes = [{"at_s": s["at"] - t_open, "seconds": s["seconds"]}
                      for s in watch.late_wakes]
        gen.halt()
        # open loop: the requests that were due inside the window are owed a
        # first token; nothing new is sent while they get it
        owed = [s for s in gen.streams
                if traffic.loop == "open" and t_open <= s.start_t < t_close]
        deadline = time.monotonic() + FIRST_TOKEN_GRACE_S
        while any(not s.delta_t and not s.req.future.done() for s in owed):
            if time.monotonic() > deadline:
                break
            time.sleep(0.01)
        gen.cancel_outstanding()
    finally:
        watch.stop()
        sched.stop()
    mem = dev.memory_stats() or {}
    os.makedirs(os.path.join(ROOT, ".bench_out"), exist_ok=True)
    with open(os.path.join(ROOT, ".bench_out", "requests.log"), "w") as f:
        f.write(sched.telemetry.logger.stream.getvalue())

    counters = {
        k: stats_close[k] - stats_open[k]
        for k in stats_close
        if isinstance(stats_close[k], (int, float)) and not isinstance(stats_close[k], bool)
    }
    streams = gen.streams
    finished = [s for s in streams if s.req.finish_reason == "length"]
    wrong = [s for s in finished if len(s.req.generated_tokens) != s.spec.max_tokens
             or len(s.delta_t) != s.spec.max_tokens]
    errored = [s for s in streams if s.req.error is not None]
    unserved = [s for s in owed if not s.delta_t]
    failed = len({id(s) for s in wrong + errored + unserved})
    attempted = len([s for s in streams if s.submit_t < t_close])
    log(f"requests: {attempted} sent before the window closed, {len(finished)} ran to "
        f"max_tokens, {len(wrong)} with another count, {len(errored)} errors, "
        f"{len(unserved)} owed a first token")

    per_5s = [0] * (int(args.seconds // 5) + 1)
    for s in streams:
        for t in s.delta_t:
            if t_open <= t < t_close:
                per_5s[int((t - t_open) // 5)] += 1
    log(f"tokens delivered in each 5 s of the window: {per_5s}")
    if traffic.loop == "open":
        # requests due by each 5 s mark that had no first token by then
        marks = [t_open + 5.0 * (i + 1) for i in range(int(args.seconds // 5))]
        waiting = [sum(1 for s in streams if s.start_t <= t
                       and not (s.delta_t and s.delta_t[0] <= t)
                       and not (s.req.future.done() and not s.delta_t))
                   for t in marks]
        log(f"requests due and still without a first token at each 5 s mark: {waiting}")

    compared = correct.compare(family, cfg, tensors, engine, args.seed)

    reduced = None
    if trace_file:
        reduced = xplane.reduce_trace(xplane.read_xplane(trace_file))
        if not reduced["chips"]:  # a trace with no device plane (the CPU)
            reduced = None
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    # what a metric's reader may look at
    ctx = SimpleNamespace(
        streams=streams, t_open=t_open, t_close=t_close, seconds=args.seconds,
        setup_s=t_open - T_PROCESS_START, traffic=traffic,
        counters=counters, trace=reduced, cfg=cfg, config=config,
        lanes=engine.n_lanes, padded_vocab=padded_d_out(config.vocab_size),
        peaks=peaks, consume_returns=consume_returns, kv_dtype=cfg["serving"]["kv_dtype"],
    )
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cells.cell_metrics(bench, args.workload, kind):
        value = load_metric(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
        "memory_peak_bytes": mem.get("peak_bytes_in_use", "not measured"),
    }
    result = {
        "correct": bool(compared["ok"] and failed == 0),
        "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device,
        "compile_cache": cache_counts.counts,
        # the first requests as the traffic file fixes them, and how many were
        # issued: the same for every seed
        "schedule": {"digest": traffic.digest(64),
                     "issued": len([s for s in streams if s.start_t < t_close])},
        "compiles_in_window": counters.get("jit_compiles_after_warmup"),
        # stretches of over half a second in which the engine dispatched
        # nothing, and wake-ups of a sleeping thread that came late
        "stalls": stalls,
        "late_wakes": late_wakes,
    }
    if reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": xplane.top_device_ops(reduced, 10),
            "idle_gaps": [[k, v] for k, v in reduced["idle_gaps"][:10]],
        }
    if args.rehearse:
        # a CPU run gives counts; its times are not the device's and are
        # never written under the name of a metric
        result["rehearsal"] = True
        result["rehearsal_values"] = result.pop("metrics")
        result["metrics"] = {}
        for key in ("busy_s", "window_s"):
            device[key] = "not measured"
        device["memory_peak_bytes"] = "not measured"
    # each number compared beside its limit: the last lines of the log and the
    # last key of the result's line, where the driver's record keeps them
    result["compared"] = compared
    log(f"correct {result['correct']}, compared with the plain reference: "
        f"{correct.describe(compared)}; requests failed {failed} (limit 0)")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
