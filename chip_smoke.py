#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

    python3 chip_smoke.py             # one TPU chip: what the driver runs
    python3 chip_smoke.py --chips 4   # the tensor-parallel phase only

Default run, one chip, everything through the entry points a user calls:

1. write a seeded Llama-3.2-1B-shaped Q40 `.m` (published widths, 16 layers)
   and a `.t` with formats/synthetic;
2. start `dllama_api --max-lanes 8` with its defaults (packed Q40 in HBM,
   Pallas kernel, pipelined + fused + speculative) under DLLAMA_JITCHECK=1;
3. HTTP traffic: a 64-token greedy completion and the same twice more (the
   repeats, both served through the prefix cache, are byte-identical), one
   long SSE stream with four staggered requests joining it (greedy and
   seeded-sampled, one chat completion);
4. /stats and the start-up lines: platform tpu, Pallas kernel active, no
   compile after warm-up, no pipeline flush, fused steps taken, no engine
   failure, breaker closed; SIGTERM -> drain -> exit code 0;
5. a second start on the same tree must hit the compile cache and repeat the
   greedy text; then, each in its own process, every dequant mode of the
   Q40 kernel against the XLA dequant reference at the 1B shapes, and
   `dllama inference` on the same greedy prompt.

`--chips 4` runs only the one-chip server as the comparison and then
`dllama_api --workers tp4` over the four chips on the same greedy requests.

Greedy texts that reach one prompt by numerically different routes (prefix-
cached repeat, one lane against eight, four chips against one) must be equal
or part at a near tie of the one-chip model's logits (NEAR_TIE_STD).

This script never imports JAX: every process it starts is the only one
holding the chip while it lives, and all of them are stopped on the way out.
Any failed check exits non-zero. Without a TPU it fails, naming the platform
it found. The LAST line of a passing run is
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`,
taken from what the serving process reported. Every other number printed is
information from one run, not a benchmark. `--rehearse` drives the same
control flow at a tiny size on the CPU and can never print `"ok": true`.
"""

from __future__ import annotations

import argparse
import glob
import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# logs go where the chip tool brings files back from; the 1.7 GB model does not
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
MODEL_DIR = os.path.join(ROOT, "chip_smoke_out", "model")
MODEL = os.path.join(MODEL_DIR, "smoke.m")
TOKENIZER = os.path.join(MODEL_DIR, "smoke.t")
PKG = "distributed_llama_multiusers_tpu"

GREEDY_PROMPT = "hello world, this is a smoke test of the serving path"
SECOND_PROMPT = "the quick brown fox jumps over the lazy dog"
N_GREEDY = 64
# Two numerically different routes to the same greedy text (a prefix-cached
# repeat, one lane against eight, four chips against one) may part ways only
# where the model itself is undecided: at the first divergence both tokens
# must lie within this many logit standard deviations of the top-1 logit of
# the one-chip model. Random weights leave top-1 margins of a few percent of
# a deviation about one token in ten, which bf16 reductions in another order
# do flip; a wrong kernel, mask or shard moves logits by whole deviations.
NEAR_TIE_STD = 0.15

# (d_in, d_out) of the Llama-3.2-1B matmuls: wq/wo, wk/wv, w1/w3, w2, wcls
# (vocab padded to the kernel's wide tile, as the loader pads it)
SHAPES_1B = [(2048, 2048), (2048, 512), (2048, 8192), (8192, 2048),
             (2048, 131072)]
SHAPES_TINY = [(256, 256), (256, 512), (512, 256)]
KERNEL_MS = (1, 8, 128)

_children: list[subprocess.Popen] = []
_failed: list[str] = []


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def fail(msg: str):
    """A failure nothing later can be judged after: stop here."""
    raise SystemExit(f"[smoke] FAILED: {msg}")


def require(cond, msg: str) -> None:
    if not cond:
        fail(msg)
    say(f"ok: {msg}")


def check(cond, msg: str) -> None:
    """A failed check fails the run (exit code 1, no result line) but lets
    the remaining phases report: one run on a budgeted chip shows them all."""
    if cond:
        say(f"ok: {msg}")
    else:
        _failed.append(msg)
        say(f"FAILED: {msg}")


# ---------------------------------------------------------------------------
# Phases that need the package (and JAX): each runs as its own process,
# `python chip_smoke.py --phase NAME`, started by the parent below.
# ---------------------------------------------------------------------------


def _smoke_header(rehearse: bool):
    from distributed_llama_multiusers_tpu.formats.model_file import RopeType
    from distributed_llama_multiusers_tpu.formats.synthetic import tiny_header

    if rehearse:
        return tiny_header(dim=256, hidden_dim=512, n_layers=2, n_heads=8,
                           n_kv_heads=4, vocab_size=512, seq_len=1024)
    # Llama-3.2-1B, config.json of the published checkpoint (the widths of
    # __graft_entry__._flagship_config): full width AND full depth
    h = tiny_header(dim=2048, hidden_dim=8192, n_layers=16, n_heads=32,
                    n_kv_heads=8, vocab_size=128256, seq_len=2048,
                    rope_type=RopeType.LLAMA3_1, rope_theta=500000.0)
    h.rope_scaling_factor = 32.0
    h.rope_scaling_orig_max_seq_len = 8192
    return h


def phase_prepare(args) -> int:
    """Report the device JAX finds; on a TPU (or under --rehearse) write the
    seeded model and tokenizer. Touches the backend, so it runs — and ends —
    before any server starts."""
    import jax

    dev = jax.devices()[0]
    facts = {"platform": dev.platform, "kind": dev.device_kind,
             "count": len(jax.devices())}
    print("DEVICE " + json.dumps(facts), flush=True)
    if facts["platform"] != "tpu" and not args.rehearse:
        return 3
    from distributed_llama_multiusers_tpu.formats.synthetic import (
        write_synthetic_model,
        write_synthetic_tokenizer,
    )

    header = _smoke_header(args.rehearse)
    os.makedirs(MODEL_DIR, exist_ok=True)
    t0 = time.perf_counter()
    write_synthetic_model(MODEL, header, seed=args.seed)
    write_synthetic_tokenizer(TOKENIZER, vocab_size=header.vocab_size)
    print(f"MODEL dim={header.dim} hidden={header.hidden_dim} "
          f"layers={header.n_layers} heads={header.n_heads}/"
          f"{header.n_kv_heads} vocab={header.vocab_size} "
          f"seq_len={header.seq_len} q40 "
          f"{os.path.getsize(MODEL) / 2**20:.0f} MiB "
          f"written in {time.perf_counter() - t0:.1f}s", flush=True)
    return 0


def phase_kernels(args) -> int:
    """q40_matmul_pallas against q40_matmul_xla ON THE DEVICE for the default
    mode and every other mode, inside the bound tests/test_pallas_q40.py
    uses (max error over max |reference|: 2e-2; 5e-2 where the activations
    are Q80-quantized). Under --rehearse: interpret mode, tiny shapes."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_multiusers_tpu.ops import pallas_q40 as pq
    from distributed_llama_multiusers_tpu.quants.packed import (
        PackedQ40,
        q40_matmul_xla,
    )

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"kernels: no TPU (found {dev.platform})", flush=True)
        return 3
    interpret = dev.platform != "tpu"
    shapes = SHAPES_TINY if args.rehearse else SHAPES_1B
    modes = [pq.DEQUANT_MODE] + [
        m for m in pq.DEQUANT_MODES if m != pq.DEQUANT_MODE
    ]
    worst: dict[str, float] = {}
    bad = 0
    t0 = time.perf_counter()
    for d_in, d_out in shapes:
        kp, ks, kx = jax.random.split(jax.random.PRNGKey(args.seed + d_in + d_out), 3)
        w = PackedQ40(
            packed=jax.random.bits(kp, (d_in // 2, d_out), jnp.uint8),
            scales=(jax.random.uniform(ks, (d_in // 32, d_out), jnp.float32,
                                       0.002, 0.02)).astype(jnp.float16),
        )
        for m in KERNEL_MS:
            x = jax.random.normal(kx, (m, d_in), jnp.float32)
            with jax.default_matmul_precision("highest"):
                ref = jax.device_get(q40_matmul_xla(x, w))
            scale = float(abs(ref).max()) + 1e-9
            for mode in modes:
                pq.set_dequant_mode(mode)
                got = jax.device_get(pq.q40_matmul_pallas(
                    x, w, interpret=interpret, w_dtype=jnp.bfloat16))
                q80_acts = mode == "i8blockdot" and m <= pq.BLOCKDOT_MAX_M
                bound = 5e-2 if q80_acts else 2e-2
                rel = float(abs(got - ref).max()) / scale
                worst[mode] = max(worst.get(mode, 0.0), rel)
                finite = bool((got == got).all()) and got.shape == ref.shape
                if not finite or not rel < bound:
                    bad += 1
                    print(f"kernels: MISMATCH mode={mode} {d_in}x{d_out} "
                          f"m={m} rel={rel:.3e} bound={bound} "
                          f"finite={finite}", flush=True)
    pq.set_dequant_mode(None)
    n = len(shapes) * len(KERNEL_MS) * len(modes)
    print(f"kernels: {n - bad}/{n} cases inside the bound on "
          f"{dev.platform} ({dev.device_kind}), interpret={interpret}, "
          f"{time.perf_counter() - t0:.1f}s; worst rel error per mode: "
          + json.dumps({k: float(f"{v:.3e}") for k, v in worst.items()}),
          flush=True)
    return 1 if bad else 0


def phase_margin(args) -> int:
    """For each recorded greedy divergence: prefill the prompt plus the text
    both routes agree on through a one-lane engine and report where the two
    candidate tokens stand in its logits."""
    import numpy as np

    from distributed_llama_multiusers_tpu.app.args import build_parser
    from distributed_llama_multiusers_tpu.app.runtime_setup import load_stack

    cli = build_parser("dllama").parse_args(
        ["inference", "--model", MODEL, "--tokenizer", TOKENIZER])
    _, _, tokenizer, engine = load_stack(cli, n_lanes=1)
    with open(args.queries) as f:
        queries = json.load(f)
    for q in queries:
        tokens = tokenizer.encode(q["prompt"])
        if q["agreed"]:
            tokens += tokenizer.encode(q["agreed"], add_bos=False)
        logits, _, _ = engine.prefill(0, tokens)
        row = np.asarray(logits, np.float32).reshape(-1)
        top2 = np.sort(row)[-2:]
        ids = [tokenizer.encode(p, add_bos=False)[0] for p in q["pieces"]]
        print("MARGIN " + json.dumps({
            "label": q["label"], "std": float(row.std()),
            "top1": float(top2[1]), "top2": float(top2[0]),
            "logits": [float(row[i]) for i in ids],
            "ranks": [int((row > row[i]).sum()) for i in ids],
        }), flush=True)
    return 0


# ---------------------------------------------------------------------------
# The parent: processes, HTTP, checks. Standard library only.
# ---------------------------------------------------------------------------


def child_env(args) -> dict:
    """Children run from ROOT (script or `-m`), so the package is importable
    as it is; they inherit JAX_COMPILATION_CACHE_DIR when it is set."""
    env = dict(os.environ)
    env["PYTHONUNBUFFERED"] = "1"
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                                + " --xla_force_host_platform_device_count=4")
    return env


def run_phase(args, name: str, extra=(), timeout=900) -> tuple[int, str]:
    """Run `chip_smoke.py --phase name` to its end; echo and return stdout.
    stderr goes to a log under chiprun_out/chip_smoke/."""
    argv = [sys.executable, os.path.abspath(__file__), "--phase", name,
            "--seed", str(args.seed), "--chips", str(args.chips), *extra]
    if args.rehearse:
        argv.append("--rehearse")
    return run_logged(args, name, argv, timeout)


def run_logged(args, name: str, argv, timeout) -> tuple[int, str]:
    log_path = os.path.join(OUT, f"{name}.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(argv, cwd=ROOT, env=child_env(args),
                                stdout=subprocess.PIPE, stderr=log, text=True)
        _children.append(proc)
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
            fail(f"{name}: no end within {timeout}s (log: {log_path})")
    for line in out.splitlines():
        print(f"  {name}| {line}", flush=True)
    if proc.returncode != 0:
        print_tail(name, log_path)
    return proc.returncode, out


def print_tail(name: str, log_path: str, n: int = 30) -> None:
    print(f"  {name}| --- last lines of {log_path} ---", flush=True)
    with open(log_path) as f:
        for line in f.readlines()[-n:]:
            print(f"  {name}| {line.rstrip()}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """One `dllama_api` process with its defaults; stderr+stdout in a log."""

    def __init__(self, args, name: str, extra=()):
        self.name = name
        self.port = free_port()
        self.log_path = os.path.join(OUT, f"{name}.log")
        env = child_env(args)
        env["DLLAMA_JITCHECK"] = "1"
        self._log = open(self.log_path, "w")
        self.t_start = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", f"{PKG}.app.dllama_api",
             "--model", MODEL, "--tokenizer", TOKENIZER, "--max-lanes", "8",
             "--host", "127.0.0.1", "--port", str(self.port), *extra],
            cwd=ROOT, env=env, stdout=self._log, stderr=subprocess.STDOUT,
        )
        _children.append(self.proc)
        say(f"{name}: started pid {self.proc.pid} on port {self.port} "
            f"(log: {self.log_path})")

    def tail(self) -> None:
        print_tail(self.name, self.log_path)

    def wait_healthy(self, timeout: float) -> float:
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            if self.proc.poll() is not None:
                self.tail()
                fail(f"{self.name}: exited with code {self.proc.returncode} "
                     "before it answered /health")
            try:
                status, _ = http_json(self.port, "GET", "/health", timeout=5)
                if status == 200:
                    return time.perf_counter() - self.t_start
            except OSError:
                pass
            time.sleep(1.0)
        self.tail()
        fail(f"{self.name}: /health not answering after {timeout:.0f}s")

    def events(self) -> dict:
        """The structured start-up lines (telemetry/logs.log_event), by name."""
        out = {}
        with open(self.log_path) as f:
            for line in f:
                if line.startswith('{"event"'):
                    rec = json.loads(line)
                    out.setdefault(rec["event"], rec)
        return out

    def stop(self, timeout=120) -> int:
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.tail()
            fail(f"{self.name}: still running {timeout}s after SIGTERM")
        self._log.close()
        return rc


def http_json(port: int, method: str, path: str, body=None, timeout=600):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = None if body is None else json.dumps(body)
        conn.request(method, path, payload,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


def complete(port: int, body: dict, path="/v1/completions") -> dict:
    """A non-streaming completion -> {text, finish_reason, n_tokens, s}."""
    t0 = time.perf_counter()
    status, resp = http_json(port, "POST", path, body)
    if status != 200:
        fail(f"POST {path} -> {status}: {resp}")
    choice = resp["choices"][0]
    text = choice["text"] if "text" in choice else choice["message"]["content"]
    return {"text": text, "finish_reason": choice.get("finish_reason"),
            "n_tokens": resp["usage"]["completion_tokens"],
            "s": time.perf_counter() - t0}


def stream(port: int, body: dict, first_delta: threading.Event | None = None,
           path="/v1/completions") -> dict:
    """A streaming (SSE) completion, read to the terminal chunk."""
    t0 = time.perf_counter()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request("POST", path, json.dumps({**body, "stream": True}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            fail(f"POST {path} (stream) -> {resp.status}: {resp.read()!r}")
        text, finish, summary, n_events = [], None, None, 0
        for raw in resp:
            line = raw.decode("utf-8").rstrip("\r\n")
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            chunk = json.loads(line[len("data: "):])
            if "error" in chunk:
                fail(f"stream error chunk: {chunk}")
            choice = chunk["choices"][0]
            delta = (choice["text"] if "text" in choice
                     else choice.get("delta", {}).get("content", ""))
            if delta:
                text.append(delta)
                n_events += 1
                if first_delta is not None:
                    first_delta.set()
            if choice.get("finish_reason"):
                finish = choice["finish_reason"]
                summary = chunk.get("summary")
        return {"text": "".join(text), "finish_reason": finish,
                "n_events": n_events, "summary": summary,
                "s": time.perf_counter() - t0}
    finally:
        conn.close()
        if first_delta is not None:
            first_delta.set()  # never leave the joiners waiting on a failure


def pieces(text: str) -> list[str]:
    """The text back as token pieces: the synthetic vocabulary is single
    printable bytes plus `<|...|>` specials (a few byte merges aside), and
    every generated token is rendered as its piece."""
    return re.findall(r"<\|[^|>]*\|>|.", text, re.S)


class GreedyRoutes:
    """Pairs of greedy texts that reached the same prompt by numerically
    different routes. Identical pairs pass at once; the others are settled
    together, by ONE one-lane process after the servers are gone, under the
    near-tie rule (NEAR_TIE_STD)."""

    def __init__(self):
        self.queries: list[dict] = []

    def compare(self, label: str, prompt: str, a: str, b: str) -> None:
        pa, pb = pieces(a), pieces(b)
        div = next((i for i, (x, y) in enumerate(zip(pa, pb)) if x != y), None)
        if div is None:
            check(len(pa) == len(pb), f"{label}: the same {len(pa)} tokens")
            return
        say(f"{label}: first divergence at token {div} of {len(pa)}: "
            f"{pa[div]!r} against {pb[div]!r}")
        self.queries.append({"label": label, "prompt": prompt,
                             "agreed": "".join(pa[:div]),
                             "pieces": [pa[div], pb[div]]})

    def settle(self, args) -> None:
        if not self.queries:
            return
        path = os.path.join(OUT, "divergences.json")
        with open(path, "w") as f:
            json.dump(self.queries, f)
        rc, out = run_phase(args, "margin", ["--queries", path])
        require(rc == 0, "margin process: exit code 0")
        found = {m["label"]: m for m in
                 map(json.loads, re.findall(r"^MARGIN (\{.*\})$", out, re.M))}
        for q in self.queries:
            m = found[q["label"]]
            gap = (m["top1"] - min(m["logits"])) / m["std"]
            check(gap <= NEAR_TIE_STD,
                  f"{q['label']}: the routes part at a near tie — both tokens "
                  f"within {gap:.4f} logit std of the top-1 (ranks "
                  f"{m['ranks']}, top-1 margin "
                  f"{(m['top1'] - m['top2']) / m['std']:.4f} std; bound "
                  f"{NEAR_TIE_STD})")


def greedy_body(prompt: str) -> dict:
    return {"prompt": prompt, "max_tokens": N_GREEDY, "temperature": 0.0}


def prepare(args) -> dict:
    """Phase 1: a tree with no native library, the device gate, the model."""
    for so in glob.glob(os.path.join(ROOT, PKG, "native", "*.so")):
        os.remove(so)
        say(f"removed {os.path.relpath(so, ROOT)}: the native codec is "
            "built from quant_codec.cpp on this machine")
    rc, out = run_phase(args, "prepare", timeout=900)
    m = re.search(r"^DEVICE (\{.*\})$", out, re.M)
    if m is None:
        fail(f"prepare: exit code {rc} and no device report (is the "
             f"{PKG} package next to this script?)")
    found = json.loads(m.group(1))
    if found["platform"] != "tpu" and not args.rehearse:
        fail(f"no TPU: JAX found platform {found['platform']!r} "
             f"({found['kind']}, {found['count']} device(s)). This smoke "
             "proves the chip path and has no CPU mode; --rehearse drives "
             "its control flow at a tiny size and never reports ok.")
    require(rc == 0, "model and tokenizer written from the seed")
    require(found["count"] >= args.chips,
            f"{found['count']} device(s) visible, {args.chips} needed")
    return found


def start_server(args, name: str, extra=()) -> tuple[Server, dict]:
    """Start a server, wait for /health, print what its start-up lines say."""
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or os.path.join(ROOT, ".jax_cache"))
    n_before = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    srv = Server(args, name, extra)
    up_s = srv.wait_healthy(timeout=900)
    ev = srv.events()
    for needed in ("runtime_device", "warmup_engine", "warmup_done"):
        if needed not in ev:
            srv.tail()
            fail(f"{name}: no {needed!r} start-up line in its log")
    dev, warm = ev["runtime_device"], ev["warmup_done"]
    say(f"{name}: healthy after {up_s:.1f}s (load {dev['load_s']}s, warm-up "
        f"{warm['warmup_s']}s); compile cache {dev['compile_cache_dir']}: "
        f"{n_before} entries before, {warm['compile_cache_hits']} hits / "
        f"{warm['compile_cache_misses']} misses in this start")
    say(f"{name}: runtime_device " + json.dumps(
        {k: dev[k] for k in ("platform", "device_kind", "device_count",
                             "mesh_shape", "weights", "kv_dtype",
                             "dequant_mode", "pallas_kernel", "ring_sync",
                             "device_bytes_in_use")}))
    check(dev["compile_cache_dir"] == cache_dir,
          f"{name}: compile cache at {cache_dir}")
    if not args.rehearse:
        check(dev["platform"] == "tpu", f"{name}: serving from a TPU")
        check(dev["weights"] == "packed" and dev["pallas_kernel"] is True,
              f"{name}: packed Q40 weights through the Pallas kernel")
        check(dev["kv_dtype"] == "bfloat16", f"{name}: bf16 KV cache")
    return srv, {"up_s": up_s, "device": dev, "warm": warm,
                 "engine": ev["warmup_engine"]}


def check_stats(args, srv: Server, need_fused: bool) -> dict:
    status, st = http_json(srv.port, "GET", "/stats")
    require(status == 200, f"{srv.name}: /stats answers")
    keys = ("platform", "device_kind", "device_count", "weights", "kv_dtype",
            "dequant_mode", "pallas_kernel", "decode_steps",
            "pipeline_dispatches", "pipeline_flushes", "fused_steps",
            "spec_pipelined_steps", "jit_compiles_after_warmup",
            "engine_failure_rounds", "engine_failures_total",
            "breaker_state", "breaker_trips", "watchdog_trips",
            "resource_leaks_total", "sync_bytes_total", "prefill_tokens")
    say(f"{srv.name}: /stats " + json.dumps(
        {k: st[k] for k in keys if k in st}))
    if not args.rehearse:
        check(st["platform"] == "tpu" and st["pallas_kernel"] is True,
              "/stats: platform tpu, Pallas kernel active")
    check(st["decode_steps"] > 0, "/stats: decode steps were taken")
    check(st["jit_compiles_after_warmup"] == 0,
          "/stats: no compile after warm-up (DLLAMA_JITCHECK=1)")
    check(st["pipeline_flushes"] == 0, "/stats: no pipeline flush")
    if need_fused:
        check(st["fused_steps"] > 0,
              "/stats: admissions rode the live chain (fused_steps > 0)")
    check(st["engine_failure_rounds"] == 0
          and st["engine_failures_total"] == 0
          and st["breaker_trips"] == 0 and st["breaker_state"] == "closed"
          and st.get("watchdog_trips", 0) == 0
          and st.get("resource_leaks_total", 0) == 0,
          "/stats: no engine failure, breaker closed, no leak")
    return st


def staggered(port: int) -> None:
    """One long SSE stream; four requests join it while it decodes."""
    first = threading.Event()
    results: dict[str, dict] = {}

    def run(name, fn, *a, **kw):
        results[name] = fn(*a, **kw)

    text, chat = "/v1/completions", "/v1/chat/completions"
    joiners = [
        ("greedy", complete, text, {"prompt": SECOND_PROMPT, "max_tokens": 48,
                                    "temperature": 0.0}),
        ("sampled", complete, text, {"prompt": "aa bb cc dd", "max_tokens": 48,
                                     "temperature": 0.8, "top_p": 0.9,
                                     "seed": 7}),
        ("chat", complete, chat, {"messages": [{"role": "user",
                                                "content": "hi"}],
                                  "max_tokens": 48, "temperature": 0.0}),
        ("sampled_stream", stream, text, {"prompt": "one two three",
                                          "max_tokens": 48, "temperature": 0.7,
                                          "seed": 11}),
    ]
    threads = [threading.Thread(
        target=run, args=("long", stream, port,
                          {"prompt": GREEDY_PROMPT, "max_tokens": 384,
                           "temperature": 0.0}, first))]
    threads[0].start()
    if not first.wait(timeout=300):
        fail("long stream: no first delta within 300s")
    for name, fn, path, body in joiners:
        t = threading.Thread(target=run, args=(name, fn, port, body),
                             kwargs={"path": path})
        t.start()
        threads.append(t)
        time.sleep(0.15)
    for t in threads:
        t.join(timeout=600)
        if t.is_alive():
            fail("a staggered request did not complete within 600s")
    for name in ["long"] + [j[0] for j in joiners]:
        r = results.get(name)
        if r is None:
            fail(f"staggered {name}: the request failed")
        check(r["finish_reason"] in ("stop", "length"),
              f"staggered {name}: finish_reason {r['finish_reason']!r}, "
              f"{r['s']:.2f}s")
    long = results["long"]
    n_tok = (long["summary"] or {}).get("n_generated_tokens")
    say(f"long stream: {n_tok} tokens in {long['n_events']} SSE deltas, "
        f"{long['s']:.2f}s"
        + (f" ({n_tok / long['s']:.1f} tok/s with four joiners; information "
           "only)" if n_tok else ""))


def run_one_chip(args) -> dict:
    t_all = time.perf_counter()
    prepare(args)

    srv, cold = start_server(args, "server_cold")
    routes = GreedyRoutes()
    g1, g2, g3 = (complete(srv.port, greedy_body(GREEDY_PROMPT))
                  for _ in range(3))
    check(g1["finish_reason"] in ("stop", "length") and g1["n_tokens"] > 0,
          f"greedy completion: {g1['n_tokens']} tokens, finish_reason "
          f"{g1['finish_reason']!r}, {g1['s']:.2f}s then {g2['s']:.2f}s "
          f"({g2['n_tokens'] / g2['s']:.1f} tok/s single lane; information "
          "only)")
    say("greedy text starts: " + repr(g1["text"][:120]))
    # the first request prefills its whole prompt; every repeat is served
    # from the prefix cache (one token prefilled in another bucket) — the
    # same route twice must give the same bytes, two routes the near-tie rule
    check(g2["text"] == g3["text"] and g2["text"] != "",
          "the same greedy request again is byte-identical (both served "
          "through the prefix cache)")
    routes.compare("whole-prompt prefill against prefix-cached repeat",
                   GREEDY_PROMPT, g1["text"], g2["text"])
    staggered(srv.port)
    check_stats(args, srv, need_fused=True)
    rc = srv.stop()
    check(rc == 0, "SIGTERM -> drain -> server exit code 0")

    srv2, warm = start_server(args, "server_warm")
    check(warm["warm"]["compile_cache_hits"] > 0,
          f"second start hit the compile cache "
          f"({warm['warm']['compile_cache_hits']} hits, "
          f"{warm['warm']['compile_cache_misses']} misses)")
    g1w = complete(srv2.port, greedy_body(GREEDY_PROMPT))
    check(g1w["text"] == g1["text"],
          "restarted server repeats the greedy text byte for byte")
    check_stats(args, srv2, need_fused=False)
    check(srv2.stop() == 0, "second server: exit code 0")
    say(f"server start to /health: cold {cold['up_s']:.1f}s "
        f"(warm-up {cold['warm']['warmup_s']}s), warm {warm['up_s']:.1f}s "
        f"(warm-up {warm['warm']['warmup_s']}s)")

    rc, _ = run_phase(args, "kernels", timeout=900)
    check(rc == 0, "every dequant mode matches the XLA reference on the "
                   "device")

    rc, out = run_logged(args, "dllama_inference", [
        sys.executable, "-m", f"{PKG}.app.dllama", "inference",
        "--model", MODEL, "--tokenizer", TOKENIZER,
        "--prompt", GREEDY_PROMPT, "--steps", str(N_GREEDY),
        "--temperature", "0"], timeout=900)
    require(rc == 0, "dllama inference: exit code 0")
    m = re.search(r"^🔷 Eval[^\n]*\n(.*)\n⏱ Evaluation:", out, re.S | re.M)
    require(m is not None, "dllama inference printed its text")
    routes.compare("server (eight lanes) against dllama inference (one)",
                   GREEDY_PROMPT, g1["text"], m.group(1))
    routes.settle(args)
    say(f"whole run: {time.perf_counter() - t_all:.0f}s")
    return cold["device"]


def run_four_chips(args) -> dict:
    t_all = time.perf_counter()
    prepare(args)
    prompts = (GREEDY_PROMPT, SECOND_PROMPT)

    srv1, one = start_server(args, "server_1chip")
    ref = [complete(srv1.port, greedy_body(p)) for p in prompts]
    check_stats(args, srv1, need_fused=False)
    check(srv1.stop() == 0, "one-chip server: exit code 0")

    srv4, tp = start_server(args, "server_tp4", ["--workers", "tp4"])
    dev = tp["device"]
    check((dev["mesh_shape"] or {}).get("tp") == 4 and dev["device_count"] >= 4,
          "tp4 server: mesh tp=4")
    check(dev["ring_sync"] is True and tp["engine"]["ring_sync"] is True,
          "tp4 server: start-up lines show the ring sync engaged")
    got = [complete(srv4.port, greedy_body(p)) for p in prompts]
    st = check_stats(args, srv4, need_fused=False)
    check(st["sync_bytes_total"] > 0,
          f"tp4 server: sync_bytes_total {st['sync_bytes_total']} "
          f"({st['sync_bytes_per_decode']} bytes in "
          f"{st['sync_collectives_per_decode']} collectives per decode step)")
    one_b, tp_b = one["device"]["device_bytes_in_use"], dev["device_bytes_in_use"]
    say(f"device bytes in use after load: one chip {one_b}, tp4 {tp_b}")
    if tp_b is None or one_b is None:
        check(args.rehearse, "the backend reports memory_stats() (only the "
                             "CPU rehearsal may go without)")
    else:
        check(len(tp_b) >= 4 and min(tp_b[:4]) > 0
              and max(tp_b[:4]) < 1.25 * min(tp_b[:4])
              and max(tp_b[:4]) < 0.6 * one_b[0],
              "tp4 server: weights and KV split over the four devices, not "
              "resident on device 0")
    check(srv4.stop() == 0, "tp4 server: exit code 0")

    routes = GreedyRoutes()
    for i, (p, r1, r4) in enumerate(zip(prompts, ref, got)):
        routes.compare(f"one chip against tp4, prompt {i}", p,
                       r1["text"], r4["text"])
    routes.settle(args)
    say(f"whole run: {time.perf_counter() - t_all:.0f}s")
    return dev


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the tensor-parallel phase and the one-chip "
                         "server it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic weights")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny model: control-flow rehearsal that never "
                         "reports ok")
    ap.add_argument("--phase", choices=("prepare", "kernels", "margin"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--queries", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase is not None:
        return {"prepare": phase_prepare, "kernels": phase_kernels,
                "margin": phase_margin}[args.phase](args)

    os.makedirs(OUT, exist_ok=True)
    try:
        dev = run_four_chips(args) if args.chips == 4 else run_one_chip(args)
    finally:
        for proc in _children:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    if _failed:
        say(f"{len(_failed)} check(s) FAILED:")
        for msg in _failed:
            say(f"  - {msg}")
        return 1
    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": dev["device_count"]}
    ok = device["platform"] == "tpu" and not args.rehearse
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
