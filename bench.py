"""Benchmark: decode + serving throughput of the flagship model on TPU.

Prints JSON lines: {"metric", "value", "unit", "vs_baseline", ...extras}.
The LAST line is the cumulative artifact; it is re-printed after every
completed phase, so a driver timeout at any point still records everything
measured so far (round-3 failure mode: rc=124 with nothing parsed).

Primary metric: batch=1 greedy decode tokens/sec for a Llama-3.2-1B-shaped
model with Q40 weights at rest in HBM (int4+f16 scales, dequant-in-matmul
Pallas kernel — the same weight format the reference runs,
src/nn/nn-quants.hpp:64-67) and a 2048-token KV cache.

Extra phases, each in its OWN child process with its OWN timeout so no
single phase can eat the budget:
  serving    — aggregate tok/s + p50/p95 step latency through the
               ContinuousBatchingScheduler at 8 concurrent requests (the
               reference's headline numbers are end-to-end app-loop
               per-token times, src/dllama.cpp:36-113)
  serving_churn — Poisson arrivals against the real scheduler: TTFT
               p50/p95 (submit -> first stream delta), aggregate tok/s,
               and the pipeline flush count under churn — the stall-free
               admission path (fused prefill+decode dispatch) keeps
               flushes ~0 while requests join mid-chain
  pod_serving — the same churn workload on a pure-TP mesh(tp=N): Q40
               planes TP-sharded (each chip reads 1/N of the weights per
               token), mesh-native pipelined+fused dispatch, ring-
               overlapped activation sync; reports tok/s/chip against
               the 200 north star plus the measured sync-ms split
  serving_faults — the chaos gate: churn with a deterministic engine
               fault injected mid-run (DLLAMA_FAULTS, utils/faults.py);
               reports error rate, hang-free, and breaker recovery time
               — the failure-containment layer's evidence
  serving_recovery — the crash-durability gate: churn with the request
               journal on, a simulated process death mid-stream, and a
               --recover-journal restart; reports resume-latency-ms,
               lost-token count (must be 0) and duplicate-token count
               (must be 0) for clients reattaching via Last-Event-ID
  serving_fleet — the fleet gate: Poisson SSE traffic through the
               dllama-router at 3 mock-backed replicas while one is
               SIGTERM-drained and one is killed mid-run; reports
               TTFT/TBT percentiles through the router, shed rate
               (must be 0 — sheds are retried or migrated), affinity
               hit rate, migration count + latency, and the loss
               ledger (byte-identical, 0 lost / 0 duplicated)
  serving_structured — the structured-output gate: Poisson churn with a
               JSON-schema workload mixed into plain lanes; reports
               valid-JSON rate (must be 1.0), schema-compile ms
               (cold/cached), masked-steps per dispatch, pipeline
               flushes (must be 0), and a constrained stream killed
               mid-flight replaying byte-identically through journal
               recovery
  serving_disagg — the disaggregated-prefill gate: a prefill-role, a
               decode-role and a mixed replica behind the router with
               prompt-length routing on; reports long-prompt TTFT, the
               KV-page hand-off count/latency (integrity-verified,
               refcount-correct adoption on the REAL pool), co-resident
               short-session TBT p95 vs a no-long-prompt baseline (must
               stay within 10%), byte-identity across the hand-off, and
               the monolithic fallback after the prefill replica dies
  ablations  — packed Q40 via XLA dequant, dense bf16 (what the kernel buys)
  8b         — the BASELINE north star: Llama-3.1-8B Q40 decode tok/s vs
               200 tok/s/chip (BASELINE.md), now on by default
  parity     — greedy token-identity of the shipping bf16-dot kernel vs
               exact f32 over 256 tokens (BASELINE.md gate-dtype clause)
  longctx    — decode tok/s at FULL context (whole-KV attention reads),
               bf16 KV vs --kv-dtype f8 (macbeth.sh's regime, measured)

Perf-path hygiene: weights are generated DIRECTLY as random packed planes
(no 2.5-16 GB dense intermediate on the host), so the first measurement
lands within a couple of minutes.

No chip, no run: a child that finds no TPU fails unless BENCH_FORCE_CPU=1
was asked for (CPU smoke of the phase logic, at the size GRAFT_SMALL picks),
and a device_kind missing from the peaks table is an error.

vs_baseline: ratio against the reference's best published single-device
number — Llama 2 7B on 1x RPi 4B at 1312.50 ms/token = 0.762 tok/s
(report.pdf Fig. 3, BASELINE.md). Reported ONLY for TPU runs (null under
BENCH_FORCE_CPU=1: a 1B-on-CPU vs 7B-on-RPi ratio is not a comparison), and
overwritten with the matched-model Llama-3.1-8B ratio when the 8b phase
lands; vs_baseline_model names the pairing.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REFERENCE_SINGLE_DEVICE_TOK_S = 1000.0 / 1312.50  # report.pdf Fig. 3
METRIC = "llama32_1b_q40_decode_tok_s"

# bf16 peak TFLOP/s and HBM GB/s per chip by device kind (public specs)
_CHIP_SPECS = {
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}


def _chip_spec(device_kind: str):
    for k, v in _CHIP_SPECS.items():
        if device_kind.lower().startswith(k.lower()):
            return v
    raise ValueError(
        f"device_kind {device_kind!r} is not in the peaks table "
        f"({sorted(_CHIP_SPECS)}): add its published peaks, do not guess"
    )


# ---------------------------------------------------------------------------
# Child: one benchmark phase per process (BENCH_PHASE env).
# ---------------------------------------------------------------------------


def _weight_specs(config):
    """(d_in, d_out, lead) per matmul weight (wcls/embedding handled by
    callers) — the single shape table every bench param generator draws
    from, so the TPU on-device path, the CPU host path, and the dense
    ablation cannot drift apart."""
    L, d, h = config.n_layers, config.dim, config.hidden_dim
    kv = config.n_kv_heads * config.head_size
    e = (config.n_experts,) if config.n_experts > 0 else ()
    return {
        "wq": (d, d, (L,)),
        "wk": (d, kv, (L,)),
        "wv": (d, kv, (L,)),
        "wo": (d, d, (L,)),
        "w1": (d, h, (L, *e)),
        "w2": (h, d, (L, *e)),
        "w3": (d, h, (L, *e)),
    }


def _random_packed_params(config, seed: int = 0, dtype=None):
    """Random PackedQ40 params WITHOUT the dense host intermediate: the
    packed nibble/scale planes are drawn directly (values are irrelevant to
    a bandwidth benchmark; shapes and bytes are exactly the Q40 footprint).
    Returns a host pytree ready for one device_put."""
    import numpy as np
    import jax.numpy as jnp

    from distributed_llama_multiusers_tpu.models.llama import (
        LlamaLayerParams,
        LlamaParams,
    )
    from distributed_llama_multiusers_tpu.models.loader import _rope_cache
    from distributed_llama_multiusers_tpu.quants.packed import PackedQ40

    if dtype is None:
        dtype = jnp.bfloat16
    rng = np.random.default_rng(seed)
    L, d = config.n_layers, config.dim

    from distributed_llama_multiusers_tpu.quants.packed import pad_packed_d_out

    def packed(d_in, d_out, lead=(), pad=False):
        pk = rng.integers(0, 256, (*lead, d_in // 2, d_out), dtype=np.uint8)
        sc = (rng.random((*lead, d_in // 32, d_out), dtype=np.float32)
              * 0.01 + 0.001).astype(np.float16)
        if pad:  # wcls only: vocab padding for the slab kernel's wide
            # tiles, mirroring the loader; llama_forward slices logits back
            pk, sc = pad_packed_d_out(pk, sc)
        return PackedQ40(packed=pk, scales=sc)

    w = {k: packed(*s[:2], s[2]) for k, s in _weight_specs(config).items()}
    layers = LlamaLayerParams(
        **w,
        rms_att=np.ones((L, d), np.float32),
        rms_ffn=np.ones((L, d), np.float32),
        moe_gate=(rng.standard_normal((L, d, config.n_experts), dtype=np.float32)
                  if config.n_experts > 0 else None),
    )
    cos, sin = _rope_cache(config)
    return LlamaParams(
        embedding=(rng.standard_normal((config.vocab_size, d), dtype=np.float32)
                   * 0.02).astype(dtype),
        layers=layers,
        rms_final=np.ones((d,), np.float32),
        wcls=packed(d, config.vocab_size, pad=True),
        rope_cos=cos,
        rope_sin=sin,
    )


def _assemble_params(config, t, cos, sin):
    """Shared LlamaParams assembly for the on-device generators: ``t`` maps
    weight names to device arrays; rms planes are ones; only the tiny RoPE
    tables cross the host->device link."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_multiusers_tpu.models.llama import (
        LlamaLayerParams,
        LlamaParams,
    )

    L, d = config.n_layers, config.dim
    layers = LlamaLayerParams(
        wq=t["wq"], wk=t["wk"], wv=t["wv"], wo=t["wo"],
        w1=t["w1"], w2=t["w2"], w3=t["w3"],
        rms_att=jnp.ones((L, d), jnp.float32),
        rms_ffn=jnp.ones((L, d), jnp.float32),
        moe_gate=t.get("moe_gate"),
    )
    return LlamaParams(
        embedding=t["embedding"],
        layers=layers,
        rms_final=jnp.ones((d,), jnp.float32),
        wcls=t["wcls"],
        rope_cos=jax.device_put(cos),
        rope_sin=jax.device_put(sin),
    )


def _device_packed_params(config, seed: int = 0, dtype=None):
    """Random PackedQ40 params generated ON DEVICE in one jitted program.

    Values are irrelevant to a bandwidth benchmark; on-chip random bits
    have identical shapes/bytes to the 0.7 GB (1B) / 4.3 GB (8B) host
    planes and cost zero host->device traffic (only the tiny RoPE tables
    cross)."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_multiusers_tpu.models.loader import _rope_cache
    from distributed_llama_multiusers_tpu.quants.packed import (
        PackedQ40,
        padded_d_out,
    )

    if dtype is None:
        dtype = jnp.bfloat16
    L, d = config.n_layers, config.dim
    specs = dict(_weight_specs(config))
    specs["wcls"] = (d, padded_d_out(config.vocab_size), ())

    def gen(key):
        out = {}
        for name, (d_in, d_out, lead) in specs.items():
            key, kp, ks = jax.random.split(key, 3)
            pk = jax.random.bits(kp, (*lead, d_in // 2, d_out), jnp.uint8)
            sc = (
                jax.random.uniform(ks, (*lead, d_in // 32, d_out), jnp.float32)
                * 0.01 + 0.001
            )
            if name == "wcls" and d_out > config.vocab_size:
                # keep the loader's invariant: zero scales make the vocab
                # pad columns dequantize to exact zeros
                sc = jnp.where(
                    jnp.arange(d_out) < config.vocab_size, sc, 0.0
                )
            out[name] = PackedQ40(packed=pk, scales=sc.astype(jnp.float16))
        key, ke, kg = jax.random.split(key, 3)
        out["embedding"] = (
            jax.random.normal(ke, (config.vocab_size, d), jnp.float32) * 0.02
        ).astype(dtype)
        if config.n_experts > 0:
            out["moe_gate"] = jax.random.normal(
                kg, (L, d, config.n_experts), jnp.float32
            )
        return out

    t = jax.jit(gen)(jax.random.PRNGKey(seed))
    jax.block_until_ready(t)
    return _assemble_params(config, t, *_rope_cache(config))


def _device_dense_params(config, seed: int = 0, dtype=None):
    """Dense random params generated on device (see _device_packed_params
    for why): the 1B bf16 tree is ~2.5 GB of host->device traffic an
    ablation does not need."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_multiusers_tpu.models.loader import _rope_cache

    if dtype is None:
        dtype = jnp.bfloat16
    L, d = config.n_layers, config.dim
    specs = {
        k: (*lead, d_in, d_out)
        for k, (d_in, d_out, lead) in _weight_specs(config).items()
    }
    specs["embedding"] = (config.vocab_size, d)
    specs["wcls"] = (d, config.vocab_size)

    def gen(key):
        out = {}
        for name, shape in specs.items():
            key, k1 = jax.random.split(key)
            out[name] = (jax.random.normal(k1, shape, jnp.float32) * 0.02).astype(dtype)
        if config.n_experts > 0:
            key, kg = jax.random.split(key)
            out["moe_gate"] = jax.random.normal(
                kg, (L, d, config.n_experts), jnp.float32
            )
        return out

    t = jax.jit(gen)(jax.random.PRNGKey(seed))
    jax.block_until_ready(t)
    return _assemble_params(config, t, *_rope_cache(config))


def _resident_packed_params(config, seed: int = 0):
    """Device-resident PackedQ40 params by the cheapest route for the
    backend: on-chip generation on TPU (zero bulk host->device traffic),
    host numpy + device_put on CPU (threefry on XLA:CPU is slower than one
    memcpy)."""
    import jax

    if jax.devices()[0].platform == "tpu":
        return _device_packed_params(config, seed)
    return jax.tree.map(jax.device_put, _random_packed_params(config, seed))


def _resident_dense_params(config, seed: int = 0, dtype=None):
    """Dense twin of _resident_packed_params (same backend dispatch)."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_multiusers_tpu.models import params_from_random

    if dtype is None:
        dtype = jnp.bfloat16
    if jax.devices()[0].platform == "tpu":
        return _device_dense_params(config, seed, dtype)
    host = params_from_random(config, seed=seed, dtype=dtype, to_device=False)
    return jax.tree.map(jax.device_put, host)


def _tree_device_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _param_matmul_flops_per_token(config) -> int:
    """2 * weight-params FLOPs/token (embedding lookup excluded, wcls
    included; MoE counts k active experts)."""
    d, h, kv = config.dim, config.hidden_dim, config.n_kv_heads * config.head_size
    ffn_mults = config.n_active_experts if config.n_experts > 0 else 1
    per_layer = d * d * 2 + d * kv * 2 + ffn_mults * 3 * d * h
    return 2 * (config.n_layers * per_layer + d * config.vocab_size)


def _bench_decode(config, params, n_short, n_long, reps=3, tag="",
                  start_pos=0, cache_dtype=None):
    """Marginal decode tok/s for one param set. ``start_pos``/``cache_dtype``
    parameterize the long-context phase (full-KV attention reads, f8 KV)
    without a second copy of the timing protocol."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llama_multiusers_tpu.models import init_kv_cache, llama_forward

    kv_dtype = cache_dtype or jnp.bfloat16

    def make_generate(n_steps):
        @partial(jax.jit, donate_argnums=(1,))
        def generate(params, cache, first_token, start_pos):
            def body(carry, _):
                tok, pos, cache = carry
                logits, cache = llama_forward(
                    config, params, tok[:, None], pos[:, None], cache
                )
                nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
                return (nxt, pos + 1, cache), nxt

            (_, _, cache), toks = jax.lax.scan(
                body, (first_token, start_pos, cache), None, length=n_steps
            )
            return toks, cache

        return generate

    first = jnp.zeros((1,), jnp.int32)
    pos0 = jnp.full((1,), start_pos, jnp.int32)

    def timed(n_steps):
        gen = make_generate(n_steps)

        def run():
            cache = init_kv_cache(config, n_lanes=1, dtype=kv_dtype)
            t0 = time.perf_counter()
            toks, _ = gen(params, cache, first, pos0)
            np.asarray(toks)  # forces completion (block_until_ready may not)
            return time.perf_counter() - t0

        return _best_of_reps(run, reps)

    t_short = timed(n_short)
    t_long = timed(n_long)
    print(f"[bench] {tag}: short({n_short})={t_short:.3f}s long({n_long})={t_long:.3f}s",
          file=sys.stderr, flush=True)
    if t_long - t_short > 0.1 * t_long:
        return (n_long - n_short) / (t_long - t_short)
    # marginal signal below dispatch-overhead noise: conservative whole-run rate
    return n_long / t_long


class _BenchTokenizer:
    """Duck-typed tokenizer stub for the serving phase: the measurement is
    the engine + scheduler loop, not BPE. EOS id = vocab_size (never
    produced), so every request runs to max_tokens."""

    class _Vocab:  # TokenizerChatStops renders eos pieces from .vocab
        def __getitem__(self, i) -> bytes:
            return b"</s>"

    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size
        self.eos_token_ids = [vocab_size]
        self.chat_template = None
        self.bos_id = 1
        self.vocab = self._Vocab()

    def encode(self, text, add_bos=True, add_special_tokens=True):
        # long enough that the serving phase's identical prompts clear the
        # scheduler's prefix_min_tokens=16, so admissions 2..8 exercise
        # prefix caching in the measured number
        n = max(1, min(len(text), 48))
        return [(7 + i) % self.vocab_size for i in range(n)]

    def make_stream_decoder(self):
        return self

    def decode(self, token):  # stream-decoder protocol
        return "x"


def _best_of_reps(run, reps):
    """min-of-(reps+1) of run()'s self-reported seconds (first rep doubles
    as compile + warmup). run times its own measured segment so setup (e.g.
    allocating the donated KV cache) stays OUTSIDE the window, and must
    block on the device (np.asarray a result)."""
    return min(run() for _ in range(reps + 1))


def _bench_prefill(config, params, t_prompt, reps=3, t_short=None):
    """(seconds for one t_prompt-token prefill, marginal tok/s).

    The single-call seconds (-> ttft_ms) is honest end-to-end latency and
    includes one host<->device round trip, so the throughput number uses
    the MARGINAL rate between a long and a short prefill (same fixed costs,
    different token counts), the same trick the decode metric uses. Reference
    analogue: the Eval phase readout, src/dllama.cpp:36-55."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llama_multiusers_tpu.models import init_kv_cache, llama_forward

    if t_short is None:
        t_short = max(16, t_prompt // 8)

    def timed(n_tok):
        @partial(jax.jit, donate_argnums=(1,))
        def prefill(params, cache, tokens, positions):
            logits, cache = llama_forward(config, params, tokens, positions, cache)
            return jnp.argmax(logits[:, -1, :], axis=-1), cache

        tokens = jnp.zeros((1, n_tok), jnp.int32)
        positions = jnp.arange(n_tok, dtype=jnp.int32)[None, :]

        def run():
            cache = init_kv_cache(config, n_lanes=1, dtype=jnp.bfloat16)
            t0 = time.perf_counter()
            nxt, _ = prefill(params, cache, tokens, positions)
            np.asarray(nxt)
            return time.perf_counter() - t0

        return _best_of_reps(run, reps)

    t_long_s = timed(t_prompt)
    marginal = None
    if t_short < t_prompt:
        t_short_s = timed(t_short)
        if t_long_s - t_short_s > 0.05 * t_long_s:
            marginal = (t_prompt - t_short) / (t_long_s - t_short_s)
    if marginal is None:
        # fixed costs dominate: whole-run rate (different semantics — the
        # caller records which method produced the number)
        return t_long_s, t_prompt / t_long_s, "whole_run"
    return t_long_s, marginal, "marginal"


def _phase_primary(config, platform, device_kind, small):
    import jax

    n_short, n_long = (4, 16) if small else (16, 128)
    t0 = time.perf_counter()
    params_q = _resident_packed_params(config)
    print(f"[bench] packed params resident in {time.perf_counter()-t0:.1f}s "
          f"({_tree_device_bytes(params_q)/1e9:.2f} GB)", file=sys.stderr, flush=True)

    tok_s = _bench_decode(config, params_q, n_short, n_long, tag="packed+pallas")
    # prefill is additive: a failure here must not discard the banked decode
    # number (the round-3 lesson: never lose the primary metric)
    t_prompt = 16 if small else 128
    prefill_extra = {}
    try:
        prefill_s, prefill_rate, rate_method = _bench_prefill(
            config, params_q, t_prompt
        )
        print(f"[bench] prefill({t_prompt})={prefill_s * 1e3:.1f} ms "
              f"({rate_method} {prefill_rate:.0f} tok/s)",
              file=sys.stderr, flush=True)
        prefill_extra = {
            "prefill_tok_s": round(prefill_rate, 1),
            "prefill_rate_method": rate_method,
            "ttft_ms": round(prefill_s * 1e3, 1),
        }
    except Exception as e:  # noqa: BLE001
        prefill_extra = {"prefill_error": f"{type(e).__name__}: {e}"[:200]}
    weight_bytes = _tree_device_bytes(params_q)
    # utilization is a device metric: a forced-CPU smoke reports none
    peak_flops, peak_bw = (
        _chip_spec(str(device_kind)) if platform == "tpu" else (None, None)
    )
    flops_tok = _param_matmul_flops_per_token(config)
    return {
        "metric": METRIC,
        "value": round(tok_s, 2),
        "unit": "tok/s",
        # ratio only for TPU runs (a forced-CPU 1B number vs the
        # reference's 7B-on-RPi invites misreading — round-4 weak #8); the
        # 8b phase overwrites this with the matched-model ratio when it
        # lands (see main)
        "vs_baseline": (
            round(tok_s / REFERENCE_SINGLE_DEVICE_TOK_S, 2)
            if platform == "tpu" else None
        ),
        "vs_baseline_model": (
            "llama32_1b (this) vs llama2_7b on 1x RPi 4B (reference)"
            if platform == "tpu" else None
        ),
        "platform": platform,
        "device_kind": str(device_kind),
        "weight_read_gb_s": round(weight_bytes * tok_s / 1e9, 1),
        "mfu": round(flops_tok * tok_s / peak_flops, 4) if peak_flops else None,
        "hbm_util": round(weight_bytes * tok_s / peak_bw, 3) if peak_bw else None,
        **prefill_extra,
        "baseline_note": "reference Llama-2-7B on 1x RPi 4B, 0.762 tok/s (report.pdf Fig.3)",
    }


def _serve_batch(config, params, n_lanes, max_tokens):
    """One warmup + one measured batch of n_lanes concurrent requests
    (half greedy, half sampled) through the real serving loop. Returns
    (tok/s, sorted step latencies, engine stats)."""
    import numpy as np

    from distributed_llama_multiusers_tpu.runtime import InferenceEngine
    from distributed_llama_multiusers_tpu.runtime.scheduler import (
        ContinuousBatchingScheduler,
        Request,
    )

    engine = InferenceEngine(
        config, params, n_lanes=n_lanes, prefill_buckets=(16,)
    )

    step_times: list[float] = []

    def _timed(fn):
        def wrapper(*a, **k):
            t0 = time.perf_counter()
            out = fn(*a, **k)
            step_times.append(time.perf_counter() - t0)
            return out
        return wrapper

    # pipeline_consume is the pipelined path's per-step blocking point (the
    # dispatch half is async), so timing it is the step-latency analogue of
    # timing the synchronous decode call
    for name in ("decode", "decode_spec", "decode_multi", "pipeline_consume"):
        setattr(engine, name, _timed(getattr(engine, name)))

    tokenizer = _BenchTokenizer(config.vocab_size)
    sched = ContinuousBatchingScheduler(engine, tokenizer)

    def run_batch():
        reqs = [
            Request(
                prompt="benchmark " * 2,
                max_tokens=max_tokens,
                temperature=0.0 if i % 2 == 0 else 0.8,
                seed=100 + i,
            )
            for i in range(n_lanes)
        ]
        t0 = time.perf_counter()
        sched.start()
        try:
            for r in reqs:
                sched.submit(r)
            for r in reqs:
                r.future.result(timeout=600)
        finally:
            sched.stop()
        wall = time.perf_counter() - t0
        toks = sum(len(r.generated_tokens) for r in reqs)
        assert all(r.error is None for r in reqs), [r.error for r in reqs]
        return toks, wall

    run_batch()  # compile + warmup (prefill bucket + decode programs)
    step_times.clear()
    engine.stats.reset()  # spec counters must cover the measured batch only
    toks, wall = run_batch()
    _drained_report("serve_batch", sched)
    return toks / wall, np.sort(np.asarray(step_times)), engine.stats


def _drained_report(phase, sched, pre_pages=0):
    """``leaked_resources == 0`` beside ``compiles_after_warmup == 0``
    (ISSUE 17): after stop() the leak witness's drain snapshot
    (scheduler.leak_counts() — session mirrors, pending device ops, open
    journal marks, lane-held KV pages) must be all-zero, with
    ``pool_pages_in_use`` back at its pre-phase count. Asserted, not just
    reported: a phase that leaked measured a dirtier steady state than
    the number it banked claims."""
    counts = sched.leak_counts()
    leaked = {
        k: v for k, v in counts.items()
        if v != (pre_pages if k == "kv_lane_pages" else 0)
    }
    assert not leaked, (
        f"{phase}: resources still held after stop: {leaked} "
        "(rerun under DLLAMA_LEAKCHECK=1 to raise at the exact drain "
        "point; docs/LINT.md resource-balance names the static twin)"
    )
    return {f"{phase}_leaked_resources": 0}


def _phase_serving(config, small):
    """Aggregate multi-user throughput through the real serving loop:
    ContinuousBatchingScheduler + InferenceEngine, 8 concurrent requests
    (half greedy, half sampled), chunked prefill interleaving with decode.
    A second 32-lane batch measures throughput scaling: decode is
    weight-read-bound, so the shared weight pass amortizes over lanes
    (the multi-user fork's raison d'etre; HBM holds far more than 8
    lanes of KV)."""
    max_tokens = 12 if small else 48
    params = _resident_packed_params(config)
    tok_s, lat, stats = _serve_batch(config, params, 8, max_tokens)

    # 32-lane scaling batch: TPU only (the rationale — amortizing the HBM
    # weight pass over lanes — doesn't exist on the CPU smoke path, and a
    # 32-lane compile would eat the unattended window's budget)
    wide: dict = {}
    if not small:
        try:
            import gc

            gc.collect()  # the _timed wrappers cycle-trap the 8-lane
            # engine (engine.decode -> wrapper -> bound method -> engine);
            # its ~GB-scale cache must be freed before the 32-lane
            # engine allocates, not whenever the cycle GC gets around to it
            wide_tok_s, _, _ = _serve_batch(config, params, 32, max_tokens)
            wide = {"serving_tok_s_32lanes": round(wide_tok_s, 2)}
        except Exception as e:  # noqa: BLE001 - the 8-lane number survives
            wide = {"serving_32lanes_error": f"{type(e).__name__}: {e}"[:200]}

    return {
        "serving_tok_s_8lanes": round(tok_s, 2),
        **wide,
        "serving_step_ms_p50": round(float(lat[len(lat) // 2]) * 1e3, 2),
        "serving_step_ms_p95": round(float(lat[int(len(lat) * 0.95)]) * 1e3, 2),
        "serving_requests": 8,
        "serving_leaked_resources": 0,  # asserted in _serve_batch
        # speculation acceptance over the measured batch, per (DRAFTED
        # lane, verify-step): 1.0 = no draft accepted, K+1 = full
        # acceptance. Sampled/draft-less lanes are excluded from both
        # counters, so the ratio is undiluted acceptance.
        "serving_spec_steps": stats.spec_steps,
        "spec_tokens_per_lane_step": (
            round(stats.spec_emitted / stats.spec_lane_steps, 2)
            if stats.spec_lane_steps else None
        ),
        # the 8 requests share a prompt, so admissions 2..8 reuse lane KV
        # via prefix caching — the measured serving number includes it
        "prefix_hits": stats.prefix_hits,
        "prefix_tokens_saved": stats.prefix_tokens_saved,
        # multi-step horizons taken during the measured batch (each = up to
        # 8 decode steps in one dispatch; step_ms percentiles count a whole
        # horizon as one step, so read them alongside this)
        "multi_dispatches": stats.multi_dispatches,
        # async decode pipeline over the measured batch: fraction of engine
        # decode wall-time the lagged consume hid behind device execution
        # (0 = fully serialized, the pre-pipeline regime), dispatches taken
        # device-fed, and chains aborted before their lanes finished
        "serving_overlap_frac": (
            round(stats.overlap_s / (stats.overlap_s + stats.decode_s), 3)
            if (stats.overlap_s + stats.decode_s) > 0 else None
        ),
        "pipeline_dispatches": stats.pipeline_dispatches,
        "pipeline_flushes": stats.pipeline_flushes,
        # deterministic overlap evidence independent of backend timing
        # noise: a mocked-engine scheduler run (see _pipeline_microbench)
        **_pipeline_microbench_safe(),
    }


def _run_churn(sched, n_requests, max_tokens, interval_mean=0.05, seed=7):
    """Poisson-arrival churn against a STARTED-then-stopped scheduler:
    deterministic seeded arrivals, MIXED traffic — half greedy (their
    generated streams go repetitive on the tiny config, so the n-gram
    drafter genuinely hits), a quarter regular-nucleus sampled, and a
    quarter WIDE-nucleus sampled (top_p = 1.0 — the class that used to
    flush to the host-exact path and now samples on device with the
    exact full-vocab sampler). Returns (total generated tokens, wall
    seconds). Shared by the single-chip ``serving_churn`` phase and the
    mesh ``pod_serving`` phase so the two workloads cannot drift apart."""
    import numpy as np

    from distributed_llama_multiusers_tpu.runtime.scheduler import Request

    rng = np.random.default_rng(seed)
    intervals = rng.exponential(interval_mean, n_requests)
    reqs = [
        Request(
            prompt="churn benchmark prompt " * 2,
            max_tokens=max_tokens,
            temperature=0.0 if i % 2 == 0 else 0.8,
            topp=1.0 if i % 4 == 3 else 0.9,
            seed=200 + i,
        )
        for i in range(n_requests)
    ]
    sched.start()
    t0 = time.perf_counter()
    try:
        for r, dt in zip(reqs, intervals):
            time.sleep(dt)
            sched.submit(r)
        for r in reqs:
            r.future.result(timeout=600)
    finally:
        sched.stop()
    wall = time.perf_counter() - t0
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return sum(len(r.generated_tokens) for r in reqs), wall


def _phase_serving_churn(config, small):
    """Poisson-arrival churn against the REAL scheduler: requests join a
    live serving loop mid-generation (the regime the fused prefill+decode
    dispatch exists for) instead of arriving all up front like the
    `serving` phase's batch. ZERO-FLUSH configuration: speculation ON
    (drafts verify inside the pipelined chain) and wide-nucleus sampled
    lanes in the mix (on-device exact top-p — the old host-exact flush
    class), so `serving_churn_pipeline_flushes` must read 0: no
    systematic flush class is left except stop/drain. Reports aggregate
    `serving_churn_tok_s`, `spec_emitted_per_dispatch` (tokens per
    drafted-lane verify step, >1 = speculation composing with the
    chain), and TTFT/TBT percentiles read from the SAME telemetry
    histogram registry the server's /metrics serves — bench numbers and
    scraped metrics cannot drift, because they are the same counts. Also
    writes the span ring as a Perfetto-loadable Chrome trace artifact
    (BENCH_TRACE_PATH overrides the tmp-dir default) and reports its
    fused/spec slice counts — the visible form of "admissions and
    speculation rode the live chain". CPU-smoke safe: small lane/request
    counts, deterministic seeded arrivals."""
    import numpy as np

    from distributed_llama_multiusers_tpu.runtime import InferenceEngine
    from distributed_llama_multiusers_tpu.runtime.engine import warmup_engine
    from distributed_llama_multiusers_tpu.runtime.scheduler import (
        ContinuousBatchingScheduler,
        Request,
    )
    from distributed_llama_multiusers_tpu.telemetry import Telemetry

    n_lanes = 4 if small else 8
    n_requests = 10 if small else 48
    max_tokens = 10 if small else 48
    params = _resident_packed_params(config)
    engine = InferenceEngine(
        config, params, n_lanes=n_lanes, prefill_buckets=(16,)
    )
    tokenizer = _BenchTokenizer(config.vocab_size)
    telemetry = Telemetry()
    # speculation ON: drafts verify INSIDE the pipelined chain (the
    # zero-flush tentpole) — the phase measures admission AND speculation
    # composing, not one at a time
    sched = ContinuousBatchingScheduler(
        engine, tokenizer, telemetry=telemetry
    )
    # compile everything (incl. the per-bucket fused family AND the spec
    # verify families) OUTSIDE the measured window: TTFT under churn must
    # not read as XLA compile time
    warmup_engine(engine, spec=True, multi_step=sched.multi_step)

    toks, wall = _run_churn(sched, n_requests, max_tokens)
    drained = _drained_report("serving_churn", sched)
    stats = engine.stats.snapshot()
    # compile-stability evidence (ISSUE 15): warmup armed the recompile
    # witness (analysis/jitcheck.py), so this is the MEASURED count of
    # XLA compiles the churn paid mid-serving. Assert, not just report:
    # a phase that recompiled measured warmup latency as serving tok/s,
    # and the artifact must not bank that silently.
    assert stats["jit_compiles_after_warmup"] == 0, (
        f"serving_churn recompiled {stats['jit_compiles_after_warmup']} "
        "program(s) after warmup — an unwarmed (family, bucket) is back "
        "(run the suite under DLLAMA_JITCHECK=1 for the guilty stack)"
    )

    # percentiles from the serving histogram registry (TTFT = submit ->
    # first consumed token, observed by the scheduler's telemetry hook)
    def pct_ms(hist, q):
        v = hist.quantile(q)
        return None if v is None else round(v * 1e3, 2)

    # the Perfetto artifact: lanes as tracks, fused/pipelined steps as
    # slices, admissions/finishes as instants
    import tempfile

    trace_path = os.environ.get("BENCH_TRACE_PATH") or os.path.join(
        tempfile.gettempdir(), "dllama_serving_churn_trace.json"
    )
    try:
        doc = telemetry.dump_trace(trace_path)
        slices = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        trace_extra = {
            "serving_churn_trace_path": trace_path,
            "serving_churn_trace_events": len(doc["traceEvents"]),
            "serving_churn_trace_fused_slices": sum(
                1 for e in slices if e["name"] == "step.fused"
            ),
            # the full composition made visible: verify steps that ALSO
            # carried an admission chunk (one dispatch, both jobs)
            "serving_churn_trace_spec_fused_slices": sum(
                1 for e in slices if e["name"] == "step.spec_fused"
            ),
            "serving_churn_trace_spec_slices": sum(
                1 for e in slices if e["name"] == "step.spec_pipelined"
            ),
            "serving_churn_trace_pipelined_slices": sum(
                1 for e in slices if e["name"] == "step.pipelined"
            ),
        }
    except OSError as e:  # artifact is evidence, not the headline
        trace_extra = {"serving_churn_trace_error": f"{type(e).__name__}: {e}"[:200]}

    return {
        "serving_churn_tok_s": round(toks / wall, 2),
        "serving_churn_requests": n_requests,
        "serving_churn_lanes": n_lanes,
        "serving_churn_ttft_ms_p50": pct_ms(telemetry.ttft, 0.5),
        "serving_churn_ttft_ms_p95": pct_ms(telemetry.ttft, 0.95),
        "serving_churn_tbt_ms_p50": pct_ms(telemetry.tbt, 0.5),
        "serving_churn_tbt_ms_p95": pct_ms(telemetry.tbt, 0.95),
        "serving_churn_queue_wait_ms_p95": pct_ms(telemetry.queue_wait, 0.95),
        # the headline churn evidence: admissions rode fused dispatches
        # and drafts rode spec-verify dispatches inside the live chain —
        # pipeline_flushes MUST read 0 (no systematic flush class remains)
        "serving_churn_pipeline_flushes": stats["pipeline_flushes"],
        "serving_churn_fused_steps": stats["fused_steps"],
        "serving_churn_pipeline_dispatches": stats["pipeline_dispatches"],
        # zero-flush speculation: verify steps dispatched in-chain, and
        # tokens consumed per DRAFTED-lane verify step (1.0 = drafts never
        # accepted, K+1 = full acceptance; > 1 means speculation's extra
        # tokens multiplied with the overlap instead of aborting it)
        "serving_churn_spec_pipelined_steps": stats["spec_pipelined_steps"],
        "serving_churn_spec_emitted_per_dispatch": (
            round(stats["spec_emitted"] / stats["spec_lane_steps"], 3)
            if stats["spec_lane_steps"] else None
        ),
        "serving_churn_spec_accept_hist": {
            str(k): v for k, v in sorted(stats["spec_accept_hist"].items())
        },
        # must read 0: the exact on-device sampler serves wide-nucleus
        # lanes; host_sampling=True is the only remaining host-exact path
        "serving_churn_host_exact_lanes": stats["host_exact_lanes"],
        "serving_churn_admission_stall_s": round(
            stats["admission_stall_s"], 4
        ),
        # compile stability alongside tok/s: 0 = every program the churn
        # dispatched was compiled at warmup — the asserted invariant above
        "serving_churn_compiles_after_warmup": stats[
            "jit_compiles_after_warmup"
        ],
        "serving_churn_prefix_hits": stats["prefix_hits"],
        **drained,
        **trace_extra,
    }


def _phase_serving_prefix(config, small):
    """Paged KV + cross-request prefix sharing under a shared-system-
    prompt Poisson workload with SESSIONS > LANES (the oversubscription
    regime ROADMAP item 3 names): N sessions arrive Poisson against a
    paged engine (``--paged-kv on`` equivalent), every prompt opens with
    the same system prefix, and finished sessions PARK — their tree-
    registered pages stay resident (refcounted) so follow-up admissions
    share them copy-free. Reports the prefix hit rate, pages per
    resident session, shared admissions and the zero-copy subset that
    needed no single-page COW either (a paged engine refuses
    ``copy_lane`` outright, so lane-copy HBM traffic is zero by
    construction — ``serving_prefix_lane_copies`` counts actual
    ``copy_lane`` entries to show it measured, not asserted), and the
    park vs drop-rebuild TTFT pair: a parked follow-up served by
    refcount bump against the same prompt re-prefilled from scratch
    after ``drop_parked()`` (the LRU-eviction path an oversubscribed
    admission takes; determinism of the rebuild is pinned in
    tests/test_prefix_cache.py). ``pipeline_flushes`` must stay 0:
    paged indirection lives inside the step families, not beside them.
    CPU-smoke safe: small lane/session counts, deterministic arrivals."""
    import numpy as np

    from distributed_llama_multiusers_tpu.runtime import InferenceEngine
    from distributed_llama_multiusers_tpu.runtime.engine import warmup_engine
    from distributed_llama_multiusers_tpu.runtime.scheduler import (
        ContinuousBatchingScheduler,
        Request,
    )
    from distributed_llama_multiusers_tpu.telemetry import Telemetry
    from distributed_llama_multiusers_tpu.utils.testing import (
        # prompt-DEPENDENT char-level encoding (shared text prefixes stay
        # shared token prefixes), one home with tests/test_prefix_cache.py
        CharStreamTokenizer,
    )

    n_lanes = 2 if small else 4
    n_sessions = 3 * n_lanes  # oversubscription: sessions >> lanes
    max_tokens = 8 if small else 32
    # long enough that the shared prefix spans several full pages — the
    # swap rung's TTFT delta scales with pages swapped back in, and a
    # one-page swap would drown in CPU-smoke scheduler jitter
    system = ("system: you are a terse and careful assistant. "
              "answer each user question briefly. ")
    params = _resident_packed_params(config)
    engine = InferenceEngine(
        config, params, n_lanes=n_lanes, prefill_buckets=(16,),
        paged_kv=True, kv_page_size=16,
        # host-RAM swap tier (runtime/kvpool.py HostTier): budget big
        # enough that every parked chain swaps rather than drops — the
        # phase measures all THREE residency tiers (park / swap / rebuild).
        # BENCH_KV_HOST_BYTES=0 is the evidence loop's A/B lever (swap
        # off -> the tier walk vanishes and swap_ttft degenerates to
        # rebuild, the pre-tier behavior)
        kv_host_bytes=int(os.environ.get("BENCH_KV_HOST_BYTES", 64 << 20)),
    )
    # MEASURE whole-lane HBM copy attempts instead of asserting zero:
    # every copy_lane entry (the contiguous path's prefix-reuse
    # primitive, the copy class this phase exists to show dying) is
    # counted BEFORE the call — a paged engine refuses copy_lane, so a
    # future change routing admissions back through a lane copy either
    # surfaces in this count (if the refusal were lifted) or fails the
    # phase loudly on the refusal; it can never read as a silent 0
    lane_copy_calls = 0
    _orig_copy_lane = engine.copy_lane

    def _counting_copy_lane(src, dst, prefix_len=None):
        nonlocal lane_copy_calls
        lane_copy_calls += 1
        return _orig_copy_lane(src, dst, prefix_len=prefix_len)

    engine.copy_lane = _counting_copy_lane
    # pre-phase lane-page occupancy: the drain check below asserts the
    # pool returns exactly here (parked pages are intentionally resident
    # and excluded from pool_pages_in_use by construction)
    pre_pages = engine.pool_stats().get("pool_pages_in_use", 0)
    tokenizer = CharStreamTokenizer(config.vocab_size, max_chars=96)
    telemetry = Telemetry()
    sched = ContinuousBatchingScheduler(engine, tokenizer,
                                        telemetry=telemetry)
    warmup_engine(engine, spec=True, multi_step=sched.multi_step)

    rng = np.random.default_rng(11)
    intervals = rng.exponential(0.05, n_sessions)
    reqs = [
        Request(prompt=system + f"user {i}: question {i}",
                max_tokens=max_tokens,
                temperature=0.0 if i % 2 == 0 else 0.8, seed=300 + i)
        for i in range(n_sessions)
    ]
    sched.start()
    t0 = time.perf_counter()
    try:
        for r, dt in zip(reqs, intervals):
            time.sleep(dt)
            sched.submit(r)
        for r in reqs:
            r.future.result(timeout=600)
        wall = time.perf_counter() - t0
        assert all(r.error is None for r in reqs), [r.error for r in reqs]
        toks = sum(len(r.generated_tokens) for r in reqs)
        pool_wave = engine.pool_stats()

        def ttft_one():
            r = Request(prompt=system + "user 0: question 0",
                        max_tokens=2, temperature=0.0)
            t = time.perf_counter()
            sched.submit(r)
            r.future.result(timeout=600)
            assert r.error is None, r.error
            return (time.perf_counter() - t) * 1e3

        def drop_all():
            # what LRU eviction does under an oversubscribed admission
            # when the host tier is full or disabled — drop the parked
            # chains AND clear the host tier (a served follow-up
            # re-parks, and its chain may still live in host RAM, so
            # without the clear a "rebuild" would quietly serve from
            # the tier)
            n = engine.kvpool.drop_parked()
            engine.kvpool.host_tier.clear()
            return n

        # the three residency rungs' TTFTs, measured INTERLEAVED as
        # min-of-N floors: the per-request cost differences (refcount
        # bump vs host->device swap-in vs full re-prefill) sit near the
        # scheduler's polling jitter on the CPU smoke, the MIN is the
        # jitter-free estimator of a deterministic cost, and the
        # round-robin ordering makes all three floors share the same
        # load drift instead of each eating a different slice of it.
        # Each rep re-establishes the state its request must hit: a
        # served follow-up re-parks, so the park rep is free, the swap
        # rep re-evicts to the tier, the rebuild rep drops everything
        park_ttft_ms = swap_ttft_ms = rebuild_ttft_ms = float("inf")
        swapped = dropped = 0
        for rep in range(15):
            # warm: served from PARKED pages by refcount bump (plus at
            # most one single-page COW)
            park_ttft_ms = min(park_ttft_ms, ttft_one())
            # swap: evict every parked chain into the host-RAM tier
            # (device gather -> sha256-framed host store, via the loop
            # thread — the gather must not race a dispatch that donates
            # the cache), then the same follow-up misses HBM, hits the
            # host tier, and swaps its prefix pages back in
            n = sched.run_device_op(lambda: engine.swap_out_parked())
            swapped = max(swapped, n)
            swap_ttft_ms = min(swap_ttft_ms, ttft_one())
            # rebuild: nothing resident anywhere — full re-prefill from
            # the prompt (the journal-rebuild cost class)
            dropped = max(dropped, drop_all())
            rebuild_ttft_ms = min(rebuild_ttft_ms, ttft_one())
        pool_swap = engine.pool_stats()
    finally:
        sched.stop()
    drained = _drained_report("serving_prefix", sched, pre_pages)
    stats = engine.stats.snapshot()
    pool = engine.pool_stats()
    # the swap gather/scatter programs were warmed (warmup_engine's
    # swap_in([0], swap_out([0])) round-trip), so even with the host
    # tier active the phase must run compile-free after warmup
    assert stats["jit_compiles_after_warmup"] == 0, (
        f"serving_prefix recompiled {stats['jit_compiles_after_warmup']} "
        "time(s) after warmup — the swap programs must be warmup-covered"
    )

    return {
        "serving_prefix_tok_s": round(toks / wall, 2),
        "serving_prefix_lanes": n_lanes,
        "serving_prefix_sessions": n_sessions,
        # resident sessions at the end of the wave: every finished
        # session parked (>= 2x lanes = the oversubscription headline)
        "serving_prefix_resident_sessions": pool_wave[
            "pool_parked_sessions"
        ],
        "serving_prefix_hit_rate": round(
            pool_wave["pool_prefix_admits"]
            / max(1, pool_wave["pool_admits"]), 3
        ),
        "serving_prefix_tokens_shared": pool_wave[
            "pool_prefix_tokens_shared"
        ],
        # shared-prefix admissions: full blocks by refcount bump on the
        # SAME physical pages, plus AT MOST one single-page COW at a
        # divergent block (the pool counts one cow_copy per such
        # admission, so shared - cow = the subset that needed no page
        # traffic at all). Both from the SAME end-of-wave snapshot, so
        # the subset can never read larger than its superset. Whole-lane
        # (copy_lane-class) copies are the class this layout kills —
        # measured via the call counter
        "serving_prefix_shared_admissions": pool_wave[
            "pool_prefix_admits"
        ],
        "serving_prefix_zero_copy_admissions": (
            pool_wave["pool_prefix_admits"] - pool_wave["pool_cow_copies"]
        ),
        "serving_prefix_lane_copies": lane_copy_calls,
        "serving_prefix_cow_copies": pool_wave["pool_cow_copies"],
        # HBM cost of a resident (parked) session, in pages: parked
        # pages are DISTINCT physical pages (shared pages count once),
        # so LOWER = sessions overlap more — pure-private sessions
        # would each pay their full ceil((prompt+gen)/page)
        "serving_prefix_pages_per_session": round(
            pool_wave["pool_parked_pages"]
            / max(1, pool_wave["pool_parked_sessions"]), 2
        ),
        "serving_prefix_pool_pages_total": pool["pool_pages_total"],
        "serving_prefix_park_ttft_ms": round(park_ttft_ms, 2),
        # the middle residency rung: same follow-up served by host-tier
        # swap-in — dearer than a refcount bump (park), cheaper than a
        # full re-prefill (rebuild); the three TTFTs together are the
        # tiered-residency headline
        "serving_prefix_swap_ttft_ms": round(swap_ttft_ms, 2),
        "serving_prefix_swapped_sessions": swapped,
        "serving_prefix_swap_outs": pool_swap["swap_outs"],
        "serving_prefix_swap_ins": pool_swap["swap_ins"],
        "serving_prefix_swap_out_bytes": pool_swap["swap_out_bytes"],
        "serving_prefix_swap_in_bytes": pool_swap["swap_in_bytes"],
        "serving_prefix_swap_in_ms": pool_swap["swap_in_ms"],
        "serving_prefix_host_hit_rate": round(
            pool_swap["pool_host_hits"]
            / max(1, pool_swap["pool_host_hits"]
                  + pool_swap["pool_host_misses"]), 3
        ),
        "serving_prefix_dropped_sessions": dropped,
        "serving_prefix_rebuild_ttft_ms": round(rebuild_ttft_ms, 2),
        "serving_prefix_parked_evicted": pool["pool_parked_evicted"],
        "serving_prefix_exhausted_sheds": pool["pool_exhausted_sheds"],
        "serving_prefix_ttft_ms_p50": (
            None if telemetry.ttft.quantile(0.5) is None
            else round(telemetry.ttft.quantile(0.5) * 1e3, 2)
        ),
        "serving_prefix_ttft_ms_p95": (
            None if telemetry.ttft.quantile(0.95) is None
            else round(telemetry.ttft.quantile(0.95) * 1e3, 2)
        ),
        "serving_prefix_pipeline_flushes": stats["pipeline_flushes"],
        "serving_prefix_compiles_after_warmup": stats[
            "jit_compiles_after_warmup"
        ],
        "serving_prefix_prefix_hits": stats["prefix_hits"],
        "serving_prefix_prefix_tokens_saved": stats["prefix_tokens_saved"],
        **drained,
    }


def _phase_pod_serving(config, small):
    """Pod-native serving: the churn workload (the `serving_churn` phase's
    exact arrival process) on a pure-TP mesh(tp=N) with the Q40 planes
    TP-sharded — each chip reads 1/N of the weights per token, the explicit
    route past the single-chip HBM roofline (BASELINE.md: ~182 tok/s
    theoretical, 200 tok/s/chip north star needs the pod). The engine is
    mesh-native end to end: sharded KV (cache_shardings), replicated token
    carry, pipelined + fused-admission dispatches, and the TP activation
    sync ring-overlapped with the dequant matmul (DLLAMA_RING_SYNC;
    ops/ring_collective.py). Honors DLLAMA_DEQUANT so the in-bench kernel
    sweep can bank the kernel A/B and the pod number in one unattended
    pass. Reports `pod_serving_tok_s_per_chip` against the 200 north star
    plus the measured per-step sync split (engine.measured_sync_stats).

    Off-TPU (CPU smoke) the mesh is the 8-virtual-device test mesh; with a
    single real chip tp degenerates to 1 (the mesh-native path still runs
    — dispatch under GSPMD — but the sync is trivial and the per-chip
    number equals the aggregate)."""
    import jax

    from distributed_llama_multiusers_tpu.ops.ring_collective import (
        ring_sync_enabled,
    )
    from distributed_llama_multiusers_tpu.parallel import (
        MeshPlan,
        make_mesh,
        validate_mesh_for_config,
    )
    from distributed_llama_multiusers_tpu.parallel.sharding import shard_params
    from distributed_llama_multiusers_tpu.runtime import InferenceEngine
    from distributed_llama_multiusers_tpu.runtime.engine import warmup_engine
    from distributed_llama_multiusers_tpu.runtime.scheduler import (
        ContinuousBatchingScheduler,
    )
    from distributed_llama_multiusers_tpu.telemetry import Telemetry

    n_dev = len(jax.devices())
    # largest valid pure-TP width, by the validator itself (the single
    # source of truth for mesh constraints — a new rule there must degrade
    # this phase to a smaller tp, not crash it)
    tp, plan = 1, MeshPlan(tp=1)
    for cand in range(min(n_dev, config.n_kv_heads), 0, -1):
        try:
            validate_mesh_for_config(config, MeshPlan(tp=cand))
        except ValueError:
            continue
        tp, plan = cand, MeshPlan(tp=cand)
        break
    mesh = make_mesh(plan)
    print(f"[bench] pod_serving: mesh(tp={tp}) over {n_dev} device(s), "
          f"ring_sync={'on' if ring_sync_enabled() else 'off'}",
          file=sys.stderr, flush=True)

    if jax.devices()[0].platform == "tpu":
        params = shard_params(_device_packed_params(config), mesh)
    else:
        params = shard_params(_random_packed_params(config), mesh)

    n_lanes = 4 if small else 8
    n_requests = 10 if small else 48
    max_tokens = 10 if small else 48
    engine = InferenceEngine(
        config, params, n_lanes=n_lanes, prefill_buckets=(16,), mesh=mesh
    )
    tokenizer = _BenchTokenizer(config.vocab_size)
    telemetry = Telemetry()
    sched = ContinuousBatchingScheduler(
        engine, tokenizer, speculative=False, telemetry=telemetry
    )
    # compiles every sharded program family per bucket (and AOT-compiles
    # the decode step for the collective byte estimate) OUTSIDE the window
    warmup_engine(engine, spec=False, multi_step=sched.multi_step)
    coll = engine.collective_stats()

    toks, wall = _run_churn(sched, n_requests, max_tokens)
    drained = _drained_report("pod_serving", sched)
    # snapshot BEFORE the sync probe below: the probe is diagnostics and
    # must not blur the serving window's compile-stability evidence
    stats = engine.stats.snapshot()
    # the pod twin of serving_churn's compile-stability gate: a recompile
    # on a mesh stalls EVERY chip of the pod mid-serving, and a phase
    # that recompiled banked warmup latency as tok/s/chip (the number
    # ROADMAP item 2 spends real v5e-8 time on)
    assert stats["jit_compiles_after_warmup"] == 0, (
        f"pod_serving recompiled {stats['jit_compiles_after_warmup']} "
        "program(s) after warmup — an unwarmed mesh family is back"
    )

    # measured per-step sync split (profiler probe; rewrites cache slot 0,
    # safe after the workload)
    sync = engine.measured_sync_stats(steps=4)

    def pct_ms(hist, q):
        v = hist.quantile(q)
        return None if v is None else round(v * 1e3, 2)

    tok_s = toks / wall
    return {
        "pod_serving_tok_s": round(tok_s, 2),
        "pod_serving_tok_s_per_chip": round(tok_s / tp, 2),
        "pod_serving_northstar_frac": round(tok_s / tp / 200.0, 4),
        "pod_serving_mesh_tp": tp,
        "pod_serving_devices": n_dev,
        "pod_serving_ring_sync": ring_sync_enabled(),
        "pod_serving_requests": n_requests,
        "pod_serving_lanes": n_lanes,
        "pod_serving_ttft_ms_p50": pct_ms(telemetry.ttft, 0.5),
        "pod_serving_ttft_ms_p95": pct_ms(telemetry.ttft, 0.95),
        "pod_serving_tbt_ms_p50": pct_ms(telemetry.tbt, 0.5),
        # the mesh-native async chain held under churn: admissions rode
        # fused dispatches, zero aborts
        "pod_serving_pipeline_flushes": stats["pipeline_flushes"],
        "pod_serving_fused_steps": stats["fused_steps"],
        "pod_serving_pipeline_dispatches": stats["pipeline_dispatches"],
        # compile stability over the measured window (asserted 0 above)
        "pod_serving_compiles_after_warmup": stats[
            "jit_compiles_after_warmup"
        ],
        # static per-step collective payload (post-SPMD HLO) + measured split
        "pod_serving_sync_bytes_per_decode": coll.get("total_bytes", 0),
        "pod_serving_sync_collectives_per_decode": coll.get("n_collectives", 0),
        "pod_serving_sync_bytes_total": stats["sync_bytes_total"],
        "pod_serving_step_ms": sync.get("step_ms"),
        "pod_serving_sync_ms": sync.get("sync_ms"),
        "pod_serving_sync_frac": sync.get("sync_frac"),
        "pod_serving_sync_source": sync.get("source"),
        **drained,
    }


def _phase_serving_faults(config, small):
    """Chaos gate as a bench phase (failure containment, ISSUE 8): the
    churn arrival process with a DETERMINISTIC engine fault injected
    mid-run (utils/faults.py; `DLLAMA_FAULTS` overrides the default
    one-shot dispatch fault). Reports what the containment layer is FOR:

    - error rate — how many requests the one engine fault actually cost
      (only the lanes occupied at the failure instant, finish_reason
      "error", request_id-carrying failures);
    - hang-free — every submitted future RESOLVED (the pre-containment
      failure mode was a dead loop thread with every client blocked);
    - recovery — the circuit breaker re-closed after the fault
      (`serving_faults_recovery_ms` = how long the circuit held open),
      and the loop kept serving: requests after the fault completed
      normally with the ring drained."""
    from concurrent.futures import TimeoutError as FuturesTimeout

    import numpy as np

    from distributed_llama_multiusers_tpu.runtime import InferenceEngine
    from distributed_llama_multiusers_tpu.runtime.engine import warmup_engine
    from distributed_llama_multiusers_tpu.runtime.scheduler import (
        ContinuousBatchingScheduler,
        Request,
    )
    from distributed_llama_multiusers_tpu.serving import (
        AdmissionRejected,
        CircuitBreaker,
    )
    from distributed_llama_multiusers_tpu.telemetry import Telemetry
    from distributed_llama_multiusers_tpu.utils import faults

    n_lanes = 2 if small else 4
    n_requests = 10 if small else 24
    max_tokens = 8 if small else 24
    params = _resident_packed_params(config)
    engine = InferenceEngine(
        config, params, n_lanes=n_lanes, prefill_buckets=(16,)
    )
    telemetry = Telemetry()
    breaker = CircuitBreaker(threshold=1, cooldown_s=0.5)
    # threshold 1: the single default fault also walks the breaker through
    # open -> (cooldown) -> recovery, so the phase banks a recovery time
    sched = ContinuousBatchingScheduler(
        engine, _BenchTokenizer(config.vocab_size), speculative=False,
        telemetry=telemetry, breaker=breaker,
    )
    # compile OUTSIDE the armed window: warmup dispatches must not
    # advance the fault plan's arrival counters
    warmup_engine(engine, spec=False, multi_step=sched.multi_step)
    spec = os.environ.get("DLLAMA_FAULTS", "engine.dispatch:@20:n=1")
    plan = faults.arm(spec)

    rng = np.random.default_rng(11)
    intervals = rng.exponential(0.05, n_requests)
    reqs = [
        Request(
            prompt="chaos benchmark prompt " * 2,
            max_tokens=max_tokens,
            temperature=0.0 if i % 2 == 0 else 0.8,
            seed=300 + i,
        )
        for i in range(n_requests)
    ]
    submitted, shed = [], 0
    hang_free = True
    sched.start()
    t0 = time.perf_counter()
    try:
        for r, dt in zip(reqs, intervals):
            time.sleep(dt)
            try:
                sched.submit(r)
                submitted.append(r)
            except AdmissionRejected:
                shed += 1  # open circuit mid-churn: shed is correct behavior
        for r in submitted:
            try:
                r.future.result(timeout=300)
            except FuturesTimeout:
                hang_free = False  # THE failure containment exists to prevent
                r.cancel()
            except Exception:  # noqa: BLE001 — failed requests are the point
                pass
        # recovery: if the circuit is still open (fault landed late), give
        # it a cooldown and drive one probe request through
        probes = 0
        deadline = time.monotonic() + 10
        while breaker.state != "closed" and time.monotonic() < deadline:
            time.sleep(0.55)
            probe = Request(prompt="probe", max_tokens=2, temperature=0.0)
            try:
                sched.submit(probe)
                probes += 1
                probe.future.result(timeout=60)
            except Exception:  # noqa: BLE001 — the state read below decides
                pass
        wall = time.perf_counter() - t0
    finally:
        faults.disarm()
        sched.stop()
    # the chaos twin of ring-drained: even with a fault mid-dispatch,
    # containment released every mirror/page/op the failed lanes held
    drained = _drained_report("serving_faults", sched)

    outcomes: dict[str, int] = {}
    for r in submitted:
        outcomes[str(r.finish_reason)] = outcomes.get(str(r.finish_reason), 0) + 1
    n_err = outcomes.get("error", 0)
    br = breaker.stats()
    qos = sched.qos_stats()
    rec_ms = (
        None if br["breaker_last_recovery_s"] is None
        else round(br["breaker_last_recovery_s"] * 1e3, 1)
    )
    return {
        "serving_faults_spec": spec,
        "serving_faults_fired": len(plan.fired_log()),
        "serving_faults_requests": n_requests,
        "serving_faults_submitted": len(submitted),
        "serving_faults_shed": shed,
        "serving_faults_errors": n_err,
        "serving_faults_error_rate": round(n_err / max(1, len(submitted)), 4),
        "serving_faults_finish_reasons": outcomes,
        # the three headline properties of the chaos gate:
        "serving_faults_hang_free": hang_free,
        "serving_faults_recovered": breaker.state == "closed",
        "serving_faults_recovery_ms": rec_ms,
        "serving_faults_probes": probes,
        "serving_faults_engine_failure_rounds": qos["engine_failure_rounds"],
        "serving_faults_breaker_trips": br["breaker_trips"],
        "serving_faults_ring_drained": engine.pipeline_inflight() == 0,
        "serving_faults_wall_s": round(wall, 2),
        **drained,
    }


def _phase_serving_recovery(config, small):
    """Crash-durability gate as a bench phase (ISSUE 10): the churn
    arrival process with the JOURNAL on, a simulated process death
    mid-stream, and a ``--recover-journal``-style restart. Reports what
    the recovery layer is FOR:

    - resume-latency-ms — recovery start -> first RESUMED delta reaching
      a reattached client (the "latency blip" claim, measured);
    - lost tokens (MUST be 0) — reference-stream tokens a client that
      reconnected with its Last-Event-ID never saw;
    - duplicate tokens (MUST be 0) — tokens delivered twice across the
      kill.

    The kill is a journal detach + abrupt stop, NOT an injected engine
    fault: PR 8's containment layer CATCHES injected faults and journals
    a finish (finish_reason="error") — by design, a contained failure is
    final. Only a real process death leaves admit records without
    finishes, so that is what the phase models (the same crash image a
    watchdog ``os._exit(17)`` or an OOM kill leaves behind)."""
    import numpy as np

    from distributed_llama_multiusers_tpu.runtime import InferenceEngine
    from distributed_llama_multiusers_tpu.runtime.engine import warmup_engine
    from distributed_llama_multiusers_tpu.runtime.scheduler import (
        ContinuousBatchingScheduler,
        Request,
    )
    from distributed_llama_multiusers_tpu.serving import (
        RequestJournal,
        StreamRegistry,
        read_journal,
        recover_scheduler,
    )
    from distributed_llama_multiusers_tpu.telemetry import Telemetry

    n_lanes = 2 if small else 4
    n_requests = n_lanes  # all lanes mid-flight at the kill
    max_tokens = 24 if small else 64

    class _RecoveryTokenizer(_BenchTokenizer):
        """Per-token distinct text + prompt-dependent encoding, so
        byte-identity across the kill is a REAL assertion (the base
        bench tokenizer decodes every token to "x")."""

        def encode(self, text, add_bos=True, add_special_tokens=True):
            h = sum(ord(c) * (i + 1) for i, c in enumerate(text))
            return [(h + 5 * i) % self.vocab_size for i in range(24)]

        def decode(self, token):
            return f"[{token}]"

    def make_sched(journal):
        params = _resident_packed_params(config)
        engine = InferenceEngine(
            config, params, n_lanes=n_lanes, prefill_buckets=(16,)
        )
        sched = ContinuousBatchingScheduler(
            engine, _RecoveryTokenizer(config.vocab_size),
            speculative=False, prefix_min_tokens=0, telemetry=Telemetry(),
            journal=journal,
        )
        warmup_engine(engine, spec=False, multi_step=sched.multi_step)
        return sched

    def make_reqs():
        return [
            Request(
                prompt=f"recovery benchmark prompt {i}",
                max_tokens=max_tokens,
                temperature=0.0 if i % 2 == 0 else 0.8,
                seed=400 + i,
            )
            for i in range(n_requests)
        ]

    # -- reference: the uninterrupted streams --------------------------------
    sched = make_sched(None)
    refs = make_reqs()
    ref_streams: dict[int, list] = {i: [] for i in range(n_requests)}

    def ref_cb(i, rq):
        return lambda d: ref_streams[i].append(
            (len(rq.generated_tokens), d)
        )

    sched.start()
    for i, rq in enumerate(refs):
        rq.on_delta = ref_cb(i, rq)
        sched.submit(rq)
    for rq in refs:
        rq.future.result(timeout=300)
    sched.stop()
    _drained_report("serving_recovery_ref", sched)

    # -- crash run: journal on, die mid-stream -------------------------------
    journal_path = os.path.join(
        tempfile.mkdtemp(prefix="dllama_recovery_"), "journal.bin"
    )
    journal = RequestJournal(journal_path, progress_every=2, fsync=False)
    sched = make_sched(journal)
    crash = make_reqs()
    pre: dict[int, list] = {i: [] for i in range(n_requests)}
    delivered = {i: 0 for i in range(n_requests)}

    def crash_cb(i, rq):
        def cb(d):
            pre[i].append((len(rq.generated_tokens), d))
            delivered[i] = len(rq.generated_tokens)
            journal.note_progress(rq.id, delivered[i])
        return cb

    rng = np.random.default_rng(17)
    intervals = rng.exponential(0.02, n_requests)
    sched.start()
    for (i, rq), dt in zip(enumerate(crash), intervals):
        time.sleep(dt)
        rq.on_delta = crash_cb(i, rq)
        sched.submit(rq)
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline and any(
        len(v) < 4 for v in pre.values()
    ):
        time.sleep(0.005)
    # the kill: nothing after this instant reaches the journal — the
    # stop() below stands in for the process dying with lanes mid-decode
    sched.journal = None
    journal.flush()
    journal.close()
    sched.stop()
    # the crash image's open marks live in the DETACHED journal (the
    # whole point); the scheduler's own resources must still settle —
    # stop() is a clean shutdown standing in for the process dying
    _drained_report("serving_recovery_crash", sched)
    pre_tokens = sum(len(v) for v in pre.values())
    incomplete = read_journal(journal_path).incomplete()

    # -- restart + recovery --------------------------------------------------
    registry = StreamRegistry(grace_s=60.0)
    sched = make_sched(None)
    sched.start()
    t_recover = time.perf_counter()
    coordinator = recover_scheduler(sched, journal_path, registry=registry)
    first_delta_at: dict[int, float] = {}
    resumed: dict[int, list] = {}

    def reattach(i, rid, last):
        got = registry.attach(rid)
        if got is None:
            return
        _rq, relay, _kind, gen = got
        out = []
        while True:
            item = relay.next_after(last, timeout=120, gen=gen)
            if item is None:
                break
            if item[0] == "delta":
                if i not in first_delta_at:
                    first_delta_at[i] = time.perf_counter()
                _, last, text = item
                out.append((last, text))
            elif item[0] == "done":
                break
            else:
                break  # gap/superseded: recorded via lost-token count
        resumed[i] = out

    coordinator.join(240)
    by_id = {rq.id: i for i, rq in enumerate(crash)}
    threads = [
        threading.Thread(
            target=reattach, args=(by_id[e.request_id], e.request_id,
                                   delivered[by_id[e.request_id]]),
        )
        for e in incomplete
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    sched.stop()
    registry.close()
    drained = _drained_report("serving_recovery", sched)

    # -- reconcile: the client view vs the uninterrupted streams -------------
    lost = dup = 0
    identical = True
    resume_ms = []
    for i in range(n_requests):
        view = pre[i] + resumed.get(i, [])
        seen: dict[int, str] = {}
        for idx, text in view:
            if idx in seen:
                dup += 1
            seen[idx] = text
        ref = dict(ref_streams[i])
        lost += sum(1 for idx in ref if idx not in seen)
        if "".join(t for _, t in sorted(seen.items())) != "".join(
            t for _, t in sorted(ref.items())
        ):
            identical = False
        if i in first_delta_at:
            resume_ms.append((first_delta_at[i] - t_recover) * 1e3)
    rec = coordinator.stats()
    jstats = read_journal(journal_path)
    return {
        "serving_recovery_requests": n_requests,
        "serving_recovery_killed_inflight": len(incomplete),
        "serving_recovery_pre_kill_tokens": pre_tokens,
        "serving_recovery_recovered_requests": rec["recovered_requests"],
        "serving_recovery_replayed_tokens": rec["recovery_replayed_tokens"],
        # the three headline properties of the recovery gate:
        "serving_recovery_resume_latency_ms": (
            round(min(resume_ms), 1) if resume_ms else None
        ),
        "serving_recovery_lost_tokens": lost,
        "serving_recovery_duplicate_tokens": dup,
        "serving_recovery_byte_identical": identical,
        "serving_recovery_journal_records": jstats.records,
        "serving_recovery_journal_torn_tail": jstats.torn,
        **drained,
    }


def _phase_serving_structured(config, small):
    """The structured-output gate (ISSUE 13): Poisson churn with a
    JSON-schema workload MIXED into plain lanes against the real
    scheduler — constrained (json_object + json_schema, greedy and
    sampled) and unconstrained requests share the fused pipelined chain.
    Reports:

    - ``structured_valid_json_rate`` — fraction of constrained
      completions that parse as (schema-valid) JSON: MUST be 1.0, the
      on-device mask is the whole point;
    - ``structured_schema_compile_ms`` — cold automaton compile cost
      (token closure over the vocab) and the cached re-admission cost;
    - ``structured_masked_steps_per_dispatch`` — how often the mask
      actually bit, over all pipeline dispatches;
    - ``structured_pipeline_flushes`` — MUST be 0: constrained lanes
      ride the zero-flush chain like everyone else;
    - ``structured_replay_identical`` — a constrained stream killed
      mid-flight replays byte-identically through journal recovery
      (the crash-durability contract extended to grammars).

    Mock-backed on purpose (content_keyed determinism class): the phase
    measures the GRAMMAR layer — compile cost, mask cadence, validity,
    replay — not kernel speed, and runs identically on any host."""
    import tempfile

    import numpy as np

    from distributed_llama_multiusers_tpu.grammar.automaton import (
        _cache as _gram_cache,
    )
    from distributed_llama_multiusers_tpu.runtime.scheduler import (
        ContinuousBatchingScheduler,
        Request,
    )
    from distributed_llama_multiusers_tpu.serving import (
        RequestJournal,
        read_journal,
    )
    from distributed_llama_multiusers_tpu.utils.testing import (
        ByteJsonTokenizer,
        MockAsyncEngine,
    )

    schema = {
        "type": "object",
        "properties": {
            "name": {"type": "string"},
            "score": {"type": "integer"},
            "tags": {"type": "array", "items": {"type": "string"}},
            "verdict": {"enum": ["pass", "fail", None]},
        },
        "required": ["name", "verdict"],
    }
    schema_rf = {"type": "json_schema", "json_schema": {"schema": schema}}
    n_requests = 12 if small else 48
    n_lanes = 4 if small else 8

    def build():
        tok = ByteJsonTokenizer()
        eng = MockAsyncEngine(
            n_lanes=n_lanes, vocab=258, speculative=True,
            content_keyed=True,
        )
        eng.grammar_init(tok.token_table(), tok.eos_token_ids)
        return tok, eng

    # cold vs cached schema compile (the per-admission cost ladder)
    tok0, eng0 = build()
    _gram_cache.clear()
    t0 = time.perf_counter()
    h0 = eng0.grammar_attach(schema_rf)
    compile_cold_ms = (time.perf_counter() - t0) * 1e3
    eng0.grammar_detach(h0.key)
    t0 = time.perf_counter()
    eng0.grammar_attach(schema_rf)  # cache hit + parked-slab re-attach
    compile_cached_ms = (time.perf_counter() - t0) * 1e3

    tok, engine = build()
    sched = ContinuousBatchingScheduler(engine, tok, prefix_min_tokens=0)
    rng = np.random.default_rng(7)
    reqs = [
        Request(
            prompt=f"structured churn {i}",
            max_tokens=800,
            temperature=0.0 if i % 2 == 0 else 0.7,
            seed=300 + i,
            response_format=[
                schema_rf, None, {"type": "json_object"}, None
            ][i % 4],
        )
        for i in range(n_requests)
    ]
    sched.start()
    t0 = time.perf_counter()
    try:
        for r, dt in zip(reqs, rng.exponential(0.01, n_requests)):
            time.sleep(dt)
            sched.submit(r)
        for r in reqs:
            r.future.result(timeout=600)
    finally:
        sched.stop()
    drained = _drained_report("serving_structured", sched)
    wall = time.perf_counter() - t0
    assert all(r.error is None for r in reqs), [r.error for r in reqs]

    constrained = [r for r in reqs if r.response_format is not None]
    valid = 0
    for r in constrained:
        try:
            obj = json.loads(r.generated_text)
        except ValueError:
            continue
        if r.response_format is schema_rf:
            if (
                isinstance(obj, dict)
                and {"name", "verdict"} <= set(obj)
                and set(obj) <= {"name", "score", "tags", "verdict"}
                and obj["verdict"] in ("pass", "fail", None)
            ):
                valid += 1
        elif isinstance(obj, dict):
            valid += 1
    stats = engine.stats.snapshot()

    # kill-and-replay: journal a constrained stream, cancel it mid-
    # flight (the crash stand-in), regenerate from the journaled
    # (prompt, seed, schema) on a FRESH scheduler — byte-identical
    tokr, engr = build()
    ref_sched = ContinuousBatchingScheduler(engr, tokr, prefix_min_tokens=0)
    ref_sched.start()
    try:
        ref = ref_sched.submit(Request(
            prompt="replay probe", max_tokens=800, seed=99,
            response_format=schema_rf,
        ))
        ref_text = ref.future.result(timeout=120)
    finally:
        ref_sched.stop()
    jpath = os.path.join(
        tempfile.gettempdir(), "dllama_structured_bench_journal.bin"
    )
    if os.path.exists(jpath):
        os.unlink(jpath)
    journal = RequestJournal(jpath, progress_every=1, fsync=False)
    tokc, engc = build()
    crash_sched = ContinuousBatchingScheduler(
        engc, tokc, prefix_min_tokens=0, journal=journal
    )
    crash_sched.start()
    try:
        crash = crash_sched.submit(Request(
            prompt="replay probe", max_tokens=800, seed=99,
            response_format=schema_rf,
        ))
        while not crash.generated_tokens:
            time.sleep(0.001)
        journal.flush()
        img = read_journal(jpath)
    finally:
        crash_sched.stop()
        journal.close()
    tok2, eng2 = build()
    sched2 = ContinuousBatchingScheduler(eng2, tok2, prefix_min_tokens=0)
    sched2.start()
    try:
        re_req = sched2.build_recovered_request(img.entries[crash.id])
        sched2.submit(re_req)
        replayed = re_req.future.result(timeout=120)
    finally:
        sched2.stop()
    # all three replay schedulers drain clean too — the crash stand-in's
    # force-cancel journals its finish, so even ITS marks close
    for tag, s in (("ref", ref_sched), ("crash", crash_sched),
                   ("replay", sched2)):
        _drained_report(f"serving_structured_{tag}", s)

    return {
        "phase": "serving_structured",
        "structured_requests": n_requests,
        "structured_constrained": len(constrained),
        "structured_valid_json_rate": round(valid / len(constrained), 4),
        "structured_tok_s": round(
            sum(len(r.generated_tokens) for r in reqs) / wall, 2
        ),
        "structured_schema_compile_ms": round(compile_cold_ms, 2),
        "structured_schema_compile_cached_ms": round(compile_cached_ms, 3),
        "structured_masked_steps_per_dispatch": round(
            stats["grammar_masked_steps"]
            / max(1, stats["pipeline_dispatches"]), 3
        ),
        "structured_grammar_lanes": stats["grammar_lanes"],
        "structured_pipeline_flushes": stats["pipeline_flushes"],
        "structured_fused_steps": stats["fused_steps"],
        "structured_spec_pipelined_steps": stats["spec_pipelined_steps"],
        "structured_replay_identical": bool(
            replayed == ref_text and json.loads(replayed)
        ),
        **drained,
    }


def _phase_serving_fleet(config, small):
    """The fleet gate (ISSUE 12): Poisson SSE traffic through the
    ``dllama-router`` at THREE MockAsyncEngine-backed replicas while one
    replica is SIGTERM-drained and another is KILLED mid-run — the
    measured "millions of users" curve ROADMAP item 4 asks for. Reports:

    - TTFT / TBT percentiles THROUGH the router (the routing + proxy
      overhead is in the number);
    - shed rate (client-visible give-ups; the zero-requests-shed claim:
      must be 0 — replica sheds are retried or migrated, never passed
      through);
    - affinity hit rate (streams landing on their consistent-hash ring
      owner — the prefix-warmth multiplier);
    - migration count + latency (stream break -> first resumed byte),
      and the loss ledger: every completed stream byte-identical to its
      oracle run, 0 lost / 0 duplicated.

    Mock-backed on purpose (the same content_keyed determinism class the
    recovery bench and chaos tests pin): the phase measures the FLEET
    layer — routing, shed handling, migration — not kernel speed, and
    runs identically on any host."""
    import numpy as np

    from distributed_llama_multiusers_tpu.fleet import FleetRouter
    from distributed_llama_multiusers_tpu.runtime.scheduler import (
        ContinuousBatchingScheduler,
    )
    from distributed_llama_multiusers_tpu.serving import StreamRegistry
    from distributed_llama_multiusers_tpu.server import ApiServer
    from distributed_llama_multiusers_tpu.tokenizer import TemplateType
    from distributed_llama_multiusers_tpu.utils.testing import (
        CharStreamTokenizer,
        MockAsyncEngine,
    )
    import json as _json
    import urllib.request

    class _FleetTokenizer(CharStreamTokenizer):
        def decode(self, token):
            return f"[{token}]"

    n_lanes = 2 if small else 4
    n_requests = 12 if small else 32
    max_tokens = 24 if small else 40
    step_s = 0.004

    def make_replica(rid):
        engine = MockAsyncEngine(n_lanes=n_lanes, max_chunk=8,
                                 content_keyed=True, step_s=step_s)
        sched = ContinuousBatchingScheduler(
            engine, _FleetTokenizer(64, max_chars=24),
            speculative=False, prefix_min_tokens=0, multi_step=0,
        )
        sched.start()
        registry = StreamRegistry(grace_s=60.0)
        api = ApiServer(sched, _FleetTokenizer(64, max_chars=24),
                        model_name="fleet",
                        template_type=TemplateType.LLAMA2,
                        resume=registry, replica_id=rid)
        httpd = api.serve(host="127.0.0.1", port=0)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        return {"rid": rid, "sched": sched, "registry": registry,
                "httpd": httpd,
                "base": f"127.0.0.1:{httpd.server_address[1]}"}

    replicas = [make_replica(f"r{i}") for i in range(3)]
    # the 1000-char threshold makes every 4th prompt classify "long"
    # (below): with NO prefill-role replica in this fleet the long class
    # routes monolithic — exercising the disagg policy's fallback under
    # churn — and the TTFT/TBT columns split by length class
    router = FleetRouter(
        {r["rid"]: r["base"] for r in replicas}, scrape_interval_s=0.1,
        long_prompt_chars=1000,
    ).start()
    rhttpd = router.serve(host="127.0.0.1", port=0)
    threading.Thread(target=rhttpd.serve_forever, daemon=True).start()
    router.scrape_once()
    rbase = f"http://127.0.0.1:{rhttpd.server_address[1]}"

    # three shared-system-prompt families: affinity has something to
    # steer, and the hit-rate number means prefix-warmth concentration.
    # Every 4th prompt carries a long tail AFTER the family prefix (the
    # affinity key covers leading blocks only, so the key is unchanged)
    # to populate the long length class.
    def prompt_for(i):
        fam = i % 3
        text = ("family %d system prompt " % fam) * 20 + f"user {i}"
        if i % 4 == 0:
            text += " long-context filler" * 40
        return text

    bodies = [
        {"prompt": prompt_for(i), "max_tokens": max_tokens, "stream": True}
        for i in range(n_requests)
    ]

    # oracle pass: each prompt's uninterrupted text, straight off one
    # replica (content_keyed: the stream is a pure function of prompt
    # content, identical on every replica — the determinism class)
    oracle = {}
    for i, body in enumerate(bodies):
        req = urllib.request.Request(
            f"http://{replicas[0]['base']}/v1/completions",
            data=_json.dumps({**body, "stream": False}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            oracle[i] = _json.loads(resp.read())["generated_text"]

    # the churn: Poisson arrivals, one client thread per stream
    results = {}
    lock = threading.Lock()

    def client(i, body, t_submit):
        req = urllib.request.Request(
            rbase + "/v1/completions", data=_json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        texts, stamps, err, phases = [], [], None, None
        try:
            with urllib.request.urlopen(req, timeout=240) as resp:
                for line in resp:
                    line = line.decode().strip()
                    if not line.startswith("data: ") or line == "data: [DONE]":
                        continue
                    p = _json.loads(line[6:])
                    if "error" in p:
                        err = p.get("reason", "error")
                        continue
                    ch = p.get("choices", [{}])[0]
                    if ch.get("finish_reason") is None:
                        texts.append(ch.get("text", ""))
                        stamps.append(time.perf_counter())
                    else:
                        # terminal chunk: the per-request phases record
                        # (queue/prefill/decode/ITL/migration gap) the
                        # router stamped its gap attribution into
                        s = p.get("summary")
                        if isinstance(s, dict) and isinstance(
                            s.get("phases"), dict
                        ):
                            phases = s["phases"]
        except Exception as e:  # noqa: BLE001 — the ledger records it
            err = f"{type(e).__name__}"
        with lock:
            results[i] = ("".join(texts), stamps, t_submit, err, phases)

    rng = np.random.default_rng(23)
    intervals = rng.exponential(0.04, n_requests)
    threads = []
    t0 = time.perf_counter()
    drained = killed = False
    for i, (body, dt) in enumerate(zip(bodies, intervals)):
        time.sleep(dt)
        th = threading.Thread(
            target=client, args=(i, body, time.perf_counter()),
        )
        th.start()
        threads.append(th)
        if not drained and i >= n_requests // 3:
            # SIGTERM shape on r1: health flips + sheds immediately, a
            # SHORT drain window, then force-cancel of the remainder —
            # exactly what a rolling restart that runs out of patience
            # does. Streams still on r1 must migrate, not die.
            drained = True
            threading.Thread(
                target=lambda: replicas[1]["sched"].drain(timeout=0.3),
                daemon=True,
            ).start()
        if not killed and i >= (2 * n_requests) // 3:
            # replica death on r2: listener closed (new connects get
            # ECONNREFUSED, like a dead process) + abrupt stop with
            # streams mid-flight
            killed = True
            replicas[2]["httpd"].shutdown()
            replicas[2]["httpd"].server_close()
            threading.Thread(
                target=replicas[2]["sched"].stop, daemon=True,
            ).start()
    for th in threads:
        th.join(timeout=300)
    wall = time.perf_counter() - t0

    # the loss ledger: byte-identity against the oracle per stream
    lost = dup = failed = completed = 0
    byte_identical = True
    # latency split by the router's prompt-length class: long prompts
    # are the disagg policy's subject, and their TTFT must be
    # attributable separately from the short traffic's TBT
    ttfts = {"short": [], "long": []}
    tbts = {"short": [], "long": []}
    for i in range(n_requests):
        text, stamps, t_submit, err, _phases = results.get(
            i, ("", [], t0, "no_result", None)
        )
        if err is not None:
            failed += 1
            continue
        completed += 1
        if text != oracle[i]:
            byte_identical = False
            # char-level ledger: missing chars = lost, extras = dup
            if len(text) < len(oracle[i]):
                lost += len(oracle[i]) - len(text)
            else:
                dup += len(text) - len(oracle[i])
        if stamps:
            cls = (
                "long" if len(bodies[i]["prompt"]) >= 1000 else "short"
            )
            ttfts[cls].append((stamps[0] - t_submit) * 1e3)
            tbts[cls].extend(
                (b - a) * 1e3 for a, b in zip(stamps, stamps[1:])
            )

    def pct(vals, q):
        if not vals:
            return None
        s = sorted(vals)
        return round(s[min(len(s) - 1, int(q * len(s)))], 1)

    # per-request phase attribution (telemetry/tracectx.py PHASE_KEYS):
    # the replica-reported records off the terminal chunks, with the
    # router's migration-gap stamp — where each stream's wall time went
    phase_recs = [
        r[4] for r in results.values() if r[3] is None and r[4]
    ]

    def phase_vals(key):
        return [
            float(p[key]) for p in phase_recs
            if isinstance(p.get(key), (int, float))
        ]

    stats = router.handle_stats()
    mig_hist = router.registry.get("dllama_router_migration_seconds")
    mig_p50 = mig_hist.quantile(0.5) if mig_hist.count else None
    # the router-side aggregation of the SAME records: its ttft series
    # must reconcile with the client-collected phases (the histogram is
    # bucket-interpolated — a coarse estimate, reported as such)
    phase_hist = router.registry.get("dllama_request_phase_seconds")
    router_ttft_p95_s = (
        phase_hist.quantile(0.95, phase="ttft_ms")
        if phase_hist is not None else None
    )
    router.close()
    rhttpd.shutdown()
    fleet_drained = True
    for r in replicas:
        try:
            r["httpd"].shutdown()
            r["registry"].close()
            r["sched"].stop()
            _drained_report(f"serving_fleet_{r['rid']}", r["sched"])
        except RuntimeError:
            fleet_drained = False  # a hung stop can't certify its drain
    affinity_routes = max(1, stats["fleet_affinity_routes"])
    return {
        "serving_fleet_replicas": 3,
        "serving_fleet_requests": n_requests,
        "serving_fleet_completed": completed,
        "serving_fleet_failed": failed,
        "serving_fleet_wall_s": round(wall, 2),
        "serving_fleet_ttft_p50_ms": pct(
            ttfts["short"] + ttfts["long"], 0.50
        ),
        "serving_fleet_ttft_p95_ms": pct(
            ttfts["short"] + ttfts["long"], 0.95
        ),
        "serving_fleet_ttft_p99_ms": pct(
            ttfts["short"] + ttfts["long"], 0.99
        ),
        "serving_fleet_tbt_p50_ms": pct(
            tbts["short"] + tbts["long"], 0.50
        ),
        "serving_fleet_tbt_p95_ms": pct(
            tbts["short"] + tbts["long"], 0.95
        ),
        "serving_fleet_tbt_p99_ms": pct(
            tbts["short"] + tbts["long"], 0.99
        ),
        # the length-class split: what disagg routing acts on (long
        # prompts here ride the monolithic fallback — no prefill-role
        # replica in this fleet; serving_disagg measures the split
        # WITH one)
        "serving_fleet_ttft_p95_ms_short": pct(ttfts["short"], 0.95),
        "serving_fleet_ttft_p95_ms_long": pct(ttfts["long"], 0.95),
        "serving_fleet_tbt_p95_ms_short": pct(tbts["short"], 0.95),
        "serving_fleet_tbt_p95_ms_long": pct(tbts["long"], 0.95),
        # the zero-requests-shed claim: replica sheds are retried or
        # migrated by the router; only a total fleet outage reaches the
        # client (must be 0 here — one replica stays healthy)
        "serving_fleet_shed_rate": round(
            stats["router_giveups"] / n_requests, 3
        ),
        "serving_fleet_replica_shed_retries": stats["router_shed_retries"],
        "serving_fleet_affinity_hit_rate": round(
            stats["fleet_affinity_hits"] / affinity_routes, 3
        ),
        "serving_fleet_migrations": stats["router_migrations_ok"],
        "serving_fleet_migrations_failed": stats["router_migrations_failed"],
        "serving_fleet_migration_p50_ms": (
            round(mig_p50 * 1e3, 1) if mig_p50 is not None else None
        ),
        # the loss ledger across a drain AND a kill (chars, not tokens:
        # finer — a partial-token text diff still counts)
        "serving_fleet_lost_chars": lost,
        "serving_fleet_duplicate_chars": dup,
        "serving_fleet_byte_identical": byte_identical,
        # per-replica leak_counts() asserted zero above — the drained
        # replica AND the killed one both released every mirror/page
        "serving_fleet_leaked_resources": 0 if fleet_drained else None,
        # per-phase latency attribution (replica-reported phases records
        # off the terminal chunks + the router's migration-gap stamp):
        # where completed streams' wall time went, phase by phase
        "serving_fleet_phase_records": len(phase_recs),
        "serving_fleet_phase_queue_wait_p95_ms": pct(
            phase_vals("queue_wait_ms"), 0.95
        ),
        "serving_fleet_phase_prefill_p95_ms": pct(
            phase_vals("prefill_ms"), 0.95
        ),
        "serving_fleet_phase_decode_p95_ms": pct(
            phase_vals("decode_ms"), 0.95
        ),
        "serving_fleet_phase_itl_p50_ms": pct(
            phase_vals("itl_p50_ms"), 0.50
        ),
        "serving_fleet_phase_itl_p99_ms": pct(
            phase_vals("itl_p99_ms"), 0.95
        ),
        "serving_fleet_phase_migration_gap_max_ms": round(
            max(phase_vals("migration_gap_ms"), default=0.0), 1
        ),
        # the router-side dllama_request_phase_seconds aggregation of
        # the same records (bucket-interpolated estimate)
        "serving_fleet_router_phase_ttft_p95_ms": (
            round(router_ttft_p95_s * 1e3, 1)
            if router_ttft_p95_s is not None else None
        ),
    }


def _phase_serving_disagg(config, small):
    """The disaggregated-prefill gate (ISSUE 16): a three-replica fleet
    with an explicit **prefill** replica, a **decode** replica and a
    **mixed** replica behind the ``dllama-router`` with prompt-length
    routing on. The phase measures the policy's whole claim:

    - a long-classified prompt routes to the prefill-role replica, its
      KV pages transfer (integrity hashes verified by the importer) and
      adopt refcount-correctly into the decode replica's pool, and the
      client stream hands off char-exact vs the single-replica oracle;
    - decode TBT p95 on co-resident SHORT sessions stays within 10% of
      a no-long-prompt baseline (the DistServe/Splitwise motivation:
      prefill interference off the decode tier);
    - zero device-program compiles after warmup in-phase;
    - killing the prefill replica degrades long traffic to the
      monolithic path (typed, routed, byte-identical) — not a hung
      stream.

    Mock-backed like serving_fleet (the same content-keyed determinism
    class), but the KV POOL IS REAL: adoption, refcounts, parking and
    the integrity hashes run the shipping ``runtime/kvpool.py`` +
    ``disagg/kvtransfer.py`` code on every host."""
    import numpy as np

    from distributed_llama_multiusers_tpu.fleet import FleetRouter
    from distributed_llama_multiusers_tpu.runtime.scheduler import (
        ContinuousBatchingScheduler,
    )
    from distributed_llama_multiusers_tpu.serving import StreamRegistry
    from distributed_llama_multiusers_tpu.server import ApiServer
    from distributed_llama_multiusers_tpu.tokenizer import TemplateType
    from distributed_llama_multiusers_tpu.utils.testing import (
        CharStreamTokenizer,
        MockAsyncEngine,
    )
    import json as _json
    import urllib.request

    class _DisaggTokenizer(CharStreamTokenizer):
        def decode(self, token):
            return f"[{token}]"

    n_lanes = 2 if small else 4
    n_short = 8 if small else 20
    max_tokens = 16 if small else 32
    step_s = 0.004
    page = 16
    # 160 prompt tokens = 10 full pool blocks: enough chain for the
    # transfer to mean something, small enough for a CPU smoke
    max_chars = 160
    long_chars = 1000  # the router threshold for THIS phase

    def make_tok():
        return _DisaggTokenizer(64, max_chars=max_chars)

    def make_replica(rid, role):
        engine = MockAsyncEngine(
            n_lanes=n_lanes, max_chunk=8, content_keyed=True,
            step_s=step_s, paged=True, kv_page_size=page,
            kv_pool_pages=256, kv_max_parked=64,
        )
        sched = ContinuousBatchingScheduler(
            engine, make_tok(), speculative=False,
            prefix_min_tokens=page, multi_step=0,
        )
        sched.start()
        registry = StreamRegistry(grace_s=60.0)
        api = ApiServer(sched, make_tok(), model_name="disagg",
                        template_type=TemplateType.LLAMA2,
                        resume=registry, replica_id=rid, role=role)
        httpd = api.serve(host="127.0.0.1", port=0)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        return {"rid": rid, "role": role, "engine": engine,
                "sched": sched, "registry": registry, "httpd": httpd,
                "base": f"127.0.0.1:{httpd.server_address[1]}"}

    replicas = [
        make_replica("p0", "prefill"),
        make_replica("d0", "decode"),
        make_replica("m0", "mixed"),
    ]
    router = FleetRouter(
        {r["rid"]: r["base"] for r in replicas}, scrape_interval_s=0.1,
        long_prompt_chars=long_chars,
    ).start()
    rhttpd = router.serve(host="127.0.0.1", port=0)
    threading.Thread(target=rhttpd.serve_forever, daemon=True).start()
    router.scrape_once()
    rbase = f"http://127.0.0.1:{rhttpd.server_address[1]}"

    # prompts: shorts stay under one affinity block (keyless, least-
    # loaded — today's path); longs clear the router threshold by chars
    # (the tokenizer caps TOKENS, the classifier reads the raw text)
    long_a = "analyse this corpus properly: " + "lorem ipsum filler " * 60
    long_b = "second long corpus to survive: " + "dolor sit amet pad " * 60
    assert min(len(long_a), len(long_b)) >= long_chars
    shorts_a = [f"baseline question {i} topic {i % 5}" for i in range(n_short)]
    shorts_b = [f"coresident question {i} topic {i % 5}" for i in range(n_short)]

    # oracle pass — every prompt's uninterrupted text off ONE replica
    # (content-keyed: identical on all three), BEFORE any churn and
    # before the prefill replica is killed for the fallback leg
    def oracle_for(prompt, mt):
        req = urllib.request.Request(
            f"http://{replicas[0]['base']}/v1/completions",
            data=_json.dumps({"prompt": prompt, "max_tokens": mt,
                              "stream": False}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            return _json.loads(resp.read())["generated_text"]

    oracle = {p: oracle_for(p, max_tokens)
              for p in [long_a, long_b, *shorts_a, *shorts_b]}

    results = {}
    lock = threading.Lock()

    def client(tag, prompt, t_submit):
        req = urllib.request.Request(
            rbase + "/v1/completions",
            data=_json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                              "stream": True}).encode(),
            headers={"Content-Type": "application/json"},
        )
        texts, stamps, err, served_by, phases = [], [], None, None, None
        try:
            with urllib.request.urlopen(req, timeout=240) as resp:
                served_by = resp.headers.get("X-DLlama-Replica")
                for line in resp:
                    line = line.decode().strip()
                    if not line.startswith("data: ") or line == "data: [DONE]":
                        continue
                    p = _json.loads(line[6:])
                    if "error" in p:
                        err = p.get("reason", "error")
                        continue
                    ch = p.get("choices", [{}])[0]
                    if ch.get("finish_reason") is None:
                        texts.append(ch.get("text", ""))
                        stamps.append(time.perf_counter())
                    else:
                        # terminal chunk: the per-request phases record
                        # (the hand-off's decode side reports it for the
                        # long stream)
                        s = p.get("summary")
                        if isinstance(s, dict) and isinstance(
                            s.get("phases"), dict
                        ):
                            phases = s["phases"]
        except Exception as e:  # noqa: BLE001 — the ledger records it
            err = f"{type(e).__name__}"
        with lock:
            results[tag] = ("".join(texts), stamps, t_submit, err,
                            served_by, phases)

    rng = np.random.default_rng(31)

    def run_wave(tagged_prompts):
        threads = []
        for (tag, prompt), dt in zip(
            tagged_prompts, rng.exponential(0.03, len(tagged_prompts))
        ):
            time.sleep(dt)
            th = threading.Thread(
                target=client, args=(tag, prompt, time.perf_counter()),
            )
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=300)

    def tbts_of(tags):
        out = []
        for tag in tags:
            _, stamps, _, err, _, _ = results[tag]
            if err is None:
                out.extend(
                    (b - a) * 1e3 for a, b in zip(stamps, stamps[1:])
                )
        return out

    def pct(vals, q):
        if not vals:
            return None
        s = sorted(vals)
        return round(s[min(len(s) - 1, int(q * len(s)))], 1)

    # wave A — the no-long-prompt baseline for short-session decode TBT
    run_wave([(f"a{i}", p) for i, p in enumerate(shorts_a)])
    tbt_base_p95 = pct(tbts_of([f"a{i}" for i in range(n_short)]), 0.95)

    # wave B — the measured regime: one long prompt CO-RESIDENT with the
    # short traffic; the router steers it to p0, hands it to d0 at first
    # token, and the shorts' TBT must not notice
    run_wave([("long", long_a)]
             + [(f"b{i}", p) for i, p in enumerate(shorts_b)])
    tbt_co_p95 = pct(tbts_of([f"b{i}" for i in range(n_short)]), 0.95)

    long_text, long_stamps, long_t0, long_err, long_served, long_phases = (
        results["long"]
    )
    assert long_err is None, f"long stream failed: {long_err}"
    # acceptance: the long prompt ROUTED to the prefill-role replica
    assert long_served == "p0", (
        f"long prompt served by {long_served!r}, want prefill replica p0"
    )
    # acceptance: char-exact across the hand-off vs the oracle
    assert long_text == oracle[long_a], (
        f"hand-off stream diverged: {len(long_text)} chars vs "
        f"{len(oracle[long_a])} oracle chars"
    )
    short_ok = sum(
        1 for i in range(n_short)
        if results[f"b{i}"][3] is None
        and results[f"b{i}"][0] == oracle[shorts_b[i]]
    )
    assert short_ok == n_short, (
        f"only {short_ok}/{n_short} co-resident shorts byte-identical"
    )
    stats = router.handle_stats()
    # acceptance: pages genuinely transferred + adopted (receipt counts
    # come from the DESTINATION pool's real bookkeeping)
    assert stats["router_disagg_handoffs_ok"] >= 1, stats
    assert stats["router_disagg_pages_fresh"] >= 1, stats
    d0 = replicas[1]
    d0_pool = d0["engine"].kvpool.stats()
    assert d0_pool["pool_adopts"] >= 1, d0_pool
    assert d0["engine"].pages_imported >= 1
    # acceptance: decode TBT p95 within 10% of baseline (+2ms noise
    # floor: mock steps are 4ms, thread-scheduling jitter on a shared
    # CI host must not fail the gate the policy passed)
    assert tbt_co_p95 <= tbt_base_p95 * 1.10 + 2.0, (
        f"co-resident short TBT p95 {tbt_co_p95}ms vs "
        f"baseline {tbt_base_p95}ms"
    )
    # acceptance: compile stability in-phase, every replica
    for r in replicas:
        snap = r["engine"].stats.snapshot()
        assert snap["jit_compiles_after_warmup"] == 0, (r["rid"], snap)

    # fallback leg — kill the PREFILL replica, then send another long
    # prompt: with no prefill-role replica eligible the router routes it
    # monolithic (typed, still byte-identical), never a hung stream
    replicas[0]["httpd"].shutdown()
    replicas[0]["httpd"].server_close()
    threading.Thread(target=replicas[0]["sched"].stop, daemon=True).start()
    router.scrape_once()
    run_wave([("long_fb", long_b)])
    fb_text, _, _, fb_err, fb_served, _fb_phases = results["long_fb"]
    assert fb_err is None, f"post-kill long stream failed: {fb_err}"
    assert fb_served in ("d0", "m0"), fb_served
    assert fb_text == oracle[long_b], "monolithic fallback diverged"

    hand_hist = router.registry.get("dllama_router_disagg_handoff_seconds")
    hand_p50 = hand_hist.quantile(0.5) if hand_hist.count else None
    phase_hist = router.registry.get("dllama_request_phase_seconds")
    router_ttft_p95_s = (
        phase_hist.quantile(0.95, phase="ttft_ms")
        if phase_hist is not None else None
    )
    router.close()
    rhttpd.shutdown()
    for r in replicas[1:]:
        try:
            r["httpd"].shutdown()
            r["registry"].close()
            r["sched"].stop()
            # the decode replica ADOPTED transferred pages mid-phase: its
            # pool must still drain to zero lane-held pages (adopted
            # pages park or free with their session like native ones)
            _drained_report(f"serving_disagg_{r['rid']}", r["sched"])
        except RuntimeError:
            pass
    long_ttft_ms = (
        round((long_stamps[0] - long_t0) * 1e3, 1) if long_stamps else None
    )
    # fleet-wide latency attribution: client-observed TTFT/ITL over every
    # successful stream, plus the per-request phases records the replicas
    # attached to their terminal chunks (satellite of the tracing PR)
    ttfts = [
        (r[1][0] - r[2]) * 1e3
        for r in results.values() if r[3] is None and r[1]
    ]
    itls = tbts_of([t for t in results if results[t][3] is None])
    phase_recs = [
        r[5] for r in results.values() if r[3] is None and r[5]
    ]

    def phase_vals(key):
        return [
            float(p[key]) for p in phase_recs
            if isinstance(p.get(key), (int, float))
        ]

    return {
        "serving_disagg_replicas": 3,
        "serving_disagg_short_requests": 2 * n_short,
        "serving_disagg_long_requests": 2,
        "serving_disagg_long_routed_to": long_served,
        "serving_disagg_long_ttft_ms": long_ttft_ms,
        "serving_disagg_handoffs_ok": stats["router_disagg_handoffs_ok"],
        "serving_disagg_fallbacks": stats["router_disagg_fallbacks"],
        "serving_disagg_pages_moved": stats["router_disagg_pages_moved"],
        "serving_disagg_pages_fresh": stats["router_disagg_pages_fresh"],
        "serving_disagg_handoff_p50_ms": (
            round(hand_p50 * 1e3, 1) if hand_p50 is not None else None
        ),
        "serving_disagg_decode_adopts": d0_pool["pool_adopts"],
        "serving_disagg_decode_pages_imported": d0["engine"].pages_imported,
        "serving_disagg_tbt_p95_ms_baseline": tbt_base_p95,
        "serving_disagg_tbt_p95_ms_coresident": tbt_co_p95,
        "serving_disagg_tbt_ratio": (
            round(tbt_co_p95 / tbt_base_p95, 3)
            if tbt_base_p95 else None
        ),
        "serving_disagg_ttft_p50_ms": pct(ttfts, 0.50),
        "serving_disagg_ttft_p95_ms": pct(ttfts, 0.95),
        "serving_disagg_ttft_p99_ms": pct(ttfts, 0.99),
        "serving_disagg_itl_p50_ms": pct(itls, 0.50),
        "serving_disagg_itl_p95_ms": pct(itls, 0.95),
        "serving_disagg_itl_p99_ms": pct(itls, 0.99),
        "serving_disagg_phase_records": len(phase_recs),
        "serving_disagg_phase_prefill_p95_ms": pct(
            phase_vals("prefill_ms"), 0.95
        ),
        "serving_disagg_phase_decode_p95_ms": pct(
            phase_vals("decode_ms"), 0.95
        ),
        "serving_disagg_phase_queue_wait_p95_ms": pct(
            phase_vals("queue_wait_ms"), 0.95
        ),
        "serving_disagg_phase_swap_in_max_ms": round(
            max(phase_vals("swap_in_ms"), default=0.0), 1
        ),
        "serving_disagg_phase_migration_gap_max_ms": round(
            max(phase_vals("migration_gap_ms"), default=0.0), 1
        ),
        "serving_disagg_router_phase_ttft_p95_ms": (
            round(router_ttft_p95_s * 1e3, 1)
            if router_ttft_p95_s is not None else None
        ),
        "serving_disagg_byte_identical": True,  # asserted above
        "serving_disagg_monolithic_fallback_ok": True,  # asserted above
        "serving_disagg_compiles_after_warmup": 0,  # asserted above
        "serving_disagg_leaked_resources": 0,  # asserted per replica above
    }


def _pipeline_microbench(n_requests=4, max_tokens=48):
    """Drive the REAL scheduler loop over the mocked async engine
    (utils.testing.MockAsyncEngine — the same stub the pinned tests in
    tests/test_pipelined_decode.py use, so bench evidence and tests cannot
    drift) and read back the overlap evidence: in steady-state decode the
    consume of step k must happen after step k+1's dispatch (one-step
    lag), with zero chain aborts. Deterministic on any host — the CPU
    fallback's real-engine timings are too noisy to prove overlap."""
    from distributed_llama_multiusers_tpu.runtime.scheduler import (
        ContinuousBatchingScheduler,
        Request,
    )
    from distributed_llama_multiusers_tpu.utils.testing import (
        MockAsyncEngine,
        StubStreamTokenizer,
    )

    engine = MockAsyncEngine()
    sched = ContinuousBatchingScheduler(
        engine, StubStreamTokenizer(engine.config.vocab_size),
        speculative=False, prefix_min_tokens=0, multi_step=0,
    )
    reqs = [
        Request(prompt="microbench", max_tokens=max_tokens, temperature=0.0)
        for _ in range(n_requests)
    ]
    sched.start()
    try:
        for r in reqs:
            sched.submit(r)
        for r in reqs:
            r.future.result(timeout=60)
    finally:
        sched.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    consumed, overlapped = engine.count_overlapped_consumes()
    stats = engine.stats.snapshot()
    return {
        "pipeline_microbench_steps": consumed,
        "pipeline_microbench_overlapped_consumes": overlapped,
        "pipeline_microbench_flushes": stats["pipeline_flushes"],
        "pipeline_microbench_overlap_s": round(stats["overlap_s"], 4),
    }


def _pipeline_microbench_safe() -> dict:
    try:
        return _pipeline_microbench()
    except Exception as e:  # noqa: BLE001 — evidence, not the headline
        return {"pipeline_microbench_error": f"{type(e).__name__}: {e}"[:200]}


def _phase_ablations(config, small):
    import jax
    import jax.numpy as jnp

    from distributed_llama_multiusers_tpu.ops import linear

    n_short, n_long = (4, 16) if small else (16, 128)
    out = {}
    params_q = _resident_packed_params(config)
    linear.set_pallas_enabled(False)
    try:
        out["ablation_xla_dequant_tok_s"] = round(
            _bench_decode(config, params_q, n_short, n_long, tag="packed+xla-dequant"), 2
        )
    finally:
        linear.set_pallas_enabled(True)
    # f32 dequantized-weight planes (multi-pass f32 MXU semantics — what the
    # pre-round-4 "exact" default cost; bf16 planes are now the TPU default
    # since f32 dot operands round to bf16 MXU passes anyway)
    linear.set_pallas_w_dtype(jnp.float32)
    try:
        out["ablation_pallas_f32w_tok_s"] = round(
            _bench_decode(config, params_q, n_short, n_long, tag="packed+pallas-f32w"), 2
        )
    finally:
        linear.set_pallas_w_dtype(None)
    del params_q
    params_d = _resident_dense_params(config, seed=0, dtype=jnp.bfloat16)
    out["ablation_dense_bf16_tok_s"] = round(
        _bench_decode(config, params_d, n_short, n_long, tag="dense-bf16"), 2
    )
    return out


def _phase_8b(platform):
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    if platform != "tpu":
        return {"llama31_8b_q40_decode_tok_s": None,
                "llama31_8b_note": f"skipped off-TPU ({platform})"}
    cfg8 = LlamaConfig(
        dim=4096, hidden_dim=14336, n_layers=32, n_heads=32, n_kv_heads=8,
        vocab_size=128256, seq_len=2048, rope_theta=500000.0,
        rope_scaling_factor=8.0, rope_scaling_low_freq_factor=1.0,
        rope_scaling_high_freq_factor=4.0, rope_scaling_orig_max_seq_len=8192,
    )
    import jax

    t0 = time.perf_counter()
    params8 = _resident_packed_params(cfg8)
    print(f"[bench] 8B packed params resident in {time.perf_counter()-t0:.1f}s "
          f"({_tree_device_bytes(params8)/1e9:.2f} GB)", file=sys.stderr, flush=True)
    tok8 = _bench_decode(cfg8, params8, 8, 64, reps=2, tag="8b packed+pallas")
    return {
        "llama31_8b_q40_decode_tok_s": round(tok8, 2),
        "llama31_8b_northstar_frac": round(tok8 / 200.0, 3),
    }


def _phase_longctx(config, small):
    """Decode throughput at FULL context: every step's attention reads the
    whole KV cache (the long-context serving regime; reference analogue:
    macbeth.sh's cache-filling generation). Measured with the bf16 KV
    default AND --kv-dtype f8 — at long context the KV read is marginal
    traffic alongside the weights, so f8 is a bandwidth lever there, not
    just a capacity one. Cache CONTENTS are irrelevant to bandwidth, so
    the cache starts zeroed at a high position (no prefill cost)."""
    import jax
    import jax.numpy as jnp

    n_short, n_long = (8, 16) if small else (16, 64)
    start = config.seq_len - n_long - 1
    params = _resident_packed_params(config)
    out = {"longctx_context": start, "longctx_steps": n_long}

    for name, dtype in (("bf16", jnp.bfloat16), ("f8", jnp.float8_e4m3fn)):
        tok_s = _bench_decode(
            config, params, n_short, n_long, reps=2,
            tag=f"longctx-{name}kv", start_pos=start, cache_dtype=dtype,
        )
        out[f"longctx_decode_tok_s_{name}kv"] = round(tok_s, 2)
    return out


def _phase_parity(config, platform):
    """BASELINE.md's token-identity gate, measured with the SHIPPING TPU
    dtype: greedy-decode 256 tokens with the default bf16-dot kernel and
    with the exact-f32 XLA dequant path (set_pallas_enabled(False); both
    streams on f32 activations), same synthetic Q40 weights, and report
    whether the streams are token-identical — plus the first divergence
    step if not. Random weights have near-zero logit margins, so a
    divergence here is the worst case, not the real-model rate; the
    interpret-mode CI test (tests/test_pallas_q40.py) pins model-scale
    identity."""
    if platform != "tpu":
        return {"token_parity_bf16": None,
                "parity_note": f"skipped off-TPU ({platform})"}
    import jax
    import jax.numpy as jnp

    import numpy as np

    from distributed_llama_multiusers_tpu.ops import linear
    from distributed_llama_multiusers_tpu.runtime import InferenceEngine

    # f32 embedding -> f32 activations in BOTH streams: the comparison then
    # isolates exactly the shipping kernel's bf16 dot (which casts x down
    # internally) against full-f32 math, instead of confounding it with
    # bf16 activations everywhere else
    params = _device_packed_params(config, seed=0, dtype=jnp.float32)
    prompt = list(range(1, 17))
    n = 256
    streams = {}
    # exact-f32 oracle = the XLA dequant path (unpack + f32 matmul), NOT
    # set_pallas_w_dtype(f32): the multi-pass f32 Pallas compile blew the
    # phase budget on hardware (round 5: >300 s). The XLA path is the same
    # math at ordinary compile cost and is independently pinned against the
    # numpy oracle in CI.
    def greedy_multi(engine, n_tokens):
        """Greedy rollout in multi-step horizons: n/8 dispatches instead
        of n (per-step host round trips blew this phase's budget in
        round 5)."""
        _, g0, pos = engine.prefill(0, prompt)
        out = [int(g0)]
        toks = np.asarray([g0], np.int32)
        poss = np.asarray([pos], np.int32)
        while len(out) < n_tokens:
            # always h=8: a shorter final horizon would compile a SECOND
            # full-model scan program (decode_multi caches per h) in the
            # budget-tightest phase; overshot tokens are just trimmed
            chosen = engine.decode_multi(toks, poss, h=8)
            out.extend(int(chosen[j, 0]) for j in range(chosen.shape[0]))
            toks = chosen[-1].astype(np.int32)
            poss = poss + chosen.shape[0]
        return out[:n_tokens]

    for name, enabled in (("bf16", True), ("f32", False)):
        linear.set_pallas_enabled(enabled)
        try:
            engine = InferenceEngine(
                config, params, n_lanes=1, prefill_buckets=(16,)
            )
            streams[name] = greedy_multi(engine, n)
        finally:
            linear.set_pallas_enabled(True)
        del engine
    mism = [i for i, (a, b) in enumerate(zip(streams["bf16"], streams["f32"]))
            if a != b]
    return {
        "token_parity_bf16": not mism,
        "parity_tokens": n,
        "parity_first_divergence": mism[0] if mism else None,
        "parity_divergent_steps": len(mism),
    }


def child_main() -> None:
    # the parent's timeout sends SIGTERM; without a handler the default
    # disposition kills the process as abruptly as SIGKILL (no finally
    # blocks, no PJRT teardown) and the graceful-shutdown grace period in
    # _run_child buys nothing. SystemExit unwinds the stack so the device
    # client closes cleanly instead of dying mid-call.
    import signal

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    # BENCH_FORCE_CPU=1 is the one way onto the CPU. The pod_serving smoke
    # needs the 8-virtual-device mesh (the tests' standard TP fixture);
    # every other phase runs single-device.
    force_cpu = os.environ.get("BENCH_FORCE_CPU") == "1"
    if force_cpu:
        from distributed_llama_multiusers_tpu.utils.testing import force_cpu_mesh

        force_cpu_mesh(
            n_devices=8
            if os.environ.get("BENCH_PHASE") == "pod_serving"
            else 1
        )

    import jax

    from __graft_entry__ import _flagship_config
    from distributed_llama_multiusers_tpu.app.runtime_setup import (
        enable_compilation_cache,
    )

    # phase children build many identical programs (primary retries, the
    # parity phase's two engines, serving warmup, longctx variants): the
    # persistent cache makes every repeat compile near-instant
    enable_compilation_cache()

    phase = os.environ.get("BENCH_PHASE", "primary")
    dev = jax.devices()[0]
    platform = dev.platform
    device_kind = getattr(dev, "device_kind", platform)
    print(f"[bench] backend up: {platform} ({device_kind}) phase={phase}",
          file=sys.stderr, flush=True)

    if platform != "tpu" and not force_cpu:
        raise SystemExit(
            f"[bench] no TPU: JAX came up on {platform!r} ({device_kind}). "
            "A benchmark number comes from the chip; BENCH_FORCE_CPU=1 asks "
            "for a CPU smoke of the phase logic explicitly."
        )
    small = os.environ.get("GRAFT_SMALL") == "1"
    config = _flagship_config(small=small)

    if phase == "primary":
        result = _phase_primary(config, platform, device_kind, small)
    elif phase == "serving":
        result = _phase_serving(config, small)
    elif phase == "serving_churn":
        result = _phase_serving_churn(config, small)
    elif phase == "serving_prefix":
        result = _phase_serving_prefix(config, small)
    elif phase == "pod_serving":
        result = _phase_pod_serving(config, small)
    elif phase == "serving_faults":
        result = _phase_serving_faults(config, small)
    elif phase == "serving_recovery":
        result = _phase_serving_recovery(config, small)
    elif phase == "serving_fleet":
        result = _phase_serving_fleet(config, small)
    elif phase == "serving_structured":
        result = _phase_serving_structured(config, small)
    elif phase == "serving_disagg":
        result = _phase_serving_disagg(config, small)
    elif phase == "ablations":
        result = _phase_ablations(config, small)
    elif phase == "8b":
        result = _phase_8b(platform)
    elif phase == "parity":
        result = _phase_parity(config, platform)
    elif phase == "longctx":
        result = _phase_longctx(config, small)
    else:
        raise ValueError(f"unknown BENCH_PHASE {phase!r}")
    # Every phase result carries the resolved dequant mode (and, under
    # auto, the selection-table provenance + per-site resolutions) next to
    # its tok/s numbers, so result lines are self-describing.
    from distributed_llama_multiusers_tpu.ops.dequant_select import bench_stamp

    result.update(bench_stamp(phase))
    print(json.dumps(result), flush=True)


# ---------------------------------------------------------------------------
# Parent: watchdog. Phase children with own timeouts; cumulative artifact
# re-printed after every phase; diagnostic JSON + exit 1 when the primary
# phase never lands (no chip, no number). Never imports JAX: each child is
# the one process holding the chip while it lives.
# ---------------------------------------------------------------------------


def _text(x) -> str:
    if isinstance(x, bytes):
        return x.decode(errors="replace")
    return x or ""


def _last_json_line(out: str):
    for line in reversed(out.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def _run_child(env_extra: dict, timeout_s: float):
    env = dict(os.environ)
    env["BENCH_CHILD"] = "1"
    env.update(env_extra)
    # Popen + SIGTERM-then-SIGKILL, NOT subprocess.run(timeout=...): run()
    # SIGKILLs on timeout, and a child killed mid-device-call can leave the
    # chip held. A TERMed child unwinds the Python/PJRT stack and releases
    # the device cleanly; 20 s grace before the hard kill.
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
    )
    timed_out = False
    try:
        stdout, stderr = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        timed_out = True
        proc.terminate()
        try:
            stdout, stderr = proc.communicate(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
            stdout, stderr = proc.communicate()
    except BaseException:
        # subprocess.run killed the child on ANY exception; keep that
        # guarantee (e.g. KeyboardInterrupt mid-communicate) — an orphaned
        # child would keep holding the chip
        proc.terminate()
        try:
            proc.communicate(timeout=20)
        except Exception:
            proc.kill()
        raise
    if timed_out:
        parsed = _last_json_line(_text(stdout))
        if parsed is not None:
            return parsed, None
        err = f"timeout after {timeout_s:.0f}s; stderr tail: {_text(stderr)[-300:]}"
        if "[bench] backend up" not in _text(stderr):
            # the backend never came up: retrying burns the whole
            # deadline on another hang
            err = "NO_BACKEND " + err
        return None, err
    parsed = _last_json_line(_text(stdout))
    if parsed is not None:
        if proc.returncode != 0:
            parsed.setdefault("phase_rc", proc.returncode)
        return parsed, None
    return None, f"rc={proc.returncode}; stderr tail: {_text(stderr)[-400:]}"


def main() -> None:
    # the driver's outer limit killed round 3 at 1500 s with nothing parsed;
    # keep the WHOLE watchdog comfortably under it
    deadline = time.monotonic() + float(os.environ.get("BENCH_DEADLINE", "1260"))
    errors: list[str] = []
    merged: dict | None = None

    def bank(update: dict) -> None:
        nonlocal merged
        if merged is None:
            merged = dict(update)
        else:
            merged.update(update)
        print(json.dumps(merged), flush=True)  # driver parses the LAST line

    # -- primary metric first, retried: nothing else runs until it banks ----
    for attempt in range(2):
        # 420 s is generous for the primary phase alone (~90 s observed on
        # hardware incl. param gen); capping it keeps a hung backend from
        # eating the whole deadline
        budget = min(420.0, deadline - time.monotonic())
        if budget < 120:
            break
        result, err = _run_child({"BENCH_PHASE": "primary"}, budget)
        if result is not None:
            result["attempts"] = attempt + 1
            bank(result)
            break
        errors.append(f"primary[{attempt}]: {err}")
        print(f"[bench-watchdog] {errors[-1]}", file=sys.stderr, flush=True)
        if err and err.startswith("NO_BACKEND"):
            break  # no backend: a retry would only hang again
        if attempt < 1:
            time.sleep(15)

    if merged is None:
        # no chip, no number: a CPU run under the device metric's name would
        # be worse than none (BENCH_FORCE_CPU=1 in the environment is the
        # explicit way to smoke the phases on the CPU — children inherit it)
        print(json.dumps({
            "metric": METRIC, "value": None, "unit": "tok/s",
            "vs_baseline": None, "error": "; ".join(errors)[-1200:],
        }))
        raise SystemExit(1)

    # -- extras, each sandboxed in its own child + timeout ------------------
    # priority order under a shared deadline = the round-4 verdict's:
    # serving numbers, the 8B north star, then the ablation diagnostics.
    # parity runs LAST (after the sweep): it is the phase most likely to
    # blow its budget (two fresh engine compiles + 512 host-stepped
    # decodes) — order so an overrun there costs nothing.
    for phase, cap in (
        ("serving", 420.0), ("serving_churn", 300.0),
        ("serving_prefix", 240.0), ("pod_serving", 300.0),
        ("serving_faults", 240.0), ("serving_recovery", 240.0),
        ("serving_fleet", 240.0), ("serving_structured", 240.0),
        ("serving_disagg", 240.0),
        ("8b", 500.0), ("ablations", 420.0), ("longctx", 300.0),
    ):
        budget = min(cap, deadline - time.monotonic() - 10)
        if budget < 90:
            errors.append(f"{phase}: skipped (out of budget)")
            continue
        result, err = _run_child({"BENCH_PHASE": phase}, budget)
        if result is not None:
            bank(result)
        else:
            errors.append(f"{phase}: {err}")
            print(f"[bench-watchdog] {errors[-1]}", file=sys.stderr, flush=True)

    # -- kernel-knob sweep, TPU only: A/B the slab kernel's DMA geometry ----
    # (round-4 verdict #1: the sweep harness existed but never produced a
    # datapoint; running it inside the bench banks the A/B). Each combo is
    # a fresh primary child (the knobs are read at module import); if one
    # beats the default headline by >2%, the headline adopts it and records
    # the knobs.
    if merged.get("platform") == "tpu":
        from distributed_llama_multiusers_tpu.ops.pallas_q40 import (
            DEFAULT_COMBO,
            DEQUANT_MODES,
            SWEEP_COMBOS,
        )

        backend_lost = False
        sweep: dict = {}
        # dequant-arithmetic variants FIRST (the round-5 hypothesis: the
        # kernel is VPU-bound on the dequant chain, so arithmetic beats DMA
        # geometry as the lever), then the DMA geometry combos
        candidates = [
            (f"dequant_{m}", {"DLLAMA_DEQUANT": m})
            for m in DEQUANT_MODES if m != "v4"
        ] + [
            # the round-2 kernel's narrow-tile layout (512-lane blocks,
            # ~256 KB chunks) measured hbm_util 0.438 where the full-width
            # slab measured 0.259 — reproduce it as a geometry candidate
            ("r02_narrow512", {
                "DLLAMA_W_MAX": "512",
                "DLLAMA_SINGLE_SLAB": "262144",
                "DLLAMA_TARGET_BLOCK": "262144",
            }),
        ] + [
            # geometry largest-first: the whole-plane single-DMA combo is
            # the most distinct datapoint, the near-default ones the least
            (n, {"DLLAMA_SINGLE_SLAB": str(s), "DLLAMA_TARGET_BLOCK": str(b)})
            for n, (s, b) in reversed(list(SWEEP_COMBOS.items()))
            if n != DEFAULT_COMBO
        ]
        combos = candidates[:7]
        for n, _ in candidates[7:]:  # no silent caps
            errors.append(f"sweep[{n}]: skipped (combo cap)")
        best_env: dict = {}
        for name, env in combos:
            budget = min(300.0, deadline - time.monotonic() - 10)
            if budget < 90:
                errors.append("sweep: skipped (out of budget)")
                break
            result, err = _run_child({"BENCH_PHASE": "primary", **env}, budget)
            if result is not None and result.get("value"):
                sweep[name] = {
                    k: result.get(k)
                    for k in ("value", "hbm_util", "weight_read_gb_s")
                }
                if result["value"] > (merged.get("value") or 0) * 1.02:
                    merged.update({
                        k: result[k]
                        for k in ("value", "hbm_util", "weight_read_gb_s", "mfu")
                        if k in result
                    })
                    merged["kernel_knobs"] = name
                    best_env = env
                    if name.startswith("dequant_"):
                        # a measured dequant win feeds the persisted
                        # selection table so DLLAMA_DEQUANT=auto serves it
                        # from the next warmup on (primary measures decode,
                        # so the row lands in the decode m-class)
                        try:
                            from distributed_llama_multiusers_tpu.ops import (
                                dequant_select,
                            )

                            dequant_select.record_win(
                                "*", "*", "decode", name[len("dequant_"):],
                                source="bench.py in-bench sweep (primary A/B"
                                f", {merged.get('device_kind') or 'tpu'})",
                            )
                        except Exception as exc:  # table update is advisory
                            errors.append(f"sweep[{name}]: record_win: {exc}")
                    # keep the headline ratio consistent with the adopted
                    # value (the 8b matched-model overwrite below may still
                    # supersede it)
                    merged["vs_baseline"] = round(
                        result["value"] / REFERENCE_SINGLE_DEVICE_TOK_S, 2
                    )
            else:
                errors.append(f"sweep[{name}]: {err}")
                if err and err.startswith("NO_BACKEND"):
                    backend_lost = True
                    break  # backend lost mid-sweep: stop burning budget
        if sweep:
            bank({"kernel_sweep": sweep})

        # pod serving under the ADOPTED kernel knobs (if the sweep found a
        # winner): one unattended pass banks the kernel A/B AND the pod
        # number for the same configuration — no second run needed to
        # connect them
        if best_env and not backend_lost:
            budget = min(300.0, deadline - time.monotonic() - 10)
            if budget >= 90:
                result, err = _run_child(
                    {"BENCH_PHASE": "pod_serving", **best_env}, budget
                )
                if result is not None:
                    bank({"pod_serving_swept": {
                        **result, "knobs": merged.get("kernel_knobs"),
                    }})
                else:
                    errors.append(f"pod_serving_swept: {err}")
            else:
                errors.append("pod_serving_swept: skipped (out of budget)")

        # parity last — see the phase-order comment above. It runs under
        # the ADOPTED sweep knobs (if any), so the token-identity gate
        # describes the same configuration as the headline number
        budget = min(300.0, deadline - time.monotonic() - 10)
        if backend_lost:
            errors.append("parity: skipped (backend lost mid-sweep)")
        elif budget >= 90:
            result, err = _run_child(
                {"BENCH_PHASE": "parity", **best_env}, budget
            )
            if result is not None:
                if best_env:
                    result["parity_knobs"] = merged.get("kernel_knobs")
                bank(result)
            else:
                errors.append(f"parity: {err}")
        else:
            errors.append("parity: skipped (out of budget)")
    else:
        errors.append("parity: skipped (off-TPU)")

    # matched-model headline ratio: once the 8B north star lands on TPU,
    # compare it (not the 1B primary) against the reference's published 7B
    # number — the closest model-for-model comparison available
    eight_b = merged.get("llama31_8b_q40_decode_tok_s")
    if eight_b and merged.get("platform") == "tpu":
        merged["vs_baseline"] = round(eight_b / REFERENCE_SINGLE_DEVICE_TOK_S, 2)
        merged["vs_baseline_model"] = (
            "llama31_8b_q40 (this, 1 TPU chip) vs llama2_7b_q40 "
            "(reference, 1x RPi 4B, report.pdf Fig.3)"
        )

    if errors:
        merged["phase_errors"] = "; ".join(errors)[-600:]
    print(json.dumps(merged), flush=True)


if __name__ == "__main__":
    if os.environ.get("BENCH_CHILD") == "1":
        child_main()
    else:
        main()
