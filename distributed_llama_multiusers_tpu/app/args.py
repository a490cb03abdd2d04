"""CLI argument surface — flag-compatible with the reference's hand-rolled
parser (src/app.cpp:33-146), reinterpreted for TPU where needed:

--workers      reference: space-separated worker ip:port list; here: a device
               count or mesh spec ("8" or "dp2,tp2,sp2") selecting how many
               chips / which axes to shard over.
--nthreads     reference: executor thread count; here: host-side threads
               (tokenization etc.) — accepted, mostly advisory.
--gpu-index / --gpu-segments / --net-turbo: accepted for CLI compatibility,
               no-ops on TPU (single-program SPMD has no segment split or
               socket turbo mode).
"""

from __future__ import annotations

import argparse


def _float_type(s: str) -> int:
    # lazy: quants.codec pulls numpy, and this module is also the
    # dllama-router CLI's surface — the router is stdlib-only by design
    # and must start on hosts without numpy/jax installed
    from ..quants.codec import FloatType

    m = {"f32": FloatType.F32, "f16": FloatType.F16, "q40": FloatType.Q40, "q80": FloatType.Q80}
    if s not in m:
        raise argparse.ArgumentTypeError(f"unknown float type {s!r}")
    return m[s]


def build_parser(prog: str, api: bool = False) -> argparse.ArgumentParser:
    # imported here, not at module top: build_router_parser below shares
    # this module, and the router CLI must import without numpy
    from ..quants.codec import FloatType

    p = argparse.ArgumentParser(prog=prog)
    if not api:
        p.add_argument("mode", choices=["inference", "chat", "worker", "train"],
                       help="run mode (src/dllama.cpp:216-239; train is a "
                            "beyond-parity extension — the reference is "
                            "inference-only)")
    p.add_argument("--model", help="path to .m model file")
    p.add_argument("--tokenizer", help="path to .t tokenizer file")
    p.add_argument("--prompt", default=None)
    p.add_argument("--steps", type=int, default=64, help="tokens to generate (inference mode)")
    p.add_argument("--max-seq-len", type=int, default=0, help="clamp context length (src/llm.cpp:89-91)")
    p.add_argument("--buffer-float-type", type=_float_type, default=FloatType.F32,
                   help="activation quant emulation: q80 reproduces the reference's lossy "
                        "activation casts (bit-fidelity mode); f32 (default) runs clean — "
                        "the reference defaults to q80 because its TCP links need the "
                        "bandwidth, which ICI does not")
    p.add_argument("--weights", default="auto", choices=["auto", "packed", "dense"],
                   help="Q40 models: 'packed' keeps int4+scales resident in HBM with "
                        "dequant-in-matmul (the reference's Q40-at-rest execution, "
                        "src/nn/nn-cpu-ops.cpp:222-440); 'dense' dequantizes at load. "
                        "auto = packed on TPU, dense elsewhere")
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--topp", type=float, default=0.9)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--nthreads", type=int, default=1)
    p.add_argument("--max-lanes", type=int, default=8, help="concurrent request lanes (continuous batching)")
    p.add_argument("--kv-dtype", default="auto",
                   choices=["auto", "bf16", "f32", "f8"],
                   help="KV cache dtype: auto = bf16 on TPU (half the HBM), "
                        "f32 on CPU; f8 = float8_e4m3 storage (quarter the "
                        "f32 HBM — double the lanes or context per chip; "
                        "dequant fuses into the attention reads)")
    p.add_argument("--chat-template", default=None,
                   choices=[None, "llama2", "llama3", "deepSeek3", "chatml"])
    p.add_argument("--workers", nargs="*", default=None,
                   help="TPU: device count or mesh spec (dp2,tp4); reference compat")
    # multi-host pod bootstrap (reference: worker serve() + root connect,
    # src/app.cpp:405-464 -> jax.distributed). Run the SAME command on every
    # host with its own --process-id; workers use mode `worker`.
    p.add_argument("--coordinator", default=None,
                   help="host:port of process 0 for jax.distributed multi-host")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--port", type=int, default=9990)
    p.add_argument("--host", default="0.0.0.0")
    # accepted for reference CLI compatibility; no-ops on TPU:
    p.add_argument("--gpu-index", type=int, default=-1, help=argparse.SUPPRESS)
    p.add_argument("--gpu-segments", default=None, help=argparse.SUPPRESS)
    p.add_argument("--net-turbo", type=int, default=1, help=argparse.SUPPRESS)
    p.add_argument("--benchmark", action="store_true", help="print per-token timing stats")
    p.add_argument("--no-spec", action="store_true",
                   help="disable prompt-lookup speculative decoding "
                        "(serving and greedy CLI inference)")
    p.add_argument("--prefix-min-tokens", type=int, default=None,
                   help="serving: reuse resident lane KV when a new "
                        "request shares at least this many leading prompt "
                        "tokens (prefix caching); 0 disables; default: "
                        "scheduler default (16)")
    # paged KV pool (runtime/kvpool.py; docs/SERVING.md "Paged KV")
    p.add_argument("--paged-kv", default="off", choices=["on", "off"],
                   help="serving: store KV as a pooled set of fixed-size "
                        "pages behind a per-lane page table instead of "
                        "contiguous per-lane planes. Prefix sharing "
                        "becomes a refcount bump on the SAME physical "
                        "pages (zero HBM copies; copy_lane dies), "
                        "divergence is a single-page copy-on-write, and "
                        "finished sessions park their sharable pages so "
                        "resident sessions exceed lanes; pool exhaustion "
                        "sheds with a retryable 429. Token streams are "
                        "byte-identical to the contiguous layout. 'off' "
                        "(default) keeps contiguous planes bit-for-bit "
                        "(escape hatch)")
    p.add_argument("--kv-page-size", type=int, default=None,
                   help="--paged-kv on: tokens per KV page (power of two; "
                        "shrunk automatically to fit short contexts). "
                        "Smaller pages = finer sharing granularity and "
                        "less tail waste, larger = smaller page tables "
                        "and fewer, bigger COW copies; default: pool "
                        "default (64)")
    p.add_argument("--kv-pool-pages", type=int, default=None,
                   help="--paged-kv on: total pages in the device pool "
                        "(default: the contiguous layout's exact HBM "
                        "footprint, max-lanes x blocks-per-full-lane — "
                        "oversubscription then comes from sessions "
                        "reserving only prompt + max_tokens, not from a "
                        "bigger pool)")
    p.add_argument("--kv-max-parked", type=int, default=None,
                   help="--paged-kv on: max finished sessions whose "
                        "sharable prefix pages stay resident (refcounted, "
                        "LRU-evicted under pool pressure; evicted "
                        "sessions rebuild deterministically from the "
                        "request journal on next activity); 0 disables "
                        "parking; default: pool default (64)")
    p.add_argument("--kv-host-bytes", type=int, default=None,
                   help="--paged-kv on: host-RAM byte budget for the KV "
                        "swap tier (runtime/kvpool.py HostTier). Parked "
                        "pages evicted under pool pressure swap their "
                        "bytes to host RAM (sha256-framed, LRU within "
                        "the budget) instead of dropping; a later "
                        "admission that misses HBM but hits the host "
                        "tier swaps pages back in — cheaper than a "
                        "journal rebuild, dearer than resident reuse. "
                        "0 (default) disables the tier and restores "
                        "drop-to-rebuild behavior bit-for-bit")
    # structured output (grammar/; docs/SERVING.md "Structured output")
    p.add_argument("--grammar", default="on", choices=["on", "off"],
                   help="serving: grammar-constrained decoding — requests "
                        "with response_format {'type':'json_object'} or "
                        "{'type':'json_schema',...} compile into a "
                        "token-level automaton enforced INSIDE the "
                        "compiled step families (masked exact top-p + "
                        "on-device state carry), so constrained and "
                        "unconstrained lanes coexist with zero pipeline "
                        "flushes. 'off' (escape hatch) makes such "
                        "requests fail with a typed 400")
    p.add_argument("--grammar-slab-states", type=int, default=None,
                   help="structured output: device slab capacity in "
                        "automaton states shared by all live schemas "
                        "(fixed at startup so schema churn can never "
                        "recompile XLA programs; admissions beyond it "
                        "shed retryably). Default: grammar default "
                        "(1024)")
    # serving QoS (serving/ package): bounded admission + deadlines
    p.add_argument("--max-queue", type=int, default=256,
                   help="serving: max requests waiting for a lane before "
                        "submissions are shed with HTTP 429 + Retry-After "
                        "(bounded admission; 0 = unbounded)")
    p.add_argument("--queue-timeout", type=float, default=0.0,
                   help="serving: seconds a request may wait queued before "
                        "finishing with finish_reason=timeout instead of "
                        "holding the client open (0 disables)")
    p.add_argument("--request-budget", type=float, default=0.0,
                   help="serving: wall-clock seconds a request may spend "
                        "generating after admission; exceeding it finishes "
                        "with finish_reason=timeout and frees the lane "
                        "(0 disables)")
    p.add_argument("--multi-step", type=int, default=None,
                   help="serving: chain up to this many decode steps per "
                        "device dispatch in steady-state decode (identical "
                        "token streams, 1/h the per-token dispatch "
                        "overhead); 0 disables; default: scheduler "
                        "default (8)")
    p.add_argument("--pipeline-depth", type=int, default=None,
                   help="serving: async decode pipeline — bound on "
                        "dispatched-but-unconsumed decode steps. Step k+1 "
                        "dispatches from the on-device token carry while "
                        "step k's host readback (detokenize, stream, "
                        "stop/EOS checks) runs one step behind, overlapped "
                        "with device execution; token streams stay "
                        "byte-identical to synchronous stepping. 0 or 1 "
                        "disables; default: engine default (2)")
    p.add_argument("--fused-prefill", default="on", choices=["on", "off"],
                   help="serving: stall-free admissions — a queued request "
                        "claims a lane inside the live async decode chain "
                        "and its prompt chunks ride fused prefill+decode "
                        "dispatches (one compiled program advances every "
                        "decoding lane one token AND consumes one bounded "
                        "prompt chunk), so admissions never flush the "
                        "pipeline and pipeline_flushes stays ~0 under "
                        "churn. 'off' restores the pre-fused behavior: an "
                        "admission exits the chain to the synchronous "
                        "admit+prefill path (escape hatch)")
    p.add_argument("--ring-sync", default=None, choices=["on", "off"],
                   help="pure-TP mesh serving: overlap the wo/w2 TP "
                        "activation sync with the dequant matmul as a ring "
                        "reduce-scatter + all-gather (chunked hops XLA "
                        "hides under compute; Q80 wire when "
                        "--buffer-float-type q80 engages) instead of "
                        "XLA's sequential post-matmul all-reduce. Default "
                        "on (DLLAMA_RING_SYNC env equivalent); 'off' "
                        "restores the plain psum sync bit-for-bit "
                        "(escape hatch)")
    # mirrors ops/pallas_q40.DEQUANT_MODES; argparse must stay importable
    # without jax, so the list is spelled out and the pairing is pinned by
    # tests/test_pallas_q40.py
    p.add_argument("--dequant", default=None,
                   choices=["v4", "bf16chain", "repeat", "u8chain",
                            "blockdot", "i8blockdot"],
                   help="Q40 dequant arithmetic variant for the Pallas "
                        "kernel's bf16 dot path (DLLAMA_DEQUANT env "
                        "equivalent; default v4), set BEFORE warmup, so "
                        "every program compiles exactly once; "
                        "interpret/CPU always runs the exact-f32 v4 chain")
    p.add_argument("--step-deadline", type=float, default=None,
                   help="serving: failure-containment watchdog — if a "
                        "dispatched engine step makes no progress for "
                        "this many seconds, trip the circuit breaker and "
                        "abort the async chain (single host) or crash "
                        "the process deliberately (pods, where "
                        "jax.distributed peer-failure detection turns "
                        "death into a pod-wide signal while a silent "
                        "hang wedges everything). Default: "
                        "DLLAMA_STEP_DEADLINE env, else off (0)")
    # crash-durable serving (serving/journal.py, serving/recovery.py,
    # serving/resume.py; docs/SERVING.md "Crash recovery")
    p.add_argument("--journal-path", default=None,
                   help="serving: append-only CRC-framed request journal "
                        "(crash durability) — admitted requests with "
                        "their resolved sampler seeds plus periodic "
                        "delivery watermarks, written by a background "
                        "thread off the hot path. Off by default; pair "
                        "with --recover-journal to resume after a crash")
    p.add_argument("--recover-journal", action="store_true",
                   help="serving: on startup, replay the --journal-path "
                        "journal — every admitted-but-unfinished request "
                        "is re-admitted and regenerated from its prompt "
                        "with the same seed (byte-identical streams), "
                        "fast-forwarded through its delivered-token "
                        "watermark; re-admission is paced through the "
                        "circuit breaker so recovery cannot stampede a "
                        "freshly restarted engine")
    # fleet serving (fleet/; docs/SERVING.md "Fleet serving")
    p.add_argument("--replica-id", default=None,
                   help="serving: this replica's name in a fleet — "
                        "stamped as the X-DLlama-Replica header on every "
                        "response and onto SSE terminal chunks so the "
                        "dllama-router's traces and the migration path "
                        "can attribute sheds and streams to their source "
                        "replica. Default: host:port (the machine "
                        "hostname when binding all interfaces — a fleet "
                        "of 0.0.0.0:8080s would all share one id)")
    p.add_argument("--role", default="mixed",
                   choices=["mixed", "prefill", "decode"],
                   help="serving: this replica's fleet role, advertised "
                        "on GET /load. 'prefill': the dllama-router "
                        "steers long-classified prompts here and hands "
                        "their sessions (KV pages + migration ticket) to "
                        "a decode replica at first token "
                        "(disagg/; docs/DISAGG.md). 'decode': preferred "
                        "hand-off target. 'mixed' (default): the "
                        "monolithic single-tier behavior")
    p.add_argument("--reconnect-grace", type=float, default=0.0,
                   help="serving: seconds a disconnected SSE client may "
                        "reattach (GET /v1/stream/<id> with "
                        "Last-Event-ID) before the request is cancelled; "
                        "while the window is open the request keeps "
                        "generating into a bounded delta buffer. 0 "
                        "(default) preserves cancel-on-disconnect")
    # observability (telemetry/, docs/OBSERVABILITY.md)
    p.add_argument("--trace-path", default=None,
                   help="serving: write the request-lifecycle span ring as "
                        "Chrome trace-event JSON (Perfetto / "
                        "chrome://tracing loadable) to this path when the "
                        "server drains; the live ring is always fetchable "
                        "at GET /trace and metrics at GET /metrics")
    # train mode (beyond parity — no reference analogue)
    p.add_argument("--data", default=None,
                   help="train: UTF-8 text file tokenized into training batches")
    p.add_argument("--train-steps", type=int, default=100)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup-steps", type=int, default=0,
                   help="train: linear LR warmup steps, then cosine decay "
                        "to 10%% of --lr over --train-steps (0 = flat --lr)")
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--train-seq-len", type=int, default=0,
                   help="tokens per training sequence (0 = model seq_len)")
    p.add_argument("--ckpt-dir", default=None,
                   help="train: save/resume orbax checkpoints here "
                        "(resumes from the latest step_<N> if present)")
    p.add_argument("--save-every", type=int, default=50,
                   help="train: checkpoint every N steps (and at the end)")
    return p


def build_router_parser(prog: str = "dllama-router") -> argparse.ArgumentParser:
    """CLI surface for the fleet front-end (fleet/router.py) — model-free
    by design: the router holds no weights and no tokenizer, only the
    replica table and the client sockets."""
    p = argparse.ArgumentParser(prog=prog)
    p.add_argument("--replicas", nargs="+", required=True,
                   help="engine replica addresses (host:port ...), each a "
                        "dllama-api process; replica ids default to the "
                        "addresses (match each replica's --replica-id)")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=9980)
    p.add_argument("--affinity-block-chars", type=int, default=None,
                   help="prefix-affinity block size in prompt characters "
                        "(~4 chars/token x the KV pool's 64-token page); "
                        "the affinity key chains content hashes over the "
                        "prompt's leading blocks, the router twin of the "
                        "KV prefix tree's node-key chain. Default: "
                        "fleet default (256)")
    p.add_argument("--affinity-blocks", type=int, default=None,
                   help="how many leading blocks the affinity key covers "
                        "(a long shared system prompt maps to ONE key "
                        "regardless of what follows); 0 disables prefix "
                        "affinity — every request balances by load. "
                        "Default: fleet default (4)")
    p.add_argument("--scrape-interval", type=float, default=0.5,
                   help="seconds between /load scrapes of each replica "
                        "(queue depth, free lanes, pool pressure, "
                        "breaker, draining — the routing signals)")
    p.add_argument("--migration", default="on", choices=["on", "off"],
                   help="live session migration: cache each stream's "
                        "exported journal admit record (its migration "
                        "ticket) and, when the serving replica dies or "
                        "drains mid-stream, regenerate the session "
                        "byte-identically on another replica and splice "
                        "the resumed stream onto the same client socket "
                        "— zero lost, zero duplicated tokens. Replicas "
                        "need --reconnect-grace > 0 for the reattach "
                        "half. 'off': mid-stream failures surface to "
                        "the client as typed errors instead")
    p.add_argument("--disagg-threshold", type=int, default=None,
                   help="disaggregated prefill: prompts at/above this "
                        "many characters classify 'long' and route to a "
                        "replica advertising role=prefill on /load; at "
                        "first token the session (KV-page bundle + "
                        "migration ticket) hands off to a decode "
                        "replica, char-exact on the same client socket. "
                        "0 disables the policy. Default: disagg default "
                        "(8000). Needs --migration on and at least one "
                        "--role prefill replica to take effect; without "
                        "them every request rides the monolithic path")
    return p


def parse_mesh_spec(workers: list[str] | None):
    """--workers '8' -> tp=8 (reference pure-TP); 'dp2,tp2,sp2,ep2' -> explicit."""
    from ..parallel import MeshPlan

    if not workers:
        return None
    spec = workers[0]
    if spec.isdigit():
        return MeshPlan(tp=int(spec))
    plan = {"dp": 1, "tp": 1, "sp": 1, "ep": 1, "pp": 1}
    for part in spec.split(","):
        for axis in plan:
            if part.startswith(axis):
                plan[axis] = int(part[len(axis):])
                break
        else:
            raise ValueError(f"bad mesh spec part {part!r}")
    return MeshPlan(**plan)
