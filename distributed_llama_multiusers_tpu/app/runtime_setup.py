"""Shared model/engine bootstrapping for the CLI entry points — the analogue
of runInferenceApp's setup sequence (src/app.cpp:233-312): load header ->
validate -> tokenizer -> build model -> place on devices -> engine."""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp

from ..analysis import jitcheck
from ..formats import load_model_header
from ..models import load_params_from_m
from ..models.loader import load_params_from_m_quantized
from ..parallel import make_mesh, validate_mesh_for_config
from ..parallel.sharding import shard_params
from ..runtime import ContinuousBatchingScheduler, InferenceEngine
from ..runtime.kvpool import DEFAULT_MAX_PARKED, DEFAULT_PAGE_SIZE
from ..telemetry.logs import log_event
from ..tokenizer import Tokenizer
from .args import parse_mesh_spec


def log(emoji: str, msg: str) -> None:
    print(f"{emoji} {msg}", flush=True)


def honor_cpu_platform_env() -> None:
    """`JAX_PLATFORMS=cpu dllama ...` (tests, CPU drives) runs on the CPU:
    JAX reads the variable itself, so all that is left to do is SAY so — a
    CPU run must never pass for a chip run in the logs."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms.startswith("cpu"):
        log_event("platform_pinned", platform="cpu", JAX_PLATFORMS=platforms)


# the in-checkout default of the persistent compile cache (git-ignored). A
# FIXED path: the directory is part of every cache key, so one derived from
# a pid, a time or a temporary name would never hit.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache() -> str | None:
    """Persistent XLA compilation cache (opt-out: DLLAMA_NO_COMPILE_CACHE=1);
    returns the directory in use. Where JAX_COMPILATION_CACHE_DIR is set JAX
    already caches there and nothing else is configured; otherwise the cache
    lives at DEFAULT_COMPILE_CACHE_DIR. Repeat builds of the same programs
    (server restarts, benchmark runs of one checkout, pod workers replaying
    identical programs) then load instead of compiling. A change of the
    kernel's block geometry changes the serialized Mosaic kernel inside
    the HLO, so the cache key differs."""
    if os.environ.get("DLLAMA_NO_COMPILE_CACHE") == "1":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_COMPILE_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # persistent-cache traffic of this process, fed by jax.monitoring
    # (analysis/jitcheck.py): a restart on the same tree must show hits,
    # and the warmup_program / warmup_done lines say whether it did
    jitcheck.install()
    return path


def _build_engine(config, params, **kw) -> InferenceEngine:
    """``InferenceEngine(...)``; what a latent-attention model or one with a
    layer pattern does not serve (the paged pool, the host tier, a mesh) ends
    the start-up with the engine's own sentence instead of a traceback."""
    try:
        return InferenceEngine(config, params, **kw)
    except ValueError as e:
        if not (config.latent_attention or config.layer_kinds):
            raise
        print(f"error: {e}", file=sys.stderr)
        raise SystemExit(2) from e


def load_stack(args, n_lanes: int | None = None):
    """Returns (config, params, tokenizer, engine).

    Multi-host (--coordinator): joins the pod before touching the backend;
    on process 0 the engine comes back wrapped in RootControlEngine (every
    call is broadcast to the workers first), and on workers the raw engine
    carries `.control_plane` for `worker_loop`. Each host loads the model
    file itself — under SPMD there is no root-ships-weights protocol
    (reference: src/nn/nn-network.cpp:824-901)."""
    from ..parallel.multihost import maybe_initialize_distributed

    t_load = time.perf_counter()
    cache_dir = enable_compilation_cache()
    n_proc = maybe_initialize_distributed(args)
    if not args.model or not args.tokenizer:
        print("error: --model and --tokenizer are required", file=sys.stderr)
        raise SystemExit(2)
    header = load_model_header(args.model, max_seq_len=args.max_seq_len)
    config_dtype = jnp.bfloat16
    if jax.default_backend() == "cpu":
        config_dtype = jnp.float32  # parity-friendly on host runs

    log("💡", f"Dim: {header.dim}  HiddenDim: {header.hidden_dim}  Layers: {header.n_layers}")
    log("💡", f"Heads: {header.n_heads}/{header.n_kv_heads}  Vocab: {header.vocab_size}  SeqLen: {header.seq_len}")

    tokenizer = Tokenizer(args.tokenizer)
    log("📄", f"Vocab: {tokenizer.vocab_size}  Bos: {tokenizer.bos_id}  Eos: {tokenizer.eos_token_ids}")

    weights_mode = getattr(args, "weights", "auto")
    if weights_mode == "auto":
        weights_mode = "packed" if jax.default_backend() == "tpu" else "dense"
    if weights_mode == "packed":
        config, params = load_params_from_m_quantized(args.model, header, dtype=config_dtype)
        from ..quants.packed import PackedQ40

        if config.layer_kinds:
            first = next(m for m in (params.conv, params.ssm, params.linear, params.delta,
                                      params.attn) if m is not None)[0]
        else:
            first = (params.attn if config.latent_attention else params.layers).wq
        if any(isinstance(x, PackedQ40) for x in [params.wcls, first]):
            log("🔷", "Q40 weights resident in HBM (dequant-in-matmul)")
        else:
            weights_mode = "dense"
            log("🔶", "model has no Q40 tensors; loaded dense")
    else:
        config, params = load_params_from_m(args.model, header, dtype=config_dtype)

    mesh = None
    plan = parse_mesh_spec(args.workers)
    if plan is not None and plan.n_devices > 1:
        validate_mesh_for_config(config, plan)
        mesh = make_mesh(plan)
        params = shard_params(params, mesh)
        # the Pallas Q40 kernel stays enabled: q40_matmul_partitioned carries
        # a GSPMD partitioning rule, so every shard runs dequant-in-matmul —
        # the reference's every-node-runs-the-quantized-matmul property
        # (src/nn/nn-cpu-ops.cpp:222-440)
        log(
            "⭕",
            f"Mesh: dp={plan.dp} pp={plan.pp} tp={plan.tp} sp={plan.sp} "
            f"ep={plan.ep} over {plan.n_devices} devices",
        )
    log("💿", "Weights loaded")
    from ..ops.linear import pallas_kernel_active
    from ..ops.ring_collective import pure_tp

    if (
        mesh is not None
        and weights_mode == "packed"
        and jax.default_backend() == "tpu"
        and pallas_kernel_active()
        and not pure_tp(dict(mesh.shape))
    ):
        # pure-TP meshes run the kernel per shard under shard_map; every
        # other layout reaches it through the GSPMD custom_partitioning
        # wrapper, which libtpu cannot compile — say so now, not as an
        # "emitter not found" from the middle of warm-up
        print(
            "error: on a TPU, packed Q40 weights through the Pallas kernel "
            "serve pure-TP meshes only (--workers tpN): libtpu has no "
            "custom-call partitioner for the dp/sp/ep/pp layouts. Use "
            "--weights dense, or DLLAMA_NO_PALLAS=1 for XLA dequant.",
            file=sys.stderr,
        )
        raise SystemExit(2)

    # dequant chain selection (ops/pallas_q40.py): the CLI flag overrides
    # the DLLAMA_DEQUANT env default; both validate against the known-mode
    # list. Applied HERE — before the engine exists and warmup compiles —
    # because the mode is a static argname of the jitted matmul: a later
    # switch would retrace every warmed family mid-serving.
    from ..ops import pallas_q40 as _pq

    if getattr(args, "dequant", None) is not None:
        _pq.set_dequant_mode(args.dequant)
    if _pq.DEQUANT_MODE != "v4":
        log("🎛️", f"Dequant mode: {_pq.DEQUANT_MODE} "
                  "(--dequant / DLLAMA_DEQUANT)")

    from ..quants.codec import FloatType

    emulate_q80 = args.buffer_float_type == FloatType.Q80
    q80_sync = False
    if emulate_q80 and mesh is not None:
        # same predicate llama_forward uses, so the log only claims the
        # transport when it will actually engage
        from ..parallel.collectives import q80_sync_engages

        q80_sync = q80_sync_engages(config, dict(mesh.shape))
    if q80_sync:
        synced = "wo" if config.n_experts > 0 else "wo/w2"
        log("🔶", f"Q80 sync transport: {synced} TP boundaries ship int8+scales "
                  "(--buffer-float-type q80 on a tp mesh)")
    elif emulate_q80:
        log("🔶", "Q80 activation-cast emulation enabled (--buffer-float-type q80)")
    # ring-overlapped TP activation sync (ops/ring_collective.py): CLI flag
    # overrides the DLLAMA_RING_SYNC env default; the log uses the same
    # predicate llama_forward does, so what is announced is what runs
    from ..ops.ring_collective import (
        ring_sync_engages,
        ring_sync_supported,
        set_ring_sync,
    )

    if getattr(args, "ring_sync", None) is not None:
        set_ring_sync(args.ring_sync == "on")
    # mirror llama_forward's FULL gate (engages + per-output support,
    # q80-wire blocks included — q80_sync_engages already guarantees the
    # block divisibility today, but the log must not outlive that
    # coincidence): what is announced is what runs
    ring_sync = bool(
        mesh is not None
        and ring_sync_engages(config, dict(mesh.shape))
        and ring_sync_supported(
            config.dim, dict(mesh.shape).get("tp", 1), q80_sync
        )
    )
    if ring_sync:
        synced = "wo" if config.n_experts > 0 else "wo/w2"
        log("🔗", f"Ring TP sync: {synced} activation sync overlapped "
                  "with the dequant matmul"
                  + (" (Q80 wire)" if q80_sync else "")
                  + " — DLLAMA_RING_SYNC=off / --ring-sync off to fall "
                    "back to psum")
    if n_proc > 1 and mesh is None:
        print(
            "error: multi-host runs need a --workers mesh spec spanning the "
            "global device set",
            file=sys.stderr,
        )
        raise SystemExit(2)
    engine = _build_engine(
        config,
        params,
        # every process must compile identical programs: lane count comes
        # from --max-lanes on all hosts (n_lanes overrides are single-host)
        n_lanes=(n_lanes if n_proc == 1 else None) or args.max_lanes,
        # None -> bf16 KV on TPU, f32 on CPU (parity oracle); --kv-dtype
        # overrides (e.g. f32 on TPU for strict-parity serving, f8 for
        # double the lanes/context per chip)
        cache_dtype={
            "f32": jnp.float32, "bf16": jnp.bfloat16,
            "f8": jnp.float8_e4m3fn, "auto": None,
        }[getattr(args, "kv_dtype", "auto") or "auto"],
        emulate_q80_activations=emulate_q80,
        q80_sync=q80_sync,
        mesh=mesh,
        replicate_outputs=n_proc > 1,
        # async decode pipeline ring bound (None -> engine default 2);
        # every process must agree, like --max-lanes
        pipeline_depth=getattr(args, "pipeline_depth", None),
        # paged KV pool (runtime/kvpool.py): every process must agree on
        # the layout — the table leaf is part of the compiled programs'
        # pytree structure (OP_KV_TABLE replays assume paged workers)
        paged_kv=getattr(args, "paged_kv", "off") == "on",
        # pass explicit values through unmodified (None = flag absent):
        # a 0/negative --kv-page-size must die in for_seq_len's
        # validation, not silently become the default
        kv_page_size=(DEFAULT_PAGE_SIZE
                      if getattr(args, "kv_page_size", None) is None
                      else args.kv_page_size),
        kv_pool_pages=getattr(args, "kv_pool_pages", None),
        kv_max_parked=(DEFAULT_MAX_PARKED
                       if getattr(args, "kv_max_parked", None) is None
                       else args.kv_max_parked),
        # host-RAM swap tier budget (0 = disabled, drop-to-rebuild
        # bit-for-bit); host-side only, so processes need not agree,
        # but the OP_KV_SWAP replay assumes paged workers like pages
        kv_host_bytes=getattr(args, "kv_host_bytes", None) or 0,
        # grammar slab capacity (structured output): every process must
        # agree — the slab arrays are compiled-program operands
        grammar_slab_states=getattr(args, "grammar_slab_states", None),
    )
    # the tree the engine serves from: where it made the kernel's form of a
    # Q40 scale stack (quants/packed.py ``q40_at_rest``) the loader's float16
    # copy is dropped here and not held beside it for the process's life
    params = engine.params
    if engine.kvpool is not None:
        log(
            "📑",
            f"Paged KV: {engine.kvpool.n_pages} pages x "
            f"{engine.kvpool.page_size} tokens, "
            f"{engine.kvpool.blocks_per_lane} blocks/lane, "
            f"max parked {engine.kvpool.max_parked}, "
            + (f"host swap tier "
               f"{engine.kvpool.host_tier.budget_bytes // (1 << 20)} MiB"
               if engine.kvpool.host_tier.enabled
               else "host swap tier off")
            + " (--paged-kv off restores contiguous planes)",
        )
    # structured output (grammar/; docs/SERVING.md "Structured output"):
    # register the tokenizer's piece table so response_format requests
    # compile token-level automata — on EVERY process (workers replay
    # OP_GRAMMAR attaches against their own identical table). --grammar
    # off is the escape hatch: requests carrying response_format then 400.
    if getattr(args, "grammar", "on") != "off":
        engine.grammar_init(
            [tokenizer.vocab[i] if i < tokenizer.bos_id else None
             for i in range(tokenizer.vocab_size)],
            tokenizer.eos_token_ids,
        )
        log("🧩", "Structured output: json_object / json_schema enabled "
                  "(--grammar off disables)")
    # the one line (and /stats block, server/http.py) that says which device
    # this process really serves from and through which weight/kernel path:
    # a CPU or XLA-dequant run must never pass for a chip run
    dev = jax.devices()[0]
    mem = [d.memory_stats() for d in jax.local_devices()]
    engine.device_facts = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "mesh_shape": dict(mesh.shape) if mesh is not None else None,
        "weights": weights_mode,
        "kv_dtype": jnp.dtype(engine.cache_dtype).name,
        "dequant_mode": _pq.DEQUANT_MODE,
        "pallas_kernel": bool(
            weights_mode == "packed" and pallas_kernel_active()
        ),
        "ring_sync": ring_sync,
        # which attention and which expert path the decode steps run
        **engine.path_facts(),
        # weights + KV as placed, per local device (None: the backend does
        # not report it) — a mesh that left everything on device 0 shows
        "device_bytes_in_use": (
            [m["bytes_in_use"] for m in mem] if all(mem) else None
        ),
        "compile_cache_dir": cache_dir,
    }
    log_event(
        "runtime_device",
        load_s=round(time.perf_counter() - t_load, 2),
        **engine.device_facts,
    )
    if n_proc > 1:
        from ..parallel.multihost import ControlPlane, RootControlEngine

        # packet slots must fit the largest prefill chunk AND (paged) a
        # full page-table row, or send_kv_table's pre-broadcast check
        # rejects long-context table updates
        plane_chunk = engine.prefill_buckets[-1]
        if engine.kvpool is not None:
            plane_chunk = max(plane_chunk, engine.kvpool.blocks_per_lane)
        plane = ControlPlane(engine.n_lanes, chunk=plane_chunk)
        if jax.process_index() == 0:
            log("⭕", f"Multi-host root: {n_proc} processes, control plane up")
            engine = RootControlEngine(engine, plane)
        else:
            log("⭕", f"Multi-host worker {jax.process_index()}/{n_proc}")
            engine.control_plane = plane
    return config, params, tokenizer, engine


def make_scheduler(engine, tokenizer, args=None) -> ContinuousBatchingScheduler:
    from ..runtime.engine import warmup_engine
    from ..serving import DeadlinePolicy, QosQueue

    speculative = not getattr(args, "no_spec", False)
    # pass prefix_min_tokens/multi_step only when the CLI provided them: the
    # scheduler defaults are the single source of truth for fallback values
    pmt = getattr(args, "prefix_min_tokens", None)
    ms = getattr(args, "multi_step", None)
    overrides = {}
    if pmt is not None:
        overrides["prefix_min_tokens"] = pmt
    if ms is not None:
        overrides["multi_step"] = ms
    fp = getattr(args, "fused_prefill", None)
    if fp is not None:  # --fused-prefill on/off (stall-free admissions)
        overrides["fused_prefill"] = fp == "on"
    # failure containment (serving/breaker.py, serving/watchdog.py): the
    # step watchdog arms when --step-deadline / DLLAMA_STEP_DEADLINE is
    # set; on a pod ROOT a trip crashes the process deliberately so
    # jax.distributed peer-failure detection surfaces the hang (the
    # multihost.py analysis: death beats silent desync)
    sd = getattr(args, "step_deadline", None)
    if sd is not None:
        overrides["step_deadline_s"] = sd
    overrides["watchdog_fatal"] = (
        getattr(engine, "_plane", None) is not None  # RootControlEngine
    )
    # crash durability (serving/journal.py): the append-only request
    # journal, off unless --journal-path names a file; recovery replay
    # (--recover-journal) is wired by dllama_api after the scheduler is
    # up, since stream reattach also needs the resume registry
    jp = getattr(args, "journal_path", None)
    if jp:
        from ..serving import RequestJournal

        overrides["journal"] = RequestJournal(jp)
        log("📓", f"Request journal: {jp} (crash-durable serving)")
    # QoS surface (--max-queue / --queue-timeout / --request-budget):
    # bounded admission with per-user fair share, plus deadlines
    max_queue = getattr(args, "max_queue", 0) or 0
    policy = DeadlinePolicy.from_args(args) if args is not None else DeadlinePolicy()
    # paged engines charge DRR fair share in PAGES — what admission
    # actually takes from the pool — instead of decode tokens; the
    # quantum rescales so the rotation grain stays ~128 tokens' worth
    qos_kw = {}
    pool = getattr(engine, "kvpool", None)
    if pool is not None:
        from ..serving.qos import page_cost

        qos_kw = {
            "cost": page_cost(pool.page_size),
            "quantum": max(1.0, 128.0 / pool.page_size),
        }
    log(
        "🚦",
        f"QoS: queue capacity {max_queue or 'unbounded'}, "
        f"queue timeout {policy.queue_timeout_s or 'off'}, "
        f"request budget {policy.request_budget_s or 'off'}"
        + (", fair share in KV pages" if pool is not None else ""),
    )
    log("⏳", "Warming serving programs (prefill buckets, decode, spec)...")
    t0 = time.perf_counter()
    sched = ContinuousBatchingScheduler(
        engine, tokenizer, speculative=speculative,
        queue_=QosQueue(capacity=max_queue, **qos_kw),
        deadlines=policy, **overrides,
    )
    warmup_engine(engine, spec=speculative, multi_step=sched.multi_step)
    warmup_s = time.perf_counter() - t0
    log("⏳", f"Warmup done in {warmup_s:.1f}s")
    log_event("warmup_done", warmup_s=round(warmup_s, 2),
              **jitcheck.cache_counts())
    sched.start()
    return sched
