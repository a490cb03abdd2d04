"""Multi-user request queue + continuous-batching scheduler.

The capability the MultiUsers fork exists for (src/Request.hpp,
src/app.cpp:314-402): N concurrent requests dynamically join and leave a
shared batched decode loop. The reference's loop has five defects documented
in SURVEY.md §2.3; this implementation is the corrected design:

  (a) full prompt prefill (bucketed chunks), not just token[0]
  (b) per-lane position vectors — no shared positionPipe overwrite
  (c) per-lane KV cache slots — no cross-request corruption
  (d) clean shutdown via stop() — the loop thread joins
  (e) streaming decode through per-lane StreamDecoder + EosDetector

Flow: HTTP/CLI threads push Request objects into the queue (by default a
serving.QosQueue — bounded admission, priority classes, per-user
deficit-round-robin fair share; the bare RequestQueue FIFO remains for
strict reference-parity use); the scheduler thread drains the queue into
free lanes (prefill), then advances ALL active lanes one token per
engine.decode() step, sampling per-lane, emitting stream deltas, and
fulfilling each request's future on EOS / max_tokens. Deadlines
(serving/deadlines.py) bound queue wait and generation wall-clock;
drain() (serving/drain.py) is the graceful-shutdown counterpart to stop().
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable

import numpy as np

from ..serving import (
    AdmissionRejected,
    CircuitBreaker,
    DeadlinePolicy,
    Priority,
    QosQueue,
    RequestJournal,
    StepWatchdog,
    admit_record,
    budget_expired,
    drain_scheduler,
    queue_expired,
)
from ..analysis import jitcheck, leakcheck
from ..lockcheck import make_lock
from ..ops.block_sparse import SparseSizes, blocks_attended
from ..ops.pallas_attention import ring_rows_read, rows_read
from ..serving.watchdog import deadline_from_env
from ..telemetry import StepRecord, Telemetry
from ..telemetry.names import (
    LOOP_ADMIT,
    LOOP_DISPATCH,
    LOOP_STREAM,
    LOOP_TRACK,
    LOOP_WAIT,
    pipelined_step_class,
    step_class,
)
from ..tokenizer import EosDetector, EosResult, Sampler, Tokenizer, TokenizerChatStops
from ..utils import faults
from ..utils.seeds import fresh_seed
from .engine import DEFAULT_TOPP
from .kvpool import PoolExhausted
from .spec import NgramDraftIndex


class EngineFailure(RuntimeError):
    """Engine-scoped serving failure, resolved onto a request's future by
    the containment layer. Carries the ``request_id`` so the HTTP 500
    body / terminal SSE error chunk can name it — the future's exception
    is all the transport layer sees."""

    def __init__(self, message: str, request_id: int | None = None):
        self.request_id = request_id
        super().__init__(message)


def classify_failure(e: BaseException) -> str:
    """Failure containment classification (the supervised loop's rule):

    - ``"request"`` — per-request input errors: tokenization, empty
      prompts, per-lane validation. The ``ValueError`` family by
      convention (every engine-side argument check raises it). Fails
      only that request (``finish_reason="error"``); the engine is fine.
    - ``"engine"`` — everything an engine dispatch/consume/transfer can
      raise (XLA ``RESOURCE_EXHAUSTED``, transfer errors, injected
      faults): the pipeline flushes, affected lanes fail, lane state
      resets, and the loop keeps serving behind the circuit breaker.

    ``AdmissionRejected`` (the paged pool's exhaustion shed) is request-
    scoped despite being a RuntimeError: the pool being pinned by active
    lanes is LOAD, not engine failure — the client gets the retryable
    429/503 shape ``submit()`` sheds with, and the breaker stays closed.
    """
    if isinstance(e, AdmissionRejected):
        return "request"
    return "request" if isinstance(e, ValueError) else "engine"


class RequestState(Enum):
    QUEUED = 0
    PROMPT_PROCESSING = 1
    GENERATING = 2
    DONE = 3
    FAILED = 4


_req_ids = itertools.count(1)
# guards the counter-object SWAP in ensure_request_id_floor against the
# dataclass default_factory draws on HTTP threads: an unlocked
# read-then-replace could let a fresh request draw from the old counter
# an id the new counter re-issues later (two live requests, one id)
_req_ids_lock = threading.Lock()


def _next_request_id() -> int:
    with _req_ids_lock:
        return next(_req_ids)


def fresh_request_id() -> int:
    """A new unique id from the shared counter — public surface for the
    fleet migration endpoint, which REMAPS an injected session whose
    original id collides with a live request on this replica (every
    replica numbers from 1, so same-id-live collisions across a fleet
    are routine; see server/http.py _admin_migrate)."""
    return _next_request_id()


def ensure_request_id_floor(min_used_id: int) -> None:
    """Advance the shared request-id counter past ``min_used_id`` —
    recovery (serving/recovery.py) re-admits crashed requests under
    their ORIGINAL ids (the SSE reattach key), and fresh requests
    admitted after a recovery must never collide with them."""
    global _req_ids
    with _req_ids_lock:
        nxt = next(_req_ids)
        _req_ids = itertools.count(max(nxt, int(min_used_id) + 1))


@dataclass
class Request:
    """One generation request (mirror of the fork's Request, src/Request.hpp:21-36,
    with correct per-request sampling/stop config)."""

    prompt: str
    max_tokens: int = 128
    temperature: float = 0.0
    topp: float = DEFAULT_TOPP
    seed: int | None = None
    stop: list[str] = field(default_factory=list)
    add_bos: bool = True
    add_special_tokens: bool = True
    # QoS identity (serving/qos.py): fair-share key + admission class
    user_id: str = ""
    priority: int = Priority.NORMAL
    # per-request deadline overrides (serving/deadlines.py); None = policy
    queue_timeout_s: float | None = None
    budget_s: float | None = None
    # structured output (grammar/): the request's response_format —
    # {"type": "json_object"} or {"type": "json_schema", ...} — compiled
    # into a token-level automaton at admission and enforced on device;
    # None = unconstrained. Journaled (and carried by fleet migration
    # tickets) so replay rebuilds the identical automaton from
    # (prompt, seed, schema).
    response_format: dict | None = None
    # crash-durable serving (serving/journal.py): which API route built
    # this request ("chat" | "completion" | None) — journaled so a
    # recovered stream renders the right SSE chunk shape on reattach —
    # and whether this request IS a journal replay (re-admitted under
    # its original id with its original resolved seed)
    api_kind: str | None = None
    recovered: bool = False
    # fleet trace context (telemetry/tracectx.py), "tid-sid" wire form:
    # accepted from the client/router X-DLlama-Trace header, journaled
    # with the admit record and carried by migration tickets, so spans
    # on every replica a request touches share one trace_id
    trace: str | None = None
    id: int = field(default_factory=_next_request_id)
    state: RequestState = RequestState.QUEUED
    future: Future = field(default_factory=Future)
    on_delta: Callable[[str], None] | None = None  # streaming callback
    # filled by the scheduler
    generated_text: str = ""
    generated_tokens: list[int] = field(default_factory=list)
    n_prompt_tokens: int = 0
    error: str | None = None
    finish_reason: str | None = None  # "stop" | "length" | "cancelled" | "timeout"
    submitted_at: float | None = None  # monotonic, stamped by submit()/push()
    admitted_at: float | None = None  # monotonic, stamped at lane claim
    # telemetry (telemetry/): per-request latency record attached at
    # submit, and the summary dict (ttft_s, tbt p50/p95, queued_s, ...)
    # produced at finish — the SAME object the HTTP layer attaches to
    # completion responses and the JSON request log line carries
    tel: object = None
    summary: dict | None = None
    _cancelled: threading.Event = field(default_factory=threading.Event)

    def cancel(self) -> None:
        """Ask the scheduler to stop generating (e.g. client disconnected);
        the lane frees at the next decode step."""
        self._cancelled.set()


class RequestQueue:
    """Thread-safe FIFO handoff (mirror of RequestQueue, src/Request.hpp:39-64)."""

    def __init__(self):
        self._q: "queue.Queue[Request]" = queue.Queue()

    def push(self, request: Request) -> None:
        self._q.put(request)

    def pop(self, timeout: float | None = None) -> Request | None:
        try:
            return self._q.get(timeout=timeout)
        except queue.Empty:
            return None

    def empty(self) -> bool:
        """Advisory emptiness (racy by nature): the scheduler uses it to
        decide whether a multi-step decode would delay an admission."""
        return self._q.empty()

    def drain(self) -> list[Request]:
        out = []
        while True:
            try:
                out.append(self._q.get_nowait())
            except queue.Empty:
                return out

    def remove_if(self, predicate) -> list[Request]:
        """Remove and return every queued request matching ``predicate``
        (same contract as QosQueue.remove_if) — the scheduler's deadline
        sweep and the submit()/drain() race both need targeted removal,
        on this queue no less than on the QoS one."""
        with self._q.mutex:
            q = self._q.queue
            out = [r for r in q if predicate(r)]
            for r in out:
                q.remove(r)
        return out


def _common_prefix_len(a, b) -> int:
    """Length of the longest common leading run of two token lists."""
    n = min(len(a), len(b))
    i = 0
    while i < n and a[i] == b[i]:
        i += 1
    return i


@dataclass
class _Lane:
    request: Request | None = None
    pos: int = 0  # next write position
    # token to feed at pos. On a GENERATING lane it is already STREAMED
    # (the readback that produced it streamed it) and not yet COMMITTED
    # (the readback of the step that feeds it commits it)
    next_token: int = 0
    # set when ``next_token`` ended the stream (EOS / stop string: "stop";
    # max_tokens: "length"): nothing more is streamed, and the request
    # finishes with this reason where next_token commits
    ended: str | None = None
    sampler: Sampler | None = None
    eos: EosDetector | None = None
    decoder: object = None
    pending: list[int] = field(default_factory=list)  # unprocessed prompt tail
    seed: int = 0
    host_exact: bool = False  # route this lane through the host Sampler
    # speculation state: committed (prompt + consumed) token history with
    # an O(1) prompt-lookup draft probe (runtime/spec.py)
    drafter: NgramDraftIndex = field(default_factory=NgramDraftIndex)
    # grammar-constrained decoding (grammar/): the attached slab handle
    # (None = unconstrained) and the HOST MIRROR of the lane's automaton
    # state — absolute slab id, advanced by every token the host streams
    # (at the readback that delivers it). Exact on the sync paths; one
    # step behind on the pipelined chain (where the device carry is
    # authoritative and the mirror only steers draft pre-filtering).
    grammar: object = None
    g_state: int = 0


# Historical routing boundary, kept for the sampler-parity test grid and
# the docs: requests at/above these used to fall back to the host Sampler
# because the old on-device sampler truncated to top-`device_topk` logits,
# which a near-1.0 top-p or a very high temperature defeats. The device
# sampler is now EXACT (full-vocab softmax → threshold search for the
# nucleus, engine.py nucleus_keep), so no request routes host-exact on
# numerics grounds anymore — `host_sampling=True` (bit-exact reference xorshift
# semantics, one [vocab] f32 transfer per token) is the only remaining
# host-exact path, and steady-state serving never reads logits back.
HOST_EXACT_TOPP = 0.99
HOST_EXACT_TEMP = 1.5


class ContinuousBatchingScheduler:
    # dlint guarded-by declaration (analysis/lock_check.py): the pending
    # device-op list moves only under its lock — appended by admin/HTTP
    # threads (run_device_op), drained by the batching loop.
    _dlint_guarded_by = {
        ("_device_ops_lock",): ("_device_ops",),
    }

    # dlint resource-lifecycle declaration (analysis/resourcemodel.py):
    # the live-session mirror. ``_mirror_admit`` (in _start_request)
    # publishes the migration ticket; every request that reached a lane
    # must pass ``_mirror_finish`` (_finish or _fail_request) or the
    # mirror grows one dead ticket per request. Checked by
    # resource-balance; counted at stop() by the leak witness
    # (analysis/leakcheck.py, DLLAMA_LEAKCHECK=1).
    _dlint_acquires = {"session-record": ("_mirror_admit",)}
    _dlint_releases = {"session-record": ("_mirror_finish",)}

    # dlint device-affinity declaration: the batching-loop closure grows
    # from here by same-class ``self.X()`` calls — methods in it may
    # call the engine's ``_dlint_device_affine`` surface directly; every
    # other thread goes through run_device_op().
    _dlint_loop_roots = ("_run",)

    def __init__(
        self,
        engine,
        tokenizer: Tokenizer,
        queue_: RequestQueue | None = None,
        eos_padding: tuple[int, int] = (2, 2),
        host_sampling: bool = False,
        speculative: bool = True,
        prefix_min_tokens: int = 16,
        multi_step: int = 8,
        deadlines: DeadlinePolicy | None = None,
        pipelined: bool = True,
        fused_prefill: bool = True,
        telemetry: Telemetry | None = None,
        breaker: CircuitBreaker | None = None,
        step_deadline_s: float | None = None,
        watchdog_fatal: bool = False,
        journal: RequestJournal | None = None,
    ):
        """``host_sampling=True`` routes sampled lanes through the bit-exact
        host Sampler (reference xorshift semantics, one [vocab] f32 transfer
        per token); the default samples on device inside the compiled decode
        step, transferring only the 4-byte token per lane.

        ``speculative=False`` disables prompt-lookup speculative decoding
        (greedy-lane draft verification); it is otherwise used automatically
        whenever the engine supports it.

        ``prefix_min_tokens`` gates prefix caching: a new request whose
        prompt shares at least that many leading tokens with the tokens
        already resident in some lane's KV cache (including finished
        lanes — their KV stays until overwritten) skips prefilling the
        shared prefix via ``engine.copy_lane``. 0 disables.

        ``multi_step``: when the batch is in steady-state decode (no prompt
        chunks pending, no admissions queued, no drafts to verify, no
        host-exact-sampling lane), run up to this many decode steps in ONE
        device dispatch (``engine.decode_multi``) — token streams identical
        to single stepping, but per-token host dispatch overhead divided by
        the horizon (the dominant serving cost through a high-latency
        device link). Stops/EOS are applied retroactively; a cancel or a
        new admission takes effect at the next horizon boundary. 0 or 1
        disables.

        ``pipelined`` (default on, engines with ``pipeline_depth > 1``
        only): in steady-state decode with no drafts to verify, dispatch
        step k+1 from the engine's ON-DEVICE token carry while step k's
        host readback (detokenize, stream deltas, stop/EOS/deadline
        checks) runs one step behind, overlapped with the device — the
        synchronous dispatch→block→consume cycle leaves the accelerator
        idle for the whole host half. Token streams are byte-identical to
        the synchronous path (the device feed rule applies the same
        where(temp==0, greedy, sampled) select with the same
        fold_in(seed, pos) draws). Speculation drafts, host-exact lanes,
        a queued admission, or a prefill force a flush back to the
        synchronous path.

        ``fused_prefill`` (default on; engines with
        ``supports_fused_prefill`` and pipelining active only): admissions
        no longer flush the pipelined chain. A queued request claims a
        free lane inside the live chain and its prompt chunks ride FUSED
        prefill+decode dispatches (``engine.decode_prefill_fused``): one
        device program advances every generating lane one token AND
        consumes one bounded chunk, so decode lanes never stall behind an
        admission and ``pipeline_flushes`` stays 0 under steady churn.
        Streams remain byte-identical to the synchronous path (the fused
        program's decode half is the pipelined step's math verbatim; the
        prefill half is ``prefill_chunk``'s). Host-exact admissions are
        the one kind that still flushes (they read full logits every
        step). Off: the pre-fused behavior — an admission exits the chain
        to the synchronous admit+prefill path.

        ``deadlines`` (serving/deadlines.py): server-wide queue-wait
        timeout and wall-clock generation budget; expired requests finish
        with ``finish_reason="timeout"`` (queued ones without ever taking a
        lane, active ones at the next loop iteration, freeing their lane).
        Defaults to a policy with both limits disabled; per-request
        overrides on ``Request`` apply either way.

        The default queue is a :class:`~..serving.qos.QosQueue` (unbounded
        unless the caller passes a capacity-bounded one): per-user
        deficit-round-robin fair share and priority classes replace the
        seed's bare FIFO.

        ``telemetry`` (telemetry/): the span tracer + metrics registry +
        JSON logger hub this scheduler stamps request lifecycles and step
        slices into; a default hub is built when the caller passes none
        (host-side only, bounded ring — always on). The server exposes it
        at ``GET /metrics`` / ``GET /trace``; the benchmark reads its
        spans. Span stamping never happens inside the pipelined
        dispatch half (dlint ``pipeline-sync`` pins that): pipelined step
        slices are recorded by the consume half, one step behind.

        ``breaker`` (serving/breaker.py): the circuit breaker the
        supervised loop feeds — N consecutive engine-scoped failures flip
        ``/health`` unhealthy and ``submit()`` sheds with 503 +
        Retry-After until a half-open probe succeeds. Always present
        (a default is built when the caller passes none).

        ``step_deadline_s`` (serving/watchdog.py): when > 0, a watchdog
        thread trips if a blocking engine step (sync decode, prefill
        chunk, lagged pipeline consume) makes no progress within the
        deadline — tripping the breaker and aborting the chain
        single-host, crashing the process deliberately on a pod
        (``watchdog_fatal=True``) so ``jax.distributed`` peer-failure
        detection surfaces the hang. ``None`` reads
        ``DLLAMA_STEP_DEADLINE``; 0 disables.

        ``journal`` (serving/journal.py): the crash-durable request
        journal — every admission writes an admit record (prompt tokens,
        sampler params with the RESOLVED seed, QoS class, deadlines) and
        every ending a finish record, via the journal's background
        writer thread; delivery watermarks are written by the transport
        layer (server/http.py) AFTER each delta reaches the client. On
        restart, serving/recovery.py replays the incomplete set
        byte-identically. ``None`` (the default) disables journaling
        entirely — the ``--journal-path`` flag wires one up."""
        self.engine = engine
        self.tokenizer = tokenizer
        self.queue = queue_ or QosQueue()
        self.deadlines = deadlines or DeadlinePolicy()
        self.telemetry = telemetry or Telemetry()
        if self.telemetry.annotation_factory is None:
            # the loop's spans ride the profiler's clock too (telemetry/
            # itself imports no jax: the factory is handed in here). With
            # no profiler session an annotation is one atomic load.
            from jax.profiler import TraceAnnotation

            self.telemetry.annotation_factory = TraceAnnotation
        # sequence number of the pipelined dispatches: the `step` every
        # loop.* span and step slice of one dispatch carries
        self._step_seq = 0
        # when the chain's last readback returned (perf_counter; None until
        # a chain has been read back once): where a step's interval and a
        # dry dispatch's idle bound start
        self._readback_at: float | None = None
        # queue-wait histogram source: the queue's own pop-time measurement
        # when it offers one (reconciles with queue_popped exactly), else
        # observed at lane-claim time
        self._observe_wait_at_claim = not self.telemetry.bind_queue(self.queue)
        self.eos_padding = eos_padding
        self.host_sampling = host_sampling
        self.speculative = speculative
        self.prefix_min_tokens = prefix_min_tokens
        # a per-lane state overwritten in place (models/hybrid.py): prefix
        # reuse by lane copy and speculation are declined for such a model,
        # by what its configuration is (the scheduler_start line says so)
        self._recurrent_state = bool(
            getattr(getattr(engine, "config", None), "recurrent_state", False))
        # learned sparse attention (models/deepseek.py): rows a lane's decode
        # step attends at most (0: every row held); speculation is declined
        # for such a model (the scheduler_start line says so)
        cfg = getattr(engine, "config", None)
        self._index_topk = int(getattr(cfg, "index_topk", 0) or 0)
        # selective state-space layers: the decode steps' ssm_lane_steps
        self._n_ssm_layers = int(getattr(cfg, "n_ssm_layers", 0) or 0)
        # window attention layers: the decode steps' attn_window_rows_*
        self._window = int(getattr(cfg, "sliding_window", 0) or 0) if int(
            getattr(cfg, "n_window_layers", 0) or 0) else 0
        # linear-attention layers' state bytes a live lane a decode step (in
        # and out), and the block-sparse layers' sizes: the decode steps'
        # linear_state_bytes_moved, attn_blocks_* and sparse_lane_steps
        # (and the delta-rule layers': delta_state_bytes_moved)
        def matrix_step_bytes(kind: str) -> int:  # float32, in and out, every layer of the kind
            layers, heads, width = (int(getattr(cfg, name.format(kind), 0) or 0) for name in (
                "n_{}_layers", "{}_n_heads", "{}_head_dim"))
            return 2 * 4 * layers * heads * width ** 2

        self._linear_step_bytes = matrix_step_bytes("linear")
        self._delta_step_bytes = matrix_step_bytes("delta")
        self._sparse_sizes = (
            SparseSizes.of(cfg) if int(getattr(cfg, "n_sparse_layers", 0) or 0) else None)
        # a held share of the routed experts, "16/256" (None: every expert)
        held = int(getattr(cfg, "experts_held_count", 0) or 0)
        self._experts_held = f"{held}/{cfg.n_experts}" if held else None
        self.multi_step = multi_step
        self.pipelined = pipelined
        self.fused_prefill = fused_prefill
        self._lanes = [_Lane() for _ in range(engine.n_lanes)]
        # tokens whose KV each lane's cache currently holds at slots
        # [0, len): survives request finish (the KV physically remains),
        # reset when a new request claims the lane
        self._lane_kv: list[list[int]] = [[] for _ in range(engine.n_lanes)]
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._thread: threading.Thread | None = None
        # device ops posted by admin threads (disagg page export/import),
        # executed by the batching loop at its next step boundary — the
        # one point where engine.cache is the live chain output and the
        # next dispatch has not yet donated it (run_device_op)
        self._device_ops: list = []
        self._device_ops_lock = make_lock(
            "ContinuousBatchingScheduler._device_ops_lock"
        )
        # failure containment (serving/breaker.py, serving/watchdog.py):
        # the supervised loop's admission gate + stall detector
        self.breaker = breaker or CircuitBreaker()
        deadline = deadline_from_env(step_deadline_s)
        self.watchdog = (
            StepWatchdog(
                deadline, on_trip=self._on_watchdog_trip,
                fatal=watchdog_fatal,
            )
            if deadline > 0
            else None
        )
        # watchdog -> loop signal: abort the pipelined chain at the next
        # host-side opportunity (a slow-but-alive step returns eventually;
        # the chain must not keep extending behind it)
        self._wd_abort = threading.Event()
        # engine-scoped containment rounds (loop thread writes, /stats
        # reads; single GIL-atomic int bump like the timeout counters)
        self.engine_failures = 0
        # crash durability (serving/journal.py, serving/recovery.py):
        # the request journal (None = off) and, after a --recover-journal
        # restart, the replay coordinator whose counters /stats merges
        self.journal = journal
        self.recovery = None
        # fleet migration (serving/journal.admit_record, fleet/migrate.py):
        # the live-session mirror of each admitted request's journal admit
        # record — journal-independent, so a replica without --journal-path
        # can still export a migration ticket. Entries are built whole on
        # the loop thread and assigned/popped with single-key dict ops
        # (GIL-atomic); export_session reads whole entries from HTTP
        # threads. Bounded by n_lanes: records exist only while the
        # request holds a lane.
        self._session_records: dict[int, tuple[dict, Request]] = {}
        self._chat_stops = TokenizerChatStops(tokenizer)
        self._prefill_rr = 0  # round-robin cursor over admitting lanes
        # deadline enforcement counters (loop thread writes, /stats reads;
        # int += is a single atomic-enough bump under the GIL)
        self.queue_timeouts = 0
        self.budget_timeouts = 0
        self._last_sweep = 0.0

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        self._stop.clear()  # restartable: a stop()ed scheduler can start again
        self._draining.clear()
        self._wd_abort.clear()
        # chaos harness: DLLAMA_FAULTS arms the process-global fault plan
        # (utils/faults.py) — one env read, idempotent, no-op otherwise
        faults.maybe_arm_from_env()
        if self.watchdog is not None:
            self.watchdog.start()
        self._thread = threading.Thread(target=self._run, name="batching-loop", daemon=True)
        self._thread.start()
        # one structured line deployments verify serving config from
        # (the engine-side twin — mesh shape, buckets warmed — comes from
        # warmup_engine)
        engine = self.engine
        self.telemetry.startup_log(
            "scheduler_start",
            n_lanes=engine.n_lanes,
            pipeline_depth=getattr(engine, "pipeline_depth", 0),
            pipelined=self.pipelined,
            fused_prefill=self._fused_ok(),
            multi_step=self.multi_step,
            speculative=self.speculative,
            # drafts verify INSIDE the chain only while the ring lag is
            # <= 1 (the host's carry candidate aligns one step behind):
            # true at the default depth 2; deeper rings trade in-chain
            # speculation for extra overlap — surfaced here so the
            # trade-off is visible in logs, not silent
            spec_in_chain=bool(
                self._spec_pl_ok()
                and self.pipelined
                and getattr(self.engine, "pipeline_depth", 0) == 2
            ),
            prefix_min_tokens=self.prefix_min_tokens,
            # a model whose lanes carry a state overwritten in place: a
            # lane copy would carry the source's state at its last
            # position and a verify step advance it by rejected rows, so
            # both are declined whatever the two settings above say
            **({"recurrent_state": True, "prefix_reuse": "declined",
                "speculation": "declined"} if self._recurrent_state else {}),
            # an indexer chooses the rows attention reads: said with what is
            # declined for it (a verify step's rows have no selection)
            **({"attention_path": "sparse_topk", "index_topk": self._index_topk,
                "speculation": "declined"} if self._index_topk else {}),
            **({"experts_held": self._experts_held} if self._experts_held else {}),
            # compile stability: True once warmup_engine armed the
            # recompile witness (analysis/jitcheck.py) — the normal
            # make_scheduler order warms before start(), so a False here
            # means this scheduler is serving UNWARMED programs and
            # every first dispatch will compile mid-request
            jitcheck_armed=jitcheck.armed(),
            queue_capacity=getattr(self.queue, "capacity", None),
            queue_timeout_s=self.deadlines.queue_timeout_s,
            request_budget_s=self.deadlines.request_budget_s,
            breaker_threshold=self.breaker.threshold,
            step_deadline_s=(
                self.watchdog.deadline_s if self.watchdog is not None else 0
            ),
            faults_armed=faults.armed(),
        )

    def stop(self) -> None:
        """Clean shutdown — the reference's loop never terminates (defect (d)).
        Raises if the loop thread outlives the join timeout (a hung device
        dispatch): silently dropping the reference would leak a live thread
        still mutating lanes and the KV cache."""
        self._stop.set()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=30)
            if thread.is_alive():
                raise RuntimeError(
                    "batching loop failed to stop within 30s; thread is still "
                    "alive (likely a hung device dispatch) and still owns the "
                    "lanes — not dropping the reference"
                )
            self._thread = None
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.journal is not None:
            # barrier, not close: the journal outlives scheduler restarts
            # (its creator — runtime_setup / the test — owns closing it)
            self.journal.flush()
        # resource-leak witness (analysis/leakcheck.py): the loop joined
        # and _resolve_exit settled every lane, so every count below is
        # zero on a clean stop — anything held is an acquire whose
        # release lost an exit path. Counted always; raises under
        # DLLAMA_LEAKCHECK=1.
        leakcheck.check_drained("scheduler stop", self.leak_counts())

    def leak_counts(self) -> dict[str, int]:
        """Authoritative live counts for every resource kind this
        scheduler owns (the declared _dlint_acquires surfaces): lane-held
        KV pages, session-mirror tickets, open journal marks, pending
        device ops. The leak witness's drain snapshot — also surfaced on
        /stats as ``resources_live`` between drains."""
        counts = {"session_records": len(self._session_records)}
        with self._device_ops_lock:
            counts["device_ops"] = len(self._device_ops)
        pool_stats = getattr(self.engine, "pool_stats", None)
        if callable(pool_stats):
            pstats = pool_stats() or {}
            counts["kv_lane_pages"] = int(
                pstats.get("pool_pages_in_use", 0)
            )
            # host-page kind (tiered residency): swap-outs the pool
            # staged but no engine drain has taken to the host tier —
            # a non-zero drained count means an eviction path lost its
            # drain call and the pages' payloads leaked in limbo
            counts["kv_swap_pending"] = int(
                pstats.get("pool_swap_pending", 0)
            )
        if self.journal is not None:
            counts["journal_marks"] = int(
                self.journal.stats().get("journal_open_marks", 0)
            )
        return counts

    def drain(self, timeout: float | None = None) -> bool:
        """Graceful shutdown (serving/drain.py): stop admitting — submit()
        sheds with AdmissionRejected("draining") and /health flips to 503 —
        let queued + active work finish or hit its deadline, then join the
        loop thread. Returns True on a clean drain; on ``timeout`` the
        remainder is force-cancelled (every future still resolves)."""
        return drain_scheduler(self, timeout)

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def submit(self, request: Request) -> Request:
        if self._draining.is_set():
            self._shed_draining()
        if not self.breaker.allow():
            # engine unhealthy (open circuit): shed BEFORE the queue so a
            # broken engine degrades into fast 503s + Retry-After instead
            # of a backlog of clients waiting on an engine that cannot
            # serve them. Half-open probes pass through here.
            note = getattr(self.queue, "note_rejection", None)
            if note is not None:
                note("breaker_open")
            raise AdmissionRejected(
                "breaker_open", retry_after_s=self.breaker.retry_after_s()
            )
        if request.submitted_at is None:
            request.submitted_at = time.monotonic()
        # attach the lifecycle record BEFORE the push: the loop thread may
        # pop and admit this request before push() even returns here
        self.telemetry.on_submit(request)
        try:
            self.queue.push(request)
        except AdmissionRejected:
            request.submitted_at = None  # rejected: never entered the queue
            raise
        if self._draining.is_set():
            # raced with drain(): the flag flipped during the push, so the
            # loop may already have taken its exit snapshot without seeing
            # this request. Pull it back out and shed; if it's already gone,
            # the loop popped it and will serve it normally.
            remove_if = getattr(self.queue, "remove_if", None)
            if remove_if is not None and remove_if(lambda r: r is request):
                request.submitted_at = None
                self._shed_draining()
        return request

    def _shed_draining(self) -> None:
        note = getattr(self.queue, "note_rejection", None)
        if note is not None:
            note("draining")  # drain-shed load shows up in /stats too
        raise AdmissionRejected("draining", retry_after_s=5.0)

    def build_recovered_request(self, entry) -> Request:
        """Materialize a journal entry (serving/journal.JournalEntry)
        back into a Request for deterministic replay — called by
        serving/recovery.py, which stays runtime-free. The ORIGINAL
        request id is kept (it is the SSE reattach key) and the fresh-id
        counter advances past it so post-recovery admissions never
        collide; the journaled RESOLVED seed rides in ``seed``, so the
        lane re-derives the identical ``fold_in(seed, pos)`` stream the
        crashed process was sampling."""
        ensure_request_id_floor(entry.request_id)
        return Request(
            prompt=entry.prompt,
            max_tokens=entry.max_tokens,
            temperature=entry.temperature,
            topp=entry.topp,
            seed=entry.seed,
            stop=list(entry.stop),
            add_bos=entry.add_bos,
            add_special_tokens=entry.add_special_tokens,
            user_id=entry.user,
            priority=entry.priority,
            queue_timeout_s=entry.queue_timeout_s,
            budget_s=entry.budget_s,
            response_format=entry.response_format,
            api_kind=entry.kind,
            recovered=True,
            trace=entry.trace,
            id=entry.request_id,
        )

    def export_session(self, request_id: int) -> dict | None:
        """Export a live session's migration ticket (fleet/migrate.py,
        ``GET /admin/session/<id>``): its admit wire record — prompt
        tokens, sampler params with the RESOLVED seed, QoS class,
        deadlines (serving/journal.admit_record) — plus a ``watermark``
        (tokens STREAMED so far, the last of which may not be committed
        yet; informational: the migration target regenerates from 0,
        re-buffers, and the client's ``Last-Event-ID`` picks the resume
        point, so a token streamed here is never emitted twice nor lost).
        ``None`` for unknown/finished requests — only an
        ADMITTED request has a resolved seed to regenerate from; queued
        ones are re-sent by the router, not migrated."""
        got = self._session_records.get(int(request_id))
        if got is None:
            return None
        rec, req = got
        out = dict(rec)
        out["watermark"] = len(req.generated_tokens)
        return out

    def run_device_op(self, fn: Callable, timeout_s: float = 10.0):
        """Run ``fn()`` on the batching-loop thread at its next step
        boundary and return its result (exceptions re-raise here, with
        their original type). Device-touching admin work — the disagg
        page export/import (``export_kv_page`` / ``import_kv_page``) —
        must NOT run on the calling HTTP thread: the pipelined chain
        donates the cache pytree into every dispatch, so an admin-thread
        read of ``engine.cache`` mid-chain hits a deleted buffer, and a
        write would fork the pytree against the next dispatch. At the
        loop's step boundary the consume half has rebound the live
        arrays and nothing is in flight against them.

        Runs ``fn`` inline when the loop is not running (tests, a
        drained server — nothing to race) or when already ON the loop
        thread. Raises ``TimeoutError`` if the loop never reaches a
        boundary within ``timeout_s`` (wedged step; callers surface it
        as a typed admin error, the router falls back monolithic)."""
        thread = self._thread
        if (
            thread is None
            or not thread.is_alive()
            or threading.current_thread() is thread
        ):
            return fn()
        box: dict = {}
        done = threading.Event()
        with self._device_ops_lock:
            self._device_ops.append((fn, box, done))
        if not done.wait(timeout_s):
            raise TimeoutError(
                "device op timed out waiting for a scheduler step boundary"
            )
        if "error" in box:
            raise box["error"]
        return box.get("value")

    def _drain_device_ops(self) -> None:
        """Loop-thread half of :meth:`run_device_op`: execute pending
        device ops at the step boundary. Op exceptions land in the
        caller's box (re-raised on ITS thread) — never in the serving
        loop, so a bad bundle cannot trip engine containment."""
        while True:
            with self._device_ops_lock:
                if not self._device_ops:
                    return
                fn, box, done = self._device_ops.pop(0)
            try:
                box["value"] = fn()
            except BaseException as e:  # noqa: BLE001 — relayed to caller
                box["error"] = e
            finally:
                done.set()

    def export_session_pages(self, request_id: int) -> dict | None:
        """Export a live session's committed KV-page bundle (disagg/
        kvtransfer.py, ``GET /admin/kvpages/<id>``): the prompt's
        registered prefix chain out of the paged pool, each page's
        payload integrity-hashed. ``None`` for unknown/finished requests
        and on contiguous engines (there are no pages to ship — the
        hand-off degrades to ticket-only migration, which re-prefills).
        Only FULL committed blocks export (immutable by the pool's
        granularity rule), so the bytes are stable while this replica
        keeps decoding the session."""
        if getattr(self.engine, "kvpool", None) is None:
            return None
        got = self._session_records.get(int(request_id))
        if got is None:
            return None
        from ..disagg.kvtransfer import export_bundle

        rec, _req = got
        tokens = list(rec.get("tokens") or ())
        # through the loop thread: export_kv_page reads engine.cache,
        # which the in-flight pipelined chain donates (run_device_op)
        return self.run_device_op(
            lambda: export_bundle(self.engine.kvpool, self.engine, tokens)
        )

    # -- internals ----------------------------------------------------------

    def _free_lane_indices(self) -> list[int]:
        return [i for i, l in enumerate(self._lanes) if l.request is None]

    def _paged_commit(self, lane_idx: int) -> None:
        """Register lane ``lane_idx``'s newly completed FULL blocks into
        the paged pool's prefix tree (host dict walk, incremental — a
        no-op for contiguous engines and for unfinished blocks). Called
        wherever ``_lane_kv`` grows: commits only ever trail the
        committed watermark, so shared pages are never write targets."""
        if getattr(self.engine, "kvpool", None) is not None:
            self.engine.paged_commit(lane_idx, self._lane_kv[lane_idx])

    def _paged_release(self, lane_idx: int, park: bool) -> None:
        """Release a lane's pages at request end: ``park=True`` keeps its
        tree-registered blocks resident for copy-free follow-ups (the
        oversubscription lever — resident sessions outnumber lanes),
        ``park=False`` frees everything (failure path: contents are not
        trusted). No-op for contiguous engines."""
        if getattr(self.engine, "kvpool", None) is not None:
            self.engine.paged_finish(lane_idx, park=park)

    def _grammar_release(self, lane: _Lane) -> None:
        """Detach a lane's grammar at request end (the tables PARK in the
        slab for the next same-schema admission). Never raises —
        containment paths call this too."""
        if lane.grammar is not None:
            try:
                self.engine.grammar_detach(lane.grammar.key)
            except Exception:  # noqa: BLE001 — release must not throw
                pass

    def _g_adv(self, lane: _Lane, tok: int) -> None:
        """Advance a constrained lane's HOST automaton mirror by one
        emitted token — called exactly once per NEW emitted token, by the
        stream half (``_stream``) at the readback that delivers it, so the
        mirror equals the device carry on the sync paths and trails it by
        the ring lag on the pipelined chain (where it only steers draft
        pre-filtering; the device state is authoritative)."""
        if lane.grammar is not None:
            lane.g_state = lane.grammar.next_state(lane.g_state, tok)

    def _g_states_sync(self, active) -> tuple[np.ndarray | None, bool]:
        """(per-lane grammar-state vector, any-constrained flag) for a
        synchronous dispatch: the host mirror is exact here. None when no
        lane is constrained — the engine defaults to all-FREE."""
        constrained = [
            (i, l) for i, l in active if l.grammar is not None
        ]
        if not constrained:
            return None, False
        gs = np.zeros(self.engine.n_lanes, np.int32)
        for i, lane in constrained:
            gs[i] = lane.g_state
        return gs, True

    def _count_masked_step(self) -> None:
        with self.engine.stats.lock:
            self.engine.stats.grammar_masked_steps += 1

    def _device_rows(self, live: dict, meta) -> np.ndarray:
        """Where each live lane's row is on the DEVICE at a pipelined
        dispatch: what the host has consumed (``lane.pos``) plus the lane's
        steps still in flight (``meta``: one entry a dispatched step, its
        first field that step's live lanes); every other lane parked."""
        at = np.full(self.engine.n_lanes, self.engine.config.seq_len, np.int32)
        for i, lane in live.items():
            at[i] = lane.pos
        for step_lanes, *_ in meta:
            for i, lane in step_lanes:
                if live.get(i) is lane:
                    at[i] += 1
        return at

    def _count_attention_rows(self, positions, steps: int = 1) -> None:
        """Bump the attn_kv_rows_* counters for a dispatch of ``steps`` decode
        steps whose lanes stand at ``positions`` (parked: ``seq_len``) and
        advance one row a step: host integers only, no device value."""
        engine = self.engine
        seq_len = engine.config.seq_len
        block = getattr(engine, "decode_attention_block", None)
        if self._index_topk:
            # an indexer chose: a live lane's step attends min(rows held,
            # index_topk) rows of the rows it holds, a parked lane none
            # dlint: ok[host-sync] the host's own lane positions (numpy ints), no device value
            pos = np.asarray(positions, np.int64)
            held = [pos[pos < seq_len] + s + 1 for s in range(steps)]
            whole = int(sum(h.sum() for h in held))
            read = int(sum(np.minimum(h, self._index_topk).sum() for h in held))
        else:
            whole = len(positions) * seq_len * steps
            read = whole if block is None else sum(
                rows_read(positions + s, seq_len, block) for s in range(steps)
            )
        # a state-space layer advances every live lane's running sum a step
        ssm = self._n_ssm_layers and self._n_ssm_layers * steps * sum(
            1 for p in positions if p < seq_len)
        # a window layer's ring, in rows of one such layer: what its read
        # fetched. What a plane read as the full-context kind's would have
        # fetched is `read` itself (the pair above stays that kind's)
        ring_read = full_needed = window_needed = 0
        at = None
        if (self._window or self._linear_step_bytes or self._delta_step_bytes
                or self._sparse_sizes):
            # dlint: ok[host-sync] the host's own lane positions (numpy ints), no device value
            at = np.asarray(positions, np.int64)[:, None] + np.arange(steps)[None, :]
            at = at[at < seq_len]  # a lane's steps inside the context
        if self._window:
            # the rows either kind's read attends: pos + 1 and min(pos + 1, W)
            full_needed = int((at + 1).sum())
            window_needed = int(np.minimum(at + 1, self._window).sum())
            ring_block = getattr(engine, "decode_ring_block", None)
            ring_read = (
                len(positions) * getattr(engine, "ring_rows", 0) * steps if ring_block is None
                else sum(ring_rows_read(positions + s, seq_len, self._window, ring_block)
                         for s in range(steps)))
        blocks_read = blocks_held = choosing = live_steps = 0
        if at is not None:  # the lanes' steps were laid out above: one condition, not two
            live_steps = int(at.size)
            if self._sparse_sizes:
                attended, held = blocks_attended(at, self._sparse_sizes)
                blocks_read, blocks_held = int(attended.sum()), int(held.sum())
                choosing = int((at >= self._sparse_sizes.dense_len).sum())
                # what a kv head's attention fetched, in rows of one plane
                read = blocks_read * self._sparse_sizes.block_size
        with engine.stats.lock:
            engine.stats.attn_kv_rows_read += read
            engine.stats.attn_kv_rows_whole += whole
            engine.stats.ssm_lane_steps += ssm
            engine.stats.linear_state_bytes_moved += live_steps * self._linear_step_bytes
            engine.stats.delta_state_bytes_moved += live_steps * self._delta_step_bytes
            engine.stats.attn_blocks_read += blocks_read
            engine.stats.attn_blocks_held += blocks_held
            engine.stats.sparse_lane_steps += choosing
            if self._window:
                engine.stats.attn_window_rows_read += ring_read
                engine.stats.attn_full_rows_read += read
                engine.stats.attn_window_rows_plane += read
                engine.stats.attn_full_rows_needed += full_needed
                engine.stats.attn_window_rows_needed += window_needed

    def occupancy(self) -> tuple[int, int]:
        """(busy lanes, total lanes) — public surface for /stats."""
        return (
            sum(1 for l in self._lanes if l.request is not None),
            len(self._lanes),
        )

    def qos_stats(self) -> dict:
        """QoS counters for /stats: queue depth/wait/rejections (when the
        queue tracks them) plus deadline enforcement and drain state."""
        out = {
            "draining": self.draining,
            "queue_timeouts": self.queue_timeouts,
            "budget_timeouts": self.budget_timeouts,
            # failure containment: engine-scoped containment rounds, the
            # breaker state machine, and the watchdog (0 trips when off)
            "engine_failure_rounds": self.engine_failures,
        }
        out.update(self.breaker.stats())
        if self.watchdog is not None:
            out.update(self.watchdog.stats())
        # crash durability: journal write accounting and — after a
        # --recover-journal restart — the replay counters; every field
        # is bridged to /metrics as a dllama_stats_* gauge (plus the
        # delta-fed native counters in telemetry/hub.bridge_stats), so
        # the two endpoints reconcile field-for-field
        if self.journal is not None:
            out.update(self.journal.stats())
        if self.recovery is not None:
            out.update(self.recovery.stats())
        stats = getattr(self.queue, "stats", None)
        if callable(stats):
            out.update(stats())
        # paged KV pool pressure (occupancy, prefix sharing, COW,
        # park/evict = drop-rebuild, exhaustion sheds): every field lands
        # on /stats and is bridged to /metrics as a dllama_stats_* gauge
        pool = getattr(self.engine, "pool_stats", None)
        if callable(pool):
            out.update(pool())
        # grammar slab pressure (schemas installed/live, state occupancy):
        # bridged to /metrics as dllama_stats_* gauges like every field
        gram = getattr(self.engine, "grammar_stats", None)
        if callable(gram):
            out.update(gram())
        return out

    def _on_watchdog_trip(self, waited_s: float) -> None:
        """Watchdog callback (runs on the watchdog thread): a dispatched
        step made no progress within the deadline. Trip the breaker —
        /health flips unhealthy and new work sheds — and flag the
        pipelined chain to abort at its next host-side opportunity (a
        slow-but-alive step eventually returns; the chain must not keep
        extending behind it). On pods the watchdog itself then crashes
        the process (fatal=True) — deliberate death over silent desync."""
        self.breaker.trip(
            f"watchdog: no step progress within {waited_s:.1f}s"
        )
        self._wd_abort.set()
        self.telemetry.on_watchdog_trip(
            waited_s,
            fatal=self.watchdog.fatal if self.watchdog is not None else False,
        )

    def _resolve_unadmitted(self, req: Request, reason: str) -> None:
        """Finish a request that never claimed a lane (queue timeout, cancel
        while queued): empty text, typed finish_reason."""
        req.state = RequestState.DONE
        req.finish_reason = reason
        self.telemetry.on_unadmitted(req, reason)
        if not req.future.done():
            req.future.set_result(req.generated_text)

    def _shed_unadmitted(self, req: Request) -> None:
        """Fail a request the drain window flushed before it ever claimed a
        lane: the client got no service, so it must see a retryable 503
        (AdmissionRejected, same shape submit() sheds with) — resolving it
        as an empty 200 "cancelled" would read as the model's answer and
        never be retried."""
        req.state = RequestState.FAILED
        req.finish_reason = "cancelled"
        self.telemetry.on_unadmitted(req, "shed")
        if not req.future.done():
            req.future.set_exception(AdmissionRejected("draining", retry_after_s=5.0))

    def _mirror_admit(self, req: Request, admit_kw: dict) -> None:
        """Publish the live-session mirror entry (the fleet migration
        ticket). Loop thread only; entries are built whole and assigned
        with a single-key dict op (GIL-atomic) so export_session can
        read whole tickets from HTTP threads. The declared acquire half
        of the session-record lifecycle (_dlint_acquires)."""
        self._session_records[req.id] = (admit_record(**admit_kw), req)

    def _mirror_finish(self, req: Request) -> None:
        """Retire the mirror entry — the declared release half; idempotent
        (a drain force-cancel may race a normal finish)."""
        self._session_records.pop(req.id, None)

    def _fail_request(self, lane_idx: int, req: Request, error: str,
                      exc: BaseException | None = None) -> None:
        """Fail ONE request with ``finish_reason="error"`` and reclaim its
        lane: the request-scoped containment unit (also the per-lane body
        of engine-scoped containment). The lane's resident-KV map is
        DISCARDED — after a failed dispatch the cache contents are
        unknown, and prefix caching must never reuse garbage. The
        future's exception carries the request_id (EngineFailure) unless
        the original exception is more specific (a tokenizer ValueError
        maps to a 400, not a 500)."""
        req.state = RequestState.FAILED
        req.error = error
        req.finish_reason = "error"
        # failed contents are final: the session can no longer migrate
        self._mirror_finish(req)
        self._grammar_release(self._lanes[lane_idx])
        self._lanes[lane_idx] = _Lane()
        self._lane_kv[lane_idx] = []
        try:
            # paged: free the lane's pages WITHOUT parking — after a
            # failed dispatch the cache contents are unknown, and the
            # prefix tree must never serve garbage
            self._paged_release(lane_idx, park=False)
            self.engine.reset_lane(lane_idx)
        except Exception:  # noqa: BLE001 — containment must not throw
            pass
        self.telemetry.on_error(req, lane_idx, error)
        if not req.future.done():
            req.future.set_exception(
                exc if exc is not None
                else EngineFailure(error, request_id=req.id)
            )
        if self.journal is not None:
            # recorded after the future resolves, like _finish: a lost
            # "error" finish record merely re-runs the request on
            # recovery, which is always safe
            self.journal.record_finish(
                req.id, "error",
                phases=(req.summary or {}).get("phases"),
            )

    def _sweep_queue(self, now: float) -> None:
        """Resolve queued requests that expired or were cancelled while
        waiting — without this, a saturated server (no lane ever frees, so
        nothing is ever popped) would hold its backlog open forever.
        Throttled to ~20 Hz: the walk is O(queue depth) under the queue
        lock, far too costly to contend with submit() on every decode
        step, and 50ms of extra expiry/cancel latency is immaterial."""
        remove_if = getattr(self.queue, "remove_if", None)
        if remove_if is None:  # custom queue without removal: pop-time checks still apply
            return
        if self.queue.empty() or now - self._last_sweep < 0.05:
            return
        self._last_sweep = now
        for req in remove_if(
            lambda r: r._cancelled.is_set()
            or queue_expired(r, self.deadlines, now)
        ):
            if req._cancelled.is_set():
                self._resolve_unadmitted(req, "cancelled")
            else:
                self.queue_timeouts += 1
                self._resolve_unadmitted(req, "timeout")

    def _claim_next(self, free: list[int], wait_s: float = 0.0):
        """Pop ONE queued request and claim a lane for it — the shared
        admission body behind the synchronous ``_admit`` loop and the
        in-chain ``_claim_admissions``: cancel/expiry resolution at pop
        time, the ``admitted_at`` stamp, tokenize+seed via
        ``_start_request`` with its failure handling. Returns the claimed
        lane index, ``None`` when the pop found nothing (stop polling), or
        ``-1`` when the popped request was resolved without taking a lane
        (cancelled/expired/failed — keep popping)."""
        req = self.queue.pop(timeout=wait_s)
        if req is None:
            return None
        now = time.monotonic()
        if self._observe_wait_at_claim:
            # bare-FIFO fallback: observe at pop time like the QosQueue
            # observer does, cancelled/expired pops included, so both
            # queue kinds feed the histogram the same population
            self.telemetry.on_queue_pop(req, now)
        if req._cancelled.is_set():
            self._resolve_unadmitted(req, "cancelled")
            return -1
        if queue_expired(req, self.deadlines, now):
            self.queue_timeouts += 1
            self._resolve_unadmitted(req, "timeout")
            return -1
        req.admitted_at = now
        lane_idx = free.pop(0)
        self.telemetry.on_admit(req, lane_idx)
        try:
            self._start_request(lane_idx, req)
        except Exception as e:
            # tokenization / validation errors fail ONLY this request
            # (finish_reason="error", original exception preserved so the
            # HTTP layer can 400 a ValueError); an engine-scoped raise
            # (the prefix-cache lane copy is a device op) fails it too,
            # then propagates to the supervisor for full containment
            self._fail_request(lane_idx, req, str(e), exc=e)
            if classify_failure(e) == "engine":
                raise
            self.breaker.record_request_failure()
            return -1
        return lane_idx

    def _admit(self, wait_s: float = 0.0) -> None:
        free = self._free_lane_indices()
        while free:
            claimed = self._claim_next(free, wait_s)
            wait_s = 0.0  # only the first pop may park; the rest are polls
            if claimed is None:
                return

    def _start_request(self, lane_idx: int, req: Request) -> None:
        """Tokenize and claim a lane. Prompt processing itself happens one
        bucket per scheduler iteration in ``_prefill_step`` so concurrent
        decoding lanes are never stalled by a long admission prefill
        (the reference stalls all lanes, src/app.cpp:360-366)."""
        req.state = RequestState.PROMPT_PROCESSING
        tokens = self.tokenizer.encode(
            req.prompt, add_bos=req.add_bos, add_special_tokens=req.add_special_tokens
        )
        if not tokens:
            raise ValueError("prefill needs at least one token (empty prompt)")
        max_ctx = self.engine.config.seq_len
        if len(tokens) >= max_ctx:
            # keep the tail (the reference just aborts; truncation serves better)
            tokens = tokens[-(max_ctx - req.max_tokens - 1) :] if max_ctx > req.max_tokens + 1 else tokens[-max_ctx + 1 :]
        req.n_prompt_tokens = len(tokens)

        # prefix caching. Paged engines (engine.kvpool set): admission
        # charges the lane's whole potential range in PAGES up front and
        # the pool's prefix tree serves shared leading blocks by refcount
        # bump on the SAME physical pages — zero HBM copies, plus at most
        # one single-page copy-on-write at the divergent block. Contiguous
        # engines: if some lane's resident KV (finished lanes included —
        # their cache persists until overwritten) shares a long enough
        # prompt prefix, copy that lane's KV (an HBM move, orders of
        # magnitude cheaper than prefill) and prefill only the tail. A
        # chat follow-up landing on its own previous lane hits with
        # src == dst, which copy_lane no-ops.
        start = 0
        if getattr(self.engine, "kvpool", None) is not None:
            # +1 reserves the slot the boundary token's own KV write needs
            # when generation runs to max_tokens exactly
            reserve = min(len(tokens) + req.max_tokens + 1, max_ctx)
            # per-request swap-in attribution (phases record): the
            # engine's cumulative swap_in_ms only moves inside THIS
            # paged_admit call on this loop thread, so the delta is
            # exactly the host-tier reactivation cost this admission paid
            swap_ms0 = float(getattr(self.engine, "swap_in_ms", 0.0) or 0.0)
            try:
                start = self.engine.paged_admit(
                    lane_idx, list(tokens), reserve,
                    min_share_tokens=self.prefix_min_tokens,
                )
                swap_ms1 = float(
                    getattr(self.engine, "swap_in_ms", 0.0) or 0.0
                )
                if swap_ms1 > swap_ms0:
                    self.telemetry.trace_of(req).swap_in_s = (
                        (swap_ms1 - swap_ms0) / 1e3
                    )
            except PoolExhausted as e:
                # typed retryable shed (the 429/503 + Retry-After shape
                # submit() sheds with), never a 500: a pool pinned by
                # active lanes is load, not engine failure. Counted on
                # the QoS rejection surface like every other shed reason
                # (queue_full/draining/breaker_open), so dashboards on
                # the rejection counters see paged-pool sheds too. The
                # tiered-residency distinction rides the reason string:
                # "host_tier_full" means the swap tier was enabled AND
                # at budget when the shed fired — the operator's lever
                # is --kv-host-bytes, not --kv-pool-pages.
                reason = (
                    "host_tier_full"
                    if getattr(e, "host_tier_full", False)
                    else "pool_exhausted"
                )
                note = getattr(self.queue, "note_rejection", None)
                if note is not None:
                    note(reason)
                raise AdmissionRejected(
                    reason, retry_after_s=1.0
                ) from e
        elif (
            self.prefix_min_tokens > 0
            and getattr(self.engine, "copy_lane", None) is not None
        ):
            best_lane, best_lcp = -1, 0
            for j, kv in enumerate(self._lane_kv):
                if not kv:
                    # discarded resident map (_fail_request after a failed
                    # dispatch, or a never-used lane): probing the dead
                    # entry is wasted work and must never win the scan
                    continue
                lcp = _common_prefix_len(tokens, kv)
                if lcp > best_lcp:
                    best_lane, best_lcp = j, lcp
            best_lcp = min(best_lcp, len(tokens) - 1)  # >= 1 token to prefill
            if best_lcp >= self.prefix_min_tokens:
                if self._recurrent_state:
                    # a copied lane would carry the source's state at ITS
                    # last position, not at the shared prefix: the prompt
                    # is prefilled whole, and the decline is counted
                    with self.engine.stats.lock:
                        self.engine.stats.prefix_reuse_declined += 1
                else:
                    self.engine.copy_lane(best_lane, lane_idx,
                                          prefix_len=best_lcp)
                    start = best_lcp
        if start > 0:  # one accounting site for both layouts
            self.telemetry.on_prefix_hit(req, start)
            with self.engine.stats.lock:
                self.engine.stats.prefix_hits += 1
                self.engine.stats.prefix_tokens_saved += start
        self._lane_kv[lane_idx] = list(tokens[:start])

        lane = self._lanes[lane_idx]
        lane.request = req
        lane.pos = start
        lane.pending = list(tokens[start:])
        lane.drafter = NgramDraftIndex(tokens)  # seed with the prompt
        # unseeded requests draw OS entropy (utils/seeds.py), not the wall
        # clock: two requests admitted in the same clock tick must not
        # sample identical streams, and NTP steps must not replay seeds
        lane.seed = (
            req.seed if req.seed is not None else fresh_seed()
        ) & 0xFFFFFFFF
        # the on-device sampler is full-vocab exact, so host-exact survives
        # only as the host_sampling=True escape hatch (bit-exact reference
        # xorshift streams); wide-nucleus/high-temp requests stay on device.
        # Constrained requests stay on device UNCONDITIONALLY: the grammar
        # mask lives inside the compiled step, and a host xorshift draw
        # over unmasked logits could emit an illegal token.
        lane.host_exact = self.host_sampling and req.response_format is None
        if lane.host_exact and req.temperature > 0.0:
            with self.engine.stats.lock:
                self.engine.stats.host_exact_lanes += 1
        # structured output (grammar/): compile + attach the automaton
        # BEFORE the admit record, so a schema that fails to compile
        # fails the request with no journal entry to resurrect. The
        # ValueError family (GrammarError, unsupported engine) is
        # request-scoped -> HTTP 400; a slab exhausted by live schemas
        # sheds retryably like the paged pool.
        if req.response_format is not None:
            from ..grammar.slab import GrammarSlabFull

            try:
                lane.grammar = self.engine.grammar_attach(
                    req.response_format
                )
            except GrammarSlabFull as e:
                note = getattr(self.queue, "note_rejection", None)
                if note is not None:
                    note("grammar_slab_full")
                raise AdmissionRejected(
                    "grammar_slab_full", retry_after_s=1.0
                ) from e
            lane.g_state = lane.grammar.start_state
        lane.sampler = Sampler(
            self.engine.config.vocab_size, req.temperature, req.topp, lane.seed
        )
        stops = list(req.stop) or self._chat_stops.stops
        lane.eos = EosDetector(
            self.tokenizer.eos_token_ids, stops, self.eos_padding[0], self.eos_padding[1]
        )
        lane.decoder = self.tokenizer.make_stream_decoder()
        # admit record LAST, with the RESOLVED seed (an unseeded request
        # just drew OS entropy into lane.seed): everything a deterministic
        # replay needs, and nothing is recorded for a request that failed
        # tokenization above (no admit record -> nothing to resurrect or
        # migrate). ONE kwargs set feeds both consumers — the journal's
        # on-disk record and the live-session mirror export_session serves
        # as the fleet migration ticket — so the two cannot drift.
        admit_kw = dict(
            request_id=req.id, prompt=req.prompt, tokens=list(tokens),
            max_tokens=req.max_tokens, temperature=req.temperature,
            topp=req.topp, seed=int(lane.seed), stop=list(req.stop),
            add_bos=req.add_bos,
            add_special_tokens=req.add_special_tokens,
            user=req.user_id, priority=int(req.priority),
            queue_timeout_s=req.queue_timeout_s, budget_s=req.budget_s,
            stream=req.on_delta is not None, kind=req.api_kind,
            response_format=req.response_format, trace=req.trace,
        )
        self._mirror_admit(req, admit_kw)
        if self.journal is not None:
            # the call only enqueues — the journal's writer thread does
            # the file I/O off this loop
            self.journal.record_admit(**admit_kw)

    def _prefill_step(self) -> bool:
        """Advance ONE admitting lane by one prompt bucket (round-robin).
        Returns True when a chunk was processed."""
        n = len(self._lanes)
        admitting = [
            i for i in range(n)
            if self._lanes[i].request is not None and self._lanes[i].pending
        ]
        if not admitting:
            return False
        # round-robin so several admitting prompts make progress together
        lane_idx = min(admitting, key=lambda i: (i - self._prefill_rr) % n)
        self._prefill_rr = (lane_idx + 1) % n
        lane = self._lanes[lane_idx]
        req = lane.request
        chunk = lane.pending[: self.engine.max_chunk(lane.pos)]
        t_chunk = time.perf_counter()
        self.telemetry.on_prefill_dispatch(req, time.monotonic())
        wd = self.watchdog
        if wd is not None:
            wd.begin_step()
        try:
            logits, greedy, sampled = self.engine.prefill_chunk(
                lane_idx, chunk, lane.pos,
                temp=0.0 if lane.host_exact else req.temperature,
                topp=req.topp, seed=lane.seed,
                # boundary token (the first generated one, on the final
                # chunk) samples under the automaton's start-state mask
                g_state=lane.g_state,
            )
        except Exception as e:
            # request-scoped (chunk validation, the ValueError family):
            # fail this request only; engine-scoped (a dispatch raise):
            # propagate to the supervisor, which flushes the pipeline and
            # fails every affected lane — this one included
            if classify_failure(e) == "engine":
                raise
            self._fail_request(lane_idx, req, str(e), exc=e)
            self.breaker.record_request_failure()
            return True
        finally:
            if wd is not None:
                wd.step_done()
        self.breaker.record_success()
        self.telemetry.on_prefill_chunk(
            req, lane_idx, t_chunk, len(chunk),
            bucket=self.engine.bucket_for(len(chunk)),
            p_start=lane.pos, final=len(chunk) == len(lane.pending))
        lane.pos += len(chunk)
        lane.pending = lane.pending[len(chunk):]
        self._lane_kv[lane_idx].extend(chunk)  # committed: prefix-cacheable
        self._paged_commit(lane_idx)
        if lane.pending:
            return True
        # prompt complete: pick the first generated token and stream it
        # here, at the readback that delivers it (first_token_hold_ms is
        # the stream work alone); the next decode step is fed it and its
        # readback commits it
        self.telemetry.on_prefill_done(req, time.monotonic())
        if req.temperature == 0.0:
            first = int(greedy)
        elif lane.host_exact:
            # dlint: ok[host-sync] host-exact lane: one [n,vocab] f32 batch at prompt end, counted by all_logits
            first = lane.sampler.sample(self.engine.all_logits(logits))
        else:
            first = int(sampled)  # sampled inside the compiled prefill step
        lane.next_token = first
        req.state = RequestState.GENERATING
        self._stream(lane_idx, lane, first)
        return True

    def _contained(self, half, lane_idx: int, lane: _Lane, tok: int) -> bool:
        """Run one half of a token's host work (``_stream_inner`` /
        ``_commit_inner``) request-scoped: everything in either is
        host-side per-request work (stream decoder, EOS detector, delta
        callback, the resident-KV map), so a raise of ANY type says
        nothing about engine health and fails this request only, while
        the batch keeps decoding. Returns False when it did."""
        req = lane.request
        try:
            return half(lane_idx, lane, req, tok)
        except Exception as e:  # noqa: BLE001 — request-scoped by construction
            self._fail_request(lane_idx, req, str(e), exc=e)
            self.breaker.record_request_failure()
            return False

    def _stream(self, lane_idx: int, lane: _Lane, tok: int) -> bool:
        """STREAM one generated token, at the readback that first gives it
        to the host: count it, stamp it, advance the grammar mirror,
        stream-decode, EOS/stop detection, the delta callback. A token
        that ends the stream (EOS, a stop string, ``max_tokens``) sets
        ``lane.ended``; the lane is released where that token COMMITS.
        Returns False when the request failed (a raise in here)."""
        return self._contained(self._stream_inner, lane_idx, lane, tok)

    def _stream_inner(self, lane_idx: int, lane: _Lane, req: Request,
                      tok: int) -> bool:
        req.generated_tokens.append(tok)
        # per-token stamp: first token observes TTFT, later ones the
        # inter-token gap (multi-step/spec bursts land near-zero gaps —
        # that IS when their stream deltas reach the client)
        self.telemetry.on_token(req)
        self._g_adv(lane, tok)
        piece = lane.decoder.decode(tok)
        result = lane.eos.append(tok, piece)
        if result == EosResult.EOS:
            lane.ended = "stop"
            return True
        if result == EosResult.NOT_EOS:
            delta = lane.eos.get_delta()
            if delta:
                req.generated_text += delta
                if req.on_delta:
                    req.on_delta(delta)
            lane.eos.reset()
        # MAYBE_EOS: hold back
        if len(req.generated_tokens) >= req.max_tokens:
            lane.ended = "length"
        return True

    def _commit(self, lane_idx: int, lane: _Lane, tok: int) -> bool:
        """COMMIT one streamed token, at the readback of the step that was
        fed it (its KV write has executed): the resident-KV map and the
        paged prefix tree, the draft index, the position, the length
        check. Returns False when the lane finished here (the token had
        ended the stream, or the context is full) or failed."""
        return self._contained(self._commit_inner, lane_idx, lane, tok)

    def _commit_inner(self, lane_idx: int, lane: _Lane, req: Request,
                      tok: int) -> bool:
        self._lane_kv[lane_idx].append(tok)  # its KV write is committed
        self._paged_commit(lane_idx)
        lane.drafter.append(tok)
        if lane.ended == "stop":
            self._finish(lane_idx, req)
            return False
        lane.pos += 1
        if lane.ended or lane.pos >= self.engine.config.seq_len:
            self._finish(lane_idx, req, reason="length")
            return False
        return True

    def _advance(self, lane_idx: int, lane: _Lane,
                 produced: list[int]) -> tuple[bool, int]:
        """One readback's worth of one lane, the one rule of every decode
        path: COMMIT the token the step was fed (``lane.next_token``,
        streamed at the readback before), then STREAM what the step
        produced, in order. Every produced token but the last was fed
        inside the same step too (a verify step's accepted drafts, a
        multi-step horizon's chain), so it commits as soon as it is
        streamed; the last becomes ``next_token`` and waits for the step
        that feeds it. Stops at the token whose commit releases the lane:
        what the step produced past it is never streamed. Returns (lane
        still live, tokens committed — the finishing one included)."""
        n_fed = 0
        for tok in produced:
            n_fed += 1
            if not self._commit(lane_idx, lane, lane.next_token):
                return False, n_fed
            lane.next_token = tok
            if not self._stream(lane_idx, lane, tok):
                return False, n_fed
        return True, n_fed

    def _multi_horizon(self, active, prefilled: bool) -> int:
        """How many decode steps to chain in one device dispatch (0/1 =
        plain single step). Multi-step is correct only in steady-state
        decode: no prompt chunk was processed this iteration (no lane is
        admitting), nothing is queued (unlike the fused pipelined path,
        ``decode_multi`` cannot carry a prompt chunk, so an admission
        would wait out the whole horizon — the queue check stays even
        though the pipelined gate dropped it), and no active lane needs
        host-exact sampling (it reads full logits every step). The horizon
        is capped by the longest-remaining lane and bucketed to powers of
        two so at most log2(multi_step) programs ever compile."""
        if self.multi_step <= 1 or prefilled:
            return 0
        if not getattr(self.engine, "supports_multi_step", False):
            return 0
        if not self.queue.empty():
            return 0
        if any(l.host_exact and l.request.temperature > 0 for _, l in active):
            return 0
        rem = 0
        for _, lane in active:
            req = lane.request
            # tokens still to COMMIT: next_token is streamed (counted in
            # generated_tokens) and commits in this dispatch
            rem = max(rem, min(
                req.max_tokens - len(req.generated_tokens) + 1,
                self.engine.config.seq_len - lane.pos,
            ))
        from .spec import pow2_floor

        p = pow2_floor(min(self.multi_step, rem))
        return p if p > 1 else 0

    def _fused_ok(self) -> bool:
        """Fused prefill+decode admissions available: the flag is on, the
        engine compiles the fused step family, and pipelining is live."""
        return (
            self.fused_prefill
            and self.pipelined
            and getattr(self.engine, "supports_fused_prefill", False)
            and getattr(self.engine, "supports_pipelined", False)
            and getattr(self.engine, "pipeline_depth", 0) >= 2
        )

    def _spec_pl_ok(self) -> bool:
        """Speculation rides the pipelined chain (the zero-flush path): the
        engine compiles the in-chain verify family and speculation is on.
        When False (engine without the family, or speculative=False), the
        pre-zero-flush behavior applies: a draft hit flushes to the
        synchronous spec path."""
        return (
            self.speculative
            and getattr(self.engine, "SPEC_DRAFT", 0) > 0
            and getattr(self.engine, "supports_speculative", False)
            and getattr(self.engine, "supports_spec_pipelined", False)
        )

    def _drafts_pending(self, live: dict) -> bool:
        """Host-side probe: does any GENERATING greedy lane's history draft?
        A hit is a pipeline flush condition ONLY for engines without the
        in-chain verify family (``_spec_pl_ok`` False) — there the sync
        spec path emits >1 token per forward and wins. Lanes still
        mid-admission (their final chunk not yet read back) are skipped:
        their ``next_token`` is not set."""
        spec_k = (
            getattr(self.engine, "SPEC_DRAFT", 0)
            if self.speculative
            and getattr(self.engine, "supports_speculative", False)
            else 0
        )
        if spec_k <= 0:
            return False
        seq_len = self.engine.config.seq_len
        return any(
            lane.request.state == RequestState.GENERATING
            and lane.request.temperature == 0.0
            and seq_len - lane.pos - 1 > 0
            and lane.drafter.draft(lane.next_token, spec_k)
            for lane in live.values()
        )

    def _pipeline_ok(self, active, prefilled: bool = False) -> bool:
        """Gate for the pipelined path. The unconditional steady-state
        terms: engine support, a ring depth that actually buys a lag, and
        no host-exact-sampling lane (it reads full logits every step).
        With fused prefill, a queued admission or a pending prompt chunk
        no longer disqualifies — the chain itself claims lanes and streams
        their chunks through fused dispatches, so admission never exits
        steady state. Without it, the pre-fused conditions apply (nothing
        queued, no admitting lane, no chunk processed this iteration).
        Drafts are the caller's business: when the speculative probe
        produced any, the spec path runs instead."""
        if not self.pipelined or prefilled:
            return False
        if not getattr(self.engine, "supports_pipelined", False):
            return False
        if getattr(self.engine, "pipeline_depth", 0) < 2:
            return False
        # ALL occupied lanes, not just the generating ones: a host-exact
        # request still mid-admission (claimed by the sync _admit) must
        # keep the whole batch on the synchronous path too — its boundary
        # token needs the full-logits host sampler, which neither the
        # fused prefill nor the pipelined decode ever reads back
        if any(
            l.request is not None
            and l.host_exact
            and l.request.temperature > 0
            for l in self._lanes
        ):
            return False
        if self._fused_ok():
            return True
        if not self.queue.empty():
            return False
        return not any(
            l.request is not None and l.pending for l in self._lanes
        )

    def _claim_admissions(self, admitting: dict) -> bool:
        """Claim queued requests into free lanes WITHOUT leaving the chain
        (the fused-prefill admission path): pop, stamp, tokenize, seed the
        lane — host work only (plus the async prefix-cache lane copy) —
        and hand the lane to the dispatch half, which streams its chunks
        through fused dispatches. Returns False when a claimed lane needs
        the synchronous path (host-exact sampling reads full logits every
        step), the one admission kind that still flushes; the sync loop
        picks its pending chunks up after the drain."""
        free = self._free_lane_indices()
        if not free or self.queue.empty():
            return True
        # claim time is a real decode-lane stall only when the ring is
        # empty (nothing dispatched for the device to chew on meanwhile)
        stalled = self.engine.pipeline_inflight() == 0
        t0 = time.perf_counter()
        ok = True
        while free:
            claimed = self._claim_next(free)
            if claimed is None:
                break
            if claimed < 0:
                continue
            lane = self._lanes[claimed]
            if lane.host_exact and lane.request.temperature > 0:
                ok = False  # needs the sync path: flush after this claim
                break
            admitting[claimed] = lane
            self.telemetry.on_fused_admit(lane.request)
        if stalled:
            with self.engine.stats.lock:
                self.engine.stats.admission_stall_s += (
                    time.perf_counter() - t0
                )
        return ok

    def _pipeline_dispatch(self, live: dict, admitting: dict, feed,
                           spec_ok: bool = False):
        """Dispatch half of the pipelined loop: queue the next decode step
        from host-side lane METADATA only — sampling params, the ``-1``
        carried-position sentinel for live lanes (their write positions
        ride the DEVICE carry: a spec verify step advances each lane by
        its own accept count, which the host only learns one step behind),
        and, when an admitting lane has prompt chunks pending, ONE bounded
        chunk for ONE lane (round-robin, the sync ``_prefill_step`` rule)
        piggybacked on the SAME dispatch via ``engine.decode_prefill_fused``.

        When ``spec_ok`` (speculation rides the chain, no spec step
        already in flight, ring lag <= 1), the dispatch also probes each
        GENERATING greedy lane's n-gram index — a pure host-side lookup,
        no device value is touched — and ships up to SPEC_DRAFT+1 draft
        candidates with the dispatch (``engine.decode_spec_pipelined`` /
        ``decode_spec_prefill_fused`` when a chunk rides too). Candidate 0
        is the host's guess at the device's carry token (the index is one
        step behind — the consume half's own lag); the device verifies it
        before counting the rest, so a stale probe costs acceptance, never
        correctness, and the chain NEVER flushes for a draft hit.

        The tokens stay on device (``feed=None`` selects the engine's
        carry); nothing in here may read a device value back, or the whole
        overlap dies — machine-checked by dlint's pipeline-sync.

        Returns ``(fused_info, spec_drafted)``: ``fused_info`` is
        ``(lane_idx, lane, final, n_chunk)`` for a chunk-carrying dispatch
        (None otherwise); ``spec_drafted`` is ``{lane_idx: True}`` for
        lanes whose shipped drafts can accept (None for a non-spec step —
        the consume half needs it to interpret the packed readback and to
        scope the acceptance counters to drafted lanes). Chunk bookkeeping
        — ``lane.pos``, ``lane.pending``, ``_lane_kv`` — commits here at
        DISPATCH time: the chunk's KV writes execute in dispatch order
        whether or not the step's outputs are ever consumed, so the
        resident-KV map stays truthful even for a request cancelled
        mid-prompt."""
        engine = self.engine
        n_lanes = engine.n_lanes
        seq_len = engine.config.seq_len
        reseed = feed is not None
        # idle/finished lanes park at seq_len: the mode="drop" KV scatter
        # discards their junk writes (same rule as the sync loop). An
        # admitting lane parks there too — its REAL writes this step are
        # the fused chunk's, not the decode half's. Live lanes read the
        # device position carry (-1) except on a reseed, where the ring is
        # empty and the host's committed positions are exact.
        positions = np.full(n_lanes, seq_len, np.int32)
        temps = np.zeros(n_lanes, np.float32)
        topps = np.full(n_lanes, DEFAULT_TOPP, np.float32)
        seeds = np.zeros(n_lanes, np.uint32)
        # grammar states ride the dispatch like positions: -1 = the
        # device carry (authoritative in-chain), host mirror on a reseed
        # (ring empty: the mirror is exact), 0 = FREE for idle/admitting
        # lanes (an admitting lane's constraint enters via p_g below)
        g_any = any(
            l.grammar is not None
            for l in (*live.values(), *admitting.values())
        )
        gs = np.zeros(n_lanes, np.int32) if g_any else None
        for i, lane in live.items():
            positions[i] = min(lane.pos, seq_len) if reseed else -1
            if gs is not None:
                gs[i] = lane.g_state if reseed else -1
            temps[i] = lane.request.temperature
            topps[i] = lane.request.topp
            seeds[i] = lane.seed
        if g_any:
            self._count_masked_step()
        # draft probe (host-side n-gram lookup over committed history +
        # the last known fed token; legal here by construction — dlint's
        # pipeline-sync pins that nothing below syncs a device value)
        drafts = draft_len = None
        drafted: dict[int, bool] = {}
        if spec_ok:
            spec_k = engine.SPEC_DRAFT
            for i, lane in live.items():
                req = lane.request
                if (
                    req.state != RequestState.GENERATING
                    or req.temperature != 0.0
                    or seq_len - lane.pos - 1 <= 0
                ):
                    continue
                nt = lane.next_token
                if reseed:
                    # ring empty: nt IS this dispatch's feed — ship it as
                    # candidate 0 (the carry gate passes trivially)
                    d = [nt] + lane.drafter.draft(nt, spec_k)
                    if lane.grammar is not None and len(d) > 1:
                        # pre-filter through the host mirror (exact here:
                        # nt is already counted in it) — a draft the mask
                        # would reject is simply not proposed
                        d = d[: 1 + lane.grammar.filter_prefix(
                            lane.g_state, d[1:]
                        )]
                else:
                    # one step behind: nt fed the in-flight step; its
                    # output is the carry, so the probe's first
                    # continuation IS the carry candidate
                    d = lane.drafter.draft(nt, spec_k + 1)
                    if lane.grammar is not None and d:
                        # mirror trails the device by the in-flight step;
                        # filtering from it is approximate — harmless
                        # (device verification is exact), it only avoids
                        # shipping obviously illegal candidates
                        d = d[: lane.grammar.filter_prefix(
                            lane.g_state, d
                        )]
                if len(d) >= 2:  # candidate 0 alone cannot accept anything
                    if drafts is None:
                        drafts = np.zeros((n_lanes, spec_k + 1), np.int32)
                        draft_len = np.zeros(n_lanes, np.int32)
                    drafts[i, : len(d)] = d
                    draft_len[i] = len(d)
                    drafted[i] = True
        target = None
        if admitting:
            # round-robin over admitting lanes so several prompts make
            # progress together, one chunk per dispatch
            target = min(
                admitting, key=lambda i: (i - self._prefill_rr) % n_lanes
            )
            self._prefill_rr = (target + 1) % n_lanes
        if target is None:
            if drafts is None:
                engine.decode_pipelined(positions, temps, topps, seeds,
                                        tokens=feed, g_states=gs)
                return None, None
            engine.decode_spec_pipelined(
                positions, drafts, draft_len, temps, topps, seeds,
                tokens=feed, g_states=gs,
            )
            return None, drafted
        lane = admitting[target]
        req = lane.request
        chunk = lane.pending[: engine.max_chunk(lane.pos)]
        # the admitting lane's boundary token samples under its
        # automaton's START state (== lane.g_state until its first
        # emission); junk for mid-prompt chunks, decisive on the final one
        p_g = lane.g_state if lane.grammar is not None else 0
        if drafts is None:
            engine.decode_prefill_fused(
                positions, temps, topps, seeds,
                p_lane=target, chunk=chunk, p_start=lane.pos,
                p_temp=0.0 if lane.host_exact else req.temperature,
                p_topp=req.topp, p_seed=lane.seed,
                tokens=feed, g_states=gs, p_g=p_g,
            )
        else:
            # the full composition: an admitting chunk and a spec verify
            # step share one dispatch
            engine.decode_spec_prefill_fused(
                positions, drafts, draft_len, temps, topps, seeds,
                p_lane=target, chunk=chunk, p_start=lane.pos,
                p_temp=0.0 if lane.host_exact else req.temperature,
                p_topp=req.topp, p_seed=lane.seed,
                tokens=feed, g_states=gs, p_g=p_g,
            )
        lane.pos += len(chunk)
        lane.pending = lane.pending[len(chunk):]
        self._lane_kv[target].extend(chunk)  # committed: prefix-cacheable
        self._paged_commit(target)
        return (
            (target, lane, not lane.pending, len(chunk)),
            drafted if drafts is not None else None,
        )

    def _pipeline_consume(self, live: dict, entry: tuple) -> None:
        """Consume half, one step behind: block on the oldest in-flight
        step's packed token readback and run the host work the synchronous
        loop does inline — stream decode, EOS/stop, cancel/budget checks —
        while the younger dispatches keep the device busy. At a step's
        readback each live lane COMMITS the token the step was fed
        (resident-KV map, draft index, position, release of the lane) and
        STREAMS the token the step produced (count, stamp, detokenize,
        EOS/stop, ``on_delta``): a token reaches its client at the
        readback that first gives it to the host, and is committed one
        readback later, when the step that wrote its KV has returned.
        ``entry`` is ``(step_lanes, fused, t_dispatch, spec_drafted, step,
        dry_s, p_start)`` recorded AT DISPATCH TIME: ``step_lanes`` pairs
        each live lane index with its lane OBJECT — the identity check skips both lanes that finished at
        an earlier consumed step AND lanes already reclaimed by a NEW
        request while this step was still in flight (either way the
        column is junk, and its in-flight KV writes die under the
        overwrite-before-readable rule). ``fused`` is the dispatch half's
        ``(lane_idx, lane, final, n_chunk)`` for a chunk-carrying step,
        whose extra readback column (row, for a spec pack) carries the
        chunk's boundary token pair: on the FINAL chunk that token is the
        request's first generated token, STREAMED at this readback (the
        same point the synchronous path reads and streams it) and
        committed at the next one.
        ``spec_drafted`` (None for a plain step) marks the step as a spec
        verify: the readback is ``decode_spec``'s (emitted, n_emit) pack,
        each live lane commits a VARIABLE-LENGTH accept — next_token + the
        accepted drafts, exactly the sync spec path's feed sequence —
        streams the accepted drafts and the model's token after them, and
        drafted lanes feed the acceptance counters (consumed-only, and
        only when the lane actually fed tokens: a lane cancelled mid-draft
        must not count a lane-step with zero emitted, which would push the
        /stats acceptance ratio below its [1, K+1] class). ``t_dispatch``
        is the step's dispatch stamp (its ``prefill.fused`` slice starts
        there); ``step`` is the dispatch's sequence number, which this
        half's ``loop.wait`` and ``loop.stream`` spans and the step slice
        carry; ``dry_s`` is the dispatch half's witness (None: the device
        still had work when the step was handed over) and ``p_start`` where
        the chunk began. The step's RECORD (``StepRecord``) is made HERE,
        when the readback returns, so the dispatch half stays span-free
        (dlint pipeline-sync): its interval runs from the readback before
        (a chain's first: from its own dispatch) to this one."""
        step_lanes, fused, t_dispatch, spec_drafted, step, dry_s, p_start = entry
        wd = self.watchdog
        if wd is not None:
            wd.begin_step()
        t_wait = time.perf_counter()
        try:
            # loop.wait: the lagged readback alone — the one span of the
            # loop in which the host has nothing to do but wait
            with self.telemetry.span(LOOP_WAIT, LOOP_TRACK,
                                     args={"step": step}):
                out_a, out_b = self.engine.pipeline_consume()
        finally:
            if wd is not None:
                wd.step_done()
        t_done = time.perf_counter()
        since = t_dispatch if self._readback_at is None else self._readback_at
        self._readback_at = t_done
        bucket = None if fused is None else self.engine.bucket_for(fused[3])
        record = StepRecord(
            step=step,
            cls=pipelined_step_class(spec_drafted is not None, bucket),
            chunk=0 if fused is None else fused[3],
            p_start=p_start,
            final=fused is not None and fused[2],
            lanes=len(step_lanes),
            dry=dry_s is not None,
            dry_s=dry_s or 0.0,
            interval_s=t_done - since,
            wait_s=t_done - t_wait,
            host_s=t_wait - since,
            at=time.monotonic(),
        )
        # loop.stream: everything the host does with the step's tokens; the
        # span (and so the profiler's dl.loop.stream) carries the record
        with self.telemetry.span(LOOP_STREAM, LOOP_TRACK, args=record.args()):
            self._pipeline_stream(
                live, step_lanes, fused, t_dispatch, spec_drafted, record,
                bucket, t_done, out_a, out_b,
            )

    def _pipeline_stream(self, live: dict, step_lanes, fused, t_dispatch,
                         spec_drafted, record: StepRecord, bucket,
                         t_done: float, out_a, out_b) -> None:
        """The host half of one consumed step (``_pipeline_consume``'s
        contract): cancel/budget checks, then per lane ``_advance`` —
        commit the token the step was fed (finishing the request if that
        token had ended its stream), stream what the step produced with
        detokenize/``on_delta`` — and the fused boundary token, streamed
        here as its request's first."""
        self.breaker.record_success()
        now = record.at
        is_spec = spec_drafted is not None
        self.telemetry.on_pipelined_step(
            t_dispatch, fused,
            kind="spec_pipelined" if is_spec else "pipelined",
            bucket=bucket, record=record, t_done=t_done,
        )
        if is_spec:
            emitted, n_emit = out_a, out_b
        else:
            greedy_np, sampled_np = out_a, out_b
        for i, lane in step_lanes:
            if live.get(i) is not lane:
                continue  # finished earlier (or lane reclaimed): junk column
            req = lane.request
            if req._cancelled.is_set():
                self._finish(i, req, reason="cancelled")
                live.pop(i)
                continue
            if budget_expired(req, self.deadlines, now):
                self.budget_timeouts += 1
                self._finish(i, req, reason="timeout")
                live.pop(i)
                continue
            # the one rule (_advance): commit what the step was FED, stream
            # what it PRODUCED. A spec verify step produced a variable-length
            # accept (the plain-decode stream, per the verification
            # identity): next_token and the accepted drafts commit, the
            # model's token after the accepted prefix becomes the new
            # next_token — the sync spec path's rule verbatim. A plain step
            # produced the token this lane fed into the NEXT in-flight step
            # (the on-device feed rule, reconstructed for host bookkeeping)
            if is_spec:
                produced = [int(t) for t in emitted[i, : int(n_emit[i])]]
            elif req.temperature == 0.0:
                produced = [int(greedy_np[i])]
            else:
                produced = [int(sampled_np[i])]
            alive, n_fed = self._advance(i, lane, produced)
            if is_spec and spec_drafted.get(i):
                with self.engine.stats.lock:
                    self.engine.stats.spec_lane_steps += 1
                    self.engine.stats.spec_emitted += n_fed
                    acc = len(produced) - 1  # the device's accept count
                    self.engine.stats.spec_accept_hist[acc] = (
                        self.engine.stats.spec_accept_hist.get(acc, 0)
                        + 1
                    )
            if not alive:
                live.pop(i)
        if fused is not None:
            i, lane, final, _n_chunk = fused
            if final and live.get(i) is lane:
                # prompt complete: adopt the boundary token as the first
                # generated token (greedy at temp 0, fused-sampled else —
                # host-exact admissions never take the fused path), go
                # GENERATING and STREAM it, here, at the readback that
                # delivers it. The lane already joined the dispatch half's
                # live set when its final chunk went out; the carry fed it
                # on device, and the NEXT consumed step commits this token.
                # Spec packs carry the boundary pair in the extra ROW's
                # first two columns; token packs in the extra COLUMN.
                req = lane.request
                self.telemetry.on_prefill_done(req, now)
                if is_spec:
                    b_greedy = int(emitted[-1, 0])
                    b_sampled = int(emitted[-1, 1])
                else:
                    b_greedy = int(greedy_np[-1])
                    b_sampled = int(sampled_np[-1])
                lane.next_token = (
                    b_greedy if req.temperature == 0.0 else b_sampled
                )
                req.state = RequestState.GENERATING
                # (the grammar mirror's start state advances by the
                # boundary emission inside the stream half)
                if not self._stream(i, lane, lane.next_token):
                    live.pop(i)

    def _pipeline_admit(self, live: dict, admitting: dict, fused: bool,
                        spec_chain: bool, probe_drafts: bool) -> bool:
        """The admission part of one iteration of the pipelined loop (the
        ``loop.admit`` span): sweep the queue, drop admitting requests
        that were cancelled or ran out of budget, claim queued requests
        into free lanes (tokenizing them). Returns whether the chain must
        flush."""
        now = time.monotonic()
        # queued cancels/expiries must not wait out a long chain
        # (throttled internally to ~20 Hz)
        self._sweep_queue(now)
        # an admitting request cancelled/expired mid-prompt: stop
        # streaming its chunks; the in-flight ones are junk-KV-safe
        for i in [
            j for j, l in admitting.items()
            if l.request._cancelled.is_set()
            or budget_expired(l.request, self.deadlines, now)
        ]:
            lane = admitting.pop(i)
            if lane.request._cancelled.is_set():
                self._finish(i, lane.request, reason="cancelled")
            else:
                self.budget_timeouts += 1
                self._finish(i, lane.request, reason="timeout")
        flush = (
            self._stop.is_set()
            or self._wd_abort.is_set()  # watchdog: abort the chain
            or (not live and not admitting)
        )
        if not flush and fused:
            # a claimed lane whose chunks cannot ride the chain (a
            # host-exact admission): only the synchronous path can
            # serve it — keep flushing until it does. Checked every
            # iteration, not just at claim time, so the lane is never
            # starved behind a long-lived chain.
            flush = any(
                l.request is not None
                and l.pending
                and i not in admitting
                and i not in live
                for i, l in enumerate(self._lanes)
            )
        if not flush and not self.queue.empty():
            if fused:
                # admissions ride the chain; only a host-exact claim
                # still needs the synchronous path
                flush = not self._claim_admissions(admitting)
            else:
                flush = True
        if not flush and probe_drafts and not spec_chain:
            # engines without the in-chain verify family: a draft hit
            # still flushes to the synchronous spec path
            flush = self._drafts_pending(live)
        return flush

    def _run_pipelined(self, active) -> None:
        """Steady-state pipelined decode: keep the ring at ``pipeline_depth``
        dispatched steps, consuming the oldest one step behind — step k's
        detokenize/stream/stop work overlaps step k+1's device execution
        instead of serializing ahead of it.

        With fused prefill (the default), admission is part of steady
        state, not an exit: a queued request claims a free lane in-chain
        (``_claim_admissions``), its prompt chunks ride fused dispatches
        (``_pipeline_dispatch``), and when the final chunk goes out the
        lane joins the decode half fed by the on-device carry — the chain
        never breaks and ``pipeline_flushes`` stays 0 under churn. The
        readback of that final chunk's step streams the first token.

        Speculation is part of steady state too (the zero-flush tentpole):
        when the engine compiles the in-chain verify family
        (``_spec_pl_ok``), a greedy lane whose history drafts ships its
        candidates WITH the dispatch (``decode_spec_pipelined``, or the
        chunk-carrying ``decode_spec_prefill_fused``) and the consume half
        streams and commits the variable-length accept one step behind —
        speculation's extra tokens MULTIPLY with the overlap instead of
        aborting it.
        Probing is gated to dispatches whose ring lag is <= 1 with no
        other spec step in flight: past that the host's one-step-behind
        carry candidate cannot align, so drafts would verify-and-miss
        (correct but pointless).

        Exits by DRAINING the remaining in-flight steps through the normal
        consume path (their tokens are valid — no generated token is ever
        discarded for a live lane) when a flush condition appears: stop(),
        a draft hit on an engine WITHOUT the in-chain verify family, a
        host-exact admission (host_sampling mode reads full logits every
        step, so the sync path must run it), a queued admission with fused
        prefill OFF, or every lane finishing. An exit with lanes still
        live counts as a pipeline flush in the engine stats."""
        engine = self.engine
        depth = max(2, int(getattr(engine, "pipeline_depth", 2)))
        fused = self._fused_ok()
        spec_chain = self._spec_pl_ok()
        live: dict[int, _Lane] = dict(active)
        # lanes still streaming prompt chunks (sync-admitted leftovers on
        # entry; in-chain claims join via _claim_admissions)
        admitting: dict[int, _Lane] = {}
        if fused:
            admitting = {
                i: l for i, l in enumerate(self._lanes)
                if l.request is not None and l.pending and i not in live
            }
            for l in admitting.values():
                # sync-admitted leftovers joining the chain: their
                # remaining chunks ride fused dispatches too
                self.telemetry.on_fused_admit(l.request)
        feed = np.zeros(engine.n_lanes, np.int32)
        for i, lane in live.items():
            feed[i] = lane.next_token
        # (live lanes, fused info, dispatch stamp, spec-drafted set, step
        # number, dry seconds or None, the chunk's start) per dispatch —
        # positions no longer tracked host-side:
        # they ride the device carry (spec accept counts are only known
        # one step behind)
        meta: deque = deque()
        self._readback_at = None  # a new chain: no readback of its own yet
        host_feed = True  # first dispatch reseeds the chain from host tokens
        dispatched_any = False
        # both entry gates (_run's early fused entry and the post-spec
        # branch) just probed the drafters; skip the duplicate probe on
        # the first iteration of the hot loop
        probe_drafts = False
        tel = self.telemetry
        while True:
            # one iteration = loop.admit, loop.dispatch (per dispatch),
            # loop.wait, loop.stream: four spans on the ring's `loop` track
            # and, as dl.loop.* annotations, on the profiler's clock
            with tel.span(LOOP_ADMIT, LOOP_TRACK,
                          args={"step": self._step_seq + 1}):
                flush = self._pipeline_admit(
                    live, admitting, fused, spec_chain, probe_drafts
                )
            probe_drafts = True  # entry gates probed already; re-check
            # from the second iteration on (new tokens land per consume)
            while not flush and engine.pipeline_inflight() < depth:
                # dispatch stamp taken HERE, not inside the dispatch half:
                # the consume half pairs it with the lagged readback into
                # the step's trace slice (no tracer call — no lock, no
                # sync — ever runs inside _pipeline_dispatch itself)
                t_d = time.perf_counter()
                t_mono = time.monotonic()
                # spec drafts align only at ring lag <= 1 with no other
                # spec step in flight (the host's carry candidate is one
                # step behind — see _pipeline_dispatch)
                spec_ok = (
                    spec_chain
                    and engine.pipeline_inflight() <= 1
                    and not any(m[3] is not None for m in meta)
                )
                self._step_seq += 1
                step = self._step_seq
                # the dry-dispatch witness: this chain has been read back
                # (its first fill had nothing running to run dry) and the
                # device has already finished all that is in flight, so it
                # stands idle until this step reaches it. A poll, asked
                # here and not inside the dispatch half
                dry = self._readback_at is not None and engine.pipeline_ready()
                # the span wraps the CALL; nothing is recorded inside the
                # dispatch half (dlint pipeline-sync)
                with tel.span(LOOP_DISPATCH, LOOP_TRACK,
                              args={"step": step, "dry": int(dry)}):
                    fused_info, spec_drafted = self._pipeline_dispatch(
                        live, admitting, feed if host_feed else None, spec_ok
                    )
                dry_s = time.perf_counter() - self._readback_at if dry else None
                with engine.stats.lock:
                    engine.stats.live_lane_steps += len(live)
                    if dry:
                        engine.stats.pipeline_dry_dispatches += 1
                        engine.stats.pipeline_dry_s += dry_s
                if spec_drafted is None:
                    self._count_attention_rows(self._device_rows(live, meta))
                host_feed = False
                dispatched_any = True
                # the chunk's bookkeeping committed at dispatch: its lane
                # stands at the chunk's end
                p_start = (0 if fused_info is None
                           else fused_info[1].pos - fused_info[3])
                meta.append((tuple(live.items()), fused_info, t_d,
                             spec_drafted, step, dry_s, p_start))
                if fused_info is not None:
                    i, lane, final, _ = fused_info
                    tel.on_prefill_dispatch(lane.request, t_mono)
                    if final:
                        # final chunk dispatched: the lane joins the decode
                        # half from the NEXT dispatch — the device carry
                        # holds both its first token AND its position (set
                        # by the fused program), no host round-trip involved
                        admitting.pop(i)
                        live[i] = lane
            if engine.pipeline_inflight() == 0:
                break
            self._pipeline_consume(live, meta.popleft())
        if (live or admitting) and dispatched_any:
            # cut short with lanes still generating or admitting: an actual
            # flush (the natural all-lanes-finished drain is not)
            self.telemetry.on_flush(len(live), len(admitting))
            with engine.stats.lock:
                engine.stats.pipeline_flushes += 1
        engine.pipeline_flush()  # ring already drained; drops the carry

    def _finish(self, lane_idx: int, req: Request, reason: str = "stop") -> None:
        req.state = RequestState.DONE
        req.finish_reason = reason
        # the migration ticket dies with the session: a finished request
        # has nothing left to move (routers fetch their ticket at stream
        # start, so a drain/stop force-cancel popping this is fine)
        self._mirror_finish(req)
        delta = self._lanes[lane_idx].eos.get_delta()
        if delta:
            req.generated_text += delta
            if req.on_delta:
                req.on_delta(delta)
        self._grammar_release(self._lanes[lane_idx])
        self._lanes[lane_idx] = _Lane()
        # paged: the finished session PARKS — its tree-registered blocks
        # stay resident (refcounted, LRU-bounded) so chat follow-ups and
        # same-prompt admissions share copy-free; its non-sharable tail
        # frees now. This is how resident sessions exceed lanes.
        self._paged_release(lane_idx, park=True)
        self.engine.reset_lane(lane_idx)
        # summary/spans/log line BEFORE the future resolves: the HTTP
        # thread reads req.summary the moment result() returns
        self.telemetry.on_finish(req, lane_idx, reason)
        if not req.future.done():
            req.future.set_result(req.generated_text)
        if self.journal is not None:
            # a deliberate ending (stop/length/cancel/timeout) is final:
            # the finish record keeps a later --recover-journal restart
            # from resurrecting this request. A CRASH writes no finish
            # records — that absence IS the journal's in-flight set.
            # Recorded LAST — after the held-back tail delta and the
            # future resolution — because the two crash windows are
            # asymmetric: a finish record that never lands just re-runs
            # the request on recovery (the client's Last-Event-ID filter
            # dedups), while a finish record durable BEFORE the tail
            # reached the transport would make the tail unrecoverable.
            # The phases dict produced by on_finish rides along: the
            # journal's finish record carries the same latency
            # attribution the completion response does.
            self.journal.record_finish(
                req.id, reason,
                phases=(req.summary or {}).get("phases"),
            )

    def _run(self) -> None:
        """Supervised outer loop (failure containment, the ISSUE 8
        tentpole — the analogue of the reference's supervised serve loop,
        src/app.cpp:455-463, on the ROOT side): the serving loop body
        runs inside a containment boundary, so an engine exception
        escaping a dispatch/consume/transfer can no longer kill the
        daemon batching thread and leave every future unresolved with
        /health still green. Engine-scoped failures are contained
        (`_contain_engine_failure`: abort the pipeline ring, fail the
        affected lanes with finish_reason="error", reset lane state, feed
        the circuit breaker) and the loop KEEPS SERVING — shedding at
        admission while the breaker is open, probing half-open, closing
        on recovery. Request-scoped failures never reach here (their
        sites fail the one request inline). The `finally` runs the
        stop()-style future cleanup even on a truly-fatal path (a raise
        out of containment itself), so no client ever hangs on a dead
        loop."""
        try:
            while True:
                try:
                    self._serve_loop()
                    break  # clean exit: stop() or drain complete
                except Exception as e:  # noqa: BLE001 — containment boundary
                    self._contain_engine_failure(e)
                    if self._stop.is_set():
                        break
        finally:
            self._resolve_exit()

    def _contain_engine_failure(self, e: BaseException) -> None:
        """Engine-scoped containment: log + count the failure, abort the
        pipeline ring WITHOUT consuming (each readback of a poisoned
        in-flight step would re-raise), fail every occupied lane with
        ``finish_reason="error"`` (their KV is garbage now — the
        resident-KV maps are discarded so prefix caching can never reuse
        it), and leave the lanes fresh for the next admission. Never
        raises: containment is the one layer that must not fail."""
        err = f"{type(e).__name__}: {e}"
        self.engine_failures += 1
        state = self.breaker.record_engine_failure(err)
        busy = [
            (i, l.request)
            for i, l in enumerate(self._lanes)
            if l.request is not None
        ]
        self.telemetry.on_engine_failure(
            err, lanes_failed=len(busy), breaker_state=state
        )
        try:
            abort = getattr(self.engine, "pipeline_abort", None)
            if abort is not None:
                abort()
            elif getattr(self.engine, "pipeline_active", False):
                # fallback for engines without the abort primitive; no
                # count= kwarg — proxies (RootControlEngine) don't take it,
                # and an aborted chain SHOULD count as a flush anyway
                self.engine.pipeline_flush()
        except Exception:  # noqa: BLE001 — containment must not throw
            pass
        for i, req in busy:
            try:
                self._fail_request(i, req, err)
            except Exception:  # noqa: BLE001 — containment must not throw
                pass
        # paged: after an engine-scoped failure the device pool contents
        # are not trusted — drop parked sessions and the whole prefix
        # tree too, not just the failed lanes' mappings
        try:
            reset = getattr(self.engine, "paged_reset", None)
            if reset is not None and getattr(self.engine, "kvpool", None) is not None:
                reset()
        except Exception:  # noqa: BLE001 — containment must not throw
            pass

    def _resolve_exit(self) -> None:
        """The stop()/drain() future cleanup, in a ``finally`` so it runs
        even when the supervised loop dies fatally: every in-flight lane
        resolves as cancelled and every queued future resolves (shed on a
        graceful drain, failed otherwise) — no client hangs on a dead
        loop thread."""
        for i, lane in enumerate(self._lanes):
            if lane.request is not None:
                self._finish(i, lane.request, reason="cancelled")
        # pending device ops resolve as failed, not as a timeout wait —
        # an admin thread must never hang on a dead loop
        with self._device_ops_lock:
            pending = list(self._device_ops)
            del self._device_ops[:]
        for _fn, box, done in pending:
            box["error"] = RuntimeError("scheduler stopped")
            done.set()
        draining = self._draining.is_set()
        for req in self.queue.drain():
            if draining:
                # graceful drain: a submit() that passed the pre-push shed
                # check can land its push after this loop's exit snapshot;
                # shed it like submit() would (503 + Retry-After) —
                # "scheduler stopped" would surface as a 500 in the middle
                # of a rolling restart
                self._shed_unadmitted(req)
            else:
                req.state = RequestState.FAILED
                self.telemetry.on_error(req, None, "scheduler stopped")
                if not req.future.done():
                    req.future.set_exception(RuntimeError("scheduler stopped"))

    def _serve_loop(self) -> None:
        n_lanes = self.engine.n_lanes
        cfg = self.engine.config
        while not self._stop.is_set():
            if self._wd_abort.is_set():
                # watchdog tripped but the step eventually returned (slow,
                # not dead): the chain already aborted; clear the flag so
                # serving resumes (the breaker stays open until a probe
                # succeeds)
                self._wd_abort.clear()
            # step boundary: engine.cache is the live chain output here,
            # so posted admin device ops (disagg export/import) run now
            self._drain_device_ops()
            idle = all(l.request is None for l in self._lanes)
            # when every lane is free, park on the queue's condition variable
            # instead of spinning pop(timeout=0)+sleep — an idle server burns
            # no core, and a push wakes the loop immediately
            self._admit(wait_s=0.25 if idle else 0.0)
            now = time.monotonic()
            self._sweep_queue(now)
            if (
                self._draining.is_set()
                and self.queue.empty()
                and all(l.request is None for l in self._lanes)
            ):
                break  # graceful drain: all work done, submit() is shedding
            occupied = [(i, l) for i, l in enumerate(self._lanes) if l.request is not None]
            if not occupied:
                continue  # _admit already waited on the queue

            # drop cancelled / budget-expired requests before spending a
            # step on them (expiry frees the lane for the next admission)
            for i, lane in occupied:
                if lane.request._cancelled.is_set():
                    self._finish(i, lane.request, reason="cancelled")
                elif budget_expired(lane.request, self.deadlines, now):
                    self.budget_timeouts += 1
                    self._finish(i, lane.request, reason="timeout")

            # stall-free admissions: with fused prefill, enter the
            # pipelined path BEFORE the synchronous prefill step — pending
            # prompt chunks and queued admissions ride the chain itself
            # (fused prefill+decode dispatches), so an admission no longer
            # exits steady state
            if self._fused_ok():
                active = [
                    (i, self._lanes[i])
                    for i in range(n_lanes)
                    if self._lanes[i].request is not None
                    and self._lanes[i].request.state
                    == RequestState.GENERATING
                ]
                if (
                    active
                    and self._pipeline_ok(active)
                    and (
                        # drafts ride the chain when the engine verifies
                        # in-chain; only legacy engines flush for them
                        self._spec_pl_ok()
                        or not self._drafts_pending(dict(active))
                    )
                ):
                    self._run_pipelined(active)
                    continue

            # at most ONE prompt bucket per iteration: decoding lanes below
            # stall no longer than one bucket while admissions stream in.
            # Any generating lane held up by this chunk is a real admission
            # stall (with fused prefill this path only runs when the chain
            # declined: drafts pending, a host-exact lane, or pipelining
            # off — the fused chain otherwise hides admission work behind
            # device execution)
            had_generating = any(
                l.request is not None
                and l.request.state == RequestState.GENERATING
                for l in self._lanes
            )
            t_pf = time.perf_counter()
            prefilled = self._prefill_step()
            if prefilled and had_generating:
                with self.engine.stats.lock:
                    self.engine.stats.admission_stall_s += (
                        time.perf_counter() - t_pf
                    )

            active = [
                (i, self._lanes[i])
                for i in range(n_lanes)
                if self._lanes[i].request is not None
                and self._lanes[i].request.state == RequestState.GENERATING
            ]
            if not active:
                # Nothing decodable and no prompt chunk processed. This is
                # only reachable when the cancel/expiry pass above freed
                # every lane after the `occupied` snapshot was taken (an
                # admitting lane implies prefilled; a generating lane
                # implies active) — so loop straight back to the idle
                # check, which parks on the queue's condition variable
                # (QosQueue.pop wait / Queue.get) until the next push or
                # the 0.25s stop-flag recheck, instead of busy-polling
                # `self._stop` at 1ms as earlier revisions did.
                continue

            host_exact_active = any(
                l.host_exact and l.request.temperature > 0 for _, l in active
            )
            tokens = np.zeros(n_lanes, np.int32)
            # EVERY lane gets a KV write from this decode step (one compiled
            # program, all lanes scatter). Idle/finished lanes point at
            # seq_len so the mode="drop" scatter discards the junk write
            # outright — position 0 would clobber slot 0 of a finished
            # lane's cache, which prefix caching may still reuse
            # (round-5 code-review finding). Lanes mid-prefill point at
            # their next unwritten slot, which the next prefill chunk
            # rewrites before any query can read it; where a lane carries a
            # state overwritten in place they stand parked like the idle
            # ones, as they do in the chain: a running sum or a window of
            # inputs would absorb the junk row and keep it (PR 43: the
            # second of two requests admitted together chose other tokens
            # than it chose alone).
            positions = np.full(n_lanes, cfg.seq_len, np.int32)
            temps = np.zeros(n_lanes, np.float32)
            topps = np.full(n_lanes, DEFAULT_TOPP, np.float32)
            seeds = np.zeros(n_lanes, np.uint32)
            for i, lane in enumerate(self._lanes):
                if lane.request is not None and lane.pending and not self._recurrent_state:
                    positions[i] = lane.pos
            for i, lane in active:
                tokens[i] = lane.next_token
                positions[i] = lane.pos
                if not lane.host_exact:
                    temps[i] = lane.request.temperature
                    topps[i] = lane.request.topp
                    seeds[i] = lane.seed

            # speculative step (prompt-lookup drafts, greedy lanes), gated
            # PER LANE: each lane drafts at most the uncommitted cache slots
            # it has left before seq_len (emitting m tokens reads logits at
            # pos..pos+m-1, which need in-bounds KV writes; writes at
            # >= seq_len are dropped by the cache scatter, so a lane at the
            # end of its sequence cannot clobber state or disable drafting
            # on other lanes)
            spec_k = getattr(self.engine, "SPEC_DRAFT", 0)
            draft_len = None
            if self._spec_pl_ok() and self._pipeline_ok(active, prefilled):
                # drafts ride the chain: don't build the sync-path draft
                # arrays just to discard them — the chain's dispatch half
                # probes the SAME indices itself, with the carry-candidate
                # layout the in-chain verify needs
                self._run_pipelined(active)
                continue
            if (
                self.speculative
                and spec_k > 0
                and getattr(self.engine, "supports_speculative", False)
            ):
                drafts = np.zeros((n_lanes, spec_k), np.int32)
                draft_len = np.zeros(n_lanes, np.int32)
                for i, lane in active:
                    d_max = min(spec_k, cfg.seq_len - lane.pos - 1)
                    if lane.request.temperature == 0.0 and d_max > 0:
                        d = lane.drafter.draft(lane.next_token, spec_k)[:d_max]
                        if lane.grammar is not None and d:
                            # host pre-filter: a draft the mask would
                            # reject is simply not proposed (the sync
                            # mirror is exact here), so verification
                            # stays the model's own masked-greedy path
                            d = d[: lane.grammar.filter_prefix(
                                lane.g_state, d
                            )]
                        drafts[i, : len(d)] = d
                        draft_len[i] = len(d)
                if not draft_len.any():
                    draft_len = None  # nothing to verify: plain step

            if draft_len is None and self._pipeline_ok(active, prefilled):
                # steady state with no drafts to verify on a LEGACY engine
                # (the in-chain-verify entry above handles the default):
                # the pipelined path overlaps step k's host consume with
                # step k+1's device execution (device-fed token carry,
                # lagged readback)
                self._run_pipelined(active)
                continue

            chosen = None
            h = 0 if draft_len is not None else self._multi_horizon(
                active, prefilled
            )
            # grammar states for this dispatch (exact host mirror on the
            # sync paths); None -> the engine's all-FREE default
            g_states, g_any = self._g_states_sync(active)
            if g_any:
                self._count_masked_step()
            wd = self.watchdog
            if wd is not None:
                wd.begin_step()
            t_step = time.perf_counter()
            try:
                if draft_len is not None:
                    logits, emitted, n_emit = self.engine.decode_spec(
                        tokens, drafts, draft_len, positions, temps, topps,
                        seeds, g_states=g_states,
                    )
                elif h > 1:
                    logits = None  # host-exact lanes are excluded by the gate
                    self._count_attention_rows(positions, h)
                    chosen = self.engine.decode_multi(
                        tokens, positions, temps, topps, seeds, h,
                        g_states=g_states,
                    )
                else:
                    # logits materialize only when a host-exact lane will
                    # read them: the common all-device-sampling step keeps
                    # no [n_lanes, vocab] buffer alive
                    self._count_attention_rows(positions)
                    logits, greedy, sampled = self.engine.decode(
                        tokens, positions, temps, topps, seeds,
                        want_logits=host_exact_active,
                        g_states=g_states,
                    )
                if draft_len is not None:
                    kind, cls = "spec", step_class("spec")
                elif h > 1:
                    kind, cls = "multi", step_class("decode_multi", h)
                else:
                    kind, cls = "sync", step_class(
                        "decode_sync" if host_exact_active
                        else "decode_sync_nologits")
                self.telemetry.on_step(
                    kind, t_step, args={"h": h} if h > 1 else None, cls=cls,
                )
                # host-exact lanes (host_sampling=True only — the
                # bit-exact reference-xorshift escape hatch; the device
                # sampler is full-vocab exact, so no request routes here
                # on numerics grounds): one batched [n_lanes, vocab]
                # transfer; pure on-device batches: tokens only
                logits_np = None
                if host_exact_active:
                    # dlint: ok[host-sync] host-exact lanes only: ONE batched [n,vocab] f32 transfer, counted by all_logits
                    logits_np = self.engine.all_logits(logits)
            finally:
                # disarm on success AND on a raise (a raised step is the
                # containment layer's business, not a stall)
                if wd is not None:
                    wd.step_done()
            self.breaker.record_success()

            for i, lane in active:
                req = lane.request
                # what the step produced for this lane, in order; the
                # synchronous ladder holds tokens by the chain's rule
                # (_advance): next_token, streamed when the step before
                # produced it, commits now, and these stream now
                if req.temperature > 0.0 and lane.host_exact:
                    # (never a multi-step horizon: the gate excludes it;
                    # a verify step emits one token for temp > 0)
                    produced = [lane.sampler.sample(logits_np[i])]
                elif draft_len is not None:
                    # the accepted drafts (they equal the greedy
                    # continuations, so this is exactly the plain-decode
                    # token stream) and the model's token after them
                    produced = [int(t) for t in emitted[i, : int(n_emit[i])]]
                elif chosen is not None:
                    # multi-step horizon: h chained choices (greedy /
                    # sampled already encoded). Tokens past a stop are
                    # discarded (their junk KV is rewritten before any
                    # query reads it)
                    produced = [int(chosen[j, i]) for j in range(h)]
                elif req.temperature == 0.0:
                    produced = [int(greedy[i])]
                else:
                    produced = [int(sampled[i])]
                _alive, n_fed = self._advance(i, lane, produced)
                # acceptance counters cover DRAFTED lanes only — sampled
                # and draft-less lanes ride the same batched verify call
                # but always emit 1, which would dilute the metric
                if draft_len is not None and int(draft_len[i]) > 0:
                    with self.engine.stats.lock:
                        self.engine.stats.spec_lane_steps += 1
                        self.engine.stats.spec_emitted += n_fed
