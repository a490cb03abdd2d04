"""Telemetry hub: the one object the scheduler/server/benchmark share.

Bundles the span tracer (spans.py), the metrics registry (metrics.py)
with the standard serving instruments pre-registered, and the JSON
logger (logs.py), and exposes the lifecycle hooks the scheduler calls:

    submit -> on_submit          (queued instant, RequestTrace attached)
    admit  -> on_admit           (queued slice, queue-wait histogram)
    chunk  -> on_prefill_chunk   (lane slice, step-duration histogram)
    token  -> on_token           (TTFT on first, inter-token gaps after)
    step   -> on_step / on_pipelined_step  (pipeline-track slices; a
              pipelined step's carries its StepRecord)
    loop   -> span(name, track)  (a slice AND a profiler annotation: the
              batching loop's own work, on the ring and on the device's clock)
    end    -> on_finish / on_unadmitted / on_error  (summary, counters,
              one JSON log line, finish instant)

Design constraint, inherited from the async pipeline: NO hook runs
inside the pipelined dispatch half. A step's slice (the interval between
two readbacks) is recorded by ``on_pipelined_step`` from the scheduler's
consume half, one step behind, where the host has just blocked on the
lagged readback —
dlint's ``pipeline-sync`` check stays green because the dispatch half
never calls in here.

Exposition: ``render_prometheus(bridge=stats_dict)`` re-publishes the
``/stats`` payload as ``dllama_stats_*`` gauges next to the native
histograms/counters, sampled from the SAME snapshot the JSON endpoint
serves — so ``/metrics`` and ``/stats`` reconcile by construction.
"""

from __future__ import annotations

import time

from .logs import JsonLogger, default_logger
from .metrics import LATENCY_BUCKETS_S, MetricsRegistry
from .names import ANNOTATION_PREFIX, step_class
from .spans import RequestTrace, SpanTracer, StepRecord
from .trace import dump_chrome_trace, tracer_chrome_trace
from .tracectx import trace_id_of

STATS_PREFIX = "dllama_stats_"


class _Span:
    """One open ``Telemetry.span``: the annotation (if a factory is set)
    is held open from enter to exit, and the ring slice is appended on
    exit, as ``SpanTracer.slice`` would. The annotation is handed the
    span's args as keywords, so the profiler's copy of ``dl.loop.*`` says
    the ``step`` the ring's does. Always on: with no profiler session a
    ``jax.profiler.TraceAnnotation`` is one atomic load, and encodes
    nothing."""

    __slots__ = ("_tel", "_name", "_track", "_req_id", "_args", "_t0", "_ann")

    def __init__(self, tel, name, track, req_id, args):
        self._tel, self._name, self._track = tel, name, track
        self._req_id, self._args = req_id, args

    def __enter__(self):
        factory = self._tel.annotation_factory
        self._ann = ann = (
            None if factory is None
            else factory(ANNOTATION_PREFIX + self._name, **(self._args or {}))
        )
        self._t0 = time.perf_counter()
        if ann is not None:
            ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tel = self._tel
        tel.tracer.slice(self._name, self._track, self._t0,
                         req_id=self._req_id,
                         args=tel.span_args(extra=self._args))
        return False


class Telemetry:
    def __init__(
        self,
        tracer: SpanTracer | None = None,
        registry: MetricsRegistry | None = None,
        logger: JsonLogger | None = None,
        trace_capacity: int = 16384,
        replica: str | None = None,
    ):
        self.tracer = tracer or SpanTracer(capacity=trace_capacity)
        self.registry = registry or MetricsRegistry()
        self.logger = logger or default_logger()
        # replica attribution on every span (ISSUE 20): the merged
        # cross-replica timeline needs each event to say where it ran.
        # Set at construction or later by the server once it knows its id
        # (ApiServer stamps it when the scheduler built its own hub).
        self.replica = replica
        # what holds a span open on the PROFILER's clock: a callable
        # ``(name, **args) -> context manager``, injected by the scheduler
        # (``jax.profiler.TraceAnnotation``) so this package stays free of
        # jax. None: spans are ring-only.
        self.annotation_factory = None
        reg = self.registry
        self.ttft = reg.histogram(
            "dllama_ttft_seconds",
            "submit -> first consumed token, per request",
            LATENCY_BUCKETS_S,
        )
        self.tbt = reg.histogram(
            "dllama_time_between_tokens_seconds",
            "gap between consecutive consumed tokens, per lane",
            LATENCY_BUCKETS_S,
        )
        self.queue_wait = reg.histogram(
            "dllama_queue_wait_seconds",
            "submit -> queue pop, per popped request (pops that resolve "
            "cancelled/expired without claiming a lane included)",
            LATENCY_BUCKETS_S,
        )
        self.step_duration = reg.labelled_histogram(
            "dllama_step_duration_seconds",
            "one engine step by the class of its program (dlstep.*): a "
            "pipelined step's interval between two lagged readbacks (what "
            "every live lane waited for its token); a synchronous prefill "
            "chunk or decode step (sync/spec/multi horizon) start to end",
            LATENCY_BUCKETS_S,
        )
        self.requests_finished = reg.counter(
            "dllama_requests_finished_total",
            "finished requests by finish_reason (shed = drain-flushed, "
            "error = failed before generating)",
        )
        self.tokens_generated = reg.counter(
            "dllama_tokens_generated_total", "tokens consumed across lanes"
        )
        # pod-serving sync cost next to TTFT/TBT: the estimated collective
        # payload accrued per decode-family dispatch (reconciles with the
        # /stats sync_bytes_total field the bridge republishes — same
        # source, delta-fed below)
        self.sync_bytes = reg.counter(
            "dllama_sync_bytes_total",
            "estimated collective payload bytes (per chip) dispatched with "
            "decode-family steps, from the compiled program's post-SPMD HLO",
        )
        # failure containment (serving/breaker.py, runtime/scheduler.py):
        # the breaker state machine as a gauge and classified failures as
        # a labelled counter — both reconciled with the /stats twins via
        # bridge_stats (the state gauge is set from breaker_state_code,
        # the counter delta-fed from the engine_failures dict, so counter
        # semantics survive window resets like dllama_sync_bytes_total)
        self.breaker_state = reg.gauge(
            "dllama_breaker_state",
            "serving circuit breaker: 0 closed, 1 half-open, 2 open "
            "(anything > 0 means /health is reporting unhealthy)",
        )
        self.engine_failures = reg.counter(
            "dllama_engine_failures_total",
            "classified serving failures by failure_class label: engine "
            "(dispatch/consume/transfer raise, contained), request "
            "(per-request input error), watchdog (stalled step)",
        )
        # zero-flush serving: speculation acceptance as a native counter
        # next to the dllama_stats_spec_* gauges the bridge republishes —
        # delta-fed from the /stats spec_emitted field (same recipe as
        # dllama_sync_bytes_total) so counter semantics survive
        # engine.stats.reset() windows
        self.spec_accepted = reg.counter(
            "dllama_spec_accepted_total",
            "tokens consumed from speculative verify steps on DRAFTED "
            "lanes (the /stats spec_emitted field, delta-fed)",
        )
        # crash-durable serving (serving/journal.py, serving/recovery.py):
        # journal writes and replay re-admissions as native counters next
        # to the dllama_stats_* gauges the bridge republishes — delta-fed
        # from the same /stats fields, so the endpoints reconcile while
        # the counters keep Prometheus semantics across window resets
        self.journal_records = reg.counter(
            "dllama_journal_records_total",
            "request-journal records durably written (the /stats "
            "journal_records field, delta-fed)",
        )
        self.recovered_requests = reg.counter(
            "dllama_recovered_requests_total",
            "crashed requests re-admitted by journal replay (the /stats "
            "recovered_requests field, delta-fed)",
        )
        # compile stability (analysis/jitcheck.py): post-warmup XLA
        # compiles as a native counter next to the
        # dllama_stats_jit_compiles_after_warmup gauge the bridge
        # republishes — delta-fed with the sync-bytes recipe so alerting
        # on `increase(dllama_jit_compiles_total[5m]) > 0` works even
        # across /stats window semantics; MUST stay flat in steady
        # serving (one compiled program per family/bucket, warmup-only)
        self.jit_compiles = reg.counter(
            "dllama_jit_compiles_total",
            "XLA backend compiles observed after warmup_engine armed the "
            "recompile witness (the /stats jit_compiles_after_warmup "
            "field, delta-fed) — non-zero means a mid-serving recompile",
        )
        # resource lifecycle (analysis/leakcheck.py): resources found
        # still held at a drain point (scheduler stop, registry close) as
        # a native counter beside the dllama_stats_resource_leaks_total
        # gauge the bridge republishes — delta-fed with the sync-bytes
        # recipe; MUST stay flat (a rise means an acquire escaped every
        # release path, the runtime twin of the resource-balance lint)
        self.resource_leaks = reg.counter(
            "dllama_resource_leaks_total",
            "resources still held at a drain point — scheduler stop or "
            "stream-registry close (the /stats resource_leaks_total "
            "field, delta-fed); non-zero means a lifecycle leak",
        )
        # tiered KV residency (runtime/kvpool.py HostTier): page traffic
        # across the HBM<->host-RAM boundary as a native direction-labelled
        # counter beside the dllama_stats_pool_host_* / dllama_stats_swap_*
        # gauges the bridge republishes — delta-fed from the /stats
        # swap_ins / swap_outs fields with the sync-bytes recipe (a drop
        # means the engine's swap counters were reset: re-baseline, the
        # counter never goes back)
        self.kv_swap = reg.counter(
            "dllama_kv_swap_total",
            "KV pages moved across the residency boundary by direction "
            "label: 'in' host-RAM->HBM reactivations, 'out' HBM->host-RAM "
            "swap-outs (the /stats swap_ins / swap_outs fields, delta-fed)",
        )
        self._sync_bytes_seen = 0
        self._jit_compiles_seen = 0.0
        self._resource_leaks_seen = 0.0
        self._spec_emitted_seen = 0.0
        self._journal_records_seen = 0.0
        self._recovered_seen = 0.0
        self._failures_seen: dict[str, float] = {}
        self._kv_swap_seen: dict[str, float] = {"in": 0.0, "out": 0.0}

    # -- queue binding -------------------------------------------------------

    def bind_queue(self, queue) -> bool:
        """Feed the queue-wait histogram from the queue's own pop-time
        measurement when it offers one (QosQueue.set_wait_observer), so
        the histogram's count reconciles with ``queue_popped`` exactly.
        Returns False when the queue can't (bare FIFO) — the scheduler
        then observes at claim time instead."""
        setter = getattr(queue, "set_wait_observer", None)
        if setter is None:
            return False
        setter(self.queue_wait.observe)
        return True

    # -- request lifecycle hooks --------------------------------------------

    @staticmethod
    def trace_of(req) -> RequestTrace:
        tel = getattr(req, "tel", None)
        if tel is None:
            tel = req.tel = RequestTrace(getattr(req, "submitted_at", None))
        return tel

    def span_args(self, req=None, extra: dict | None = None) -> dict | None:
        """The args every span carries since ISSUE 20: the request's
        fleet-wide ``trace_id`` (when it carried an ``X-DLlama-Trace``
        context) and this process's ``replica`` id — what the router's
        cross-replica merge filters and attributes by."""
        args = dict(extra) if extra else {}
        if req is not None:
            tid = trace_id_of(getattr(req, "trace", None))
            if tid:
                args["trace_id"] = tid
        if self.replica:
            args["replica"] = self.replica
        return args or None

    def span(self, name: str, track: str, req_id: int | None = None,
             args: dict | None = None) -> _Span:
        """Context manager: ``with telemetry.span("loop.wait", "loop"):``
        appends the slice ``name`` to the ring on exit AND holds the
        annotation ``dl.<name>`` open while it runs, so a profiler trace
        carries the same span on the device's clock. The one entry point
        for work that is timed where it happens; work timed after the
        fact (a step's dispatch -> lagged consume) stays with
        ``tracer.slice``."""
        return _Span(self, name, track, req_id, args)

    def on_submit(self, req) -> None:
        tel = self.trace_of(req)
        self.tracer.instant("submitted", "queue", ts=tel.span_t0,
                            req_id=req.id, args=self.span_args(req))

    def on_admit(self, req, lane: int) -> None:
        tel = self.trace_of(req)
        tel.admitted_at = req.admitted_at
        tel.lane = lane
        now_pc = self.tracer.now()
        self.tracer.slice("queued", "queue", tel.span_t0, now_pc,
                          req_id=req.id,
                          args=self.span_args(req, {"lane": lane}))
        tel.span_t0 = now_pc  # the generate slice starts here

    def on_queue_pop(self, req, now: float) -> None:
        """Fallback queue-wait observation for queues WITHOUT a pop-time
        observer (bare FIFO): called by the scheduler right after every
        pop — cancelled/expired pops included — so both queue kinds feed
        the histogram the same population."""
        t0 = getattr(req, "submitted_at", None)
        if t0 is not None:
            self.queue_wait.observe(max(0.0, now - t0))

    def on_prefix_hit(self, req, tokens_saved: int) -> None:
        self.trace_of(req).prefix_saved = int(tokens_saved)

    def on_fused_admit(self, req) -> None:
        """The request's prompt chunks are riding fused dispatches inside
        the live chain (claimed in-chain, or joined the chain with chunks
        still pending)."""
        self.trace_of(req).fused_admitted = True

    def on_prefill_dispatch(self, req, now: float) -> None:
        """A prompt chunk of ``req`` is handed to the engine (``now`` =
        time.monotonic(), taken before the call): the first one stamps
        ``first_dispatch_at``."""
        tel = self.trace_of(req)
        if tel.first_dispatch_at is None:
            tel.first_dispatch_at = now

    def on_prefill_done(self, req, now: float) -> None:
        """The step that carried ``req``'s final prompt chunk has been
        read back: the host now knows the boundary token, and streams
        it right after this stamp."""
        self.trace_of(req).prefill_done_at = now

    def on_prefill_chunk(self, req, lane: int, t0: float, n_tokens: int,
                         fused: bool = False, step: int | None = None,
                         bucket: int | None = None, p_start: int = 0,
                         final: bool = False) -> None:
        """``bucket`` is the prefill bucket the chunk rode
        (``engine.bucket_for(n_tokens)``): the rows the program computed.
        A chunk dispatched alone (not ``fused``) is timed start to end and
        leaves its own row on the request's ``chunks`` (nothing overlapped
        it, so its interval is its wait); a fused chunk's row is its step's
        record, which ``on_pipelined_step`` appends."""
        now_pc = self.tracer.now()
        extra = {"tokens": n_tokens}
        if bucket is not None:
            extra["bucket"] = bucket
        if step is not None:
            extra["step"] = step
        self.tracer.slice(
            "prefill.fused" if fused else "prefill.sync", f"lane{lane}",
            t0, now_pc, req_id=req.id,
            args=self.span_args(req, extra),
        )
        if not fused:
            took = max(0.0, now_pc - t0)
            cls = step_class("prefill", bucket)
            self.step_duration.observe(took, **{"class": cls})
            self.trace_of(req).chunks.append(StepRecord(
                step=0, cls=cls, chunk=n_tokens, p_start=p_start, final=final,
                lanes=0, dry=False, dry_s=0.0, interval_s=took, wait_s=took,
                host_s=0.0, at=time.monotonic(),
            ))

    def on_token(self, req, now: float | None = None) -> None:
        """One streamed token (``now`` = time.monotonic()). First token
        observes TTFT; every later one observes the inter-token gap."""
        tel = self.trace_of(req)
        if now is None:
            now = time.monotonic()
        first = tel.first_token_at is None
        tel.on_token(now)
        self.tokens_generated.inc()
        if first:
            if tel.ttft_s is not None:
                self.ttft.observe(tel.ttft_s)
        else:
            self.tbt.observe(tel.gaps[-1])

    # -- step hooks ----------------------------------------------------------

    def on_step(self, kind: str, t0: float, args: dict | None = None,
                *, cls: str) -> None:
        """One synchronous engine dispatch (kind: sync/spec/multi), timed
        start to end; ``cls`` is the class of the program it ran."""
        now_pc = self.tracer.now()
        self.tracer.slice(f"step.{kind}", "pipeline", t0, now_pc,
                          args=self.span_args(extra=args))
        self.step_duration.observe(max(0.0, now_pc - t0),
                                   **{"class": cls})

    def on_pipelined_step(self, t_dispatch: float, fused_info=None,
                          kind: str = "pipelined",
                          bucket: int | None = None, *,
                          record: StepRecord,
                          t_done: float) -> None:
        """One pipelined step, recorded at CONSUME time (one step behind).
        The slice spans the step's INTERVAL, from the readback before it to
        its own (``t_done``, on the tracer's clock): the slices of a chain
        tile the ``pipeline`` track, each as long as the lanes waited for
        that step's token, and its args are the loop's ``record`` of the
        step (``StepRecord.args``: class, lanes, dry, interval / wait /
        host seconds, the chunk's tokens, start and whether it was its
        prompt's last). ``kind`` distinguishes the in-chain spec verify
        steps (``"spec_pipelined"`` — the zero-flush speculation path)
        from plain pipelined decodes on the trace. For a fused
        prefill+decode step, ``fused_info`` is the scheduler's
        ``(lane_idx, lane, final, n_chunk)``; the admitting lane also gets
        a ``prefill.fused`` slice on its own track (dispatch -> readback)
        and the record joins its request's ``chunks``. ``record.step`` is
        the dispatch's sequence number, the one its ``loop.*`` spans carry;
        ``bucket`` the prefill bucket a fused step's chunk rode, which is
        the class of step program the lanes waited through."""
        step_args = record.args()
        t0 = t_done - record.interval_s
        if fused_info is None:
            self.tracer.slice(f"step.{kind}", "pipeline", t0,
                              t_done, args=self.span_args(extra=step_args))
        else:
            lane_idx, lane, _final, n_chunk = fused_info
            if bucket is not None:
                step_args["bucket"] = bucket
            req = lane.request
            req_id = getattr(req, "id", None)
            # a verify step that ALSO carries a chunk keeps its spec
            # identity on the trace — the composition the zero-flush
            # chain exists for must be countable, not folded into plain
            # fused slices
            name = "step.fused" if kind == "pipelined" else "step.spec_fused"
            self.tracer.slice(
                name, "pipeline", t0, t_done, req_id=req_id,
                args=self.span_args(req, step_args),
            )
            if req is not None:
                self.on_prefill_chunk(req, lane_idx, t_dispatch, n_chunk,
                                      fused=True, step=record.step,
                                      bucket=bucket)
                self.trace_of(req).chunks.append(record)
        self.step_duration.observe(record.interval_s, **{"class": record.cls})

    def on_flush(self, live: int, admitting: int) -> None:
        self.tracer.instant(
            "pipeline.flush", "pipeline",
            args=self.span_args(extra={"live": live, "admitting": admitting}),
        )

    # -- failure containment -------------------------------------------------

    def on_engine_failure(self, error: str, lanes_failed: int,
                          breaker_state: str) -> None:
        """One engine-scoped containment round (runtime/scheduler.py's
        supervised loop): the loop caught an engine raise, failed the
        affected lanes, and kept serving. One trace instant + one
        structured log line — the event operators grep for when error-rate
        alarms fire."""
        self.tracer.instant(
            "engine.failure", "pipeline",
            args=self.span_args(extra={
                "error": error[:200],
                "lanes_failed": lanes_failed,
                "breaker_state": breaker_state,
            }),
        )
        self.logger.emit(
            "engine_failure",
            error=error[:200],
            lanes_failed=lanes_failed,
            breaker_state=breaker_state,
        )

    def on_watchdog_trip(self, waited_s: float, fatal: bool) -> None:
        """The step watchdog (serving/watchdog.py) found a dispatched step
        with no progress past its deadline. The watchdog emits its own
        log line before any fatal exit; this is the scheduler-side trace
        instant tying the trip to the pipeline track."""
        self.tracer.instant(
            "watchdog.trip", "pipeline",
            args=self.span_args(
                extra={"waited_s": round(waited_s, 3), "fatal": fatal}
            ),
        )

    # -- request endings -----------------------------------------------------

    def _summarize(self, req, reason: str | None,
                   error: str | None = None) -> dict:
        tel = self.trace_of(req)
        summary = tel.summary(req, reason)
        if error is not None:
            summary["error"] = error
        req.summary = summary
        self.logger.emit("request", **summary)
        return summary

    def on_finish(self, req, lane: int, reason: str | None) -> None:
        """A request that held a lane ended (stop/length/cancel/timeout)."""
        tel = self.trace_of(req)
        track = f"lane{lane}"
        self.tracer.slice("generate", track, tel.span_t0, req_id=req.id,
                          args=self.span_args(req,
                                              {"finish_reason": reason}))
        self.tracer.instant(f"finish.{reason}", track, req_id=req.id,
                            args=self.span_args(req))
        self.requests_finished.inc(finish_reason=str(reason))
        self._summarize(req, reason)

    def on_unadmitted(self, req, reason: str) -> None:
        """A request resolved without ever claiming a lane (queue timeout,
        cancel while queued, drain shed)."""
        tel = self.trace_of(req)
        self.tracer.slice("queued", "queue", tel.span_t0, req_id=req.id,
                          args=self.span_args(req,
                                              {"finish_reason": reason}))
        self.tracer.instant(f"finish.{reason}", "queue", req_id=req.id,
                            args=self.span_args(req))
        self.requests_finished.inc(finish_reason=reason)
        self._summarize(req, reason)

    def on_error(self, req, lane: int | None, error: str) -> None:
        """A request failed before generating (tokenization/engine error).
        The error string rides the summary BEFORE the log line is emitted,
        so the request's log record carries the reason the 500 names."""
        track = "queue" if lane is None else f"lane{lane}"
        self.tracer.instant("finish.error", track, req_id=req.id,
                            args=self.span_args(req,
                                                {"error": error[:200]}))
        self.requests_finished.inc(finish_reason="error")
        self._summarize(req, "error", error=error[:200])

    # -- startup -------------------------------------------------------------

    def startup_log(self, event: str, **fields) -> None:
        """One structured line deployments verify config from (satellite:
        mesh shape / buckets / pipeline depth / fused on-off in logs)."""
        self.logger.emit(event, **fields)

    # -- exposition ----------------------------------------------------------

    def bridge_stats(self, stats: dict) -> None:
        """Republish a ``/stats`` payload as ``dllama_stats_*`` gauges
        (dict-valued histogram counters become labelled gauges). Values
        land verbatim, so a scrape reconciles with the JSON endpoint
        field-for-field."""
        reg = self.registry
        for key, value in stats.items():
            if value is None:
                continue
            name = STATS_PREFIX + key
            if isinstance(value, bool):
                reg.gauge(name).set(1.0 if value else 0.0)
            elif isinstance(value, (int, float)):
                reg.gauge(name).set(float(value))
            elif isinstance(value, dict):
                g = reg.gauge(name)
                for k, v in value.items():
                    if isinstance(v, (int, float)):
                        g.set(float(v), key=str(k))
        # the native sync-bytes counter tracks the same accounting the
        # dllama_stats_sync_bytes_total gauge republishes, delta-fed so it
        # keeps Prometheus counter semantics across engine.stats.reset()
        # windows (the gauge resets with /stats; the counter never goes back)
        total = stats.get("sync_bytes_total")
        if isinstance(total, (int, float)):
            if total > self._sync_bytes_seen:
                self.sync_bytes.inc(float(total - self._sync_bytes_seen))
            # a drop means the stats window reset: re-baseline, counter keeps
            self._sync_bytes_seen = float(total)
        # speculation acceptance: delta-fed like the sync-bytes counter,
        # with one extra rule — spec_emitted can DIP without a window
        # reset (SpecStream.discard_pending retracts a partially consumed
        # verify step), and re-baselining downward would re-count the
        # retracted tokens on the next rise. Keep the HIGH-WATER mark
        # across a partial dip (the counter stays monotone; the retracted
        # tokens remain counted — they really were consumed) and
        # re-baseline only on a drop to 0 (engine.stats.reset()).
        emitted = stats.get("spec_emitted")
        if isinstance(emitted, (int, float)) and not isinstance(emitted, bool):
            if emitted > self._spec_emitted_seen:
                self.spec_accepted.inc(float(emitted - self._spec_emitted_seen))
                self._spec_emitted_seen = float(emitted)
            elif emitted == 0:
                self._spec_emitted_seen = 0.0
        # crash durability: journal writes and recovery re-admissions,
        # delta-fed with the sync-bytes recipe (monotone within a
        # process; a drop to 0 means the journal/coordinator was swapped,
        # re-baseline without re-counting)
        for fld, ctr, seen_attr in (
            ("journal_records", self.journal_records,
             "_journal_records_seen"),
            ("recovered_requests", self.recovered_requests,
             "_recovered_seen"),
            # jit_compiles_after_warmup never resets within a process
            # (engine.stats.reset() deliberately keeps it), so the
            # monotone delta-feed recipe applies verbatim
            ("jit_compiles_after_warmup", self.jit_compiles,
             "_jit_compiles_seen"),
            # resource_leaks_total never resets within a process either
            # (leakcheck.force(fresh=True) is test-only), so the same
            # monotone recipe applies
            ("resource_leaks_total", self.resource_leaks,
             "_resource_leaks_seen"),
        ):
            v = stats.get(fld)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                seen = getattr(self, seen_attr)
                if v > seen:
                    ctr.inc(float(v - seen))
                setattr(self, seen_attr, float(v))
        # tiered KV residency: direction-labelled swap-page counter,
        # delta-fed from the engine's swap traffic counters (monotone
        # while the engine lives; a drop means reset_swap_stats() /
        # warmup re-baselined — re-baseline here too, counter keeps)
        for fld, direction in (("swap_ins", "in"), ("swap_outs", "out")):
            v = stats.get(fld)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                seen = self._kv_swap_seen[direction]
                if v > seen:
                    self.kv_swap.inc(float(v - seen), direction=direction)
                self._kv_swap_seen[direction] = float(v)
        # breaker exposition (serving/breaker.py): the state gauge tracks
        # breaker_state_code verbatim; the classified-failure counter is
        # delta-fed from the engine_failures dict, same recipe as above
        code = stats.get("breaker_state_code")
        if isinstance(code, (int, float)) and not isinstance(code, bool):
            self.breaker_state.set(float(code))
        fails = stats.get("engine_failures")
        if isinstance(fails, dict):
            for cls, v in fails.items():
                if not isinstance(v, (int, float)):
                    continue
                seen = self._failures_seen.get(cls, 0.0)
                if v > seen:
                    self.engine_failures.inc(
                        float(v - seen), failure_class=str(cls)
                    )
                self._failures_seen[cls] = float(v)

    def render_prometheus(self, bridge: dict | None = None) -> str:
        if bridge:
            self.bridge_stats(bridge)
        return self.registry.render()

    def chrome_trace(self, since: int = 0,
                     trace_id: str | None = None) -> dict:
        return tracer_chrome_trace(self.tracer, since=since,
                                   trace_id=trace_id)

    def dump_trace(self, path: str) -> dict:
        doc = dump_chrome_trace(self.tracer, path)
        self.logger.emit("trace_dump", path=path,
                         events=len(doc["traceEvents"]))
        return doc
