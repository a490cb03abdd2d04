"""Multi-user HTTP API server.

Routes match the reference's dllama-api (src/dllama-api.cpp:338-349):
POST /v1/chat/completions and GET /v1/models, with CORS preflight —
plus, beyond parity: POST /v1/completions (raw-prompt text completion,
no chat template), GET /stats, and GET /health.

Concurrency model is where this departs from the fork: the fork accepts one
connection at a time and blocks the accept loop on future.get()
(dllama-api.cpp:250-288,351-365), so despite its batching loop only one HTTP
request is ever in flight. Here a ThreadingHTTPServer gives every connection
its own thread; all of them submit into the shared RequestQueue and their
generations proceed concurrently in the continuous batch. SSE streaming
(``"stream": true``) is supported — upstream shipped the chunk types but
never wired them (api-types.hpp:45-57).

Observability surface (telemetry/, docs/OBSERVABILITY.md): ``GET /metrics``
serves Prometheus text bridged from the same snapshot ``GET /stats``
returns (the two reconcile by construction), ``GET /trace`` serves the
span ring as Perfetto-loadable Chrome trace JSON, completion responses
carry the per-request summary (ttft_s, tbt p50/p95, queued_s, ...), and
every error payload — 400/500 JSON and mid-stream SSE error events —
names the ``request_id``, so a streamed failure correlates with the
server's per-request JSON log line.

Resumable SSE (crash-durable serving, docs/SERVING.md): every streamed
delta carries its token index as the SSE ``id:`` line; with
``--reconnect-grace`` > 0 a disconnected client reattaches within the
window via ``GET /v1/stream/<request_id>`` + ``Last-Event-ID`` — to the
live request (which kept generating into its bounded relay) or to one
recovered from the request journal after a crash — and the stream
resumes byte-identically. All shed Retry-After hints (queue full,
breaker open, stalled-503) carry deterministic ±20% per-request jitter
so a shed burst's synchronized retries cannot thundering-herd a
recovering replica.
"""

from __future__ import annotations

import itertools
import json
import time
from concurrent.futures import TimeoutError as FutureTimeout
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlsplit

from ..analysis import leakcheck
from ..runtime.scheduler import Request, fresh_request_id
from ..telemetry.tracectx import TRACE_HEADER, TraceContext
from ..serving import (
    AdmissionRejected,
    StreamRelay,
    attach_recovered_stream,
    entry_from_admit_record,
    jittered_retry_after,
)
from ..tokenizer import ChatItem, TemplateType, chat_generator_for
from . import api_types

# defense-in-depth bound on how long an HTTP thread waits on the
# scheduler (seconds). GENEROUS by design: the scheduler's own deadlines
# (queue timeout, generation budget) and the failure-containment layer
# resolve futures long before this; the bound only exists so a wedged
# scheduler — the failure mode the watchdog detects but cannot unblock —
# can never hang a client socket forever.
DEFAULT_RESULT_TIMEOUT_S = 600.0

# Retry-After jitter keys for sheds with no request yet (a draining
# submit that failed before a Request existed): a distinct key per shed
# keeps even those spread across the ±20% band (serving/qos.py)
_shed_keys = itertools.count(1)


class SchedulerStalled(RuntimeError):
    """A request's future made no progress within the server-side wait
    bound: the scheduler is wedged (or the request leaked). Mapped to a
    request_id-carrying 503 + Retry-After — retryable, because a restart
    or the watchdog will have replaced the engine by then."""

    def __init__(self, request_id: int, waited_s: float):
        self.request_id = request_id
        super().__init__(
            f"no scheduler progress on request {request_id} within "
            f"{waited_s:.0f}s; the server is unhealthy — retry elsewhere"
        )


class ApiServer:
    def __init__(self, scheduler, tokenizer, model_name: str = "dllama",
                 template_type: TemplateType = TemplateType.UNKNOWN,
                 result_timeout_s: float = DEFAULT_RESULT_TIMEOUT_S,
                 resume=None, replica_id: str | None = None,
                 role: str = "mixed"):
        """``resume`` (serving/resume.StreamRegistry, built by dllama-api
        when ``--reconnect-grace`` > 0): streamed requests register their
        delta relay so a disconnected client can reattach within the
        grace window (``GET /v1/stream/<id>`` + ``Last-Event-ID``) —
        including streams recovered from the journal after a crash. None
        (the default) preserves cancel-on-disconnect exactly.

        ``replica_id`` (``--replica-id``, default host:port at
        ``serve()``): this replica's name in a fleet — stamped as the
        ``X-DLlama-Replica`` header on every response and onto the SSE
        terminal chunk, so fleet traces and the migration path can
        attribute every shed and every stream to its source replica.

        ``role`` (``--role``, default ``"mixed"``): this replica's
        disaggregation role — ``"prefill"`` replicas take long-prompt
        traffic and hand sessions off after first token,
        ``"decode"``/``"mixed"`` replicas take the decode side.
        Surfaced on ``GET /load`` so the router's scrape learns the
        fleet topology instead of being configured twice."""
        self.scheduler = scheduler
        self.tokenizer = tokenizer
        self.model_name = model_name
        self.chat_template = chat_generator_for(tokenizer, template_type)
        self.result_timeout_s = result_timeout_s
        self.resume = resume
        self.replica_id = replica_id
        self.role = str(role or "mixed")
        self._httpd: ThreadingHTTPServer | None = None
        self._fallback_tel = None  # see _telemetry()

    # -- request handling ---------------------------------------------------

    def _make_request(self, prompt: str, body: dict, streaming: bool,
                      kind: str | None = None,
                      trace: str | None = None) -> tuple[Request, StreamRelay | None]:
        """Shared Request construction for both routes (one place owns the
        body->Request field mapping). Streaming requests get a
        :class:`~..serving.resume.StreamRelay`: every delta is buffered
        with its TOKEN INDEX (the SSE ``id:`` line), which is what makes
        a stream resumable — the pump and any reconnecting client
        address the stream by index, not by socket position.

        ``trace`` is the validated ``X-DLlama-Trace`` wire context (or
        None): stamped onto the Request, so every span this request emits
        carries the fleet-wide trace id and the admit journal record
        (hence migration tickets and crash recovery) re-joins the trace."""
        params = api_types.InferenceParams.from_body(body)
        req = Request(
            prompt=prompt,
            max_tokens=params.max_tokens,
            temperature=params.temperature,
            topp=params.top_p,
            seed=params.seed,
            stop=params.stop,
            user_id=params.user,
            priority=params.priority,
            response_format=params.response_format,
            api_kind=kind,
            trace=trace,
        )
        relay = None
        if streaming:
            if self.resume is not None:
                relay = self.resume.register(req, kind=kind)
            else:
                # no reconnect semantics: unbounded (capacity 0), the
                # pre-resume delta queue's exact behavior — a slow but
                # connected client backpressures into memory, nothing
                # is ever evicted out from under it
                relay = StreamRelay(req.id, capacity=0)
                req.future.add_done_callback(lambda _f: relay.finish())
            # on_delta runs on the scheduler thread right after the token
            # was streamed (counted), so len(generated_tokens) IS the
            # delta's token index
            req.on_delta = lambda d: relay.push(len(req.generated_tokens), d)
        return req, relay

    def build_request(self, body: dict, streaming: bool,
                      trace: str | None = None) -> tuple[Request, StreamRelay | None]:
        """Validate the body and build the Request. Raises ValueError on bad
        input — callers must do this BEFORE committing response headers."""
        messages = api_types.parse_chat_messages(body)
        chat = self.chat_template.generate(
            [ChatItem(m.role, m.content) for m in messages], append_generation_prompt=True
        )
        return self._make_request(chat.content, body, streaming, kind="chat",
                                  trace=trace)

    def build_completion_request(self, body: dict, streaming: bool,
                                 trace: str | None = None) -> tuple[Request, StreamRelay | None]:
        """/v1/completions: the raw prompt goes straight to the scheduler —
        no chat template. Beyond reference parity (the fork serves only
        the chat route, src/dllama-api.cpp:338-349)."""
        prompt = api_types.parse_completion_prompt(body)
        return self._make_request(prompt, body, streaming, kind="completion",
                                  trace=trace)

    def handle_chat_completion(self, body: dict, send_chunk=None, prepared=None) -> dict:
        """Run a (pre-validated) request through the shared batching loop.
        If send_chunk is given, stream deltas through it."""
        req, deltas = prepared if prepared is not None else self.build_request(body, send_chunk is not None)
        return self._run_request(
            req, deltas, send_chunk,
            api_types.chat_chunk_response, api_types.chat_completion_response,
        )

    def handle_completion(self, body: dict, send_chunk=None, prepared=None) -> dict:
        req, deltas = prepared if prepared is not None else self.build_completion_request(body, send_chunk is not None)
        return self._run_request(
            req, deltas, send_chunk,
            api_types.completion_chunk_response, api_types.completion_response,
        )

    def _run_request(self, req, relay, send_chunk, chunk_fn, response_fn) -> dict:
        if req.submitted_at is None:  # streaming pre-submits before headers
            self.scheduler.submit(req)

        if send_chunk:
            try:
                self._pump(req, relay, relay.attach(), 0, send_chunk,
                           chunk_fn)
            except (BrokenPipeError, ConnectionError, OSError):
                if self.resume is not None:
                    # reconnect-grace window: the request KEEPS generating
                    # into its bounded relay; a client reattaching with
                    # Last-Event-ID (GET /v1/stream/<id>) resumes
                    # mid-stream, and the registry reaper cancels on
                    # grace expiry if nobody returns
                    self.resume.detach(req.id)
                else:
                    # default (grace 0): free the lane instead of
                    # generating to max_tokens into an orphaned buffer
                    req.cancel()
                raise
            return {}

        try:
            # satellite (failure containment): a generous bound so a wedged
            # scheduler can never hang a client socket forever — mapped to
            # a request_id-carrying 503 by the route handler
            text = req.future.result(timeout=self.result_timeout_s)
        except FutureTimeout:
            req.cancel()  # frees the lane if the loop ever recovers
            raise SchedulerStalled(req.id, self.result_timeout_s) from None
        return response_fn(
            self.model_name, req.id, text, req.n_prompt_tokens, len(req.generated_tokens),
            req.finish_reason or "stop", summary=req.summary,
        )

    def _pump(self, req, relay, gen, after, send_chunk, chunk_fn) -> bool:
        """Drain a stream's relay to one SSE consumer, starting after
        token index ``after`` (0 for a fresh stream, the client's
        Last-Event-ID on a reconnect). Every delta goes out with its
        token index as the SSE ``id:`` line and — once it has reached
        the client transport — advances the journal's delivery
        watermark, so a crash recovers to a point the client had
        actually seen. Returns True when the terminal chunk went out,
        False on a quiet end (superseded by a newer consumer, or a
        resume gap the client must restart from)."""
        journal = getattr(self.scheduler, "journal", None)
        while True:
            item = relay.next_after(after, timeout=self.result_timeout_s,
                                    gen=gen)
            if item is None:
                # bounded like the non-streaming wait: the gap between
                # deltas is the streaming liveness signal, and a wedged
                # scheduler must become a terminal error chunk, not a
                # socket held open forever
                req.cancel()
                raise SchedulerStalled(req.id, self.result_timeout_s)
            tag = item[0]
            if tag == "delta":
                _, idx, text = item
                send_chunk(
                    chunk_fn(self.model_name, req.id, text, False),
                    event_id=idx,
                )
                after = idx
                if journal is not None:
                    # watermark AFTER the chunk reached the transport
                    # (a diagnostics floor — recovery never discards by
                    # it, since a socket write is not client receipt)
                    journal.note_progress(req.id, idx)
                continue
            if tag == "superseded":
                return False  # a reconnect took the stream over; unwind
            if tag == "gap":
                # deltas past this consumer's position were evicted from
                # the bounded buffer: byte-identical resumption is
                # impossible — fail closed rather than silently skip
                send_chunk({
                    "error": "resume window exceeded; restart the request",
                    "reason": "resume_gap", "request_id": req.id,
                })
                if self.resume is not None:
                    # a client that closes cleanly after this error chunk
                    # raises no socket exception, so nothing else would
                    # start the grace clock — without this the request
                    # generates to max_tokens for nobody and its entry
                    # only clears at natural finish plus a grace window
                    self.resume.detach(req.id)
                return False
            break  # ("done",): the future resolved
        try:
            req.future.result()  # re-raise failures
        except AdmissionRejected as e:
            # shed after the SSE headers were committed (drain flush, or
            # the paged pool's post-submit pool_exhausted) — too late
            # for the 429/503 status line, so the typed shed ships as an
            # error chunk first: reason + Retry-After hint, or a stream
            # client reads the empty "cancelled" terminal as the model's
            # answer and never backs off or retries
            shed = {
                "error": str(e), "reason": e.reason,
                "retry_after_s": round(
                    jittered_retry_after(e.retry_after_s, req.id), 2
                ),
                "request_id": req.id,
            }
            if self.replica_id:
                shed["replica"] = self.replica_id
            send_chunk(shed)
            req.finish_reason = "cancelled"
        # terminal chunk carries the SAME per-request summary the
        # non-streaming response does (one producer: the scheduler's
        # telemetry finish hook), so stream clients are not blind —
        # plus the replica id, so fleet traces can attribute the stream
        # (and a router can name the source on migration) even when the
        # response headers were consumed by an intermediary
        term = chunk_fn(
            self.model_name, req.id, None, True,
            req.finish_reason or "stop", summary=req.summary,
        )
        if self.replica_id:
            term["replica"] = self.replica_id
        send_chunk(term, event_id=len(req.generated_tokens))
        return True

    def handle_models(self) -> dict:
        return api_types.models_response(self.model_name)

    def handle_stats(self) -> dict:
        """Serving metrics (beyond reference parity — SURVEY §5.5 notes it
        has no metrics endpoint): engine counters plus scheduler occupancy
        and QoS state. Engine counters come from ONE locked snapshot, not
        field-by-field reads racing the batching thread."""
        sched = self.scheduler
        stats = sched.engine.stats.snapshot()
        busy, total = sched.occupancy()
        out = {
            "prefill_tokens": stats["prefill_tokens"],
            "prefill_s": round(stats["prefill_s"], 3),
            "decode_steps": stats["decode_steps"],
            "decode_s": round(stats["decode_s"], 3),
            "host_bytes_in": stats["host_bytes_in"],
            "spec_steps": stats["spec_steps"],
            "spec_emitted": stats["spec_emitted"],
            "spec_lane_steps": stats["spec_lane_steps"],
            # acceptance per (DRAFTED lane, verify-step): 1.0 = no draft
            # accepted, K+1 = full acceptance. Sampled/draft-less lanes ride
            # the same batched call but are excluded from both counters.
            "spec_tokens_per_lane_step": (
                round(stats["spec_emitted"] / stats["spec_lane_steps"], 3)
                if stats["spec_lane_steps"] else None
            ),
            # zero-flush serving: spec verify steps dispatched INSIDE the
            # pipelined ring, the device accept-count histogram (drafted
            # lanes only; 0 = nothing survived the carry-alignment gate,
            # SPEC_DRAFT = full acceptance), and lanes routed through the
            # host Sampler (host_sampling=True only — 0 in default
            # serving, where the on-device sampler is full-vocab exact).
            # /metrics carries dllama_spec_accepted_total delta-fed from
            # the spec_emitted field (telemetry/hub.bridge_stats).
            "spec_pipelined_steps": stats["spec_pipelined_steps"],
            "spec_accept_hist": {
                str(k): v
                for k, v in sorted(stats["spec_accept_hist"].items())
            },
            "host_exact_lanes": stats["host_exact_lanes"],
            # per-step collective traffic (mesh runs; 0 single-chip): the
            # static per-decode estimate, the collective count behind it,
            # and the cumulative payload accrued per decode-family
            # dispatch — the /metrics dllama_sync_bytes_total counter is
            # delta-fed from the same field (telemetry/hub.bridge_stats)
            "sync_bytes_per_decode": stats["sync_bytes_per_decode"],
            "sync_collectives_per_decode": stats["sync_collectives_per_decode"],
            "sync_bytes_total": stats["sync_bytes_total"],
            # multi-step horizons taken (each = several decode steps in one
            # device dispatch; decode_steps counts the chained steps)
            "multi_dispatches": stats["multi_dispatches"],
            # async decode pipeline: host consume time hidden behind device
            # execution, steps dispatched device-fed, chains aborted before
            # their lanes finished, and ring occupancy right after each
            # dispatch (how deep the overlap actually ran)
            "overlap_s": round(stats["overlap_s"], 3),
            "pipeline_dispatches": stats["pipeline_dispatches"],
            "pipeline_flushes": stats["pipeline_flushes"],
            "pipeline_depth_hist": {
                str(k): v
                for k, v in sorted(stats["pipeline_depth_hist"].items())
            },
            # the dry-dispatch witness (runtime/scheduler.py): dispatches
            # made when the device had already finished everything in
            # flight, the most it can have stood idle for them (host
            # clock), and live lanes summed over the pipelined dispatches
            # (over pipeline_dispatches x lanes: how full the batch ran)
            "pipeline_dry_dispatches": stats["pipeline_dry_dispatches"],
            "pipeline_dry_s": round(stats["pipeline_dry_s"], 6),
            "live_lane_steps": stats["live_lane_steps"],
            # stall-free admissions: fused prefill+decode dispatches taken
            # (admissions riding the live chain), host time decode lanes
            # spent stalled behind admission work, and which prefill
            # buckets the fused dispatches carried
            "fused_steps": stats["fused_steps"],
            "admission_stall_s": round(stats["admission_stall_s"], 3),
            "fused_bucket_hist": {
                str(k): v
                for k, v in sorted(stats["fused_bucket_hist"].items())
            },
            "prefix_hits": stats["prefix_hits"],
            "prefix_tokens_saved": stats["prefix_tokens_saved"],
            # grammar-constrained decoding (grammar/): admissions that
            # attached a compiled automaton and dispatches that carried
            # at least one constrained lane; the slab-pressure gauges
            # (schemas installed/live, state occupancy) ride qos_stats
            "grammar_lanes": stats["grammar_lanes"],
            "grammar_masked_steps": stats["grammar_masked_steps"],
            # decode attention's cache reads in rows of one layer's plane:
            # what the decode steps fetched (whole blocks up to each live
            # lane's row where the in-place kernel engages) against reading
            # every lane's whole plane
            "attn_kv_rows_read": stats["attn_kv_rows_read"],
            "attn_kv_rows_whole": stats["attn_kv_rows_whole"],
            # selective state-space layers: (live lane, layer) running sums
            # the decode steps advanced, and prompt rows through the chunked
            # scan (real, and with their buckets' padding); 0 for a model
            # without such layers
            "ssm_lane_steps": stats["ssm_lane_steps"],
            "ssm_rows_scanned": stats["ssm_rows_scanned"],
            "ssm_rows_computed": stats["ssm_rows_computed"],
            # linear-attention layers: bytes of float32 matrix state the
            # decode steps read and wrote; block-sparse layers: blocks a kv
            # head attended and held over live lanes and steps, and lane-steps
            # at or past sparse_dense_len; all 0 for a model without them
            "linear_state_bytes_moved": stats["linear_state_bytes_moved"],
            "linear_rows_computed": stats["linear_rows_computed"],
            # delta-rule layers, counted alike (0 for a model without them)
            "delta_state_bytes_moved": stats["delta_state_bytes_moved"],
            "delta_rows_computed": stats["delta_rows_computed"],
            "attn_blocks_read": stats["attn_blocks_read"],
            "attn_blocks_held": stats["attn_blocks_held"],
            "sparse_lane_steps": stats["sparse_lane_steps"],
            # window attention layers, in rows of one such layer's ring: what
            # the decode steps fetched, what a full-context layer fetched,
            # what a window layer would have fetched of a plane; and the
            # prefill attention computed a key block at a time, in (query
            # row, key block) pairs: what ran and the least the mask allows;
            # all 0 for a model without window layers
            "attn_window_rows_read": stats["attn_window_rows_read"],
            "attn_full_rows_read": stats["attn_full_rows_read"],
            "attn_window_rows_plane": stats["attn_window_rows_plane"],
            # the rows either kind's reads attend (pos + 1; min(pos + 1, W))
            "attn_full_rows_needed": stats["attn_full_rows_needed"],
            "attn_window_rows_needed": stats["attn_window_rows_needed"],
            "prefill_attn_blocks_visited": stats["prefill_attn_blocks_visited"],
            "prefill_attn_blocks_causal": stats["prefill_attn_blocks_causal"],
            # a routed FFN's reads of its expert stacks over the decode
            # steps: distinct (layer, expert) slabs fetched, what a sweep of
            # every expert fetches, and (row, expert) pairs routed; then, over
            # those steps AND the prompt chunks that rode them, the pairs that
            # took a row of the grouped kernel's tiles and those tiles' rows;
            # all 0 for a model without routed layers
            "moe_slabs_read": stats["moe_slabs_read"],
            "moe_slabs_whole": stats["moe_slabs_whole"],
            "moe_assignments": stats["moe_assignments"],
            "moe_tile_pairs": stats["moe_tile_pairs"],
            "moe_tile_rows": stats["moe_tile_rows"],
            # failure containment (multihost.worker_serve): supervised
            # restarts + classified protocol errors on THIS process —
            # non-zero only on pod processes that actually restarted
            "worker_restarts": stats["worker_restarts"],
            "worker_replay_errors": stats["worker_replay_errors"],
            # compile stability (analysis/jitcheck.py): XLA compiles
            # observed after warmup armed the recompile witness — MUST
            # read 0 in steady serving (one compiled program per
            # (family, bucket), compiled only at warmup); /metrics
            # carries the dllama_stats_* gauge plus the delta-fed
            # dllama_jit_compiles_total counter (telemetry/hub)
            "jit_compiles_after_warmup": stats["jit_compiles_after_warmup"],
            "lanes_total": total,
            "lanes_busy": busy,
        }
        # resource lifecycles (analysis/leakcheck.py): the process-wide
        # witness counters — resources found held at drain points (MUST
        # read 0, the leak twin of jit_compiles_after_warmup) — plus
        # this scheduler's LIVE ownership gauge (busy serving holds
        # pages/tickets/marks legitimately; only drain points assert
        # zero). bridge_stats republishes resources_live as a labelled
        # gauge and delta-feeds dllama_resource_leaks_total (telemetry/hub)
        out.update(leakcheck.stats())
        # the configured dequant arithmetic (--dequant / DLLAMA_DEQUANT), so
        # a /stats snapshot pins WHICH kernel chain produced the throughput
        # it reports
        from ..ops import pallas_q40

        out["dequant_mode"] = pallas_q40.DEQUANT_MODE
        # which device and weight/kernel path this process really serves
        # from (app/runtime_setup.load_stack — the runtime_device start-up
        # line carries the same facts); absent on engines built without it
        out.update(getattr(sched.engine, "device_facts", None) or {})
        leak_counts = getattr(sched, "leak_counts", None)
        if callable(leak_counts):
            out["resources_live"] = leak_counts()
        qos = getattr(sched, "qos_stats", None)
        if callable(qos):  # queue depth/wait/rejections, timeouts, drain
            out.update(qos())
        if self.resume is not None:  # SSE reattach registry (resume.py)
            out.update(self.resume.stats())
        tel = self._telemetry()
        if tel is not None:  # ring occupancy/eviction: a truncated /trace
            out.update(tel.tracer.counts())  # window is visible, not silent
        return out

    def handle_load(self) -> dict:
        """The fleet routing surface (``GET /load``; the same fields ride
        the ``/health`` body): ONE cheap JSON with everything a router
        needs per routing decision — queue depth, free lanes, paged-pool
        pressure, breaker state, draining flag — so a fleet front-end
        never has to parse full Prometheus text to pick a replica.
        Always HTTP 200 (it is a machine surface, not a readiness
        probe; ``/health`` keeps the status-code semantics)."""
        sched = self.scheduler
        busy, total = sched.occupancy()
        breaker = getattr(sched, "breaker", None)
        depth_fn = getattr(sched.queue, "depth", None)
        draining = bool(getattr(sched, "draining", False))
        br_state = breaker.state if breaker is not None else "closed"
        out = {
            "status": (
                "draining" if draining
                else ("unhealthy" if br_state != "closed" else "ok")
            ),
            "replica": self.replica_id,
            "model": self.model_name,
            "role": self.role,
            "queue_depth": int(depth_fn()) if callable(depth_fn) else 0,
            "lanes_free": total - busy,
            "lanes_total": total,
            "breaker": br_state,
            "draining": draining,
        }
        pool = getattr(sched.engine, "pool_stats", None)
        ps = pool() if callable(pool) else {}
        if ps:  # paged engines only — contiguous ones OMIT the fields
            # (a literal 0 pages free would read as a full pool)
            out["pool_pages_free"] = ps.get("pool_pages_free", 0)
            out["pool_pages_total"] = ps.get("pool_pages_total", 0)
            out["pool_parked_pages"] = ps.get("pool_parked_pages", 0)
        # clock-offset anchor for the fleet trace merge: this replica's
        # CURRENT position on its /trace timebase (µs since the span
        # tracer's perf_counter origin — the same rebasing chrome_trace
        # applies). The router brackets the scrape with its own clock and
        # estimates offset = local_midpoint − this stamp, uncertainty =
        # RTT/2; perf_counter origins are per-process, so there is no
        # cross-host clock to read directly.
        out["trace_clock_us"] = round(
            (time.perf_counter() - self._telemetry().tracer.origin) * 1e6, 1
        )
        return out

    def _telemetry(self):
        """The scheduler's telemetry hub (telemetry/), or a lazily built
        standalone one for custom schedulers without it — /metrics then
        still serves the bridged /stats gauges."""
        tel = getattr(self.scheduler, "telemetry", None)
        if tel is None:
            if self._fallback_tel is None:
                from ..telemetry import Telemetry

                self._fallback_tel = Telemetry()
            tel = self._fallback_tel
        return tel

    def handle_metrics(self) -> str:
        """Prometheus text exposition: the native latency histograms and
        request counters plus every /stats field bridged as a
        ``dllama_stats_*`` gauge — sampled from the same snapshot, so the
        two endpoints reconcile (docs/OBSERVABILITY.md)."""
        return self._telemetry().render_prometheus(bridge=self.handle_stats())

    def handle_trace(self, since: int = 0,
                     trace_id: str | None = None) -> dict:
        """The span ring as Chrome trace-event JSON (Perfetto loadable).

        ``since`` (the doc's top-level ``cursor`` from a prior pull)
        returns only newer events — incremental polling instead of
        re-downloading the whole ring; ``trace_id`` returns only the
        events of one fleet trace (what the router's cross-replica merge
        pulls per replica)."""
        return self._telemetry().chrome_trace(since=since, trace_id=trace_id)

    # -- plumbing -----------------------------------------------------------

    def serve(self, host: str = "0.0.0.0", port: int = 9990) -> ThreadingHTTPServer:
        api = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # quiet
                pass

            def _cors(self):
                self.send_header("Access-Control-Allow-Origin", "*")
                self.send_header("Access-Control-Allow-Methods", "GET, POST, OPTIONS")
                self.send_header("Access-Control-Allow-Headers", "Content-Type, Authorization")

            def _json(self, code: int, payload: dict, headers: dict | None = None):
                self._raw(
                    code, json.dumps(payload).encode(), "application/json",
                    headers,
                )

            def _raw(self, code: int, data: bytes, content_type: str,
                     headers: dict | None = None):
                self.send_response(code)
                self._cors()
                if api.replica_id:
                    # fleet attribution: every response names its source
                    # replica, so router traces and migration decisions
                    # can attribute sheds/errors without guessing
                    self.send_header("X-DLlama-Replica", api.replica_id)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(data)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(data)

            def _reject(self, e: AdmissionRejected, key: int | None = None):
                # load shed: 429 (queue full) / 503 (draining/breaker),
                # with a Retry-After hint so well-behaved clients back
                # off — jittered ±20% per request (serving/qos.py) so a
                # shed burst's synchronized retries don't thundering-herd
                # the replica the moment it recovers
                retry = jittered_retry_after(
                    e.retry_after_s, key if key is not None else next(_shed_keys)
                )
                self._json(
                    e.http_status,
                    {"error": str(e), "reason": e.reason},
                    headers={"Retry-After": str(max(1, round(retry)))},
                )

            def _sse_headers(self, request_id: int | None = None):
                self.send_response(200)
                self._cors()
                if api.replica_id:
                    self.send_header("X-DLlama-Replica", api.replica_id)
                if request_id is not None:
                    # names the stream BEFORE any delta payload does: a
                    # fleet router fetches its migration ticket
                    # (/admin/session/<id>) off this, so a stream that
                    # dies before its first delta is still migratable
                    self.send_header("X-DLlama-Request", str(request_id))
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                self.end_headers()

            def _sse_chunk(self, payload: dict, event_id=None):
                # the `id:` line is the delta's TOKEN INDEX — what a
                # reconnecting client echoes back as Last-Event-ID to
                # resume the stream exactly where it left off
                buf = b""
                if event_id is not None:
                    buf += f"id: {event_id}\n".encode()
                buf += b"data: " + json.dumps(payload).encode() + b"\n\n"
                self.wfile.write(buf)
                self.wfile.flush()

            def do_OPTIONS(self):  # CORS preflight (dllama-api.cpp:228-236)
                self.send_response(204)
                self._cors()
                self.send_header("Content-Length", "0")
                self.end_headers()

            def do_GET(self):
                if self.path == "/v1/models":
                    self._json(200, api.handle_models())
                elif self.path.startswith("/v1/stream/"):
                    # resumable SSE (serving/resume.py): reattach to a
                    # live or journal-recovered stream by request id,
                    # replaying from the client's Last-Event-ID
                    self._resume_stream()
                elif self.path == "/load":
                    # fleet routing surface: one cheap JSON per routing
                    # decision (queue depth, free lanes, pool pressure,
                    # breaker, draining) — always 200, the router reads
                    # the fields, not the status line
                    self._json(200, api.handle_load())
                elif self.path.startswith("/admin/session/"):
                    # fleet migration ticket: a live session's admit wire
                    # record (resolved seed included) + watermark, for a
                    # router to hand to another replica's /admin/migrate
                    self._export_session()
                elif self.path.startswith("/admin/kvpages/"):
                    # disaggregated prefill: a live session's committed
                    # KV-page bundle (disagg/kvtransfer.py), for a router
                    # to push to a decode replica's /admin/kvimport
                    self._export_pages()
                elif self.path == "/stats":
                    self._json(200, api.handle_stats())
                elif self.path == "/metrics":
                    # Prometheus text exposition format (the version the
                    # format spec names; scrapers key on it)
                    self._raw(
                        200, api.handle_metrics().encode(),
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
                elif self.path.split("?", 1)[0] == "/trace":
                    # Chrome trace-event JSON: save and load in Perfetto.
                    # ?since=<cursor> returns only newer events (the
                    # response's top-level `cursor` is the resume point);
                    # ?trace_id=<32-hex> filters to one fleet trace (what
                    # the router's /trace/<id> merge pulls per replica)
                    q = parse_qs(urlsplit(self.path).query)
                    try:
                        since = int(q.get("since", ["0"])[0])
                    except ValueError:
                        self._json(400, {"error": "bad since cursor"})
                        return
                    trace_id = q.get("trace_id", [None])[0]
                    self._json(200, api.handle_trace(
                        since=since, trace_id=trace_id,
                    ))
                elif self.path in ("/", "/health"):
                    # readiness: flips to 503 during drain so load balancers
                    # stop routing here while in-flight work finishes — and
                    # while the engine circuit breaker is open/half-open
                    # (serving/breaker.py: repeated engine failures or a
                    # watchdog-detected stall), so a failing replica stops
                    # taking traffic instead of collecting hung clients
                    # the body is handle_load()'s full machine surface
                    # (queue depth, free lanes, pool pressure, breaker,
                    # draining) so a router scraping /health per routing
                    # decision gets everything in one parse; the status
                    # CODE keeps the load-balancer readiness semantics
                    breaker = getattr(api.scheduler, "breaker", None)
                    load = api.handle_load()
                    if load["draining"]:
                        self._json(503, load, headers={"Retry-After": "5"})
                    elif load["breaker"] != "closed":
                        self._json(
                            503, load,
                            headers={
                                "Retry-After": str(
                                    max(1, round(breaker.retry_after_s()))
                                )
                            },
                        )
                    else:
                        self._json(200, load)
                else:
                    self._json(404, {"error": "not found"})

            def _export_session(self):
                """``GET /admin/session/<request_id>``: export a live
                session's migration ticket — the admit wire record
                (prompt tokens + RESOLVED seed + params) plus the
                streamed-token watermark. 404 for unknown/finished
                requests and for schedulers without the export surface.
                The router caches this at stream start so a replica
                death can still be migrated after the source is gone."""
                try:
                    rid = int(self.path.rsplit("/", 1)[1])
                except ValueError:
                    self._json(400, {"error": "bad session id"})
                    return
                export = getattr(api.scheduler, "export_session", None)
                rec = export(rid) if callable(export) else None
                if rec is None:
                    self._json(404, {
                        "error": "unknown or finished session "
                                 "(only admitted, in-flight requests "
                                 "export a migration ticket)",
                        "request_id": rid,
                    })
                    return
                self._json(200, rec)

            def _export_pages(self):
                """``GET /admin/kvpages/<request_id>``: export a live
                session's committed KV-page bundle (integrity-hashed,
                ``disagg/kvtransfer.py``'s wire format) for disaggregated
                prefill hand-off. 404 for unknown/finished requests, for
                contiguous (non-paged) engines, and for schedulers
                without the export surface — the router then degrades to
                ticket-only migration, which re-prefills on the decode
                replica instead of adopting pages."""
                try:
                    rid = int(self.path.rsplit("/", 1)[1])
                except ValueError:
                    self._json(400, {"error": "bad session id"})
                    return
                export = getattr(
                    api.scheduler, "export_session_pages", None
                )
                try:
                    bundle = export(rid) if callable(export) else None
                except Exception as e:  # noqa: BLE001 — admin plane
                    # answers JSON (e.g. a device-op timeout on a wedged
                    # step); the router degrades to ticket-only migration
                    self._json(503, {
                        "error": f"kv page export failed: {e}",
                        "reason": "export_failed",
                        "request_id": rid,
                    })
                    return
                if bundle is None:
                    self._json(404, {
                        "error": "no exportable kv pages "
                                 "(unknown/finished session, or this "
                                 "replica runs a contiguous kv cache)",
                        "request_id": rid,
                    })
                    return
                self._json(200, bundle)

            def _admin_kvimport(self, body: dict):
                """``POST /admin/kvimport``: verify + adopt a KV-page
                bundle exported from another replica's
                ``/admin/kvpages/<id>``. Every page hash re-verifies
                BEFORE any pool mutation; adoption is refcount-correct
                (``KVPagePool.adopt``) and pins the chain like a parked
                session, so a following ``/admin/migrate`` of the same
                session finds the prefix in the tree and prefills
                tail-only. A pool-exhausted adoption answers the same
                typed 429 + Retry-After shape every admission shed uses
                (the router's fallback is the monolithic path — the
                session is still live on the prefill replica)."""
                from ..disagg.kvtransfer import KVTransferError, adopt_bundle
                from ..runtime.kvpool import PoolExhausted

                engine = getattr(api.scheduler, "engine", None)
                pool = getattr(engine, "kvpool", None)
                if pool is None:
                    self._json(409, {
                        "error": "kv import needs a paged engine "
                                 "(--paged-kv) on this replica",
                    })
                    return
                # through the scheduler loop's step boundary: the adopt
                # mutates the pool and writes device pages, which must
                # not race the pipelined chain's cache donation
                run = getattr(api.scheduler, "run_device_op", None)
                try:
                    if callable(run):
                        receipt = run(lambda: adopt_bundle(pool, engine, body))
                    else:
                        # dlint: ok[device-affinity] scheduler stand-ins without run_device_op have no loop thread racing the adopt
                        receipt = adopt_bundle(pool, engine, body)
                except KVTransferError as e:
                    # 422: the bundle itself is bad (corrupt, wrong
                    # geometry) — NOT retryable against this payload
                    self._json(422, {"error": str(e), "reason": e.reason})
                    return
                except PoolExhausted as e:
                    self._reject(AdmissionRejected(
                        "pool_exhausted", retry_after_s=2.0,
                    ))
                    del e
                    return
                except Exception as e:  # noqa: BLE001 — admin plane
                    # answers JSON, never a raw handler stack trace
                    self._json(500, {"error": str(e)})
                    return
                receipt["replica"] = api.replica_id
                self._json(200, receipt)

            def _admin_migrate(self, body: dict):
                """``POST /admin/migrate``: accept a session exported
                from another replica (the admit wire record
                ``/admin/session/<id>`` serves) and regenerate it here
                byte-identically through NORMAL breaker-gated admission —
                PR 10's deterministic replay as a migration primitive.
                The client (usually the router) then reattaches via
                ``GET /v1/stream/<id>`` + ``Last-Event-ID``; the relay
                re-buffers the whole regenerated stream from base=0 and
                Last-Event-ID alone picks the resume point (zero lost,
                zero duplicated tokens). A shed (breaker open, queue
                full, draining, pool exhausted) answers with the same
                typed 429/503 + Retry-After shape every admission shed
                uses, so routers retry elsewhere on the hint."""
                try:
                    entry = entry_from_admit_record(body)
                except ValueError as e:
                    self._json(400, {"error": f"bad migration record: {e}"})
                    return
                if entry.stream and api.resume is None:
                    # without a resume registry the regenerated stream
                    # has nowhere to buffer and no reattach route — a
                    # clear config error, not a retryable shed
                    self._json(409, {
                        "error": "stream migration needs "
                                 "--reconnect-grace > 0 on the target "
                                 "replica (no resume registry)",
                    })
                    return
                # id-collision remap: every replica numbers requests
                # from 1, so the injected ORIGINAL id routinely names a
                # LIVE request here — registering under it would clobber
                # that request's relay/session record and hand its
                # reattaching client ANOTHER user's stream. A live
                # session record (admitted) or registry entry (streamed,
                # queued ones register at build time) means collision:
                # re-admit under a fresh local id. The response's
                # request_id is authoritative either way — the router
                # reattaches by it, never by the ticket's original id.
                export = getattr(api.scheduler, "export_session", None)
                live = (
                    callable(export)
                    and export(entry.request_id) is not None
                ) or (
                    api.resume is not None
                    and api.resume.contains(entry.request_id)
                )
                if live:
                    entry.request_id = fresh_request_id()
                req, registered = attach_recovered_stream(
                    api.scheduler, entry, api.resume
                )
                try:
                    api.scheduler.submit(req)
                except AdmissionRejected as e:
                    if registered:
                        # nothing will ever resolve the future — drop
                        # the entry or the registry leaks one per shed
                        api.resume.discard(req.id)
                    self._reject(e, key=req.id)
                    return
                except Exception as e:  # noqa: BLE001 — a migrate inject
                    # must answer JSON, never a raw handler stack trace
                    if registered:
                        api.resume.discard(req.id)
                    self._json(500, {"error": str(e), "request_id": req.id})
                    return
                self._json(200, {
                    "request_id": req.id,
                    "stream_path": f"/v1/stream/{req.id}",
                    "watermark": entry.watermark,
                    "replica": api.replica_id,
                })

            def _resume_stream(self):
                """GET /v1/stream/<request_id> + ``Last-Event-ID``: the
                reconnect half of resumable SSE. 404s when resumption is
                off (--reconnect-grace 0, the default), the id is
                unknown, or the grace window expired."""
                if api.resume is None:
                    self._json(404, {
                        "error": "stream resumption disabled "
                                 "(--reconnect-grace is 0)",
                    })
                    return
                try:
                    rid = int(self.path.rsplit("/", 1)[1])
                except ValueError:
                    self._json(400, {"error": "bad stream id"})
                    return
                raw = self.headers.get("Last-Event-ID")
                try:
                    # no Last-Event-ID -> resume from the relay's base
                    # (0 for recovered streams: without the client's own
                    # position there is no safe skip point — the full
                    # regenerated stream replays)
                    after = None if raw is None else int(raw)
                except ValueError:
                    self._json(400, {"error": f"bad Last-Event-ID {raw!r}"})
                    return
                entry = api.resume.attach(rid)
                if entry is None:
                    self._json(404, {
                        "error": "unknown or expired stream "
                                 "(reconnect-grace window passed?)",
                        "request_id": rid,
                    })
                    return
                req, relay, kind, gen = entry
                chunk_fn = (
                    api_types.completion_chunk_response
                    if kind == "completion"
                    else api_types.chat_chunk_response
                )
                self._sse_headers(request_id=req.id)
                try:
                    api._pump(req, relay, gen,
                              relay.base if after is None else after,
                              self._sse_chunk, chunk_fn)
                    self.wfile.write(b"data: [DONE]\n\n")
                except (BrokenPipeError, ConnectionError, OSError):
                    api.resume.detach(rid)  # gone again: restart the grace clock
                except Exception as e:  # headers already out: SSE error event
                    self._sse_chunk({"error": str(e), "request_id": rid})
                    self.wfile.write(b"data: [DONE]\n\n")

            def do_POST(self):
                routes = {
                    "/v1/chat/completions": (
                        api.build_request, api.handle_chat_completion
                    ),
                    "/v1/completions": (
                        api.build_completion_request, api.handle_completion
                    ),
                }
                route = routes.get(self.path)
                admin = self.path in ("/admin/migrate", "/admin/kvimport")
                if route is None and not admin:
                    self._json(404, {"error": "not found"})
                    return
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(length) or b"{}")
                except (ValueError, json.JSONDecodeError) as e:
                    self._json(400, {"error": f"bad request: {e}"})
                    return
                if self.path == "/admin/migrate":
                    # fleet migration inject (see _admin_migrate): rides
                    # the same body parse, then the recovery path
                    self._admin_migrate(body)
                    return
                if self.path == "/admin/kvimport":
                    # disagg page adoption (see _admin_kvimport)
                    self._admin_kvimport(body)
                    return
                build_fn, handle_fn = route
                # fleet trace context: accept a VALID X-DLlama-Trace wire
                # value (the router mints one per request; clients may
                # send their own); malformed/absent values are dropped —
                # tracing never fails or sheds a request
                ctx = TraceContext.parse(self.headers.get(TRACE_HEADER))
                trace = ctx.to_header() if ctx is not None else None
                # request id in EVERY failure payload once a Request exists
                # (satellite: a streamed failure must correlate with the
                # server's per-request log lines); None before build_fn
                # succeeds — those are input errors with no request yet
                req = None

                def err(payload: dict) -> dict:
                    if req is not None:
                        payload["request_id"] = req.id
                    return payload

                try:
                    if body.get("stream"):
                        # validate AND submit BEFORE committing SSE headers so
                        # bad input still gets a proper 400 and a shed request
                        # (queue full / draining) a proper 429/503
                        prepared = build_fn(body, streaming=True, trace=trace)
                        req = prepared[0]
                        try:
                            api.scheduler.submit(req)
                        except BaseException:
                            # shed (breaker/queue/draining): the relay
                            # was registered at build time, and nothing
                            # will ever resolve this future or detach it
                            # — drop the entry or the registry leaks one
                            # per shed streaming POST
                            if api.resume is not None:
                                api.resume.discard(req.id)
                            raise
                        try:
                            self._sse_headers(request_id=req.id)
                        except BaseException:
                            # client vanished between submit and the header
                            # commit: no pump will ever run, so cancel or the
                            # lane generates max_tokens into an orphaned queue
                            req.cancel()
                            raise
                        try:
                            handle_fn(body, send_chunk=self._sse_chunk,
                                      prepared=prepared)
                            self.wfile.write(b"data: [DONE]\n\n")
                        except (BrokenPipeError, ConnectionError, OSError):
                            # client gone; _run_request already cancelled
                            # the request (or parked it in the resume
                            # registry's grace window)
                            return
                        except Exception as e:  # headers already sent: SSE error event
                            self._sse_chunk(err({"error": str(e)}))
                            self.wfile.write(b"data: [DONE]\n\n")
                    else:
                        prepared = build_fn(body, streaming=False, trace=trace)
                        req = prepared[0]
                        self._json(200, handle_fn(body, prepared=prepared))
                except AdmissionRejected as e:  # shed before any headers
                    self._reject(e, key=req.id if req is not None else None)
                except SchedulerStalled as e:
                    # wedged scheduler: retryable 503 naming the request
                    # (streamed variants surface as terminal SSE error
                    # chunks through the generic handler above — their
                    # headers are already out). Jittered like every shed.
                    retry = jittered_retry_after(
                        30.0, req.id if req is not None else next(_shed_keys)
                    )
                    self._json(
                        503, err({"error": str(e), "reason": "stalled"}),
                        headers={"Retry-After": str(max(1, round(retry)))},
                    )
                except ValueError as e:
                    self._json(400, err({"error": str(e)}))
                except Exception as e:  # generation failure
                    self._json(500, err({"error": str(e)}))

        httpd = ThreadingHTTPServer((host, port), Handler)
        if self.replica_id is None:
            # default fleet identity: where this replica listens (read
            # off the bound socket, so port=0 ephemeral binds resolve).
            # A wildcard bind substitutes the machine's hostname — every
            # replica defaulting to "0.0.0.0:8080" would make the
            # attribution header identical (useless) across the fleet.
            id_host = host
            if id_host in ("", "0.0.0.0", "::"):
                import socket as _socket

                id_host = _socket.gethostname()
            self.replica_id = f"{id_host}:{httpd.server_address[1]}"
        # fleet span attribution: once the replica's identity is known,
        # every span the hub emits carries it as a `replica` arg — the
        # merged fleet timeline needs each event to name its source even
        # after docs from several replicas are interleaved
        tel = self._telemetry()
        if getattr(tel, "replica", None) is None:
            tel.replica = self.replica_id
        self._httpd = httpd
        return httpd

    def shutdown(self):
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd = None
