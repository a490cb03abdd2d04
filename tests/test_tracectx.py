"""Fleet-wide distributed tracing tests (telemetry/tracectx.py — ISSUE 20).

Four layers:

- **wire units** — the ``X-DLlama-Trace`` format round-trips, malformed
  and all-zero ids are refused (never 400d: callers mint instead), and
  ``child()`` keeps the trace id while re-minting the hop span id.
- **aggregation units** — ``PhaseAccumulator`` validates/cleans records,
  ``LabelledHistogram`` renders one labelled metric family and answers
  per-label quantiles, the span ring's ``since=`` cursor and per-track
  drop counts behave, and ``merge_chrome_traces`` applies clock-offset
  corrections VISIBLY (stamped per event, never silent).
- **replica surfaces** — a client header rides a request into the
  replica's summary and span ring; ``/trace?trace_id=&since=`` filters
  over real HTTP; ``/stats`` reports ring occupancy.
- **THE pins** — a stream spliced across a mid-flight replica kill keeps
  ONE trace id end to end, and ``GET /trace/<id>`` on the router returns
  ONE loadable Perfetto timeline holding the router's route span, the
  migration gap, and both replicas' spans; the disagg prefill→decode
  hand-off rejoins the same trace on the decode side via the ticket.
"""

from __future__ import annotations

import json
import threading
import urllib.error
import urllib.request

import pytest

from distributed_llama_multiusers_tpu.fleet import FleetRouter
from distributed_llama_multiusers_tpu.runtime.scheduler import (
    ContinuousBatchingScheduler,
)
from distributed_llama_multiusers_tpu.serving import StreamRegistry
from distributed_llama_multiusers_tpu.server import ApiServer
from distributed_llama_multiusers_tpu.telemetry.metrics import MetricsRegistry
from distributed_llama_multiusers_tpu.telemetry.spans import (
    SpanEvent,
    SpanTracer,
)
from distributed_llama_multiusers_tpu.telemetry.trace import (
    chrome_trace,
    merge_chrome_traces,
    tracer_chrome_trace,
)
from distributed_llama_multiusers_tpu.telemetry.tracectx import (
    PHASE_KEYS,
    TRACE_HEADER,
    PhaseAccumulator,
    TraceContext,
    trace_id_of,
)
from distributed_llama_multiusers_tpu.tokenizer import TemplateType
from distributed_llama_multiusers_tpu.utils import faults
from distributed_llama_multiusers_tpu.utils.testing import (
    CharStreamTokenizer,
    MockAsyncEngine,
)


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.disarm()
    yield
    faults.disarm()


# ---------------------------------------------------------------------------
# wire format units
# ---------------------------------------------------------------------------


def test_wire_mint_parse_round_trip():
    ctx = TraceContext.mint()
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    wire = ctx.to_header()
    assert wire == f"{ctx.trace_id}-{ctx.span_id}"
    back = TraceContext.parse(wire)
    assert back == ctx
    # uppercase and padding normalise (header values survive proxies)
    assert TraceContext.parse("  " + wire.upper() + " ") == ctx
    # child: same trace, fresh hop span
    kid = ctx.child()
    assert kid.trace_id == ctx.trace_id
    assert kid.span_id != ctx.span_id
    assert trace_id_of(wire) == ctx.trace_id


def test_parse_rejects_malformed_and_zero_ids():
    bad = [
        None, "", "not-a-trace", "deadbeef", "-".join(["ab" * 16] * 2),
        "g" * 32 + "-" + "0" * 16,       # non-hex
        "0" * 32 + "-" + "1234567890abcdef",  # zero trace id
        "a" * 32 + "-" + "0" * 16,       # zero span id
        "a" * 32 + "1234567890abcdef",   # missing dash
        "a" * 31 + "-" + "1" * 16,       # short trace id
    ]
    for v in bad:
        assert TraceContext.parse(v) is None, v
        assert trace_id_of(v) is None, v


def test_accept_honours_valid_mints_otherwise():
    ctx = TraceContext.mint()
    assert TraceContext.accept(ctx.to_header()) == ctx
    minted = TraceContext.accept("garbage header")
    assert minted.trace_id != ctx.trace_id
    assert TraceContext.parse(minted.to_header()) == minted
    # two mints never collide on the ids that matter
    assert TraceContext.accept(None).trace_id != minted.trace_id


# ---------------------------------------------------------------------------
# aggregation units
# ---------------------------------------------------------------------------


def test_phase_accumulator_cleans_and_aggregates():
    acc = PhaseAccumulator()
    assert acc.observe(None) is None
    assert acc.observe("nope") is None
    assert acc.observe({"unknown_key": 3.0}) is None
    clean = acc.observe({
        "ttft_ms": 12.5, "decode_ms": 40.0,
        "queue_wait_ms": -1.0,           # negative: dropped
        "prefill_ms": "fast",            # non-numeric: dropped
        "bogus": 9.0,                    # unknown: dropped
    })
    assert clean == {"ttft_ms": 12.5, "decode_ms": 40.0}
    acc.observe({"ttft_ms": 7.5})
    snap = acc.snapshot()
    assert snap["phase_records"] == 2
    assert snap["phase_counts"]["ttft_ms"] == 2
    assert snap["phase_sum_ms"]["ttft_ms"] == pytest.approx(20.0)
    assert snap["phase_counts"]["decode_ms"] == 1
    assert set(clean) <= set(PHASE_KEYS)


def test_labelled_histogram_render_and_quantile():
    reg = MetricsRegistry()
    h = reg.labelled_histogram(
        "dllama_request_phase_seconds", "per-request phase attribution",
    )
    assert reg.labelled_histogram("dllama_request_phase_seconds") is h
    for v in (0.010, 0.020, 0.040):
        h.observe(v, phase="ttft_ms")
    h.observe(1.5, phase="decode_ms")
    assert h.quantile(0.5, phase="ttft_ms") == pytest.approx(0.020, rel=0.6)
    assert h.quantile(0.5, phase="never_seen") is None
    counts, total, n = h.snapshot(phase="ttft_ms")
    assert n == 3 and total == pytest.approx(0.070)
    assert sum(counts) == 3
    text = "\n".join(h.render())
    assert "# TYPE dllama_request_phase_seconds histogram" in text
    assert 'phase="ttft_ms"' in text and 'phase="decode_ms"' in text
    assert 'le="+Inf"' in text
    assert 'dllama_request_phase_seconds_count{phase="ttft_ms"} 3' in text
    # the registry renders the family exactly once
    assert reg.render().count("# TYPE dllama_request_phase_seconds") == 1


def test_span_ring_since_cursor_and_per_track_drops():
    tracer = SpanTracer(capacity=3)
    t = tracer.now()
    tracer.slice("a", "lane0", t)
    tracer.slice("b", "lane0", t)
    tracer.slice("c", "queue", t)
    doc = tracer_chrome_trace(tracer)
    cursor = doc["cursor"]
    assert cursor == 3
    # nothing newer: the incremental poll is empty but keeps the cursor
    doc2 = tracer_chrome_trace(tracer, since=cursor)
    assert doc2["cursor"] == cursor
    assert [e for e in doc2["traceEvents"] if e["ph"] != "M"] == []
    # overflow: the two oldest (both lane0) evict, attributed per track
    tracer.slice("d", "queue", t)
    tracer.slice("e", "queue", t)
    counts = tracer.counts()
    assert counts["trace_events_recorded"] == 5
    assert counts["trace_events_dropped"] == 2
    assert counts["trace_events_dropped_by_track"] == {"lane0": 2}
    assert counts["trace_events_buffered"] == 3
    # since= returns only the post-cursor events
    newer = tracer.snapshot(since=cursor)
    assert [e.name for e in newer] == ["d", "e"]
    # trace_id filter: only args-tagged events survive
    tracer.slice("f", "queue", t, args={"trace_id": "ab" * 16})
    assert [e.name for e in tracer.snapshot(trace_id="ab" * 16)] == ["f"]


def test_clock_skew_merge_corrects_and_stamps():
    """Two rings on skewed fake clocks: replica B's raw timestamps LOOK
    earlier than A's, but with its known offset applied it lands later —
    and the correction is stamped on every migrated event, not silently
    absorbed."""
    ev = lambda name, ts: SpanEvent(name, "X", ts, 0.010, "lane0")
    doc_a = chrome_trace([ev("generate", 1.000)], origin=0.0)
    doc_b = chrome_trace([ev("generate", 0.400)], origin=0.0)
    merged = merge_chrome_traces([
        ("a", doc_a, 0.0, 0.0),
        ("b", doc_b, 700_000.0, 1_500.0),
    ])
    # loadable: plain JSON, fleet process name, per-source track rows
    merged = json.loads(json.dumps(merged))
    events = merged["traceEvents"]
    procs = [e for e in events
             if e["ph"] == "M" and e["name"] == "process_name"]
    assert [p["args"]["name"] for p in procs] == ["dllama-fleet"]
    tracks = {e["args"]["name"] for e in events
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"a/lane0", "b/lane0"} <= tracks
    slices = [e for e in events if e["ph"] == "X"]
    by_src = {e["args"]["span_source"]: e for e in slices}
    assert by_src["a"]["ts"] == pytest.approx(1_000_000.0)
    assert by_src["b"]["ts"] == pytest.approx(1_100_000.0)  # 0.4s + offset
    # corrected ordering: a before b despite b's smaller raw ts
    assert [e["args"]["span_source"] for e in slices] == ["a", "b"]
    assert by_src["b"]["args"]["clock_offset_us"] == pytest.approx(700_000.0)
    assert by_src["b"]["args"]["clock_uncertainty_us"] == pytest.approx(
        1_500.0
    )
    assert by_src["a"]["args"]["clock_offset_us"] == 0.0


# ---------------------------------------------------------------------------
# replica surfaces over real HTTP
# ---------------------------------------------------------------------------


class _Tok(CharStreamTokenizer):
    def decode(self, token):
        return f"[{token}]"


def _replica(rid, n_lanes=2, step_s=0.005, paged=False, role="mixed"):
    kw = {}
    if paged:
        kw = dict(paged=True, kv_page_size=16, kv_pool_pages=128,
                  kv_max_parked=32)
    engine = MockAsyncEngine(n_lanes=n_lanes, max_chunk=8,
                             content_keyed=True, step_s=step_s, **kw)
    sched = ContinuousBatchingScheduler(
        engine, _Tok(64, max_chars=96),
        speculative=False, prefix_min_tokens=0, multi_step=0,
    )
    sched.start()
    registry = StreamRegistry(grace_s=30.0)
    api = ApiServer(sched, _Tok(64, max_chars=96), model_name="tracefleet",
                    template_type=TemplateType.LLAMA2, resume=registry,
                    replica_id=rid, role=role)
    httpd = api.serve(host="127.0.0.1", port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return {"api": api, "engine": engine, "sched": sched,
            "registry": registry, "httpd": httpd,
            "base": f"127.0.0.1:{httpd.server_address[1]}", "rid": rid}


def _stop_replica(r):
    try:
        r["httpd"].shutdown()
    finally:
        if r["registry"] is not None:
            r["registry"].close()
        try:
            r["sched"].stop()
        except RuntimeError:
            pass


def _get_json(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read()), dict(resp.headers)


def _router(replicas, **kw):
    router = FleetRouter(
        {r["rid"]: r["base"] for r in replicas},
        scrape_interval_s=kw.pop("scrape_interval_s", 0.1),
        **kw,
    ).start()
    httpd = router.serve(host="127.0.0.1", port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    router.scrape_once()
    return router, httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


def _stream(url, body, headers=None, on_delta=None, timeout=120):
    """(text, terminal payload, response headers) for one SSE POST."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    texts, term = [], None
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        resp_headers = dict(resp.headers)
        for line in resp:
            line = line.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            p = json.loads(line[6:])
            if "error" in p:
                term = p
                continue
            ch = p.get("choices", [{}])[0]
            if ch.get("finish_reason") is None:
                texts.append(ch.get("text", ""))
                if on_delta is not None:
                    on_delta(len(texts))
            else:
                term = p
    return "".join(texts), term, resp_headers


def test_replica_honours_trace_header_and_filters_ring():
    r = _replica("tr1")
    ctx = TraceContext.mint()
    try:
        url = f"http://{r['base']}/v1/completions"
        # non-streaming: the summary surfaces the trace id
        req = urllib.request.Request(
            url,
            data=json.dumps({"prompt": "trace header round trip",
                             "max_tokens": 4}).encode(),
            headers={"Content-Type": "application/json",
                     TRACE_HEADER: ctx.to_header()},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            body = json.loads(resp.read())
        assert body["summary"]["trace_id"] == ctx.trace_id
        assert set(PHASE_KEYS) <= set(body["summary"]["phases"])
        # the span ring tagged this request's events with trace + replica
        doc, _ = _get_json(
            f"http://{r['base']}/trace?trace_id={ctx.trace_id}"
        )
        events = [e for e in doc["traceEvents"] if e["ph"] != "M"]
        assert events, "no ring events carried the trace id"
        assert {e["args"]["trace_id"] for e in events} == {ctx.trace_id}
        assert {e["args"]["replica"] for e in events} == {"tr1"}
        assert "generate" in {e["name"] for e in events}
        # incremental poll: pass the cursor back, get nothing twice. The
        # response above came back at the request's last token, with the
        # pipelined chain's overshoot steps still to consume: their
        # loop.wait / loop.stream spans land after it. A device op runs
        # at the serving loop's step boundary only, outside the chain, so
        # once this one has run the chain has recorded all it will and
        # the loop is parked on its queue: the ring stands still
        r["sched"].run_device_op(lambda: None)
        full, _ = _get_json(f"http://{r['base']}/trace")
        inc, _ = _get_json(f"http://{r['base']}/trace?since={full['cursor']}")
        assert [e for e in inc["traceEvents"] if e["ph"] != "M"] == []
        assert inc["cursor"] == full["cursor"]
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                f"http://{r['base']}/trace?since=nonsense", timeout=10
            )
        assert e.value.code == 400
        # /stats surfaces ring occupancy + per-track drop attribution
        stats, _ = _get_json(f"http://{r['base']}/stats")
        assert stats["trace_events_recorded"] >= len(events)
        assert stats["trace_events_dropped"] == 0
        assert isinstance(stats["trace_events_dropped_by_track"], dict)
        # a malformed header is IGNORED, never an error: the request
        # runs untraced (replicas don't mint; the router does)
        req = urllib.request.Request(
            url,
            data=json.dumps({"prompt": "malformed header ignored",
                             "max_tokens": 2}).encode(),
            headers={"Content-Type": "application/json",
                     TRACE_HEADER: "not a context"},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            body = json.loads(resp.read())
        assert "trace_id" not in body["summary"]
    finally:
        _stop_replica(r)


# ---------------------------------------------------------------------------
# router: minting, echo, phase aggregation
# ---------------------------------------------------------------------------


def test_router_mints_echoes_and_aggregates_phases():
    r = _replica("ag1")
    router, rhttpd, rbase = _router([r])
    try:
        body = {"prompt": "router trace minting probe " * 4,
                "max_tokens": 6, "stream": True}
        text, term, headers = _stream(rbase + "/v1/completions", body)
        assert text and term["choices"][0]["finish_reason"] == "length"
        # no client header: the router MINTED a context and echoed it
        minted = TraceContext.parse(headers.get(TRACE_HEADER))
        assert minted is not None
        phases = term["summary"]["phases"]
        assert set(PHASE_KEYS) <= set(phases)
        assert phases["ttft_ms"] > 0
        assert phases["migration_gap_ms"] == 0.0
        # terminal phases fold into the router-side aggregation: the
        # /stats sums reconcile with the record the client just read
        stats = router.handle_stats()
        assert stats["phase_records"] == 1
        assert stats["phase_sum_ms"]["ttft_ms"] == pytest.approx(
            phases["ttft_ms"], abs=0.01
        )
        assert stats["trace_events_recorded"] >= 1  # the route span
        assert "ag1" in stats["clock_offset_us"]
        assert stats["clock_uncertainty_us"]["ag1"] >= 0.0
        # /metrics: ONE labelled histogram family, count == records
        metrics = router.handle_metrics()
        assert 'dllama_request_phase_seconds_count{phase="ttft_ms"} 1' \
            in metrics
        assert 'dllama_request_phase_seconds_bucket{phase="decode_ms"' \
            in metrics
        # a client-supplied context is honoured end to end: echoed trace
        # id matches, and the replica's summary carries it back through
        ctx = TraceContext.mint()
        _, term2, headers2 = _stream(
            rbase + "/v1/completions", body,
            headers={TRACE_HEADER: ctx.to_header()},
        )
        # the echo is the CLIENT'S context verbatim (the id it will
        # correlate on); the per-hop child contexts ride upstream only
        assert TraceContext.parse(headers2.get(TRACE_HEADER)) == ctx
        assert term2["summary"]["trace_id"] == ctx.trace_id
        # /trace/<id> input validation: non-hex ids are 400, not crashes
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(rbase + "/trace/nothex", timeout=10)
        assert e.value.code == 400
    finally:
        router.close()
        rhttpd.shutdown()
        _stop_replica(r)


# ---------------------------------------------------------------------------
# THE pins: one trace id across a mid-stream kill / a disagg hand-off
# ---------------------------------------------------------------------------


def test_trace_survives_migration_one_merged_timeline():
    """THE pin (acceptance criterion): a stream spliced across a replica
    kill keeps ONE trace id, and the router's ``GET /trace/<id>`` merges
    the router's route span, the migration gap, and BOTH replicas' spans
    into one loadable Perfetto doc — with the clock correction stamped
    per event. The kill stops the scheduler only (force-cancel → typed
    cancelled → migrate) and leaves the victim's HTTP surface up, the
    orderly-drain shape where the dead replica's ring is still readable;
    a replica that vanished entirely contributes nothing by design."""
    a, b = _replica("v1"), _replica("v2")
    router, rhttpd, rbase = _router([a, b])
    killed = []
    ctx = TraceContext.mint()
    try:
        # > 256 prompt chars: a full affinity block, so the traced rerun
        # lands on the same replica the reference run named
        body = {"prompt": "trace migration pin " * 20, "max_tokens": 30,
                "stream": True}
        ref_text, _, ref_headers = _stream(rbase + "/v1/completions", body)
        source = ref_headers.get("X-DLlama-Replica")

        def kill_source(n_deltas):
            if n_deltas == 5 and not killed:
                victim = a if source == "v1" else b
                killed.append(victim)
                victim["sched"].stop()

        text, term, headers = _stream(
            rbase + "/v1/completions", body,
            headers={TRACE_HEADER: ctx.to_header()}, on_delta=kill_source,
        )
        assert killed, "the kill never fired"
        survivor = "v2" if killed[0] is a else "v1"
        assert text == ref_text  # byte-identical across the splice
        assert term["choices"][0]["finish_reason"] == "length"
        assert router.migrations_ok == 1
        # one trace id end to end: echoed header, decode-side summary
        assert TraceContext.parse(
            headers.get(TRACE_HEADER)
        ).trace_id == ctx.trace_id
        assert term["summary"]["trace_id"] == ctx.trace_id
        # the router stamped the gap ONLY IT saw into the terminal record
        gap_ms = term["summary"]["phases"]["migration_gap_ms"]
        assert gap_ms > 0.0
        stats = router.handle_stats()
        assert stats["phase_sum_ms"]["migration_gap_ms"] == pytest.approx(
            gap_ms, abs=0.01
        )

        # ONE merged timeline over HTTP, loadable Chrome-trace JSON
        doc, _ = _get_json(rbase + f"/trace/{ctx.trace_id}")
        doc = json.loads(json.dumps(doc))
        events = doc["traceEvents"]
        procs = [e for e in events
                 if e["ph"] == "M" and e["name"] == "process_name"]
        assert [p["args"]["name"] for p in procs] == ["dllama-fleet"]
        real = [e for e in events if e["ph"] != "M"]
        assert real and all(
            e["args"]["trace_id"] == ctx.trace_id for e in real
        )
        sources = {e["args"]["span_source"] for e in real}
        assert {"router", source, survivor} <= sources
        names = {(e["args"]["span_source"], e["name"]) for e in real}
        assert ("router", "route") in names
        assert ("router", "migration.gap") in names
        assert (survivor, "generate") in names  # the spliced-to stream
        gap = next(e for e in real if e["name"] == "migration.gap")
        assert gap["args"]["from"] == source
        assert gap["args"]["to"] == survivor
        assert gap["args"]["kind"] == "migration"
        # replica events landed on the router timebase with the estimate
        # stamped — measured ordering stays distinguishable from aligned
        for e in real:
            assert "clock_offset_us" in e["args"]
            assert e["args"]["clock_uncertainty_us"] >= 0.0
        tracks = {e["args"]["name"] for e in events
                  if e["ph"] == "M" and e["name"] == "thread_name"}
        assert any(t.startswith("router/") for t in tracks)
        assert any(t.startswith(f"{survivor}/") for t in tracks)
    finally:
        router.close()
        rhttpd.shutdown()
        for r in (a, b):
            _stop_replica(r)


def test_disagg_handoff_rejoins_trace_on_decode_side():
    """The prefill→decode hand-off carries the context on every admin
    hop AND inside the migration ticket: the decode replica's session
    rejoins the ORIGINAL trace (its summary names it), and the fleet
    timeline shows the transfer as a ``disagg.handoff`` row between the
    two replicas' spans."""
    p = _replica("p0", paged=True, role="prefill")
    d = _replica("d0", paged=True, role="decode")
    router, rhttpd, rbase = _router([p, d], long_prompt_chars=120)
    ctx = TraceContext.mint()
    try:
        body = {"prompt": "disagg trace pin prompt " * 12,  # > 120 chars
                "max_tokens": 20, "stream": True}
        text, term, headers = _stream(
            rbase + "/v1/completions", body,
            headers={TRACE_HEADER: ctx.to_header()},
        )
        assert text and term["choices"][0]["finish_reason"] == "length"
        assert router.disagg_handoffs_ok == 1
        # the decode-side session REJOINED the original trace
        assert term["summary"]["trace_id"] == ctx.trace_id
        assert TraceContext.parse(
            headers.get(TRACE_HEADER)
        ).trace_id == ctx.trace_id
        doc, _ = _get_json(rbase + f"/trace/{ctx.trace_id}")
        real = [e for e in doc["traceEvents"] if e["ph"] != "M"]
        sources = {e["args"]["span_source"] for e in real}
        assert {"router", "p0", "d0"} <= sources
        hand = next(e for e in real if e["name"] == "disagg.handoff")
        assert hand["args"]["from"] == "p0"
        assert hand["args"]["to"] == "d0"
        names = {(e["args"]["span_source"], e["name"]) for e in real}
        assert ("d0", "generate") in names
    finally:
        router.close()
        rhttpd.shutdown()
        _stop_replica(p)
        _stop_replica(d)
