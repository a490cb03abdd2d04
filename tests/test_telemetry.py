"""Telemetry subsystem (distributed_llama_multiusers_tpu/telemetry): the
instruments themselves AND their wiring through the serving path.

Three layers, per the PR-5 contract:

- **unit** — histogram bucket edges / le semantics / quantiles, ring
  eviction under overflow, Chrome trace JSON validity (pid/tid/ts/ph),
  Prometheus text that actually parses;
- **scheduler** — lifecycle spans and per-request summaries over the
  mocked async engine (utils.testing.MockAsyncEngine — the same stub the
  pipelined-decode tests pin), including the cancel/timeout/flush span
  endings and the queue-wait histogram reconciling with ``queue_popped``;
- **HTTP** — ``GET /metrics`` parses and reconciles field-for-field with
  ``GET /stats``, ``GET /trace`` is loadable, per-request summaries are
  identical between the stream and non-stream paths, and error payloads
  carry the request id.
"""

from __future__ import annotations

import io
import json
import re
import threading
import time
import urllib.error
import urllib.request

import pytest

from distributed_llama_multiusers_tpu.telemetry import (
    JsonLogger,
    MetricsRegistry,
    SpanTracer,
    Telemetry,
    chrome_trace,
    log_buckets,
)
from distributed_llama_multiusers_tpu.telemetry.metrics import (
    LATENCY_BUCKETS_S,
    Histogram,
)

# -- Prometheus text parser (the format contract, enforced line by line) -----

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'        # metric name
    r'(\{[^{}]*\})?'                        # optional labels
    r' (-?(?:[0-9]*\.?[0-9]+(?:[eE][+-]?[0-9]+)?|Inf)|NaN)$'
)


def parse_prometheus(text: str) -> dict[tuple[str, str], float]:
    """Parse Prometheus text exposition; asserts every non-comment line
    matches the sample grammar. Returns {(name, labels): value}."""
    samples: dict[tuple[str, str], float] = {}
    assert text.endswith("\n"), "exposition must end with a newline"
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) ", line), line
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        samples[(m.group(1), m.group(2) or "")] = float(m.group(3))
    return samples


# -- unit: histograms ---------------------------------------------------------


def test_log_buckets_are_geometric_and_cover_range():
    edges = log_buckets(1e-3, 1.0, per_decade=3)
    assert edges[0] == pytest.approx(1e-3)
    assert edges[-1] >= 1.0
    assert all(b > a for a, b in zip(edges, edges[1:]))
    ratios = [b / a for a, b in zip(edges, edges[1:])]
    for r in ratios:  # fixed log scale: constant ratio 10^(1/3)
        assert r == pytest.approx(10 ** (1 / 3), rel=1e-3)
    # the shared latency grid spans 100 µs .. >= 100 s
    assert LATENCY_BUCKETS_S[0] == pytest.approx(1e-4)
    assert LATENCY_BUCKETS_S[-1] >= 100.0


def test_histogram_le_semantics_and_counts():
    h = Histogram("t_seconds", buckets=(0.1, 1.0, 10.0))
    h.observe(0.1)   # exactly an edge: belongs to that bucket (le)
    h.observe(0.05)
    h.observe(5.0)
    h.observe(100.0)  # past the last edge: +Inf bucket
    counts, total, n = h.snapshot()
    assert counts == [2, 0, 1, 1]
    assert n == 4
    assert total == pytest.approx(105.15)


def test_histogram_quantile_interpolates():
    h = Histogram("t_seconds", buckets=(1.0, 2.0, 4.0))
    for _ in range(100):
        h.observe(1.5)  # all in the (1, 2] bucket
    q50 = h.quantile(0.5)
    assert 1.0 < q50 <= 2.0
    assert h.quantile(1.0) <= 2.0
    assert Histogram("e_seconds", buckets=(1.0,)).quantile(0.5) is None
    with pytest.raises(ValueError):
        h.quantile(0.0)


def test_histogram_rejects_bad_edges():
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(1.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("bad", buckets=())


def test_registry_render_parses_and_histogram_invariants():
    reg = MetricsRegistry()
    c = reg.counter("x_total", "a counter")
    c.inc()
    c.inc(2, reason="stop")
    reg.gauge("g", "a gauge").set(3.5, depth="2")
    h = reg.histogram("lat_seconds", "a histogram", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(50.0)
    samples = parse_prometheus(reg.render())
    assert samples[("x_total", "")] == 1
    assert samples[("x_total", '{reason="stop"}')] == 2
    assert samples[("g", '{depth="2"}')] == 3.5
    # cumulative buckets are non-decreasing and +Inf == count
    cum = [samples[("lat_seconds_bucket", '{le="0.1"}')],
           samples[("lat_seconds_bucket", '{le="1"}')],
           samples[("lat_seconds_bucket", '{le="+Inf"}')]]
    assert cum == sorted(cum) and cum[-1] == samples[("lat_seconds_count", "")]
    assert samples[("lat_seconds_sum", "")] == pytest.approx(50.55)
    # idempotent re-registration returns the same instrument
    assert reg.histogram("lat_seconds") is h
    with pytest.raises(ValueError):
        reg.counter("lat_seconds")  # name claimed by another kind


# -- unit: ring + chrome trace ------------------------------------------------


def test_ring_eviction_under_overflow():
    tr = SpanTracer(capacity=8)
    for i in range(20):
        tr.instant(f"ev{i}", "queue")
    events = tr.snapshot()
    assert len(events) == 8
    assert [e.name for e in events] == [f"ev{i}" for i in range(12, 20)]
    counts = tr.counts()
    assert counts["trace_events_recorded"] == 20
    assert counts["trace_events_dropped"] == 12
    assert counts["trace_events_buffered"] == 8


def test_chrome_trace_json_validity():
    tr = SpanTracer(capacity=64)
    t0 = tr.now()
    tr.slice("generate", "lane0", t0, t0 + 0.01, req_id=7)
    tr.slice("step.pipelined", "pipeline", t0, t0 + 0.002)
    tr.instant("finish.stop", "lane0", req_id=7)
    doc = chrome_trace(tr.snapshot(), origin=tr.origin)
    doc = json.loads(json.dumps(doc))  # round-trips
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    tids_named = set()
    for e in events:
        assert {"name", "ph", "pid", "tid", "ts"} <= set(e), e
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] >= 0
        elif e["ph"] == "i":
            assert e["s"] == "t"
        elif e["ph"] == "M" and e["name"] == "thread_name":
            tids_named.add(e["tid"])
    # every tid used by a real event has a thread_name metadata row
    used = {e["tid"] for e in events if e["ph"] in ("X", "i")}
    assert used <= tids_named
    gen = [e for e in events if e["name"] == "generate"][0]
    assert gen["dur"] == pytest.approx(10_000, rel=0.01)  # µs
    assert gen["args"]["request_id"] == 7
    # lanes sort ahead of the pipeline track
    name_of = {e["tid"]: e["args"]["name"] for e in events
               if e["ph"] == "M" and e["name"] == "thread_name"}
    lane_tid = [t for t, n in name_of.items() if n == "lane0"][0]
    pipe_tid = [t for t, n in name_of.items() if n == "pipeline"][0]
    assert lane_tid < pipe_tid


# -- scheduler wiring (mocked async engine) -----------------------------------


def _mock_stack(log_sink=None, **sched_kw):
    from distributed_llama_multiusers_tpu.runtime.scheduler import (
        ContinuousBatchingScheduler,
    )
    from distributed_llama_multiusers_tpu.utils.testing import (
        MockAsyncEngine,
        StubStreamTokenizer,
    )

    tel = Telemetry(logger=JsonLogger(log_sink) if log_sink is not None else None)
    engine = MockAsyncEngine()
    kw = dict(speculative=False, prefix_min_tokens=0, multi_step=0)
    kw.update(sched_kw)
    sched = ContinuousBatchingScheduler(
        engine, StubStreamTokenizer(engine.config.vocab_size),
        telemetry=tel, **kw,
    )
    return engine, sched, tel


def _run_requests(sched, reqs, timeout=60):
    sched.start()
    try:
        for r in reqs:
            sched.submit(r)
        for r in reqs:
            r.future.result(timeout=timeout)
    finally:
        sched.stop()


def _wait(pred, timeout=10):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, "condition never became true"
        time.sleep(0.002)


def test_request_summary_and_log_line():
    from distributed_llama_multiusers_tpu.runtime.scheduler import Request

    sink = io.StringIO()
    engine, sched, tel = _mock_stack(log_sink=sink)
    reqs = [Request(prompt="hello world", max_tokens=8) for _ in range(3)]
    _run_requests(sched, reqs)
    for r in reqs:
        s = r.summary
        assert s is not None and s["request_id"] == r.id
        assert s["finish_reason"] == "length"
        assert s["n_generated_tokens"] == 8
        assert s["ttft_s"] is not None and s["ttft_s"] >= 0
        assert s["tbt_p50_s"] is not None and s["queued_s"] is not None
    # exactly one structured JSON log line per request, same dict
    lines = [json.loads(l) for l in sink.getvalue().splitlines()]
    req_lines = [l for l in lines if l["event"] == "request"]
    assert sorted(l["request_id"] for l in req_lines) == sorted(r.id for r in reqs)
    by_id = {l["request_id"]: l for l in req_lines}
    for r in reqs:
        for k, v in r.summary.items():
            assert by_id[r.id][k] == v
    # startup log line names the serving config
    boot = [l for l in lines if l["event"] == "scheduler_start"]
    assert boot and {"n_lanes", "pipeline_depth", "fused_prefill"} <= set(boot[0])
    # metrics observed once per request / once per token
    assert tel.ttft.count == 3
    assert tel.tokens_generated.value() == 24
    assert tel.requests_finished.value(finish_reason="length") == 3


def test_failed_request_log_line_carries_error():
    """A request that fails before generating gets a summary/log line with
    finish_reason=error AND the error string — the log record must name
    the reason the 500 carries, or the request_id correlation is
    pointless."""
    from distributed_llama_multiusers_tpu.runtime.scheduler import Request

    sink = io.StringIO()
    engine, sched, tel = _mock_stack(log_sink=sink)

    class BoomTokenizer(type(sched.tokenizer)):
        def encode(self, text, add_bos=True, add_special_tokens=True):
            raise RuntimeError("tokenizer exploded")

    sched.tokenizer = BoomTokenizer(engine.config.vocab_size)
    req = Request(prompt="anything", max_tokens=4)
    sched.start()
    try:
        sched.submit(req)
        with pytest.raises(RuntimeError, match="tokenizer exploded"):
            req.future.result(timeout=30)
    finally:
        sched.stop()
    assert req.summary["finish_reason"] == "error"
    assert req.summary["error"] == "tokenizer exploded"
    line = [
        json.loads(l) for l in sink.getvalue().splitlines()
        if '"event": "request"' in l
    ][0]
    assert line["request_id"] == req.id
    assert line["error"] == "tokenizer exploded"
    assert tel.requests_finished.value(finish_reason="error") == 1


def test_lifecycle_spans_complete_for_normal_finish():
    from distributed_llama_multiusers_tpu.runtime.scheduler import Request

    engine, sched, tel = _mock_stack()
    req = Request(prompt="hello world", max_tokens=6)
    _run_requests(sched, [req])
    mine = [e for e in tel.tracer.snapshot() if e.req_id == req.id]
    names = [e.name for e in mine]
    for expected in ("submitted", "queued", "generate", "finish.length"):
        assert expected in names, names
    gen = [e for e in mine if e.name == "generate"][0]
    assert gen.track.startswith("lane") and gen.ph == "X"
    assert gen.args["finish_reason"] == "length"
    queued = [e for e in mine if e.name == "queued"][0]
    assert queued.track == "queue" and queued.ph == "X"


def test_span_endings_cancel_and_timeout():
    from distributed_llama_multiusers_tpu.runtime.scheduler import Request

    engine, sched, tel = _mock_stack()
    cancelled = Request(prompt="hello world", max_tokens=100_000)
    timed_out = Request(prompt="hello world", max_tokens=100_000, budget_s=0.05)
    sched.start()
    try:
        sched.submit(cancelled)
        sched.submit(timed_out)
        _wait(lambda: len(cancelled.generated_tokens) > 2)
        cancelled.cancel()
        cancelled.future.result(timeout=30)
        timed_out.future.result(timeout=30)
    finally:
        sched.stop()
    assert cancelled.finish_reason == "cancelled"
    assert timed_out.finish_reason == "timeout"
    assert cancelled.summary["finish_reason"] == "cancelled"
    assert timed_out.summary["finish_reason"] == "timeout"
    names = {(e.req_id, e.name) for e in tel.tracer.snapshot()}
    assert (cancelled.id, "finish.cancelled") in names
    assert (timed_out.id, "finish.timeout") in names
    # both still have complete generate slices (admit -> ending)
    assert (cancelled.id, "generate") in names
    assert (timed_out.id, "generate") in names
    assert tel.requests_finished.value(finish_reason="cancelled") == 1
    assert tel.requests_finished.value(finish_reason="timeout") == 1


def test_span_ending_for_queued_timeout_without_lane():
    """A request that expires while QUEUED (all lanes busy) ends with a
    queued slice + finish instant on the queue track and a summary whose
    ttft is None — it never generated."""
    from distributed_llama_multiusers_tpu.runtime.scheduler import Request
    from distributed_llama_multiusers_tpu.serving import DeadlinePolicy

    engine, sched, tel = _mock_stack(
        deadlines=DeadlinePolicy(queue_timeout_s=0.05)
    )
    blockers = [
        Request(prompt="hello world", max_tokens=100_000)
        for _ in range(engine.n_lanes)
    ]
    starved = Request(prompt="hello world", max_tokens=4)
    sched.start()
    try:
        for r in blockers:
            sched.submit(r)
        _wait(lambda: all(len(r.generated_tokens) > 0 for r in blockers))
        sched.submit(starved)
        starved.future.result(timeout=30)
        assert starved.finish_reason == "timeout"
    finally:
        for r in blockers:
            r.cancel()
        sched.stop()
    s = starved.summary
    assert s["finish_reason"] == "timeout"
    assert s["ttft_s"] is None and s["queued_s"] is None
    assert s["n_generated_tokens"] == 0
    mine = [e for e in tel.tracer.snapshot() if e.req_id == starved.id]
    assert {"queued", "finish.timeout"} <= {e.name for e in mine}
    assert all(e.track == "queue" for e in mine)


def test_pipeline_flush_instant_recorded():
    """With the fused-prefill escape hatch OFF, an admission into a live
    chain forces a flush — the trace must carry the pipeline.flush
    instant (span completeness for the flush ending)."""
    from distributed_llama_multiusers_tpu.runtime.scheduler import Request

    engine, sched, tel = _mock_stack(fused_prefill=False)
    a = Request(prompt="hello world", max_tokens=200)
    b = Request(prompt="hello world", max_tokens=4)
    sched.start()
    try:
        sched.submit(a)
        _wait(lambda: len(a.generated_tokens) > 3)  # chain is live
        sched.submit(b)  # fused off: this admission flushes the chain
        b.future.result(timeout=30)
        a.cancel()
        a.future.result(timeout=30)
    finally:
        sched.stop()
    flushes = [e for e in tel.tracer.snapshot() if e.name == "pipeline.flush"]
    assert flushes and flushes[0].ph == "i"
    assert engine.stats.snapshot()["pipeline_flushes"] >= 1


def test_queue_wait_histogram_reconciles_with_queue_popped():
    from distributed_llama_multiusers_tpu.runtime.scheduler import Request

    engine, sched, tel = _mock_stack()
    reqs = [Request(prompt="hello world", max_tokens=4) for _ in range(6)]
    _run_requests(sched, reqs)
    qstats = sched.queue.stats()
    assert tel.queue_wait.count == qstats["queue_popped"] == 6
    # and the histogram's total wait tracks the queue's own accounting
    assert tel.queue_wait.sum == pytest.approx(
        qstats["queue_wait_s_total"], abs=0.05
    )


def test_fused_admission_marked_in_summary():
    """A request admitted into a LIVE chain rides fused dispatches and its
    summary says so; the first request (admitted into an idle scheduler,
    sync prefill) does not."""
    from distributed_llama_multiusers_tpu.runtime.scheduler import Request

    engine, sched, tel = _mock_stack()
    a = Request(prompt="hello world", max_tokens=60)
    sched.start()
    try:
        sched.submit(a)
        _wait(lambda: len(a.generated_tokens) > 3)  # chain is live
        b = Request(prompt="hello world", max_tokens=4)
        sched.submit(b)
        b.future.result(timeout=30)
        a.future.result(timeout=30)
    finally:
        sched.stop()
    assert a.summary["fused_admitted"] is False
    assert b.summary["fused_admitted"] is True
    fused_slices = [
        e for e in tel.tracer.snapshot() if e.name == "step.fused"
    ]
    assert fused_slices, "no fused-step slices in the trace"


# -- HTTP surface -------------------------------------------------------------


@pytest.fixture()
def mock_server():
    from distributed_llama_multiusers_tpu.server import ApiServer
    from distributed_llama_multiusers_tpu.tokenizer import TemplateType

    engine, sched, tel = _mock_stack()
    sched.start()
    api = ApiServer(
        sched, sched.tokenizer, model_name="mock-tel",
        template_type=TemplateType.CHATML,
    )
    httpd = api.serve(host="127.0.0.1", port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    yield base, sched, tel
    httpd.shutdown()
    sched.stop()


def _post(base, path, body, timeout=60):
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get_raw(base, path, timeout=30):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return r.headers, r.read()


def _sse(base, path, body, timeout=60):
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    chunks = []
    with urllib.request.urlopen(req, timeout=timeout) as r:
        assert r.headers["Content-Type"].startswith("text/event-stream")
        for line in r:
            line = line.decode().strip()
            if line.startswith("data: "):
                chunks.append(line[6:])
    assert chunks[-1] == "[DONE]"
    return [json.loads(c) for c in chunks[:-1]]


def test_metrics_endpoint_parses_and_reconciles_with_stats(mock_server):
    base, sched, tel = mock_server
    _post(base, "/v1/completions",
          {"prompt": "hello world", "max_tokens": 5, "temperature": 0})
    # the response returns while the pipelined chain may still consume its
    # one overshoot step: wait until /stats stands still, then /stats and
    # /metrics sample the same counters
    stats = None
    for _ in range(100):
        prev, stats = stats, json.loads(_get_raw(base, "/stats")[1])
        if prev is not None and prev["decode_steps"] == stats["decode_steps"]:
            break
        time.sleep(0.05)
    headers, metrics_raw = _get_raw(base, "/metrics")
    assert headers["Content-Type"].startswith("text/plain; version=0.0.4")
    samples = parse_prometheus(metrics_raw.decode())
    # the bridge: every scalar /stats field is a dllama_stats_* gauge with
    # the SAME value (counters reconcile across the two endpoints)
    for key in ("decode_steps", "pipeline_dispatches", "fused_steps",
                "queue_popped", "prefill_tokens", "lanes_total"):
        assert samples[(f"dllama_stats_{key}", "")] == stats[key], key
    # dict-valued /stats histograms become labelled gauges
    for depth, n in stats["pipeline_depth_hist"].items():
        assert samples[("dllama_stats_pipeline_depth_hist",
                        f'{{key="{depth}"}}')] == n
    # native latency instruments are present and populated
    assert samples[("dllama_ttft_seconds_count", "")] >= 1
    assert samples[("dllama_requests_finished_total",
                    '{finish_reason="length"}')] >= 1
    # /stats surfaces the ring accounting
    assert stats["trace_events_recorded"] > 0


def test_trace_endpoint_is_loadable_chrome_json(mock_server):
    base, sched, tel = mock_server
    _post(base, "/v1/completions",
          {"prompt": "hello world", "max_tokens": 4, "temperature": 0})
    _, raw = _get_raw(base, "/trace")
    doc = json.loads(raw)
    events = doc["traceEvents"]
    assert events
    for e in events:
        assert {"name", "ph", "pid", "tid", "ts"} <= set(e)
    assert any(e["name"] == "generate" and e["ph"] == "X" for e in events)
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in events)


def test_summary_identical_between_stream_and_nonstream(mock_server):
    base, sched, tel = mock_server
    body = {"prompt": "hello world", "max_tokens": 6, "temperature": 0}
    _, full = _post(base, "/v1/completions", body)
    payloads = _sse(base, "/v1/completions", {**body, "stream": True})
    final = payloads[-1]
    assert final["choices"][0]["finish_reason"] == "length"
    # the summary rides ONLY the terminal chunk
    assert all("summary" not in p for p in payloads[:-1])
    s_stream, s_full = final["summary"], full["summary"]
    assert set(s_stream) == set(s_full)
    for key in ("finish_reason", "n_prompt_tokens", "n_generated_tokens",
                "prefix_tokens_saved", "fused_admitted"):
        assert s_stream[key] == s_full[key], key
    assert s_stream["request_id"] != s_full["request_id"]  # distinct requests
    assert s_stream["ttft_s"] is not None and s_full["ttft_s"] is not None


def test_error_payloads_carry_request_id(mock_server):
    base, sched, tel = mock_server
    from distributed_llama_multiusers_tpu.utils.testing import StubStreamTokenizer

    class BoomTokenizer(StubStreamTokenizer):
        def encode(self, text, add_bos=True, add_special_tokens=True):
            if "boom" in text:
                raise RuntimeError("tokenizer exploded")
            return super().encode(text, add_bos, add_special_tokens)

    sched.tokenizer = BoomTokenizer(sched.engine.config.vocab_size)
    try:
        # non-streaming: a 500 whose body names the request
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(base, "/v1/completions", {"prompt": "boom", "max_tokens": 3})
        assert e.value.code == 500
        payload = json.loads(e.value.read())
        assert payload["request_id"] > 0 and "error" in payload
        # streaming: headers already out, so the error is an SSE event —
        # still correlatable with server logs via the id
        payloads = _sse(base, "/v1/completions",
                        {"prompt": "boom", "max_tokens": 3, "stream": True})
        err = payloads[-1]
        assert err["error"] == "tokenizer exploded"
        assert err["request_id"] > 0
    finally:
        sched.tokenizer = StubStreamTokenizer(sched.engine.config.vocab_size)


def test_sync_bytes_bridge_is_delta_fed_across_resets():
    """The PR-7 sync bridge (telemetry/hub.bridge_stats): the native
    ``dllama_sync_bytes_total`` counter tracks the /stats
    ``sync_bytes_total`` field by DELTAS, so it keeps Prometheus counter
    semantics across engine.stats.reset() windows — the bridged gauge
    resets with /stats, the counter never goes backwards."""
    tel = Telemetry(logger=JsonLogger(stream=io.StringIO()))

    def counter_value():
        m = re.search(
            r"^dllama_sync_bytes_total (\S+)$",
            tel.registry.render(), re.M,
        )
        return float(m.group(1)) if m else 0.0

    tel.bridge_stats({"sync_bytes_total": 1000})
    assert counter_value() == 1000
    tel.bridge_stats({"sync_bytes_total": 1000})  # unchanged window
    assert counter_value() == 1000
    tel.bridge_stats({"sync_bytes_total": 1500})
    assert counter_value() == 1500
    # stats window reset: the gauge drops to 0, the counter must NOT
    tel.bridge_stats({"sync_bytes_total": 0})
    assert counter_value() == 1500
    # accrual resumes from the new baseline
    tel.bridge_stats({"sync_bytes_total": 300})
    assert counter_value() == 1800
    # and the verbatim gauge tracks the raw field (endpoint reconciliation)
    m = re.search(
        r"^dllama_stats_sync_bytes_total (\S+)$", tel.registry.render(), re.M
    )
    assert float(m.group(1)) == 300


def test_kv_swap_bridge_is_delta_fed_by_direction():
    """The tiered-residency bridge: ``dllama_kv_swap_total`` tracks the
    /stats ``swap_ins``/``swap_outs`` fields by DELTAS under a direction
    label, keeping Prometheus counter semantics across stats-window
    resets — while the verbatim ``dllama_stats_swap_*`` gauges keep the
    endpoint-reconciliation property (same number on /stats and
    /metrics when sampled idle)."""
    tel = Telemetry(logger=JsonLogger(stream=io.StringIO()))

    def counter(direction):
        m = re.search(
            r'^dllama_kv_swap_total\{direction="%s"\} (\S+)$' % direction,
            tel.registry.render(), re.M,
        )
        return float(m.group(1)) if m else 0.0

    tel.bridge_stats({"swap_ins": 5, "swap_outs": 2})
    assert counter("in") == 5 and counter("out") == 2
    tel.bridge_stats({"swap_ins": 5, "swap_outs": 4})  # only outs moved
    assert counter("in") == 5 and counter("out") == 4
    # stats window reset: the gauges drop to 0, the counters must NOT
    tel.bridge_stats({"swap_ins": 0, "swap_outs": 0})
    assert counter("in") == 5 and counter("out") == 4
    # accrual resumes from the new baseline
    tel.bridge_stats({"swap_ins": 3, "swap_outs": 1})
    assert counter("in") == 8 and counter("out") == 5
    # verbatim gauges track the raw fields, host-tier occupancy included
    render = tel.registry.render()
    assert re.search(r"^dllama_stats_swap_ins 3(\.0)?$", render, re.M)
    assert re.search(r"^dllama_stats_swap_outs 1(\.0)?$", render, re.M)
    tel.bridge_stats({"pool_host_pages": 7, "pool_host_bytes": 448,
                      "swap_in_ms": 1.25})
    render = tel.registry.render()
    assert re.search(r"^dllama_stats_pool_host_pages 7(\.0)?$", render, re.M)
    assert re.search(r"^dllama_stats_pool_host_bytes 448(\.0)?$", render, re.M)
    assert re.search(r"^dllama_stats_swap_in_ms 1\.25$", render, re.M)
