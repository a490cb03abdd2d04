"""Every pipelined step leaves a record (telemetry/spans.py ``StepRecord``).

(a) the dry-dispatch witness: ``EngineStats`` ``pipeline_dry_dispatches`` /
    ``pipeline_dry_s`` / ``live_lane_steps`` step by step over the mock
    engine, whose ``pipeline_ready()`` answers what the test tells it, and
    what the witness leaves out: a chain's first fill, a drain, a flush;
(b) the record: ``interval_s == wait_s + host_s`` on every ``step.*`` slice,
    the slices of a chain tile the ``pipeline`` track, and a prompt admitted
    in three chunks leaves three rows on its request, on the chain and alone;
(c) ``Telemetry.span`` hands its args to the annotation factory as keywords
    (``dl.loop.*`` carry ``step``, ``dl.loop.stream`` the whole record);
(d) ``dllama_step_duration_seconds`` has one series a class of step program,
    ``dllama_overlap_fraction`` is gone, ``/stats`` serves the three fields
    and a completion's ``summary`` lists its chunks.
"""

import json
import threading
import time
import urllib.request

import pytest

from distributed_llama_multiusers_tpu.runtime import (
    ContinuousBatchingScheduler,
    Request,
)
from distributed_llama_multiusers_tpu.runtime.engine import EngineStats
from distributed_llama_multiusers_tpu.telemetry import StepRecord, Telemetry, names
from distributed_llama_multiusers_tpu.utils.testing import (
    MockAsyncEngine,
    StubStreamTokenizer,
)

DRY_FIELDS = ("pipeline_dry_dispatches", "pipeline_dry_s", "live_lane_steps")


class Annotation:
    """Stands in for jax.profiler.TraceAnnotation: keeps what it was made with."""

    made: list = []

    def __init__(self, name, **kwargs):
        Annotation.made.append((name, kwargs))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def make_sched(engine, prompt_tokens=8, tel=None, **kw):
    tel = tel or Telemetry()
    sched = ContinuousBatchingScheduler(
        engine, StubStreamTokenizer(prompt_tokens=prompt_tokens), telemetry=tel,
        speculative=False, multi_step=0, prefix_min_tokens=0, **kw)
    return sched, tel


def run(sched, reqs, stagger_after=0):
    """The first request alone (admitted synchronously by an idle scheduler);
    the others once it has ``stagger_after`` tokens, so they ride its chain."""
    sched.start()
    try:
        sched.submit(reqs[0])
        deadline = time.monotonic() + 60
        while len(reqs[0].generated_tokens) < stagger_after:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        for r in reqs[1:]:
            sched.submit(r)
        for r in reqs:
            r.future.result(timeout=60)
    finally:
        sched.stop()
    assert all(r.error is None for r in reqs)


def step_slices(tel):
    return sorted((e for e in tel.tracer.snapshot()
                   if e.track == "pipeline" and e.name.startswith("step.")
                   and "interval_s" in (e.args or {})), key=lambda e: e.args["step"])


# ---------------------------------------------------------------------------
# (a) the dry-dispatch witness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ready", [False, True, None])
def test_dry_dispatches_count_what_the_engine_says_after_the_first_fill(ready):
    """One lane, one chain (or more: counted from the depth histogram). With
    the device always dry every dispatch but each chain's first two (the ring
    filling from empty) is dry; with it never ready none is; by the mock's
    simulated clock (None) whatever it says, and never more than that."""
    engine = MockAsyncEngine(n_lanes=2, step_s=0.002)
    engine.ready_override = ready
    sched, tel = make_sched(engine)
    run(sched, [Request(prompt="hello there", max_tokens=30, temperature=0.0)])
    s = engine.stats.snapshot()
    chains = s["pipeline_depth_hist"][1]   # a dispatch into an empty ring: a chain's first
    steps = s["pipeline_dispatches"]
    assert steps > 20 and s["pipeline_flushes"] == 0
    # one lane decoding in every step of its chain
    assert s["live_lane_steps"] == steps
    bound = steps - 2 * chains
    if ready is True:
        assert s["pipeline_dry_dispatches"] == bound
        assert 0.0 < s["pipeline_dry_s"] < 60.0
    elif ready is False:
        assert s["pipeline_dry_dispatches"] == 0 and s["pipeline_dry_s"] == 0.0
    else:
        assert 0 <= s["pipeline_dry_dispatches"] <= bound
    # the slices say which steps: as many dry ones as the counter counted,
    # none among a chain's first two, and their dry_s add up to the counter
    slices = step_slices(tel)
    assert len(slices) == steps
    dry = [e.args for e in slices if e.args["dry"]]
    assert len(dry) == s["pipeline_dry_dispatches"]
    assert sum(a["dry_s"] for a in dry) == pytest.approx(s["pipeline_dry_s"])
    assert all("dry_s" not in e.args for e in slices if not e.args["dry"])
    assert not slices[0].args["dry"] and not slices[1].args["dry"]
    assert all(e.args["lanes"] == 1 for e in slices)


def test_a_drain_and_a_flush_count_no_dry_dispatch():
    """A chain cut by stop() drains its in-flight steps through the consume
    half: no dispatch, so nothing for the witness, however dry the device."""
    engine = MockAsyncEngine(n_lanes=2, step_s=0.002)
    engine.ready_override = True
    sched, _tel = make_sched(engine)
    req = Request(prompt="hello there", max_tokens=10_000, temperature=0.0)
    sched.start()
    try:
        sched.submit(req)
        deadline = time.monotonic() + 60
        while len(req.generated_tokens) < 20:
            assert time.monotonic() < deadline
            time.sleep(0.001)
    finally:
        sched.stop()
    s = engine.stats.snapshot()
    assert s["pipeline_flushes"] >= 1
    # every step consumed or drained. (Twenty tokens: the prefill's readback
    # streamed the first and those of steps 1-19 the rest, with step 20
    # already in flight behind step 19; before PR 57 a step's token was
    # streamed a readback later and twenty tokens meant 21 dispatches.)
    assert s["decode_steps"] >= s["pipeline_dispatches"] >= 20
    assert s["pipeline_dry_dispatches"] == (
        s["pipeline_dispatches"] - 2 * s["pipeline_depth_hist"][1])


def test_live_lane_steps_sum_the_lanes_each_step_carried():
    engine = MockAsyncEngine(n_lanes=4, step_s=0.002, max_chunk=16)
    sched, tel = make_sched(engine)
    run(sched, [Request(prompt="hello there", max_tokens=200 - 5 * i, temperature=0.0)
                for i in range(4)], stagger_after=3)
    s = engine.stats.snapshot()
    slices = step_slices(tel)
    assert len(slices) == s["pipeline_dispatches"]
    assert s["live_lane_steps"] == sum(e.args["lanes"] for e in slices)
    assert max(e.args["lanes"] for e in slices) == 4
    assert s["live_lane_steps"] < 4 * s["pipeline_dispatches"]


def test_the_three_fields_are_stats_fields_like_any_other():
    stats = EngineStats()
    assert set(DRY_FIELDS) <= set(EngineStats._dlint_guarded_by[("lock",)])
    with stats.lock:
        stats.pipeline_dry_dispatches, stats.pipeline_dry_s, stats.live_lane_steps = 3, 0.25, 40
    assert [stats.snapshot()[k] for k in DRY_FIELDS] == [3, 0.25, 40]
    before = stats.reset()
    assert [getattr(before, k) for k in DRY_FIELDS] == [3, 0.25, 40]
    assert [stats.snapshot()[k] for k in DRY_FIELDS] == [0, 0.0, 0]


def test_pipeline_ready_is_false_on_an_empty_ring_and_follows_the_clock():
    engine = MockAsyncEngine(n_lanes=2, step_s=0.3)
    assert engine.pipeline_ready() is False
    engine.ready_override = True
    assert engine.pipeline_ready() is False   # nothing in flight: nothing ran dry
    engine.ready_override = None
    import numpy as np

    z = np.zeros(2, np.int32)
    engine.decode_pipelined(z, tokens=z)
    assert engine.pipeline_ready() is False   # 300 ms of simulated work ahead
    time.sleep(0.35)
    assert engine.pipeline_ready() is True
    engine.pipeline_flush()


def test_the_real_engines_poll_turns_true_once_the_step_has_run(tiny_model):
    """``jax.Array.is_ready()`` on the youngest in-flight step's packed
    output: a poll (the step may or may not have finished when it is asked),
    true for certain once something has blocked on that output."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_llama_multiusers_tpu.formats import load_model_header
    from distributed_llama_multiusers_tpu.models import load_params_from_m
    from distributed_llama_multiusers_tpu.runtime import InferenceEngine

    h = load_model_header(tiny_model["model"])
    config, params = load_params_from_m(tiny_model["model"], h, dtype=jnp.float32)
    engine = InferenceEngine(config, params, n_lanes=2, prefill_buckets=(4,))
    assert engine.pipeline_ready() is False   # an empty ring
    z = np.zeros(2, np.int32)
    engine.decode_pipelined(z, tokens=z)
    engine.decode_pipelined(z + 1)
    assert engine.pipeline_ready() in (True, False)
    engine._pl_inflight[-1][1].block_until_ready()
    assert engine.pipeline_ready() is True
    assert engine.pipeline_inflight() == 2    # the poll consumed nothing
    engine.pipeline_flush()
    assert engine.pipeline_ready() is False


# ---------------------------------------------------------------------------
# (b) the record
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def churn():
    Annotation.made = []
    engine = MockAsyncEngine(n_lanes=4, step_s=0.002, max_chunk=16)
    tel = Telemetry()
    tel.annotation_factory = Annotation
    sched, tel = make_sched(engine, tel=tel)
    reqs = [Request(prompt="hello there", max_tokens=120 - i, temperature=0.0) for i in range(5)]
    run(sched, reqs, stagger_after=3)
    return reqs, sched, tel, list(Annotation.made)


def test_interval_is_wait_plus_host_and_the_slices_tile_the_track(churn):
    _reqs, sched, tel, _made = churn
    slices = step_slices(tel)
    assert [e.args["step"] for e in slices] == list(range(1, sched._step_seq + 1))
    for e in slices:
        a = e.args
        assert a["interval_s"] == pytest.approx(a["wait_s"] + a["host_s"], abs=1e-9)
        assert a["wait_s"] >= 0.0 and a["host_s"] >= 0.0
        assert e.dur == pytest.approx(a["interval_s"], abs=1e-9)
        assert a["class"] == names.pipelined_step_class(False, a.get("bucket"))
        assert ("chunk" in a) == (e.name == "step.fused")
    # inside a chain a step's slice starts where the one before it ended;
    # a chain's first starts at its own dispatch
    tiled = sum(1 for a, b in zip(slices, slices[1:])
                if abs(a.ts + a.dur - b.ts) < 1e-9)
    assert tiled >= len(slices) - 1 - sched.engine.stats.snapshot()["pipeline_depth_hist"][1]
    # what the loop's own spans of the same step measured: the wait is the
    # loop.wait span and a little more (the span's own enter and exit)
    waits = {e.args["step"]: e.dur for e in tel.tracer.snapshot() if e.name == names.LOOP_WAIT}
    for e in slices:
        assert waits[e.args["step"]] <= e.args["wait_s"] + 1e-9


@pytest.mark.parametrize("on_the_chain", [True, False])
def test_a_prompt_admitted_in_three_chunks_leaves_three_rows(on_the_chain):
    """2304 prompt tokens through a 1024-row bucket: 1024 at 0, 1024 at 1024,
    256 at 2048, the last one final; as fused steps of a live chain, and
    dispatched alone by an idle scheduler."""
    engine = MockAsyncEngine(n_lanes=2, seq_len=4096, step_s=0.001, max_chunk=1024)
    sched, tel = make_sched(engine, prompt_tokens=2304)
    long = Request(prompt="x" * 2304, max_tokens=6, temperature=0.0)
    first = Request(prompt="hello", max_tokens=600, temperature=0.0)
    if on_the_chain:
        run(sched, [first, long], stagger_after=3)
        cls = names.step_class("fused", 1024)
    else:
        run(sched, [long])
        cls = names.step_class("prefill", 1024)
    rows = long.tel.chunks
    assert all(isinstance(r, StepRecord) for r in rows)
    assert [(r.cls, r.chunk, r.p_start, r.final) for r in rows] == [
        (cls, 1024, 0, False), (cls, 1024, 1024, False), (cls, 256, 2048, True)]
    assert long.tel.fused_admitted is on_the_chain
    for r in rows:
        assert r.interval_s == pytest.approx(r.wait_s + r.host_s, abs=1e-9)
        assert long.tel.first_dispatch_at <= r.at <= long.tel.first_token_at
    assert [r.at for r in rows] == sorted(r.at for r in rows)
    # the last chunk's readback is where the prompt is done (the very stamp,
    # on the chain)
    assert rows[-1].at <= long.tel.prefill_done_at
    assert not on_the_chain or rows[-1].at == long.tel.prefill_done_at
    if on_the_chain:
        # the rows ARE the records of the steps that carried them
        by_step = {e.args["step"]: e.args for e in step_slices(tel)}
        for r in rows:
            assert by_step[r.step]["chunk"] == r.chunk and by_step[r.step]["p_start"] == r.p_start
            assert by_step[r.step]["final"] == int(r.final) and r.lanes == 1
    # the completion's summary says them compactly, and its log line too
    brief = long.summary["chunks"]
    assert [(c["class"], c["tokens"], c["p_start"]) for c in brief] == [
        (cls, 1024, 0), (cls, 1024, 1024), (cls, 256, 2048)]
    assert all(set(c) == {"class", "tokens", "p_start", "interval_ms", "wait_ms",
                          "host_ms", "dry"} for c in brief)
    assert brief[0]["interval_ms"] == pytest.approx(1e3 * rows[0].interval_s, abs=1e-3)
    json.dumps(long.summary)
    # a request of one short chunk: one row
    if on_the_chain:
        assert len(first.tel.chunks) == 1 and first.tel.chunks[0].final


# ---------------------------------------------------------------------------
# (c) the annotation is handed the span's args
# ---------------------------------------------------------------------------


def test_span_hands_its_args_to_the_factory_and_records_without_one():
    Annotation.made = []
    tel = Telemetry()
    tel.annotation_factory = Annotation
    with tel.span("loop.dispatch", "loop", args={"step": 7, "dry": 1}):
        pass
    with tel.span("loop.admit", "loop"):
        pass
    assert Annotation.made == [("dl.loop.dispatch", {"step": 7, "dry": 1}), ("dl.loop.admit", {})]
    bare = Telemetry()
    assert bare.annotation_factory is None
    with bare.span("loop.dispatch", "loop", args={"step": 7, "dry": 1}):
        pass
    (a, _), (b,) = tel.tracer.snapshot(), bare.tracer.snapshot()
    assert (a.name, a.track, a.args) == (b.name, b.track, b.args) == (
        "loop.dispatch", "loop", {"step": 7, "dry": 1})


def test_the_loops_annotations_carry_the_step_and_the_record(churn):
    _reqs, sched, tel, made = churn
    loop = [(n, kw) for n, kw in made if n.startswith("dl.loop.")]
    assert {n for n, _ in loop} == {names.ANNOTATION_PREFIX + s for s in names.LOOP_SPANS}
    assert all(isinstance(kw["step"], int) for _n, kw in loop)
    dispatched = [kw for n, kw in loop if n == "dl.loop.dispatch"]
    assert [kw["step"] for kw in dispatched] == list(range(1, sched._step_seq + 1))
    assert all(set(kw) == {"step", "dry"} and kw["dry"] in (0, 1) for kw in dispatched)
    # dl.loop.stream is handed the record: what the step.* slice of the same
    # step keeps in the ring, value for value
    streamed = {kw["step"]: kw for n, kw in loop if n == "dl.loop.stream"}
    slices = step_slices(tel)
    assert len(streamed) == len(slices) > 20
    for e in slices:
        kw = streamed[e.args["step"]]
        assert kw == {k: v for k, v in e.args.items() if k != "bucket"}
        # plain values only: what a profiler annotation can encode
        assert all(isinstance(v, (int, float, str)) and not isinstance(v, bool)
                   for v in kw.values())
        assert kw["dry"] == {d["step"]: d["dry"] for d in dispatched}[kw["step"]]


def test_a_real_annotation_takes_the_records_keywords():
    import jax

    rec = StepRecord(step=3, cls="dlstep.fused.b1024", chunk=1024, p_start=2048, final=True,
                     lanes=15, dry=True, dry_s=0.01, interval_s=0.134, wait_s=0.13,
                     host_s=0.004, at=1.0)
    with jax.profiler.TraceAnnotation("dl.loop.stream", **rec.args()):
        pass
    assert rec.args() == {"step": 3, "class": "dlstep.fused.b1024", "lanes": 15, "dry": 1,
                          "dry_s": 0.01, "interval_s": 0.134, "wait_s": 0.13, "host_s": 0.004,
                          "chunk": 1024, "p_start": 2048, "final": 1}
    plain = rec._replace(chunk=0, p_start=0, final=False, dry=False, dry_s=0.0,
                         cls="dlstep.decode")
    assert set(plain.args()) == {"step", "class", "lanes", "dry", "interval_s", "wait_s", "host_s"}


# ---------------------------------------------------------------------------
# (d) the instruments and the endpoints
# ---------------------------------------------------------------------------


def test_step_duration_renders_one_series_a_class(churn):
    _reqs, sched, tel, _made = churn
    text = tel.render_prometheus(bridge=sched.engine.stats.snapshot())
    assert "dllama_overlap_fraction" not in text
    assert not hasattr(tel, "overlap_fraction")
    counts = {}
    for line in text.splitlines():
        if line.startswith("dllama_step_duration_seconds_count"):
            label, n = line[len("dllama_step_duration_seconds_count"):].rsplit(" ", 1)
            counts[label] = int(n)
    decode, fused, alone = (names.step_class("decode"), names.step_class("fused", 16),
                            names.step_class("prefill", 16))
    # (an idle scheduler may take one synchronous step before its chain starts)
    sync = f'{{class="{names.step_class("decode_sync_nologits")}"}}'
    assert set(counts) - {sync} == {f'{{class="{c}"}}' for c in (decode, fused, alone)}
    slices = step_slices(tel)
    n_fused = sum(e.name == "step.fused" for e in slices)
    assert counts[f'{{class="{fused}"}}'] == n_fused >= 1
    assert counts[f'{{class="{decode}"}}'] == sum(e.name == "step.pipelined" for e in slices)
    # the first request's chunk was dispatched alone (and any that missed the chain)
    assert counts[f'{{class="{alone}"}}'] == 5 - n_fused
    # a pipelined step observes its interval: the series' sum is the slices'
    total = sum(e.args["interval_s"] for e in slices if e.name == "step.fused")
    assert tel.step_duration.snapshot(**{"class": fused})[1] == pytest.approx(total)
    # the bridge exports the witness by itself
    for key in DRY_FIELDS:
        assert f"dllama_stats_{key} " in text


def test_synchronous_steps_observe_their_class():
    engine = MockAsyncEngine(n_lanes=2, step_s=0.001)
    sched, tel = make_sched(engine, pipelined=False)
    run(sched, [Request(prompt="hello there", max_tokens=6, temperature=0.0)])
    series = {dict(k)["class"] for k in tel.step_duration._hist_series}
    assert series == {names.step_class("prefill", engine.max_chunk()),
                      names.step_class("decode_sync_nologits")}
    assert engine.stats.snapshot()["pipeline_dispatches"] == 0


@pytest.fixture()
def server():
    from distributed_llama_multiusers_tpu.server import ApiServer
    from distributed_llama_multiusers_tpu.tokenizer import TemplateType

    engine = MockAsyncEngine()
    sched, tel = make_sched(engine)
    sched.start()
    api = ApiServer(sched, sched.tokenizer, model_name="mock-steps",
                    template_type=TemplateType.CHATML)
    httpd = api.serve(host="127.0.0.1", port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{httpd.server_address[1]}"
    httpd.shutdown()
    sched.stop()


def test_the_endpoints_serve_the_witness_the_class_and_the_chunks(server):
    post = urllib.request.Request(
        server + "/v1/completions",
        data=json.dumps({"prompt": "hello world", "max_tokens": 12, "temperature": 0}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(post, timeout=60) as r:
        body = json.loads(r.read())
    chunks = body["summary"]["chunks"]
    assert len(chunks) == 1 and chunks[0]["p_start"] == 0 and chunks[0]["tokens"] == 8
    with urllib.request.urlopen(server + "/stats", timeout=30) as r:
        stats = json.loads(r.read())
    assert all(k in stats for k in DRY_FIELDS)
    assert 0 < stats["live_lane_steps"] <= stats["pipeline_dispatches"]
    assert "overlap_s" in stats
    with urllib.request.urlopen(server + "/metrics", timeout=30) as r:
        text = r.read().decode()
    assert "dllama_overlap_fraction" not in text
    assert 'dllama_step_duration_seconds_count{class="dlstep.decode"}' in text
    for key in DRY_FIELDS:
        assert f"dllama_stats_{key} " in text
