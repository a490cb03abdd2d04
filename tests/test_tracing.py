"""One trace from inside the program (telemetry/names.py).

(a) every step family carries the ``dl.*`` device scopes, and its heavy
    operations all sit under one;
(b) a pipelined scheduler run leaves, per dispatch, the ``loop.*`` slices in
    order, apart, covering the loop's wall time, with nothing dropped;
(c) per finished request the four phases of the first token add up to
    ``ttft_ms``;
(d) ``Telemetry.span`` holds an annotation ``dl.<name>`` open around every
    slice it records, and records the same ring without a factory.
"""

import dataclasses
import re
import time

import numpy as np
import pytest
import jax.numpy as jnp

from distributed_llama_multiusers_tpu.formats import load_model_header
from distributed_llama_multiusers_tpu.formats.synthetic import (
    tiny_header,
    write_synthetic_model,
)
from distributed_llama_multiusers_tpu.models import load_params_from_m
from distributed_llama_multiusers_tpu.runtime import (
    ContinuousBatchingScheduler,
    InferenceEngine,
    Request,
)
from distributed_llama_multiusers_tpu.telemetry import PHASE_KEYS, Telemetry
from distributed_llama_multiusers_tpu.telemetry import names
from distributed_llama_multiusers_tpu.utils.testing import (
    MockAsyncEngine,
    StubStreamTokenizer,
)

# ---------------------------------------------------------------------------
# (a) device scopes in the lowered step programs
# ---------------------------------------------------------------------------

MODELS = {
    "dense": {},
    "biased": dict(dim=64, hidden_dim=160, vocab_size=96, seq_len=48, qkv_bias=1),
    "moe": dict(n_experts=4, n_active_experts=2),
}
PROGRAMS = {
    # attribute of the engine -> the entry point's call that reaches it
    "_decode_pl_fn": lambda e, z: (e.decode_pipelined(z, tokens=z), e.pipeline_flush()),
    "_decode_prefill_fn": lambda e, z: (
        e.decode_prefill_fused(np.full(len(z), e.config.seq_len, np.int32),
                               p_lane=0, chunk=[1, 2, 3], tokens=z),
        e.pipeline_flush()),
    "_decode_nologits_fn": lambda e, z: e.decode(z, z, want_logits=False),
    "_prefill_fn": lambda e, z: e.prefill_chunk(0, [1, 2, 3], 0),
}
# operations that do the step's work: each must sit under some dl.* scope
HEAVY = re.compile(
    r"\b(stablehlo\.dot_general|stablehlo\.sort|chlo\.top_k|stablehlo\.scatter|"
    r"stablehlo\.dynamic_update_slice|stablehlo\.custom_call)\b")
LOC_DEF = re.compile(r'^(#loc\d+) = loc\("([^"]*)"', re.M)
LOC_USE = re.compile(r"loc\((#loc\d+)\)\s*$")


@pytest.fixture(scope="module")
def engines(tmp_path_factory):
    d = tmp_path_factory.mktemp("tracing_models")
    made = {}

    def get(model: str, paged: bool):
        key = (model, paged)
        if key not in made:
            path = str(d / f"{model}.m")
            write_synthetic_model(path, tiny_header(**MODELS[model]), seed=5)
            config, params = load_params_from_m(
                path, load_model_header(path), dtype=jnp.float32)
            made[key] = InferenceEngine(config, params, n_lanes=2,
                                        prefill_buckets=(4,), paged_kv=paged)
        return made[key]

    return get


class _Lowered(Exception):
    """Ends an entry point's call where its program is lowered."""


def lowered_with_debug_info(engine, attr: str) -> str:
    """The StableHLO text, locations included, of the program ``attr`` as the
    engine's own entry point calls it. The call ends there: the program is
    neither compiled nor run (every entry point does its bookkeeping after
    its program returns, so nothing of the engine has moved), which is most
    of what a case that reads the text used to cost."""
    fn = getattr(engine, attr)

    def spy(*args):
        raise _Lowered(fn.lower(*args).as_text(debug_info=True))

    setattr(engine, attr, spy)
    try:
        PROGRAMS[attr](engine, np.zeros(engine.n_lanes, np.int32))
    except _Lowered as lowered:
        return lowered.args[0]
    finally:
        setattr(engine, attr, fn)
    raise AssertionError(f"{attr} was not called")


@pytest.mark.parametrize("attr", sorted(PROGRAMS))
@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_step_program_carries_every_scope(engines, model, paged, attr):
    text = lowered_with_debug_info(engines(model, paged), attr)
    locs = dict(LOC_DEF.findall(text))
    for scope in names.ALL_SCOPES:
        assert any(scope in names.scope_path(n) for n in locs.values()), scope
    heavy = unscoped = 0
    for line in text.splitlines():
        if not HEAVY.search(line.split(" loc(")[0]):
            continue
        use = LOC_USE.search(line)
        if use is None:   # an op with a region: its location closes the region
            continue
        heavy += 1
        if names.scope_of(locs.get(use.group(1), "")) is None:
            unscoped += 1
            print("no scope:", line.strip()[:160], locs.get(use.group(1)))
    assert heavy >= 8 and unscoped == 0


def test_scope_of_takes_the_deepest_component():
    p = "jit(_decode_pl)/jit(main)/dl.layers/while/body/closed_call/dl.attention/dot_general:"
    assert names.scope_path(p) == ["dl.layers", "dl.attention"]
    assert names.scope_of(p) == "dl.attention"
    assert names.scope_of("jit(_decode_pl)/dl.sampler/cond/branch_1_fun/vmap()/top_k") == "dl.sampler"
    assert names.scope_of("jit(_decode_pl)/dl.layers/while/body/dynamic_update_slice") == "dl.layers"
    assert names.scope_of("jit(_decode_pl)/vmap(dl.sampler)/sort") == "dl.sampler"
    assert names.scope_of("jit(model.embed)/gather") is None and names.scope_of("") is None
    assert set(names.LEAF_SCOPES) | {names.SCOPE_LAYERS} == set(names.ALL_SCOPES)
    assert set(names.LAYER_SCOPES) < set(names.LEAF_SCOPES)


# ---------------------------------------------------------------------------
# (b) - (d) the batching loop's spans and the first token's stamps
# ---------------------------------------------------------------------------


class FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs its open and close
    (the keywords a span hands it are tests/test_step_record.py's)."""

    log: list = []

    def __init__(self, name, **_kwargs):
        self.name = name

    def __enter__(self):
        FakeAnnotation.log.append(("open", self.name, time.perf_counter()))
        return self

    def __exit__(self, *exc):
        FakeAnnotation.log.append(("close", self.name, time.perf_counter()))
        return False


def run_scheduler(reqs, stagger=True, annotate=None, prompt_tokens=8,
                  engine=None, tel=None, **kw):
    """A few requests through the real scheduler loop over the mock engine:
    the first is admitted synchronously (idle scheduler), the others ride the
    live chain as fused admissions."""
    engine = engine or MockAsyncEngine(n_lanes=4, step_s=0.002, max_chunk=16)
    tel = tel or Telemetry()
    if annotate is not None:
        tel.annotation_factory = annotate
    sched = ContinuousBatchingScheduler(
        engine, StubStreamTokenizer(prompt_tokens=prompt_tokens), telemetry=tel,
        speculative=False, multi_step=0, prefix_min_tokens=0, **kw)
    sched.start()
    try:
        sched.submit(reqs[0])
        deadline = time.monotonic() + 60
        while stagger and len(reqs[0].generated_tokens) < 3:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        for r in reqs[1:]:
            sched.submit(r)
            time.sleep(0.003 if stagger else 0)
        for r in reqs:
            r.future.result(timeout=60)
    finally:
        sched.stop()
    assert all(r.error is None for r in reqs)
    return sched, tel


def some_requests(n=5, max_tokens=24, prompt="hello there"):
    return [Request(prompt=prompt, max_tokens=max_tokens - i, temperature=0.0)
            for i in range(n)]


@pytest.fixture(scope="module")
def traced_run():
    FakeAnnotation.log = []
    reqs = some_requests()
    sched, tel = run_scheduler(reqs, annotate=FakeAnnotation)
    return reqs, sched, tel, list(FakeAnnotation.log)


def loop_slices(tel):
    return [e for e in tel.tracer.snapshot() if e.track == names.LOOP_TRACK]


def test_loop_slices_partition_every_step(traced_run):
    _reqs, sched, tel, _log = traced_run
    counts = tel.tracer.counts()
    assert counts["trace_events_dropped"] == 0
    loop = loop_slices(tel)
    assert {e.name for e in loop} == set(names.LOOP_SPANS)
    by_step: dict = {}
    for e in loop:
        by_step.setdefault(e.args["step"], {}).setdefault(e.name, []).append(e)
    steps = sorted(s for s, d in by_step.items() if names.LOOP_DISPATCH in d)
    assert steps == list(range(1, sched._step_seq + 1)) and len(steps) > 20
    order = (names.LOOP_ADMIT, names.LOOP_DISPATCH, names.LOOP_WAIT, names.LOOP_STREAM)
    with_admit = 0
    for s in steps:
        d = by_step[s]
        # every dispatched step was waited for and streamed, once each; the
        # admission that precedes a dispatch carries the same step (a ring
        # fill dispatches twice behind one admission)
        assert [len(d.get(n, [])) for n in order[1:]] == [1, 1, 1], (s, d)
        assert len(d.get(names.LOOP_ADMIT, [])) <= 1
        with_admit += names.LOOP_ADMIT in d
        present = [d[n][0] for n in order if n in d]
        for a, b in zip(present, present[1:]):
            assert a.ts + a.dur <= b.ts + 1e-9, (s, a, b)
    assert with_admit >= len(steps) - 4
    # the step slice of the same dispatch carries the same number
    stepped = [e for e in tel.tracer.snapshot()
               if e.name in ("step.pipelined", "step.fused")]
    assert sorted(e.args["step"] for e in stepped) == steps
    fused_steps = {e.args["step"] for e in stepped if e.name == "step.fused"}
    chunks = [e for e in tel.tracer.snapshot() if e.name == "prefill.fused"]
    assert fused_steps and {e.args["step"] for e in chunks} == fused_steps


UNCOVERED_BOUND = 0.4


def typical_uncovered_share(loop) -> tuple[float, int]:
    """(the share of the loop's own time in a typical iteration that no span
    covers, the iterations) of the loop in ``loop``, its slices by time.

    The rule. An iteration is the slices up to and including a
    ``loop.stream``. Its uncovered time is the sum of the gaps between
    neighbouring slices, the gap behind it included, so every gap is charged
    to exactly one iteration (the last iteration, and one behind which the
    loop parked on an empty queue for over 50 ms, have no gap behind them: a
    parked loop is not the pipelined loop's time). Its own time is that and
    its slices but ``loop.wait``: what this thread's Python took, and not the
    device's step it waited out. The share is the MEDIAN uncovered time over
    the median own time: the typical iteration, not the sum of the seconds.
    The loop's thread losing the CPU between two spans lengthens one gap of
    one iteration, however long it was away, and moves no median; loop code
    without a span runs in every iteration that takes its branch, and moves
    the median as soon as most iterations take it.

    Why the wait is left out (PR 58). The uncovered time was held to 5 % of
    the whole iteration beside the mock's 2 ms step: 0.1 ms, where the spans'
    own time is 0.15 ms. Load stretches gaps and spans alike and the mock's
    sleep not at all, so that share rose with the load (0.030-0.043 quiet,
    0.031-0.060 beside a running suite: 3 runs of 9 over the bound) while the
    share of the own time stood still (0.26-0.33 in 100 runs at load averages
    of 3 to 22; the control 0.95-0.97). ``UNCOVERED_BOUND`` is the same 0.1 ms
    on a quiet machine, in the unit a loaded one does not move."""
    iterations, cur = [], []
    for e in loop:
        cur.append(e)
        if e.name == names.LOOP_STREAM:
            iterations.append(cur)
            cur = []
    if cur:
        iterations.append(cur)
    own, uncovered = [], []
    for it, nxt in zip(iterations, iterations[1:] + [None]):
        end = it[-1].ts + it[-1].dur
        if nxt is not None and nxt[0].ts - end <= 0.05:
            end = nxt[0].ts
        uncovered.append(end - it[0].ts - sum(e.dur for e in it))
        own.append(uncovered[-1] + sum(e.dur for e in it if e.name != names.LOOP_WAIT))
    return float(np.median(uncovered) / np.median(own)), len(iterations)


def test_loop_slices_do_not_overlap_and_cover_the_loop(traced_run):
    _reqs, _sched, tel, _log = traced_run
    loop = sorted(loop_slices(tel), key=lambda e: e.ts)
    for a, b in zip(loop, loop[1:]):
        assert a.ts + a.dur <= b.ts + 1e-9, (a, b)
    share, n = typical_uncovered_share(loop)
    assert n > 20 and share <= UNCOVERED_BOUND, (share, n)


def test_a_loop_branch_without_a_span_fails_the_coverage(traced_run):
    """The control: the same run, read by the same rule at the same bound,
    with two of the loop's branches left without their span (dispatch and
    stream: all but a twentieth of the loop's own time) is past the bound in
    its typical iteration."""
    _reqs, _sched, tel, _log = traced_run
    loop = sorted(loop_slices(tel), key=lambda e: e.ts)
    bare = {names.LOOP_DISPATCH, names.LOOP_STREAM}
    kept = []
    for e in loop:
        if e.name not in bare:
            kept.append(e)
        elif e.name == names.LOOP_STREAM:
            # the iteration still ends where its stream ended: a marker of no
            # duration stands for it
            kept.append(dataclasses.replace(e, ts=e.ts + e.dur, dur=0.0))
    share, n = typical_uncovered_share(kept)
    assert n > 20 and share > UNCOVERED_BOUND, (share, n)


def test_every_span_slice_has_its_annotation(traced_run):
    _reqs, _sched, tel, log = traced_run
    loop = sorted(loop_slices(tel), key=lambda e: e.seq)
    opens = [(n, t) for kind, n, t in log if kind == "open"]
    closes = [(n, t) for kind, n, t in log if kind == "close"]
    assert len(opens) == len(closes) == len(loop)
    # annotations never nest here, so the k-th open pairs with the k-th close
    # slice timestamps are perf_counter values, as the log's are
    for ev, (n_open, t_open), (n_close, t_close) in zip(loop, opens, closes):
        assert n_open == n_close == names.ANNOTATION_PREFIX + ev.name
        assert t_open <= t_close
        # the slice lies inside its annotation (opened just before, closed
        # just before the slice was appended)
        assert t_open - 1e-3 <= ev.ts <= ev.ts + ev.dur <= t_close + 1e-3


def test_without_a_factory_the_ring_is_the_same():
    tel = Telemetry()
    assert tel.annotation_factory is None
    with tel.span("loop.wait", "loop", args={"step": 7}):
        pass
    FakeAnnotation.log = []
    tel2 = Telemetry()
    tel2.annotation_factory = FakeAnnotation
    with tel2.span("loop.wait", "loop", args={"step": 7}):
        pass
    (a,), (b,) = tel.tracer.snapshot(), tel2.tracer.snapshot()
    assert (a.name, a.ph, a.track, a.req_id, a.args, a.seq) == \
        (b.name, b.ph, b.track, b.req_id, b.args, b.seq) == \
        ("loop.wait", "X", "loop", None, {"step": 7}, 1)
    assert [(k, n) for k, n, _t in FakeAnnotation.log] == [
        ("open", "dl.loop.wait"), ("close", "dl.loop.wait")]


def test_span_records_its_slice_when_the_body_raises():
    tel = Telemetry()
    tel.annotation_factory = FakeAnnotation
    FakeAnnotation.log = []
    with pytest.raises(RuntimeError):
        with tel.span("loop.dispatch", "loop"):
            raise RuntimeError("dispatch failed")
    assert [e.name for e in tel.tracer.snapshot()] == ["loop.dispatch"]
    assert [k for k, _n, _t in FakeAnnotation.log] == ["open", "close"]


def test_scheduler_injects_the_profiler_annotation():
    import jax

    sched = ContinuousBatchingScheduler(
        MockAsyncEngine(n_lanes=2), StubStreamTokenizer(), telemetry=Telemetry())
    assert sched.telemetry.annotation_factory is jax.profiler.TraceAnnotation
    own = Telemetry()
    own.annotation_factory = FakeAnnotation
    sched = ContinuousBatchingScheduler(
        MockAsyncEngine(n_lanes=2), StubStreamTokenizer(), telemetry=own)
    assert sched.telemetry.annotation_factory is FakeAnnotation


def test_event_seq_is_assigned_once_at_construction():
    tel = Telemetry(trace_capacity=4)
    for i in range(6):
        tel.tracer.instant(f"i{i}", "queue")
    evs = tel.tracer.snapshot()
    assert [e.seq for e in evs] == [3, 4, 5, 6] and [e.name for e in evs] == ["i2", "i3", "i4", "i5"]
    assert tel.tracer.snapshot(since=5)[0].seq == 6
    assert tel.tracer.counts()["trace_events_dropped"] == 2


FIRST_TOKEN_PHASES = ("queue_wait_ms", "dispatch_wait_ms", "prefill_ms", "first_token_hold_ms")

ADMISSIONS = {
    # name -> (scheduler kwargs, prompt length in tokens, index of the request looked at)
    "fused": (dict(pipelined=True, fused_prefill=True), 8, 2),
    "synchronous": (dict(pipelined=False), 8, 1),
    "several_chunks_fused": (dict(pipelined=True, fused_prefill=True), 40, 2),
    "several_chunks_synchronous": (dict(pipelined=True, fused_prefill=False), 40, 1),
}


class StepStamps(Telemetry):
    """Telemetry that also notes, per request, WHICH consumed step read back
    its prompt's last chunk and which one streamed its first token: the
    scheduler reports a consumed step (``on_pipelined_step``) before it
    streams that step's tokens and adopts its boundary token. Synchronous
    decode steps (``on_step``) are counted too: a first token streamed
    before the next one leaves the count where the prefill's end found it."""

    def __init__(self):
        super().__init__()
        self.consuming = None
        self.prefill_done_step = {}
        self.first_token_step = {}

    def on_pipelined_step(self, *args, record, **kw):
        self.consuming = record.step
        super().on_pipelined_step(*args, record=record, **kw)

    def on_step(self, *args, **kw):
        self.consuming = (self.consuming or 0) + 1
        super().on_step(*args, **kw)

    def on_prefill_done(self, req, now):
        self.prefill_done_step[req.id] = self.consuming
        super().on_prefill_done(req, now)

    def on_token(self, req, now=None):
        self.first_token_step.setdefault(req.id, self.consuming)
        super().on_token(req, now)


def engine_holding_the_chain_for(reqs):
    """A mock engine whose device, once the first request has three tokens,
    returns no further step until the LAST request's submit has begun, by
    which time every request before it is in the queue: the chain is live
    at their admission whatever the machine does to the submitting thread
    meanwhile."""
    engine = MockAsyncEngine(n_lanes=4, step_s=0.002, max_chunk=16)
    device_returns = engine.pipeline_consume

    def held_consume():
        deadline = time.monotonic() + 60
        while len(reqs[0].generated_tokens) >= 3 and reqs[-1].submitted_at is None:
            assert time.monotonic() < deadline
            time.sleep(0.0005)
        return device_returns()

    engine.pipeline_consume = held_consume
    return engine


@pytest.mark.parametrize("kind", sorted(ADMISSIONS))
def test_first_token_phases_add_up_to_ttft(kind):
    kw, prompt_tokens, idx = ADMISSIONS[kind]
    reqs = some_requests(4, max_tokens=12, prompt="p" * 64)
    assert idx < len(reqs) - 1  # the request looked at is not the last one
    stamps = StepStamps()
    # a fused admission needs a live chain to ride
    engine = engine_holding_the_chain_for(reqs) if kw["pipelined"] else None
    run_scheduler(reqs, prompt_tokens=prompt_tokens, engine=engine, tel=stamps, **kw)
    for r in reqs:
        ph = r.summary["phases"]
        assert set(ph) == set(PHASE_KEYS)
        assert sum(ph[k] for k in FIRST_TOKEN_PHASES) == pytest.approx(ph["ttft_ms"], abs=0.01)
        tel = r.tel
        assert tel.submitted_at <= tel.admitted_at <= tel.first_dispatch_at \
            <= tel.prefill_done_at <= tel.first_token_at
    r = reqs[idx]
    assert r.tel.fused_admitted == kind.endswith("fused")
    ph = r.summary["phases"]
    if r.tel.fused_admitted:
        # the prompt rode the (mock) device's steps: 2 ms a chunk at least
        # (the mock's synchronous prefill takes no device time)
        n_chunks = -(-prompt_tokens // 16)
        assert ph["prefill_ms"] >= 2.0 * n_chunks * 0.9
    # the first token is streamed at the readback that delivers it, the one
    # that ended the prefill (PR 57; until then the step after it emitted
    # it, a whole decode step later): no step is consumed between the two
    # stamps, on the chain or on the synchronous ladder, and
    # first_token_hold_ms is the stream work alone
    assert stamps.first_token_step[r.id] == stamps.prefill_done_step[r.id]


def test_phases_without_a_first_token_are_zero_not_missing():
    from distributed_llama_multiusers_tpu.telemetry import RequestTrace

    tel = RequestTrace(submitted_at=10.0)
    tel.admitted_at = 10.5
    ph = tel.phases()
    assert ph["queue_wait_ms"] == 500.0
    assert [ph[k] for k in FIRST_TOKEN_PHASES[1:]] == [0.0, 0.0, 0.0] and ph["ttft_ms"] == 0.0
    # a missing middle stamp collapses onto the one before it
    tel.first_token_at = tel.last_token_at = 11.0
    ph = tel.phases()
    assert (ph["dispatch_wait_ms"], ph["prefill_ms"], ph["first_token_hold_ms"]) == (0.0, 0.0, 500.0)
    assert sum(ph[k] for k in FIRST_TOKEN_PHASES) == ph["ttft_ms"] == 1000.0


def test_warmup_logs_one_line_a_program(engines, capsys):
    import io
    import json

    from distributed_llama_multiusers_tpu.runtime.engine import warmup_engine
    from distributed_llama_multiusers_tpu.telemetry import logs

    stream = io.StringIO()
    old = logs.default_logger().stream
    logs.default_logger().stream = stream
    try:
        warmup_engine(engines("dense", False), spec=False, multi_step=0)
    finally:
        logs.default_logger().stream = old
    lines = [json.loads(x) for x in stream.getvalue().splitlines()]
    progs = [x for x in lines if x["event"] == "warmup_program"]
    assert [p["program"] for p in progs] == [
        "prefill[4]", "decode", "decode_nologits", "decode_pl", "decode_prefill[4]",
        "copy_lane", "sample_one"]
    for p in progs:
        assert p["seconds"] >= 0 and p["source"] in ("compiled", "cache", "memory")
        assert (p["compiled"] > 0) == (p["source"] == "compiled")
    assert lines[-1]["event"] == "warmup_engine"
