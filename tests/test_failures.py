"""Chaos suite: the failure-containment layer under deterministic faults.

The properties pinned here are the ISSUE 8 acceptance criteria:

- an engine-scoped fault mid-churn fails ONLY the requests holding lanes
  at that moment (``finish_reason="error"``, futures carry the
  request_id), the pipeline ring drains, and every later request's
  stream is byte-identical to a fault-free run — the loop thread never
  dies;
- the circuit breaker walks closed → open → half-open → closed over
  real ``/health`` + ``/stats`` HTTP reads;
- the watchdog fires on a stalled (blackholed) consume within its
  deadline and trips the breaker;
- a fault plan is a pure function of its spec: same seed, same schedule;
- control-plane packets carry a validated magic/version word: a torn or
  skewed packet is a classified ReplayError that does not burn a
  supervised-restart budget;
- the HTTP layer's bounded future waits turn a wedged scheduler into a
  request_id-carrying 503 instead of a hung socket.

Everything runs on the MockAsyncEngine (utils/testing.py) — tokens are
a pure function of (lane, position), so stream identity across a
contained failure is exact equality, with zero accelerator timing noise.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from distributed_llama_multiusers_tpu.runtime.scheduler import (
    ContinuousBatchingScheduler,
    EngineFailure,
    Request,
    classify_failure,
)
from distributed_llama_multiusers_tpu.serving import (
    AdmissionRejected,
    CircuitBreaker,
    StepWatchdog,
)
from distributed_llama_multiusers_tpu.utils import faults
from distributed_llama_multiusers_tpu.utils.faults import (
    FaultPlan,
    InjectedFault,
)
from distributed_llama_multiusers_tpu.utils.testing import (
    MockAsyncEngine,
    StubStreamTokenizer,
)


@pytest.fixture(autouse=True)
def _disarm_faults():
    """Every test leaves the process-global fault plan unarmed."""
    faults.disarm()
    yield
    faults.disarm()


def _sched(engine, **kw):
    kw.setdefault("speculative", False)
    kw.setdefault("prefix_min_tokens", 0)
    kw.setdefault("multi_step", 0)
    return ContinuousBatchingScheduler(
        engine, StubStreamTokenizer(engine.config.vocab_size), **kw
    )


def _drive(engine, reqs, staggered=True, gap=None, **kw):
    """Submit ``reqs`` (staggered behind a live chain, or all up front)
    and wait for every future to RESOLVE — success or failure. Returns
    the scheduler."""
    sched = _sched(engine, **kw)
    sched.start()
    try:
        if staggered:
            sched.submit(reqs[0])
            deadline = time.monotonic() + 60
            while len(reqs[0].generated_tokens) < 2:
                assert time.monotonic() < deadline, "first request never ran"
                time.sleep(0.002)
            for r in reqs[1:]:
                sched.submit(r)
                time.sleep(gap if gap is not None else engine.step_s * 2)
        else:
            for r in reqs:
                sched.submit(r)
        for r in reqs:
            try:
                r.future.result(timeout=60)
            except Exception:  # noqa: BLE001 — failures are the subject here
                pass
    finally:
        sched.stop()
    return sched


def _reqs(n, max_tokens=20):
    return [
        Request(prompt="chaos request text", max_tokens=max_tokens,
                temperature=0.0)
        for _ in range(n)
    ]


# ---------------------------------------------------------------------------
# failure classification
# ---------------------------------------------------------------------------


def test_classify_failure():
    assert classify_failure(ValueError("empty prompt")) == "request"
    assert classify_failure(RuntimeError("XLA boom")) == "engine"
    assert classify_failure(InjectedFault("engine.dispatch", 3)) == "engine"


# ---------------------------------------------------------------------------
# the headline: mid-churn engine fault, contained
# ---------------------------------------------------------------------------


def test_engine_fault_mid_churn_contained():
    """One injected dispatch fault mid-churn: the requests holding lanes
    fail with finish_reason="error" and an EngineFailure carrying their
    request_id; everything admitted afterwards completes with streams
    byte-identical to a fault-free run; the ring drains; the loop thread
    is still alive and serving."""
    n = 6
    base_engine = MockAsyncEngine(n_lanes=2, max_chunk=4)
    base_reqs = _reqs(n)
    _drive(base_engine, base_reqs, staggered=False, pipelined=False)
    base = [list(r.generated_tokens) for r in base_reqs]
    assert all(r.error is None for r in base_reqs)

    engine = MockAsyncEngine(n_lanes=2, max_chunk=4, step_s=0.002)
    reqs = _reqs(n)
    # fire once, well after the chain forms (the _drive gate waits for
    # the first request to be demonstrably generating)
    faults.arm("engine.dispatch:@10:n=1")
    sched = _drive(engine, reqs, staggered=True)

    failed = [r for r in reqs if r.finish_reason == "error"]
    ok = [r for r in reqs if r.finish_reason != "error"]
    assert failed, "the injected fault failed no request"
    assert len(failed) <= 2, "containment failed more lanes than exist"
    for r in failed:
        assert r.error and "injected fault" in r.error
        exc = r.future.exception()
        assert isinstance(exc, EngineFailure)
        assert exc.request_id == r.id  # the 500/SSE payload can name it
    # every unaffected request's stream is byte-identical to the
    # fault-free run (mock tokens are f(lane, pos): exact equality)
    by_prompt = {r.id: list(r.generated_tokens) for r in reqs}
    for r in ok:
        assert r.error is None, r.error
        assert by_prompt[r.id] in base, (
            f"stream of unaffected request {r.id} diverged from the "
            "fault-free run"
        )
    assert len(ok) == n - len(failed)
    # hang-free: no future was left unresolved (the failure mode before
    # containment was a dead loop thread with every client blocked)
    assert all(r.future.done() for r in reqs)
    # and containment released what the failed lanes held
    assert all(v == 0 for v in sched.leak_counts().values()), (
        sched.leak_counts()
    )
    # ring drained, loop survived long enough to serve everything after
    # the fault and to stop cleanly (sched.stop() in _drive did not raise)
    assert engine.pipeline_inflight() == 0
    assert not engine.pipeline_active
    snap = engine.stats.snapshot()
    assert snap["pipeline_dispatches"] > 6  # served on after containment
    stats = sched.qos_stats()
    assert stats["engine_failure_rounds"] == 1
    assert stats["engine_failures"].get("engine") == 1


def test_engine_fault_sync_path_contained():
    """The same containment on the synchronous (pipelined=False) path:
    a decode raise fails the active lanes and the loop keeps serving."""
    engine = MockAsyncEngine(n_lanes=2, max_chunk=4)
    reqs = _reqs(4, max_tokens=8)
    faults.arm("engine.dispatch:@3:n=1")
    sched = _drive(engine, reqs, staggered=False, pipelined=False)
    failed = [r for r in reqs if r.finish_reason == "error"]
    ok = [r for r in reqs if r.finish_reason != "error"]
    assert failed and ok
    assert all(len(r.generated_tokens) == 8 for r in ok)
    assert sched.qos_stats()["engine_failure_rounds"] == 1


def test_request_scoped_failure_fails_one_request():
    """A tokenizer failure (request-scoped) fails only that request —
    no containment round, no breaker movement, batch untouched."""

    class _BadTok(StubStreamTokenizer):
        def encode(self, text, add_bos=True, add_special_tokens=True):
            if "poison" in text:
                raise ValueError("tokenizer rejected prompt")
            return super().encode(text, add_bos, add_special_tokens)

    engine = MockAsyncEngine(n_lanes=2, max_chunk=4)
    sched = ContinuousBatchingScheduler(
        engine, _BadTok(engine.config.vocab_size), speculative=False,
        prefix_min_tokens=0, multi_step=0,
    )
    good = Request(prompt="fine", max_tokens=6, temperature=0.0)
    bad = Request(prompt="poison", max_tokens=6, temperature=0.0)
    sched.start()
    try:
        sched.submit(good)
        sched.submit(bad)
        assert good.future.result(timeout=60)is not None
        with pytest.raises(ValueError, match="tokenizer rejected"):
            bad.future.result(timeout=60)
    finally:
        sched.stop()
    assert bad.finish_reason == "error"
    assert good.error is None and len(good.generated_tokens) == 6
    stats = sched.qos_stats()
    assert stats["engine_failure_rounds"] == 0
    assert stats["breaker_state"] == "closed"
    assert stats["engine_failures"].get("request") == 1


# ---------------------------------------------------------------------------
# circuit breaker: transitions over /health + /stats
# ---------------------------------------------------------------------------


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_breaker_unit_transitions():
    b = CircuitBreaker(threshold=2, cooldown_s=0.05)
    assert b.state == "closed" and b.allow()
    b.record_engine_failure("one")
    assert b.state == "closed"  # not consecutive enough yet
    b.record_success()
    b.record_engine_failure("one")
    b.record_engine_failure("two")
    assert b.state == "open"
    assert not b.allow()  # inside cooldown: shed
    assert b.retry_after_s() >= 1.0
    time.sleep(0.06)
    assert b.allow()  # the probe
    assert b.state == "half_open"
    assert not b.allow()  # only one probe per window
    b.record_engine_failure("probe failed")
    assert b.state == "open"  # probe failure re-opens
    time.sleep(0.06)
    assert b.allow()
    b.record_success()
    assert b.state == "closed"
    s = b.stats()
    assert s["breaker_trips"] == 2
    assert s["engine_failures"]["engine"] == 4
    assert s["breaker_last_recovery_s"] is not None


def test_breaker_over_health_and_stats_http():
    """closed → open (engine faults) → half-open probe → closed, observed
    through real /health and /stats HTTP reads, with shed submissions
    getting 503 + Retry-After."""
    from distributed_llama_multiusers_tpu.server import ApiServer
    from distributed_llama_multiusers_tpu.tokenizer import TemplateType

    engine = MockAsyncEngine(n_lanes=2, max_chunk=4)
    tok = StubStreamTokenizer(engine.config.vocab_size)
    breaker = CircuitBreaker(threshold=1, cooldown_s=0.3)
    sched = ContinuousBatchingScheduler(
        engine, tok, speculative=False, prefix_min_tokens=0, multi_step=0,
        breaker=breaker,
    )
    api = ApiServer(sched, tok, model_name="chaos-test",
                    template_type=TemplateType.LLAMA2)
    httpd = api.serve(host="127.0.0.1", port=0)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{port}"
    sched.start()
    try:
        status, body = _get(base + "/health")
        assert status == 200 and body["status"] == "ok"
        # the first /stats of a process starts the JAX backend (about a
        # second where no earlier test has): read it once before the
        # 0.3 s cooldown below is running
        assert _get(base + "/stats")[0] == 200

        # one engine fault trips the threshold-1 breaker
        faults.arm("engine.dispatch:@1:n=1")
        victim = Request(prompt="x", max_tokens=4, temperature=0.0)
        sched.submit(victim)
        with pytest.raises(EngineFailure):
            victim.future.result(timeout=60)

        status, body = _get(base + "/health")
        assert status == 503 and body["status"] == "unhealthy"
        assert body["breaker"] == "open"
        status, stats = _get(base + "/stats")
        assert stats["breaker_state"] == "open"
        assert stats["breaker_state_code"] == 2
        assert stats["engine_failures"]["engine"] == 1

        # shed while open: typed 503 with Retry-After
        with pytest.raises(AdmissionRejected) as ei:
            sched.submit(Request(prompt="y", max_tokens=4))
        assert ei.value.reason == "breaker_open"
        assert ei.value.http_status == 503
        status, stats = _get(base + "/stats")
        assert stats["breaker_shed"] >= 1
        assert stats["queue_rejected_breaker"] >= 1

        # /metrics carries the native gauge + classified counter
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "dllama_breaker_state 2" in text
        assert (
            'dllama_engine_failures_total{failure_class="engine"} 1' in text
        )

        # cooldown elapses: the next submit is the half-open probe, its
        # success closes the breaker
        time.sleep(0.35)
        probe = sched.submit(Request(prompt="z", max_tokens=4,
                                     temperature=0.0))
        probe.future.result(timeout=60)
        assert probe.error is None
        deadline = time.monotonic() + 10
        while breaker.state != "closed":
            assert time.monotonic() < deadline, breaker.stats()
            time.sleep(0.01)
        status, body = _get(base + "/health")
        assert status == 200 and body["status"] == "ok"
        status, stats = _get(base + "/stats")
        assert stats["breaker_state"] == "closed"
        assert stats["breaker_probes"] >= 1
        assert stats["breaker_last_recovery_s"] is not None
    finally:
        httpd.shutdown()
        sched.stop()


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------


def test_watchdog_fires_on_stalled_consume():
    """A blackholed consume (kind=hang fault) trips the watchdog within
    its deadline: the breaker opens while the step is still stuck, and
    serving resumes once the hang clears."""
    engine = MockAsyncEngine(n_lanes=2, max_chunk=4, step_s=0.002)
    # one consume blackholes for ~1.2s; watchdog deadline 0.25s
    faults.arm("engine.consume:@4:n=1:kind=hang:hang=1.2")
    sched = _sched(engine, step_deadline_s=0.25)
    req = Request(prompt="stall", max_tokens=30, temperature=0.0)
    t0 = time.monotonic()
    sched.start()
    try:
        sched.submit(req)
        # the breaker must open while the consume is still blackholed
        deadline = time.monotonic() + 30
        while sched.breaker.state != "open":
            assert time.monotonic() < deadline, (
                "watchdog never tripped the breaker"
            )
            time.sleep(0.01)
        tripped_after = time.monotonic() - t0
        # fired within the deadline's order of magnitude, not the hang's
        assert tripped_after < 1.2, tripped_after
        assert sched.watchdog.stats()["watchdog_trips"] == 1
        # the hang clears; the request still completes (slow, not dead)
        req.future.result(timeout=60)
        assert req.error is None
        assert len(req.generated_tokens) == 30
    finally:
        sched.stop()
    stats = sched.qos_stats()
    assert stats["engine_failures"].get("watchdog") == 1
    assert stats["watchdog_trips"] == 1


def test_breaker_holds_open_across_watchdog_trip_no_flap():
    """ISSUE-10 satellite: breaker/watchdog interaction under repeated
    injected faults. A watchdog trip opens the breaker; the blackholed
    step then RETURNS (slow, not dead) and successful steps resume
    immediately — but the breaker must NOT flap closed off those early
    successes (`record_success` from OPEN closes only once the cooldown
    has held), and a later injected engine fault during the same window
    must not re-count a trip. One trip, one recovery, monotone
    closed -> open -> closed."""
    engine = MockAsyncEngine(n_lanes=2, max_chunk=4, step_s=0.002)
    # one consume blackholes for ~0.9s (watchdog deadline 0.2s) AND two
    # dispatch faults land while the breaker is already open: repeated
    # faults across the trip window
    faults.arm(
        "engine.consume:@4:n=1:kind=hang:hang=0.9;"
        "engine.dispatch:@40:n=2"
    )
    breaker = CircuitBreaker(threshold=3, cooldown_s=0.6)
    sched = _sched(engine, step_deadline_s=0.2, breaker=breaker)
    reqs = _reqs(4, max_tokens=40)
    sched.start()
    flapped = []
    stop_probe = threading.Event()

    def probe():
        # watch for an open->closed transition BEFORE the cooldown held
        opened_at = None
        while not stop_probe.is_set():
            s = breaker.state
            now = time.monotonic()
            if s == "open" and opened_at is None:
                opened_at = now
            elif s == "closed" and opened_at is not None:
                if now - opened_at < 0.5:  # cooldown is 0.6
                    flapped.append(now - opened_at)
                opened_at = None
            time.sleep(0.005)

    watcher = threading.Thread(target=probe, daemon=True)
    watcher.start()
    try:
        for r in reqs:
            try:
                sched.submit(r)
            except AdmissionRejected:
                pass  # shed while open is correct behavior
            time.sleep(0.05)
        deadline = time.monotonic() + 30
        while breaker.state != "open":
            assert time.monotonic() < deadline, "watchdog never tripped"
            time.sleep(0.01)
        # recovery: successful steps + cooldown close it exactly once
        deadline = time.monotonic() + 30
        while breaker.state != "closed":
            assert time.monotonic() < deadline, "breaker never recovered"
            time.sleep(0.02)
        for r in reqs:
            if r.future.done() or r.submitted_at is not None:
                try:
                    r.future.result(timeout=60)
                except Exception:  # noqa: BLE001 — faulted ones may error
                    pass
    finally:
        stop_probe.set()
        watcher.join(timeout=5)
        sched.stop()
    assert flapped == [], f"breaker flapped closed early: {flapped}"
    br = breaker.stats()
    # ONE trip (the watchdog's): the dispatch faults inside the open
    # window are contained + counted but never re-trip an open breaker,
    # and the early successes never closed it before the cooldown held
    assert br["breaker_trips"] == 1, br
    assert br["breaker_state"] == "closed"
    assert sched.watchdog.stats()["watchdog_trips"] == 1


def test_watchdog_unit_no_false_trip():
    """Armed steps that finish inside the deadline never trip; an armed
    step past the deadline trips exactly once."""
    trips = []
    wd = StepWatchdog(0.1, on_trip=trips.append)
    wd.start()
    try:
        for _ in range(5):
            wd.begin_step()
            time.sleep(0.01)
            wd.step_done()
        time.sleep(0.25)  # idle (disarmed): no trip
        assert trips == []
        wd.begin_step()
        deadline = time.monotonic() + 5
        while not trips:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        time.sleep(0.25)  # tripped once, stays disarmed
        assert len(trips) == 1
        assert trips[0] >= 0.1
    finally:
        wd.stop()
    assert wd.stats()["watchdog_trips"] == 1


# ---------------------------------------------------------------------------
# fault plan determinism
# ---------------------------------------------------------------------------


def test_fault_plan_determinism():
    """Same spec (same seed) → same schedule, both via the pure
    schedule() enumeration and via live fire() counting."""
    spec = "engine.dispatch:p=0.3,seed=42:n=5;engine.consume:@3+4"
    a = FaultPlan.parse(spec)
    b = FaultPlan.parse(spec)
    assert a.schedule("engine.dispatch", 50) == b.schedule(
        "engine.dispatch", 50
    )
    assert a.schedule("engine.consume", 20) == [3, 7, 11, 15, 19]
    # live fires land exactly on the precomputed schedule
    want = a.schedule("engine.dispatch", 50)
    fired = []
    for i in range(1, 51):
        try:
            a.fire("engine.dispatch")
        except InjectedFault as f:
            assert f.arrival == i
            fired.append(i)
    assert fired == want
    assert len(fired) == 5  # the n=5 cap held
    # a different seed produces a different schedule (overwhelmingly)
    c = FaultPlan.parse("engine.dispatch:p=0.3,seed=43:n=5")
    assert c.schedule("engine.dispatch", 50) != want


def test_fault_plan_parse_errors():
    with pytest.raises(ValueError, match="unknown fault point"):
        FaultPlan.parse("engine.bogus:@1")
    with pytest.raises(ValueError, match="trigger"):
        FaultPlan.parse("engine.dispatch")
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan.parse("engine.dispatch:@1:kind=explode")
    with pytest.raises(ValueError, match="empty fault spec"):
        FaultPlan.parse(" ; ")


def test_faults_env_arming(monkeypatch):
    monkeypatch.setenv("DLLAMA_FAULTS", "engine.dispatch:@2:n=1")
    plan = faults.maybe_arm_from_env()
    assert plan is not None and faults.armed()
    faults.fire("engine.dispatch")  # arrival 1: no fire
    with pytest.raises(InjectedFault):
        faults.fire("engine.dispatch")
    faults.disarm()
    assert not faults.armed()
    faults.fire("engine.dispatch")  # unarmed: no-op


# ---------------------------------------------------------------------------
# control-plane packet integrity
# ---------------------------------------------------------------------------


def test_packet_magic_and_version_validated():
    from distributed_llama_multiusers_tpu.parallel.multihost import (
        PACKET_MAGIC,
        PROTOCOL_VERSION,
        ControlPlane,
        ReplayError,
    )

    sent = []

    class _Plane(ControlPlane):
        def _bcast(self, pkt):
            sent.append(pkt.copy())
            return pkt

    plane = _Plane(n_lanes=2, chunk=8)
    plane.send_stop()
    pkt = sent[0]
    assert int(pkt[0]) == PACKET_MAGIC
    assert int(pkt[1]) == PROTOCOL_VERSION
    ControlPlane.validate(pkt)  # round-trips clean

    torn = pkt.copy()
    torn[0] = 0xDEAD
    with pytest.raises(ReplayError, match="magic mismatch"):
        ControlPlane.validate(torn)

    skewed = pkt.copy()
    skewed[1] = PROTOCOL_VERSION + 1
    with pytest.raises(ReplayError, match="protocol version"):
        ControlPlane.validate(skewed)

    # a truncated (even empty) packet is still the CLASSIFIED error, not
    # an IndexError burning a restart
    with pytest.raises(ReplayError, match="truncated"):
        ControlPlane.validate(np.zeros(0, np.int32))
    with pytest.raises(ReplayError, match="truncated"):
        ControlPlane.validate(pkt[:3])


def test_pod_root_pipeline_abort_broadcasts_flush():
    """Containment on a pod root must tell the workers: pipeline_abort
    broadcasts OP_PIPELINE_FLUSH (the drain op workers already honor)
    before aborting the root ring WITHOUT consuming — a silent
    __getattr__ forward would leave worker rings permanently diverged
    and burn their restart budgets on every later pipelined packet."""
    from distributed_llama_multiusers_tpu.parallel.multihost import (
        OP_PIPELINE_FLUSH,
        ControlPlane,
        RootControlEngine,
    )

    sent = []

    class _Plane(ControlPlane):
        def _bcast(self, pkt):
            sent.append(pkt.copy())
            return pkt

    class _Inner:
        n_lanes = 2
        aborted = 0
        consumed = 0

        def pipeline_abort(self):
            self.aborted += 1
            return 2

        def pipeline_consume(self):  # must NOT be called: it would re-raise
            self.consumed += 1

    inner = _Inner()
    root = RootControlEngine(inner, _Plane(n_lanes=2, chunk=8))
    assert root.pipeline_abort() == 2
    assert inner.aborted == 1 and inner.consumed == 0
    assert len(sent) == 1 and int(sent[0][2]) == OP_PIPELINE_FLUSH


def test_worker_serve_protocol_errors_do_not_burn_restarts():
    """Torn packets interleaved with good replays: worker_serve absorbs
    them as classified protocol errors WITHOUT burning its (tiny) restart
    budget, keeps replaying, counts them on engine.stats, and still exits
    on stop."""
    from distributed_llama_multiusers_tpu.parallel.multihost import (
        OP_DECODE,
        OP_STOP,
        PACKET_MAGIC,
        PROTOCOL_VERSION,
        worker_serve,
    )
    from distributed_llama_multiusers_tpu.runtime.engine import EngineStats

    class _Plane:
        HEADER = 6

        def __init__(self, script, chunk=8):
            self.chunk = chunk
            self._pkts = [self._pkt(kind) for kind in script]

        def _pkt(self, kind):
            from distributed_llama_multiusers_tpu.parallel.multihost import (
                ControlPlane,
            )

            pkt = np.zeros(self.HEADER + 7 * self.chunk, np.int32)
            if kind == "torn":
                pkt[0:6] = (0xBAD, PROTOCOL_VERSION, OP_DECODE, 0, 2, 0)
            elif kind == "skewed":
                pkt[0:6] = (PACKET_MAGIC, 99, OP_DECODE, 0, 2, 0)
            elif kind == "unknown":
                pkt[0:6] = (PACKET_MAGIC, PROTOCOL_VERSION, 777, 0, 2, 0)
            else:
                pkt[0:6] = (PACKET_MAGIC, PROTOCOL_VERSION, kind, 0, 2, 0)
            return pkt

        def recv(self):
            from distributed_llama_multiusers_tpu.parallel.multihost import (
                ControlPlane,
            )

            pkt = self._pkts.pop(0)
            ControlPlane.validate(pkt)
            return pkt

        def slot(self, pkt, i, n):
            start = self.HEADER + i * self.chunk
            return pkt[start : start + n]

    class _Eng:
        SPEC_DRAFT = 3
        stats = EngineStats()

        def __init__(self):
            self.calls = 0

        def decode(self, *a, want_logits=True, g_states=None):
            self.calls += 1

    script = [OP_DECODE, "torn", OP_DECODE, "skewed", OP_DECODE,
              "unknown", OP_DECODE, OP_STOP]
    engine = _Eng()
    # max_restarts=0: ANY non-classified error would raise immediately —
    # surviving the script proves protocol errors burn no restarts
    worker_serve(engine, _Plane(script), max_restarts=0, log=lambda m: None)
    assert engine.calls == 4  # every good packet replayed
    snap = engine.stats.snapshot()
    assert snap["worker_replay_errors"] == 3
    assert snap["worker_restarts"] == 0


def test_worker_serve_engine_errors_still_bounded():
    """Engine replay errors (post-validation) still burn the budget and
    raise when persistent — the desync signature must stay fatal."""
    from distributed_llama_multiusers_tpu.parallel.multihost import (
        OP_DECODE,
        worker_serve,
    )
    from distributed_llama_multiusers_tpu.runtime.engine import EngineStats

    class _Plane:
        HEADER = 6

        def __init__(self, n, chunk=8):
            from distributed_llama_multiusers_tpu.parallel.multihost import (
                PACKET_MAGIC,
                PROTOCOL_VERSION,
            )

            self.chunk = chunk
            pkt = np.zeros(self.HEADER + 7 * self.chunk, np.int32)
            pkt[0:6] = (PACKET_MAGIC, PROTOCOL_VERSION, OP_DECODE, 0, 2, 0)
            self._pkts = [pkt.copy() for _ in range(n)]

        def recv(self):
            return self._pkts.pop(0)

        def slot(self, pkt, i, n):
            start = self.HEADER + i * self.chunk
            return pkt[start : start + n]

    class _Eng:
        SPEC_DRAFT = 3
        stats = EngineStats()

        def __init__(self):
            self.calls = 0

        def decode(self, *a, want_logits=True, g_states=None):
            self.calls += 1
            raise RuntimeError(f"replay #{self.calls}")

    engine = _Eng()
    with pytest.raises(RuntimeError, match="replay"):
        worker_serve(engine, _Plane(20), max_restarts=2, log=lambda m: None)
    assert engine.calls == 3  # restarts 1..3 > max_restarts=2
    assert engine.stats.snapshot()["worker_restarts"] == 3


# ---------------------------------------------------------------------------
# HTTP defense-in-depth: bounded waits
# ---------------------------------------------------------------------------


def test_http_bounded_wait_maps_to_503():
    """A scheduler that never resolves a future cannot hang a client
    socket: the server's bounded wait turns it into a request_id-carrying
    503 with Retry-After."""
    from distributed_llama_multiusers_tpu.server import ApiServer

    class _WedgedScheduler:
        """Accepts submissions and never serves them."""

        draining = False

        def __init__(self):
            self.cancelled = []

        def submit(self, req):
            req.submitted_at = time.monotonic()
            return req

        def occupancy(self):
            return (0, 1)

        class _E:
            class _S:
                @staticmethod
                def snapshot():
                    import collections

                    return collections.defaultdict(int, {
                        "pipeline_depth_hist": {}, "fused_bucket_hist": {},
                    })

            stats = _S()

        engine = _E()

    from distributed_llama_multiusers_tpu.tokenizer import TemplateType

    sched = _WedgedScheduler()
    tok = StubStreamTokenizer(64)
    api = ApiServer(sched, tok, model_name="wedged", result_timeout_s=0.3,
                    template_type=TemplateType.LLAMA2)
    httpd = api.serve(host="127.0.0.1", port=0)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        body = json.dumps({
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4,
        }).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/chat/completions", data=body,
            headers={"Content-Type": "application/json"},
        )
        t0 = time.monotonic()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        waited = time.monotonic() - t0
        assert ei.value.code == 503
        assert waited < 10  # bounded, not the urllib timeout
        payload = json.loads(ei.value.read())
        assert payload["reason"] == "stalled"
        assert "request_id" in payload
        assert ei.value.headers.get("Retry-After") is not None
    finally:
        httpd.shutdown()


# ---------------------------------------------------------------------------
# containment cleanup: the truly-fatal path still resolves futures
# ---------------------------------------------------------------------------


def test_fatal_loop_exit_still_resolves_futures():
    """Even when containment itself fails (engine so broken that failing
    lanes raises again — simulated with an engine whose every surface
    raises), the finally-path cleanup resolves every future."""

    class _BrokenEngine(MockAsyncEngine):
        def decode(self, *a, **kw):
            raise RuntimeError("dead device")

        def decode_pipelined(self, *a, **kw):
            raise RuntimeError("dead device")

        def prefill_chunk(self, *a, **kw):
            raise RuntimeError("dead device")

        def pipeline_abort(self):
            raise RuntimeError("even abort is dead")

    engine = _BrokenEngine(n_lanes=2, max_chunk=4)
    sched = _sched(engine, breaker=CircuitBreaker(threshold=2,
                                                  cooldown_s=30.0))
    reqs = _reqs(3, max_tokens=4)
    sched.start()
    try:
        for r in reqs:
            try:
                sched.submit(r)
            except AdmissionRejected:
                r.future.set_exception(RuntimeError("shed"))
        for r in reqs:
            with pytest.raises(Exception):
                r.future.result(timeout=60)
    finally:
        sched.stop()
    # every future resolved; the loop thread exited via stop() cleanly
    assert all(r.future.done() for r in reqs)
    assert sched.breaker.state == "open"
