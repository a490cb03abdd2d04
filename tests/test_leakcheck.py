"""The runtime resource-leak witness (analysis/leakcheck.py,
``DLLAMA_LEAKCHECK=1``): resource lifecycles proven drained at runtime.

Layers, mirroring tests/test_jitcheck.py / test_lockcheck.py:

- **wiring** — counting-mode accumulation, strict-mode raising, the
  ``force(fresh=True)`` reset, the /stats surface shape;
- **the serving pin** — a REAL scheduler churn over the mock engine
  under the forced witness: submit, generate, stop — and the drain
  snapshot reads all-zero (``leak_counts()`` is the authoritative
  source, not a shadow counter);
- **the firing regression** — a deliberately leaked StreamRegistry
  entry (registered, never serviced, never discarded: the PR 10 shed
  class) makes ``close()`` RAISE under the witness and the counter
  record it;
- **the tier-1 fixture pattern** — a subprocess rerun of the serving +
  prefix-cache suites with ``DLLAMA_LEAKCHECK=1`` in the environment
  (the env path, not ``force()``), the test_lockcheck.py recipe.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

from distributed_llama_multiusers_tpu.analysis import leakcheck
from distributed_llama_multiusers_tpu.analysis.leakcheck import ResourceLeak
from distributed_llama_multiusers_tpu.runtime.scheduler import (
    ContinuousBatchingScheduler,
    Request,
)
from distributed_llama_multiusers_tpu.serving import StreamRegistry
from distributed_llama_multiusers_tpu.utils.testing import (
    MockAsyncEngine,
    StubStreamTokenizer,
)

REPO_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def witness_on():
    """Force strict mode (fresh counters) and restore the env-driven
    default afterwards."""
    leakcheck.force(True, fresh=True)
    try:
        yield
    finally:
        leakcheck.force(None, fresh=True)


@pytest.fixture
def witness_off():
    """Counting-only mode, fresh counters."""
    leakcheck.force(False, fresh=True)
    try:
        yield
    finally:
        leakcheck.force(None, fresh=True)


# -- wiring -------------------------------------------------------------------


def test_resource_leak_is_assertion_error():
    assert issubclass(ResourceLeak, AssertionError)


def test_counting_mode_counts_without_raising(witness_off):
    leaked = leakcheck.check_drained("t", {"kv_pages": 3, "marks": 0})
    assert leaked == 3
    assert leakcheck.leaks_total() == 3
    assert leakcheck.live_counts() == {"kv_pages": 3, "marks": 0}
    assert leakcheck.last_leak() == {
        "where": "t", "leaked": {"kv_pages": 3}
    }
    # a later clean drain updates the gauge but not the lifetime counter
    assert leakcheck.check_drained("t", {"kv_pages": 0}) == 0
    assert leakcheck.leaks_total() == 3
    assert leakcheck.live_counts()["kv_pages"] == 0


def test_strict_mode_raises_and_counts(witness_on):
    with pytest.raises(ResourceLeak, match="kv_pages"):
        leakcheck.check_drained("stop", {"kv_pages": 2})
    assert leakcheck.leaks_total() == 2


def test_clean_drain_never_raises(witness_on):
    assert leakcheck.check_drained("stop", {"kv_pages": 0}) == 0
    assert leakcheck.leaks_total() == 0


def test_force_fresh_resets_counters(witness_off):
    leakcheck.check_drained("t", {"x": 5})
    leakcheck.force(False, fresh=True)
    assert leakcheck.leaks_total() == 0
    assert leakcheck.live_counts() == {}
    assert leakcheck.last_leak() is None


def test_stats_surface_shape(witness_off):
    leakcheck.check_drained("t", {"x": 1})
    s = leakcheck.stats()
    assert s["resource_leaks_total"] == 1
    assert s["resource_drain_checks"] == 1
    assert s["resources_live"] == {"x": 1}


# -- the serving pin: a real churn drains clean ------------------------------


@pytest.mark.parametrize("fault", [None, "engine.dispatch:@6:n=1"])
def test_scheduler_stop_drains_clean(witness_on, fault):
    """``fault``: one injected dispatch fault mid-churn. Containment
    fails the lanes it held and must release every mirror, page and
    pending op they held; every future still resolves (no client hangs)
    and the loop serves on."""
    from distributed_llama_multiusers_tpu.utils import faults

    engine = MockAsyncEngine(n_lanes=2)
    sched = ContinuousBatchingScheduler(
        engine, StubStreamTokenizer(engine.config.vocab_size),
        speculative=False, prefix_min_tokens=0,
    )
    reqs = [
        Request(prompt=f"drain pin {i}", max_tokens=8, temperature=0.0)
        for i in range(4)
    ]
    if fault:
        faults.arm(fault)
    sched.start()
    try:
        for r in reqs:
            sched.submit(r)
        for r in reqs:
            try:
                r.future.result(timeout=60)
            except Exception:  # noqa: BLE001 - the contained lanes' failure
                assert fault and r.finish_reason == "error", r.error
    finally:
        faults.disarm()
        sched.stop()  # raises ResourceLeak if anything is still held
    assert all(r.future.done() for r in reqs)
    failed = [r for r in reqs if r.finish_reason == "error"]
    if fault:
        assert 1 <= len(failed) <= 2  # the lanes held at the fault, no more
        assert sched.qos_stats()["engine_failure_rounds"] == 1
        assert engine.pipeline_inflight() == 0
    else:
        assert not failed
    assert all(r.error is None for r in reqs if r not in failed)
    assert all(v == 0 for v in sched.leak_counts().values())
    assert leakcheck.leaks_total() == 0


def test_scheduler_stop_mid_flight_drains_clean(witness_on):
    """The crash-sim shape every recovery test uses: stop with lanes
    mid-decode — _resolve_exit must settle every mirror record."""
    engine = MockAsyncEngine(n_lanes=2, step_s=0.01)
    sched = ContinuousBatchingScheduler(
        engine, StubStreamTokenizer(engine.config.vocab_size),
        speculative=False, prefix_min_tokens=0,
    )
    reqs = [
        Request(prompt=f"mid-flight {i}", max_tokens=500, temperature=0.0)
        for i in range(2)
    ]
    sched.start()
    for r in reqs:
        sched.submit(r)
    while not any(r.generated_tokens for r in reqs):
        pass
    sched.stop()  # force-cancels the lanes; must still drain clean
    assert all(v == 0 for v in sched.leak_counts().values())


# -- the firing regression: a leaked registry entry is caught ----------------


def test_leaked_registry_entry_fires(witness_on):
    """Register a request that never enters service and never gets
    discarded — the orphan class nothing can reap. close() must raise."""
    registry = StreamRegistry(grace_s=60.0)
    leaked = Request(prompt="never serviced", max_tokens=4)
    registry.register(leaked)
    with pytest.raises(ResourceLeak, match="stream_entries"):
        registry.close()
    assert leakcheck.leaks_total() == 1
    assert leakcheck.last_leak()["where"] == "stream registry close"


def test_leaked_registry_entry_counted_without_witness(witness_off):
    registry = StreamRegistry(grace_s=60.0)
    registry.register(Request(prompt="never serviced", max_tokens=4))
    registry.close()  # counting mode: no raise
    assert leakcheck.leaks_total() == 1


def test_discarded_entry_is_clean(witness_on):
    """The fix for the orphan class: discard() releases the entry."""
    registry = StreamRegistry(grace_s=60.0)
    req = Request(prompt="shed at submit", max_tokens=4)
    registry.register(req)
    registry.discard(req.id)
    registry.close()
    assert leakcheck.leaks_total() == 0


def test_resolved_entry_is_clean(witness_on):
    """A finished stream's entry is retention, not a leak — the reaper
    owns its grace expiry."""
    registry = StreamRegistry(grace_s=60.0)
    req = Request(prompt="served", max_tokens=4)
    registry.register(req)
    req.future.set_result("done")
    registry.close()
    assert leakcheck.leaks_total() == 0


# -- the env path, end to end ------------------------------------------------


@pytest.mark.slow
def test_serving_suites_leak_free_under_env_flag():
    """Rerun the scheduler-serving and prefix-cache suites in a
    subprocess with DLLAMA_LEAKCHECK=1: every stop()/close() they
    perform becomes a raising drain point. Green = the whole serving
    lifecycle holds nothing at any drain."""
    env = dict(os.environ)
    env["DLLAMA_LEAKCHECK"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "pytest",
         "tests/test_scheduler_serving.py", "tests/test_prefix_cache.py",
         "-q", "-p", "no:cacheprovider"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, (
        f"serving suites leaked under DLLAMA_LEAKCHECK=1:\n"
        f"{proc.stdout[-4000:]}\n{proc.stderr[-2000:]}"
    )
