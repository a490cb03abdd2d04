"""The runtime recompile witness (analysis/jitcheck.py,
``DLLAMA_JITCHECK=1``): compile stability proven at runtime.

Layers, mirroring tests/test_lockcheck.py:

- **wiring** — arming, pausing (``warming()``), strict-mode raising,
  the always-on counter, weak sink registration;
- **the serving pin** — a REAL engine + scheduler churn under the
  forced witness: warmup arms it, mixed greedy/sampled requests with a
  shared prefix (the copy_lane path this PR added to warmup) generate
  end to end, and ``jit_compiles_after_warmup`` reads 0 — the
  machine-checked form of "one compiled program per (family, bucket),
  compiled only at warmup";
- **the firing regression** — a deliberately unwarmed family
  (``decode_multi`` horizons with ``multi_step=0`` warmup) makes the
  witness RAISE at the guilty dispatch and the counter record it;
- **the tier-1 fixture pattern** — a subprocess rerun of the serving
  pin with ``DLLAMA_JITCHECK=1`` in the environment (the env path, not
  ``force()``), the test_lockcheck.py recipe.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
import jax.numpy as jnp

from distributed_llama_multiusers_tpu.analysis import jitcheck
from distributed_llama_multiusers_tpu.analysis.jitcheck import (
    RecompileAfterWarmup,
)
from distributed_llama_multiusers_tpu.formats import load_model_header
from distributed_llama_multiusers_tpu.models import load_params_from_m
from distributed_llama_multiusers_tpu.runtime import (
    ContinuousBatchingScheduler,
    InferenceEngine,
    Request,
)
from distributed_llama_multiusers_tpu.runtime.engine import warmup_engine
from distributed_llama_multiusers_tpu.tokenizer import Tokenizer

REPO_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def witness_on():
    """Force strict mode (fresh sink registry) and restore the
    env-driven default afterwards."""
    jitcheck.force(True, fresh=True)
    try:
        yield
    finally:
        jitcheck.force(None, fresh=True)


@pytest.fixture
def counter_only():
    """Counter armed, strict raising OFF — the production default once
    warmup has run."""
    jitcheck.force(False, fresh=True)
    try:
        yield
    finally:
        jitcheck.force(None, fresh=True)


class _Stats:
    """Minimal EngineStats stand-in for unit tests."""

    def __init__(self):
        import threading

        self.lock = threading.Lock()
        self.jit_compiles_after_warmup = 0


# -- wiring -------------------------------------------------------------------


def test_disabled_by_default():
    assert not jitcheck.enabled()


def test_env_flag_enables(monkeypatch):
    jitcheck.force(None, fresh=False)
    monkeypatch.setenv(jitcheck.ENV_FLAG, "1")
    assert jitcheck.enabled()
    monkeypatch.setenv(jitcheck.ENV_FLAG, "0")
    assert not jitcheck.enabled()


def test_counter_bumps_without_strict(counter_only):
    import jax

    x = jnp.zeros(5)  # the operand's own fill compiles BEFORE arming
    stats = _Stats()
    jitcheck.arm(stats)
    f = jax.jit(lambda x: x * 2)
    f(x)  # compiles: armed, not strict -> counted, no raise
    assert stats.jit_compiles_after_warmup == 1
    f(x)  # executable-cache hit: no event, no bump
    assert stats.jit_compiles_after_warmup == 1


def test_warming_pause_suppresses_counting(counter_only):
    import jax

    x = jnp.zeros(6)
    stats = _Stats()
    jitcheck.arm(stats)
    f = jax.jit(lambda x: x * 3)
    with jitcheck.warming():
        f(x)  # a fresh compile, but paused
    assert stats.jit_compiles_after_warmup == 0


def test_strict_raises_at_the_guilty_call(witness_on):
    import jax

    x = jnp.zeros(7)
    stats = _Stats()
    jitcheck.arm(stats)
    f = jax.jit(lambda x: x * 5)
    with pytest.raises(RecompileAfterWarmup):
        f(x)
    assert stats.jit_compiles_after_warmup >= 1


def test_arm_is_idempotent_and_sinks_are_weak(counter_only):
    import jax

    x = jnp.zeros(9)
    stats = _Stats()
    jitcheck.arm(stats)
    jitcheck.arm(stats)  # no duplicate bumps
    f = jax.jit(lambda x: x * 7)
    f(x)
    assert stats.jit_compiles_after_warmup == 1
    assert jitcheck.armed()


# -- the serving pin ----------------------------------------------------------


def _stack(tiny_model, n_lanes=2, tp=None):
    h = load_model_header(tiny_model["model"])
    config, params = load_params_from_m(
        tiny_model["model"], h, dtype=jnp.float32
    )
    tok = Tokenizer(tiny_model["tokenizer"])
    mesh = None
    if tp is not None:
        from distributed_llama_multiusers_tpu.parallel import (
            MeshPlan,
            make_mesh,
        )
        from distributed_llama_multiusers_tpu.parallel.sharding import (
            shard_params,
        )

        mesh = make_mesh(MeshPlan(tp=tp))
        params = shard_params(params, mesh)
    engine = InferenceEngine(
        config, params, n_lanes=n_lanes, prefill_buckets=(8, 16), mesh=mesh
    )
    return engine, tok


def _churn(engine, tok, n=4, max_tokens=6, speculative=True):
    sched = ContinuousBatchingScheduler(engine, tok, speculative=speculative)
    warmup_engine(engine, spec=speculative, multi_step=sched.multi_step)
    sched.start()
    try:
        # mixed traffic over a SHARED prompt: greedy + device-sampled
        # lanes, prefix reuse (the copy_lane program this PR added to
        # warmup), fused admissions into the live chain
        reqs = [
            Request(
                prompt="hello world shared prefix",
                max_tokens=max_tokens,
                temperature=0.0 if i % 2 == 0 else 0.8,
                seed=11 + i,
            )
            for i in range(n)
        ]
        for r in reqs:
            sched.submit(r)
        for r in reqs:
            r.future.result(timeout=300)
    finally:
        sched.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    # and after stop() nothing is still held: session mirrors, pending
    # device ops, open journal marks, lane-held KV pages
    assert all(v == 0 for v in sched.leak_counts().values()), (
        sched.leak_counts()
    )
    return sched


@pytest.mark.parametrize("plane", ["one_device", "tp2_mesh"])
def test_serving_churn_is_compile_stable_under_witness(
    tiny_model, witness_on, plane
):
    """THE pin: a real serving churn after warmup compiles NOTHING —
    strict mode would have raised at the guilty dispatch, and the
    counter /stats and the benchmark report reads 0. ``tp2_mesh`` is the
    pod plane's form of it (speculation off, as a pod serves): a
    recompile on a mesh stalls every chip of it, and the sharded step
    families, the replicated token carry and the cache shardings must
    all come out of warmup with the avals the churn dispatches. Runs
    under the default dequant mode, as every cell does."""
    if plane == "tp2_mesh":
        engine, tok = _stack(tiny_model, tp=2)
        _churn(engine, tok, speculative=False)
    else:
        engine, tok = _stack(tiny_model)
        _churn(engine, tok)
    assert engine.stats.snapshot()["jit_compiles_after_warmup"] == 0


@pytest.fixture(scope="module")
def nospec_engine(tiny_model):
    """ONE engine warmed WITHOUT multi-step horizons (multi_step=0),
    shared by the two unwarmed-family tests below: warmup is the
    expensive part (~10s of CPU compiles), and each test dispatches a
    DIFFERENT horizon, so each still pays — and witnesses — its own
    fresh compile. Tests re-arm after their force(fresh=True) fixture
    clears the sink registry."""
    engine, tok = _stack(tiny_model)
    warmup_engine(engine, spec=False, multi_step=0)
    return engine


def test_witness_fires_on_deliberately_unwarmed_family(
    nospec_engine, witness_on
):
    """The regression the satellite asks for: a family warmup skipped
    (multi-step horizons with multi_step=0) RAISES at its first
    dispatch and the counter records the compile."""
    engine = nospec_engine
    jitcheck.arm(engine.stats)
    z = np.zeros(engine.n_lanes, np.int32)
    with pytest.raises(RecompileAfterWarmup):
        engine.decode_multi(z, z, h=2)
    assert engine.stats.snapshot()["jit_compiles_after_warmup"] >= 1


def test_counter_survives_stats_reset(nospec_engine, counter_only):
    """jit_compiles_after_warmup describes compile stability since
    warmup, not a stats window: reset() must not clear it (a window
    reset hiding a mid-serving recompile would defeat the witness)."""
    engine = nospec_engine
    jitcheck.arm(engine.stats)
    before = engine.stats.snapshot()["jit_compiles_after_warmup"]
    z = np.zeros(engine.n_lanes, np.int32)
    engine.decode_multi(z, z, h=3)  # unwarmed horizon: counts, no raise
    assert engine.stats.snapshot()["jit_compiles_after_warmup"] > before
    engine.stats.reset()
    assert engine.stats.snapshot()["jit_compiles_after_warmup"] > before


# -- the tier-1 fixture pattern (subprocess, env-armed) -----------------------


@pytest.mark.slow  # tier-2: a fresh jax process + full warmup; the
# in-process serving pin above keeps this class covered in tier-1
def test_serving_suite_clean_under_env_jitcheck():
    """Rerun the serving pin in a subprocess with DLLAMA_JITCHECK=1 in
    the ENVIRONMENT (the deployment spelling, exercising the env-flag
    path end to end) — the test_lockcheck.py tier-1 fixture pattern."""
    env = dict(os.environ)
    env["DLLAMA_JITCHECK"] = "1"
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [
            sys.executable, "-m", "pytest",
            "tests/test_jitcheck.py",
            "-k", "serving_churn_is_compile_stable",
            "-q", "-p", "no:cacheprovider",
        ],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, (
        f"serving churn recompiled under DLLAMA_JITCHECK=1:\n"
        f"{proc.stdout[-4000:]}\n{proc.stderr[-2000:]}"
    )
