"""dlint (distributed_llama_multiusers_tpu/analysis): the analyzer itself
AND its verdict on the real tree.

Two layers, per the PR-2 contract:

- **self-tests** — every checker gets known-bad and known-good fixture
  snippets (including waiver syntax), so the analyzer is regression-tested
  as a program, not just trusted on its current verdict;
- **the tier-1 gate** — the full package must analyze clean (zero
  non-baselined findings). A new unlocked counter bump, un-waived
  host-sync in the decode path, wall-clock read, busy-poll, or undeclared
  sharding axis anywhere in the package fails this test.

Pure-stdlib imports: these tests run without jax.
"""

from __future__ import annotations

import textwrap
from pathlib import Path

import pytest

from distributed_llama_multiusers_tpu.analysis import (
    DEFAULT_BASELINE,
    PACKAGE_ROOT,
    Analyzer,
    analyze_paths,
    default_checkers,
    load_baseline,
)
from distributed_llama_multiusers_tpu.analysis.cli import main as dlint_main


def run_on(tmp_path: Path, files: dict[str, str], baseline: set | None = None):
    """Write fixture files under tmp_path and analyze them (no baseline
    unless given). Returns the finding list."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src), encoding="utf-8")
    analyzer = Analyzer(default_checkers())
    return analyzer.run([tmp_path], baseline=baseline or set(), root=tmp_path)


def checks_of(findings):
    return sorted(f.check for f in findings)


# -- the tier-1 gate ---------------------------------------------------------


def test_package_analyzes_clean():
    """THE gate: zero non-baselined findings over the real package. If this
    fails, either fix the finding, waive it in place with a reason, or (last
    resort) baseline it — see docs/LINT.md."""
    findings = analyze_paths()
    assert findings == [], "dlint findings on the tree:\n" + "\n".join(
        f.render() for f in findings
    )


def test_cli_runs_clean_with_shipped_baseline(capsys):
    assert dlint_main([]) == 0
    assert "clean" in capsys.readouterr().out


def test_shipped_baseline_is_empty():
    """Adoption fixed or waived everything; keep it that way."""
    assert load_baseline(DEFAULT_BASELINE) == set()


def test_real_decl_sites_are_collected():
    """The EngineStats/QosQueue declarations actually reach the checker
    (guards against the declaration syntax silently rotting)."""
    from distributed_llama_multiusers_tpu.analysis.core import Project
    from distributed_llama_multiusers_tpu.analysis.lock_check import GuardedByChecker
    import ast

    project = Project()
    checker = GuardedByChecker()
    for rel in ("runtime/engine.py", "serving/qos.py"):
        p = PACKAGE_ROOT / rel
        from distributed_llama_multiusers_tpu.analysis.core import SourceFile

        sf = SourceFile(
            path=p, display=rel, text=p.read_text(), tree=ast.parse(p.read_text())
        )
        checker.collect(sf, project)
    assert "decode_steps" in project.guarded
    assert "prefix_hits" in project.guarded
    assert "_deficit" in project.guarded
    assert project.guarded["_depth"][0] == frozenset({"_lock", "_not_empty"})


# -- guarded-by --------------------------------------------------------------

GUARDED_CLS = """
    import threading

    class Stats:
        _dlint_guarded_by = {("lock",): ("hits", "misses")}

        def __init__(self):
            self.lock = threading.Lock()
            self.hits = 0
            self.misses = 0
"""


def test_guarded_by_flags_unlocked_access(tmp_path):
    findings = run_on(tmp_path, {"m.py": GUARDED_CLS + """
        def bump(s):
            s.hits += 1
    """})
    assert checks_of(findings) == ["guarded-by"]
    assert "'s.hits'" in findings[0].message


def test_guarded_by_engine_stats_shape(tmp_path):
    """Acceptance-criterion demo: a guarded EngineStats-style counter
    accessed outside stats.lock is a finding, even through a chain base
    (self.engine.stats) and even when SOME lock is held — it must be the
    declared lock on the SAME base."""
    src = GUARDED_CLS + """
        class Scheduler:
            def __init__(self, engine):
                self.engine = engine

            def good(self):
                with self.engine.stats.lock:
                    self.engine.stats.hits += 1

            def bad_unlocked(self):
                self.engine.stats.hits += 1

            def bad_wrong_base(self, other):
                with other.stats.lock:
                    self.engine.stats.hits += 1
    """
    findings = run_on(tmp_path, {"m.py": src})
    assert checks_of(findings) == ["guarded-by", "guarded-by"]
    lines = {f.line for f in findings}
    assert len(lines) == 2


def test_guarded_by_accepts_lock_locked_and_init(tmp_path):
    findings = run_on(tmp_path, {"m.py": GUARDED_CLS + """
        class User:
            def ok_with(self, s):
                with s.lock:
                    s.hits += 1

            def _bump_locked(self, s):
                s.misses += 1  # caller holds s.lock by contract
    """})
    assert findings == []


def test_guarded_by_alternate_locks_and_waiver(tmp_path):
    findings = run_on(tmp_path, {"m.py": """
        import threading

        class Q:
            _dlint_guarded_by = {("_lock", "_cv"): ("_depth",)}

            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)
                self._depth = 0

            def push(self):
                with self._cv:
                    self._depth += 1

            def empty(self):
                # dlint: ok[guarded-by] advisory racy read by contract
                return self._depth == 0
    """})
    assert findings == []


def test_guarded_by_closure_in_with_block_is_not_protected(tmp_path):
    """A closure defined inside `with lock:` runs after the lock is
    released — the enclosing with must not count across the def/lambda
    boundary."""
    findings = run_on(tmp_path, {"m.py": GUARDED_CLS + """
        def make_cb(s):
            with s.lock:
                cb = lambda: s.hits + 1
                def cb2():
                    return s.misses
            return cb, cb2
    """})
    assert checks_of(findings) == ["guarded-by", "guarded-by"]


def test_guarded_by_malformed_declaration(tmp_path):
    findings = run_on(tmp_path, {"m.py": """
        class Bad:
            _dlint_guarded_by = {("lock",): 42}
    """})
    assert checks_of(findings) == ["guarded-by"]
    assert "malformed" in findings[0].message


# -- host-sync ---------------------------------------------------------------


def test_host_sync_flags_unwaived_asarray_in_decode_path(tmp_path):
    """Acceptance-criterion demo: a new un-waived host sync in the decode
    path is a finding."""
    src = """
        import numpy as np

        def decode(logits):
            return np.asarray(logits)
    """
    findings = run_on(tmp_path, {"runtime/engine.py": src})
    assert checks_of(findings) == ["host-sync"]
    # the same code OUTSIDE the decode-path scope is not flagged
    assert run_on(tmp_path / "other", {"models/llama.py": src}) == []


def test_host_sync_waiver_suppresses(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": """
        import numpy as np

        def decode(logits):
            # dlint: ok[host-sync] the one packed readback per step
            return np.asarray(logits)
    """})
    assert findings == []


def test_host_sync_flags_item_and_cast(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": """
        def f(x, toks_np):
            a = x.item()
            b = int(x)
            c = int(toks_np[0])  # *_np host-array convention: exempt
            return a, b, c
    """})
    assert checks_of(findings) == ["host-sync", "host-sync"]


def test_host_sync_cast_rule_is_engine_only(tmp_path):
    findings = run_on(tmp_path, {"runtime/scheduler.py": """
        def f(greedy):
            return int(greedy[0])  # host numpy from the engine: fine here
    """})
    assert findings == []


def test_host_sync_implicit_bool_on_compiled_step_output(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": """
        class E:
            def step(self, x):
                logits, toks = self._decode_fn(x)
                if logits:
                    return toks
                return None
    """})
    assert checks_of(findings) == ["host-sync"]
    assert "implicit bool" in findings[0].message


def test_host_sync_covers_telemetry_package(tmp_path):
    """PR-5 satellite: the telemetry package is registered under host-sync
    — a device->host transfer construct added to a telemetry hot path
    (the scheduler calls these hooks from inside the serving loop) is a
    finding there exactly like in runtime/."""
    bad = """
        import numpy as np

        def on_token(tokens):
            return np.asarray(tokens)
    """
    findings = run_on(tmp_path, {"telemetry/spans.py": bad})
    assert checks_of(findings) == ["host-sync"]
    # metrics.py is scoped too; .item() is the other transfer spelling
    findings = run_on(tmp_path / "b", {"telemetry/metrics.py": """
        def observe(h, v):
            h.observe(v.item())
    """})
    assert checks_of(findings) == ["host-sync"]
    # the clean shape: host floats in, host floats out — no findings
    clean = run_on(tmp_path / "c", {"telemetry/hub.py": """
        import time

        def on_step(tracer, t0):
            tracer.slice("step.sync", "pipeline", t0, time.perf_counter())
    """})
    assert clean == []


def test_clock_covers_telemetry_files(tmp_path):
    """clock is package-wide, telemetry included: a wall-clock duration in
    a telemetry file is a finding; the one sanctioned absolute-timestamp
    site (the JSON log envelope) carries a waiver in the real tree."""
    findings = run_on(tmp_path, {"telemetry/logs.py": """
        import time

        def stamp():
            return time.time()
    """})
    assert checks_of(findings) == ["clock"]


def test_real_telemetry_guard_decls_are_collected():
    """The SpanTracer/metrics declarations reach the guarded-by checker
    (same rot-guard as the EngineStats/QosQueue assertion above)."""
    import ast

    from distributed_llama_multiusers_tpu.analysis.core import Project, SourceFile
    from distributed_llama_multiusers_tpu.analysis.lock_check import GuardedByChecker

    project = Project()
    checker = GuardedByChecker()
    for rel in ("telemetry/spans.py", "telemetry/metrics.py"):
        p = PACKAGE_ROOT / rel
        sf = SourceFile(
            path=p, display=rel, text=p.read_text(), tree=ast.parse(p.read_text())
        )
        checker.collect(sf, project)
    assert "_trace_ring" in project.guarded
    assert "_hist_counts" in project.guarded
    assert "_reg_metrics" in project.guarded
    assert project.guarded["_trace_dropped"][0] == frozenset({"_trace_lock"})


def test_guarded_by_flags_unlocked_telemetry_ring_access(tmp_path):
    """A new unlocked touch of the tracer ring state is a finding — the
    telemetry satellite's known-bad fixture."""
    findings = run_on(tmp_path, {"telemetry/spans.py": """
        import threading

        class SpanTracer:
            _dlint_guarded_by = {("_trace_lock",): ("_trace_ring",)}

            def __init__(self):
                self._trace_lock = threading.Lock()
                self._trace_ring = []

            def bad_append(self, ev):
                self._trace_ring.append(ev)

            def good_append(self, ev):
                with self._trace_lock:
                    self._trace_ring.append(ev)
    """})
    assert checks_of(findings) == ["guarded-by"]
    assert "_trace_ring" in findings[0].message


# -- paged KV pool (runtime/kvpool.py) ---------------------------------------


def test_host_sync_covers_kvpool_file(tmp_path):
    """PR-11 satellite: runtime/kvpool.py is registered under host-sync —
    the pool bookkeeping runs inside the admission path
    (scheduler._start_request -> engine.paged_admit) and is host
    dicts/lists by contract; a device->host transfer construct added
    there is a finding exactly like in runtime/."""
    findings = run_on(tmp_path, {"runtime/kvpool.py": """
        import numpy as np

        class KVPagePool:
            def admit(self, tokens):
                return np.asarray(tokens)
    """})
    assert checks_of(findings) == ["host-sync"]
    # the clean shape: pure host bookkeeping — block the prompt into
    # content tuples, walk the tree dict, no transfer spelling anywhere
    clean = run_on(tmp_path / "b", {"runtime/kvpool.py": """
        class KVPagePool:
            def blocks(self, tokens, bs):
                return [
                    tuple(tokens[i : i + bs])
                    for i in range(0, len(tokens), bs)
                ]
    """})
    assert clean == []


def test_real_kvpool_guard_decls_are_collected():
    """KVPagePool's free-list/refcount/prefix-tree declaration reaches
    the guarded-by checker (the rot-guard pattern: the declaration
    syntax must not silently rot out of collection)."""
    import ast

    from distributed_llama_multiusers_tpu.analysis.core import Project, SourceFile
    from distributed_llama_multiusers_tpu.analysis.lock_check import GuardedByChecker

    project = Project()
    checker = GuardedByChecker()
    p = PACKAGE_ROOT / "runtime/kvpool.py"
    sf = SourceFile(
        path=p, display="runtime/kvpool.py", text=p.read_text(),
        tree=ast.parse(p.read_text()),
    )
    checker.collect(sf, project)
    assert "_free" in project.guarded
    assert "_nodes" in project.guarded
    assert "_parked" in project.guarded
    assert "cow_copies" in project.guarded
    assert project.guarded["_free"][0] == frozenset({"_lock"})
    # the swap tier's own declaration (HostTier._lock over the LRU store
    # and its counters) must keep reaching the checker too
    assert "_swapped" in project.guarded
    assert "_pending_swapouts" in project.guarded
    assert project.guarded["_swapped"][0] == frozenset({"_lock"})


def test_guarded_by_flags_unlocked_kvpool_free_list(tmp_path):
    """Known-bad: a pool free-list pop outside the lock (stats() races
    the scheduler thread through exactly this state) is a finding;
    the locked and *_locked-helper shapes stay clean."""
    findings = run_on(tmp_path, {"runtime/kvpool.py": """
        import threading

        class KVPagePool:
            _dlint_guarded_by = {("_lock",): ("_free", "_ref")}

            def __init__(self):
                self._lock = threading.Lock()
                self._free = [0, 1, 2]
                self._ref = [0, 0, 0]

            def bad_alloc(self):
                return self._free.pop()

            def good_alloc(self):
                with self._lock:
                    page = self._free.pop()
                    self._ref[page] = 1
                    return page

            def _deref_locked(self, page):
                self._ref[page] -= 1
    """})
    assert checks_of(findings) == ["guarded-by"]
    assert "_free" in findings[0].message


# -- pipeline-sync -----------------------------------------------------------


def test_pipeline_sync_flags_sync_in_dispatch_half(tmp_path):
    """Acceptance-criterion demo: a host-sync construct inside the
    pipelined dispatch half is a finding (on top of the file-wide host-sync
    rule) — the dispatch half must enqueue device work from host metadata
    only, or the async chain silently re-serializes."""
    findings = run_on(tmp_path, {"runtime/scheduler.py": """
        import numpy as np

        class Sched:
            def _pipeline_dispatch(self, live, pl_pos, feed):
                arr = np.asarray(feed)
                self.engine.decode_pipelined(arr)
    """})
    assert "pipeline-sync" in checks_of(findings)
    # the same sync OUTSIDE the dispatch half is host-sync's business only
    other = run_on(tmp_path / "other", {"runtime/scheduler.py": """
        import numpy as np

        class Sched:
            def _pipeline_consume(self, live):
                # dlint: ok[host-sync] the lagged per-step readback
                return np.asarray(self.engine.pipeline_consume())
    """})
    assert "pipeline-sync" not in checks_of(other)


def test_pipeline_sync_clean_dispatch_half(tmp_path):
    """Building host metadata arrays and dispatching is exactly what the
    dispatch half is for — no findings."""
    findings = run_on(tmp_path, {"runtime/scheduler.py": """
        import numpy as np

        class Sched:
            def _pipeline_dispatch(self, live, pl_pos, feed):
                positions = np.full(4, 128, np.int32)
                for i, lane in live.items():
                    positions[i] = pl_pos[i]
                self.engine.decode_pipelined(positions, tokens=feed)
    """})
    assert findings == []


def test_a_poll_of_readiness_is_no_sync(tmp_path):
    """``jax.Array.is_ready()`` neither blocks nor transfers: the engine's
    ``pipeline_ready`` and the loop that asks it carry no waiver, under
    host-sync and pipeline-sync alike; ``block_until_ready`` in the same
    places is a finding. The poll is DECLARED (``POLL_METHODS``), so that it
    is not added to the sync list by analogy."""
    import distributed_llama_multiusers_tpu.analysis.host_sync_check as hs
    import distributed_llama_multiusers_tpu.analysis.pipeline_check as pc

    assert hs.POLL_METHODS == {"is_ready"}
    assert not hs.POLL_METHODS & (hs.SYNC_METHODS | pc.SYNC_METHODS)
    src = """
        class Engine:
            def pipeline_ready(self):
                return len(self._pl_inflight) > 0 and self._pl_inflight[-1][1].POLL()

            def decode_pipelined(self, positions):
                idle = self._pl_inflight[-1][1].POLL()
                self._pl_inflight.append(self._decode_pl_fn(positions))
    """
    assert run_on(tmp_path / "poll", {"runtime/engine.py": src.replace("POLL", "is_ready")}) == []
    blocking = run_on(tmp_path / "block",
                      {"runtime/engine.py": src.replace("POLL", "block_until_ready")})
    assert sorted(checks_of(blocking)) == ["host-sync", "host-sync", "pipeline-sync"]


def test_pipeline_sync_implicit_bool_and_cast(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": """
        class E:
            def decode_pipelined(self, positions, tokens=None):
                nxt, packed, self.cache = self._decode_pl_fn(positions)
                if nxt:
                    return int(packed)
                return None
    """})
    pipeline = [f for f in findings if f.check == "pipeline-sync"]
    msgs = " ".join(f.message for f in pipeline)
    assert "implicit bool" in msgs and "cast" in msgs


def test_pipeline_sync_covers_fused_dispatch(tmp_path):
    """The fused prefill+decode admission step is a dispatch half too: a
    host-sync construct inside ``engine.decode_prefill_fused`` (or the
    fused branch of ``_pipeline_dispatch``) re-serializes the chain at the
    exact moment it is supposed to hide admission work — a finding."""
    findings = run_on(tmp_path, {"runtime/engine.py": """
        import numpy as np

        class E:
            def decode_prefill_fused(self, positions, chunk=None, tokens=None):
                nxt, packed, self.cache = self._decode_prefill_fn(positions)
                return np.asarray(packed)
    """})
    assert "pipeline-sync" in checks_of(findings)
    # the clean shape: host chunk data goes IN, nothing comes back
    clean = run_on(tmp_path / "clean", {"runtime/engine.py": """
        import numpy as np

        class E:
            def decode_prefill_fused(self, positions, chunk=None, tokens=None):
                padded = np.zeros(16, np.int32)
                padded[: len(chunk)] = chunk
                nxt, packed, self.cache = self._decode_prefill_fn(
                    positions, padded
                )
                self._pl_carry = nxt
                self._pl_inflight.append(packed)
    """})
    assert "pipeline-sync" not in checks_of(clean)


def test_pipeline_sync_covers_spec_pipelined_dispatch(tmp_path):
    """Zero-flush serving: the in-chain spec verify steps
    (``decode_spec_pipelined`` / ``decode_spec_prefill_fused``) are
    dispatch halves too — a host-sync construct inside them (reading the
    accept counts eagerly is the tempting bug) re-serializes the chain
    exactly when speculation was supposed to multiply with it."""
    findings = run_on(tmp_path, {"runtime/engine.py": """
        import numpy as np

        class E:
            def decode_spec_pipelined(self, positions, drafts, draft_len,
                                      tokens=None):
                nxt, packed, self.cache = self._decode_spec_pl_fn(
                    positions, drafts
                )
                return np.asarray(packed)

            def decode_spec_prefill_fused(self, positions, drafts,
                                          draft_len, chunk=None,
                                          tokens=None):
                nxt, packed, self.cache = self._decode_spec_prefill_fn(
                    positions, drafts
                )
                return int(packed)
    """})
    checks = [f.check for f in findings if f.check == "pipeline-sync"]
    assert len(checks) == 2  # one per spec dispatch half
    # the clean shape: host draft candidates go IN, the packed verify
    # readback stays on device in the ring
    clean = run_on(tmp_path / "clean", {"runtime/engine.py": """
        import numpy as np

        class E:
            def decode_spec_pipelined(self, positions, drafts, draft_len,
                                      tokens=None):
                nxt, new_pos, packed, self.cache = self._decode_spec_pl_fn(
                    positions, drafts, draft_len
                )
                self._pl_carry = nxt
                self._pl_carry_pos = new_pos
                self._pl_inflight.append(("spec", packed))
    """})
    assert "pipeline-sync" not in checks_of(clean)


def test_pipeline_sync_draft_probe_branch_legal(tmp_path):
    """The draft-probing branch of ``_pipeline_dispatch`` is a pure
    host-side n-gram lookup — building candidate arrays from the lane's
    committed history is legal; syncing a device value to 'improve' the
    probe is a finding."""
    clean = run_on(tmp_path, {"runtime/scheduler.py": """
        import numpy as np

        class Sched:
            def _pipeline_dispatch(self, live, admitting, feed, spec_ok):
                positions = np.full(4, 128, np.int32)
                drafts = None
                draft_len = None
                for i, lane in live.items():
                    positions[i] = -1
                    d = lane.drafter.draft(lane.next_token, 4)
                    if len(d) >= 2:
                        if drafts is None:
                            drafts = np.zeros((4, 4), np.int32)
                            draft_len = np.zeros(4, np.int32)
                        drafts[i, : len(d)] = d
                        draft_len[i] = len(d)
                if drafts is None:
                    self.engine.decode_pipelined(positions, tokens=feed)
                else:
                    self.engine.decode_spec_pipelined(
                        positions, drafts, draft_len, tokens=feed
                    )
    """})
    assert "pipeline-sync" not in checks_of(clean)
    # probing off a DEVICE value instead of host history: a finding
    bad = run_on(tmp_path / "bad", {"runtime/scheduler.py": """
        import numpy as np

        class Sched:
            def _pipeline_dispatch(self, live, admitting, feed, spec_ok):
                carry = np.asarray(self.engine._pl_carry)
                self.engine.decode_spec_pipelined(carry)
    """})
    assert "pipeline-sync" in checks_of(bad)


def test_pipeline_sync_real_spec_dispatch_funcs_registered():
    """Rot-guard: the REAL engine/scheduler still define every dispatch
    half the check scopes, and the check's scope list names the spec
    families — a rename without a scope update would silently un-lint
    the zero-flush path."""
    import distributed_llama_multiusers_tpu.analysis.pipeline_check as pc
    from distributed_llama_multiusers_tpu.runtime.engine import (
        InferenceEngine,
    )
    from distributed_llama_multiusers_tpu.runtime.scheduler import (
        ContinuousBatchingScheduler,
    )

    for name in ("decode_spec_pipelined", "decode_spec_prefill_fused"):
        assert name in pc.PIPELINE_FUNCS
        assert hasattr(InferenceEngine, name)
    assert "_pipeline_dispatch" in pc.PIPELINE_FUNCS
    assert hasattr(ContinuousBatchingScheduler, "_pipeline_dispatch")


def test_pipeline_sync_mesh_native_dispatch(tmp_path):
    """The mesh-native dispatch path (pod serving): sharding constraints
    on the device token carry are pure trace-time annotations — no
    finding — but reading the carry back to pick a shard (the tempting
    'just check the carry is replicated' bug) re-serializes the chain on
    every chip and IS one."""
    clean = run_on(tmp_path, {"runtime/engine.py": """
        import jax
        import numpy as np

        class E:
            def decode_pipelined(self, positions, tokens=None):
                feed = self._pl_carry if tokens is None else tokens
                feed = jax.lax.with_sharding_constraint(feed, self._tok_rep)
                nxt, packed, self.cache = self._decode_pl_fn(feed, positions)
                self._pl_carry = nxt
                self._pl_inflight.append(packed)
    """})
    assert "pipeline-sync" not in checks_of(clean)
    bad = run_on(tmp_path / "bad", {"runtime/engine.py": """
        import numpy as np

        class E:
            def decode_pipelined(self, positions, tokens=None):
                feed = self._pl_carry if tokens is None else tokens
                # 'verify' the carry landed replicated: a full device sync
                carry_host = np.asarray(feed)
                nxt, packed, self.cache = self._decode_pl_fn(
                    carry_host, positions
                )
                self._pl_carry = nxt
    """})
    assert "pipeline-sync" in checks_of(bad)


def test_pipeline_sync_waiver_suppresses(tmp_path):
    """A waiver naming BOTH overlapping checks silences the line (host-sync
    also scopes these files)."""
    findings = run_on(tmp_path, {"runtime/engine.py": """
        import numpy as np

        class E:
            def decode_pipelined(self, positions, tokens=None):
                # dlint: ok[host-sync, pipeline-sync] probe build: deliberate sync
                return np.asarray(positions)
    """})
    assert findings == []


# -- clock -------------------------------------------------------------------


def test_clock_flags_time_time_everywhere(tmp_path):
    findings = run_on(tmp_path, {"anywhere/mod.py": """
        import time

        def seed():
            return int(time.time())
    """})
    assert checks_of(findings) == ["clock"]


def test_clock_accepts_monotonic_and_waived_timestamps(tmp_path):
    findings = run_on(tmp_path, {"mod.py": """
        import time

        def dur():
            return time.monotonic() + time.perf_counter()

        def created():
            return int(time.time())  # dlint: ok[clock] absolute API timestamp
    """})
    assert findings == []


def test_clock_is_import_aware(tmp_path):
    """`from time import time` and `import time as t` must not bypass the
    wall-clock ban (the dotted-attribute spelling is not the only one)."""
    findings = run_on(tmp_path, {"a.py": """
        from time import time

        def deadline():
            return time() + 5.0
    """})
    assert checks_of(findings) == ["clock"]
    assert "from time import time" in findings[0].message
    findings = run_on(tmp_path / "b", {"b.py": """
        import time as t

        def seed():
            return int(t.time())
    """})
    assert checks_of(findings) == ["clock"]


def test_clock_flags_naive_datetime_now(tmp_path):
    findings = run_on(tmp_path, {"mod.py": """
        from datetime import datetime

        def now():
            return datetime.now()
    """})
    assert checks_of(findings) == ["clock"]


# -- condvar -----------------------------------------------------------------


def test_condvar_wait_needs_predicate_loop(tmp_path):
    findings = run_on(tmp_path, {"mod.py": """
        import threading

        class Q:
            def __init__(self):
                self._cv = threading.Condition()
                self._n = 0

            def bad(self):
                with self._cv:
                    self._cv.wait()

            def good_loop(self):
                with self._cv:
                    while self._n == 0:
                        self._cv.wait()

            def good_wait_for(self):
                with self._cv:
                    self._cv.wait_for(lambda: self._n > 0)
    """})
    assert checks_of(findings) == ["condvar"]
    assert "predicate loop" in findings[0].message


def test_condvar_flags_event_busy_poll(tmp_path):
    findings = run_on(tmp_path, {"mod.py": """
        import threading

        class Loop:
            def __init__(self):
                self._stop = threading.Event()

            def bad(self):
                while not self._stop.is_set():
                    self._stop.wait(0.001)

            def good(self):
                self._stop.wait(0.25)
    """})
    assert checks_of(findings) == ["condvar"]
    assert "busy-poll" in findings[0].message


def test_condvar_daemon_thread_needs_join(tmp_path):
    bad = """
        import threading

        def serve():
            t = threading.Thread(target=print, daemon=True)
            t.start()
    """
    findings = run_on(tmp_path, {"mod.py": bad})
    assert checks_of(findings) == ["condvar"]
    assert "join" in findings[0].message
    good = """
        import threading

        class S:
            def start(self):
                self._t = threading.Thread(target=print, daemon=True)
                self._t.start()

            def stop(self):
                self._t.join(timeout=30)
    """
    assert run_on(tmp_path / "g", {"mod.py": good}) == []


# -- sharding-axis -----------------------------------------------------------


def test_sharding_axis_must_be_declared(tmp_path):
    """Acceptance-criterion demo: a PartitionSpec naming an axis the mesh
    builders never create is a finding."""
    findings = run_on(tmp_path, {
        "parallel/mesh.py": 'AXES = ("dp", "tp")\n',
        "parallel/sharding.py": """
            from jax.sharding import PartitionSpec as P

            GOOD = P("dp", None, "tp")
            BAD = P("dp", "model")
        """,
    })
    assert checks_of(findings) == ["sharding-axis"]
    assert "'model'" in findings[0].message


def test_sharding_axis_covers_collectives_and_shape_lookups(tmp_path):
    findings = run_on(tmp_path, {
        "parallel/mesh.py": 'AXES = ("dp", "tp", "sp")\n',
        "parallel/ops.py": """
            import jax

            def f(x, mesh):
                a = jax.lax.psum(x, "sp")
                b = jax.lax.ppermute(x, "ring", [(0, 1)])
                n = mesh.shape["tp"]
                m = mesh.shape.get("oops", 1)
                return a, b, n, m
        """,
    })
    assert checks_of(findings) == ["sharding-axis", "sharding-axis"]
    msgs = " ".join(f.message for f in findings)
    assert "'ring'" in msgs and "'oops'" in msgs


def test_sharding_axis_default_axes_without_decl(tmp_path):
    findings = run_on(tmp_path, {"mod.py": """
        from jax.sharding import PartitionSpec as P

        OK = P("tp")
        BAD = P("nope")
    """})
    assert checks_of(findings) == ["sharding-axis"]


def test_sharding_axis_covers_ring_collectives(tmp_path):
    """The ring-collective entry points (ops/ring_collective.py) take the
    mesh axis name as a plain argument like the lax primitives they wrap —
    a misspelled axis there must be a lint finding, not a trace-time error
    on a real pod. Known-bad: bogus axes through every ring call shape;
    known-good: the declared axes pass clean."""
    findings = run_on(tmp_path, {
        "parallel/mesh.py": 'AXES = ("dp", "tp")\n',
        "ops/ring_collective.py": """
            import jax

            def sync(x, w, mesh, n):
                a = ring_reduce_scatter(x, "ring", n)
                b = ring_all_gather(a, "tp", n)
                c = ring_all_reduce(x, "tpx", n)
                d = ring_sync_matmul(x, w, mesh, axis="modell")
                return b, c, d
        """,
    })
    assert checks_of(findings) == ["sharding-axis"] * 3
    msgs = " ".join(f.message for f in findings)
    assert "'ring'" in msgs and "'tpx'" in msgs and "'modell'" in msgs
    clean = run_on(tmp_path / "clean", {
        "parallel/mesh.py": 'AXES = ("dp", "tp")\n',
        "ops/ring_collective.py": """
            import jax

            def sync(x, w, mesh, n):
                a = ring_reduce_scatter(x, "tp", n)
                b = ring_all_gather_q80(a, "tp", n)
                r = jax.lax.axis_index("tp")
                return ring_sync_matmul(x, w, mesh, axis="tp"), b, r
        """,
    })
    assert "sharding-axis" not in checks_of(clean)


def test_real_ring_collective_axis_sites_are_covered():
    """Rot-guard: the shipped ring_collective module really contains the
    call shapes the checker knows (ring calls with a positional or axis=
    axis name), so the vocabulary cannot silently drift from the code."""
    import ast

    from distributed_llama_multiusers_tpu.analysis.sharding_check import (
        COLLECTIVE_CALLS,
    )

    src = (
        PACKAGE_ROOT / "ops" / "ring_collective.py"
    ).read_text(encoding="utf-8")
    tree = ast.parse(src)
    called = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            name = fn.id if isinstance(fn, ast.Name) else getattr(fn, "attr", None)
            if name in COLLECTIVE_CALLS:
                called.add(name)
    # the module itself exercises the ring vocabulary plus the lax
    # primitives underneath it
    assert {"ppermute", "axis_index"} <= called
    assert {"ring_reduce_scatter", "ring_all_gather"} & called


# -- lock-order (dlint v2 cross-file concurrency layer) ----------------------


TWO_LOCK_CLASSES = """
    import threading

    class A:
        def __init__(self):
            self._a_lock = threading.Lock()

    class B:
        def __init__(self):
            self._b_lock = threading.Lock()
"""


def test_lock_order_cycle_is_a_finding(tmp_path):
    """Acceptance-criterion demo: two call sites taking the same two locks
    in opposite orders is a lock-order cycle — the deadlock the test suite
    only reproduces under exactly the wrong interleaving becomes a lint
    failure instead."""
    findings = run_on(tmp_path, {"m.py": TWO_LOCK_CLASSES + """
        def forward(a, b):
            with a._a_lock:
                with b._b_lock:
                    pass

        def backward(a, b):
            with b._b_lock:
                with a._a_lock:
                    pass
    """})
    assert "lock-order" in checks_of(findings)
    msgs = " ".join(f.message for f in findings if f.check == "lock-order")
    assert "cycle" in msgs and "A._a_lock" in msgs and "B._b_lock" in msgs


def test_lock_order_consistent_nesting_is_clean(tmp_path):
    findings = run_on(tmp_path, {"m.py": TWO_LOCK_CLASSES + """
        def one(a, b):
            with a._a_lock:
                with b._b_lock:
                    pass

        def two(a, b):
            with a._a_lock:
                with b._b_lock:
                    pass
    """})
    assert findings == []


def test_lock_order_cycle_across_files(tmp_path):
    """The graph is cross-file: each direction of the inversion lives in
    its own module and no single-file pass could see the cycle."""
    findings = run_on(tmp_path, {
        "serving/q.py": """
            import threading

            class Q:
                def __init__(self):
                    self._q_lock = threading.Lock()

                def visit(self, tracer):
                    with self._q_lock:
                        with tracer._t_lock:
                            pass
        """,
        "telemetry/t.py": """
            import threading

            class Tracer:
                def __init__(self):
                    self._t_lock = threading.Lock()

                def visit(self, q):
                    with self._t_lock:
                        with q._q_lock:
                            pass
        """,
    })
    assert "lock-order" in checks_of(findings)


def test_lock_order_one_level_call_edge(tmp_path):
    """A `with lock:` body calling a method that takes another known lock
    contributes an edge through the call — the cycle here is invisible to
    any with-statement-only analysis."""
    findings = run_on(tmp_path, {"m.py": """
        import threading

        class Stats:
            def __init__(self):
                self._st_lock = threading.Lock()

            def bump(self):
                with self._st_lock:
                    pass

            def rev(self, q):
                with self._st_lock:
                    with q._q_lock:
                        pass

        class Queue:
            def __init__(self):
                self._q_lock = threading.Lock()

            def popped(self, stats):
                with self._q_lock:
                    stats.bump()
    """})
    lock_order = [f for f in findings if f.check == "lock-order"]
    assert lock_order, checks_of(findings)
    assert any("via" in f.message or "cycle" in f.message for f in lock_order)


def test_lock_order_self_reacquisition(tmp_path):
    findings = run_on(tmp_path, {"m.py": """
        import threading

        class S:
            def __init__(self):
                self._s_lock = threading.Lock()

            def outer(self):
                with self._s_lock:
                    self.inner()

            def inner(self):
                with self._s_lock:
                    pass
    """})
    assert checks_of(findings) == ["lock-order"]
    assert "re-acquisition" in findings[0].message


def test_lock_order_condition_alias_is_not_an_edge(tmp_path):
    """Condition(self._lock) IS self._lock: nesting the condition inside
    the lock's own guarded-by sibling must not read as a second lock."""
    findings = run_on(tmp_path, {"m.py": """
        import threading

        class Q:
            def __init__(self):
                self._lk = threading.Lock()
                self._cv = threading.Condition(self._lk)

            def pop(self):
                with self._cv:
                    while True:
                        self._cv.wait()
    """})
    assert findings == []


def test_lock_order_waiver_suppresses_edge(tmp_path):
    findings = run_on(tmp_path, {"m.py": TWO_LOCK_CLASSES + """
        def forward(a, b):
            with a._a_lock:
                with b._b_lock:
                    pass

        def backward(a, b):
            with b._b_lock:
                # dlint: ok[lock-order] shutdown path: forward() provably quiesced before this runs
                with a._a_lock:
                    pass
    """})
    assert findings == []


def test_lock_order_witness_name_mismatch(tmp_path):
    """make_lock literals are the runtime witness's vocabulary; a literal
    that drifts from its class-qualified declaration is a finding."""
    findings = run_on(tmp_path, {"m.py": """
        from distributed_llama_multiusers_tpu.lockcheck import make_lock

        class Q:
            def __init__(self):
                self._lk = make_lock("SomethingElse._lk")
    """})
    assert checks_of(findings) == ["lock-order"]
    assert "does not match" in findings[0].message


def test_real_lock_decls_are_collected():
    """Rot-guard: the real declarations the concurrency checks key on
    still exist, under their witness names, with the QosQueue condition
    aliased to its lock."""
    from distributed_llama_multiusers_tpu.analysis.lockgraph import scan_paths

    model = scan_paths([PACKAGE_ROOT])
    model.ensure_semantics()
    for qual in (
        "QosQueue._lock", "EngineStats.lock", "SpanTracer._trace_lock",
        "JsonLogger._log_lock", "Counter._m_lock", "Gauge._m_lock",
        "Histogram._m_lock", "MetricsRegistry._reg_lock", "native._lock",
        # failure containment (ISSUE 8): breaker/watchdog/fault-plan state
        # is lock-guarded and witness-wrapped like every other lock here
        "CircuitBreaker._lock", "StepWatchdog._lock", "FaultPlan._lock",
        # crash durability (ISSUE 10): journal queue, resume relays, and
        # recovery counters are lock-guarded and witness-wrapped too
        "RequestJournal._lock", "StreamRelay._lock",
        "StreamRegistry._lock", "RecoveryCoordinator._lock",
    ):
        assert qual in model.decls, f"lock declaration rotted: {qual}"
    assert model.canonical("QosQueue._not_empty") == "QosQueue._lock"
    # the watchdog condition is a view of its lock, same as the queue's
    assert model.canonical("StepWatchdog._cond") == "StepWatchdog._lock"
    # the journal/relay/registry conditions fold into their locks too
    assert model.canonical("RequestJournal._cv") == "RequestJournal._lock"
    assert model.canonical("StreamRelay._cv") == "StreamRelay._lock"
    assert model.canonical("StreamRegistry._cv") == "StreamRegistry._lock"


def test_host_sync_covers_containment_files(tmp_path):
    """ISSUE-8 satellite: the failure-containment files ride the serving
    loop (breaker fed per step, watchdog bracketing every blocking call,
    fault hooks inside dispatch paths) — a device->host transfer added to
    any of them is a host-sync finding like in runtime/."""
    bad = """
        import numpy as np

        def fire(point, value):
            return np.asarray(value)
    """
    for rel in ("serving/breaker.py", "serving/watchdog.py",
                "utils/faults.py"):
        findings = run_on(tmp_path / rel.replace("/", "_"), {rel: bad})
        assert checks_of(findings) == ["host-sync"], rel


def test_crash_durability_files_in_all_scopes(tmp_path):
    """ISSUE-10 satellite: serving/journal.py, serving/recovery.py and
    serving/resume.py ride the serving loop (admit/finish records
    enqueue from it, relay pushes run inside _stream, recovery
    re-admits through submit()) — so they sit in the host-sync scope,
    the package-wide clock ban, and the guarded-by discipline like the
    containment files before them. Known-bad fixtures per check, plus
    the clean shapes the real files use."""
    sync_bad = """
        import numpy as np

        def record(journal, value):
            journal.push(np.asarray(value))
    """
    clock_bad = """
        import time

        def stamp():
            return time.time()
    """
    for rel in ("serving/journal.py", "serving/recovery.py",
                "serving/resume.py"):
        tag = rel.replace("/", "_")
        findings = run_on(tmp_path / ("s_" + tag), {rel: sync_bad})
        assert checks_of(findings) == ["host-sync"], rel
        findings = run_on(tmp_path / ("c_" + tag), {rel: clock_bad})
        assert checks_of(findings) == ["clock"], rel
    # guarded-by: an unlocked touch of declared journal state is a
    # finding; the locked touch is clean (the real writer's shape)
    findings = run_on(tmp_path / "g", {"serving/journal.py": """
        import threading

        class RequestJournal:
            _dlint_guarded_by = {("_lock",): ("_j_pending",)}

            def __init__(self):
                self._lock = threading.Lock()
                self._j_pending = []

            def bad_enqueue(self, rec):
                self._j_pending.append(rec)

            def good_enqueue(self, rec):
                with self._lock:
                    self._j_pending.append(rec)
    """})
    assert checks_of(findings) == ["guarded-by"]
    assert "_j_pending" in findings[0].message
    # known-good: monotonic waits + locked state, the real files' idiom
    clean = run_on(tmp_path / "ok", {"serving/resume.py": """
        import threading
        import time

        class StreamRelay:
            _dlint_guarded_by = {("_lock", "_cv"): ("_rl_deltas",)}

            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)
                self._rl_deltas = []

            def push(self, index, text):
                with self._cv:
                    self._rl_deltas.append((index, text))
                    self._cv.notify_all()

            def wait_next(self, timeout):
                deadline = time.monotonic() + timeout
                with self._cv:
                    while not self._rl_deltas:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            return None
                        self._cv.wait(remaining)
                    return self._rl_deltas[0]
    """})
    assert clean == []


# -- fleet front-end (fleet/) -------------------------------------------------


def test_host_sync_covers_fleet_files(tmp_path):
    """ISSUE-12 satellite: the fleet package is pure stdlib BY DESIGN
    (the router holds no model and no device) — a transfer spelling in
    any fleet module means device state leaked a layer up, and is a
    host-sync finding like in runtime/ and serving/."""
    bad = """
        import numpy as np

        def pick(keys):
            return np.asarray(keys)
    """
    for rel in ("fleet/balancer.py", "fleet/router.py",
                "fleet/migrate.py"):
        findings = run_on(tmp_path / rel.replace("/", "_"), {rel: bad})
        assert checks_of(findings) == ["host-sync"], rel
    # the clean shape: pure host hashing/bisect, the real balancer idiom
    clean = run_on(tmp_path / "ok", {"fleet/balancer.py": """
        import bisect
        import zlib

        def prefix_key(data, block):
            key = 0
            for b in range(len(data) // block):
                key = zlib.crc32(data[b * block:(b + 1) * block], key)
            return key

        def ring_find(ring, point):
            return bisect.bisect_left(ring, (point, ""))
    """})
    assert clean == []


def test_host_sync_covers_grammar_files(tmp_path):
    """ISSUE-13 satellite: the grammar package (schema compiler + slab)
    is pure-host numpy BY CONTRACT — it rides the admission and dispatch
    paths, so a device transfer spelling there would serialize every
    constrained dispatch on the automaton tables. Known-bad fixtures
    flag; the known-good shape (packbits/searchsorted host math, the
    real compiler idiom) stays clean; the shipped package keeps an
    empty baseline (test_package_analyzes_clean is the gate)."""
    bad = """
        import numpy as np

        def masks_of(rows):
            return np.asarray(rows)
    """
    for rel in ("grammar/automaton.py", "grammar/slab.py"):
        findings = run_on(tmp_path / rel.replace("/", "_"), {rel: bad})
        assert checks_of(findings) == ["host-sync"], rel
    bad_item = """
        def next_state(keys, key):
            return keys.searchsorted(key).item()
    """
    findings = run_on(tmp_path / "item", {"grammar/slab.py": bad_item})
    assert checks_of(findings) == ["host-sync"]
    # the clean shape: the compiler's real host idiom — packed masks and
    # sorted sparse edges, no transfer spellings anywhere
    clean = run_on(tmp_path / "ok", {"grammar/automaton.py": """
        import numpy as np

        def pack_masks(legal):
            bits = np.zeros((legal.shape[1], legal.shape[0]), np.uint8)
            bits[:, : legal.shape[0]] = legal.T
            return np.packbits(bits, axis=1, bitorder="little")

        def edge_lookup(keys, nexts, default, key):
            j = int(np.searchsorted(keys, key))
            if j < len(keys) and int(keys[j]) == key:
                return int(nexts[j])
            return int(default)
    """})
    assert clean == []


def test_real_fleet_balancer_guard_decls_are_collected():
    """FleetBalancer's replica-table declaration reaches the guarded-by
    checker (the rot-guard pattern: the declaration syntax must not
    silently rot out of collection)."""
    import ast

    from distributed_llama_multiusers_tpu.analysis.core import Project, SourceFile
    from distributed_llama_multiusers_tpu.analysis.lock_check import GuardedByChecker

    project = Project()
    checker = GuardedByChecker()
    p = PACKAGE_ROOT / "fleet/balancer.py"
    sf = SourceFile(
        path=p, display="fleet/balancer.py", text=p.read_text(),
        tree=ast.parse(p.read_text()),
    )
    checker.collect(sf, project)
    assert "_fb_replicas" in project.guarded
    assert "_fb_ring" in project.guarded
    assert "_fb_affinity_hits" in project.guarded
    assert project.guarded["_fb_replicas"][0] == frozenset({"_lock"})


def test_guarded_by_flags_unlocked_fleet_table(tmp_path):
    """Known-bad: a replica-table read outside the balancer lock (picks
    race the scrape thread through exactly this state) is a finding;
    the locked shape is clean."""
    findings = run_on(tmp_path, {"fleet/balancer.py": """
        import threading

        class FleetBalancer:
            _dlint_guarded_by = {("_lock",): ("_fb_replicas", "_fb_ring")}

            def __init__(self):
                self._lock = threading.Lock()
                self._fb_replicas = {}
                self._fb_ring = []

            def bad_pick(self, rid):
                return self._fb_replicas.get(rid)

            def good_pick(self, rid):
                with self._lock:
                    return self._fb_replicas.get(rid)
    """})
    assert checks_of(findings) == ["guarded-by"]
    assert "_fb_replicas" in findings[0].message


# -- lock-blocking ------------------------------------------------------------


def test_lock_blocking_flags_broadcast_under_lock(tmp_path):
    """'Never broadcast under a lock', mechanized: a control-packet send
    while holding any known lock serializes every pod process on one
    host's lock hold."""
    findings = run_on(tmp_path, {"m.py": """
        import threading

        class Root:
            def __init__(self):
                self._r_lock = threading.Lock()

            def bad(self, plane, pkt):
                with self._r_lock:
                    plane.send_decode(pkt)
    """})
    assert checks_of(findings) == ["lock-blocking"]
    assert "send" in findings[0].message


def test_lock_blocking_flags_observer_call_under_lock(tmp_path):
    """The PR 5 wait-observer rule, mechanized: observer/hook callbacks
    run OUTSIDE the queue lock."""
    findings = run_on(tmp_path, {"m.py": """
        import threading

        class Q:
            def __init__(self):
                self._wq_lock = threading.Lock()
                self._on_pop_wait = None

            def bad_pop(self, wait):
                with self._wq_lock:
                    self._on_pop_wait(wait)

            def good_pop(self, wait):
                with self._wq_lock:
                    observer = self._on_pop_wait
                return observer(wait)
    """})
    assert checks_of(findings) == ["lock-blocking"]
    assert "observer" in findings[0].message


def test_lock_blocking_flags_sleep_result_and_foreign_wait(tmp_path):
    findings = run_on(tmp_path, {"m.py": """
        import threading
        import time

        class W:
            def __init__(self):
                self._w_lock = threading.Lock()
                self._done = threading.Event()

            def bad_sleep(self):
                with self._w_lock:
                    time.sleep(0.5)

            def bad_future(self, fut):
                with self._w_lock:
                    return fut.result()

            def bad_foreign_wait(self):
                with self._w_lock:
                    self._done.wait(5.0)
    """})
    assert checks_of(findings) == ["lock-blocking"] * 3


def test_lock_blocking_own_condition_wait_is_fine(tmp_path):
    """cv.wait on the condition built over the held lock releases it —
    the one legitimate blocking-under-lock."""
    findings = run_on(tmp_path, {"m.py": """
        import threading

        class Q:
            def __init__(self):
                self._bq_lock = threading.Lock()
                self._ready = threading.Condition(self._bq_lock)
                self._n = 0

            def pop(self):
                with self._ready:
                    while self._n == 0:
                        self._ready.wait()
    """})
    assert findings == []


def test_lock_blocking_one_level_call_expansion(tmp_path):
    """Calling a function that directly blocks, with the lock held, holds
    the lock across the block just the same — flagged at the call site."""
    findings = run_on(tmp_path, {"m.py": """
        import subprocess
        import threading

        _build_lock = threading.Lock()

        def compile_it():
            subprocess.run(["cc", "x.c"], check=True)

        def build():
            with _build_lock:
                compile_it()
    """})
    assert checks_of(findings) == ["lock-blocking"]
    assert "callee blocks" in findings[0].message


def test_lock_blocking_host_sync_set_under_lock(tmp_path):
    findings = run_on(tmp_path, {"m.py": """
        import threading
        import numpy as np

        class E:
            def __init__(self):
                self._e_lock = threading.Lock()

            def bad(self, logits):
                with self._e_lock:
                    return np.asarray(logits)
    """})
    assert checks_of(findings) == ["lock-blocking"]


# -- lock-atomicity -----------------------------------------------------------

GUARDED_DEPTH = """
    import threading

    class Q:
        _dlint_guarded_by = {("_at_lock",): ("_depth",)}

        def __init__(self):
            self._at_lock = threading.Lock()
            self._depth = 0
"""


def test_lock_atomicity_flags_split_rmw(tmp_path):
    """Acceptance-criterion demo: read under one hold, write under a
    later hold — each section is individually locked (guarded-by green)
    yet the interleaving loses updates."""
    findings = run_on(tmp_path, {"m.py": GUARDED_DEPTH + """
        def shrink(q):
            with q._at_lock:
                d = q._depth
            with q._at_lock:
                q._depth = d - 1
    """})
    assert checks_of(findings) == ["lock-atomicity"]
    assert "straddles" in findings[0].message


def test_lock_atomicity_check_then_act_variant(tmp_path):
    findings = run_on(tmp_path, {"m.py": GUARDED_DEPTH + """
        def maybe_shrink(q):
            with q._at_lock:
                has_items = q._depth > 0
            if has_items:
                with q._at_lock:
                    q._depth -= 1
    """})
    assert checks_of(findings) == ["lock-atomicity"]


def test_lock_atomicity_single_section_is_clean(tmp_path):
    """The shipped shape: read-modify-write folded into one hold; two
    disjoint WRITE-only sections are also fine (each += is atomic under
    its own hold)."""
    findings = run_on(tmp_path, {"m.py": GUARDED_DEPTH + """
        def shrink(q):
            with q._at_lock:
                q._depth = q._depth - 1

        def bump_twice(q, a, b):
            with q._at_lock:
                q._depth += a
            with q._at_lock:
                q._depth += b
    """})
    assert findings == []


def test_lock_atomicity_waiver(tmp_path):
    findings = run_on(tmp_path, {"m.py": GUARDED_DEPTH + """
        def optimistic(q):
            with q._at_lock:
                d = q._depth
            with q._at_lock:
                # dlint: ok[lock-atomicity] revalidated: d is a hint, the write re-checks under the lock
                q._depth = min(d, q._depth)
    """})
    assert findings == []


# -- pod-broadcast ------------------------------------------------------------


def test_pod_broadcast_flags_raise_between_send_and_pair(tmp_path):
    """Acceptance-criterion demo: a raise reachable after the packet went
    out but before the root's paired engine call — workers enter the
    collective the root never dispatches; the pod hangs."""
    findings = run_on(tmp_path, {"parallel/multihost.py": """
        class RootControlEngine:
            def decode(self, tokens):
                self._plane.send_decode(tokens)
                if not tokens:
                    raise ValueError("empty decode batch")
                return self._engine.decode(tokens)
    """})
    assert checks_of(findings) == ["pod-broadcast"]
    assert "raise" in findings[0].message and "deadlock" in findings[0].message


def test_pod_broadcast_flags_early_return(tmp_path):
    findings = run_on(tmp_path, {"parallel/multihost.py": """
        class RootControlEngine:
            def prefill(self, tokens):
                self._plane.send_prefill(tokens)
                if len(tokens) > 512:
                    return None
                return self._engine.prefill(tokens)
    """})
    assert checks_of(findings) == ["pod-broadcast"]
    assert "early return" in findings[0].message


def test_pod_broadcast_validate_first_is_clean(tmp_path):
    """The shipped shape: validation (raises) precedes the broadcast, the
    pair is the next engine call, and a return CONTAINING the pair is the
    pair, not an escape."""
    findings = run_on(tmp_path, {"parallel/multihost.py": """
        class RootControlEngine:
            def decode(self, tokens):
                if not tokens:
                    raise ValueError("empty decode batch")
                self._plane.send_decode(tokens)
                return self._engine.decode(tokens)

            def prefill(self, tokens, chunk):
                for off in range(0, len(tokens), chunk):
                    part = tokens[off : off + chunk]
                    self._plane.send_prefill(part)
                    out = self._engine.prefill(part)
                return out

            def stop_workers(self):
                self._plane.send_stop()
    """})
    assert findings == []


def test_pod_broadcast_scoped_to_multihost(tmp_path):
    """The same shape outside parallel/multihost.py is not this check's
    business."""
    findings = run_on(tmp_path, {"parallel/other.py": """
        class RootControlEngine:
            def decode(self, tokens):
                self._plane.send_decode(tokens)
                raise ValueError("nope")
    """})
    assert "pod-broadcast" not in checks_of(findings)


def test_pod_broadcast_real_sites_still_exist():
    """Rot-guard: the real RootControlEngine still broadcasts through
    self._plane.send_* with self._engine pairs — the exact spellings the
    check keys on. If this fails, the check went blind, not green."""
    import ast as ast_mod

    src = (PACKAGE_ROOT / "parallel" / "multihost.py").read_text()
    tree = ast_mod.parse(src)
    sends = pairs = 0
    for node in ast_mod.walk(tree):
        if isinstance(node, ast_mod.Call):
            spelled = ast_mod.unparse(node.func)
            if spelled.startswith("self._plane.send_"):
                sends += 1
            elif spelled.startswith("self._engine."):
                pairs += 1
    assert sends >= 8, f"only {sends} broadcast sites found"
    assert pairs >= 8, f"only {pairs} engine-pair sites found"
    assert "machine-checked" in src.splitlines()[0] or "pod-broadcast" in src


def test_pod_broadcast_return_after_pairless_send_is_legal(tmp_path):
    """OP_STOP-style ops replay no device program: an explicit trailing
    return after a pair-less broadcast is its normal shape (only a raise
    still flags — the packet is already out)."""
    findings = run_on(tmp_path, {"parallel/multihost.py": """
        class RootControlEngine:
            def stop_workers(self):
                self._plane.send_stop()
                return

            def bad_reset(self, ok):
                self._plane.send_stats_reset()
                if not ok:
                    raise RuntimeError("too late: the packet is out")
    """})
    assert checks_of(findings) == ["pod-broadcast"]
    assert "raise" in findings[0].message


def test_pod_broadcast_ignores_nested_def_returns(tmp_path):
    """A closure's return is its own call stack, not an escape of the
    proxy method."""
    findings = run_on(tmp_path, {"parallel/multihost.py": """
        class RootControlEngine:
            def decode(self, tokens):
                self._plane.send_decode(tokens)

                def fmt(x):
                    return x + 1
                return self._engine.decode(tokens, fmt)
    """})
    assert findings == []


def test_lock_blocking_local_lock_name_does_not_misbind(tmp_path):
    """A function-local `lock = threading.Lock()` is not shared state and
    must not resolve to an unrelated class's declared lock of the same
    attribute name (the EngineStats.lock mis-bind)."""
    findings = run_on(tmp_path, {"m.py": """
        import threading
        import time

        class Stats:
            def __init__(self):
                self.lock = threading.Lock()

        def scratch():
            lock = threading.Lock()
            with lock:
                time.sleep(0.1)
    """})
    assert findings == []


def test_lock_blocking_observer_attribute_spellings(tmp_path):
    """The documented observer vocabulary covers attribute callees too:
    renaming `_on_pop_wait` to `_wait_observer` must not retire the
    machine-checked wait-observer rule."""
    findings = run_on(tmp_path, {"m.py": """
        import threading

        class Q:
            def __init__(self):
                self._ob_lock = threading.Lock()
                self._wait_observer = None
                self._done_callback = None

            def bad_a(self, w):
                with self._ob_lock:
                    self._wait_observer(w)

            def bad_b(self, w):
                with self._ob_lock:
                    self._done_callback(w)
    """})
    assert checks_of(findings) == ["lock-blocking", "lock-blocking"]


# -- CLI output formats & the lock-order graph dump ---------------------------


def test_cli_format_github_annotations(tmp_path, capsys):
    (tmp_path / "mod.py").write_text("import time\nT = time.time()\n")
    rc = dlint_main([str(tmp_path), "--no-baseline", "--format", "github"])
    assert rc == 1
    out = capsys.readouterr().out
    assert out.startswith("::error file=")
    assert "title=dlint[clock]" in out
    assert ",line=2," in out


def test_cli_format_sarif(tmp_path, capsys):
    import json

    (tmp_path / "mod.py").write_text("import time\nT = time.time()\n")
    rc = dlint_main([str(tmp_path), "--no-baseline", "--format", "sarif"])
    assert rc == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "dlint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"lock-order", "lock-blocking", "lock-atomicity",
            "pod-broadcast", "clock"} <= rule_ids
    assert run["results"][0]["ruleId"] == "clock"
    line = run["results"][0]["locations"][0]["physicalLocation"]["region"]["startLine"]
    assert line == 2


def test_cli_format_sarif_clean_tree_emits_document(capsys):
    assert dlint_main(["--format", "sarif"]) == 0
    import json

    doc = json.loads(capsys.readouterr().out)
    assert doc["runs"][0]["results"] == []


def test_cli_graph_dumps_dot(capsys):
    assert dlint_main(["--graph"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("digraph dlint_lock_order")
    assert '"QosQueue._lock"' in out
    assert "QosQueue._not_empty" in out  # the alias stays visible
    assert '"EngineStats.lock"' in out


def test_cli_graph_shows_edges_and_waived_style(tmp_path, capsys):
    (tmp_path / "m.py").write_text(textwrap.dedent("""
        import threading

        class A:
            def __init__(self):
                self._ga_lock = threading.Lock()

        class B:
            def __init__(self):
                self._gb_lock = threading.Lock()

        def nest(a, b):
            with a._ga_lock:
                # dlint: ok[lock-order] drawn dashed, not cycle-checked
                with b._gb_lock:
                    pass
    """))
    assert dlint_main([str(tmp_path), "--graph"]) == 0
    out = capsys.readouterr().out
    assert '"A._ga_lock" -> "B._gb_lock"' in out
    assert "style=dashed" in out


# -- waiver hygiene ----------------------------------------------------------


def test_bare_waiver_is_a_finding(tmp_path):
    findings = run_on(tmp_path, {"mod.py": """
        import time

        def f():
            return time.time()  # dlint: ok[clock]
    """})
    # the bare waiver is rejected AND therefore does not suppress the clock
    # finding either
    assert checks_of(findings) == ["clock", "waiver"]
    assert "without a reason" in [f for f in findings if f.check == "waiver"][0].message


def test_unknown_check_name_in_waiver(tmp_path):
    findings = run_on(tmp_path, {"mod.py": """
        X = 1  # dlint: ok[not-a-check] some reason
    """})
    assert checks_of(findings) == ["waiver"]
    assert "unknown check" in findings[0].message


def test_waiver_only_covers_named_check(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": """
        import numpy as np
        import time

        def f(logits):
            # dlint: ok[clock] wrong check name for this line
            return np.asarray(logits)

        def g():
            return time.time()  # dlint: ok[host-sync] also wrong
    """})
    assert checks_of(findings) == ["clock", "host-sync"]


def test_star_waiver_and_standalone_placement(tmp_path):
    findings = run_on(tmp_path, {"runtime/engine.py": """
        import numpy as np

        def f(logits):
            # dlint: ok[*] benchmark probe: sync everything on purpose
            return np.asarray(logits)
    """})
    assert findings == []


def test_waiver_in_string_literal_does_not_suppress(tmp_path):
    findings = run_on(tmp_path, {"mod.py": '''
        import time

        def f():
            doc = "# dlint: ok[clock] not a comment"
            return time.time(), doc
    '''})
    assert checks_of(findings) == ["clock"]


# -- baseline ----------------------------------------------------------------


def test_baseline_suppresses_only_listed_findings(tmp_path):
    files = {"mod.py": """
        import time

        def f():
            return time.time()

        def g():
            return datetime.datetime.now()

        import datetime
    """}
    all_findings = run_on(tmp_path, files)
    assert len(all_findings) == 2
    baseline = {all_findings[0].key}
    remaining = run_on(tmp_path, files, baseline=baseline)
    assert len(remaining) == 1
    assert remaining[0].key == all_findings[1].key


def test_write_baseline_roundtrip(tmp_path, capsys):
    (tmp_path / "mod.py").write_text("import time\nT = time.time()\n")
    bl = tmp_path / "bl.txt"
    assert dlint_main([str(tmp_path), "--baseline", str(bl), "--write-baseline"]) == 0
    assert bl.exists()
    capsys.readouterr()
    # with the written baseline the same tree is clean
    assert dlint_main([str(tmp_path), "--baseline", str(bl)]) == 0
    # without it, the finding is back
    assert dlint_main([str(tmp_path), "--no-baseline", "--baseline", str(bl)]) == 1


def test_write_baseline_excludes_unbaselinable_findings(tmp_path, capsys):
    """waiver/parse findings are never filtered by the baseline, so writing
    their keys would strand dead entries while the gate keeps failing; the
    CLI must report them and exit 1 instead."""
    (tmp_path / "mod.py").write_text(
        "import time\nT = time.time()  # dlint: ok[clock]\n"
    )
    bl = tmp_path / "bl.txt"
    rc = dlint_main([str(tmp_path), "--baseline", str(bl), "--write-baseline"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "cannot be baselined" in out
    keys = [
        line for line in bl.read_text().splitlines()
        if line.strip() and not line.startswith("#")
    ]
    # the clock finding (un-suppressed by the bare waiver) was baselined;
    # the waiver finding was not
    assert len(keys) == 1 and keys[0].startswith("clock\t")


def test_cli_missing_path_is_usage_error(tmp_path):
    assert dlint_main([str(tmp_path / "nope")]) == 2


def test_syntax_error_is_a_parse_finding(tmp_path):
    findings = run_on(tmp_path, {"mod.py": "def broken(:\n"})
    assert checks_of(findings) == ["parse"]


# -- fleet tracing (ISSUE 20) -------------------------------------------------


def test_host_sync_covers_tracectx(tmp_path):
    """ISSUE-20 satellite: the fleet trace context rides every router
    hop and the replica admission path (journal admit records), so it is
    registered under host-sync like the rest of telemetry/ — a transfer
    spelling there would mean device state leaked into the tracing
    layer. Known-bad fixtures flag; the real idiom (os.urandom ids,
    dict folding under a lock) stays clean."""
    findings = run_on(tmp_path, {"telemetry/tracectx.py": """
        import numpy as np

        def observe(phases):
            return np.asarray(list(phases.values()))
    """})
    assert checks_of(findings) == ["host-sync"]
    findings = run_on(tmp_path / "b", {"telemetry/tracectx.py": """
        def fold(totals, v):
            totals.append(v.item())
    """})
    assert checks_of(findings) == ["host-sync"]
    # the clean shape: the shipped module's real idiom
    clean = run_on(tmp_path / "c", {"telemetry/tracectx.py": """
        import os
        import threading

        def mint():
            return os.urandom(16).hex() + "-" + os.urandom(8).hex()

        class PhaseAccumulator:
            _dlint_guarded_by = {("_phase_lock",): ("_phase_counts",)}

            def __init__(self):
                self._phase_lock = threading.Lock()
                self._phase_counts = {}

            def observe(self, key):
                with self._phase_lock:
                    self._phase_counts[key] = (
                        self._phase_counts.get(key, 0) + 1
                    )
    """})
    assert clean == []


def test_real_tracing_guard_decls_are_collected():
    """Rot-guard for ISSUE 20's lock declarations: the shipped
    PhaseAccumulator, LabelledHistogram, and FleetRouter clock-offset
    declarations reach the guarded-by checker — the declaration syntax
    must not silently rot out of collection."""
    import ast

    from distributed_llama_multiusers_tpu.analysis.core import (
        Project,
        SourceFile,
    )
    from distributed_llama_multiusers_tpu.analysis.lock_check import (
        GuardedByChecker,
    )

    def collected(rel):
        project = Project()
        checker = GuardedByChecker()
        p = PACKAGE_ROOT / rel
        sf = SourceFile(path=p, display=rel, text=p.read_text(),
                        tree=ast.parse(p.read_text()))
        checker.collect(sf, project)
        return project.guarded

    guarded = collected("telemetry/tracectx.py")
    for attr in ("_phase_counts", "_phase_sums_ms", "_phase_records"):
        assert attr in guarded, attr
        assert guarded[attr][0] == frozenset({"_phase_lock"})
    guarded = collected("telemetry/metrics.py")
    assert "_hist_series" in guarded
    assert guarded["_hist_series"][0] == frozenset({"_m_lock"})
    guarded = collected("fleet/router.py")
    assert "_clock_offsets" in guarded
    assert guarded["_clock_offsets"][0] == frozenset({"_clock_lock"})


def test_guarded_by_flags_unlocked_phase_state(tmp_path):
    """Known-bad: phase-aggregation state read outside the accumulator
    lock (the router's stream pumps fold records from many client
    threads) is a finding; the locked shape is clean."""
    findings = run_on(tmp_path, {"telemetry/tracectx.py": """
        import threading

        class PhaseAccumulator:
            _dlint_guarded_by = {("_phase_lock",): ("_phase_counts",)}

            def __init__(self):
                self._phase_lock = threading.Lock()
                self._phase_counts = {}

            def bad_snapshot(self):
                return dict(self._phase_counts)

            def good_snapshot(self):
                with self._phase_lock:
                    return dict(self._phase_counts)
    """})
    assert checks_of(findings) == ["guarded-by"]
    assert "_phase_counts" in findings[0].message
