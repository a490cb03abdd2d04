"""Zero-flush serving: speculative verification inside the pipelined step
family (``engine.decode_spec_pipelined`` / ``decode_spec_prefill_fused``)
and exact on-device top-p, so the async chain never aborts for a draft hit
or a wide-nucleus lane.

The invariants under test:

1. STREAM IDENTITY — a chain carrying spec verify steps emits exactly the
   plain-decode streams (speculative-verification identity composed with
   the carry-alignment gate), for greedy AND device-sampled lanes.
2. ZERO FLUSHES — mocked-engine churn with speculation ON and wide-nucleus
   sampled lanes in the mix completes with ``pipeline_flushes == 0``
   (the PR-9 acceptance criterion: only stop/drain may flush).
3. COMPOSITION — fused admissions and spec verify steps share dispatches
   (``fused_steps > 0`` and ``spec_emitted_per_lane_step > 1`` in one
   run), multiplying instead of trading off.
4. The POSITION CARRY — per-lane accept counts advance write positions on
   device (``pos + accepted + 1``); the device clamps drafts near
   seq_len from the carried positions (the host's view can be stale).
"""

import time

import numpy as np
import pytest
import jax.numpy as jnp

from distributed_llama_multiusers_tpu.formats import load_model_header
from distributed_llama_multiusers_tpu.models import load_params_from_m
from distributed_llama_multiusers_tpu.runtime import (
    ContinuousBatchingScheduler,
    InferenceEngine,
    Request,
)
from distributed_llama_multiusers_tpu.runtime.engine import warmup_engine
from distributed_llama_multiusers_tpu.tokenizer import Tokenizer
from distributed_llama_multiusers_tpu.utils.testing import (
    MockAsyncEngine,
    StubStreamTokenizer,
    greedy_rollout,
)


@pytest.fixture(scope="module")
def loaded(tiny_model):
    h = load_model_header(tiny_model["model"])
    config, params = load_params_from_m(tiny_model["model"], h, dtype=jnp.float32)
    tok = Tokenizer(tiny_model["tokenizer"])
    return config, params, tok


def _fresh_engine(config, params, n_lanes=2, **kw):
    return InferenceEngine(
        config, params, n_lanes=n_lanes, prefill_buckets=(4,), **kw
    )


# ---------------------------------------------------------------------------
# engine level: the in-chain verify step
# ---------------------------------------------------------------------------


def test_engine_spec_pipelined_chain_identity(loaded):
    """A pipelined chain mixing spec verify steps (reseed-aligned AND
    chained one-step-behind drafts) with plain pipelined steps emits
    exactly the plain greedy stream, with full draft acceptance when the
    candidates are right — the zero-flush composition at engine level."""
    config, params, _ = loaded
    prompt = [5, 9, 3, 5, 9, 3, 5, 9]
    ref, _ = greedy_rollout(_fresh_engine(config, params), prompt, 16)

    engine = _fresh_engine(config, params)
    _, g0, pos = engine.prefill(0, prompt)
    assert int(g0) == ref[0]
    k = engine.SPEC_DRAFT
    n = engine.n_lanes
    out = [int(g0)]
    seq_len = config.seq_len

    # dispatch 0: RESEED spec step — the host knows the feed exactly and
    # ships it as candidate 0, followed by the true continuation
    drafts = np.zeros((n, k + 1), np.int32)
    dlen = np.zeros(n, np.int32)
    drafts[0] = [ref[0]] + ref[1 : 1 + k]
    dlen[0] = k + 1
    engine.decode_spec_pipelined(
        np.asarray([pos, seq_len], np.int32), drafts, dlen,
        tokens=np.asarray([g0, 0], np.int32),
    )
    # dispatch 1: chained plain step on the carried positions (-1)
    neg = np.asarray([-1, seq_len], np.int32)
    engine.decode_pipelined(neg)
    emitted, n_emit = engine.pipeline_consume()  # the spec step
    cnt = int(n_emit[0])
    assert cnt == k + 1  # full acceptance: every candidate was right
    out.extend(int(t) for t in emitted[0, : cnt - 1])
    out.append(int(emitted[0, cnt - 1]))
    g, _ = engine.pipeline_consume()  # the plain step
    out.append(int(g[0]))
    assert out == ref[: len(out)]

    # dispatch 2: plain in flight, then a CHAINED spec step — the host is
    # one token behind, so candidate 0 guesses the in-flight step's output
    engine.decode_pipelined(neg)
    i = len(out)
    drafts2 = np.zeros((n, k + 1), np.int32)
    dlen2 = np.zeros(n, np.int32)
    drafts2[0] = ref[i : i + k + 1]
    dlen2[0] = k + 1
    engine.decode_spec_pipelined(neg, drafts2, dlen2)
    g, _ = engine.pipeline_consume()
    out.append(int(g[0]))
    emitted, n_emit = engine.pipeline_consume()
    cnt = int(n_emit[0])
    assert cnt == k + 1  # the alignment gate passed and all drafts hit
    out.extend(int(t) for t in emitted[0, : cnt - 1])
    out.append(int(emitted[0, cnt - 1]))
    engine.pipeline_flush()
    assert out == ref[: len(out)]


def test_engine_spec_pipelined_wrong_carry_candidate_is_safe(loaded):
    """A candidate-0 mismatch (the host's stale guess at the carry) zeroes
    the effective draft — n_emit == 1 and the stream stays exactly the
    plain-decode stream. Misalignment costs acceptance, never
    correctness."""
    config, params, _ = loaded
    prompt = [5, 9, 3, 5, 9, 3, 5, 9]
    ref, _ = greedy_rollout(_fresh_engine(config, params), prompt, 8)

    engine = _fresh_engine(config, params)
    _, g0, pos = engine.prefill(0, prompt)
    k = engine.SPEC_DRAFT
    n = engine.n_lanes
    drafts = np.zeros((n, k + 1), np.int32)
    dlen = np.zeros(n, np.int32)
    # wrong candidate 0, RIGHT continuations: the gate must still reject
    drafts[0] = [(ref[0] + 1) % config.vocab_size] + ref[1 : 1 + k]
    dlen[0] = k + 1
    engine.decode_spec_pipelined(
        np.asarray([pos, config.seq_len], np.int32), drafts, dlen,
        tokens=np.asarray([g0, 0], np.int32),
    )
    emitted, n_emit = engine.pipeline_consume()
    engine.pipeline_flush()
    assert int(n_emit[0]) == 1
    assert int(emitted[0, 0]) == ref[1]


def test_engine_spec_pipelined_clamps_on_device_near_seq_len(loaded):
    """The draft clamp moved ON DEVICE (the host's stale position could
    under-clamp once accept counts ride the carry): a lane whose carried
    position sits within SPEC_DRAFT slots of seq_len accepts at most the
    slots it has left, and never scribbles past the end."""
    config, params, _ = loaded
    engine = _fresh_engine(config, params)
    seq_len = config.seq_len
    k = engine.SPEC_DRAFT
    n = engine.n_lanes
    prompt = [5, 9, 3]
    _, g0, pos = engine.prefill(0, prompt)
    # park the lane 2 slots short of seq_len: at most 1 draft can commit
    start = seq_len - 2
    drafts = np.full((n, k + 1), int(g0), np.int32)
    dlen = np.full(n, 0, np.int32)
    drafts[0, 0] = int(g0)  # candidate 0 == feed: gate passes
    dlen[0] = k + 1
    engine.decode_spec_pipelined(
        np.asarray([start, seq_len], np.int32), drafts, dlen,
        tokens=np.asarray([g0, 0], np.int32),
    )
    emitted, n_emit = engine.pipeline_consume()
    engine.pipeline_flush()
    # eff_len clamped to seq_len - pos - 1 = 1, so n_emit <= 2 regardless
    # of how many candidates matched
    assert 1 <= int(n_emit[0]) <= 2


def test_engine_spec_drafts_shape_validated(loaded):
    """The draft-shape contract raises BEFORE any dispatch (the root
    proxy's pre-broadcast validation relies on it)."""
    config, params, _ = loaded
    engine = _fresh_engine(config, params)
    z = np.zeros(engine.n_lanes, np.int32)
    bad = np.zeros((engine.n_lanes, engine.SPEC_DRAFT), np.int32)  # K, not K+1
    with pytest.raises(ValueError, match="drafts shape"):
        engine.decode_spec_pipelined(z, bad, z, tokens=z)
    with pytest.raises(ValueError, match="drafts shape"):
        engine.decode_spec_prefill_fused(z, bad, z, chunk=[1, 2], tokens=z)


@pytest.mark.slow  # tier-2: heavy; the fused-pack class stays tier-1 via test_pod_packet_replays_decode_spec_prefill_fused and the scheduler fused-admission pins (see pyproject markers)
def test_engine_spec_prefill_fused_pack(loaded):
    """The chunk+verify composition returns the spec pack with the
    boundary pair as an extra row, and the admitting lane's carry holds
    the boundary token at the chunk-boundary position — a freshly joined
    lane can ride the NEXT dispatch (spec or plain) straight from
    device."""
    config, params, _ = loaded
    ref_engine = _fresh_engine(config, params)
    prompt = [5, 9, 3, 7]
    ref, _ = greedy_rollout(ref_engine, prompt, 4)

    engine = _fresh_engine(config, params)
    warmup_engine(engine, spec=True, multi_step=0)
    k = engine.SPEC_DRAFT
    n = engine.n_lanes
    seq_len = config.seq_len
    drafts = np.zeros((n, k + 1), np.int32)
    dlen = np.zeros(n, np.int32)
    # lane 1 admits via the fused-spec step (lane 0 idle, no drafts):
    # the prefill half must behave exactly like prefill_chunk
    engine.decode_spec_prefill_fused(
        np.full(n, seq_len, np.int32), drafts, dlen,
        p_lane=1, chunk=prompt, p_start=0,
        tokens=np.zeros(n, np.int32),
    )
    emitted, n_emit = engine.pipeline_consume()
    assert emitted.shape == (n + 1, k + 1)
    assert int(emitted[-1, 0]) == ref[0]  # boundary greedy == cold prefill
    # the carry now feeds lane 1 at the boundary position: a plain chained
    # step must emit the next plain-decode token
    engine.decode_pipelined(np.asarray([seq_len, -1], np.int32))
    g, _ = engine.pipeline_consume()
    engine.pipeline_flush()
    assert int(g[1]) == ref[1]


# ---------------------------------------------------------------------------
# scheduler level (real engine): streams and flush accounting
# ---------------------------------------------------------------------------


def _run_sched(config, params, tok, reqs, n_lanes=4, **kw):
    engine = _fresh_engine(config, params, n_lanes=n_lanes)
    kw.setdefault("prefix_min_tokens", 0)
    kw.setdefault("multi_step", 0)
    sched = ContinuousBatchingScheduler(engine, tok, **kw)
    sched.start()
    try:
        for r in reqs:
            sched.submit(r)
        for r in reqs:
            r.future.result(timeout=300)
    finally:
        sched.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return [list(r.generated_tokens) for r in reqs], engine.stats.snapshot()


def test_scheduler_spec_rides_chain_zero_flush(loaded):
    """Draft-friendly greedy lanes + a seeded sampled lane + a WIDE-
    nucleus sampled lane (the old host-exact class): with speculation on,
    the chain serves everything — streams identical to the synchronous
    spec scheduler, spec verify steps dispatched IN-chain, and zero
    pipeline flushes (the PR-9 acceptance criterion)."""
    config, params, tok = loaded

    def reqs():
        return [
            Request(prompt="aa bb aa bb aa", max_tokens=14, temperature=0.0),
            Request(prompt="aa bb aa bb aa bb", max_tokens=10,
                    temperature=0.0),
            Request(prompt="sampled one", max_tokens=8, temperature=0.8,
                    seed=123),
            Request(prompt="wide nucleus", max_tokens=6, temperature=0.8,
                    topp=1.0, seed=7),
        ]

    base, base_stats = _run_sched(config, params, tok, reqs(),
                                  pipelined=False)
    out, stats = _run_sched(config, params, tok, reqs(), pipelined=True)
    assert out == base
    assert stats["spec_pipelined_steps"] > 0  # verify steps rode the ring
    assert stats["pipeline_flushes"] == 0  # nothing left to flush for
    assert stats["host_exact_lanes"] == 0
    # acceptance realized: more tokens than drafted-lane verify steps
    assert stats["spec_emitted"] > stats["spec_lane_steps"] > 0
    assert sum(stats["spec_accept_hist"].values()) == stats["spec_lane_steps"]


def test_scheduler_spec_chain_stop_string(loaded):
    """A stop string landing inside a spec step's multi-token commit: the
    lane finishes mid-sequence, surplus accepted tokens are discarded
    (junk-KV rule), and the stream equals the synchronous path's."""
    config, params, tok = loaded
    probe = Request(prompt="aa bb aa bb aa", max_tokens=20, temperature=0.0)
    _run_sched(config, params, tok, [probe], pipelined=False)
    dec = tok.make_stream_decoder()
    pieces = [dec.decode(t) for t in probe.generated_tokens]
    stop = next(
        (p for i, p in enumerate(pieces)
         if 4 <= i <= len(pieces) - 6 and p and p.strip()),
        None,
    )
    if stop is None:
        pytest.skip(f"no usable mid-stream piece in {pieces!r}")

    def stopped():
        return [Request(prompt="aa bb aa bb aa", max_tokens=20,
                        temperature=0.0, stop=[stop])]

    base, _ = _run_sched(config, params, tok, stopped(), pipelined=False)
    reqs = stopped()
    out, stats = _run_sched(config, params, tok, reqs, pipelined=True)
    assert out == base
    assert reqs[0].finish_reason == "stop"
    assert len(out[0]) < 20


# ---------------------------------------------------------------------------
# mocked-engine churn: THE zero-flush gate (tier-1 acceptance criterion)
# ---------------------------------------------------------------------------


def _drive(engine, rs, pipelined, staggered, **kw):
    sched = ContinuousBatchingScheduler(
        engine, StubStreamTokenizer(engine.config.vocab_size),
        prefix_min_tokens=0, multi_step=0, pipelined=pipelined, **kw,
    )
    sched.start()
    try:
        if not staggered:
            for r in rs:
                sched.submit(r)
        else:
            sched.submit(rs[0])
            deadline = time.monotonic() + 60
            while engine.stats.snapshot()["pipeline_dispatches"] < 3:
                assert time.monotonic() < deadline, "chain never formed"
                time.sleep(0.002)
            for r in rs[1:]:
                sched.submit(r)
                time.sleep(engine.step_s * 2)
        for r in rs:
            r.future.result(timeout=60)
    finally:
        sched.stop()
    assert all(r.error is None for r in rs), [r.error for r in rs]
    return [list(r.generated_tokens) for r in rs]


def test_mocked_churn_spec_and_wide_nucleus_zero_flush():
    """The PR-9 acceptance criterion, pinned deterministically: mocked-
    engine churn with speculation ON and wide-nucleus sampled lanes in
    the mix completes with ``pipeline_flushes == 0`` (only stop/drain),
    greedy streams byte-identical to the synchronous spec path, sampled
    streams identical to the on-device sampler's sync path under the
    same seeds — and speculation COMPOSES with fused admission in the
    same run (``fused_steps > 0`` with accepted drafts > 0)."""
    N = 8

    def reqs():
        return [
            Request(
                prompt="churn request text", max_tokens=24,
                temperature=0.0 if i % 2 == 0 else 0.8,
                topp=1.0 if i % 4 == 3 else 0.9,  # wide nucleus in the mix
                seed=50 + i,
            )
            for i in range(N)
        ]

    # vocab 16: the mock's f(lane, pos) streams have period 2, so the
    # n-gram drafter hits hard — near-full acceptance when aligned
    base_engine = MockAsyncEngine(n_lanes=4, vocab=16, max_chunk=4,
                                  speculative=True)
    base = _drive(base_engine, reqs(), pipelined=False, staggered=False)

    churn_engine = MockAsyncEngine(n_lanes=4, vocab=16, max_chunk=4,
                                   step_s=0.003, speculative=True)
    churn_reqs = reqs()
    out = _drive(churn_engine, churn_reqs, pipelined=True, staggered=True)

    assert out == base
    snap = churn_engine.stats.snapshot()
    assert snap["pipeline_flushes"] == 0  # THE zero-flush invariant
    assert snap["spec_pipelined_steps"] > 0  # drafts verified in-chain
    assert snap["fused_steps"] > 0  # admissions rode the chain too
    assert snap["host_exact_lanes"] == 0  # wide nucleus stayed on device
    # speculation genuinely multiplied: >1 token per drafted lane-step
    assert snap["spec_lane_steps"] > 0
    assert snap["spec_emitted"] > snap["spec_lane_steps"]
    # accept-hist accounts exactly the drafted lane-steps
    assert sum(snap["spec_accept_hist"].values()) == snap["spec_lane_steps"]


def test_mocked_spec_cancel_mid_draft_keeps_ratio_consistent():
    """A lane cancelled while a spec step is in flight must not count a
    drafted lane-step with zero consumed tokens — the acceptance ratio
    (spec_emitted / spec_lane_steps) stays in its [1, K+1] class (the
    PR-9 spec-accounting leak fix, scheduler side)."""
    engine = MockAsyncEngine(n_lanes=2, vocab=16, speculative=True,
                             step_s=0.004)
    victim = Request(prompt="cancel me", max_tokens=200, temperature=0.0)
    sched = ContinuousBatchingScheduler(
        engine, StubStreamTokenizer(engine.config.vocab_size),
        prefix_min_tokens=0, multi_step=0, pipelined=True,
    )
    sched.start()
    try:
        sched.submit(victim)
        deadline = time.monotonic() + 60
        while engine.stats.snapshot()["spec_pipelined_steps"] < 3:
            assert time.monotonic() < deadline, "speculation never engaged"
            time.sleep(0.002)
        victim.cancel()
        victim.future.result(timeout=60)
    finally:
        sched.stop()
    assert victim.finish_reason == "cancelled"
    snap = engine.stats.snapshot()
    if snap["spec_lane_steps"]:  # ratio class holds even after the cancel
        assert snap["spec_emitted"] >= snap["spec_lane_steps"]


# ---------------------------------------------------------------------------
# pod control plane: the new ops replay
# ---------------------------------------------------------------------------


def test_pod_packet_replays_decode_spec_pipelined():
    """OP_DECODE_SPEC_PIPELINED round-trips the feed flag, ring depth,
    drafts (K+1 candidates), and lengths through the control-plane packet
    into the worker's in-chain verify call, with the bounded-lag consume
    and flush-then-reseed rules of OP_DECODE_PIPELINED."""
    from distributed_llama_multiusers_tpu.parallel import multihost as mh

    calls = []

    class _Eng:
        n_lanes = 2
        SPEC_DRAFT = 3
        pipeline_depth = 2

        def __init__(self):
            self._ring = 0

        def pipeline_inflight(self):
            return self._ring

        def pipeline_consume(self):
            calls.append(("consume",))
            self._ring -= 1

        def pipeline_flush(self, count=True):
            assert count is False
            calls.append(("flush", self._ring))
            self._ring = 0

        def decode_spec_pipelined(self, positions, drafts, draft_len,
                                  temps=None, topps=None, seeds=None,
                                  tokens=None, g_states=None):
            self._ring += 1
            calls.append((
                "spec",
                None if tokens is None else np.asarray(tokens).tolist(),
                np.asarray(positions).tolist(),
                np.asarray(drafts).tolist(),
                np.asarray(draft_len).tolist(),
            ))

    sent = []

    class _Plane(mh.ControlPlane):
        def __init__(self):
            super().__init__(n_lanes=2, chunk=8)

        def _bcast(self, pkt):
            sent.append(pkt.copy())
            return pkt

    plane = _Plane()
    temps = np.asarray([0.0, 0.8], np.float32)
    topps = np.full(2, 0.9, np.float32)
    seeds = np.asarray([1, 2], np.uint32)
    drafts = np.asarray([[7, 8, 9, 10], [0, 0, 0, 0]], np.int32)
    dlen = np.asarray([4, 0], np.int32)
    plane.send_decode_spec_pipelined(
        np.asarray([7, 9], np.int32), np.asarray([3, 4], np.int32),
        temps, topps, seeds, depth=2, drafts=drafts, draft_len=dlen,
    )
    # device-fed chained verify on carried positions (-1 rides the packet)
    plane.send_decode_spec_pipelined(
        None, np.asarray([-1, 4], np.int32), temps, topps, seeds, depth=2,
        drafts=drafts, draft_len=dlen,
    )
    plane.send_pipeline_flush()
    plane.send_stop()

    replay = iter(sent)

    class _ReplayPlane:
        def recv(self):
            return next(replay)

        def slot(self, pkt, i, n):
            return plane.slot(pkt, i, n)

    mh.worker_loop(_Eng(), _ReplayPlane())
    kinds = [c[0] for c in calls]
    assert kinds == ["flush", "spec", "spec", "flush"], calls
    first = calls[1]
    assert first[1] == [7, 9] and first[2] == [3, 4]
    assert first[3] == [[7, 8, 9, 10], [0, 0, 0, 0]]
    assert first[4] == [4, 0]
    assert calls[2][1] is None and calls[2][2] == [-1, 4]


def test_pod_packet_replays_decode_spec_prefill_fused():
    """The fused-spec packet carries drafts AND the chunk + prefill
    header (slots 7/8) into one worker call."""
    from distributed_llama_multiusers_tpu.parallel import multihost as mh

    calls = []

    class _Eng:
        n_lanes = 2
        SPEC_DRAFT = 3
        pipeline_depth = 2

        def pipeline_inflight(self):
            return 0

        def pipeline_flush(self, count=True):
            calls.append(("flush",))

        def decode_spec_prefill_fused(self, positions, drafts, draft_len,
                                      temps=None, topps=None, seeds=None,
                                      p_lane=0, chunk=None, p_start=0,
                                      p_temp=0.0, p_topp=0.9, p_seed=0,
                                      tokens=None, g_states=None, p_g=0):
            calls.append((
                "specfused",
                np.asarray(drafts).tolist(),
                np.asarray(draft_len).tolist(),
                list(chunk), p_lane, p_start,
                round(float(p_temp), 4), p_seed,
            ))

    sent = []

    class _Plane(mh.ControlPlane):
        def __init__(self):
            super().__init__(n_lanes=2, chunk=8)

        def _bcast(self, pkt):
            sent.append(pkt.copy())
            return pkt

    plane = _Plane()
    temps = np.asarray([0.0, 0.0], np.float32)
    topps = np.full(2, 0.9, np.float32)
    seeds = np.asarray([1, 2], np.uint32)
    drafts = np.asarray([[5, 6, 7, 8], [0, 0, 0, 0]], np.int32)
    dlen = np.asarray([4, 0], np.int32)
    plane.send_decode_spec_prefill_fused(
        np.asarray([7, 9], np.int32), np.asarray([3, 4], np.int32),
        temps, topps, seeds, depth=2, drafts=drafts, draft_len=dlen,
        p_lane=1, chunk=[11, 12, 13], p_start=5,
        p_temp=0.8, p_topp=0.9, p_seed=99,
    )
    plane.send_stop()

    replay = iter(sent)

    class _ReplayPlane:
        def recv(self):
            return next(replay)

        def slot(self, pkt, i, n):
            return plane.slot(pkt, i, n)

    mh.worker_loop(_Eng(), _ReplayPlane())
    kinds = [c[0] for c in calls]
    assert kinds == ["flush", "specfused"], calls
    _, d, dl, chunk, p_lane, p_start, p_temp, p_seed = calls[1]
    assert d == [[5, 6, 7, 8], [0, 0, 0, 0]] and dl == [4, 0]
    assert chunk == [11, 12, 13] and p_lane == 1 and p_start == 5
    assert p_temp == 0.8 and p_seed == 99


def test_root_engine_validates_spec_dispatch_before_broadcast():
    """A bad draft shape or chunk must raise BEFORE any packet goes out
    (the pod-deadlock rule, extended to the new ops)."""
    from distributed_llama_multiusers_tpu.parallel import multihost as mh

    sent = []

    class _Plane(mh.ControlPlane):
        def __init__(self):
            super().__init__(n_lanes=2, chunk=8)

        def _bcast(self, pkt):
            sent.append(pkt.copy())
            return pkt

    class _Eng:
        n_lanes = 2
        SPEC_DRAFT = 3

        def max_chunk(self):
            return 4

        def check_spec_drafts(self, drafts):
            want = (2, 4)
            if getattr(drafts, "shape", None) != want:
                raise ValueError(f"spec drafts shape != {want}")

        def check_spec_pipelined_dispatch(self, drafts, reseed,
                                          positions=None, g_states=None):
            self.check_spec_drafts(drafts)

    root = mh.RootControlEngine(_Eng(), _Plane())
    z = np.zeros(2, np.int32)
    bad = np.zeros((2, 3), np.int32)
    with pytest.raises(ValueError, match="drafts shape"):
        root.decode_spec_pipelined(z, bad, z, tokens=z)
    good = np.zeros((2, 4), np.int32)
    with pytest.raises(ValueError, match="outside"):
        root.decode_spec_prefill_fused(z, good, z, chunk=[1] * 9, tokens=z)
    with pytest.raises(ValueError, match="drafts shape"):
        root.decode_spec_prefill_fused(z, bad, z, chunk=[1, 2], tokens=z)
    assert sent == []  # nothing was broadcast


# ---------------------------------------------------------------------------
# SpecStream accounting (the leak fix, CLI side)
# ---------------------------------------------------------------------------


def test_spec_accepted_counter_survives_retraction():
    """dllama_spec_accepted_total stays monotone AND does not re-count
    retracted tokens: a partial spec_emitted dip (discard_pending's
    retraction) keeps the high-water baseline, so the next rise counts
    only genuinely new consumption; a drop to 0 (stats reset) re-baselines
    like the other delta-fed counters."""
    from distributed_llama_multiusers_tpu.telemetry import Telemetry

    tel = Telemetry()

    def counter_value():
        for line in tel.registry.render().splitlines():
            if line.startswith("dllama_spec_accepted_total "):
                return float(line.split()[-1])
        return 0.0

    tel.bridge_stats({"spec_emitted": 10})
    assert counter_value() == 10
    tel.bridge_stats({"spec_emitted": 8})  # retraction: no change
    assert counter_value() == 10
    tel.bridge_stats({"spec_emitted": 12})  # only past the high water
    assert counter_value() == 12
    tel.bridge_stats({"spec_emitted": 0})  # window reset: re-baseline
    tel.bridge_stats({"spec_emitted": 3})
    assert counter_value() == 15


def test_specstream_discard_pending_retracts_partial_step(loaded):
    """A turn ending with unconsumed lookahead RETRACTS the partially
    consumed verify step from the acceptance counters: the /stats ratio
    (emitted per drafted lane-step, class [1, K+1]) aggregates only
    fully realized steps — a discard can neither deflate it nor strand
    a dangling lane-step."""
    from distributed_llama_multiusers_tpu.runtime.spec import SpecStream

    config, params, tok = loaded
    prompt = tok.encode("aa bb aa bb aa bb aa bb")
    engine = _fresh_engine(config, params, n_lanes=1)
    _, g0, pos = engine.prefill(0, prompt)
    engine.stats.reset()
    spec = SpecStream(engine, config, enabled=True, prompt_tokens=prompt)
    cur = int(g0)
    # advance until a verify actually leaves lookahead pending
    for _ in range(32):
        nxt, _ = spec.advance(cur, pos)
        pos += 1
        cur = nxt
        if spec.pending:
            break
    assert spec.pending, "speculation never left a lookahead pending"
    before = engine.stats.snapshot()
    assert before["spec_lane_steps"] >= 1
    spec.discard_pending()
    after = engine.stats.snapshot()
    # the partially consumed step is gone from BOTH counters
    assert after["spec_lane_steps"] == before["spec_lane_steps"] - 1
    assert after["spec_emitted"] < before["spec_emitted"]
    assert spec.pending == [] and spec._pending_consumed == 0
    # ratio class: emitted >= lane_steps (>= 1 token per counted step)
    assert after["spec_emitted"] >= after["spec_lane_steps"]
