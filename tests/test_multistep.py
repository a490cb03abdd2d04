"""Multi-step decode (``engine.decode_multi`` + scheduler horizon).

The serving loop's dominant per-token cost is the host round-trip per decode
dispatch (the reference pays the same per-forward socket turnaround,
src/app.cpp:369-402). ``decode_multi`` chains h decode steps in one compiled
``lax.scan`` — the invariant under test is stream identity: multi-step must
emit EXACTLY the tokens single stepping would, for greedy AND device-sampled
lanes, including lanes that stop mid-horizon (their overshoot KV writes must
be unobservable afterwards — the chunked-prefill invariant).
"""

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
from distributed_llama_multiusers_tpu.tokenizer import Tokenizer


@pytest.fixture(scope="module")
def loaded(tiny_model):
    h = load_model_header(tiny_model["model"])
    config, params = load_params_from_m(tiny_model["model"], h, dtype=jnp.float32)
    tok = Tokenizer(tiny_model["tokenizer"])
    return config, params, tok


def _fresh_engine(config, params, n_lanes=2):
    return InferenceEngine(config, params, n_lanes=n_lanes, prefill_buckets=(4,))


def test_decode_multi_matches_single_steps(loaded):
    """h chained steps emit the exact token sequence of h single steps, for
    a greedy lane and a device-sampled lane together, and leave the engine
    in a state that continues identically."""
    config, params, _ = loaded
    prompt = [5, 9, 3]
    h = 4
    temps = np.asarray([0.0, 0.8], np.float32)
    topps = np.full(2, 0.9, np.float32)
    seeds = np.asarray([0, 123], np.uint32)

    def rollout(engine, n_steps, multi):
        _, g0, pos = engine.prefill(0, prompt)
        _, g1, _ = engine.prefill(1, prompt)
        toks = np.asarray([g0, g1], np.int32)
        out = [toks.copy()]
        positions = np.asarray([pos, pos], np.int32)
        if multi:
            for _ in range(n_steps // h):
                chosen = engine.decode_multi(
                    toks, positions, temps, topps, seeds, h
                )
                for j in range(h):
                    out.append(chosen[j].copy())
                toks = chosen[h - 1].astype(np.int32)
                positions = positions + h
        else:
            for _ in range(n_steps):
                _, greedy, sampled = engine.decode(
                    toks, positions, temps, topps, seeds
                )
                toks = np.where(temps == 0.0, greedy, sampled).astype(np.int32)
                out.append(toks.copy())
                positions = positions + 1
        return np.stack(out)

    single = rollout(_fresh_engine(config, params), 8, multi=False)
    multi = rollout(_fresh_engine(config, params), 8, multi=True)
    np.testing.assert_array_equal(single, multi)
    eng = _fresh_engine(config, params)
    assert eng.stats.multi_dispatches == 0
    eng.decode_multi(np.zeros(2, np.int32), np.zeros(2, np.int32), h=2)
    assert eng.stats.multi_dispatches == 1
    assert eng.stats.decode_steps == 2


def _run_requests(config, params, tok, reqs_spec, multi_step, n_lanes=2):
    engine = _fresh_engine(config, params, n_lanes=n_lanes)
    # pipelined=False isolates the multi-step horizon (the pipelined path
    # would otherwise win the steady-state gate; its own stream-identity
    # tests live in test_pipelined_decode.py)
    sched = ContinuousBatchingScheduler(
        engine, tok, speculative=False, prefix_min_tokens=0,
        multi_step=multi_step, pipelined=False,
    )
    reqs = [
        Request(prompt=p, max_tokens=m, temperature=t, seed=s)
        for (p, m, t, s) in reqs_spec
    ]
    sched.start()
    try:
        for r in reqs:
            sched.submit(r)
        for r in reqs:
            r.future.result(timeout=300)
    finally:
        sched.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return [list(r.generated_tokens) for r in reqs], engine.stats


def test_scheduler_multi_step_stream_identity(loaded):
    """The serving loop with a multi-step horizon produces EXACTLY the
    single-step token streams — greedy and sampled lanes, different
    max_tokens so one lane finishes mid-horizon and its overshoot is
    discarded."""
    config, params, tok = loaded
    spec = [
        ("hello world", 13, 0.0, None),   # greedy, finishes mid-horizon
        ("other prompt", 24, 0.8, 42),    # device-sampled, seeded
    ]
    base, base_stats = _run_requests(config, params, tok, spec, multi_step=0)
    multi, stats = _run_requests(config, params, tok, spec, multi_step=4)
    assert multi == base
    assert stats.multi_dispatches > 0  # the horizon actually engaged
    assert base_stats.multi_dispatches == 0


def test_multi_step_overshoot_does_not_corrupt_prefix_reuse(loaded):
    """A lane that finished mid-horizon holds junk KV past its consumed
    tokens; a later request prefix-reusing that lane must still decode the
    cold-prefill stream (the claimed prefix covers only consumed tokens,
    and junk slots are rewritten before any query reads them)."""
    config, params, tok = loaded
    # > prefix_min_tokens tokens but well under the tiny model's seq_len
    # (an over-long prompt truncates to a max_tokens-dependent TAIL, which
    # destroys the common prefix between the two requests)
    prompt = "shared prefix for reuse "

    def run(prefix_min, multi_step):
        engine = _fresh_engine(config, params, n_lanes=2)
        sched = ContinuousBatchingScheduler(
            engine, tok, speculative=False, prefix_min_tokens=prefix_min,
            multi_step=multi_step, pipelined=False,
        )
        sched.start()
        try:
            a = sched.submit(Request(prompt=prompt, max_tokens=9))
            a.future.result(timeout=300)
            b = sched.submit(Request(prompt=prompt, max_tokens=16))
            b.future.result(timeout=300)
        finally:
            sched.stop()
        assert a.error is None and b.error is None
        return list(b.generated_tokens), engine.stats.prefix_hits

    cold, _ = run(prefix_min=0, multi_step=4)
    warm, hits = run(prefix_min=4, multi_step=4)
    assert hits >= 1  # the second request actually reused lane KV
    assert warm == cold


def test_horizon_gating(loaded):
    """The horizon engages only in steady state: host-exact lanes, queued
    admissions, or a 1-token remainder force single stepping."""
    config, params, tok = loaded
    engine = _fresh_engine(config, params)
    sched = ContinuousBatchingScheduler(
        engine, tok, speculative=False, prefix_min_tokens=0, multi_step=8
    )

    class _L:
        def __init__(self, host_exact, temp, gen, pos, max_tokens):
            class _R:
                temperature = temp
                max_tokens = 0
                generated_tokens = []
            self.request = _R()
            self.request.max_tokens = max_tokens
            # `gen` tokens committed; a generating lane's next_token is
            # streamed (counted) before the step that commits it (PR 57)
            self.request.generated_tokens = [0] * (gen + 1)
            self.host_exact = host_exact
            self.pos = pos

    active = [(0, _L(False, 0.0, 0, 10, 100))]
    assert sched._multi_horizon(active, prefilled=False) == 8
    assert sched._multi_horizon(active, prefilled=True) == 0
    # host-exact sampled lane disables the horizon
    hx = [(0, _L(True, 0.9, 0, 10, 100))]
    assert sched._multi_horizon(hx, prefilled=False) == 0
    # horizon capped by remaining budget, bucketed to powers of two
    short = [(0, _L(False, 0.0, 95, 10, 100))]  # 5 tokens left
    assert sched._multi_horizon(short, prefilled=False) == 4
    one = [(0, _L(False, 0.0, 99, 10, 100))]  # 1 token left
    assert sched._multi_horizon(one, prefilled=False) == 0
    # queued admission disables the horizon
    sched.queue.push(Request(prompt="x"))
    assert sched._multi_horizon(active, prefilled=False) == 0


def test_pod_packet_replays_decode_multi():
    """OP_DECODE_MULTI round-trips the horizon + all operand arrays through
    the control plane packet into the worker's engine.decode_multi."""
    from distributed_llama_multiusers_tpu.parallel import multihost as mh

    calls = []

    class _Eng:
        n_lanes = 2
        SPEC_DRAFT = 3

        class stats:
            @staticmethod
            def reset():
                pass

        def decode_multi(self, tokens, positions, temps, topps, seeds, h,
                         g_states=None):
            calls.append((
                np.asarray(tokens).tolist(), np.asarray(positions).tolist(),
                np.asarray(temps).tolist(), np.asarray(seeds).tolist(), h,
            ))
            return np.zeros((h, 2), np.int32)

    sent = []

    class _Plane(mh.ControlPlane):
        def __init__(self):
            super().__init__(n_lanes=2, chunk=8)

        def _bcast(self, pkt):
            sent.append(pkt.copy())
            return pkt

    plane = _Plane()
    plane.send_decode_multi(
        np.asarray([7, 9], np.int32), np.asarray([3, 4], np.int32),
        np.asarray([0.0, 0.8], np.float32), np.full(2, 0.9, np.float32),
        np.asarray([1, 2], np.uint32), h=4,
    )
    plane.send_stop()

    replay = iter(sent)

    class _ReplayPlane:
        def recv(self):
            return next(replay)

        def slot(self, pkt, i, n):
            return plane.slot(pkt, i, n)

    mh.worker_loop(_Eng(), _ReplayPlane())
    assert calls == [([7, 9], [3, 4], [0.0, pytest.approx(0.8)], [1, 2], 4)]
