"""The route check on a per-lane state that no step can rewrite.

A toy engine in plain numpy whose only per-lane state is a running sum,
``h = A * h + E[token]`` with logits ``W h``: what a state-space layer keeps,
with no positions to write over. The check (`correct.route_check`) reads 0, 0, 0
on it, steps no lane twice over one position, and fails when the toy steps a
parked lane, when its fused step absorbs a bucket's padded tail or restarts
the admitted lane's state at a second chunk, or when its admissions are
swapped. (The check holds the fused program against the synchronous one; a
fault that both share is the logits' part's to catch, against the reference.)
A `model_config` PR for a recurrent family can try its `lane_state_rel_err`
here before the chip.
"""
from collections import Counter, deque
from types import SimpleNamespace

import numpy as np
import pytest

from harness import correct

VOCAB, DIM, A = 97, 12, 0.98
LIMITS = {"route_greedy_gap": 0.25, "route_nucleus_excess": 0.01, "route_kv_rel_err": 0.01}
CFG = {"vocab_size": VOCAB,
       "correctness": {"sampler": {"temperature": 0.7, "top_p": 0.9}, "route_admits": [1, 2]}}


class ToyEngine:
    """The methods `route_check` calls, with the program's meanings: a lane at
    ``seq_len`` is parked, -1 reads the carried position, a fused step's extra
    column is the admitted chunk's boundary pair. ``fault`` breaks one rule:
    "step_parked" in every step, the other two in the fused step alone."""

    def __init__(self, seed, n_lanes=12, seq_len=256, buckets=(16, 64), fault=None):
        rng = np.random.default_rng(seed)
        self.E, self.W = rng.normal(size=(VOCAB, DIM)), rng.normal(size=(VOCAB, DIM))
        self.n_lanes, self.config, self.pipeline_depth = n_lanes, SimpleNamespace(seq_len=seq_len), 2
        self.prefill_buckets, self.fault = buckets, fault
        self.h = rng.normal(size=(n_lanes, DIM))  # what earlier requests left behind
        self.stepped = Counter()                  # (lane, position) -> times absorbed
        self.ring, self.carry = deque(), None

    def _absorb(self, lane, pos, token):
        self.h[lane] = A * self.h[lane] + self.E[token]
        self.stepped[lane, pos] += 1

    def _choose(self, row, temp, topp, seed, pos):
        greedy = int(np.argmax(row))
        if temp == 0.0:
            return greedy, greedy
        order = np.argsort(-row, kind="stable")
        p = np.exp((row[order] - row[order[0]]) / temp)
        p /= p.sum()
        p = np.where(np.cumsum(p) - p < topp, p, 0.0)
        draw = np.random.default_rng([int(seed), int(pos)]).choice(VOCAB, p=p / p.sum())
        return greedy, int(order[draw])

    def _chunk(self, lane, chunk, start, temp, topp, seed, fused=False):
        if start == 0 or (fused and self.fault == "restart_at_chunk"):
            self.h[lane] = 0.0
        bucket = next(b for b in self.prefill_buckets if len(chunk) <= b)
        padded = list(chunk) + [0] * (bucket - len(chunk))
        for j, token in enumerate(padded if fused and self.fault == "absorb_padding" else chunk):
            self._absorb(lane, start + j, token)
        row = self.W @ self.h[lane]
        return row, self._choose(row, temp, topp, seed, start + len(chunk) - 1)

    def _step(self, tokens, positions, temps, topps, seeds):
        logits = np.zeros((self.n_lanes, VOCAB))
        out = np.zeros((2, self.n_lanes), np.int64)
        for lane in range(self.n_lanes):
            if positions[lane] < self.config.seq_len or self.fault == "step_parked":
                self._absorb(lane, int(positions[lane]), int(tokens[lane]))
            logits[lane] = self.W @ self.h[lane]
            out[:, lane] = self._choose(logits[lane], temps[lane], topps[lane],
                                        seeds[lane], positions[lane])
        return logits, out

    def prefill(self, lane, tokens, start_pos=0):
        assert len(tokens) <= self.prefill_buckets[-1]
        row, (greedy, _sampled) = self._chunk(lane, tokens, start_pos, 0.0, 1.0, 0)
        return row, greedy, start_pos + len(tokens)

    def decode(self, tokens, positions, temps, topps, seeds, want_logits=True):
        logits, out = self._step(tokens, positions, temps, topps, seeds)
        return logits, out[0], out[1]

    def decode_pipelined(self, positions, temps, topps, seeds, tokens=None, admitted=None):
        assert len(self.ring) < self.pipeline_depth and (tokens is None) == (self.carry is not None)
        feed, carried = (tokens, None) if tokens is not None else self.carry
        assert tokens is None or positions.min() >= 0
        pos = np.where(positions < 0, carried, positions) if carried is not None else positions
        _logits, out = self._step(feed, pos, temps, topps, seeds)
        nxt = np.where(temps == 0.0, out[0], out[1])
        new_pos = np.minimum(pos + 1, self.config.seq_len)
        if admitted is not None:
            lane, boundary, p_temp, p_end = admitted
            nxt[lane], new_pos[lane] = boundary[0] if p_temp == 0.0 else boundary[1], p_end
            out = np.concatenate([out, np.asarray(boundary)[:, None]], axis=1)
        self.carry = (nxt, new_pos)
        self.ring.append((out[0], out[1]))

    def decode_prefill_fused(self, positions, temps, topps, seeds, p_lane, chunk, p_start,
                             p_temp, p_topp, p_seed):
        assert positions[p_lane] == self.config.seq_len  # the admitting lane parks
        _row, boundary = self._chunk(p_lane, chunk, p_start, p_temp, p_topp, p_seed, fused=True)
        self.decode_pipelined(positions, temps, topps, seeds,
                              admitted=(p_lane, boundary, p_temp, p_start + len(chunk)))

    def pipeline_consume(self):
        return self.ring.popleft()

    def pipeline_flush(self):
        self.ring.clear()
        self.carry = None


def lane_state_rel_err(engine, lane_x, lane_y, n):
    """The toy family's: nothing is kept by position, so the whole state."""
    x, y = engine.h[lane_x], engine.h[lane_y]
    return float(np.abs(x - y).max() / max(np.abs(y).max(), 1e-30))


FAMILY = SimpleNamespace(lane_state_rel_err=lane_state_rel_err)


def _prompts(seed):
    rng = np.random.default_rng([seed, 1])
    return [[int(t) for t in rng.integers(2, VOCAB, size=n)] for n in (20, 30, 60, 44)]


def _check(seed, fault=None, swap=False, **kw):
    engine = ToyEngine(seed, fault=fault, **kw)
    out = correct.route_check(FAMILY, CFG, engine, _prompts(seed), seed,
                              fault="swap_admits" if swap else None)
    return out, engine


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_007])
def test_a_sound_recurrent_engine_reads_zero_and_no_lane_is_stepped_twice(seed):
    out, engine = _check(seed)
    assert [out[k] for k in LIMITS] == [0.0, 0.0, 0.0]
    assert out["route_token_mismatches"] == 0
    # 4 chain lanes x 5 steps, a's 3, b's 1, two boundary tokens; of them the
    # sampled: 2 chain lanes x 5, b's 1 and b's boundary
    assert out["route_tokens"] == (4 * 5 + 3 + 1 + 2) + (2 * 5 + 1 + 1)
    assert max(engine.stepped.values()) == 1, engine.stepped.most_common(3)
    # the chain's lanes, their twins, a, b and theirs: every lane was used
    assert {lane for lane, _pos in engine.stepped} == set(range(12))
    # every pair compared, and each has absorbed what the check says it has
    assert out["route_state_rel_errs"] == [0.0] * 6


def _over(out):
    """The route numbers that read over three times their limit, and
    "tokens" where the replay chose another token than the chain."""
    return ({k for k, limit in LIMITS.items() if out[k] > 3 * limit}
            | ({"tokens"} if out["route_token_mismatches"] else set()))


@pytest.mark.parametrize("fault", ["step_parked", "absorb_padding", "restart_at_chunk"])
@pytest.mark.parametrize("seed", [1, 2, 3_000_000_007])
def test_a_recurrent_engine_that_breaks_a_rule_is_not_correct(seed, fault):
    """The state is the number that has to catch each: a restart at the
    second chunk leaves b's three tokens of a 97-word toy as they were."""
    out, _engine = _check(seed, fault=fault)
    assert "route_kv_rel_err" in _over(out), out
    if fault == "step_parked":  # every twin was disturbed under the chain
        assert _over(out) >= {"route_greedy_gap", "route_nucleus_excess", "tokens"}, out


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_007])
def test_swapped_admissions_are_not_correct(seed):
    out, _engine = _check(seed, swap=True)
    assert _over(out) >= {"route_greedy_gap", "route_kv_rel_err", "tokens"}, out


def test_too_few_lanes_are_refused_by_name():
    with pytest.raises(ValueError, match="8 lanes, have 7"):
        _check(1, n_lanes=7)
    out, engine = _check(1, n_lanes=8)  # two chain lanes
    assert out["route_tokens"] == (2 * 5 + 3 + 1 + 2) + (5 + 1 + 1)
    assert [out[k] for k in LIMITS] == [0.0, 0.0, 0.0]


def test_an_admission_is_cut_at_a_bucket():
    buckets = (16, 64, 256, 1024)
    for n, cut in ((150, 64), (60, 16), (2048, 1024), (20, 10)):  # 20: no bucket that small
        first, rest = correct.split_admission(list(range(n)), buckets)
        assert first + rest == list(range(n)) and len(first) == cut
