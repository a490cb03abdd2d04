"""The one rule every per-lane state must keep (ROADMAP.md M1), written once
for every family whose cache holds a leaf that a step overwrites in place: a
conv's window, a running sum, a matrix state, a ring. Through
``InferenceEngine`` at the toys' size on the CPU, one module-scoped engine a
toy on the toy's one ladder:

- a parked lane keeps every leaf in every step family the block serves;
- a bucket's padded tail is ignored, and token by token is the same state;
- a second chunk continues the first, synchronous or fused;
- position zero reads zeros in a lane that served before;
- a fused admission splices every leaf of its lane and moves no other lane;
- verify steps, lane copies and a paged pool are refused by name.

A ``model_config`` PR adds ONE ROW to ``tests/latent_toy.py`` ``TOYS`` (the
rehearsal configuration's file, the names of the cache leaves that hold a
lane's state, the ladder and the cuts, the step families its block serves) and
these cases run over it, each (case, toy) pair a counted case. The family's own
file holds only what is its own: the compare against the plain reference, the
kernels in interpret mode, its counters and facts, its loader. Where a family
asserts more than this body (a leaf beside the state, an odd cut) the row
carries it."""

import functools

import numpy as np
import pytest

from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

import latent_toy
from latent_toy import park

ROWS = sorted(name for name, toy in latent_toy.TOYS.items() if toy.state)
BY_FAMILY = [(name, f) for name in ROWS for f in latent_toy.TOYS[name].families]


class Row:
    """A row's toy, its engine and what the cases read of both."""

    def __init__(self, name: str):
        self.toy = latent_toy.TOYS[name]
        self.cfg, self.family, _ = latent_toy.toy(name)
        self.eng, self.tensors = latent_toy.engine(
            self.family, self.cfg, seed=5, lanes=8, prefill_buckets=self.toy.ladder)
        self.seq = self.eng.config.seq_len
        self.prompt = [int(x) for x in np.random.default_rng(0).integers(
            2, self.cfg["vocab_size"], size=self.toy.lengths[-1])]
        # the family's comparison of two lanes that absorbed the same tokens
        self.rel_err = getattr(self.family, "lanes_rel_err", self.family.lane_state_rel_err)

    def leaves(self, lane: int, names=None) -> dict:
        names = names or self.toy.state + self.toy.kept
        return {n: np.asarray(getattr(self.eng.cache, n)[:, lane]) for n in names}

    def fill(self, lane: int, value: float):
        cache = self.eng.cache
        self.eng.cache = cache._replace(
            **{n: getattr(cache, n).at[:, lane].set(value) for n in self.toy.state})

    def admit_fused(self, lane: int, chunk, start: int = 0):
        """A fused admission beside parked lanes (the chain goes on: the
        caller flushes)."""
        n = self.eng.n_lanes
        kw = {} if start else {"tokens": np.zeros(n, np.int32)}
        self.eng.decode_prefill_fused(np.full(n, self.seq, np.int32), p_lane=lane,
                                      chunk=chunk, p_start=start, **kw)


@pytest.fixture(scope="module")
def rows():
    """One engine a toy, built when its first case asks."""
    return functools.cache(Row)


@pytest.mark.parametrize("toy,family", BY_FAMILY)
def test_a_parked_lane_keeps_every_leaf_in_every_step_family(rows, toy, family):
    r = rows(toy)
    eng, p, (short, other, _, _) = r.eng, r.prompt, r.toy.lengths
    eng.prefill(0, p[:short])
    eng.prefill(1, p[:other])
    before, live_before = r.leaves(1), r.leaves(0, r.toy.state)
    # (a token a family: a ring's row written twice with one token does not move)
    tokens, positions = park(eng, {0: (5 + latent_toy.STEP_FAMILIES.index(family), short)})
    if family == "decode":
        eng.decode(tokens, positions)
    elif family == "decode_nologits":
        eng.decode(tokens, positions, want_logits=False)
    elif family == "decode_multi":
        eng.decode_multi(tokens, positions, h=2)
    elif family == "decode_pl":
        eng.decode_pipelined(positions, tokens=tokens)
        eng.decode_pipelined(np.where(positions < r.seq, -1, positions).astype(np.int32))
        eng.pipeline_flush()
    else:
        eng.decode_prefill_fused(positions, p_lane=2, chunk=p[:10], tokens=tokens)
        eng.pipeline_flush()
    for name, leaf in r.leaves(1).items():
        np.testing.assert_array_equal(leaf, before[name], err_msg=name)
    for name, leaf in r.leaves(0, r.toy.state).items():  # the live lane moved
        assert not np.array_equal(leaf, live_before[name]), name
        assert not np.array_equal(leaf, before[name]), name


@pytest.mark.parametrize("toy", ROWS)
def test_a_padded_tail_is_ignored_and_token_by_token_is_the_same_state(rows, toy):
    """A short prompt through a bucket's padded rows against the same tokens
    one decode step each, and as a fused admission: other programs, the same
    state in every leaf."""
    r = rows(toy)
    eng, p, short = r.eng, r.prompt, r.toy.lengths[0]
    eng.prefill(0, p[:short])
    for i, tok in enumerate(p[:short]):
        eng.decode(*park(eng, {1: (tok, i)}))
    assert r.rel_err(eng, 0, 1, short) < 1e-5
    r.admit_fused(2, p[:short])
    eng.pipeline_flush()
    assert r.rel_err(eng, 0, 2, short) < 1e-5
    if r.toy.pad:  # a state that absorbed the padding would differ throughout
        eng.prefill(3, p[:short] + [0] * r.toy.pad)
        assert r.rel_err(eng, 0, 3, short) > 1e-3


@pytest.mark.parametrize("toy", ROWS)
def test_a_second_chunk_continues_the_first(rows, toy):
    r = rows(toy)
    eng, p, t = r.eng, r.prompt, r.toy
    _, _, mid, long = t.lengths
    eng.prefill(0, p[:long])  # in chunks of the ladder's widest, a padded tail
    eng.prefill(1, p[:t.cut])
    eng.prefill(1, p[t.cut:long], start_pos=t.cut)
    assert r.rel_err(eng, 0, 1, long) < 1e-5
    r.admit_fused(2, p[:t.fused_cut])
    r.admit_fused(2, p[t.fused_cut:mid], start=t.fused_cut)  # parked between
    eng.pipeline_flush()
    eng.prefill(3, p[:mid])
    assert r.rel_err(eng, 3, 2, mid) < 1e-5
    if t.restart_differs:  # a second chunk that restarted from zero is another state
        eng.prefill(4, p[t.fused_cut:mid])
        assert r.rel_err(eng, 3, 4, 1) > 1e-3
        for name, leaf in r.leaves(3, t.state).items():
            assert not np.array_equal(leaf, r.leaves(4)[name]), name


@pytest.mark.parametrize("toy", ROWS)
def test_position_zero_reads_zeros_in_a_lane_that_served_before(rows, toy):
    r = rows(toy)
    eng, p, t = r.eng, r.prompt, r.toy
    short, _, mid, _ = t.lengths
    eng.prefill(4, p[short:mid])  # what an earlier request left behind
    dirty = r.leaves(4, t.state)
    zero_starts = eng.stats.state_zero_starts
    first, _, _ = eng.prefill(4, p[:short])
    eng.prefill(5, p[mid:mid + 10])
    r.fill(5, 0.0)  # a lane never used
    fresh, _, _ = eng.prefill(5, p[:short])
    assert eng.stats.state_zero_starts == zero_starts + 3
    np.testing.assert_array_equal(np.asarray(first), np.asarray(fresh))
    assert r.rel_err(eng, 4, 5, short) == 0
    for name, leaf in r.leaves(4, t.state).items():
        if not t.stale_rows:  # (a ring keeps the rows no step reads again)
            np.testing.assert_array_equal(leaf, r.leaves(5)[name], err_msg=name)
        assert not np.array_equal(leaf, dirty[name]), name
    # a decode step at position 0 starts a sequence too
    r.fill(6, 3.0)
    eng.decode(*park(eng, {6: (9, 0), 7: (9, 0)}))
    assert r.rel_err(eng, 6, 7, 1) == 0
    if not t.stale_rows:
        for name, leaf in r.leaves(6, t.state).items():
            np.testing.assert_array_equal(leaf, r.leaves(7)[name], err_msg=name)


@pytest.mark.parametrize("toy", ROWS)
def test_a_lane_taken_out_and_put_back_carries_every_leaf(rows, toy):
    """The fused step's splice of the admitted lane: its rows of every leaf
    after a fused admission are the rows a synchronous prefill writes, and no
    other lane's rows moved."""
    r = rows(toy)
    eng, p = r.eng, r.prompt
    n = min(r.toy.lengths[2], r.toy.ladder[-1])
    eng.prefill(5, p[:50])
    others = r.leaves(5)
    eng.prefill(3, p[:n])
    r.admit_fused(2, p[:n])
    eng.pipeline_flush()
    assert r.rel_err(eng, 3, 2, n) < 1e-5
    for name, leaf in r.leaves(5).items():
        np.testing.assert_array_equal(leaf, others[name], err_msg=name)


@pytest.mark.parametrize("toy", ROWS)
def test_verify_steps_and_lane_copies_are_refused(rows, toy):
    r = rows(toy)
    eng, n = r.eng, r.eng.n_lanes
    z = np.zeros(n, np.int32)
    assert eng.config.recurrent_state and not eng.supports_speculative
    with pytest.raises(ValueError, match="without speculation"):
        eng.decode_spec(z, np.zeros((n, eng.SPEC_DRAFT), np.int32), z, z)
    with pytest.raises(ValueError, match="without speculation"):
        eng.decode_spec_pipelined(z, np.zeros((n, eng.SPEC_DRAFT + 1), np.int32), z, tokens=z)
    with pytest.raises(RuntimeError, match="recurrent state"):
        eng.copy_lane(0, 1)
    before = r.leaves(2)
    eng.copy_lane(2, 2)  # nothing moves
    for name, leaf in r.leaves(2).items():
        np.testing.assert_array_equal(leaf, before[name], err_msg=name)
    with pytest.raises(ValueError, match=r.toy.refuses_paged):
        InferenceEngine(eng.config, eng.params, n_lanes=4, paged_kv=True)
