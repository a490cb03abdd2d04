"""Numerics parity: the EXACT on-device top-p sampler vs the host
``Sampler`` (tokenizer/sampler.py) over a seeded (temperature, top_p) grid.

PINNED NUMERICS CLASS (the contract this file enforces):

- SUPPORT-EXACT: the device sampler's nucleus — p = softmax(row / temp)
  over the whole vocabulary; a token is kept iff the mass strictly ahead
  of it (larger logits, and equal logits of a lower token id) is under
  top_p, i.e. up to and including the crossing token. The device finds
  that set WITHOUT an order (``nucleus_keep``: the kept set is
  ``z >= v*`` for the least value v* of the row whose mass above is under
  top_p, by bisection over the floats' bit patterns; ties at v* by token
  id) — and it equals the host Sampler's exact nucleus, and the set the
  full stable descending sort + cumulative sum of builds before PR 34
  gave (``_sorted_keep`` below is that code), for every (temp, topp) in
  the grid, including topp <= 0 / >= 1 (both samplers define those as
  full-vocab multinomial: every token with p > 0) and the old
  HOST_EXACT_TOPP / HOST_EXACT_TEMP routing boundaries, which no longer
  route anywhere: every draw from either sampler lands inside that set.
- DISTRIBUTION: probabilities are the same f32 softmax on both sides;
  empirical frequencies agree with the analytic distribution (loose
  total-variation bound — this is a smoke bound, not a statistical
  proof).
- RNG STREAMS DIFFER BY CONSTRUCTION: fold_in(seed, pos) + categorical
  on device vs xorshift64* on host — token-for-token equality between
  the two samplers is NOT part of the class and is never asserted (nor
  between builds: since PR 34 the device's noise belongs to a token's id,
  before it to the token's rank in the sort).
  What IS asserted: the device draw is deterministic per (seed, pos),
  so seeded serving runs reproduce, and the device sampler equals
  itself across the sync/pipelined scheduler paths (pinned by the
  stream-identity tests in test_pipelined_decode.py /
  test_spec_pipelined.py).
"""

import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from distributed_llama_multiusers_tpu.formats import load_model_header
from distributed_llama_multiusers_tpu.models import load_params_from_m
from distributed_llama_multiusers_tpu.runtime import InferenceEngine
from distributed_llama_multiusers_tpu.runtime import engine as engine_mod
from distributed_llama_multiusers_tpu.runtime.engine import (
    _sample_lane,
    nucleus_keep,
    sample_lanes,
    sampler_group,
)
from distributed_llama_multiusers_tpu.runtime.scheduler import (
    HOST_EXACT_TEMP,
    HOST_EXACT_TOPP,
)
from distributed_llama_multiusers_tpu.tokenizer.sampler import Sampler


@pytest.fixture(scope="module")
def engine(tiny_model):
    h = load_model_header(tiny_model["model"])
    config, params = load_params_from_m(tiny_model["model"], h,
                                        dtype=jnp.float32)
    return InferenceEngine(config, params, n_lanes=1, prefill_buckets=(4,))


def _logits(vocab, seed=11):
    rng = np.random.default_rng(seed)
    # well-separated values: no nucleus-boundary ties for f32-vs-f64
    # cumsum order to disagree on (the documented edge of the class)
    return rng.permutation(np.linspace(-4.0, 4.0, vocab)).astype(np.float32)


def _host_nucleus(logits, temp, topp):
    """The host Sampler's exact kept set (src/tokenizer.cpp:416-457
    semantics): softmax, stable sort desc, keep through the first token
    whose cumulative crosses topp; topp <= 0 / >= 1 keep everything."""
    x = logits.astype(np.float32) / np.float32(temp)
    x = x - x.max()
    p = np.exp(x, dtype=np.float32)
    p /= p.sum(dtype=np.float32)
    if topp <= 0 or topp >= 1:
        return set(np.nonzero(p > 0)[0].tolist()), p
    order = np.argsort(-p, kind="stable")
    csum = np.cumsum(p[order], dtype=np.float64)
    over = np.nonzero(csum > topp)[0]
    last = int(over[0]) if len(over) else len(order) - 1
    return set(order[: last + 1].tolist()), p


GRID = [
    (0.2, 0.3),
    (0.7, 0.9),
    (0.8, 1.0),          # wide nucleus: full multinomial
    (0.8, 0.0),          # topp <= 0: both samplers define as full-vocab
    (0.8, -0.5),         # negative topp: same rule
    (0.8, HOST_EXACT_TOPP),   # the old host-exact routing boundary
    (HOST_EXACT_TEMP, 0.9),   # the old high-temp routing boundary
    (2.0, 0.5),
]


def test_device_draws_stay_in_exact_nucleus(engine):
    """Every device draw lands in the host Sampler's exact nucleus, for
    every grid point — the support-exactness half of the pinned class
    (the old top-k sampler violated this for wide nuclei, which is why
    host-exact routing existed)."""
    vocab = engine.config.vocab_size
    logits = _logits(vocab)
    for temp, topp in GRID:
        nucleus, _ = _host_nucleus(logits, temp, topp)
        draws = {
            engine.sample_token(logits, temp, topp, seed, pos)
            for seed in (1, 2, 3, 4, 5)
            for pos in range(10)
        }
        assert draws <= nucleus, (
            f"device draw outside the exact nucleus at temp={temp}, "
            f"topp={topp}: {sorted(draws - nucleus)}"
        )


def test_host_draws_stay_in_same_nucleus(engine):
    """The host Sampler's own draws land in the same analytic nucleus —
    i.e. the set both samplers are being held to IS the host's."""
    vocab = engine.config.vocab_size
    logits = _logits(vocab)
    for temp, topp in GRID:
        nucleus, _ = _host_nucleus(logits, temp, topp)
        s = Sampler(vocab, temp, topp, 42)
        draws = {s.sample(logits) for _ in range(50)}
        assert draws <= nucleus, (temp, topp, sorted(draws - nucleus))


def test_device_sampler_deterministic_per_seed_pos(engine):
    """Same (seed, pos) -> same token; different pos -> a fresh draw from
    the same stream (fold_in). Seeded serving runs reproduce."""
    logits = _logits(engine.config.vocab_size)
    a = [engine.sample_token(logits, 0.9, 0.95, 123, p) for p in range(20)]
    b = [engine.sample_token(logits, 0.9, 0.95, 123, p) for p in range(20)]
    assert a == b
    assert len(set(a)) > 1  # the position folds into the stream


def test_device_temp0_equals_host_greedy(engine):
    """temp == 0 is argmax on both sides — bit-equal, no RNG involved."""
    logits = _logits(engine.config.vocab_size)
    host = Sampler(engine.config.vocab_size, 0.0, 0.9, 7)
    assert engine.sample_token(logits, 0.0, 0.9, 7, 0) == host.sample(logits)


def test_device_frequencies_match_analytic_distribution(engine):
    """Distributional half of the pinned class: empirical device
    frequencies track the analytic f32-softmax nucleus distribution
    (loose total-variation smoke bound over a narrow nucleus, where a
    truncated sampler would be visibly wrong)."""
    vocab = engine.config.vocab_size
    logits = _logits(vocab)
    temp, topp = 0.7, 0.9
    nucleus, p = _host_nucleus(logits, temp, topp)
    keep = np.zeros(vocab)
    keep[list(nucleus)] = 1
    q = p * keep
    q /= q.sum()
    n = 1200
    counts = np.zeros(vocab)
    for seed in range(n):
        counts[engine.sample_token(logits, temp, topp, seed, seed % 7)] += 1
    emp = counts / n
    tv = 0.5 * np.abs(emp - q).sum()
    assert tv < 0.12, f"total variation {tv:.3f} vs analytic nucleus dist"


def test_wide_nucleus_tail_actually_reachable(engine):
    """The regression the exact sampler fixes: at topp=1.0 every token
    with meaningful mass is reachable — including tokens far past any
    fixed top-k cutoff. (With vocab > 64 = the old device_topk default,
    the truncated sampler could never emit rank-65+.)"""
    vocab = engine.config.vocab_size
    assert vocab > 64, "tiny model vocab must exceed the old top-k"
    # near-flat logits at high temp: substantial mass beyond rank 64
    logits = _logits(vocab)
    ranks = np.argsort(-logits)
    tail = set(ranks[64:].tolist())
    hit_tail = any(
        engine.sample_token(logits, 2.0, 1.0, seed, 0) in tail
        for seed in range(200)
    )
    assert hit_tail, "no draw ever reached past the old top-64 truncation"


# ---------------------------------------------------------------------------
# the kept set of the threshold search against the sort it replaced
# ---------------------------------------------------------------------------

QWEN_VOCAB = 152064


@jax.jit
def _sorted_keep(row, temp, topp):
    """The kept set as builds before PR 34 computed it (the deleted body of
    ``_sample_lane``): a total stable descending sort of the row with its
    indices, softmax, cumulative sum, ``(csum - p) < topp``. Its ``topp``
    <= 0 / >= 1 branch compared a rounded float32 sum with 1.0 and dropped
    tokens of the tail, so those points are held to the contract instead
    (``test_full_vocab_topp_keeps_every_token_with_mass``)."""
    vals, idx = jax.lax.top_k(row, row.shape[0])
    p = jax.nn.softmax(vals.astype(jnp.float32) / jnp.maximum(temp, 1e-6))
    keep = (jnp.cumsum(p) - p) < topp
    return jnp.zeros(row.shape, bool).at[idx].set(keep)


@jax.jit
def _searched_keep(row, temp, topp):
    z = row.astype(jnp.float32) / jnp.maximum(temp, 1e-6)
    return nucleus_keep(z, topp)


def _bf16_row(vocab, seed):
    """Logits as the head's kernel writes them: normal at the benchmark's
    gain, rounded to bfloat16, so values repeat and a tie group of tens of
    tokens straddles the nucleus's edge."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(vocab) * 1.78, jnp.float32)
    return np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32))


def _masked_row(vocab, seed):
    """A grammar-masked row: most tokens -inf, the legal ones spread."""
    rng = np.random.default_rng(seed)
    row = np.full(vocab, -np.inf, np.float32)
    legal = rng.choice(vocab, size=max(3, vocab // 7), replace=False)
    row[legal] = rng.standard_normal(len(legal)).astype(np.float32) * 2.0
    return row


ROWS = {
    "separated": lambda: _logits(4096),
    "bf16_ties": lambda: _bf16_row(32768, 3),
    "grammar_masked": lambda: _masked_row(4096, 5),
}
PARTIAL_GRID = [g for g in GRID if 0.0 < g[1] < 1.0]


@pytest.mark.parametrize("temp,topp", PARTIAL_GRID)
@pytest.mark.parametrize("kind", sorted(ROWS))
def test_searched_nucleus_equals_the_sorted_one(kind, temp, topp):
    """(a), (b), (c): the search's kept set IS the stable sort's, token
    for token, on a well-separated row, on a row of bfloat16 values whose
    tie group straddles the edge, and on a row with -inf entries."""
    row = ROWS[kind]()
    want = np.asarray(_sorted_keep(row, np.float32(temp), np.float32(topp)))
    got = np.asarray(_searched_keep(row, np.float32(temp), np.float32(topp)))
    assert got.sum() >= 1 and not got[~np.isfinite(row)].any()
    assert np.array_equal(got, want), (
        f"{kind} temp={temp} topp={topp}: search keeps {got.sum()}, sort "
        f"{want.sum()}, {np.sum(got != want)} tokens differ"
    )
    host, _ = _host_nucleus(row, temp, topp)
    assert len(host ^ set(np.nonzero(got)[0].tolist())) <= 2  # f32 vs f64 sums


@pytest.mark.parametrize("temp,topp", [(0.7, 0.9), (0.8, 0.95), (2.0, 0.5)])
def test_tie_group_at_the_edge_keeps_its_lowest_ids(temp, topp):
    """(b) spelled out: the edge of the nucleus falls INSIDE a group of equal
    logits; of that group exactly the lowest token ids are kept, as many as
    the stable sort keeps, and every token above the group is."""
    row = _bf16_row(32768, 3)
    got = np.asarray(_searched_keep(row, np.float32(temp), np.float32(topp)))
    edge = row[got].min()
    group = np.nonzero(row == edge)[0]
    kept = got[group]
    assert 0 < kept.sum() < len(group), "pick a row whose tie group straddles"
    assert kept[: kept.sum()].all() and not kept[kept.sum():].any()
    assert got[row > edge].all() and not got[row < edge].any()
    want = np.asarray(_sorted_keep(row, np.float32(temp), np.float32(topp)))
    assert want[group].sum() == kept.sum()


@pytest.mark.parametrize("topp", [1.0, 0.0, -0.5])
@pytest.mark.parametrize("kind", ["normal", "bf16", "grammar_masked"])
def test_full_vocab_topp_keeps_every_token_with_mass(kind, topp):
    """(d): topp >= 1 or <= 0 is the full-vocabulary multinomial at Qwen's
    152064 tokens: EVERY token with p > 0 is kept. (The sorted form compared
    an f32 running sum with 1.0 and lost 80-320 tokens of the tail in half
    the rows: the control below shows the reference failing where it does.)"""
    rng = np.random.default_rng(17)
    row = {
        "normal": lambda: (rng.standard_normal(QWEN_VOCAB) * 1.78).astype(np.float32),
        "bf16": lambda: _bf16_row(QWEN_VOCAB, 17),
        "grammar_masked": lambda: _masked_row(QWEN_VOCAB, 17),
    }[kind]()
    got = np.asarray(_searched_keep(row, np.float32(0.7), np.float32(topp)))
    nucleus, p = _host_nucleus(row, 0.7, topp)
    assert set(np.nonzero(got)[0].tolist()) == nucleus
    assert got.sum() == np.sum(p > 0) >= QWEN_VOCAB // 8


def test_sorted_form_dropped_tail_tokens_at_topp_one():
    """The control of (d): on some row of 152064 tokens the old form, fed
    the 1.0 it substituted for topp >= 1, keeps fewer tokens than have
    mass; the search keeps them all on the same row."""
    lost = 0
    for seed in range(4):
        row = (np.random.default_rng(seed).standard_normal(QWEN_VOCAB)
               * 1.78).astype(np.float32)
        old = np.asarray(_sorted_keep(row, np.float32(0.7), np.float32(1.0)))
        new = np.asarray(_searched_keep(row, np.float32(0.7), np.float32(1.0)))
        assert new.all()
        lost += int(QWEN_VOCAB - old.sum())
    assert lost > 0


@pytest.mark.parametrize("vocab", [96, 4096, QWEN_VOCAB])
def test_one_token_nucleus(vocab):
    """(e): a cold temperature and a small topp keep the argmax alone."""
    row = _logits(vocab)
    row[np.argmax(row)] += 2.0   # one token holds over a third of the mass
    got = np.asarray(_searched_keep(row, np.float32(0.2), np.float32(0.3)))
    want = np.asarray(_sorted_keep(row, np.float32(0.2), np.float32(0.3)))
    assert np.array_equal(got, want)
    assert got.sum() == 1 and got[np.argmax(row)]


def test_all_equal_row_is_cut_by_token_id():
    """Every logit equal: one tie group holds the whole row, and the nucleus
    is its first ceil(topp * V) ids (the running count, not the values,
    decides)."""
    got = np.asarray(_searched_keep(np.zeros(1000, np.float32),
                                    np.float32(1.0), np.float32(0.25)))
    assert got[:250].all() and not got[251:].any()


def test_device_draws_cover_a_tie_group_only_up_to_the_edge(engine):
    """End to end through ``engine.sample_token``: with a row of two values
    the draws reach exactly the tokens the sorted nucleus holds."""
    vocab = engine.config.vocab_size
    row = np.where(np.arange(vocab) % 2 == 0, 1.0, 0.0).astype(np.float32)
    want = np.asarray(_sorted_keep(row, np.float32(1.0), np.float32(0.8)))
    draws = {engine.sample_token(row, 1.0, 0.8, seed, 0) for seed in range(400)}
    assert draws <= set(np.nonzero(want)[0].tolist())
    assert len(draws) > want.sum() // 2


# ---------------------------------------------------------------------------
# the sampler over groups of lanes (PR 44): same tokens as all lanes at once
# ---------------------------------------------------------------------------


def _lane_operands(kind, n):
    """``n`` lanes over rolled copies of one of ``ROWS`` (the tie groups and
    the masked tokens move, the values stay), the grid's temperatures and
    top_ps by turns, every fourth lane greedy."""
    base = ROWS[kind]()
    rows = np.stack([np.roll(base, 37 * i) for i in range(n)])
    temps = np.array([GRID[i % len(GRID)][0] for i in range(n)], np.float32)
    temps[::4] = 0.0
    topps = np.array([GRID[i % len(GRID)][1] for i in range(n)], np.float32)
    lanes = np.arange(n, dtype=np.int32)
    return (rows, temps, topps, 1000 + lanes, 7 * lanes + 3,
            np.argmax(rows, axis=-1).astype(np.int32))


# (rows, rows that fit the budget) -> the group: 1, 2 and 4 groups of 8 rows,
# and 12 rows where 5 fit but only 4 divide, or 3
GROUPINGS = {(8, 8): 8, (8, 5): 4, (8, 2): 2, (12, 5): 4, (12, 3): 3}


@pytest.mark.parametrize("kind", sorted(ROWS))
@pytest.mark.parametrize("n,fit", sorted(GROUPINGS))
def test_grouped_entry_draws_the_ungrouped_vmaps_tokens(monkeypatch, kind, n, fit):
    operands = _lane_operands(kind, n)
    vocab = operands[0].shape[1]
    want = np.asarray(jax.jit(jax.vmap(_sample_lane))(*operands))
    # (+ 7: a budget need not be a whole number of rows)
    monkeypatch.setattr(engine_mod, "SAMPLER_GROUP_BYTES", 8 * vocab * fit + 7)
    group = sampler_group(n, vocab)
    assert group == GROUPINGS[n, fit]
    # the mechanism engaged: the 32 passes are one loop, the groups' another
    # (a function of its own a case: JAX keys a trace by the function and the
    # shapes, and the budget is neither)
    def entry(*a):
        return sample_lanes(*a)

    traced = str(jax.make_jaxpr(entry)(*operands))
    assert traced.count("scan[") == 1 + (group < n)
    if group == n:   # one group IS the vmap: a cell under the budget traces as it did
        assert traced == str(jax.make_jaxpr(jax.vmap(_sample_lane))(*operands))
    got = np.asarray(jax.jit(entry)(*operands))
    assert np.array_equal(got, want), (group, np.nonzero(got != want)[0])
    greedy = operands[1] == 0.0
    assert np.array_equal(got[greedy], operands[5][greedy])
    assert (got[~greedy] != operands[5][~greedy]).any()


def _cells():
    """Every cell of BENCHMARK.json with its (lanes, vocabulary)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: os.path.join(root, c["file"]) for c in bench["configs"]}
    for w in bench["workloads"]:
        with open(files[w["config"]]) as f:
            cfg = json.load(f)
        yield w["config"], w["name"], cfg["serving"]["lanes"], cfg["vocab_size"]


def test_the_rule_over_the_benchmarks_cells():
    """Every cell the benchmark had before PR 43 is ONE group (its step
    programs trace as they did); jamba2-3b's 256 lanes are four groups of
    64, LFM2's own shape."""
    cells = list(_cells())
    assert len(cells) >= 7
    for config, cell, lanes, vocab in cells:
        group = sampler_group(lanes, vocab)
        assert lanes % group == 0 and 8 * group * vocab <= engine_mod.SAMPLER_GROUP_BYTES
        if config == "jamba2-3b":
            assert (lanes, vocab, group) == (256, 65536, 64), cell
        else:
            assert group == lanes, cell


@pytest.mark.parametrize("rows,vocab,group", [
    (1, 152064, 1), (256, 65536, 64), (512, 65536, 64), (96, 65536, 48),
    (7, 1 << 20, 1),      # a prime count: one row at a time
    (4, 1 << 23, 1),      # not even one row fits: one row at a time all the same
])
def test_the_rule_takes_the_largest_divisor_that_fits(rows, vocab, group):
    assert sampler_group(rows, vocab) == group
