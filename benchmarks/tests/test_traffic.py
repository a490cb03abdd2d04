"""The generator: the schedule is the traffic file's, the seed sets ids only."""
import json
import os
from collections import Counter

import pytest

from harness.cells import BENCH_DIR
from harness.traffic import Traffic, _quantile_lengths


def _files():
    d = os.path.join(BENCH_DIR, "traffic")
    r = os.path.join(BENCH_DIR, "tests", "rehearsal", "traffic")
    return [os.path.join(x, f) for x in (d, r) for f in sorted(os.listdir(x))]


@pytest.mark.parametrize("path", _files(), ids=os.path.basename)
def test_two_seeds_same_requests_other_ids(path):
    params = json.load(open(path))
    a, b = Traffic(params, lanes=16), Traffic(params, lanes=16)
    n = 3 * params["requests"] // 2  # past the end of the list: it repeats
    specs_a = [a.spec(k) for k in range(n)]
    specs_b = [b.spec(k) for k in reversed(range(n))][::-1]  # asked in another order
    assert specs_a == specs_b
    assert a.digest(64) == b.digest(64)
    # --seed changes the ids and the sampler seeds, and nothing else
    ids1 = [a.token_ids(1, k, 32768) for k in range(8)]
    ids2 = [a.token_ids(3_000_000_001, k, 32768) for k in range(8)]
    assert [len(x) for x in ids1] == [len(x) for x in ids2] == [s.prompt_tokens for s in specs_a[:8]]
    assert ids1 != ids2
    assert ids1 == [b.token_ids(1, k, 32768) for k in range(8)]
    assert a.sampler_seed(1, 5) != a.sampler_seed(2, 5)
    if a.loop == "open":
        due = [s.due_s for s in specs_a]
        assert due == sorted(due)
        assert all(d == 0.0 for d in due[: a.in_flight])


def test_lengths_are_quantiles_not_draws():
    spec = {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 16, "max": 1024}
    lens = _quantile_lengths(spec, 512)
    assert lens == sorted(lens) and lens[0] >= 16 and lens[-1] == 1024
    assert abs(lens[256] - 192) <= 2  # the median is the median
    params = json.load(open(os.path.join(BENCH_DIR, "traffic", "chat_saturated.json")))
    t = Traffic(params, lanes=16)
    assert Counter(t._prompt_len) == Counter(lens)  # ordered, never redrawn
    other = Traffic(dict(params, schedule_seed=params["schedule_seed"] + 1), lanes=16)
    assert Counter(other._prompt_len) == Counter(lens)
    assert other._prompt_len != t._prompt_len


def test_requests_under_way_are_cut_and_later_ones_whole():
    params = json.load(open(os.path.join(BENCH_DIR, "traffic", "chat_saturated.json")))
    t = Traffic(params, lanes=16)
    assert t.clients == 48 and t.in_flight == 16
    first = [t.spec(k) for k in range(16)]
    assert all(1 <= s.max_tokens <= s.full_max_tokens for s in first)
    assert first[0].max_tokens < first[0].full_max_tokens
    assert all(t.spec(k).max_tokens == t.spec(k).full_max_tokens for k in range(16, 200))


def test_repeat_pattern():
    params = json.load(open(os.path.join(
        BENCH_DIR, "tests", "rehearsal", "traffic", "tiny_extract_greedy.json")))
    t = Traffic(params, lanes=4)
    a, b = (t.token_ids(9, k, 256) for k in (0, 1))
    assert a[:8] != b[:8]          # each prompt its own ids
    assert a[20:28] == a[28:36]    # which repeat with period 8


def test_what_no_mix_uses_yet_is_refused():
    params = json.load(open(os.path.join(BENCH_DIR, "traffic", "chat_steady.json")))
    with pytest.raises(ValueError, match="arrival kind"):
        Traffic(dict(params, arrival={"kind": "poisson"}), lanes=16).spec(20)
    with pytest.raises(ValueError, match="length distribution"):
        Traffic(dict(params, max_tokens={"dist": "fixed", "value": 8}), lanes=16)
