"""The controls of `correct`, at a size a test run can hold: the engine as
configured passes its limits; the program's own lower-precision paths (an f8
key/value cache, Q80-emulated activations) and the reference computed in f8
read at least three times the sound runs' largest on the logits; and a fused
admission spliced into the wrong lane fails every number of the route check.
On the chip the same readings are made at the cells' own sizes by
benchmarks/control.py (PERF.md section 2 has both and the limits)."""
import json
import os

import pytest

import control
from harness import correct
from harness.cells import BENCH_DIR, load_family

LOGITS = ("prefill_rel_err", "decode_rel_err")
ROUTES = ("route_greedy_gap", "route_nucleus_excess", "route_kv_rel_err")


@pytest.fixture(scope="module")
def cfg():
    path = os.path.join(BENCH_DIR, "tests", "rehearsal", "configs", "tiny_bias.json")
    cfg = json.load(open(path))
    cfg["serving"]["kv_dtype"] = "bfloat16"  # what the cells serve with
    cfg["correctness"]["limits"].update(prefill_rel_err=0.004, decode_rel_err=0.004)
    return cfg


@pytest.fixture(scope="module")
def family(cfg):
    return load_family(cfg)


@pytest.fixture(scope="module")
def sound(family, cfg):
    return control.readings(family, cfg, "as_configured", [11, 12, 13, 14], log=lambda s: None)


def test_engine_as_configured_is_correct(sound):
    assert all(r["ok"] for r in sound), sound
    # the sibling programs agree with the synchronous ones to the token and to
    # the bit of what they leave in a lane: 2 chain lanes x 5 steps, a's 3,
    # b's 1 and two boundary tokens, of which 7 sampled; four pairs of lanes
    assert all(r[k] == 0.0 for r in sound for k in ROUTES), sound
    assert all((r["route_tokens"], r["route_token_mismatches"]) == (23, 0) for r in sound)
    assert all(r["route_state_rel_errs"] == [0.0] * 4 for r in sound)


@pytest.mark.parametrize("variant", ["f8_kv_cache", "q80_activations", "reference_in_f8"])
def test_lower_precision_is_not_correct(family, cfg, sound, variant):
    runs = control.readings(family, cfg, variant, [11, 12, 13], log=lambda s: None)
    assert not any(r["ok"] for r in runs), runs
    for key in LOGITS:
        largest_sound = max(r[key] for r in sound)
        assert min(r[key] for r in runs) > 3 * largest_sound


def test_admission_into_the_wrong_lane_is_not_correct(family, cfg, sound):
    runs = control.readings(family, cfg, "admits_swapped", [11, 12, 13], log=lambda s: None)
    assert not any(r["ok"] for r in runs), runs
    limits = cfg["correctness"]["limits"]
    for key in ROUTES:
        assert min(r[key] for r in runs) > 3 * limits[key], (key, runs)
    # the logits' part is untouched by the fault
    assert [r["decode_rel_err"] for r in runs] == [r["decode_rel_err"] for r in sound[:3]]


def test_route_numbers_counted_by_hand():
    import numpy as np

    row = np.array([0.0, 2.0, 1.0, -3.0], np.float32)
    assert correct.greedy_gap(row, 1) == 0.0
    assert correct.greedy_gap(row, 2) == pytest.approx(1.0 / row.std())
    # at temperature 1: p = softmax(2, 1, 0, -3) = 0.6619, 0.2435, 0.0896, 0.0045
    excess, share = correct.nucleus_excess(row, 1, 1.0, 0.9)
    assert excess == 0.0 and share == 0.5      # two of four tokens come before 0.9
    assert correct.nucleus_excess(row, 2, 1.0, 0.9)[0] == 0.0   # 0.6619 ahead of it
    assert correct.nucleus_excess(row, 0, 1.0, 0.9)[0] == pytest.approx(0.0054, abs=1e-3)
    assert correct.nucleus_excess(row, 3, 1.0, 0.9)[0] == pytest.approx(0.0955, abs=1e-3)
