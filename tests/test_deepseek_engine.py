"""The latent-attention block with a routed FFN (models/deepseek.py) through
``InferenceEngine`` at a toy size on the CPU, against the benchmark family's
plain float32 reference: prefill then decode through the latent cache, a
chunked prefill (100 tokens through buckets of 64), fused admissions beside
decoding lanes and twins left parked (the harness's route check), with the
Pallas kernels in interpret mode, and in bfloat16."""

import jax.numpy as jnp
import numpy as np
import pytest

import latent_toy

CFG, FAMILY, CORRECT = latent_toy.toy("latent")


def _compare(seed, dtype=None):
    eng, tensors = latent_toy.engine(FAMILY, CFG, seed, dtype)
    return CORRECT.compare(FAMILY, CFG, tensors, eng, seed), eng


def test_kernels_in_interpret_mode_agree_with_the_reference(pallas_interpret):
    r, eng = _compare(5)
    assert r["ok"], r
    assert r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5
    assert (r["route_greedy_gap"], r["route_nucleus_excess"], r["route_kv_rel_err"]) == (0, 0, 0)
    assert r["route_token_mismatches"] == 0
    assert eng.decode_attention_block is None  # 64-wide latent rows: the dense path


def test_bfloat16_stays_near_the_reference_and_the_routes_read_zero():
    r, _ = _compare(6, jnp.bfloat16)
    assert r["prefill_rel_err"] < 0.08 and r["decode_rel_err"] < 0.08, r
    assert (r["route_greedy_gap"], r["route_nucleus_excess"], r["route_kv_rel_err"]) == (0, 0, 0)


@pytest.fixture(scope="module")
def eng():
    """The one engine of the cases that only read and write lanes."""
    return latent_toy.engine(FAMILY, CFG, 7)[0]


def test_a_parked_lane_is_left_alone_and_routes_nowhere(eng):
    n, seq = eng.n_lanes, eng.config.seq_len
    prompt = list(range(3, 23))
    eng.prefill(0, prompt)
    eng.prefill(1, prompt)
    before = [np.asarray(x) for x in eng.cache]
    tokens = np.full(n, 9, np.int32)
    positions = np.full(n, seq, np.int32)
    positions[0] = len(prompt)  # lane 0 decodes, lane 1 and the rest stand parked
    eng.decode(tokens, positions)
    after = [np.asarray(x) for x in eng.cache]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b[:, 1:], a[:, 1:])
        assert (b[:, 0, len(prompt)] != a[:, 0, len(prompt)]).any()
        np.testing.assert_array_equal(b[:, 0, : len(prompt)], a[:, 0, : len(prompt)])


def test_prefill_in_chunks_gives_the_rows_of_a_prefill_in_one(eng):
    prompt = [int(x) for x in np.random.default_rng(0).integers(2, 250, size=60)]
    whole, _, _ = eng.prefill(0, prompt)
    eng.prefill(1, prompt[:16])
    parts, _, _ = eng.prefill(1, prompt[16:], start_pos=16)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), rtol=2e-4, atol=2e-4)
    assert FAMILY.lane_state_rel_err(eng, 0, 1, 60) < 1e-4
