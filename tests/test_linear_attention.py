"""Linear attention with a decay a head (ops/linear_attention.py): the chunk
form and the one-row kernel (interpret mode) against the recurrence a row at a
time, the rule for the matrix state (a row that is not real moves nothing, a
start from zero, a second chunk continues the first), decays that neither
overflow nor vanish, and the compiled chunk holds no tensor with both a time
axis and the state's axes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.ops import linear_attention as la


def _rows(seed, b, t, h, d, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), dtype) for _ in range(3))
    state = jnp.asarray(rng.standard_normal((b, h, d, d)), jnp.float32)
    return state, q, k, v


def test_the_slopes_are_the_published_ones():
    s = la.decay_slopes(32)
    assert s.dtype == np.float32 and s.shape == (32,)
    np.testing.assert_allclose(s[0], 2.0 ** -0.25, rtol=1e-6)
    np.testing.assert_allclose(s[-1], 2.0 ** -8, rtol=1e-6)
    lam = np.exp(-s)
    assert 0.43 < lam[0] < 0.44 and 0.996 < lam[-1] < 0.9962 and np.all(np.diff(lam) > 0)


@pytest.mark.parametrize("t,chunk", [(1, 128), (7, 128), (64, 16), (100, 32), (256, 128)])
def test_the_chunk_form_is_the_recurrence_a_row_at_a_time(t, chunk):
    state, q, k, v = _rows(t, 2, t, 4, 16)
    real = jnp.asarray(np.arange(t)[None, :] < np.array([[t], [max(t - 5, 0)]]))
    slopes = jnp.asarray(la.decay_slopes(4))
    want_o, want_s = la.scan_rows(state, q, k, v, real, slopes, 0.25)
    got_o, got_s = la._chunk_form(state, q, k, v, real, slopes, 0.25, chunk=chunk)
    np.testing.assert_allclose(got_s, want_s, rtol=2e-5, atol=2e-5)
    # the rows past a lane's real ones are nobody's to read
    for lane, n in enumerate([t, max(t - 5, 0)]):
        np.testing.assert_allclose(got_o[lane, :n], want_o[lane, :n], rtol=2e-4, atol=2e-4)


def test_a_long_chunk_under_the_fastest_decay_neither_overflows_nor_loses_the_slowest():
    """1024 rows: head 0's factor over a chunk is exp(-108), head 31's 0.6;
    every exponent is a difference of sums that is never positive."""
    state, q, k, v = _rows(3, 1, 1024, 32, 8)
    real = jnp.ones((1, 1024), bool)
    slopes = jnp.asarray(la.decay_slopes(32))
    want_o, want_s = la.scan_rows(state, q, k, v, real, slopes, 0.3)
    got_o, got_s = la._chunk_form(state, q, k, v, real, slopes, 0.3)
    assert np.isfinite(np.asarray(got_o)).all() and np.isfinite(np.asarray(got_s)).all()
    np.testing.assert_allclose(got_s, want_s, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-3, atol=1e-3)
    # the slowest head still holds the state it was given: 0.996^1024 = 0.018
    assert abs(float(got_s[0, 31, 0, 0])) > 1e-4 or abs(float(state[0, 31, 0, 0])) < 1e-2


def _stack(state, layers=3, at=1):
    """A stack ``[layers, lanes, H * d * d]`` holding ``state`` at layer ``at``."""
    b, h, d, _ = state.shape
    s_all = jnp.full((layers, b, h * d * d), 7.0, jnp.float32)
    return s_all.at[at].set(state.reshape(b, -1))


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "kernel"])
def test_one_row_a_lane_in_place_with_parked_and_fresh_lanes(use_kernel):
    """Eight lanes, one row each: lane 2 is parked (its row is not real: the
    state stays to the bit), lane 5 starts a sequence (reads zeros whatever it
    held); the other layers of the stack are untouched."""
    from distributed_llama_multiusers_tpu.ops import linear

    state, q, k, v = _rows(11, 8, 1, 2, 128)
    slopes = jnp.asarray(la.decay_slopes(2))
    real = jnp.asarray(np.arange(8) != 2)[:, None]
    from_zero = jnp.asarray(np.arange(8) == 5)[:, None, None]
    start = jnp.where(from_zero[..., None], 0.0, state)
    want_o, want_s = la.scan_rows(start, q, k, v, real, slopes, 0.09)
    linear.set_pallas_interpret(use_kernel)
    try:
        o, s_all = jax.jit(
            lambda s, *a: la.linear_attention(s, jnp.int32(1), from_zero, *a, real, slopes, 0.09,
                                              use_kernel=use_kernel))(_stack(state), q, k, v)
    finally:
        linear.set_pallas_interpret(False)
    got = np.asarray(s_all[1]).reshape(8, 2, 128, 128)
    np.testing.assert_allclose(got, want_s, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[2], np.asarray(state[2]))
    live = [i for i in range(8) if i != 2]
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live], rtol=1e-4, atol=1e-4)
    assert np.all(np.asarray(s_all[0]) == 7.0) and np.all(np.asarray(s_all[2]) == 7.0)


def test_the_kernel_tiles_what_it_says():
    assert la.step_kernel_supports(16, 32, 128) and la.step_kernel_supports(8, 2, 256)
    assert not la.step_kernel_supports(4, 32, 128) and not la.step_kernel_supports(8, 4, 32)
    assert not la.step_kernel_supports(8, 3, 128)


def test_a_second_chunk_continues_the_first_and_a_padded_tail_is_ignored():
    state, q, k, v = _rows(5, 1, 96, 4, 16)
    slopes = jnp.asarray(la.decay_slopes(4))
    zero = jnp.zeros_like(state)
    s_all = _stack(zero, layers=1, at=0)
    run = lambda s, lo, hi, n, fresh: la.linear_attention(  # noqa: E731
        s, jnp.int32(0), jnp.full((1, 1, 1), fresh), q[:, lo:hi], k[:, lo:hi], v[:, lo:hi],
        jnp.arange(hi - lo)[None, :] < n, slopes, 0.25)
    o_whole, s_whole = run(s_all, 0, 96, 96, True)
    # 40 real rows of a 64-row step, then 56 from row 40 on: the tail of the
    # first step (rows 40-63 of other tokens) must leave nothing behind
    o_a, s_a = run(jnp.full_like(s_all, 3.0), 0, 64, 40, True)
    o_b, s_b = run(s_a, 40, 96, 56, False)
    np.testing.assert_allclose(s_b, s_whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(o_a[:, :40], o_whole[:, :40], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(o_b, o_whole[:, 40:], rtol=1e-4, atol=1e-4)
    # no real row at all: the state as it was
    _, s_c = run(s_whole, 0, 64, 0, False)
    np.testing.assert_array_equal(np.asarray(s_c), np.asarray(s_whole))


def test_no_compiled_chunk_holds_time_and_state_axes_together():
    """1024 rows of 32 heads of 128: ``[T, 32, 128, 128]`` float32 would be
    2 GB. The chunk form's largest tensors are ``[chunks, 32, 128, 128]``-free:
    the carried matrix alone has the two head axes."""
    t, h, d = 1024, 32, 128
    f = jax.jit(lambda s, q, k, v: la.linear_attention(
        s, jnp.int32(0), jnp.zeros((1, 1, 1), bool), q, k, v, jnp.ones((1, t), bool),
        jnp.asarray(la.decay_slopes(h)), 0.088))
    rows = jax.ShapeDtypeStruct((1, t, h, d), jnp.bfloat16)
    hlo = f.lower(jax.ShapeDtypeStruct((2, 1, h * d * d), jnp.float32), rows, rows, rows
                  ).compile().as_text()
    shapes = set(re.findall(r"f32\[([\d,]+)\]", hlo)) | set(re.findall(r"bf16\[([\d,]+)\]", hlo))
    big = [s for s in shapes if np.prod([int(x) for x in s.split(",")]) >= t * h * d * d // 8]
    assert not big, big
