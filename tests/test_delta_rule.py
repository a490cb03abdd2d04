"""The gated delta rule with a decay a key channel (ops/delta_rule.py): the
chunk form and the one-row kernel (interpret mode) against the recurrence a
row at a time; the rule for the matrix state (a row that is not real moves
nothing, a start from zero, a second chunk continues the first); decays near 0
and near 1 and steps near 2, where no exponent is ever positive and the solve
is a substitution; and the compiled chunk holds no tensor with both a time axis
and the state's axes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.ops import delta_rule as dr

# jitted once: called eagerly, every call of either compiles its scan anew
CHUNK_FORM = jax.jit(dr._chunk_form, static_argnames=("chunk",))
SCAN_ROWS = jax.jit(dr.scan_rows)


def _rows(seed, b, t, h, d, rate=(-6.0, 1.5), b_logit=2.0):
    """A step's operands as the mixer hands them over: q and k L2-normed (q
    over sqrt(d) too), a log decay a channel ``-exp(uniform(rate))`` (from
    0.998 a row down to exp(-4.5): a chunk of the fastest underflows), a step
    ``2 sigmoid(b_logit * normal)`` in (0, 2), and a state to continue."""
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.standard_normal((b, t, h, d))) / d ** 0.5
    k = unit(rng.standard_normal((b, t, h, d)))
    v = rng.standard_normal((b, t, h, d))
    g = -np.exp(rng.uniform(*rate, (b, t, h, d)))
    beta = 2.0 / (1.0 + np.exp(-b_logit * rng.standard_normal((b, t, h))))
    state = rng.standard_normal((b, h, d, d))
    return tuple(jnp.asarray(x, jnp.float32) for x in (state, q, k, v, g, beta))


def _real(t, *valid):
    return jnp.asarray(np.arange(t)[None, :] < np.asarray(valid)[:, None])


@pytest.mark.parametrize("t,chunk,valid", [
    (1, 64, (1, 0)),        # one row; a parked lane
    (7, 64, (7, 2)),        # inside a chunk
    (64, 16, (64, 59)),     # on a chunk's edge, four chunks
    (100, 32, (100, 95)),   # many chunks and a padded tail (100 = 3 x 32 + 4)
    (150, 32, (150, 64)),   # the default chunk; a lane whose real rows end on an edge
])
def test_the_chunk_form_is_the_recurrence_a_row_at_a_time(t, chunk, valid):
    state, q, k, v, g, beta = _rows(t, 2, t, 4, 16)
    real = _real(t, *valid)
    want_o, want_s = SCAN_ROWS(state, q, k, v, g, beta, real)
    got_o, got_s = CHUNK_FORM(state, q, k, v, g, beta, real, chunk=chunk)
    np.testing.assert_allclose(got_s, want_s, rtol=2e-5, atol=2e-5)
    # the rows past a lane's real ones are nobody's to read
    for lane, n in enumerate(valid):
        np.testing.assert_allclose(got_o[lane, :n], want_o[lane, :n], rtol=2e-5, atol=2e-5)
    # a lane with no real row keeps its state to the bit
    if 0 in valid:
        np.testing.assert_array_equal(got_s[valid.index(0)], state[valid.index(0)])


@pytest.mark.parametrize("case,rate,b_logit", [
    ("decays near 0", (1.0, 3.0), 2.0),     # exp(g) from 0.07 down to 2e-9 a row
    ("decays near 1", (-14.0, -9.0), 2.0),  # a state that forgets nothing in 256 rows
    ("b near 2", (-6.0, 1.5), 8.0),         # half the rows at b > 1.99: eigenvalue -1
])
def test_extreme_decays_and_steps_stay_finite_and_exact(case, rate, b_logit):
    state, q, k, v, g, beta = _rows(5, 1, 256, 4, 16, rate, b_logit)
    real = jnp.ones((1, 256), bool)
    want_o, want_s = SCAN_ROWS(state, q, k, v, g, beta, real)
    got_o, got_s = CHUNK_FORM(state, q, k, v, g, beta, real)
    assert np.isfinite(np.asarray(got_o)).all() and np.isfinite(np.asarray(got_s)).all()
    scale = max(float(jnp.abs(want_o).max()), 1e-3)
    np.testing.assert_allclose(got_o / scale, want_o / scale, atol=5e-5, err_msg=case)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-4, atol=1e-4, err_msg=case)


def test_a_second_chunk_continues_the_first_and_a_fault_in_the_rule_shows():
    """100 rows at once against 37 + 63 with the state handed on; and what the
    benchmark's controls change each gives another state."""
    state, q, k, v, g, beta = _rows(9, 2, 100, 4, 16)
    real = jnp.ones((2, 100), bool)
    want_o, want_s = CHUNK_FORM(state, q, k, v, g, beta, real)
    cut = lambda x, lo, hi: x[:, lo:hi]  # noqa: E731
    o1, s1 = CHUNK_FORM(state, *(cut(x, 0, 37) for x in (q, k, v, g, beta)), real[:, :37])
    o2, s2 = CHUNK_FORM(s1, *(cut(x, 37, 100) for x in (q, k, v, g, beta)), real[:, 37:])
    np.testing.assert_allclose(s2, want_s, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), want_o, rtol=2e-5, atol=2e-5)
    for name, args in (
        ("no decay", (q, k, v, jnp.zeros_like(g), beta)),
        ("a decay a head", (q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta)),
        ("b without its 2", (q, k, v, g, beta / 2)),
    ):
        other = CHUNK_FORM(state, *args, real)[1]
        assert float(jnp.abs(other - want_s).max()) > 0.05, name


def _stack(state, layers=3, at=1):
    """A stack ``[layers, lanes, H * d * d]`` holding ``state`` at layer ``at``."""
    b, h, d, _ = state.shape
    s_all = jnp.full((layers, b, h * d * d), 7.0, jnp.float32)
    return s_all.at[at].set(state.reshape(b, -1))


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "kernel"])
def test_one_row_a_lane_in_place_with_parked_and_fresh_lanes(use_kernel):
    """Eight lanes, one row each: lane 2 is parked (its row is not real: the
    state stays to the bit), lane 5 starts a sequence (reads zeros whatever it
    held); the other layers of the stack are untouched; the kernel (interpret
    mode) and the XLA path give the recurrence's row and say which they were."""
    from distributed_llama_multiusers_tpu.ops import linear

    # heads of 32: the kernel's loops are unrolled a matrix row at a time, and
    # interpret mode pays for each (128-wide heads: tests/test_chip_compile_solar.py)
    state, q, k, v, g, beta = _rows(11, 8, 1, 2, 32)
    real = jnp.asarray(np.arange(8) != 2)[:, None]
    from_zero = jnp.asarray(np.arange(8) == 5)[:, None, None]
    start = jnp.where(from_zero[..., None], 0.0, state)
    want_o, want_s = SCAN_ROWS(start, q, k, v, g, beta, real)
    linear.set_pallas_interpret(use_kernel)
    try:
        o, s_all = dr.delta_rule(_stack(state), jnp.int32(1), from_zero, q, k, v, g, beta, real,
                                 use_kernel=use_kernel)
    finally:
        linear.set_pallas_interpret(False)
    assert dr.TRACE_STATS["one_row_path"] == ("pallas_in_place" if use_kernel else "xla")
    live = np.arange(8) != 2
    np.testing.assert_allclose(o[live], want_o[live], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(s_all[1], want_s.reshape(8, -1), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(s_all[1, 2], state[2].reshape(-1))
    assert float(jnp.abs(s_all[0] - 7.0).max()) == 0.0 == float(jnp.abs(s_all[2] - 7.0).max())


def test_the_kernel_is_chosen_by_what_tiles():
    assert dr.step_kernel_supports(16, 64, 128)
    assert not dr.step_kernel_supports(4, 64, 128)   # half a sublane tile of lanes
    assert not dr.step_kernel_supports(16, 4, 16)    # a matrix row under a lane tile
    assert dr.one_row_path(16, 64, 128) == "xla"     # no kernel on the CPU


def test_the_compiled_chunk_holds_no_state_by_time_and_no_positive_exponent():
    """512 rows of 4 heads of 32: nothing in the optimized program has the
    rows' axis (512, or 16 chunks of 32) beside both of the matrix's; and the
    exponent of every decayed score is masked before the exponential (an
    unmasked one overflows at these decays: the result is finite)."""
    state, q, k, v, g, beta = _rows(2, 1, 512, 4, 32, rate=(0.0, 2.5))
    real = jnp.ones((1, 512), bool)
    fn = jax.jit(lambda *a: dr._chunk_form(*a, real))
    hlo = fn.lower(state, q, k, v, g, beta).compile().as_text()
    assert not re.search(r"f32\[(1,)?(512|16,32|32,16),4,32,32\]", hlo)
    assert not re.search(r"f32\[(16,)?(1,)?4,(512|16,32),32,32\]", hlo)
    o, s = fn(state, q, k, v, g, beta)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
