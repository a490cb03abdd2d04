"""The selective state-space recurrence (ops/ssm_scan.py) against the
recurrence written a row at a time, and the rule for a running sum: one chunk
equals two, a padded tail and a parked lane leave the state bit-equal, a start
at position 0 ignores what the lane held, the state is float32 under a
bfloat16 cache. Both Pallas kernels run in interpret mode against the XLA
forms. The first case is the toy running sum of
``benchmarks/tests/test_route_recurrent.py`` (``h = A h + x``) said in the
operation's terms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.models.config import LlamaConfig
from distributed_llama_multiusers_tpu.models.hybrid import init_hybrid_cache, state_leaves
from distributed_llama_multiusers_tpu.ops import linear, ssm_scan


def _inputs(seed, b, t, n, e):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    delta = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (b, t, e))), jnp.float32)
    a = -jnp.asarray(np.broadcast_to(np.arange(1, n + 1, dtype=np.float32)[:, None], (n, e)))
    return f(b, n, e), delta, f(b, t, e), f(b, t, n), f(b, t, n), a, f(e)


def _row_by_row(state, delta, u, b, c, a, d):
    """The recurrence as the module's header writes it, in numpy float64."""
    state, delta, u, b, c, a, d = (np.asarray(x, np.float64) for x in (state, delta, u, b, c, a, d))
    ys = []
    for t in range(delta.shape[1]):
        decay = np.exp(delta[:, t, None, :] * a[None])
        state = decay * state + (delta[:, t] * u[:, t])[:, None, :] * b[:, t, :, None]
        ys.append(np.einsum("bne,bn->be", state, c[:, t]) + d * u[:, t])
    return np.stack(ys, axis=1), state


def test_the_toy_running_sum_of_the_route_check():
    """``h_t = A h_{t-1} + x_t`` with ``A = 0.98``: one state a channel, a step
    size of 1, maps of 1 and no skip term. A padded tail (step size 0) and a
    parked lane leave ``h`` as it was; a start from zero forgets it."""
    decay, e, t = 0.98, 12, 40
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, t, e)).astype(np.float32)
    h0 = rng.standard_normal((2, 1, e)).astype(np.float32)
    want = h0[:, 0].astype(np.float64)
    for i in range(t - 7):
        want = decay * want + x[:, i]
    ones = jnp.ones((2, t, 1), jnp.float32)
    delta = jnp.ones((2, t, e), jnp.float32).at[:, t - 7:].set(0.0)  # a tail of padding
    delta = delta.at[1].set(0.0)  # lane 1 parked
    a = jnp.full((1, e), np.log(decay), jnp.float32)
    y, h = ssm_scan.selective_scan(jnp.asarray(h0), delta, jnp.asarray(x), ones, ones, a,
                                   jnp.zeros((e,), jnp.float32))
    np.testing.assert_allclose(np.asarray(h[0, 0]), want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(h[1]), h0[1])
    np.testing.assert_allclose(np.asarray(y[0, t - 8]), np.asarray(h[0, 0]), rtol=1e-6)  # y = S C


@pytest.mark.parametrize("b,t,n,e", [(3, 1, 4, 256), (2, 24, 8, 128), (1, 64, 16, 1024)])
def test_the_operation_is_the_recurrence_a_row_at_a_time(b, t, n, e):
    args = _inputs(0, b, t, n, e)
    want_y, want_s = _row_by_row(*args)
    y, s = ssm_scan.selective_scan(*args)
    assert y.dtype == s.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t", [64, 512])
def test_the_chunk_kernel_in_interpret_mode_is_the_scan_over_rows(t):
    args = _inputs(2, 2, t, 16, 2048)
    assert ssm_scan.kernel_supports(t, 16, 2048) and not ssm_scan.kernel_supports(t, 16, 640)
    linear.set_pallas_interpret(True)
    try:
        y, s = jax.jit(ssm_scan.selective_scan)(*args)
    finally:
        linear.set_pallas_interpret(False)
    want_y, want_s = ssm_scan.selective_scan(*args, use_kernel=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), rtol=1e-5, atol=1e-5)


def _stack_step(kernel: bool, s_all, si, from_zero, delta, u, b, c, a, d):
    linear.set_pallas_interpret(kernel)
    try:
        return jax.jit(ssm_scan.state_step)(s_all, si, from_zero, delta, u, b, c, jnp.log(-a), d)
    finally:
        linear.set_pallas_interpret(False)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "one_row_kernel"])
def test_a_step_on_the_stack_moves_one_layer_and_the_rule_holds(kernel):
    """Layer 1 of three, sixteen lanes: a parked lane (step size 0) keeps its
    state bit for bit, a lane that starts a sequence reads zeros whatever it
    held, the other layers are not touched, and the kernel is the XLA form."""
    layers, lanes, n, e = 3, 16, 8, 256
    state, delta, u, b, c, a, d = _inputs(3, lanes, 1, n, e)
    s_all = jnp.asarray(np.random.default_rng(4).standard_normal((layers, lanes, n * e)), jnp.float32)
    delta = delta.at[5].set(0.0)  # parked
    from_zero = jnp.zeros((lanes, 1, 1), bool).at[3].set(True)
    assert ssm_scan.step_kernel_supports(lanes, n, e) and not ssm_scan.step_kernel_supports(4, n, e)
    y, out = _stack_step(kernel, s_all, jnp.int32(1), from_zero, delta, u, b, c, a, d)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(s_all[0]))
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(s_all[2]))
    np.testing.assert_array_equal(np.asarray(out[1, 5]), np.asarray(s_all[1, 5]))
    held = np.asarray(s_all[1]).reshape(lanes, n, e).copy()
    held[3] = 0.0
    want_y, want_s = _row_by_row(held, delta, u, b, c, a, d)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out[1]).reshape(lanes, n, e), want_s, rtol=2e-5, atol=2e-5)
    dirty = s_all.at[1, 3].set(7.0)  # whatever the lane held
    _, again = _stack_step(kernel, dirty, jnp.int32(1), from_zero, delta, u, b, c, a, d)
    np.testing.assert_array_equal(np.asarray(again[1, 3]), np.asarray(out[1, 3]))


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "chunk_kernel"])
def test_one_chunk_equals_two_and_a_padded_tail_is_not_absorbed(kernel):
    state, delta, u, b, c, a, d = _inputs(5, 1, 128, 16, 1024)
    scan = lambda *x: ssm_scan.selective_scan(*x, use_kernel=kernel)
    linear.set_pallas_interpret(kernel)
    try:
        y, s = scan(state, delta, u, b, c, a, d)
        first = scan(state, delta[:, :64], u[:, :64], b[:, :64], c[:, :64], a, d)
        second = scan(first[1], delta[:, 64:], u[:, 64:], b[:, 64:], c[:, 64:], a, d)
        # 100 real rows in a bucket of 128: the tail's step size is 0
        cut = delta.at[:, 100:].set(0.0)
        _, s_cut = scan(state, cut, u, b, c, a, d)
        s_100 = None if kernel else scan(  # 100 rows are no whole block of the kernel's
            state, delta[:, :100], u[:, :100], b[:, :100], c[:, :100], a, d)[1]
        _, parked = scan(state, jnp.zeros_like(delta), u, b, c, a, d)
    finally:
        linear.set_pallas_interpret(False)
    # the kernel walks the same rows in the same order, bit for bit; XLA fuses
    # a loop of 128 rows and one of 64 differently (a rounding in the last place)
    same = np.testing.assert_array_equal if kernel else (
        lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6))
    same(np.asarray(second[1]), np.asarray(s))
    same(np.asarray(second[0]), np.asarray(y[:, 64:]))
    np.testing.assert_array_equal(np.asarray(parked), np.asarray(state))
    if not kernel:  # the state after row 99
        same(np.asarray(s_cut), np.asarray(s_100))
    assert not np.array_equal(np.asarray(s_cut), np.asarray(s))


def test_the_state_is_float32_under_a_bfloat16_cache():
    config = LlamaConfig(dim=64, hidden_dim=128, n_layers=3, n_heads=4, n_kv_heads=1, vocab_size=64,
                         seq_len=32, layer_kinds=(2, 0, 2), ssm_d_inner=128, ssm_d_state=8,
                         ssm_dt_rank=8, ssm_conv_kernel=4)
    cache = init_hybrid_cache(config, 4, jnp.bfloat16)
    assert cache.ssm.dtype == jnp.float32 and cache.ssm.shape == (2, 4, 8 * 128)
    assert cache.ssm_conv.dtype == cache.k.dtype == jnp.bfloat16 and cache.ssm_conv.shape == (2, 4, 3 * 128)
    assert cache.k.shape[0] == 1 and cache.conv.shape == (0, 4, 0)
    assert [x.shape for x in state_leaves(cache)] == [(0, 4, 0), (2, 4, 1024), (2, 4, 384)]
    assert config.n_ssm_layers == 2 and config.n_attention_layers == 1 and config.recurrent_state
    with pytest.raises(ValueError, match="state-space layer needs"):
        LlamaConfig(dim=64, hidden_dim=128, n_layers=1, n_heads=4, n_kv_heads=1, vocab_size=64,
                    seq_len=32, layer_kinds=(2,))
