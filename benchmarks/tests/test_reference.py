"""The plain reference's own pieces against element-by-element arithmetic."""
import numpy as np

from harness import reference


def test_dequant_q40_matches_the_block_layout():
    rng = np.random.default_rng(0)
    d_in, d_out = 64, 5
    packed = rng.integers(0, 256, (d_in // 2, d_out), dtype=np.uint8)
    scales = rng.random((d_in // 32, d_out)).astype(np.float16)
    got = np.asarray(reference.dequant_q40(packed, scales))
    want = np.zeros((d_in, d_out), np.float32)
    for r in range(d_in // 2):
        b, j = divmod(r, 16)
        for o in range(d_out):
            s = np.float32(scales[b, o])
            want[32 * b + j, o] = (int(packed[r, o]) & 15) - 8
            want[32 * b + j + 16, o] = (int(packed[r, o]) >> 4) - 8
            want[32 * b + j, o] *= s
            want[32 * b + j + 16, o] *= s
    np.testing.assert_array_equal(got, want)


def test_rope_rotates_adjacent_pairs_by_position():
    cos, sin = reference.rope_tables(4, 8, 10000.0)
    x = np.zeros((1, 4, 1, 8), np.float32)
    x[..., 0] = 1.0  # the first pair's first element
    y = np.asarray(reference._rope(x, cos, sin))
    for pos in range(4):
        np.testing.assert_allclose(y[0, pos, 0, 0], np.cos(pos), rtol=1e-6)
        np.testing.assert_allclose(y[0, pos, 0, 1], np.sin(pos), rtol=1e-6, atol=1e-7)
    assert np.allclose(y[..., 2:], 0.0)


def test_relative_errors_ignore_a_shift_of_a_row():
    from harness.correct import relative_errors

    rng = np.random.default_rng(1)
    want = rng.standard_normal((2, 3, 50)).astype(np.float32)
    assert np.allclose(relative_errors(want + 7.0, want), 0.0, atol=1e-6)
    got = want + 0.01 * rng.standard_normal(want.shape).astype(np.float32)
    err = relative_errors(got, want)
    assert err.shape == (2, 3) and np.all((err > 0.005) & (err < 0.02))
