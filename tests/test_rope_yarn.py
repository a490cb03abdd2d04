"""YaRN's frequencies (ops/rope.py) against numbers computed by hand from the
published rule at DeepSeek-V3.2's settings, and its factor on the softmax
scale (models/config.py)."""

import math

import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats.model_file import RopeType
from distributed_llama_multiusers_tpu.models.config import LlamaConfig
from distributed_llama_multiusers_tpu.models.loader import _rope_cache
from distributed_llama_multiusers_tpu.ops.rope import build_rope_cache, yarn_frequencies, yarn_mscale

PUBLISHED = dict(head_size=64, rope_theta=10000.0, factor=40.0, beta_fast=32.0, beta_slow=1.0,
                 orig_max_seq_len=4096)


def test_the_correction_range_is_pairs_10_to_23():
    f = yarn_frequencies(**PUBLISHED)
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-12)            # ramp 0 up to pair 10
    np.testing.assert_allclose(f[23:], plain[23:] / 40.0, rtol=1e-12)     # ramp 1 from pair 23
    # pair 15, by hand: ramp = (15 - 10) / 13; f = theta^(-30/64)
    ramp = 5.0 / 13.0
    f15 = 10000.0 ** (-30.0 / 64.0)
    assert f[15] == pytest.approx(f15 * (1 - ramp) + f15 / 40.0 * ramp, rel=1e-12)
    assert f[15] == pytest.approx(0.013335214321633 * (1 - ramp * 39.0 / 40.0), rel=1e-9)
    assert (np.diff(f) < 0).all()


@pytest.mark.parametrize("beta,pair", [(32.0, 10.47), (1.0, 22.51)])
def test_the_pair_a_turn_count_is_reached_at(beta, pair):
    at = 64 * math.log(4096 / (beta * 2 * math.pi)) / (2 * math.log(10000.0))
    assert at == pytest.approx(pair, abs=0.01)


def test_the_tables_and_the_softmax_scale_of_a_configuration():
    cfg = LlamaConfig(
        dim=128, hidden_dim=256, n_layers=1, n_heads=2, n_kv_heads=2, vocab_size=64, seq_len=50,
        kv_lora_rank=32, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_type=RopeType.YARN, rope_scaling_factor=40.0, rope_scaling_low_freq_factor=1.0,
        rope_scaling_high_freq_factor=32.0, rope_scaling_orig_max_seq_len=4096,
        rope_yarn_mscale_all_dim=1.0)
    cos, sin = _rope_cache(cfg)
    f = yarn_frequencies(**PUBLISHED)
    np.testing.assert_allclose(cos[7], np.cos(7 * f), rtol=1e-6)   # mscale == mscale_all_dim:
    np.testing.assert_allclose(sin[49], np.sin(49 * f), rtol=1e-5, atol=1e-7)  # the rotation unscaled
    m = 0.1 * math.log(40.0) + 1.0
    assert yarn_mscale(40.0, 1.0) == pytest.approx(m) and m == pytest.approx(1.36888794541)
    assert cfg.softmax_scale_factor == pytest.approx(m * m)
    plain = LlamaConfig(dim=128, hidden_dim=256, n_layers=1, n_heads=2, n_kv_heads=2,
                        vocab_size=64, seq_len=8)
    assert plain.softmax_scale_factor == 1.0


def test_a_table_without_yarn_is_what_it_was():
    a = build_rope_cache(16, 64, 10000.0)
    b = build_rope_cache(16, 64, 10000.0, yarn=False)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_allclose(a[0][3], np.cos(3 * 10000.0 ** (-2.0 * np.arange(32) / 64)), rtol=1e-6)
