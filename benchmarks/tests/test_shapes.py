"""The byte function of q40_decode_roofline, the peaks table, the stats."""
import pytest

from harness import roofline
from harness.peaks import chip_peaks
from harness.stats import percentile, union_seconds


def test_q40_matmul_bytes_hand_counted():
    # [16, 4096] @ Q40[4096, 14336]: 2048 rows of nibble pairs, 128 rows of
    # float16 scales, bf16 activations in and out
    assert roofline.q40_matmul_bytes(16, 4096, 14336) == (
        2048 * 14336 + 128 * 14336 * 2 + 16 * 4096 * 2 + 16 * 14336 * 2
    ) == 33_619_968


def test_decode_step_bytes_mistral():
    class C:  # Mistral-7B-v0.3
        dim, hidden_dim, n_layers, n_kv_heads, head_size = 4096, 14336, 32, 8, 128
    calls = roofline.decode_step_q40_calls(C, 32768)
    assert sum(n for _i, _o, n in calls) == 7 * 32 + 1
    weights = 32 * (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336) + 4096 * 32768
    planes = weights // 2 + weights // 32 * 2  # 4.5 bits a weight
    total = roofline.decode_step_q40_bytes(C, 32768, lanes=16)
    acts = total - planes
    assert weights == 7_113_539_584 and planes == weights * 9 // 16 == 4_001_366_016
    # activations: 16 rows in and out of every call
    assert acts == 16 * 2 * (32 * (2 * 8192 + 2 * 5120 + 2 * 18432 + 18432) + 4096 + 32768)


def test_peaks_known_and_unknown():
    assert chip_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert chip_peaks("TPU v5p")["hbm_bytes_per_s"] == 2765e9
    with pytest.raises(ValueError, match="not in the peaks table"):
        chip_peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        chip_peaks("cpu")


def test_percentile_and_union():
    assert percentile([], 50) is None
    assert percentile([3.0], 99) == 3.0
    assert percentile([1, 2, 3, 4], 50) == 2.5
    assert percentile(list(range(101)), 99) == 99.0
    assert union_seconds([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == 4.0
