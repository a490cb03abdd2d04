"""The dense Q40 kernel at 1024 rows reading a layer of a stack under a traced
index (PR 30), compiled for a described v5e (tests/chip_compile_util.py): the
seven planes of a Mistral / Qwen layer in the default mode, two of them in
every mode."""

import pytest

from chip_compile_util import (  # noqa: F401  (v5e, v5e_devices: the fixtures)
    DEFAULT_MODE,
    OTHER_MODES,
    STACK_SHAPES,
    _compile_stacked,
    _is_slab_chain,
    _lane_splits,
    _scales_stack_converted_whole,
    v5e,
    v5e_devices,
)


@pytest.mark.parametrize("prefill", [True], ids=["prefill1024"])
@pytest.mark.parametrize("d_in,d_out,m", STACK_SHAPES)
def test_stacked_weight_default_mode_compiles_for_v5e(v5e, d_in, d_out, m, prefill):
    hlo = _compile_stacked(v5e, DEFAULT_MODE, d_in, d_out, 1024 if prefill else m)
    assert "tpu_custom_call" in hlo
    assert not _scales_stack_converted_whole(hlo, d_in, d_out)
    assert _lane_splits(hlo) == []


@pytest.mark.parametrize("prefill", [True], ids=["prefill1024"])
@pytest.mark.parametrize("d_in,d_out,m", [(4096, 14336, 16), (3584, 512, 32)])
@pytest.mark.parametrize("mode", OTHER_MODES)
def test_stacked_weight_every_selectable_mode_compiles_for_v5e(
        v5e, mode, d_in, d_out, m, prefill):
    """Every mode `--dequant` offers, at a
    multi-chunk two-wide-tile plan and at a single-slab plan, at decode
    width and at 1024 rows (where a block-dot mode is served by bf16chain)."""
    m = 1024 if prefill else m
    hlo = _compile_stacked(v5e, mode, d_in, d_out, m)
    assert "tpu_custom_call" in hlo
    assert not (_is_slab_chain(mode, m) and _lane_splits(hlo))
