"""The dense Q40 kernel at 1024 rows (the widest default prefill bucket; since
PR 45 one block of 1024 rows, four 256-row m tiles before: the plan with the
largest VMEM footprint), compiled for a described v5e
(tests/chip_compile_util.py): the 1B / 8B shapes in every mode (a block-dot
mode is served by bf16chain there) and the narrow whole-half plans, each one
plane (a layer of a stack: test_chip_compile_q40_prefill_stacked.py)."""

import pytest

from distributed_llama_multiusers_tpu.ops import pallas_q40 as pq

from chip_compile_util import (  # noqa: F401  (v5e, v5e_devices: the fixtures)
    DEFAULT_MODE,
    OTHER_MODES,
    SHAPES,
    TWO_SHAPES,
    _compile,
    _is_slab_chain,
    _lane_splits,
    v5e,
    v5e_devices,
)


@pytest.mark.parametrize("m", [1024])
@pytest.mark.parametrize("d_in,d_out", SHAPES)
def test_default_mode_compiles_for_v5e(v5e, d_in, d_out, m):
    hlo = _compile(v5e, DEFAULT_MODE, d_in, d_out, m)
    assert "tpu_custom_call" in hlo
    assert _lane_splits(hlo) == []


@pytest.mark.parametrize("m", [1024])
@pytest.mark.parametrize("d_in,d_out", TWO_SHAPES)
@pytest.mark.parametrize("mode", OTHER_MODES)
def test_every_selectable_mode_compiles_for_v5e(v5e, mode, d_in, d_out, m):
    """At 1024 rows the block-dot modes route to bf16chain, as they are
    served. A slab chain's program splits no lane of x."""
    hlo = _compile(v5e, mode, d_in, d_out, m)
    assert "tpu_custom_call" in hlo
    assert not (_is_slab_chain(mode, m) and _lane_splits(hlo))


@pytest.mark.parametrize("m", [128, 256, 512])
@pytest.mark.parametrize("d_in,d_out", TWO_SHAPES)
@pytest.mark.parametrize("mode", [m for m in pq.DEQUANT_MODES
                                  if m not in pq.BLOCK_DOT_MODES])
def test_subtracted_offset_compiles_in_every_slab_chain(v5e, mode, d_in, d_out, m):
    """PR 49: the body that takes the -8 off in the dequant chain (no block
    sums, no correction dot) at the narrower blocks that trace it, in each
    of the four slab chains (1024 rows: the two tests above; u8chain subtracts
    after its bf16 cast, Mosaic having no 8-bit-lane subtract for the v5e)."""
    assert m >= pq.SUBTRACT_MIN_ROWS
    hlo = _compile(v5e, mode, d_in, d_out, m)
    assert "tpu_custom_call" in hlo and _lane_splits(hlo) == []


def test_bf16chain_compiles_at_the_widest_slab_for_v5e(v5e):
    """bf16chain (what a block-dot mode is served by above BLOCKDOT_MAX_M)
    at 1024 rows against the 1B head's 8192-wide slabs: the one case of the
    retired `auto` cases that no other compiles under its resolved mode."""
    assert "tpu_custom_call" in _compile(v5e, "bf16chain", 2048, 131072, 1024)


# a narrow d_out keeps the whole half as one slab, so the kernel's chunk of x
# is all d_in columns: the DeepSeek indexer's 7168 x 128, Qwen2.5's wk / wv,
# and one four times as deep. The block sums are then taken in slices against
# one 0/1 matrix of at most BSUM_SLICE columns (whole, the matrix of 16384
# columns is 8M elements a grid step).
@pytest.mark.parametrize("mode,d_in,d_out,m", [
    (DEFAULT_MODE, 7168, 128, 512), (DEFAULT_MODE, 3584, 512, 1024),
    (DEFAULT_MODE, 16384, 128, 512),
])
def test_whole_half_narrow_plans_compile_for_v5e(v5e, mode, d_in, d_out, m):
    assert pq._plan_blocks(d_in, d_out) == (d_out, d_in // 2)
    assert d_in // pq._sum_slice(d_in) > 1 and pq._sum_slice(d_in) <= pq.BSUM_SLICE
    hlo = _compile(v5e, mode, d_in, d_out, m)
    assert "tpu_custom_call" in hlo and _lane_splits(hlo) == []
