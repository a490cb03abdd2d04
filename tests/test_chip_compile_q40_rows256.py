"""PR 49, the -8 taken off in the dequant chain: the dense Q40 kernel's default
mode at 256 rows (the smallest block any cell runs that subtracts: a 256-row
prefill bucket, Jamba's 256 lanes; ``SUBTRACT_MIN_ROWS`` is 128) at every distinct
(d_in, d_out) the benchmark's seven configurations send through it
(``CELL_SHAPES``, tests/chip_compile_util.py), stacks and heads, compiled for a
described v5e. The 512- and 1024-row blocks of the same body:
test_chip_compile_q40_rows512.py, _rows1024.py, _heads.py."""

import pytest

from distributed_llama_multiusers_tpu.ops import pallas_q40 as pq

from chip_compile_util import (  # noqa: F401  (v5e, v5e_devices: the fixtures)
    CELL_SHAPES,
    check_one_row_block,
    v5e,
    v5e_devices,
)


@pytest.mark.parametrize("m", [256])
@pytest.mark.parametrize("d_in,d_out,stacked", CELL_SHAPES)
def test_subtracting_block_compiles_for_v5e_at_every_cell_shape(
        v5e, d_in, d_out, stacked, m):
    assert m >= pq.SUBTRACT_MIN_ROWS  # the body without the correction dot
    check_one_row_block(v5e, d_in, d_out, stacked, m)
