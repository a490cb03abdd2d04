"""PR 45, one block of rows a call: the dense Q40 kernel's default mode at
512 and 1024 rows against the seven configurations' output heads, each one plane
of 8192-wide tiles or thereabouts (``CELL_SHAPES``' entries that are no stack,
tests/chip_compile_util.py), compiled for a described v5e."""

import pytest

from chip_compile_util import (  # noqa: F401  (v5e, v5e_devices: the fixtures)
    CELL_SHAPES,
    check_one_row_block,
    v5e,
    v5e_devices,
)


@pytest.mark.parametrize("m", [512, 1024])
@pytest.mark.parametrize("d_in,d_out,stacked", [s for s in CELL_SHAPES if not s[2]])
def test_one_row_block_compiles_for_v5e_at_every_cell_shape(
        v5e, d_in, d_out, stacked, m):
    check_one_row_block(v5e, d_in, d_out, stacked, m)
