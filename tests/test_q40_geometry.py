"""What the Q40 kernel's two modules and the CLI agree on without touching a
device: the block geometry's constants, the packed layout against the block
plan at the benchmark cells' widths, and the dequant modes ``--dequant`` /
``DLLAMA_DEQUANT`` offer: the six fixed arithmetics of
``ops/pallas_q40.DEQUANT_MODES`` and nothing else (``auto`` and its selection
table went with PR 46; it is refused like any unknown value).
"""

from __future__ import annotations

import pytest

from distributed_llama_multiusers_tpu.ops import pallas_q40 as pq
from distributed_llama_multiusers_tpu.ops.pallas_q40 import DEQUANT_MODES


# -- block geometry -------------------------------------------------------------


def test_block_geometry_constants_are_usable():
    """The kernel's block geometry is three constants (a chip measurement
    may move them, ROADMAP S2(b)(ii)). The widest block must be a positive
    multiple of 128, or no tile candidate divides any plane and every
    matmul silently takes the XLA fallback; and the block a plan aims for
    fits the VMEM bound."""
    from distributed_llama_multiusers_tpu.quants import packed

    assert packed.PALLAS_W_MAX > 0 and packed.PALLAS_W_MAX % 128 == 0
    assert 0 < pq.SINGLE_SLAB_BYTES <= pq.MAX_BLOCK_BYTES
    assert 0 < pq.TARGET_BLOCK_BYTES <= pq.MAX_BLOCK_BYTES


@pytest.mark.parametrize("d_in,d_out", [
    (4096, 4096), (4096, 1024), (4096, 14336), (14336, 4096),  # Mistral 7B
    (4096, 32768),  # its head
    (3584, 3584), (3584, 512), (3584, 18944), (18944, 3584),  # Qwen2.5 7B
    (3584, 152064),  # its head, which the loader pads
])
def test_packed_layout_and_block_plan_agree(d_in, d_out):
    """The two modules that share the geometry agree at the benchmark
    cells' widths: the width the packed layout pads a plane to is a width
    the kernel plans blocks for, inside its VMEM bound."""
    from distributed_llama_multiusers_tpu.quants import packed

    padded = packed.padded_d_out(d_out)
    w_tile = packed.pallas_wide_tile(padded)
    assert w_tile is not None and w_tile <= packed.PALLAS_W_MAX
    assert padded % w_tile == 0
    assert sum(packed.pallas_sub_tiles(w_tile)) == w_tile
    plan = pq._plan_blocks(d_in, padded)
    assert plan is not None and plan[0] == w_tile
    rows = plan[1]
    assert (d_in // 2) % rows == 0
    assert rows * w_tile <= pq.MAX_BLOCK_BYTES


# -- CLI pairing --------------------------------------------------------------


def test_args_dequant_choices_match_selectable_modes():
    """app/args.py stays jax-free, so its --dequant choices list is a
    hand-copied mirror of DEQUANT_MODES — this pins the pairing."""
    from distributed_llama_multiusers_tpu.app.args import build_parser

    parser = build_parser("test")
    action = next(a for a in parser._actions if a.dest == "dequant")
    assert tuple(action.choices) == DEQUANT_MODES
    assert action.default is None  # None -> leave the env/default alone


def test_auto_is_refused_by_the_flag_with_the_modes_listed(capsys):
    """``--dequant auto`` exits 2 by argparse's own choices check, and the
    message names the six modes there are."""
    from distributed_llama_multiusers_tpu.app.args import build_parser

    with pytest.raises(SystemExit) as exit_:
        build_parser("test").parse_args(["--dequant", "auto"])
    assert exit_.value.code == 2
    err = capsys.readouterr().err
    assert "invalid choice: 'auto'" in err and ", ".join(DEQUANT_MODES) in err


def test_auto_is_refused_by_the_environment_with_the_modes_listed(monkeypatch):
    """``DLLAMA_DEQUANT=auto`` fails where the variable is read (import of
    ops/pallas_q40, and ``set_dequant_mode(None)``), as a typo does; so does
    ``set_dequant_mode("auto")``. Both name the modes there are."""
    monkeypatch.setenv("DLLAMA_DEQUANT", "auto")
    with pytest.raises(ValueError, match="DLLAMA_DEQUANT='auto'") as e:
        pq._env_dequant_default()
    assert str(DEQUANT_MODES) in str(e.value)
    was = pq.DEQUANT_MODE
    with pytest.raises(ValueError, match="auto") as e:
        pq.set_dequant_mode("auto")
    assert str(DEQUANT_MODES) in str(e.value) and pq.DEQUANT_MODE == was
