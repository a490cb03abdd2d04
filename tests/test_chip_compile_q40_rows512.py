"""PR 45, one block of rows a call: the dense Q40 kernel's default mode at
512 rows at every distinct (d_in, d_out) the benchmark's seven configurations
send through it as a stack of layers (``CELL_SHAPES``, tests/chip_compile_util.py;
the heads, one plane each: test_chip_compile_q40_heads.py), compiled for a
described v5e. The list is held to the configuration files here."""

import os

import jax
import jax.numpy as jnp
import pytest

from distributed_llama_multiusers_tpu.ops import pallas_q40 as pq
from distributed_llama_multiusers_tpu.quants.packed import PackedQ40

from chip_compile_util import (  # noqa: F401  (v5e, v5e_devices: the fixtures)
    CELL_SHAPES,
    check_one_row_block,
    v5e,
    v5e_devices,
)


def _config_file_shapes():
    """{(d_in, d_out, stacked)} of every PackedQ40 leaf of rank 2 or 3 in the
    parameter trees the benchmark's families build from the ten files under
    benchmarks/configs/, by shape only (nothing is generated)."""
    import sys

    import latent_toy

    path = list(sys.path)
    sys.path[:0] = [latent_toy.BENCH_DIR, latent_toy.ROOT]
    try:
        from harness import cells

        bench = cells.load_benchmark()
        found = set()
        for name in sorted(os.listdir(os.path.join(latent_toy.BENCH_DIR, "configs"))):
            cfg = cells.load_config_file(bench, name[:-len(".json")])
            family = cells.load_family(cfg)
            config = family.program_config(cfg)
            tensors = jax.eval_shape(
                lambda: family.device_weights(config, 0, jnp.bfloat16))
            params = jax.eval_shape(
                lambda t: family.assemble_params(config, t), tensors)
            for w in jax.tree_util.tree_leaves(
                    params, is_leaf=lambda n: isinstance(n, PackedQ40)):
                if isinstance(w, PackedQ40) and w.packed.ndim in (2, 3):
                    found.add((w.packed.shape[-2] * 2, w.packed.shape[-1],
                               w.packed.ndim == 3))
        return found
    finally:
        sys.path[:] = path


def test_cell_shapes_are_what_the_config_files_give():
    found = _config_file_shapes()
    assert len(os.listdir(os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "configs"))) == 10
    assert all(pq._plan_blocks(d_in, d_out) for d_in, d_out, _ in found)
    assert found == set(CELL_SHAPES), found ^ set(CELL_SHAPES)
    # the 8192-wide tiles among them: the heads of Mistral and Qwen (6912 x
    # 22), Jamba's MLP
    wide = {(d_in, d_out) for d_in, d_out, _ in found
            if pq._plan_blocks(d_in, d_out)[0] == 8192}
    assert {(4096, 32768), (2560, 8192), (2560, 65536)} <= wide


@pytest.mark.parametrize("m", [512])
@pytest.mark.parametrize("d_in,d_out,stacked", [s for s in CELL_SHAPES if s[2]])
def test_one_row_block_compiles_for_v5e_at_every_cell_shape(
        v5e, d_in, d_out, stacked, m):
    check_one_row_block(v5e, d_in, d_out, stacked, m)
