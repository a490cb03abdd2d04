"""The grouped expert kernel (ops/pallas_q40_grouped.py) compiled for a
described v5e (tests/chip_compile_util.py), at the benchmark's routed widths
and at every tile height the rule gives their decode steps and chunks."""

import jax
import jax.numpy as jnp
import pytest

from chip_compile_util import v5e, v5e_devices  # noqa: F401  (the fixtures)

# one expert's w1 / w3 and w2 at the benchmark's routed width, 128 experts
EXPERT_SHAPES = [(2048, 768), (768, 2048)]


@pytest.mark.parametrize("rows", [32, 256, 512, 1024], ids=["decode", "prefill256", "prefill512", "prefill1024"])
@pytest.mark.parametrize("d_in,d_out", EXPERT_SHAPES)
def test_grouped_expert_kernel_compiles_for_v5e(v5e, d_in, d_out, rows):
    """The grouped Q40 kernel at 6 experts a token of 128, at decode width (8
    rows a tile) and at the prefill buckets (the heights the rule gives
    groups of 12, 24 and 48 rows)."""
    from distributed_llama_multiusers_tpu.ops import pallas_q40_grouped as pg
    from distributed_llama_multiusers_tpu.quants.packed import Q40Experts

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    L, E, a = 3, 128, rows * 6
    tm = pg.tile_rows(a, E, d_in * d_out)
    n_tiles = pg.max_tiles(a, E, tm)
    w = Q40Experts(sds((L, E, d_in // 2, d_out), jnp.uint8),
                   sds((L, E, d_in // 32, d_out), jnp.int16))
    assert pg.grouped_supports(w)
    hlo = pg._grouped_impl.lower(
        sds((n_tiles * tm, d_in), jnp.bfloat16), w, sds((), jnp.int32),
        sds((n_tiles,), jnp.int32), sds((), jnp.int32),
        interpret=False, w_dtype=jnp.bfloat16,
    ).compile().as_text()
    assert "tpu_custom_call" in hlo
    # the stack goes in whole and is read by id: no slab, layer or stack of it
    # is the result of a slice, a copy or a fusion
    assert f"= u8[{E},{d_in // 2},{d_out}]" not in hlo
    assert f"= u8[{L},{E},{d_in // 2},{d_out}]" not in hlo.split("ENTRY")[0]


@pytest.mark.parametrize("rows", [64, 256, 512, 1024], ids=["decode", "prefill256", "prefill512", "prefill1024"])
@pytest.mark.parametrize("d_in,d_out", [(2048, 1536), (1536, 2048)])
def test_grouped_expert_kernel_walks_a_wide_slab_for_v5e(v5e, d_in, d_out, rows):
    """The grouped kernel at LFM2's expert width (4 experts a token of 64):
    a 1.5 MiB slab walked in two reduction blocks, at 8 rows a tile and at
    the heights its chunks get; no slab, layer or stack leaves the stack."""
    from distributed_llama_multiusers_tpu.ops import pallas_q40_grouped as pg
    from distributed_llama_multiusers_tpu.quants.packed import Q40Experts

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    L, E, a = 3, 64, rows * 4
    tm = pg.tile_rows(a, E, d_in * d_out)
    assert tm == 8 if rows == 64 else tm > 8
    n_tiles = pg.max_tiles(a, E, tm)
    w = Q40Experts(sds((L, E, d_in // 2, d_out), jnp.uint8),
                   sds((L, E, d_in // 32, d_out), jnp.int16))
    assert pg.slab_blocks(d_in, d_out) == 2 and pg.grouped_supports(w)
    hlo = pg._grouped_impl.lower(
        sds((n_tiles * tm, d_in), jnp.bfloat16), w, sds((), jnp.int32),
        sds((n_tiles,), jnp.int32), sds((), jnp.int32),
        interpret=False, w_dtype=jnp.bfloat16,
    ).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert f"= u8[{E},{d_in // 2},{d_out}]" not in hlo
    assert f"= u8[{L},{E},{d_in // 2},{d_out}]" not in hlo.split("ENTRY")[0]
