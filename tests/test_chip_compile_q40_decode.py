"""The dense Q40 kernel at decode-width rows, compiled for a described v5e
(tests/chip_compile_util.py): every mode `--dequant` offers at the 1B / 8B
shapes, the narrow whole-half plans, an f32 operand, a layer of a stack under
a traced index, and the kernel under shard_map on a four-chip mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_llama_multiusers_tpu.ops import linear, pallas_q40 as pq, ring_collective
from distributed_llama_multiusers_tpu.quants.packed import PackedQ40

from chip_compile_util import (  # noqa: F401  (v5e, v5e_devices: the fixtures)
    AT_REST,
    DEFAULT_MODE,
    OTHER_MODES,
    SHAPES,
    STACK_SHAPES,
    TWO_SHAPES,
    _compile,
    _compile_stacked,
    _is_slab_chain,
    _lane_splits,
    _scales_stack_converted_whole,
    v5e,
    v5e_devices,
)


def test_default_mode_is_what_this_file_calls_default(monkeypatch):
    monkeypatch.delenv("DLLAMA_DEQUANT", raising=False)
    assert pq._env_dequant_default() == DEFAULT_MODE


# m = 1: decode. m = 16: a decode batch whose bf16 rows are one whole tile,
# handed over as they are (PR 42). (m = 1024: test_chip_compile_q40_prefill.py)
@pytest.mark.parametrize("m", [1, 16])
@pytest.mark.parametrize("d_in,d_out", SHAPES)
def test_default_mode_compiles_for_v5e(v5e, d_in, d_out, m):
    hlo = _compile(v5e, DEFAULT_MODE, d_in, d_out, m)
    assert "tpu_custom_call" in hlo
    assert _lane_splits(hlo) == []


@pytest.mark.parametrize("m", [1, 16])
@pytest.mark.parametrize("d_in,d_out", TWO_SHAPES)
@pytest.mark.parametrize("mode", OTHER_MODES)
def test_every_selectable_mode_compiles_for_v5e(v5e, mode, d_in, d_out, m):
    """A slab chain's program splits no lane of x."""
    hlo = _compile(v5e, mode, d_in, d_out, m)
    assert "tpu_custom_call" in hlo
    assert not (_is_slab_chain(mode, m) and _lane_splits(hlo))


# a narrow d_out keeps the whole half as one slab, so the kernel's chunk of x
# is all d_in columns: the DeepSeek indexer's 7168 x 128, Qwen2.5's wk / wv,
# and one four times as deep. The block sums are then taken in slices against
# one 0/1 matrix of at most BSUM_SLICE columns (whole, the matrix of 16384
# columns is 8M elements a grid step).
@pytest.mark.parametrize("mode,d_in,d_out,m", [
    (DEFAULT_MODE, 7168, 128, 16), (DEFAULT_MODE, 3584, 512, 32),
    (DEFAULT_MODE, 16384, 128, 16),
    ("bf16chain", 7168, 128, 16), ("repeat", 7168, 128, 16),
    ("u8chain", 7168, 128, 16),
])
def test_whole_half_narrow_plans_compile_for_v5e(v5e, mode, d_in, d_out, m):
    assert pq._plan_blocks(d_in, d_out) == (d_out, d_in // 2)
    assert d_in // pq._sum_slice(d_in) > 1 and pq._sum_slice(d_in) <= pq.BSUM_SLICE
    hlo = _compile(v5e, mode, d_in, d_out, m)
    assert "tpu_custom_call" in hlo and _lane_splits(hlo) == []


# an f32 x (no cell hands one over): rounded to the bf16 dot's dtype before
# its blocks are summed, or under an f32 dot summed at Precision.HIGHEST
@pytest.mark.parametrize("w_dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16_dot", "f32_dot"])
@pytest.mark.parametrize("d_in,d_out", TWO_SHAPES + [(7168, 128)])
def test_f32_operand_compiles_for_v5e(v5e, d_in, d_out, w_dtype):
    hlo = _compile(v5e, DEFAULT_MODE, d_in, d_out, 8, jnp.float32, w_dtype)
    assert "tpu_custom_call" in hlo and _lane_splits(hlo) == []


@pytest.mark.parametrize("fn,d_in,d_out,x_spec,w_spec,collective", [
    # wq/wk/wv/w1/w3/wcls: d_out sharded, no sync
    (ring_collective.tp_sliced_matmul, 2048, 8192, P(), P(None, "tp"), None),
    # wo/w2 with the ring off: d_in sharded, psum
    (ring_collective.tp_reduced_matmul, 8192, 2048, P(None, "tp"),
     P("tp", None), "all-reduce"),
    # wo/w2 by default: d_in sharded, ring-overlapped
    (ring_collective.ring_sync_matmul, 8192, 2048, P(None, "tp"),
     P("tp", None), "collective-permute"),
])
def test_pure_tp_kernel_paths_compile_for_a_v5e_mesh(
    v5e_devices, monkeypatch, fn, d_in, d_out, x_spec, w_spec, collective
):
    """libtpu has no custom-call partitioner, so a mesh reaches the kernel
    through shard_map only: each pure-TP form compiles for four described
    chips with the kernel and its collective in the program."""
    mesh = Mesh(np.array(v5e_devices).reshape(4), ("tp",))
    # ops/linear.py asks jax.devices(), which is the CPU here: steer it
    monkeypatch.setattr(
        linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas
    )

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    x = sds((8, d_in), jnp.bfloat16, x_spec)
    w = PackedQ40(packed=sds((d_in // 2, d_out), jnp.uint8, w_spec),
                  scales=sds((d_in // 32, d_out), jnp.float16, w_spec))
    hlo = jax.jit(lambda x, w: fn(x, w, mesh)).lower(x, w).compile().as_text()
    assert "tpu_custom_call" in hlo and "CustomSPMDPartitioning" not in hlo
    assert collective is None or collective in hlo


# Stacked weights (PR 30): the kernel reads layer l's tiles out of a [L, ...]
# stack by a scalar-prefetch index (STACK_SHAPES: tests/chip_compile_util.py)
@pytest.mark.parametrize("scales", [jnp.float16, AT_REST], ids=["float16", "at_rest"])
@pytest.mark.parametrize("prefill", [False], ids=["decode"])
@pytest.mark.parametrize("d_in,d_out,m", STACK_SHAPES)
def test_stacked_weight_default_mode_compiles_for_v5e(v5e, d_in, d_out, m, prefill, scales):
    """A stack as small as these four layers is one the engine leaves float16
    (``pq.reads_scales_in_place``): its layer's scale plane is sliced out and
    converted beside the call, the parent's program. Handed over at rest all
    the same (a direct caller), its plane is sliced out as bits and nothing
    converts (a stack read in place is compiled at a model's depth in
    tests/test_chip_compile_steps.py)."""
    hlo = _compile_stacked(v5e, DEFAULT_MODE, d_in, d_out, 1024 if prefill else m,
                           scales=scales)
    assert "tpu_custom_call" in hlo
    assert not _scales_stack_converted_whole(hlo, d_in, d_out)
    assert _lane_splits(hlo) == []
    assert f"= s16[{d_in // 32},{d_out}]" in hlo  # the kernel's scale operand: one plane
    assert hlo.count(" bitcast-convert(") == (0 if scales == AT_REST else 1)


@pytest.mark.parametrize("prefill", [False], ids=["decode"])
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
