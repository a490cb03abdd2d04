"""Ahead-of-time compiles for a DESCRIBED TPU v5e (no chip attached).

Interpret mode proves a Pallas kernel's arithmetic; only the chip's own
compiler (Mosaic, inside libtpu, which is installed here) proves the kernel
exists on the chip. PR 21 found three of the six dequant chains refused by
it while every interpret-mode test was green — an 8-bit-lane shift in
u8chain / i8blockdot, a gather in blockdot — and every chain refused at
prefill widths (a 256-row m tile against an 8192-wide slab overran the
default scoped-VMEM limit). These compiles guard every later PR at no chip
time: each mode `--dequant` offers, and each mode ops/dequant_table.json can
resolve `auto` to, must compile at the matmul shapes of Llama-3.2-1B and
Llama-3.1-8B. A compile that passes is not a chip run — numerics on the chip
are chip_smoke.py's kernel phase.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep libtpu's logs out of /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from distributed_llama_multiusers_tpu.ops import (
    dequant_select,
    linear,
    pallas_q40 as pq,
    ring_collective,
)
from distributed_llama_multiusers_tpu.quants.packed import PackedQ40

# (d_in, d_out): 1B wq/wo, wk/wv, w1/w3, w2, wcls (vocab padded to the wide
# tile); 8B w1/w3, w2
SHAPES = [
    (2048, 2048), (2048, 512), (2048, 8192), (8192, 2048), (2048, 131072),
    (4096, 14336), (14336, 4096),
]
# one single-chunk plan (direct write, 64 unrolled quant blocks) and one
# multi-chunk, two-wide-tile plan (the f32 accumulator path)
TWO_SHAPES = [(2048, 512), (4096, 14336)]
DEFAULT_MODE = "v4"
OTHER_MODES = [m for m in pq.SELECTABLE_MODES if m != DEFAULT_MODE]


@pytest.fixture(scope="module")
def v5e_devices():
    """The four described chips of a v5e 2x2 host, persistent compile cache
    off around the module: an AOT executable is written to the cache but
    cannot be read back without a chip, and the next compile would warn
    about it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e topology: {type(e).__name__}: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield list(topo.devices)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e(v5e_devices):
    return SingleDeviceSharding(v5e_devices[0])


def _routed_mode(mode: str, d_in: int, d_out: int, m: int) -> str:
    """The kernel mode q40_matmul_pallas would hand its jitted entry."""
    if mode == "auto":
        mode = dequant_select.DequantTable().resolve(
            d_in, d_out, dequant_select.m_class_of(m)
        )
    if mode in ("blockdot", "i8blockdot") and m > pq.BLOCKDOT_MAX_M:
        mode = "bf16chain"
    return mode


def _compile(sharding, mode: str, d_in: int, d_out: int, m: int,
             x_dtype=jnp.bfloat16, w_dtype=jnp.bfloat16) -> str:
    """Compile the bf16-dot kernel the way q40_matmul_pallas routes it and
    return the optimized HLO text."""
    x = jax.ShapeDtypeStruct((m, d_in), x_dtype, sharding=sharding)
    w = PackedQ40(
        packed=jax.ShapeDtypeStruct((d_in // 2, d_out), jnp.uint8,
                                    sharding=sharding),
        scales=jax.ShapeDtypeStruct((d_in // 32, d_out), jnp.float16,
                                    sharding=sharding),
    )
    return pq._q40_matmul_pallas_impl.lower(
        x, w, interpret=False, w_dtype=w_dtype,
        mode=_routed_mode(mode, d_in, d_out, m),
    ).compile().as_text()


def _lane_splits(hlo: str) -> list[str]:
    """Arrays `[rows, n_blk, 16]` / `[rows, n_blk, 2, 16]` in the compiled
    program: an activation's lane axis split into quant-block halves, which
    XLA:TPU does by a physical relayout. Since PR 42 the slab chains take x
    as it is and no program of theirs makes one."""
    import re

    return sorted(set(re.findall(r"(?:f32|bf16)\[\d+,\d+,(?:2,)?16\]", hlo)))


def _is_slab_chain(mode: str, d_in: int, d_out: int, m: int) -> bool:
    return _routed_mode(mode, d_in, d_out, m) not in pq.BLOCK_DOT_MODES


def test_default_mode_is_what_this_file_calls_default(monkeypatch):
    monkeypatch.delenv("DLLAMA_DEQUANT", raising=False)
    assert pq._env_dequant_default() == DEFAULT_MODE


# m = 1: decode. m = 1024: the widest default prefill bucket — since PR 45
# one block of 1024 rows (four 256-row m tiles before), the plan with the
# largest VMEM footprint (m = 128 is a smaller block of the same plan).
# m = 16: a decode batch whose bf16 rows are one whole tile, handed over as
# they are (PR 42).
@pytest.mark.parametrize("m", [1, 16, 1024])
@pytest.mark.parametrize("d_in,d_out", SHAPES)
def test_default_mode_compiles_for_v5e(v5e, d_in, d_out, m):
    hlo = _compile(v5e, DEFAULT_MODE, d_in, d_out, m)
    assert "tpu_custom_call" in hlo
    assert _lane_splits(hlo) == []


@pytest.mark.parametrize("m", [1, 16, 1024])
@pytest.mark.parametrize("d_in,d_out", TWO_SHAPES)
@pytest.mark.parametrize("mode", OTHER_MODES)
def test_every_selectable_mode_compiles_for_v5e(v5e, mode, d_in, d_out, m):
    """At 1024 rows the block-dot modes route to bf16chain, as they are
    served. A slab chain's program splits no lane of x."""
    hlo = _compile(v5e, mode, d_in, d_out, m)
    assert "tpu_custom_call" in hlo
    assert not (_is_slab_chain(mode, d_in, d_out, m) and _lane_splits(hlo))


# a narrow d_out keeps the whole half as one slab, so the kernel's chunk of x
# is all d_in columns: the DeepSeek indexer's 7168 x 128, Qwen2.5's wk / wv,
# and one four times as deep. The block sums are then taken in slices against
# one 0/1 matrix of at most BSUM_SLICE columns (whole, the matrix of 16384
# columns is 8M elements a grid step).
@pytest.mark.parametrize("mode,d_in,d_out,m", [
    (DEFAULT_MODE, 7168, 128, 16), (DEFAULT_MODE, 7168, 128, 512),
    (DEFAULT_MODE, 3584, 512, 32), (DEFAULT_MODE, 3584, 512, 1024),
    (DEFAULT_MODE, 16384, 128, 16), (DEFAULT_MODE, 16384, 128, 512),
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


@pytest.mark.parametrize("d_in,d_out", [(2048, 131072), (4096, 14336)])
def test_auto_prefill_class_compiles_for_v5e(v5e, d_in, d_out):
    """What `auto` resolves prefill-wide calls to, at the two widest slabs."""
    assert "tpu_custom_call" in _compile(v5e, "auto", d_in, d_out, 1024)


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
# stack by a scalar-prefetch index. (d_in, d_out, decode m) of the seven
# planes of a layer at the benchmark's two configurations: Mistral-7B
# (16 lanes) wq/wo, wk/wv, w1/w3, w2; Qwen2.5-7B (32 lanes) the same
STACK_SHAPES = [
    (4096, 4096, 16), (4096, 1024, 16), (4096, 14336, 16), (14336, 4096, 16),
    (3584, 3584, 32), (3584, 512, 32), (3584, 18944, 32), (18944, 3584, 32),
]
STACK_LAYERS = 4


def _compile_stacked(sharding, mode: str, d_in: int, d_out: int, m: int) -> str:
    """As `_compile`, the weight a stack and the layer a traced scalar."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    x = sds((m, d_in), jnp.bfloat16)
    w = PackedQ40(
        packed=sds((STACK_LAYERS, d_in // 2, d_out), jnp.uint8),
        scales=sds((STACK_LAYERS, d_in // 32, d_out), jnp.float16),
    )
    return pq._q40_matmul_pallas_impl.lower(
        x, w, interpret=False, w_dtype=jnp.bfloat16,
        mode=_routed_mode(mode, d_in, d_out, m), layer=sds((), jnp.int32),
    ).compile().as_text()


def _scales_stack_converted_whole(hlo: str, d_in: int, d_out: int) -> bool:
    """Whether the program makes the WHOLE stack's scale bit patterns: for
    XLA:TPU f16 -> s16 is a pass over the data, so the kernel converts one
    layer's slice (ops/pallas_q40.py); hoisted out of a layer loop, the
    stack's conversion was 441 MB of temporaries at 7B widths."""
    return f"= s16[{STACK_LAYERS},{d_in // 32},{d_out}]" in hlo


@pytest.mark.parametrize("prefill", [False, True], ids=["decode", "prefill1024"])
@pytest.mark.parametrize("d_in,d_out,m", STACK_SHAPES)
def test_stacked_weight_default_mode_compiles_for_v5e(v5e, d_in, d_out, m, prefill):
    hlo = _compile_stacked(v5e, DEFAULT_MODE, d_in, d_out, 1024 if prefill else m)
    assert "tpu_custom_call" in hlo
    assert not _scales_stack_converted_whole(hlo, d_in, d_out)
    assert _lane_splits(hlo) == []


@pytest.mark.parametrize("prefill", [False, True], ids=["decode", "prefill1024"])
@pytest.mark.parametrize("d_in,d_out,m", [(4096, 14336, 16), (3584, 512, 32)])
@pytest.mark.parametrize("mode", OTHER_MODES)
def test_stacked_weight_every_selectable_mode_compiles_for_v5e(
        v5e, mode, d_in, d_out, m, prefill):
    """Every mode `--dequant` offers and `auto` can resolve to, at a
    multi-chunk two-wide-tile plan and at a single-slab plan, at decode
    width and at 1024 rows (where a block-dot mode is served by bf16chain)."""
    m = 1024 if prefill else m
    hlo = _compile_stacked(v5e, mode, d_in, d_out, m)
    assert "tpu_custom_call" in hlo
    assert not (_is_slab_chain(mode, d_in, d_out, m) and _lane_splits(hlo))


# PR 45: one block of rows a call. Every distinct (d_in, d_out) that the
# benchmark's six configurations send through the slab kernel (the routed
# experts' [L, E, ...] slabs go through ops/pallas_q40_grouped.py), and
# whether the configuration holds it as a stack of layers (the kernel is
# handed the stack and a layer index) or as one plane (the heads). The list
# is held to the files by ``test_cell_shapes_are_what_the_config_files_give``.
CELL_SHAPES = [
    (1536, 2048, True), (1536, 8192, True), (1536, 24576, True),
    (2048, 512, True), (2048, 576, True), (2048, 1536, True),
    (2048, 2048, True), (2048, 6144, True), (2048, 7168, True),
    (2048, 11776, True), (2048, 65536, False), (2048, 131072, False),
    (2560, 128, True), (2560, 2560, True), (2560, 8192, True),
    (2560, 10240, True), (2560, 65536, False), (3584, 512, True),
    (3584, 3584, True), (3584, 18944, True), (3584, 152064, False),
    (4096, 1024, True), (4096, 2048, True), (4096, 4096, True),
    (4096, 14336, True), (4096, 32768, False), (5120, 192, True),
    (5120, 2560, True), (6144, 2048, True), (7168, 128, True),
    (7168, 576, True), (7168, 1536, True), (7168, 2048, True),
    (7168, 16384, False), (7168, 18432, True), (8192, 2560, True),
    (11776, 2048, True), (14336, 4096, True), (16384, 7168, True),
    (18432, 7168, True), (18944, 3584, True),
]


def _config_file_shapes():
    """{(d_in, d_out, stacked)} of every PackedQ40 leaf of rank 2 or 3 in the
    parameter trees the benchmark's families build from the six files under
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
        os.path.dirname(__file__), "..", "benchmarks", "configs"))) == 6
    assert all(pq._plan_blocks(d_in, d_out) for d_in, d_out, _ in found)
    assert found == set(CELL_SHAPES), found ^ set(CELL_SHAPES)
    # the 8192-wide tiles among them: the heads of Mistral and Qwen (6912 x
    # 22), Jamba's MLP
    wide = {(d_in, d_out) for d_in, d_out, _ in found
            if pq._plan_blocks(d_in, d_out)[0] == 8192}
    assert {(4096, 32768), (2560, 8192), (2560, 65536)} <= wide


@pytest.mark.parametrize("m", [512, 1024])
@pytest.mark.parametrize("d_in,d_out,stacked", CELL_SHAPES)
def test_one_row_block_compiles_for_v5e_at_every_cell_shape(
        v5e, d_in, d_out, stacked, m):
    """The default mode at the two prefill buckets above 256 rows, where the
    block of rows is now the call's rows (PR 45): Mosaic takes the x block,
    the f32 accumulator and the output block of ``m`` rows against every
    wide tile the cells have, under the ceiling the plan asks for; one
    kernel call, no lane of x split."""
    w_tile, rows = pq._plan_blocks(d_in, d_out)
    n_k = (d_in // 2) // rows
    assert pq._row_plan(m, w_tile, rows, n_k, 2)[0] == m  # one pass
    compile_ = _compile_stacked if stacked else _compile
    hlo = compile_(v5e, DEFAULT_MODE, d_in, d_out, m)
    assert hlo.count("tpu_custom_call") == 1
    assert _lane_splits(hlo) == []
    if stacked:
        assert not _scales_stack_converted_whole(hlo, d_in, d_out)


def _three_layer_decode_hlo(v5e, monkeypatch, lanes=16, n_heads=32, n_kv=8):
    """The optimized HLO of a three-layer decode forward (one row a lane, the
    cache donated) for a described v5e, and its dimensions: Mistral-7B's
    widths, or Qwen2.5-7B's at 28 heads."""
    from distributed_llama_multiusers_tpu.models import llama
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    monkeypatch.setattr(
        linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas
    )
    L, d, kv, vocab, seq = 3, n_heads * 128, n_kv * 128, 8192, 256
    h = {4096: 14336, 3584: 18944}[d]
    cfg = LlamaConfig(dim=d, hidden_dim=h, n_layers=L, n_heads=n_heads,
                      n_kv_heads=n_kv, vocab_size=vocab, seq_len=seq)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    q40 = lambda d_in, d_out, lead=(L,): PackedQ40(
        packed=sds(lead + (d_in // 2, d_out), jnp.uint8),
        scales=sds(lead + (d_in // 32, d_out), jnp.float16))
    params = llama.LlamaParams(
        embedding=sds((vocab, d), jnp.bfloat16),
        layers=llama.LlamaLayerParams(
            wq=q40(d, d), wk=q40(d, kv), wv=q40(d, kv), wo=q40(d, d),
            w1=q40(d, h), w2=q40(h, d), w3=q40(d, h),
            rms_att=sds((L, d), jnp.float32), rms_ffn=sds((L, d), jnp.float32)),
        rms_final=sds((d,), jnp.float32), wcls=q40(d, vocab, ()),
        rope_cos=sds((seq, 64), jnp.float32), rope_sin=sds((seq, 64), jnp.float32))
    cache = llama.KVCache(*(sds((L, lanes, seq, n_kv, 128), jnp.bfloat16),) * 2)
    tok = sds((lanes, 1), jnp.int32)
    hlo = jax.jit(
        lambda p, t, c: llama.llama_forward(cfg, p, t, t, c), donate_argnums=(2,)
    ).lower(params, tok, cache).compile().as_text()
    return hlo, dict(L=L, d=d, h=h, kv=kv, lanes=lanes, seq=seq, n_kv=n_kv)


@pytest.mark.parametrize("reads_stack", [True, False],
                         ids=["kernel_reads_stack", "control_scanned_planes"])
def test_layer_loop_slices_no_q40_plane_for_v5e(v5e, monkeypatch, reads_stack):
    """The optimized HLO of a three-layer decode forward at (4096, 14336) and
    the other planes of that width: no slice, fusion or copy has a nibble
    plane's shape as its result. The control scans the planes as the program
    did before PR 30, and shows the slices this check looks for. (Stacks as
    small as three layers XLA may stage WHOLE in fast memory ahead of the
    loop, by `slice-start`s of its own; that is not what is looked for.)
    Nine kernel calls: seven Q40 matmuls a layer body and the head, and since
    PR 32 the decode attention that reads the cache in place."""
    import re

    from distributed_llama_multiusers_tpu.models import llama

    if not reads_stack:
        monkeypatch.setattr(llama, "reads_q40_stack", lambda w: False)
    hlo, dims = _three_layer_decode_hlo(v5e, monkeypatch)
    d, h, kv = dims["d"], dims["h"], dims["kv"]
    assert hlo.count("tpu_custom_call") == 9
    # a plane sliced out for a kernel call: the result of a slice fusion of
    # its own (`[1, d_in/2, d_out]`: the kernel takes a plane as a stack of one)
    planes = {(a // 2, b) for a, b in ((d, d), (d, kv), (d, h), (h, d))}
    sliced = {(int(r), int(w)) for r, w in re.findall(
        r"= u8\[(?:1,)?(\d+),(\d+)\]\S* (?:fusion|dynamic-slice|copy)\(", hlo)}
    sliced &= planes
    if reads_stack:
        assert sliced == set(), sliced
    else:
        assert sliced == planes, sliced


@pytest.mark.parametrize("mode,splits", [(DEFAULT_MODE, False), ("blockdot", True)],
                         ids=["x_as_it_is", "control_block_dot_operands"])
def test_decode_forward_splits_no_activation_lane_for_v5e(v5e, monkeypatch, mode, splits):
    """The compiled three-layer decode forward at Mistral-7B's widths holds
    no `[16, 128, 16]` / `[16, 448, 16]` / `[16, 448, 2, 16]` array: the
    operations that were 3.65 ms of an 18.8 ms decode step (PERF.md section
    6, PR 42). The control compiles the same forward in the mode that still
    takes pre-split operands and finds them."""
    import re

    monkeypatch.setattr(pq, "DEQUANT_MODE", mode)
    hlo, dims = _three_layer_decode_hlo(v5e, monkeypatch)
    assert hlo.count("tpu_custom_call") == 9
    if not splits:
        # x reaches each of the eight Q40 kernels as the bf16 the model made
        # (its second operand, after the layer index): rounded once, for the
        # dot and for the block sums alike
        made = dict(re.findall(r"(%[\w.\-]+) = (\w+\[[\d,]*\])", hlo))
        x_ops = [made[ops.split(",")[1].strip()] for ops in re.findall(
            r"%_q40_matmul_\w+\.\d+ = \S+ custom-call\(([^)]*)\)", hlo)]
        widths = {dims["d"], dims["h"]}
        assert len(x_ops) == 8 and all(
            re.fullmatch(rf"bf16\[{dims['lanes']},(\d+)\]", x)
            and int(x.split(",")[1][:-1]) in widths for x in x_ops), x_ops
    blocks = {dims["d"] // 32, dims["h"] // 32}
    found = [s for s in _lane_splits(hlo)
             if int(s.split(",")[1]) in blocks and s.split("[")[1].startswith(f"{dims['lanes']},")]
    assert bool(found) == splits, found


def _results_of_shape(hlo: str, shape: str) -> list[str]:
    """Instructions that MAKE an array of ``shape`` (a regex): a slice, a
    fusion, a copy or a conversion, in any layout. The in-place appends (a
    scatter fusion whose operand is the stack it returns) are what a decode
    step is allowed; parameters, tuple elements and bitcasts move nothing."""
    import re

    made = re.findall(
        rf"^\s*(?:ROOT )?(\S+) = {shape}\S* "
        r"(fusion|dynamic-slice|slice|copy|convert|transpose|copy-start)\((.*)$",
        hlo, flags=re.M)
    return [f"{name} = {op}" for name, op, rest in made
            if not (op == "fusion" and "dl.kv_write" in rest)]


def _cache_sized_results(hlo: str, L, lanes, seq, n_kv) -> list[str]:
    """What makes an array of the size of a K or V plane or of the stack, in
    the carry's shape or with (S, n_kv) merged (``_results_of_shape``)."""
    lead = rf"(?:{L},|1,)?{lanes},"
    return _results_of_shape(
        hlo, rf"(?:bf16|f32)\[{lead}(?:{seq},{n_kv}|{seq * n_kv}),128\]")


# Decode attention in place (PR 32, ops/pallas_attention.py): (lanes, n_heads,
# n_kv) of the benchmark's two configurations at their cells' lanes, bf16,
# 2048 positions
ATTENTION_SHAPES = [(16, 32, 8), (32, 28, 4)]


@pytest.mark.parametrize("lanes,n_heads,n_kv", ATTENTION_SHAPES,
                         ids=["mistral7b", "qwen25_7b"])
def test_decode_attention_compiles_for_v5e(v5e, lanes, n_heads, n_kv):
    """Mosaic takes the kernel at both head shapes, the stack of a few layers
    as the carry holds it, the layer and the work list traced."""
    from distributed_llama_multiusers_tpu.ops import pallas_attention as pa

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    stack = sds((STACK_LAYERS, lanes, 2048, n_kv, 128), jnp.bfloat16)

    def attend(q, k, v, layer, positions):
        return pa.decode_attention(
            q, k, v, layer, pa.lane_blocks(positions, 2048), 128 ** -0.5)

    hlo = jax.jit(attend).lower(
        sds((lanes, n_heads, 128), jnp.bfloat16), stack, stack,
        sds((), jnp.int32), sds((lanes,), jnp.int32),
    ).compile().as_text()
    assert "tpu_custom_call" in hlo and "decode_attention" in hlo
    # merging (S, n_kv) for the kernel moved no byte of either stack
    assert not _cache_sized_results(hlo, STACK_LAYERS, lanes, 2048, n_kv)


@pytest.mark.parametrize("in_place", [True, False],
                         ids=["kernel_reads_in_place", "control_plane_reads"])
@pytest.mark.parametrize("lanes,n_heads,n_kv", ATTENTION_SHAPES,
                         ids=["mistral7b", "qwen25_7b"])
def test_decode_forward_reads_no_kv_plane_for_v5e(
        v5e, monkeypatch, lanes, n_heads, n_kv, in_place):
    """The optimized HLO of a three-layer decode forward: nothing has a K or V
    plane, or the stack, as its result but the two in-place appends: the
    kernel is handed the carry. The control patches the kernel's predicate
    off, as the program was before PR 32, and shows what the check looks
    for: each plane read out of the stack (and, at 4 kv heads, copied)."""
    from distributed_llama_multiusers_tpu.models import llama

    if not in_place:
        monkeypatch.setattr(llama, "decode_attention_engages",
                            lambda cache, mesh, n_heads: False)
    hlo, dims = _three_layer_decode_hlo(v5e, monkeypatch, lanes, n_heads, n_kv)
    assert hlo.count("decode_attention") >= int(in_place)
    made = _cache_sized_results(hlo, dims["L"], lanes, dims["seq"], n_kv)
    if in_place:
        assert made == [], made
    else:
        reads = [m for m in made if "dynamic-slice" in m or "fusion" in m]
        assert len(reads) >= 2, made  # K's plane and V's


# -- the latent-attention block with a routed FFN (models/deepseek.py) --------

# one expert's w1 / w3 and w2 at the benchmark's routed width, 128 experts
EXPERT_SHAPES = [(2048, 768), (768, 2048)]


@pytest.mark.parametrize("rows", [32, 1024], ids=["decode", "prefill1024"])
@pytest.mark.parametrize("d_in,d_out", EXPERT_SHAPES)
def test_grouped_expert_kernel_compiles_for_v5e(v5e, d_in, d_out, rows):
    """The grouped Q40 kernel at 6 experts a token of 128, at decode width (8
    rows a tile) and at the widest prefill bucket (128 rows a tile)."""
    from distributed_llama_multiusers_tpu.ops import pallas_q40_grouped as pg
    from distributed_llama_multiusers_tpu.quants.packed import Q40Experts

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    L, E, a = 3, 128, rows * 6
    tm = pg.tile_rows(a, E)
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


@pytest.mark.parametrize("rows", [64, 1024], ids=["decode", "prefill1024"])
@pytest.mark.parametrize("d_in,d_out", [(2048, 1536), (1536, 2048)])
def test_grouped_expert_kernel_walks_a_wide_slab_for_v5e(v5e, d_in, d_out, rows):
    """The grouped kernel at LFM2's expert width (4 experts a token of 64):
    a 1.5 MiB slab walked in two reduction blocks, at 8 and at 128 rows a
    tile; no slab, layer or stack leaves the stack."""
    from distributed_llama_multiusers_tpu.ops import pallas_q40_grouped as pg
    from distributed_llama_multiusers_tpu.quants.packed import Q40Experts

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    L, E, a = 3, 64, rows * 4
    tm = pg.tile_rows(a, E)
    assert tm == (8 if rows == 64 else 128)
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


def test_latent_decode_forward_copies_no_cache_and_no_expert_stack_for_v5e(v5e, monkeypatch):
    """Three layers (one dense, two routed) of the benchmark's latent block at
    its published widths, one row a lane, the cache donated: the kernels are
    there (wq, wkva, wo and the dense FFN or the grouped and shared experts,
    the head), the latent stack is the result of its in-place scatters alone
    (no copy, no relayout: a size-one head axis cost four whole-stack copies,
    PR 33), and no expert plane leaves its stack."""
    import re

    from distributed_llama_multiusers_tpu.models import deepseek
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig
    from distributed_llama_multiusers_tpu.models.llama import KVCache
    from distributed_llama_multiusers_tpu.quants.packed import Q40Experts

    monkeypatch.setattr(linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas)
    L, Lm, E, d, lanes, seq, vocab = 3, 2, 128, 2048, 32, 512, 8192
    cfg = LlamaConfig(
        dim=d, hidden_dim=6144, n_layers=L, n_heads=32, n_kv_heads=32, vocab_size=vocab,
        seq_len=seq, norm_epsilon=1e-6, n_experts=E, n_active_experts=6, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, moe_hidden_dim=768,
        shared_hidden_dim=1536, n_dense_layers=1, moe_score_func=1, moe_select_bias=1,
        moe_routed_scale=2.448)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    q40 = lambda d_in, d_out, lead: PackedQ40(
        packed=sds(lead + (d_in // 2, d_out), jnp.uint8),
        scales=sds(lead + (d_in // 32, d_out), jnp.float16))
    experts = lambda d_in, d_out: Q40Experts(
        sds((Lm, E, d_in // 2, d_out), jnp.uint8), sds((Lm, E, d_in // 32, d_out), jnp.int16))
    params = deepseek.DeepseekParams(
        embedding=sds((vocab, d), jnp.bfloat16),
        attn=deepseek.LatentAttnParams(
            wq=q40(d, 32 * 192, (L,)), wkva=q40(d, 576, (L,)),
            wuk=sds((L, 32, 128, 512), jnp.bfloat16), wuv=sds((L, 32, 512, 128), jnp.bfloat16),
            wo=q40(32 * 128, d, (L,)),
            rms_att=sds((L, d), jnp.float32), rms_kv=sds((L, 512), jnp.float32)),
        dense=deepseek.DenseFfnParams(
            w1=q40(d, 6144, (1,)), w2=q40(6144, d, (1,)), w3=q40(d, 6144, (1,)),
            rms_ffn=sds((1, d), jnp.float32)),
        routed=deepseek.RoutedFfnParams(
            gate=sds((Lm, d, E), jnp.float32), bias=sds((Lm, E), jnp.float32),
            w1=experts(d, 768), w2=experts(768, d), w3=experts(d, 768),
            s1=q40(d, 1536, (Lm,)), s2=q40(1536, d, (Lm,)), s3=q40(d, 1536, (Lm,)),
            rms_ffn=sds((Lm, d), jnp.float32)),
        rms_final=sds((d,), jnp.float32), wcls=q40(d, vocab, ()),
        rope_cos=sds((seq, 32), jnp.float32), rope_sin=sds((seq, 32), jnp.float32))
    cache = KVCache(sds((L, lanes, seq, 512), jnp.bfloat16), sds((L, lanes, seq, 128), jnp.bfloat16))
    tok = sds((lanes, 1), jnp.int32)
    hlo = jax.jit(
        lambda p, t, c: deepseek.deepseek_forward(cfg, p, t, t, c), donate_argnums=(2,)
    ).lower(params, tok, cache).compile().as_text()
    # layer 0: wq, wkva, wo, w1, w3, w2; the scan's body: wq, wkva, wo, three
    # grouped products, the shared experts' three; the head
    assert hlo.count("tpu_custom_call") == 16
    stack = rf"bf16\[{L},{lanes},{seq},512\]"
    # (a stack this small XLA may stage whole in fast memory by copy-start /
    # copy-done of its own, as the Llama block's test above notes; a plain
    # copy of it is what is looked for)
    assert not re.search(rf"= {stack}\S* copy\(", hlo)
    assert f"= u8[{E},1024,768]" not in hlo and f"= u8[{E},384,2048]" not in hlo


def _pattern_decode_hlo(v5e, monkeypatch, periods: int, seq: int):
    """The optimized HLO of the benchmark's layer-pattern block at its
    published widths, ``periods`` times ``c c A c`` (two dense layers, then
    whole periods ``A c c c`` of routed layers in the scan and an odd tail
    ``A c``), one row a lane at 64 lanes, the cache donated; and its
    dimensions."""
    from distributed_llama_multiusers_tpu.models import hybrid
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig
    from distributed_llama_multiusers_tpu.models.deepseek import DenseFfnParams, RoutedFfnParams
    from distributed_llama_multiusers_tpu.quants.packed import Q40Experts

    monkeypatch.setattr(linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas)
    kinds = (1, 1, 0, 1) * periods
    L, Ld, La, E, d, lanes, vocab = 4 * periods, 2, periods, 64, 2048, 64, 8192
    Lm, Lc = L - Ld, L - La
    cfg = LlamaConfig(
        dim=d, hidden_dim=11776, n_layers=L, n_heads=32, n_kv_heads=8, vocab_size=vocab,
        seq_len=seq, rope_theta=1e6, n_experts=E, n_active_experts=4, moe_hidden_dim=1536,
        n_dense_layers=Ld, moe_score_func=1, moe_select_bias=1, layer_kinds=kinds,
        conv_kernel=3, qk_norm=1)
    assert hybrid.layer_periods(kinds[Ld:]) == (4, periods - 1)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    q40 = lambda d_in, d_out, lead: PackedQ40(
        packed=sds(lead + (d_in // 2, d_out), jnp.uint8),
        scales=sds(lead + (d_in // 32, d_out), jnp.float16))
    experts = lambda d_in, d_out: Q40Experts(
        sds((Lm, E, d_in // 2, d_out), jnp.uint8), sds((Lm, E, d_in // 32, d_out), jnp.int16))
    params = hybrid.HybridParams(
        embedding=sds((vocab, d), jnp.bfloat16),
        attn=hybrid.GqaParams(
            wq=q40(d, d, (La,)), wk=q40(d, 512, (La,)), wv=q40(d, 512, (La,)), wo=q40(d, d, (La,)),
            q_norm=sds((La, 64), jnp.float32), k_norm=sds((La, 64), jnp.float32),
            rms=sds((La, d), jnp.float32)),
        conv=hybrid.ConvParams(
            w_in=q40(d, 3 * d, (Lc,)), taps=sds((Lc, 3, d), jnp.float32),
            w_out=q40(d, d, (Lc,)), rms=sds((Lc, d), jnp.float32)),
        dense=DenseFfnParams(
            w1=q40(d, 11776, (Ld,)), w2=q40(11776, d, (Ld,)), w3=q40(d, 11776, (Ld,)),
            rms_ffn=sds((Ld, d), jnp.float32)),
        routed=RoutedFfnParams(
            gate=sds((Lm, d, E), jnp.float32), bias=sds((Lm, E), jnp.float32),
            w1=experts(d, 1536), w2=experts(1536, d), w3=experts(d, 1536),
            s1=None, s2=None, s3=None, rms_ffn=sds((Lm, d), jnp.float32)),
        rms_final=sds((d,), jnp.float32), wcls=q40(d, vocab, ()),
        rope_cos=sds((seq, 32), jnp.float32), rope_sin=sds((seq, 32), jnp.float32))
    cache = hybrid.HybridCache(
        sds((La, lanes, seq, 512), jnp.bfloat16), sds((La, lanes, seq, 512), jnp.bfloat16),
        sds((Lc, lanes, 2 * d), jnp.bfloat16))
    tok = sds((lanes, 1), jnp.int32)
    hlo = jax.jit(
        lambda p, t, c: hybrid.hybrid_forward_counted(cfg, p, t, t, c)[:2], donate_argnums=(2,)
    ).lower(params, tok, cache).compile().as_text()
    return hlo, dict(La=La, Lc=Lc, Lm=Lm, E=E, d=d, lanes=lanes, seq=seq)


def test_pattern_decode_forward_copies_no_cache_no_state_and_no_expert_stack_for_v5e(v5e, monkeypatch):
    """Eight layers of the benchmark's layer-pattern block at its published
    widths (two dense, then one whole period of routed layers and an odd tail
    of two; six conv and two attention layers), one row a lane, the cache
    donated: the kernels are there, the K/V stack and the conv state stack are
    the results of their in-place writes alone, and no expert plane leaves its
    stack. With the head's 64 as the K/V stack's last axis XLA gave the stack
    another layout inside the loop and copied it whole, in and out (PR 35):
    the stack keeps ``n_kv * head`` merged."""
    import re

    hlo, dims = _pattern_decode_hlo(v5e, monkeypatch, periods=2, seq=512)
    La, Lc, Lm, E, d, lanes, seq = (dims[k] for k in ("La", "Lc", "Lm", "E", "d", "lanes", "seq"))
    # two dense layers: conv_in, conv_out, w1, w3, w2 each; the scan's body, one
    # period: 3 conv layers of 2 + 1 attention layer of 4 and its decode
    # attention (PR 36), and 4 x 3 grouped products; the tail: an attention (4
    # and its decode attention) and a conv layer, 2 x 3 grouped; the head
    assert hlo.count("tpu_custom_call") == 10 + (6 + 5 + 12) + (5 + 2 + 6) + 1
    for stack in (rf"bf16\[{La},{lanes},{seq},512\]", rf"bf16\[{Lc},{lanes},{2 * d}\]"):
        assert not re.search(rf"= {stack}\S* copy\(", hlo), stack
    assert f"= u8[{E},1024,1536]" not in hlo and f"= u8[{E},768,2048]" not in hlo
    assert f"= u8[{Lm},{E},1024,1536]" not in hlo.split("ENTRY")[0]


def _merged_plane_results(hlo: str, La: int, lanes: int, seq: int, n_kv: int, hd: int) -> list[str]:
    """What makes an array of the size of one K or V plane of the merged
    stack (in the carry's shape or split by head, bf16 or float32) or of the
    stack itself (``_results_of_shape``)."""
    lead = rf"(?:{La},|1,)?{lanes},{seq},"
    return _results_of_shape(
        hlo, rf"(?:bf16|f32)\[{lead}(?:{n_kv * hd}|{n_kv},{hd}|{n_kv},1,{hd})\]")


@pytest.mark.parametrize("in_place", [True, False],
                         ids=["kernel_reads_in_place", "control_plane_reads"])
def test_pattern_decode_forward_reads_no_kv_plane_for_v5e(v5e, monkeypatch, in_place):
    """The layer-pattern block at the benchmark's depth and cache (20 layers,
    five of them attention; 64 lanes x 2048 positions x 8 heads of 64, merged
    to rows of 512): the optimized decode forward holds a ``decode_attention``
    kernel for each attention instance (the scan's period body and the odd
    tail) and nothing has a ``[64, 2048, 512]`` plane, a float32 plane or the
    ``[5, 64, 2048, 512]`` stack as its result but the in-place appends. The
    control patches the predicate off, as the program was before PR 36, and
    shows what the check looks for: each instance's K and V planes read out
    of the stack and converted."""
    import re

    from distributed_llama_multiusers_tpu.models import hybrid

    if not in_place:
        monkeypatch.setattr(hybrid, "decode_attention_engages", lambda *a: False)
    hlo, dims = _pattern_decode_hlo(v5e, monkeypatch, periods=5, seq=2048)
    kernels = len(re.findall(r'custom-call\(.*custom_call_target="tpu_custom_call".*decode_attention', hlo))
    made = _merged_plane_results(hlo, dims["La"], dims["lanes"], dims["seq"], 8, 64)
    if in_place:
        assert kernels == 2 and made == [], (kernels, made)
    else:
        assert kernels == 0
        reads = [m for m in made if "dynamic-slice" in m or "fusion" in m or "convert" in m]
        assert len(reads) >= 4, made  # K's plane and V's, in the body and in the tail


@pytest.mark.parametrize("lanes,vocab", [(32, 152064), (32, 128256), (16, 32768)])
def test_nucleus_search_compiles_with_no_sort(v5e, lanes, vocab):
    """The sampler's kept set at the cells' widths (Qwen, Kanana, Mistral), as
    the chip's compiler sees it: one `while` of 32 passes, no `sort` and no
    TopK custom call (PR 34; the sorted form it replaced was 13-16 s of every
    step program's compile and 6.6 ms of Qwen's decode step)."""
    import re

    from distributed_llama_multiusers_tpu.runtime.engine import nucleus_keep

    def keep(rows, topps):
        return jax.vmap(nucleus_keep)(rows / 0.7, topps)

    hlo = jax.jit(keep).lower(
        jax.ShapeDtypeStruct((lanes, vocab), jnp.float32, sharding=v5e),
        jax.ShapeDtypeStruct((lanes,), jnp.float32, sharding=v5e),
    ).compile().as_text()
    assert " while(" in hlo
    assert not re.search(r"\bsort[.(]|TopK|top_k|topk", hlo)


def test_grouped_sampler_holds_a_groups_rows_in_fast_memory(v5e):
    """Jamba's 256 lanes x 65536 through the sampler's entry (PR 44): four
    groups of 64, and the chip's compiler keeps BOTH operands of the 32 passes
    (the keys and the probabilities of a group) in memory space 1 across the
    inner `while`, where all 256 rows at once leave the keys in HBM and every
    pass streams them (5.5 ms of that cell's decode half). No sort either."""
    import re

    from distributed_llama_multiusers_tpu.runtime.engine import (
        _sample_lane, sample_lanes, sampler_group)

    lanes, vocab = 256, 65536
    assert sampler_group(lanes, vocab) == 64
    operands = [jax.ShapeDtypeStruct((lanes, vocab), jnp.float32, sharding=v5e)] + [
        jax.ShapeDtypeStruct((lanes,), d, sharding=v5e)
        for d in (jnp.float32, jnp.float32, jnp.int32, jnp.int32, jnp.int32)]

    def searches(fn):
        hlo = jax.jit(fn).lower(*operands).compile().as_text()
        assert not re.search(r"\bsort[.(]|TopK|top_k|topk", hlo)
        # the loops that carry a [rows, vocab] key: the 32 passes
        return [line for line in hlo.splitlines()
                if " while(" in line and re.search(r"u32\[\d+,65536\]", line)]

    (search,) = searches(sample_lanes)
    resident = r"\[64,65536\]\{1,0:T\(8,128\)S\(1\)\}"
    assert re.search("u32" + resident, search) and re.search("f32" + resident, search)
    (search,) = searches(jax.vmap(_sample_lane))   # the control: ungrouped
    assert re.search(r"u32\[256,65536\]\{1,0:T\(8,128\)\}", search)


def test_selection_table_resolves_only_to_compile_tested_modes():
    """`auto` may only land on a mode the grid above compiles."""
    modes = {r["mode"] for r in dequant_select.DequantTable().rules}
    assert modes <= set(OTHER_MODES) | {DEFAULT_MODE}
    assert dequant_select.FALLBACK_MODE in pq.DEQUANT_MODES


def _deepseek_v32_cell_program(v5e, monkeypatch, b: int, t: int):
    """The optimized HLO of the benchmark's deepseek-v3.2 configuration at the
    cell's own depth, widths and cache (9 layers, 16 of 256 experts held, 8
    lanes of 32768 positions), ``b`` lanes of ``t`` rows, the cache donated;
    and its configuration. The arrays are the family generator's shapes."""
    import latent_toy
    from distributed_llama_multiusers_tpu.models import deepseek
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    path = list(__import__("sys").path)
    __import__("sys").path[:0] = [latent_toy.BENCH_DIR, latent_toy.ROOT]
    try:
        from harness import cells

        bench = cells.load_benchmark()
        cfg = cells.load_config_file(bench, "deepseek-v3.2")
        family = cells.load_family(cfg)
    finally:
        __import__("sys").path[:] = path
    config = family.program_config(cfg)
    monkeypatch.setattr(linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas)
    monkeypatch.setattr(linear, "pallas_kernel_active", lambda: True)
    monkeypatch.setattr(deepseek, "pallas_kernel_active", lambda: True)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), tree)
    arrays = jax.eval_shape(
        lambda k: family._generate(config, k, jnp.bfloat16, padded_d_out(config.vocab_size)),
        jax.random.PRNGKey(0))
    params = on_chip(jax.eval_shape(lambda a: family.assemble_params(config, a), arrays))
    cache = on_chip(jax.eval_shape(lambda: deepseek.init_latent_cache(config, b, jnp.bfloat16)))
    tok = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=v5e)
    hlo = jax.jit(
        lambda p, tk, c: deepseek.deepseek_forward_counted(config, p, tk, tk, c),
        donate_argnums=(2,),
    ).lower(params, tok, cache).compile().as_text()
    return hlo, config


def test_sparse_latent_decode_copies_no_cache_stack_and_reads_no_whole_plane_for_v5e(v5e, monkeypatch):
    """One row a lane at the cell's 8 lanes: none of the three cache stacks
    (latent, rope, index keys) is copied or re-laid (each is the result of its
    in-place scatters alone), attention gathers the chosen rows out of the
    stacks as they sit (no ``[lanes, S, 512]`` latent plane is sliced out to
    gather from, no ``[lanes, S, 640]`` float32 plane is made to attend), and
    the kernels are there."""
    import re

    hlo, c = _deepseek_v32_cell_program(v5e, monkeypatch, 8, 1)
    L, lanes, S = c.n_layers, 8, c.seq_len
    for width in (c.kv_lora_rank, 128, c.index_head_dim):
        assert not re.search(rf"= bf16\[{L},{lanes},{S},{width}\]\S* copy\(", hlo), width
    assert not re.search(rf"f32\[{lanes},{S},(640|512|576)\]", hlo)
    assert not re.search(rf"= bf16\[{lanes},{S},{c.kv_lora_rank}\]\S* (fusion|copy)\(", hlo)
    # the dense layer and the scan's body: q_a, q_b, kv_a, the indexer's two,
    # wo, and a dense or a routed-and-shared FFN; the head
    assert hlo.count("tpu_custom_call") == 22
    assert "approx" not in hlo.lower()


def test_sparse_latent_chunk_compiles_for_v5e_and_gathers_in_blocks(v5e, monkeypatch):
    """A 1024-row chunk against the cell's 32768-position lane: the chip's
    compiler takes it, the lane's three stacks are copied nowhere, no
    ``[1024, index_topk, 512]`` block of every query's rows exists at once (a
    block of queries at a time), and the selection is a sort, never the
    approximate top-k."""
    import re

    hlo, c = _deepseek_v32_cell_program(v5e, monkeypatch, 1, 1024)
    L, S = c.n_layers, c.seq_len
    for width in (c.kv_lora_rank, 128, c.index_head_dim):
        assert not re.search(rf"= bf16\[{L},1,{S},{width}\]\S* copy\(", hlo), width
    assert not re.search(rf"\[(1,)?1024,{c.index_topk},(512|128|640)\]", hlo)
    assert re.search(rf"\[(1,)?256,{c.index_topk},512\]", hlo)  # one block's gathered rows
    assert " sort(" in hlo and "approx" not in hlo.lower()
