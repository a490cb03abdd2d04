"""Pallas Q40 matmul kernel (interpret mode on CPU): the lowered-program
witness, one pass over the weights a call (row blocks), and where the -8
goes by rows (the offset forms). The third part of tests/test_pallas_q40.py,
split by subject (PR 58): the same cases under the same names."""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_llama_multiusers_tpu.ops import pallas_q40 as pq
from distributed_llama_multiusers_tpu.ops.pallas_q40 import (
    TRACE_STATS,
    q40_matmul_pallas,
    reset_trace_stats,
    set_dequant_mode,
)
from distributed_llama_multiusers_tpu.quants.packed import (
    PackedQ40,
    q40_matmul_xla,
)

from test_pallas_q40 import SCALE_FORMS, _pack
from test_pallas_q40_stacks import _plane, _stack

# --- the lowered-program witness -------------------------------------------


WITNESS_SCOPES = ("dl.ffn", "dl.qkv", "dl.attn_out")


def _arrays_under(jaxpr, scopes, prefix=""):
    """(scope path, shape) of every array an equation makes under one of
    ``scopes``, through scans, jits and conditionals, NOT into a kernel: what
    a Pallas kernel does inside is not an XLA operation."""
    found = []
    for eqn in jaxpr.eqns:
        path = f"{prefix}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            continue
        if any(s in path for s in scopes):
            found += [(path, tuple(v.aval.shape)) for v in eqn.outvars
                      if hasattr(v.aval, "shape")]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _arrays_under(sub, scopes, path)
    return found


def _lane_splits(found):
    """Arrays of rank 3 and up whose last axis is 16: a quant block's half,
    split off the lane axis."""
    return sorted({shape for _, shape in found
                   if len(shape) >= 3 and shape[-1] == 16})


@pytest.fixture(scope="module")
def witness_engine(tmp_path_factory):
    """A two-layer quantised model no dimension of which is 16 (64-wide
    heads, 128 / 256-wide matmul inputs: 4 and 8 quant blocks), served by a
    real engine with the kernel in interpret mode."""
    from distributed_llama_multiusers_tpu.formats.model_file import load_model_header
    from distributed_llama_multiusers_tpu.formats.synthetic import (
        tiny_header,
        write_synthetic_model,
    )
    from distributed_llama_multiusers_tpu.models.loader import (
        load_params_from_m_quantized,
    )
    from distributed_llama_multiusers_tpu.ops import linear
    from distributed_llama_multiusers_tpu.runtime import InferenceEngine

    path = str(tmp_path_factory.mktemp("witness") / "w.m")
    write_synthetic_model(path, tiny_header(
        dim=128, hidden_dim=256, n_heads=2, n_kv_heads=1, seq_len=32), seed=5)
    h = load_model_header(path)
    config, qparams = load_params_from_m_quantized(path, h, dtype=jnp.bfloat16)
    linear.set_pallas_interpret(True)
    linear.set_pallas_w_dtype(jnp.bfloat16)
    try:
        yield InferenceEngine(config, qparams, n_lanes=2, prefill_buckets=(8,))
    finally:
        linear.set_pallas_w_dtype(None)
        linear.set_pallas_interpret(False)


def _decode_step_forms(engine):
    """(StableHLO text, jaxpr) of the pipelined decode step program as the
    engine's own entry point dispatches it."""
    fn, seen = engine._decode_pl_fn, []

    def spy(*args, **kw):
        seen.append((fn.lower(*args, **kw).as_text(),
                     fn.trace(*args, **kw).jaxpr))
        return fn(*args, **kw)

    engine._decode_pl_fn = spy
    try:
        z = np.zeros(engine.n_lanes, np.int32)
        engine.decode_pipelined(z, tokens=z)
        engine.pipeline_flush()
    finally:
        engine._decode_pl_fn = fn
    assert seen, "the decode step program was not dispatched"
    return seen[0]


# an activation [rows, d_in] seen as [rows, d_in / 32, 2, 16]
SPLIT_RESHAPE = re.compile(r"tensor<\d+x\d+x2x16x(?:f32|bf16)>")


@pytest.mark.parametrize("mode,splits", [
    ("v4", False), ("bf16chain", False), ("repeat", False), ("u8chain", False),
    ("blockdot", True),
])
def test_decode_step_program_splits_no_activation_lane(witness_engine, mode,
                                                       splits):
    """There is no fallback whose hits could be counted, so the witness is
    the program: the lowered decode step of a quantised model holds no
    reshape of an activation to [.., d_in / 32, 2, 16] and, outside the
    kernels, no array whose last axis is 16 under the three scopes the dense
    Q40 matmuls run in. The control is the mode that still takes pre-split
    operands (blockdot), in which the same search finds both."""
    set_dequant_mode(mode)
    try:
        reset_trace_stats()
        jax.clear_caches()  # the step program is traced anew under this mode
        text, jaxpr = _decode_step_forms(witness_engine)
    finally:
        set_dequant_mode(None)
        jax.clear_caches()
    found = _arrays_under(jaxpr, WITNESS_SCOPES)
    assert any("dl.ffn" in p for p, _ in found), "no dl.ffn scope in the program"
    # the loader's tree is float16 and the engine leaves stacks this small so
    # (``reads_scales_in_place``): every kernel body of the step, in any mode,
    # was fed by a plane sliced out and converted, and says so
    assert TRACE_STATS["scale_converts"] == TRACE_STATS["impl_traces"], TRACE_STATS
    assert TRACE_STATS["scale_stack_reads"] == 0, TRACE_STATS
    if splits:
        assert SPLIT_RESHAPE.search(text)
        assert _lane_splits(found), found
        assert TRACE_STATS["natural_x_consumes"] == 0, TRACE_STATS
    else:
        assert not SPLIT_RESHAPE.search(text), SPLIT_RESHAPE.findall(text)
        assert _lane_splits(found) == [], _lane_splits(found)
        assert TRACE_STATS["natural_x_consumes"] == TRACE_STATS["impl_traces"] > 0


def test_the_witness_finds_the_split_the_kernel_took_before():
    """Control on the preparation itself: the operands every chain took
    before PR 42, and the block-dot modes still take, are built by that
    split; lowered alone under a scope it shows what both searches look for,
    and the rows the kernel pads (``_padded_rows``) hold none of it."""
    def prep(x):
        with jax.named_scope("dl.ffn"):
            return pq._block_dot_operands(pq._padded_rows(x), "blockdot")

    x = jax.ShapeDtypeStruct((16, 256), jnp.bfloat16)
    assert SPLIT_RESHAPE.search(jax.jit(prep).lower(x).as_text())
    found = _arrays_under(jax.make_jaxpr(prep)(x).jaxpr, WITNESS_SCOPES)
    assert (16, 8, 16) in _lane_splits(found), found


# ---------------------------------------------------------------------------
# PR 45: a weight slab is fetched and dequantised once for a BLOCK of rows,
# and the block is the call's rows up to M_BLOCK_MAX. A row's result does not
# depend on which other rows share its block: the same chain, the same dots
# over the same k chunks in the same order.
# ---------------------------------------------------------------------------

ROW_BLOCK_PLANS = {
    # (d_in, d_out): one slab (no k axis), several k chunks through the f32
    # accumulator (1024 x 1152 packed bytes: two chunks of 512 rows, sub
    # tiles 512 + 512 + 128), two wide tiles of 8192
    "one_slab": (64, 256),
    "k_chunks": (2048, 1152),
    "two_wide_tiles": (64, 16384),
}


@pytest.mark.parametrize("weight", ["plane", "stack"])
@pytest.mark.parametrize("plan", list(ROW_BLOCK_PLANS))
@pytest.mark.parametrize("m", [300, 512, 1024, 1300])
def test_row_blocks_do_not_change_a_rows_result(m, plan, weight):
    """The call's output equals, to the bit in interpret-mode f32, the
    outputs of the same rows sent 256 at a time (calls of M_TILE rows or
    fewer: the grid and the blocks they always had), and the XLA dequant to
    this file's tolerance."""
    d_in, d_out = ROW_BLOCK_PLANS[plan]
    w_tile, rows = pq._plan_blocks(d_in, d_out)
    assert ((d_in // 2) // rows, d_out // w_tile) == {
        "one_slab": (1, 1), "k_chunks": (2, 1), "two_wide_tiles": (1, 2)}[plan]
    rng = np.random.default_rng(m + d_in + d_out)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    if weight == "stack":
        w = _stack(rng, d_out, d_in, n=2)
        kw, plane = dict(interpret=True, layer=1), _plane(w, 1)
    else:
        w = plane = _pack(rng, d_out, d_in)
        kw = dict(interpret=True)
    got = np.asarray(q40_matmul_pallas(x, w, **kw))
    # the plan this call traced under (the trace itself may be another
    # test's, so the counter is not read here): one block up to 1024 rows
    m_pad, _ = pq._m_geometry(m, x.dtype)
    m_block, _ = pq._row_plan(m_pad, w_tile, rows, (d_in // 2) // rows, 4)
    assert m_pad // m_block == (1 if m <= 1024 else 2), (m_pad, m_block)
    # whole 256-row tiles, as the parent's grid cut the padded rows (XLA:CPU
    # sums a dot of 44 rows in another order than one of 256: the tail is
    # padded here as the kernel pads it)
    x_tiles = jnp.pad(x, ((0, -m % pq.M_TILE), (0, 0)))
    by_tile = np.concatenate([
        np.asarray(q40_matmul_pallas(x_tiles[r:r + pq.M_TILE], w, **kw))
        for r in range(0, m, pq.M_TILE)])[:m]
    np.testing.assert_array_equal(got, by_tile)
    np.testing.assert_allclose(got, np.asarray(q40_matmul_xla(x, plane)),
                               atol=2e-4, rtol=2e-4)


def _parent_m_pad(m, itemsize):
    """x rows as PR 44 padded them: whole sublane tiles, whole 256-row tiles
    above 256."""
    align = 8 * max(1, 4 // itemsize)
    m_pad = max(align, -(-m // align) * align)
    return m_pad if m_pad <= 256 else -(-m_pad // 256) * 256


# (d_in, d_out) of the benchmark's dense cells and of every 8192-wide tile in
# its six configurations (the heads, Jamba's MLP, DeepSeek's wide projections)
PLAN_SHAPES = [(4096, 14336), (14336, 4096), (4096, 1024), (3584, 18944),
               (18944, 3584), (4096, 32768), (3584, 152064), (2560, 8192),
               (2560, 65536), (1536, 24576), (7168, 128)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("d_in,d_out", PLAN_SHAPES)
def test_plan_from_shapes_one_pass_up_to_1024_rows(d_in, d_out, dtype):
    """From shapes alone: the padding is what it was for every m; every call
    of up to 256 rows keeps the grid, the wide tile and the VMEM ceiling it
    had; every call of up to 1024 rows is ONE block of rows, one pass over
    the plane, its pipelined blocks inside the 64 MiB the kernel always
    asked for (an 8192-wide tile narrows to 4096 at 1024 rows; f32 rows
    narrow a 7168-wide one too); longer calls are cut into equal blocks of
    whole 256-row tiles."""
    itemsize = jnp.dtype(dtype).itemsize
    w_plan, rows = pq._plan_blocks(d_in, d_out)
    n_k = (d_in // 2) // rows
    for m in (1, 7, 16, 32, 200, 256, 257, 300, 512, 777, 1000, 1024, 1025,
              1300, 2048, 2560, 4096):
        m_pad, _ = pq._m_geometry(m, dtype)
        assert m_pad == _parent_m_pad(m, itemsize), m
        m_block, w_tile = pq._row_plan(m_pad, w_plan, rows, n_k, itemsize)
        need = pq._block_bytes(m_block, w_tile, rows, n_k, itemsize)
        assert m_pad % m_block == 0 and m_block <= pq.M_BLOCK_MAX, (m, m_block)
        assert w_plan % w_tile == 0 and w_tile % 128 == 0
        assert w_tile == w_plan or w_tile >= pq.MIN_W_TILE
        limit = pq._vmem_limit(need)
        assert pq.VMEM_LIMIT_BYTES <= limit <= 80 << 20 < 128 << 20
        if m <= 256:
            # the parent's m tile, wide tile and compiler parameters
            assert (m_block, w_tile) == (min(256, m_pad), w_plan)
            assert limit == pq.VMEM_LIMIT_BYTES
        else:
            assert need <= pq.VMEM_LIMIT_BYTES, (m, m_block, w_tile)
            assert m_block % 256 == 0
            if m <= 1024:
                assert m_block == m_pad, (m, m_block)  # one pass
    at_1024 = pq._row_plan(1024, w_plan, rows, n_k, itemsize)
    if dtype == jnp.bfloat16:
        # what the cells send: only the 8192-wide tile gives way, by halving
        assert at_1024 == (1024, 4096 if w_plan == 8192 else w_plan)
        # 1300 rows: 1536 padded as before, two blocks of 768 (not 6 of 256)
        assert pq._row_plan(1536, w_plan, rows, n_k, 2)[0] == 768


def test_a_narrowed_wide_tile_does_not_change_a_result():
    """Jamba's MLP shape at a depth CPU interpret mode can afford: the plan
    is one 8192-wide tile, which 1024 rows meet as two tiles of 4096 with
    the k chunks as planned: every element sums the same products in the
    same order, so the call equals the same rows sent 256 at a time
    (against the 8192-wide tile) to the bit."""
    d_in, d_out, m = 512, 8192, 1024
    w_plan, rows = pq._plan_blocks(d_in, d_out)
    n_k = (d_in // 2) // rows
    assert (w_plan, n_k) == (8192, 2)
    assert pq._row_plan(m, w_plan, rows, n_k, 4) == (1024, 4096)
    assert pq._row_plan(256, w_plan, rows, n_k, 4) == (256, 8192)
    rng = np.random.default_rng(45)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    w = _pack(rng, d_out, d_in)
    got = np.asarray(q40_matmul_pallas(x, w, interpret=True))
    by_tile = np.concatenate([
        np.asarray(q40_matmul_pallas(x[r:r + 256], w, interpret=True))
        for r in range(0, m, 256)])
    np.testing.assert_array_equal(got, by_tile)
    np.testing.assert_allclose(got, np.asarray(q40_matmul_xla(x, w)),
                               atol=2e-4, rtol=2e-4)


def _trace_w1_call(m, mode="v4", scales=jnp.float16, layers=None):
    """Trace (nothing runs) a bf16 call of ``m`` rows at Mistral's w1: the
    plane, or a layer of a stack of ``layers``."""
    lead = () if layers is None else (layers,)
    w = PackedQ40(packed=jax.ShapeDtypeStruct(lead + (2048, 14336), jnp.uint8),
                  scales=jax.ShapeDtypeStruct(lead + (128, 14336), scales))
    x = jax.ShapeDtypeStruct((m, 4096), jnp.bfloat16)
    layer = None if layers is None else jax.ShapeDtypeStruct((), jnp.int32)
    jax.eval_shape(lambda x, w, l: pq._q40_matmul_core(
        x, w, True, jnp.bfloat16, mode, l), x, w, layer)


def test_weight_passes_witness_in_trace_stats_and_path_facts(witness_engine):
    """After tracing a 1024-row and a 16-row call the witness reads 1 (the
    parent's plan made 4 passes at 1024 rows: ``m_pad // 256``), and the
    engine's start-up facts carry it; a call past M_BLOCK_MAX says so."""
    trace = _trace_w1_call
    reset_trace_stats()
    assert witness_engine.path_facts()["q40_weight_passes"] == 0  # none traced
    trace(1024)
    trace(16)
    assert TRACE_STATS["weight_passes_max"] == 1, TRACE_STATS
    assert witness_engine.path_facts()["q40_weight_passes"] == 1
    trace(4096)
    assert TRACE_STATS["weight_passes_max"] == 4
    assert witness_engine.path_facts()["q40_weight_passes"] == 4
    reset_trace_stats()


# ---------------------------------------------------------------------------
# PR 49: where the nibbles' -8 goes is read off the block of rows. A block of
# SUBTRACT_MIN_ROWS rows and more takes it off the nibbles in the dequant
# chain and traces neither the block sums nor the correction dot; a smaller
# block traces the body it always did.
# ---------------------------------------------------------------------------

T = pq.SUBTRACT_MIN_ROWS
# (d_in, d_out) small enough for interpret mode, one of every class of plan
# the cells' shapes have: (k chunks?, wide tiles?, block-sum slices?)
OFFSET_PLANS = {
    "one_slab": (64, 256),
    "k_chunks": (2048, 1152),          # sub tiles 512 + 512 + 128
    "two_wide_tiles": (64, 16384),
    "k_chunks_and_wide_tiles": (512, 16384),
    "block_sum_slices": (7168, 128),   # the whole half one chunk: 4 slices
    "k_chunks_of_slices": (7168, 576),  # DeepSeek's wkva: two chunks of two
    # a head whose width only 128 divides (MiMo's vocabulary slice, 19072 =
    # 149 x 128, which padding to 8192s would grow by 29 %): 67 tiles here
    "wide_tiles_of_slices": (2304, 8576),
}


def _plan_class(d_in, d_out):
    w_tile, rows = pq._plan_blocks(d_in, d_out)
    return ((d_in // 2) // rows > 1, d_out // w_tile > 1,
            (2 * rows) // pq._sum_slice(2 * rows) > 1)


def test_offset_plans_cover_every_cell_shapes_plan():
    from chip_compile_util import CELL_SHAPES

    tested = {_plan_class(*shape) for shape in OFFSET_PLANS.values()}
    assert len(tested) == len(OFFSET_PLANS)
    assert {_plan_class(d_in, d_out) for d_in, d_out, _ in CELL_SHAPES} <= tested


def _kernel_dots(m, d_in, d_out, mode="v4", w_dtype=jnp.float32):
    """dot_general equations in the kernel body a call of ``m`` rows traces."""
    w = PackedQ40(packed=jax.ShapeDtypeStruct((d_in // 2, d_out), jnp.uint8),
                  scales=jax.ShapeDtypeStruct((d_in // 32, d_out), jnp.float16))
    x = jax.ShapeDtypeStruct((m, d_in), w_dtype)
    jaxpr = jax.make_jaxpr(lambda x, w: pq._q40_matmul_core(
        x, w, True, w_dtype, mode))(x, w)
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    return str(call.params["jaxpr"]).count("dot_general")


@pytest.mark.parametrize("plan", list(OFFSET_PLANS))
@pytest.mark.parametrize("m", [T - 8, T, T + 8], ids=["under", "at", "over"])
def test_offset_form_either_side_of_the_threshold(m, plan):
    """Both forms against the XLA dequant, at every class of plan the cells
    have; the witness counts the bodies without a correction dot and only
    those; the subtracting body holds one dot a sub-tile, the folding one two
    and a dot a block-sum slice."""
    d_in, d_out = OFFSET_PLANS[plan]
    rng = np.random.default_rng(49 + d_in + d_out)
    w = _pack(rng, d_out, d_in)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    pq._q40_matmul_pallas_impl.clear_cache()
    reset_trace_stats()
    got = np.asarray(q40_matmul_pallas(x, w, interpret=True))
    assert TRACE_STATS["impl_traces"] == 1, TRACE_STATS
    assert TRACE_STATS["offset_subtracted_traces"] == (m >= T), TRACE_STATS
    np.testing.assert_allclose(got, np.asarray(q40_matmul_xla(x, w)),
                               atol=2e-4, rtol=2e-4)
    w_tile, rows = pq._plan_blocks(d_in, d_out)
    m_block, w_tile = pq._row_plan(pq._m_geometry(m, x.dtype)[0], w_tile, rows,
                                   (d_in // 2) // rows, 4)
    n_sub = len(pq._sub_tiles(w_tile))
    slices = (2 * rows) // pq._sum_slice(2 * rows)
    assert _kernel_dots(m, d_in, d_out) == (
        n_sub if m >= T else slices + n_sub * (1 + slices))


@pytest.mark.parametrize("mode", ["v4", "bf16chain", "repeat", "u8chain"])
def test_offset_subtracted_in_every_slab_chain(mode):
    """The four slab chains in bf16, as the cells run v4: each takes the 8 off
    before the scale and holds one dot a sub-tile; the result is as close to
    the XLA dequant as the folded form's, and the three bf16 chains agree to
    the bit (the nibbles less 8 are exact in bf16 wherever they are taken)."""
    d_in, d_out = OFFSET_PLANS["k_chunks"]
    rng = np.random.default_rng(490)
    w = _pack(rng, d_out, d_in)
    x = jnp.asarray(rng.standard_normal((T, d_in), dtype=np.float32)).astype(jnp.bfloat16)
    want = np.asarray(q40_matmul_xla(x.astype(jnp.float32), w))
    got = {}
    for md in (mode, "bf16chain"):
        set_dequant_mode(md)
        try:
            got[md] = np.asarray(q40_matmul_pallas(
                x, w, interpret=True, w_dtype=jnp.bfloat16)).astype(np.float32)
        finally:
            set_dequant_mode(None)
    assert _kernel_dots(T, d_in, d_out, mode, jnp.bfloat16) == 3
    assert np.abs(got[mode] - want).max() <= 1e-2 * np.abs(want).max()
    if mode != "v4":
        np.testing.assert_array_equal(got[mode], got["bf16chain"])


@pytest.mark.parametrize("scales", list(SCALE_FORMS))
@pytest.mark.parametrize("m", [T - 8, T, 2 * T], ids=["under", "at", "two_tiles"])
def test_offset_form_on_a_stack_with_a_traced_layer(m, scales):
    """A layer read out of a stack under a traced index, the counter of a scan
    as the layer loop hands it, the stack's scales float16 or at rest as bits:
    equal to the float16 plane's own call to the bit on either side of the
    threshold (the -8 folded, the -8 subtracted), and to the XLA dequant."""
    d_in, d_out = OFFSET_PLANS["k_chunks"]
    rng = np.random.default_rng(m)
    stack = _stack(rng, d_out, d_in, n=2)
    served = SCALE_FORMS[scales](stack)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    _, got = jax.lax.scan(
        lambda c, l: (c, q40_matmul_pallas(x, served, interpret=True, layer=l)),
        0, jnp.arange(2, dtype=jnp.int32))
    for l in (0, 1):
        plane = _plane(stack, l)
        np.testing.assert_array_equal(
            np.asarray(got[l]), np.asarray(q40_matmul_pallas(x, plane, interpret=True)))
        np.testing.assert_allclose(np.asarray(got[l]), np.asarray(q40_matmul_xla(x, plane)),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("plan", ["k_chunks", "two_wide_tiles"])
def test_a_subtracted_rows_result_does_not_depend_on_its_block(plan):
    """Rows in a block of 2 T with other rows beside them, in a block of
    T alone, and beside different rows: the same bits each time."""
    d_in, d_out = OFFSET_PLANS[plan]
    rng = np.random.default_rng(4949)
    w = _pack(rng, d_out, d_in)
    a, b, c = (jnp.asarray(rng.standard_normal((T, d_in), dtype=np.float32))
               for _ in range(3))
    alone = np.asarray(q40_matmul_pallas(a, w, interpret=True))
    beside_b = np.asarray(q40_matmul_pallas(jnp.concatenate([a, b]), w, interpret=True))
    beside_c = np.asarray(q40_matmul_pallas(jnp.concatenate([c, a]), w, interpret=True))
    np.testing.assert_array_equal(beside_b[:T], alone)
    np.testing.assert_array_equal(beside_c[T:], alone)


def test_offset_witness_in_trace_stats_and_path_facts(witness_engine):
    """The engine's start-up facts carry the count of kernel bodies traced
    without the correction dot: 0 after decode-width calls alone, one more a
    prefill-width body."""
    trace = _trace_w1_call
    reset_trace_stats()
    for m in (8, 16, 32, 64, T - 16):
        trace(m)
    trace(16, "blockdot")
    assert TRACE_STATS["offset_subtracted_traces"] == 0, TRACE_STATS
    assert witness_engine.path_facts()["q40_offset_subtracted"] == 0
    for m in (T, 512, 1024):
        trace(m)
    assert TRACE_STATS["offset_subtracted_traces"] == 3, TRACE_STATS
    assert witness_engine.path_facts()["q40_offset_subtracted"] == 3
    reset_trace_stats()


def test_scale_stack_witness_in_trace_stats_and_path_facts(witness_engine):
    """The engine's start-up facts carry the Q40 kernel bodies traced whose
    scale tiles were read out of the weight's own int16 plane or stack in
    place and those fed by a float16 plane sliced out and converted (beside
    them here: all bodies traced), whatever the mode: a plane at rest and a 7B model's
    32-layer FFN stack are read in place, a stack XLA could stage whole has
    its plane sliced out as bits (neither count), float16 is converted."""
    def facts():
        f = witness_engine.path_facts()
        return f["q40_scales_in_stack"], f["q40_scale_converts"], TRACE_STATS["impl_traces"]

    reset_trace_stats()
    assert facts() == (0, 0, 0)
    for m in (16, T, 1024):
        _trace_w1_call(m, scales=jnp.int16)
    _trace_w1_call(16, "blockdot", scales=jnp.int16)
    assert facts() == (4, 0, 4)
    _trace_w1_call(16, layers=32, scales=jnp.int16)  # 117 MB of scales: in place
    assert facts() == (5, 0, 5)
    _trace_w1_call(16, layers=8, scales=jnp.int16)  # 29 MB: its plane sliced out
    assert facts() == (5, 0, 6)
    _trace_w1_call(16)  # float16: sliced and converted, and counted as such
    _trace_w1_call(16, layers=32)
    assert facts() == (5, 2, 8)
    reset_trace_stats()


def test_a_stack_is_read_in_place_only_where_xla_cannot_stage_it():
    """``reads_scales_in_place`` from shapes alone, in either form: the scale
    stacks of a 7B or 9B model's FFN (117-134 MB) cannot sit in fast memory
    beside the kernel's 64 MiB and are read in place (every configuration of
    the benchmark: tests/test_weight_residency.py); attention projections'
    (8-34 MB), a 9-layer 66 MB stack and every test's can, and XLA would copy
    them there whole a call (compiled for a v5e:
    tests/test_chip_compile_steps.py; what that costs on the chip: the
    predicate's docstring); a stack of one is its plane, and a plane (a
    head's) has no layer to slice out: the engine converts neither."""
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int16)
    in_place = [sds(32, 128, 14336), sds(32, 448, 4096), sds(28, 112, 18944),
                sds(32, 128, 16384), sds(32, 512, 4096)]  # the last two: MiniCPM-SALA's FFN
    sliced = [sds(32, 128, 4096), sds(32, 128, 1024), sds(28, 112, 3584),
              sds(9, 512, 7168), sds(8, 128, 16384), sds(2, 4, 256), sds(1, 592, 3584),
              sds(112, 152064)]
    f16 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float16)
    for form in (lambda s: s, f16):
        assert [pq.reads_scales_in_place(form(s)) for s in in_place] == [True] * len(in_place)
        assert [pq.reads_scales_in_place(form(s)) for s in sliced] == [False] * len(sliced)


# sha256[:16] of the output bytes of the seeded call below, 112 rows, on the
# PARENT of PR 49 (commit 0b69b2f), whose kernel folded the -8 at every row
# count
PARENT_FOLDED_DIGESTS = {"f32": "cfc4611c88a055e3", "v4": "438cc3ffae29ff8a"}


@pytest.mark.parametrize("dot", ["f32", "v4"])
def test_a_block_under_the_threshold_gives_what_the_parent_gave(dot):
    """The largest block that still folds the -8 (16 rows under
    SUBTRACT_MIN_ROWS: bf16 rows pad to whole 16-row tiles) traces the body
    the tree had before PR 49: the same bits, and no body counted as
    subtracting. (The traced programs of 16 such calls, the four slab chains
    at four cell shapes, were compared with the parent's equation by
    equation when this was written: identical.)"""
    import hashlib

    m = T - 16
    assert m == 112  # what the digests were taken at
    rng = np.random.default_rng(49)
    pw = _pack(rng, 1152, 2048)
    x = jnp.asarray(rng.standard_normal((m, 2048), dtype=np.float32))
    kw = {} if dot == "f32" else {"w_dtype": jnp.bfloat16}
    if dot == "v4":
        x = x.astype(jnp.bfloat16)
    pq._q40_matmul_pallas_impl.clear_cache()
    reset_trace_stats()
    got = np.asarray(q40_matmul_pallas(x, pw, interpret=True, **kw))
    assert TRACE_STATS["impl_traces"] == 1, TRACE_STATS
    assert TRACE_STATS["offset_subtracted_traces"] == 0, TRACE_STATS
    assert (hashlib.sha256(got.tobytes()).hexdigest()[:16]
            == PARENT_FOLDED_DIGESTS[dot])
