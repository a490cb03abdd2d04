"""Pallas Q40 matmul kernel (interpret mode on CPU): weights read out of a
stack, scale tiles read in place, and x handed to the kernel as it is. The
second part of tests/test_pallas_q40.py, split by subject (PR 58): the same
cases under the same names."""

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_llama_multiusers_tpu.ops import pallas_q40 as pq
from distributed_llama_multiusers_tpu.ops.pallas_q40 import (
    DEQUANT_MODES,
    TRACE_STATS,
    q40_matmul_pallas,
    reset_trace_stats,
    set_dequant_mode,
)
from distributed_llama_multiusers_tpu.quants.packed import (
    PackedQ40,
    q40_at_rest,
    q40_matmul_xla,
)

from test_pallas_q40 import SCALE_FORMS, _pack

# ---------------------------------------------------------------------------
# Stacked weights: the kernel reads layer ``l``'s tiles out of a [L, ...]
# stack itself (q40_matmul_pallas(layer=l)), so a layer scan never slices the
# plane into a buffer of its own. Same arithmetic, same bits: only where the
# weight blocks are fetched from differs.
# ---------------------------------------------------------------------------

STACK_L = 3


def _stack(rng, d_out, d_in, n=STACK_L):
    planes = [_pack(rng, d_out, d_in) for _ in range(n)]
    return PackedQ40(packed=jnp.stack([p.packed for p in planes]),
                     scales=jnp.stack([p.scales for p in planes]))


def _plane(stack, l):
    return PackedQ40(packed=stack.packed[l], scales=stack.scales[l])


@pytest.mark.parametrize("how", ["jit", "scan"])
@pytest.mark.parametrize("entry", ["raw_x", "rank3"])
@pytest.mark.parametrize("mode", DEQUANT_MODES)
def test_stacked_weight_equals_its_plane_bit_for_bit(mode, entry, how):
    """Layer ``l`` read out of the stack equals the 2-D kernel on plane ``l``
    to the bit, in every dequant mode, x two-dimensional or with leading axes
    ``[lanes, t, d_in]`` that the kernel merges, with
    ``l`` a traced scalar (an argument of a jit; the counter of a lax.scan)
    at the stack's first and last layer. Both sides are computed inside one
    traced program, so the operand builds are the same operations. (A stack
    at rest: ``test_stacked_weight_on_every_grid_axis`` and the ``in_place``
    tests below.)"""
    rng = np.random.default_rng(30)
    stack = _stack(rng, 256, 128)
    x = jnp.asarray(rng.standard_normal((4, 128), dtype=np.float32))
    kw = dict(interpret=True, w_dtype=jnp.bfloat16)

    def both(x, stack, l):
        xin = x.reshape(2, 2, 128) if entry == "rank3" else x
        return (q40_matmul_pallas(xin, stack, layer=l, **kw),
                q40_matmul_pallas(xin, _plane(stack, l), **kw))

    set_dequant_mode(mode)
    try:
        reset_trace_stats()
        if how == "jit":
            fn = jax.jit(both)
            pairs = {l: fn(x, stack, jnp.int32(l)) for l in (0, STACK_L - 1)}
        else:
            _, (got, want) = jax.lax.scan(
                lambda c, l: (c, both(x, stack, l)), 0,
                jnp.arange(STACK_L, dtype=jnp.int32))
            pairs = {l: (got[l], want[l]) for l in (0, STACK_L - 1)}
        # one trace of ``both``: one kernel call that indexes a stack
        assert TRACE_STATS["stacked_consumes"] == 1, TRACE_STATS
        # float16 scales are never read in place
        assert TRACE_STATS["scale_stack_reads"] == 0, TRACE_STATS
        # stack and plane alike: x itself in a slab chain, never in a
        # block-dot mode
        natural = mode not in ("blockdot", "i8blockdot")
        assert TRACE_STATS["natural_x_consumes"] == (
            TRACE_STATS["impl_traces"] if natural else 0), TRACE_STATS
    finally:
        set_dequant_mode(None)
    for l, (got, want) in pairs.items():
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"layer {l}")
    assert not np.array_equal(np.asarray(pairs[0][0]),
                              np.asarray(pairs[STACK_L - 1][0]))


@pytest.mark.parametrize("m,d_in,d_out", [
    (4, 4096, 2048),   # n_k > 1: the k axis walks chunks of layer l's plane
    (2, 512, 16384),   # two wide tiles: the j axis
    (300, 64, 256),    # rows above M_TILE, padded to 512
])
@pytest.mark.parametrize("scales", list(SCALE_FORMS))
def test_stacked_weight_on_every_grid_axis(m, d_in, d_out, scales):
    """The layer offset composes with each axis of the grid, for the nibbles
    and, where the scales rest as bits, for the scale tiles beside them
    (against the float16 plane's call)."""
    rng = np.random.default_rng(d_in + d_out)
    stack = _stack(rng, d_out, d_in, n=2)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    for l in (0, 1):
        got = q40_matmul_pallas(x, SCALE_FORMS[scales](stack), interpret=True, layer=l)
        want = q40_matmul_pallas(x, _plane(stack, l), interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(q40_matmul_xla(x, _plane(stack, l))),
            atol=2e-4, rtol=2e-4)


def _in_place(monkeypatch):
    """A call of the kernel's core over a stack at rest whose scale tiles are
    read IN PLACE, as a stack too large to stage is: ``reads_scales_in_place``
    says so for the trace, and the core is traced anew under a jit of its own
    (the entry's jit would serve a body traced before the patch)."""
    monkeypatch.setattr(pq, "reads_scales_in_place", lambda scales: scales.ndim == 3)

    def call(x, stack, layer, mode="v4", w_dtype=jnp.float32):
        return pq._q40_matmul_core(x, q40_at_rest(stack), True, w_dtype, mode, layer)

    return call


@pytest.mark.parametrize("how", ["jit", "scan"])
@pytest.mark.parametrize("m,d_in,d_out", [
    (4, 4096, 2048),    # n_k > 1: the k axis walks chunks of layer l's scale plane
    (2, 512, 16384),    # two wide tiles: the j axis
    (300, 64, 256),     # rows above M_TILE: the -8 subtracted, one block of 512
    (16, 2048, 1152),   # k chunks and sub tiles 512 + 512 + 128, the -8 folded
    (128, 2048, 1152),  # the same plan at the threshold: subtracted
])
def test_scale_tiles_read_in_place_equal_the_float16_planes_call(monkeypatch, m, d_in, d_out, how):
    """Layer l's scale tiles addressed inside the int16 stack by the index
    maps, ``l`` traced (a jit's argument; a scan's counter): the bit-identical
    result of the float16 plane's own call, on every grid axis, decode and
    prefill widths, fold and subtract."""
    rng = np.random.default_rng(d_in + d_out + m)
    stack = _stack(rng, d_out, d_in, n=3)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    call = _in_place(monkeypatch)
    reset_trace_stats()
    if how == "jit":
        fn = jax.jit(lambda x, s, l: call(x, s, l))
        got = {l: fn(x, stack, jnp.int32(l)) for l in (0, 2)}
    else:
        _, ys = jax.lax.scan(lambda c, l: (c, call(x, stack, l)), 0,
                             jnp.arange(3, dtype=jnp.int32))
        got = {l: ys[l] for l in (0, 2)}
    assert TRACE_STATS["scale_stack_reads"] == TRACE_STATS["impl_traces"] == 1, TRACE_STATS
    assert TRACE_STATS["scale_converts"] == 0
    for l, y in got.items():
        want = q40_matmul_pallas(x, _plane(stack, l), interpret=True)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(want), err_msg=f"layer {l}")
    assert not np.array_equal(np.asarray(got[0]), np.asarray(got[2]))


@pytest.mark.parametrize("mode", DEQUANT_MODES)
def test_scale_tiles_read_in_place_in_every_mode(monkeypatch, mode):
    """The block-dot modes' kernels take the scale operand by the same spec:
    in place out of the stack in each of the six, equal to the float16
    plane's call in that mode to the bit."""
    rng = np.random.default_rng(55)
    stack = _stack(rng, 256, 128)
    x = jnp.asarray(rng.standard_normal((4, 128), dtype=np.float32))
    call = _in_place(monkeypatch)
    set_dequant_mode(mode)
    try:
        got = jax.jit(lambda x, s, l: call(x, s, l, mode, jnp.bfloat16))(
            x, stack, jnp.int32(STACK_L - 1))
        want = q40_matmul_pallas(x, _plane(stack, STACK_L - 1), interpret=True,
                                 w_dtype=jnp.bfloat16)
    finally:
        set_dequant_mode(None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# sha256[:16] of the output bytes of the seeded call below. The two block-dot
# modes: what the PARENT of PR 30 gave (its kernel took 2-D planes only; a 2-D
# weight goes through the same pallas_call as a stack now, as the stack of one
# read at layer 0), untouched since. The slab chains: what PR 42 gives, whose
# one dot of depth 2 * rows sums the same products in another order than the
# two dots of depth rows it replaced, and whose block sums are of x as the
# dot sees it (this call hands an f32 x to a bf16 dot: the parent summed the
# unrounded f32 there; a bf16 x, as every cell's, reads the same either
# way). PR 30's parent gave 152c5bc3acc7695f for v4, 8cd3d3f237e607c8 for
# the bf16 chains, a00e1bb2e19dc500 in f32; how far the order moves a result
# is held against ``_two_dot_form`` below.
PARENT_2D_DIGESTS = {
    "f32": "4405722f4a618a94", "v4": "aab6fc6aa5b9fec8",
    "bf16chain": "b84f88086d153cd2", "repeat": "b84f88086d153cd2",
    "u8chain": "b84f88086d153cd2", "blockdot": "2e41ab0e772529f9",
    "i8blockdot": "e537117c72b1fdf8",
}


@pytest.mark.parametrize("mode", list(PARENT_2D_DIGESTS))
def test_plain_weight_gives_what_it_gave_before_stacks(mode):
    import hashlib

    rng = np.random.default_rng(30)
    pw = _pack(rng, 384, 256)
    x = jnp.asarray(rng.standard_normal((5, 256), dtype=np.float32))
    kw = {} if mode == "f32" else {"w_dtype": jnp.bfloat16}
    set_dequant_mode(None if mode == "f32" else mode)
    try:
        got = np.asarray(q40_matmul_pallas(x, pw, interpret=True, **kw))
        as_stack = np.asarray(q40_matmul_pallas(
            x, PackedQ40(pw.packed[None], pw.scales[None]), interpret=True,
            layer=0, **kw))
        at_rest = np.asarray(q40_matmul_pallas(
            x, q40_at_rest(pw), interpret=True, **kw))
    finally:
        set_dequant_mode(None)
    assert hashlib.sha256(got.tobytes()).hexdigest()[:16] == PARENT_2D_DIGESTS[mode]
    np.testing.assert_array_equal(as_stack, got)
    np.testing.assert_array_equal(at_rest, got)  # the plane's scales as bits
    if mode in ("f32", "v4"):
        # the same products as the two-dot form, summed in another order
        want = np.asarray(_two_dot_form(
            x, pw, jnp.float32 if mode == "f32" else jnp.bfloat16))
        np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max(),
                                   rtol=0)


def test_stack_and_layer_go_together():
    """A stack without a layer, or a layer with a 2-D plane, is an error and
    not a guess."""
    rng = np.random.default_rng(3)
    stack = _stack(rng, 128, 64, n=2)
    x = jnp.asarray(rng.standard_normal((2, 64), dtype=np.float32))
    with pytest.raises(ValueError, match="stack and its layer"):
        q40_matmul_pallas(x, stack, interpret=True)
    with pytest.raises(ValueError, match="stack and its layer"):
        q40_matmul_pallas(x, _plane(stack, 0), interpret=True, layer=0)


# ---------------------------------------------------------------------------
# The slab chains take x as it is (PR 42): its own column order, its own
# dtype, one BlockSpec. The kernel puts the dequantised nibble planes back in
# the input's order by whole 16-row tiles, multiplies in one dot and sums x's
# quant blocks itself. Before, the operand build split x's lane axis into
# [n_blk, 2, 16] in XLA ahead of every distinct input.
# ---------------------------------------------------------------------------



def _two_dot_form(x, pw, w_dtype, sums_of=None):
    """What the kernel computed before PR 42, in plain jax.numpy on a whole
    plane: the pre-split halves of x against the low and the high nibble
    plane in two dots, the folded -8 against exact f32 block sums.
    ``sums_of``: the array whose blocks are summed. By default x as the dots
    see it, rounded to ``w_dtype``: what a bf16 x gave then and gives now.
    The parent summed the f32 it was handed (``sums_of=x`` for an f32 x,
    or the f32 a fused convert pair let through: see the norm-then-cast
    test)."""
    m, d_in = x.shape
    n_blk, half = d_in // 32, d_in // 2
    xf = x.astype(jnp.float32)
    xb = xf.reshape(m, n_blk, 2, 16)
    x_lo = xb[:, :, 0, :].reshape(m, half).astype(w_dtype)
    x_hi = xb[:, :, 1, :].reshape(m, half).astype(w_dtype)
    if sums_of is None:
        sums_of = x.astype(w_dtype)
    bsum = sums_of.astype(jnp.float32).reshape(m, n_blk, 32).sum(axis=2)
    p = pw.packed.astype(jnp.int32)
    s = pw.scales.astype(jnp.float32)
    planes = [
        (nib.astype(jnp.float32).reshape(n_blk, 16, -1) * s[:, None, :])
        .reshape(half, -1).astype(w_dtype)
        for nib in (p & 0x0F, p >> 4)
    ]
    dot = partial(jnp.dot, preferred_element_type=jnp.float32,
                  precision="highest")
    y = dot(x_lo, planes[0]) + dot(x_hi, planes[1]) - 8.0 * dot(bsum, s)
    return y.astype(x.dtype)


# every plan the cells' shapes take, at sizes interpret mode can carry
NATURAL_SHAPES = [
    # m = 8 (DeepSeek's decode width), 112 blocks (3584 / 32), one slab
    (8, 3584, 256),
    # m = 16, 112 blocks in two reduction chunks of 56 (rows 896)
    (16, 3584, 1024),
    # m = 32, 128 blocks in chunks, the f32 accumulator
    (32, 4096, 2048),
    # m = 64, two wide tiles
    (64, 512, 16384),
    # one whole m tile; 43 blocks
    (256, 1376, 128),
    # above M_TILE (one block of 512 rows), and rows that need padding
    (300, 64, 256),
    # rows that need padding under either dtype; 448 blocks (14336 / 32)
    (5, 14336, 128),
]


@pytest.mark.parametrize("weight", ["plane", "stack"])
@pytest.mark.parametrize("entry", ["raw_x", "rank3"])
@pytest.mark.parametrize("m,d_in,d_out", NATURAL_SHAPES)
def test_natural_operand_matches_xla_and_the_two_dot_form(m, d_in, d_out,
                                                          entry, weight):
    """The kernel handed x itself, in exact f32: against the XLA dequant to
    the tolerance this file has always had, against the two-dot form (the
    same products in another order) closer, and the four ways in (x in two
    dimensions or as ``[lanes, t, d_in]``, a plane or a layer of a stack under
    a traced index) equal to the bit."""
    rng = np.random.default_rng(d_in + d_out + m)
    stack = _stack(rng, d_out, d_in, n=2)
    pw = _plane(stack, 1)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))

    @jax.jit
    def run(x, stack, l):
        lanes = 2 if m % 2 == 0 else 1
        xin = x.reshape(lanes, m // lanes, d_in) if entry == "rank3" else x
        if weight == "stack":
            y = q40_matmul_pallas(xin, stack, interpret=True, layer=l)
        else:
            y = q40_matmul_pallas(xin, _plane(stack, 1), interpret=True)
        assert y.shape == xin.shape[:-1] + (d_out,)
        return y.reshape(m, d_out)

    got = np.asarray(run(x, stack, jnp.int32(1)))
    np.testing.assert_allclose(
        got, np.asarray(q40_matmul_xla(x, pw)), atol=2e-4, rtol=2e-4)
    want = np.asarray(_two_dot_form(x, pw, jnp.float32))
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max(),
                               rtol=0)
    base = np.asarray(q40_matmul_pallas(x, pw, interpret=True))
    np.testing.assert_array_equal(got, base)


@pytest.mark.parametrize("mode", ["v4", "bf16chain", "repeat", "u8chain"])
@pytest.mark.parametrize("m,d_in,d_out", [
    (8, 3584, 256), (16, 3584, 1024), (32, 512, 1024), (300, 64, 256)])
def test_natural_operand_in_bf16_as_the_cells_run_it(m, d_in, d_out, mode):
    """x in bf16 under the bf16 dot, every slab chain: rows padded to whole
    16-row tiles, the block sums a bf16 dot with f32 accumulation (every
    product exact). Against the two-dot form in the same precision the
    result differs by the summation order and the output's one rounding to
    bf16; v4 dequantises exactly as that form does, the bf16 chains also
    round the scale."""
    rng = np.random.default_rng(d_in + d_out + m)
    pw = _pack(rng, d_out, d_in)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32)
                    ).astype(jnp.bfloat16)
    set_dequant_mode(mode)
    try:
        got = q40_matmul_pallas(x, pw, interpret=True, w_dtype=jnp.bfloat16)
    finally:
        set_dequant_mode(None)
    assert got.dtype == jnp.bfloat16 and got.shape == (m, d_out)
    got = np.asarray(got, np.float32)
    want = np.asarray(_two_dot_form(x, pw, jnp.bfloat16), np.float32)
    top = np.abs(want).max()
    # one bf16 rounding of the output is 2**-8 of a value; the chains that
    # round the scale to bf16 as well stay inside this file's 2e-2 of max
    bound = 2 ** -7 if mode == "v4" else 2e-2
    assert np.abs(got - want).max() <= bound * top, (
        mode, np.abs(got - want).max() / top)


@pytest.mark.parametrize("made_by", ["norm", "gated_product"])
def test_x_rounded_once_feeds_both_terms_of_the_folded_minus_8(made_by):
    """x as the model makes it in f32 and casts to bf16: an RMS norm, and the
    FFN's gated product silu(a) * b of two bf16 arrays. The kernel handed the
    bf16 x agrees with the two-dot form on that x to the order of a sum and
    the output's one rounding. The parent's compiled decode step summed the
    blocks of the UNROUNDED product at w2's input (XLA removed the f32 ->
    bf16 -> f32 pair after the multiply: allow_excess_precision; the four
    other inputs of a layer were rounded, PERF.md section 6, PR 42), while
    its dots saw the rounded one; that form (``sums_of`` the f32) is the
    farther of the two from the f32 result, by the 8 * s * sum(x - bf16(x))
    the two terms then disagree by."""
    rng = np.random.default_rng(42)
    m, d_in, d_out = 16, 3584, 1024
    pw = _pack(rng, d_out, d_in)
    as_bf16 = lambda a: jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32)
    a = as_bf16(rng.standard_normal((m, d_in), dtype=np.float32))
    if made_by == "norm":
        g = jnp.asarray(1 + 0.1 * rng.standard_normal(d_in, dtype=np.float32))
        xf = a * jax.lax.rsqrt((a * a).mean(-1, keepdims=True) + 1e-5) * g
    else:
        b = as_bf16(rng.standard_normal((m, d_in), dtype=np.float32))
        xf = as_bf16(jax.nn.silu(a)) * b
    xb = xf.astype(jnp.bfloat16)
    exact = np.asarray(q40_matmul_xla(xf, pw), np.float32)
    rel = lambda y: (np.linalg.norm(np.asarray(y, np.float32) - exact)
                     / np.linalg.norm(exact))

    got = q40_matmul_pallas(xb, pw, interpret=True, w_dtype=jnp.bfloat16)
    same_x = _two_dot_form(xb, pw, jnp.bfloat16)
    sums_unrounded = _two_dot_form(xb, pw, jnp.bfloat16, sums_of=xf)
    gap = np.abs(np.asarray(got, np.float32) - np.asarray(same_x, np.float32))
    assert gap.max() <= 2 ** -7 * np.abs(exact).max()
    # 0.0048 against 0.0062 on this seed, either way x was made
    assert rel(got) < 0.9 * rel(sums_unrounded), (rel(got), rel(sums_unrounded))
    assert abs(rel(got) - rel(same_x)) < 0.01 * rel(same_x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,n", [(8, 512), (16, 3584), (5, 14336), (256, 64),
                                 (16, 7168), (8, 2048)])
def test_block_sums_equal_the_reshaped_sum(m, n, dtype):
    """The kernel's block sums (a dot against a 0/1 matrix: no lane of x is
    split) against ``x.reshape(m, n_blk, 32).sum(-1)`` in f32: the same 32
    numbers summed in f32 either way, so equal to the order of a sum."""
    rng = np.random.default_rng(m + n)
    x = jnp.asarray(rng.standard_normal((m, n), dtype=np.float32)).astype(dtype)
    pieces = pq._block_sums(x)
    # one 0/1 matrix of at most BSUM_SLICE columns, whatever the chunk's width
    assert len(pieces) == n // pq._sum_slice(n) and pq._sum_slice(n) <= 2048
    got = np.concatenate([np.asarray(p) for p in pieces], axis=1)
    assert got.dtype == np.float32 and got.shape == (m, n // 32)
    want = np.asarray(x.astype(jnp.float32).reshape(m, n // 32, 32).sum(-1))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("m,dtype,want", [
    (1, jnp.float32, (8, 8)), (8, jnp.float32, (8, 8)),
    (8, jnp.bfloat16, (16, 16)), (16, jnp.bfloat16, (16, 16)),
    (20, jnp.bfloat16, (32, 32)), (64, jnp.bfloat16, (64, 64)),
    (300, jnp.float32, (512, 256)), (300, jnp.bfloat16, (512, 256)),
    (1024, jnp.bfloat16, (1024, 256)),
])
def test_rows_pad_to_whole_tiles_of_their_dtype(m, dtype, want):
    """A bf16 block wants whole 16-row tiles (two rows a sublane), an f32
    one 8: decided by the input's dtype and static row count, nothing else."""
    assert pq._m_geometry(m, dtype) == want
    x = jnp.zeros((m, 64), dtype)
    assert pq._padded_rows(x).shape == (want[0], 64)
    assert pq._padded_rows(x).dtype == dtype
