"""Kernel lab 3: cheaper-dequant Q40 matmul variants, measured on real TPU.

Round-5 finding (BENCH_LIVE primary + 8b phases): hbm_util is ~0.26 for the
1B AND ~0.24 for the 8B — a per-BYTE cost, not per-launch. The dequant chain
costs ~4.5 VPU ops/weight (int32 unpack relayout, mask/shift, int->f32 cast,
f32 scale mul, f32->bf16 cast); at the VPU's ~1e12 ops/s that alone accounts
for the entire observed decode time — DMA hides under it. These variants cut
per-weight VPU work:

  full_v4         current product chain (baseline: f32 dequant -> bf16 cast)
  full_bf16chain  dequant in bf16 end-to-end: nib int32->bf16, bf16 scale mul
                  (drops the f32 round-trip: ~1 op/weight less)
  full_repeat     bf16 chain + scale broadcast via pltpu.repeat instead of
                  the reshape(n_blk,16,t)*s3 reshape dance (relayout suspect)
  full_blockdot   per-quant-block MXU dots on raw bf16 nibbles; the scale is
                  applied to each block's [m,t] OUTPUT (m/32 ops per weight
                  instead of 1): per-weight VPU = mask + cast only
  full_u8nib      nibble extraction on native 8-bit lanes (mask before the
                  int32 relayout), then one int8->bf16 cast

XLA-level (no Pallas) int4-resident alternatives:
  xla_int4_raw    y = x @ W4.astype(bf16) — XLA's own int4 read+convert+dot
  xla_int4_scaled same with the per-block scale woven in pre-dot

Run on TPU:  python scripts/kernel_lab3.py [d_in] [d_out] [L] [reps]
Correctness: python scripts/kernel_lab3.py --check   (interpret mode, CPU)
"""

from __future__ import annotations

import sys
import time
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, ".")

from distributed_llama_multiusers_tpu.ops.pallas_q40 import (  # noqa: E402
    _f16_bits_to_f32,
)

HBM_GB_S = 819.0  # v5e
M = 8
CHUNK = 2048  # d_in per grid step
TILE = 512  # d_out per grid step
_REPS = 8
_INTERPRET = False


# ---------------------------------------------------------------------------
# kernel bodies. Shared operand layout (all pre-split outside the kernel, as
# the product's block-dot modes still take them; its slab chains have taken
# x as it is since PR 42, and a variant that wins here is ported to that):
#   xl/xh  [M, half]        block-local nibble halves of x's columns
#   xlt/xht[half, M]        the same, transposed (blockdot wants sublane
#                           slicing at 16-row granularity)
#   bsum_t [n_blk, M]       per-quant-block x sums, transposed
#   p      [half, d_out]    packed nibbles
#   s      [n_blk, d_out]   f16 scale bits (int16)
# ---------------------------------------------------------------------------


def _k_v4(t_ref, xl_ref, xh_ref, bs_ref, p_ref, s_ref, o_ref):
    """Current product chain: f32 dequant, bf16 dot operands."""
    rows, tile = p_ref.shape
    n_blk = rows // 16
    p = p_ref[...].astype(jnp.int32)
    s = _f16_bits_to_f32(s_ref[...])
    s3 = s[:, None, :]
    w_lo = ((p & 0x0F).astype(jnp.float32).reshape(n_blk, 16, tile) * s3)
    w_hi = ((p >> 4).astype(jnp.float32).reshape(n_blk, 16, tile) * s3)
    w_lo = w_lo.reshape(rows, tile).astype(jnp.bfloat16)
    w_hi = w_hi.reshape(rows, tile).astype(jnp.bfloat16)
    corr = jax.lax.dot_general(
        bs_ref[...], s, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = (
        jnp.dot(xl_ref[...].astype(jnp.bfloat16), w_lo,
                preferred_element_type=jnp.float32)
        + jnp.dot(xh_ref[...].astype(jnp.bfloat16), w_hi,
                  preferred_element_type=jnp.float32)
        - 8.0 * corr + t_ref[0, 0]
    )


def _k_bf16chain(t_ref, xl_ref, xh_ref, bs_ref, p_ref, s_ref, o_ref):
    """Dequant entirely in bf16: int32 nibbles cast straight to bf16 (exact:
    0..15), scales decoded once to bf16 (amortized /32), one bf16 mul."""
    rows, tile = p_ref.shape
    n_blk = rows // 16
    p = p_ref[...].astype(jnp.int32)
    s_f32 = _f16_bits_to_f32(s_ref[...])
    s_bf = s_f32.astype(jnp.bfloat16)[:, None, :]
    w_lo = ((p & 0x0F).astype(jnp.bfloat16).reshape(n_blk, 16, tile) * s_bf)
    w_hi = ((p >> 4).astype(jnp.bfloat16).reshape(n_blk, 16, tile) * s_bf)
    w_lo = w_lo.reshape(rows, tile)
    w_hi = w_hi.reshape(rows, tile)
    corr = jax.lax.dot_general(
        bs_ref[...], s_f32, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = (
        jnp.dot(xl_ref[...].astype(jnp.bfloat16), w_lo,
                preferred_element_type=jnp.float32)
        + jnp.dot(xh_ref[...].astype(jnp.bfloat16), w_hi,
                  preferred_element_type=jnp.float32)
        - 8.0 * corr + t_ref[0, 0]
    )


def _k_repeat(t_ref, xl_ref, xh_ref, bs_ref, p_ref, s_ref, o_ref):
    """bf16 chain, scale broadcast via jnp.repeat (no reshape dance).
    (pltpu.repeat TILES the array — s0..sB,s0..sB — which is the wrong
    order for the block-contiguous packed layout; jnp.repeat keeps each
    block's 16 rows consecutive.)"""
    rows, tile = p_ref.shape
    p = p_ref[...].astype(jnp.int32)
    s_f32 = _f16_bits_to_f32(s_ref[...])
    s_rep = jnp.repeat(s_f32.astype(jnp.bfloat16), 16, axis=0)  # [rows, tile]
    w_lo = (p & 0x0F).astype(jnp.bfloat16) * s_rep
    w_hi = (p >> 4).astype(jnp.bfloat16) * s_rep
    corr = jax.lax.dot_general(
        bs_ref[...], s_f32, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = (
        jnp.dot(xl_ref[...].astype(jnp.bfloat16), w_lo,
                preferred_element_type=jnp.float32)
        + jnp.dot(xh_ref[...].astype(jnp.bfloat16), w_hi,
                  preferred_element_type=jnp.float32)
        - 8.0 * corr + t_ref[0, 0]
    )


def _k_blockdot(t_ref, xlt_ref, xht_ref, bs_ref, p_ref, s_ref, o_ref):
    """Per-block MXU dots on RAW nibbles; scales hit each block's [M, tile]
    output: per-weight VPU work = mask + int->bf16 cast only. The -8 offset
    folds into the same post-scale FMA via the per-block x sums."""
    rows, tile = p_ref.shape
    n_blk = rows // 16
    p = p_ref[...].astype(jnp.int32)
    nib_lo = (p & 0x0F).astype(jnp.bfloat16)  # [rows, tile]
    nib_hi = (p >> 4).astype(jnp.bfloat16)
    s = _f16_bits_to_f32(s_ref[...])  # [n_blk, tile] f32
    bs = bs_ref[...]  # [n_blk, M]
    acc = jnp.zeros_like(o_ref)
    dn = (((0,), (0,)), ((), ()))
    for b in range(n_blk):
        lo = jax.lax.dot_general(
            xlt_ref[16 * b:16 * (b + 1), :].astype(jnp.bfloat16),
            nib_lo[16 * b:16 * (b + 1), :], dn,
            preferred_element_type=jnp.float32,
        )
        hi = jax.lax.dot_general(
            xht_ref[16 * b:16 * (b + 1), :].astype(jnp.bfloat16),
            nib_hi[16 * b:16 * (b + 1), :], dn,
            preferred_element_type=jnp.float32,
        )
        acc = acc + (lo + hi - 8.0 * bs[b, :, None]) * s[b][None, :]
    o_ref[...] = acc + t_ref[0, 0]


def _k_i8blockdot(t_ref, xlt_ref, xht_ref, aux_ref, p_ref, s_ref, o_ref):
    """Q80-style int8 MXU dots: the raw nibbles (int8, no cast, no scale)
    feed the MXU directly; activations arrive pre-quantized to int8 per
    quant block (xq = round(x / sx), the reference's own activation
    format). Per block b:

        y += s_b * (sx[:,b,None] * (xq_lo_b @ nib_lo_b + xq_hi_b @ nib_hi_b)
                    - 8 * bsum_b)

    Per-weight VPU work = the 8-bit-lane mask ONLY (~0.5-1 op); the
    rescale costs ~4*m/32 ops/weight. aux packs bsum and sx interleaved
    on the sublane axis: aux[2b] = bsum[b], aux[2b+1] = sx[b]."""
    rows, tile = p_ref.shape
    n_blk = rows // 16
    aux = aux_ref[...].reshape(n_blk, 2, M)
    bs = aux[:, 0, :]  # [n_blk, M] f32
    sx = aux[:, 1, :]  # [n_blk, M] f32
    p8 = p_ref[...]
    nib_lo = (p8 & jnp.uint8(0x0F)).astype(jnp.int8)
    nib_hi = (p8 >> jnp.uint8(4)).astype(jnp.int8)
    s = _f16_bits_to_f32(s_ref[...])  # [n_blk, tile]
    xl = xlt_ref[...]  # [rows, M] int8
    xh = xht_ref[...]
    dn = (((0,), (0,)), ((), ()))
    acc = None
    for b in range(n_blk):
        lo = jax.lax.dot_general(
            xl[16 * b:16 * (b + 1), :], nib_lo[16 * b:16 * (b + 1), :], dn,
            preferred_element_type=jnp.int32,
        )
        hi = jax.lax.dot_general(
            xh[16 * b:16 * (b + 1), :], nib_hi[16 * b:16 * (b + 1), :], dn,
            preferred_element_type=jnp.int32,
        )
        d = (lo + hi).astype(jnp.float32)  # [M, tile]
        contrib = (sx[b][:, None] * d - 8.0 * bs[b][:, None]) * s[b][None, :]
        acc = contrib if acc is None else acc + contrib
    o_ref[...] = acc + t_ref[0, 0]


def _quantize_x_blocks(xf, d_in):
    """Reference-Q80-style per-block activation quantization for the
    i8blockdot operands: returns (xq_lo_T, xq_hi_T int8 [half, M],
    aux f32 [n_blk*2, M] with bsum/sx interleaved)."""
    m = xf.shape[0]
    n_blk = d_in // 32
    xb = np.asarray(xf, np.float32).reshape(m, n_blk, 32)
    sx = np.abs(xb).max(axis=2) / 127.0  # [m, n_blk]
    sx = np.where(sx == 0, 1e-8, sx)
    xq = np.clip(np.rint(xb / sx[:, :, None]), -127, 127).astype(np.int8)
    bsum = xb.sum(axis=2)  # [m, n_blk] (EXACT x sums for the -8 fold)
    xq_lo = xq[:, :, :16].reshape(m, d_in // 2)
    xq_hi = xq[:, :, 16:].reshape(m, d_in // 2)
    aux = np.empty((n_blk * 2, m), np.float32)
    aux[0::2] = bsum.T
    aux[1::2] = sx.T
    return (
        jnp.asarray(xq_lo.T), jnp.asarray(xq_hi.T), jnp.asarray(aux)
    )


def _k_u8nib(t_ref, xl_ref, xh_ref, bs_ref, p_ref, s_ref, o_ref):
    """Mask on native 8-bit lanes BEFORE any widening, then int8->bf16."""
    rows, tile = p_ref.shape
    n_blk = rows // 16
    p8 = p_ref[...]
    lo8 = (p8 & jnp.uint8(0x0F)).astype(jnp.int8)
    hi8 = (p8 >> jnp.uint8(4)).astype(jnp.int8)
    s_f32 = _f16_bits_to_f32(s_ref[...])
    s_bf = s_f32.astype(jnp.bfloat16)[:, None, :]
    w_lo = (lo8.astype(jnp.bfloat16).reshape(n_blk, 16, tile) * s_bf)
    w_hi = (hi8.astype(jnp.bfloat16).reshape(n_blk, 16, tile) * s_bf)
    w_lo = w_lo.reshape(rows, tile)
    w_hi = w_hi.reshape(rows, tile)
    corr = jax.lax.dot_general(
        bs_ref[...], s_f32, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = (
        jnp.dot(xl_ref[...].astype(jnp.bfloat16), w_lo,
                preferred_element_type=jnp.float32)
        + jnp.dot(xh_ref[...].astype(jnp.bfloat16), w_hi,
                  preferred_element_type=jnp.float32)
        - 8.0 * corr + t_ref[0, 0]
    )


KERNELS = {
    "full_v4": (_k_v4, False),
    "full_bf16chain": (_k_bf16chain, False),
    "full_repeat": (_k_repeat, False),
    "full_blockdot": (_k_blockdot, True),  # True: wants transposed x
    "full_u8nib": (_k_u8nib, False),
}
# i8blockdot is special-cased (int8 x operands + interleaved bsum/sx aux)

def _call_i8blockdot(xf, packed, sbits, d_in, d_out, chunk, tile):
    half = d_in // 2
    xq_lo, xq_hi, aux = _quantize_x_blocks(np.asarray(xf), d_in)
    t = jnp.zeros((1, 128), jnp.float32)
    return pl.pallas_call(
        lambda t_ref, a, b, c, p_, s_, o: _k_i8blockdot(t_ref, a, b, c, p_, s_, o),
        grid=(d_out // tile, half // (chunk // 2)),
        in_specs=[
            pl.BlockSpec((1, 128), lambda j, k: (0, 0)),
            pl.BlockSpec((chunk // 2, M), lambda j, k: (k, 0)),
            pl.BlockSpec((chunk // 2, M), lambda j, k: (k, 0)),
            pl.BlockSpec(((chunk // 32) * 2, M), lambda j, k: (k, 0)),
            pl.BlockSpec((chunk // 2, tile), lambda j, k: (k, j)),
            pl.BlockSpec((chunk // 32, tile), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((M, tile), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((M, d_out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=_INTERPRET,
    )(t, xq_lo, xq_hi, aux, packed, sbits)


def _ref_dequant(packed, scales):
    """numpy oracle: dense f32 weights from one packed plane."""
    half, d_out = packed.shape
    n_blk = half // 16
    p = np.asarray(packed).astype(np.int32)
    s = np.asarray(scales).astype(np.float32)  # [n_blk, d_out]
    lo = (p & 0x0F).reshape(n_blk, 16, d_out)
    hi = (p >> 4).reshape(n_blk, 16, d_out)
    w = np.zeros((half * 2, d_out), np.float32)
    wb = w.reshape(n_blk, 32, d_out)
    wb[:, :16] = (lo - 8) * s[:, None, :]
    wb[:, 16:] = (hi - 8) * s[:, None, :]
    return w


def _split_x(xf, d_in):
    m = xf.shape[0]
    half = d_in // 2
    xb = xf.reshape(m, d_in // 32, 2, 16)
    x_lo = xb[:, :, 0, :].reshape(m, half)
    x_hi = xb[:, :, 1, :].reshape(m, half)
    bsum_t = xf.reshape(m, d_in // 32, 32).sum(axis=2).T
    return x_lo, x_hi, bsum_t


def _call_kernel(name, xf, packed, sbits, d_in, d_out, chunk, tile):
    """One full-plane matmul through variant `name` (single-plane grid)."""
    kern, transposed = KERNELS[name]
    half = d_in // 2
    x_lo, x_hi, bsum_t = _split_x(xf, d_in)
    if transposed:
        xa, xb_ = x_lo.T, x_hi.T
        x_spec = pl.BlockSpec((chunk // 2, M), lambda j, k: (k, 0))
    else:
        xa, xb_ = x_lo, x_hi
        x_spec = pl.BlockSpec((M, chunk // 2), lambda j, k: (0, k))
    t = jnp.zeros((1, 128), jnp.float32)
    return pl.pallas_call(
        lambda t_ref, a, b, c, p_, s_, o: kern(t_ref, a, b, c, p_, s_, o),
        grid=(d_out // tile, half // (chunk // 2)),
        in_specs=[
            pl.BlockSpec((1, 128), lambda j, k: (0, 0)),
            x_spec,
            x_spec,
            pl.BlockSpec((chunk // 32, M), lambda j, k: (k, 0)),
            pl.BlockSpec((chunk // 2, tile), lambda j, k: (k, j)),
            pl.BlockSpec((chunk // 32, tile), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((M, tile), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((M, d_out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=_INTERPRET,
    )(t, xa, xb_, bsum_t, packed, sbits)


def check():
    """Interpret-mode correctness: every variant vs the numpy oracle.

    NOTE: accumulation over the d_in grid axis relies on out_ref revisiting
    (arbitrary k axis) — in this lab the k axis ADDs t_ref noise per step, so
    for the check we use a single-chunk plane (d_in == chunk). Small shapes:
    interpret mode emulates the blockdot's unrolled per-block dots slowly."""
    global _INTERPRET
    _INTERPRET = True
    chunk, tile = 512, 256
    d_in, d_out = chunk, tile * 2
    rng = np.random.default_rng(0)
    packed = jnp.asarray(rng.integers(0, 256, (d_in // 2, d_out), np.uint8))
    scales = (rng.random((d_in // 32, d_out), np.float32) * 0.01 + 1e-3)
    sb = jax.lax.bitcast_convert_type(
        jnp.asarray(scales, jnp.float32).astype(jnp.float16), jnp.int16
    )
    xf = jnp.asarray(rng.standard_normal((M, d_in), np.float32))
    w_ref = _ref_dequant(packed, np.asarray(scales, np.float32).astype(np.float16))
    y_ref = np.asarray(xf) @ w_ref
    failed = False
    for name in KERNELS:
        y = np.asarray(
            _call_kernel(name, xf, packed, sb, d_in, d_out, chunk, tile)
        )
        rel = np.abs(y - y_ref).max() / (np.abs(y_ref).max() + 1e-9)
        ok = rel < 2e-2
        failed |= not ok
        print(f"{name:16s} max-rel-err {rel:.2e}  {'ok' if ok else 'FAIL'}")
    # i8blockdot quantizes the ACTIVATIONS too (reference Q80 semantics) —
    # looser bound than the weight-only variants
    y = np.asarray(_call_i8blockdot(xf, packed, sb, d_in, d_out, chunk, tile))
    rel = np.abs(y - y_ref).max() / (np.abs(y_ref).max() + 1e-9)
    ok = rel < 5e-2
    failed |= not ok
    print(f"{'full_i8blockdot':16s} max-rel-err {rel:.2e}  {'ok' if ok else 'FAIL'}")
    if failed:
        sys.exit(1)


def main():
    if "--check" in sys.argv:
        check()
        return
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    d_in = int(args[0]) if len(args) > 0 else 4096
    d_out = int(args[1]) if len(args) > 1 else 14336
    L = int(args[2]) if len(args) > 2 else 8
    global _REPS
    _REPS = int(args[3]) if len(args) > 3 else 8
    half = d_in // 2
    n_blk_all = d_in // 32

    kp, ks, kx = jax.random.split(jax.random.PRNGKey(0), 3)
    packed = jax.random.bits(kp, (L, half, d_out), jnp.uint8)
    scales = (
        jax.random.uniform(ks, (L, n_blk_all, d_out), jnp.float32) * 0.01
        + 0.001
    ).astype(jnp.float16)
    sbits = jax.lax.bitcast_convert_type(scales, jnp.int16)
    xf = jax.random.normal(kx, (M, d_in), jnp.float32)
    x_lo, x_hi, bsum_t = _split_x(xf, d_in)
    jax.block_until_ready((packed, sbits, x_lo))
    pbytes = packed.size
    print(f"d_in={d_in} d_out={d_out} L={L} M={M} packed={pbytes/1e6:.1f} MB "
          f"device={jax.devices()[0].device_kind}", flush=True)

    grid = (L, d_out // TILE, half // (CHUNK // 2))
    t_spec = pl.BlockSpec((1, 128), lambda l, j, k: (0, 0))
    p_spec = pl.BlockSpec((1, CHUNK // 2, TILE), lambda l, j, k: (l, k, j))
    s_spec = pl.BlockSpec((1, CHUNK // 32, TILE), lambda l, j, k: (l, k, j))
    o_spec = pl.BlockSpec((M, TILE), lambda l, j, k: (0, j))
    o_shape = jax.ShapeDtypeStruct((M, d_out), jnp.float32)
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "parallel", "arbitrary"),
    )

    for name, (kern, transposed) in KERNELS.items():
        if transposed:
            xa, xb_ = x_lo.T, x_hi.T
            x_spec = pl.BlockSpec((CHUNK // 2, M), lambda l, j, k: (k, 0))
        else:
            xa, xb_ = x_lo, x_hi
            x_spec = pl.BlockSpec((M, CHUNK // 2), lambda l, j, k: (0, k))
        bs_spec = pl.BlockSpec((CHUNK // 32, M), lambda l, j, k: (k, 0))

        def call(t, kern=kern, xa=xa, xb_=xb_, x_spec=x_spec, bs_spec=bs_spec):
            def wrapped(t_ref, xa_ref, xb_ref, bs_ref, p_ref, s_ref, o_ref):
                kern(t_ref, xa_ref, xb_ref, bs_ref, p_ref.at[0], s_ref.at[0],
                     o_ref)

            return pl.pallas_call(
                wrapped, grid=grid,
                in_specs=[t_spec, x_spec, x_spec, bs_spec, p_spec, s_spec],
                out_specs=o_spec, out_shape=o_shape,
                compiler_params=params,
            )(t, xa, xb_, bsum_t, packed, sbits)

        timeit(name, call, pbytes)

    # ---- i8blockdot: int8 MXU dots on Q80-quantized activations -----------
    xq_lo, xq_hi, aux = _quantize_x_blocks(np.asarray(xf), d_in)
    jax.block_until_ready((xq_lo, xq_hi, aux))
    xi8_spec = pl.BlockSpec((CHUNK // 2, M), lambda l, j, k: (k, 0))
    aux_spec = pl.BlockSpec(((CHUNK // 32) * 2, M), lambda l, j, k: (k, 0))

    def call_i8(t):
        def wrapped(t_ref, a, b, c, p_ref, s_ref, o_ref):
            _k_i8blockdot(t_ref, a, b, c, p_ref.at[0], s_ref.at[0], o_ref)

        return pl.pallas_call(
            wrapped, grid=grid,
            in_specs=[t_spec, xi8_spec, xi8_spec, aux_spec, p_spec, s_spec],
            out_specs=o_spec, out_shape=o_shape,
            compiler_params=params,
        )(t, xq_lo, xq_hi, aux, packed, sbits)

    timeit("full_i8blockdot", call_i8, pbytes)

    # ---- XLA-level int4 alternatives (no Pallas) --------------------------
    try:
        w4 = jax.random.randint(
            jax.random.PRNGKey(7), (L, d_in, d_out), -8, 8, jnp.int8
        ).astype(jnp.int4)
        s_bf = scales.astype(jnp.bfloat16)
        jax.block_until_ready(w4)
        i4bytes = w4.size // 2  # int4 packs 2/byte in HBM

        def raw(t):
            def body(_, acc):
                y = None
                for i in range(L):
                    yi = jnp.matmul(
                        xf.astype(jnp.bfloat16) + acc.astype(jnp.bfloat16),
                        w4[i].astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32,
                    )
                    y = yi if y is None else y + yi
                return y.reshape(-1)[0] * 1e-30
            return jax.lax.fori_loop(0, 1, body, t)

        timeit_xla("xla_int4_raw", raw, i4bytes)

        def scaled(t):
            def body(_, acc):
                y = None
                for i in range(L):
                    wd = (
                        w4[i].astype(jnp.bfloat16).reshape(n_blk_all, 32, d_out)
                        * s_bf[i][:, None, :]
                    ).reshape(d_in, d_out)
                    yi = jnp.matmul(
                        xf.astype(jnp.bfloat16) + acc.astype(jnp.bfloat16),
                        wd, preferred_element_type=jnp.float32,
                    )
                    y = yi if y is None else y + yi
                return y.reshape(-1)[0] * 1e-30
            return jax.lax.fori_loop(0, 1, body, t)

        timeit_xla("xla_int4_scaled", scaled, i4bytes)
    except Exception as e:  # noqa: BLE001
        print(f"xla_int4: unavailable ({type(e).__name__}: {str(e)[:120]})")


def timeit(name, build_call, bytes_per_pass, reps=None):
    reps = reps if reps is not None else _REPS

    @jax.jit
    def loop(seed):
        def body(_, acc):
            t = jnp.full((1, 128), acc, jnp.float32)
            out = build_call(t)
            return out.reshape(-1)[0].astype(jnp.float32) * 1e-30
        return jax.lax.fori_loop(0, reps, body, seed)

    return _report(name, loop, bytes_per_pass, reps)


def timeit_xla(name, fn, bytes_per_pass, reps=None):
    reps = reps if reps is not None else _REPS

    @jax.jit
    def loop(seed):
        def body(_, acc):
            return fn(acc)
        return jax.lax.fori_loop(0, reps, body, seed)

    return _report(name, loop, bytes_per_pass, reps)


def _report(name, loop, bytes_per_pass, reps):
    try:
        np.asarray(loop(jnp.float32(0)))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(loop(jnp.float32(0)))
            best = min(best, time.perf_counter() - t0)
        sec = best / reps
        gbs = bytes_per_pass / sec / 1e9
        print(f"{name:16s} {sec * 1e3:8.3f} ms  {gbs:7.1f} GB/s "
              f"({gbs / HBM_GB_S * 100:5.1f}% HBM)", flush=True)
        return sec
    except Exception as e:  # noqa: BLE001
        print(f"{name:16s} FAILED: {type(e).__name__}: {str(e)[:140]}",
              flush=True)
        return None


if __name__ == "__main__":
    main()
