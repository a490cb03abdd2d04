"""Kernel lab: measure Q40 matmul variants on the real TPU.

Times a chain of L layer-like PackedQ40 matmuls (decode shape: m small) and
reports effective weight-read GB/s per variant, vs the v5e HBM roofline
(819 GB/s). Used to drive the round-3 kernel optimization (VERDICT Weak #1:
current kernel at 43.8% HBM while XLA dense-bf16 runs at ~92%).

Run: python scripts/kernel_lab.py [m] [d_in] [d_out] [L]
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

from distributed_llama_multiusers_tpu.quants.packed import (  # noqa: E402
    PackedQ40,
    pack_q40_host,
    q40_matmul_xla,
)
from distributed_llama_multiusers_tpu.ops.pallas_q40 import (  # noqa: E402
    _f16_bits_to_f32,
    q40_matmul_pallas,
)

HBM_GB_S = 819.0  # v5e


# ---------------------------------------------------------------------------
# v1: two-dot nibble kernel. No concat, no per-weight subtract: the -8 offset
# is folded into a per-block correction dot; x arrives pre-split into the
# lo/hi column groups so the kernel does no x shuffling at all.
# ---------------------------------------------------------------------------


def _v1_kernel(x_lo_ref, x_hi_ref, bsum_t_ref, packed_ref, scales_ref, out_ref,
               acc_ref, *, out_dtype_w):
    k = pl.program_id(2)
    half_rows, tile = packed_ref.shape
    n_blk = half_rows // 16

    p = packed_ref[...].astype(jnp.int32)
    s = _f16_bits_to_f32(scales_ref[...])  # [n_blk, tile] f32
    s3 = s[:, None, :]
    w_lo = ((p & 0x0F).astype(jnp.float32).reshape(n_blk, 16, tile) * s3)
    w_hi = ((p >> 4).astype(jnp.float32).reshape(n_blk, 16, tile) * s3)
    w_lo = w_lo.reshape(half_rows, tile).astype(out_dtype_w)
    w_hi = w_hi.reshape(half_rows, tile).astype(out_dtype_w)

    # correction for the folded -8 offset: 8 * bsum_b @ s  ([m, tile])
    corr = jax.lax.dot_general(
        bsum_t_ref[...], s, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    partial_sum = (
        jnp.dot(x_lo_ref[...], w_lo, preferred_element_type=jnp.float32)
        + jnp.dot(x_hi_ref[...], w_hi, preferred_element_type=jnp.float32)
        - 8.0 * corr
    )

    @pl.when(k == 0)
    def _():
        acc_ref[...] = partial_sum

    @pl.when(k > 0)
    def _():
        acc_ref[...] = acc_ref[...] + partial_sum

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _pick_chunk(d_in, cap):
    if d_in % 32 != 0:
        return None
    best = 32
    for c in range(64, min(d_in, cap) + 1, 32):
        if d_in % c == 0:
            best = c
    return best


def _pick_tile(n, cap):
    for c in range(cap, 127, -128):
        if n % c == 0:
            return c
    return n


@partial(jax.jit, static_argnames=("din_chunk", "dout_tile", "w_dtype", "x_dtype"))
def q40_matmul_v1(x, packed, scales, din_chunk=2048, dout_tile=512,
                  w_dtype=jnp.float32, x_dtype=jnp.float32):
    w = PackedQ40(packed=packed, scales=scales)
    d_in, d_out = w.d_in, w.d_out
    chunk = _pick_chunk(d_in, din_chunk)
    lead = x.shape[:-1]
    m = int(np.prod(lead)) if lead else 1

    xf = x.reshape(m, d_in).astype(jnp.float32)
    m_pad = max(8, ((m + 7) // 8) * 8)
    m_tile = min(256, m_pad)
    if m_pad != m:
        xf = jnp.pad(xf, ((0, m_pad - m), (0, 0)))

    n_blk_total = d_in // 32
    xb = xf.reshape(m_pad, n_blk_total, 2, 16)
    x_lo = xb[:, :, 0, :].reshape(m_pad, d_in // 2).astype(x_dtype)
    x_hi = xb[:, :, 1, :].reshape(m_pad, d_in // 2).astype(x_dtype)
    # transposed [n_blk, m] so the lane dim is m_pad (full) — Pallas lane-dim
    # blocking requires multiples of 128 or the full extent
    bsum_t = xf.reshape(m_pad, n_blk_total, 32).sum(axis=2).T

    tile = _pick_tile(d_out, dout_tile)
    grid = (m_pad // m_tile, d_out // tile, d_in // chunk)
    scale_bits = jax.lax.bitcast_convert_type(scales, jnp.int16)

    out = pl.pallas_call(
        partial(_v1_kernel, out_dtype_w=w_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m_tile, chunk // 2), lambda i, j, k: (i, k)),
            pl.BlockSpec((m_tile, chunk // 2), lambda i, j, k: (i, k)),
            pl.BlockSpec((chunk // 32, m_tile), lambda i, j, k: (k, i)),
            pl.BlockSpec((chunk // 2, tile), lambda i, j, k: (k, j)),
            pl.BlockSpec((chunk // 32, tile), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((m_tile, tile), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, d_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((m_tile, tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m_pad * d_in * d_out,
            bytes_accessed=d_in * d_out // 2 + (d_in // 32) * d_out * 2
            + m_pad * d_in * 4 + m_pad * d_out * x.dtype.itemsize,
            transcendentals=0,
        ),
    )(x_lo, x_hi, bsum_t, packed, scale_bits)
    return out[:m].reshape(*lead, d_out)


# ---------------------------------------------------------------------------
# v2: v1 math + PRE-TILED weight planes. packed [J, d_in//2, T] u8 and
# scales [J, d_in//32, T] i16 with J = d_out // T: each grid step's weight
# block is one fully contiguous slab in HBM (the [d_in//2, d_out] layout
# gives the DMA 512-byte rows strided by d_out).
# ---------------------------------------------------------------------------

V2_TILE = 512


def retile(packed, scales, tile=V2_TILE):
    d_out = packed.shape[-1]
    j = d_out // tile
    pt = jnp.moveaxis(packed.reshape(packed.shape[0], j, tile), 1, 0)
    st = jnp.moveaxis(scales.reshape(scales.shape[0], j, tile), 1, 0)
    sbits = jax.lax.bitcast_convert_type(st, jnp.int16)
    return jnp.copy(pt), jnp.copy(sbits)


def _v2_kernel(x_lo_ref, x_hi_ref, bsum_t_ref, packed_ref, scales_ref, out_ref,
               acc_ref, *, out_dtype_w):
    k = pl.program_id(2)
    _, half_rows, tile = packed_ref.shape
    n_blk = half_rows // 16

    p = packed_ref[0].astype(jnp.int32)
    s = _f16_bits_to_f32(scales_ref[0])
    s3 = s[:, None, :]
    w_lo = ((p & 0x0F).astype(out_dtype_w).reshape(n_blk, 16, tile)
            * s3.astype(out_dtype_w)).reshape(half_rows, tile)
    w_hi = (((p >> 4).astype(out_dtype_w)).reshape(n_blk, 16, tile)
            * s3.astype(out_dtype_w)).reshape(half_rows, tile)

    corr = jax.lax.dot_general(
        bsum_t_ref[...], s, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    partial_sum = (
        jnp.dot(x_lo_ref[...], w_lo, preferred_element_type=jnp.float32)
        + jnp.dot(x_hi_ref[...], w_hi, preferred_element_type=jnp.float32)
        - 8.0 * corr
    )

    @pl.when(k == 0)
    def _():
        acc_ref[...] = partial_sum

    @pl.when(k > 0)
    def _():
        acc_ref[...] = acc_ref[...] + partial_sum

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@partial(jax.jit, static_argnames=("din_chunk", "w_dtype", "x_dtype"))
def q40_matmul_v2(x, packed_t, scales_t, din_chunk=2048,
                  w_dtype=jnp.float32, x_dtype=jnp.float32):
    """x: [..., d_in]; packed_t [J, d_in//2, T] u8; scales_t [J, d_in//32, T]
    int16 (f16 bits)."""
    j, half, tile = packed_t.shape
    d_in, d_out = half * 2, j * tile
    chunk = _pick_chunk(d_in, din_chunk)
    lead = x.shape[:-1]
    m = int(np.prod(lead)) if lead else 1

    xf = x.reshape(m, d_in).astype(jnp.float32)
    m_pad = max(8, ((m + 7) // 8) * 8)
    m_tile = min(256, m_pad)
    if m_pad != m:
        xf = jnp.pad(xf, ((0, m_pad - m), (0, 0)))

    n_blk_total = d_in // 32
    xb = xf.reshape(m_pad, n_blk_total, 2, 16)
    x_lo = xb[:, :, 0, :].reshape(m_pad, d_in // 2).astype(x_dtype)
    x_hi = xb[:, :, 1, :].reshape(m_pad, d_in // 2).astype(x_dtype)
    bsum_t = xf.reshape(m_pad, n_blk_total, 32).sum(axis=2).T

    grid = (m_pad // m_tile, j, d_in // chunk)

    out = pl.pallas_call(
        partial(_v2_kernel, out_dtype_w=w_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m_tile, chunk // 2), lambda i, j, k: (i, k)),
            pl.BlockSpec((m_tile, chunk // 2), lambda i, j, k: (i, k)),
            pl.BlockSpec((chunk // 32, m_tile), lambda i, j, k: (k, i)),
            pl.BlockSpec((1, chunk // 2, tile), lambda i, j, k: (j, k, 0)),
            pl.BlockSpec((1, chunk // 32, tile), lambda i, j, k: (j, k, 0)),
        ],
        out_specs=pl.BlockSpec((m_tile, tile), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, d_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((m_tile, tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m_pad * d_in * d_out,
            bytes_accessed=d_in * d_out // 2 + (d_in // 32) * d_out * 2
            + m_pad * d_in * 4 + m_pad * d_out * x.dtype.itemsize,
            transcendentals=0,
        ),
    )(x_lo, x_hi, bsum_t, packed_t, scales_t)
    return out[:m].reshape(*lead, d_out)


# ---------------------------------------------------------------------------
# read-only roofline probe: how fast can Pallas merely stream the packed
# bytes through VMEM with ~1 op/byte? Upper bound for any dequant kernel.
# ---------------------------------------------------------------------------


def _probe_kernel(packed_ref, out_ref, acc_ref):
    k = pl.program_id(1)
    p = packed_ref[...].astype(jnp.int32)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.sum(p, axis=0, keepdims=True).astype(jnp.float32)

    @pl.when(k > 0)
    def _():
        acc_ref[...] = acc_ref[...] + jnp.sum(p, axis=0, keepdims=True)

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc_ref[...]


@partial(jax.jit, static_argnames=("chunk", "tile"))
def read_probe(packed, chunk=2048, tile=512):
    rows, d_out = packed.shape
    grid = (d_out // tile, rows // (chunk // 2))
    return pl.pallas_call(
        _probe_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((chunk // 2, tile), lambda j, k: (k, j))],
        out_specs=pl.BlockSpec((1, tile), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, d_out), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(packed)


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------


def bench_chain(fn, x, weights, repeats=20, prep=None):
    """Time fn(x, w) chained over all weights, repeated on-device via
    fori_loop (one dispatch: per-call host overhead stays out of the time).
    Returns seconds per single pass over all weights."""

    @jax.jit
    def chain(x, ws):
        def body(_, x):
            for packed, scales in ws:
                y = fn(x, packed, scales)
                x = y[..., : x.shape[-1]].astype(x.dtype)
            return x

        return jax.lax.fori_loop(0, repeats, body, x)

    if prep is not None:
        weights = [PackedQ40(*prep(w.packed, w.scales)) for w in weights]

    ws = [(w.packed, w.scales) for w in weights]
    # np.asarray forces completion
    np.asarray(chain(x, ws))  # compile + warmup
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(chain(x, ws))
        best = min(best, time.perf_counter() - t0)
    return best / repeats


def main():
    m = int(sys.argv[1]) if len(sys.argv) > 1 else 1
    d_in = int(sys.argv[2]) if len(sys.argv) > 2 else 4096
    d_out = int(sys.argv[3]) if len(sys.argv) > 3 else 14336
    L = int(sys.argv[4]) if len(sys.argv) > 4 else 8

    rng = np.random.default_rng(0)
    weights = []
    for _ in range(L):
        w = (rng.standard_normal((d_out, d_in), dtype=np.float32) * 0.05)
        packed, scales = pack_q40_host(w)
        weights.append(
            PackedQ40(packed=jnp.asarray(packed), scales=jnp.asarray(scales))
        )
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))

    wbytes = L * (d_in * d_out // 2 + (d_in // 32) * d_out * 2)
    print(f"m={m} d_in={d_in} d_out={d_out} L={L} "
          f"weights={wbytes / 1e9:.3f} GB  device={jax.devices()[0].device_kind}")

    # correctness spot check
    ref = q40_matmul_xla(x, weights[0])
    pt, st = retile(weights[0].packed, weights[0].scales)
    for name, f in [
        ("v0", lambda: q40_matmul_pallas(x, weights[0])),
        ("v1", lambda: q40_matmul_v1(x, weights[0].packed, weights[0].scales)),
        ("v1_bf16", lambda: q40_matmul_v1(
            x, weights[0].packed, weights[0].scales,
            w_dtype=jnp.bfloat16, x_dtype=jnp.bfloat16)),
        ("v2", lambda: q40_matmul_v2(x, pt, st)),
    ]:
        err = float(jnp.max(jnp.abs(ref - f())) / (jnp.max(jnp.abs(ref)) + 1e-9))
        print(f"{name} rel err vs xla: {err:.2e}", flush=True)

    variants = {
        "v0_current": lambda x, p, s: q40_matmul_pallas(x, PackedQ40(p, s)),
        "v1_f32": lambda x, p, s: q40_matmul_v1(x, p, s),
        "v1_bf16w": lambda x, p, s: q40_matmul_v1(x, p, s, w_dtype=jnp.bfloat16),
        "v1_bf16wx": lambda x, p, s: q40_matmul_v1(
            x, p, s, w_dtype=jnp.bfloat16, x_dtype=jnp.bfloat16
        ),
        "v1_bf16_c4096_t512": lambda x, p, s: q40_matmul_v1(
            x, p, s, din_chunk=4096, dout_tile=512,
            w_dtype=jnp.bfloat16, x_dtype=jnp.bfloat16
        ),
        "v1_bf16_c2048_t1024": lambda x, p, s: q40_matmul_v1(
            x, p, s, din_chunk=2048, dout_tile=1024,
            w_dtype=jnp.bfloat16, x_dtype=jnp.bfloat16
        ),
        "v1_bf16_c1024_t1024": lambda x, p, s: q40_matmul_v1(
            x, p, s, din_chunk=1024, dout_tile=1024,
            w_dtype=jnp.bfloat16, x_dtype=jnp.bfloat16
        ),
        "v1_bf16_c1024_t2048": lambda x, p, s: q40_matmul_v1(
            x, p, s, din_chunk=1024, dout_tile=2048,
            w_dtype=jnp.bfloat16, x_dtype=jnp.bfloat16
        ),
        "v2_tiled_f32": (
            lambda x, p, s: q40_matmul_v2(x, p, s),
            retile,
        ),
        "v2_tiled_bf16": (
            lambda x, p, s: q40_matmul_v2(
                x, p, s, w_dtype=jnp.bfloat16, x_dtype=jnp.bfloat16),
            retile,
        ),
        "v2_tiled_bf16_c4096": (
            lambda x, p, s: q40_matmul_v2(
                x, p, s, din_chunk=4096,
                w_dtype=jnp.bfloat16, x_dtype=jnp.bfloat16),
            retile,
        ),
    }

    for name, fn in variants.items():
        prep = None
        if isinstance(fn, tuple):
            fn, prep = fn
        try:
            sec = bench_chain(fn, x, weights, prep=prep)
            gbs = wbytes / sec / 1e9
            print(f"{name:24s} {sec * 1e3:8.3f} ms  {gbs:7.1f} GB/s "
                  f"({gbs / HBM_GB_S * 100:5.1f}% HBM)")
        except Exception as e:
            print(f"{name:24s} FAILED: {type(e).__name__}: {str(e)[:120]}")

    # harness validation: dense bf16 chain (BENCH_r02 showed ~92% HBM for
    # the dense path inside the full model; if this shows garbage the harness
    # is broken, not the kernel)
    dense = [jnp.asarray(
        rng.standard_normal((d_in, d_out), dtype=np.float32), jnp.bfloat16)
        for _ in range(L)]
    dbytes = L * d_in * d_out * 2

    @jax.jit
    def dense_chain(x, ws):
        def body(_, x):
            for w in ws:
                y = jnp.dot(x.astype(jnp.bfloat16), w,
                            preferred_element_type=jnp.float32)
                x = y[..., : x.shape[-1]]
            return x

        return jax.lax.fori_loop(0, 20, body, x)

    dense_chain(x, dense).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        dense_chain(x, dense).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    sec = best / 20
    gbs = dbytes / sec / 1e9
    print(f"{'dense_bf16_xla':24s} {sec * 1e3:8.3f} ms  {gbs:7.1f} GB/s "
          f"({gbs / HBM_GB_S * 100:5.1f}% HBM)")

    # pure read probe
    try:
        pk = weights[0].packed
        reps = 50

        @jax.jit
        def probe_loop(pk):
            def body(_, acc):
                return acc + read_probe(pk)[0, 0]

            return jax.lax.fori_loop(0, reps, body, jnp.float32(0))

        probe_loop(pk).block_until_ready()
        t0 = time.perf_counter()
        probe_loop(pk).block_until_ready()
        sec = (time.perf_counter() - t0) / reps
        gbs = pk.size / sec / 1e9
        print(f"{'read_probe':24s} {sec * 1e3:8.3f} ms  {gbs:7.1f} GB/s "
              f"({gbs / HBM_GB_S * 100:5.1f}% HBM)")
    except Exception as e:
        print(f"read_probe FAILED: {type(e).__name__}: {str(e)[:160]}")


if __name__ == "__main__":
    main()
