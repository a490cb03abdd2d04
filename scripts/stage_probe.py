"""Staged Q40 kernel diagnostic: where do the cycles go?

Measures steady-state kernel throughput by streaming a stack of L weight
planes in ONE pallas_call (grid leads with the stack axis), with a small
carry operand threaded through a fori_loop so XLA cannot hoist the call out
of the timing loop (the round-3 kernel lab's read probe had exactly that
bug: a loop-invariant body gets CSE'd and you time one dispatch / reps).

Stages: DMA only, +u8 unpack to i32 lanes, +nibble extract, +float convert,
+scale mul, full matmul (two-dot formulation) — plus the same with the
packed plane pre-bitcast to u32 lanes, and dot-only MXU references with
pre-dequantized bf16/f32 planes.

Run: python scripts/stage_probe.py [d_in] [d_out] [L] [reps]
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

CHUNK = 2048
TILE = 512
M = 8
_REPS = 8  # overridden by argv[4]


# Kernels get (t_ref, ...) and add t_ref[0, 0] to the output: the timing
# loop feeds the previous iteration's result through t, defeating CSE/LICM.


def _k_dma(t_ref, p_ref, o_ref):
    o_ref[...] = (
        p_ref[0:1, :].astype(jnp.int32).astype(jnp.float32) + t_ref[0, 0]
    )


def _k_unpack(t_ref, p_ref, o_ref):
    p = p_ref[...].astype(jnp.int32)
    o_ref[...] = (
        jnp.sum(p, axis=0, keepdims=True).astype(jnp.float32) + t_ref[0, 0]
    )


def _k_nib(t_ref, p_ref, o_ref):
    p = p_ref[...].astype(jnp.int32)
    lo = p & 0x0F
    hi = p >> 4
    o_ref[...] = (
        jnp.sum(lo + hi, axis=0, keepdims=True).astype(jnp.float32)
        + t_ref[0, 0]
    )


def _k_conv(t_ref, p_ref, o_ref, *, dt):
    p = p_ref[...].astype(jnp.int32)
    lo = (p & 0x0F).astype(dt)
    hi = (p >> 4).astype(dt)
    o_ref[...] = (
        jnp.sum((lo + hi).astype(jnp.float32), axis=0, keepdims=True)
        + t_ref[0, 0]
    )


def _k_scale(t_ref, p_ref, s_ref, o_ref):
    half_rows, tile = p_ref.shape
    n_blk = half_rows // 16
    p = p_ref[...].astype(jnp.int32)
    s = _f16_bits_to_f32(s_ref[...])[:, None, :]
    lo = (p & 0x0F).astype(jnp.float32).reshape(n_blk, 16, tile) * s
    hi = (p >> 4).astype(jnp.float32).reshape(n_blk, 16, tile) * s
    o_ref[...] = (
        jnp.sum((lo + hi).reshape(half_rows, tile), axis=0, keepdims=True)
        + t_ref[0, 0]
    )


def _k_full(t_ref, x_lo_ref, x_hi_ref, p_ref, s_ref, o_ref, *, w_dtype):
    half_rows, tile = p_ref.shape
    n_blk = half_rows // 16
    p = p_ref[...].astype(jnp.int32)
    s = _f16_bits_to_f32(s_ref[...])[:, None, :]
    w_lo = ((p & 0x0F).astype(jnp.float32).reshape(n_blk, 16, tile) * s)
    w_hi = ((p >> 4).astype(jnp.float32).reshape(n_blk, 16, tile) * s)
    w_lo = w_lo.reshape(half_rows, tile).astype(w_dtype)
    w_hi = w_hi.reshape(half_rows, tile).astype(w_dtype)
    # cast x DOWN to w_dtype (the product kernel's convention): w_dtype is
    # the dot's compute dtype, so "bf16w" really times a bf16 MXU dot
    o_ref[...] = (
        jnp.dot(x_lo_ref[...].astype(w_dtype), w_lo,
                preferred_element_type=jnp.float32)
        + jnp.dot(x_hi_ref[...].astype(w_dtype), w_hi,
                  preferred_element_type=jnp.float32)
        + t_ref[0, 0]
    )


def _k32_nib(t_ref, p_ref, o_ref):
    w = p_ref[...]
    acc = None
    for sh in range(0, 32, 4):
        nib = (w >> sh) & 0x0F
        acc = nib if acc is None else acc + nib
    o_ref[...] = (
        jnp.sum(acc, axis=0, keepdims=True).astype(jnp.float32) + t_ref[0, 0]
    )


def _k32_conv(t_ref, p_ref, o_ref, *, dt):
    w = p_ref[...]
    acc = None
    for sh in range(0, 32, 4):
        nib = ((w >> sh) & 0x0F).astype(dt)
        acc = nib if acc is None else acc + nib
    o_ref[...] = (
        jnp.sum(acc.astype(jnp.float32), axis=0, keepdims=True) + t_ref[0, 0]
    )


def _k_dot_only(t_ref, x_ref, w_ref, o_ref):
    o_ref[...] = (
        jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
        + t_ref[0, 0]
    )


def timeit(name, build_call, bytes_per_pass, reps=None):
    reps = reps if reps is not None else _REPS
    """build_call(t) -> output array; t is the carry scalar array [1, 128]."""

    @jax.jit
    def loop(seed):
        def body(_, acc):
            t = jnp.full((1, 128), acc, jnp.float32)
            out = build_call(t)
            return out.reshape(-1)[0].astype(jnp.float32) * 1e-30

        return jax.lax.fori_loop(0, reps, body, seed)

    try:
        np.asarray(loop(jnp.float32(0)))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(loop(jnp.float32(0)))
            best = min(best, time.perf_counter() - t0)
        sec = best / reps
        gbs = bytes_per_pass / sec / 1e9
        print(f"{name:22s} {sec * 1e3:8.3f} ms  {gbs:7.1f} GB/s "
              f"({gbs / HBM_GB_S * 100:5.1f}% HBM)", flush=True)
    except Exception as e:
        print(f"{name:22s} FAILED: {type(e).__name__}: {str(e)[:140]}",
              flush=True)


def main():
    d_in = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
    d_out = int(sys.argv[2]) if len(sys.argv) > 2 else 14336
    global _REPS
    L = int(sys.argv[3]) if len(sys.argv) > 3 else 8
    _REPS = int(sys.argv[4]) if len(sys.argv) > 4 else 8

    # Draw the planes ON DEVICE: timing only cares about bytes, so only
    # scalars cross the host->device link.
    half = d_in // 2
    kp, ks = jax.random.split(jax.random.PRNGKey(0))
    packed = jax.random.bits(kp, (L, half, d_out), jnp.uint8)
    scales = (
        jax.random.uniform(ks, (L, d_in // 32, d_out), jnp.float32) * 0.01
        + 0.001
    ).astype(jnp.float16)
    sbits = jax.lax.bitcast_convert_type(scales, jnp.int16)
    jax.block_until_ready((packed, sbits))
    pbytes = packed.size
    print(f"d_in={d_in} d_out={d_out} L={L} packed={pbytes / 1e6:.1f} MB "
          f"device={jax.devices()[0].device_kind}", flush=True)

    grid = (L, d_out // TILE, half // (CHUNK // 2))
    t_spec = pl.BlockSpec((1, 128), lambda l, j, k: (0, 0))
    p_spec = pl.BlockSpec((1, CHUNK // 2, TILE), lambda l, j, k: (l, k, j))
    s_spec = pl.BlockSpec((1, CHUNK // 32, TILE), lambda l, j, k: (l, k, j))
    o_spec = pl.BlockSpec((1, TILE), lambda l, j, k: (0, j))
    o_shape = jax.ShapeDtypeStruct((1, d_out), jnp.float32)
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "parallel", "arbitrary"),
    )

    def staged(kernel, n_in):
        def call(t):
            ops = (packed, sbits)[:n_in]
            return pl.pallas_call(
                _squeeze_lead(kernel, n_in),
                grid=grid,
                in_specs=[t_spec] + [p_spec, s_spec][:n_in],
                out_specs=o_spec,
                out_shape=o_shape,
                compiler_params=params,
            )(t, *ops)

        return call

    def _squeeze_lead(kernel, n_in):
        # blocks arrive [1, r, c] because of the stack axis; drop the lead
        def wrapped(t_ref, *refs):
            ins = [r.at[0] for r in refs[:n_in]]
            kernel(t_ref, *ins, refs[-1])

        return wrapped

    timeit("u8 dma", staged(_k_dma, 1), pbytes)
    timeit("u8 +unpack_i32", staged(_k_unpack, 1), pbytes)
    timeit("u8 +nibbles", staged(_k_nib, 1), pbytes)
    timeit("u8 +convert_f32", staged(partial(_k_conv, dt=jnp.float32), 1), pbytes)
    timeit("u8 +convert_bf16", staged(partial(_k_conv, dt=jnp.bfloat16), 1), pbytes)
    timeit("u8 +scale", staged(_k_scale, 2), pbytes)

    # u32 lanes: group 4 consecutive d_out columns per lane
    p32 = jax.lax.bitcast_convert_type(
        packed.reshape(L, half, d_out // 4, 4), jnp.uint32
    ).astype(jnp.int32)
    grid32 = (L, d_out // 4 // (TILE // 4), half // (CHUNK // 2))
    p32_spec = pl.BlockSpec((1, CHUNK // 2, TILE // 4), lambda l, j, k: (l, k, j))
    o32_spec = pl.BlockSpec((1, TILE // 4), lambda l, j, k: (0, j))
    o32_shape = jax.ShapeDtypeStruct((1, d_out // 4), jnp.float32)

    def staged32(kernel):
        def call(t):
            def wrapped(t_ref, p_ref, o_ref):
                kernel(t_ref, p_ref.at[0], o_ref)

            return pl.pallas_call(
                wrapped, grid=grid32,
                in_specs=[t_spec, p32_spec],
                out_specs=o32_spec, out_shape=o32_shape,
                compiler_params=params,
            )(t, p32)

        return call

    timeit("u32 +nibbles", staged32(_k32_nib), pbytes)
    timeit("u32 +convert_f32", staged32(partial(_k32_conv, dt=jnp.float32)), pbytes)
    timeit("u32 +convert_bf16", staged32(partial(_k32_conv, dt=jnp.bfloat16)), pbytes)

    # MXU stream reference: dot over pre-dequantized planes at same shapes
    kx = jax.random.PRNGKey(1)
    x = jax.random.normal(kx, (M, d_in), jnp.float32)
    for dt, tag in ((jnp.bfloat16, "bf16"), (jnp.float32, "f32")):
        wd = jax.random.normal(
            jax.random.PRNGKey(2), (L, d_in, d_out), jnp.float32
        ).astype(dt)
        jax.block_until_ready(wd)
        x_spec = pl.BlockSpec((M, CHUNK), lambda l, j, k: (0, k))
        w_spec = pl.BlockSpec((1, CHUNK, TILE), lambda l, j, k: (l, k, j))
        od_spec = pl.BlockSpec((M, TILE), lambda l, j, k: (0, j))
        od_shape = jax.ShapeDtypeStruct((M, d_out), jnp.float32)
        xd = x.astype(dt)

        def call(t, w_stack=wd, x_op=xd):
            def wrapped(t_ref, x_ref, w_ref, o_ref):
                _k_dot_only(t_ref, x_ref, w_ref.at[0], o_ref)

            return pl.pallas_call(
                wrapped, grid=grid,
                in_specs=[t_spec, x_spec, w_spec],
                out_specs=od_spec, out_shape=od_shape,
                compiler_params=params,
            )(t, x_op, w_stack)

        timeit(f"dot_only {tag}", call, wd.size * wd.dtype.itemsize)
        del wd

    # full two-dot kernel (current product formulation), f32 and bf16 planes
    xf = jax.random.normal(jax.random.PRNGKey(3), (M, d_in), jnp.float32)
    xb = xf.reshape(M, d_in // 32, 2, 16)
    x_lo = xb[:, :, 0, :].reshape(M, half)
    x_hi = xb[:, :, 1, :].reshape(M, half)
    xs_spec = pl.BlockSpec((M, CHUNK // 2), lambda l, j, k: (0, k))
    of_spec = pl.BlockSpec((M, TILE), lambda l, j, k: (0, j))
    of_shape = jax.ShapeDtypeStruct((M, d_out), jnp.float32)
    for w_dt, x_dt, tag in (
        (jnp.float32, jnp.float32, "f32"),
        (jnp.bfloat16, jnp.float32, "bf16w"),
        (jnp.bfloat16, jnp.bfloat16, "bf16wx"),
    ):
        xl, xh = x_lo.astype(x_dt), x_hi.astype(x_dt)

        def call(t, xl=xl, xh=xh, w_dt=w_dt):
            def wrapped(t_ref, xl_ref, xh_ref, p_ref, s_ref, o_ref):
                _k_full(t_ref, xl_ref, xh_ref, p_ref.at[0], s_ref.at[0],
                        o_ref, w_dtype=w_dt)

            return pl.pallas_call(
                wrapped, grid=grid,
                in_specs=[t_spec, xs_spec, xs_spec, p_spec, s_spec],
                out_specs=of_spec, out_shape=of_shape,
                compiler_params=params,
            )(t, xl, xh, packed, sbits)

        timeit(f"full {tag}", call, pbytes)


if __name__ == "__main__":
    main()
