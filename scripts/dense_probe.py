"""Probe: dense bf16 matvec HBM utilization by shape on the real TPU.

Establishes the XLA roofline for decode matmuls (what the Pallas Q40 kernel
competes against) shape by shape, instead of the model-average number in
BENCH_r02 (which counted the never-streamed embedding table in read bytes).
"""

import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

HBM = 819.0

SHAPES = [
    # trimmed for compile latency
    (1, 4096, 14336),
    (8, 4096, 14336),
    (1, 2048, 128256),
    (1, 2048, 8192),
]


def bench(m, d_in, d_out, reps=30):
    rng = np.random.default_rng(0)
    # two weights ping-ponged so we can chain x -> y -> x
    w1 = jnp.asarray(rng.standard_normal((d_in, d_out), np.float32), jnp.bfloat16)
    w2 = jnp.asarray(rng.standard_normal((d_out, d_in), np.float32), jnp.bfloat16)
    x = jnp.asarray(rng.standard_normal((m, d_in), np.float32), jnp.bfloat16)

    @jax.jit
    def chain(x):
        def body(_, x):
            y = jnp.dot(x, w1, preferred_element_type=jnp.float32)
            x2 = jnp.dot(y.astype(jnp.bfloat16), w2,
                         preferred_element_type=jnp.float32)
            return (x2 * 1e-4).astype(jnp.bfloat16)

        return jax.lax.fori_loop(0, reps, body, x)

    chain(x).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        chain(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    sec = best / reps / 2  # per single matmul
    gbs = d_in * d_out * 2 / sec / 1e9
    print(f"m={m:<4d} {d_in:>6d}x{d_out:<6d}  {sec * 1e6:8.1f} us  "
          f"{gbs:7.1f} GB/s ({gbs / HBM * 100:5.1f}% HBM)")


if __name__ == "__main__":
    print(f"device={jax.devices()[0].device_kind}")
    for m, d_in, d_out in SHAPES:
        bench(m, d_in, d_out)
