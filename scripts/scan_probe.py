"""Probe: matmul bandwidth inside lax.scan over stacked layer weights —
the model's real execution context (llama_forward scans layers). Standalone
matvecs measure ~135 GB/s while the full model implies ~600 GB/s; this
isolates whether cross-layer pipelining is the difference, and how the
Pallas Q40 kernel behaves in that context.
"""

import sys
import time
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, ".")

from distributed_llama_multiusers_tpu.quants.packed import (  # noqa: E402
    PackedQ40,
    pack_q40_host,
)
from distributed_llama_multiusers_tpu.ops.pallas_q40 import q40_matmul_pallas  # noqa: E402
from scripts.kernel_lab import q40_matmul_v1  # noqa: E402

HBM = 819.0


def timeit(fn, *args, reps=3):
    # np.asarray forces completion
    np.asarray(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    m = int(sys.argv[1]) if len(sys.argv) > 1 else 1
    d_in = int(sys.argv[2]) if len(sys.argv) > 2 else 4096
    d_out = int(sys.argv[3]) if len(sys.argv) > 3 else 14336
    L = int(sys.argv[4]) if len(sys.argv) > 4 else 16
    loops = 8

    rng = np.random.default_rng(0)
    print(f"m={m} {d_in}x{d_out} L={L} device={jax.devices()[0].device_kind}",
          flush=True)

    # stacked planes, like LlamaLayerParams
    host_w = rng.standard_normal((L, d_out, d_in), dtype=np.float32) * 0.05
    packed_l, scales_l = [], []
    for l in range(L):
        p, s = pack_q40_host(host_w[l])
        packed_l.append(p)
        scales_l.append(s)
    packed = jnp.asarray(np.stack(packed_l))   # [L, d_in//2, d_out]
    scales = jnp.asarray(np.stack(scales_l))   # [L, d_in//32, d_out]
    dense = jnp.asarray(np.swapaxes(host_w, 1, 2), jnp.bfloat16)  # [L, d_in, d_out]
    x = jnp.asarray(rng.standard_normal((m, d_in), np.float32))

    pbytes = packed.size + scales.size * 2
    dbytes = dense.size * 2

    @jax.jit
    def scan_dense(x, dense):
        def outer(_, x):
            def step(x, w):
                y = jnp.dot(x.astype(jnp.bfloat16), w,
                            preferred_element_type=jnp.float32)
                return (y[..., :d_in] * 1e-2).astype(x.dtype), None

            x, _ = jax.lax.scan(step, x, dense)
            return x

        return jax.lax.fori_loop(0, loops, outer, x)

    @partial(jax.jit, static_argnames=("which",))
    def scan_q40(x, packed, scales, which="v0"):
        def outer(_, x):
            def step(x, ws):
                p, s = ws
                if which == "v0":
                    y = q40_matmul_pallas(x, PackedQ40(p, s))
                else:
                    y = q40_matmul_v1(x, p, s, w_dtype=jnp.bfloat16,
                                      x_dtype=jnp.bfloat16)
                return (y[..., :d_in] * 1e-2).astype(x.dtype), None

            x, _ = jax.lax.scan(step, x, (packed, scales))
            return x

        return jax.lax.fori_loop(0, loops, outer, x)

    sec = timeit(scan_dense, x, dense) / loops / L
    gbs = dbytes / L / sec / 1e9
    print(f"{'dense_scan':16s} {sec * 1e6:8.1f} us/mm  {gbs:7.1f} GB/s "
          f"({gbs / HBM * 100:5.1f}% HBM)", flush=True)

    for which in ("v0", "v1"):
        try:
            sec = timeit(lambda a, b, c: scan_q40(a, b, c, which=which),
                         x, packed, scales) / loops / L
            gbs = pbytes / L / sec / 1e9
            print(f"{'q40_scan_' + which:16s} {sec * 1e6:8.1f} us/mm  {gbs:7.1f} GB/s "
                  f"({gbs / HBM * 100:5.1f}% HBM)", flush=True)
        except Exception as e:
            print(f"q40_scan_{which} FAILED: {type(e).__name__}: {str(e)[:150]}",
                  flush=True)


if __name__ == "__main__":
    main()
