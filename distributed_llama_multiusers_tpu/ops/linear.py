"""Matmul dispatch: dense arrays or PackedQ40 weights, Pallas or XLA path.

The reference routes every matmul through a per-(op, quant-signature) kernel
registry (getCpuOpForward, src/nn/nn-cpu-ops.cpp:1315-1361); here the same
seam is a single function — ``matmul(x, w)`` — that picks the dequant-in-VMEM
Pallas kernel for quantized weights on TPU and a fused XLA fallback
elsewhere (CPU tests, interpret mode).
"""

from __future__ import annotations

import os
from functools import lru_cache

import jax
import jax.numpy as jnp

from ..quants.packed import PackedQ40, Q40Layer, q40_matmul_xla
from ..telemetry.names import SCOPE_HEAD

# The kernel carries its own GSPMD partitioning rule
# (ops/pallas_q40.q40_matmul_partitioned), so it stays on under meshes:
# row-sliced shards run it locally, col-sliced shards psum the partials.
_pallas_enabled = True

# Test hook: route PackedQ40 matmuls through the partitioned Pallas path in
# interpret mode even off-TPU, so CPU meshes exercise kernel + partitioning.
_pallas_interpret = False

# Compute dtype of the Pallas Q40 dot (dequantized weight planes AND the
# x operand). None -> kernel default: bf16 on TPU (single-pass MXU, the
# reference's Q80-activation precision class), exact f32 under interpret/
# CPU tests. Explicit jnp.float32 restores ~f32-accurate multi-pass MXU
# dots on TPU.
_pallas_w_dtype = None


def set_pallas_enabled(enabled: bool) -> None:
    global _pallas_enabled
    _pallas_enabled = enabled


def set_pallas_interpret(enabled: bool) -> None:
    global _pallas_interpret
    _pallas_interpret = enabled


def set_pallas_w_dtype(dtype) -> None:
    """dtype of dequantized weight tiles in VMEM (None -> exact f32)."""
    global _pallas_w_dtype
    _pallas_w_dtype = dtype


def pallas_w_dtype_kw() -> dict:
    """The ``w_dtype`` keyword a Pallas Q40 call takes: empty for the
    kernel's own default."""
    return {} if _pallas_w_dtype is None else {"w_dtype": _pallas_w_dtype}


@lru_cache(maxsize=1)
def _pallas_q40_matmul():
    """The Pallas kernel entry on a TPU; None on any other platform and
    under DLLAMA_NO_PALLAS=1, the one explicit switch. A backend that does
    not answer or a kernel module that does not import raises here: the XLA
    dequant path must never stand in for the kernel unannounced."""
    if os.environ.get("DLLAMA_NO_PALLAS") == "1":
        return None
    if jax.devices()[0].platform != "tpu":
        return None
    from .pallas_q40 import q40_matmul_pallas

    return q40_matmul_pallas


def pallas_kernel_active() -> bool:
    """Whether PackedQ40 matmuls currently route to the Pallas kernel."""
    return _pallas_enabled and (_pallas_interpret or _pallas_q40_matmul() is not None)


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode (the tests' hook)."""
    return _pallas_interpret


def reads_q40_stack(w) -> bool:
    """Whether ``matmul`` multiplies by one layer of ``w`` without the plane
    being sliced out first: a PackedQ40 whose planes carry exactly one
    leading axis, at widths the Pallas kernel tiles, with the kernel on. A
    layer scan on one device then closes over such a stack and hands
    ``matmul`` a ``Q40Layer``; every other leaf it scans as ever."""
    if not (isinstance(w, PackedQ40) and pallas_kernel_active()):
        return False
    from .pallas_q40 import pallas_supports_stack

    return pallas_supports_stack(w)


def matmul(x: jnp.ndarray, w) -> jnp.ndarray:
    """y = x @ w for dense [.., d_in, d_out] arrays or PackedQ40 weights.
    What ``w`` is decides the route, never who calls:

    - a ``Q40Layer`` (a stack and a layer index, which a layer scan builds
      where ``reads_q40_stack`` holds): the kernel, which reads that layer's
      tiles out of the stack. Nearly every call of a step on one device;
    - a 2-D ``PackedQ40`` with the kernel on (the head's ``wcls``, a plane a
      scan sliced out): ``q40_matmul_partitioned``, whose one-device body is
      the kernel where it tiles the plane and the XLA dequant where not;
    - any other ``PackedQ40``: the XLA dequant."""
    if isinstance(w, Q40Layer):
        from .pallas_q40 import q40_matmul_pallas

        return q40_matmul_pallas(
            x, w.stack, interpret=_pallas_interpret, layer=w.layer,
            **pallas_w_dtype_kw()
        )
    if isinstance(w, PackedQ40):
        if w.packed.ndim == 2 and pallas_kernel_active():
            from .pallas_q40 import q40_matmul_partitioned

            return q40_matmul_partitioned(
                x, w, interpret=_pallas_interpret, **pallas_w_dtype_kw()
            )
        return q40_matmul_xla(x, w)
    return x @ w


def head(x: jnp.ndarray, norm, wcls, vocab_size: int, *, head_row=None,
         logit_divisor: float = 1.0, qdq=lambda y: y, project=matmul) -> jnp.ndarray:
    """Every block's head, under ``dl.head``: the final norm (``norm``, the
    block's own, of ``x`` alone), ``logit_divisor`` where the family has one,
    ``wcls`` through ``project`` (``matmul``, or a mesh's sliced matmul),
    float32, the vocabulary's columns (``wcls`` may be padded past
    ``vocab_size`` for the slab kernel's wide tiles:
    quants/packed.pad_packed_d_out). x: ``[B, T, dim]``; returns
    ``[B, T, vocab]`` float32.

    ``head_row`` (``[B]`` int32; None: every row) is the one row a lane its
    caller keeps: ``x`` is cut to ``[B, 1, dim]`` BEFORE the norm, and the
    result is ``[B, 1, vocab]``, the row a whole head would hold there. A
    prefill chunk keeps the row of its last real token (the engine's
    ``_prefill_half``) and pays ``wcls`` for that row, not for its bucket."""
    with jax.named_scope(SCOPE_HEAD):
        if head_row is not None:
            x = jax.vmap(
                lambda lane, row: jax.lax.dynamic_slice_in_dim(lane, row, 1, axis=0)
            )(x, head_row)
        y = norm(x)
        if logit_divisor != 1.0:
            y = (y.astype(jnp.float32) / logit_divisor).astype(y.dtype)
        logits = project(qdq(y), wcls).astype(jnp.float32)
        return logits[..., :vocab_size]


def q40_matmul_local(x: jnp.ndarray, w: PackedQ40) -> jnp.ndarray:
    """y = x @ dequant(w) on ALREADY-LOCAL shard shapes — for use inside
    shard_map regions, where operands are per-device and the GSPMD
    custom_partitioning wrapper must not re-partition. Pallas when the local
    shapes fit, fused XLA dequant otherwise."""
    if w.packed.ndim == 2 and pallas_kernel_active():
        from .pallas_q40 import pallas_supports, q40_matmul_pallas

        # pallas_supports gates BOTH modes: interpret runs must not reach
        # the kernel with shapes the tiling planner rejects
        if pallas_supports(w):
            return q40_matmul_pallas(
                x, w, interpret=_pallas_interpret, **pallas_w_dtype_kw()
            )
    return q40_matmul_xla(x, w)
