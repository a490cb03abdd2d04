"""Linear attention with a decay a head (Lightning Attention), one entry for
every step family.

A head ``i`` of a lane keeps a matrix ``S`` ``[d, d]``, float32 whatever the
activations are:

    S_t = lambda_i * S_{t-1} + k_t^T v_t
    o_t = scale * q_t S_t

with ``lambda_i = exp(-slope_i)`` a constant of the head (``decay_slopes``).
The stack is ``[layers, lanes, heads * d * d]``, flat in its last axis as
models/hybrid.py's header asks of a cache leaf: row ``j`` of head ``i``'s
matrix is columns ``[(i * d + j) * d, (i * d + j + 1) * d)`` of a lane's row,
whole 128-lane tiles at the published ``d`` = 128.

The rule for the matrix (``ops/ssm_scan.py``'s running sum, in this
recurrence's terms): a row that is not real takes ``lambda = 1`` and ``k = 0``,
so ``S`` passes through it unchanged. The caller says which rows are real
(the first ``n_valid`` of a lane): a bucket's padded tail and a parked lane
leave the state as they found it, a step of ``T`` rows of which ``a`` are real
leaves the state AFTER ROW ``a - 1``, and a second chunk continues the first
exactly. A step whose first position is 0 reads zeros whatever the lane held
(``from_zero``): nothing is cleared when a lane is given to a new request.

At one row a lane (``T = 1``: every decode step and decode half) the update is
elementwise over the lane's matrices: the layer is read once and written
once, in place in the carry. On a TPU that is one Pallas kernel
(``_step_kernel``) over the flat stack as it sits, eight lanes and
``HEAD_BLOCK`` heads a grid step, the layer's index a prefetched scalar and
the stack aliased to its output; a matrix row is one ``(8, 128)`` register
across the eight lanes. Left to XLA the flat row and the ``[d, d]`` form the
products want are two tiled layouts and every layer's state is copied between
them (what ops/ssm_scan.py found for its state, PR 43).

At ``T > 1`` the chunk form (``_chunk_form``): rows in chunks of ``CHUNK``;
inside a chunk the products ``(q k^T * decay) v`` of an attention without
softmax, across chunks the carried matrix, decayed: ``q_t S`` and ``k^T v`` are
matmuls of ``[CHUNK, d] x [d, d]`` a head, a ``lax.scan`` over the chunks with
the matrix as its carry. Every exponent is a sum of ``-slope`` over real rows,
never positive, so nothing overflows whatever the slope. No tensor has both a
time axis and the ``[d, d]`` axes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.names import SCOPE_LINEAR_STATE
from .linear import pallas_interpret, pallas_kernel_active

# rows of a chunk of the chunk form: the intra-chunk scores are [CHUNK, CHUNK]
CHUNK = 128
LANE_BLOCK = 8  # lanes a grid step of the one-row kernel advances: a sublane tile
HEAD_BLOCK = 2  # heads a grid step advances: 1 MB of state in, 1 MB out at d = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def decay_slopes(n_heads: int) -> np.ndarray:
    """``slope_i = 2 ** (-8 (i + 1) / n_heads)``, float32 ``[n_heads]``:
    Lightning Attention's ALiBi-like slopes; head ``i`` decays by
    ``exp(-slope_i)`` a row."""
    i = np.arange(1, n_heads + 1, dtype=np.float64)
    return (2.0 ** (-8.0 * i / n_heads)).astype(np.float32)


def scan_rows(state, q, k, v, real, slopes, scale: float):
    """The recurrence a row at a time by ``lax.scan``, float32: what the
    chunk form and the kernel are held to. state ``[B, H, d, d]``; q, k, v
    ``[B, T, H, d]``; real ``[B, T]``; returns ``(o [B, T, H, d], state)``."""
    f32 = jnp.float32

    def step(s, row):
        qt, kt, vt, rt = row  # [B, H, d] x 3, [B]
        lam = jnp.where(rt[:, None], jnp.exp(-slopes)[None, :], 1.0)  # [B, H]
        kt = jnp.where(rt[:, None, None], kt, 0.0)
        s = lam[:, :, None, None] * s + kt[..., :, None] * vt[..., None, :]
        return s, scale * jnp.einsum("bhd,bhde->bhe", qt, s, precision=_HIGHEST)

    rows = (*(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v)), jnp.moveaxis(real, 1, 0))
    state, o = jax.lax.scan(step, state.astype(f32), rows)
    return jnp.moveaxis(o, 0, 1), state


def _chunk_form(state, q, k, v, real, slopes, scale: float, chunk: int = CHUNK):
    """``scan_rows`` in chunks (module header). The intra-chunk products take
    the operands as they come (bf16 on a TPU, accumulated in float32), what
    touches the carried matrix is float32 at the highest precision."""
    b, t, h, d = q.shape
    c = min(chunk, t)
    pad = -t % c
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v))
        real = jnp.pad(real, ((0, 0), (0, pad)))
    nc = (t + pad) // c
    k = jnp.where(real[:, :, None, None], k, jnp.zeros_like(k))
    # G_t: minus the slope times the real rows of the chunk up to and with t
    steps = real.reshape(b, nc, c).astype(jnp.float32)
    g = -jnp.cumsum(steps, axis=2)[..., None] * slopes  # [B, nc, C, H]
    causal = jnp.tril(jnp.ones((c, c), bool))

    def one_chunk(s, xs):
        qc, kc, vc, gc = xs  # [B, C, H, d] x 3, [B, C, H]
        gh = jnp.moveaxis(gc, 2, 1)  # [B, H, C]
        a = jnp.einsum("bthd,bshd->bhts", qc, kc, preferred_element_type=jnp.float32)
        w = jnp.exp(jnp.where(causal, gh[:, :, :, None] - gh[:, :, None, :], -jnp.inf))
        o = jnp.einsum("bhts,bshe->bthe", (a * w).astype(vc.dtype), vc,
                       preferred_element_type=jnp.float32)
        o = o + jnp.exp(gc)[..., None] * jnp.einsum(
            "bthd,bhde->bthe", qc.astype(jnp.float32), s, precision=_HIGHEST)
        g_end = gh[:, :, -1]  # [B, H]
        kw = kc.astype(jnp.float32) * jnp.exp(g_end[:, None, :] - gc)[..., None]
        s = jnp.exp(g_end)[:, :, None, None] * s + jnp.einsum(
            "bshd,bshe->bhde", kw, vc.astype(jnp.float32), precision=_HIGHEST)
        return s, o

    by_chunk = lambda x: jnp.moveaxis(x.reshape(b, nc, c, *x.shape[2:]), 1, 0)
    state, o = jax.lax.scan(
        one_chunk, state, (by_chunk(q), by_chunk(k), by_chunk(v), jnp.moveaxis(g, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, nc * c, h, d)[:, :t]
    return scale * o, state


def _step_kernel(li_ref, s_ref, q_ref, k_ref, v_ref, dec_ref, zero_ref, s_out_ref, o_ref,
                 *, heads: int, d: int, scale: float):
    """One row for ``LANE_BLOCK`` lanes and ``heads`` heads of layer ``li`` of
    the flat stack. ``dec_ref`` ``[lanes, heads * d]``: the head's decay
    (1 for a lane whose row is not real) across the head's columns;
    ``zero_ref`` ``[lanes, 1]``: nonzero where the lane starts a sequence."""
    del li_ref  # the block's layer is chosen by the index maps
    fresh = zero_ref[...] != 0.0  # [LANE_BLOCK, 1]
    for h in range(heads):
        cols = slice(h * d, (h + 1) * d)
        v, dec = v_ref[:, cols], dec_ref[:, cols]  # [LANE_BLOCK, d]
        o = jnp.zeros_like(v)
        for j in range(d):
            at = slice((h * d + j) * d, (h * d + j + 1) * d)
            s = jnp.where(fresh, 0.0, s_ref[0, :, at])
            s = dec * s + k_ref[:, h * d + j:h * d + j + 1] * v
            s_out_ref[0, :, at] = s
            o = o + q_ref[:, h * d + j:h * d + j + 1] * s
        o_ref[:, cols] = o * scale


def step_kernel_supports(lanes: int, n_heads: int, d: int) -> bool:
    """Whether the one-row kernel tiles these sizes: whole sublane tiles of
    lanes, whole lane tiles of a matrix row, whole blocks of heads."""
    return lanes % LANE_BLOCK == 0 and d % 128 == 0 and n_heads % HEAD_BLOCK == 0


def _step_pallas(s_all, li, from_zero, q, k, v, decay, scale: float, interpret: bool):
    """Layer ``li`` of ``s_all`` ``[layers, lanes, H * d * d]`` advanced by one
    row a lane, in place. q, k, v ``[lanes, H, d]`` float32, decay ``[lanes,
    H]``; returns ``(o [lanes, H, d], the stack)``."""
    lanes, n_heads, d = q.shape
    hb = HEAD_BLOCK
    row = pl.BlockSpec((LANE_BLOCK, hb * d), lambda i, j, li: (i, j))
    state = pl.BlockSpec((1, LANE_BLOCK, hb * d * d), lambda i, j, li: (li[0], i, j))
    flat = lambda x: x.reshape(lanes, n_heads * d)
    s_all, o = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb, d=d, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(lanes // LANE_BLOCK, n_heads // hb),
            in_specs=[state, row, row, row, row,
                      pl.BlockSpec((LANE_BLOCK, 1), lambda i, j, li: (i, 0))],
            out_specs=[state, row],
        ),
        out_shape=[jax.ShapeDtypeStruct(s_all.shape, s_all.dtype),
                   jax.ShapeDtypeStruct((lanes, n_heads * d), jnp.float32)],
        input_output_aliases={1: 0},  # the stack (after the prefetched scalar)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=64 * 2**20),
        interpret=interpret,
        name="linear_attention_step",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), s_all, flat(q), flat(k), flat(v),
      flat(jnp.broadcast_to(decay[:, :, None], q.shape)),
      from_zero.reshape(lanes, 1).astype(jnp.float32))
    return o.reshape(lanes, n_heads, d), s_all


def linear_attention(s_all, li, from_zero, q, k, v, real, slopes, scale: float,
                     use_kernel: bool | None = None):
    """A linear-attention layer's part in a step: layer ``li`` of the stack
    ``[layers, lanes, H * d * d]`` read (zeros where the step starts a
    sequence: ``from_zero`` ``[B, 1, 1]``), advanced by the step's real rows
    (``real`` ``[B, T]``) and committed in place in the carry. q, k, v ``[B,
    T, H, d]``; ``slopes`` ``[H]`` float32. Returns ``(o [B, T, H, d] float32,
    the stack)``. ``use_kernel`` (tests): force the one-row kernel on or off;
    None: where Pallas kernels are active and the sizes tile."""
    with jax.named_scope(SCOPE_LINEAR_STATE):
        b, t, n_heads, d = q.shape
        f32 = jnp.float32
        if use_kernel is None:
            use_kernel = pallas_kernel_active() and step_kernel_supports(b, n_heads, d)
        if t == 1 and use_kernel:
            decay = jnp.where(real, jnp.exp(-slopes)[None, :], 1.0)  # [B, H]
            k1 = jnp.where(real[:, :, None], k[:, 0].astype(f32), 0.0)
            o, s_all = _step_pallas(
                s_all, li, from_zero, q[:, 0].astype(f32), k1, v[:, 0].astype(f32),
                decay, scale, pallas_interpret())
            return o[:, None], s_all
        state = jax.lax.dynamic_index_in_dim(s_all, li, 0, keepdims=False)
        state = state.reshape(b, n_heads, d, d)
        state = jnp.where(from_zero[..., None], jnp.zeros_like(state), state)
        o, state = _chunk_form(state, q, k, v, real, slopes, scale)
        return o, s_all.at[li].set(state.reshape(b, n_heads * d * d))
