"""The selective state-space recurrence (Mamba-1), one entry for every step
family.

A channel ``e`` of a lane keeps ``N`` running sums, float32 whatever the
activations are:

    S_t[n, e] = exp(D_t[e] * A[n, e]) * S_{t-1}[n, e] + D_t[e] * u_t[e] * B_t[n]
    y_t[e]    = sum_n S_t[n, e] * C_t[n] + D[e] * u_t[e]

with ``A = -exp(A_log) < 0``, ``D_t > 0`` the row's step size (``delta``
here), ``B_t`` and ``C_t`` the row's input and output maps. The state is laid
``[N, E]``: ``E`` (thousands) along a TPU's 128 lanes, the stack
``[layers, lanes, N * E]`` flat in its last axis as models/hybrid.py's header
asks of a cache leaf.

The rule for the running sum (``window_state``'s twin): a row whose
``delta`` is 0 multiplies the state by ``exp(0) = 1`` and adds 0, so the state
passes through it unchanged. The caller zeroes ``delta`` at and past a lane's
``n_valid`` rows: a bucket's padded tail and a parked lane (``n_valid = 0``)
leave the state as they found it, a step of ``T`` rows of which ``a`` are real
leaves the state AFTER ROW ``a - 1``, and a second chunk continues the first
exactly. A step whose first position is 0 reads zeros whatever the lane held
(``from_zero``): nothing is cleared when a lane is given to a new request.

At one row a lane (``T = 1``: every decode step and decode half) the update is
elementwise over ``[lanes, N, E]``: the stack's layer is read once and written
once, in place in the carry. On a TPU that is one Pallas kernel
(``_step_kernel``) over the flat stack as it sits, eight lanes a grid step, the
layer's index a prefetched scalar and the stack aliased to its output: left to
XLA the flat row and the ``[N, E]`` form the arithmetic wants are two tiled
layouts, and the program copied every layer's state between them and computed
the update twice (compiled for a described v5e, PR 43). At ``T > 1`` the
recurrence runs over time with the state held: on a TPU one Pallas kernel
(``_scan_kernel``: grid over lane and tiles of ``E``, the state tile in
registers, time innermost, ``exp(D_t A)`` made a row at a time and never
stored), elsewhere a ``lax.scan`` over rows. Neither writes a ``[T, N, E]``
tensor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.names import SCOPE_SSM_SCAN
from .linear import pallas_interpret, pallas_kernel_active

# rows unrolled in one iteration of the XLA form's loop over time
ROW_BLOCK = 8
# channels a grid step of the kernel holds: N vregs of (8, 128) float32
E_TILE = 1024
# rows a grid step of the kernel walks before the next block is fetched
T_TILE = 256


def _one_row(state, delta, u, b, c, a):
    """One row a lane. state ``[B, N, E]``, delta/u ``[B, E]``, b/c ``[B, N]``,
    a ``[N, E]``; returns (y ``[B, E]`` without the skip term, new state)."""
    decay = jnp.exp(delta[:, None, :] * a[None])
    state = decay * state + (delta * u)[:, None, :] * b[:, :, None]
    return jnp.sum(state * c[:, :, None], axis=1), state


def _scan_rows(state, delta, u, b, c, a):
    """The recurrence over ``T`` rows by ``lax.scan``, ``ROW_BLOCK`` rows
    unrolled an iteration; what the kernel is held to."""
    def step(s, row):
        y, s = _one_row(s, *row, a)
        return s, y

    rows = tuple(jnp.moveaxis(x, 1, 0) for x in (delta, u, b, c))  # time-major
    state, y = jax.lax.scan(step, state, rows, unroll=min(ROW_BLOCK, delta.shape[1]))
    return jnp.moveaxis(y, 0, 1), state


def _scan_kernel(delta_ref, u_ref, b_ref, c_ref, a_ref, s_in_ref, y_ref, s_out_ref, s_acc,
                 *, n: int, rows: int):
    """One lane, one tile of ``E_TILE`` channels, ``rows`` rows of time; the
    time axis is the innermost grid axis, so the state tile stays in
    ``s_acc`` between its steps. A channel tile is ``(8, 128)``: one vreg a
    state index, the sum over ``n`` is adds of whole vregs. ``b`` and ``c``
    are read a scalar at a time from SMEM and broadcast."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        s_acc[...] = s_in_ref[0]

    a = a_ref[...]  # [n, 8, 128]

    def row(t, s):
        d = delta_ref[0, t]  # [8, 128]
        du = d * u_ref[0, t]
        y = jnp.zeros_like(d)
        out = []
        for i in range(n):
            s_i = jnp.exp(d * a[i]) * s[i] + du * b_ref[0, i, t]
            y = y + s_i * c_ref[0, i, t]
            out.append(s_i)
        y_ref[0, t] = y
        return tuple(out)

    s = jax.lax.fori_loop(0, rows, row, tuple(s_acc[i] for i in range(n)))
    for i in range(n):
        s_acc[i] = s[i]

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        s_out_ref[0] = s_acc[...]


LANE_BLOCK = 8  # lanes a grid step of the one-row kernel advances: a sublane tile


def _step_kernel(si_ref, s_ref, delta_ref, u_ref, b_ref, c_ref, a_ref, zero_ref,
                 s_out_ref, y_ref, *, n: int, e: int):
    """One row for ``LANE_BLOCK`` lanes of layer ``si`` of the flat stack:
    state index ``i`` of a lane is columns ``[i * e, (i + 1) * e)`` of its
    row, whole 128-lane tiles. ``zero_ref`` ``[lanes, 1]``: nonzero where the
    lane starts a sequence and reads zeros."""
    del si_ref  # the block's layer is chosen by the index maps
    d = delta_ref[...]  # [LANE_BLOCK, e]
    du = d * u_ref[...]
    fresh = zero_ref[...] != 0.0  # [LANE_BLOCK, 1]
    y = jnp.zeros_like(d)
    for i in range(n):
        s = jnp.where(fresh, 0.0, s_ref[0, :, i * e:(i + 1) * e])
        s = jnp.exp(d * a_ref[i:i + 1, :]) * s + du * b_ref[:, i:i + 1]
        s_out_ref[0, :, i * e:(i + 1) * e] = s
        y = y + s * c_ref[:, i:i + 1]
    y_ref[...] = y


def step_kernel_supports(lanes: int, n: int, e: int) -> bool:
    """Whether the one-row kernel tiles these sizes: whole sublane tiles of
    lanes and whole lane tiles of channels."""
    return lanes % LANE_BLOCK == 0 and e % 128 == 0


def _step_pallas(s_all, si, from_zero, delta, u, b, c, a, interpret: bool):
    """Layer ``si`` of ``s_all`` ``[layers, lanes, N * E]`` advanced by one
    row a lane, in place: ``(y [lanes, E], the stack)``."""
    lanes, e = delta.shape
    n = a.shape[0]
    row = lambda w: pl.BlockSpec((LANE_BLOCK, w), lambda i, si: (i, 0))
    state = pl.BlockSpec((1, LANE_BLOCK, n * e), lambda i, si: (si[0], i, 0))
    s_all, y = pl.pallas_call(
        functools.partial(_step_kernel, n=n, e=e),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(lanes // LANE_BLOCK,),
            in_specs=[state, row(e), row(e), row(n), row(n),
                      pl.BlockSpec((n, e), lambda i, si: (0, 0)), row(1)],
            out_specs=[state, row(e)],
        ),
        out_shape=[jax.ShapeDtypeStruct(s_all.shape, s_all.dtype),
                   jax.ShapeDtypeStruct((lanes, e), jnp.float32)],
        input_output_aliases={1: 0},  # the stack (after the prefetched scalar)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=64 * 2**20),
        interpret=interpret,
        name="ssm_step",
    )(jnp.reshape(si, (1,)).astype(jnp.int32), s_all, delta, u, b, c, a,
      from_zero.reshape(lanes, 1).astype(jnp.float32))
    return y, s_all


def kernel_supports(t: int, n: int, e: int) -> bool:
    """Whether the kernel tiles these sizes: whole channel tiles, and rows
    that are whole blocks of time (every prefill bucket is)."""
    return e % E_TILE == 0 and t > 1 and (t <= T_TILE or t % T_TILE == 0) and n <= 64


def _scan_pallas(state, delta, u, b, c, a, interpret: bool):
    bsz, t, e = delta.shape
    n = a.shape[0]
    rows = min(t, T_TILE)
    tiles = e // E_TILE
    sub = E_TILE // 128
    # a channel tile as (8, 128): the last two axes of every block
    d4 = delta.reshape(bsz, t, tiles * sub, 128)
    u4 = u.reshape(bsz, t, tiles * sub, 128)
    a4 = a.reshape(n, tiles * sub, 128)
    s4 = state.reshape(bsz, n, tiles * sub, 128)
    row_spec = pl.BlockSpec((1, rows, sub, 128), lambda i, j, k: (i, k, j, 0))
    # the maps time-minor, as SMEM pads a block's last axis least that way
    map_spec = pl.BlockSpec((1, n, rows), lambda i, j, k: (i, 0, k),
                            memory_space=pltpu.SMEM)
    state_spec = pl.BlockSpec((1, n, sub, 128), lambda i, j, k: (i, 0, j, 0))
    y, s_out = pl.pallas_call(
        functools.partial(_scan_kernel, n=n, rows=rows),
        grid=(bsz, tiles, t // rows),
        in_specs=[row_spec, row_spec, map_spec, map_spec,
                  pl.BlockSpec((n, sub, 128), lambda i, j, k: (0, j, 0)), state_spec],
        out_specs=[row_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct(d4.shape, jnp.float32),
                   jax.ShapeDtypeStruct(s4.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, sub, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(d4, u4, jnp.swapaxes(b, 1, 2), jnp.swapaxes(c, 1, 2), a4, s4)
    return y.reshape(bsz, t, e), s_out.reshape(bsz, n, e)


def selective_scan(state, delta, u, b, c, a, d, use_kernel: bool | None = None):
    """``(y, new state)`` of the recurrence in the module's header, float32.
    state ``[B, N, E]``; delta, u ``[B, T, E]``; b, c ``[B, T, N]``; a
    ``[N, E]``; d ``[E]``. ``use_kernel`` (tests): force the Pallas kernel on
    or off at ``T > 1``; None: where Pallas kernels are active and the sizes
    tile."""
    t = delta.shape[1]
    if t == 1:
        y, state = _one_row(state, delta[:, 0], u[:, 0], b[:, 0], c[:, 0], a)
        y = y[:, None]
    else:
        if use_kernel is None:
            use_kernel = pallas_kernel_active() and kernel_supports(t, a.shape[0], a.shape[1])
        if use_kernel:
            y, state = _scan_pallas(state, delta, u, b, c, a, pallas_interpret())
        else:
            y, state = _scan_rows(state, delta, u, b, c, a)
    return y + d * u, state


def state_step(s_all, si, from_zero, delta, u, b, c, a_log, d):
    """A state-space layer's part in a step: layer ``si`` of the stack
    ``[layers, lanes, N * E]`` read (zeros where the step starts a sequence),
    advanced by the step's rows and committed in place in the carry. Returns
    ``(y [B, T, E] float32, the stack)``."""
    with jax.named_scope(SCOPE_SSM_SCAN):
        n, e = a_log.shape
        bsz, t = delta.shape[:2]
        if t == 1 and pallas_kernel_active() and step_kernel_supports(bsz, n, e):
            y, s_all = _step_pallas(
                s_all, si, from_zero, delta[:, 0], u[:, 0], b[:, 0], c[:, 0],
                -jnp.exp(a_log), pallas_interpret())
            return (y + d * u[:, 0])[:, None], s_all
        state = jax.lax.dynamic_index_in_dim(s_all, si, 0, keepdims=False)
        state = state.reshape(bsz, n, e)
        state = jnp.where(from_zero, jnp.zeros_like(state), state)
        y, state = selective_scan(state, delta, u, b, c, -jnp.exp(a_log), d)
        return y, s_all.at[si].set(state.reshape(bsz, n * e))
