"""The gated delta rule with a decay a key channel (Kimi Delta Attention,
arXiv:2510.26692), one entry for every step family.

A head of a lane keeps a matrix ``S`` ``[d (key), d (value)]``, float32
whatever the activations are. A row brings ``q``, ``k``, ``v`` ``[d]``, a log
decay ``g`` ``[d]`` (``<= 0``, one a KEY CHANNEL: a row of ``S``) and a step
``b`` (in ``(0, 2)``):

    S'  = Diag(exp(g_t)) S_{t-1}
    u_t = b_t * (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

The stack is ``[layers, lanes, heads * d * d]``, flat in its last axis as
models/hybrid.py's header asks of a cache leaf: key row ``j`` of head ``i``'s
matrix is columns ``[(i * d + j) * d, (i * d + j + 1) * d)`` of a lane's row
(ops/linear_attention.py's layout, and its lane rules).

The rule for the matrix: a row that is not real takes ``g = 0`` and ``b = 0``,
so ``u = 0`` and ``S`` passes through it unchanged. The caller says which rows
are real (the first ``n_valid`` of a lane): a bucket's padded tail and a
parked lane leave the state as they found it, a step of ``T`` rows of which
``a`` are real leaves the state AFTER ROW ``a - 1``, and a second chunk
continues the first exactly. A step whose first position is 0 reads zeros
whatever the lane held (``from_zero``): nothing is cleared when a lane is
given to a new request.

At one row a lane (``T = 1``: every decode step and decode half), on a TPU,
one Pallas kernel (``_step_kernel``) over the flat stack as it sits: eight
lanes and ``HEAD_BLOCK`` heads a grid step, the layer's index a prefetched
scalar and the stack aliased to its output, so a matrix is read from HBM once
and written once. A key row of the matrix is one ``(8, 128)`` register across
the eight lanes; a first pass over a head's rows decays them and sums ``S'^T
k`` and ``S'^T q``, then ``u``, then a second pass adds ``k_j u`` to row ``j``
(in VMEM, on the output block); ``o = S'^T q + (q . k) u``. Left to XLA the
flat row and the ``[d, d]`` form the contractions want are two tiled layouts
and every layer's state is copied between them.

At ``T > 1`` the chunk form (``_chunk_form``), float32 throughout at the
highest precision, rows in chunks of ``CHUNK``. With ``G_t`` the running sum
of ``g`` inside the chunk and ``S0`` the state the chunk meets:

    A_kk[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])      s < t
    A_qk[t, s] = sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])      s <= t
    T  = (I + diag(b) tril(A_kk, -1))^-1 diag(b)
    U  = T (V - (K * exp(G)) S0)
    O  = (Q * exp(G)) S0 + tril(A_qk) U
    S_C = Diag(exp(G_C)) S0 + sum_s (k_s * exp(G_C - G_s)) u_s^T

which equals the recurrence row for row. The decayed scores are NOT a matmul
of decayed q and k (``exp(-G_s)`` alone overflows): the ``[C, C, d]`` products
are formed under the mask, every exponent a sum of ``g`` over rows ``(s, t]``,
never positive. ``T`` is solved by forward substitution a row at a time
(``T[t] = b_t (e_t - A_kk[t, :t] T[:t])``: stable where ``b`` nears 2 and a
Neumann product of powers is not). The scores and ``T`` need no state, so
they are made before the scan (the solve for all chunks at once); a ``lax.scan`` over the chunks carries
the matrix. No tensor has both a time axis and the ``[d, d]`` axes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.names import SCOPE_DELTA_STATE
from .linear import pallas_interpret, pallas_kernel_active

# rows of a chunk of the chunk form: the decayed scores are [CHUNK, CHUNK, d]
# products on the vector units and the solve CHUNK substitution steps. One
# layer over a 512-row prompt chunk at 64 heads of 128 on a v5e
# (a one-off timing on the chip, PR 56): 3.50 ms at 32 rows a chunk, 4.29 at 64,
# 6.98 at 128 (the exponentials and products grow with CHUNK, the scan's
# matmuls are small either way)
CHUNK = 32
LANE_BLOCK = 8  # lanes a grid step of the one-row kernel advances: a sublane tile
HEAD_BLOCK = 2  # heads a grid step advances: 1 MB of state in, 1 MB out at d = 128
_HIGHEST = jax.lax.Precision.HIGHEST

# which one-row path the last trace took (tests and the engine's start-up line)
TRACE_STATS = {"one_row_path": None}


def _row(s, q, k, v, g, b):
    """One row of the recurrence for every lane and head: s ``[B, H, d, d]``;
    q, k, v, g ``[B, H, d]``; b ``[B, H]``. Returns ``(o [B, H, d], s)``."""
    s = jnp.exp(g)[..., None] * s
    u = b[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k, precision=_HIGHEST))
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HIGHEST), s


def scan_rows(state, q, k, v, g, b, real):
    """The recurrence a row at a time by ``lax.scan``, float32: what the
    chunk form and the kernel are held to. state ``[B, H, d, d]``; q, k, v, g
    ``[B, T, H, d]``; b ``[B, T, H]``; real ``[B, T]``; returns ``(o [B, T, H,
    d], state)``."""
    f32 = jnp.float32
    g = jnp.where(real[:, :, None, None], g.astype(f32), 0.0)
    b = jnp.where(real[:, :, None], b.astype(f32), 0.0)

    def step(s, row):
        o, s = _row(s, *row)
        return s, o

    rows = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, b))
    state, o = jax.lax.scan(step, state.astype(f32), rows)
    return jnp.moveaxis(o, 0, 1), state


def _decayed_scores(x, k, gc):
    """``sum_c x_t[c] k_s[c] exp(G_t[c] - G_s[c])`` for ``s <= t``, 0 above
    the diagonal: x, k, gc ``[N, C, d]`` -> ``[N, C, C]``. The exponent is
    masked BEFORE the exponential: above the diagonal it is positive."""
    c = x.shape[1]
    lower = jnp.tril(jnp.ones((c, c), bool))[None, :, :, None]
    e = jnp.where(lower, gc[:, :, None, :] - gc[:, None, :, :], -jnp.inf)
    return jnp.sum(x[:, :, None, :] * k[:, None, :, :] * jnp.exp(e), axis=-1)


def _solve(a_kk, b):
    """``(I + diag(b) tril(a_kk, -1))^-1 diag(b)`` by forward substitution:
    a_kk ``[N, C, C]``, b ``[N, C]`` -> ``[N, C, C]``."""
    n, c, _ = a_kk.shape
    strict = jnp.tril(a_kk, -1)
    eye = jnp.eye(c, dtype=a_kk.dtype)

    def row(t, out):
        # rows of ``out`` at or past t are still zero, and strict[t, s >= t] is
        a_t = jax.lax.dynamic_index_in_dim(strict, t, 1, keepdims=False)  # [N, C]
        b_t = jax.lax.dynamic_index_in_dim(b, t, 1, keepdims=True)  # [N, 1]
        e_t = jax.lax.dynamic_index_in_dim(eye, t, 0, keepdims=True)  # [1, C]
        new = b_t * (e_t - jnp.einsum("ns,nsc->nc", a_t, out, precision=_HIGHEST))
        return jax.lax.dynamic_update_index_in_dim(out, new, t, 1)

    return jax.lax.fori_loop(0, c, row, jnp.zeros_like(a_kk))


def _chunk_form(state, q, k, v, g, b, real, chunk: int = CHUNK):
    """``scan_rows`` in chunks (module header), float32 at the highest
    precision throughout."""
    bsz, t, h, d = q.shape
    f32 = jnp.float32
    c = min(chunk, t)
    pad = -t % c
    q, k, v = (x.astype(f32) for x in (q, k, v))
    g = jnp.where(real[:, :, None, None], g.astype(f32), 0.0)
    b = jnp.where(real[:, :, None], b.astype(f32), 0.0)
    if pad:  # rows past the step's: not real
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v, g))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
    nc = (t + pad) // c

    def heads_first(x):  # [B, T, H, ...] -> [nc, B, H, C, ...]
        x = x.reshape(bsz, nc, c, h, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g = (heads_first(x) for x in (q, k, v, g))  # [nc, B, H, C, d]
    b = heads_first(b)  # [nc, B, H, C]
    gc = jnp.cumsum(g, axis=3)  # G_t: the chunk's running log decay

    def scores(xs):  # a chunk's: one chunk's [C, C, d] products live at once
        qc, kc, gg = (x.reshape(bsz * h, c, d) for x in xs)
        return _decayed_scores(qc, kc, gg), _decayed_scores(kc, kc, gg)

    a_qk, a_kk = jax.lax.map(scores, (q, k, gc))  # [nc, B * H, C, C] each
    a_qk = a_qk.reshape(nc, bsz, h, c, c)
    solved = _solve(a_kk.reshape(nc * bsz * h, c, c), b.reshape(nc * bsz * h, c))
    solved = solved.reshape(nc, bsz, h, c, c)

    def one_chunk(s, xs):
        qc, kc, vc, gg, tt, aq = xs  # [B, H, C, d] x 4, [B, H, C, C] x 2
        decay = jnp.exp(gg)
        r = vc - jnp.einsum("bhtk,bhkv->bhtv", kc * decay, s, precision=_HIGHEST)
        u = jnp.einsum("bhts,bhsv->bhtv", tt, r, precision=_HIGHEST)
        o = jnp.einsum("bhtk,bhkv->bhtv", qc * decay, s, precision=_HIGHEST)
        o = o + jnp.einsum("bhts,bhsv->bhtv", aq, u, precision=_HIGHEST)
        g_end = gg[:, :, -1:]  # [B, H, 1, d]
        s = jnp.exp(g_end[:, :, 0])[..., None] * s + jnp.einsum(
            "bhsk,bhsv->bhkv", kc * jnp.exp(g_end - gg), u, precision=_HIGHEST)
        return s, o

    state, o = jax.lax.scan(one_chunk, state.astype(f32), (q, k, v, gc, solved, a_qk))
    # [nc, B, H, C, d] -> [B, T, H, d]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(bsz, nc * c, h, d)[:, :t]
    return o, state


def _step_kernel(li_ref, s_ref, q_ref, k_ref, v_ref, dec_ref, b_ref, zero_ref,
                 s_out_ref, o_ref, *, heads: int, d: int):
    """One row for ``LANE_BLOCK`` lanes and ``heads`` heads of layer ``li`` of
    the flat stack. ``dec_ref`` ``[lanes, heads * d]``: ``exp(g)`` a key
    channel (1 for a lane whose row is not real); ``b_ref`` the head's step
    across the head's columns (0 for such a lane); ``zero_ref`` ``[lanes, 1]``:
    nonzero where the lane starts a sequence."""
    del li_ref  # the block's layer is chosen by the index maps
    fresh = zero_ref[...] != 0.0  # [LANE_BLOCK, 1]
    for h in range(heads):
        cols = slice(h * d, (h + 1) * d)
        q, k, v, b = q_ref[:, cols], k_ref[:, cols], v_ref[:, cols], b_ref[:, cols]
        sk = jnp.zeros_like(v)  # S'^T k
        sq = jnp.zeros_like(v)  # S'^T q
        for j in range(d):
            at = slice((h * d + j) * d, (h * d + j + 1) * d)
            col = slice(h * d + j, h * d + j + 1)
            s = dec_ref[:, col] * jnp.where(fresh, 0.0, s_ref[0, :, at])
            s_out_ref[0, :, at] = s
            sk = sk + k_ref[:, col] * s
            sq = sq + q_ref[:, col] * s
        u = b * (v - sk)
        for j in range(d):
            at = slice((h * d + j) * d, (h * d + j + 1) * d)
            s_out_ref[0, :, at] = s_out_ref[0, :, at] + k_ref[:, h * d + j:h * d + j + 1] * u
        o_ref[:, cols] = sq + jnp.sum(q * k, axis=1, keepdims=True) * u


def step_kernel_supports(lanes: int, n_heads: int, d: int) -> bool:
    """Whether the one-row kernel tiles these sizes: whole sublane tiles of
    lanes, whole lane tiles of a matrix row, whole blocks of heads."""
    return lanes % LANE_BLOCK == 0 and d % 128 == 0 and n_heads % HEAD_BLOCK == 0


def one_row_path(lanes: int, n_heads: int, d: int) -> str:
    """Which path a step of one row a lane takes at these sizes, as the
    start-up line says it: ``pallas_in_place`` or ``xla``."""
    on = pallas_kernel_active() and step_kernel_supports(lanes, n_heads, d)
    return "pallas_in_place" if on else "xla"


def _step_pallas(s_all, li, from_zero, q, k, v, decay, b, interpret: bool):
    """Layer ``li`` of ``s_all`` ``[layers, lanes, H * d * d]`` advanced by one
    row a lane, in place. q, k, v, decay ``[lanes, H, d]`` float32, b
    ``[lanes, H]``; returns ``(o [lanes, H, d], the stack)``."""
    lanes, n_heads, d = q.shape
    hb = HEAD_BLOCK
    row = pl.BlockSpec((LANE_BLOCK, hb * d), lambda i, j, li: (i, j))
    state = pl.BlockSpec((1, LANE_BLOCK, hb * d * d), lambda i, j, li: (li[0], i, j))
    flat = lambda x: x.reshape(lanes, n_heads * d)  # noqa: E731
    s_all, o = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb, d=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(lanes // LANE_BLOCK, n_heads // hb),
            in_specs=[state, row, row, row, row, row,
                      pl.BlockSpec((LANE_BLOCK, 1), lambda i, j, li: (i, 0))],
            out_specs=[state, row],
        ),
        out_shape=[jax.ShapeDtypeStruct(s_all.shape, s_all.dtype),
                   jax.ShapeDtypeStruct((lanes, n_heads * d), jnp.float32)],
        input_output_aliases={1: 0},  # the stack (after the prefetched scalar)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=64 * 2**20),
        interpret=interpret,
        name="delta_rule_step",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), s_all, flat(q), flat(k), flat(v), flat(decay),
      flat(jnp.broadcast_to(b[:, :, None], q.shape)),
      from_zero.reshape(lanes, 1).astype(jnp.float32))
    return o.reshape(lanes, n_heads, d), s_all


def delta_rule(s_all, li, from_zero, q, k, v, g, b, real, use_kernel: bool | None = None):
    """A delta-rule layer's part in a step: layer ``li`` of the stack
    ``[layers, lanes, H * d * d]`` read (zeros where the step starts a
    sequence: ``from_zero`` ``[B, 1, 1]``), advanced by the step's real rows
    (``real`` ``[B, T]``) and committed in place in the carry. q (scaled), k,
    v, g ``[B, T, H, d]``; b ``[B, T, H]``. Returns ``(o [B, T, H, d] float32,
    the stack)``. ``use_kernel`` (tests): force the one-row kernel on or off;
    None: where Pallas kernels are active and the sizes tile."""
    with jax.named_scope(SCOPE_DELTA_STATE):
        bsz, t, n_heads, d = q.shape
        f32 = jnp.float32
        if use_kernel is None:
            use_kernel = one_row_path(bsz, n_heads, d) == "pallas_in_place"
        if t == 1 and use_kernel:
            TRACE_STATS["one_row_path"] = "pallas_in_place"
            decay = jnp.where(real[:, :, None], jnp.exp(g[:, 0].astype(f32)), 1.0)
            b1 = jnp.where(real, b[:, 0].astype(f32), 0.0)
            o, s_all = _step_pallas(
                s_all, li, from_zero, q[:, 0].astype(f32), k[:, 0].astype(f32),
                v[:, 0].astype(f32), decay, b1, pallas_interpret())
            return o[:, None], s_all
        state = jax.lax.dynamic_index_in_dim(s_all, li, 0, keepdims=False)
        state = state.reshape(bsz, n_heads, d, d)
        state = jnp.where(from_zero[..., None], jnp.zeros_like(state), state)
        if t == 1:
            TRACE_STATS["one_row_path"] = "xla"
        o, state = _chunk_form(state, q, k, v, g, b, real)
        return o, s_all.at[li].set(state.reshape(bsz, n_heads * d * d))
