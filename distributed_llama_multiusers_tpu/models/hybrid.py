"""A block whose layers differ in their mixer (``model_type: lfm2_moe``,
``jamba``, ``cohere2_moe``, ``minicpm_sala``, ``solar_open2``): gated short
convolutions, selective state-space mixers, linear attention, gated
delta-rule layers, GQA attention over the whole
context, over a window of it and over the blocks of it that a row chooses, in
a published per-layer pattern, leading dense FFNs, then routed ones (or dense
ones throughout). Assembled from the parts of the other two
blocks, with ``llama_forward``'s signature: the GQA projection, append and
plane attention are ``models/llama.py``'s, the router, the route plan, the
grouped kernel and the gated FFN ``models/deepseek.py``'s, the state-space
recurrence ``ops/ssm_scan.py``'s. New here are the conv and state-space
mixers, their state, and a forward over a list of layer kinds.

The layer (``h`` the stream, ``K = conv_kernel``):

    n = rmsnorm(h, g_op)
    conv:       [B; C; X] = W_in n          three parts of ``dim``, in that order
                u_t = B_t * X_t
                v_t = sum_{j<K} w[j] * u_{t-(K-1)+j}      depthwise, causal, u_{<0} = 0
                h' = h + W_out (C_t * v_t)
    state-space: [x; z] = W_in n         two parts of ``E = ssm_d_inner``, in that order
                c_t = b_c + sum_{j<K'} w[j] * x_{t-(K'-1)+j}   depthwise, causal, x_{<0} = 0
                u_t = silu(c_t);  [dt; B; C] = W_x u_t      R + N + N numbers, each normed
                D_t = softplus(W_dt dt + b_dt),  A = -exp(A_log)        float32
                S_t = exp(D_t (x) A) * S_{t-1} + (D_t * u_t) (x) B_t    S_{-1} = 0
                h' = h + W_out ((S_t C_t + D * u_t) * silu(z_t))
    attention:  q, k normed per head (where ``qk_norm``), rotated (unless
                ``rope_type`` NONE, or ``full_attention_nope``: then the
                window layers alone rotate), GQA over the cache: ``n_heads``
                heads of ``head_size`` (``head_dim``, else ``dim // n_heads``)
                o(t) = sum over s <= t of softmax_s(q(t) . k(s) / sqrt(head_size)) v(s)
                h' = h + Wo o                 Wo: n_heads * head_size -> dim
    window:     the same with the sum over s in (t - W, t], W = ``sliding_window``
    linear:     q, k normed per head and rotated, ``linear_n_heads`` heads of
                ``d = linear_head_dim``; per head i, float32:
                S_t = lambda_i * S_{t-1} + k_t^T v_t,  o_t = q_t S_t / sqrt(d)
                h' = h + c W_out (rmsnorm(o_t, g_o) * sigmoid(W_g n))
                lambda_i = exp(-2^(-8 (i + 1) / heads)) (ops/linear_attention.py)
    sparse:     attention's q, k normed per head and NOT rotated; a row at
                position t >= ``sparse_dense_len`` sums over the s <= t of
                the ``sparse_topk`` blocks its compressed keys choose, the
                first and the window's among them (ops/block_sparse.py), a
                set a kv head; a row under it over every s <= t
                h' = h + c Wo (o * sigmoid(W_g n))
    delta:      ``delta_n_heads`` heads of ``d = delta_head_dim``, nothing
                rotated; q~, k~, v~ = W_q n, W_k n, W_v n, each through its own
                causal depthwise conv of ``delta_conv_kernel`` taps and a silu;
                q = q' / |q'| / sqrt(d), k = k' / |k'| per head (eps 1e-6)
                g_t = -exp(A_log_i) * softplus(W_f2 (W_f1 n) + dt_bias)  a key channel
                b_t = 2 sigmoid(W_b n) (``delta_neg_eigval``; else sigmoid)
                S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T,  o_t = S_t^T q_t
                h' = h + W_out (rmsnorm(o_t, g_o) * sigmoid(W_g2 (W_g1 n)))
                (ops/delta_rule.py; float32)
    gated:      ``attn_output_gate``: a full-context layer's output is
                multiplied by sigmoid(W_g n) before wo, as a sparse layer's is
    ``c = residual_scale`` multiplies every mixer's and FFN's term in such a
    block, ``embed_scale`` the embedding, and the final norm's output is
    divided by ``logit_divisor`` before the head.
    FFN:        dense in the first ``n_dense_layers`` layers, routed in the others
    mixed heads (``model_type: mimo_v2_flash``; each by its own config field):
                the window kind has ``window_n_kv_heads`` kv heads and rotates
                at ``window_rope_theta`` where the full-context kind has
                ``n_kv_heads`` and ``rope_theta``; either rotates only the
                first ``rotary_dim`` numbers of a head; a value head is
                ``v_head_dim`` wide (wo reads ``n_heads * v_head_dim``) and
                every value is scaled by ``attn_value_scale``; a window row's
                softmax has one more column, a learned logit b_i a query head
                (``window_sink``) that takes mass and gives no value:
                p(t, s) = exp(e(t, s)) / (exp(b_i) + sum_s' exp(e(t, s')))
    ``norm_kind`` LAYER: every ``rmsnorm`` above is g * (h - mean h) / sqrt(var h + eps).
    ``parallel_block``: ONE norm a layer, n = norm(h, g), feeds the mixer and
                the routed FFN (shared experts scaled by ``shared_expert_scale``),
                h' = h + Wo o + ffn(n)

The state. Each kind of layer keeps its own stack, indexed by the count of
that kind: ``k`` and ``v`` ``[attention layers, lanes, S, n_kv * head]`` (no
plane for a conv layer), the window layers' ring ``wk`` and ``wv`` ``[window
layers, lanes, R, n_kv * head]`` with ``R < S`` (None in a block without such
layers), each of the four as wide as ITS kind's kv heads times ITS head (a
key 192 and a value 128 wide at 4 and 8 kv heads: 768, 512, 1536 and 1024;
``config.kv_widths``), and the conv layers' window of inputs
``[conv layers, lanes, (K-1) * dim]``: a lane's last ``K - 1`` rows of ``u``;
and, where the block has state-space layers, their running sum ``ssm``
``[SSM layers, lanes, N * E]``, FLOAT32 whatever the cache's type, and their
conv's window ``ssm_conv`` ``[SSM layers, lanes, (K'-1) * E]`` (both None in a
block without such layers: its programs are what they were); the linear
layers' matrix state ``lin`` ``[linear layers, lanes, H * d * d]``, FLOAT32
too and under the running sum's rule; the delta-rule layers' matrix state
``delta`` ``[delta layers, lanes, H * d * d]``, FLOAT32 and under the same rule
(a row that is not real takes ``g = 0`` and ``b = 0``), and the windows of
their three convs' inputs in ONE leaf ``delta_conv`` ``[delta layers, lanes,
(K-1) * 3 * H * d]`` (q~, k~, v~ side by side in a row, under the conv
state's rule below); and, kept by position like the planes,
the sparse layers' compressed keys ``ck`` ``[sparse layers, lanes, S /
kernel_stride, n_kv * head]`` (a sparse layer's K and V are planes of ``k``
and ``v``).
All are flat in their last axis, so that it is whole tiles of a TPU's 128
lanes: with a 64-wide head as the last axis XLA gave the K/V stack another
layout inside the layer loop and copied it whole, in and out, every step
(compiled for a described v5e, PR 35), and a size-one axis before the last
cost whole-stack copies before (PERF.md section 6, PR 33). All ride the carry and are written in place;
the lane axis is axis 1 of each, so the engine's lane splice, slice and copy
treat them alike.

One rule for the conv state (``window_state``), whatever the step family: a
step that computes ``T`` rows of ``u`` for a lane, of which the first ``a`` are
real, leaves rows ``[a - (K-1), a)`` of ``concat(state, u)``. So a parked lane
(``a = 0``) keeps its state, a bucket's padded tail is ignored, and a second
chunk continues the first. A step whose first position is 0 reads a zero
state whatever the lane held: nothing is cleared when a lane is given to a
new request. ``a`` is ``n_valid`` where the engine says it (a prefill chunk's
real tokens), else the rows whose position lies inside the context. The state
is overwritten, not kept by position: a lane cannot be rewound, and a copy of
a lane is its state at its LAST position (runtime/engine.py refuses what
would need either).

The running sum has the window's rule in its own terms (``ops/ssm_scan.py``):
rows at or past ``a`` take ``D_t = 0`` and no input, so ``S`` passes through
them unchanged and the step leaves the state AFTER ROW ``a - 1`` (the state it
read where ``a = 0``); a step whose first position is 0 reads ``S = 0`` and a
zero window; a second chunk continues the first exactly.

One rule for the ring (``ops/blocked_attention.py`` ``held_position``),
beside those two. ``R`` is the window plus the largest step of more than one
row a lane (the largest prefill bucket), in whole decode blocks (``ring_rows``:
4096 + 512 = 4608 = 18 blocks of 256), so that a chunk's last row never lands
on a row its first query still reads. Position ``p`` lives in row ``p mod R``;
a reader at position ``t`` takes row ``r`` to hold the largest ``p <= t``
congruent to ``r``, and reads it only where ``t - W < p`` and ``p >= 0``: by
arithmetic, never by what the row contains, so nothing is cleared when a lane
is given to a new request. A row at or past ``a`` (``n_valid``), or past the
context, writes nothing: a parked lane's ring is untouched, a bucket's padded
tail is ignored, and a second chunk continues the first exactly. Like the
other two the ring is overwritten in place: a lane cannot be rewound past
``R - W`` rows, and a copy of a lane is its ring at its LAST position.

The layers. In a routed model the leading dense layers run first, unrolled;
the others (every layer of a model without routed ones, its dense FFNs read
by layer) run as one ``lax.scan`` over whole periods of their kinds (a run of
``RUN_SCAN_MIN`` or more layers of one kind inside a period a scan of its own; the shortest
period the published list repeats with; its layers unrolled inside the body,
each computing only its own mixer) and the odd tail unrolled after it. Every
weight stack is closed over and read at its index (``deepseek._pick``).
Attention at one row a lane (``t == 1``: every decode step and the decode half
of every fused step) reads the K/V stack in place, as ``models/llama.py``'s
block does ("How the cache is read at decode width"): the decode kernel
(ops/pallas_attention.py) is handed the merged stack as it sits, the count of
attention layers before this one and the lanes' positions, and fetches for
each lane the row blocks up to its position. A prefill bucket's rows a lane
read the same stack in place too, the key blocks up to the chunk's last real
row (``prefill_attention``; ``llama.prefill_attention_engages`` decides, from
the inputs). Other widths, the CPU, a float32 or f8 cache and a context that
is not whole blocks take XLA's dense path over the layer's whole plane
(``llama.decode_attention_engages`` decides, from the inputs). Dense, the
five planes of a 64-lane step were 1.34 GB read and converted whatever the
lanes held: 10.2 of a 28 ms decode step on a v5e (PERF.md section 6, PR 36). A window layer's read at one row a lane is the
same kernel over its ring with a work list of its own (``ring_blocks``: the
blocks that hold ``(pos - W, pos]``), under ``dl.window_attention``. At more
rows a lane, where dense scores ``[B, T, heads, rows]`` would pass
``blocked_attention.DENSE_SCORE_BYTES``, either kind is computed a key block
at a time over the blocks the mask admits (ops/blocked_attention.py).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ..formats.model_file import LayerKind, NormKind
from ..ops import block_sparse, blocked_attention, pallas_attention
from ..ops.linear import head, matmul, pallas_interpret, pallas_kernel_active
from ..ops.delta_rule import delta_rule
from ..ops.linear_attention import decay_slopes, linear_attention
from ..ops.norm import layer_norm, rms_norm
from ..ops.rope import apply_rope
from ..ops.ssm_scan import state_step
from ..quants.packed import PackedQ40, Q40Experts
from ..telemetry.names import (
    SCOPE_ATTENTION,
    SCOPE_ATTN_OUT,
    SCOPE_BLOCK_SCORES,
    SCOPE_CONV,
    SCOPE_CONV_STATE,
    SCOPE_DELTA,
    SCOPE_DELTA_CONV,
    SCOPE_EMBED,
    SCOPE_KV_WRITE,
    SCOPE_LAYERS,
    SCOPE_LINEAR_ATTENTION,
    SCOPE_QKV,
    SCOPE_SPARSE_SELECT,
    SCOPE_SSM,
    SCOPE_WINDOW_ATTENTION,
)
from .config import LlamaConfig
from .deepseek import (
    ROUTED_COUNTS,
    DenseFfnParams,
    RoutedFfnParams,
    _pick,
    dense_ffn,
    ffn_ops,
    routed_ffn,
)
from .llama import (
    _to_cache_dtype,
    decode_attention_engages,
    dense_plane_attention,
    gqa_project,
    kv_append,
    prefill_attention_engages,
)


class GqaParams(NamedTuple):
    """The attention layers' weights, full-context and window layers in one
    stack in layer order, ``[La, ...]``; where the kinds' kv heads differ
    (``config.split_kv_kinds``) ``wk`` / ``wv`` are the full-context kind's
    alone and the window kind's are ``wk_w`` / ``wv_w``, each stacked by the
    count of its kind."""

    wq: jnp.ndarray  # [La, dim, n_heads * head_size]
    wk: jnp.ndarray  # [La, dim, n_kv * head_size]
    wv: jnp.ndarray  # [La, dim, n_kv * value_head_size]
    wo: jnp.ndarray  # [La, n_heads * value_head_size, dim]
    q_norm: jnp.ndarray | None  # [La, head] f32 (config.qk_norm)
    k_norm: jnp.ndarray | None
    rms: jnp.ndarray  # [La, dim]: the layer's operator norm
    # the output gate: every block-sparse layer's, and the full-context
    # layers' under config.attn_output_gate (None elsewhere)
    gate: jnp.ndarray | None = None  # [La, dim, n_heads * head_size]
    # the window kind's own (None elsewhere): its K/V projections, and the
    # sink's logit a query head (config.window_sink), float32
    wk_w: jnp.ndarray | None = None  # [Lw, dim, window_n_kv * head_size]
    wv_w: jnp.ndarray | None = None
    sink: jnp.ndarray | None = None  # [Lw, n_heads]


class LinearParams(NamedTuple):
    """The linear-attention layers' weights, stacked ``[linear layers, ...]``;
    ``D = linear_n_heads * linear_head_dim``."""

    wq: jnp.ndarray  # [Ll, dim, D]
    wk: jnp.ndarray
    wv: jnp.ndarray
    q_norm: jnp.ndarray  # [Ll, head] f32: the per-head norm of the queries
    k_norm: jnp.ndarray
    gate: jnp.ndarray  # [Ll, dim, D]: the output gate
    o_norm: jnp.ndarray  # [Ll, head] f32: the per-head norm of the output
    w_out: jnp.ndarray  # [Ll, D, dim]
    rms: jnp.ndarray  # [Ll, dim]: the layer's input norm


class DeltaParams(NamedTuple):
    """The delta-rule layers' weights, stacked ``[delta layers, ...]``; ``D =
    delta_n_heads * delta_head_dim``, ``r = delta_gate_rank``. What steers the
    state's exponential is float32 whatever the activations are."""

    wq: jnp.ndarray  # [Ld, dim, D]
    wk: jnp.ndarray
    wv: jnp.ndarray
    taps: jnp.ndarray  # [Ld, K, 3 * D] f32: q's, k's and v's convs side by side
    f1: jnp.ndarray  # [Ld, dim, r]: the decay's low-rank gate
    f2: jnp.ndarray  # [Ld, r, D] f32
    dt_bias: jnp.ndarray  # [Ld, D] f32
    a_log: jnp.ndarray  # [Ld, H] f32: the decay's rate a head is -exp(a_log)
    wb: jnp.ndarray  # [Ld, dim, H] f32: the step b a head
    g1: jnp.ndarray  # [Ld, dim, r]: the output gate, low-rank
    g2: jnp.ndarray  # [Ld, r, D] f32
    o_norm: jnp.ndarray  # [Ld, head] f32: the per-head norm of the output
    w_out: jnp.ndarray  # [Ld, D, dim]
    rms: jnp.ndarray  # [Ld, dim]: the layer's input norm


class ConvParams(NamedTuple):
    """The conv layers' weights, stacked ``[conv layers, ...]``."""

    w_in: jnp.ndarray  # [Lc, dim, 3 * dim]: B, C, x
    taps: jnp.ndarray  # [Lc, K, dim] f32: tap j multiplies u_{t-(K-1)+j}
    w_out: jnp.ndarray  # [Lc, dim, dim]
    rms: jnp.ndarray  # [Lc, dim]: the layer's operator norm


class SsmParams(NamedTuple):
    """The state-space layers' weights, stacked ``[SSM layers, ...]``. What
    steers the state's exponential is float32 whatever the activations are."""

    w_in: jnp.ndarray  # [Ls, dim, 2 * E]: x, z
    taps: jnp.ndarray  # [Ls, K, E] f32: tap j multiplies x_{t-(K-1)+j}
    conv_bias: jnp.ndarray | None  # [Ls, E] f32 (config.ssm_conv_bias)
    w_x: jnp.ndarray  # [Ls, E, R + 2 * N]: dt, B, C
    dt_norm: jnp.ndarray | None  # [Ls, R] f32 (config.ssm_inner_norms)
    b_norm: jnp.ndarray | None  # [Ls, N]
    c_norm: jnp.ndarray | None
    w_dt: jnp.ndarray  # [Ls, R, E] f32
    dt_bias: jnp.ndarray  # [Ls, E] f32
    a_log: jnp.ndarray  # [Ls, N, E] f32: A = -exp(a_log)
    d: jnp.ndarray  # [Ls, E] f32: the skip term
    w_out: jnp.ndarray  # [Ls, E, dim]
    rms: jnp.ndarray  # [Ls, dim]: the layer's input norm


class HybridParams(NamedTuple):
    embedding: jnp.ndarray  # [vocab, dim]
    attn: GqaParams | None
    conv: ConvParams | None
    dense: DenseFfnParams | None
    routed: RoutedFfnParams | None
    rms_final: jnp.ndarray
    wcls: jnp.ndarray
    rope_cos: jnp.ndarray | None  # [seq_len, head_size // 2] f32; None: no rotation
    rope_sin: jnp.ndarray | None
    ssm: SsmParams | None = None
    linear: LinearParams | None = None
    # the window kind's rotation tables where its base is its own
    # (config.window_rope_theta; None: the ones above)
    rope_cos_w: jnp.ndarray | None = None
    rope_sin_w: jnp.ndarray | None = None
    delta: DeltaParams | None = None


class HybridCache(NamedTuple):
    """A lane's state by kind of layer; the lane axis is axis 1 of each."""

    k: jnp.ndarray  # [La, lanes, S, n_kv * head]
    v: jnp.ndarray  # [La, lanes, S, n_kv * value head]
    conv: jnp.ndarray  # [Lc, lanes, (K-1) * dim]: the last K-1 rows of u
    # state-space layers only (None elsewhere): the running sum, float32
    # whatever the cache's type, and the conv's last K'-1 inputs
    ssm: jnp.ndarray | None = None  # [Ls, lanes, N * E]
    ssm_conv: jnp.ndarray | None = None  # [Ls, lanes, (K'-1) * E]
    # window layers only (None elsewhere): their keys' and values' ring
    wk: jnp.ndarray | None = None  # [Lw, lanes, R, window n_kv * head]
    wv: jnp.ndarray | None = None  # [Lw, lanes, R, window n_kv * value head]
    # linear-attention layers only (None elsewhere): the matrix a head,
    # float32 whatever the cache's type
    lin: jnp.ndarray | None = None  # [Ll, lanes, H * head * head]
    # block-sparse layers only (None elsewhere): the compressed keys, one a kv
    # head every kernel_stride positions, kept by position like the planes
    ck: jnp.ndarray | None = None  # [Lp, lanes, S / kernel_stride, n_kv * head]
    # delta-rule layers only (None elsewhere): the matrix a head, float32
    # whatever the cache's type, and the three convs' last K-1 inputs
    delta: jnp.ndarray | None = None  # [Ld, lanes, H * head * head]
    delta_conv: jnp.ndarray | None = None  # [Ld, lanes, (K-1) * 3 * H * head]


def ring_rows(config: LlamaConfig, max_chunk: int) -> int:
    """Rows of a window layer's ring: the window plus the widest step of more
    than one row a lane, in whole decode blocks where the context is (module
    header); never more than the context."""
    unit = pallas_attention.BLOCK_ROWS if config.seq_len % pallas_attention.BLOCK_ROWS == 0 else 1
    return min(-(-(config.sliding_window + max_chunk) // unit) * unit, config.seq_len)


def init_hybrid_cache(config: LlamaConfig, n_lanes: int, dtype=jnp.float32,
                      max_chunk: int = 1) -> HybridCache:
    """``max_chunk``: the most rows a lane any step writes (the largest prefill
    bucket): it sizes the ring (``ring_rows``)."""
    plane = (config.n_attention_layers, n_lanes, config.seq_len)
    k_dim, v_dim = config.kv_widths()
    ssm = ssm_conv = wk = wv = lin = ck = delta = delta_conv = None
    if config.n_delta_layers:
        ld, dd = config.n_delta_layers, config.delta_dim
        delta = jnp.zeros((ld, n_lanes, dd * config.delta_head_dim), jnp.float32)
        delta_conv = jnp.zeros((ld, n_lanes, (config.delta_conv_kernel - 1) * 3 * dd), dtype)
    if config.n_linear_layers:
        lin = jnp.zeros(
            (config.n_linear_layers, n_lanes,
             config.linear_n_heads * config.linear_head_dim ** 2), jnp.float32)
    if config.n_sparse_layers:
        ck = jnp.zeros(
            (config.n_sparse_layers, n_lanes, config.seq_len // config.sparse_kernel_stride,
             config.kv_dim), dtype)
    if config.n_window_layers:
        ring = (config.n_window_layers, n_lanes, ring_rows(config, max_chunk))
        wk_dim, wv_dim = config.kv_widths(windowed=True)
        wk, wv = jnp.zeros((*ring, wk_dim), dtype), jnp.zeros((*ring, wv_dim), dtype)
    if config.n_ssm_layers:
        ls, e = config.n_ssm_layers, config.ssm_d_inner
        ssm = jnp.zeros((ls, n_lanes, config.ssm_d_state * e), jnp.float32)
        ssm_conv = jnp.zeros((ls, n_lanes, (config.ssm_conv_kernel - 1) * e), dtype)
    return HybridCache(
        k=jnp.zeros((*plane, k_dim), dtype), v=jnp.zeros((*plane, v_dim), dtype),
        conv=jnp.zeros(
            (config.n_conv_layers, n_lanes, max(config.conv_kernel - 1, 0) * config.dim), dtype),
        ssm=ssm, ssm_conv=ssm_conv, wk=wk, wv=wv, lin=lin, ck=ck,
        delta=delta, delta_conv=delta_conv,
    )


def state_leaves(cache) -> tuple:
    """The leaves of a cache that are overwritten in place and not kept by
    position: a lane's recurrent state (none for any other cache)."""
    if not isinstance(cache, HybridCache):
        return ()
    return tuple(
        x for x in (cache.conv, cache.ssm, cache.ssm_conv, cache.wk, cache.wv, cache.lin,
                    cache.delta, cache.delta_conv)
        if x is not None)


def ring_attention_engages(cache, mesh, n_heads: int, n_kv: int) -> bool:
    """``llama.decode_attention_engages`` for a window layer's ring: whether a
    step of one row a lane reads it in place through the decode kernel."""
    return (
        getattr(cache, "wk", None) is not None and mesh is None
        and pallas_kernel_active()
        and pallas_attention.supports(cache.wk, n_heads, n_kv, cache.wv)
    )


def block_sparse_engages(cache, mesh, config: LlamaConfig) -> bool:
    """``llama.decode_attention_engages`` for a block-sparse layer: whether a
    step of one row a lane fetches the blocks it chose out of the planes in
    place (``pallas_attention.sparse_decode_attention``); else the planes are
    read a key block at a time under the rows' mask."""
    return (
        config.n_sparse_layers > 0 and mesh is None and pallas_kernel_active()
        and pallas_attention.supports_sparse(
            cache.k, config.n_heads, config.n_kv_heads, config.sparse_block_size))


def hybrid_params(t: dict, rope_cos, rope_sin, rope_cos_w=None, rope_sin_w=None) -> HybridParams:
    """The parameter tree around a model's arrays, by the tensor names of the
    ``.m`` walk without their ``block_`` prefix: what the loader and a
    benchmark's generator both hand over. Expert stacks that arrive as
    ``PackedQ40`` become ``Q40Experts``. ``rope_cos_w`` / ``rope_sin_w``: the
    window kind's tables where it rotates at a base of its own."""
    def experts(w):
        return Q40Experts.from_packed(w) if isinstance(w, PackedQ40) else w

    attn = conv = dense = routed = ssm = linear = delta = None
    if "delta_q" in t:
        delta = DeltaParams(
            wq=t["delta_q"], wk=t["delta_k"], wv=t["delta_v"], taps=t["delta_taps"],
            f1=t["delta_f1"], f2=t["delta_f2"], dt_bias=t["delta_dt_bias"],
            a_log=t["delta_a_log"], wb=t["delta_b"], g1=t["delta_g1"], g2=t["delta_g2"],
            o_norm=t["delta_o_norm"], w_out=t["delta_out"], rms=t["delta_rms"])
    if "wq" in t:
        attn = GqaParams(
            wq=t["wq"], wk=t["wk"], wv=t["wv"], wo=t["wo"],
            q_norm=t.get("q_norm"), k_norm=t.get("k_norm"), rms=t["attn_rms"],
            gate=t.get("attn_gate"),
            wk_w=t.get("wk_w"), wv_w=t.get("wv_w"), sink=t.get("attn_sink"),
        )
    if "lin_q" in t:
        linear = LinearParams(
            wq=t["lin_q"], wk=t["lin_k"], wv=t["lin_v"], q_norm=t["lin_q_norm"],
            k_norm=t["lin_k_norm"], gate=t["lin_gate"], o_norm=t["lin_o_norm"],
            w_out=t["lin_out"], rms=t["lin_rms"])
    if "conv_in" in t:
        conv = ConvParams(
            w_in=t["conv_in"], taps=t["conv_taps"], w_out=t["conv_out"], rms=t["conv_rms"])
    if "ssm_in" in t:
        ssm = SsmParams(
            w_in=t["ssm_in"], taps=t["ssm_taps"], conv_bias=t.get("ssm_conv_bias"),
            w_x=t["ssm_x"], dt_norm=t.get("ssm_dt_norm"), b_norm=t.get("ssm_b_norm"),
            c_norm=t.get("ssm_c_norm"), w_dt=t["ssm_dt_proj"], dt_bias=t["ssm_dt_bias"],
            a_log=t["ssm_a_log"], d=t["ssm_d"], w_out=t["ssm_out"], rms=t["ssm_rms"])
    if "dense_w1" in t:
        dense = DenseFfnParams(
            w1=t["dense_w1"], w2=t["dense_w2"], w3=t["dense_w3"], rms_ffn=t["dense_rms_ffn"])
    if "moe_gate" in t:
        routed = RoutedFfnParams(
            gate=t["moe_gate"], bias=t.get("moe_bias"),
            w1=experts(t["w1"]), w2=experts(t["w2"]), w3=experts(t["w3"]),
            s1=t.get("shared_w1"), s2=t.get("shared_w2"), s3=t.get("shared_w3"),
            rms_ffn=t.get("rms_ffn"),  # none in a parallel block: the mixer's norm feeds it
        )
    return HybridParams(
        embedding=t["embedding"], attn=attn, conv=conv, dense=dense, routed=routed,
        rms_final=t["rms_final"], wcls=t["wcls"], rope_cos=rope_cos, rope_sin=rope_sin,
        ssm=ssm, linear=linear, rope_cos_w=rope_cos_w, rope_sin_w=rope_sin_w,
        delta=delta,
    )


def window_state(state, u, n_valid):
    """The one rule for a state that is a window of inputs. ``state``
    ``[B, K-1, d]``: the rows before this step's; ``u`` ``[B, T, d]``: this
    step's, of which the first ``n_valid`` ``[B]`` are real. Returns
    ``(window, new_state)``: ``concat(state, u)`` (row ``t + j`` of it is
    ``u_{t-(K-1)+j}``), and its rows ``[a - (K-1), a)`` in ``u``'s numbering,
    which is the state itself where ``a = 0``."""
    k1 = state.shape[1]
    window = jnp.concatenate([state.astype(u.dtype), u], axis=1)
    rows = n_valid[:, None] + jnp.arange(k1, dtype=jnp.int32)[None, :]  # [B, K-1]
    return window, jnp.take_along_axis(window, rows[:, :, None], axis=1)


def short_conv(window, taps, t: int):
    """``v_t = sum_j taps[j] * window[t + j]``, float32; window ``[B, K-1+T, d]``,
    taps ``[K, d]``."""
    wf = window.astype(jnp.float32)
    return sum(
        taps[j].astype(jnp.float32) * wf[:, j:j + t] for j in range(taps.shape[0])
    )


def output_gate(yq, w):
    """``sigmoid(W_g n)``, float32: what a gated mixer's output is multiplied
    by before its out-projection (the block-sparse kind, the full-context
    kind under ``attn_output_gate``, linear attention)."""
    return jax.nn.sigmoid(matmul(yq, w).astype(jnp.float32))


# the eps under a delta-rule layer's L2 norms of q and k
DELTA_L2_EPS = 1e-6


# layers of one kind in a row, inside a period, that run as a scan of their
# own and not unrolled: a period of thirteen state-space layers and one
# attention layer unrolled is fourteen layers compiled into every one of a
# dozen step programs (Jamba2-3B: 252 s of warm-up and executables that
# outgrew the compile cache, PR 43); LFM2's runs of one and two stay unrolled
RUN_SCAN_MIN = 4


def kind_runs(kinds: tuple) -> list:
    """``(start, length)`` of every run of equal neighbours in ``kinds``."""
    runs, start = [], 0
    for j in range(1, len(kinds) + 1):
        if j == len(kinds) or kinds[j] != kinds[start]:
            runs.append((start, j - start))
            start = j
    return runs


# a layer kind's place in the counts a layer is read by
KIND_SLOTS = (LayerKind.ATTENTION, LayerKind.CONV, LayerKind.SSM, LayerKind.WINDOW,
              LayerKind.LINEAR, LayerKind.SPARSE, LayerKind.DELTA)


def kinds_after(kind, nth: tuple) -> tuple:
    """The (attention, conv, state-space, window, linear, sparse, delta) counts after
    one more layer of ``kind``."""
    slot = KIND_SLOTS.index(kind)
    return tuple(n + (k == slot) for k, n in enumerate(nth))


def layer_periods(kinds: tuple) -> tuple[int, int]:
    """(period, whole periods) of a list of layer kinds: the shortest period
    whose repetitions cover all but an odd tail shorter than it."""
    n = len(kinds)
    for p in range(1, n + 1):
        whole = n // p
        if all(kinds[i] == kinds[i % p] for i in range(whole * p)):
            return p, whole
    return 1, 0


def hybrid_forward_counted(
    config: LlamaConfig,
    params: HybridParams,
    tokens: jnp.ndarray,  # [B, T] int32
    positions: jnp.ndarray,  # [B, T] int32
    cache: HybridCache,
    n_valid: jnp.ndarray | None = None,  # [B] int32: leading real rows a lane
    emulate_q80_activations: bool = False,
    mesh=None,
    q80_sync: bool = False,
    head_row: jnp.ndarray | None = None,  # [B] int32: the one row a lane whose logits are kept
):
    """(logits ``[B, T, vocab]`` f32, updated cache, counts), as
    ``deepseek_forward_counted``; ``counts`` None without routed layers. With
    ``head_row``, logits ``[B, 1, vocab]``: each lane's row at that index
    alone, as in ``llama_forward``."""
    if mesh is not None or q80_sync:
        raise ValueError("the layer-pattern block runs on one device: no mesh")
    if not isinstance(cache, HybridCache):
        raise ValueError("the layer-pattern block keeps a HybridCache")
    cfg = config
    b, t = tokens.shape
    eps, kinds = cfg.norm_epsilon, cfg.layer_kinds
    k_taps = cfg.conv_kernel
    ops = ffn_ops(cfg, emulate_q80_activations)
    maybe_qdq = ops.maybe_qdq
    if cfg.norm_kind == NormKind.LAYER:
        norm = lambda x, g: layer_norm(x, g, None, eps)  # noqa: E731
    else:
        norm = lambda x, g: rms_norm(x, g, eps)  # noqa: E731

    with jax.named_scope(SCOPE_EMBED):
        x = params.embedding[tokens]
        if cfg.embed_scale != 1.0:
            x = (x.astype(jnp.float32) * cfg.embed_scale).astype(x.dtype)
    dtype = x.dtype
    # the factor on every mixer's and FFN's term before it joins the stream
    res = cfg.residual_scale
    lane_idx = jnp.arange(b)[:, None]
    in_context = positions < cfg.seq_len
    live = in_context.reshape(b * t)
    if n_valid is None:
        n_valid = jnp.sum(in_context, axis=1).astype(jnp.int32)
    # one row a lane: the K/V stack is attended in place (module header)
    in_place = t == 1 and decode_attention_engages(cache, mesh, cfg.n_heads, cfg.n_kv_heads)
    window, ring = cfg.sliding_window, 0 if cache.wk is None else cache.wk.shape[2]
    ring_in_place = t == 1 and ring_attention_engages(
        cache, mesh, cfg.n_heads, cfg.kv_heads(windowed=True))
    # more rows a lane against many keys: a key block at a time (module header)
    plane_blocked = blocked_attention.engages(b, t, cfg.n_heads, cfg.seq_len)
    ring_blocked = blocked_attention.engages(b, t, cfg.n_heads, ring)
    # a prefill bucket's rows against a plane whose scores would be dense: in
    # place too, a key block at a time (models/llama.py, "How the cache is
    # read at prefill width")
    chunk_in_place = prefill_attention_engages(
        cache, mesh, b, t, cfg.n_heads, cfg.n_kv_heads)
    with jax.named_scope(SCOPE_ATTENTION):
        if in_place:
            attn_plan = pallas_attention.lane_blocks(positions, cfg.seq_len)
        elif chunk_in_place:
            attn_plan = pallas_attention.chunk_blocks(
                positions, n_valid, cfg.seq_len, pallas_attention.query_rows(t))
        elif not plane_blocked:
            s_idx = jnp.arange(cfg.seq_len)
            attn_mask = s_idx[None, None, :] <= positions[:, :, None]  # [B, T, S]
        if ring_in_place:
            ring_plan = pallas_attention.ring_blocks(positions, cfg.seq_len, window, ring)
        elif ring and not ring_blocked:
            ring_mask = blocked_attention.ring_mask(positions, ring, window)  # [B, T, R]
    row_major = Layout(major_to_minor=tuple(range(cache.k.ndim)))
    scale = 1.0 / float(cfg.head_size) ** 0.5
    if cfg.n_linear_layers:
        slopes = jnp.asarray(decay_slopes(cfg.linear_n_heads))
        lin_scale = 1.0 / float(cfg.linear_head_dim) ** 0.5
    from_zero = (positions[:, :1] == 0)[:, :, None]  # [B, 1, 1]
    # rows a state may absorb: the first n_valid of a lane (module header)
    real_row = (jnp.arange(t, dtype=jnp.int32)[None, :] < n_valid[:, None])[:, :, None]
    if cfg.n_sparse_layers:
        sizes = block_sparse.SparseSizes.of(cfg)
        n_blocks = cfg.seq_len // sizes.block_size
        sparse_in_place = t == 1 and block_sparse_engages(cache, mesh, cfg)
        # whether any real row chooses (stands at or past dense_len): else
        # every row takes the blocks it holds and nothing is scored
        any_sparse = jnp.any(real_row[:, :, 0] & in_context & (positions >= sizes.dense_len))
    if ring:
        # where a row's key and value go in a ring: position mod R; past the
        # ring (dropped) for a row that is not real or lies past the context
        ring_at = jnp.where(real_row[:, :, 0] & in_context, positions % ring, ring)

    def attention(x, ai, ci, k_all, v_all, windowed=False):
        """A full-context layer's or a window layer's attention half: weights
        at ``ai``, the kind's cache stack at ``ci``. Returns the stream with
        the half added (a parallel block: the half's term alone), the normed
        input, and the two stacks."""
        # the stacks in layer order are read at ai, a kind's own (its K/V
        # projections where the kinds' kv heads differ, a window layer's
        # sink) at the count of its kind, which is its cache stack's too
        pa = params.attn
        own = pa._replace(wk_w=None, wv_w=None, sink=None)
        if cfg.split_kv_kinds:
            own = own._replace(wk=None, wv=None)
        ap = GqaParams(*(_pick(leaf, ai) for leaf in own))
        if cfg.split_kv_kinds:
            wk, wv = (pa.wk_w, pa.wv_w) if windowed else (pa.wk, pa.wv)
            ap = ap._replace(wk=_pick(wk, ci), wv=_pick(wv, ci))
        sink = _pick(pa.sink, ci) if windowed else None
        rotate = windowed or not cfg.full_attention_nope
        cos, sin = params.rope_cos, params.rope_sin
        if windowed and params.rope_cos_w is not None:
            cos, sin = params.rope_cos_w, params.rope_sin_w
        n_kv = cfg.kv_heads(windowed)
        k_dim, v_dim = cfg.kv_widths(windowed)
        with jax.named_scope(SCOPE_QKV):
            y = norm(x, ap.rms)
            yq = maybe_qdq(y)
            q, k, v = gqa_project(
                cfg, yq, ap.wq, ap.wk, ap.wv, positions,
                cos if rotate else None, sin if rotate else None,
                norms=(ap.q_norm, ap.k_norm) if cfg.qk_norm else None, n_kv=n_kv,
            )
            gate = output_gate(yq, ap.gate) if ap.gate is not None else None
        with jax.named_scope(SCOPE_KV_WRITE):
            k_all, v_all = kv_append(
                k_all, v_all, (ci, lane_idx, ring_at if windowed else positions),
                k.reshape(b, t, k_dim), v.reshape(b, t, v_dim), row_major)
        with jax.named_scope(SCOPE_ATTENTION):
            if windowed:
                with jax.named_scope(SCOPE_WINDOW_ATTENTION):
                    attn = window_attention(q, ci, k_all, v_all, n_kv, sink)
            elif in_place:
                # the kernel fetches each lane's rows [0, pos] of attention
                # layer ai out of the carry, AFTER the append
                attn = pallas_attention.decode_attention(
                    q.reshape(b, cfg.n_heads, cfg.head_size), k_all, v_all, ci,
                    attn_plan, scale, interpret=pallas_interpret())
            elif chunk_in_place:
                attn = pallas_attention.prefill_attention(
                    q, k_all, v_all, ci, attn_plan, scale,
                    interpret=pallas_interpret())
            elif plane_blocked:
                attn = blocked_attention.blocked_attention(
                    q, k_all, v_all, ci, positions, n_valid, cfg.n_kv_heads, scale)
            else:
                attn = dense_plane_attention(
                    q, k_all, v_all, ci, attn_mask, scale, cfg.n_kv_heads)
            if cfg.attn_value_scale != 1.0:
                # every value is scaled: by linearity the float32 sum is
                attn = attn.astype(jnp.float32) * cfg.attn_value_scale
            attn = attn.reshape(b, t, cfg.o_dim)
            if gate is not None:
                attn = attn * gate
            attn = attn.astype(dtype)
        with jax.named_scope(SCOPE_ATTN_OUT):
            out = maybe_qdq(matmul(maybe_qdq(attn), ap.wo))
            if not cfg.parallel_block:
                out = x + out
        return out, y, k_all, v_all

    def sparse_attention(x, at, pi, k_all, v_all, ck_all):
        """A block-sparse layer's attention half: weights and planes at
        ``at``, compressed keys at ``pi``. Queries and keys are normed and
        not rotated; the rows choose their blocks (ops/block_sparse.py); the
        output is gated before ``wo``."""
        ap = GqaParams(*(_pick(leaf, at) for leaf in params.attn))
        with jax.named_scope(SCOPE_QKV):
            y = norm(x, ap.rms)
            yq = maybe_qdq(y)
            q, k, v = gqa_project(
                cfg, yq, ap.wq, ap.wk, ap.wv, positions, None, None,
                norms=(ap.q_norm, ap.k_norm) if cfg.qk_norm else None)
            gate = output_gate(yq, ap.gate)
        with jax.named_scope(SCOPE_KV_WRITE):
            k_all, v_all = kv_append(
                k_all, v_all, (at, lane_idx, positions),
                k.reshape(b, t, cfg.kv_dim), v.reshape(b, t, cfg.kv_dim), row_major)
        with jax.named_scope(SCOPE_BLOCK_SCORES):
            # row-major like the planes (kv_append says why): left free, XLA
            # gave the stack the score pass's layout and copied it whole
            ck_all = with_layout_constraint(block_sparse.append_compressed(
                ck_all, k_all, pi, at, positions, n_valid, sizes), row_major)
        held = block_sparse.held_blocks(positions, n_blocks, sizes)

        def choosing():
            with jax.named_scope(SCOPE_BLOCK_SCORES):
                r = block_sparse.block_scores(
                    q, ck_all, pi, positions, cfg.n_kv_heads, sizes, scale)
            with jax.named_scope(SCOPE_SPARSE_SELECT):
                return block_sparse.choose(r, positions, sizes)

        chosen = jax.lax.cond(
            any_sparse, choosing,
            lambda: jnp.broadcast_to(held, (b, t, cfg.n_kv_heads, n_blocks)))
        if sparse_in_place:
            with jax.named_scope(SCOPE_SPARSE_SELECT):
                work = block_sparse.chosen_list(chosen, positions, cfg.seq_len, sizes)
            with jax.named_scope(SCOPE_ATTENTION):
                attn = pallas_attention.sparse_decode_attention(
                    q.reshape(b, cfg.n_heads, cfg.head_size), k_all, v_all, at, work,
                    scale, sizes.block_size, interpret=pallas_interpret())
        else:
            with jax.named_scope(SCOPE_ATTENTION):
                attn = blocked_attention.blocked_attention(
                    q, k_all, v_all, at, positions, n_valid, cfg.n_kv_heads, scale,
                    chosen=chosen, chosen_block=sizes.block_size)
        with jax.named_scope(SCOPE_ATTN_OUT):
            gated = (attn.reshape(b, t, cfg.q_dim) * gate).astype(dtype)
            x = x + res * maybe_qdq(matmul(maybe_qdq(gated), ap.wo))
        return x, k_all, v_all, ck_all

    def linear(x, li, lin_all):
        """A linear-attention layer: queries and keys normed per head and
        rotated, the decayed matrix state (ops/linear_attention.py), the
        output normed per head and gated."""
        lp = LinearParams(*(_pick(leaf, li) for leaf in params.linear))
        heads = (b, t, cfg.linear_n_heads, cfg.linear_head_dim)
        with jax.named_scope(SCOPE_LINEAR_ATTENTION):
            y = norm(x, lp.rms)
            yq = maybe_qdq(y)
            q = rms_norm(matmul(yq, lp.wq).reshape(heads), lp.q_norm, eps)
            k = rms_norm(matmul(yq, lp.wk).reshape(heads), lp.k_norm, eps)
            v = matmul(yq, lp.wv).reshape(heads)
            q = apply_rope(q, params.rope_cos, params.rope_sin, positions)
            k = apply_rope(k, params.rope_cos, params.rope_sin, positions)
            gate = output_gate(yq, lp.gate)
            o, lin_all = linear_attention(
                lin_all, li, from_zero, q, k, v, real_row[:, :, 0], slopes, lin_scale)
            o = rms_norm(o, lp.o_norm, eps).reshape(b, t, cfg.linear_dim)
            x = x + res * maybe_qdq(matmul(maybe_qdq((o * gate).astype(dtype)), lp.w_out))
        return x, lin_all

    def delta(x, di, s_all, w_all):
        """A delta-rule layer: q, k and v through their short convs and a
        silu, q and k L2-normed per head, the decay a key channel and the step
        a head from the normed input, the matrix state (ops/delta_rule.py),
        the output normed per head and gated."""
        dp = DeltaParams(*(_pick(leaf, di) for leaf in params.delta))
        n_h, d = cfg.delta_n_heads, cfg.delta_head_dim
        heads, f32 = (b, t, n_h, d), jnp.float32
        # the float32 factors (what steers the state: module header)
        low_rank = lambda y, w: jnp.einsum(  # noqa: E731
            "btr,rd->btd", y.astype(f32), w, precision=jax.lax.Precision.HIGHEST)
        with jax.named_scope(SCOPE_DELTA):
            y = norm(x, dp.rms)
            yq = maybe_qdq(y)
            qkv = jnp.concatenate([matmul(yq, w) for w in (dp.wq, dp.wk, dp.wv)], axis=-1)
            with jax.named_scope(SCOPE_DELTA_CONV):
                window, w_all = window_step(w_all, di, qkv, cfg.delta_conv_kernel)
                qkv = jax.nn.silu(short_conv(window, dp.taps, t))  # float32
            q, k, v = (a.reshape(heads) for a in jnp.split(qkv, 3, axis=-1))
            l2 = lambda a: a * jax.lax.rsqrt(  # noqa: E731
                jnp.sum(a * a, axis=-1, keepdims=True) + DELTA_L2_EPS)
            q, k = l2(q) * (1.0 / float(d) ** 0.5), l2(k)
            # the decay steers an exponential: a float32 product
            g = jax.nn.softplus(low_rank(matmul(yq, dp.f1), dp.f2) + dp.dt_bias)
            g = -jnp.exp(dp.a_log)[:, None] * g.reshape(heads)
            beta = jax.nn.sigmoid(low_rank(yq, dp.wb))
            if cfg.delta_neg_eigval:
                beta = 2.0 * beta
            gate = jax.nn.sigmoid(low_rank(matmul(yq, dp.g1), dp.g2))
            o, s_all = delta_rule(s_all, di, from_zero, q, k, v, g, beta, real_row[:, :, 0])
            o = rms_norm(o, dp.o_norm, eps).reshape(b, t, cfg.delta_dim)
            x = x + maybe_qdq(matmul(maybe_qdq((o * gate).astype(dtype)), dp.w_out))
        return x, s_all, w_all

    def window_attention(q, wi, k_all, v_all, n_kv, sink):
        """Window layer ``wi``'s read of its ring, after the append: the
        decode kernel over the blocks that hold ``(pos - W, pos]``, a key
        block at a time, or dense under the ring's mask (module header); the
        sink's column (None: none) joins the softmax in all three."""
        if ring_in_place:
            return pallas_attention.decode_attention(
                q.reshape(b, cfg.n_heads, cfg.head_size), k_all, v_all, wi,
                ring_plan, scale, interpret=pallas_interpret(), sink=sink)
        if ring_blocked:
            return blocked_attention.blocked_attention(
                q, k_all, v_all, wi, positions, n_valid, n_kv, scale, window=window,
                sink=sink)
        return dense_plane_attention(q, k_all, v_all, wi, ring_mask, scale, n_kv, sink)

    def window_step(w_all, wi, u, taps_k: int):
        """Layer ``wi``'s window of inputs read (zeros where the step starts
        a sequence: module header), joined with this step's rows and
        committed; returns ``(window, the stack)``."""
        state = jax.lax.dynamic_index_in_dim(w_all, wi, 0, keepdims=False)
        state = state.reshape(b, taps_k - 1, u.shape[-1])
        state = jnp.where(from_zero, jnp.zeros_like(state), state)
        window, new_state = window_state(state, u, n_valid)
        return window, w_all.at[wi].set(
            _to_cache_dtype(new_state, w_all.dtype).reshape(b, -1))

    def conv(x, ci, s_all):
        cp = ConvParams(*(_pick(leaf, ci) for leaf in params.conv))
        with jax.named_scope(SCOPE_CONV):
            y = norm(x, cp.rms)
            bcx = matmul(maybe_qdq(y), cp.w_in)  # [B, T, 3 * dim]
            gate_b, gate_c, xin = jnp.split(bcx, 3, axis=-1)
            u = gate_b * xin
            with jax.named_scope(SCOPE_CONV_STATE):
                window, s_all = window_step(s_all, ci, u, k_taps)
            v = short_conv(window, cp.taps, t).astype(dtype)
            x = x + maybe_qdq(matmul(maybe_qdq(gate_c * v), cp.w_out))
        return x, s_all

    def ssm(x, si, ssm_all, win_all):
        sp = SsmParams(*(_pick(leaf, si) for leaf in params.ssm))
        n_state, rank = cfg.ssm_d_state, cfg.ssm_dt_rank
        f32 = jnp.float32
        with jax.named_scope(SCOPE_SSM):
            y = norm(x, sp.rms)
            xin, z = jnp.split(matmul(maybe_qdq(y), sp.w_in), 2, axis=-1)  # [B, T, E] each
            window, win_all = window_step(win_all, si, xin, cfg.ssm_conv_kernel)
            c = short_conv(window, sp.taps, t)
            if sp.conv_bias is not None:
                c = c + sp.conv_bias
            u = jax.nn.silu(c)  # float32: the recurrence's input
            dbc = matmul(maybe_qdq(u.astype(dtype)), sp.w_x).astype(f32)
            dt, bm, cm = jnp.split(dbc, (rank, rank + n_state), axis=-1)
            if cfg.ssm_inner_norms:
                dt = rms_norm(dt, sp.dt_norm, eps)
                bm = rms_norm(bm, sp.b_norm, eps)
                cm = rms_norm(cm, sp.c_norm, eps)
            # the step size steers an exponential: a float32 product
            delta = jax.nn.softplus(
                jnp.einsum("btr,re->bte", dt, sp.w_dt,
                           precision=jax.lax.Precision.HIGHEST) + sp.dt_bias)
            # a row past the lane's real ones moves nothing (module header)
            delta = jnp.where(real_row, delta, 0.0)
            out, ssm_all = state_step(
                ssm_all, si, from_zero, delta, u, bm, cm, sp.a_log, sp.d)
            gated = (out * jax.nn.silu(z.astype(f32))).astype(dtype)
            x = x + maybe_qdq(matmul(maybe_qdq(gated), sp.w_out))
        return x, ssm_all, win_all

    # the carry: the stream, then every state stack (a kind the block lacks
    # is None and no leaf), then a routed model's counts (ROUTED_COUNTS)
    def mixer(kind, carry, ai, ci, si, wi, li, pi, di):
        """The layer's mixer on the carry; with it, in a parallel block, the
        mixer's term and the normed input it read (else None)."""
        (x, k_all, v_all, s_all, ssm_all, win_all, wk_all, wv_all, lin_all, ck_all,
         dl_all, dlw_all, *counts) = carry
        parallel = None
        if kind == LayerKind.CONV:
            x, s_all = conv(x, ci, s_all)
        elif kind == LayerKind.SSM:
            x, ssm_all, win_all = ssm(x, si, ssm_all, win_all)
        elif kind == LayerKind.LINEAR:
            x, lin_all = linear(x, li, lin_all)
        elif kind == LayerKind.DELTA:
            x, dl_all, dlw_all = delta(x, di, dl_all, dlw_all)
        elif kind == LayerKind.SPARSE:
            x, k_all, v_all, ck_all = sparse_attention(x, ai + pi, pi, k_all, v_all, ck_all)
        else:
            # both kinds of attention layer: one stack of weights, in layer
            # order, and a stack of cache a kind
            at = ai + wi if cfg.n_window_layers else ai
            if kind == LayerKind.WINDOW:
                out, normed, wk_all, wv_all = attention(x, at, wi, wk_all, wv_all, True)
            else:
                out, normed, k_all, v_all = attention(x, at, ai, k_all, v_all)
            x, parallel = (x, (out, normed)) if cfg.parallel_block else (out, None)
        return (x, k_all, v_all, s_all, ssm_all, win_all, wk_all, wv_all, lin_all, ck_all,
                dl_all, dlw_all, *counts), parallel

    def kinds_before(lo, hi):
        """Layers of each kind (``KIND_SLOTS``) among ``kinds[lo:hi]``."""
        return tuple(sum(k == slot for k in kinds[lo:hi]) for slot in KIND_SLOTS)

    def dense_layer(kind, carry, nth, l):
        (x, *rest), _ = mixer(kind, carry, *nth)
        dp = DenseFfnParams(*(_pick(leaf, l) for leaf in params.dense))
        return (dense_ffn(cfg, ops, x, dp, res), *rest)

    def routed_layer(kind, carry, nth, lm):
        (x, *rest), parallel = mixer(kind, carry, *nth)
        stacks, counts = rest[:-len(ROUTED_COUNTS)], rest[-len(ROUTED_COUNTS):]
        rp = RoutedFfnParams(*(_pick(leaf, lm) for leaf in params.routed))
        if parallel is None:
            x, *more = routed_ffn(cfg, ops, x, rp, lm, live)
        else:
            # one norm fed both halves; their terms join the stream together
            mixed, normed = parallel
            ffn, *more = routed_ffn(cfg, ops, x, rp, lm, live, normed=normed)
            x = x + mixed + ffn
        # the pairs outside a held share, routed_ffn's last, are not carried here
        return (x, *stacks, *(c + m for c, m in zip(counts, more)))

    routed = params.routed is not None
    n_lead = cfg.n_dense_layers if routed else 0
    with jax.named_scope(SCOPE_LAYERS):
        carry = (x, *cache)
        for l in range(n_lead):  # a routed model's leading dense layers, before the scan
            nth = tuple(jnp.int32(n) for n in kinds_before(0, l))
            carry = dense_layer(kinds[l], carry, nth, jnp.int32(l))

        # the others: whole periods of their kinds in one scan, then the odd
        # tail; the FFN routed where the model routes, else dense by layer
        body_kinds = kinds[n_lead:]
        period, whole = layer_periods(body_kinds)
        before = kinds_before(0, n_lead)
        per = kinds_before(n_lead, n_lead + period)
        if routed:
            zero = jnp.zeros((), jnp.int32)
            carry = (*carry, *(zero,) * len(ROUTED_COUNTS))
        # a layer's FFN is read at its count after the leading layers (none
        # lead where every layer is dense: the dense stacks count from 0)
        layer = routed_layer if routed else dense_layer

        def period_step(carry, i):
            # a whole period of layers, each reading its kind's stacks at
            # the count of that kind before it; a long run of one kind is a
            # scan of its own (kind_runs)
            for j, n_run in kind_runs(body_kinds[:period]):
                within = kinds_before(n_lead, n_lead + j)
                nth = tuple(n0 + i * n_per + n_in
                            for n0, n_per, n_in in zip(before, per, within))
                if n_run < RUN_SCAN_MIN:
                    for r in range(n_run):  # every kind's count but the run's own stands still
                        carry = layer(body_kinds[j], carry, nth, i * period + j)
                        j, nth = j + 1, kinds_after(body_kinds[j], nth)
                    continue

                def run_step(carry, r, j=j, nth=nth):
                    at = tuple(n + r * (m - n) for n, m in zip(nth, kinds_after(body_kinds[j], nth)))
                    return layer(body_kinds[j], carry, at, i * period + j + r), None

                carry, _ = jax.lax.scan(run_step, carry, jnp.arange(n_run, dtype=jnp.int32))
            return carry, None

        if whole:
            carry, _ = jax.lax.scan(
                period_step, carry, jnp.arange(whole, dtype=jnp.int32))
        # the odd tail, unrolled but for its long runs of one kind (a list
        # that repeats nothing is a "period" of over half of it and a tail)
        tail = n_lead + whole * period
        for j, n_run in kind_runs(kinds[tail:]):
            l = tail + j
            nth = tuple(jnp.int32(n) for n in kinds_before(0, l))
            if n_run < RUN_SCAN_MIN:
                for r in range(n_run):
                    carry = layer(kinds[l + r], carry, nth, jnp.int32(l + r - n_lead))
                    nth = kinds_after(kinds[l + r], nth)
                continue

            def tail_run(carry, r, l=l, nth=nth):
                at = tuple(n + r * (m - n) for n, m in zip(nth, kinds_after(kinds[l], nth)))
                return layer(kinds[l], carry, at, l - n_lead + r), None

            carry, _ = jax.lax.scan(tail_run, carry, jnp.arange(n_run, dtype=jnp.int32))
        x, *stacks = carry
        counts = None
        if routed:
            counts = tuple(stacks[-len(ROUTED_COUNTS):])
            stacks = stacks[:-len(ROUTED_COUNTS)]

    logits = head(
        x, lambda x: norm(x, params.rms_final), params.wcls, cfg.vocab_size,
        head_row=head_row, logit_divisor=cfg.logit_divisor, qdq=maybe_qdq,
    )
    return logits, HybridCache(*stacks), counts
