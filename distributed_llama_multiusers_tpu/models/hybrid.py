"""A block whose layers differ in their mixer (``model_type: lfm2_moe``):
gated short convolutions and GQA attention in a published per-layer pattern,
leading dense FFNs, then routed ones. Assembled from the parts of the other
two blocks, with ``llama_forward``'s signature: the GQA projection, append and
plane attention are ``models/llama.py``'s, the router, the route plan, the
grouped kernel and the gated FFN ``models/deepseek.py``'s. New here are the
conv mixer, its state, and a forward over a list of layer kinds.

The layer (``h`` the stream, ``K = conv_kernel``):

    n = rmsnorm(h, g_op)
    conv:       [B; C; X] = W_in n          three parts of ``dim``, in that order
                u_t = B_t * X_t
                v_t = sum_{j<K} w[j] * u_{t-(K-1)+j}      depthwise, causal, u_{<0} = 0
                h' = h + W_out (C_t * v_t)
    attention:  q, k normed per head (where ``qk_norm``), rotated, GQA over the cache
                h' = h + Wo o
    FFN:        dense in the first ``n_dense_layers`` layers, routed in the others

The state. Each kind of layer keeps its own stack, indexed by the count of
that kind: ``k`` and ``v`` ``[attention layers, lanes, S, n_kv * head]`` (no
plane for a conv layer), and the conv layers' window of inputs
``[conv layers, lanes, (K-1) * dim]``: a lane's last ``K - 1`` rows of ``u``.
Both are flat in their last axis, so that it is whole tiles of a TPU's 128
lanes: with a 64-wide head as the last axis XLA gave the K/V stack another
layout inside the layer loop and copied it whole, in and out, every step
(compiled for a described v5e, PR 35), and a size-one axis before the last
cost whole-stack copies before (PERF.md section 6, PR 33). All three ride the carry and are written in place;
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

The layers. The leading dense layers run first, unrolled; the routed layers
run as one ``lax.scan`` over whole periods of their kinds (the shortest
period the published list repeats with; its layers unrolled inside the body,
each computing only its own mixer) and the odd tail unrolled after it. Every
weight stack is closed over and read at its index (``deepseek._pick``).
Attention at one row a lane (``t == 1``: every decode step and the decode half
of every fused step) reads the K/V stack in place, as ``models/llama.py``'s
block does ("How the cache is read at decode width"): the decode kernel
(ops/pallas_attention.py) is handed the merged stack as it sits, the count of
attention layers before this one and the lanes' positions, and fetches for
each lane the row blocks up to its position. Wider steps, the CPU, a float32
or f8 cache and a context that is not whole blocks take XLA's dense path over
the layer's whole plane (``llama.decode_attention_engages`` decides, from the
inputs). Dense, the five planes of a 64-lane step were 1.34 GB read and
converted whatever the lanes held: 10.2 of a 28 ms decode step on a v5e
(PERF.md section 6, PR 36).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout

from ..formats.model_file import LayerKind
from ..ops import pallas_attention
from ..ops.linear import matmul, pallas_interpret
from ..ops.norm import rms_norm
from ..quants.packed import PackedQ40, Q40Experts
from ..telemetry.names import (
    SCOPE_ATTENTION,
    SCOPE_ATTN_OUT,
    SCOPE_CONV,
    SCOPE_CONV_STATE,
    SCOPE_EMBED,
    SCOPE_HEAD,
    SCOPE_KV_WRITE,
    SCOPE_LAYERS,
    SCOPE_QKV,
)
from .config import LlamaConfig
from .deepseek import (
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
)


class GqaParams(NamedTuple):
    """The attention layers' weights, stacked ``[attention layers, ...]``."""

    wq: jnp.ndarray  # [La, dim, dim]
    wk: jnp.ndarray  # [La, dim, kv_dim]
    wv: jnp.ndarray
    wo: jnp.ndarray  # [La, dim, dim]
    q_norm: jnp.ndarray | None  # [La, head] f32 (config.qk_norm)
    k_norm: jnp.ndarray | None
    rms: jnp.ndarray  # [La, dim]: the layer's operator norm


class ConvParams(NamedTuple):
    """The conv layers' weights, stacked ``[conv layers, ...]``."""

    w_in: jnp.ndarray  # [Lc, dim, 3 * dim]: B, C, x
    taps: jnp.ndarray  # [Lc, K, dim] f32: tap j multiplies u_{t-(K-1)+j}
    w_out: jnp.ndarray  # [Lc, dim, dim]
    rms: jnp.ndarray  # [Lc, dim]: the layer's operator norm


class HybridParams(NamedTuple):
    embedding: jnp.ndarray  # [vocab, dim]
    attn: GqaParams | None
    conv: ConvParams | None
    dense: DenseFfnParams | None
    routed: RoutedFfnParams | None
    rms_final: jnp.ndarray
    wcls: jnp.ndarray
    rope_cos: jnp.ndarray  # [seq_len, head_size // 2] f32
    rope_sin: jnp.ndarray


class HybridCache(NamedTuple):
    """A lane's state by kind of layer; the lane axis is axis 1 of each."""

    k: jnp.ndarray  # [La, lanes, S, n_kv * head]
    v: jnp.ndarray
    conv: jnp.ndarray  # [Lc, lanes, (K-1) * dim]: the last K-1 rows of u


def init_hybrid_cache(config: LlamaConfig, n_lanes: int, dtype=jnp.float32) -> HybridCache:
    kv = (config.n_attention_layers, n_lanes, config.seq_len, config.kv_dim)
    return HybridCache(
        k=jnp.zeros(kv, dtype), v=jnp.zeros(kv, dtype),
        conv=jnp.zeros(
            (config.n_conv_layers, n_lanes, (config.conv_kernel - 1) * config.dim), dtype),
    )


def hybrid_params(t: dict, rope_cos, rope_sin) -> HybridParams:
    """The parameter tree around a model's arrays, by the tensor names of the
    ``.m`` walk without their ``block_`` prefix: what the loader and a
    benchmark's generator both hand over. Expert stacks that arrive as
    ``PackedQ40`` become ``Q40Experts``."""
    def experts(w):
        return Q40Experts.from_packed(w) if isinstance(w, PackedQ40) else w

    attn = conv = dense = routed = None
    if "wq" in t:
        attn = GqaParams(
            wq=t["wq"], wk=t["wk"], wv=t["wv"], wo=t["wo"],
            q_norm=t.get("q_norm"), k_norm=t.get("k_norm"), rms=t["attn_rms"],
        )
    if "conv_in" in t:
        conv = ConvParams(
            w_in=t["conv_in"], taps=t["conv_taps"], w_out=t["conv_out"], rms=t["conv_rms"])
    if "dense_w1" in t:
        dense = DenseFfnParams(
            w1=t["dense_w1"], w2=t["dense_w2"], w3=t["dense_w3"], rms_ffn=t["dense_rms_ffn"])
    if "moe_gate" in t:
        routed = RoutedFfnParams(
            gate=t["moe_gate"], bias=t.get("moe_bias"),
            w1=experts(t["w1"]), w2=experts(t["w2"]), w3=experts(t["w3"]),
            s1=None, s2=None, s3=None, rms_ffn=t["rms_ffn"],
        )
    return HybridParams(
        embedding=t["embedding"], attn=attn, conv=conv, dense=dense, routed=routed,
        rms_final=t["rms_final"], wcls=t["wcls"], rope_cos=rope_cos, rope_sin=rope_sin,
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
):
    """(logits ``[B, T, vocab]`` f32, updated cache, counts), as
    ``deepseek_forward_counted``; ``counts`` None without routed layers."""
    if mesh is not None or q80_sync:
        raise ValueError("the layer-pattern block runs on one device: no mesh")
    if not isinstance(cache, HybridCache):
        raise ValueError("the layer-pattern block keeps a HybridCache")
    cfg = config
    b, t = tokens.shape
    eps, kinds = cfg.norm_epsilon, cfg.layer_kinds
    k_taps = cfg.conv_kernel
    quantized = isinstance(
        params.conv.w_in if params.conv is not None else params.attn.wq, PackedQ40)
    ops = ffn_ops(cfg, emulate_q80_activations, quantized)
    maybe_qdq, share_q80 = ops.maybe_qdq, ops.share_q80

    with jax.named_scope(SCOPE_EMBED):
        x = params.embedding[tokens]
    dtype = x.dtype
    lane_idx = jnp.arange(b)[:, None]
    in_context = positions < cfg.seq_len
    live = in_context.reshape(b * t)
    if n_valid is None:
        n_valid = jnp.sum(in_context, axis=1).astype(jnp.int32)
    # one row a lane: the K/V stack is attended in place (module header)
    in_place = t == 1 and decode_attention_engages(cache, mesh, cfg.n_heads, cfg.n_kv_heads)
    with jax.named_scope(SCOPE_ATTENTION):
        s_idx = jnp.arange(cfg.seq_len)
        attn_mask = s_idx[None, None, :] <= positions[:, :, None]  # [B, T, S]
        if in_place:
            attn_plan = pallas_attention.lane_blocks(positions, cfg.seq_len)
    row_major = Layout(major_to_minor=tuple(range(cache.k.ndim)))
    scale = 1.0 / float(cfg.head_size) ** 0.5
    from_zero = (positions[:, :1] == 0)[:, :, None]  # [B, 1, 1]

    def attention(x, ai, k_all, v_all):
        ap = GqaParams(*(_pick(leaf, ai) for leaf in params.attn))
        with jax.named_scope(SCOPE_QKV):
            y = rms_norm(x, ap.rms, eps)
            yq = share_q80(maybe_qdq(y))  # one operand build for wq/wk/wv
            q, k, v = gqa_project(
                cfg, yq, ap.wq, ap.wk, ap.wv, positions, params.rope_cos, params.rope_sin,
                norms=(ap.q_norm, ap.k_norm) if cfg.qk_norm else None,
            )
        with jax.named_scope(SCOPE_KV_WRITE):
            k_all, v_all = kv_append(
                k_all, v_all, (ai, lane_idx, positions),
                k.reshape(b, t, cfg.kv_dim), v.reshape(b, t, cfg.kv_dim), row_major)
        with jax.named_scope(SCOPE_ATTENTION):
            if in_place:
                # the kernel fetches each lane's rows [0, pos] of attention
                # layer ai out of the carry, AFTER the append
                attn = pallas_attention.decode_attention(
                    q.reshape(b, cfg.n_heads, cfg.head_size), k_all, v_all, ai,
                    attn_plan, scale, interpret=pallas_interpret())
            else:
                attn = dense_plane_attention(
                    q, k_all, v_all, ai, attn_mask, scale, cfg.n_kv_heads)
            attn = attn.reshape(b, t, cfg.dim).astype(dtype)
        with jax.named_scope(SCOPE_ATTN_OUT):
            x = x + maybe_qdq(matmul(maybe_qdq(attn), ap.wo))
        return x, k_all, v_all

    def conv(x, ci, s_all):
        cp = ConvParams(*(_pick(leaf, ci) for leaf in params.conv))
        with jax.named_scope(SCOPE_CONV):
            y = rms_norm(x, cp.rms, eps)
            bcx = matmul(maybe_qdq(y), cp.w_in)  # [B, T, 3 * dim]
            gate_b, gate_c, xin = jnp.split(bcx, 3, axis=-1)
            u = gate_b * xin
            with jax.named_scope(SCOPE_CONV_STATE):
                state = jax.lax.dynamic_index_in_dim(s_all, ci, 0, keepdims=False)
                state = state.reshape(b, k_taps - 1, cfg.dim)
                # a step that starts a sequence reads zeros, whatever the
                # lane held (module header)
                state = jnp.where(from_zero, jnp.zeros_like(state), state)
                window, new_state = window_state(state, u, n_valid)
                s_all = s_all.at[ci].set(
                    _to_cache_dtype(new_state, s_all.dtype).reshape(b, -1))
            v = short_conv(window, cp.taps, t).astype(dtype)
            x = x + maybe_qdq(matmul(maybe_qdq(gate_c * v), cp.w_out))
        return x, s_all

    def mixer(kind, x, ai, ci, k_all, v_all, s_all):
        if kind == LayerKind.CONV:
            x, s_all = conv(x, ci, s_all)
        else:
            x, k_all, v_all = attention(x, ai, k_all, v_all)
        return x, k_all, v_all, s_all

    def kinds_before(lo, hi):
        n_conv = sum(k == LayerKind.CONV for k in kinds[lo:hi])
        return (hi - lo) - n_conv, n_conv

    def routed_layer(kind, carry, ai, ci, lm):
        x, k_all, v_all, s_all, slabs, assigned = carry
        x, k_all, v_all, s_all = mixer(kind, x, ai, ci, k_all, v_all, s_all)
        rp = RoutedFfnParams(*(_pick(leaf, lm) for leaf in params.routed))
        x, s, a, _ = routed_ffn(cfg, ops, x, rp, lm, live)
        return (x, k_all, v_all, s_all, slabs + s, assigned + a)

    n_dense = cfg.n_dense_layers if params.routed is not None else cfg.n_layers
    with jax.named_scope(SCOPE_LAYERS):
        k_all, v_all, s_all = cache
        for l in range(n_dense):  # the leading dense layers, before the scan
            ai, ci = (jnp.int32(n) for n in kinds_before(0, l))
            x, k_all, v_all, s_all = mixer(kinds[l], x, ai, ci, k_all, v_all, s_all)
            dp = DenseFfnParams(*(_pick(leaf, jnp.int32(l)) for leaf in params.dense))
            x = dense_ffn(cfg, ops, x, dp)

        counts = None
        if params.routed is not None:
            routed_kinds = kinds[n_dense:]
            period, whole = layer_periods(routed_kinds)
            a0, c0 = kinds_before(0, n_dense)
            a_per, c_per = kinds_before(n_dense, n_dense + period)
            zero = jnp.zeros((), jnp.int32)
            carry = (x, k_all, v_all, s_all, zero, zero)

            def period_step(carry, i):
                # a whole period of layers, each reading its kind's stacks at
                # the count of that kind before it
                for j in range(period):
                    a_in, c_in = kinds_before(n_dense, n_dense + j)
                    carry = routed_layer(
                        routed_kinds[j], carry, a0 + i * a_per + a_in,
                        c0 + i * c_per + c_in, i * period + j)
                return carry, None

            if whole:
                carry, _ = jax.lax.scan(
                    period_step, carry, jnp.arange(whole, dtype=jnp.int32))
            for l in range(n_dense + whole * period, cfg.n_layers):  # the odd tail
                ai, ci = (jnp.int32(n) for n in kinds_before(0, l))
                carry = routed_layer(kinds[l], carry, ai, ci, jnp.int32(l - n_dense))
            x, k_all, v_all, s_all, slabs, assigned = carry
            counts = (slabs, assigned)

    with jax.named_scope(SCOPE_HEAD):
        y = rms_norm(x, params.rms_final, eps)
        logits = matmul(maybe_qdq(y), params.wcls).astype(jnp.float32)
        logits = logits[..., : cfg.vocab_size]
    return logits, HybridCache(k=k_all, v=v_all, conv=s_all), counts
