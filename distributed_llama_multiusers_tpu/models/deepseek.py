"""The latent-attention block with a routed FFN (``model_type: deepseek_v3``),
beside ``models/llama.py``'s and with its forward's signature.

The layer (``h`` the stream; published keys in brackets):

    n  = rmsnorm(h, g_att)
    q  = Wq n                  heads of [q_nope (qk_nope_head_dim); q_pe (qk_rope_head_dim)]
    [c; k_pe] = Wkva n         ONE row a token: kv_lora_rank + qk_rope_head_dim
    c' = rmsnorm(c, g_kv)
    [k_nope_i; v_i] = Wkvb_i c'    (a head: qk_nope_head_dim + v_head_dim)
    rotary embedding, interleaved pairs, on q_pe and on the one k_pe
    s_i(t, u) = (q_nope_i(t) . k_nope_i(u) + q_pe_i(t) . k_pe(u)) / sqrt(nope + rope)
    h' = h + Wo [o_1 .. o_H],  o_i = sum_u softmax(s_i)(u) v_i(u)

It is computed in the ABSORBED form at every width: ``q~_i = Wuk_i^T q_nope_i``
(``kv_lora_rank`` wide), ``s_i = (q~_i . c' + q_pe_i . k_pe) / sqrt(..)``,
``o~_i = sum p_i c'``, ``o_i = Wuv_i o~_i``: all query heads against one shared
key row of ``rank + rope`` numbers and one value row of ``rank`` a token.

The cache. One row a token a layer, in the cache dtype: ``KVCache.k`` holds
``c'`` as ``[L, lanes, S, kv_lora_rank]`` and ``KVCache.v`` the ROTATED
``k_pe`` as ``[L, lanes, S, rope_leaf_width]``, zero past ``qk_rope_head_dim``
(``ROPE_LEAF_ALIGN``): the Llama block's two-leaf
tree with other leaf shapes, so the engine's lane splice, lane copy and
admitted-lane slice (all along axis 1) hold as they are. There is no head
axis: a size-one axis before the last would be padded to a whole tile of
sublanes on a TPU, and XLA copied the whole stack to be rid of it (compiled
for a described v5e, PR 33); and a last axis of 64 is half a tile of lanes,
for which XLA copied the whole rope leaf once a layer and scattered into it at
a tenth of the speed (42 of a 70 ms decode step on a v5e, PERF.md section 6,
PR 33), so the rope leaf is padded to whole tiles: 1280 bytes a token a layer
in bf16 at rank 512, not 1152. Both ride the layer scan's
carry and are appended in place, then read, exactly as K and V are
(``models/llama.py``, "How the cache moves"). Attention is XLA's dense path
over the layer's latent plane sliced out of the carry; the in-place decode
kernel (ops/pallas_attention.py) takes K and V stacks of heads (128-wide on
an axis of their own, or narrower ones merged into rows of whole tiles), not
a latent row and its rope part, and does not engage.

The FFN. The first ``n_dense_layers`` layers run a dense gated FFN, before
the scan; the others are the scan: a router in float32 (sigmoid or softmax
scores over all experts, the ``n_active_experts`` largest of score + selection
bias chosen, weights the chosen scores, renormalised, times
``moe_routed_scale``), the routed experts through the grouped Q40 kernel
(ops/pallas_q40_grouped.py: rows sorted by expert, the slabs read by layer
index and expert id out of the ``[L, E, ...]`` stacks), and the shared experts
as one gated FFN of two ordinary matmul sites. A parked row (position
``>= seq_len``) routes nowhere and fetches nothing.

``Wkvb`` is Q40 at rest; the step wants it a head at a time in two
orientations, so the parameter tree holds its dequantized values in the
activation dtype (``latent_params``: 2 x rank x heads x (nope + v) bytes a
layer in bf16).

Not served by this block, and refused by ``InferenceEngine`` at start-up: the
paged pool (and with it prefix page sharing, the host tier and KV-page
transfer: pages are framed as a K/V pair of heads), and any mesh.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout

from ..formats.model_file import HiddenAct, MoeScore
from ..ops.activations import gelu, silu
from ..ops.linear import (
    matmul,
    pallas_interpret,
    pallas_kernel_active,
    pallas_w_dtype_kw,
    reads_q40_stack,
    shared_q80_acts,
)
from ..ops.norm import rms_norm
from ..ops.pallas_q40_grouped import (
    grouped_matmul_xla,
    grouped_supports,
    q40_grouped_pallas,
    route_plan,
)
from ..ops.rope import apply_rope
from ..quants.packed import PackedQ40, Q40Experts, Q40Layer, unpack_q40
from ..telemetry.names import (
    SCOPE_ATTENTION,
    SCOPE_ATTN_OUT,
    SCOPE_EMBED,
    SCOPE_EXPERTS,
    SCOPE_FFN,
    SCOPE_HEAD,
    SCOPE_KV_LATENT,
    SCOPE_KV_WRITE,
    SCOPE_LAYERS,
    SCOPE_QKV,
    SCOPE_ROUTER,
    SCOPE_SHARED_EXPERT,
)
from .config import LlamaConfig
from .llama import KVCache, _qdq_q80, kv_append


class LatentAttnParams(NamedTuple):
    """Attention weights of every layer, stacked ``[L, ...]``; matmul weights
    ``[d_in, d_out]``, dense or ``PackedQ40``."""

    wq: jnp.ndarray  # [L, dim, H * (nope + rope)]
    wkva: jnp.ndarray  # [L, dim, rank + rope]
    # Wkvb, DENSE, a head at a time in the two orientations the absorbed
    # form multiplies by (see latent_params)
    wuk: jnp.ndarray  # [L, H, nope, rank]: q~_i = q_nope_i @ wuk_i
    wuv: jnp.ndarray  # [L, H, rank, v]: o_i = o~_i @ wuv_i
    wo: jnp.ndarray  # [L, H * v, dim]
    rms_att: jnp.ndarray  # [L, dim]
    rms_kv: jnp.ndarray  # [L, rank]


class DenseFfnParams(NamedTuple):
    """The leading dense layers' FFN, stacked ``[n_dense_layers, ...]``."""

    w1: jnp.ndarray  # [Ld, dim, hidden]
    w2: jnp.ndarray  # [Ld, hidden, dim]
    w3: jnp.ndarray  # [Ld, dim, hidden]
    rms_ffn: jnp.ndarray  # [Ld, dim]


class RoutedFfnParams(NamedTuple):
    """The routed layers' FFN, stacked ``[L - n_dense_layers, ...]``."""

    gate: jnp.ndarray  # [Lm, dim, E] f32 router
    bias: jnp.ndarray | None  # [Lm, E] f32 selection bias (moe_select_bias)
    w1: jnp.ndarray  # Q40Experts, or dense [Lm, E, dim, moe_hidden]
    w2: jnp.ndarray  # ... [Lm, E, moe_hidden, dim]
    w3: jnp.ndarray
    s1: jnp.ndarray | None  # [Lm, dim, shared_hidden]: the shared experts
    s2: jnp.ndarray | None  # [Lm, shared_hidden, dim]
    s3: jnp.ndarray | None
    rms_ffn: jnp.ndarray  # [Lm, dim]


class DeepseekParams(NamedTuple):
    embedding: jnp.ndarray  # [vocab, dim]
    attn: LatentAttnParams
    dense: DenseFfnParams | None
    routed: RoutedFfnParams | None
    rms_final: jnp.ndarray  # [dim]
    wcls: jnp.ndarray  # [dim, vocab]
    rope_cos: jnp.ndarray  # [seq_len, qk_rope_head_dim // 2] f32
    rope_sin: jnp.ndarray


ROPE_LEAF_ALIGN = 128  # lanes of a TPU tile: the rope leaf's last axis is whole tiles


def rope_leaf_width(config: LlamaConfig) -> int:
    return -(-config.qk_rope_head_dim // ROPE_LEAF_ALIGN) * ROPE_LEAF_ALIGN


def init_latent_cache(config: LlamaConfig, n_lanes: int, dtype=jnp.float32) -> KVCache:
    """One row a token a layer: ``k`` the normed latent ``c'``, ``v`` the
    rotated ``k_pe`` (module header, "The cache")."""
    lead = (config.n_layers, n_lanes, config.seq_len)
    return KVCache(
        k=jnp.zeros((*lead, config.kv_lora_rank), dtype),
        v=jnp.zeros((*lead, rope_leaf_width(config)), dtype),
    )


def latent_params(t: dict, rope_cos, rope_sin, dtype, config: LlamaConfig) -> DeepseekParams:
    """The parameter tree around a model's arrays, by the tensor names of the
    ``.m`` walk without their ``block_`` prefix: what the loader and a
    benchmark's generator both hand over. ``wkvb`` (a ``PackedQ40`` stack or
    dense, ``[L, rank, H * (nope + v)]``) becomes its dequantized values in
    ``dtype``, split by head into the key part and the value part, each laid
    out for its product (as one array XLA re-laid the whole stack every step:
    0.6 ms of a decode step on a v5e, PR 33); expert stacks that arrive as
    ``PackedQ40`` become ``Q40Experts``."""
    def experts(w):
        return Q40Experts.from_packed(w) if isinstance(w, PackedQ40) else w

    wkvb = t["wkvb"]
    wkvb = unpack_q40(wkvb, dtype) if isinstance(wkvb, PackedQ40) else wkvb.astype(dtype)
    nope = config.qk_nope_head_dim
    wkvb = wkvb.reshape(*wkvb.shape[:2], config.n_heads, nope + config.v_head_dim)
    attn = LatentAttnParams(
        wq=t["wq"], wkva=t["wkva"], wo=t["wo"],
        wuk=jnp.transpose(wkvb[..., :nope], (0, 2, 3, 1)),
        wuv=jnp.transpose(wkvb[..., nope:], (0, 2, 1, 3)),
        rms_att=t["rms_att"], rms_kv=t["rms_kv"],
    )
    dense = routed = None
    if "dense_w1" in t:
        dense = DenseFfnParams(
            w1=t["dense_w1"], w2=t["dense_w2"], w3=t["dense_w3"],
            rms_ffn=t["dense_rms_ffn"],
        )
    if "moe_gate" in t:
        routed = RoutedFfnParams(
            gate=t["moe_gate"], bias=t.get("moe_bias"),
            w1=experts(t["w1"]), w2=experts(t["w2"]), w3=experts(t["w3"]),
            s1=t.get("shared_w1"), s2=t.get("shared_w2"), s3=t.get("shared_w3"),
            rms_ffn=t["rms_ffn"],
        )
    return DeepseekParams(
        embedding=t["embedding"], attn=attn, dense=dense, routed=routed,
        rms_final=t["rms_final"], wcls=t["wcls"],
        rope_cos=rope_cos, rope_sin=rope_sin,
    )


def moe_router(config: LlamaConfig, y: jnp.ndarray, gate: jnp.ndarray,
               bias: jnp.ndarray | None):
    """(weights ``[..., k]`` f32, expert ids ``[..., k]`` int32). Float32
    throughout, the logits at the highest matmul precision: a choice between
    two experts is no place for a bf16 pass. The bias chooses and does not
    weigh."""
    logits = jnp.einsum(
        "...d,de->...e", y.astype(jnp.float32), gate.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if config.moe_score_func == MoeScore.SIGMOID:
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choose = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(choose, config.n_active_experts)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if config.moe_norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * config.moe_routed_scale, idx.astype(jnp.int32)


def grouped_matmul(x_rows, w, layer, plan):
    """Rows in the plan's order by their tiles' experts: the grouped Q40
    kernel where it engages (``Q40Experts`` it tiles, Pallas on), else the
    gathered-slab product (the CPU). What ``w`` is decides it."""
    if grouped_supports(w) and pallas_kernel_active():
        return q40_grouped_pallas(
            x_rows, w, layer, plan, interpret=pallas_interpret(), **pallas_w_dtype_kw()
        )
    return grouped_matmul_xla(x_rows, w, layer, plan)


def absorbed_attention(q_nope, q_pe, wuk, wuv, c_plane, r_plane, mask, scale):
    """All heads against the one latent row a token. q_nope ``[B,T,H,nope]``,
    q_pe ``[B,T,H,rope]`` (rotated), wuk ``[H, nope, rank]``, wuv
    ``[H, rank, v]``, c_plane
    ``[B,S,rank]``, r_plane ``[B,S,rope]`` (rotated; both rope parts may be
    padded with zeros alike), mask ``[B,T,S]``.
    Returns ``[B,T,H,v]`` f32. The planes are multiplied in the dtype they are
    cached in (bf16, f32), accumulated in f32."""
    plane_dtype = c_plane.dtype if c_plane.dtype in (jnp.bfloat16, jnp.float32) else jnp.float32
    # (the two products by head take no float32 result type: a bf16 pair is
    # rounded once either way, to the plane's dtype here and to the stream's
    # after the second, and XLA:CPU has no bf16 x bf16 -> f32 batched dot)
    q_abs = jnp.einsum("bthn,hnc->bthc", q_nope.astype(wuk.dtype), wuk)
    scores = jnp.einsum(
        "bthc,bsc->bths", q_abs.astype(plane_dtype), c_plane.astype(plane_dtype),
        preferred_element_type=jnp.float32,
    ) + jnp.einsum(
        "bthr,bsr->bths", q_pe.astype(plane_dtype), r_plane.astype(plane_dtype),
        preferred_element_type=jnp.float32,
    )
    scores = jnp.where(mask[:, :, None, :], scores * scale, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum(
        "bths,bsc->bthc", probs.astype(plane_dtype), c_plane.astype(plane_dtype),
        preferred_element_type=jnp.float32,
    )
    return jnp.einsum("bthc,hcv->bthv", o_lat.astype(wuv.dtype), wuv).astype(jnp.float32)


class FfnOps(NamedTuple):
    """What a block's FFNs share across its layers: the activation, the Q80
    emulation's cast (identity unless asked for) and the shared operand build
    of two matmuls on one input (identity unless the weights are Q40)."""

    act_fn: object
    maybe_qdq: object
    share_q80: object


def ffn_ops(cfg: LlamaConfig, emulate_q80_activations: bool, quantized: bool) -> FfnOps:
    return FfnOps(
        act_fn=silu if cfg.hidden_act == HiddenAct.SILU else gelu,
        maybe_qdq=_qdq_q80 if emulate_q80_activations else (lambda y: y),
        share_q80=shared_q80_acts if quantized else (lambda y: y),
    )


def gated_ffn(ops: FfnOps, yq, w1, w2, w3):
    """``W2 (act(W1 y) * W3 y)``: a dense layer's FFN, or the shared experts."""
    yqs = ops.share_q80(yq)  # one operand build for the gate and the up matmul
    return matmul(ops.maybe_qdq(ops.act_fn(matmul(yqs, w1)) * matmul(yqs, w3)), w2)


def dense_ffn(cfg: LlamaConfig, ops: FfnOps, x, dp: "DenseFfnParams"):
    """A leading dense layer's FFN half: norm, gated FFN, residual add."""
    with jax.named_scope(SCOPE_FFN):
        y = rms_norm(x, dp.rms_ffn, cfg.norm_epsilon)
        return x + ops.maybe_qdq(gated_ffn(ops, ops.maybe_qdq(y), dp.w1, dp.w2, dp.w3))


def routed_ffn(cfg: LlamaConfig, ops: FfnOps, x, rp: "RoutedFfnParams", lm, live):
    """A routed layer's FFN half. ``rp``: the layer's parameters, the expert
    stacks whole; ``lm`` its index into them; ``live`` ``[B * T]``: False for
    a parked row, which routes nowhere. Returns (x, slabs, assignments)."""
    b, t, _ = x.shape
    n = b * t
    dtype = x.dtype
    with jax.named_scope(SCOPE_FFN):
        y = rms_norm(x, rp.rms_ffn, cfg.norm_epsilon)
        yq = ops.maybe_qdq(y)
        with jax.named_scope(SCOPE_ROUTER):
            topw, topi = moe_router(cfg, y.reshape(n, -1), rp.gate, rp.bias)
        with jax.named_scope(SCOPE_EXPERTS):
            plan = route_plan(topi, live, cfg.n_experts)
            rows = jnp.concatenate(
                [yq.reshape(n, -1), jnp.zeros((1, yq.shape[-1]), yq.dtype)]
            )[plan.src]  # [P, dim], sorted by expert, a zero row where none
            g = grouped_matmul(rows, rp.w1, lm, plan)
            u = grouped_matmul(rows, rp.w3, lm, plan)
            ys = grouped_matmul(ops.maybe_qdq(ops.act_fn(g) * u), rp.w2, lm, plan)
            # a parked row's assignments point past the last row: zeros
            ys = jnp.concatenate([ys, jnp.zeros((1, ys.shape[-1]), ys.dtype)])
            routed = jnp.einsum("nk,nkd->nd", topw, ys[plan.pos])
            out = routed.reshape(b, t, -1)
        if rp.s1 is not None:
            with jax.named_scope(SCOPE_SHARED_EXPERT):
                out = out + gated_ffn(ops, yq, rp.s1, rp.s2, rp.s3)
        x = x + ops.maybe_qdq(out.astype(dtype))
    return x, plan.slabs, plan.assignments


def _pick(leaf, index):
    """Layer ``index`` of a stacked leaf: a ``Q40Layer`` where the kernel
    reads the stack itself, the expert stacks as they are (the grouped kernel
    takes the index), else the layer sliced out."""
    if leaf is None or isinstance(leaf, Q40Experts):
        return leaf
    if reads_q40_stack(leaf):
        return Q40Layer(leaf, index)
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False), leaf
    )


def deepseek_forward_counted(
    config: LlamaConfig,
    params: DeepseekParams,
    tokens: jnp.ndarray,  # [B, T] int32
    positions: jnp.ndarray,  # [B, T] int32
    cache: KVCache,
    emulate_q80_activations: bool = False,
    mesh=None,
    q80_sync: bool = False,
):
    """(logits ``[B, T, vocab]`` f32, updated cache, counts). ``counts`` is
    ``(slabs, assignments)``, int32 scalars summed over the routed layers:
    distinct (layer, expert) slabs one expert matrix read, and (row, expert)
    pairs routed; None for a model without routed layers."""
    if mesh is not None or q80_sync:
        raise ValueError("the latent-attention block runs on one device: no mesh")
    if not isinstance(cache, KVCache):
        raise ValueError("the latent-attention block keeps a contiguous latent cache")
    cfg = config
    b, t = tokens.shape
    n_heads, rank = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    eps = cfg.norm_epsilon
    ops = ffn_ops(cfg, emulate_q80_activations, isinstance(params.attn.wq, PackedQ40))
    maybe_qdq, share_q80 = ops.maybe_qdq, ops.share_q80
    scale = 1.0 / float(nope + rope) ** 0.5

    with jax.named_scope(SCOPE_EMBED):
        x = params.embedding[tokens]
    dtype = x.dtype
    lane_idx = jnp.arange(b)[:, None]
    live = (positions < cfg.seq_len).reshape(b * t)
    with jax.named_scope(SCOPE_ATTENTION):
        s_idx = jnp.arange(cfg.seq_len)
        attn_mask = s_idx[None, None, :] <= positions[:, :, None]  # [B, T, S]
    row_major = Layout(major_to_minor=tuple(range(cache.k.ndim)))

    def attention(x, ap, l, c_all, r_all):
        with jax.named_scope(SCOPE_QKV):
            y = rms_norm(x, ap.rms_att, eps)
            yq = share_q80(maybe_qdq(y))  # one operand build for wq and wkva
            q = matmul(yq, ap.wq).reshape(b, t, n_heads, nope + rope)
            with jax.named_scope(SCOPE_KV_LATENT):
                kva = matmul(yq, ap.wkva)  # [B, T, rank + rope]
                c = rms_norm(kva[..., :rank], ap.rms_kv, eps)
                k_pe = apply_rope(
                    kva[..., None, rank:], params.rope_cos, params.rope_sin, positions
                )
            q_pe = apply_rope(q[..., nope:], params.rope_cos, params.rope_sin, positions)
            # both rotated parts at the rope leaf's width, zeros past the end
            k_pe, q_pe = (
                jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, r_all.shape[-1] - rope)])
                for a in (k_pe[:, :, 0], q_pe)
            )
            # the projections finish before the cache is touched, as in
            # models/llama.py's layer_step
            q, q_pe, c, k_pe = jax.lax.optimization_barrier((q, q_pe, c, k_pe))
        with jax.named_scope(SCOPE_KV_WRITE):
            at = (l, lane_idx, positions)
            c_all, r_all = kv_append(c_all, r_all, at, c, k_pe, row_major)
        with jax.named_scope(SCOPE_ATTENTION):
            # the layer's latent plane, read out of the carry AFTER the append
            c_plane = jax.lax.dynamic_index_in_dim(c_all, l, 0, keepdims=False)
            r_plane = jax.lax.dynamic_index_in_dim(r_all, l, 0, keepdims=False)
            o = absorbed_attention(
                q[..., :nope], q_pe, ap.wuk, ap.wuv, c_plane, r_plane, attn_mask, scale,
            )
            attn = o.reshape(b, t, n_heads * vd).astype(dtype)
        with jax.named_scope(SCOPE_ATTN_OUT):
            x = x + maybe_qdq(matmul(maybe_qdq(attn), ap.wo))
        return x, c_all, r_all

    n_dense = cfg.n_dense_layers if params.routed is not None else cfg.n_layers
    with jax.named_scope(SCOPE_LAYERS):
        c_all, r_all = cache.k, cache.v
        for i in range(n_dense):  # the leading dense layers, before the scan
            l = jnp.int32(i)
            ap = LatentAttnParams(*(_pick(leaf, l) for leaf in params.attn))
            x, c_all, r_all = attention(x, ap, l, c_all, r_all)
            dp = DenseFfnParams(*(_pick(leaf, l) for leaf in params.dense))
            x = dense_ffn(cfg, ops, x, dp)

        counts = None
        if params.routed is not None:
            def layer_step(carry, lm):
                # every stack is closed over and read at its layer index: a
                # Q40 stack by the kernels, the rest by a slice of one layer
                x, c_all, r_all, slabs, assigned = carry
                l = lm + n_dense
                ap = LatentAttnParams(*(_pick(leaf, l) for leaf in params.attn))
                x, c_all, r_all = attention(x, ap, l, c_all, r_all)
                rp = RoutedFfnParams(*(_pick(leaf, lm) for leaf in params.routed))
                x, s, a = routed_ffn(cfg, ops, x, rp, lm, live)
                return (x, c_all, r_all, slabs + s, assigned + a), None

            zero = jnp.zeros((), jnp.int32)
            (x, c_all, r_all, slabs, assigned), _ = jax.lax.scan(
                layer_step, (x, c_all, r_all, zero, zero),
                jnp.arange(cfg.n_layers - n_dense, dtype=jnp.int32),
            )
            counts = (slabs, assigned)

    with jax.named_scope(SCOPE_HEAD):
        y = rms_norm(x, params.rms_final, eps)
        logits = matmul(maybe_qdq(y), params.wcls).astype(jnp.float32)
        logits = logits[..., : cfg.vocab_size]
    return logits, KVCache(k=c_all, v=r_all), counts


def deepseek_forward(config, params, tokens, positions, cache, **kw):
    """``llama_forward``'s signature and result for this block."""
    logits, cache, _ = deepseek_forward_counted(
        config, params, tokens, positions, cache, **kw)
    return logits, cache


def forward_counted(config: LlamaConfig):
    """The forward function of a configuration's block, every step family's
    one entry: ``f(config, params, tokens, positions, cache, **kw) ->
    (logits, cache, counts)``. What the configuration is decides it; a Llama
    block counts nothing (None). A block with a recurrent state
    (models/hybrid.py) also takes ``n_valid``: how many leading rows of each
    lane are real."""
    if config.layer_kinds:
        from .hybrid import hybrid_forward_counted

        return hybrid_forward_counted
    if config.latent_attention:
        return deepseek_forward_counted
    from .llama import llama_forward

    def counted(*a, **kw):
        return (*llama_forward(*a, **kw), None)

    return counted
