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
(``models/llama.py``, "How the cache moves"). How they are read: at one row
a lane the in-place decode kernel (ops/pallas_attention.py, "Latent rows") is
handed the two stacks as the carry holds them and fetches each lane's rows
``[0, pos]`` of the layer in whole blocks, the latent block as keys and as
values at once, where ``llama.decode_attention_engages`` says so (bf16 leaves
of whole 128-lane tiles, a context of whole blocks, one device, Pallas on; the
question the other two blocks' forwards and the engine's counters ask).
Elsewhere (a prefill chunk or a verify step's rows, a rank that is no whole
tile, an f8 or f32 cache, the CPU) attention is XLA's dense path over the
layer's latent plane sliced out of the carry (``latent_plane_attention``).
With an indexer neither: ``sparse_attention``, below.

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

What ``model_type: deepseek_v32`` adds, each engaged by its own field and by
no model's name. A QUERY LATENT (``q_lora_rank > 0``): ``cq = rmsnorm(Wqa n)``,
``q = Wqb cq``. EXPERT GROUPS (``moe_n_group > 1``; ``moe_router``). A HELD
SHARE of the routed experts (``experts_held_count``; ``routed_ffn``): the
router keeps every output, the stacks hold the share's slabs, a chosen expert
outside it is another chip's and adds nothing here. YaRN (``ops/rope.py``) and
its factor on the softmax scale. And LEARNED SPARSE ATTENTION
(``index_topk > 0``), the lightning indexer:

    qI_j = WIq cq                     index_n_heads heads of index_head_dim
    kI   = layernorm(WIk n)           ONE row a token, the indexer's own cache
    rotary embedding on the first qk_rope_head_dim numbers of every qI_j and of kI
    w    = WIw n / sqrt(index_n_heads * index_head_dim)
    I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s)),  s <= t
    S_t  = the index_topk positions of largest I(t, .), ties to the lower position

and attention's softmax runs over ``s in S_t`` only. ``kI`` is a third leaf of
the cache tree (``IndexedLatentCache.ik``, ``[L, lanes, S, index_head_dim]``,
whole 128-lane tiles), appended in place through the carry beside the two
latent leaves. The selection is EXACT at every width and one code path
(``sparse_attention``): the score pass over the key blocks the step's
positions reach and no further, ``jax.lax.top_k`` (a sort; equal scores to the
lower position) in segments and then over the segments' winners, the chosen
rows GATHERED out of the stacks by (layer, lane, position), a block of queries
at a time, and the absorbed form over those rows alone: attention reads
``index_topk`` rows a query, not the plane. (A threshold search as the
sampler's finds the ``index_topk``-th score of a 1024-row chunk in a third of
the sort's time, but leaves a mask: attending a mask costs operations by the
context's length, 580 ms of an 856 ms chunk on a v5e, where gathering costs
by ``index_topk``: PERF.md section 6, PR 41.) No path attends an unchosen
row, and none skips the indexer: below ``index_topk`` positions every row is
chosen because the top-k of fewer rows is all of them.

Not served by this block, and refused by ``InferenceEngine`` at start-up: the
paged pool (and with it prefix page sharing, the host tier and KV-page
transfer: pages are framed as a K/V pair of heads), and any mesh.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ..formats.model_file import HiddenAct, MoeScore
from ..ops.activations import gelu, silu
from ..ops.linear import (
    head,
    matmul,
    pallas_interpret,
    pallas_kernel_active,
    pallas_w_dtype_kw,
    reads_q40_stack,
)
from ..ops.norm import layer_norm, rms_norm
from ..ops.pallas_attention import block_rows, decode_attention, lane_blocks
from ..ops.pallas_q40_grouped import (
    HEIGHTS,
    grouped_matmul_xla,
    grouped_supports,
    q40_grouped_pallas,
    route_plan,
    tile_rows,
)
from ..ops.rope import apply_rope, apply_rope_first
from ..quants.packed import PackedQ40, Q40Experts, Q40Layer, unpack_q40
from ..telemetry.names import (
    SCOPE_ATTENTION,
    SCOPE_ATTN_OUT,
    SCOPE_EMBED,
    SCOPE_EXPERTS,
    SCOPE_FFN,
    SCOPE_INDEXER,
    SCOPE_KV_LATENT,
    SCOPE_KV_WRITE,
    SCOPE_LAYERS,
    SCOPE_QKV,
    SCOPE_ROUTER,
    SCOPE_SHARED_EXPERT,
    SCOPE_SPARSE_SELECT,
)
from .config import LlamaConfig
from .llama import KVCache, _qdq_q80, _to_cache_dtype, decode_attention_engages, kv_append


class LatentAttnParams(NamedTuple):
    """Attention weights of every layer, stacked ``[L, ...]``; matmul weights
    ``[d_in, d_out]``, dense or ``PackedQ40``."""

    wq: jnp.ndarray  # [L, dim, H * (nope + rope)]; from the query latent: [L, q_rank, ...]
    wkva: jnp.ndarray  # [L, dim, rank + rope]
    # Wkvb, DENSE, a head at a time in the two orientations the absorbed
    # form multiplies by (see latent_params)
    wuk: jnp.ndarray  # [L, H, nope, rank]: q~_i = q_nope_i @ wuk_i
    wuv: jnp.ndarray  # [L, H, rank, v]: o_i = o~_i @ wuv_i
    wo: jnp.ndarray  # [L, H * v, dim]
    rms_att: jnp.ndarray  # [L, dim]
    rms_kv: jnp.ndarray  # [L, rank]
    # a query latent (config.q_lora_rank)
    wqa: jnp.ndarray | None = None  # [L, dim, q_rank]
    rms_q: jnp.ndarray | None = None  # [L, q_rank]
    # the indexer (config.index_topk)
    idx_wq: jnp.ndarray | None = None  # [L, q_rank or dim, Hi * Di]
    idx_wk: jnp.ndarray | None = None  # [L, dim, Di]
    idx_ww: jnp.ndarray | None = None  # [L, dim, Hi] f32: the heads' weights
    idx_k_gain: jnp.ndarray | None = None  # [L, Di] f32: the key's layer norm
    idx_k_bias: jnp.ndarray | None = None  # [L, Di] f32


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


class IndexedLatentCache(NamedTuple):
    """The latent cache of a model with an indexer: the two latent leaves and
    the index keys, one row a token a layer each, the lanes on axis 1."""

    k: jnp.ndarray  # [L, lanes, S, kv_lora_rank]: the normed latent
    v: jnp.ndarray  # [L, lanes, S, rope_leaf_width]: the rotated k_pe
    ik: jnp.ndarray  # [L, lanes, S, index_head_dim]: the indexer's keys


def init_latent_cache(config: LlamaConfig, n_lanes: int, dtype=jnp.float32):
    """One row a token a layer: ``k`` the normed latent ``c'``, ``v`` the
    rotated ``k_pe`` (module header, "The cache"); with an indexer, ``ik``
    its keys."""
    lead = (config.n_layers, n_lanes, config.seq_len)
    k = jnp.zeros((*lead, config.kv_lora_rank), dtype)
    v = jnp.zeros((*lead, rope_leaf_width(config)), dtype)
    if config.sparse_attention:
        return IndexedLatentCache(k=k, v=v, ik=jnp.zeros((*lead, config.index_head_dim), dtype))
    return KVCache(k=k, v=v)


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
        # the query latent's and the indexer's, where the model has them
        **{name: t[name] for name in LatentAttnParams._field_defaults if name in t},
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
    weigh; with expert groups (``moe_n_group > 1``) it also ranks the groups."""
    logits = jnp.einsum(
        "...d,de->...e", y.astype(jnp.float32), gate.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if config.moe_score_func == MoeScore.SIGMOID:
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choose = scores if bias is None else scores + bias.astype(jnp.float32)
    if config.moe_n_group > 1:
        # a group scores the sum of its two largest; outside the
        # moe_topk_group best groups nothing can be chosen
        groups = choose.reshape(*choose.shape[:-1], config.moe_n_group, -1)
        group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
        _, best = jax.lax.top_k(group_score, config.moe_topk_group)
        kept = jnp.any(best[..., None] == jnp.arange(config.moe_n_group), axis=-2)
        choose = jnp.where(kept[..., None], groups, -jnp.inf).reshape(choose.shape)
    _, idx = jax.lax.top_k(choose, config.n_active_experts)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if config.moe_norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + config.moe_norm_floor)
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


def _product_dtype(cached):
    """The dtype cached rows are multiplied in: their own (bf16, f32), float32
    for a narrower cache."""
    return cached.dtype if cached.dtype in (jnp.bfloat16, jnp.float32) else jnp.float32


def absorb_queries(q_nope, wuk):
    """``q~_i = Wuk_i^T q_nope_i``: q_nope ``[B,T,H,nope]``, wuk ``[H, nope,
    rank]`` -> ``[B,T,H,rank]`` in wuk's dtype. (The two products by head take
    no float32 result type: a bf16 pair is rounded once either way, to the
    cached rows' dtype here and to the stream's after ``expand_values``, and
    XLA:CPU has no bf16 x bf16 -> f32 batched dot.)"""
    return jnp.einsum("bthn,hnc->bthc", q_nope.astype(wuk.dtype), wuk)


def expand_values(o_lat, wuv):
    """``o_i = Wuv_i o~_i``: o_lat ``[B,T,H,rank]``, wuv ``[H, rank, v]`` ->
    ``[B,T,H,v]`` f32."""
    return jnp.einsum("bthc,hcv->bthv", o_lat.astype(wuv.dtype), wuv).astype(jnp.float32)


def latent_plane_attention(q_abs, q_pe, c_plane, r_plane, mask, scale):
    """The absorbed queries against one layer's whole planes: q_abs
    ``[B,T,H,rank]``, q_pe ``[B,T,H,rope]`` (rotated), c_plane ``[B,S,rank]``,
    r_plane ``[B,S,rope]`` (rotated; both rope parts may be padded with zeros
    alike), mask ``[B,T,S]``. Returns ``o~`` ``[B,T,H,rank]`` f32: the
    probabilities over the latent rows themselves. The planes are multiplied
    in the dtype they are cached in (bf16, f32), accumulated in f32, the scale
    on the f32 scores: the operands and rounding points the in-place kernel
    keeps (``ops/pallas_attention.py``, "Latent rows")."""
    plane_dtype = _product_dtype(c_plane)
    scores = jnp.einsum(
        "bthc,bsc->bths", q_abs.astype(plane_dtype), c_plane.astype(plane_dtype),
        preferred_element_type=jnp.float32,
    ) + jnp.einsum(
        "bthr,bsr->bths", q_pe.astype(plane_dtype), r_plane.astype(plane_dtype),
        preferred_element_type=jnp.float32,
    )
    scores = jnp.where(mask[:, :, None, :], scores * scale, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bths,bsc->bthc", probs.astype(plane_dtype), c_plane.astype(plane_dtype),
        preferred_element_type=jnp.float32,
    )

# -- learned sparse attention -------------------------------------------------

# positions a segment of the exact top-k (one sort a segment, then one of the
# segments' winners): a sort of 8192 is a ninth of a quarter of a sort of
# 32768 on a v5e (PERF.md section 6, PR 41)
TOPK_SEGMENT = 8192
# queries whose chosen rows are gathered and attended at a time (a block's
# rows: QUERY_BLOCK x index_topk x (rank + rope leaf) in the cache's dtype)
QUERY_BLOCK = 256
# index scores made at a time, all heads: rows x heads x keys float32
SCORE_BLOCK_ELEMENTS = 1 << 26


def index_scores_block(qi, w, ik_block):
    """``I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s))`` for a block of keys:
    qi ``[B, T, Hi, Di]``, w ``[B, T, Hi]`` f32, ik_block ``[B, K, Di]`` ->
    ``[B, T, K]`` f32. The products are made in the dtype the keys are cached
    in and accumulated in float32; the weighted sum over heads is float32
    arithmetic, not a second matmul pass."""
    pd = _product_dtype(ik_block)
    s = jnp.einsum("btjd,bkd->btjk", qi.astype(pd), ik_block.astype(pd),
                   preferred_element_type=jnp.float32)
    return jnp.sum(w[..., None] * jax.nn.relu(s), axis=2)


def exact_topk(scores, k: int, last):
    """The positions of the ``k`` largest of every row of ``scores``
    ``[B, T, S]``, equal scores to the lower position: a sort (never
    ``approx_max_k``), over segments of ``TOPK_SEGMENT`` positions and then
    over the segments' winners, which hold the row's ``k`` largest whatever
    the split. A segment that starts past ``last`` (the last position any row
    may choose) is not sorted: its places are filled with ``-inf`` at its own
    positions, which no row can choose; while ``last`` lies in the first
    segment its winners are the answer and nothing is merged. Ties: a
    segment's winners come by falling score and, equal, by rising position
    (``jax.lax.top_k``: the lower index first), the segments in order, and the
    merge is a STABLE sort of (score, position) pairs, so equal scores keep
    that order: the lower position first."""
    b, t, seq = scores.shape
    seg = min(TOPK_SEGMENT, seq)
    if seq % seg:
        raise ValueError(f"a context of {seq} is not whole segments of {seg}")
    if seq == seg or t == 1:
        # one segment, or one row a lane: a handful of rows sort whole in a
        # third of the time their four segments and the merge take (0.31
        # against 1.1 ms for 8 rows of 32768 on a v5e)
        return jax.lax.top_k(scores, k)[1]
    ks = min(k, seg)

    def winners(j):
        def sort():
            v, i = jax.lax.top_k(jax.lax.dynamic_slice_in_dim(scores, j * seg, seg, axis=2), ks)
            return v, i + j * seg

        def skip():
            return (jnp.full((b, t, ks), -jnp.inf, scores.dtype),
                    jnp.broadcast_to(j * seg + jnp.arange(ks, dtype=jnp.int32), (b, t, ks)))

        return jax.lax.cond(j * seg <= last, sort, skip)

    vals, idx = jax.lax.map(winners, jnp.arange(seq // seg, dtype=jnp.int32))

    def merge():
        # the pairs sorted together: no gather of positions by rank afterwards
        v = jnp.moveaxis(vals, 0, 2).reshape(b, t, -1)
        i = jnp.moveaxis(idx, 0, 2).reshape(b, t, -1)
        return jax.lax.sort((-v, i), dimension=2, is_stable=True, num_keys=1)[1][..., :k]

    if ks < k:  # a segment holds fewer than k positions: always merged
        return merge()
    return jax.lax.cond(last < seg, lambda: idx[0][..., :k], merge)


def sparse_attention(cfg, q_nope, q_pe, qi, w, wuk, wuv, c_all, r_all, ik_all, l,
                     positions, scale):
    """Attention over the rows the indexer chooses, at any width (module
    header): every query's scores over the key blocks the step's positions
    reach, its ``index_topk`` best positions (exact), their latent rows
    GATHERED out of the stacks as the carry holds them, by (layer, lane,
    position), a block of queries at a time, and the absorbed form over those
    rows alone. Returns (``[B, T, H, v]`` f32, index keys scored, rows
    attended), the counts over live rows."""
    b, t = positions.shape
    seq = c_all.shape[2]
    k = min(cfg.index_topk, seq)
    live = positions < seq
    last = jnp.max(jnp.where(live, positions, -1))  # the last row any query may read
    with jax.named_scope(SCOPE_INDEXER):
        # the score pass, a block of keys at a time up to ``last``
        kb = seq
        while kb > 1024 and b * t * cfg.index_n_heads * kb > SCORE_BLOCK_ELEMENTS and kb % 2 == 0:
            kb //= 2
        s_in_block = jnp.arange(kb)

        def score_block(j, out):
            ik_block = jax.lax.dynamic_slice(
                ik_all, (l, 0, j * kb, 0), (1, b, kb, ik_all.shape[-1]))[0]
            sc = index_scores_block(qi, w, ik_block)
            held = (j * kb + s_in_block)[None, None, :] <= positions[..., None]
            return jax.lax.dynamic_update_slice(
                out, jnp.where(held, sc, -jnp.inf), (0, 0, j * kb))

        scores = jax.lax.fori_loop(
            0, (last + kb) // kb, score_block, jnp.full((b, t, seq), -jnp.inf, jnp.float32))
    with jax.named_scope(SCOPE_SPARSE_SELECT):
        idx = exact_topk(scores, k, last)  # [B, T, k]
        chosen = idx <= positions[..., None]  # fewer than k rows held: the -inf fill drops out
    qb = min(QUERY_BLOCK, t)
    if t % qb:
        raise ValueError(f"a step of {t} rows is not whole blocks of {qb} queries")
    pd = _product_dtype(c_all)
    with jax.named_scope(SCOPE_ATTENTION):
        q_abs = jnp.einsum("bthn,hnc->bthc", q_nope.astype(wuk.dtype), wuk).astype(pd)
        q_pe = q_pe.astype(pd)
    # a chosen row's place in a stack seen as rows (layer, lane, position): a
    # view that moves no byte, gathered by ONE index a row (XLA's general
    # gather by (layer, lane, position) triples ran at a third of the speed on
    # a v5e: PERF.md section 6, PR 41)
    row = (l * c_all.shape[1] + jnp.arange(b, dtype=jnp.int32)[:, None, None]) * seq + idx
    c_flat = c_all.reshape(-1, c_all.shape[-1])
    r_flat = r_all.reshape(-1, r_all.shape[-1])

    def attend(q0):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, q0, qb, axis=1)
        with jax.named_scope(SCOPE_SPARSE_SELECT):  # the gather of the chosen rows
            at = cut(row)
            c_rows, r_rows = c_flat[at].astype(pd), r_flat[at].astype(pd)  # [B, qb, k, width]
        with jax.named_scope(SCOPE_ATTENTION):
            sc = jnp.einsum("bthc,btkc->bthk", cut(q_abs), c_rows,
                            preferred_element_type=jnp.float32)
            sc = sc + jnp.einsum("bthr,btkr->bthk", cut(q_pe), r_rows,
                                 preferred_element_type=jnp.float32)
            sc = jnp.where(cut(chosen)[:, :, None, :], sc * scale, -jnp.inf)
            probs = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("bthk,btkc->bthc", probs.astype(pd), c_rows,
                              preferred_element_type=jnp.float32)

    if t == qb:
        o_lat = attend(0)
    else:  # a block of queries at a time: one block's rows live at once
        o_lat = jax.lax.map(attend, jnp.arange(0, t, qb))  # [t / qb, B, qb, H, rank]
        o_lat = jnp.moveaxis(o_lat, 0, 1).reshape(b, t, *o_lat.shape[3:])
    with jax.named_scope(SCOPE_ATTENTION):
        o = jnp.einsum("bthc,hcv->bthv", o_lat.astype(wuv.dtype), wuv).astype(jnp.float32)
    scored = jnp.sum(jnp.where(live, positions + 1, 0)).astype(jnp.int32)
    picked = jnp.sum(chosen & live[..., None]).astype(jnp.int32)
    return o, scored, picked


class FfnOps(NamedTuple):
    """What a block's FFNs share across its layers: the activation and the
    Q80 emulation's cast (identity unless asked for)."""

    act_fn: object
    maybe_qdq: object


def ffn_ops(cfg: LlamaConfig, emulate_q80_activations: bool) -> FfnOps:
    return FfnOps(
        act_fn=silu if cfg.hidden_act == HiddenAct.SILU else gelu,
        maybe_qdq=_qdq_q80 if emulate_q80_activations else (lambda y: y),
    )


def gated_ffn(ops: FfnOps, yq, w1, w2, w3):
    """``W2 (act(W1 y) * W3 y)``: a dense layer's FFN, or the shared experts."""
    return matmul(ops.maybe_qdq(ops.act_fn(matmul(yq, w1)) * matmul(yq, w3)), w2)


def dense_ffn(cfg: LlamaConfig, ops: FfnOps, x, dp: "DenseFfnParams", residual_scale=1.0):
    """A leading dense layer's FFN half: norm, gated FFN, residual add (the
    FFN's term times ``residual_scale`` where a model scales it)."""
    with jax.named_scope(SCOPE_FFN):
        y = rms_norm(x, dp.rms_ffn, cfg.norm_epsilon)
        out = ops.maybe_qdq(gated_ffn(ops, ops.maybe_qdq(y), dp.w1, dp.w2, dp.w3))
        return x + (out if residual_scale == 1.0 else residual_scale * out)


def routed_ffn(cfg: LlamaConfig, ops: FfnOps, x, rp: "RoutedFfnParams", lm, live, normed=None):
    """A routed layer's FFN half. ``rp``: the layer's parameters, the expert
    stacks whole; ``lm`` its index into them; ``live`` ``[B * T]``: False for
    a parked row, which routes nowhere. Returns (x, slabs, assignments,
    tiled_rows, unheld): distinct slabs fetched, (live row, expert) pairs
    that fetched one, the rows of the tiles those pairs sit in (what each of
    the three grouped products multiplied), and pairs whose expert lies
    outside the held share (``cfg.experts_held``): those fetch nothing and
    add nothing, exactly as a parked row's do, and their weight stays in the
    renormalising sum.
    ``normed`` (a parallel block, ``cfg.parallel_block``): the layer's one
    normed input; the FFN's TERM is returned in the place of ``x``, for the
    caller to add beside attention's."""
    b, t, _ = x.shape
    n = b * t
    dtype = x.dtype
    with jax.named_scope(SCOPE_FFN):
        y = rms_norm(x, rp.rms_ffn, cfg.norm_epsilon) if normed is None else normed
        yq = ops.maybe_qdq(y)
        with jax.named_scope(SCOPE_ROUTER):
            topw, topi = moe_router(cfg, y.reshape(n, -1), rp.gate, rp.bias)
        with jax.named_scope(SCOPE_EXPERTS):
            pairs = n * topi.shape[1]
            if cfg.experts_held_count:
                first, held = cfg.experts_held
                local = topi - first  # ids into the stacks, which hold the share
                here = (local >= 0) & (local < held)
                # a held share still picks between the two heights it had (a
                # held expert's group is the pairs over the ROUTER's width);
                # tile_rows is theirs after PERF.md question 42(f)
                tm = HEIGHTS[0] if pairs <= HEIGHTS[0] * held else HEIGHTS[-1]
                # an expert of another chip's share sorts with the parked rows
                plan = route_plan(jnp.where(here, local, held), live, held, tm)
                unheld = jnp.sum(live[:, None] & ~here).astype(jnp.int32)
                fetched = plan.assignments - unheld
            else:
                tm = tile_rows(pairs, cfg.n_experts, cfg.dim * cfg.moe_hidden_dim)
                plan = route_plan(topi, live, cfg.n_experts, tm)
                unheld, fetched = jnp.zeros((), jnp.int32), plan.assignments
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
                shared = gated_ffn(ops, yq, rp.s1, rp.s2, rp.s3)
                if cfg.shared_expert_scale != 1.0:  # averaged shared experts
                    shared = shared * cfg.shared_expert_scale
                out = out + shared
        out = ops.maybe_qdq(out.astype(dtype))
        x = x + out if normed is None else out
    return x, plan.slabs, fetched, plan.tiled_rows, unheld


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
    head_row: jnp.ndarray | None = None,  # [B] int32: the one row a lane whose logits are kept
):
    """(logits ``[B, T, vocab]`` f32, updated cache, counts); with
    ``head_row``, logits ``[B, 1, vocab]``: each lane's row at that index
    alone, as in ``llama_forward``. ``counts`` is a
    tuple of int32 scalars summed over the layers, named by
    ``count_names(config)``: ``ROUTED_COUNTS`` of the routed layers (distinct
    (layer, expert) slabs one expert matrix read, (row, expert) pairs that
    read one, and the rows of the tiles they sat in), then ``unheld`` where a
    share of the experts is held
    (pairs whose expert is another chip's), then ``(scored, selected)`` where
    an indexer chooses (index keys scored, rows attended); None for a model
    with none of them."""
    if mesh is not None or q80_sync:
        raise ValueError("the latent-attention block runs on one device: no mesh")
    if not isinstance(cache, (KVCache, IndexedLatentCache)):
        raise ValueError("the latent-attention block keeps a contiguous latent cache")
    cfg = config
    b, t = tokens.shape
    n_heads, rank = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    eps = cfg.norm_epsilon
    ops = ffn_ops(cfg, emulate_q80_activations)
    maybe_qdq = ops.maybe_qdq
    scale = cfg.softmax_scale_factor / float(nope + rope) ** 0.5
    sparse = cfg.sparse_attention
    if sparse != isinstance(cache, IndexedLatentCache):
        raise ValueError("an indexer's keys are a third leaf of the cache, and only its")

    with jax.named_scope(SCOPE_EMBED):
        x = params.embedding[tokens]
    dtype = x.dtype
    lane_idx = jnp.arange(b)[:, None]
    live = (positions < cfg.seq_len).reshape(b * t)
    # one row a lane over the two latent leaves alone: read in place (module
    # header, "The cache"); the one question models/llama.py's forward asks
    in_place = t == 1 and decode_attention_engages(cache, mesh, n_heads, latent=True)
    if in_place:
        with jax.named_scope(SCOPE_ATTENTION):
            attn_plan = lane_blocks(positions, cfg.seq_len, block_rows(latent=True))
    elif not sparse:
        with jax.named_scope(SCOPE_ATTENTION):
            s_idx = jnp.arange(cfg.seq_len)
            attn_mask = s_idx[None, None, :] <= positions[:, :, None]  # [B, T, S]
    row_major = Layout(major_to_minor=tuple(range(cache.k.ndim)))
    cos, sin = params.rope_cos, params.rope_sin

    def attention(x, ap, l, leaves):
        """One layer's attention half. ``leaves``: the cache's stacks as the
        carry holds them; returns (x, leaves, (rows scored, rows chosen))."""
        c_all, r_all = leaves[:2]
        with jax.named_scope(SCOPE_QKV):
            y = rms_norm(x, ap.rms_att, eps)
            yq = maybe_qdq(y)
            if ap.wqa is not None:  # the query latent
                cq = maybe_qdq(rms_norm(matmul(yq, ap.wqa), ap.rms_q, eps))
            else:
                cq = yq
            q = matmul(cq, ap.wq).reshape(b, t, n_heads, nope + rope)
            with jax.named_scope(SCOPE_KV_LATENT):
                kva = matmul(yq, ap.wkva)  # [B, T, rank + rope]
                c = rms_norm(kva[..., :rank], ap.rms_kv, eps)
                k_pe = apply_rope(kva[..., None, rank:], cos, sin, positions)
            q_pe = apply_rope(q[..., nope:], cos, sin, positions)
            # both rotated parts at the rope leaf's width, zeros past the end
            k_pe, q_pe = (
                jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, r_all.shape[-1] - rope)])
                for a in (k_pe[:, :, 0], q_pe)
            )
            fresh = (q, q_pe, c, k_pe)
            if sparse:
                with jax.named_scope(SCOPE_INDEXER):
                    qi = matmul(cq, ap.idx_wq).reshape(
                        b, t, cfg.index_n_heads, cfg.index_head_dim)
                    qi = apply_rope_first(qi, rope, cos, sin, positions)
                    ki = layer_norm(matmul(yq, ap.idx_wk), ap.idx_k_gain, ap.idx_k_bias, eps)
                    ki = apply_rope_first(ki[:, :, None], rope, cos, sin, positions)[:, :, 0]
                    # float32 like the router: the weights rank positions
                    w = jnp.einsum(
                        "btd,dj->btj", y.astype(jnp.float32), ap.idx_ww.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST,
                    ) * float(cfg.index_n_heads * cfg.index_head_dim) ** -0.5
                fresh += (qi, ki, w)
            # the projections finish before the cache is touched, as in
            # models/llama.py's layer_step
            fresh = jax.lax.optimization_barrier(fresh)
            q, q_pe, c, k_pe = fresh[:4]
        with jax.named_scope(SCOPE_KV_WRITE):
            at = (l, lane_idx, positions)
            c_all, r_all = kv_append(c_all, r_all, at, c, k_pe, row_major)
        if not sparse:
            with jax.named_scope(SCOPE_ATTENTION):
                q_abs = absorb_queries(q[..., :nope], ap.wuk)
                if in_place:
                    # the kernel fetches each lane's rows [0, pos] of layer l
                    # out of the carry, AFTER the append: no plane is sliced
                    # out, and a latent block is keys and values at once
                    o_lat = decode_attention(
                        jnp.concatenate([q_abs, q_pe], axis=-1)[:, 0],
                        c_all, r_all, l, attn_plan, scale, interpret=pallas_interpret(),
                        latent=True)[:, None]
                else:
                    # the layer's latent plane, read out of the carry AFTER the append
                    c_plane = jax.lax.dynamic_index_in_dim(c_all, l, 0, keepdims=False)
                    r_plane = jax.lax.dynamic_index_in_dim(r_all, l, 0, keepdims=False)
                    o_lat = latent_plane_attention(
                        q_abs, q_pe, c_plane, r_plane, attn_mask, scale)
                o = expand_values(o_lat, ap.wuv)
            leaves, seen = (c_all, r_all), ()
        else:
            qi, ki, w = fresh[4:]
            with jax.named_scope(SCOPE_INDEXER):  # the index key's append
                ik_all = leaves[2].at[at].set(
                    _to_cache_dtype(ki, leaves[2].dtype), mode="drop")
                ik_all = with_layout_constraint(ik_all, row_major)
            o, scored, picked = sparse_attention(
                cfg, q[..., :nope], q_pe, qi, w, ap.wuk, ap.wuv, c_all, r_all, ik_all, l,
                positions, scale)
            leaves, seen = (c_all, r_all, ik_all), (scored, picked)
        attn = o.reshape(b, t, n_heads * vd).astype(dtype)
        with jax.named_scope(SCOPE_ATTN_OUT):
            x = x + maybe_qdq(matmul(maybe_qdq(attn), ap.wo))
        return x, leaves, seen

    n_dense = cfg.n_dense_layers if params.routed is not None else cfg.n_layers
    zero = jnp.zeros((), jnp.int32)
    with jax.named_scope(SCOPE_LAYERS):
        leaves = tuple(cache)
        seen = (zero, zero) if sparse else ()
        for i in range(n_dense):  # the leading dense layers, before the scan
            l = jnp.int32(i)
            ap = LatentAttnParams(*(_pick(leaf, l) for leaf in params.attn))
            x, leaves, more = attention(x, ap, l, leaves)
            seen = tuple(a + m for a, m in zip(seen, more))
            dp = DenseFfnParams(*(_pick(leaf, l) for leaf in params.dense))
            x = dense_ffn(cfg, ops, x, dp)

        counts = None
        if params.routed is not None:
            def layer_step(carry, lm):
                # every stack is closed over and read at its layer index: a
                # Q40 stack by the kernels, the rest by a slice of one layer
                x, leaves, routed, seen = carry
                l = lm + n_dense
                ap = LatentAttnParams(*(_pick(leaf, l) for leaf in params.attn))
                x, leaves, more = attention(x, ap, l, leaves)
                rp = RoutedFfnParams(*(_pick(leaf, lm) for leaf in params.routed))
                x, *more_routed = routed_ffn(cfg, ops, x, rp, lm, live)
                return (x, leaves, tuple(a + m for a, m in zip(routed, more_routed)),
                        tuple(a + m for a, m in zip(seen, more))), None

            # ROUTED_COUNTS and, where a share of the experts is held, the
            # pairs that fell outside it
            routed0 = (zero,) * (len(ROUTED_COUNTS) + bool(cfg.experts_held_count))
            (x, leaves, routed, seen), _ = jax.lax.scan(
                layer_step, (x, leaves, routed0, seen),
                jnp.arange(cfg.n_layers - n_dense, dtype=jnp.int32),
            )
            counts = routed + seen
        elif sparse:
            counts = seen

    logits = head(
        x, lambda x: rms_norm(x, params.rms_final, eps), params.wcls, cfg.vocab_size,
        head_row=head_row, qdq=maybe_qdq,
    )
    return logits, type(cache)(*leaves), counts


# what a routed layer counts on the device, in ``routed_ffn``'s order
ROUTED_COUNTS = ("slabs", "assignments", "tiled_rows")
# the two of them that say how full the grouped kernel's tiles were: a prompt
# chunk's are brought back too (the engine's fused step)
TILE_COUNTS = ("assignments", "tiled_rows")


def count_names(config: LlamaConfig) -> tuple:
    """The names of ``deepseek_forward_counted``'s counts, in their order."""
    names = ()
    if config.n_routed_layers:
        names += ROUTED_COUNTS + (("unheld",) if config.experts_held_count else ())
    if config.sparse_attention:
        names += ("scored", "selected")
    return names


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
    lane are real. Every block takes ``head_row`` (``ops.linear.head``): the
    one row a lane whose logits its caller keeps, ``[B, 1, vocab]`` then."""
    if config.layer_kinds:
        from .hybrid import hybrid_forward_counted

        return hybrid_forward_counted
    if config.latent_attention:
        return deepseek_forward_counted
    from .llama import llama_forward

    def counted(*a, **kw):
        return (*llama_forward(*a, **kw), None)

    return counted
