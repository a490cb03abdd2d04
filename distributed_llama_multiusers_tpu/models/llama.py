"""Pure-functional Llama forward pass, designed for XLA/TPU.

This is the TPU-native re-design of the reference's graph builder
(src/llm.cpp:126-438). The reference emits a per-node static op list with
explicit sync points; here the entire decode step is ONE traced function —
layers run under ``lax.scan`` (compile-time O(1) in depth), tensor-parallel
slicing is expressed as sharding annotations (see ``parallel/sharding.py``)
and XLA inserts the collectives that the reference implements as
SYNC_NODE_SLICES quantized all-gathers over TCP (src/nn/nn-network.cpp:537-569).

Layer math (reference data flow, SURVEY.md §3.4):
    x += attn(rms_norm(x)) ; x += ffn(rms_norm(x))
with GQA attention over a pre-allocated per-lane KV cache, interleaved RoPE,
and SiLU/GELU gated FFN. All reductions and attention math run in float32;
matmuls run in the params' dtype (bf16 on TPU) with f32 accumulation.

How the cache moves. The stacked K and V arrays (``[L, ...]``) ride the
layer scan as its CARRY, next to the residual stream; the scanned input is
the layer index ``l`` and what of the layers' weights is not read by that
index (below). Layer ``l`` scatters its fresh
rows straight into the stack (``k_all.at[l, lane, position]``; paged:
``k_all.at[l, page, slot]``) and then reads its plane back out of the carry,
rows included. Nothing of the cache's size is a scanned input or a stacked
output: every step family donates the cache (``donate_argnums`` in
runtime/engine.py), and a donated buffer aliases through a loop only as its
carry. With the stack as scanned input and stacked output XLA kept both, and
a one-row append cost two whole-cache copies plus a slice and a write-back of
every layer's plane (two thirds of a 7B decode step on a v5e, PERF.md
section 6, PR 27). The scan runs over one run of identical layers whose cache
is addressed by layer index (ROADMAP D5's shape).

How the cache is read at decode width. A step of one row a lane (``t == 1``:
the pipelined decode step, the decode half of every fused step, the
synchronous and multi-step decodes) on one device, with a contiguous bf16
cache and Pallas on, slices no plane out of the carry: a Pallas kernel
(ops/pallas_attention.py) is handed the stack, the layer index and the lanes'
positions, and fetches for each lane the row blocks up to its position, of
layer ``l``'s K and V. A parked lane (position ``seq_len``: idle, or admitting
through a fused step's prefill half) fetches nothing and yields zeros. Read
whole, the two planes were 4.3 GB a 7B decode step whatever the lanes held,
two thirds of attention's time (PERF.md section 6, PR 32). The stack goes in
as the carry holds it, ``(S, n_kv)`` merged into rows by a reshape that moves
no byte (``models/hybrid.py``'s stack of narrower heads, kept as rows of
``n_kv * head``, goes in as it sits, with block-diagonal queries).

How the cache is read at prefill width. A step of a prefill bucket's rows a
lane (``t`` whole query blocks of the kernel: 64 / 256 / 512 / 1024; the
prefill step and the prefill half of every fused step) over the same kind of
cache reads it in place too: ``pallas_attention.prefill_attention`` is handed
the stack, the layer index, the rows' positions and a work list built once
from them and from each lane's count of real rows (``n_valid``), and fetches
the key blocks ``[0, last real position]`` of layer ``l`` that each block of
query rows can see, and no other. Scores and probabilities live in VMEM a
``[query rows x group, key block]`` tile at a time; a kv head's ``group`` query
heads ride one product as more rows; causal masking is by position, inside
the blocks a query block's rows end in. Dense, a 1024-row chunk formed
``[T, heads, S]`` float32 scores over all 2048 positions, 268 MB a layer
written and read back, a quarter of a 7B prefill half on a v5e (PERF.md
section 6, PR 51). Where the dense scores would pass
``blocked_attention.DENSE_SCORE_BYTES`` (no 2048-position configuration) the
kernel does not engage (``prefill_attention_engages``).

Everything else takes ``_dense_attention`` over the plane read out of the
carry, as before: a verify step's ``K + 1`` rows (no whole query block), the
paged pool's gather, any mesh, a cache the kernels do not tile, the CPU. What
the inputs are decides it (``decode_attention_engages``,
``prefill_attention_engages``); the paths share no logic, the dense one being
the plain form the kernels are tested against.

How the weights move. On one device with the Pallas kernel on, the scan's body
closes over the stacked Q40 planes (``PackedQ40`` leaves ``[L, d_in/2, d_out]``
at widths the kernel tiles: loop invariants) and hands ``matmul`` a
``Q40Layer(stack, l)``: the kernel's weight blocks are addressed
``(l, k, j)`` inside the stack (ops/pallas_q40.py), so the nibbles go from
HBM into the kernel once. A Pallas call is opaque to XLA and gets its operands
materialised: scanned, each layer's planes were first copied into a buffer
of their own and then read again by the kernels, a sixth of a 7B decode step
on a v5e (PERF.md section 6, PR 30). Everything else is a scanned input as
ever, the scan slicing layer ``l`` out: the norms and biases, dense weights,
Q40 planes that XLA dequantizes (the kernel off), that a mesh's shard-local
paths take, or that carry an expert axis (``[L, E, ...]``). What a leaf is
decides it (``ops.linear.reads_q40_stack``); nothing selects a path by name.

Optional ``emulate_q80_activations`` reproduces the reference's lossy
activation quantization (cast to Q80 before each quantized matmul and at the
TP sync boundary, src/llm.cpp:232-239,308-314) for numerical parity testing.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental.layout import Layout, with_layout_constraint

from ..formats.model_file import HiddenAct
from ..ops import blocked_attention, pallas_attention
from ..ops.activations import gelu, silu
from ..ops.linear import (
    head,
    matmul,
    pallas_interpret,
    pallas_kernel_active,
    reads_q40_stack,
)
from ..ops.norm import rms_norm
from ..ops.rope import apply_rope, apply_rope_first
from ..telemetry.names import (
    SCOPE_ATTENTION,
    SCOPE_ATTN_OUT,
    SCOPE_EMBED,
    SCOPE_FFN,
    SCOPE_KV_WRITE,
    SCOPE_LAYERS,
    SCOPE_QKV,
)
from .config import LlamaConfig


class LlamaLayerParams(NamedTuple):
    """Per-layer weights, stacked along a leading [n_layers] axis.

    Matmul weights are stored [d_in, d_out] so that y = x @ W (the .m file
    stores the transpose, [d_out, d_in]; the loader transposes once). Each
    matmul field holds either a dense array or a ``PackedQ40`` (weights kept
    quantized in HBM, dequantized inside the matmul — ops/linear.py).

    MoE models (config.n_experts > 0): w1/w2/w3 gain a leading expert axis
    ([L, E, d_in, d_out]) and ``moe_gate`` holds the router ([L, dim, E]);
    dense models carry moe_gate=None.
    """

    wq: jnp.ndarray  # [L, dim, n_heads * head_size] (head_size: config.head_dim, else dim // n_heads)
    wk: jnp.ndarray  # [L, dim, kv_dim]
    wv: jnp.ndarray  # [L, dim, kv_dim]
    wo: jnp.ndarray  # [L, n_heads * head_size, dim]
    w1: jnp.ndarray  # [L, dim, hidden]   gate     (MoE: [L, E, dim, hidden])
    w2: jnp.ndarray  # [L, hidden, dim]   down     (MoE: [L, E, hidden, dim])
    w3: jnp.ndarray  # [L, dim, hidden]   up       (MoE: [L, E, dim, hidden])
    rms_att: jnp.ndarray  # [L, dim]
    rms_ffn: jnp.ndarray  # [L, dim]
    moe_gate: jnp.ndarray | None = None  # [L, dim, n_experts] router, f32
    # Qwen2-family q/k/v projection biases (config.qkv_bias); None for the
    # Llama/Mistral/Mixtral families. Added to the matmul outputs BEFORE
    # RoPE, matching the HF formulation.
    bq: jnp.ndarray | None = None  # [L, dim]
    bk: jnp.ndarray | None = None  # [L, kv_dim]
    bv: jnp.ndarray | None = None  # [L, kv_dim]


class LlamaParams(NamedTuple):
    embedding: jnp.ndarray  # [vocab, dim]
    layers: LlamaLayerParams
    rms_final: jnp.ndarray  # [dim]
    wcls: jnp.ndarray  # [dim, vocab]
    rope_cos: jnp.ndarray  # [seq_len, head_size//2] f32
    rope_sin: jnp.ndarray  # [seq_len, head_size//2] f32


class KVCache(NamedTuple):
    k: jnp.ndarray  # [L, B, S, n_kv_heads, head_size]
    v: jnp.ndarray  # [L, B, S, n_kv_heads, head_size]


class PagedKVCache(NamedTuple):
    """Paged KV layout (the vLLM-style indirection): one device-resident
    pool of fixed-size pages shared by every lane, plus a per-lane page
    table mapping logical block ``b`` of lane ``i`` to physical page
    ``table[i, b]``. A page holds ``page_size`` tokens' K/V for EVERY
    layer at the same physical index, so one table drives all layers.

    ``table`` entries equal to ``n_pages`` mean "unmapped": writes
    through them are dropped by the ``mode="drop"`` scatter and reads
    land past the attention mask. The table rides the cache pytree, so
    every compiled step family threads the indirection automatically —
    no signature changes, and a table update between dispatches is just
    a new pytree leaf (the pool arrays are donated through as always)."""

    k: jnp.ndarray  # [L, n_pages, page_size, n_kv_heads, head_size]
    v: jnp.ndarray  # [L, n_pages, page_size, n_kv_heads, head_size]
    table: jnp.ndarray  # [B, blocks_per_lane] int32 physical page ids


def init_kv_cache(config: LlamaConfig, n_lanes: int, dtype=jnp.float32) -> KVCache:
    shape = (config.n_layers, n_lanes, config.seq_len, config.n_kv_heads, config.head_size)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def init_paged_kv_cache(
    config: LlamaConfig,
    n_lanes: int,
    n_pages: int,
    page_size: int,
    n_blocks: int | None = None,
    dtype=jnp.float32,
) -> PagedKVCache:
    """Zero-filled page pool + all-unmapped tables (every entry is the
    ``n_pages`` sentinel; admission maps real pages per lane).
    ``n_blocks`` is the table width — pass the pool's authoritative
    ``blocks_per_lane`` so the device leaf and the host mirror cannot
    drift; the ceil-div fallback serves direct/test construction."""
    blocks = n_blocks if n_blocks is not None else -(-config.seq_len // page_size)
    shape = (
        config.n_layers, n_pages, page_size,
        config.n_kv_heads, config.head_size,
    )
    return PagedKVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        table=jnp.full((n_lanes, blocks), n_pages, jnp.int32),
    )


def _to_cache_dtype(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """Cast fresh K/V rows to the cache storage dtype. float8_e4m3 (the
    quarter-footprint serving option, --kv-dtype f8) has no inf: saturate
    at its finite max so a rare activation outlier degrades to clipping
    instead of NaN-poisoning the lane's cache."""
    if dtype == jnp.float8_e4m3fn:
        lim = float(jnp.finfo(dtype).max)
        x = jnp.clip(x, -lim, lim)
    return x.astype(dtype)


def _maybe_bias(y: jnp.ndarray, b: jnp.ndarray | None) -> jnp.ndarray:
    """Add a per-layer projection bias when present (Qwen2-family q/k/v,
    config.qkv_bias); identity for the bias-free families."""
    if b is None:
        return y
    return y + b.astype(y.dtype)


def _qdq_q80(x: jnp.ndarray) -> jnp.ndarray:
    """Quantize-dequantize through Q80 blocks — emulates the reference's
    F32->Q80 casts (src/nn/nn-quants.cpp:154-172) via the shared JAX codec."""
    from ..quants.jax_codec import qdq_q80

    return qdq_q80(x, mode="runtime")


def _use_sp(mesh, b: int, t: int | None = None) -> bool:
    """Whether attention should take the sequence-parallel shard_map path:
    needs an sp>1 mesh and whole shards — lanes tiling dp (single-lane
    prefill with dp>1 stays on GSPMD) and, when queries are sequence-sharded
    (t given, ring attention), t tiling sp."""
    if mesh is None or mesh.shape.get("sp", 1) <= 1:
        return False
    if b % mesh.shape.get("dp", 1) != 0:
        return False
    return t is None or t % mesh.shape["sp"] == 0


def _moe_topk(y: jnp.ndarray, moe_gate: jnp.ndarray, n_active: int):
    """Router top-k: returns (weights [B,T,k] f32 — softmax renormalized over
    the selected k, Mixtral semantics — and expert ids [B,T,k] int32). The
    router reads the unquantized normed activations. The reference carries
    n_experts in its header but never executes MoE — SURVEY.md §2.4."""
    logits = jnp.einsum(
        "btd,de->bte", y.astype(jnp.float32), moe_gate.astype(jnp.float32)
    )
    vals, idx = jax.lax.top_k(logits, n_active)
    return jax.nn.softmax(vals, axis=-1), idx


def _moe_router_weights(y: jnp.ndarray, moe_gate: jnp.ndarray, n_active: int) -> jnp.ndarray:
    """Dense routing weights [B, T, E]: top-k weights scattered over the
    expert axis, zero for unselected experts."""
    w, idx = _moe_topk(y, moe_gate, n_active)
    onehot = jax.nn.one_hot(idx, moe_gate.shape[-1], dtype=w.dtype)  # [B,T,k,E]
    return jnp.einsum("btk,btke->bte", w, onehot)


def _moe_ffn_sparse(yq, topw, topi, w1, w2, w3, act_fn, maybe_qdq):
    """Exact sparse top-k dispatch via grouped matmuls: the B*T*k
    (token, expert) assignments are sorted by expert and each expert
    multiplies only its own contiguous row group (``lax.ragged_dot`` — the
    MXU-native MoE primitive; static shapes, no capacity, no token drops).
    Per-token FFN FLOPs scale with k = n_active, not E, unlike a dense
    dispatch that runs every expert on every token."""
    b, t, d = yq.shape
    e, k = w1.shape[0], topi.shape[-1]
    n = b * t
    x_flat = yq.reshape(n, d)
    expert_flat = topi.reshape(n * k)
    token_flat = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    weight_flat = topw.reshape(n * k)
    order = jnp.argsort(expert_flat)  # stable: ties keep token order
    tok_sorted = token_flat[order]
    xs = x_flat[tok_sorted]  # [n*k, d]
    group_sizes = jnp.zeros((e,), jnp.int32).at[expert_flat].add(1)
    g = act_fn(jax.lax.ragged_dot(xs, w1, group_sizes))
    u = jax.lax.ragged_dot(xs, w3, group_sizes)
    ds = jax.lax.ragged_dot(maybe_qdq(g * u), w2, group_sizes)  # [n*k, d]
    contrib = ds * weight_flat[order][:, None].astype(ds.dtype)
    out = jnp.zeros((n, d), ds.dtype).at[tok_sorted].add(contrib)
    return out.reshape(b, t, d)


def _moe_ffn_ep_packed(yq, rw, w1, w2, w3, act_fn, maybe_qdq, mesh):
    """Expert-parallel MoE over PackedQ40 stacks WITHOUT dequantizing to
    HBM: shard_map pins each device's resident experts (ep axis) and tp
    slice, runs the dequant-in-matmul kernel per local expert, and psums the
    routed partial sums over (ep, tp) — the EP-native layout where weights
    never move, only the (small) activations are replicated."""
    from jax.sharding import PartitionSpec as P

    from ..ops.linear import q40_matmul_local
    from ..quants.packed import PackedQ40

    e = w1.packed.shape[0]
    ep = mesh.shape.get("ep", 1)
    e_local = e // ep

    def body(yq, rw, p1, s1, p2, s2, p3, s3):
        ep_idx = jax.lax.axis_index("ep")
        out = None
        for el in range(e_local):
            g = act_fn(q40_matmul_local(yq, PackedQ40(p1[el], s1[el])))
            u = q40_matmul_local(yq, PackedQ40(p3[el], s3[el]))
            d = q40_matmul_local(maybe_qdq(g * u), PackedQ40(p2[el], s2[el]))
            w_e = jax.lax.dynamic_slice_in_dim(rw, ep_idx * e_local + el, 1, axis=-1)
            term = d * w_e.astype(d.dtype)
            out = term if out is None else out + term
        return jax.lax.psum(out, ("ep", "tp"))

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(), P(),
            P("ep", None, "tp"), P("ep", None, "tp"),  # w1 planes [E, din/2|32, h]
            P("ep", "tp", None), P("ep", "tp", None),  # w2 planes [E, h/2|32, d]
            P("ep", None, "tp"), P("ep", None, "tp"),  # w3 planes
        ),
        out_specs=P(),
        check_vma=False,
    )(yq, rw, w1.packed, w1.scales, w2.packed, w2.scales, w3.packed, w3.scales)


# sequence-length threshold at which the single-shard PackedQ40 path stops
# looping over every expert (dequant-in-matmul, bytes-optimal) and instead
# dequantizes each expert ONCE and takes the grouped ragged_dot dispatch
# (FLOPs ∝ k). Shapes are static under jit, so this is a compile-time
# branch. Gated on T (per-lane step length), NOT B*T: decode (T=1) and
# speculative verify (T=K=4) are weight-bandwidth-bound at ANY lane count —
# every resident expert's bytes are the cost either way, so dequantizing to
# a dense temp would only add traffic — while prefill/training sequences
# (T >= this) are compute-bound, where paying ~4.5x the expert bytes once
# buys an E/k FLOPs cut.
MOE_PACKED_SPARSE_MIN_TOKENS = 32


def _moe_ffn(y, yq, lp, act_fn, n_active: int, maybe_qdq, ep_sharded: bool = False,
             mesh=None):
    """Gated-FFN mixture. Dispatch:

    - dense expert weights, single shard: exact sparse grouped dispatch
      (``_moe_ffn_sparse``) — FLOPs proportional to k, not E.
    - PackedQ40 + Pallas, single shard, decode-shaped (T below
      MOE_PACKED_SPARSE_MIN_TOKENS — plain decode and speculative verify):
      static per-expert dequant-in-matmul loop (weight-bandwidth-bound:
      every resident expert's bytes are the cost, and they are read exactly
      once, straight from the packed planes).
    - PackedQ40, single shard, prefill/training-shaped
      (T >= MOE_PACKED_SPARSE_MIN_TOKENS): dequantize each expert once and
      run the same grouped ragged_dot dispatch as dense — FLOPs ∝ k, not E
      (round-4 weak #3: the loop paid E/k× the FLOPs on prefill).
    - PackedQ40 + Pallas, ep-sharded mesh: shard_map expert-parallel path
      (``_moe_ffn_ep_packed``) — weights stay quantized and resident.
    - otherwise (dense weights on an ep mesh, or no Pallas): dense-dispatch
      einsums whose expert axis GSPMD partitions over ep; selection happens
      through the zero routing weights."""
    from ..ops.linear import pallas_kernel_active
    from ..quants.packed import PackedQ40, unpack_q40

    w1, w2, w3 = lp.w1, lp.w2, lp.w3
    if isinstance(w1, PackedQ40):
        # the ep shard_map path needs the mesh handle (pipeline stages run
        # under vmap, where shard_map does not nest) and whole-block tp
        # shards: hidden % (32*tp) covers the w2 plane sharding AND the
        # per-shard Q80 qdq blocks; otherwise fall through to unpack+einsum
        def _ep_path_ok():
            if mesh is None:
                return False
            tp = mesh.shape.get("tp", 1)
            hidden = w1.packed.shape[-1]
            return tp == 1 or hidden % (32 * tp) == 0

        keep_packed = ep_sharded or yq.shape[1] < MOE_PACKED_SPARSE_MIN_TOKENS
        if pallas_kernel_active() and keep_packed and (
            not ep_sharded or _ep_path_ok()
        ):
            rw = _moe_router_weights(y, lp.moe_gate, n_active)
            if ep_sharded:
                return _moe_ffn_ep_packed(
                    yq, rw, w1, w2, w3, act_fn, maybe_qdq, mesh
                )
            out = None
            for e in range(w1.packed.shape[0]):
                g = act_fn(matmul(yq, PackedQ40(w1.packed[e], w1.scales[e])))
                u = matmul(yq, PackedQ40(w3.packed[e], w3.scales[e]))
                d = matmul(maybe_qdq(g * u), PackedQ40(w2.packed[e], w2.scales[e]))
                term = d * rw[..., e : e + 1].astype(d.dtype)
                out = term if out is None else out + term
            return out
        w1 = unpack_q40(w1, yq.dtype)
        w2 = unpack_q40(w2, yq.dtype)
        w3 = unpack_q40(w3, yq.dtype)
    if not ep_sharded:
        topw, topi = _moe_topk(y, lp.moe_gate, n_active)
        return _moe_ffn_sparse(yq, topw, topi, w1, w2, w3, act_fn, maybe_qdq)
    rw = _moe_router_weights(y, lp.moe_gate, n_active)
    g = act_fn(jnp.einsum("btd,edh->bteh", yq, w1))
    u = jnp.einsum("btd,edh->bteh", yq, w3)
    d = jnp.einsum("bteh,ehd->bted", maybe_qdq(g * u), w2)
    return jnp.einsum("bted,bte->btd", d, rw.astype(d.dtype))


def _dense_attention(qf, kf, vf, mask, scale, sink=None):
    """Single-device GQA attention with materialized scores (reference
    multiheadAtt_F32, src/nn/nn-cpu-ops.cpp:749-784). qf: [B,T,K,G,H] f32;
    kf: [B,S,K,H] f32, vf: [B,S,K,Hv] f32; mask: [B,T,S]. ``sink`` [K,G] f32
    (None: none): a logit a head that joins the softmax as one more column
    and gives no value.

    Who still takes it: ``llama_forward`` and ``models/hybrid.py``'s
    attention layers wherever neither in-place kernel engages (the verify
    programs' ``K + 1`` rows, the paged pool's gathered view, a mesh without
    sp, a float32 or f8 cache, a head size or row width the kernels do not
    tile, a context that is not whole blocks, the CPU; a prefill chunk over
    any of those), and training (``train_layer_step_fn``). It is also what
    tests/test_pallas_attention.py holds both kernels to, at both forms of
    stack."""
    scores = jnp.einsum("btkgh,bskh->btkgs", qf * scale, kf)
    scores = jnp.where(mask[:, :, None, None, :], scores, -jnp.inf)
    if sink is not None:
        column = jnp.broadcast_to(sink[None, None, :, :, None], (*scores.shape[:-1], 1))
        probs = jax.nn.softmax(jnp.concatenate([scores, column], axis=-1), axis=-1)[..., :-1]
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("btkgs,bskh->btkgh", probs, vf)


def gqa_project(cfg: LlamaConfig, yq, wq, wk, wv, positions, rope_cos, rope_sin,
                biases=(None, None, None), norms=None, project=matmul, n_kv=None):
    """A GQA layer's queries, keys and values from its normed input: the
    three projections (``project``: ``matmul``, or a mesh's sliced matmul),
    their biases where the family has them, the per-head norm of queries and
    keys where it has that (``norms``: the two gains, applied over a head
    BEFORE the rotation), the rotation (none where ``rope_cos`` is None: a
    model without a positional term; the first ``rotary_dim`` numbers of a
    head alone where the config names that width), and the barrier that holds
    the cache back until all three are done. ``n_kv``: the kv heads of this
    layer's kind where they are not ``n_kv_heads``; a value head is
    ``value_head_size`` wide. One form for models/llama.py's scan and
    models/hybrid.py's attention layers."""
    b, t = positions.shape
    n_heads, n_kv, hd = cfg.n_heads, n_kv or cfg.n_kv_heads, cfg.head_size
    q = _maybe_bias(project(yq, wq), biases[0]).reshape(b, t, n_heads, hd)
    k = _maybe_bias(project(yq, wk), biases[1]).reshape(b, t, n_kv, hd)
    v = _maybe_bias(project(yq, wv), biases[2]).reshape(b, t, n_kv, cfg.value_head_size)
    if norms is not None:
        q = rms_norm(q, norms[0], cfg.norm_epsilon)
        k = rms_norm(k, norms[1], cfg.norm_epsilon)

    if rope_cos is not None:
        q = apply_rope_first(q, cfg.rope_dim, rope_cos, rope_sin, positions)
        k = apply_rope_first(k, cfg.rope_dim, rope_cos, rope_sin, positions)
    # all three projections finish before the cache is touched. Left
    # to itself XLA schedules the wq kernel between the K plane's read
    # and the scores that use it, the kernel claims the fast memory
    # the plane could sit in, and the plane is parked in HBM instead:
    # written and read once more, 6.5 ms against 2.9 a 7B decode step
    # on a v5e (PERF.md section 6, PR 27). An identity on the values.
    return jax.lax.optimization_barrier((q, k, v))


def kv_append(k_all, v_all, at, k, v, row_major=None):
    """Fresh rows scattered into the stacked cache at ``at`` (layer, lane or
    page, position or slot), in place on the carry; ``mode="drop"``: a row
    whose position lies past the context is written nowhere. ``row_major``
    (one device only: GSPMD cannot partition the constraint and would gather
    a sharded cache to apply it) keeps the stack in the row-major layout it
    arrives and leaves in. Left free, XLA gives the carry of a loop whose
    attention is wide (a 1024-token prefill chunk at 4 kv heads) a
    kv-head-major layout and converts the WHOLE carry to the scatter's layout
    and back in every layer (PERF.md section 6, PR 27)."""
    k_all = k_all.at[at].set(_to_cache_dtype(k, k_all.dtype), mode="drop")
    v_all = v_all.at[at].set(_to_cache_dtype(v, v_all.dtype), mode="drop")
    if row_major is not None:
        k_all = with_layout_constraint(k_all, row_major)
        v_all = with_layout_constraint(v_all, row_major)
    return k_all, v_all


def dense_plane_attention(q, k_all, v_all, l, attn_mask, scale, n_kv: int, sink=None):
    """GQA attention over layer ``l``'s whole contiguous plane
    (``[B, S, n_kv, hd]``, or with the two last axes merged, as
    models/hybrid.py keeps 64-wide heads; a merged value plane may hold heads
    of another width), read out of the carry AFTER the append: the fresh rows
    are in it. q: ``[B, T, n_heads, hd]``; ``sink`` ``[n_heads]``
    (``_dense_attention``)."""
    b, t, n_heads, hd = q.shape
    qf = q.astype(jnp.float32).reshape(b, t, n_kv, n_heads // n_kv, hd)
    k_cache = jax.lax.dynamic_index_in_dim(k_all, l, 0, keepdims=False)
    v_cache = jax.lax.dynamic_index_in_dim(v_all, l, 0, keepdims=False)
    planes = (c.astype(jnp.float32).reshape(b, c.shape[1], n_kv, -1)
              for c in (k_cache, v_cache))
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(n_kv, n_heads // n_kv)
    return _dense_attention(qf, *planes, attn_mask, scale, sink)


def decode_attention_engages(cache, mesh, n_heads: int, n_kv: int | None = None,
                             latent: bool = False) -> bool:
    """Whether a step of one row a lane (``t == 1``) attends this cache in
    place through ``ops/pallas_attention.py``: contiguous stacks that the
    kernel tiles (a ``KVCache``'s, or a ``models/hybrid.py`` ``HybridCache``'s
    merged ones; not the paged pool), on one device, where Pallas compiles (a
    TPU, or interpret mode). A leaf's shape does not say what its row holds,
    so the caller does: ``n_kv``, the kv heads of a merged row; ``latent``
    (``models/deepseek.py``), the two leaves one latent row and its rope part
    a position, read in place where the cache is those two alone (with an
    indexer's keys beside them, attention reads the rows the indexer chooses).
    What the inputs are decides it, as ``reads_q40_stack`` does for the
    weights; the three blocks' forwards and the engine's counters ask this
    one question."""
    return (
        not isinstance(cache, PagedKVCache)
        and (not latent or isinstance(cache, KVCache))
        and mesh is None
        and pallas_kernel_active()
        # (which stacks, and a value stack of another width than the keys',
        # in which form: the kernel says what it takes)
        and pallas_attention.supports(cache.k, n_heads, n_kv, cache.v, latent)
    )


def prefill_attention_engages(cache, mesh, b: int, t: int, n_heads: int,
                             n_kv: int | None = None) -> bool:
    """Whether a step of ``t > 1`` rows a lane attends a plain full-context
    plane of this cache in place, a key block at a time
    (``pallas_attention.prefill_attention``): ``t`` whole query blocks of the
    kernel (the prefill buckets; not a verify step's ``K + 1`` rows), a cache
    the decode kernel takes (``decode_attention_engages``) in a form the
    prefill kernel takes too, and dense scores under the size from which
    ``ops/blocked_attention.py`` walks the key blocks as an XLA loop. Both
    blocks' forwards and the engine's ``path_facts`` ask this one question; a
    window layer's ring and a block-sparse layer's chosen blocks are not
    plain planes and do not ask."""
    return (
        t > 1
        and pallas_attention.query_rows(t) is not None
        and decode_attention_engages(cache, mesh, n_heads, n_kv)
        and cache.k.shape == cache.v.shape
        and pallas_attention.supports_prefill(cache.k, n_heads, n_kv)
        and not blocked_attention.engages(b, t, n_heads, cache.k.shape[2])
    )


def llama_forward(
    config: LlamaConfig,
    params: LlamaParams,
    tokens: jnp.ndarray,  # [B, T] int32
    positions: jnp.ndarray,  # [B, T] int32 (per-lane positions; fixes reference defect (b))
    cache: KVCache,
    emulate_q80_activations: bool = False,
    mesh=None,
    q80_sync: bool = False,
    n_valid: jnp.ndarray | None = None,  # [B] int32: leading real rows a lane
    head_row: jnp.ndarray | None = None,  # [B] int32: the one row a lane whose logits are kept
) -> tuple[jnp.ndarray, KVCache]:
    """Returns (logits [B, T, vocab] float32, updated cache); with
    ``head_row``, logits ``[B, 1, vocab]``: each lane's row at that index and
    no other (``ops.linear.head`` cuts the hidden state to it before the final
    norm and ``wcls``; the cache is written for all ``T`` rows either way). A
    prefill chunk names the row of its last real token; a decode step and a
    verify window name none and get every row.

    Works for prefill (T > 1) and decode (T = 1) alike; the KV cache is
    per-lane (fixes reference defect (c) where all lanes shared one cache).

    The cache is the layer scan's carry and is appended in place (module
    header, "How the cache moves"): a caller that donates ``cache`` gets the
    same buffers back with ``B * T`` rows a layer written, and no copy of a
    plane or of the stack is part of the program's dataflow. Attention reads
    layer ``l``'s plane AFTER that layer's append, so a query sees its own
    fresh key, as it did when each plane was updated on its own. At ``T = 1``
    the read is each lane's rows up to its position, in place (module header,
    "How the cache is read at decode width"); at a prefill bucket's ``T`` it is
    the key blocks up to the chunk's last real row, in place too ("How the
    cache is read at prefill width"). ``n_valid`` says how many leading rows
    of each lane are real (a bucket's padded tail bounds no read; None: the
    rows whose position lies inside the context); it changes no real row's
    result.

    ``cache`` may be a :class:`PagedKVCache` (paged attention): K/V are
    gathered per lane through the page table into the same ``[B, S, ...]``
    view the contiguous path reads — identical values in identical order,
    so the attention math (and the token streams) are byte-identical to
    the contiguous layout — and the KV append scatters through the table
    to ``(page, slot)``. The choice is a pytree-structure property, fixed
    at trace time: one compiled program per layout, no runtime flag.

    With ``mesh`` (axes dp/tp/sp) and sp > 1, attention runs sequence-
    parallel over the S-sharded cache via flash-stats psum
    (parallel/ring_attention.sp_attention) instead of relying on GSPMD to
    partition the dense-scores einsum.

    ``q80_sync`` (with a tp>1 mesh): the wo/w2 row-parallel outputs cross
    the mesh as Q80 (int8 + f16 block scales) instead of f32 — the
    reference's default transport (--buffer-float-type q80, ZQ pipe
    src/llm.cpp:150) realized as psum_scatter + quantized all_gather
    (parallel/collectives.q80_sync_matmul).
    """
    b, t = tokens.shape
    h_cfg = config
    n_heads, n_kv, hd = h_cfg.n_heads, h_cfg.n_kv_heads, h_cfg.head_size
    eps = h_cfg.norm_epsilon
    act_fn = silu if h_cfg.hidden_act == HiddenAct.SILU else gelu

    maybe_qdq = _qdq_q80 if emulate_q80_activations else (lambda y: y)
    paged = isinstance(cache, PagedKVCache)
    # sp (sequence-parallel) attention shards the contiguous S axis; the
    # paged pool has no per-lane S axis to shard, so paged caches take
    # the dense path (GSPMD still partitions the einsums) — pod serving
    # meshes are pure-TP, where the pool shards over kv heads instead
    use_sp = _use_sp(mesh, b) and not paged
    use_q80_sync = False
    if q80_sync and mesh is not None:
        from ..parallel.collectives import q80_sync_engages, q80_sync_matmul

        # shared predicate with the runtime_setup startup log
        use_q80_sync = q80_sync_engages(h_cfg, dict(mesh.shape))
    from ..quants.packed import PackedQ40, Q40Layer

    use_ring_sync = False
    # pure-TP mesh + Q40 planes + the kernel: every matmul runs the kernel
    # on its LOCAL shard under shard_map (ops/ring_collective.py) — the
    # GSPMD custom_partitioning wrapper behind ``matmul`` cannot compile
    # for real chips (libtpu has no custom-call partitioner)
    tp_local = False
    if mesh is not None:
        from ..ops.ring_collective import (
            pure_tp,
            ring_sync_engages,
            ring_sync_matmul,
            ring_sync_supported,
            tp_reduced_matmul,
            tp_sliced_matmul,
        )

        # ring-overlapped TP sync (default on, DLLAMA_RING_SYNC=off escape
        # hatch): pure-TP meshes route the wo/w2 row-parallel matmuls
        # through the chunked ring instead of GSPMD's post-matmul
        # all-reduce; with q80_sync the gather half ships the Q80 wire
        use_ring_sync = ring_sync_engages(h_cfg, dict(mesh.shape))
        tp_local = pure_tp(dict(mesh.shape)) and pallas_kernel_active()

    def shard_local(w) -> bool:
        return tp_local and isinstance(w, PackedQ40) and w.packed.ndim == 2

    def sliced_matmul(y, w):
        """A column-parallel (row-sliced) matmul — wq/wk/wv/w1/w3/wcls: the
        output stays sharded on d_out, no sync."""
        if shard_local(w):
            return tp_sliced_matmul(y, w, mesh)
        return matmul(y, w)

    def synced_matmul(y, w):
        """A row-parallel (col-sliced) wo/w2 matmul plus its TP sync:
        ring-overlapped (optionally Q80-wire), Q80 psum_scatter+gather, or
        the plain psum (shard-local on a pure-TP mesh, else the GSPMD
        matmul whose all-reduce XLA inserts)."""
        if use_ring_sync:
            d_out = w.d_out if hasattr(w, "d_out") else w.shape[-1]
            if ring_sync_supported(d_out, mesh.shape["tp"], use_q80_sync):
                out = ring_sync_matmul(y, w, mesh, q80_wire=use_q80_sync)
                # the Q80 wire quantizes ON the wire (the q80 branch's
                # contract); the f32 wire keeps the output-side cast
                return out if use_q80_sync else maybe_qdq(out)
        if use_q80_sync:
            return q80_sync_matmul(y, w, mesh)
        if shard_local(w):
            return maybe_qdq(tp_reduced_matmul(y, w, mesh))
        return maybe_qdq(matmul(y, w))

    # device scopes (telemetry/names.py): every part of the step carries a
    # fixed ``dl.*`` name in its HLO metadata, which is what a device trace
    # is reduced by — no cost on the device, none in the compile-cache key
    with jax.named_scope(SCOPE_EMBED):
        x = params.embedding[tokens]  # [B, T, dim]
    lane_idx = jnp.arange(b)[:, None]  # [B, 1]

    # one row a lane on one device: the cache is attended in place (module
    # header, "How the cache is read at decode width")
    in_place = t == 1 and decode_attention_engages(cache, mesh, n_heads)
    # a prefill bucket's rows on one device: in place too, a key block at a
    # time (module header, "How the cache is read at prefill width")
    chunk_in_place = prefill_attention_engages(cache, mesh, b, t, n_heads)
    with jax.named_scope(SCOPE_ATTENTION):
        if in_place:
            attn_plan = pallas_attention.lane_blocks(positions, h_cfg.seq_len)
        elif chunk_in_place:
            if n_valid is None:
                n_valid = jnp.sum(positions < h_cfg.seq_len, axis=1).astype(jnp.int32)
            attn_plan = pallas_attention.chunk_blocks(
                positions, n_valid, h_cfg.seq_len, pallas_attention.query_rows(t))
        else:
            # cache index validity: query at position p attends to cache slots s <= p
            s_idx = jnp.arange(h_cfg.seq_len)  # [S]
            attn_mask = s_idx[None, None, :] <= positions[:, :, None]  # [B, T, S]

    if paged:
        # page indirection, computed ONCE (the table is layer-invariant):
        # write targets (page, slot) per (lane, position) and the flat
        # gather index reassembling each lane's logical [S] view from its
        # pages. Sentinel table entries (== n_pages: unmapped blocks) and
        # positions >= seq_len (parked/idle lanes) become out-of-range
        # indices — the mode="drop" scatter discards those writes and the
        # clamped gather reads slots the s <= pos mask already excludes.
        n_pages, page = cache.k.shape[1], cache.k.shape[2]
        table = cache.table  # [B, blocks_per_lane]
        n_blocks = table.shape[1]
        with jax.named_scope(SCOPE_KV_WRITE):
            w_blk = jnp.clip(positions // page, 0, n_blocks - 1)
            w_page = jnp.take_along_axis(table, w_blk, axis=1)  # [B, T]
            w_page = jnp.where(positions < h_cfg.seq_len, w_page, n_pages)
            w_slot = positions % page
        with jax.named_scope(SCOPE_ATTENTION):
            gather_idx = (
                table[:, :, None] * page
                + jnp.arange(page, dtype=jnp.int32)[None, None, :]
            ).reshape(b, n_blocks * page)[:, : h_cfg.seq_len]  # [B, S]

    row_major = Layout(major_to_minor=tuple(range(cache.k.ndim)))

    # the Q40 stacks the kernel reads by layer index (module header, "How
    # the weights move"): closed over by the scan's body, not scanned. What a
    # leaf is decides it, and a mesh's shard-local paths keep their planes
    layers = params.layers
    in_stack = tuple(
        f for f in ("wq", "wk", "wv", "wo", "w1", "w2", "w3")
        if mesh is None and reads_q40_stack(getattr(layers, f))
    )
    scanned_layers = layers._replace(**dict.fromkeys(in_stack))

    def plane_attention(q, k_all, v_all, l, scale):
        """Attention over layer ``l``'s whole plane, sliced out of the carry:
        every width and layout the in-place kernel does not take."""
        if not (paged or use_sp):
            return dense_plane_attention(q, k_all, v_all, l, attn_mask, scale, n_kv)
        group = n_heads // n_kv
        qf = q.astype(jnp.float32).reshape(b, t, n_kv, group, hd)
        # layer l's plane, read out of the carry AFTER the append: the
        # fresh rows are in it (contiguous: [B, S, n_kv, hd]; paged:
        # [n_pages, page_size, n_kv, hd])
        k_cache = jax.lax.dynamic_index_in_dim(k_all, l, 0, keepdims=False)
        v_cache = jax.lax.dynamic_index_in_dim(v_all, l, 0, keepdims=False)
        if paged:
            # gather each lane's logical [S] view through the page table:
            # the same values a contiguous lane plane would hold, in the
            # same order, so the f32 attention below is byte-identical to
            # the contiguous path (pinned by tests/test_prefix_cache.py)
            kf = k_cache.reshape(n_pages * page, n_kv, hd)[gather_idx]
            vf = v_cache.reshape(n_pages * page, n_kv, hd)[gather_idx]
            return _dense_attention(
                qf, kf.astype(jnp.float32), vf.astype(jnp.float32),
                attn_mask, scale,
            )
        from ..parallel.ring_attention import sp_attention

        return sp_attention(qf, k_cache, v_cache, positions, mesh, scale)

    def layer_step(carry, layer_in):
        # the stacked cache rides the carry ([L, ...]; module header, "How
        # the cache moves"); ``l`` is this layer's index into it, and into
        # the weight stacks the kernel reads
        x, k_all, v_all = carry
        lp, l = layer_in
        lp = lp._replace(**{f: Q40Layer(getattr(layers, f), l) for f in in_stack})
        dtype = x.dtype

        with jax.named_scope(SCOPE_QKV):
            y = rms_norm(x, lp.rms_att, eps)
            yq = maybe_qdq(y)
            q, k, v = gqa_project(
                h_cfg, yq, lp.wq, lp.wk, lp.wv, positions, params.rope_cos,
                params.rope_sin, biases=(lp.bq, lp.bk, lp.bv), project=sliced_matmul,
            )

        # KV append at per-lane positions (reference OP_SHIFT, scatter on
        # TPU). mode="drop" pins JAX's default out-of-bounds scatter
        # semantics: a speculative-verify lane near seq_len writes its
        # overshooting draft slots nowhere, so per-lane spec gating needs no
        # global barrier (scheduler._run's per-lane d_max relies on this).
        # Paged caches scatter through the page table to (page, slot)
        # instead of (lane, position) — same drop rule, and unmapped
        # sentinel entries drop the write too.
        with jax.named_scope(SCOPE_KV_WRITE):
            at = (l, w_page, w_slot) if paged else (l, lane_idx, positions)
            k_all, v_all = kv_append(
                k_all, v_all, at, k, v, row_major if mesh is None else None)

        # GQA attention in f32 (reference multiheadAtt_F32, nn-cpu-ops.cpp:749-784)
        with jax.named_scope(SCOPE_ATTENTION):
            scale = 1.0 / float(hd) ** 0.5
            if in_place:
                # the kernel fetches each lane's rows [0, pos] of layer l out
                # of the carry, AFTER the append: no plane is sliced out
                attn = pallas_attention.decode_attention(
                    q.reshape(b, n_heads, hd), k_all, v_all, l, attn_plan,
                    scale, interpret=pallas_interpret(),
                )
            elif chunk_in_place:
                # the key blocks up to the chunk's last real row, of layer l,
                # out of the carry: no plane is sliced out, no score leaves VMEM
                attn = pallas_attention.prefill_attention(
                    q, k_all, v_all, l, attn_plan, scale,
                    interpret=pallas_interpret(),
                )
            else:
                attn = plane_attention(q, k_all, v_all, l, scale)
            attn = attn.reshape(b, t, n_heads * hd).astype(dtype)

        # sync-boundary cast (ZQ pipe) + merge_add; with a compressed wire
        # (q80/ring-q80) the quantization happens ON the wire instead of as
        # an output-side qdq cast
        with jax.named_scope(SCOPE_ATTN_OUT):
            x = x + synced_matmul(maybe_qdq(attn), lp.wo)

        with jax.named_scope(SCOPE_FFN):
            y = rms_norm(x, lp.rms_ffn, eps)
            yq = maybe_qdq(y)
            if h_cfg.n_experts > 0:
                d = _moe_ffn(
                    y, yq, lp, act_fn, h_cfg.n_active_experts, maybe_qdq,
                    ep_sharded=mesh is not None and mesh.shape.get("ep", 1) > 1,
                    mesh=mesh,
                )
                x = x + maybe_qdq(d)
            else:
                g = act_fn(sliced_matmul(yq, lp.w1))
                u = sliced_matmul(yq, lp.w3)
                x = x + synced_matmul(maybe_qdq(g * u), lp.w2)

        return (x, k_all, v_all), None

    with jax.named_scope(SCOPE_LAYERS):
        layer_index = jnp.arange(cache.k.shape[0], dtype=jnp.int32)
        (x, new_k, new_v), _ = jax.lax.scan(
            layer_step, (x, cache.k, cache.v), (scanned_layers, layer_index)
        )

    logits = head(  # [B, T, vocab], or [B, 1, vocab] at head_row
        x, lambda x: rms_norm(x, params.rms_final, eps), params.wcls, h_cfg.vocab_size,
        head_row=head_row, qdq=maybe_qdq, project=sliced_matmul,
    )
    out_cache = (
        PagedKVCache(k=new_k, v=new_v, table=cache.table)
        if paged
        else KVCache(k=new_k, v=new_v)
    )
    return logits, out_cache


def llama_forward_train(
    config: LlamaConfig,
    params: LlamaParams,
    tokens: jnp.ndarray,  # [B, T] int32
    mesh=None,
) -> jnp.ndarray:
    """Cache-free causal forward over a full sequence — the training-mode twin
    of ``llama_forward`` (the reference is inference-only; training support is
    a capability extension, same layer math). Returns logits [B, T, vocab].

    With ``mesh`` and sp > 1 the sequence axis is sharded and attention runs
    as ring attention (KV blocks rotate over the sp axis via ppermute,
    parallel/ring_attention.ring_attention) — long-context training/prefill
    never materializes the full [T, T] score matrix per device."""
    b, t = tokens.shape
    eps = config.norm_epsilon
    use_sp = _use_sp(mesh, b, t)

    x = params.embedding[tokens]
    layer_step = train_layer_step_fn(
        config, params.rope_cos, params.rope_sin, mesh=mesh if use_sp else None,
        ep_sharded=mesh is not None and mesh.shape.get("ep", 1) > 1,
        moe_mesh=mesh,
    )
    x, _ = jax.lax.scan(layer_step, x, params.layers)
    y = rms_norm(x, params.rms_final, eps)
    return matmul(y, params.wcls).astype(jnp.float32)[..., : config.vocab_size]


def train_layer_step_fn(config: LlamaConfig, rope_cos, rope_sin, mesh=None,
                        ep_sharded=False, moe_mesh=None):
    """The causal full-sequence transformer layer as a lax.scan step
    ``(x [B,T,dim], lp) -> (x, None)`` — shared by llama_forward_train and
    the pipeline-parallel schedule (parallel/pipeline.py). With ``mesh``,
    attention runs as ring attention over sp (caller must guarantee whole
    shards; pipeline stages pass mesh=None — shard_map does not nest)."""
    n_heads, n_kv, hd = config.n_heads, config.n_kv_heads, config.head_size
    eps = config.norm_epsilon
    act_fn = silu if config.hidden_act == HiddenAct.SILU else gelu

    def layer_step(x, lp):
        b, t = x.shape[0], x.shape[1]
        dtype = x.dtype
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
        y = rms_norm(x, lp.rms_att, eps)
        q = _maybe_bias(matmul(y, lp.wq), lp.bq).reshape(b, t, n_heads, hd)
        k = _maybe_bias(matmul(y, lp.wk), lp.bk).reshape(b, t, n_kv, hd)
        v = _maybe_bias(matmul(y, lp.wv), lp.bv).reshape(b, t, n_kv, hd)
        q = apply_rope(q, rope_cos, rope_sin, positions)
        k = apply_rope(k, rope_cos, rope_sin, positions)

        group = n_heads // n_kv
        qf = q.astype(jnp.float32).reshape(b, t, n_kv, group, hd)
        scale = 1.0 / float(hd) ** 0.5
        if mesh is not None:
            from ..parallel.ring_attention import ring_attention

            attn = ring_attention(qf, k.astype(jnp.float32), v.astype(jnp.float32), mesh, scale)
        else:
            causal = jnp.tril(jnp.ones((t, t), bool))
            attn = _dense_attention(
                qf, k.astype(jnp.float32), v.astype(jnp.float32),
                jnp.broadcast_to(causal[None], (b, t, t)), scale,
            )
        attn = attn.reshape(b, t, n_heads * hd)
        x = x + matmul(attn.astype(dtype), lp.wo)

        y = rms_norm(x, lp.rms_ffn, eps)
        if config.n_experts > 0:
            x = x + _moe_ffn(
                y, y, lp, act_fn, config.n_active_experts, lambda v: v,
                ep_sharded=ep_sharded, mesh=moe_mesh,
            )
        else:
            x = x + matmul(act_fn(matmul(y, lp.w1)) * matmul(y, lp.w3), lp.w2)
        return x, None

    return layer_step
