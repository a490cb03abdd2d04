"""The DeepSeek-V3.2 family (``model_type: deepseek_v32``): DeepSeek-V3's block
(`families/deepseek_v3.py`) with a query latent, expert groups in the router,
YaRN frequencies, and learned sparse attention: a lightning indexer with a
cache of its own scores every earlier position for a query, and attention
reads the ``index_topk`` best. What `harness/cells.py` `load_family` asks of
an architecture; the plain reference below imports nothing of the program.

The block (``h`` the stream, ``n = rmsnorm(h)``; `config.json` keys in brackets):

    cq  = rmsnorm(Wqa n)                     [q_lora_rank], a gain of its own
    q   = Wqb cq                             heads of [q_nope; q_pe]
    [c; k_pe] = Wkva n;  [k_nope_i; v_i] = Wkvb_i rmsnorm(c)
    rotary embedding on q_pe and k_pe with YaRN's frequencies [rope_scaling]:
        f_i = theta^(-2i/64); low, high = floor, ceil of
        64 ln(orig / (beta 2 pi)) / (2 ln theta) for beta_fast, beta_slow, clamped;
        ramp_i = clip((i - low) / (high - low), 0, 1);  f'_i = f_i (1 - ramp_i) + f_i / factor ramp_i
    the indexer [index_n_heads, index_head_dim, index_topk]:
        qI_j = WIq cq;  kI = layernorm(WIk n) (gain and bias)
        the rotary embedding on the first 64 numbers of every qI_j and of kI
        w = WIw n / sqrt(index_n_heads index_head_dim)
        I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s)),  s <= t
        S_t = the index_topk positions of largest I(t, .), ties to the lower position
    s_i(t, u) = (q_nope_i(t) . k_nope_i(u) + q_pe_i(t) . k_pe(u)) m^2 / sqrt(nope + rope),
        m = 0.1 mscale_all_dim ln(factor) + 1;  softmax over u in S_t ONLY
    h'  = h + Wo [o_i]

    layers < first_k_dense_replace: a dense gated FFN; the others:
        s = sigmoid(Wg n2), s' = s + b; a group [n_group] scores the sum of its
        two largest s'; outside the [topk_group] best groups s' is masked; the
        [num_experts_per_tok] largest s' are chosen; w_e = routed_scaling_factor s_e / sum_chosen s
        h'' = h' + sum_{e chosen AND held here} w_e FFN_e(n2) + FFN_shared(n2)

The held share (`model-configs` guide, section 4): the chip holds
``n_routed_experts`` experts, ids ``deployment.experts_first`` onward, of the
``deployment.n_routed_experts_published`` the router scores; what the absent
experts would add is left out, here as in the program, and that partial
result goes on to the next layer.

The reference computes the EXPANDED attention, a sort for the top-k, every
held expert on every token weighted by the route as written, one sequence and
one block of queries at a time so that it fits beside the engine.
Departures: adjacent-pair rotary embedding in both the attention and the
indexer (``assumed``); the published indexer turns qI and kI by a Hadamard
matrix and holds them in FP8, an orthogonal turn that changes no product and
is left out, the keys kept in the cache's dtype; weights Q40, dequantized here.
"""

from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from harness import cells
from harness.reference import _rms_norm, _rope, _rounder, dequant_q40
from harness.weights import q40_plane, seed_key

_v3 = cells.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "deepseek_v3.py"),
    "bench_family_deepseek_v3")
route_difference_share = _v3.route_difference_share

_HIDDEN_ACT = {"gelu": 0, "silu": 1}
_SCORE = {"softmax": 0, "sigmoid": 1}
# Output rms of each matmul for an input of rms 1: deepseek_v3's, and the
# indexer's. Index queries and keys have gain 2 like the attention's, so that
# the scores of the positions near the 2048th place are spread and not one
# value; the heads' weights are of rms 1 and either sign.
GAIN = dict(_v3.GAIN, wqa=1.0, idx_wq=2.0, idx_wk=2.0)
# The selection bias is uniform in +-BIAS_SPREAD, a fifth of deepseek_v3's. A
# trained bias evens the experts' load; a drawn one does not, and where a chip
# holds a sixteenth of the experts the mean of its 16 draws moves THIS chip's
# load by the seed: at +-0.05 the pairs that land on the held share vary by
# 13 % a layer from seed to seed (a simulation of the rule over 12 seeds;
# 4.7 % at 0.02, 1.3 % at none), and the decode step's median with them
# (`itl_p50_ms` 15.00-15.25 over six seeds on the chip, PR 41, a spread of
# 1.2 % where half the bound is 0.5 %). At +-0.01 the bias still moves the
# choice at the eighth place (neighbouring scores there lie about 0.01 apart).
BIAS_SPREAD = 0.01
# queries a block of the reference's attention (scores of a block: heads x
# QUERY_BLOCK x T float32)
QUERY_BLOCK = 128
# held experts dequantized and multiplied at a time (three float32 matrices of
# hidden x expert width each)
EXPERT_BLOCK = 4


def held(cfg: dict) -> tuple[int, int, int]:
    """(first id, experts held here, experts the router scores)."""
    dep = cfg.get("deployment", {})
    n = cfg["n_routed_experts"]
    return int(dep.get("experts_first", 0)), n, int(dep.get("n_routed_experts_published", n))


def program_config(cfg: dict):
    """The program's configuration object from the published keys."""
    from distributed_llama_multiusers_tpu.formats.model_file import RopeType
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    if cfg.get("moe_layer_freq", 1) != 1:
        raise SystemExit("every layer past the dense ones is routed")
    if not hasattr(RopeType, "YARN") or "index_topk" not in LlamaConfig.__dataclass_fields__:
        # a program from before them (the parent commit given this file):
        # said at once, before a weight is made
        raise SystemExit("the program has no YaRN frequencies, query latent, expert groups, "
                         "held share or indexer: it cannot run a deepseek_v32 configuration")
    first, n_held, n_all = held(cfg)
    yarn = cfg.get("rope_scaling") or {}
    if yarn and yarn.get("type") != "yarn":
        raise SystemExit(f"rope_scaling of type {yarn.get('type')!r}: this family's is yarn")
    return LlamaConfig(
        dim=cfg["hidden_size"], hidden_dim=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], vocab_size=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"],
        hidden_act=_HIDDEN_ACT[cfg["hidden_act"]],
        rope_theta=float(cfg["rope_theta"]), norm_epsilon=float(cfg["rms_norm_eps"]),
        n_experts=n_all, n_active_experts=cfg["num_experts_per_tok"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        moe_hidden_dim=cfg["moe_intermediate_size"],
        shared_hidden_dim=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        n_dense_layers=cfg["first_k_dense_replace"],
        moe_score_func=_SCORE[cfg["scoring_func"]],
        moe_select_bias=1 if cfg["topk_method"] == "noaux_tc" else 0,
        moe_norm_topk=1 if cfg["norm_topk_prob"] else 0,
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        moe_norm_floor=float(cfg.get("assumed_values", {}).get("router_norm_floor", 0.0)),
        moe_n_group=cfg["n_group"], moe_topk_group=cfg["topk_group"],
        experts_held_first=first, experts_held_count=n_held if n_held < n_all else 0,
        q_lora_rank=cfg["q_lora_rank"] or 0,
        index_n_heads=cfg["index_n_heads"], index_head_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"],
        **(dict(
            rope_type=RopeType.YARN, rope_scaling_factor=float(yarn["factor"]),
            rope_scaling_low_freq_factor=float(yarn["beta_slow"]),
            rope_scaling_high_freq_factor=float(yarn["beta_fast"]),
            rope_scaling_orig_max_seq_len=int(yarn["original_max_position_embeddings"]),
            rope_yarn_mscale_all_dim=float(yarn.get("mscale_all_dim", 0.0)),
        ) if yarn else {}),
    )


def _generate(c, key, dtype, vocab_out):
    from distributed_llama_multiusers_tpu.quants.packed import Q40Experts

    L, Ld, d = c.n_layers, c.n_dense_layers, c.dim
    Lm, E, Eh = L - Ld, c.n_experts, c.experts_held[1]
    qk, kv = c.qk_nope_head_dim + c.qk_rope_head_dim, c.qk_nope_head_dim + c.v_head_dim
    qr = c.q_lora_rank
    shapes = {
        "wqa": ((L,), d, qr, "wqa"),
        "wq": ((L,), qr, c.n_heads * qk, "wq"),
        "wkva": ((L,), d, c.kv_lora_rank + c.qk_rope_head_dim, "wkva"),
        "wkvb": ((L,), c.kv_lora_rank, c.n_heads * kv, "wkvb"),
        "wo": ((L,), c.n_heads * c.v_head_dim, d, "wo"),
        "idx_wq": ((L,), qr, c.index_n_heads * c.index_head_dim, "idx_wq"),
        "idx_wk": ((L,), d, c.index_head_dim, "idx_wk"),
        "dense_w1": ((Ld,), d, c.hidden_dim, "w1"),
        "dense_w2": ((Ld,), c.hidden_dim, d, "w2"),
        "dense_w3": ((Ld,), d, c.hidden_dim, "w3"),
        # the expert planes are stacked [routed layers, experts held, ...]
        "w1": ((Lm, Eh), d, c.moe_hidden_dim, "w1"),
        "w2": ((Lm, Eh), c.moe_hidden_dim, d, "w2"),
        "w3": ((Lm, Eh), d, c.moe_hidden_dim, "w3"),
        "shared_w1": ((Lm,), d, c.shared_hidden_dim, "w1"),
        "shared_w2": ((Lm,), c.shared_hidden_dim, d, "w2"),
        "shared_w3": ((Lm,), d, c.shared_hidden_dim, "w3"),
        "wcls": ((), d, vocab_out, "wcls"),
    }
    keys = jax.random.split(key, len(shapes) + 12)
    out = {}
    for k, (name, (lead, d_in, d_out, gain)) in zip(keys, shapes.items()):
        live = c.vocab_size if name == "wcls" else None
        out[name] = q40_plane(*jax.random.split(k), lead, d_in, d_out, GAIN[gain], live_out=live)
    for name in ("w1", "w2", "w3"):
        out[name] = Q40Experts.from_packed(out[name])
    kg, kb, ke, k1, k2, k3, k4, k5, k6, k7, k8, k9 = keys[len(shapes):]

    def gains(k, *shape):
        return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)

    out["moe_gate"] = _v3.ROUTER_SPREAD * d ** -0.5 * jax.random.normal(kg, (Lm, d, E), jnp.float32)
    out["moe_bias"] = jax.random.uniform(
        kb, (Lm, E), jnp.float32, -BIAS_SPREAD, BIAS_SPREAD)
    out["embedding"] = jax.random.normal(ke, (c.vocab_size, d), jnp.float32).astype(dtype)
    out["rms_att"], out["rms_kv"] = gains(k1, L, d), gains(k2, L, c.kv_lora_rank)
    out["dense_rms_ffn"], out["rms_ffn"] = gains(k3, Ld, d), gains(k4, Lm, d)
    out["rms_final"], out["rms_q"] = gains(k5, d), gains(k6, L, qr)
    out["idx_k_gain"] = gains(k7, L, c.index_head_dim)
    out["idx_k_bias"] = 0.1 * jax.random.normal(k8, (L, c.index_head_dim), jnp.float32)
    out["idx_ww"] = d ** -0.5 * jax.random.normal(k9, (L, d, c.index_n_heads), jnp.float32)
    return out


def device_weights(config, seed: int, dtype=jnp.bfloat16) -> dict:
    """name -> device array (``PackedQ40`` of two; the experts ``Q40Experts``),
    all from one program. The vocabulary is padded as the loader pads it."""
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    vocab_out = padded_d_out(config.vocab_size)
    t = jax.jit(lambda k: _generate(config, k, dtype, vocab_out))(seed_key(seed))
    jax.block_until_ready(t)
    return t


assemble_params = _v3.assemble_params


def lane_state_rel_err(engine, lane_x: int, lane_y: int, n: int):
    """Both lanes have absorbed the same n tokens. Largest difference between
    their rows ``[0, n)`` of every leaf of the cache (the normed latent, the
    rotated key part, the index keys), over the largest magnitude there: the
    family's only per-lane state, all of it kept by position."""
    cache = engine.cache
    if getattr(cache, "table", None) is not None or cache.k.ndim != 4:
        return None
    worst = 0.0
    for plane in cache:
        x = np.asarray(plane[:, lane_x, :n].astype(jnp.float32))
        y = np.asarray(plane[:, lane_y, :n].astype(jnp.float32))
        worst = max(worst, float(np.abs(x - y).max() / max(np.abs(y).max(), 1e-30)))
    return worst


# -- the plain reference ------------------------------------------------------


def yarn_rope_tables(n_pos: int, head_size: int, theta: float, yarn: dict | None):
    """cos, sin ``[n_pos, head_size/2]`` with YaRN's frequencies (module
    header); the plain ones without ``rope_scaling``. (scale on the softmax:
    ``softmax_factor``.)"""
    i = np.arange(head_size // 2, dtype=np.float64)
    freq = float(theta) ** (-2.0 * i / head_size)
    if yarn:
        def pair_at(beta):
            return (head_size * math.log(yarn["original_max_position_embeddings"]
                                         / (beta * 2.0 * math.pi)) / (2.0 * math.log(theta)))

        low = max(math.floor(pair_at(yarn["beta_fast"])), 0)
        high = min(math.ceil(pair_at(yarn["beta_slow"])), head_size - 1)
        ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
        freq = freq * (1.0 - ramp) + freq / yarn["factor"] * ramp
    ang = np.arange(n_pos, dtype=np.float64)[:, None] * freq[None, :]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def softmax_factor(yarn: dict | None) -> float:
    if not yarn or yarn["factor"] <= 1 or not yarn.get("mscale_all_dim"):
        return 1.0
    return (0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1.0) ** 2


def _layer_norm(x, gain, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def chosen_positions(scores, valid, top_k: int, select: str = "indexer"):
    """``S_t`` as a mask ``[..., T]``: the ``top_k`` valid positions of largest
    score by a stable sort (ties to the lower position); every valid position
    where there are no more than ``top_k``. ``select`` (the control only):
    "recent" puts the position itself in the score's place (the ``top_k``
    most recent), "all" chooses every valid position (dense attention)."""
    if select == "all":
        return valid
    if select == "recent":
        scores = jnp.broadcast_to(jnp.arange(scores.shape[-1], dtype=jnp.float32), scores.shape)
    order = jnp.argsort(-jnp.where(valid, scores, -jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)  # the place of every position in that order
    return valid & (rank < top_k)


@partial(jax.jit, static_argnames=("n_heads", "nope", "rope", "vd", "rank", "eps", "ih", "idim",
                                   "top_k", "scale", "lossy", "select"))
def _attention(x, lw, cos, sin, *, n_heads, nope, rope, vd, rank, eps, ih, idim, top_k,
               scale, lossy=None, select="indexer"):
    """The attention half of a block over ONE whole sequence ``x [1, T, d]``
    (T whole blocks of QUERY_BLOCK), expanded as published; returns ``h'``."""
    r = _rounder(lossy)
    _, t, _ = x.shape
    w = {k: dequant_q40(*lw[k]) for k in ("wqa", "wq", "wkva", "wkvb", "wo", "idx_wq", "idx_wk")}
    n1 = r(_rms_norm(x, lw["rms_att"], eps))
    cq = r(_rms_norm(r(n1 @ w["wqa"]), lw["rms_q"], eps))
    q = r(cq @ w["wq"]).reshape(1, t, n_heads, nope + rope)
    kva = r(n1 @ w["wkva"])
    c = r(_rms_norm(kva[..., :rank], lw["rms_kv"], eps))
    k_pe = _rope(kva[..., None, rank:], cos, sin)
    kv = r(c @ w["wkvb"]).reshape(1, t, n_heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (1, t, n_heads, rope))], axis=-1)[0]
    v = kv[0, ..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], axis=-1)[0]
    # the indexer
    qi = r(cq @ w["idx_wq"]).reshape(1, t, ih, idim)
    qi = jnp.concatenate([_rope(qi[..., :rope], cos, sin), qi[..., rope:]], axis=-1)[0]
    ki = _layer_norm(n1 @ w["idx_wk"], lw["idx_k_gain"], lw["idx_k_bias"], eps)
    ki = r(jnp.concatenate(
        [_rope(ki[:, :, None, :rope], cos, sin)[:, :, 0], ki[..., rope:]], axis=-1))[0]
    wi = (n1 @ lw["idx_ww"])[0] * (ih * idim) ** -0.5  # [T, ih]
    s_idx = jnp.arange(t)

    def block(q0):
        rows = q0 + jnp.arange(QUERY_BLOCK)
        valid = s_idx[None, :] <= rows[:, None]
        qi_b = jax.lax.dynamic_slice_in_dim(qi, q0, QUERY_BLOCK)
        wi_b = jax.lax.dynamic_slice_in_dim(wi, q0, QUERY_BLOCK)
        index = jnp.einsum("tj,tjs->ts", wi_b, jax.nn.relu(jnp.einsum("tjd,sd->tjs", qi_b, ki)))
        chosen = chosen_positions(index, valid, top_k, select)
        q_b = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK)
        scores = jnp.einsum("thx,shx->hts", q_b, k) * scale
        scores = jnp.where(chosen[None], scores, -jnp.inf)
        return jnp.einsum("hts,shv->thv", jax.nn.softmax(scores, axis=-1), v)

    att = jax.lax.map(block, jnp.arange(0, t, QUERY_BLOCK)).reshape(1, t, n_heads * vd)
    return r(x + r(att) @ w["wo"])


@partial(jax.jit, static_argnames=("top_k", "groups", "top_groups", "scale", "floor"))
def _route(n2, gate, bias, *, top_k, groups, top_groups, scale, floor):
    """The weight of every expert the router scores for every token
    ``[b, t, E]``, zero outside the chosen set, as written in the module's
    header; and the chosen set."""
    s = jax.nn.sigmoid(n2 @ gate)
    choose = s + bias
    if groups > 1:
        g = choose.reshape(*choose.shape[:-1], groups, -1)
        group_score = jnp.sort(g, axis=-1)[..., -2:].sum(-1)
        # a group is kept if fewer than top_groups groups score higher (ties
        # to the lower group, as a stable sort has them)
        order = jnp.argsort(-group_score, axis=-1, stable=True)
        kept = jnp.argsort(order, axis=-1) < top_groups
        choose = jnp.where(kept[..., None], g, -jnp.inf).reshape(choose.shape)
    order = jnp.argsort(-choose, axis=-1, stable=True)
    chosen = jnp.argsort(order, axis=-1) < top_k
    w = jnp.where(chosen, s, 0.0)
    return w / (w.sum(axis=-1, keepdims=True) + floor) * scale, chosen


def reference_forward(cfg: dict, t: dict, tokens, lossy: str | None = None,
                      routes: list | None = None, select: str = "indexer",
                      held_range: tuple | None = None):
    """The stream after the last block, float32 ``[B, T, d]``, a sequence at a
    time. ``routes``, a list, is given the chosen set of every routed layer of
    every sequence (bool ``[1, T, E]``); ``select``: ``chosen_positions``';
    ``held_range`` ``(first, count)``: another share of the experts than the
    configuration's (the share test), the arrays' experts being those."""
    tokens = np.asarray(tokens, np.int32)
    r = _rounder(lossy)
    eps = float(cfg["rms_norm_eps"])
    n_dense = cfg["first_k_dense_replace"]
    first, n_held, _ = held(cfg)
    if held_range is not None:
        first, n_held = held_range
    yarn = cfg.get("rope_scaling")
    t_pad = -(-tokens.shape[1] // QUERY_BLOCK) * QUERY_BLOCK
    cos, sin = (jnp.asarray(a) for a in yarn_rope_tables(
        t_pad, cfg["qk_rope_head_dim"], cfg["rope_theta"], yarn))
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    attn_names = ("wqa", "wq", "wkva", "wkvb", "wo", "idx_wq", "idx_wk")
    out = []
    for row in tokens:
        ids = np.zeros(t_pad, np.int32)
        ids[: len(row)] = row
        x = t["embedding"][jnp.asarray(ids)[None]].astype(jnp.float32)
        for layer in range(cfg["num_hidden_layers"]):
            lw = _v3._layer_planes(t, attn_names, layer)
            lw.update({k: t[k][layer] for k in (
                "rms_att", "rms_kv", "rms_q", "idx_k_gain", "idx_k_bias", "idx_ww")})
            h = _attention(
                x, lw, cos, sin, n_heads=cfg["num_attention_heads"], nope=nope, rope=rope,
                vd=cfg["v_head_dim"], rank=cfg["kv_lora_rank"], eps=eps,
                ih=cfg["index_n_heads"], idim=cfg["index_head_dim"],
                top_k=int(cfg["index_topk"]),
                scale=softmax_factor(yarn) / math.sqrt(nope + rope), lossy=lossy, select=select)
            if layer < n_dense:
                n2 = r(_rms_norm(h, t["dense_rms_ffn"][layer], eps))
                d = _v3._layer_planes(t, ("w1", "w2", "w3"), layer, "dense_")
                x = r(h + _v3._gated_ffn(n2, d["w1"], d["w2"], d["w3"], lossy=lossy))
                continue
            lm = layer - n_dense
            n2 = r(_rms_norm(h, t["rms_ffn"][lm], eps))
            route, chosen = _route(
                n2, t["moe_gate"][lm], t["moe_bias"][lm], top_k=int(cfg["num_experts_per_tok"]),
                groups=int(cfg["n_group"]), top_groups=int(cfg["topk_group"]),
                scale=float(cfg["routed_scaling_factor"]),
                floor=float(cfg.get("assumed_values", {}).get("router_norm_floor", 0.0)))
            if routes is not None:
                routes.append(np.asarray(chosen))
            s = _v3._layer_planes(t, ("w1", "w2", "w3"), lm, "shared_")
            ffn = _v3._gated_ffn(n2, s["w1"], s["w2"], s["w3"], lossy=lossy)
            for e0 in range(0, n_held, EXPERT_BLOCK):
                blk = slice(e0, min(e0 + EXPERT_BLOCK, n_held))
                ffn = ffn + _v3._expert_block(
                    n2, route[..., first + blk.start: first + blk.stop],
                    *(a for k in ("w1", "w2", "w3")
                      for a in (t[k].packed[lm, blk], t[k].scale_bits[lm, blk])),
                    lossy=lossy)
            x = r(h + ffn)
        out.append(x[0, : tokens.shape[1]])
    return jnp.stack(out)


def reference_logits(cfg: dict, t: dict, tokens, row_positions, lossy: str | None = None,
                     chunk: int = 16384, select: str = "indexer"):
    """Float32 logits ``[B, R, vocab]`` at ``row_positions`` of each sequence,
    from the benchmark's own arrays; imports nothing of the program. ``lossy``
    (the controls only) names the type every value a block hands on is rounded
    to, or ``"select:recent"`` / ``"select:all"``: ``chosen_positions``' fault
    in the selection's place (`control_sparse.py`)."""
    if lossy and lossy.startswith("select:"):
        lossy, select = None, lossy.split(":", 1)[1]
    row_positions = jnp.asarray(row_positions, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = reference_forward(cfg, t, tokens, lossy, select=select)
        x = jnp.take_along_axis(x, row_positions[:, :, None], axis=1)
        y = _rounder(lossy)(_rms_norm(x, t["rms_final"], float(cfg["rms_norm_eps"])))
        packed, scales = t["wcls"].packed, t["wcls"].scales
        outs = [np.asarray(_v3._head_chunk(y, packed[:, lo:lo + chunk], scales[:, lo:lo + chunk]))
                for lo in range(0, packed.shape[-1], chunk)]
    return np.concatenate(outs, axis=-1)[..., : cfg["vocab_size"]]
