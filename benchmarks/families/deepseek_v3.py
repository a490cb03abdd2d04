"""The DeepSeek-V3 family (``model_type: deepseek_v3``): latent attention with a
one-row-a-token cache, leading dense layers, then routed layers with shared
experts, served from Q40. What `harness/cells.py` `load_family` asks of an
architecture; the plain reference below imports nothing of the program.

The block, from the published description (DeepSeek-V2, section 2.1, and
DeepSeek-V3, section 2.1; `config.json` keys in brackets; ``q_lora_rank`` null,
so the query has no latent), ``h`` the stream:

    n   = rmsnorm(h, g_att)
    q   = Wq n                       heads of [q_nope (qk_nope_head_dim); q_pe (qk_rope_head_dim)]
    [c; k_pe] = Wkva n               kv_lora_rank + qk_rope_head_dim, one row a token
    [k_nope_i; v_i] = Wkvb_i rmsnorm(c, g_kv)
    s_i(t, u) = (q_nope_i(t) . k_nope_i(u) + rope(q_pe_i)(t) . rope(k_pe)(u)) / sqrt(nope + rope)
    h'  = h + Wo [o_i],   o_i = sum_u softmax_u<=t(s_i)(u) v_i(u)

    layers < first_k_dense_replace:   h'' = h' + W2 (silu(W1 n2) * W3 n2)
    the others:  s = sigmoid(Wg n2)                      [scoring_func], float32
                 S = the num_experts_per_tok experts with the largest s + b
                     (e_score_correction_bias: chooses, does not weigh;
                      n_group = topk_group = 1: no group step)
                 w_e = routed_scaling_factor * s_e / (sum_S s + 1e-20)   [norm_topk_prob]
                 h'' = h' + sum_{e in S} w_e W2_e (silu(W1_e n2) * W3_e n2)
                          + Ws2 (silu(Ws1 n2) * Ws3 n2)    the n_shared_experts as one FFN

The reference computes the EXPANDED attention (every head's own keys and
values, no absorption, no cache) and every expert on every token, weighted by
the scores as written (zero outside S), a block of experts at a time so that
it fits beside the engine. Departures from the published form: the rotary
embedding rotates adjacent pairs, which is what ``rope_interleave: true``
publishes and the .m format's convention; weights are Q40, dequantized here.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from harness.reference import _rms_norm, _rope, _rounder, dequant_q40, rope_tables
from harness.weights import q40_plane, seed_key

_HIDDEN_ACT = {"gelu": 0, "silu": 1}
_SCORE = {"softmax": 0, "sigmoid": 1}
# Output rms of each matmul for an input of rms 1 (`harness/weights.py` GAIN
# argues the Llama block's). Query and key parts have gain 2, so scores spread
# by about 4; the value half of Wkvb shares its plane's gain, and Wo halves
# its own, so the attention branch still adds about 0.3 a layer. An expert's
# W2 has gain 0.15: six experts weighted 2.448 / 6 each add about 0.11, the
# shared FFN as much, the FFN branch about 0.16.
GAIN = {"wq": 2.0, "wkva": 2.0, "wkvb": 2.0, "wo": 0.15,
        "w1": 1.0, "w3": 1.0, "w2": 0.15, "wcls": 1.78}
# experts dequantized and multiplied at a time by the reference
EXPERT_BLOCK = 16
# The router's logits have standard deviation ROUTER_SPREAD for a unit input
# and the selection bias is uniform in +-BIAS_SPREAD. A trained bias evens the
# experts' load out; a drawn one that is large against the spread of the scores
# near the sixth place (0.03-0.09) makes every token choose the experts with
# the largest bias: at 2.0 and 0.2 a decode step of 32 rows touched 40 % of a
# layer's experts on the chip (PR 33) where independent rows touch 78 %. At
# 1.0 and 0.05 it is 74 % (a simulation of the rule), and the bias still moves
# the choice and, used as a weight, a chosen expert's weight by up to 6 %.
BIAS_SPREAD = 0.05
ROUTER_SPREAD = 1.0


def program_config(cfg: dict):
    """The program's configuration object from the published keys."""
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    if cfg.get("q_lora_rank") is not None:
        raise SystemExit("the program's latent attention has no query latent (q_lora_rank)")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise SystemExit("the program's router has no group step (n_group, topk_group)")
    if cfg.get("moe_layer_freq", 1) != 1 or cfg.get("rope_scaling") is not None:
        raise SystemExit("every layer past the dense ones is routed, and the rotary embedding unscaled")
    return LlamaConfig(
        dim=cfg["hidden_size"], hidden_dim=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], vocab_size=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"],
        hidden_act=_HIDDEN_ACT[cfg["hidden_act"]],
        rope_theta=float(cfg["rope_theta"]), norm_epsilon=float(cfg["rms_norm_eps"]),
        n_experts=cfg["n_routed_experts"], n_active_experts=cfg["num_experts_per_tok"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        moe_hidden_dim=cfg["moe_intermediate_size"],
        shared_hidden_dim=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        n_dense_layers=cfg["first_k_dense_replace"],
        moe_score_func=_SCORE[cfg["scoring_func"]],
        moe_select_bias=1 if cfg["topk_method"] == "noaux_tc" else 0,
        moe_norm_topk=1 if cfg["norm_topk_prob"] else 0,
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
    )


def _generate(c, key, dtype, vocab_out):
    from distributed_llama_multiusers_tpu.quants.packed import Q40Experts

    L, Ld, E, d = c.n_layers, c.n_dense_layers, c.n_experts, c.dim
    Lm = L - Ld
    qk, kv = c.qk_nope_head_dim + c.qk_rope_head_dim, c.qk_nope_head_dim + c.v_head_dim
    shapes = {
        "wq": ((L,), d, c.n_heads * qk, "wq"),
        "wkva": ((L,), d, c.kv_lora_rank + c.qk_rope_head_dim, "wkva"),
        "wkvb": ((L,), c.kv_lora_rank, c.n_heads * kv, "wkvb"),
        "wo": ((L,), c.n_heads * c.v_head_dim, d, "wo"),
        "dense_w1": ((Ld,), d, c.hidden_dim, "w1"),
        "dense_w2": ((Ld,), c.hidden_dim, d, "w2"),
        "dense_w3": ((Ld,), d, c.hidden_dim, "w3"),
        # the expert planes are stacked [routed layers, E, ...]
        "w1": ((Lm, E), d, c.moe_hidden_dim, "w1"),
        "w2": ((Lm, E), c.moe_hidden_dim, d, "w2"),
        "w3": ((Lm, E), d, c.moe_hidden_dim, "w3"),
        "shared_w1": ((Lm,), d, c.shared_hidden_dim, "w1"),
        "shared_w2": ((Lm,), c.shared_hidden_dim, d, "w2"),
        "shared_w3": ((Lm,), d, c.shared_hidden_dim, "w3"),
        "wcls": ((), d, vocab_out, "wcls"),
    }
    keys = jax.random.split(key, len(shapes) + 8)
    out = {}
    for k, (name, (lead, d_in, d_out, gain)) in zip(keys, shapes.items()):
        live = c.vocab_size if name == "wcls" else None
        out[name] = q40_plane(*jax.random.split(k), lead, d_in, d_out, GAIN[gain], live_out=live)
    # the program keeps expert scales as float16 bit patterns; made so here,
    # in the same program, so that no float16 copy stays on the device
    for name in ("w1", "w2", "w3"):
        out[name] = Q40Experts.from_packed(out[name])
    kg, kb, ke, k1, k2, k3, k4, k5 = keys[len(shapes):]
    out["moe_gate"] = ROUTER_SPREAD * d ** -0.5 * jax.random.normal(kg, (Lm, d, E), jnp.float32)
    out["moe_bias"] = jax.random.uniform(kb, (Lm, E), jnp.float32, -BIAS_SPREAD, BIAS_SPREAD)
    out["embedding"] = jax.random.normal(ke, (c.vocab_size, d), jnp.float32).astype(dtype)
    out["rms_att"] = 1.0 + 0.1 * jax.random.normal(k1, (L, d), jnp.float32)
    out["rms_kv"] = 1.0 + 0.1 * jax.random.normal(k2, (L, c.kv_lora_rank), jnp.float32)
    out["dense_rms_ffn"] = 1.0 + 0.1 * jax.random.normal(k3, (Ld, d), jnp.float32)
    out["rms_ffn"] = 1.0 + 0.1 * jax.random.normal(k4, (Lm, d), jnp.float32)
    out["rms_final"] = 1.0 + 0.1 * jax.random.normal(k5, (d,), jnp.float32)
    return out


def device_weights(config, seed: int, dtype=jnp.bfloat16) -> dict:
    """name -> device array (``PackedQ40`` of two; the experts ``Q40Experts``),
    all from one program. The vocabulary is padded as the loader pads it."""
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    vocab_out = padded_d_out(config.vocab_size)
    t = jax.jit(lambda k: _generate(config, k, dtype, vocab_out))(seed_key(seed))
    jax.block_until_ready(t)
    return t


def assemble_params(config, t: dict):
    """The program's parameter tree around the arrays (its own function: the
    loader's, which also makes the dequantized copy of Wkvb the absorbed
    attention reads); the RoPE tables are the program's own."""
    from distributed_llama_multiusers_tpu.models.deepseek import latent_params
    from distributed_llama_multiusers_tpu.models.loader import _rope_cache

    cos, sin = _rope_cache(config)
    return latent_params(t, jax.device_put(cos), jax.device_put(sin), t["embedding"].dtype, config)


def lane_state_rel_err(engine, lane_x: int, lane_y: int, n: int):
    """Both lanes have absorbed the same n tokens. Largest difference between
    their rows ``[0, n)`` of the two latent leaves (the normed latent, and the
    rotated key part), over the largest magnitude there: the family's only
    per-lane state, all of it kept by position."""
    cache = engine.cache
    if getattr(cache, "table", None) is not None or cache.k.ndim != 4:
        return None
    worst = 0.0
    for plane in (cache.k, cache.v):
        x = np.asarray(plane[:, lane_x, :n].astype(jnp.float32))
        y = np.asarray(plane[:, lane_y, :n].astype(jnp.float32))
        worst = max(worst, float(np.abs(x - y).max() / max(np.abs(y).max(), 1e-30)))
    return worst


def route_difference_share(routes_x: list, routes_y: list) -> float:
    """Share of (row, routed layer) pairs whose chosen sets differ between two
    passes over the same tokens (``reference_forward``'s ``routes``): routing
    is discontinuous, and a pass that rounds more can choose another expert
    at the last place."""
    differ = [np.any(x != y, axis=-1) for x, y in zip(routes_x, routes_y)]
    return float(np.mean(np.stack(differ)))


# -- the plain reference ------------------------------------------------------


@partial(jax.jit, static_argnames=("n_heads", "nope", "rope", "vd", "rank", "eps", "lossy"))
def _attention(x, lw, cos, sin, *, n_heads, nope, rope, vd, rank, eps, lossy=None):
    """The attention half of a block over whole sequences, expanded as
    published; returns ``h'``."""
    r = _rounder(lossy)
    b, t, _ = x.shape
    w = {k: dequant_q40(*lw[k]) for k in ("wq", "wkva", "wkvb", "wo")}
    n1 = r(_rms_norm(x, lw["rms_att"], eps))
    q = r(n1 @ w["wq"]).reshape(b, t, n_heads, nope + rope)
    kva = r(n1 @ w["wkva"])
    c = r(_rms_norm(kva[..., :rank], lw["rms_kv"], eps))
    k_pe = _rope(kva[..., None, rank:], cos, sin)  # [b, t, 1, rope]
    kv = r(c @ w["wkvb"]).reshape(b, t, n_heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, t, n_heads, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], axis=-1)
    scores = jnp.einsum("bthx,bshx->bhts", q, k) / np.sqrt(nope + rope)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], scores, -jnp.inf)
    att = jnp.einsum("bhts,bshv->bthv", jax.nn.softmax(scores, axis=-1), kv[..., nope:])
    return r(x + r(att.reshape(b, t, n_heads * vd)) @ w["wo"])


@partial(jax.jit, static_argnames=("lossy",))
def _gated_ffn(n2, w1, w2, w3, lossy=None):
    r = _rounder(lossy)
    w1, w2, w3 = dequant_q40(*w1), dequant_q40(*w2), dequant_q40(*w3)
    return r(jax.nn.silu(n2 @ w1) * (n2 @ w3)) @ w2


@partial(jax.jit, static_argnames=("top_k", "sigmoid", "norm", "scale"))
def _route(n2, gate, bias, *, top_k, sigmoid, norm, scale):
    """The weight of every expert for every token ``[b, t, E]``, zero outside
    the chosen set, as written in the module's header; and the chosen set."""
    logits = n2 @ gate
    s = jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits, axis=-1)
    choose = s + bias
    kth = jnp.sort(choose, axis=-1)[..., -top_k, None]
    chosen = choose >= kth
    w = jnp.where(chosen, s, 0.0)
    if norm:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * scale, chosen


@partial(jax.jit, static_argnames=("lossy",))
def _expert_block(n2, route, p1, s1, p2, s2, p3, s3, lossy=None):
    """``sum_e route[..., e] . W2_e (silu(W1_e n2) * W3_e n2)`` over a block of
    experts. Scales arrive as float16 bit patterns."""
    r = _rounder(lossy)
    f16 = lambda s: jax.lax.bitcast_convert_type(s, jnp.float16)
    w1 = jax.vmap(dequant_q40)(p1, f16(s1))  # [e, d, h]
    w2 = jax.vmap(dequant_q40)(p2, f16(s2))
    w3 = jax.vmap(dequant_q40)(p3, f16(s3))
    act = r(jax.nn.silu(jnp.einsum("btd,edh->bteh", n2, w1)) * jnp.einsum("btd,edh->bteh", n2, w3))
    return jnp.einsum("bte,bted->btd", route, jnp.einsum("bteh,ehd->bted", act, w2))


def _layer_planes(t, names, layer, prefix=""):
    return {k: (t[prefix + k].packed[layer], t[prefix + k].scales[layer]) for k in names}


def reference_forward(cfg: dict, t: dict, tokens, lossy: str | None = None,
                      routes: list | None = None):
    """The stream after the last block, float32 ``[B, T, d]``. ``routes``, a
    list, is given the chosen set of every routed layer (bool ``[B, T, E]``)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    r = _rounder(lossy)
    eps = float(cfg["rms_norm_eps"])
    n_dense, E = cfg["first_k_dense_replace"], cfg["n_routed_experts"]
    cos, sin = rope_tables(tokens.shape[1], cfg["qk_rope_head_dim"], cfg["rope_theta"])
    cos, sin = jnp.asarray(cos), jnp.asarray(sin)
    x = t["embedding"][tokens].astype(jnp.float32)
    for layer in range(cfg["num_hidden_layers"]):
        lw = _layer_planes(t, ("wq", "wkva", "wkvb", "wo"), layer)
        lw.update(rms_att=t["rms_att"][layer], rms_kv=t["rms_kv"][layer])
        h = _attention(
            x, lw, cos, sin, n_heads=cfg["num_attention_heads"],
            nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
            vd=cfg["v_head_dim"], rank=cfg["kv_lora_rank"], eps=eps, lossy=lossy)
        if layer < n_dense:
            n2 = r(_rms_norm(h, t["dense_rms_ffn"][layer], eps))
            d = _layer_planes(t, ("w1", "w2", "w3"), layer, "dense_")
            x = r(h + _gated_ffn(n2, d["w1"], d["w2"], d["w3"], lossy=lossy))
            continue
        lm = layer - n_dense
        n2 = r(_rms_norm(h, t["rms_ffn"][lm], eps))
        route, chosen = _route(
            n2, t["moe_gate"][lm], t["moe_bias"][lm], top_k=int(cfg["num_experts_per_tok"]),
            sigmoid=cfg["scoring_func"] == "sigmoid", norm=bool(cfg["norm_topk_prob"]),
            scale=float(cfg["routed_scaling_factor"]))
        if routes is not None:
            routes.append(np.asarray(chosen))
        s = _layer_planes(t, ("w1", "w2", "w3"), lm, "shared_")
        ffn = _gated_ffn(n2, s["w1"], s["w2"], s["w3"], lossy=lossy)
        for e0 in range(0, E, EXPERT_BLOCK):
            blk = slice(e0, e0 + EXPERT_BLOCK)
            ffn = ffn + _expert_block(
                n2, route[..., blk],
                *(a for k in ("w1", "w2", "w3")
                  for a in (t[k].packed[lm, blk], t[k].scale_bits[lm, blk])),
                lossy=lossy)
        x = r(h + ffn)
    return x


@jax.jit
def _head_chunk(y, packed, scales):
    return y @ dequant_q40(packed, scales)


def reference_logits(cfg: dict, t: dict, tokens, row_positions, lossy: str | None = None,
                     chunk: int = 16384):
    """Float32 logits ``[B, R, vocab]`` at ``row_positions`` of each sequence,
    from the benchmark's own arrays; imports nothing of the program."""
    row_positions = jnp.asarray(row_positions, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = reference_forward(cfg, t, tokens, lossy)
        x = jnp.take_along_axis(x, row_positions[:, :, None], axis=1)
        y = _rounder(lossy)(_rms_norm(x, t["rms_final"], float(cfg["rms_norm_eps"])))
        packed, scales = t["wcls"].packed, t["wcls"].scales
        outs = [np.asarray(_head_chunk(y, packed[:, lo:lo + chunk], scales[:, lo:lo + chunk]))
                for lo in range(0, packed.shape[-1], chunk)]
    return np.concatenate(outs, axis=-1)[..., : cfg["vocab_size"]]
