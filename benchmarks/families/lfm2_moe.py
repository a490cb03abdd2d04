"""The LFM2-MoE family (``model_type: lfm2_moe``): gated short convolutions and
GQA attention in a published per-layer pattern, leading dense FFNs, then
routed ones, served from Q40. What `harness/cells.py` `load_family` asks of an
architecture; the plain reference below imports nothing of the program.

The layer, as published (`config.json` keys in brackets; ``h`` the stream,
``eps`` = [norm_eps], ``K`` = [conv_L_cache], no bias anywhere):

    n = rmsnorm(h, g_op)                                   operator_norm
    [layer_types][l] == "conv":
        [B; C; X] = W_in n              three parts of hidden_size, in that order
        u_t = B_t * X_t
        v_t = sum_{j<K} w[:, j] * u_{t-(K-1)+j}            depthwise, causal, u_{<0} = 0
        h' = h + W_out (C_t * v_t)
    "full_attention": [num_attention_heads] query / [num_key_value_heads] kv heads
        q_i = rmsnorm(Wq_i n, g_q); k_j = rmsnorm(Wk_j n, g_k)   per head, BEFORE the rotation
        rotary embedding, theta [rope_parameters.rope_theta], on the whole head
        h' = h + Wo [o_i],  o_i = sum_u softmax_u<=t(q_i . k / sqrt(head))(u) v(u)
    m = rmsnorm(h', g_ffn)
    l < [num_dense_layers]:  h'' = h' + W2 (silu(W1 m) * W3 m)          [intermediate_size]
    else:  s = sigmoid(Wg m)                  [num_experts], float32
           S = the [num_experts_per_tok] experts with the largest s + b   [use_expert_bias]
           w_e = [routed_scaling_factor] * s_e / (sum_S s + 1e-6)         [norm_topk_prob]
           h'' = h' + sum_{e in S} w_e W2_e (silu(W1_e m) * W3_e m)      [moe_intermediate_size]
    logits = W_head rmsnorm(h_last, g_final)

The reference computes every expert on every token, weighted by the scores as
written (zero outside S), a block of experts at a time, and the attention a
key/value head at a time, so that 1100 tokens fit beside the engine.
Departures from the published form: the rotary embedding rotates adjacent
pairs, the .m format's convention (it equals the published half-split form
under the converter's permutation of Wq's and Wk's rows and of g_q and g_k);
weights are Q40, dequantized here.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from harness.reference import _rms_norm, _rope, _rounder, dequant_q40, rope_tables
from harness.weights import q40_plane, seed_key

CONV, ATTENTION = "conv", "full_attention"
# Output rms of each matmul for an input of rms 1 (`harness/weights.py` GAIN
# argues the Llama block's). The three parts of W_in have rms 1, so u = B * X
# has rms 1; the taps are drawn with rms 3^-1/2, so v has rms 1, C * v rms 1,
# and W_out adds about 0.3 a layer, as the attention branch does through Wo.
# Queries and keys are normed per head, so their projections' gains do not
# matter; the norms' gains (QK_GAIN) spread the scores by about 4, as trained
# heads' are sharp. Four experts weighted about 1/4 each add about half of one
# expert's output: W2_e at 0.3 adds what a dense FFN at 0.2 adds.
GAIN = {"wq": 1.0, "wk": 1.0, "wv": 1.0, "wo": 0.3, "conv_in": 1.0, "conv_out": 0.3,
        "dense_w1": 1.0, "dense_w3": 1.0, "dense_w2": 0.2,
        "w1": 1.0, "w3": 1.0, "w2": 0.3, "wcls": 1.78}
QK_GAIN = 2.0
# experts dequantized and multiplied at a time by the reference
EXPERT_BLOCK = 8
# as families/deepseek_v3.py argues them: a selection bias large against the
# spread of the scores near the last chosen place makes every token choose the
# same experts, which a trained bias exists to prevent
BIAS_SPREAD = 0.05
ROUTER_SPREAD = 1.0


def _kinds(cfg: dict) -> list[str]:
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {CONV, ATTENTION}:
        raise SystemExit("layer_types: one of 'conv', 'full_attention' a layer")
    return kinds


def program_config(cfg: dict):
    """The program's configuration object from the published keys."""
    from distributed_llama_multiusers_tpu.formats.model_file import LayerKind, MoeScore
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    if cfg.get("conv_bias"):
        raise SystemExit("the program's short convolution has no bias (conv_bias)")
    rope = cfg["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise SystemExit("the program's rotary embedding here is unscaled (rope_parameters)")
    return LlamaConfig(
        dim=cfg["hidden_size"], hidden_dim=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], vocab_size=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"],
        rope_theta=float(rope["rope_theta"]), norm_epsilon=float(cfg["norm_eps"]),
        n_experts=cfg["num_experts"], n_active_experts=cfg["num_experts_per_tok"],
        moe_hidden_dim=cfg["moe_intermediate_size"], n_dense_layers=cfg["num_dense_layers"],
        moe_score_func=MoeScore.SIGMOID,
        moe_select_bias=1 if cfg["use_expert_bias"] else 0,
        moe_norm_topk=1 if cfg["norm_topk_prob"] else 0,
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        layer_kinds=tuple(
            LayerKind.CONV if k == CONV else LayerKind.ATTENTION for k in _kinds(cfg)),
        conv_kernel=cfg["conv_L_cache"], qk_norm=1,
    )


def _generate(c, key, dtype, vocab_out):
    from distributed_llama_multiusers_tpu.quants.packed import Q40Experts

    La, Lc, Ld, E, d = c.n_attention_layers, c.n_conv_layers, c.n_dense_layers, c.n_experts, c.dim
    Lm, hd = c.n_layers - Ld, c.head_size
    shapes = {
        "wq": ((La,), d, d), "wk": ((La,), d, c.kv_dim), "wv": ((La,), d, c.kv_dim),
        "wo": ((La,), d, d),
        "conv_in": ((Lc,), d, 3 * d), "conv_out": ((Lc,), d, d),
        "dense_w1": ((Ld,), d, c.hidden_dim), "dense_w2": ((Ld,), c.hidden_dim, d),
        "dense_w3": ((Ld,), d, c.hidden_dim),
        # the expert planes are stacked [routed layers, E, ...]
        "w1": ((Lm, E), d, c.moe_hidden_dim), "w2": ((Lm, E), c.moe_hidden_dim, d),
        "w3": ((Lm, E), d, c.moe_hidden_dim),
        "wcls": ((), d, vocab_out),
    }
    keys = jax.random.split(key, len(shapes) + 11)
    out = {}
    for k, (name, (lead, d_in, d_out)) in zip(keys, shapes.items()):
        live = c.vocab_size if name == "wcls" else None
        out[name] = q40_plane(*jax.random.split(k), lead, d_in, d_out, GAIN[name], live_out=live)
    # the program keeps expert scales as float16 bit patterns; made so here,
    # in the same program, so that no float16 copy stays on the device
    for name in ("w1", "w2", "w3"):
        out[name] = Q40Experts.from_packed(out[name])
    kg, kb, ke, kt, kq, kk, k1, k2, k3, k4, k5 = keys[len(shapes):]
    normal = jax.random.normal
    out["moe_gate"] = ROUTER_SPREAD * d ** -0.5 * normal(kg, (Lm, d, E), jnp.float32)
    out["moe_bias"] = jax.random.uniform(kb, (Lm, E), jnp.float32, -BIAS_SPREAD, BIAS_SPREAD)
    out["embedding"] = normal(ke, (c.vocab_size, d), jnp.float32).astype(dtype)
    out["conv_taps"] = c.conv_kernel ** -0.5 * normal(kt, (Lc, c.conv_kernel, d), jnp.float32)
    out["q_norm"] = QK_GAIN * (1.0 + 0.1 * normal(kq, (La, hd), jnp.float32))
    out["k_norm"] = QK_GAIN * (1.0 + 0.1 * normal(kk, (La, hd), jnp.float32))
    out["attn_rms"] = 1.0 + 0.1 * normal(k1, (La, d), jnp.float32)
    out["conv_rms"] = 1.0 + 0.1 * normal(k2, (Lc, d), jnp.float32)
    out["dense_rms_ffn"] = 1.0 + 0.1 * normal(k3, (Ld, d), jnp.float32)
    out["rms_ffn"] = 1.0 + 0.1 * normal(k4, (Lm, d), jnp.float32)
    out["rms_final"] = 1.0 + 0.1 * normal(k5, (d,), jnp.float32)
    return out


def device_weights(config, seed: int, dtype=jnp.bfloat16) -> dict:
    """name -> device array (``PackedQ40`` of two; the experts ``Q40Experts``),
    all from one program; each kind of layer's tensors stacked by the count of
    that kind. The vocabulary is padded as the loader pads it."""
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    vocab_out = padded_d_out(config.vocab_size)
    t = jax.jit(lambda k: _generate(config, k, dtype, vocab_out))(seed_key(seed))
    jax.block_until_ready(t)
    return t


def assemble_params(config, t: dict):
    """The program's parameter tree around the arrays (its own function: the
    loader's); the RoPE tables are the program's own."""
    from distributed_llama_multiusers_tpu.models.hybrid import hybrid_params
    from distributed_llama_multiusers_tpu.models.loader import _rope_cache

    cos, sin = _rope_cache(config)
    return hybrid_params(t, jax.device_put(cos), jax.device_put(sin))


def lane_state_rel_err(engine, lane_x: int, lane_y: int, n: int):
    """Both lanes have absorbed the same n tokens. Largest difference between
    their rows ``[0, n)`` of K and of V (what is kept by position) and between
    the WHOLE of their conv state (what is not: the last inputs of every conv
    layer, overwritten in place), each over the largest magnitude there."""
    cache = engine.cache
    if getattr(cache, "table", None) is not None or not hasattr(cache, "conv"):
        return None
    worst = 0.0
    for leaf, rows in ((cache.k, n), (cache.v, n), (cache.conv, None)):
        if leaf.size == 0:
            continue
        x = np.asarray(leaf[:, lane_x].astype(jnp.float32))[:, :rows]
        y = np.asarray(leaf[:, lane_y].astype(jnp.float32))[:, :rows]
        worst = max(worst, float(np.abs(x - y).max() / max(np.abs(y).max(), 1e-30)))
    return worst


def route_difference_share(routes_x: list, routes_y: list) -> float:
    """Share of (row, routed layer) pairs whose chosen sets differ between two
    passes over the same tokens (``reference_forward``'s ``routes``)."""
    differ = [np.any(x != y, axis=-1) for x, y in zip(routes_x, routes_y)]
    return float(np.mean(np.stack(differ)))


# -- the plain reference ------------------------------------------------------


@partial(jax.jit, static_argnames=("eps", "lossy"))
def _conv_mixer(x, lw, *, eps, lossy=None):
    """The conv half of a block over whole sequences; returns ``h'``."""
    r = _rounder(lossy)
    t = x.shape[1]
    n = r(_rms_norm(x, lw["rms"], eps))
    gate_b, gate_c, xin = jnp.split(r(n @ dequant_q40(*lw["conv_in"])), 3, axis=-1)
    u = r(gate_b * xin)
    taps = lw["taps"]  # [K, d]: tap j multiplies u_{t-(K-1)+j}
    k = taps.shape[0]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))  # u_{<0} = 0
    v = sum(taps[j] * padded[:, j:j + t] for j in range(k))
    return r(x + r(gate_c * r(v)) @ dequant_q40(*lw["conv_out"]))


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps", "lossy"))
def _attention(x, lw, cos, sin, *, n_heads, n_kv, eps, lossy=None):
    """The attention half of a block over whole sequences, a key/value head
    at a time; returns ``h'``."""
    r = _rounder(lossy)
    b, t, d = x.shape
    hd, g = d // n_heads, n_heads // n_kv
    n = r(_rms_norm(x, lw["rms"], eps))
    q = (n @ dequant_q40(*lw["wq"])).reshape(b, t, n_heads, hd)
    k = (n @ dequant_q40(*lw["wk"])).reshape(b, t, n_kv, hd)
    v = r(n @ dequant_q40(*lw["wv"])).reshape(b, t, n_kv, hd)
    q = _rope(r(_rms_norm(q, lw["q_norm"], eps)), cos, sin)
    k = _rope(r(_rms_norm(k, lw["k_norm"], eps)), cos, sin)
    causal = jnp.tril(jnp.ones((t, t), bool))[None, None]
    heads = []
    for j in range(n_kv):
        scores = jnp.einsum("btgh,bsh->bgts", q[:, :, j * g:(j + 1) * g], k[:, :, j]) / np.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        heads.append(jnp.einsum("bgts,bsh->btgh", probs, v[:, :, j]))
    att = jnp.concatenate(heads, axis=2).reshape(b, t, d)
    return r(x + r(att) @ dequant_q40(*lw["wo"]))


@partial(jax.jit, static_argnames=("lossy",))
def _gated_ffn(m, w1, w2, w3, lossy=None):
    r = _rounder(lossy)
    w1, w2, w3 = dequant_q40(*w1), dequant_q40(*w2), dequant_q40(*w3)
    return r(jax.nn.silu(m @ w1) * (m @ w3)) @ w2


@partial(jax.jit, static_argnames=("top_k", "norm", "scale"))
def _route(m, gate, bias, *, top_k, norm, scale):
    """The weight of every expert for every token ``[b, t, E]``, zero outside
    the chosen set, as written in the module's header; and the chosen set."""
    s = jax.nn.sigmoid(m @ gate)
    choose = s + bias
    kth = jnp.sort(choose, axis=-1)[..., -top_k, None]
    chosen = choose >= kth
    w = jnp.where(chosen, s, 0.0)
    if norm:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-6)
    return w * scale, chosen


@partial(jax.jit, static_argnames=("lossy",))
def _expert_block(m, route, p1, s1, p2, s2, p3, s3, lossy=None):
    """``sum_e route[..., e] . W2_e (silu(W1_e m) * W3_e m)`` over a block of
    experts. Scales arrive as float16 bit patterns."""
    r = _rounder(lossy)
    f16 = lambda s: jax.lax.bitcast_convert_type(s, jnp.float16)
    w1 = jax.vmap(dequant_q40)(p1, f16(s1))  # [e, d, h]
    w2 = jax.vmap(dequant_q40)(p2, f16(s2))
    w3 = jax.vmap(dequant_q40)(p3, f16(s3))
    act = r(jax.nn.silu(jnp.einsum("btd,edh->bteh", m, w1)) * jnp.einsum("btd,edh->bteh", m, w3))
    return jnp.einsum("bte,bted->btd", route, jnp.einsum("bteh,ehd->bted", act, w2))


def _planes(t, names, index, prefix=""):
    return {k: (t[prefix + k].packed[index], t[prefix + k].scales[index]) for k in names}


def reference_forward(cfg: dict, t: dict, tokens, lossy: str | None = None,
                      routes: list | None = None):
    """The stream after the last block, float32 ``[B, T, d]``. ``routes``, a
    list, is given the chosen set of every routed layer (bool ``[B, T, E]``)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    r = _rounder(lossy)
    eps = float(cfg["norm_eps"])
    n_dense, E = cfg["num_dense_layers"], cfg["num_experts"]
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    cos, sin = rope_tables(tokens.shape[1], hd, cfg["rope_parameters"]["rope_theta"])
    cos, sin = jnp.asarray(cos), jnp.asarray(sin)
    x = t["embedding"][tokens].astype(jnp.float32)
    n_attn = n_conv = 0
    for layer, kind in enumerate(_kinds(cfg)):
        if kind == CONV:
            lw = _planes(t, ("conv_in", "conv_out"), n_conv)
            lw.update(rms=t["conv_rms"][n_conv], taps=t["conv_taps"][n_conv])
            h = _conv_mixer(x, lw, eps=eps, lossy=lossy)
            n_conv += 1
        else:
            lw = _planes(t, ("wq", "wk", "wv", "wo"), n_attn)
            lw.update(rms=t["attn_rms"][n_attn], q_norm=t["q_norm"][n_attn],
                      k_norm=t["k_norm"][n_attn])
            h = _attention(x, lw, cos, sin, n_heads=cfg["num_attention_heads"],
                           n_kv=cfg["num_key_value_heads"], eps=eps, lossy=lossy)
            n_attn += 1
        if layer < n_dense:
            m = r(_rms_norm(h, t["dense_rms_ffn"][layer], eps))
            d = _planes(t, ("w1", "w2", "w3"), layer, "dense_")
            x = r(h + _gated_ffn(m, d["w1"], d["w2"], d["w3"], lossy=lossy))
            continue
        lm = layer - n_dense
        m = r(_rms_norm(h, t["rms_ffn"][lm], eps))
        bias = t["moe_bias"][lm] if cfg["use_expert_bias"] else jnp.zeros((E,), jnp.float32)
        route, chosen = _route(
            m, t["moe_gate"][lm], bias, top_k=int(cfg["num_experts_per_tok"]),
            norm=bool(cfg["norm_topk_prob"]), scale=float(cfg["routed_scaling_factor"]))
        if routes is not None:
            routes.append(np.asarray(chosen))
        ffn = jnp.zeros_like(h)
        for e0 in range(0, E, EXPERT_BLOCK):
            blk = slice(e0, e0 + EXPERT_BLOCK)
            ffn = ffn + _expert_block(
                m, route[..., blk],
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
        y = _rounder(lossy)(_rms_norm(x, t["rms_final"], float(cfg["norm_eps"])))
        packed, scales = t["wcls"].packed, t["wcls"].scales
        outs = [np.asarray(_head_chunk(y, packed[:, lo:lo + chunk], scales[:, lo:lo + chunk]))
                for lo in range(0, packed.shape[-1], chunk)]
    return np.concatenate(outs, axis=-1)[..., : cfg["vocab_size"]]
