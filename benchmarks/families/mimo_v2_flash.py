"""The MiMo-V2-Flash family (``model_type: mimo_v2_flash``): window attention of
128 keys with a learned sink a query head beside full-context attention, the
two kinds with kv head counts and rotation bases of their own; keys 192 wide of
which the first 64 numbers rotate, values 128 wide and scaled; a sequential
block under RMS norms; a leading dense FFN, then 256 routed experts chosen 8 a
token by biased sigmoid scores, no shared expert; served from Q40. What
`harness/cells.py` `load_family` asks of an architecture; the plain reference
below imports nothing of the program.

The layer, as published (`config.json` keys in brackets; ``h`` the stream; no
bias anywhere [attention_bias false]; RMS norms with [layernorm_epsilon]):

    every layer l:  n = rmsnorm(h, g_in);   h'  = h  + Wo [o_0 .. o_{H-1}]
                    m = rmsnorm(h', g_ff);  h'' = h' + ffn_l(m)
    [hybrid_layer_pattern][l] == 0, full context:
        H = [num_attention_heads], Hk = [num_key_value_heads], theta = [rope_theta],
        every s <= t, NO sink [add_full_attention_sink_bias false]
    == 1, window:
        H = [swa_num_attention_heads], Hk = [swa_num_key_value_heads], theta = [swa_rope_theta],
        t - W < s <= t with W = [sliding_window], a sink b_i a query head
        [add_swa_attention_sink_bias true]
    either kind:
        q_i = Wq_i n in R^[head_dim];  k_j = Wk_j n in R^[head_dim]
        v_j = [attention_value_scale] * Wv_j n in R^[v_head_dim]
        the first r = int([head_dim] * [partial_rotary_factor]) numbers of every
        q_i and k_j are rotated at the kind's theta; the others are not
        e_i(t, s) = q_i(t) . k_{i // (H / Hk)}(s) / sqrt([head_dim])
        full:    p_i(t, s) = exp(e_i(t, s)) / sum_s' exp(e_i(t, s'))
        window:  p_i(t, s) = exp(e_i(t, s)) / (exp(b_i) + sum_s' exp(e_i(t, s')))
        o_i(t) = sum_s p_i(t, s) v_{i // (H / Hk)}(s)
    [moe_layer_freq][l] == 0:  ffn = W_down(silu(W_gate m) * W_up m)   [intermediate_size]
    == 1:  s = sigmoid(Wg m), float32                  [scoring_func, n_routed_experts]
        S = the [num_experts_per_tok] largest of s + c  [topk_method noaux_tc: c chooses
                                                        and does not weigh; n_group 1]
        w_e = s_e / sum_S s [norm_topk_prob];  no further factor [routed_scaling_factor null]
        ffn = sum_{e in S AND held here} w_e W2_e (silu(W1_e m) * W3_e m)   [moe_intermediate_size]
    logits = W_head rmsnorm(h_last, g_final)            [tie_word_embeddings false]

The held share (`model-configs` guide, section 4): the chip holds
``n_routed_experts`` experts, ids ``deployment.experts_first`` onward, of the
``deployment.n_routed_experts_published`` the router scores; a chosen expert
outside the share adds nothing, here as in the program, and its score stays in
the renormalising sum.

The reference builds full ``[T, S]`` masks from positions, takes the sink as a
concatenated column that is dropped after the softmax, keeps no cache, no ring
and no chunks, and computes every held expert on every token weighted by the
scores as written: a sequence at a time, attention a block of queries at a
time and the FFNs a block of rows and of experts at a time, so that 9300
tokens fit beside the engine. Departures: the rotary embedding rotates
adjacent pairs of the first ``r`` numbers, the .m format's convention (the
converter permutes the published ``rotate_half`` pairing to it); weights are
Q40, dequantized here.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from harness import cells
from harness.reference import _rms_norm, _rope, _rounder, dequant_q40, rope_tables
from harness.weights import q40_plane, seed_key

# what the two families of window and full-context layers over a held share of
# routed experts have in common is cohere2_moe's, used as it is: the comparison
# of two lanes' planes and rings (whatever their widths), the share of chosen
# sets that differ, the gated FFN, a block of experts, a layer's planes, the head
_cohere = cells.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "cohere2_moe.py"),
    "bench_family_cohere2_moe")
lane_state_rel_err = _cohere.lane_state_rel_err
route_difference_share = _cohere.route_difference_share
_gated_ffn, _expert_block = _cohere._gated_ffn, _cohere._expert_block
_planes, _head_chunk = _cohere._planes, _cohere._head_chunk

WINDOW, FULL = 1, 0  # [hybrid_layer_pattern]'s entries
# Output rms of each matmul for an input of rms 1 (`harness/weights.py` GAIN
# argues the Llama block's; `families/cohere2_moe.py` the routed experts').
# Queries and keys are not normed, so their projections' gains of 2 spread the
# scores by about 4, as trained heads are sharp: a window row's weight sits on
# a few of its 128 keys and a full-context row's mostly more than a window
# back, so a window left out, a base or a rotated width mistaken move the
# logits by far more than rounding does. Attention adds about a quarter of the
# stream's rms a layer (Wo at 0.3 over values of rms 0.7 of which the sink
# takes its share), the dense FFN and the routed experts as much.
GAIN = {"wq": 2.0, "wk": 2.0, "wv": 1.0, "wk_w": 2.0, "wv_w": 1.0, "wo": 0.4,
        "dense_w1": 1.0, "dense_w3": 1.0, "dense_w2": 0.5,
        "w1": 1.0, "w3": 1.0, "w2": 1.2, "wcls": 1.78}
ROUTER_SPREAD = 1.0  # float32 logits of standard deviation 1 for a unit input
# The selection bias spans +-BIAS_SPREAD. Neighbouring scores near the eighth
# largest of 256 lie about 0.007 apart, so a bias of this size swaps an expert
# or two of a row's eight (`route_difference_share` measures it): left out,
# the chosen sets of most rows differ and the logits with them. It is a seeded
# PERMUTATION of an even grid, the held experts' and the other chips' apart: a
# trained bias evens the experts' load and a drawn one does not, and where a
# chip holds 16 of 256 a uniform draw made the held experts' share of the
# choices, and with it a decode step's slabs, follow the seed (0.43-0.59 of a
# row's eight by layer, `itl_p50_ms` 11.70-11.81 over three seeds: 0.97 %
# where half the bound is 0.5 %; chip, PR 54). Every seed now hands this chip
# the same biases in another order.
BIAS_SPREAD = 0.03
SCORE_SPREAD = 4.0  # standard deviation of a row's scores under the gains above


def sink_range(window: int) -> tuple[float, float]:
    """The range a sink's logit is drawn from, uniform, a query head: scores
    spread by ``SCORE_SPREAD`` over ``window`` keys sum to about ``exp(spread *
    z + 0.7)`` in a typical row, ``z`` the expected largest of ``window``
    standard normals, so a sink from 2.2 under that sum to level with it holds
    between a tenth and a half of a window row's mass (8.3 to 10.5 at 128
    keys; ``reference_forward``'s ``sink_mass`` measures it)."""
    root = np.sqrt(2.0 * np.log(window))
    z = root - (np.log(np.log(window)) + np.log(4.0 * np.pi)) / (2.0 * root)
    top = SCORE_SPREAD * z + 0.7
    return float(top - 2.2), float(top)


QUERY_BLOCK = 128    # queries a block of the reference's attention
ROW_BLOCK = 2048     # rows a block of the reference's FFNs
EXPERT_BLOCK = 4     # held experts dequantized and multiplied at a time
# what the controls put in the reference's place (`lossy="fault:<name>"`)
FAULTS = ("no_sink", "no_window", "window_at_full_base", "rotate_whole_head",
          "no_value_scale", "no_select_bias")


def _kinds(cfg: dict) -> list[int]:
    kinds = list(cfg["hybrid_layer_pattern"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {WINDOW, FULL}:
        raise SystemExit("hybrid_layer_pattern: 0 (full context) or 1 (window) a layer")
    return kinds


def _n_dense(cfg: dict) -> int:
    """Leading layers whose FFN is dense: [moe_layer_freq]'s leading zeros,
    which have to be all of its zeros."""
    freq = list(cfg["moe_layer_freq"])
    n = next((i for i, f in enumerate(freq) if f), len(freq))
    if len(freq) != cfg["num_hidden_layers"] or any(f != 1 for f in freq[n:]):
        raise SystemExit("moe_layer_freq: dense layers (0) lead, routed ones (1) follow")
    return n


def rotary_width(cfg: dict) -> int:
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


def held(cfg: dict) -> tuple[int, int, int]:
    """(first id, experts held here, experts the router scores)."""
    dep = cfg.get("deployment", {})
    n = cfg["n_routed_experts"]
    return (int(dep.get("experts_first", 0)), n,
            int(dep.get("n_routed_experts_published", n)))


def program_config(cfg: dict):
    """The program's configuration object from the published keys. What the
    family needs of the program is asked for FIRST, and a program without it
    (the parent commit given this file) is refused in one line, before a
    weight is made or a program compiled."""
    from distributed_llama_multiusers_tpu.formats import model_file
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    fields = LlamaConfig.__dataclass_fields__
    missing = [f"LlamaConfig.{f}" for f in (
        "rotary_dim", "window_n_kv_heads", "window_rope_theta", "attn_value_scale",
        "window_sink") if f not in fields]
    if missing:
        raise SystemExit("the program cannot run a mimo_v2_flash configuration: it has no "
                         + ", ".join(missing))
    refused = [key for key, bad in (
        ("attention_bias", cfg.get("attention_bias")),
        ("add_full_attention_sink_bias", cfg.get("add_full_attention_sink_bias")),
        ("add_swa_attention_sink_bias", not cfg.get("add_swa_attention_sink_bias")),
        ("swa_num_attention_heads", cfg["swa_num_attention_heads"] != cfg["num_attention_heads"]),
        ("swa_head_dim", cfg["swa_head_dim"] != cfg["head_dim"]),
        ("swa_v_head_dim", cfg["swa_v_head_dim"] != cfg["v_head_dim"]),
        ("n_shared_experts", cfg.get("n_shared_experts")),
        ("routed_scaling_factor", cfg.get("routed_scaling_factor") not in (None, 1, 1.0)),
        ("n_group", cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1),
        ("scoring_func", cfg["scoring_func"] != "sigmoid"),
        ("tie_word_embeddings", cfg.get("tie_word_embeddings")),
    ) if bad]
    if refused:
        raise SystemExit(f"the program does not run a mimo_v2_flash with {', '.join(refused)} "
                         "as this configuration sets it")
    first, n_held, n_all = held(cfg)
    kind = {WINDOW: model_file.LayerKind.WINDOW, FULL: model_file.LayerKind.ATTENTION}
    return LlamaConfig(
        dim=cfg["hidden_size"], hidden_dim=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], vocab_size=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"], head_dim=cfg["head_dim"],
        v_head_dim=cfg["v_head_dim"], rotary_dim=rotary_width(cfg),
        rope_theta=float(cfg["rope_theta"]), window_rope_theta=float(cfg["swa_rope_theta"]),
        window_n_kv_heads=cfg["swa_num_key_value_heads"],
        sliding_window=cfg["sliding_window"],
        attn_value_scale=float(cfg["attention_value_scale"]), window_sink=1,
        norm_epsilon=float(cfg["layernorm_epsilon"]),
        n_experts=n_all, n_active_experts=cfg["num_experts_per_tok"],
        moe_hidden_dim=cfg["moe_intermediate_size"], shared_hidden_dim=0,
        n_dense_layers=_n_dense(cfg), moe_score_func=model_file.MoeScore.SIGMOID,
        moe_select_bias=1 if cfg["topk_method"] == "noaux_tc" else 0,
        moe_norm_topk=1 if cfg["norm_topk_prob"] else 0,
        moe_norm_floor=0.0,  # sigmoid scores are positive: the family divides by their sum
        experts_held_first=first, experts_held_count=n_held if n_held < n_all else 0,
        layer_kinds=tuple(kind[k] for k in _kinds(cfg)),
    )


def _generate(c, key, dtype, vocab_out):
    from distributed_llama_multiusers_tpu.quants.packed import Q40Experts

    L, d, Eh = c.n_layers, c.dim, c.experts_held[1]
    Lw, Ld = c.n_window_layers, c.n_dense_layers
    Lf, Lm = L - Lw, L - Ld
    (kf, vf), (kw, vw) = c.kv_widths(), c.kv_widths(True)
    shapes = {
        "wq": ((L,), d, c.q_dim), "wo": ((L,), c.o_dim, d),
        # a stack a kind: their kv heads differ
        "wk": ((Lf,), d, kf), "wv": ((Lf,), d, vf),
        "wk_w": ((Lw,), d, kw), "wv_w": ((Lw,), d, vw),
        "dense_w1": ((Ld,), d, c.hidden_dim), "dense_w2": ((Ld,), c.hidden_dim, d),
        "dense_w3": ((Ld,), d, c.hidden_dim),
        # the expert planes are stacked [routed layers, experts held, ...]
        "w1": ((Lm, Eh), d, c.moe_hidden_dim), "w2": ((Lm, Eh), c.moe_hidden_dim, d),
        "w3": ((Lm, Eh), d, c.moe_hidden_dim),
        "wcls": ((), d, vocab_out),
    }
    keys = jax.random.split(key, len(shapes) + 8)
    out = {}
    for k, (name, (lead, d_in, d_out)) in zip(keys, shapes.items()):
        live = c.vocab_size if name == "wcls" else None
        out[name] = q40_plane(*jax.random.split(k), lead, d_in, d_out, GAIN[name], live_out=live)
    # the program keeps expert scales as float16 bit patterns; made so here,
    # in the same program, so that no float16 copy stays on the device
    for name in ("w1", "w2", "w3"):
        out[name] = Q40Experts.from_packed(out[name])
    kg, kb, ks, ke, k1, k2, k3, k4 = keys[len(shapes):]
    normal = jax.random.normal
    out["moe_gate"] = ROUTER_SPREAD * d ** -0.5 * normal(kg, (Lm, d, c.n_experts), jnp.float32)
    first = c.experts_held[0]

    def layer_bias(k):  # a permutation of an even grid, held and other experts apart
        kh, ko = jax.random.split(k)
        grid = lambda n: jnp.linspace(-BIAS_SPREAD, BIAS_SPREAD, n, dtype=jnp.float32)  # noqa: E731
        held_, rest = (jax.random.permutation(kh, grid(Eh)),
                       jax.random.permutation(ko, grid(c.n_experts - Eh)))
        return jnp.concatenate([rest[:first], held_, rest[first:]])

    out["moe_bias"] = jax.vmap(layer_bias)(jax.random.split(kb, Lm))
    out["attn_sink"] = jax.random.uniform(
        ks, (Lw, c.n_heads), jnp.float32, *sink_range(c.sliding_window))
    out["embedding"] = normal(ke, (c.vocab_size, d), jnp.float32).astype(dtype)
    out["attn_rms"] = 1.0 + 0.1 * normal(k1, (L, d), jnp.float32)
    out["rms_ffn"] = 1.0 + 0.1 * normal(k2, (Lm, d), jnp.float32)
    out["dense_rms_ffn"] = 1.0 + 0.1 * normal(k3, (Ld, d), jnp.float32)
    out["rms_final"] = 1.0 + 0.1 * normal(k4, (d,), jnp.float32)
    return out


def device_weights(config, seed: int, dtype=jnp.bfloat16) -> dict:
    """name -> device array (``PackedQ40`` of two; the experts ``Q40Experts``),
    all from one program; ``wq`` / ``wo`` in layer order, ``wk`` / ``wv`` a
    stack a kind. The vocabulary is padded as the loader pads it."""
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    vocab_out = padded_d_out(config.vocab_size)
    t = jax.jit(lambda k: _generate(config, k, dtype, vocab_out))(seed_key(seed))
    jax.block_until_ready(t)
    return t


def assemble_params(config, t: dict):
    """The program's parameter tree around the arrays (its own function: the
    loader's); the RoPE tables of both kinds are the program's own."""
    from distributed_llama_multiusers_tpu.models.hybrid import hybrid_params
    from distributed_llama_multiusers_tpu.models.loader import _rope_cache

    tables = (*_rope_cache(config), *_rope_cache(config, config.window_rope_theta))
    return hybrid_params(t, *(jax.device_put(a) for a in tables))


# -- the plain reference ------------------------------------------------------


def _rope_first(x, r: int, cos, sin):
    """The first ``r`` numbers of every head rotated, the others as they are."""
    if r >= x.shape[-1]:
        return _rope(x, cos, sin)
    return jnp.concatenate([_rope(x[..., :r], cos, sin), x[..., r:]], axis=-1)


@partial(jax.jit, static_argnames=("n_kv", "eps", "rot", "lossy"))
def _norm_kv(x, g, wk, wv, cos, sin, v_scale, *, n_kv, eps, rot, lossy=None):
    """The layer's normed input, and every position's keys and values."""
    r = _rounder(lossy)
    b, t, _ = x.shape
    n = r(_rms_norm(x, g, eps))
    k = (n @ dequant_q40(*wk)).reshape(b, t, n_kv, -1)
    v = r(v_scale * (n @ dequant_q40(*wv))).reshape(b, t, n_kv, -1)
    return n, r(_rope_first(k, rot, cos, sin)), v


@partial(jax.jit, static_argnames=("n_heads", "window", "rot", "lossy"))
def _attend(n, k, v, wq, wo, cos, sin, sink, *, n_heads, window, rot, lossy=None):
    """``(Wo [o_i], the sink's mean share of a row's mass)`` for every position
    of one sequence, a block of queries at a time; the mask is built from
    positions: ``s <= t``, and with a window ``t - window < s``. ``sink``
    ``[n_heads]`` (``-inf``: none): one more column of the softmax, dropped
    after it."""
    r = _rounder(lossy)
    _, t, n_kv, hd = k.shape
    g = n_heads // n_kv
    wq, wo = dequant_q40(*wq), dequant_q40(*wo)
    s_pos = jnp.arange(t)
    column = jnp.broadcast_to(sink.reshape(n_kv, g, 1, 1), (n_kv, g, QUERY_BLOCK, 1))

    def block(args):
        nb, cb, sb, tb = args  # [Q, d], [Q, rot / 2] twice, [Q] positions
        q = _rope_first((nb @ wq).reshape(1, -1, n_heads, hd), rot, cb, sb)
        q = r(q).reshape(-1, n_kv, g, hd)
        scores = jnp.einsum("qkgh,skh->kgqs", q, k[0]) / np.sqrt(hd)
        ok = s_pos[None, :] <= tb[:, None]
        if window:
            ok = ok & (s_pos[None, :] > tb[:, None] - window)
        scores = jnp.where(ok[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(jnp.concatenate([scores, column], axis=-1), axis=-1)
        o = jnp.einsum("kgqs,skh->qkgh", probs[..., :-1], v[0]).reshape(-1, n_heads * v.shape[-1])
        return r(o) @ wo, jnp.mean(probs[..., -1])

    split = lambda a: a.reshape(t // QUERY_BLOCK, QUERY_BLOCK, *a.shape[1:])  # noqa: E731
    out, mass = jax.lax.map(block, (split(n[0]), split(cos), split(sin), split(s_pos)))
    return out.reshape(1, t, -1), jnp.mean(mass)


@partial(jax.jit, static_argnames=("top_k", "norm"))
def _route(m, gate, bias, *, top_k, norm):
    """The weight of every expert for every token ``[b, t, E]``, zero outside
    the chosen set, as written in the module's header; and the chosen set.
    The bias chooses and does not weigh."""
    s = jax.nn.sigmoid(m @ gate)
    order = jnp.argsort(-(s + bias), axis=-1, stable=True)
    chosen = jnp.argsort(order, axis=-1) < top_k
    w = jnp.where(chosen, s, 0.0)
    if norm:
        w = w / w.sum(axis=-1, keepdims=True)
    return w, chosen


def reference_forward(cfg: dict, t: dict, tokens, lossy: str | None = None,
                      routes: list | None = None, fault: str | None = None,
                      held_range: tuple | None = None, sink_mass: list | None = None,
                      routed_only: bool = False):
    """The stream after the last block, float32 ``[B, T, d]``, a sequence at a
    time. ``routes``, a list, is given the chosen set of every routed layer of
    every sequence (bool ``[1, T, E]``); ``sink_mass`` the sink's mean share of
    a row's mass, a window layer of a sequence. ``fault`` (the controls only):
    one of ``FAULTS``. ``held_range`` ``(first, count)``: another share of the
    experts than the configuration's, the arrays' experts being those;
    ``routed_only``: the sum of the routed layers' FFN terms instead of the
    stream (the share test adds shares up)."""
    if fault not in (None, *FAULTS):
        raise ValueError(f"unknown fault {fault!r}")
    tokens = np.asarray(tokens, np.int32)
    r = _rounder(lossy)
    eps = float(cfg["layernorm_epsilon"])
    n_heads, hd, rot = cfg["num_attention_heads"], cfg["head_dim"], rotary_width(cfg)
    first, n_held, _ = held(cfg)
    if held_range is not None:
        first, n_held = held_range
    n_dense = _n_dense(cfg)
    t_pad = -(-tokens.shape[1] // QUERY_BLOCK) * QUERY_BLOCK
    if fault == "rotate_whole_head":
        rot = hd
    theta = {FULL: cfg["rope_theta"],
             WINDOW: cfg["rope_theta"] if fault == "window_at_full_base" else cfg["swa_rope_theta"]}
    tables = {kind: tuple(jnp.asarray(a) for a in rope_tables(t_pad, rot, th))
              for kind, th in theta.items()}
    v_scale = 1.0 if fault == "no_value_scale" else float(cfg["attention_value_scale"])
    no_sink = jnp.full((n_heads,), -jnp.inf, jnp.float32)
    out = []
    for row in tokens:
        ids = np.zeros(t_pad, np.int32)
        ids[: len(row)] = row
        x = t["embedding"][jnp.asarray(ids)[None]].astype(jnp.float32)
        routed_sum = jnp.zeros_like(x)
        nth = {FULL: 0, WINDOW: 0}
        for layer, kind in enumerate(_kinds(cfg)):
            windowed = kind == WINDOW
            names = ("wk_w", "wv_w") if windowed else ("wk", "wv")
            lw = {**_planes(t, ("wq", "wo"), layer), **_planes(t, names, nth[kind])}
            sink = t["attn_sink"][nth[kind]] if windowed and fault != "no_sink" else no_sink
            nth[kind] += 1
            n_kv = cfg["swa_num_key_value_heads" if windowed else "num_key_value_heads"]
            window = cfg["sliding_window"] if windowed and fault != "no_window" else 0
            cos, sin = tables[kind]
            n, k, v = _norm_kv(x, t["attn_rms"][layer], lw[names[0]], lw[names[1]], cos, sin,
                               v_scale, n_kv=n_kv, eps=eps, rot=rot, lossy=lossy)
            a, mass = _attend(n, k, v, lw["wq"], lw["wo"], cos, sin, sink, n_heads=n_heads,
                              window=int(window), rot=rot, lossy=lossy)
            if sink_mass is not None and windowed:
                sink_mass.append(float(mass))
            x = r(x + r(a))
            if layer < n_dense:
                dw = _planes(t, ("dense_w1", "dense_w2", "dense_w3"), layer)
                m = r(_rms_norm(x, t["dense_rms_ffn"][layer], eps))
                x = r(x + r(_gated_ffn(m, dw["dense_w1"], dw["dense_w2"], dw["dense_w3"],
                                       lossy=lossy)))
                continue
            lm = layer - n_dense
            m = r(_rms_norm(x, t["rms_ffn"][lm], eps))
            bias = t["moe_bias"][lm]
            route, chosen = _route(m, t["moe_gate"][lm],
                                   jnp.zeros_like(bias) if fault == "no_select_bias" else bias,
                                   top_k=int(cfg["num_experts_per_tok"]),
                                   norm=bool(cfg["norm_topk_prob"]))
            if routes is not None:
                routes.append(np.asarray(chosen))
            ffn = []
            for r0 in range(0, t_pad, ROW_BLOCK):
                rows = slice(r0, min(r0 + ROW_BLOCK, t_pad))
                f = jnp.zeros_like(m[:, rows])
                for e0 in range(0, n_held, EXPERT_BLOCK):
                    blk = slice(e0, min(e0 + EXPERT_BLOCK, n_held))
                    f = f + _expert_block(
                        m[:, rows], route[:, rows, first + blk.start: first + blk.stop],
                        *(a_ for name in ("w1", "w2", "w3")
                          for a_ in (t[name].packed[lm, blk], t[name].scale_bits[lm, blk])),
                        lossy=lossy)
                ffn.append(f)
            ffn = jnp.concatenate(ffn, axis=1)
            routed_sum = routed_sum + ffn
            x = r(x + r(ffn))
        out.append((routed_sum if routed_only else x)[0, : tokens.shape[1]])
    return jnp.stack(out)


def reference_logits(cfg: dict, t: dict, tokens, row_positions, lossy: str | None = None,
                     chunk: int = 16384):
    """Float32 logits ``[B, R, vocab]`` at ``row_positions`` of each sequence,
    from the benchmark's own arrays; imports nothing of the program. ``lossy``
    (the controls only) names the type every value a block hands on is rounded
    to, or ``"fault:<name>"``: ``reference_forward``'s fault in the layer's
    place (`control_window.py`)."""
    fault = None
    if lossy and lossy.startswith("fault:"):
        lossy, fault = None, lossy.split(":", 1)[1]
    row_positions = jnp.asarray(row_positions, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = reference_forward(cfg, t, tokens, lossy, fault=fault)
        x = jnp.take_along_axis(x, row_positions[:, :, None], axis=1)
        y = _rounder(lossy)(_rms_norm(x, t["rms_final"], float(cfg["layernorm_epsilon"])))
        packed, scales = t["wcls"].packed, t["wcls"].scales
        outs = [np.asarray(_head_chunk(y, packed[:, lo:lo + chunk], scales[:, lo:lo + chunk]))
                for lo in range(0, packed.shape[-1], chunk)]
    return np.concatenate(outs, axis=-1)[..., : cfg["vocab_size"]]
