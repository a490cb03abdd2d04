"""The Command A+ family (``model_type: cohere2_moe``): window attention that
rotates and full-context attention that does not, in a published per-layer
pattern; 128 query heads of 128 on a 4096-wide stream; one mean-subtracting
norm a layer that feeds attention, the routed experts and the averaged shared
experts, all added to the stream together; served from Q40. What
`harness/cells.py` `load_family` asks of an architecture; the plain reference
below imports nothing of the program.

The layer, as published (`config.json` keys in brackets; ``h`` the stream; no
bias anywhere [attention_bias false]; ``W`` = [sliding_window], ``H`` =
[num_attention_heads], ``Hk`` = [num_key_value_heads], ``d`` = [head_dim]):

    n = g * (h - mean(h)) / sqrt(var(h) + [layer_norm_eps])       one norm a layer
    q_i = Wq_i n (i < H),  k_j = Wk_j n,  v_j = Wv_j n (j < Hk)   [use_qk_norm false]
    [layer_types][l] == "sliding_attention":
        q, k rotated over the whole head, theta [rope_theta]      [rope_gptj, rotary_pct 1]
        o_i(t) = sum over s in (t - W, t] of softmax_s(q_i(t) . k_{i // (H/Hk)}(s) / sqrt(d)) v(s)
    "full_attention":
        NO rotation and no other positional term; the same sum over every s <= t
    a = Wo [o_0 .. o_{H-1}]                                       H d -> hidden
    s = sigmoid(Wg n)                   [num_experts] scores, float32   [expert_selection_fn]
    S = the [num_experts_per_tok] largest;  w_e = s_e / sum_S s         [norm_topk_prob]
    r = sum_{e in S AND held here} w_e W2_e (silu(W1_e n) * W3_e n)     [intermediate_size]
    c = (1 / [num_shared_experts]) sum_i V2_i (silu(V1_i n) * V3_i n)   ["average"]
    h' = h + a + r + c                                            [use_parallel_block]
    logits = W_head layernorm(h_last, g_final)                    [logit_scale 1]

The held share (`model-configs` guide, section 4): the chip holds
``num_experts`` experts, ids ``deployment.experts_first`` onward, of the
``deployment.num_experts_published`` the router scores; a chosen expert outside
the share adds nothing, here as in the program, and its score stays in the
renormalising sum. The shared experts are one gated FFN of ``num_shared_experts
x intermediate_size`` whose output is scaled by ``1 / num_shared_experts``: the
same numbers as the mean of the four.

The reference builds full ``[T, S]`` masks from positions (a window layer's
``t - W < s <= t``), keeps no cache, no ring and no chunks, and computes every
held expert on every token weighted by the scores as written: a sequence at a
time, attention a block of queries at a time and the FFNs a block of rows and
of experts at a time, so that 9300 tokens fit beside the engine. Departures:
the rotary embedding rotates adjacent pairs, which IS the published
``rope_gptj`` and the .m format's convention; weights are Q40, dequantized
here; the output head is a Q40 matrix of its own (the family ties it to the
embedding; the program's loader holds wcls as its own tensor).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from harness.reference import _rope, _rounder, dequant_q40, rope_tables
from harness.weights import q40_plane, seed_key

WINDOW, FULL = "sliding_attention", "full_attention"
# Output rms of each matmul for an input of rms 1 (`harness/weights.py` GAIN
# argues the Llama block's). One norm feeds three branches, and each is sized
# to add about a quarter of the stream's rms a layer where the whole model
# is there: attention through Wo at 0.3 as in every family here; a routed
# expert's W2 at 1.2 (silu(W1 n) * W3 n has rms about 0.6, eight experts at
# about 1/8 each add 0.35 of one: 0.25; on this chip a token finds one of its
# eight here, a third of that); the shared experts' W2 at 1.7 (0.6 x 1.7 x
# the 1/4 of the average: 0.25). Queries and keys are not normed, so their
# projections' gains of 2 spread the scores by about 4, as trained heads are
# sharp: a query's weight sits on a few keys anywhere in its reach, in a
# full-context layer at 9000 positions mostly more than a window back, so
# leaving the window out of a window layer (or rotating a layer that must not)
# moves the logits by far more than rounding does.
GAIN = {"wq": 2.0, "wk": 2.0, "wv": 1.0, "wo": 0.3,
        "w1": 1.0, "w3": 1.0, "w2": 1.2,
        "shared_w1": 1.0, "shared_w3": 1.0, "shared_w2": 1.7, "wcls": 1.78}
ROUTER_SPREAD = 1.0  # float32 logits of standard deviation 1 for a unit input
QUERY_BLOCK = 128    # queries a block of the reference's attention
ROW_BLOCK = 2048     # rows a block of the reference's FFNs
EXPERT_BLOCK = 2     # held experts dequantized and multiplied at a time
# what the controls put in the reference's place (`lossy="fault:<name>"`)
FAULTS = ("no_window", "rotate_full")


def _kinds(cfg: dict) -> list[str]:
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {WINDOW, FULL}:
        raise SystemExit("layer_types: one of 'sliding_attention', 'full_attention' a layer")
    return kinds


def held(cfg: dict) -> tuple[int, int, int]:
    """(first id, experts held here, experts the router scores)."""
    dep = cfg.get("deployment", {})
    n = cfg["num_experts"]
    return int(dep.get("experts_first", 0)), n, int(dep.get("num_experts_published", n))


def program_config(cfg: dict):
    """The program's configuration object from the published keys. What the
    family needs of the program is asked for FIRST, and a program without it
    (the parent commit given this file) is refused in one line, before a
    weight is made or a program compiled."""
    from distributed_llama_multiusers_tpu.formats import model_file
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    fields = LlamaConfig.__dataclass_fields__
    missing = [what for what, there in (
        ("LayerKind.WINDOW", hasattr(model_file.LayerKind, "WINDOW")),
        ("NormKind", hasattr(model_file, "NormKind")),
        *((f"LlamaConfig.{f}", f in fields) for f in (
            "head_dim", "sliding_window", "full_attention_nope", "norm_kind",
            "parallel_block", "shared_expert_scale")),
    ) if not there]
    if missing:
        raise SystemExit("the program cannot run a cohere2_moe configuration: it has no "
                         + ", ".join(missing))
    refused = [key for key, bad in (
        ("use_qk_norm", cfg.get("use_qk_norm")),
        ("logit_scale", cfg.get("logit_scale", 1) != 1),
        ("first_k_dense_replace", cfg.get("first_k_dense_replace", 0) > 0),
        ("use_parallel_block", not cfg.get("use_parallel_block")),
        ("attention_bias", cfg.get("attention_bias")),
        ("rotary_pct", cfg.get("rotary_pct", 1) != 1),
    ) if bad]
    if refused:
        raise SystemExit(f"the program does not run a cohere2_moe with {', '.join(refused)} "
                         "as this configuration sets it")
    if cfg["shared_expert_combination_strategy"] != "average":
        raise SystemExit("shared_expert_combination_strategy: the family's is 'average'")
    first, n_held, n_all = held(cfg)
    kind = {WINDOW: model_file.LayerKind.WINDOW, FULL: model_file.LayerKind.ATTENTION}
    return LlamaConfig(
        dim=cfg["hidden_size"], hidden_dim=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], vocab_size=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), norm_epsilon=float(cfg["layer_norm_eps"]),
        n_experts=n_all, n_active_experts=cfg["num_experts_per_tok"],
        moe_hidden_dim=cfg["intermediate_size"],
        shared_hidden_dim=cfg["num_shared_experts"] * cfg["intermediate_size"],
        shared_expert_scale=1.0 / cfg["num_shared_experts"],
        n_dense_layers=0, moe_score_func=model_file.MoeScore.SIGMOID,
        moe_select_bias=0, moe_norm_topk=1 if cfg["norm_topk_prob"] else 0,
        moe_norm_floor=0.0,  # sigmoid scores are positive: the family divides by their sum
        experts_held_first=first, experts_held_count=n_held if n_held < n_all else 0,
        layer_kinds=tuple(kind[k] for k in _kinds(cfg)),
        sliding_window=cfg["sliding_window"], full_attention_nope=1,
        norm_kind=model_file.NormKind.LAYER, parallel_block=1,
    )


def _generate(c, key, dtype, vocab_out):
    from distributed_llama_multiusers_tpu.quants.packed import Q40Experts

    L, d, Eh = c.n_layers, c.dim, c.experts_held[1]
    shapes = {
        "wq": ((L,), d, c.q_dim), "wk": ((L,), d, c.kv_dim), "wv": ((L,), d, c.kv_dim),
        "wo": ((L,), c.q_dim, d),
        # the expert planes are stacked [layers, experts held, ...]
        "w1": ((L, Eh), d, c.moe_hidden_dim), "w2": ((L, Eh), c.moe_hidden_dim, d),
        "w3": ((L, Eh), d, c.moe_hidden_dim),
        "shared_w1": ((L,), d, c.shared_hidden_dim), "shared_w2": ((L,), c.shared_hidden_dim, d),
        "shared_w3": ((L,), d, c.shared_hidden_dim),
        "wcls": ((), d, vocab_out),
    }
    keys = jax.random.split(key, len(shapes) + 4)
    out = {}
    for k, (name, (lead, d_in, d_out)) in zip(keys, shapes.items()):
        live = c.vocab_size if name == "wcls" else None
        out[name] = q40_plane(*jax.random.split(k), lead, d_in, d_out, GAIN[name], live_out=live)
    # the program keeps expert scales as float16 bit patterns; made so here,
    # in the same program, so that no float16 copy stays on the device
    for name in ("w1", "w2", "w3"):
        out[name] = Q40Experts.from_packed(out[name])
    kg, ke, k1, k2 = keys[len(shapes):]
    normal = jax.random.normal
    out["moe_gate"] = ROUTER_SPREAD * d ** -0.5 * normal(kg, (L, d, c.n_experts), jnp.float32)
    out["embedding"] = normal(ke, (c.vocab_size, d), jnp.float32).astype(dtype)
    out["attn_rms"] = 1.0 + 0.1 * normal(k1, (L, d), jnp.float32)
    out["rms_final"] = 1.0 + 0.1 * normal(k2, (d,), jnp.float32)
    return out


def device_weights(config, seed: int, dtype=jnp.bfloat16) -> dict:
    """name -> device array (``PackedQ40`` of two; the experts ``Q40Experts``),
    all from one program; both kinds of attention layer in one stack, in layer
    order. The vocabulary is padded as the loader pads it."""
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
    their rows ``[0, n)`` of the full-context layers' K and V planes (kept by
    position) and, of the window layers' rings, between the rows that hold the
    positions a step at ``n`` can still read, ``(n - W, n)``: position ``p`` in
    row ``p mod R``. Each over the largest magnitude there."""
    cache = engine.cache
    if getattr(cache, "table", None) is not None or getattr(cache, "wk", None) is None:
        return None
    ring, window = cache.wk.shape[2], engine.config.sliding_window
    held_rows = np.arange(max(n - window + 1, 0), n) % ring
    worst = 0.0
    for leaf, rows in ((cache.k, np.arange(n)), (cache.v, np.arange(n)),
                       (cache.wk, held_rows), (cache.wv, held_rows)):
        if leaf.size == 0 or rows.size == 0:
            continue
        x = np.asarray(leaf[:, lane_x].astype(jnp.float32))[:, rows]
        y = np.asarray(leaf[:, lane_y].astype(jnp.float32))[:, rows]
        worst = max(worst, float(np.abs(x - y).max() / max(np.abs(y).max(), 1e-30)))
    return worst


def route_difference_share(routes_x: list, routes_y: list) -> float:
    """Share of (row, layer) pairs whose chosen sets differ between two passes
    over the same tokens (``reference_forward``'s ``routes``)."""
    differ = [np.any(x != y, axis=-1) for x, y in zip(routes_x, routes_y)]
    return float(np.mean(np.concatenate([d.reshape(-1) for d in differ])))


# -- the plain reference ------------------------------------------------------


def _layer_norm(x, g, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g


@partial(jax.jit, static_argnames=("n_kv", "eps", "rotate", "lossy"))
def _norm_kv(x, g, wk, wv, cos, sin, *, n_kv, eps, rotate, lossy=None):
    """The layer's one normed input, and every position's keys and values."""
    r = _rounder(lossy)
    b, t, _ = x.shape
    n = r(_layer_norm(x, g, eps))
    k = (n @ dequant_q40(*wk)).reshape(b, t, n_kv, -1)
    v = r(n @ dequant_q40(*wv)).reshape(b, t, n_kv, -1)
    if rotate:
        k = _rope(k, cos, sin)
    return n, r(k), v


@partial(jax.jit, static_argnames=("n_heads", "window", "rotate", "lossy"))
def _attend(n, k, v, wq, wo, cos, sin, *, n_heads, window, rotate, lossy=None):
    """``a = Wo [o_i]`` for every position of one sequence, a block of queries
    at a time; the mask is built from positions: ``s <= t``, and with a
    window ``t - window < s``."""
    r = _rounder(lossy)
    _, t, n_kv, hd = k.shape
    g = n_heads // n_kv
    wq, wo = dequant_q40(*wq), dequant_q40(*wo)
    s_pos = jnp.arange(t)

    def block(args):
        nb, cb, sb, tb = args  # [Q, d], [Q, hd / 2] twice, [Q] positions
        q = (nb @ wq).reshape(1, -1, n_heads, hd)
        if rotate:
            q = _rope(q, cb, sb)
        q = r(q).reshape(-1, n_kv, g, hd)
        scores = jnp.einsum("qkgh,skh->kgqs", q, k[0]) / np.sqrt(hd)
        ok = s_pos[None, :] <= tb[:, None]
        if window:
            ok = ok & (s_pos[None, :] > tb[:, None] - window)
        probs = jax.nn.softmax(jnp.where(ok[None, None], scores, -jnp.inf), axis=-1)
        o = jnp.einsum("kgqs,skh->qkgh", probs, v[0]).reshape(-1, n_heads * hd)
        return r(o) @ wo

    split = lambda a: a.reshape(t // QUERY_BLOCK, QUERY_BLOCK, *a.shape[1:])  # noqa: E731
    out = jax.lax.map(block, (split(n[0]), split(cos), split(sin), split(s_pos)))
    return out.reshape(1, t, -1)


@partial(jax.jit, static_argnames=("top_k", "norm"))
def _route(n, gate, *, top_k, norm):
    """The weight of every expert for every token ``[b, t, E]``, zero outside
    the chosen set, as written in the module's header; and the chosen set."""
    s = jax.nn.sigmoid(n @ gate)
    kth = jnp.sort(s, axis=-1)[..., -top_k, None]
    chosen = s >= kth
    w = jnp.where(chosen, s, 0.0)
    if norm:
        w = w / w.sum(axis=-1, keepdims=True)
    return w, chosen


@partial(jax.jit, static_argnames=("lossy",))
def _gated_ffn(m, w1, w2, w3, lossy=None):
    r = _rounder(lossy)
    w1, w2, w3 = dequant_q40(*w1), dequant_q40(*w2), dequant_q40(*w3)
    return r(jax.nn.silu(m @ w1) * (m @ w3)) @ w2


@partial(jax.jit, static_argnames=("lossy",))
def _expert_block(m, route, p1, s1, p2, s2, p3, s3, lossy=None):
    """``sum_e route[..., e] . W2_e (silu(W1_e m) * W3_e m)`` over a block of
    experts. Scales arrive as float16 bit patterns."""
    r = _rounder(lossy)
    f16 = lambda s: jax.lax.bitcast_convert_type(s, jnp.float16)  # noqa: E731
    w1 = jax.vmap(dequant_q40)(p1, f16(s1))  # [e, d, h]
    w2 = jax.vmap(dequant_q40)(p2, f16(s2))
    w3 = jax.vmap(dequant_q40)(p3, f16(s3))
    act = r(jax.nn.silu(jnp.einsum("btd,edh->bteh", m, w1)) * jnp.einsum("btd,edh->bteh", m, w3))
    return jnp.einsum("bte,bted->btd", route, jnp.einsum("bteh,ehd->bted", act, w2))


def _planes(t, names, index):
    return {k: (t[k].packed[index], t[k].scales[index]) for k in names}


def reference_forward(cfg: dict, t: dict, tokens, lossy: str | None = None,
                      routes: list | None = None, fault: str | None = None,
                      held_range: tuple | None = None, shared: bool = True):
    """The stream after the last block, float32 ``[B, T, d]``, a sequence at a
    time. ``routes``, a list, is given the chosen set of every layer of every
    sequence (bool ``[1, T, E]``). ``fault`` (the controls only): ``no_window``,
    the window layers attend every ``s <= t``; ``rotate_full``, the
    full-context layers rotate too. ``held_range`` ``(first, count)``: another
    share of the experts than the configuration's, the arrays' experts being
    those, and ``shared`` False leaves the shared experts out (the share
    test counts them once)."""
    if fault not in (None, *FAULTS):
        raise ValueError(f"unknown fault {fault!r}")
    tokens = np.asarray(tokens, np.int32)
    r = _rounder(lossy)
    eps = float(cfg["layer_norm_eps"])
    n_heads, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    first, n_held, _ = held(cfg)
    if held_range is not None:
        first, n_held = held_range
    t_pad = -(-tokens.shape[1] // QUERY_BLOCK) * QUERY_BLOCK
    cos, sin = (jnp.asarray(a) for a in rope_tables(t_pad, cfg["head_dim"], cfg["rope_theta"]))
    share = 1.0 / cfg["num_shared_experts"]
    out = []
    for row in tokens:
        ids = np.zeros(t_pad, np.int32)
        ids[: len(row)] = row
        x = t["embedding"][jnp.asarray(ids)[None]].astype(jnp.float32)
        for layer, kind in enumerate(_kinds(cfg)):
            lw = _planes(t, ("wq", "wk", "wv", "wo"), layer)
            rotate = kind == WINDOW or fault == "rotate_full"
            window = cfg["sliding_window"] if kind == WINDOW and fault != "no_window" else 0
            n, k, v = _norm_kv(x, t["attn_rms"][layer], lw["wk"], lw["wv"], cos, sin,
                               n_kv=n_kv, eps=eps, rotate=rotate, lossy=lossy)
            a = _attend(n, k, v, lw["wq"], lw["wo"], cos, sin, n_heads=n_heads,
                        window=int(window), rotate=rotate, lossy=lossy)
            route, chosen = _route(n, t["moe_gate"][layer],
                                   top_k=int(cfg["num_experts_per_tok"]),
                                   norm=bool(cfg["norm_topk_prob"]))
            if routes is not None:
                routes.append(np.asarray(chosen))
            ffn = []
            for r0 in range(0, t_pad, ROW_BLOCK):
                rows = slice(r0, min(r0 + ROW_BLOCK, t_pad))
                m = n[:, rows]
                f = jnp.zeros_like(m)
                if shared:
                    s = _planes(t, ("shared_w1", "shared_w2", "shared_w3"), layer)
                    f = share * _gated_ffn(m, s["shared_w1"], s["shared_w2"], s["shared_w3"],
                                           lossy=lossy)
                for e0 in range(0, n_held, EXPERT_BLOCK):
                    blk = slice(e0, min(e0 + EXPERT_BLOCK, n_held))
                    f = f + _expert_block(
                        m, route[:, rows, first + blk.start: first + blk.stop],
                        *(a_ for name in ("w1", "w2", "w3")
                          for a_ in (t[name].packed[layer, blk], t[name].scale_bits[layer, blk])),
                        lossy=lossy)
                ffn.append(f)
            x = r(x + r(a) + r(jnp.concatenate(ffn, axis=1)))
        out.append(x[0, : tokens.shape[1]])
    return jnp.stack(out)


@jax.jit
def _head_chunk(y, packed, scales):
    return y @ dequant_q40(packed, scales)


def reference_logits(cfg: dict, t: dict, tokens, row_positions, lossy: str | None = None,
                     chunk: int = 16384):
    """Float32 logits ``[B, R, vocab]`` at ``row_positions`` of each sequence,
    from the benchmark's own arrays; imports nothing of the program. ``lossy``
    (the controls only) names the type every value a block hands on is rounded
    to, or ``"fault:no_window"`` / ``"fault:rotate_full"``: ``reference_forward``'s
    fault in the layer's place (`control_window.py`)."""
    fault = None
    if lossy and lossy.startswith("fault:"):
        lossy, fault = None, lossy.split(":", 1)[1]
    row_positions = jnp.asarray(row_positions, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = reference_forward(cfg, t, tokens, lossy, fault=fault)
        x = jnp.take_along_axis(x, row_positions[:, :, None], axis=1)
        y = _rounder(lossy)(_layer_norm(x, t["rms_final"], float(cfg["layer_norm_eps"])))
        packed, scales = t["wcls"].packed, t["wcls"].scales
        outs = [np.asarray(_head_chunk(y, packed[:, lo:lo + chunk], scales[:, lo:lo + chunk]))
                for lo in range(0, packed.shape[-1], chunk)]
    return np.concatenate(outs, axis=-1)[..., : cfg["vocab_size"]]
