"""The MiniCPM-SALA family (``model_type: minicpm_sala``): lightning
linear-attention layers with a float32 matrix state a head beside GQA layers
that attend the blocks their compressed keys choose (InfLLM-v2), a gated MLP in
every layer, under a width-independent parametrisation, served from Q40. What
`harness/cells.py` `load_family` asks of an architecture; the plain reference
below imports nothing of the program.

The layer, as published (`config.json` keys in brackets; ``h`` the stream,
``L`` = [num_hidden_layers], ``d`` = [head_dim] = [lightning_head_dim], ``eps``
= [rms_norm_eps]; what the config does not give is the file's ``assumed``):

    x0 = [scale_emb] * embed(token)
    n = rmsnorm(h, g_in);  h' = h + ([scale_depth] / sqrt(L)) * mixer(n)
    m = rmsnorm(h', g_ff); h'' = h' + ([scale_depth] / sqrt(L)) * W_down(silu(W_gate m) * W_up m)
    logits = W_head (rmsnorm(h_last, g_final) / ([hidden_size] / [dim_model_base]))
    [mixer_types][l] == "lightning-attn", per head i of [lightning_nh]:
        q, k, v = W_q n, W_k n, W_v n;  q, k = rmsnorm per head (gains) [qk_norm], rotated [lightning_use_rope]
        S_t = lambda_i * S_{t-1} + k_t^T v_t            S in R^{d x d}, float32, S_{-1} = 0
        o_t = (1 / sqrt(d)) * q_t S_t                    [lightning_scale]
        y_t = rmsnorm(o_t, g_o) * sigmoid(W_g n)         [use_output_norm], [use_output_gate]
        mixer = W_o y;   lambda_i = exp(-2^(-8 (i + 1) / heads))
    [mixer_types][l] == "minicpm4": GQA [num_attention_heads] / [num_key_value_heads] heads of d
        q, k normed per head [qk_norm], NOT rotated [attn_use_rope false]
        ck_j = mean of k over positions [stride j, stride j + size)
        p(t, j) = softmax over the kernels j that end at or before t of (q_i(t) . ck_j / sqrt(d)),
                  summed over the query heads i of a kv head's group
        r(t, b) = max of p(t, j) over the kernels that overlap block b = [block b, block b + block)
        r = +inf for the first init_blocks blocks and the window / block blocks that end at t's own
        chosen(t) = the topk blocks of largest r (equal scores: the lower block), a set a kv head;
                    a row at position t < dense_len chooses every block
        o_i(t) = sum over s <= t in chosen(t) of softmax_s(q_i(t) . k(s) / sqrt(d)) v(s)
        mixer = W_o (o * sigmoid(W_g n))                 [attn_use_output_gate]

The reference runs the recurrence as a plain ``lax.scan`` over single rows
from ``S = 0``; the selection is a stable sort; attention goes a block of
queries and a key/value head at a time against every key under a mask. No
cache, no chunks, no compressed-key store. Departures from the published
form: weights are Q40, dequantized here; the rotation turns adjacent pairs
(the ``.m`` format's convention: `harness/reference.py`); where the public
implementation shares one coarser compression for the softmax's normaliser,
the equations above are followed.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from harness.reference import _rms_norm, _rope, _rounder, dequant_q40, rope_tables
from harness.weights import q40_plane, seed_key

LINEAR, SPARSE = "lightning-attn", "minicpm4"
# Output rms of each matmul for an input of rms 1. The embedding is drawn with
# rms 1 / scale_emb, so the stream starts at rms 1 as a trained one does, and
# every term joins it times scale_depth / sqrt(L) = 0.2475: the out
# projections are drawn larger than the other families' by about that factor.
# A lightning layer's output is its state's and nothing else's (no skip
# term): after the output norm (rms 1) and the gate (sigmoid of a unit normal,
# rms 0.54) W_o at 2.0 adds about 0.27 a layer. Queries and keys leave their
# per-head norms with gains about QK_GAIN = 2 each in the sparse layers, as
# trained heads are sharp (scores spread by about 4), so that WHICH blocks a
# row attends decides its output; in the lightning layers the gains are about
# 1 (nothing is normalised over the keys: the output norm takes the scale
# out). The MLP adds about 0.15 through W_down at 0.8. The head reads the
# final norm's output over hidden_size / dim_model_base = 16.
GAIN = {"wq": 1.0, "wk": 1.0, "wv": 1.0, "attn_gate": 1.0, "wo": 2.0,
        "lin_q": 1.0, "lin_k": 1.0, "lin_v": 1.0, "lin_gate": 1.0, "lin_out": 2.0,
        "dense_w1": 1.0, "dense_w3": 1.0, "dense_w2": 0.8, "wcls": 1.78}
QK_GAIN = 2.0
QUERY_BLOCK = 128  # queries a block of the reference's attention
ROW_BLOCK = 2048   # rows a block of the reference's projections and MLPs

# the controls' faults (`control_window.py`): the selection replaced by the
# topk newest blocks; the selection left out (every row attends every block it
# holds); the decay left out (lambda = 1)
FAULTS = ("newest_blocks", "no_selection", "no_decay")


def _kinds(cfg: dict) -> list[str]:
    kinds = list(cfg["mixer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {LINEAR, SPARSE}:
        raise SystemExit("mixer_types must name lightning-attn or minicpm4 for every layer")
    return kinds


def sparse_sizes(cfg: dict) -> dict:
    """The sparse layers' sizes: the file's ``sparse_config`` (``assumed``)."""
    return {k: int(v) for k, v in cfg["sparse_config"].items()}


def _scalars(cfg: dict) -> tuple[float, float, float]:
    """(factor on the embedding, on every residual term, divisor before the head)."""
    return (float(cfg["scale_emb"]),
            float(cfg["scale_depth"]) / float(np.sqrt(cfg["num_hidden_layers"])),
            float(cfg["hidden_size"]) / float(cfg["dim_model_base"]))


def program_config(cfg: dict):
    """The program's configuration object from the published keys."""
    from distributed_llama_multiusers_tpu.formats.model_file import LayerKind
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    if not hasattr(LayerKind, "LINEAR") or not hasattr(LayerKind, "SPARSE"):
        raise SystemExit("this program has no linear-attention or block-sparse layer kind "
                         "(LayerKind.LINEAR, LayerKind.SPARSE): it cannot run the "
                         "minicpm_sala family")
    if cfg["lightning_nkv"] != cfg["lightning_nh"] or cfg.get("attention_bias"):
        raise SystemExit("the program's linear attention has a key head a query head and "
                         "no bias (lightning_nkv, attention_bias)")
    if not (cfg["qk_norm"] and cfg["lightning_use_rope"] and not cfg["attn_use_rope"]
            and cfg["use_output_gate"] and cfg["use_output_norm"]
            and cfg["attn_use_output_gate"]):
        raise SystemExit("the program's two mixers are the published ones: normed queries "
                         "and keys, lightning layers rotated, sparse layers not, both gated")
    s = sparse_sizes(cfg)
    embed, residual, divisor = _scalars(cfg)
    return LlamaConfig(
        dim=cfg["hidden_size"], hidden_dim=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], vocab_size=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"], rope_theta=float(cfg["rope_theta"]),
        norm_epsilon=float(cfg["rms_norm_eps"]), head_dim=cfg["head_dim"], qk_norm=1,
        full_attention_nope=1,
        layer_kinds=tuple(
            LayerKind.LINEAR if k == LINEAR else LayerKind.SPARSE for k in _kinds(cfg)),
        linear_n_heads=cfg["lightning_nh"], linear_head_dim=cfg["lightning_head_dim"],
        sparse_kernel_size=s["kernel_size"], sparse_kernel_stride=s["kernel_stride"],
        sparse_block_size=s["block_size"], sparse_topk=s["topk"],
        sparse_window=s["window_size"], sparse_init_blocks=s["init_blocks"],
        sparse_dense_len=s["dense_len"],
        embed_scale=embed, residual_scale=residual, logit_divisor=divisor,
    )


def _generate(c, key, dtype, vocab_out):
    Lp, Ll, L, d, h = c.n_sparse_layers, c.n_linear_layers, c.n_layers, c.dim, c.hidden_dim
    D, hd = c.linear_dim, c.head_size
    shapes = {
        "wq": ((Lp,), d, c.q_dim), "wk": ((Lp,), d, c.kv_dim), "wv": ((Lp,), d, c.kv_dim),
        "attn_gate": ((Lp,), d, c.q_dim), "wo": ((Lp,), c.q_dim, d),
        "lin_q": ((Ll,), d, D), "lin_k": ((Ll,), d, D), "lin_v": ((Ll,), d, D),
        "lin_gate": ((Ll,), d, D), "lin_out": ((Ll,), D, d),
        "dense_w1": ((L,), d, h), "dense_w2": ((L,), h, d), "dense_w3": ((L,), d, h),
        "wcls": ((), d, vocab_out),
    }
    keys = jax.random.split(key, len(shapes) + 10)
    out = {}
    for k, (name, (lead, d_in, d_out)) in zip(keys, shapes.items()):
        live = c.vocab_size if name == "wcls" else None
        gain = GAIN[name] * (c.logit_divisor if name == "wcls" else 1.0)
        out[name] = q40_plane(*jax.random.split(k), lead, d_in, d_out, gain, live_out=live)
    ke, k0, k1, k2, k3, k4, k5, k6, k7, k8 = keys[len(shapes):]
    normal, f32 = jax.random.normal, jnp.float32
    gains = lambda k, shape, about=1.0: about * (1.0 + 0.1 * normal(k, shape, f32))  # noqa: E731
    out["embedding"] = (normal(ke, (c.vocab_size, d), f32) / c.embed_scale).astype(dtype)
    out["q_norm"], out["k_norm"] = gains(k0, (Lp, hd), QK_GAIN), gains(k1, (Lp, hd), QK_GAIN)
    out["lin_q_norm"], out["lin_k_norm"] = gains(k2, (Ll, hd)), gains(k3, (Ll, hd))
    out["lin_o_norm"] = gains(k4, (Ll, hd))
    out["attn_rms"], out["lin_rms"] = gains(k5, (Lp, d)), gains(k6, (Ll, d))
    out["dense_rms_ffn"], out["rms_final"] = gains(k7, (L, d)), gains(k8, (d,))
    return out


def device_weights(config, seed: int, dtype=jnp.bfloat16) -> dict:
    """name -> device array (``PackedQ40`` of two), all from one program; each
    kind of layer's tensors stacked by the count of that kind, the MLPs by
    layer. The vocabulary is padded as the loader pads it."""
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    vocab_out = padded_d_out(config.vocab_size)
    t = jax.jit(lambda k: _generate(config, k, dtype, vocab_out))(seed_key(seed))
    jax.block_until_ready(t)
    return t


def assemble_params(config, t: dict):
    """The program's parameter tree around the arrays (its own function: the
    loader's), with the rotation's tables as the loader builds them."""
    from distributed_llama_multiusers_tpu.models.hybrid import hybrid_params
    from distributed_llama_multiusers_tpu.models.loader import _rope_cache

    cos, sin = _rope_cache(config)
    return hybrid_params(t, jnp.asarray(cos), jnp.asarray(sin))


def lane_state_rel_err(engine, lane_x: int, lane_y: int, n: int):
    """Both lanes have absorbed the same n tokens. Largest difference between
    their rows ``[0, n)`` of K and of V and their compressed keys that end
    inside those rows (what is kept by position), and between the WHOLE of
    their matrix states (float32: overwritten in place), each over the largest
    magnitude there."""
    cache = engine.cache
    if getattr(cache, "table", None) is not None or getattr(cache, "lin", None) is None:
        return None
    cfg = engine.config
    n_ck = max((n - cfg.sparse_kernel_size) // cfg.sparse_kernel_stride + 1, 0)
    worst = 0.0
    for leaf, rows in ((cache.k, n), (cache.v, n), (cache.ck, n_ck), (cache.lin, None)):
        if leaf is None or leaf.size == 0 or rows == 0:
            continue
        x = np.asarray(leaf[:, lane_x].astype(jnp.float32))[:, :rows]
        y = np.asarray(leaf[:, lane_y].astype(jnp.float32))[:, :rows]
        worst = max(worst, float(np.abs(x - y).max() / max(np.abs(y).max(), 1e-30)))
    return worst


# -- the plain reference ------------------------------------------------------


def decay_factors(n_heads: int) -> np.ndarray:
    """``lambda_i = exp(-2^(-8 (i + 1) / heads))``, float32 ``[heads]``."""
    i = np.arange(1, n_heads + 1, dtype=np.float64)
    return np.exp(-(2.0 ** (-8.0 * i / n_heads))).astype(np.float32)


@partial(jax.jit, static_argnames=("shape", "eps", "lossy"))
def _project(n, w, g, *, shape, eps, lossy=None):
    """``W n`` in heads, normed per head where ``g`` is given."""
    y = (n @ dequant_q40(*w)).reshape(*n.shape[:2], *shape)
    return _rounder(lossy)(y if g is None else _rms_norm(y, g, eps))


@partial(jax.jit, static_argnames=("scale",))
def _recurrence(q, k, v, lam, *, scale):
    """``o_t = scale q_t S_t``, ``S_t = lambda S_{t-1} + k_t^T v_t`` from ``S =
    0``, a row at a time; q, k, v ``[T, H, d]``, the state float32 and never
    rounded."""
    def row(s, xs):
        q_t, k_t, v_t = xs
        s = lam[:, None, None] * s + k_t[:, :, None] * v_t[:, None, :]
        return s, scale * jnp.einsum("hd,hde->he", q_t, s)

    h, d = q.shape[1:]
    _, o = jax.lax.scan(row, jnp.zeros((h, d, d), jnp.float32), (q, k, v))
    return o


def _lightning(cfg, x, lw, cos, sin, res, lossy, fault):
    """A lightning layer's mixer half over one sequence ``[1, T, dim]``."""
    r = _rounder(lossy)
    eps = float(cfg["rms_norm_eps"])
    heads = (cfg["lightning_nh"], cfg["lightning_head_dim"])
    n = r(_rms_norm(x, lw["rms"], eps))
    q = _rope(_project(n, lw["lin_q"], lw["q_norm"], shape=heads, eps=eps, lossy=lossy), cos, sin)
    k = _rope(_project(n, lw["lin_k"], lw["k_norm"], shape=heads, eps=eps, lossy=lossy), cos, sin)
    v = _project(n, lw["lin_v"], None, shape=heads, eps=eps, lossy=lossy)
    gate = jax.nn.sigmoid(_project(n, lw["lin_gate"], None, shape=heads, eps=eps))
    lam = jnp.ones(heads[0]) if fault == "no_decay" else jnp.asarray(decay_factors(heads[0]))
    o = _recurrence(r(q)[0], r(k)[0], v[0], lam, scale=float(heads[1]) ** -0.5)[None]
    y = r(r(_rms_norm(o, lw["o_norm"], eps)) * gate).reshape(1, x.shape[1], -1)
    return r(x + res * _matmul_rows(y, lw["lin_out"]))


@jax.jit
def _matmul_block(y, packed, scales):
    return y @ dequant_q40(packed, scales)


def _matmul_rows(y, w):
    return jnp.concatenate(
        [_matmul_block(y[:, r0:r0 + ROW_BLOCK], *w) for r0 in range(0, y.shape[1], ROW_BLOCK)],
        axis=1)


def _compress(k, size: int, stride: int):
    """``ck_j`` = mean of k ``[T, n_kv, d]`` over ``[stride j, stride j + size)``,
    for every kernel that fits: ``[(T - size) / stride + 1, n_kv, d]``."""
    t = k.shape[0]
    part = k.reshape(t // stride, stride, *k.shape[1:]).mean(axis=1)
    n = (t - size) // stride + 1
    return sum(part[i:i + n] for i in range(size // stride)) / (size // stride)


@partial(jax.jit, static_argnames=("sizes", "group", "fault"))
def _sparse_block(qb, tb, k, v, ck, *, sizes, group, fault=None):
    """Attention of a block of queries ``qb`` ``[Q, n_kv, group, d]`` at
    positions ``tb`` over one sequence's keys; returns ``([Q, n_kv, group, d],
    chosen [Q, n_kv, blocks])``."""
    size, stride, block, topk, window, init, dense_len = sizes
    t, n_kv, d = k.shape
    n_blocks, scale = t // block, 1.0 / np.sqrt(d)
    blk = jnp.arange(n_blocks)
    own = (tb // block)[:, None, None]
    held = blk[None, None, :] <= own
    # the score pass over the kernels that end at or before the row
    ends = jnp.arange(ck.shape[0]) * stride + size - 1
    s = jnp.einsum("qkgd,jkd->qkgj", qb, ck) * scale
    ok = (ends[None, :] <= tb[:, None])[:, None, None, :]
    p = jnp.where(ok, jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1), 0.0)
    p = jnp.where(jnp.any(ok, axis=-1, keepdims=True), p, 0.0).sum(axis=2)  # [Q, n_kv, J]
    # a block's score: the largest over the kernels that overlap it
    starts = jnp.arange(ck.shape[0]) * stride
    over = ((starts[None, :] < (blk[:, None] + 1) * block)
            & (starts[None, :] + size > blk[:, None] * block))  # [blocks, J]
    r = jnp.max(jnp.where(over[None, None], p[:, :, None, :], 0.0), axis=-1)  # [Q, n_kv, blocks]
    forced = (blk[None, None, :] < init) | (blk[None, None, :] > own - window // block)
    if fault == "newest_blocks":
        forced = blk[None, None, :] > own - topk
    r = jnp.where(held, jnp.where(forced, jnp.inf, r), -jnp.inf)
    order = jnp.argsort(-r, axis=-1, stable=True)  # equal scores: the lower block first
    rank = jnp.argsort(order, axis=-1, stable=True)
    chosen = held & ((rank < topk) | (tb < dense_len)[:, None, None])
    if fault == "no_selection":
        chosen = jnp.broadcast_to(held, chosen.shape)
    s_pos = jnp.arange(t)
    read = (s_pos[None, None, :] <= tb[:, None, None]) & jnp.repeat(chosen, block, axis=-1)
    out = []
    for j in range(n_kv):  # a key/value head at a time
        sc = jnp.einsum("qgd,sd->qgs", qb[:, j], k[:, j]) * scale
        pr = jax.nn.softmax(jnp.where(read[:, j, None, :], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("qgs,sd->qgd", pr, v[:, j]))
    return jnp.stack(out, axis=1), chosen


def _sparse(cfg, x, lw, res, lossy, fault, chosen_out):
    """A sparse layer's mixer half over one sequence ``[1, T, dim]``."""
    r = _rounder(lossy)
    eps = float(cfg["rms_norm_eps"])
    n_heads, n_kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    s = sparse_sizes(cfg)
    sizes = (s["kernel_size"], s["kernel_stride"], s["block_size"], s["topk"],
             s["window_size"], s["init_blocks"], s["dense_len"])
    t = x.shape[1]
    n = r(_rms_norm(x, lw["rms"], eps))
    q = _project(n, lw["wq"], lw["q_norm"], shape=(n_heads, hd), eps=eps, lossy=lossy)
    k = _project(n, lw["wk"], lw["k_norm"], shape=(n_kv, hd), eps=eps, lossy=lossy)[0]
    v = _project(n, lw["wv"], None, shape=(n_kv, hd), eps=eps, lossy=lossy)[0]
    gate = jax.nn.sigmoid(_project(n, lw["attn_gate"], None, shape=(n_heads * hd,), eps=eps))
    ck = r(_compress(k, s["kernel_size"], s["kernel_stride"]))
    q = q[0].reshape(t, n_kv, n_heads // n_kv, hd)
    outs, sets = [], []
    for q0 in range(0, t, QUERY_BLOCK):
        o, chosen = _sparse_block(
            q[q0:q0 + QUERY_BLOCK], jnp.arange(q0, min(q0 + QUERY_BLOCK, t)), k, v, ck,
            sizes=sizes, group=n_heads // n_kv, fault=fault)
        outs.append(o)
        sets.append(chosen)
    if chosen_out is not None:
        chosen_out.append(np.asarray(jnp.concatenate(sets)))
    o = jnp.concatenate(outs).reshape(1, t, n_heads * hd)
    return r(x + res * _matmul_rows(r(r(o) * gate), lw["wo"]))


@partial(jax.jit, static_argnames=("eps", "lossy"))
def _mlp_block(h, g, w1, w2, w3, *, eps, lossy=None):
    r = _rounder(lossy)
    m = r(_rms_norm(h, g, eps))
    w1, w2, w3 = dequant_q40(*w1), dequant_q40(*w2), dequant_q40(*w3)
    return r(jax.nn.silu(m @ w1) * (m @ w3)) @ w2


def _planes(t, names, index):
    return {k: (t[k].packed[index], t[k].scales[index]) for k in names}


def reference_forward(cfg: dict, t: dict, tokens, lossy: str | None = None,
                      fault: str | None = None, chosen: list | None = None):
    """The stream after the last block, float32 ``[B, T, d]``, a sequence at a
    time. ``fault`` (the controls only): one of ``FAULTS``. ``chosen``, a
    list, is given every sparse layer's sets of every sequence (bool ``[T,
    n_kv, blocks]``)."""
    if fault not in (None, *FAULTS):
        raise ValueError(f"unknown fault {fault!r}")
    tokens = np.asarray(tokens, np.int32)
    r = _rounder(lossy)
    eps = float(cfg["rms_norm_eps"])
    embed, res, _ = _scalars(cfg)
    unit = max(QUERY_BLOCK, sparse_sizes(cfg)["block_size"])
    t_pad = -(-tokens.shape[1] // unit) * unit
    cos, sin = (jnp.asarray(a) for a in rope_tables(
        t_pad, cfg["lightning_head_dim"], cfg["rope_theta"]))
    out = []
    for row in tokens:
        ids = np.zeros(t_pad, np.int32)
        ids[: len(row)] = row
        x = embed * t["embedding"][jnp.asarray(ids)[None]].astype(jnp.float32)
        n_lin = n_sp = 0
        for layer, kind in enumerate(_kinds(cfg)):
            if kind == LINEAR:
                lw = _planes(t, ("lin_q", "lin_k", "lin_v", "lin_gate", "lin_out"), n_lin)
                lw.update(rms=t["lin_rms"][n_lin], q_norm=t["lin_q_norm"][n_lin],
                          k_norm=t["lin_k_norm"][n_lin], o_norm=t["lin_o_norm"][n_lin])
                h = _lightning(cfg, x, lw, cos, sin, res, lossy, fault)
                n_lin += 1
            else:
                lw = _planes(t, ("wq", "wk", "wv", "attn_gate", "wo"), n_sp)
                lw.update(rms=t["attn_rms"][n_sp], q_norm=t["q_norm"][n_sp],
                          k_norm=t["k_norm"][n_sp])
                h = _sparse(cfg, x, lw, res, lossy, fault, chosen)
                n_sp += 1
            d = _planes(t, ("dense_w1", "dense_w2", "dense_w3"), layer)
            x = r(h + res * jnp.concatenate([
                _mlp_block(h[:, r0:r0 + ROW_BLOCK], t["dense_rms_ffn"][layer], d["dense_w1"],
                           d["dense_w2"], d["dense_w3"], eps=eps, lossy=lossy)
                for r0 in range(0, t_pad, ROW_BLOCK)], axis=1))
        out.append(x[0, : tokens.shape[1]])
    return jnp.stack(out)


def reference_logits(cfg: dict, t: dict, tokens, row_positions, lossy: str | None = None,
                     chunk: int = 16384):
    """Float32 logits ``[B, R, vocab]`` at ``row_positions`` of each sequence,
    from the benchmark's own arrays; imports nothing of the program. ``lossy``
    (the controls only) names the type every value a block hands on is rounded
    to, or ``"fault:<one of FAULTS>"``: ``reference_forward``'s fault in the
    layer's place (`control_window.py`)."""
    fault = None
    if lossy and lossy.startswith("fault:"):
        lossy, fault = None, lossy.split(":", 1)[1]
    row_positions = jnp.asarray(row_positions, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = reference_forward(cfg, t, tokens, lossy, fault=fault)
        x = jnp.take_along_axis(x, row_positions[:, :, None], axis=1)
        y = _rms_norm(x, t["rms_final"], float(cfg["rms_norm_eps"])) / _scalars(cfg)[2]
        y = _rounder(lossy)(y)
        packed, scales = t["wcls"].packed, t["wcls"].scales
        outs = [np.asarray(_matmul_block(y, packed[:, lo:lo + chunk], scales[:, lo:lo + chunk]))
                for lo in range(0, packed.shape[-1], chunk)]
    return np.concatenate(outs, axis=-1)[..., : cfg["vocab_size"]]
