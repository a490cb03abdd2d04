"""The Jamba family (``model_type: jamba``) with one expert a layer: selective
state-space (Mamba-1) mixers beside a few attention layers of one key/value
head and NO positional term, a gated MLP in every layer, served from Q40. What
`harness/cells.py` `load_family` asks of an architecture; the plain reference
below imports nothing of the program.

The layer, as published (`config.json` keys in brackets; ``h`` the stream,
``eps`` = [rms_norm_eps], ``E`` = [mamba_expand] x [hidden_size], ``N`` =
[mamba_d_state], ``R`` = [mamba_dt_rank], ``K`` = [mamba_d_conv]; no bias
except where said):

    n = rmsnorm(h, g_in)                                              input_layernorm
    l % [attn_layer_period] == [attn_layer_offset]:  attention, else Mamba
    Mamba:  [x; z] = W_in n                   two parts of E, in that order   [mamba_proj_bias false]
            c_t = b_c + sum_{j<K} w[:, j] * x_{t-(K-1)+j}    depthwise, causal, x_{<0} = 0   [mamba_conv_bias]
            u_t = silu(c_t)
            [dt; B; C] = W_x u_t              R + N + N numbers
            dt = rmsnorm(dt, g_dt); B = rmsnorm(B, g_B); C = rmsnorm(C, g_C)   the family's inner norms
            D_t = softplus(W_dt dt + b_dt)    E numbers (the bias is kept)
            A = -exp(A_log)                   [E, N]
            S_t = exp(D_t (x) A) * S_{t-1} + (D_t * u_t) (x) B_t      S in R^{E x N}, S_{-1} = 0
            y_t = S_t C_t + D * u_t
            h' = h + W_out (y_t * silu(z_t))
    attention: [num_attention_heads] query heads, [num_key_value_heads] key/value heads, no rotation
            h' = h + Wo [o_i],  o_i = sum_{s<=t} softmax_s(q_i . k(s) / sqrt(head)) v(s)
    m = rmsnorm(h', g_ff)                                             pre_ff_layernorm
    h'' = h' + W_down (silu(W_gate m) * W_up m)                       [intermediate_size], every layer
    logits = W_head rmsnorm(h_last, g_final)

The reference runs the recurrence as a plain ``lax.scan`` over single rows
from ``S = 0``: no cache, no chunks. Attention goes a key/value head at a time.
Departures from the published form: weights are Q40, dequantized here; the
head is a Q40 matrix of its own where the family ties it to the embedding.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from harness.reference import _rms_norm, _rounder, dequant_q40
from harness.weights import q40_plane, seed_key

MAMBA, ATTENTION = "mamba", "attention"
# Output rms of each matmul for an input of rms 1 (`harness/weights.py` GAIN
# argues the Llama block's; `families/lfm2_moe.py` a conv mixer's). Both
# parts of W_in have rms 1; the taps are drawn with rms K^-1/2, so the conv's
# output has rms about 1 before the bias and u = silu(c) about 0.6. dt, B and
# C are normed, so W_x's gain does not matter. With Mamba's initialisation
# (below) the state's part of y, S_t C_t, has a variance of about
# 0.6 * mean(D_t) * (g_B g_C)^2 = 0.013 (g_B g_C)^2 against the skip term's
# 0.31 (D = 1): at gains of 1 the state would be a twentieth of the mixer's
# output and a wrong state (a chunk boundary, a parked lane, a start not from
# zero) a rounding error in the logits. The inner norms' gains are trained
# parameters: drawn about 2 each (BC_GAIN), as QK_GAIN is in the other
# families, the state's part has rms 0.46 beside the skip's 0.56 and a model
# that uses its state is what is measured. y * silu(z) then has rms about 0.5
# and W_out at 0.6 adds about 0.3 a layer, as the attention branch does
# through Wo; the MLP adds about 0.15 through W_down at 0.2. Attention is
# sharp, as trained heads are: gains of 2 on queries and keys spread the
# scores by about 4.
GAIN = {"wq": 2.0, "wk": 2.0, "wv": 1.0, "wo": 0.3,
        "ssm_in": 1.0, "ssm_x": 1.0, "ssm_out": 0.6,
        "dense_w1": 1.0, "dense_w3": 1.0, "dense_w2": 0.2, "wcls": 1.78}
BC_GAIN = 2.0
# Mamba's own published initialisation of what steers the exponential: it
# decides how long the state remembers (hundreds to thousands of rows)
DT_MIN, DT_MAX = 1e-3, 1e-1


def _kinds(cfg: dict) -> list[str]:
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return [ATTENTION if l % period == offset else MAMBA
            for l in range(cfg["num_hidden_layers"])]


def program_config(cfg: dict):
    """The program's configuration object from the published keys."""
    from distributed_llama_multiusers_tpu.formats.model_file import LayerKind, RopeType
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    if not hasattr(LayerKind, "SSM"):
        raise SystemExit("this program has no state-space layer kind (LayerKind.SSM): "
                         "it cannot run the jamba family")
    if cfg["num_experts"] != 1:
        raise SystemExit("this family's FFN is a plain MLP (num_experts 1): the routed "
                         "variant is another family")
    if cfg.get("mamba_proj_bias") or cfg.get("sliding_window"):
        raise SystemExit("the program's state-space mixer has no projection bias and "
                         "its attention no window (mamba_proj_bias, sliding_window)")
    return LlamaConfig(
        dim=cfg["hidden_size"], hidden_dim=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], vocab_size=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"], rope_type=RopeType.NONE,
        norm_epsilon=float(cfg["rms_norm_eps"]),
        layer_kinds=tuple(
            LayerKind.ATTENTION if k == ATTENTION else LayerKind.SSM for k in _kinds(cfg)),
        ssm_d_inner=cfg["mamba_expand"] * cfg["hidden_size"],
        ssm_d_state=cfg["mamba_d_state"], ssm_dt_rank=cfg["mamba_dt_rank"],
        ssm_conv_kernel=cfg["mamba_d_conv"],
        ssm_conv_bias=1 if cfg["mamba_conv_bias"] else 0, ssm_inner_norms=1,
    )


def _generate(c, key, dtype, vocab_out):
    La, Ls, L, d, h = c.n_attention_layers, c.n_ssm_layers, c.n_layers, c.dim, c.hidden_dim
    E, N, R, K = c.ssm_d_inner, c.ssm_d_state, c.ssm_dt_rank, c.ssm_conv_kernel
    shapes = {
        "wq": ((La,), d, d), "wk": ((La,), d, c.kv_dim), "wv": ((La,), d, c.kv_dim),
        "wo": ((La,), d, d),
        "ssm_in": ((Ls,), d, 2 * E), "ssm_x": ((Ls,), E, R + 2 * N), "ssm_out": ((Ls,), E, d),
        "dense_w1": ((L,), d, h), "dense_w2": ((L,), h, d), "dense_w3": ((L,), d, h),
        "wcls": ((), d, vocab_out),
    }
    keys = jax.random.split(key, len(shapes) + 12)
    out = {}
    for k, (name, (lead, d_in, d_out)) in zip(keys, shapes.items()):
        live = c.vocab_size if name == "wcls" else None
        out[name] = q40_plane(*jax.random.split(k), lead, d_in, d_out, GAIN[name], live_out=live)
    ke, kt, kb, kd, kw, kdt, k1, k2, k3, k4, k5, k6 = keys[len(shapes):]
    normal, uniform = jax.random.normal, jax.random.uniform
    f32 = jnp.float32
    out["embedding"] = normal(ke, (c.vocab_size, d), f32).astype(dtype)
    out["ssm_taps"] = K ** -0.5 * normal(kt, (Ls, K, E), f32)
    if c.ssm_conv_bias:
        out["ssm_conv_bias"] = uniform(kb, (Ls, E), f32, -(K ** -0.5), K ** -0.5)
    # Mamba's initialisation: W_dt uniform in +-R^-1/2, softplus(b_dt)
    # log-uniform in [DT_MIN, DT_MAX], A_log = log(1..N) a channel, D = 1
    out["ssm_dt_proj"] = uniform(kw, (Ls, R, E), f32, -(R ** -0.5), R ** -0.5)
    dt = jnp.exp(uniform(kdt, (Ls, E), f32, np.log(DT_MIN), np.log(DT_MAX)))
    out["ssm_dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    out["ssm_a_log"] = jnp.broadcast_to(
        jnp.log(jnp.arange(1, N + 1, dtype=f32))[None, :, None], (Ls, N, E))
    out["ssm_d"] = jnp.ones((Ls, E), f32)
    out["ssm_dt_norm"] = 1.0 + 0.1 * normal(kd, (Ls, R), f32)
    out["ssm_b_norm"] = BC_GAIN * (1.0 + 0.1 * normal(k1, (Ls, N), f32))
    out["ssm_c_norm"] = BC_GAIN * (1.0 + 0.1 * normal(k2, (Ls, N), f32))
    out["attn_rms"] = 1.0 + 0.1 * normal(k3, (La, d), f32)
    out["ssm_rms"] = 1.0 + 0.1 * normal(k4, (Ls, d), f32)
    out["dense_rms_ffn"] = 1.0 + 0.1 * normal(k5, (L, d), f32)
    out["rms_final"] = 1.0 + 0.1 * normal(k6, (d,), f32)
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
    loader's). Nothing is rotated, so there are no tables."""
    from distributed_llama_multiusers_tpu.models.hybrid import hybrid_params

    return hybrid_params(t, None, None)


def lane_state_rel_err(engine, lane_x: int, lane_y: int, n: int):
    """Both lanes have absorbed the same n tokens. Largest difference between
    their rows ``[0, n)`` of K and of V (what is kept by position) and between
    the WHOLE of their running sums (float32) and of their conv windows (what
    is not: overwritten in place), each over the largest magnitude there."""
    cache = engine.cache
    if getattr(cache, "table", None) is not None or getattr(cache, "ssm", None) is None:
        return None
    worst = 0.0
    for leaf, rows in ((cache.k, n), (cache.v, n), (cache.ssm, None), (cache.ssm_conv, None)):
        if leaf.size == 0:
            continue
        x = np.asarray(leaf[:, lane_x].astype(jnp.float32))[:, :rows]
        y = np.asarray(leaf[:, lane_y].astype(jnp.float32))[:, :rows]
        worst = max(worst, float(np.abs(x - y).max() / max(np.abs(y).max(), 1e-30)))
    return worst


# -- the plain reference ------------------------------------------------------


@partial(jax.jit, static_argnames=("n_state", "rank", "eps", "lossy"))
def _mamba_mixer(x, lw, *, n_state, rank, eps, lossy=None):
    """The Mamba half of a block over whole sequences; returns ``h'``. The
    recurrence a row at a time from ``S = 0``, the state float32 and never
    rounded."""
    r = _rounder(lossy)
    t = x.shape[1]
    n = r(_rms_norm(x, lw["rms"], eps))
    xin, z = jnp.split(r(n @ dequant_q40(*lw["ssm_in"])), 2, axis=-1)
    taps = lw["taps"]  # [K, E]: tap j multiplies x_{t-(K-1)+j}
    k = taps.shape[0]
    padded = jnp.pad(xin, ((0, 0), (k - 1, 0), (0, 0)))  # x_{<0} = 0
    c = sum(taps[j] * padded[:, j:j + t] for j in range(k))
    if "conv_bias" in lw:
        c = c + lw["conv_bias"]
    u = r(jax.nn.silu(c))
    dt, bm, cm = jnp.split(r(u @ dequant_q40(*lw["ssm_x"])), (rank, rank + n_state), axis=-1)
    dt = _rms_norm(dt, lw["dt_norm"], eps)
    bm = _rms_norm(bm, lw["b_norm"], eps)
    cm = _rms_norm(cm, lw["c_norm"], eps)
    delta = jax.nn.softplus(dt @ lw["dt_proj"] + lw["dt_bias"])  # [B, T, E]
    a = -jnp.exp(lw["a_log"])  # [N, E]

    def row(s, xs):
        d_t, u_t, b_t, c_t = xs  # [B, E], [B, E], [B, N], [B, N]
        s = jnp.exp(d_t[:, None, :] * a) * s + (d_t * u_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.einsum("bne,bn->be", s, c_t)

    s0 = jnp.zeros((x.shape[0], n_state, u.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(row, s0, tuple(jnp.moveaxis(v, 1, 0) for v in (delta, u, bm, cm)))
    y = jnp.moveaxis(y, 0, 1) + lw["d"] * u
    return r(x + r(y * jax.nn.silu(z)) @ dequant_q40(*lw["ssm_out"]))


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps", "lossy"))
def _attention(x, lw, *, n_heads, n_kv, eps, lossy=None):
    """The attention half of a block over whole sequences, a key/value head
    at a time, nothing rotated; returns ``h'``."""
    r = _rounder(lossy)
    b, t, d = x.shape
    hd, g = d // n_heads, n_heads // n_kv
    n = r(_rms_norm(x, lw["rms"], eps))
    q = r(n @ dequant_q40(*lw["wq"])).reshape(b, t, n_heads, hd)
    k = r(n @ dequant_q40(*lw["wk"])).reshape(b, t, n_kv, hd)
    v = r(n @ dequant_q40(*lw["wv"])).reshape(b, t, n_kv, hd)
    causal = jnp.tril(jnp.ones((t, t), bool))[None, None]
    heads = []
    for j in range(n_kv):
        scores = jnp.einsum("btgh,bsh->bgts", q[:, :, j * g:(j + 1) * g], k[:, :, j]) / np.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        heads.append(jnp.einsum("bgts,bsh->btgh", probs, v[:, :, j]))
    att = jnp.concatenate(heads, axis=2).reshape(b, t, d)
    return r(x + r(att) @ dequant_q40(*lw["wo"]))


@partial(jax.jit, static_argnames=("eps", "lossy"))
def _mlp(h, g, w1, w2, w3, *, eps, lossy=None):
    r = _rounder(lossy)
    m = r(_rms_norm(h, g, eps))
    w1, w2, w3 = dequant_q40(*w1), dequant_q40(*w2), dequant_q40(*w3)
    return r(h + r(jax.nn.silu(m @ w1) * (m @ w3)) @ w2)


def _planes(t, names, index):
    return {k: (t[k].packed[index], t[k].scales[index]) for k in names}


def reference_forward(cfg: dict, t: dict, tokens, lossy: str | None = None):
    """The stream after the last block, float32 ``[B, T, d]``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    eps = float(cfg["rms_norm_eps"])
    x = t["embedding"][tokens].astype(jnp.float32)
    n_attn = n_ssm = 0
    for layer, kind in enumerate(_kinds(cfg)):
        if kind == MAMBA:
            lw = _planes(t, ("ssm_in", "ssm_x", "ssm_out"), n_ssm)
            lw.update(rms=t["ssm_rms"][n_ssm], taps=t["ssm_taps"][n_ssm],
                      dt_norm=t["ssm_dt_norm"][n_ssm], b_norm=t["ssm_b_norm"][n_ssm],
                      c_norm=t["ssm_c_norm"][n_ssm], dt_proj=t["ssm_dt_proj"][n_ssm],
                      dt_bias=t["ssm_dt_bias"][n_ssm], a_log=t["ssm_a_log"][n_ssm],
                      d=t["ssm_d"][n_ssm])
            if "ssm_conv_bias" in t:
                lw["conv_bias"] = t["ssm_conv_bias"][n_ssm]
            h = _mamba_mixer(x, lw, n_state=cfg["mamba_d_state"], rank=cfg["mamba_dt_rank"],
                             eps=eps, lossy=lossy)
            n_ssm += 1
        else:
            lw = _planes(t, ("wq", "wk", "wv", "wo"), n_attn)
            lw.update(rms=t["attn_rms"][n_attn])
            h = _attention(x, lw, n_heads=cfg["num_attention_heads"],
                           n_kv=cfg["num_key_value_heads"], eps=eps, lossy=lossy)
            n_attn += 1
        d = _planes(t, ("dense_w1", "dense_w2", "dense_w3"), layer)
        x = _mlp(h, t["dense_rms_ffn"][layer], d["dense_w1"], d["dense_w2"], d["dense_w3"],
                 eps=eps, lossy=lossy)
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
