"""The Solar-Open2 family (``model_type: solar_open2``): gated delta-rule layers
(Kimi Delta Attention: a float32 matrix state a head under a decay a key
channel, q, k and v through short convolutions) three to one beside gated GQA
layers that rotate nothing; every layer's FFN routed, 320 experts chosen 8 a
token by biased sigmoid scores beside one shared expert; served from Q40. What
`harness/cells.py` `load_family` asks of an architecture; the plain reference
below imports nothing of the program.

The layer, as published (`config.json` keys in brackets; ``h`` the stream;
``n = rmsnorm(h, g)`` with [rms_norm_eps]; no bias on any projection; every
layer is mixer then routed FFN, each behind its own norm and added to the
stream; [first_k_dense_replace] 0: no dense layer):

    layer l in [gqa_layers] ([gqa_interval] 3: layer 4j): GQA, the others delta-rule
    delta-rule ([linear_attn_config]: H = [num_heads], d = [head_dim] for keys and
    values alike, K = [short_conv_kernel_size]), per head i, float32:
        q~ = W_q n,  k~ = W_k n,  v~ = W_v n                     each H d wide
        q' = silu(conv(q~)),  k' = silu(conv(k~)),  v = silu(conv(v~))
             conv: depthwise, causal, K taps a channel, no bias, inputs before 0 are 0
        q = q' / |q'| / sqrt(d),  k = k' / |k'|                  per head, eps in the sum
        g_t = -exp(A_log_i) * softplus(W_f2 (W_f1 n) + dt_bias)  [H, d], <= 0, a CHANNEL
        b_t = 2 sigmoid(W_b n)   [kda_allow_neg_eigval]          [H], in (0, 2)
        S'  = Diag(exp(g_t)) S_{t-1};  u_t = b_t (v_t - S'^T k_t);  S_t = S' + k_t u_t^T
        o_t = S_t^T q_t                                          S: [d (key), d (value)]
        h'  = h + W_o (rmsnorm_head(o_t, g_o) * sigmoid(W_g2 (W_g1 n)))
    GQA ([num_attention_heads] / [num_key_value_heads] heads of [head_dim]):
        o(t) = sum_{s<=t} softmax_s(q(t) . k(s) / sqrt(head_dim)) v(s), nothing rotated
        [use_rope false];  h' = h + W_o (o * sigmoid(W_g n))     [use_gqa_gate]
    FFN: s = sigmoid(W_r m), float32, [n_routed_experts] scores; S = the
        [num_experts_per_tok] largest of s + c (c chooses and does not weigh);
        w_e = s_e / sum_S s [norm_topk_prob], times [routed_scaling_factor];
        h'' = h' + shared(m) + sum_{e in S AND held here} w_e expert_e(m), each expert
        and the shared one W_down(silu(W_gate m) * W_up m) at [moe_intermediate_size]
    logits = W_head rmsnorm(h_last, g_final)

What the config leaves open is the file's ``assumed`` (the gates' rank, the
shapes of ``A_log`` and ``dt_bias``, the L2 norm's eps, the gates' sigmoids, the
router's family, the shared expert's width, no q/k norm in the GQA layers).

The held share (`model-configs` guide, section 4): the chip holds
``n_routed_experts`` experts, ids ``deployment.experts_first`` onward, of the
``deployment.n_routed_experts_published`` the router scores; a chosen expert
outside the share adds nothing, here as in the program, and its score stays in
the renormalising sum. The shared expert is whole on every chip.

The reference runs the recurrence as a plain ``lax.scan`` over single rows from
``S = 0``, a block of heads at a time, builds full ``[T, S]`` masks from
positions for the GQA layers, keeps no cache, no chunks and no conv window
(the convs read a zero-padded sequence), and computes every held expert on
every token weighted by the scores as written: a sequence at a time, in blocks
of heads, queries, rows and experts, so that 9300 tokens fit beside the engine.
Departures: weights are Q40, dequantized here (the two gates' second factors,
``W_b``, ``A_log``, ``dt_bias``, the taps, the router and the norms' gains float32).
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from harness import cells
from harness.reference import _rms_norm, _rounder, dequant_q40
from harness.weights import q40_plane, seed_key

# what the families of a held share of routed experts under a biased sigmoid
# router have in common is mimo_v2_flash's (and, through it, cohere2_moe's),
# used as it is: the router, the share, a block of experts, the gated FFN, a
# layer's planes, the head, the share of chosen sets that differ
_mimo = cells.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "mimo_v2_flash.py"),
    "bench_family_mimo_v2_flash")
held, route_difference_share = _mimo.held, _mimo.route_difference_share
_route, _gated_ffn, _expert_block = _mimo._route, _mimo._gated_ffn, _mimo._expert_block
_planes, _head_chunk = _mimo._planes, _mimo._head_chunk

# Output rms of each matmul for an input of rms 1 (`harness/weights.py` GAIN
# argues the Llama block's; `families/mimo_v2_flash.py` a NoPE GQA layer's
# sharp heads and the routed experts'). A delta-rule layer's q, k and v leave
# their projections at rms 1, pass the convs (below) and a silu; q and k are
# L2-normed, so only their directions matter; the output norm takes the
# state's scale out, the gate (a sigmoid of a unit normal: rms 0.54) and W_o at
# 0.5 add about a quarter of the stream's rms a layer, as the GQA layers do
# (W_o at 0.4 over gated values). The shared expert and the held experts add
# about as much together.
GAIN = {"wq": 2.0, "wk": 2.0, "wv": 1.0, "attn_gate": 1.0, "wo": 0.6,
        "delta_q": 1.0, "delta_k": 1.0, "delta_v": 1.0, "delta_f1": 1.0, "delta_g1": 1.0,
        "delta_out": 0.5,
        "w1": 1.0, "w3": 1.0, "w2": 1.2,
        "shared_w1": 1.0, "shared_w3": 1.0, "shared_w2": 0.4, "wcls": 1.78}
ROUTER_SPREAD = 1.0  # float32 logits of standard deviation 1 for a unit input
BIAS_SPREAD = 0.03   # the selection bias: a seeded permutation of an even grid (mimo_v2_flash.py)
# The router's columns come in antithetic pairs (w, -w), the held experts'
# among themselves and the other chips' among themselves. The delta-rule
# layers' silu gives the stream a common direction that follows the seed, and
# the experts it heats are chosen by most rows; with 40 of 320 held, the held
# experts' share of a row's eight choices followed the seed layer by layer
# (0.84-1.16 of the even share over four seeds, chip, PR 56), and with it the
# slabs a decode step fetches: `itl_p50_ms` 16.01-16.23 over seven seeds (a
# spread of 0.50 % where half the bound is 0.5 %) and `tokens_per_s`
# 294.7-300.3 (1.6 %), where one seed run three times reads 16.217-16.228 and
# 295.5-295.9. In a pair a direction that heats one expert cools the other, so
# the held share is the seed's only to second order (0.93-1.09 a layer on the
# same seeds). A trained router is balanced by its bias; a drawn one is not.
# A conv's taps: the newest input's tap about 1, the three before it about
# TAP_SPREAD each (drawn normal), so that what a channel's q, k or v is
# depends on its last rows: the convs left out move every key's direction.
TAP_SPREAD = 0.5
# The decay. A channel's rate a row is exp(A_log_head) * softplus(x + dt_bias),
# x = W_f2 (W_f1 n) of standard deviation GATE_SPREAD. exp(A_log) is
# log-uniform in [1/2, 2] a head and softplus(dt_bias) log-uniform a channel
# such that their product spans RATE_RANGE: over 64 rows a channel at the
# bias alone keeps between 0.05 and 0.99 of its state (exp(-64 rate)), and the
# input moves a rate by about e^-1..e^1 around that, row by row. So a decay
# left out, or one a head in place of one a channel, is a different state
# within tens of rows.
RATE_RANGE = (-np.log(0.99) / 64.0, -np.log(0.05) / 64.0)
A_RANGE = (0.5, 2.0)
GATE_SPREAD = 1.0
# W_b's logits have standard deviation B_SPREAD for a unit input: b = 2 sigmoid
# spans (0, 2), past 1 on half of the rows (no projection has a bias) and past
# 1.5 on a quarter, so that a b without its factor 2 is another update
B_SPREAD = 1.5
L2_EPS = 1e-6

QUERY_BLOCK = 128    # queries a block of the reference's attention
ROW_BLOCK = 2048     # rows a block of the reference's projections and FFNs
EXPERT_BLOCK = 4     # held experts dequantized and multiplied at a time
HEAD_BLOCK = 16      # delta-rule heads projected, convolved and scanned at a time
# what the controls put in the reference's place (`lossy="fault:<name>"`)
FAULTS = ("no_decay", "head_decay", "b_without_2", "no_delta", "no_conv",
          "no_gqa_gate", "no_select_bias", "state_bf16")


def _is_gqa(cfg: dict) -> list[bool]:
    """Whether each of the layers run is a GQA layer: [gqa_layers] names them."""
    n = cfg["num_hidden_layers"]
    gqa = set(int(l) for l in cfg["gqa_layers"])
    if any(not 0 <= l < n for l in gqa):
        raise SystemExit("gqa_layers names a layer past num_hidden_layers")
    return [l in gqa for l in range(n)]


def _assumed(cfg: dict) -> dict:
    """The sizes the published config names none of (the file's ``assumed``
    says why each): the low-rank gates' rank, the L2 norm's eps."""
    a = cfg["linear_attn_assumed"]
    return {"gate_rank": int(a["gate_rank"]), "l2_norm_eps": float(a["l2_norm_eps"])}


def program_config(cfg: dict):
    """The program's configuration object from the published keys. What the
    family needs of the program is asked for FIRST, and a program without it
    (the parent commit given this file) is refused in one line, before a
    weight is made or a program compiled."""
    from distributed_llama_multiusers_tpu.formats import model_file
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    fields = LlamaConfig.__dataclass_fields__
    missing = [f"LlamaConfig.{f}" for f in (
        "delta_n_heads", "delta_head_dim", "delta_conv_kernel", "delta_gate_rank",
        "delta_neg_eigval", "attn_output_gate") if f not in fields]
    if not hasattr(model_file.LayerKind, "DELTA"):
        missing.insert(0, "LayerKind.DELTA")
    if missing:
        raise SystemExit("the program cannot run a solar_open2 configuration: it has no "
                         + ", ".join(missing))
    lin = cfg["linear_attn_config"]
    refused = [key for key, bad in (
        ("linear_attn_config.num_kv_heads", lin.get("num_kv_heads") not in (None, lin["num_heads"])),
        ("kda_use_full_proj", cfg.get("kda_use_full_proj")),
        ("use_rope", cfg.get("use_rope")),
        ("use_gqa_gate", not cfg.get("use_gqa_gate")),
        ("first_k_dense_replace", cfg.get("first_k_dense_replace")),
        ("tie_word_embeddings", cfg.get("tie_word_embeddings")),
        ("l2_norm_eps", _assumed(cfg)["l2_norm_eps"] != L2_EPS),
    ) if bad]
    if refused:
        raise SystemExit(f"the program does not run a solar_open2 with {', '.join(refused)} "
                         "as this configuration sets it")
    first, n_held, n_all = held(cfg)
    kind = model_file.LayerKind
    return LlamaConfig(
        dim=cfg["hidden_size"], hidden_dim=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], vocab_size=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"], head_dim=cfg["head_dim"],
        rope_type=model_file.RopeType.NONE, rope_theta=float(cfg["rope_theta"]),
        norm_epsilon=float(cfg["rms_norm_eps"]), attn_output_gate=int(any(_is_gqa(cfg))),
        delta_n_heads=lin["num_heads"], delta_head_dim=lin["head_dim"],
        delta_conv_kernel=lin["short_conv_kernel_size"],
        delta_gate_rank=_assumed(cfg)["gate_rank"],
        delta_neg_eigval=1 if cfg["kda_allow_neg_eigval"] else 0,
        n_experts=n_all, n_active_experts=cfg["num_experts_per_tok"],
        moe_hidden_dim=cfg["moe_intermediate_size"],
        shared_hidden_dim=cfg["moe_intermediate_size"] * int(cfg["n_shared_experts"]),
        n_dense_layers=0, moe_score_func=model_file.MoeScore.SIGMOID, moe_select_bias=1,
        moe_norm_topk=1 if cfg["norm_topk_prob"] else 0,
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        moe_norm_floor=0.0,  # sigmoid scores are positive: the family divides by their sum
        experts_held_first=first, experts_held_count=n_held if n_held < n_all else 0,
        layer_kinds=tuple(kind.ATTENTION if g else kind.DELTA for g in _is_gqa(cfg)),
    )


def _generate(c, key, dtype, vocab_out):
    from distributed_llama_multiusers_tpu.quants.packed import Q40Experts

    L, d, Eh = c.n_layers, c.dim, c.experts_held[1]
    La, Ld = c.n_attention_layers, c.n_delta_layers
    D, H, hd, rank, K = c.delta_dim, c.delta_n_heads, c.delta_head_dim, c.delta_gate_rank, \
        c.delta_conv_kernel
    shapes = {
        "wq": ((La,), d, c.q_dim), "wk": ((La,), d, c.kv_dim), "wv": ((La,), d, c.kv_dim),
        "attn_gate": ((La,), d, c.q_dim), "wo": ((La,), c.q_dim, d),
        "delta_q": ((Ld,), d, D), "delta_k": ((Ld,), d, D), "delta_v": ((Ld,), d, D),
        "delta_f1": ((Ld,), d, rank), "delta_g1": ((Ld,), d, rank),
        "delta_out": ((Ld,), D, d),
        # the expert planes are stacked [routed layers, experts held, ...]
        "w1": ((L, Eh), d, c.moe_hidden_dim), "w2": ((L, Eh), c.moe_hidden_dim, d),
        "w3": ((L, Eh), d, c.moe_hidden_dim),
        "shared_w1": ((L,), d, c.shared_hidden_dim), "shared_w2": ((L,), c.shared_hidden_dim, d),
        "shared_w3": ((L,), d, c.shared_hidden_dim),
        "wcls": ((), d, vocab_out),
    }
    keys = jax.random.split(key, len(shapes) + 14)
    # (the last of the fourteen draws W_b, float32)
    out = {}
    for k, (name, (lead, d_in, d_out)) in zip(keys, shapes.items()):
        live = c.vocab_size if name == "wcls" else None
        out[name] = q40_plane(*jax.random.split(k), lead, d_in, d_out, GAIN[name], live_out=live)
    # the program keeps expert scales as float16 bit patterns; made so here,
    # in the same program, so that no float16 copy stays on the device
    for name in ("w1", "w2", "w3"):
        out[name] = Q40Experts.from_packed(out[name])
    (kg, kb, ke, kt, kf, kgg, ka, kd, k1, k2, k3, k4, k5, k6) = keys[len(shapes):]
    normal, f32 = jax.random.normal, jnp.float32
    first = c.experts_held[0]

    def layer_gate(k):  # [d, E]: pairs (w, -w), held and other experts apart
        def pairs(key, n):
            half = normal(key, (d, -(-n // 2)), f32)
            return jnp.concatenate([half, -half], axis=1)[:, :n]

        kh, ko = jax.random.split(k)
        held_, rest = pairs(kh, Eh), pairs(ko, c.n_experts - Eh)
        return jnp.concatenate([rest[:, :first], held_, rest[:, first:]], axis=1)

    out["moe_gate"] = ROUTER_SPREAD * d ** -0.5 * jax.vmap(layer_gate)(jax.random.split(kg, L))

    def layer_bias(k):  # a permutation of an even grid, held and other experts apart
        kh, ko = jax.random.split(k)
        grid = lambda n: jnp.linspace(-BIAS_SPREAD, BIAS_SPREAD, n, dtype=f32)  # noqa: E731
        held_, rest = (jax.random.permutation(kh, grid(Eh)),
                       jax.random.permutation(ko, grid(c.n_experts - Eh)))
        return jnp.concatenate([rest[:first], held_, rest[first:]])

    out["moe_bias"] = jax.vmap(layer_bias)(jax.random.split(kb, L))
    out["embedding"] = normal(ke, (c.vocab_size, d), f32).astype(dtype)
    # the taps [K, 3 D]: tap K-1 multiplies the newest input
    taps = TAP_SPREAD * normal(kt, (Ld, K, 3 * D), f32)
    out["delta_taps"] = taps.at[:, K - 1].add(1.0)
    out["delta_f2"] = GATE_SPREAD * rank ** -0.5 * normal(kf, (Ld, rank, D), f32)
    out["delta_g2"] = rank ** -0.5 * normal(kgg, (Ld, rank, D), f32)
    log_a = jax.random.uniform(ka, (Ld, H), f32, np.log(A_RANGE[0]), np.log(A_RANGE[1]))
    out["delta_a_log"] = log_a
    # softplus(dt_bias) * exp(A_log) log-uniform over RATE_RANGE, a channel
    rate = jnp.exp(jax.random.uniform(kd, (Ld, H, hd), f32, *np.log(RATE_RANGE)))
    dt = rate / jnp.exp(log_a)[..., None]
    out["delta_dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).reshape(Ld, D)  # softplus^-1
    gains = lambda k, shape: 1.0 + 0.1 * normal(k, shape, f32)  # noqa: E731
    out["delta_o_norm"] = gains(k1, (Ld, hd))
    out["attn_rms"], out["delta_rms"] = gains(k2, (La, d)), gains(k3, (Ld, d))
    out["rms_ffn"], out["rms_final"] = gains(k4, (L, d)), gains(k5, (d,))
    out["delta_b"] = B_SPREAD * d ** -0.5 * normal(k6, (Ld, d, H), f32)
    return out


def device_weights(config, seed: int, dtype=jnp.bfloat16) -> dict:
    """name -> device array (``PackedQ40`` of two; the experts ``Q40Experts``),
    all from one program; each kind of layer's tensors stacked by the count of
    that kind, the FFNs by layer. The vocabulary is padded as the loader pads
    it."""
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    vocab_out = padded_d_out(config.vocab_size)
    t = jax.jit(lambda k: _generate(config, k, dtype, vocab_out))(seed_key(seed))
    jax.block_until_ready(t)
    return t


def assemble_params(config, t: dict):
    """The program's parameter tree around the arrays (its own function: the
    loader's); nothing rotates, so no tables."""
    from distributed_llama_multiusers_tpu.models.hybrid import hybrid_params

    return hybrid_params(t, None, None)


def bfloat16_exact_share(state: np.ndarray) -> float:
    """The share of a float32 array's nonzero words that a bfloat16 holds
    exactly (their low 16 bits are zero). On the chip (PR 56, 1024 and 5120
    rows, nine layers): 5.8e-5 to 8.4e-5 in the engine's matrix state, 1.8e-5
    to 3.3e-5 in the reference's own carry, exactly 1 in the reference's with
    its state rounded to bfloat16 after every row."""
    words = np.ascontiguousarray(state, np.float32).view(np.uint32).ravel()
    words = words[words != 0]
    return float(np.mean((words & 0xFFFF) == 0)) if words.size else 0.0


def lanes_rel_err(engine, lane_x: int, lane_y: int, n: int):
    """Both lanes have absorbed the same n tokens. Largest difference between
    their rows ``[0, n)`` of K and of V (kept by position) and between the
    WHOLE of their matrix states (float32) and of their convs' windows
    (overwritten in place), each over the largest magnitude there."""
    cache = engine.cache
    if getattr(cache, "table", None) is not None or getattr(cache, "delta", None) is None:
        return None
    worst = 0.0
    for leaf, rows in ((cache.k, n), (cache.v, n), (cache.delta, None), (cache.delta_conv, None)):
        if leaf is None or leaf.size == 0 or rows == 0:
            continue
        x = np.asarray(leaf[:, lane_x].astype(jnp.float32))[:, :rows]
        y = np.asarray(leaf[:, lane_y].astype(jnp.float32))[:, :rows]
        worst = max(worst, float(np.abs(x - y).max() / max(np.abs(y).max(), 1e-30)))
    return worst


def lane_state_rel_err(engine, lane_x: int, lane_y: int, n: int):
    """`lanes_rel_err`, and the precision the matrix state rests in. Two lanes
    of one engine agree whatever precision both keep their state in, and a
    matrix state rounded to bfloat16 moves the logits, and the state itself
    against the reference's, no further than the bfloat16 engine's twelve
    routers do (the configuration's ``limits_from`` has both readings). So the
    number is the larger of the lanes' difference and the share of either
    lane's matrix-state words that a bfloat16 holds exactly
    (`bfloat16_exact_share`): 1 for a state kept or rounded in bfloat16, under
    1e-4 for one carried in float32."""
    worst = lanes_rel_err(engine, lane_x, lane_y, n)
    if worst is None:
        return None
    state = engine.cache.delta
    return max(worst, *(bfloat16_exact_share(np.asarray(state[:, lane]))
                        for lane in {lane_x, lane_y}))


# -- the plain reference ------------------------------------------------------


@jax.jit
def _matmul_block(y, packed, scales):
    return y @ dequant_q40(packed, scales)


def _matmul_rows(y, w):
    """``y W`` a block of rows at a time; y ``[1, T, d_in]``."""
    return jnp.concatenate(
        [_matmul_block(y[:, r0:r0 + ROW_BLOCK], *w) for r0 in range(0, y.shape[1], ROW_BLOCK)],
        axis=1)


def _conv(x, taps):
    """``y_t = sum_j taps[j] x_{t-(K-1)+j}`` a channel, inputs before 0 zero;
    x ``[T, C]``, taps ``[K, C]``."""
    k = taps.shape[0]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(taps[j] * xp[j:j + x.shape[0]] for j in range(k))


@partial(jax.jit, static_argnames=("no_delta", "state_bits"))
def _recurrence(q, k, v, g, b, *, no_delta=False, state_bits=None):
    """The delta rule a row at a time from ``S = 0``; q, k, v, g ``[T, H, d]``,
    b ``[T, H]``; the state float32 (the ``state_bf16`` control rounds it to
    ``state_bits`` (exponent, mantissa) after every row). Returns o ``[T, H, d]``
    and the state after the last row ``[H, d, d]``."""
    def row(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[:, :, None] * s
        read = 0.0 if no_delta else jnp.einsum("hkv,hk->hv", s, k_t)
        u = b_t[:, None] * (v_t - read)
        s = s + k_t[:, :, None] * u[:, None, :]
        if state_bits is not None:
            # (an astype pair to bfloat16 and back is elided on a TPU, where XLA
            # allows excess precision: the first chip reading of this control was 0)
            s = jax.lax.reduce_precision(s, *state_bits)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    h, d = q.shape[1:]
    s, o = jax.lax.scan(row, jnp.zeros((h, d, d), jnp.float32), (q, k, v, g, b))
    return o, s


def _delta(cfg, x, lw, lossy, fault, states=None):
    """A delta-rule layer's mixer half over one sequence ``[1, T, dim]``, a
    block of heads at a time. ``states``, a list, is given the layer's matrix
    state after the last row, ``[H, d, d]``."""
    r = _rounder(lossy)
    eps = float(cfg["rms_norm_eps"])
    lin = cfg["linear_attn_config"]
    n_heads, d = lin["num_heads"], lin["head_dim"]
    l2_eps = _assumed(cfg)["l2_norm_eps"]
    n = r(_rms_norm(x, lw["rms"], eps))
    t = x.shape[1]
    f1 = _matmul_rows(n, lw["delta_f1"])[0]  # [T, rank]
    g1 = _matmul_rows(n, lw["delta_g1"])[0]
    b = jax.nn.sigmoid(n[0] @ lw["delta_b"])  # [T, H]
    if cfg["kda_allow_neg_eigval"] and fault != "b_without_2":
        b = 2.0 * b
    wq, wk, wv = (dequant_q40(*lw[name]) for name in ("delta_q", "delta_k", "delta_v"))
    w_out = dequant_q40(*lw["delta_out"])
    D = n_heads * d
    out, final = jnp.zeros_like(x), []
    for h0 in range(0, n_heads, HEAD_BLOCK):
        hs = min(HEAD_BLOCK, n_heads - h0)
        cols = slice(h0 * d, (h0 + hs) * d)
        heads = (t, hs, d)
        qkv = []
        for i, w in enumerate((wq, wk, wv)):
            y = jnp.concatenate([n[0, r0:r0 + ROW_BLOCK] @ w[:, cols]
                                 for r0 in range(0, t, ROW_BLOCK)])
            if fault != "no_conv":
                y = _conv(y, lw["delta_taps"][:, i * D + cols.start: i * D + cols.stop])
            qkv.append(r(jax.nn.silu(y)).reshape(heads))
        l2 = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + l2_eps)  # noqa: E731
        q, k, v = r(l2(qkv[0]) / np.sqrt(d)), r(l2(qkv[1])), qkv[2]
        g = jax.nn.softplus(f1 @ lw["delta_f2"][:, cols] + lw["delta_dt_bias"][cols])
        g = -jnp.exp(lw["delta_a_log"][h0:h0 + hs])[None, :, None] * g.reshape(heads)
        if fault == "no_decay":
            g = jnp.zeros_like(g)
        elif fault == "head_decay":
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
        o, last = _recurrence(q, k, v, g, b[:, h0:h0 + hs], no_delta=fault == "no_delta",
                              state_bits=(8, 7) if fault == "state_bf16" else None)
        final.append(last)
        gate = jax.nn.sigmoid(g1 @ lw["delta_g2"][:, cols]).reshape(heads)
        y = r(r(_rms_norm(o, lw["o_norm"], eps)) * gate).reshape(1, t, hs * d)
        out = out + y @ w_out[cols]
    if states is not None:
        states.append(jnp.concatenate(final))
    return r(x + r(out))


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "gated", "lossy"))
def _attend(n, wq, wk, wv, wg, wo, *, n_heads, n_kv, gated, lossy=None):
    """``Wo (o * sigmoid(Wg n))`` for every position of one sequence ``[1, T,
    d]``, a block of queries at a time; nothing is rotated; the mask is built
    from positions, ``s <= t``."""
    r = _rounder(lossy)
    t = n.shape[1]
    g = n_heads // n_kv
    wq, wk, wv, wg, wo = (dequant_q40(*w) for w in (wq, wk, wv, wg, wo))
    k = r(n[0] @ wk).reshape(t, n_kv, -1)
    v = r(n[0] @ wv).reshape(t, n_kv, -1)
    hd = k.shape[-1]
    s_pos = jnp.arange(t)

    def block(args):
        nb, tb = args  # [Q, d], [Q] positions
        q = r(nb @ wq).reshape(-1, n_kv, g, hd)
        scores = jnp.einsum("qkgh,skh->kgqs", q, k) / np.sqrt(hd)
        scores = jnp.where((s_pos[None, :] <= tb[:, None])[None, None], scores, -jnp.inf)
        o = jnp.einsum("kgqs,skh->qkgh", jax.nn.softmax(scores, axis=-1), v)
        o = r(o).reshape(-1, n_heads * hd)
        if gated:
            o = r(o * jax.nn.sigmoid(nb @ wg))
        return o @ wo

    split = lambda a: a.reshape(t // QUERY_BLOCK, QUERY_BLOCK, *a.shape[1:])  # noqa: E731
    return jax.lax.map(block, (split(n[0]), split(s_pos))).reshape(1, t, -1)


def reference_forward(cfg: dict, t: dict, tokens, lossy: str | None = None,
                      routes: list | None = None, fault: str | None = None,
                      held_range: tuple | None = None, routed_only: bool = False,
                      states: list | None = None):
    """The stream after the last block, float32 ``[B, T, d]``, a sequence at a
    time. ``routes``, a list, is given the chosen set of every layer of every
    sequence (bool ``[1, T, E]``). ``fault`` (the controls only): one of
    ``FAULTS``. ``held_range`` ``(first, count)``: another share of the experts
    than the configuration's, the arrays' experts being those; ``routed_only``:
    the sum of the layers' ROUTED terms (the shared expert's left out) instead
    of the stream (the share test adds shares up). ``states``, a list, is given
    the matrix state ``[H, d, d]`` of every delta-rule layer of every sequence
    after its last row: the sequences then fill whole blocks of queries (no
    padded row follows the last)."""
    if fault not in (None, *FAULTS):
        raise ValueError(f"unknown fault {fault!r}")
    tokens = np.asarray(tokens, np.int32)
    r = _rounder(lossy)
    eps = float(cfg["rms_norm_eps"])
    n_heads, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    first, n_held, _ = held(cfg)
    if held_range is not None:
        first, n_held = held_range
    t_pad = -(-tokens.shape[1] // QUERY_BLOCK) * QUERY_BLOCK
    if states is not None and t_pad != tokens.shape[1]:
        raise ValueError(f"the state after the last row: {tokens.shape[1]} rows are not "
                         f"whole blocks of {QUERY_BLOCK}")
    delta_names = ("delta_q", "delta_k", "delta_v", "delta_f1", "delta_g1", "delta_out")
    out = []
    for row in tokens:
        ids = np.zeros(t_pad, np.int32)
        ids[: len(row)] = row
        x = t["embedding"][jnp.asarray(ids)[None]].astype(jnp.float32)
        routed_sum = jnp.zeros_like(x)
        n_gqa = n_delta = 0
        for layer, gqa in enumerate(_is_gqa(cfg)):
            if gqa:
                lw = _planes(t, ("wq", "wk", "wv", "attn_gate", "wo"), n_gqa)
                n = r(_rms_norm(x, t["attn_rms"][n_gqa], eps))
                a = _attend(n, lw["wq"], lw["wk"], lw["wv"], lw["attn_gate"], lw["wo"],
                            n_heads=n_heads, n_kv=n_kv, gated=fault != "no_gqa_gate",
                            lossy=lossy)
                x = r(x + r(a))
                n_gqa += 1
            else:
                lw = _planes(t, delta_names, n_delta)
                lw.update({k: t[k][n_delta] for k in (
                    "delta_taps", "delta_f2", "delta_g2", "delta_a_log", "delta_dt_bias",
                    "delta_b")})
                lw.update(rms=t["delta_rms"][n_delta], o_norm=t["delta_o_norm"][n_delta])
                x = _delta(cfg, x, lw, lossy, fault, states)
                n_delta += 1
            m = r(_rms_norm(x, t["rms_ffn"][layer], eps))
            bias = t["moe_bias"][layer]
            route, chosen = _route(m, t["moe_gate"][layer],
                                   jnp.zeros_like(bias) if fault == "no_select_bias" else bias,
                                   top_k=int(cfg["num_experts_per_tok"]),
                                   norm=bool(cfg["norm_topk_prob"]))
            route = route * float(cfg["routed_scaling_factor"])
            if routes is not None:
                routes.append(np.asarray(chosen))
            sw = _planes(t, ("shared_w1", "shared_w2", "shared_w3"), layer)
            ffn, shared = [], []
            for r0 in range(0, t_pad, ROW_BLOCK):
                rows = slice(r0, min(r0 + ROW_BLOCK, t_pad))
                f = jnp.zeros_like(m[:, rows])
                for e0 in range(0, n_held, EXPERT_BLOCK):
                    blk = slice(e0, min(e0 + EXPERT_BLOCK, n_held))
                    f = f + _expert_block(
                        m[:, rows], route[:, rows, first + blk.start: first + blk.stop],
                        *(a_ for name in ("w1", "w2", "w3")
                          for a_ in (t[name].packed[layer, blk], t[name].scale_bits[layer, blk])),
                        lossy=lossy)
                ffn.append(f)
                shared.append(_gated_ffn(m[:, rows], sw["shared_w1"], sw["shared_w2"],
                                         sw["shared_w3"], lossy=lossy))
            ffn = jnp.concatenate(ffn, axis=1)
            routed_sum = routed_sum + ffn
            x = r(x + r(ffn + jnp.concatenate(shared, axis=1)))
        out.append((routed_sum if routed_only else x)[0, : tokens.shape[1]])
    return jnp.stack(out)


def reference_states(cfg: dict, t: dict, tokens, fault: str | None = None) -> np.ndarray:
    """Every delta-rule layer's matrix state after the last of ``tokens`` (one
    sequence, whole blocks of queries), float32 ``[delta layers, H, d, d]``:
    the final carry of the reference's own ``lax.scan``."""
    states: list = []
    with jax.default_matmul_precision("highest"):
        reference_forward(cfg, t, np.asarray(tokens, np.int32)[None], fault=fault, states=states)
    return np.stack([np.asarray(s) for s in states])


def state_rel_errs(got: np.ndarray, want: np.ndarray) -> list[float]:
    """A layer each: ``|got - want| / |want|`` over the layer's matrices
    together (Frobenius); ``[layers, ...]`` both."""
    diff = (got.astype(np.float64) - want).reshape(len(want), -1)
    return [float(np.linalg.norm(d) / max(np.linalg.norm(w), 1e-30))
            for d, w in zip(diff, want.reshape(len(want), -1).astype(np.float64))]


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
        y = _rounder(lossy)(_rms_norm(x, t["rms_final"], float(cfg["rms_norm_eps"])))
        packed, scales = t["wcls"].packed, t["wcls"].scales
        outs = [np.asarray(_head_chunk(y, packed[:, lo:lo + chunk], scales[:, lo:lo + chunk]))
                for lo in range(0, packed.shape[-1], chunk)]
    return np.concatenate(outs, axis=-1)[..., : cfg["vocab_size"]]
