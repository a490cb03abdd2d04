"""A second family, as test data: the Mixtral-style sparse mixture the program
already runs (`models/llama.py` `_moe_ffn`), at a toy size on the CPU.

It is here to show what a `model_config` PR adds for an architecture the
harness never heard of, and is the file such a PR starts from: one module that
exports the five functions `harness/cells.py` `load_family` asks for, a
configuration file that names it (``"family": "tiny_moe"``), and entries. No
file that is there changes (`tests/test_fourth_cell.py` proves it). It has no
entry in the root BENCHMARK.json and no cell.

The block, from the published description (Mixtral of Experts, section 2.1;
`config.json` keys ``num_local_experts``, ``num_experts_per_tok``): attention as
in the Llama family; the FFN is

    p    = softmax(Wg n2)                      over all E experts
    S    = the k experts with the largest p
    x'   = h + sum_{e in S} p_e / (sum_{s in S} p_s) . W2_e (silu(W1_e n2) * (W3_e n2))

The reference below computes every expert on every token and weights them (zero
outside S): plain, and at a real size done a block of experts at a time.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from harness import cells
from harness.reference import _rms_norm, _rope, _rounder, dequant_q40, rope_tables
from harness.weights import GAIN, q40_plane, seed_key

_ATT = ("wq", "wk", "wv", "wo")
_FFN = ("w1", "w2", "w3")

# the per-lane state is the Llama family's contiguous K/V pair, all of it kept
# by position: rows [0, n) of two lanes that have absorbed the same n tokens
lane_state_rel_err = cells.load_family({}).lane_state_rel_err


def program_config(cfg: dict):
    """The program's LlamaConfig with its expert counts set."""
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    return LlamaConfig(
        dim=cfg["hidden_size"], hidden_dim=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], vocab_size=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"], rope_theta=float(cfg["rope_theta"]),
        norm_epsilon=float(cfg["rms_norm_eps"]),
        n_experts=cfg["num_local_experts"], n_active_experts=cfg["num_experts_per_tok"],
    )


def _generate(config, key, dtype):
    L, E, d, h = config.n_layers, config.n_experts, config.dim, config.hidden_dim
    kv = config.n_kv_heads * config.head_size
    shapes = {
        "wq": ((L,), d, d), "wk": ((L,), d, kv), "wv": ((L,), d, kv), "wo": ((L,), d, d),
        # the expert planes are stacked [L, E, ...], as the program's loader stacks them
        "w1": ((L, E), d, h), "w2": ((L, E), h, d), "w3": ((L, E), d, h),
        "wcls": ((), d, config.vocab_size),
    }
    keys = jax.random.split(key, len(shapes) + 5)
    # nibbles and scales as `harness/weights.py` argues them
    out = {name: q40_plane(*jax.random.split(k), lead, d_in, d_out, GAIN[name])
           for k, (name, (lead, d_in, d_out)) in zip(keys, shapes.items())}
    kg, ke, k1, k2, k3 = keys[len(shapes):]
    # router logits of spread 2: the k largest are seldom near a tie
    out["moe_gate"] = 2.0 * d ** -0.5 * jax.random.normal(kg, (L, d, E), jnp.float32)
    out["embedding"] = jax.random.normal(ke, (config.vocab_size, d), jnp.float32).astype(dtype)
    out["rms_att"] = 1.0 + 0.1 * jax.random.normal(k1, (L, d), jnp.float32)
    out["rms_ffn"] = 1.0 + 0.1 * jax.random.normal(k2, (L, d), jnp.float32)
    out["rms_final"] = 1.0 + 0.1 * jax.random.normal(k3, (d,), jnp.float32)
    return out


def device_weights(config, seed: int, dtype=jnp.bfloat16) -> dict:
    """name -> device array (or PackedQ40 of two), all from one program."""
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    if padded_d_out(config.vocab_size) != config.vocab_size:
        raise SystemExit("tiny_moe draws no pad columns: choose a vocabulary the loader leaves alone")
    t = jax.jit(lambda k: _generate(config, k, dtype))(seed_key(seed))
    jax.block_until_ready(t)
    return t


def assemble_params(config, t: dict):
    """The program's LlamaParams around the arrays; the RoPE tables are the
    program's own."""
    from distributed_llama_multiusers_tpu.models.llama import LlamaLayerParams, LlamaParams
    from distributed_llama_multiusers_tpu.models.loader import _rope_cache

    cos, sin = _rope_cache(config)
    layers = LlamaLayerParams(
        **{k: t[k] for k in _ATT + _FFN + ("rms_att", "rms_ffn", "moe_gate")})
    return LlamaParams(
        embedding=t["embedding"], layers=layers, rms_final=t["rms_final"], wcls=t["wcls"],
        rope_cos=jax.device_put(cos), rope_sin=jax.device_put(sin),
    )


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "top_k", "eps", "lossy"))
def _layer(x, lw, cos, sin, *, n_heads, n_kv, top_k, eps, lossy=None):
    """One block over whole sequences, float32; ``lossy`` as in
    `harness/reference.py` (the control only)."""
    r = _rounder(lossy)
    b, t, d = x.shape
    hd = d // n_heads
    w = {k: dequant_q40(*lw[k]) for k in _ATT}
    n1 = r(_rms_norm(x, lw["rms_att"], eps))
    q = _rope(r(n1 @ w["wq"]).reshape(b, t, n_heads, hd), cos, sin)
    k = _rope(r(n1 @ w["wk"]).reshape(b, t, n_kv, hd), cos, sin)
    v = r(n1 @ w["wv"]).reshape(b, t, n_kv, hd)
    q = q.reshape(b, t, n_kv, n_heads // n_kv, hd)
    scores = jnp.einsum("btkgh,bskh->bkgts", q, k) / np.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None, None], scores, -jnp.inf)
    att = jnp.einsum("bkgts,bskh->btkgh", jax.nn.softmax(scores, axis=-1), v)
    h = r(x + r(att.reshape(b, t, d)) @ w["wo"])
    n2 = r(_rms_norm(h, lw["rms_ffn"], eps))
    p = jax.nn.softmax(n2 @ lw["moe_gate"], axis=-1)          # [b, t, E]
    kth = jnp.sort(p, axis=-1)[..., -top_k, None]
    chosen = jnp.where(p >= kth, p, 0.0)
    route = chosen / chosen.sum(axis=-1, keepdims=True)
    w1, w2, w3 = (jax.vmap(dequant_q40)(*lw[k]) for k in _FFN)  # [E, d_in, d_out]
    act = r(jax.nn.silu(jnp.einsum("btd,edh->bteh", n2, w1)) * jnp.einsum("btd,edh->bteh", n2, w3))
    ffn = jnp.einsum("bte,bted->btd", route, jnp.einsum("bteh,ehd->bted", act, w2))
    return r(h + ffn)


def reference_logits(cfg: dict, t: dict, tokens, row_positions, lossy: str | None = None):
    """Float32 logits ``[B, R, vocab]`` at ``row_positions`` of each sequence,
    from the benchmark's own arrays; imports nothing of the program."""
    tokens = jnp.asarray(tokens, jnp.int32)
    row_positions = jnp.asarray(row_positions, jnp.int32)
    n_heads = cfg["num_attention_heads"]
    eps = float(cfg["rms_norm_eps"])
    cos, sin = rope_tables(tokens.shape[1], cfg["hidden_size"] // n_heads, cfg["rope_theta"])
    with jax.default_matmul_precision("highest"):
        x = t["embedding"][tokens].astype(jnp.float32)
        for layer in range(cfg["num_hidden_layers"]):
            lw = {k: (t[k].packed[layer], t[k].scales[layer]) for k in _ATT + _FFN}
            lw.update({k: t[k][layer] for k in ("rms_att", "rms_ffn", "moe_gate")})
            x = _layer(x, lw, jnp.asarray(cos), jnp.asarray(sin), n_heads=n_heads,
                       n_kv=cfg["num_key_value_heads"], top_k=int(cfg["num_experts_per_tok"]),
                       eps=eps, lossy=lossy)
        x = jnp.take_along_axis(x, row_positions[:, :, None], axis=1)
        y = _rounder(lossy)(_rms_norm(x, t["rms_final"], eps))
        return np.asarray(y @ dequant_q40(t["wcls"].packed, t["wcls"].scales))
