"""The ``.m`` path of what ``model_type: mimo_v2_flash`` adds: a checkpoint's
state dict under its published names through ``converter/convert-hf.py`` (the
two kinds' projections, ``attention_sink_bias``, the router's
``e_score_correction_bias``; the rotated 64 numbers of every q and k head
permuted from the half-rotation pairing to adjacent pairs, the other rows
left), the header's new keys, ``models/loader.py`` and the engine, against
the benchmark family's plain reference on the same tensors; what the
converter does not convert, refused by name; a held share."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats.model_file import LayerKind, load_model_header
from distributed_llama_multiusers_tpu.models.loader import load_params_from_m_quantized
from distributed_llama_multiusers_tpu.quants.codec import FloatType
from distributed_llama_multiusers_tpu.quants.packed import PackedQ40, Q40Experts, pack_q40_host
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

import latent_toy
from test_deepseek_model_file import _converter, _Index

CFG, FAMILY, CORRECT = latent_toy.load("tiny_mimo_v2_flash.json")
PUBLISHED = {k: v for k, v in CFG.items()
             if k not in ("serving", "correctness", "family", "source", "deployment")}
PUBLISHED["n_routed_experts"] = 16  # the checkpoint has every expert; a file may hold a share
BUCKETS = tuple(CFG["serving"]["prefill_buckets"])
ROT = 8  # int(24 * 0.334)


def _half_rotation(w, n_heads):
    """Rows in adjacent-pair order -> the published half-rotation order of the
    first ROT rows of every head (the inverse of what the converter does)."""
    d_out, d_in = w.shape
    heads = w.reshape(n_heads, d_out // n_heads, d_in).copy()
    first = heads[:, :ROT].reshape(n_heads, ROT // 2, 2, d_in).swapaxes(1, 2)
    heads[:, :ROT] = first.reshape(n_heads, ROT, d_in)
    return heads.reshape(d_out, d_in)


def _state_dict(cfg, seed=0):
    """A mimo_v2_flash checkpoint's tensors under the names the converter
    reads, and the q / k rows in the runtime's own (adjacent-pair) order."""
    rng = np.random.default_rng(seed)
    d, hd, vd, heads = cfg["hidden_size"], cfg["head_dim"], cfg["v_head_dim"], cfg["num_attention_heads"]

    def w(d_out, d_in, gain=1.0):
        return (gain * d_in ** -0.5 * rng.normal(size=(d_out, d_in))).astype(np.float32)

    def norm(n):
        return (1.0 + 0.1 * rng.normal(size=n)).astype(np.float32)

    sd = {"model.embed_tokens.weight": rng.normal(size=(cfg["vocab_size"], d)).astype(np.float32),
          "model.norm.weight": norm(d), "lm_head.weight": w(cfg["vocab_size"], d, 1.78)}
    ours = {}
    for l, kind in enumerate(cfg["hybrid_layer_pattern"]):
        p, a, m = (f"model.layers.{l}", f"model.layers.{l}.self_attn", f"model.layers.{l}.mlp")
        n_kv = cfg["swa_num_key_value_heads" if kind else "num_key_value_heads"]
        ours[l, "q"], ours[l, "k"] = w(heads * hd, d, 2.0), w(n_kv * hd, d, 2.0)
        sd[f"{a}.q_proj.weight"] = _half_rotation(ours[l, "q"], heads)
        sd[f"{a}.k_proj.weight"] = _half_rotation(ours[l, "k"], n_kv)
        sd[f"{a}.v_proj.weight"], sd[f"{a}.o_proj.weight"] = w(n_kv * vd, d), w(d, heads * vd, 0.4)
        if kind:
            sd[f"{a}.attention_sink_bias"] = rng.uniform(3.5, 5.7, size=heads).astype(np.float32)
        sd[f"{p}.input_layernorm.weight"] = norm(d)
        sd[f"{p}.post_attention_layernorm.weight"] = norm(d)
        if not cfg["moe_layer_freq"][l]:
            inter = cfg["intermediate_size"]
            sd[f"{m}.gate_proj.weight"], sd[f"{m}.up_proj.weight"] = w(inter, d), w(inter, d)
            sd[f"{m}.down_proj.weight"] = w(d, inter, 0.5)
            continue
        inter = cfg["moe_intermediate_size"]
        sd[f"{m}.gate.weight"] = w(16, d)
        sd[f"{m}.gate.e_score_correction_bias"] = rng.uniform(-0.03, 0.03, size=16).astype(np.float32)
        for e in range(16):
            sd[f"{m}.experts.{e}.gate_proj.weight"] = w(inter, d)
            sd[f"{m}.experts.{e}.up_proj.weight"] = w(inter, d)
            sd[f"{m}.experts.{e}.down_proj.weight"] = w(d, inter, 1.2)
    return sd, ours


def _reference_tensors(cfg, sd, ours, held):
    """The family's arrays from the same state dict, quantized by the same
    bit-exact Q40 encoder the writer uses, a stack a kind."""
    kinds = cfg["hybrid_layer_pattern"]
    layers = range(len(kinds))
    routed = [l for l in layers if cfg["moe_layer_freq"][l]]
    dense = [l for l in layers if not cfg["moe_layer_freq"][l]]

    def q(mats):
        pk, sc = pack_q40_host(np.stack(mats))
        return PackedQ40(jnp.asarray(pk), jnp.asarray(sc))

    a, m = "model.layers.{}.self_attn.", "model.layers.{}.mlp."
    of = lambda ls, fmt: [sd[fmt.format(l)] for l in ls]  # noqa: E731
    full, window = [l for l in layers if not kinds[l]], [l for l in layers if kinds[l]]
    t = {
        "wq": q([ours[l, "q"] for l in layers]), "wo": q(of(layers, a + "o_proj.weight")),
        "wk": q([ours[l, "k"] for l in full]), "wv": q(of(full, a + "v_proj.weight")),
        "wk_w": q([ours[l, "k"] for l in window]), "wv_w": q(of(window, a + "v_proj.weight")),
        "attn_sink": jnp.stack(of(window, a + "attention_sink_bias")),
        "dense_w1": q(of(dense, m + "gate_proj.weight")), "dense_w2": q(of(dense, m + "down_proj.weight")),
        "dense_w3": q(of(dense, m + "up_proj.weight")),
        "moe_gate": jnp.stack([x.T for x in of(routed, m + "gate.weight")]),
        "moe_bias": jnp.stack(of(routed, m + "gate.e_score_correction_bias")),
        "attn_rms": jnp.stack(of(layers, "model.layers.{}.input_layernorm.weight")),
        "rms_ffn": jnp.stack(of(routed, "model.layers.{}.post_attention_layernorm.weight")),
        "dense_rms_ffn": jnp.stack(of(dense, "model.layers.{}.post_attention_layernorm.weight")),
        "embedding": jnp.asarray(sd["model.embed_tokens.weight"]),
        "rms_final": jnp.asarray(sd["model.norm.weight"]),
    }
    head = q([sd["lm_head.weight"]])
    t["wcls"] = PackedQ40(head.packed[0], head.scales[0])
    for key, name in (("w1", "gate_proj"), ("w2", "down_proj"), ("w3", "up_proj")):
        pk, sc = pack_q40_host(np.stack([
            np.stack([sd[f"model.layers.{l}.mlp.experts.{e}.{name}.weight"] for e in held])
            for l in routed]))
        t[key] = Q40Experts.from_packed(PackedQ40(jnp.asarray(pk), jnp.asarray(sc)))
    return t


def test_state_dict_to_m_to_engine_equals_the_reference(tmp_path):
    conv = _converter()
    (tmp_path / "config.json").write_text(json.dumps(PUBLISHED))
    sd, ours = _state_dict(CFG)
    out = str(tmp_path / "toy.m")
    conv.convert(str(tmp_path), FloatType.Q40, out, index=_Index(sd), experts_held=(0, 4))
    header = load_model_header(out)
    assert header.layer_kinds == [LayerKind.WINDOW if k else LayerKind.ATTENTION
                                  for k in CFG["hybrid_layer_pattern"]]
    assert (header.head_dim, header.v_head_dim, header.rotary_dim) == (24, 16, ROT)
    assert (header.n_kv_heads, header.window_n_kv_heads, header.sliding_window) == (2, 4, 8)
    assert (header.rope_theta, header.window_rope_theta) == (5e6, 1e4)
    assert (header.attn_value_scale, header.window_sink, header.n_dense_layers) == (0.707, 1, 1)
    assert (header.n_experts, header.n_active_experts, header.moe_hidden_dim) == (16, 4, 32)
    assert (header.moe_select_bias, header.moe_norm_topk, header.moe_norm_floor) == (1, 1, 0.0)
    assert (header.experts_held_first, header.experts_held_count) == (0, 4)
    config, params = load_params_from_m_quantized(out, header, dtype=jnp.float32)
    engine = InferenceEngine(config, params, n_lanes=CFG["serving"]["lanes"],
                             prefill_buckets=BUCKETS, cache_dtype=jnp.float32)
    t = _reference_tensors(CFG, sd, ours, range(4))
    r = CORRECT.compare(FAMILY, CFG, t, engine, 3)
    assert r["ok"], r
    assert r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5


def test_only_the_rows_that_rotate_are_permuted():
    conv = _converter()
    w = np.arange(2 * 24 * 3, dtype=np.float32).reshape(48, 3)
    got = conv.permute_rotary_first(w, 2, ROT)
    for h in range(2):
        head, out = w[h * 24:(h + 1) * 24], got[h * 24:(h + 1) * 24]
        np.testing.assert_array_equal(out[ROT:], head[ROT:])  # the rows that do not rotate
        for p in range(ROT // 2):  # published pair (p, p + ROT / 2) -> adjacent rows
            np.testing.assert_array_equal(out[2 * p], head[p])
            np.testing.assert_array_equal(out[2 * p + 1], head[p + ROT // 2])
    np.testing.assert_array_equal(_half_rotation(got, 2), w)
    # the whole head: the other families' permutation
    np.testing.assert_array_equal(conv.permute_rotary_first(w, 2, 24), conv.permute_rotary(w, 2))


@pytest.mark.parametrize("wrong,match", [
    (dict(add_full_attention_sink_bias=True), "add_full_attention_sink_bias"),
    (dict(swa_v_head_dim=24), "swa_v_head_dim"), (dict(n_shared_experts=1), "n_shared_experts"),
    (dict(routed_scaling_factor=2.5), "routed_scaling_factor"),
    (dict(moe_layer_freq=[0, 1, 0, 1, 1, 1, 1, 1]), "moe_layer_freq"),
    (dict(scoring_func="softmax"), "scoring_func")])
def test_what_the_converter_does_not_convert_is_refused_by_name(tmp_path, wrong, match):
    conv = _converter()
    (tmp_path / "config.json").write_text(json.dumps(dict(PUBLISHED, **wrong)))
    with pytest.raises(ValueError, match=match):
        conv.load_config(str(tmp_path), FloatType.Q40)
