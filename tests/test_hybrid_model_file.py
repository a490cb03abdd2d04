"""The ``.m`` path of a block whose layers differ in their mixer: a
checkpoint's state dict under its published names through
``converter/convert-hf.py`` (``model_type: lfm2_moe``), the header's new keys
(the layer-kind list, the conv's taps, the per-head norm), ``models/loader.py``
and the engine, against the benchmark family's plain reference on the same
tensors; the converter's permutation of the per-head norm gains; the synthetic
toy. A file without the new keys reads, and is written, as before."""

import io
import json

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats.model_file import (
    KEY_LAYER_KIND,
    LayerKind,
    load_model_header,
    model_tensor_specs,
    write_model_header,
)
from distributed_llama_multiusers_tpu.formats.synthetic import (
    tiny_header,
    tiny_pattern_header,
    write_synthetic_model,
)
from distributed_llama_multiusers_tpu.models.loader import (
    load_params_from_m,
    load_params_from_m_quantized,
)
from distributed_llama_multiusers_tpu.quants.codec import FloatType
from distributed_llama_multiusers_tpu.quants.packed import PackedQ40, Q40Experts, pack_q40_host
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

import latent_toy
from test_deepseek_model_file import _converter, _Index

CFG, FAMILY, CORRECT = latent_toy.load("tiny_lfm2.json")
KINDS = CFG["layer_types"]


def _state_dict(cfg, seed=0):
    """A checkpoint's tensors under their published names, ``[d_out, d_in]``."""
    rng = np.random.default_rng(seed)
    d, hd = cfg["hidden_size"], cfg["hidden_size"] // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    E, mh, K = cfg["num_experts"], cfg["moe_intermediate_size"], cfg["conv_L_cache"]

    def w(d_out, d_in, gain=1.0):
        return (gain * d_in ** -0.5 * rng.normal(size=(d_out, d_in))).astype(np.float32)

    def norm(n, mean=1.0):
        return (mean * (1.0 + 0.1 * rng.normal(size=n))).astype(np.float32)

    sd = {"model.embed_tokens.weight": rng.normal(size=(cfg["vocab_size"], d)).astype(np.float32),
          "model.embedding_norm.weight": norm(d), "lm_head.weight": w(cfg["vocab_size"], d)}
    for l, kind in enumerate(KINDS):
        p = f"model.layers.{l}"
        sd[f"{p}.operator_norm.weight"], sd[f"{p}.ffn_norm.weight"] = norm(d), norm(d)
        if kind == "conv":
            sd[f"{p}.conv.in_proj.weight"] = w(3 * d, d)
            sd[f"{p}.conv.conv.weight"] = (K ** -0.5 * rng.normal(size=(d, 1, K))).astype(np.float32)
            sd[f"{p}.conv.out_proj.weight"] = w(d, d, 0.3)
        else:
            sd[f"{p}.self_attn.q_proj.weight"], sd[f"{p}.self_attn.k_proj.weight"] = w(d, d), w(kv, d)
            sd[f"{p}.self_attn.v_proj.weight"] = w(kv, d)
            sd[f"{p}.self_attn.out_proj.weight"] = w(d, d, 0.3)
            sd[f"{p}.self_attn.q_layernorm.weight"] = norm(hd, 2.0)
            sd[f"{p}.self_attn.k_layernorm.weight"] = norm(hd, 2.0)
        ffn = f"{p}.feed_forward"
        if l < cfg["num_dense_layers"]:
            sd[f"{ffn}.w1.weight"], sd[f"{ffn}.w3.weight"] = w(cfg["intermediate_size"], d), w(cfg["intermediate_size"], d)
            sd[f"{ffn}.w2.weight"] = w(d, cfg["intermediate_size"], 0.3)
            continue
        sd[f"{ffn}.gate.weight"] = w(E, d, 2.0)
        sd[f"{ffn}.expert_bias"] = rng.uniform(-0.2, 0.2, size=E).astype(np.float32)
        for e in range(E):
            sd[f"{ffn}.experts.{e}.w1.weight"], sd[f"{ffn}.experts.{e}.w3.weight"] = w(mh, d), w(mh, d)
            sd[f"{ffn}.experts.{e}.w2.weight"] = w(d, mh, 0.3)
    return sd


def _reference_tensors(cfg, sd, permute):
    """The family's arrays from the same state dict, each kind's stacked by
    the count of that kind, quantized by the same bit-exact Q40 encoder the
    writer uses; q and k rows and their norm gains in the file's pair layout."""
    n_heads, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Ld, E = cfg["num_dense_layers"], cfg["num_experts"]
    conv = [l for l, k in enumerate(KINDS) if k == "conv"]
    attn = [l for l, k in enumerate(KINDS) if k != "conv"]
    routed = range(Ld, len(KINDS))

    def q(mats):
        pk, sc = pack_q40_host(np.stack(mats))
        return PackedQ40(jnp.asarray(pk), jnp.asarray(sc))

    def get(fmt, layers):
        return [sd[fmt.format(l=l)] for l in layers]

    def gains(name):
        return jnp.stack([permute(g.reshape(-1, 1), 1).reshape(-1)
                          for g in get("model.layers.{l}.self_attn." + name + ".weight", attn)])

    t = {
        "wq": q([permute(m, n_heads) for m in get("model.layers.{l}.self_attn.q_proj.weight", attn)]),
        "wk": q([permute(m, n_kv) for m in get("model.layers.{l}.self_attn.k_proj.weight", attn)]),
        "wv": q(get("model.layers.{l}.self_attn.v_proj.weight", attn)),
        "wo": q(get("model.layers.{l}.self_attn.out_proj.weight", attn)),
        "q_norm": gains("q_layernorm"), "k_norm": gains("k_layernorm"),
        "conv_in": q(get("model.layers.{l}.conv.in_proj.weight", conv)),
        "conv_out": q(get("model.layers.{l}.conv.out_proj.weight", conv)),
        "conv_taps": jnp.stack([m[:, 0, :].T for m in get("model.layers.{l}.conv.conv.weight", conv)]),
        "attn_rms": jnp.stack(get("model.layers.{l}.operator_norm.weight", attn)),
        "conv_rms": jnp.stack(get("model.layers.{l}.operator_norm.weight", conv)),
        "dense_rms_ffn": jnp.stack(get("model.layers.{l}.ffn_norm.weight", range(Ld))),
        "rms_ffn": jnp.stack(get("model.layers.{l}.ffn_norm.weight", routed)),
        "moe_gate": jnp.stack([m.T for m in get("model.layers.{l}.feed_forward.gate.weight", routed)]),
        "moe_bias": jnp.stack(get("model.layers.{l}.feed_forward.expert_bias", routed)),
        "embedding": jnp.asarray(sd["model.embed_tokens.weight"]),
        "rms_final": jnp.asarray(sd["model.embedding_norm.weight"]),
    }
    head = q([sd["lm_head.weight"]])
    t["wcls"] = PackedQ40(head.packed[0], head.scales[0])
    for key in ("w1", "w2", "w3"):
        t["dense_" + key] = q(get("model.layers.{l}.feed_forward." + key + ".weight", range(Ld)))
        pk, sc = pack_q40_host(np.stack([
            np.stack([sd[f"model.layers.{l}.feed_forward.experts.{e}.{key}.weight"] for e in range(E)])
            for l in routed]))
        t[key] = Q40Experts.from_packed(PackedQ40(jnp.asarray(pk), jnp.asarray(sc)))
    return t


def test_state_dict_to_m_to_engine_equals_the_reference(tmp_path):
    conv = _converter()
    cfg = {k: v for k, v in CFG.items() if k not in ("serving", "correctness", "family", "source")}
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    sd = _state_dict(CFG)
    out = str(tmp_path / "toy.m")
    conv.convert(str(tmp_path), FloatType.Q40, out, index=_Index(sd))
    header = load_model_header(out)
    want_kinds = [LayerKind.CONV if k == "conv" else LayerKind.ATTENTION for k in KINDS]
    assert header.layer_kinds == want_kinds and (header.conv_kernel, header.qk_norm) == (3, 1)
    assert (header.n_dense_layers, header.n_experts, header.n_active_experts) == (2, 8, 2)
    assert (header.moe_hidden_dim, header.moe_select_bias, header.kv_lora_rank) == (64, 1, 0)
    assert header.moe_routed_scale == 1.0 and header.norm_epsilon == 1e-5
    specs = model_tensor_specs(header)
    assert specs[-1].offset + specs[-1].n_bytes == header.file_size
    names = [s.name for s in specs if s.layer == 2 and s.expert < 0][:6]  # the first attention layer
    assert names == ["block_matmul_q", "block_matmul_k", "block_matmul_v", "block_q_norm",
                     "block_k_norm", "block_matmul_wo"]
    assert sum(s.name == "block_matmul_conv_in" for s in specs) == 6

    want_config = FAMILY.program_config(CFG)
    t = _reference_tensors(CFG, sd, conv.permute_rotary)
    prompts, forced = CORRECT.sample_sequences(CFG, 3)
    prefixes = [CORRECT.prefix_lengths(CFG, len(p)) for p in prompts]
    want = CORRECT.plain_logits(FAMILY, CFG, t, prompts, forced, prefixes)
    for load in (load_params_from_m_quantized, load_params_from_m):
        config, params = load(out, header, dtype=jnp.float32)
        assert config == want_config
        engine = InferenceEngine(config, params, n_lanes=8, cache_dtype=jnp.float32)
        got = CORRECT.engine_logits(engine, prompts, forced, prefixes)
        assert CORRECT.relative_errors(got, want).max() < 1e-5
    assert isinstance(params.routed.w1, jnp.ndarray)  # the dense load dequantizes
    _, packed = load_params_from_m_quantized(out, header, dtype=jnp.float32)
    assert isinstance(packed.routed.w1, Q40Experts) and packed.routed.w1.packed.shape[:2] == (6, 8)
    assert isinstance(packed.conv.w_in, PackedQ40) and packed.conv.w_in.packed.shape == (6, 64, 384)
    assert packed.conv.taps.dtype == jnp.float32 and packed.conv.taps.shape == (6, 3, 128)
    assert packed.attn.q_norm.dtype == jnp.float32 and packed.attn.q_norm.shape == (2, 32)


def test_the_norm_gains_follow_their_rows_through_the_permutation():
    """The published form norms a head, gains it per dimension, and rotates
    the half-split pairs (i, i + d/2); the file's form rotates adjacent
    pairs. With rows AND gains permuted alike the scores are the same."""
    conv = _converter()
    rng = np.random.default_rng(1)
    hd, pos = 16, 5
    q, k = rng.normal(size=hd), rng.normal(size=hd)
    gq, gk = 2.0 + 0.3 * rng.normal(size=hd), 2.0 + 0.3 * rng.normal(size=hd)
    freq = 1.0 / 10000.0 ** (2.0 * np.arange(hd // 2) / hd)

    def normed(x, g):
        return x / np.sqrt(np.mean(x * x) + 1e-5) * g

    def rot_half(x, p):  # the published rotation
        c, s = np.cos(p * freq), np.sin(p * freq)
        a, b = x[: hd // 2], x[hd // 2:]
        return np.concatenate([a * c - b * s, a * s + b * c])

    def rot_pairs(x, p):  # the .m convention
        c, s = np.cos(p * freq), np.sin(p * freq)
        a, b = x[0::2], x[1::2]
        return np.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(-1)

    perm = lambda v: conv.permute_rotary(v.reshape(-1, 1), 1).reshape(-1)
    want = rot_half(normed(q, gq), pos) @ rot_half(normed(k, gk), 2)
    got = rot_pairs(normed(perm(q), perm(gq)), pos) @ rot_pairs(normed(perm(k), perm(gk)), 2)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    unpermuted = rot_pairs(normed(perm(q), gq), pos) @ rot_pairs(normed(perm(k), gk), 2)
    assert abs(unpermuted - want) > 1e-3  # gains left in place are another model


def test_the_synthetic_toy_and_a_header_without_the_keys(tmp_path):
    h = tiny_pattern_header()
    keys = [k for k, _ in h.to_kv_pairs()]
    assert keys.count(KEY_LAYER_KIND) == 8
    path = str(tmp_path / "toy.m")
    write_synthetic_model(path, h, seed=1)
    back = load_model_header(path)
    assert back.layer_kinds == h.layer_kinds and back.n_dense_layers == 2 and back.conv_kernel == 3
    config, params = load_params_from_m(path, back, dtype=jnp.float32)
    assert config.recurrent_state and (config.n_conv_layers, config.n_attention_layers) == (6, 2)
    engine = InferenceEngine(config, params, n_lanes=2)
    _, greedy, pos = engine.prefill(0, [1, 2, 3, 4, 5])
    assert pos == 5 and 0 <= greedy < config.vocab_size
    plain = tiny_header()
    assert KEY_LAYER_KIND not in [k for k, _ in plain.to_kv_pairs()] and len(plain.to_kv_pairs()) == 19
    buf = io.BytesIO()
    write_model_header(buf, plain)
    assert len(buf.getvalue()) == 8 + 8 * 19
    short = tiny_pattern_header()
    short.layer_kinds = short.layer_kinds[:-1]
    with open(tmp_path / "bad.m", "wb") as f:
        write_model_header(f, short)
    with pytest.raises(ValueError, match="layer kinds"):  # a list shorter than the layers
        load_model_header(str(tmp_path / "bad.m"))


# -- selective state-space layers (``model_type: jamba``) ---------------------

JCFG, JFAMILY, JCORRECT = latent_toy.load("tiny_jamba.json")


def _jamba_state_dict(cfg, seed=0):
    """A jamba checkpoint's tensors under their published names."""
    rng = np.random.default_rng(seed)
    d, hidden = cfg["hidden_size"], cfg["intermediate_size"]
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    E, N = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
    R, K = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]

    def w(d_out, d_in, gain=1.0):
        return (gain * d_in ** -0.5 * rng.normal(size=(d_out, d_in))).astype(np.float32)

    def norm(n, mean=1.0):
        return (mean * (1.0 + 0.1 * rng.normal(size=n))).astype(np.float32)

    sd = {"model.embed_tokens.weight": rng.normal(size=(cfg["vocab_size"], d)).astype(np.float32),
          "model.final_layernorm.weight": norm(d)}  # no lm_head: the family ties it
    for l in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{l}"
        sd[f"{p}.input_layernorm.weight"], sd[f"{p}.pre_ff_layernorm.weight"] = norm(d), norm(d)
        if l % cfg["attn_layer_period"] == cfg["attn_layer_offset"]:
            a = f"{p}.self_attn"
            sd[f"{a}.q_proj.weight"], sd[f"{a}.k_proj.weight"] = w(d, d, 2.0), w(kv, d, 2.0)
            sd[f"{a}.v_proj.weight"], sd[f"{a}.o_proj.weight"] = w(kv, d), w(d, d, 0.3)
        else:
            m = f"{p}.mamba"
            sd[f"{m}.in_proj.weight"], sd[f"{m}.x_proj.weight"] = w(2 * E, d), w(R + 2 * N, E)
            sd[f"{m}.out_proj.weight"] = w(d, E, 0.6)
            sd[f"{m}.conv1d.weight"] = (K ** -0.5 * rng.normal(size=(E, 1, K))).astype(np.float32)
            sd[f"{m}.conv1d.bias"] = rng.uniform(-0.5, 0.5, size=E).astype(np.float32)
            sd[f"{m}.dt_proj.weight"] = rng.uniform(-R ** -0.5, R ** -0.5, size=(E, R)).astype(np.float32)
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=E))
            sd[f"{m}.dt_proj.bias"] = (dt + np.log(-np.expm1(-dt))).astype(np.float32)
            sd[f"{m}.A_log"] = np.log(np.broadcast_to(np.arange(1, N + 1, dtype=np.float32), (E, N))).copy()
            sd[f"{m}.D"] = norm(E)
            sd[f"{m}.dt_layernorm.weight"] = norm(R)
            sd[f"{m}.b_layernorm.weight"], sd[f"{m}.c_layernorm.weight"] = norm(N, 2.0), norm(N, 2.0)
        f = f"{p}.feed_forward"
        sd[f"{f}.gate_proj.weight"], sd[f"{f}.up_proj.weight"] = w(hidden, d), w(hidden, d)
        sd[f"{f}.down_proj.weight"] = w(d, hidden, 0.2)
    return sd


def _jamba_reference_tensors(cfg, sd):
    """The family's arrays from the same state dict, each kind's stacked by
    the count of that kind, quantized by the writer's bit-exact Q40 encoder."""
    L = cfg["num_hidden_layers"]
    attn = [l for l in range(L) if l % cfg["attn_layer_period"] == cfg["attn_layer_offset"]]
    ssm = [l for l in range(L) if l not in attn]

    def q(mats):
        pk, sc = pack_q40_host(np.stack(mats))
        return PackedQ40(jnp.asarray(pk), jnp.asarray(sc))

    def get(fmt, layers):
        return [sd[fmt.format(l=l)] for l in layers]

    m, a, f = "model.layers.{l}.mamba.", "model.layers.{l}.self_attn.", "model.layers.{l}.feed_forward."
    t = {
        "wq": q(get(a + "q_proj.weight", attn)), "wk": q(get(a + "k_proj.weight", attn)),
        "wv": q(get(a + "v_proj.weight", attn)), "wo": q(get(a + "o_proj.weight", attn)),
        "ssm_in": q(get(m + "in_proj.weight", ssm)), "ssm_x": q(get(m + "x_proj.weight", ssm)),
        "ssm_out": q(get(m + "out_proj.weight", ssm)),
        "ssm_taps": jnp.stack([x[:, 0, :].T for x in get(m + "conv1d.weight", ssm)]),
        "ssm_conv_bias": jnp.stack(get(m + "conv1d.bias", ssm)),
        "ssm_dt_proj": jnp.stack([x.T for x in get(m + "dt_proj.weight", ssm)]),
        "ssm_dt_bias": jnp.stack(get(m + "dt_proj.bias", ssm)),
        "ssm_a_log": jnp.stack([x.T for x in get(m + "A_log", ssm)]),
        "ssm_d": jnp.stack(get(m + "D", ssm)),
        "ssm_dt_norm": jnp.stack(get(m + "dt_layernorm.weight", ssm)),
        "ssm_b_norm": jnp.stack(get(m + "b_layernorm.weight", ssm)),
        "ssm_c_norm": jnp.stack(get(m + "c_layernorm.weight", ssm)),
        "attn_rms": jnp.stack(get("model.layers.{l}.input_layernorm.weight", attn)),
        "ssm_rms": jnp.stack(get("model.layers.{l}.input_layernorm.weight", ssm)),
        "dense_rms_ffn": jnp.stack(get("model.layers.{l}.pre_ff_layernorm.weight", range(L))),
        "dense_w1": q(get(f + "gate_proj.weight", range(L))),
        "dense_w2": q(get(f + "down_proj.weight", range(L))),
        "dense_w3": q(get(f + "up_proj.weight", range(L))),
        "embedding": jnp.asarray(sd["model.embed_tokens.weight"]),
        "rms_final": jnp.asarray(sd["model.final_layernorm.weight"]),
    }
    head = q([sd["model.embed_tokens.weight"]])  # tied
    t["wcls"] = PackedQ40(head.packed[0], head.scales[0])
    return t


def test_jamba_state_dict_to_m_to_engine_equals_the_reference(tmp_path):
    from distributed_llama_multiusers_tpu.formats.model_file import KEY_SSM_D_INNER, RopeType

    conv = _converter()
    cfg = {k: v for k, v in JCFG.items() if k not in ("serving", "correctness", "family", "source")}
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    sd = _jamba_state_dict(JCFG)
    out = str(tmp_path / "toy.m")
    conv.convert(str(tmp_path), FloatType.Q40, out, index=_Index(sd))
    header = load_model_header(out)
    assert header.layer_kinds == [2, 2, 0, 2, 2, 2, 0, 2] and header.rope_type == RopeType.NONE
    assert (header.ssm_d_inner, header.ssm_d_state, header.ssm_dt_rank) == (256, 8, 16)
    assert (header.ssm_conv_kernel, header.ssm_conv_bias, header.ssm_inner_norms) == (4, 1, 1)
    assert (header.n_experts, header.conv_kernel, header.qk_norm, header.norm_epsilon) == (0, 0, 0, 1e-6)
    specs = model_tensor_specs(header)
    assert specs[-1].offset + specs[-1].n_bytes == header.file_size
    assert [s.name for s in specs if s.layer == 1][:14] == [
        "block_matmul_ssm_in", "block_ssm_conv_taps", "block_ssm_conv_bias", "block_matmul_ssm_x",
        "block_ssm_dt_norm", "block_ssm_b_norm", "block_ssm_c_norm", "block_ssm_dt_proj",
        "block_ssm_dt_bias", "block_ssm_a_log", "block_ssm_d", "block_matmul_ssm_out",
        "block_matmul_w1", "block_matmul_w2"]
    steer = {s.name: s.float_type for s in specs if s.layer == 1 and "matmul" not in s.name}
    assert set(steer.values()) == {FloatType.F32}  # what steers the exponential is float32

    want_config = JFAMILY.program_config(JCFG)
    t = _jamba_reference_tensors(JCFG, sd)
    prompts, forced = JCORRECT.sample_sequences(JCFG, 3)
    prefixes = [JCORRECT.prefix_lengths(JCFG, len(p)) for p in prompts]
    want = JCORRECT.plain_logits(JFAMILY, JCFG, t, prompts, forced, prefixes)
    for load in (load_params_from_m_quantized, load_params_from_m):
        config, params = load(out, header, dtype=jnp.float32)
        assert config == want_config
        engine = InferenceEngine(config, params, n_lanes=8, cache_dtype=jnp.float32)
        got = JCORRECT.engine_logits(engine, prompts, forced, prefixes)
        assert JCORRECT.relative_errors(got, want).max() < 1e-5
    _, packed = load_params_from_m_quantized(out, header, dtype=jnp.bfloat16)
    assert isinstance(packed.ssm.w_in, PackedQ40) and packed.ssm.w_in.packed.shape == (6, 64, 512)
    assert isinstance(packed.ssm.w_x, PackedQ40) and isinstance(packed.dense.w1, PackedQ40)
    for leaf in (packed.ssm.taps, packed.ssm.w_dt, packed.ssm.dt_bias, packed.ssm.a_log,
                 packed.ssm.d, packed.ssm.b_norm, packed.ssm.conv_bias):
        assert leaf.dtype == jnp.float32  # whatever the activations are
    assert packed.ssm.a_log.shape == (6, 8, 256) and packed.ssm.w_dt.shape == (6, 16, 256)
    assert packed.rope_cos is None and packed.conv is None and packed.routed is None
    # a file without a state-space layer carries none of the keys
    assert KEY_SSM_D_INNER not in [k for k, _ in tiny_pattern_header().to_kv_pairs()]


@pytest.mark.parametrize("wrong,match", [
    (dict(num_experts=16), "num_experts = 16"), (dict(mamba_proj_bias=True), "mamba_proj_bias"),
    (dict(sliding_window=4096), "sliding_window"),
])
def test_what_the_jamba_converter_does_not_convert_is_refused_by_name(tmp_path, wrong, match):
    conv = _converter()
    cfg = {k: v for k, v in JCFG.items() if k not in ("serving", "correctness", "family", "source")}
    (tmp_path / "config.json").write_text(json.dumps(dict(cfg, **wrong)))
    with pytest.raises(ValueError, match=match):
        conv.load_config(str(tmp_path), FloatType.Q40)


def test_the_synthetic_ssm_toy_round_trips_and_a_short_header_is_refused(tmp_path):
    from distributed_llama_multiusers_tpu.formats.synthetic import tiny_ssm_header

    h = tiny_ssm_header("MMAM")
    path = str(tmp_path / "toy.m")
    write_synthetic_model(path, h, seed=1)
    back = load_model_header(path)
    assert back.layer_kinds == [2, 2, 0, 2] and back.ssm_d_inner == 128 and back.rope_type == 4
    config, params = load_params_from_m(path, back, dtype=jnp.float32)
    assert (config.n_ssm_layers, config.n_attention_layers, config.n_conv_layers) == (3, 1, 0)
    assert config.recurrent_state and params.rope_cos is None
    # the steering tensors carry the mixer's own initialisation
    np.testing.assert_allclose(np.asarray(params.ssm.a_log[0, :, 0]), np.log(np.arange(1, 9)), rtol=1e-6)
    dt = np.log1p(np.exp(np.asarray(params.ssm.dt_bias)))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01 and (np.asarray(params.ssm.d) == 1).all()
    engine = InferenceEngine(config, params, n_lanes=2)
    _, greedy, pos = engine.prefill(0, [1, 2, 3, 4, 5])
    assert pos == 5 and 0 <= greedy < config.vocab_size
    h.ssm_dt_rank = 0
    with open(tmp_path / "bad.m", "wb") as f:
        write_model_header(f, h)
    with pytest.raises(ValueError, match="state-space layer needs"):
        load_model_header(str(tmp_path / "bad.m"))
