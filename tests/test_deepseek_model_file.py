"""The ``.m`` path of the latent-attention block: a checkpoint's state dict
through ``converter/convert-hf.py`` (``model_type: deepseek_v3``), the header's
new keys, ``models/loader.py``, and the engine, against the benchmark family's
plain reference on the same tensors. A file without the new keys reads, and
is written, as before."""

import importlib.util
import io
import json
import os

import jax.numpy as jnp
import numpy as np

from distributed_llama_multiusers_tpu.formats.model_file import (
    KEY_KV_LORA_RANK,
    ModelHeader,
    load_model_header,
    model_tensor_specs,
    write_model_header,
)
from distributed_llama_multiusers_tpu.formats.synthetic import tiny_header
from distributed_llama_multiusers_tpu.models.loader import (
    load_params_from_m,
    load_params_from_m_quantized,
)
from distributed_llama_multiusers_tpu.quants.codec import FloatType
from distributed_llama_multiusers_tpu.quants.packed import PackedQ40, Q40Experts, pack_q40_host
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

import latent_toy

CFG, FAMILY, CORRECT = latent_toy.load()
ROOT = latent_toy.ROOT


def _converter():
    spec = importlib.util.spec_from_file_location(
        "convert_hf", os.path.join(ROOT, "converter", "convert-hf.py"))
    mod = importlib.util.module_from_spec(spec)
    import sys
    sys.path.insert(0, os.path.join(ROOT, "converter"))
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(os.path.join(ROOT, "converter"))
    return mod


def _state_dict(cfg, seed=0):
    """A checkpoint's tensors under their published names, ``[d_out, d_in]``."""
    rng = np.random.default_rng(seed)
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    kv = cfg["qk_nope_head_dim"] + cfg["v_head_dim"]
    mh, E = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    sh = cfg["n_shared_experts"] * mh

    def w(d_out, d_in, gain=1.0):
        return (gain * d_in ** -0.5 * rng.normal(size=(d_out, d_in))).astype(np.float32)

    def norm(n):
        return (1.0 + 0.1 * rng.normal(size=n)).astype(np.float32)

    sd = {"model.embed_tokens.weight": rng.normal(size=(cfg["vocab_size"], d)).astype(np.float32),
          "model.norm.weight": norm(d), "lm_head.weight": w(cfg["vocab_size"], d)}
    for l in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{l}"
        sd[f"{p}.self_attn.q_proj.weight"] = w(H * qk, d, 2.0)
        sd[f"{p}.self_attn.kv_a_proj_with_mqa.weight"] = w(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], d, 2.0)
        sd[f"{p}.self_attn.kv_a_layernorm.weight"] = norm(cfg["kv_lora_rank"])
        sd[f"{p}.self_attn.kv_b_proj.weight"] = w(H * kv, cfg["kv_lora_rank"], 2.0)
        sd[f"{p}.self_attn.o_proj.weight"] = w(d, H * cfg["v_head_dim"], 0.15)
        sd[f"{p}.input_layernorm.weight"] = norm(d)
        sd[f"{p}.post_attention_layernorm.weight"] = norm(d)
        if l < cfg["first_k_dense_replace"]:
            for name, shape in (("gate_proj", (cfg["intermediate_size"], d)),
                                ("down_proj", (d, cfg["intermediate_size"])),
                                ("up_proj", (cfg["intermediate_size"], d))):
                sd[f"{p}.mlp.{name}.weight"] = w(*shape, 0.5)
            continue
        sd[f"{p}.mlp.gate.weight"] = w(E, d, 2.0)
        sd[f"{p}.mlp.gate.e_score_correction_bias"] = rng.uniform(-0.2, 0.2, size=E).astype(np.float32)
        for owner, width in [(f"experts.{e}", mh) for e in range(E)] + [("shared_experts", sh)]:
            sd[f"{p}.mlp.{owner}.gate_proj.weight"] = w(width, d)
            sd[f"{p}.mlp.{owner}.down_proj.weight"] = w(d, width, 0.3)
            sd[f"{p}.mlp.{owner}.up_proj.weight"] = w(width, d)
    return sd


class _Index(dict):
    get = dict.__getitem__


def _reference_tensors(cfg, sd):
    """The family's arrays from the same state dict, quantized by the same
    bit-exact Q40 encoder the writer uses."""
    L, Ld, E = cfg["num_hidden_layers"], cfg["first_k_dense_replace"], cfg["n_routed_experts"]

    def q(names):
        pk, sc = pack_q40_host(np.stack([sd[n] for n in names]))
        return PackedQ40(jnp.asarray(pk), jnp.asarray(sc))

    def layers(fmt, rng):
        return [fmt.format(l=l) for l in rng]

    t = {
        "wq": q(layers("model.layers.{l}.self_attn.q_proj.weight", range(L))),
        "wkva": q(layers("model.layers.{l}.self_attn.kv_a_proj_with_mqa.weight", range(L))),
        "wkvb": q(layers("model.layers.{l}.self_attn.kv_b_proj.weight", range(L))),
        "wo": q(layers("model.layers.{l}.self_attn.o_proj.weight", range(L))),
        "wcls": q(["lm_head.weight"]),
        "embedding": jnp.asarray(sd["model.embed_tokens.weight"]),
        "rms_final": jnp.asarray(sd["model.norm.weight"]),
        "rms_att": jnp.stack([sd[n] for n in layers("model.layers.{l}.input_layernorm.weight", range(L))]),
        "rms_kv": jnp.stack([sd[n] for n in layers("model.layers.{l}.self_attn.kv_a_layernorm.weight", range(L))]),
        "dense_rms_ffn": jnp.stack([sd[n] for n in layers("model.layers.{l}.post_attention_layernorm.weight", range(Ld))]),
        "rms_ffn": jnp.stack([sd[n] for n in layers("model.layers.{l}.post_attention_layernorm.weight", range(Ld, L))]),
        "moe_gate": jnp.stack([sd[f"model.layers.{l}.mlp.gate.weight"].T for l in range(Ld, L)]),
        "moe_bias": jnp.stack([sd[f"model.layers.{l}.mlp.gate.e_score_correction_bias"] for l in range(Ld, L)]),
    }
    t["wcls"] = PackedQ40(t["wcls"].packed[0], t["wcls"].scales[0])
    for key, hf in (("w1", "gate_proj"), ("w2", "down_proj"), ("w3", "up_proj")):
        t["dense_" + key] = q(layers("model.layers.{l}.mlp." + hf + ".weight", range(Ld)))
        t["shared_" + key] = q(layers("model.layers.{l}.mlp.shared_experts." + hf + ".weight", range(Ld, L)))
        pk, sc = pack_q40_host(np.stack([
            np.stack([sd[f"model.layers.{l}.mlp.experts.{e}.{hf}.weight"] for e in range(E)])
            for l in range(Ld, L)]))
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
    assert (header.kv_lora_rank, header.n_dense_layers, header.moe_select_bias) == (64, 1, 1)
    assert header.moe_routed_scale == 2.448 and header.norm_epsilon == 1e-6
    specs = model_tensor_specs(header)
    assert specs[-1].offset + specs[-1].n_bytes == header.file_size
    assert sum(s.name == "block_matmul_w1" and s.expert >= 0 for s in specs) == 2 * 8

    want_config = FAMILY.program_config(CFG)
    t = _reference_tensors(CFG, sd)
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
    assert isinstance(packed.routed.w1, Q40Experts) and packed.routed.w1.packed.shape[:2] == (2, 8)
    assert isinstance(packed.attn.wkva, PackedQ40) and packed.attn.wuk.dtype == jnp.float32
    assert packed.routed.gate.dtype == jnp.float32 and packed.routed.bias.dtype == jnp.float32


def test_a_header_of_todays_files_reads_and_writes_as_before(tmp_path):
    h = tiny_header()
    keys = [k for k, _ in h.to_kv_pairs()]
    assert max(keys) < KEY_KV_LORA_RANK and len(keys) == 19
    buf = io.BytesIO()
    write_model_header(buf, h)
    assert len(buf.getvalue()) == 8 + 8 * 19
    path = tmp_path / "h.m"
    path.write_bytes(buf.getvalue())
    back = load_model_header(str(path))
    assert back.kv_lora_rank == 0 and back.norm_epsilon == 1e-5 and back.moe_routed_scale == 1.0
    assert [s.name for s in model_tensor_specs(back)][:3] == ["embedding", "block_matmul_q", "block_matmul_k"]
    latent = ModelHeader(**{**h.__dict__, "kv_lora_rank": 64, "qk_nope_head_dim": 32,
                            "qk_rope_head_dim": 16, "v_head_dim": 32, "moe_routed_scale": 2.448,
                            "norm_epsilon": 1e-6})
    buf = io.BytesIO()
    write_model_header(buf, latent)
    path.write_bytes(buf.getvalue())
    back = load_model_header(str(path))
    assert (back.kv_lora_rank, back.moe_routed_scale, back.norm_epsilon) == (64, 2.448, 1e-6)
