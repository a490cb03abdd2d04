"""The ``.m`` path of what ``deepseek_v32`` adds: the header's new keys (each
written only where it is set), a tiny synthetic checkpoint through
``formats/synthetic.py`` and ``models/loader.py`` into the engine, and a
checkpoint's state dict under its published names through
``converter/convert-hf.py``, whole and as one chip's share of the experts,
against the benchmark family's plain reference on the same tensors."""

import io
import json

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats import model_file as mf
from distributed_llama_multiusers_tpu.formats.synthetic import (
    tiny_header,
    tiny_sparse_latent_header,
    write_synthetic_model,
)
from distributed_llama_multiusers_tpu.models.config import LlamaConfig
from distributed_llama_multiusers_tpu.models.deepseek import IndexedLatentCache
from distributed_llama_multiusers_tpu.models.loader import (
    load_params_from_m,
    load_params_from_m_quantized,
)
from distributed_llama_multiusers_tpu.quants.codec import FloatType
from distributed_llama_multiusers_tpu.quants.packed import PackedQ40, Q40Experts, pack_q40_host
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

import latent_toy
from test_deepseek_model_file import _Index, _converter, _state_dict

CFG, FAMILY, CORRECT = latent_toy.load("tiny_deepseek_v32.json")
NEW_KEYS = {mf.KEY_Q_LORA_RANK, mf.KEY_INDEX_N_HEADS, mf.KEY_INDEX_HEAD_DIM, mf.KEY_INDEX_TOPK,
            mf.KEY_MOE_N_GROUP, mf.KEY_MOE_TOPK_GROUP, mf.KEY_EXPERTS_HELD_FIRST,
            mf.KEY_EXPERTS_HELD_COUNT, mf.KEY_ROPE_YARN_MSCALE_ALL_DIM_E6,
            mf.KEY_MOE_NORM_FLOOR_EXP10}


def _round_trip(tmp_path, h):
    buf = io.BytesIO()
    mf.write_model_header(buf, h)
    path = tmp_path / "h.m"
    path.write_bytes(buf.getvalue())
    return mf.load_model_header(str(path))


def test_the_new_keys_round_trip_and_are_written_only_where_set(tmp_path):
    h = tiny_sparse_latent_header(experts_held=(8, 8))
    written = {k for k, _ in h.to_kv_pairs()}
    assert NEW_KEYS <= written
    back = _round_trip(tmp_path, h)
    for name in mf.LATENT_FIELDS + ("rope_type", "rope_scaling_factor", "rope_scaling_orig_max_seq_len"):
        assert getattr(back, name) == getattr(h, name), name
    assert (back.experts_held_first, back.experts_held_count, back.moe_norm_floor) == (8, 8, 0.0)
    config = LlamaConfig.from_header(back)
    assert config.sparse_attention and config.experts_held == (8, 8)
    assert (config.moe_n_group, config.moe_topk_group, config.q_lora_rank) == (4, 2, 64)
    # a latent file of before, and a Llama file, write none of them
    old = tiny_sparse_latent_header()
    for name, value in (("q_lora_rank", 0), ("index_topk", 0), ("index_n_heads", 0),
                        ("index_head_dim", 0), ("moe_n_group", 1), ("moe_topk_group", 1),
                        ("experts_held_first", 0), ("experts_held_count", 0),
                        ("rope_yarn_mscale_all_dim", 0.0),
                        ("moe_norm_floor", 1e-20)):
        setattr(old, name, value)
    assert not NEW_KEYS & {k for k, _ in old.to_kv_pairs()}
    assert not NEW_KEYS & {k for k, _ in tiny_header().to_kv_pairs()}
    assert _round_trip(tmp_path, old).moe_norm_floor == 1e-20


@pytest.mark.parametrize("floor", [1e-20, 1e-6, 0.0])
def test_the_floor_is_held_as_a_power_of_ten(tmp_path, floor):
    h = tiny_sparse_latent_header()
    h.moe_norm_floor = floor
    assert _round_trip(tmp_path, h).moe_norm_floor == floor


def test_the_walk_holds_the_new_tensors_and_only_the_held_experts():
    h = tiny_sparse_latent_header(experts_held=(0, 8))
    h.header_size = 8 + 8 * len(h.to_kv_pairs())
    specs = mf.model_tensor_specs(h)
    names = [s.name for s in specs if s.layer == 1]
    for name in ("block_matmul_q_a", "block_rms_norm_q", "block_matmul_idx_q", "block_matmul_idx_k",
                 "block_idx_k_norm_gain", "block_idx_k_norm_bias", "block_idx_weights"):
        assert names.count(name) == 1, name
    assert names.index("block_matmul_q_a") < names.index("block_matmul_q") < names.index("block_matmul_kv_a")
    assert sum(s.name == "block_matmul_w1" and s.expert >= 0 for s in specs) == 2 * 8
    gate = next(s for s in specs if s.name == "block_moe_gate")
    assert gate.shape == (16, 128)  # the router keeps every output
    q = next(s for s in specs if s.name == "block_matmul_q")
    assert q.shape == (4 * 48, 64)  # from the query latent


@pytest.mark.parametrize("quantized", [True, False], ids=["q40", "dense"])
def test_a_synthetic_checkpoint_loads_and_serves(tmp_path, quantized):
    h = tiny_sparse_latent_header()
    path = str(tmp_path / "toy.m")
    write_synthetic_model(path, h, seed=3)
    header = mf.load_model_header(path)
    load = load_params_from_m_quantized if quantized else load_params_from_m
    config, params = load(path, header, dtype=jnp.float32)
    assert config.sparse_attention and config.experts_held == (0, 8)
    a = params.attn
    assert a.rms_q.shape == (3, 64) and a.idx_ww.shape == (3, 128, 4)
    assert (a.wqa.packed if quantized else a.wqa).shape[0] == 3
    assert a.idx_k_gain.shape == a.idx_k_bias.shape == (3, 32)
    assert a.idx_ww.dtype == jnp.float32
    assert isinstance(a.idx_wq, PackedQ40) == quantized
    held = params.routed.w1.packed.shape[1] if quantized else params.routed.w1.shape[1]
    assert held == 8 and params.routed.gate.shape == (2, 128, 16)
    eng = InferenceEngine(config, params, n_lanes=4, cache_dtype=jnp.float32)
    assert isinstance(eng.cache, IndexedLatentCache)
    prompt = list(range(2, 60))
    whole, _, _ = eng.prefill(0, prompt)
    eng.prefill(1, prompt[:16])
    parts, _, _ = eng.prefill(1, prompt[16:], start_pos=16)
    assert np.isfinite(np.asarray(whole)).all()
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), rtol=2e-4, atol=2e-4)


def _v32_state_dict(cfg, seed=0):
    """A checkpoint's tensors under deepseek_v32's published names, EVERY
    routed expert present (the converter writes the share it is told)."""
    full = dict(cfg, n_routed_experts=cfg["deployment"]["n_routed_experts_published"])
    sd = _state_dict(full, seed)
    rng = np.random.default_rng(seed + 100)
    d, qr = cfg["hidden_size"], cfg["q_lora_rank"]
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]

    def w(d_out, d_in, gain=1.0):
        return (gain * d_in ** -0.5 * rng.normal(size=(d_out, d_in))).astype(np.float32)

    for l in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{l}.self_attn"
        q = sd.pop(f"{p}.q_proj.weight")
        sd[f"{p}.q_a_proj.weight"] = w(qr, d)
        sd[f"{p}.q_a_layernorm.weight"] = (1.0 + 0.1 * rng.normal(size=qr)).astype(np.float32)
        sd[f"{p}.q_b_proj.weight"] = w(q.shape[0], qr, 2.0)
        sd[f"{p}.indexer.wq_b.weight"] = w(ih * idim, qr, 2.0)
        sd[f"{p}.indexer.wk.weight"] = w(idim, d, 2.0)
        sd[f"{p}.indexer.k_norm.weight"] = (1.0 + 0.1 * rng.normal(size=idim)).astype(np.float32)
        sd[f"{p}.indexer.k_norm.bias"] = (0.1 * rng.normal(size=idim)).astype(np.float32)
        sd[f"{p}.indexer.weights_proj.weight"] = w(ih, d)
    return sd


def _reference_tensors(cfg, sd, first, count):
    """The family's arrays from the same state dict, by the writer's own
    bit-exact Q40 encoder."""
    L, Ld = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]

    def q(names):
        pk, sc = pack_q40_host(np.stack([sd[n] for n in names]))
        return PackedQ40(jnp.asarray(pk), jnp.asarray(sc))

    def per_layer(suffix, rng=range(L)):
        return [f"model.layers.{l}.{suffix}" for l in rng]

    def f32(suffix, rng=range(L)):
        return jnp.stack([sd[n] for n in per_layer(suffix, rng)])

    att = "self_attn."
    t = {
        "wqa": q(per_layer(att + "q_a_proj.weight")), "wq": q(per_layer(att + "q_b_proj.weight")),
        "wkva": q(per_layer(att + "kv_a_proj_with_mqa.weight")),
        "wkvb": q(per_layer(att + "kv_b_proj.weight")), "wo": q(per_layer(att + "o_proj.weight")),
        "idx_wq": q(per_layer(att + "indexer.wq_b.weight")),
        "idx_wk": q(per_layer(att + "indexer.wk.weight")),
        "idx_ww": jnp.stack([sd[n].T for n in per_layer(att + "indexer.weights_proj.weight")]),
        "idx_k_gain": f32(att + "indexer.k_norm.weight"), "idx_k_bias": f32(att + "indexer.k_norm.bias"),
        "rms_q": f32(att + "q_a_layernorm.weight"), "rms_kv": f32(att + "kv_a_layernorm.weight"),
        "rms_att": f32("input_layernorm.weight"),
        "dense_rms_ffn": f32("post_attention_layernorm.weight", range(Ld)),
        "rms_ffn": f32("post_attention_layernorm.weight", range(Ld, L)),
        "moe_gate": jnp.stack([sd[n].T for n in per_layer("mlp.gate.weight", range(Ld, L))]),
        "moe_bias": f32("mlp.gate.e_score_correction_bias", range(Ld, L)),
        "embedding": jnp.asarray(sd["model.embed_tokens.weight"]),
        "rms_final": jnp.asarray(sd["model.norm.weight"]),
    }
    head = q(["lm_head.weight"])
    t["wcls"] = PackedQ40(head.packed[0], head.scales[0])
    for key, hf in (("w1", "gate_proj"), ("w2", "down_proj"), ("w3", "up_proj")):
        t["dense_" + key] = q(per_layer(f"mlp.{hf}.weight", range(Ld)))
        t["shared_" + key] = q(per_layer(f"mlp.shared_experts.{hf}.weight", range(Ld, L)))
        pk, sc = pack_q40_host(np.stack([
            np.stack([sd[f"model.layers.{l}.mlp.experts.{e}.{hf}.weight"]
                      for e in range(first, first + count)]) for l in range(Ld, L)]))
        t[key] = Q40Experts.from_packed(PackedQ40(jnp.asarray(pk), jnp.asarray(sc)))
    return t


@pytest.mark.parametrize("first", [0, 8], ids=["share0", "share1"])
def test_state_dict_to_m_to_engine_equals_the_reference(tmp_path, first):
    conv = _converter()
    published = {k: v for k, v in CFG.items()
                 if k not in ("serving", "correctness", "family", "source", "deployment")}
    published["n_routed_experts"] = 16
    published["router_norm_floor"] = 0.0
    (tmp_path / "config.json").write_text(json.dumps(published))
    sd = _v32_state_dict(CFG)
    out = str(tmp_path / "toy.m")
    conv.convert(str(tmp_path), FloatType.Q40, out, index=_Index(sd), experts_held=(first, 8))
    header = mf.load_model_header(out)
    assert (header.q_lora_rank, header.index_topk, header.moe_n_group, header.moe_topk_group) == (64, 16, 4, 2)
    assert (header.experts_held_first, header.experts_held_count, header.n_experts) == (first, 8, 16)
    assert header.rope_type == mf.RopeType.YARN and header.rope_scaling_factor == 4.0
    specs = mf.model_tensor_specs(header)
    assert specs[-1].offset + specs[-1].n_bytes == header.file_size

    cfg = dict(CFG, deployment=dict(CFG["deployment"], experts_first=first))
    assert LlamaConfig.from_header(header) == FAMILY.program_config(cfg)
    t = _reference_tensors(cfg, sd, first, 8)
    prompts, forced = CORRECT.sample_sequences(cfg, 3)
    prefixes = [CORRECT.prefix_lengths(cfg, len(p)) for p in prompts]
    want = CORRECT.plain_logits(FAMILY, cfg, t, prompts, forced, prefixes)
    config, params = load_params_from_m_quantized(out, header, dtype=jnp.float32)
    engine = InferenceEngine(config, params, n_lanes=8, cache_dtype=jnp.float32)
    got = CORRECT.engine_logits(engine, prompts, forced, prefixes)
    assert CORRECT.relative_errors(got, want).max() < 1e-5


def test_the_converter_refuses_what_the_runtime_does_not_compute(tmp_path):
    conv = _converter()
    published = {k: v for k, v in CFG.items() if k not in ("serving", "correctness", "family", "source")}
    uneven = dict(CFG["rope_scaling"], mscale=0.707)
    for key, value, said in (("moe_layer_freq", 2, "moe_layer_freq"),
                             ("rope_scaling", {"type": "linear", "factor": 2}, "rope_scaling"),
                             ("rope_scaling", uneven, "mscale differs")):
        (tmp_path / "config.json").write_text(json.dumps(dict(published, **{key: value})))
        with pytest.raises(ValueError, match=said):
            conv.load_config(str(tmp_path), FloatType.Q40)
