"""The ``.m`` path of what ``model_type: cohere2_moe`` adds: a checkpoint's
state dict under its names through ``converter/convert-hf.py`` (the four
shared experts folded into one stack, no row permuted), the header's new keys
(a head's width, the window kind and its size, full-context layers that do
not rotate, the norm's kind, the parallel block, the shared experts' scale),
``models/loader.py`` and the engine, against the benchmark family's plain
reference on the same tensors; what the converter does not convert, refused by
name; a held share; the synthetic toy. A file without the new keys reads, and
is written, as before."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats.model_file import (
    KEY_HEAD_DIM,
    KEY_NORM_KIND,
    KEY_PARALLEL_BLOCK,
    KEY_SHARED_EXPERT_SCALE_E6,
    KEY_SLIDING_WINDOW,
    LayerKind,
    NormKind,
    load_model_header,
    model_tensor_specs,
    write_model_header,
)
from distributed_llama_multiusers_tpu.formats.synthetic import (
    tiny_pattern_header,
    tiny_ssm_header,
    tiny_window_header,
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

CFG, FAMILY, CORRECT = latent_toy.load("tiny_cohere2_moe.json")
PUBLISHED = {k: v for k, v in CFG.items() if k not in ("serving", "correctness", "family", "source")}
BUCKETS = tuple(CFG["serving"]["prefill_buckets"])


def _state_dict(cfg, seed=0):
    """A cohere2_moe checkpoint's tensors under the names the converter reads."""
    rng = np.random.default_rng(seed)
    d, inter = cfg["hidden_size"], cfg["intermediate_size"]
    q_dim = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]

    def w(d_out, d_in, gain=1.0):
        return (gain * d_in ** -0.5 * rng.normal(size=(d_out, d_in))).astype(np.float32)

    def norm(n):
        return (1.0 + 0.1 * rng.normal(size=n)).astype(np.float32)

    sd = {"model.embed_tokens.weight": rng.normal(size=(cfg["vocab_size"], d)).astype(np.float32),
          "model.norm.weight": norm(d)}  # no lm_head: the family ties it
    for l in range(cfg["num_hidden_layers"]):
        p, a, m = (f"model.layers.{l}", f"model.layers.{l}.self_attn", f"model.layers.{l}.mlp")
        sd[f"{p}.input_layernorm.weight"] = norm(d)
        sd[f"{a}.q_proj.weight"], sd[f"{a}.k_proj.weight"] = w(q_dim, d, 2.0), w(kv, d, 2.0)
        sd[f"{a}.v_proj.weight"], sd[f"{a}.o_proj.weight"] = w(kv, d), w(d, q_dim, 0.3)
        sd[f"{m}.gate.weight"] = w(cfg["num_experts"], d)
        for e in range(cfg["num_experts"]):
            sd[f"{m}.experts.{e}.gate_proj.weight"] = w(inter, d)
            sd[f"{m}.experts.{e}.up_proj.weight"] = w(inter, d)
            sd[f"{m}.experts.{e}.down_proj.weight"] = w(d, inter, 1.2)
        for i in range(cfg["num_shared_experts"]):
            sd[f"{m}.shared_experts.{i}.gate_proj.weight"] = w(inter, d)
            sd[f"{m}.shared_experts.{i}.up_proj.weight"] = w(inter, d)
            sd[f"{m}.shared_experts.{i}.down_proj.weight"] = w(d, inter, 1.2)
    return sd


def _reference_tensors(cfg, sd, held=None):
    """The family's arrays from the same state dict, quantized by the same
    bit-exact Q40 encoder the writer uses; the shared experts folded as the
    converter folds them."""
    L, S = cfg["num_hidden_layers"], cfg["num_shared_experts"]
    experts = held or range(cfg["num_experts"])

    def q(mats):
        pk, sc = pack_q40_host(np.stack(mats))
        return PackedQ40(jnp.asarray(pk), jnp.asarray(sc))

    def get(fmt):
        return [sd[fmt.format(l=l)] for l in range(L)]

    def shared(name, axis):
        return [np.concatenate([sd[f"model.layers.{l}.mlp.shared_experts.{i}.{name}.weight"]
                                for i in range(S)], axis=axis) for l in range(L)]

    a, m = "model.layers.{l}.self_attn.", "model.layers.{l}.mlp."
    t = {
        "wq": q(get(a + "q_proj.weight")), "wk": q(get(a + "k_proj.weight")),
        "wv": q(get(a + "v_proj.weight")), "wo": q(get(a + "o_proj.weight")),
        "shared_w1": q(shared("gate_proj", 0)), "shared_w2": q(shared("down_proj", 1)),
        "shared_w3": q(shared("up_proj", 0)),
        "moe_gate": jnp.stack([x.T for x in get(m + "gate.weight")]),
        "attn_rms": jnp.stack(get("model.layers.{l}.input_layernorm.weight")),
        "embedding": jnp.asarray(sd["model.embed_tokens.weight"]),
        "rms_final": jnp.asarray(sd["model.norm.weight"]),
    }
    head = q([sd["model.embed_tokens.weight"]])  # tied
    t["wcls"] = PackedQ40(head.packed[0], head.scales[0])
    for key, name in (("w1", "gate_proj"), ("w2", "down_proj"), ("w3", "up_proj")):
        pk, sc = pack_q40_host(np.stack([
            np.stack([sd[f"model.layers.{l}.mlp.experts.{e}.{name}.weight"] for e in experts])
            for l in range(L)]))
        t[key] = Q40Experts.from_packed(PackedQ40(jnp.asarray(pk), jnp.asarray(sc)))
    return t


def _sample():
    prompts, forced = CORRECT.sample_sequences(CFG, 3)
    return prompts, forced, [CORRECT.prefix_lengths(CFG, len(p)) for p in prompts]


def test_state_dict_to_m_to_engine_equals_the_reference(tmp_path):
    conv = _converter()
    (tmp_path / "config.json").write_text(json.dumps(PUBLISHED))
    sd = _state_dict(CFG)
    out = str(tmp_path / "toy.m")
    conv.convert(str(tmp_path), FloatType.Q40, out, index=_Index(sd))
    header = load_model_header(out)
    assert header.layer_kinds == [LayerKind.WINDOW] * 3 + [LayerKind.ATTENTION]
    assert (header.head_dim, header.sliding_window, header.full_attention_nope) == (16, 8, 1)
    assert (header.norm_kind, header.parallel_block) == (NormKind.LAYER, 1)
    assert (header.shared_hidden_dim, header.shared_expert_scale) == (64, 0.5)
    assert (header.n_experts, header.n_active_experts, header.moe_hidden_dim) == (8, 2, 32)
    assert (header.q_dim, header.kv_dim, header.rope_theta, header.moe_norm_floor) == (64, 32, 50000.0, 0.0)
    specs = model_tensor_specs(header)
    assert specs[-1].offset + specs[-1].n_bytes == header.file_size
    names = [s.name for s in specs if s.layer == 1 and s.expert < 1 and s.name.startswith("block_")]
    assert names == [
        "block_matmul_q", "block_matmul_k", "block_matmul_v", "block_matmul_wo", "block_moe_gate",
        "block_matmul_w3", "block_matmul_w1", "block_matmul_w2", "block_matmul_shared_w1",
        "block_matmul_shared_w2", "block_matmul_shared_w3", "block_rms_norm_0"]  # ONE norm
    shapes = {s.name: s.shape for s in specs if s.layer == 0}
    assert shapes["block_matmul_q"] == (64, 32) and shapes["block_matmul_wo"] == (32, 64)
    assert shapes["block_matmul_shared_w1"] == (64, 32) and shapes["block_matmul_shared_w2"] == (32, 64)

    want_config = FAMILY.program_config(CFG)
    t = _reference_tensors(CFG, sd)
    prompts, forced, prefixes = _sample()
    want = CORRECT.plain_logits(FAMILY, CFG, t, prompts, forced, prefixes)
    for load in (load_params_from_m_quantized, load_params_from_m):
        config, params = load(out, header, dtype=jnp.float32)
        assert config == want_config
        engine = InferenceEngine(config, params, n_lanes=8, prefill_buckets=BUCKETS,
                                 cache_dtype=jnp.float32)
        assert engine.ring_rows == 12
        got = CORRECT.engine_logits(engine, prompts, forced, prefixes)
        assert CORRECT.relative_errors(got, want).max() < 1e-5
    _, packed = load_params_from_m_quantized(out, header, dtype=jnp.bfloat16)
    assert isinstance(packed.attn.wq, PackedQ40) and packed.attn.wq.packed.shape == (4, 16, 64)
    assert isinstance(packed.routed.w1, Q40Experts) and isinstance(packed.routed.s1, PackedQ40)
    assert packed.routed.rms_ffn is None and packed.routed.bias is None and packed.dense is None
    assert packed.attn.rms.dtype == jnp.float32 and packed.routed.gate.dtype == jnp.float32


def test_a_held_share_of_the_experts_through_the_converter(tmp_path):
    """Experts 2-5 of 8: the router keeps its 8 outputs, the file those four
    experts' tensors, and engine and reference agree on the partial result."""
    conv = _converter()
    (tmp_path / "config.json").write_text(json.dumps(PUBLISHED))
    sd = _state_dict(CFG, seed=1)
    out = str(tmp_path / "share.m")
    conv.convert(str(tmp_path), FloatType.Q40, out, index=_Index(sd), experts_held=(2, 4))
    header = load_model_header(out)
    assert (header.experts_held_first, header.experts_held_count, header.n_experts) == (2, 4, 8)
    config, params = load_params_from_m(out, header, dtype=jnp.float32)
    assert params.routed.w1.shape[:2] == (4, 4) and params.routed.gate.shape == (4, 32, 8)
    held_cfg = dict(CFG, num_experts=4, deployment={"num_experts_published": 8, "experts_first": 2})
    assert config == FAMILY.program_config(held_cfg)
    prompts, forced, prefixes = _sample()
    want = CORRECT.plain_logits(
        FAMILY, held_cfg, _reference_tensors(CFG, sd, held=range(2, 6)), prompts, forced, prefixes)
    engine = InferenceEngine(config, params, n_lanes=8, prefill_buckets=BUCKETS,
                             cache_dtype=jnp.float32)
    got = CORRECT.engine_logits(engine, prompts, forced, prefixes)
    assert CORRECT.relative_errors(got, want).max() < 1e-5


@pytest.mark.parametrize("wrong,match", [
    (dict(use_qk_norm=True), "use_qk_norm = True"), (dict(logit_scale=0.25), "logit_scale = 0.25"),
    (dict(first_k_dense_replace=2), "first_k_dense_replace = 2"),
    (dict(use_parallel_block=False), "use_parallel_block"),
    (dict(layer_types=["sliding_attention", "chunked_attention"] * 2), "chunked_attention"),
    (dict(shared_expert_combination_strategy="sum"), "shared_expert_combination_strategy"),
    (dict(expert_selection_fn="softmax"), "expert_selection_fn"),
    (dict(rotary_pct=0.5), "rotary_pct"), (dict(attention_bias=True), "attention_bias"),
])
def test_what_the_converter_does_not_convert_is_refused_by_name(tmp_path, wrong, match):
    conv = _converter()
    (tmp_path / "config.json").write_text(json.dumps(dict(PUBLISHED, **wrong)))
    with pytest.raises(ValueError, match=match):
        conv.load_config(str(tmp_path), FloatType.Q40)


def test_the_synthetic_toy_round_trips_and_a_window_layer_needs_its_window(tmp_path):
    h = tiny_window_header()
    path = str(tmp_path / "toy.m")
    write_synthetic_model(path, h, seed=1)
    back = load_model_header(path)
    assert back.layer_kinds == [3, 3, 3, 0] and (back.sliding_window, back.head_dim) == (8, 16)
    config, params = load_params_from_m(path, back, dtype=jnp.float32)
    assert (config.n_window_layers, config.n_attention_layers) == (3, 1) and config.recurrent_state
    assert params.attn.wq.shape == (4, 32, 64) and params.routed.s1.shape == (4, 32, 64)
    engine = InferenceEngine(config, params, n_lanes=2, prefill_buckets=(4,))
    _, greedy, pos = engine.prefill(0, list(range(2, 13)))
    assert pos == 11 and 0 <= greedy < config.vocab_size
    h.sliding_window = 0
    with open(tmp_path / "bad.m", "wb") as f:
        write_model_header(f, h)
    with pytest.raises(ValueError, match="sliding_window"):
        load_model_header(str(tmp_path / "bad.m"))


def test_a_file_without_the_new_keys_carries_none_of_them():
    new = {KEY_HEAD_DIM, KEY_SLIDING_WINDOW, KEY_NORM_KIND, KEY_PARALLEL_BLOCK,
           KEY_SHARED_EXPERT_SCALE_E6}
    for h in (tiny_pattern_header(), tiny_ssm_header()):
        assert not new & {k for k, _ in h.to_kv_pairs()}
        names = [s.name for s in model_tensor_specs(h)]
        assert names.count("block_rms_norm_1") == h.n_layers  # two norms a layer, as ever
    assert new <= {k for k, _ in tiny_window_header().to_kv_pairs()}


def test_a_parallel_block_is_attention_layers_that_all_route():
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    h = tiny_window_header()
    LlamaConfig.from_header(h)
    h.n_dense_layers = 1
    with pytest.raises(ValueError, match="parallel block"):
        LlamaConfig.from_header(h)
