"""What ``model_type: minicpm_sala`` adds to the ``.m`` format
(formats/model_file.py KEY_LINEAR_N_HEADS ...): the header's keys and their
round trip, the walk of a linear-attention and of a block-sparse layer, a
state dict under the family's tensor names through the converter, the writer
and both loaders into the engine against the benchmark's plain reference, what
the converter refuses by name, and a file without the new kinds unchanged."""

import io
import json

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats.model_file import (
    KEY_EMBED_SCALE_E6,
    KEY_LINEAR_N_HEADS,
    KEY_SPARSE_TOPK,
    LayerKind,
    load_model_header,
    model_tensor_specs,
    write_model_header,
)
from distributed_llama_multiusers_tpu.formats.synthetic import (
    tiny_pattern_header,
    tiny_sala_header,
    tiny_ssm_header,
    tiny_window_header,
    write_synthetic_model,
)
from distributed_llama_multiusers_tpu.models.config import LlamaConfig
from distributed_llama_multiusers_tpu.models.loader import (
    load_params_from_m,
    load_params_from_m_quantized,
)
from distributed_llama_multiusers_tpu.quants.codec import FloatType
from distributed_llama_multiusers_tpu.quants.packed import PackedQ40, pack_q40_host
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

import latent_toy
from test_deepseek_model_file import _converter, _Index

CFG, FAMILY, CORRECT = latent_toy.load("tiny_minicpm_sala.json")
L, S = LayerKind.LINEAR, LayerKind.SPARSE


def _published(cfg):
    return {k: v for k, v in cfg.items()
            if k not in ("serving", "correctness", "family", "source")}


def _sala_state_dict(cfg, seed=0):
    """A minicpm_sala checkpoint's tensors under the names the converter reads."""
    rng = np.random.default_rng(seed)
    d, hidden, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    q_dim, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    lin = cfg["lightning_nh"] * cfg["lightning_head_dim"]

    def w(d_out, d_in, gain=1.0):
        return (gain * d_in ** -0.5 * rng.normal(size=(d_out, d_in))).astype(np.float32)

    def norm(n, mean=1.0):
        return (mean * (1.0 + 0.1 * rng.normal(size=n))).astype(np.float32)

    sd = {"model.embed_tokens.weight":
          (rng.normal(size=(cfg["vocab_size"], d)) / cfg["scale_emb"]).astype(np.float32),
          "model.norm.weight": norm(d), "lm_head.weight": w(cfg["vocab_size"], d, 3.0)}
    for l, kind in enumerate(cfg["mixer_types"]):
        p, a = f"model.layers.{l}", f"model.layers.{l}.self_attn"
        sd[f"{p}.input_layernorm.weight"] = norm(d)
        sd[f"{p}.post_attention_layernorm.weight"] = norm(d)
        if kind == "minicpm4":
            sd[f"{a}.q_proj.weight"], sd[f"{a}.k_proj.weight"] = w(q_dim, d), w(kv, d)
            sd[f"{a}.v_proj.weight"], sd[f"{a}.o_proj.weight"] = w(kv, d), w(d, q_dim, 2.0)
            sd[f"{a}.q_norm.weight"], sd[f"{a}.k_norm.weight"] = norm(hd, 2.0), norm(hd, 2.0)
            sd[f"{a}.o_gate.weight"] = w(q_dim, d)
        else:
            for name in ("q_proj", "k_proj", "v_proj", "o_gate"):
                sd[f"{a}.{name}.weight"] = w(lin, d)
            sd[f"{a}.o_proj.weight"] = w(d, lin, 2.0)
            for name in ("q_norm", "k_norm", "o_norm"):
                sd[f"{a}.{name}.weight"] = norm(cfg["lightning_head_dim"])
        sd[f"{p}.mlp.gate_proj.weight"], sd[f"{p}.mlp.up_proj.weight"] = w(hidden, d), w(hidden, d)
        sd[f"{p}.mlp.down_proj.weight"] = w(d, hidden, 0.8)
    return sd


def _sala_reference_tensors(cfg, sd, conv):
    """The same tensors as the plain reference takes them: matmul weights
    ``[d_in, d_out]`` stacked by the count of their kind and quantized by the
    writer's bit-exact encoder; a lightning layer's q and k rows, and their
    norms' gains, in the interleaved-pair order the reference's rotation turns."""
    kinds = cfg["mixer_types"]
    sparse = [l for l, k in enumerate(kinds) if k == "minicpm4"]
    linear = [l for l, k in enumerate(kinds) if k != "minicpm4"]
    heads = cfg["lightning_nh"]

    def q(mats):
        pk, sc = pack_q40_host(np.stack(mats))
        return PackedQ40(jnp.asarray(pk), jnp.asarray(sc))

    def get(fmt, layers, fn=lambda x: x):
        return [fn(sd[fmt.format(l=l)]) for l in layers]

    rows = lambda x: conv.permute_rotary(x, heads)  # noqa: E731
    gains = lambda g: conv.permute_rotary(g.reshape(-1, 1), 1).reshape(-1)  # noqa: E731
    a, f = "model.layers.{l}.self_attn.", "model.layers.{l}.mlp."
    every = range(len(kinds))
    t = {
        "wq": q(get(a + "q_proj.weight", sparse)), "wk": q(get(a + "k_proj.weight", sparse)),
        "wv": q(get(a + "v_proj.weight", sparse)), "wo": q(get(a + "o_proj.weight", sparse)),
        "attn_gate": q(get(a + "o_gate.weight", sparse)),
        "q_norm": jnp.stack(get(a + "q_norm.weight", sparse)),
        "k_norm": jnp.stack(get(a + "k_norm.weight", sparse)),
        "lin_q": q(get(a + "q_proj.weight", linear, rows)),
        "lin_k": q(get(a + "k_proj.weight", linear, rows)),
        "lin_v": q(get(a + "v_proj.weight", linear)), "lin_gate": q(get(a + "o_gate.weight", linear)),
        "lin_out": q(get(a + "o_proj.weight", linear)),
        "lin_q_norm": jnp.stack(get(a + "q_norm.weight", linear, gains)),
        "lin_k_norm": jnp.stack(get(a + "k_norm.weight", linear, gains)),
        "lin_o_norm": jnp.stack(get(a + "o_norm.weight", linear)),
        "attn_rms": jnp.stack(get("model.layers.{l}.input_layernorm.weight", sparse)),
        "lin_rms": jnp.stack(get("model.layers.{l}.input_layernorm.weight", linear)),
        "dense_rms_ffn": jnp.stack(get("model.layers.{l}.post_attention_layernorm.weight", every)),
        "dense_w1": q(get(f + "gate_proj.weight", every)),
        "dense_w2": q(get(f + "down_proj.weight", every)),
        "dense_w3": q(get(f + "up_proj.weight", every)),
        "embedding": jnp.asarray(sd["model.embed_tokens.weight"]),
        "rms_final": jnp.asarray(sd["model.norm.weight"]),
    }
    head = q([sd["lm_head.weight"]])
    t["wcls"] = PackedQ40(head.packed[0], head.scales[0])
    return t


def test_sala_state_dict_to_m_to_engine_equals_the_reference(tmp_path):
    conv = _converter()
    (tmp_path / "config.json").write_text(json.dumps(_published(CFG)))
    sd = _sala_state_dict(CFG)
    out = str(tmp_path / "toy.m")
    conv.convert(str(tmp_path), FloatType.Q40, out, index=_Index(sd))
    header = load_model_header(out)
    assert header.layer_kinds == [S, L, L, L, L, S, S, L]
    assert (header.linear_n_heads, header.linear_head_dim, header.head_dim) == (4, 32, 32)
    assert (header.sparse_kernel_size, header.sparse_kernel_stride, header.sparse_block_size,
            header.sparse_topk, header.sparse_window, header.sparse_init_blocks,
            header.sparse_dense_len) == (4, 2, 8, 4, 16, 1, 48)
    assert (header.qk_norm, header.full_attention_nope, header.n_experts) == (1, 1, 0)
    assert (header.embed_scale, header.logit_divisor, header.norm_epsilon) == (12.0, 2.0, 1e-6)
    assert header.residual_scale == pytest.approx(1.4 / 8 ** 0.5, abs=1e-6)
    specs = model_tensor_specs(header)
    assert specs[-1].offset + specs[-1].n_bytes == header.file_size
    assert [s.name for s in specs if s.layer == 0 and s.name.startswith("block_")] == [
        "block_matmul_q", "block_matmul_k", "block_matmul_v", "block_q_norm", "block_k_norm",
        "block_matmul_attn_gate", "block_matmul_wo", "block_matmul_w1", "block_matmul_w2",
        "block_matmul_w3", "block_rms_norm_0", "block_rms_norm_1"]
    assert [s.name for s in specs if s.layer == 1][:8] == [
        "block_matmul_lin_q", "block_matmul_lin_k", "block_matmul_lin_v", "block_lin_q_norm",
        "block_lin_k_norm", "block_matmul_lin_gate", "block_lin_o_norm", "block_matmul_lin_out"]
    small = {s.name: s.float_type for s in specs if s.layer == 1 and "matmul" not in s.name}
    assert set(small.values()) == {FloatType.F32}  # the norms' gains are float32

    want_config = FAMILY.program_config(CFG)
    t = _sala_reference_tensors(CFG, sd, conv)
    prompts, forced = CORRECT.sample_sequences(CFG, 3)
    prefixes = [CORRECT.prefix_lengths(CFG, len(p)) for p in prompts]
    want = CORRECT.plain_logits(FAMILY, CFG, t, prompts, forced, prefixes)
    for load in (load_params_from_m_quantized, load_params_from_m):
        config, params = load(out, header, dtype=jnp.float32)
        # the header keeps the residual factor in millionths
        assert config.residual_scale == pytest.approx(want_config.residual_scale, abs=1e-6)
        assert config == LlamaConfig(**{**want_config.__dict__, "residual_scale": config.residual_scale})
        engine = InferenceEngine(config, params, n_lanes=8, cache_dtype=jnp.float32)
        got = CORRECT.engine_logits(engine, prompts, forced, prefixes)
        assert CORRECT.relative_errors(got, want).max() < 1e-4
    _, packed = load_params_from_m_quantized(out, header, dtype=jnp.bfloat16)
    assert isinstance(packed.linear.wq, PackedQ40) and packed.linear.wq.packed.shape == (5, 64, 128)
    assert isinstance(packed.attn.gate, PackedQ40) and packed.attn.gate.packed.shape == (3, 64, 128)
    assert isinstance(packed.linear.w_out, PackedQ40) and isinstance(packed.dense.w1, PackedQ40)
    for leaf in (packed.linear.q_norm, packed.linear.k_norm, packed.linear.o_norm,
                 packed.linear.rms, packed.attn.q_norm):
        assert leaf.dtype == jnp.float32  # whatever the activations are
    assert packed.linear.o_norm.shape == (5, 32) and packed.rope_cos is not None
    assert packed.conv is None and packed.ssm is None and packed.routed is None


@pytest.mark.parametrize("wrong,match", [
    (dict(mixer_types=["mamba"] * 8), "mixer types"), (dict(lightning_nkv=2), "lightning_nkv = 2"),
    (dict(attn_use_rope=True), "attn_use_rope"), (dict(use_output_norm=False), "use_output_norm"),
    (dict(qk_norm=False), "qk_norm"), (dict(mixer_types=["minicpm4"] * 7), "mixer types"),
])
def test_what_the_sala_converter_does_not_convert_is_refused_by_name(tmp_path, wrong, match):
    conv = _converter()
    (tmp_path / "config.json").write_text(json.dumps(dict(_published(CFG), **wrong)))
    with pytest.raises(ValueError, match=match):
        conv.load_config(str(tmp_path), FloatType.Q40)


def test_a_config_without_sparse_sizes_takes_the_familys_published_ones(tmp_path):
    conv = _converter()
    cfg = {k: v for k, v in _published(CFG).items() if k != "sparse_config"}
    (tmp_path / "config.json").write_text(json.dumps(dict(cfg, max_position_embeddings=8192)))
    h, _ = conv.load_config(str(tmp_path), FloatType.Q40)
    assert (h.sparse_kernel_size, h.sparse_kernel_stride, h.sparse_block_size, h.sparse_topk,
            h.sparse_window, h.sparse_init_blocks, h.sparse_dense_len) == (32, 16, 64, 64, 2048, 1, 8192)


def test_the_synthetic_toy_round_trips_and_a_short_header_is_refused(tmp_path):
    header = tiny_sala_header()
    buf = io.BytesIO()
    write_model_header(buf, header)
    path = str(tmp_path / "m.m")
    write_synthetic_model(path, header, seed=1, scale=0.1)
    back = load_model_header(path)
    for name in ("layer_kinds", "linear_n_heads", "linear_head_dim", "sparse_kernel_size",
                 "sparse_kernel_stride", "sparse_block_size", "sparse_topk", "sparse_window",
                 "sparse_init_blocks", "sparse_dense_len", "embed_scale", "logit_divisor",
                 "qk_norm", "full_attention_nope"):
        assert getattr(back, name) == getattr(header, name), name
    assert back.residual_scale == pytest.approx(header.residual_scale, abs=1e-6)
    config, params = load_params_from_m(path, back, dtype=jnp.float32)
    assert config.n_linear_layers == 5 and config.n_sparse_layers == 3 and config.recurrent_state
    engine = InferenceEngine(config, params, n_lanes=2, prefill_buckets=(8, 16))
    last, _, pos = engine.prefill(0, list(range(2, 70)))
    assert pos == 68 and bool(np.isfinite(np.asarray(last)).all())
    for field, wrong in (("linear_n_heads", 0), ("sparse_kernel_stride", 0), ("sparse_topk", 0),
                         ("sparse_block_size", 3), ("sparse_dense_len", 50)):
        broken = tiny_sala_header()
        setattr(broken, field, wrong)
        with open(str(tmp_path / "bad.m"), "wb") as f:
            write_model_header(f, broken)
        with pytest.raises(ValueError, match="linear-attention|block-sparse"):
            load_model_header(str(tmp_path / "bad.m"))
    mixed = FAMILY.program_config(CFG).__dict__
    with pytest.raises(ValueError, match="no other attention layers"):
        LlamaConfig(**{**mixed, "layer_kinds": (0, L, L, L, L, S, S, L)})
    with pytest.raises(ValueError, match="residual_scale"):
        LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=128,
                    seq_len=64, residual_scale=0.5)


@pytest.mark.parametrize("header", [tiny_pattern_header(), tiny_ssm_header(), tiny_window_header()],
                         ids=["lfm2", "jamba", "cohere2"])
def test_a_file_without_the_new_kinds_carries_none_of_the_keys(header):
    keys = [k for k, _ in header.to_kv_pairs()]
    assert not set(keys) & set(range(KEY_LINEAR_N_HEADS, KEY_EMBED_SCALE_E6 + 3))
    assert KEY_SPARSE_TOPK in [k for k, _ in tiny_sala_header().to_kv_pairs()]
