"""Converter tests: synthetic HF checkpoint -> .m/.t -> framework loaders."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

CONVERTER_DIR = os.path.join(os.path.dirname(__file__), "..", "converter")


def _load(name, filename):
    path = os.path.join(CONVERTER_DIR, filename)
    sys.path.insert(0, CONVERTER_DIR)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def hf_checkpoint(tmp_path_factory):
    """A tiny fake HF Llama checkpoint: config.json + model.safetensors."""
    torch = pytest.importorskip("torch")
    from safetensors.torch import save_file

    d = tmp_path_factory.mktemp("hf")
    dim, hidden, layers, heads, kv = 64, 128, 2, 4, 2
    vocab = 96
    cfg = {
        "model_type": "llama",
        "hidden_act": "silu",
        "hidden_size": dim,
        "intermediate_size": hidden,
        "num_hidden_layers": layers,
        "num_attention_heads": heads,
        "num_key_value_heads": kv,
        "max_position_embeddings": 64,
        "vocab_size": vocab,
        "rope_theta": 500000.0,
        "rope_scaling": {
            "rope_type": "llama3",
            "factor": 8.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 64,
        },
    }
    (d / "config.json").write_text(json.dumps(cfg))
    g = torch.Generator().manual_seed(0)
    tensors = {"model.embed_tokens.weight": torch.randn(vocab, dim, generator=g) * 0.02}
    kv_dim = dim * kv // heads
    for l in range(layers):
        p = f"model.layers.{l}"
        tensors[f"{p}.self_attn.q_proj.weight"] = torch.randn(dim, dim, generator=g) * 0.02
        tensors[f"{p}.self_attn.k_proj.weight"] = torch.randn(kv_dim, dim, generator=g) * 0.02
        tensors[f"{p}.self_attn.v_proj.weight"] = torch.randn(kv_dim, dim, generator=g) * 0.02
        tensors[f"{p}.self_attn.o_proj.weight"] = torch.randn(dim, dim, generator=g) * 0.02
        tensors[f"{p}.mlp.gate_proj.weight"] = torch.randn(hidden, dim, generator=g) * 0.02
        tensors[f"{p}.mlp.down_proj.weight"] = torch.randn(dim, hidden, generator=g) * 0.02
        tensors[f"{p}.mlp.up_proj.weight"] = torch.randn(hidden, dim, generator=g) * 0.02
        tensors[f"{p}.input_layernorm.weight"] = torch.ones(dim)
        tensors[f"{p}.post_attention_layernorm.weight"] = torch.ones(dim)
    tensors["model.norm.weight"] = torch.ones(dim)
    # no lm_head -> tied-embedding fallback path
    save_file(tensors, str(d / "model.safetensors"))
    return d, cfg, tensors


def test_convert_hf_roundtrip(hf_checkpoint, tmp_path):
    d, cfg, tensors = hf_checkpoint
    mod = _load("convert_hf", "convert-hf.py")
    out = str(tmp_path / "model.m")
    mod.convert(str(d), 2, out)  # q40

    from distributed_llama_multiusers_tpu.formats import load_model_header
    from distributed_llama_multiusers_tpu.models.loader import read_m_tensors
    from distributed_llama_multiusers_tpu.quants.codec import quantize_q40, dequantize_q40

    h = load_model_header(out)
    assert h.dim == cfg["hidden_size"]
    assert h.rope_type == 2  # LLAMA3_1
    assert h.rope_scaling_factor == 8.0
    w = read_m_tensors(out, h)
    # v (unpermuted): matches Q40 QDQ of the HF tensor
    v_hf = tensors["model.layers.0.self_attn.v_proj.weight"].numpy()
    expect = dequantize_q40(quantize_q40(v_hf.reshape(-1))).reshape(v_hf.shape)
    np.testing.assert_allclose(w["wv"][0], expect, rtol=0, atol=0)
    # q is permuted: same values as permuting THEN quantizing
    q_hf = tensors["model.layers.0.self_attn.q_proj.weight"].numpy()
    perm = mod.permute_rotary(q_hf, cfg["num_attention_heads"])
    expect_q = dequantize_q40(quantize_q40(perm.reshape(-1))).reshape(perm.shape)
    np.testing.assert_allclose(w["wq"][0], expect_q, rtol=0, atol=0)
    assert not np.allclose(w["wq"][0], dequantize_q40(quantize_q40(q_hf.reshape(-1))).reshape(q_hf.shape))
    # tied lm_head == embedding (quantized)
    emb = tensors["model.embed_tokens.weight"].numpy()
    np.testing.assert_allclose(
        w["wcls"], dequantize_q40(quantize_q40(emb.reshape(-1))).reshape(emb.shape)
    )
    # and the converted model actually runs
    import jax.numpy as jnp
    from distributed_llama_multiusers_tpu.models import init_kv_cache, llama_forward, load_params_from_m

    config, params = load_params_from_m(out, h, dtype=jnp.float32)
    logits, _ = llama_forward(
        config, params, jnp.array([[1]], jnp.int32), jnp.array([[0]], jnp.int32),
        init_kv_cache(config, 1),
    )
    assert bool(jnp.isfinite(logits).all())


def test_convert_tokenizer_hf(tmp_path):
    """A minimal byte-level-BPE tokenizer.json converts and encodes."""
    mod = _load("convert_tok_hf", "convert-tokenizer-hf.py")
    bd = mod.gpt2_byte_decoder()
    enc = {v: k for k, v in bd.items()}  # byte -> unicode char

    def u(s: bytes) -> str:
        return "".join(enc[b] for b in s)

    vocab = {}
    for i, b in enumerate(range(256)):
        vocab[u(bytes([b]))] = i
    vocab[u(b"he")] = 256
    vocab[u(b"ll")] = 257
    vocab[u(b"hell")] = 258
    vocab[u(b"hello")] = 259
    tok_json = {
        "model": {
            "type": "BPE",
            "vocab": vocab,
            "merges": ["h e", "l l", "he ll", "hell o"],
        },
        "added_tokens": [
            {"id": 260, "content": "<|begin_of_text|>"},
            {"id": 261, "content": "<|eot_id|>"},
        ],
    }
    cfg = {
        "bos_token": "<|begin_of_text|>",
        "eos_token": "<|eot_id|>",
        "chat_template": "x<|start_header_id|>y",
    }
    d = tmp_path / "tok"
    d.mkdir()
    (d / "tokenizer.json").write_text(json.dumps(tok_json))
    (d / "tokenizer_config.json").write_text(json.dumps(cfg))
    out = str(tmp_path / "tok.t")
    mod.convert(str(d), out)

    from distributed_llama_multiusers_tpu.tokenizer import Tokenizer

    t = Tokenizer(out)
    assert t.bos_id == 260
    assert t.eos_token_ids == [261]
    ids = t.encode("hello", add_bos=False)
    assert ids == [259]
    assert t.decode_full(t.encode("hello world")) == "hello world"


def test_convert_tokenizer_llama3(tmp_path):
    import base64

    mod = _load("convert_tok_l3", "convert-tokenizer-llama3.py")
    model = tmp_path / "tokenizer.model"
    pieces = [b"a", b"b", b"ab", b"hello"]
    model.write_bytes(b"\n".join(base64.b64encode(p) + b" %d" % i for i, p in enumerate(pieces)))
    out = str(tmp_path / "l3.t")
    mod.convert(str(model), out)

    from distributed_llama_multiusers_tpu.tokenizer import Tokenizer

    t = Tokenizer(out)
    assert t.bos_id == len(pieces)
    assert t.vocab[t.bos_id] == b"<|begin_of_text|>"
    assert len(t.eos_token_ids) == 2
    ids = t.encode("ab", add_bos=False)
    assert ids == [2]  # merged via rank-descending scores


def test_convert_hf_jamba_folder_serves(tmp_path):
    """A tiny ``model_type: jamba`` folder (config.json + safetensors under the
    published tensor names) through the command's own path: the header says
    what the layers are, the tied head is the embedding, and the file loads
    and serves a prompt through the engine."""
    torch = pytest.importorskip("torch")
    from safetensors.torch import save_file

    import jax.numpy as jnp

    from test_hybrid_model_file import JCFG, _jamba_state_dict

    cfg = {k: v for k, v in JCFG.items() if k not in ("serving", "correctness", "family", "source")}
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    sd = _jamba_state_dict(JCFG, seed=2)
    save_file({k: torch.from_numpy(np.ascontiguousarray(v)) for k, v in sd.items()},
              str(tmp_path / "model.safetensors"))
    mod = _load("convert_hf", "convert-hf.py")
    out = str(tmp_path / "jamba.m")
    mod.convert(str(tmp_path), 2, out)  # q40

    from distributed_llama_multiusers_tpu.formats import load_model_header
    from distributed_llama_multiusers_tpu.formats.model_file import LayerKind, RopeType
    from distributed_llama_multiusers_tpu.models.loader import load_params_from_m
    from distributed_llama_multiusers_tpu.quants.codec import dequantize_q40, quantize_q40
    from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

    h = load_model_header(out)
    assert h.layer_kinds.count(LayerKind.SSM) == 6 and h.layer_kinds.count(LayerKind.ATTENTION) == 2
    assert h.rope_type == RopeType.NONE and h.ssm_d_inner == 2 * cfg["hidden_size"]
    config, params = load_params_from_m(out, h, dtype=jnp.float32)
    emb = sd["model.embed_tokens.weight"]
    np.testing.assert_allclose(  # no lm_head in the folder: the tied embedding, quantized
        np.asarray(params.wcls).T, dequantize_q40(quantize_q40(emb.reshape(-1))).reshape(emb.shape))
    a_log = sd["model.layers.0.mamba.A_log"]
    np.testing.assert_array_equal(np.asarray(params.ssm.a_log[0]), a_log.T)  # float32, untouched
    engine = InferenceEngine(config, params, n_lanes=2, prefill_buckets=(8, 16))
    last, greedy, pos = engine.prefill(0, list(range(2, 40)))
    assert pos == 38 and bool(np.isfinite(np.asarray(last)).all())
    with pytest.raises(ValueError, match="Unsupported arch type"):
        (tmp_path / "config.json").write_text(json.dumps(dict(cfg, model_type="bamba")))
        mod.load_config(str(tmp_path), 2)
