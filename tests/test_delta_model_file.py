"""What ``model_type: solar_open2`` adds to the ``.m`` format
(formats/model_file.py KEY_DELTA_N_HEADS ... KEY_ATTN_OUTPUT_GATE): the header's
keys and their round trip, the walk of a delta-rule layer and of a gated
full-context layer, a synthetic file through the writer and both loaders into
the engine against the benchmark's plain reference over the SAME tensors (the
taps' and the low-rank gates' transposes among them), what the header and the
config refuse, a file without the new fields unchanged, and the shares of a
routed layer adding up to the uncut layer with the shared expert counted once."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats.model_file import (
    KEY_ATTN_OUTPUT_GATE,
    KEY_DELTA_N_HEADS,
    LayerKind,
    MoeScore,
    load_model_header,
    model_tensor_specs,
    write_model_header,
)
from distributed_llama_multiusers_tpu.formats.synthetic import (
    tiny_delta_header,
    tiny_mixed_head_header,
    tiny_pattern_header,
    tiny_sala_header,
    tiny_ssm_header,
    tiny_window_header,
    write_synthetic_model,
)
from distributed_llama_multiusers_tpu.models import deepseek
from distributed_llama_multiusers_tpu.models.config import LlamaConfig
from distributed_llama_multiusers_tpu.models.loader import (
    load_params_from_m,
    load_params_from_m_quantized,
)
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

import latent_toy

CFG, FAMILY, CORRECT = latent_toy.load("tiny_solar_open2.json")
G, D = LayerKind.ATTENTION, LayerKind.DELTA
DELTA_FIELDS = ("delta_n_heads", "delta_head_dim", "delta_conv_kernel", "delta_gate_rank",
                "delta_neg_eigval", "attn_output_gate")


def _reference_tensors(p) -> dict:
    """The loader's tree as the plain reference takes its arrays: by the names
    of ``hybrid_params``' dict."""
    t = {"embedding": p.embedding, "rms_final": p.rms_final, "wcls": p.wcls}
    a, d, r = p.attn, p.delta, p.routed
    t.update(wq=a.wq, wk=a.wk, wv=a.wv, wo=a.wo, attn_gate=a.gate, attn_rms=a.rms)
    t.update(delta_q=d.wq, delta_k=d.wk, delta_v=d.wv, delta_taps=d.taps, delta_f1=d.f1,
             delta_f2=d.f2, delta_dt_bias=d.dt_bias, delta_a_log=d.a_log, delta_b=d.wb,
             delta_g1=d.g1, delta_g2=d.g2, delta_o_norm=d.o_norm, delta_out=d.w_out,
             delta_rms=d.rms)
    t.update(moe_gate=r.gate, moe_bias=r.bias, w1=r.w1, w2=r.w2, w3=r.w3, shared_w1=r.s1,
             shared_w2=r.s2, shared_w3=r.s3, rms_ffn=r.rms_ffn)
    return t


def test_the_synthetic_toy_round_trips_and_both_loaders_agree_with_the_reference(tmp_path):
    """The toy's header as the family's toy configuration gives it; the file
    through the dequantising and the Q40 loader; the Q40 loader's own arrays
    through the plain reference: the walk's order and every transpose are the
    reference's conventions."""
    header = tiny_delta_header(vocab_size=CFG["vocab_size"])
    want = FAMILY.program_config(CFG)
    path = str(tmp_path / "m.m")
    write_synthetic_model(path, header, seed=1, scale=0.1)
    back = load_model_header(path)
    for name in ("layer_kinds", *DELTA_FIELDS, "experts_held_first", "experts_held_count",
                 "shared_hidden_dim", "moe_select_bias", "rope_type", "head_dim"):
        assert getattr(back, name) == getattr(header, name), name
    config, dense = load_params_from_m(path, back, dtype=jnp.float32)
    assert dataclasses.replace(config, experts_held_first=4, rope_theta=want.rope_theta) == want
    assert config.n_delta_layers == 6 and config.n_attention_layers == 2 and config.recurrent_state
    names = [s.name for s in model_tensor_specs(back) if s.layer == 1]
    assert names[:3] == ["block_matmul_delta_q", "block_matmul_delta_k", "block_matmul_delta_v"]
    assert names[3] == "block_delta_conv_taps" and "block_matmul_attn_gate" not in names
    assert "block_matmul_attn_gate" in [s.name for s in model_tensor_specs(back) if s.layer == 0]
    _, packed = load_params_from_m_quantized(path, back, dtype=jnp.float32)
    prompt = [int(x) for x in np.random.default_rng(2).integers(2, 250, size=60)]
    rows = []
    for params in (dense, packed):  # one bucket: one program a loader
        engine = InferenceEngine(config, params, n_lanes=2, prefill_buckets=(64,))
        last, _, pos = engine.prefill(0, prompt)
        assert pos == 60
        rows.append(np.asarray(last, np.float32))
    # the share of this file is experts 0-3: the reference is told so
    cfg = dict(CFG, deployment=dict(CFG["deployment"], experts_first=0))
    ref = FAMILY.reference_logits(cfg, _reference_tensors(packed), np.asarray([prompt]),
                                  np.asarray([[59]]))[0, 0]
    for row in rows:
        assert CORRECT.relative_errors(row[None], ref[None]).max() < 1e-4


@pytest.mark.parametrize("field,wrong,match", [
    ("delta_n_heads", 0, "delta-rule layer needs"),
    ("delta_conv_kernel", 1, "delta-rule layer needs"),
    ("delta_gate_rank", 0, "delta-rule layer needs"),
])
def test_a_short_header_is_refused(tmp_path, field, wrong, match):
    broken = tiny_delta_header()
    setattr(broken, field, wrong)
    with open(str(tmp_path / "bad.m"), "wb") as f:
        write_model_header(f, broken)
    with pytest.raises(ValueError, match=match):
        load_model_header(str(tmp_path / "bad.m"))


def test_what_the_config_refuses():
    base = FAMILY.program_config(CFG).__dict__
    with pytest.raises(ValueError, match="attn_output_gate gates"):
        LlamaConfig(**{**base, "layer_kinds": (LayerKind.WINDOW, D, D, D, G, D, D, D),
                       "sliding_window": 8})
    with pytest.raises(ValueError, match="attn_output_gate gates"):
        LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2, vocab_size=128,
                    seq_len=64, attn_output_gate=1)
    with pytest.raises(ValueError, match="delta-rule layer needs"):
        LlamaConfig(**{**base, "delta_head_dim": 0})


@pytest.mark.parametrize("header", [
    tiny_pattern_header(), tiny_ssm_header(), tiny_window_header(), tiny_sala_header(),
    tiny_mixed_head_header()], ids=["lfm2", "jamba", "cohere2", "sala", "mimo"])
def test_a_file_without_the_new_fields_carries_none_of_the_keys(header):
    keys = [k for k, _ in header.to_kv_pairs()]
    assert not set(keys) & set(range(KEY_DELTA_N_HEADS, KEY_ATTN_OUTPUT_GATE + 1))
    config = LlamaConfig.from_header(header)
    assert all(getattr(config, f) == 0 for f in DELTA_FIELDS) and config.n_delta_layers == 0
    mine = [k for k, _ in tiny_delta_header().to_kv_pairs()]
    assert set(range(KEY_DELTA_N_HEADS, KEY_ATTN_OUTPUT_GATE + 1)) <= set(mine)


@pytest.mark.parametrize("experts,chosen,shares", [(320, 8, 8)], ids=["8 x 40 of 320"])
def test_the_shares_add_up_to_the_uncut_layer(experts, chosen, shares):
    """Every share's routed part (the shared expert left out of each), summed,
    plus the shared expert once, is what the layer holding every expert gives;
    a share of 40 of 320 is the first that is not a power of two (the toy's 4
    shares of 4 of 16: the reference's side, tests/test_bench_solar_family.py;
    shares of 8 and 4 of 16: tests/test_router_groups_and_share.py)."""
    dim, hidden = 64, 32
    cfg = LlamaConfig(
        dim=dim, hidden_dim=128, n_layers=1, n_heads=4, n_kv_heads=2, vocab_size=128, seq_len=64,
        n_experts=experts, n_active_experts=chosen, moe_hidden_dim=hidden,
        shared_hidden_dim=hidden, moe_score_func=MoeScore.SIGMOID, moe_select_bias=1,
        moe_norm_topk=1, moe_norm_floor=0.0, layer_kinds=(D,), delta_n_heads=4,
        delta_head_dim=16, delta_conv_kernel=4, delta_gate_rank=8)
    rng = np.random.default_rng(5)
    w = lambda *shape: jnp.asarray(shape[-2] ** -0.5 * rng.normal(size=shape), jnp.float32)  # noqa: E731
    rp = deepseek.RoutedFfnParams(
        gate=w(dim, experts), bias=jnp.asarray(rng.uniform(-0.1, 0.1, size=experts), jnp.float32),
        w1=w(experts, dim, hidden), w2=w(experts, hidden, dim), w3=w(experts, dim, hidden),
        s1=w(dim, hidden), s2=w(hidden, dim), s3=w(dim, hidden), rms_ffn=jnp.ones(dim))
    ops = deepseek.ffn_ops(cfg, False)
    x = jnp.asarray(rng.normal(size=(2, 6, dim)), jnp.float32)
    live = jnp.ones(12, bool)

    def run(c, params):
        out, _, fetched, _, unheld = deepseek.routed_ffn(c, ops, x, params, jnp.int32(0), live)
        return np.asarray(out - x, np.float64), int(fetched), int(unheld)

    uncut, pairs, none_unheld = run(cfg, rp)
    assert none_unheld == 0 and pairs == 12 * chosen
    shared_only, _, _ = run(cfg, rp._replace(w1=rp.w1 * 0, w2=rp.w2 * 0, w3=rp.w3 * 0))
    per = experts // shares
    total, fetched_sum = np.zeros_like(uncut), 0
    for i in range(shares):
        held = dataclasses.replace(cfg, experts_held_first=i * per, experts_held_count=per)
        part = rp._replace(w1=rp.w1[i * per:(i + 1) * per], w2=rp.w2[i * per:(i + 1) * per],
                           w3=rp.w3[i * per:(i + 1) * per], s1=None, s2=None, s3=None)
        out, fetched, unheld = run(held, part)
        assert fetched + unheld == 12 * chosen
        total += out
        fetched_sum += fetched
    assert fetched_sum == 12 * chosen  # every chosen pair is some share's
    np.testing.assert_allclose(total + shared_only, uncut, rtol=1e-4, atol=1e-5)


def test_the_converter_refuses_the_model_type_and_says_which_names_are_missing(tmp_path):
    import json

    from test_deepseek_model_file import _converter

    published = {k: v for k, v in CFG.items()
                 if k not in ("serving", "correctness", "family", "source", "deployment")}
    (tmp_path / "config.json").write_text(json.dumps(published))
    with pytest.raises(ValueError, match="solar_open2: the checkpoint's tensor names are not "
                                         "known here .*conv1d.*tiny_delta_header"):
        _converter().load_config(str(tmp_path), 2)
