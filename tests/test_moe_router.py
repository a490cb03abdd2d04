"""The DeepSeek-V3 router (models/deepseek.py `moe_router`) against a numpy
transcription of the published rule, with controls that must fail."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats.model_file import MoeScore
from distributed_llama_multiusers_tpu.models.config import LlamaConfig
from distributed_llama_multiusers_tpu.models.deepseek import moe_router

E, K, D, N = 16, 4, 32, 24
SCALE = 2.448


def _config(**kw):
    base = dict(
        dim=D, hidden_dim=64, n_layers=2, n_heads=2, n_kv_heads=2, vocab_size=64, seq_len=32,
        n_experts=E, n_active_experts=K, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, moe_hidden_dim=32, n_dense_layers=1,
        moe_score_func=MoeScore.SIGMOID, moe_select_bias=1, moe_routed_scale=SCALE,
    )
    base.update(kw)
    return LlamaConfig(**base)


def _numpy_route(y, gate, bias, *, scores="sigmoid", weigh_with_bias=False, scale=SCALE, k=K):
    """Dense weights [N, E] as the config.json keys describe them."""
    logits = y.astype(np.float64) @ gate.astype(np.float64)
    if scores == "sigmoid":
        s = 1.0 / (1.0 + np.exp(-logits))
    else:
        e = np.exp(logits - logits.max(-1, keepdims=True))
        s = e / e.sum(-1, keepdims=True)
    out = np.zeros_like(s)
    for i in range(s.shape[0]):
        chosen = np.argsort(-(s[i] + bias))[:k]
        w = (s[i] + bias)[chosen] if weigh_with_bias else s[i][chosen]
        out[i, chosen] = scale * w / (w.sum() + 1e-20)
    return out


@pytest.fixture(scope="module")
def inputs():
    rng = np.random.default_rng(7)
    y = rng.normal(size=(N, D)).astype(np.float32)
    gate = (2.0 * D ** -0.5 * rng.normal(size=(D, E))).astype(np.float32)
    bias = rng.uniform(-0.2, 0.2, size=E).astype(np.float32)
    return y, gate, bias


def _dense(w, idx):
    out = np.zeros((w.shape[0], E))
    np.put_along_axis(out, np.asarray(idx), np.asarray(w, np.float64), axis=-1)
    return out


def test_router_is_the_published_rule(inputs):
    y, gate, bias = inputs
    w, idx = moe_router(_config(), jnp.asarray(y), jnp.asarray(gate), jnp.asarray(bias))
    assert w.dtype == jnp.float32 and idx.dtype == jnp.int32 and w.shape == (N, K)
    np.testing.assert_allclose(_dense(w, idx), _numpy_route(y, gate, bias), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), SCALE, rtol=1e-5)


@pytest.mark.parametrize("wrong", [
    dict(scores="softmax"), dict(weigh_with_bias=True), dict(scale=1.0), dict(k=K + 1), dict(k=K - 1),
])
def test_a_rule_that_departs_from_it_is_told_apart(inputs, wrong):
    y, gate, bias = inputs
    w, idx = moe_router(_config(), jnp.asarray(y), jnp.asarray(gate), jnp.asarray(bias))
    assert np.abs(_dense(w, idx) - _numpy_route(y, gate, bias, **wrong)).max() > 0.05


def test_the_bias_chooses_and_does_not_weigh(inputs):
    y, gate, bias = inputs
    w0, idx0 = moe_router(_config(moe_select_bias=0), jnp.asarray(y), jnp.asarray(gate), None)
    w1, idx1 = moe_router(_config(), jnp.asarray(y), jnp.asarray(gate), jnp.asarray(bias))
    assert (np.sort(idx0, -1) != np.sort(idx1, -1)).any()  # a bias of visible size
    same = (np.sort(idx0, -1) == np.sort(idx1, -1)).all(-1)
    assert same.any()
    np.testing.assert_allclose(_dense(w0, idx0)[same], _dense(w1, idx1)[same], rtol=1e-6)


def test_softmax_scores_are_the_mixtral_rule(inputs):
    """The other score function: softmax over all experts, the chosen
    renormalised, which is softmax over the chosen logits."""
    y, gate, _ = inputs
    cfg = _config(moe_score_func=MoeScore.SOFTMAX, moe_select_bias=0, moe_routed_scale=1.0)
    w, idx = moe_router(cfg, jnp.asarray(y), jnp.asarray(gate), None)
    logits = y @ gate
    top = np.take_along_axis(logits, np.asarray(idx), -1)
    e = np.exp(top - top.max(-1, keepdims=True))
    np.testing.assert_allclose(w, e / e.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(np.argsort(-logits, -1)[:, :K], -1))
