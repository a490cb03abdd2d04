"""Expert groups in the router, the floor under its renormalising sum, and a
held share of the routed experts (models/deepseek.py ``moe_router``,
``routed_ffn``): a hand-built router case, a numpy transcription of the
published rule, and the share test of the `model-configs` guide: the routed
parts that all the shares give, with the shared expert counted once, add up
to the uncut layer's result."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats.model_file import MoeScore
from distributed_llama_multiusers_tpu.models import deepseek
from distributed_llama_multiusers_tpu.models.config import LlamaConfig

E, K, D, G, TOPG = 16, 3, 32, 4, 2


def _config(**kw):
    base = dict(
        dim=D, hidden_dim=64, n_layers=2, n_heads=2, n_kv_heads=2, vocab_size=64, seq_len=32,
        n_experts=E, n_active_experts=K, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, moe_hidden_dim=32, shared_hidden_dim=32,
        n_dense_layers=1, moe_score_func=MoeScore.SIGMOID, moe_select_bias=1,
        moe_routed_scale=2.5, moe_n_group=G, moe_topk_group=TOPG, moe_norm_floor=0.0,
    )
    base.update(kw)
    return LlamaConfig(**base)


def _logit(p):
    return float(np.log(p / (1.0 - p)))


def test_groups_against_a_hand_built_case():
    """Four groups of four; the scores are set through an identity gate. The
    largest single score sits in group 3 beside small ones, so the group
    loses (its two best sum to 0.95 + 0.05 = 1.00) to group 0 (0.6 + 0.55 =
    1.15) and group 2 (0.7 + 0.5 = 1.20): the three chosen are 0.7, 0.6,
    0.55, and 0.95 is not among them."""
    scores = np.full(E, 0.05)
    scores[[0, 1]] = 0.6, 0.55          # group 0
    scores[[4, 5]] = 0.5, 0.45          # group 1: 0.95
    scores[[8, 9]] = 0.7, 0.5           # group 2
    scores[12] = 0.95                   # group 3
    gate = np.zeros((D, E), np.float32)
    gate[:E, :E] = np.eye(E)
    y = np.zeros((1, D), np.float32)
    y[0, :E] = [_logit(s) for s in scores]
    w, idx = deepseek.moe_router(_config(), jnp.asarray(y), jnp.asarray(gate), jnp.zeros(E))
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 1, 8]
    chosen = scores[np.asarray(idx)[0]]
    np.testing.assert_allclose(np.asarray(w)[0], 2.5 * chosen / chosen.sum(), rtol=1e-6)
    # without groups the largest score is simply chosen
    _, flat = deepseek.moe_router(_config(moe_n_group=1, moe_topk_group=1),
                                  jnp.asarray(y), jnp.asarray(gate), jnp.zeros(E))
    assert 12 in np.asarray(flat)[0]


def test_the_bias_ranks_the_groups_too_and_does_not_weigh():
    scores = np.full(E, 0.1)
    scores[[0, 1, 4, 5, 8, 9]] = 0.5
    bias = np.zeros(E, np.float32)
    bias[[8, 9]] = 0.3
    bias[[12, 13]] = 0.5  # group 3: 0.6 + 0.6 over groups 0 and 1 (1.0), on its bias alone
    gate = np.zeros((D, E), np.float32)
    gate[:E, :E] = np.eye(E)
    y = np.zeros((1, D), np.float32)
    y[0, :E] = [_logit(s) for s in scores]
    w, idx = deepseek.moe_router(_config(), jnp.asarray(y), jnp.asarray(gate), jnp.asarray(bias))
    idx = np.asarray(idx)[0]
    assert sorted(idx.tolist()) == [8, 9, 12]  # 12 before 13: equal, the lower id
    got = dict(zip(idx.tolist(), np.asarray(w)[0].tolist()))
    for e, score in ((8, 0.5), (9, 0.5), (12, 0.1)):  # weights are the scores, not score + bias
        assert got[e] == pytest.approx(2.5 * score / 1.1, rel=1e-5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_groups_are_the_published_rule_on_random_rows(seed):
    rng = np.random.default_rng(seed)
    y = rng.normal(size=(40, D)).astype(np.float32)
    gate = (2.0 * D ** -0.5 * rng.normal(size=(D, E))).astype(np.float32)
    bias = rng.uniform(-0.2, 0.2, size=E).astype(np.float32)
    w, idx = deepseek.moe_router(_config(), jnp.asarray(y), jnp.asarray(gate), jnp.asarray(bias))
    s = 1.0 / (1.0 + np.exp(-(y.astype(np.float64) @ gate.astype(np.float64))))
    for i in range(len(y)):
        choose = (s[i] + bias).reshape(G, -1)
        group_score = np.sort(choose, axis=-1)[:, -2:].sum(-1)
        kept = np.argsort(-group_score, kind="stable")[:TOPG]
        masked = np.full_like(choose, -np.inf)
        masked[kept] = choose[kept]
        chosen = np.argsort(-masked.reshape(-1), kind="stable")[:K]
        assert sorted(chosen.tolist()) == sorted(np.asarray(idx)[i].tolist())
        np.testing.assert_allclose(
            np.sort(np.asarray(w)[i]), np.sort(2.5 * s[i][chosen] / s[i][chosen].sum()), rtol=1e-5)


@pytest.mark.parametrize("floor,default", [(1e-20, True), (0.0, False), (1e-3, False)])
def test_the_renormalising_floor_is_the_configurations(floor, default):
    cfg = _config() if default else _config(moe_norm_floor=floor)
    if default:
        cfg = dataclasses.replace(cfg, moe_norm_floor=LlamaConfig.__dataclass_fields__["moe_norm_floor"].default)
    assert cfg.moe_norm_floor == floor
    rng = np.random.default_rng(3)
    y = rng.normal(size=(8, D)).astype(np.float32)
    gate = (D ** -0.5 * rng.normal(size=(D, E))).astype(np.float32)
    w, idx = deepseek.moe_router(cfg, jnp.asarray(y), jnp.asarray(gate), None)
    s = 1.0 / (1.0 + np.exp(-(y.astype(np.float64) @ gate.astype(np.float64))))
    picked = np.take_along_axis(s, np.asarray(idx), axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), 2.5 * picked / (picked.sum(-1, keepdims=True) + floor), rtol=1e-5)


def _layer(rng, cfg, experts):
    def w(*shape):
        return jnp.asarray(shape[-2] ** -0.5 * rng.normal(size=shape), jnp.float32)

    h = cfg.moe_hidden_dim
    return deepseek.RoutedFfnParams(
        gate=w(D, E), bias=jnp.asarray(rng.uniform(-0.1, 0.1, size=E), jnp.float32),
        w1=w(experts, D, h), w2=w(experts, h, D), w3=w(experts, D, h),
        s1=w(D, cfg.shared_hidden_dim), s2=w(cfg.shared_hidden_dim, D), s3=w(D, cfg.shared_hidden_dim),
        rms_ffn=jnp.ones(D),
    )


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """Every share's routed part (the shared expert left out of each), summed,
    plus the shared expert once, is what the layer holding every expert
    gives: the router's choice and weights are the same on every chip."""
    rng = np.random.default_rng(5)
    whole = _config()
    rp = _layer(rng, whole, E)
    ops = deepseek.ffn_ops(whole, False)
    x = jnp.asarray(rng.normal(size=(2, 6, D)), jnp.float32)
    live = jnp.ones(12, bool)

    def run(cfg, params):
        out, slabs, fetched, _, unheld = deepseek.routed_ffn(cfg, ops, x, params, jnp.int32(0), live)
        return np.asarray(out - x, np.float64), int(fetched), int(unheld)

    uncut, pairs, none_unheld = run(whole, rp)
    assert none_unheld == 0 and pairs == 12 * K
    shared_only, _, _ = run(whole, rp._replace(w1=rp.w1 * 0, w2=rp.w2 * 0, w3=rp.w3 * 0))
    per = E // shares
    total, fetched_sum = np.zeros_like(uncut), 0
    for i in range(shares):
        cfg = _config(experts_held_first=i * per, experts_held_count=per)
        part = rp._replace(w1=rp.w1[i * per:(i + 1) * per], w2=rp.w2[i * per:(i + 1) * per],
                           w3=rp.w3[i * per:(i + 1) * per], s1=None, s2=None, s3=None)
        out, fetched, unheld = run(cfg, part)
        assert fetched + unheld == 12 * K
        total += out
        fetched_sum += fetched
    assert fetched_sum == 12 * K  # every chosen pair is some share's
    np.testing.assert_allclose(total + shared_only, uncut, rtol=1e-4, atol=1e-5)


def test_a_share_outside_the_experts_is_refused():
    with pytest.raises(ValueError, match="held experts"):
        _config(experts_held_first=12, experts_held_count=8)
    with pytest.raises(ValueError, match="expert groups"):
        _config(moe_n_group=5)
