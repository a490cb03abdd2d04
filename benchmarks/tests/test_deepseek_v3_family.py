"""The deepseek_v3 family (`families/deepseek_v3.py`) at a toy size on the CPU:
the engine against the family's plain reference through `correct.compare`
(prefill then decode through the latent cache, a chunked prefill, fused
admissions beside decoding lanes, twins left parked), controls that must
fail, and the reference's own parts."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import control
from harness import cells, correct
from harness.cells import BENCH_DIR

REHEARSAL = os.path.join(BENCH_DIR, "tests", "rehearsal")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REHEARSAL, "configs", "tiny_latent.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family(cfg):
    return cells.load_family(cfg)


def test_the_real_configuration_names_the_family_and_keeps_its_widths():
    bench = cells.load_benchmark()
    cfg = cells.load_config_file(bench, "kanana-2-30b-a3b")
    family = cells.load_family(cfg)
    assert family.__file__ == os.path.join(BENCH_DIR, "families", "deepseek_v3.py")
    c = family.program_config(cfg)
    assert c.latent_attention and (c.n_layers, c.n_dense_layers) == (24, 1)
    assert (c.n_experts, c.n_active_experts, c.moe_hidden_dim, c.shared_hidden_dim) == (128, 6, 768, 1536)
    assert (c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) == (512, 128, 64, 128)
    assert (c.dim, c.hidden_dim, c.n_heads, c.vocab_size, c.seq_len) == (2048, 6144, 32, 128256, 2048)
    assert c.moe_routed_scale == 2.448 and c.moe_select_bias == 1 and c.norm_epsilon == 1e-6
    entry = next(e for e in bench["configs"] if e["name"] == "kanana-2-30b-a3b")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == ["max_position_embeddings", "num_hidden_layers"]


def test_engine_agrees_with_the_reference_and_the_routes_read_zero(cfg, family):
    sound = control.readings(family, cfg, "as_configured", [3_000_000_033], log=lambda s: None)
    assert all(r["ok"] for r in sound), sound
    assert all(r["route_kv_rel_err"] == 0.0 and r["route_token_mismatches"] == 0 for r in sound)
    assert all(r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5 for r in sound)


@pytest.fixture(scope="module")
def sample(cfg, family):
    """Seeded arrays, the sample sequences and the reference's logits."""
    t = family.device_weights(family.program_config(cfg), 31, jnp.float32)
    prompts, forced = correct.sample_sequences(cfg, 31)
    prefixes = [correct.prefix_lengths(cfg, len(p)) for p in prompts]
    return t, (prompts, forced, prefixes), correct.plain_logits(
        family, cfg, t, prompts, forced, prefixes)


@pytest.mark.parametrize("wrong", [
    dict(num_experts_per_tok=2), dict(scoring_func="softmax"), dict(routed_scaling_factor=1.0),
    dict(norm_topk_prob=False), dict(rope_theta=5000.0),
])
def test_a_reference_of_another_model_is_told_apart(cfg, family, sample, wrong):
    t, seqs, want = sample
    got = correct.plain_logits(family, dict(cfg, **wrong), t, *seqs)
    assert correct._rms(correct.relative_errors(got, want)) > 0.01


def test_a_reference_without_its_shared_expert_or_its_bias_is_told_apart(cfg, family, sample):
    t, (prompts, forced, prefixes), want = sample
    no_shared = dict(t, **{k: type(t[k])(t[k].packed, jnp.zeros_like(t[k].scales))
                           for k in ("shared_w2",)})
    got = correct.plain_logits(family, cfg, no_shared, prompts, forced, prefixes)
    assert correct.relative_errors(got, want).max() > 0.01
    shifted = dict(t, moe_bias=jnp.zeros_like(t["moe_bias"]))
    got = correct.plain_logits(family, cfg, shifted, prompts, forced, prefixes)
    assert correct.relative_errors(got, want).max() > 0.01  # a bias of visible size


def test_the_lower_precision_reference_fails(cfg, family):
    lossy = control.readings(family, cfg, "reference_in_f8", [31], log=lambda s: None)
    assert not lossy[0]["ok"]


def test_the_chosen_sets_are_reported_and_hold_k_experts(cfg, family, sample):
    t = sample[0]
    tokens = np.arange(2 * 24).reshape(2, 24) % cfg["vocab_size"]
    routes = []
    with jax.default_matmul_precision("highest"):
        family.reference_forward(cfg, t, tokens, routes=routes)
    assert len(routes) == cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert all((r.sum(-1) == cfg["num_experts_per_tok"]).all() for r in routes)
    lossy = []
    with jax.default_matmul_precision("highest"):
        family.reference_forward(cfg, t, tokens, lossy="bfloat16", routes=lossy)
    share = family.route_difference_share(routes, lossy)
    assert 0.0 <= share < 0.5


def test_lane_state_covers_both_latent_leaves(cfg, family, sample):
    from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

    config, t = family.program_config(cfg), sample[0]
    engine = InferenceEngine(config, family.assemble_params(config, t), n_lanes=4,
                             cache_dtype=jnp.float32)
    assert engine.cache.k.shape == (3, 4, 128, 64) and engine.cache.v.shape == (3, 4, 128, 128)
    prompt = list(range(2, 22))
    engine.prefill(0, prompt)
    engine.prefill(1, prompt)
    engine.prefill(2, prompt[:-1] + [99])
    assert family.lane_state_rel_err(engine, 0, 1, 20) == 0.0
    assert family.lane_state_rel_err(engine, 0, 2, 20) > 1e-3
    assert family.lane_state_rel_err(engine, 0, 2, 19) == 0.0
    engine.cache = engine.cache._replace(v=engine.cache.v.at[:, 1, 3].add(1.0))
    assert family.lane_state_rel_err(engine, 0, 1, 20) > 1e-3  # the rotated key part too
