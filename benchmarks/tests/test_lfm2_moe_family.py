"""The lfm2_moe family (`families/lfm2_moe.py`) at a toy size on the CPU: the
engine against the family's plain reference through `correct.compare`
(prefill, then decode through both kinds of state, a chunked prefill, fused
admissions beside decoding lanes, twins left parked), controls that must fail,
the reference against itself in blocks, the comparison of two lanes' state,
and the two readers this family's cell adds."""
import json
import os
from types import SimpleNamespace

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
    with open(os.path.join(REHEARSAL, "configs", "tiny_lfm2.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family(cfg):
    return cells.load_family(cfg)


def test_the_real_configuration_names_the_family_and_keeps_its_widths():
    bench = cells.load_benchmark()
    cfg = cells.load_config_file(bench, "lfm2-24b-a2b")
    family = cells.load_family(cfg)
    assert family.__file__ == os.path.join(BENCH_DIR, "families", "lfm2_moe.py")
    c = family.program_config(cfg)
    assert (c.n_layers, c.n_dense_layers, c.n_conv_layers, c.n_attention_layers) == (20, 2, 15, 5)
    assert c.layer_kinds == (1, 1, 0, 1) * 5 and c.recurrent_state and c.n_routed_layers == 18
    assert (c.n_experts, c.n_active_experts, c.moe_hidden_dim, c.shared_hidden_dim) == (64, 4, 1536, 0)
    assert (c.dim, c.hidden_dim, c.n_heads, c.n_kv_heads, c.head_size) == (2048, 11776, 32, 8, 64)
    assert (c.vocab_size, c.seq_len, c.conv_kernel, c.qk_norm) == (65536, 2048, 3, 1)
    assert c.moe_routed_scale == 1.0 and c.moe_select_bias == 1 and c.norm_epsilon == 1e-5
    entry = next(e for e in bench["configs"] if e["name"] == "lfm2-24b-a2b")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "layer_types", "max_position_embeddings", "num_hidden_layers"]
    cell = cells.find_cell(bench, "lfm2_24b_chat_saturated")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("lfm2-24b-a2b", "chat_saturated", 1)


def test_engine_agrees_with_the_reference_and_the_routes_read_zero(cfg, family):
    sound = control.readings(family, cfg, "as_configured", [3_000_000_033], log=lambda s: None)
    assert all(r["ok"] for r in sound), sound
    assert all(r["route_kv_rel_err"] == 0.0 and r["route_token_mismatches"] == 0 for r in sound)
    # float32 where float32 is stated reads 5e-7; bfloat16 activations read
    # 1e-2 and more, three orders above this limit
    assert all(r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5 for r in sound)


def test_admissions_swapped_fail_by_the_state_and_by_the_tokens(cfg, family):
    swapped = control.readings(family, cfg, "admits_swapped", [31], log=lambda s: None)
    assert not swapped[0]["ok"] and swapped[0]["route_kv_rel_err"] > 0.1


@pytest.fixture(scope="module")
def sample(cfg, family):
    """Seeded arrays, the sample sequences and the reference's logits."""
    t = family.device_weights(family.program_config(cfg), 31, jnp.float32)
    prompts, forced = correct.sample_sequences(cfg, 31)
    prefixes = [correct.prefix_lengths(cfg, len(p)) for p in prompts]
    return t, (prompts, forced, prefixes), correct.plain_logits(
        family, cfg, t, prompts, forced, prefixes)


@pytest.mark.parametrize("wrong", [
    dict(num_experts_per_tok=1), dict(routed_scaling_factor=2.0), dict(norm_topk_prob=False),
    dict(use_expert_bias=False), dict(rope_parameters={"rope_theta": 5000.0}),
    dict(layer_types=["conv", "conv", "full_attention", "conv", "conv", "full_attention", "conv", "conv"]),
], ids=["top1", "scale", "no_renorm", "no_bias", "theta", "pattern"])
def test_a_reference_of_another_model_is_told_apart(cfg, family, sample, wrong):
    t, seqs, want = sample
    got = correct.plain_logits(family, dict(cfg, **wrong), t, *seqs)
    assert correct._rms(correct.relative_errors(got, want)) > 0.01


@pytest.mark.parametrize("key", ["conv_taps", "q_norm", "k_norm"])
def test_a_reference_blind_to_a_new_tensor_is_told_apart(cfg, family, sample, key):
    t, (prompts, forced, prefixes), want = sample
    blind = dict(t, **{key: jnp.ones_like(t[key])})
    got = correct.plain_logits(family, cfg, blind, prompts, forced, prefixes)
    assert correct.relative_errors(got, want).max() > 0.01


def test_the_lower_precision_reference_fails(cfg, family):
    lossy = control.readings(family, cfg, "reference_in_f8", [31], log=lambda s: None)
    assert not lossy[0]["ok"]


def test_the_reference_in_blocks_is_the_reference_whole(cfg, family, sample, monkeypatch):
    """Another block of experts, and two sequences a pass or one, give the
    same logits: the blocks are a way to fit, not a part of the arithmetic."""
    t, (prompts, forced, prefixes), want = sample
    monkeypatch.setattr(family, "EXPERT_BLOCK", 3)
    monkeypatch.setattr(correct, "REFERENCE_BATCH", 1)
    got = correct.plain_logits(family, cfg, t, prompts, forced, prefixes)
    assert correct.relative_errors(got, want).max() < 1e-5


def test_the_chosen_sets_are_reported_and_hold_k_experts(cfg, family, sample):
    t = sample[0]
    tokens = np.arange(2 * 24).reshape(2, 24) % cfg["vocab_size"]
    routes = []
    with jax.default_matmul_precision("highest"):
        family.reference_forward(cfg, t, tokens, routes=routes)
    assert len(routes) == cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    assert all((r.sum(-1) == cfg["num_experts_per_tok"]).all() for r in routes)
    lossy = []
    with jax.default_matmul_precision("highest"):
        family.reference_forward(cfg, t, tokens, lossy="bfloat16", routes=lossy)
    assert 0.0 <= family.route_difference_share(routes, lossy) < 0.5


def test_lane_state_covers_keys_values_and_the_whole_conv_state(cfg, family, sample):
    from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

    config, t = family.program_config(cfg), sample[0]
    engine = InferenceEngine(config, family.assemble_params(config, t), n_lanes=4,
                             cache_dtype=jnp.float32)
    assert engine.cache.k.shape == (2, 4, 128, 2 * 32)  # the attention layers only
    assert engine.cache.conv.shape == (6, 4, 2 * 128)  # K - 1 rows a conv layer
    prompt = list(range(2, 22))
    engine.prefill(0, prompt)
    engine.prefill(1, prompt)
    engine.prefill(2, prompt[:-1] + [99])
    assert family.lane_state_rel_err(engine, 0, 1, 20) == 0.0
    assert family.lane_state_rel_err(engine, 0, 2, 20) > 1e-3
    # rows [0, 19) of K and V agree, the conv state (its last two inputs) does not
    assert family.lane_state_rel_err(engine, 0, 2, 19) > 1e-3
    engine.cache = engine.cache._replace(conv=engine.cache.conv.at[5, 1, 7].add(1.0))
    assert family.lane_state_rel_err(engine, 0, 1, 20) > 1e-3  # the last conv layer's too


def test_lane_state_on_the_recurrent_toy_of_the_route_check():
    """`test_route_recurrent.py`'s engine keeps a running sum in a leaf of its
    own; this family's comparison is for a cache with a `conv` leaf and says
    None for any other, so that nothing is reported as compared that was not."""
    toy = SimpleNamespace(cache=SimpleNamespace(k=jnp.zeros((1, 2, 4, 1, 2)), v=jnp.zeros((1, 2, 4, 1, 2))))
    family_mod = cells.load_family({"family": "lfm2_moe"})
    assert family_mod.lane_state_rel_err(toy, 0, 1, 2) is None


def test_the_two_readers_on_a_recorded_stretch():
    """`conv_mixer_step_ms` on the recorded Mistral trace (no conv scope:
    absent, as on the parent), and `moe_rows_per_slab` on counters."""
    from harness import progtrace

    read_conv = cells.load_module(os.path.join(BENCH_DIR, "metrics", "conv_mixer_step_ms.py"), "m_conv").read
    read_rows = cells.load_module(os.path.join(BENCH_DIR, "metrics", "moe_rows_per_slab.py"), "m_rows").read
    assert read_conv(SimpleNamespace(trace=None)) is None  # untraced
    assert read_rows(SimpleNamespace(counters={})) is None  # a program without the counters
    assert read_rows(SimpleNamespace(counters={"moe_slabs_read": 0, "moe_assignments": 0})) is None
    got = read_rows(SimpleNamespace(counters={"moe_slabs_read": 1130, "moe_assignments": 4608}))
    assert got == pytest.approx(4608 / 1130)
    fam = {"executions": 2, "per_execution": [{"dl.conv": 1.0, "dl.conv_state": 0.25, "dl.ffn": 3.0},
                                               {"dl.conv": 1.5, "dl.conv_state": 0.25}]}
    red = {"scopes": {"_decode_pl": fam}}
    assert progtrace.scope_ms_per_execution(red, "_decode_pl", ("dl.conv", "dl.conv_state")) == pytest.approx(1.5)
