"""The deepseek_v32 family (`families/deepseek_v32.py`) at a toy size on the
CPU: the engine against the family's plain reference through
`correct.compare` with ``index_topk`` (16) smaller than the toy's context
(128), the controls that must fail (the most recent rows in the selection's
place, the selection left out, a lower precision), the reference's own parts
(the sort's ties, YaRN's tables, the held share adding up), the real
configuration and traffic files, the work counts behind the two rooflines,
and the cell's control flow as a rehearsal."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import control
import control_sparse
from harness import cells, correct, sparse_roofline
from harness.cells import BENCH_DIR, ROOT

REHEARSAL = os.path.join(BENCH_DIR, "tests", "rehearsal")
CELL = "deepseek_v32_longctx_saturated"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REHEARSAL, "configs", "tiny_deepseek_v32.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family(cfg):
    return cells.load_family(cfg)


@pytest.fixture(scope="module")
def sample(cfg, family):
    """Seeded arrays, the sample sequences and the reference's logits."""
    t = family.device_weights(family.program_config(cfg), 31, jnp.float32)
    prompts, forced = correct.sample_sequences(cfg, 31)
    prefixes = [correct.prefix_lengths(cfg, len(p)) for p in prompts]
    return t, (prompts, forced, prefixes), correct.plain_logits(
        family, cfg, t, prompts, forced, prefixes)


def test_the_real_configuration_keeps_every_width_and_says_what_it_cut():
    bench = cells.load_benchmark()
    cfg = cells.load_config_file(bench, "deepseek-v3.2")
    family = cells.load_family(cfg)
    assert family.__file__ == os.path.join(BENCH_DIR, "families", "deepseek_v32.py")
    c = family.program_config(cfg)
    assert (c.dim, c.hidden_dim, c.n_heads, c.moe_hidden_dim, c.shared_hidden_dim) == (
        7168, 18432, 128, 2048, 2048)
    assert (c.kv_lora_rank, c.q_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) == (
        512, 1536, 128, 64, 128)
    assert (c.index_n_heads, c.index_head_dim, c.index_topk) == (64, 128, 2048)
    assert (c.n_experts, c.n_active_experts, c.moe_n_group, c.moe_topk_group) == (256, 8, 8, 4)
    assert c.experts_held == (0, 16) and c.moe_routed_scale == 2.5 and c.moe_norm_floor == 0.0
    assert (c.n_layers, c.n_dense_layers, c.vocab_size, c.seq_len) == (9, 1, 16160, 32768)
    assert (c.rope_scaling_factor, c.rope_scaling_high_freq_factor, c.rope_scaling_low_freq_factor,
            c.rope_scaling_orig_max_seq_len) == (40.0, 32.0, 1.0, 4096)
    assert c.softmax_scale_factor == pytest.approx((0.1 * np.log(40.0) + 1.0) ** 2)
    entry = next(e for e in bench["configs"] if e["name"] == "deepseek-v3.2")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
        "max_position_embeddings", "num_nextn_predict_layers"])
    assert cfg["deployment"]["chips_per_layer"] == 16 and cfg["serving"]["lanes"] == 8
    lengths = cfg["correctness"]["prompt_tokens"]
    assert sum(n > 2048 for n in lengths) >= 3 and min(lengths) < 2048 and max(lengths) >= 6000
    steps = cfg["correctness"]["decode_steps"]
    assert any(n < 2048 <= n + steps for n in lengths)  # a lane crosses index_topk decoding
    assert len(lengths) <= cfg["serving"]["lanes"] - 2
    # the route check's two fused admissions fit the configured ladder: one
    # whole, one cut at the largest rung not above its half
    ladder = cfg["serving"]["prefill_buckets"]
    ia, ib = cfg["correctness"]["route_admits"]
    first, rest = correct.split_admission(list(range(lengths[ib])), ladder)
    assert max(lengths[ia], len(first), len(rest)) <= max(ladder)


def test_the_cell_and_its_traffic_are_the_issues():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("deepseek-v3.2", "longctx_saturated", 1)
    t = cells.load_traffic_file("longctx_saturated")
    assert (t["loop"], t["clients_per_lane"], t["requests"], t["schedule_seed"]) == (
        "closed", 2, 64, 2509411)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 8192, "sigma": 0.6,
                                  "min": 2560, "max": 28672}
    assert t["max_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 0.5,
                               "min": 256, "max": 3072}
    assert t["sampler"] == {"temperature": 0.7, "top_p": 0.9} and t["prompt_pattern"] is None
    assert t["start"]["in_flight"] == "lanes" and t["start"]["preroll_s"] % 10 == 0
    assert t["prompt_tokens"]["max"] + t["max_tokens"]["max"] <= 31744
    per_layer = {m["name"] for m in cells.cell_metrics(bench, CELL, "per_layer")}
    assert {"indexer_step_ms", "sparse_select_step_ms", "indexer_decode_roofline",
            "sparse_attention_decode_roofline", "attention_kv_read_share"} <= per_layer
    assert {m["name"] for m in cells.cell_metrics(bench, CELL, "end_to_end")} == {
        "tokens_per_s", "itl_p50_ms", "itl_p99_ms", "setup_s"}


def test_engine_agrees_with_the_reference_and_the_routes_read_zero(cfg, family):
    sound = control.readings(family, cfg, "as_configured", [3_000_000_033], log=lambda s: None)
    assert all(r["ok"] for r in sound), sound
    assert all(r["route_kv_rel_err"] == 0.0 and r["route_token_mismatches"] == 0 for r in sound)
    assert all(r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5 for r in sound)


@pytest.mark.parametrize("fault", control_sparse.FAULTS)
def test_a_fault_in_the_selection_fails(cfg, family, fault):
    got = control_sparse.readings(family, cfg, [fault], [31], jnp.float32, log=lambda s: None)
    assert not got[0]["ok"] and got[0]["prefill_rel_err"] > 0.05 and got[0]["decode_rel_err"] > 0.05


def test_the_lower_precision_reference_fails(cfg, family):
    lossy = control.readings(family, cfg, "reference_in_f8", [31], log=lambda s: None)
    assert not lossy[0]["ok"]


@pytest.mark.parametrize("wrong", [
    dict(index_topk=8), dict(topk_group=1), dict(n_group=1, topk_group=1),
    dict(rope_scaling=None), dict(routed_scaling_factor=1.0),
    dict(deployment={"n_routed_experts_published": 16, "experts_first": 8}),
])
def test_a_reference_of_another_model_is_told_apart(cfg, family, sample, wrong):
    t, seqs, want = sample
    got = correct.plain_logits(family, dict(cfg, **wrong), t, *seqs)
    assert correct._rms(correct.relative_errors(got, want)) > 0.01


def test_a_selection_wider_than_the_context_is_dense_attention(cfg, family, sample):
    t, seqs, _ = sample
    wide = correct.plain_logits(family, dict(cfg, index_topk=4096), t, *seqs)
    dense = correct.plain_logits(family, cfg, t, *seqs, lossy="select:all")
    np.testing.assert_array_equal(wide, dense)


def test_the_sort_takes_ties_to_the_lower_position_and_short_rows_whole(family):
    scores = jnp.asarray([[3.0, 5.0, 5.0, 1.0, 5.0, 0.0],
                          [1.0, 1.0, 1.0, 1.0, 1.0, 1.0]])
    valid = jnp.asarray([[True] * 6, [True, True, True, False, False, False]])
    got = np.asarray(family.chosen_positions(scores, valid, 2))
    assert got.tolist() == [[False, True, True, False, False, False],
                            [True, True, False, False, False, False]]
    assert np.asarray(family.chosen_positions(scores, valid, 4))[1].tolist() == [True] * 3 + [False] * 3
    recent = np.asarray(family.chosen_positions(scores, valid, 2, "recent"))
    assert recent.tolist() == [[False] * 4 + [True, True], [False, True, True] + [False] * 3]
    assert (np.asarray(family.chosen_positions(scores, valid, 2, "all")) == np.asarray(valid)).all()


def test_yarn_tables_are_the_programs_and_the_plain_ones_without(cfg, family):
    from distributed_llama_multiusers_tpu.models.loader import _rope_cache

    cos, sin = family.yarn_rope_tables(128, 16, 10000.0, cfg["rope_scaling"])
    p_cos, p_sin = _rope_cache(family.program_config(cfg))
    np.testing.assert_allclose(cos, p_cos, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(sin, p_sin, rtol=1e-6, atol=1e-7)
    plain, _ = family.yarn_rope_tables(8, 16, 10000.0, None)
    np.testing.assert_allclose(plain[5], np.cos(5 * 10000.0 ** (-np.arange(8) / 8.0)), rtol=1e-6)
    assert family.softmax_factor(None) == 1.0
    assert family.softmax_factor(cfg["rope_scaling"]) == pytest.approx((0.1 * np.log(4.0) + 1) ** 2)


def test_the_shares_add_up_to_the_uncut_layer(cfg, family):
    """One dense and ONE routed layer, so that the routed layer is the last:
    the stream each share of the experts gives, less the stream with no
    expert held (the shared expert and everything a chip computes alike,
    counted once), summed over the shares, is what the uncut layer adds."""
    two = dict(cfg, num_hidden_layers=2, n_routed_experts=16,
               deployment={"n_routed_experts_published": 16, "experts_first": 0})
    t = family.device_weights(family.program_config(two), 9, jnp.float32)
    tokens = np.arange(40).reshape(1, 40) % cfg["vocab_size"]

    def stream(first, count):
        part = dict(t, **{k: type(t[k])(t[k].packed[:, first:first + count],
                                       t[k].scale_bits[:, first:first + count])
                          for k in ("w1", "w2", "w3")})
        with jax.default_matmul_precision("highest"):
            return np.asarray(family.reference_forward(two, part, tokens, held_range=(first, count)),
                              np.float64)

    uncut, none = stream(0, 16), stream(0, 0)
    parts = sum(stream(first, 8) - none for first in (0, 8))
    np.testing.assert_allclose(parts + none, uncut, rtol=1e-4, atol=1e-5)
    assert np.abs(uncut - none).max() > 0.01  # the experts add something to be split


def test_the_chosen_sets_hold_k_experts_of_the_best_groups(cfg, family, sample):
    t = sample[0]
    tokens = np.arange(2 * 24).reshape(2, 24) % cfg["vocab_size"]
    routes = []
    with jax.default_matmul_precision("highest"):
        family.reference_forward(cfg, t, tokens, routes=routes)
    assert len(routes) == 2 * (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])
    for r in routes:
        assert r.shape[-1] == 16 and (r.sum(-1) == cfg["num_experts_per_tok"]).all()
        groups_used = r.reshape(*r.shape[:-1], cfg["n_group"], -1).any(-1).sum(-1)
        assert (groups_used <= cfg["topk_group"]).all()


def test_lane_state_covers_all_three_leaves(cfg, family, sample):
    from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

    config, t = family.program_config(cfg), sample[0]
    engine = InferenceEngine(config, family.assemble_params(config, t), n_lanes=4,
                             cache_dtype=jnp.float32)
    assert [leaf.shape[-1] for leaf in engine.cache] == [64, 128, 32]
    prompt = list(range(2, 42))
    engine.prefill(0, prompt)
    engine.prefill(1, prompt)
    assert family.lane_state_rel_err(engine, 0, 1, 40) == 0.0
    engine.cache = engine.cache._replace(ik=engine.cache.ik.at[:, 1, 3].add(1.0))
    assert family.lane_state_rel_err(engine, 0, 1, 40) > 1e-3  # the index keys too


def test_the_rooflines_count_the_work_by_hand():
    bench = cells.load_benchmark()
    cfg = cells.load_config_file(bench, "deepseek-v3.2")
    c = cells.load_family(cfg).program_config(cfg)
    rows = 9 * 8 * 10_000.0  # 8 lanes of 10000 rows held, 9 layers
    nbytes, flops = sparse_roofline.indexer_step_work(c, rows, lanes=8)
    keys = rows * 128 * 2
    q40 = lambda m, di, do: di // 2 * do + di // 32 * do * 2 + m * di * 2 + m * do * 2
    proj = 9 * (q40(8, 1536, 8192) + q40(8, 7168, 128) + 7168 * 64 * 4)
    assert nbytes == keys + proj
    assert flops == rows * 64 * 258 + 9 * 2 * 8 * (1536 * 8192 + 7168 * 128 + 7168 * 64)
    chosen = 9 * 8 * 2048.0
    nbytes, flops = sparse_roofline.sparse_attention_step_work(c, chosen, lanes=8)
    kv_b = 128 * 512 * 256
    assert nbytes == chosen * 640 * 2 + 9 * kv_b * 2
    assert flops == chosen * 128 * 2 * (1024 + 64) + 9 * 8 * 2 * kv_b
    # at the chip's peaks both are some tenths of a millisecond a step: bytes bound
    assert keys / 819e9 > rows * 64 * 258 / 197e12


@pytest.mark.parametrize("metric", ["indexer_step_ms", "sparse_select_step_ms",
                                    "indexer_decode_roofline", "sparse_attention_decode_roofline"])
def test_a_new_reader_finds_nothing_in_a_program_without_the_scopes(metric):
    """The parent commit's program, and a run with no device trace: the
    reader returns None and does not raise."""
    read = cells.load_module(os.path.join(BENCH_DIR, "metrics", metric + ".py"), "m_" + metric).read
    ctx = SimpleNamespace(trace=None, counters={}, peaks=None, config=None, lanes=8)
    assert read(ctx) is None
    ctx.counters = {"indexer_rows_scored": 10, "sparse_rows_selected": 5, "decode_steps": 2}
    assert read(ctx) is None


def test_a_decode_scope_is_read_from_a_decode_step_or_a_fused_steps_decode_half(monkeypatch):
    """A stretch of a window that is mostly admissions may hold no pipelined
    decode step: the decode half of its fused steps is the same batch."""
    from harness import stepclass

    red = {"classes": {
        "dlstep.decode": {"executions": 3, "pair_ms": {("decode", "dl.indexer"): 1.2}},
        "dlstep.fused.b256": {"executions": 1, "pair_ms": {("decode", "dl.indexer"): 1.0}},
        "dlstep.fused.b512": {"executions": 5, "pair_ms": {("decode", "dl.indexer"): 1.1,
                                                          ("prefill", "dl.indexer"): 9.0}}}}
    monkeypatch.setattr(stepclass, "for_ctx", lambda ctx: red)
    ctx = SimpleNamespace(counters={"decode_steps": 10}, peaks={"hbm_bytes_per_s": 1e9, "flops_per_s": 1e12})
    assert sparse_roofline.decode_scope_ms(ctx, "dl.indexer") == 1.2
    del red["classes"]["dlstep.decode"]
    assert sparse_roofline.decode_scope_ms(ctx, "dl.indexer") == 1.1  # the class seen most
    assert sparse_roofline.decode_scope_ms(ctx, "dl.sparse_select") is None
    # 5.5e5 bytes a step at 1e9 bytes/s is 0.55 ms of the 1.1: 50 %
    share = sparse_roofline.roofline_share(ctx, "dl.indexer", lambda steps: (5.5e6 / steps, 1.0))
    assert share == pytest.approx(50.0)
    assert sparse_roofline.roofline_share(ctx, "dl.sparse_select", lambda steps: (1.0, 1.0)) is None


@pytest.fixture(scope="module")
def rehearsed():
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload",
           "tiny_v32_saturated", "--seed", "3000000019", "--seconds", "2", "--trace", "1",
           "--rehearse", "--benchmark-file", os.path.join(REHEARSAL, "BENCHMARK_v32.json")]
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    p = subprocess.run(cmd, capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_the_cells_control_flow_runs_as_a_rehearsal(rehearsed):
    res, err = rehearsed
    assert res["rehearsal"] is True and res["metrics"] == {} and res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 10 and res["compiles_in_window"] == 0
    values = res["rehearsal_values"]
    assert values["pipeline_flushes"]["value"] == 0 and values["jit_compiles_after_warmup"]["value"] == 0
    # chosen rows over held rows: under 100 % because contexts pass index_topk
    assert 0 < values["attention_kv_read_share"]["value"] < 100
    assert 0 < values["moe_expert_read_share"]["value"] <= 50  # 8 of 16 experts held
    assert "indexer_step_ms" not in values  # no device plane in a CPU trace
    assert '"attention_path": "sparse_topk"' in err and '"experts_held": "8/16"' in err
