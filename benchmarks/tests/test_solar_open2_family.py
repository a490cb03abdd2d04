"""The solar_open2 family (`families/solar_open2.py`) at a toy size on the CPU
(8 layers G D D D G D D D, 4 delta heads of 16 with convs of 4 taps and gates
of rank 8, 4 query and 2 kv heads, 16 experts of which 4 are chosen and 4 held,
one shared): the engine against the family's plain reference through
`correct.compare`, the eight faults of the family's own and the f8 reference
that must fail, what the seeded decay, step and selection bias weigh, the held
share adding up with the shared expert counted once, the real configuration
and cell, the work counts behind the two rooflines, and the readers on a
program without their counters."""
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import control
import control_window
from harness import cells, correct, delta_roofline
from harness.cells import BENCH_DIR

REHEARSAL = os.path.join(BENCH_DIR, "tests", "rehearsal")
CELL = "solar_open2_longctx_saturated"
READERS = ("delta_state_step_ms", "delta_state_decode_roofline",
           "delta_chunk_prefill_roofline", "delta_conv_step_ms")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REHEARSAL, "configs", "tiny_solar_open2.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family(cfg):
    return cells.load_family(cfg)


@pytest.fixture(scope="module")
def seeded(cfg, family):
    """Seeded arrays and the sample sequences."""
    t = family.device_weights(family.program_config(cfg), 31, jnp.float32)
    prompts, forced = correct.sample_sequences(cfg, 31)
    return t, (prompts, forced, [correct.prefix_lengths(cfg, len(p)) for p in prompts])


@pytest.fixture(scope="module")
def sample(cfg, family, seeded):
    """The arrays, the sequences and the reference's logits."""
    t, seqs = seeded
    return t, seqs, correct.plain_logits(family, cfg, t, *seqs)


def _real():
    bench = cells.load_benchmark()
    cfg = cells.load_config_file(bench, "solar-open2-250b")
    return bench, cfg, cells.load_family(cfg)


def test_the_real_configuration_keeps_every_width_and_says_what_it_cut():
    bench, cfg, family = _real()
    assert family.__file__ == os.path.join(BENCH_DIR, "families", "solar_open2.py")
    c = family.program_config(cfg)
    assert (c.dim, c.n_heads, c.n_kv_heads, c.head_size, c.q_dim, c.kv_dim) == (
        4096, 64, 8, 128, 8192, 1024)
    assert (c.delta_n_heads, c.delta_head_dim, c.delta_conv_kernel, c.delta_gate_rank,
            c.delta_neg_eigval, c.delta_dim) == (64, 128, 4, 128, 1, 8192)
    assert (c.attn_output_gate, c.rope_type, c.norm_epsilon) == (1, 4, 1e-5)
    assert (c.moe_hidden_dim, c.shared_hidden_dim, c.n_dense_layers) == (1280, 1280, 0)
    assert (c.n_experts, c.n_active_experts, c.experts_held) == (320, 8, (0, 40))
    assert (c.moe_select_bias, c.moe_norm_topk, c.moe_n_group, c.moe_routed_scale) == (1, 1, 1, 1.0)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    line = [l for l in open(catalog) if '"name": "Solar-Open2-250B"' in l] if os.path.exists(
        catalog) else []
    if line:
        pub = json.loads(line[0])["config"]
        assert cfg["gqa_layers"] == [l for l in pub["gqa_layers"] if l < 12]
        reduced = set(cfg["reduced"])
        for key, value in pub.items():  # every other published key is the file's, unchanged
            assert key in reduced or cfg[key] == value, key
    # layers 0-11 of the published order: GQA at 0, 4, 8, three whole periods
    assert c.layer_kinds == (0, 6, 6, 6) * 3
    assert (c.n_delta_layers, c.n_attention_layers, c.n_routed_layers) == (9, 3, 12)
    assert (c.n_layers, c.vocab_size, c.seq_len) == (12, 24576, 32768) and c.recurrent_state
    entry = next(e for e in bench["configs"] if e["name"] == "solar-open2-250b")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "gqa_layers", "max_position_embeddings", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    dep = cfg["deployment"]
    assert (dep["chips_per_layer"], dep["pipeline_stages"], dep["n_routed_experts_published"],
            dep["experts_first"]) == (8, 4, 320, 0)
    assert len(cfg["assumed"]) >= 7 and cfg["departures"]
    assert cfg["serving"]["lanes"] == 16 and cfg["serving"]["state_dtype"] == "float32"
    # the sample: inside a chunk of the chunk form, on its edge, in two chunks,
    # in many, and long ones
    lengths, chunk = cfg["correctness"]["prompt_tokens"], 32  # ops/delta_rule.py CHUNK
    assert any(n % chunk for n in lengths) and any(n % chunk == 0 for n in lengths)
    assert min(lengths) < 4 * chunk and max(lengths) > 8192
    assert cfg["correctness"]["decode_steps"] == 12
    assert [lengths[i] for i in cfg["correctness"]["route_admits"]] == [300, 700]
    # what holds the matrix state to float32 reads under 1e-4 or exactly 1
    assert 1e-3 < cfg["correctness"]["limits"]["route_kv_rel_err"] < 1


def test_the_cell_its_traffic_and_its_readers_are_the_issues():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b", "longctx_saturated", 1)
    e2e = {m["name"] for m in cells.cell_metrics(bench, CELL, "end_to_end")}
    assert {"tokens_per_s", "itl_p50_ms", "itl_p99_ms", "setup_s"} <= e2e
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert set(mine) == set(READERS) and all(m["workloads"] == [CELL] for m in mine.values())
    assert mine["delta_chunk_prefill_roofline"]["moves"] == "tokens_per_s"
    assert len({m["layer"] for m in mine.values()}) == 1
    reported = {m["name"] for m in cells.cell_metrics(bench, CELL, "per_layer")}
    assert set(READERS) <= reported
    # the readers of scopes and of program times that need no key of the file
    assert {"attention_step_ms", "moe_experts_step_ms", "sampler_step_ms",
            "decode_step_device_ms", "fused_step_device_ms", "fused_decode_half_ms"} <= reported
    # the shares whose counts read keys this family's file does not have are not
    # the cell's, and the file takes no key on for a reader's sake
    assert not {"full_attention_decode_roofline", "linear_state_decode_roofline",
                "moe_decode_half_roofline"} & reported
    assert "moe_layer_freq" not in cells.load_config_file(bench, cell["config"])
    for other in bench["workloads"]:
        if other["name"] != CELL:
            names = {m["name"] for m in cells.cell_metrics(bench, other["name"], "per_layer")}
            assert not names & set(READERS)


def test_a_program_without_what_the_family_needs_is_refused_in_one_line(cfg, family, monkeypatch):
    from distributed_llama_multiusers_tpu.formats import model_file
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    fields = dict(LlamaConfig.__dataclass_fields__)
    del fields["delta_gate_rank"], fields["attn_output_gate"]
    monkeypatch.setattr(LlamaConfig, "__dataclass_fields__", fields)
    with pytest.raises(SystemExit, match="it has no LlamaConfig.delta_gate_rank, "
                                         "LlamaConfig.attn_output_gate"):
        family.program_config(cfg)
    monkeypatch.delattr(model_file.LayerKind, "DELTA")
    with pytest.raises(SystemExit, match="it has no LayerKind.DELTA"):
        family.program_config(cfg)


@pytest.mark.parametrize("wrong,match", [
    (dict(kda_use_full_proj=True), "kda_use_full_proj"), (dict(use_rope=True), "use_rope"),
    (dict(use_gqa_gate=False), "use_gqa_gate"), (dict(first_k_dense_replace=1), "first_k_dense"),
    (dict(linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
                              "num_kv_heads": 2}), "num_kv_heads"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings")])
def test_what_the_program_does_not_do_is_refused_by_name(cfg, family, wrong, match):
    with pytest.raises(SystemExit, match=match):
        family.program_config(dict(cfg, **wrong))


def test_engine_agrees_with_the_reference_and_the_routes_read_zero(cfg, family):
    sound = control.readings(family, cfg, "as_configured", [3_000_000_033], log=lambda s: None)
    assert all(r["ok"] for r in sound), sound
    # (the lanes of a pair agree to the bit: what is left is the share of a
    # matrix state's words that a bfloat16 holds exactly)
    assert all(r["route_kv_rel_err"] < 1e-3 and r["route_token_mismatches"] == 0 for r in sound)
    assert all(r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5 for r in sound)


@pytest.mark.parametrize("fault", ["no_decay", "head_decay", "b_without_2", "no_delta", "no_conv",
                                   "no_gqa_gate", "no_select_bias", "state_bf16"])
def test_every_fault_of_the_family_fails(cfg, family, fault):
    assert fault in family.FAULTS
    got = control_window.readings(family, cfg, [fault], [31], jnp.float32, log=lambda s: None)
    assert not got[0]["ok"], got[0]
    # the state's rounding is the smallest of them: still ten times the limit
    assert got[0]["decode_rel_err"] > (0.005 if fault == "state_bf16" else 0.03), got[0]


def test_the_lower_precision_reference_fails(cfg, family):
    lossy = control_window.readings(family, cfg, [control_window.F8], [31], jnp.float32,
                                    log=lambda s: None)
    assert not lossy[0]["ok"]


@pytest.mark.parametrize("wrong", [
    dict(gqa_layers=[0, 5]), dict(kda_allow_neg_eigval=False), dict(num_experts_per_tok=2),
    dict(norm_topk_prob=False), dict(rms_norm_eps=1e-2), dict(routed_scaling_factor=2.5),
    dict(linear_attn_assumed={"gate_rank": 8, "l2_norm_eps": 1e-2}),
])
def test_a_reference_of_another_model_is_told_apart(cfg, family, sample, wrong):
    t, seqs, want = sample
    got = correct.plain_logits(family, dict(cfg, **wrong), t, *seqs)
    assert correct.relative_errors(got, want).max() > 1e-3


def test_the_seeded_decay_and_step_span_their_ranges_and_the_bias_changes_the_chosen_sets(
        cfg, family, seeded):
    """Else a missing decay, a ``b`` without its 2 or a missing bias would hide
    inside the limits. From the seeded arrays and a normed input of rms 1: over
    64 rows a channel keeps between 0.05 and 0.99 of its state at the bias
    alone (the generator's ``RATE_RANGE``), the input's gate moves that, and
    ``b`` passes 1 on about half of the rows (no projection has a bias, so its
    logit is symmetric; the issue asked for a third or more)."""
    t, (prompts, _forced, _) = seeded
    rate = np.exp(np.asarray(t["delta_a_log"]))[..., None] * np.log1p(
        np.exp(np.asarray(t["delta_dt_bias"]).reshape(6, 4, 16)))
    kept = np.exp(-64.0 * rate)
    assert 0.045 < kept.min() < 0.12 and 0.93 < kept.max() < 0.991, (kept.min(), kept.max())
    assert np.quantile(kept, 0.1) < 0.5 < np.quantile(kept, 0.5)  # log-uniform rates
    n = np.random.default_rng(0).standard_normal((512, cfg["hidden_size"])).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        b_logit = n @ np.asarray(t["delta_b"][0])
        gate = np.asarray(family._matmul_block(
            jnp.asarray(n), t["delta_f1"].packed[0], t["delta_f1"].scales[0])
        ) @ np.asarray(t["delta_f2"][0])
    over_one = (b_logit > 0).mean()
    assert 0.33 < over_one < 0.67 and (2 / (1 + np.exp(-b_logit)) > 1.5).mean() > 0.1
    assert 0.6 < gate.std() < 1.6  # the decay is data-dependent: a rate moves by e^-1..e^1
    tokens = np.asarray([prompts[3]], np.int32)
    routes, unbiased = [], []
    with jax.default_matmul_precision("highest"):
        family.reference_forward(cfg, t, tokens, routes=routes)
        family.reference_forward(cfg, t, tokens, routes=unbiased, fault="no_select_bias")
    assert len(routes) == 8
    assert all((r.sum(axis=-1) == cfg["num_experts_per_tok"]).all() for r in routes)
    assert family.route_difference_share(routes, routes) == 0.0
    # (the first layer's FFN input is the same in both passes)
    assert family.route_difference_share(routes[:1], unbiased[:1]) > 0.05


def test_the_shares_add_up_to_the_uncut_layer(cfg, family):
    """ONE delta-rule layer, so that its FFN is the last: the routed term each
    of the 4 shares of 4 experts gives, summed, is the term of the layer that
    holds all 16; the shared expert is in none of them (counted once, by the
    layer that adds it)."""
    one = dict(cfg, num_hidden_layers=1, gqa_layers=[], n_routed_experts=16, deployment={})
    t = family.device_weights(family.program_config(one), 9, jnp.float32)
    tokens = np.arange(40).reshape(1, 40) % cfg["vocab_size"]

    def routed(first, count, **kw):
        part = dict(t, **{k: type(t[k])(t[k].packed[:, first:first + count],
                                       t[k].scale_bits[:, first:first + count])
                          for k in ("w1", "w2", "w3")})
        with jax.default_matmul_precision("highest"):
            return np.asarray(family.reference_forward(
                one, part, tokens, held_range=(first, count), **kw), np.float64)

    uncut = routed(0, 16, routed_only=True)
    parts = sum(routed(first, 4, routed_only=True) for first in (0, 4, 8, 12))
    np.testing.assert_allclose(parts, uncut, rtol=1e-4, atol=1e-5)
    assert np.abs(uncut).max() > 0.01 and np.abs(routed(0, 4, routed_only=True)).max() < np.abs(uncut).max()
    # the stream of the uncut layer is every share's routed term and ONE shared expert
    none = dict(t, **{k: type(t[k])(t[k].packed[:, :0], t[k].scale_bits[:, :0])
                      for k in ("w1", "w2", "w3")})
    with jax.default_matmul_precision("highest"):
        shared_only = np.asarray(family.reference_forward(one, none, tokens, held_range=(0, 0)),
                                 np.float64)
    np.testing.assert_allclose(shared_only + parts, routed(0, 16), rtol=1e-4, atol=1e-5)


def test_lane_state_covers_the_planes_the_matrices_and_the_windows(cfg, family):
    """And the precision the matrix state rests in, which two lanes of one
    engine cannot tell each other and the logits do not show (the
    configuration's ``limits_from``): a state carried in float32 reads under
    1e-3 of its words exact in bfloat16, one rounded to bfloat16 reads 1, over
    the limit of ``route_kv_rel_err``; and at the toy's float32 the engine's
    state after a prompt IS the reference's own carry."""
    from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

    limit = cfg["correctness"]["limits"]["route_kv_rel_err"]
    config = family.program_config(cfg)
    t = family.device_weights(config, 5, jnp.float32)
    engine = InferenceEngine(config, family.assemble_params(config, t), n_lanes=5,
                             prefill_buckets=(16, 32), cache_dtype=jnp.float32)
    tokens = [int(x) for x in np.random.default_rng(1).integers(2, 200, size=30)]
    engine.prefill(0, tokens)
    engine.prefill(1, tokens)
    engine.prefill(2, tokens[:29] + [tokens[29] ^ 1])
    engine.prefill(3, [7] * 40)  # lane 3 held another, longer request before ...
    engine.prefill(3, tokens)    # ... and nothing of it is compared
    assert family.lanes_rel_err(engine, 0, 1, 30) == 0.0 == family.lanes_rel_err(engine, 0, 3, 30)
    assert family.lane_state_rel_err(engine, 0, 1, 30) < limit
    assert family.lanes_rel_err(engine, 0, 2, 30) > 1e-3   # the last row differs
    # the matrices and the windows are compared whole, whatever n is given
    assert family.lanes_rel_err(engine, 0, 2, 29) > 1e-3
    for leaf in ("delta", "delta_conv"):
        was = getattr(engine.cache, leaf)
        engine.cache = engine.cache._replace(**{leaf: was.at[:, 1].add(1.0)})
        assert family.lane_state_rel_err(engine, 0, 1, 30) > 1e-3, leaf
        engine.cache = engine.cache._replace(**{leaf: was})
    # the state against the reference's carry, a layer each: 128 rows, whole
    # blocks of the reference's queries
    probe = [int(x) for x in np.random.default_rng(2).integers(2, 200, size=128)]
    engine.prefill(4, probe)
    want = family.reference_states(cfg, t, probe)
    got = np.asarray(engine.cache.delta[:, 4]).reshape(want.shape)
    assert len(want) == 6 and max(family.state_rel_errs(got, want)) < 1e-5
    rounded = family.reference_states(cfg, t, probe, fault="state_bf16")
    assert min(family.state_rel_errs(rounded, want)) > 1e-3
    assert family.bfloat16_exact_share(want) < 1e-3 > family.bfloat16_exact_share(got)
    assert family.bfloat16_exact_share(rounded) == 1.0 > limit
    # both lanes kept in bfloat16 agree with each other, and are told all the same
    was = engine.cache.delta
    engine.cache = engine.cache._replace(delta=was.astype(jnp.bfloat16).astype(jnp.float32))
    assert family.lane_state_rel_err(engine, 0, 1, 30) == 1.0 > limit


def test_the_rooflines_count_the_work_by_hand():
    _bench, cfg, _family = _real()
    r = delta_roofline
    assert r.heads_and_width(cfg) == (64, 128)
    assert r.lane_state_bytes(cfg) == 2 * 64 * 128 * 128 * 4 == 8388608
    assert r.row_bytes(cfg) == 4 * 8192 * 2 + (8192 + 64) * 4
    assert r.row_ops(cfg) == 7 * 64 * 128 * 128
    # 16 live lanes, 9 layers: the state in and out, and 144 rows
    moved = 16 * 9 * 8388608
    assert r.decode_state_bytes(cfg, moved) == moved + 144 * r.row_bytes(cfg)


@pytest.mark.parametrize("metric", READERS)
def test_a_new_reader_finds_nothing_in_a_program_without_the_counters(metric):
    """The parent commit's program, and a run with no device trace: the
    reader returns None and does not raise."""
    _bench, cfg, _family = _real()
    read = cells.load_module(os.path.join(BENCH_DIR, "metrics", metric + ".py"), "m_" + metric).read
    ctx = SimpleNamespace(trace=None, counters={}, peaks=None, config=None, cfg=cfg, lanes=16,
                          kv_dtype="bfloat16")
    assert read(ctx) is None
    ctx.counters = {"decode_steps": 2, "fused_steps": 3, "linear_state_bytes_moved": 5}
    assert read(ctx) is None
    ctx.counters.update(delta_state_bytes_moved=100, delta_rows_computed=50)
    assert read(ctx) is None  # the counters are there, no trace is
    # and on a configuration of another family (an accepted cell's file)
    other = cells.load_config_file(_bench, "minicpm-sala")
    ctx.cfg = other
    assert read(ctx) is None


def test_the_readers_on_counters_and_a_reduced_stretch(monkeypatch):
    from harness import stepclass

    _bench, cfg, _family = _real()
    red = {"classes": {
        "dlstep.fused.b512": {"executions": 4, "pair_ms": {
            ("prefill", "dl.delta_state"): 30.0, ("prefill", "dl.delta_conv"): 2.0,
            ("decode", "dl.delta_state"): 2.5, ("decode", "dl.delta_conv"): 0.25,
            ("decode", "dl.delta"): 1.0}}}}
    monkeypatch.setattr(stepclass, "for_ctx", lambda ctx: red)
    read = lambda m: cells.load_module(  # noqa: E731
        os.path.join(BENCH_DIR, "metrics", m + ".py"), "m_" + m).read
    steps, moved = 10, 16 * 9 * 8388608
    ctx = SimpleNamespace(
        cfg=cfg, kv_dtype="bfloat16", peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        counters={"decode_steps": steps, "fused_steps": 5, "delta_state_bytes_moved": steps * moved,
                  "delta_rows_computed": 5 * 512 * 9})
    assert read("delta_state_step_ms")(ctx) == pytest.approx(2.5)
    assert read("delta_conv_step_ms")(ctx) == pytest.approx(0.25)
    least = (moved + 144 * delta_roofline.row_bytes(cfg)) / 819e9
    assert read("delta_state_decode_roofline")(ctx) == pytest.approx(100 * least / 2.5e-3)
    rows = 512 * 9
    chunk = max(rows * delta_roofline.row_bytes(cfg) / 819e9, rows * 7 * 64 * 128 * 128 / 197e12)
    assert read("delta_chunk_prefill_roofline")(ctx) == pytest.approx(100 * chunk / 30e-3)
    assert 0 < read("delta_chunk_prefill_roofline")(ctx) < 100
