"""The mimo_v2_flash family (`families/mimo_v2_flash.py`) at a toy size on the
CPU (8 layers F W W W W F W W with layer 0 dense, window 8, kv heads 2 and 4,
keys 24 wide of which 8 rotate, values 16, two bases, 16 experts of which 4 are
held): the engine against the family's plain reference through
`correct.compare`, the six faults of the family's own and the f8 reference
that must fail, what the seeded sink and selection bias weigh, the held share
adding up, the real configuration and cell, the work counts behind the four
rooflines, and the readers on a program without their counters."""
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import control
import control_window
from harness import cells, correct, mixed_head_roofline
from harness.cells import BENCH_DIR

REHEARSAL = os.path.join(BENCH_DIR, "tests", "rehearsal")
CELL = "mimo_v2_flash_longctx_saturated"
READERS = ("full_attention_decode_roofline", "sink_window_decode_roofline",
           "mixed_head_prefill_attention_roofline", "moe_decode_half_roofline")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REHEARSAL, "configs", "tiny_mimo_v2_flash.json")) as f:
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


def _real():
    bench = cells.load_benchmark()
    cfg = cells.load_config_file(bench, "mimo-v2-flash")
    return bench, cfg, cells.load_family(cfg)


def test_the_real_configuration_keeps_every_width_and_says_what_it_cut():
    bench, cfg, family = _real()
    assert family.__file__ == os.path.join(BENCH_DIR, "families", "mimo_v2_flash.py")
    c = family.program_config(cfg)
    assert (c.dim, c.n_heads, c.n_kv_heads, c.window_n_kv_heads) == (4096, 64, 4, 8)
    assert (c.head_size, c.value_head_size, c.rope_dim, c.q_dim, c.o_dim) == (
        192, 128, 64, 12288, 8192)
    assert c.kv_widths() == (768, 512) and c.kv_widths(True) == (1536, 1024)
    assert (c.sliding_window, c.rope_theta, c.window_rope_theta) == (128, 5e6, 1e4)
    assert (c.attn_value_scale, c.window_sink, c.norm_epsilon) == (0.707, 1, 1e-5)
    assert (c.hidden_dim, c.moe_hidden_dim, c.shared_hidden_dim) == (16384, 2048, 0)
    assert (c.n_experts, c.n_active_experts, c.experts_held) == (256, 8, (0, 16))
    assert (c.moe_select_bias, c.moe_norm_topk, c.moe_n_group, c.n_dense_layers) == (1, 1, 1, 1)
    # layers 0-15 of the published lists, nothing reordered: 13 window to 3 full
    line = [l for l in open("/opt/skills/guides/model-configs/architectures.jsonl")
            if '"name": "MiMo-V2-Flash"' in l] if os.path.exists(
                "/opt/skills/guides/model-configs/architectures.jsonl") else []
    if line:
        pub = json.loads(line[0])["config"]
        assert cfg["hybrid_layer_pattern"] == pub["hybrid_layer_pattern"][:16]
        assert cfg["moe_layer_freq"] == pub["moe_layer_freq"][:16]
        reduced = set(cfg["reduced"])
        for key, value in pub.items():  # every other published key is the file's, unchanged
            assert key in reduced or cfg[key] == value, key
    assert c.layer_kinds == (0, 3, 3, 3, 3, 0, 3, 3, 3, 3, 3, 0, 3, 3, 3, 3)
    assert (c.n_window_layers, c.n_attention_layers, c.n_routed_layers) == (13, 3, 15)
    assert (c.n_layers, c.vocab_size, c.seq_len) == (16, 19072, 32768) and c.recurrent_state
    entry = next(e for e in bench["configs"] if e["name"] == "mimo-v2-flash")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "hybrid_layer_pattern", "max_position_embeddings", "moe_layer_freq", "n_routed_experts",
        "num_hidden_layers", "vocab_size"]
    dep = cfg["deployment"]
    assert (dep["chips_per_layer"], dep["pipeline_stages"], dep["n_routed_experts_published"],
            dep["experts_first"]) == (16, 3, 256, 0)
    assert len(cfg["assumed"]) >= 7 and cfg["departures"]
    assert cfg["serving"]["lanes"] == 16 and cfg["serving"]["prefill_buckets"] == [64, 256, 512]
    # the sample: under the window, across it while decoding, past the ring's
    # wrap, and long ones where the planes dominate and the taper engages
    ring, lengths = 768, cfg["correctness"]["prompt_tokens"]
    steps = cfg["correctness"]["decode_steps"]
    assert min(lengths) < 128 and any(n < 128 <= n + steps for n in lengths)
    assert any(ring < n < 2 * ring for n in lengths) and max(lengths) > 8192
    assert [lengths[i] for i in cfg["correctness"]["route_admits"]] == [300, 700]


def test_the_cell_its_traffic_and_its_readers_are_the_issues():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mimo-v2-flash", "longctx_saturated", 1)
    e2e = {m["name"] for m in cells.cell_metrics(bench, CELL, "end_to_end")}
    assert {"tokens_per_s", "itl_p50_ms", "itl_p99_ms", "setup_s"} <= e2e
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert set(mine) == set(READERS) and all(m["workloads"] == [CELL] for m in mine.values())
    assert mine["mixed_head_prefill_attention_roofline"]["moves"] == "itl_p99_ms"
    reported = {m["name"] for m in cells.cell_metrics(bench, CELL, "per_layer")}
    assert set(READERS) <= reported
    # the shares whose counts take one head shape for every layer are not the cell's
    assert not {"window_attention_decode_roofline", "prefill_attention_roofline"} & reported
    for other in bench["workloads"]:
        if other["name"] != CELL:
            names = {m["name"] for m in cells.cell_metrics(bench, other["name"], "per_layer")}
            assert not names & set(READERS)


def test_a_program_without_what_the_family_needs_is_refused_in_one_line(cfg, family, monkeypatch):
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    fields = dict(LlamaConfig.__dataclass_fields__)
    del fields["window_sink"], fields["rotary_dim"]
    monkeypatch.setattr(LlamaConfig, "__dataclass_fields__", fields)
    with pytest.raises(SystemExit, match="it has no LlamaConfig.rotary_dim, LlamaConfig.window_sink"):
        family.program_config(cfg)


@pytest.mark.parametrize("wrong,match", [
    (dict(add_swa_attention_sink_bias=False), "add_swa_attention_sink_bias"),
    (dict(add_full_attention_sink_bias=True), "add_full_attention_sink_bias"),
    (dict(swa_head_dim=32), "swa_head_dim"), (dict(n_shared_experts=1), "n_shared_experts"),
    (dict(routed_scaling_factor=2.5), "routed_scaling_factor"), (dict(n_group=2), "n_group")])
def test_what_the_program_does_not_do_is_refused_by_name(cfg, family, wrong, match):
    with pytest.raises(SystemExit, match=match):
        family.program_config(dict(cfg, **wrong))


def test_engine_agrees_with_the_reference_and_the_routes_read_zero(cfg, family, monkeypatch):
    from distributed_llama_multiusers_tpu.runtime import engine as engine_mod

    # `control.py` builds its engine with the default ladder: the toy's own
    real = engine_mod.InferenceEngine
    buckets = tuple(cfg["serving"]["prefill_buckets"])

    class ToyLadder(real):
        def __init__(self, *a, **kw):
            super().__init__(*a, prefill_buckets=buckets, **kw)

    monkeypatch.setattr(engine_mod, "InferenceEngine", ToyLadder)
    sound = control.readings(family, cfg, "as_configured", [3_000_000_033], log=lambda s: None)
    assert all(r["ok"] for r in sound), sound
    assert all(r["route_kv_rel_err"] == 0.0 and r["route_token_mismatches"] == 0 for r in sound)
    assert all(r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5 for r in sound)


@pytest.mark.parametrize("fault", ["no_sink", "no_window", "window_at_full_base",
                                   "rotate_whole_head", "no_value_scale", "no_select_bias"])
def test_every_fault_of_the_family_fails(cfg, family, fault):
    assert fault in family.FAULTS
    got = control_window.readings(family, cfg, [fault], [31], jnp.float32, log=lambda s: None)
    assert not got[0]["ok"] and got[0]["decode_rel_err"] > 0.05, got[0]


def test_the_lower_precision_reference_fails(cfg, family):
    lossy = control_window.readings(family, cfg, [control_window.F8], [31], jnp.float32,
                                    log=lambda s: None)
    assert not lossy[0]["ok"]


@pytest.mark.parametrize("wrong", [
    dict(sliding_window=4), dict(swa_rope_theta=5000000), dict(rope_theta=10000),
    dict(partial_rotary_factor=0.5), dict(attention_value_scale=1.0),
    dict(num_experts_per_tok=2), dict(norm_topk_prob=False), dict(layernorm_epsilon=1e-2),
    dict(hybrid_layer_pattern=[0, 1, 1, 1, 1, 1, 1, 0]),
])
def test_a_reference_of_another_model_is_told_apart(cfg, family, sample, wrong):
    t, seqs, want = sample
    got = correct.plain_logits(family, dict(cfg, **wrong), t, *seqs)
    assert correct.relative_errors(got, want).max() > 1e-3


def test_the_seeded_sink_holds_mass_and_the_bias_changes_the_chosen_sets(cfg, family, sample):
    """Else a missing sink or bias would hide inside the limits: at the toy's
    size the sink holds 0.46-0.75 of a window row's mass, a layer (the real
    size's reading is in the configuration's ``limits_from``), and the bias
    left out changes the chosen set of a fifth of the (row, layer) pairs."""
    t, (prompts, _forced, _), _ = sample
    tokens = np.asarray([prompts[4]], np.int32)
    mass, routes, unbiased = [], [], []
    with jax.default_matmul_precision("highest"):
        family.reference_forward(cfg, t, tokens, sink_mass=mass, routes=routes)
        family.reference_forward(cfg, t, tokens, routes=unbiased, fault="no_select_bias")
    assert len(mass) == 6 and all(0.1 < m < 0.9 for m in mass), mass
    assert len(routes) == 7
    assert all((r.sum(axis=-1) == cfg["num_experts_per_tok"]).all() for r in routes)
    assert family.route_difference_share(routes, routes) == 0.0
    # (the first routed layer's input is the same in both passes)
    assert family.route_difference_share(routes[:1], unbiased[:1]) > 0.05
    lo, hi = family.sink_range(128)
    assert 8.0 < lo < 8.6 and 10.2 < hi < 10.8  # about a tenth to a half of exp(10.5)


def test_the_shares_add_up_to_the_uncut_layer(cfg, family):
    """TWO layers, the dense one and one routed, so that the routed layer is
    the last: its FFN term each of the 4 shares of 4 experts gives, summed, is
    the term of the layer that holds all 16."""
    two = dict(cfg, num_hidden_layers=2, hybrid_layer_pattern=[0, 1], moe_layer_freq=[0, 1],
               n_routed_experts=16, deployment={})
    t = family.device_weights(family.program_config(two), 9, jnp.float32)
    tokens = np.arange(40).reshape(1, 40) % cfg["vocab_size"]

    def routed(first, count):
        part = dict(t, **{k: type(t[k])(t[k].packed[:, first:first + count],
                                       t[k].scale_bits[:, first:first + count])
                          for k in ("w1", "w2", "w3")})
        with jax.default_matmul_precision("highest"):
            return np.asarray(family.reference_forward(
                two, part, tokens, held_range=(first, count), routed_only=True), np.float64)

    uncut = routed(0, 16)
    parts = sum(routed(first, 4) for first in (0, 4, 8, 12))
    np.testing.assert_allclose(parts, uncut, rtol=1e-4, atol=1e-5)
    assert np.abs(uncut).max() > 0.01 and np.abs(routed(0, 4)).max() < np.abs(uncut).max()


def test_lane_state_covers_the_planes_and_the_rows_of_the_ring_a_step_can_read(cfg, family):
    from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

    config = family.program_config(cfg)
    t = family.device_weights(config, 5, jnp.float32)
    engine = InferenceEngine(config, family.assemble_params(config, t), n_lanes=4,
                             prefill_buckets=(2, 4), cache_dtype=jnp.float32)
    tokens = [int(x) for x in np.random.default_rng(1).integers(2, 200, size=30)]
    engine.prefill(0, tokens)
    engine.prefill(1, tokens)
    engine.prefill(2, tokens[:29] + [tokens[29] ^ 1])
    engine.prefill(3, [7] * 40)  # lane 3 held another, longer request before ...
    engine.prefill(3, tokens)    # ... and nothing of it is compared
    assert family.lane_state_rel_err(engine, 0, 1, 30) == 0.0
    assert family.lane_state_rel_err(engine, 0, 3, 30) == 0.0
    assert family.lane_state_rel_err(engine, 0, 2, 30) > 1e-3   # the last row differs
    assert family.lane_state_rel_err(engine, 0, 2, 29) == 0.0   # and only the last


def test_the_rooflines_count_the_work_by_hand():
    _bench, cfg, _family = _real()
    r = mixed_head_roofline
    assert r.kv_row_bytes(cfg, r.FULL) == 2560 and r.kv_row_bytes(cfg, r.WINDOW) == 5120
    assert (r.n_layers_of(cfg, r.FULL), r.n_layers_of(cfg, r.WINDOW)) == (3, 13)
    # 16 lanes at 12k positions: three planes; thirteen rings of 128 rows
    assert r.decode_read_bytes(cfg, r.FULL, 16 * 12000.0) == 3 * 16 * 12000 * 2560
    assert r.decode_read_bytes(cfg, r.WINDOW, 16 * 128.0) == 13 * 16 * 128 * 5120
    assert r.prefill_attention_flops(cfg, 1000.0) == 1000 * 256 * 64 * 320 * 2
    assert r.routed_steps_counted(cfg, 10 * 15 * 256.0) == 10
    shape = r.routed_shape(cfg)
    assert (shape.dim, shape.moe_hidden_dim) == (4096, 2048)


@pytest.mark.parametrize("metric", READERS)
def test_a_new_reader_finds_nothing_in_a_program_without_the_counters(metric):
    """The parent commit's program, and a run with no device trace: the
    reader returns None and does not raise."""
    _bench, cfg, _family = _real()
    read = cells.load_module(os.path.join(BENCH_DIR, "metrics", metric + ".py"), "m_" + metric).read
    ctx = SimpleNamespace(trace=None, counters={}, peaks=None, config=None, cfg=cfg, lanes=16,
                          kv_dtype="bfloat16")
    assert read(ctx) is None
    ctx.counters = {"decode_steps": 2, "fused_steps": 3, "attn_kv_rows_read": 5,
                    "prefill_attn_blocks_causal": 7, "moe_slabs_whole": 15 * 256,
                    "moe_slabs_read": 9, "moe_assignments": 11}
    assert read(ctx) is None
    ctx.counters.update(attn_full_rows_needed=100, attn_window_rows_needed=50)
    assert read(ctx) is None  # the counters are there, no trace is


def test_the_readers_on_counters_and_a_reduced_stretch(monkeypatch):
    from harness import stepclass

    _bench, cfg, _family = _real()
    red = {"classes": {
        "dlstep.fused.b512": {"executions": 4, "pair_ms": {
            ("prefill", "dl.attention"): 30.0, ("prefill", "dl.window_attention"): 10.0,
            ("decode", "dl.attention"): 2.0, ("decode", "dl.window_attention"): 1.0,
            ("decode", "dl.experts"): 3.0}}}}
    monkeypatch.setattr(stepclass, "for_ctx", lambda ctx: red)
    read = lambda m: cells.load_module(  # noqa: E731
        os.path.join(BENCH_DIR, "metrics", m + ".py"), "m_" + m).read
    steps = 10
    ctx = SimpleNamespace(
        cfg=cfg, kv_dtype="bfloat16", peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        counters={"decode_steps": steps, "fused_steps": 5,
                  "attn_full_rows_needed": steps * 16 * 12000,
                  "attn_window_rows_needed": steps * 16 * 128,
                  "prefill_attn_blocks_causal": 5 * 100_000,
                  "moe_slabs_whole": steps * 15 * 256, "moe_slabs_read": steps * 90,
                  "moe_assignments": steps * 120})
    full_ms = 3 * 16 * 12000 * 2560 / 819e9 * 1e3
    assert read("full_attention_decode_roofline")(ctx) == pytest.approx(100 * full_ms / 2.0)
    ring_ms = 13 * 16 * 128 * 5120 / 819e9 * 1e3
    assert read("sink_window_decode_roofline")(ctx) == pytest.approx(100 * ring_ms / 1.0)
    flops = 100_000 * 256 * 64 * 320 * 2
    assert read("mixed_head_prefill_attention_roofline")(ctx) == pytest.approx(
        100 * (flops / 197e12) / 30e-3)
    matrix = 4096 * 2048 // 2 + 4096 * 2048 // 32 * 2
    moe = 90 * 3 * matrix + 120 * 3 * (4096 + 2048) * 2
    assert read("moe_decode_half_roofline")(ctx) == pytest.approx(100 * (moe / 819e9) / 3e-3)
