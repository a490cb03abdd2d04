"""The cohere2_moe family (`families/cohere2_moe.py`) at a toy size on the CPU
(window 8, a ring of 12 rows, 4 layers = one period, 8 experts top-2, 2 shared
experts averaged, heads of 16 on 4 heads of a 32-wide stream): the engine
against the family's plain reference through `correct.compare` (prefill in
chunks of 4, then decode through the ring, for contexts under the window,
across it and past the wrap; fused admissions beside decoding lanes), the two
controls of the family's own that must fail, the held share adding up, the
real configuration and cell, the work counts behind the two rooflines, and
the four readers on a program without their scopes."""
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import control
import control_window
from harness import cells, correct, window_roofline
from harness.cells import BENCH_DIR

REHEARSAL = os.path.join(BENCH_DIR, "tests", "rehearsal")
CELL = "command_a_plus_longctx_saturated"
READERS = ("window_attention_step_ms", "window_attention_decode_roofline",
           "attn_window_read_share", "prefill_attention_roofline")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REHEARSAL, "configs", "tiny_cohere2_moe.json")) as f:
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
    cfg = cells.load_config_file(bench, "command-a-plus-05-2026")
    family = cells.load_family(cfg)
    assert family.__file__ == os.path.join(BENCH_DIR, "families", "cohere2_moe.py")
    c = family.program_config(cfg)
    assert (c.dim, c.n_heads, c.n_kv_heads, c.head_size, c.q_dim, c.kv_dim) == (
        4096, 128, 8, 128, 16384, 1024)
    assert (c.moe_hidden_dim, c.shared_hidden_dim, c.shared_expert_scale) == (4096, 16384, 0.25)
    assert (c.n_experts, c.n_active_experts, c.experts_held) == (128, 8, (0, 16))
    assert (c.sliding_window, c.rope_theta, c.norm_epsilon) == (4096, 50000.0, 1e-5)
    assert c.layer_kinds == (3, 3, 3, 0) * 2 and (c.n_window_layers, c.n_attention_layers) == (6, 2)
    assert c.parallel_block and c.norm_kind == 1 and c.full_attention_nope and c.recurrent_state
    assert (c.n_layers, c.vocab_size, c.seq_len, c.n_routed_layers) == (8, 32768, 32768, 8)
    entry = next(e for e in bench["configs"] if e["name"] == "command-a-plus-05-2026")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "layer_types", "max_position_embeddings", "num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["deployment"]["chips_per_layer"] == 8 and cfg["assumed"] and cfg["departures"]
    assert cfg["serving"]["lanes"] == 16 and cfg["serving"]["prefill_buckets"] == [64, 256, 512]
    # the sample passes under the window, across it in a chunk, past the ring, past it twice
    ring = 4096 + 512
    lengths = cfg["correctness"]["prompt_tokens"]
    assert min(lengths) < 4096 < 4700 in lengths and any(ring < n < 2 * ring for n in lengths)
    assert max(lengths) > 2 * ring
    assert any(n < 4096 <= n + cfg["correctness"]["decode_steps"] for n in lengths)


def test_the_cell_its_traffic_and_its_readers_are_the_issues():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "command-a-plus-05-2026", "longctx_saturated", 1)
    assert "tokens_per_s" in {m["name"] for m in cells.cell_metrics(bench, CELL, "end_to_end")}
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert set(mine) == set(READERS) and all(m["workloads"] == [CELL] for m in mine.values())
    assert mine["prefill_attention_roofline"]["moves"] == "itl_p99_ms"
    reported = {m["name"] for m in cells.cell_metrics(bench, CELL, "per_layer")}
    assert set(READERS) <= reported and "attention_kv_read_share" in reported
    # no other cell reports the four
    for other in bench["workloads"]:
        if other["name"] != CELL:
            names = {m["name"] for m in cells.cell_metrics(bench, other["name"], "per_layer")}
            assert not names & set(READERS)


def test_a_program_without_what_the_family_needs_is_refused_in_one_line(cfg, family, monkeypatch):
    from distributed_llama_multiusers_tpu.formats import model_file

    monkeypatch.delattr(model_file.LayerKind, "WINDOW")
    with pytest.raises(SystemExit, match="it has no LayerKind.WINDOW"):
        family.program_config(cfg)


@pytest.mark.parametrize("wrong,match", [
    (dict(use_qk_norm=True), "use_qk_norm"), (dict(logit_scale=0.25), "logit_scale"),
    (dict(first_k_dense_replace=1), "first_k_dense_replace"),
    (dict(use_parallel_block=False), "use_parallel_block")])
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
    # float32 where float32 is stated reads 4e-7; bfloat16 activations read
    # 1e-2 and more
    assert all(r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5 for r in sound)


@pytest.mark.parametrize("fault", ["no_window", "rotate_full"])
def test_a_fault_in_either_kind_of_layer_fails(cfg, family, fault):
    got = control_window.readings(family, cfg, [fault], [31], jnp.float32, log=lambda s: None)
    assert not got[0]["ok"] and got[0]["decode_rel_err"] > 0.02
    # the window left out cannot show in a context under the window
    short = got[0]["by_sequence"][0]
    assert (short < 1e-5) if fault == "no_window" else (short > 1e-3)


def test_the_lower_precision_reference_fails(cfg, family):
    lossy = control_window.readings(family, cfg, [control_window.F8], [31], jnp.float32,
                                    log=lambda s: None)
    assert not lossy[0]["ok"]


@pytest.mark.parametrize("wrong", [
    dict(sliding_window=4), dict(rope_theta=10000), dict(num_experts_per_tok=1),
    dict(norm_topk_prob=False), dict(layer_norm_eps=1e-2), dict(num_shared_experts=4),
    dict(layer_types=["sliding_attention"] * 4), dict(layer_types=["full_attention"] * 4),
])
def test_a_reference_of_another_model_is_told_apart(cfg, family, sample, wrong):
    t, seqs, want = sample
    got = correct.plain_logits(family, dict(cfg, **wrong), t, *seqs)
    assert correct.relative_errors(got, want).max() > 1e-3


def test_the_shares_add_up_to_the_uncut_layer(cfg, family):
    """ONE layer, so that the routed layer is the last: the stream each share
    of the experts gives, less the stream with no expert held (attention and
    the averaged shared experts, which every chip computes alike, counted
    once), summed over the shares, is what the uncut layer adds."""
    one = dict(cfg, num_hidden_layers=1, layer_types=["sliding_attention"])
    t = family.device_weights(family.program_config(one), 9, jnp.float32)
    tokens = np.arange(40).reshape(1, 40) % cfg["vocab_size"]

    def stream(first, count):
        part = dict(t, **{k: type(t[k])(t[k].packed[:, first:first + count],
                                       t[k].scale_bits[:, first:first + count])
                          for k in ("w1", "w2", "w3")})
        with jax.default_matmul_precision("highest"):
            return np.asarray(family.reference_forward(one, part, tokens, held_range=(first, count)),
                              np.float64)

    uncut, none = stream(0, 8), stream(0, 0)
    parts = sum(stream(first, 2) - none for first in (0, 2, 4, 6))
    np.testing.assert_allclose(parts + none, uncut, rtol=1e-4, atol=1e-5)
    assert np.abs(uncut - none).max() > 0.01  # the experts add something to be split
    # and the shared experts are in `none`, once
    with jax.default_matmul_precision("highest"):
        bare = np.asarray(family.reference_forward(
            one, dict(t, **{k: type(t[k])(t[k].packed[:, :0], t[k].scale_bits[:, :0])
                            for k in ("w1", "w2", "w3")}),
            tokens, held_range=(0, 0), shared=False), np.float64)
    assert np.abs(none - bare).max() > 0.01


def test_the_chosen_sets_hold_k_experts(cfg, family, sample):
    t, (prompts, _forced, _), _ = sample
    routes = []
    with jax.default_matmul_precision("highest"):
        family.reference_forward(cfg, t, np.asarray([prompts[2]], np.int32), routes=routes)
    assert len(routes) == cfg["num_hidden_layers"]
    assert all((r.sum(axis=-1) == cfg["num_experts_per_tok"]).all() for r in routes)
    assert family.route_difference_share(routes, routes) == 0.0


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
    bench = cells.load_benchmark()
    cfg = cells.load_config_file(bench, "command-a-plus-05-2026")
    c = cells.load_family(cfg).program_config(cfg)
    assert window_roofline.kv_row_bytes(c) == 4096  # 4 KB a token a layer
    rows = 16 * 17 * 256.0  # 16 lanes past the window: 17 blocks each
    assert window_roofline.window_rows_needed(c, rows) == 16 * 4096
    assert window_roofline.window_decode_bytes(c, rows) == 6 * 16 * 4096 * 4096
    # a 512-row chunk at 8192 in a full-context layer: 33-34 blocks a row
    from distributed_llama_multiusers_tpu.ops import blocked_attention

    _, causal = blocked_attention.chunk_block_counts(8192, 512, 512, 32768)
    assert causal == 256 * 33 + 256 * 34
    flops = window_roofline.prefill_attention_flops(c, causal)
    assert flops == causal * 256 * 128 * 128 * 4
    ctx = SimpleNamespace(peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert window_roofline.mxu_share(ctx, 197e12 * 1e-3, 2.0) == pytest.approx(50.0)
    assert window_roofline.mxu_share(ctx, flops, None) is None


@pytest.mark.parametrize("metric", READERS)
def test_a_new_reader_finds_nothing_in_a_program_without_the_scopes(metric):
    """The parent commit's program, and a run with no device trace: the
    reader returns None and does not raise."""
    read = cells.load_module(os.path.join(BENCH_DIR, "metrics", metric + ".py"), "m_" + metric).read
    ctx = SimpleNamespace(trace=None, counters={}, peaks=None, config=None, lanes=8,
                          kv_dtype="bfloat16")
    assert read(ctx) is None
    ctx.counters = {"decode_steps": 2, "fused_steps": 3, "attn_kv_rows_read": 5}
    assert read(ctx) is None


def test_the_readers_on_counters_and_a_reduced_stretch(monkeypatch):
    from harness import stepclass

    bench = cells.load_benchmark()
    cfg = cells.load_config_file(bench, "command-a-plus-05-2026")
    c = cells.load_family(cfg).program_config(cfg)
    red = {"classes": {
        "dlstep.decode": {"executions": 3, "pair_ms": {("decode", "dl.window_attention"): 4.0,
                                                       ("decode", "dl.attention"): 2.0}},
        "dlstep.fused.b512": {"executions": 4, "pair_ms": {
            ("prefill", "dl.attention"): 30.0, ("prefill", "dl.window_attention"): 10.0,
            ("decode", "dl.window_attention"): 3.9}}}}
    monkeypatch.setattr(stepclass, "for_ctx", lambda ctx: red)
    read = lambda m: cells.load_module(  # noqa: E731
        os.path.join(BENCH_DIR, "metrics", m + ".py"), "m_" + m).read
    steps = 10
    ctx = SimpleNamespace(
        config=c, kv_dtype="bfloat16", peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        counters={"decode_steps": steps, "fused_steps": 5,
                  "attn_window_rows_read": steps * 16 * 17 * 256,
                  "attn_window_rows_plane": steps * 16 * 40 * 256,
                  "prefill_attn_blocks_causal": 5 * 2_000_000})
    assert read("window_attention_step_ms")(ctx) == 4.0
    assert read("attn_window_read_share")(ctx) == pytest.approx(100 * 17 / 40)
    least_ms = 6 * 16 * 4096 * 4096 / 819e9 * 1e3
    assert read("window_attention_decode_roofline")(ctx) == pytest.approx(100 * least_ms / 4.0)
    flops = 2_000_000 * 256 * 128 * 128 * 4
    assert read("prefill_attention_roofline")(ctx) == pytest.approx(
        100 * (flops / 197e12) / 40e-3)
