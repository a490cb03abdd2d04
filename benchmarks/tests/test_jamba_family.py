"""The jamba family (`families/jamba.py`) at a toy size on the CPU: the engine
against the family's plain reference through `correct.compare` (prefill, then
decode through the K/V rows and the running sums, a chunked prefill, fused
admissions beside decoding lanes, twins left parked), controls that must fail,
the reference's recurrence against the recurrence written out, the comparison
of two lanes' state, the roofline's byte counts by hand at one shape, and the
four readers this family's cell adds."""
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import control
from harness import cells, correct, ssm_roofline
from harness.cells import BENCH_DIR

REHEARSAL = os.path.join(BENCH_DIR, "tests", "rehearsal")
CELL = "jamba2_3b_chat_saturated"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REHEARSAL, "configs", "tiny_jamba.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family(cfg):
    return cells.load_family(cfg)


def test_the_real_configuration_is_whole_and_names_the_family():
    bench = cells.load_benchmark()
    cfg = cells.load_config_file(bench, "jamba2-3b")
    family = cells.load_family(cfg)
    assert family.__file__ == os.path.join(BENCH_DIR, "families", "jamba.py")
    assert all(callable(getattr(family, name)) for name in cells.FAMILY_EXPORTS)
    c = family.program_config(cfg)
    assert (c.n_layers, c.n_ssm_layers, c.n_attention_layers, c.n_conv_layers) == (28, 26, 2, 0)
    assert [l for l, k in enumerate(c.layer_kinds) if k == 0] == [7, 21]
    assert (c.dim, c.hidden_dim, c.n_heads, c.n_kv_heads, c.head_size) == (2560, 8192, 20, 1, 128)
    assert (c.ssm_d_inner, c.ssm_d_state, c.ssm_dt_rank, c.ssm_conv_kernel) == (5120, 16, 160, 4)
    assert (c.vocab_size, c.seq_len, c.n_experts, c.norm_epsilon) == (65536, 2048, 0, 1e-6)
    assert c.rope_type == 4 and c.recurrent_state and c.ssm_conv_bias == 1 and c.ssm_inner_norms == 1
    entry = next(e for e in bench["configs"] if e["name"] == "jamba2-3b")
    assert entry["reduced"] == list(cfg["reduced"]) == ["max_position_embeddings"]
    # every number of the catalog's config under its own key
    published = {"attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
                 "expert_layer_period": 2, "hidden_size": 2560, "intermediate_size": 8192,
                 "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
                 "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
                 "num_hidden_layers": 28, "num_key_value_heads": 1, "num_logits_to_keep": 1,
                 "rms_norm_eps": 1e-06, "vocab_size": 65536}
    assert {k: cfg[k] for k in published} == published
    cell = cells.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("jamba2-3b", "chat_saturated", 1)
    assert cfg["serving"]["lanes"] == 256 and max(cfg["correctness"]["prompt_tokens"]) > 1024
    mine = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == ["ssm_mixer_step_ms", "ssm_state_decode_roofline", "ssm_scan_b1024_ms",
                    "ssm_scan_prefill_roofline"]
    with pytest.raises(SystemExit, match="num_experts 1"):
        family.program_config(dict(cfg, num_experts=16))


def test_engine_agrees_with_the_reference_and_the_routes_read_zero(cfg, family):
    sound = control.readings(family, cfg, "as_configured", [3_000_000_033], log=lambda s: None)
    assert all(r["ok"] for r in sound), sound
    assert all(r["route_kv_rel_err"] == 0.0 and r["route_token_mismatches"] == 0 for r in sound)
    # float32 where float32 is stated reads 6e-7; bfloat16 activations read
    # 1e-2 and more, three orders above this limit
    assert all(r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5 for r in sound)


def test_admissions_swapped_fail_by_the_state_and_by_the_tokens(cfg, family):
    swapped = control.readings(family, cfg, "admits_swapped", [31], log=lambda s: None)
    assert not swapped[0]["ok"] and swapped[0]["route_kv_rel_err"] > 0.1


def test_the_lower_precision_reference_fails(cfg, family):
    lossy = control.readings(family, cfg, "reference_in_f8", [31], log=lambda s: None)
    assert not lossy[0]["ok"]


@pytest.fixture(scope="module")
def sample(cfg, family):
    """Seeded arrays, the sample sequences and the reference's logits."""
    t = family.device_weights(family.program_config(cfg), 31, jnp.float32)
    prompts, forced = correct.sample_sequences(cfg, 31)
    prefixes = [correct.prefix_lengths(cfg, len(p)) for p in prompts]
    return t, (prompts, forced, prefixes), correct.plain_logits(
        family, cfg, t, prompts, forced, prefixes)


@pytest.mark.parametrize("wrong", [
    dict(attn_layer_offset=1), dict(attn_layer_period=8), dict(rms_norm_eps=1e-2),
], ids=["offset", "period", "eps"])
def test_a_reference_of_another_model_is_told_apart(cfg, family, sample, wrong):
    t, seqs, want = sample
    got = correct.plain_logits(family, dict(cfg, **wrong), t, *seqs)
    assert correct._rms(correct.relative_errors(got, want)) > 0.01


@pytest.mark.parametrize("key", ["ssm_taps", "ssm_conv_bias", "ssm_dt_bias", "ssm_a_log", "ssm_d",
                                 "ssm_b_norm", "ssm_c_norm", "ssm_dt_norm", "ssm_dt_proj"])
def test_a_reference_blind_to_a_new_tensor_is_told_apart(cfg, family, sample, key):
    t, (prompts, forced, prefixes), want = sample
    fill = jnp.zeros_like if key == "ssm_d" else jnp.ones_like  # D is drawn as ones
    blind = dict(t, **{key: fill(t[key])})
    got = correct.plain_logits(family, cfg, blind, prompts, forced, prefixes)
    assert correct.relative_errors(got, want).max() > 0.01


def test_the_state_carries_a_share_of_the_mixer_and_remembers_far(cfg, family, sample):
    """With the gains as argued (`GAIN`, `BC_GAIN`) a sequence's last logits
    depend on its FIRST token, a hundred rows back, through the running sums
    alone would not show were the state a rounding error: a wrong state has to
    show in the logits."""
    t, (prompts, forced, prefixes), want = sample
    moved = [[p[0] ^ 1] + p[1:] for p in prompts]
    got = correct.plain_logits(family, cfg, t, moved, forced, prefixes)
    assert correct.relative_errors(got, want)[-1, -1] > 1e-3  # 100 + 4 rows later
    # the mixer's own initialisation: how long a state remembers
    a = -np.exp(np.asarray(t["ssm_a_log"][0, :, 0]))
    np.testing.assert_allclose(a, -np.arange(1, cfg["mamba_d_state"] + 1), rtol=1e-6)
    dt = np.log1p(np.exp(np.asarray(t["ssm_dt_bias"])))
    assert family.DT_MIN * 0.99 < dt.min() < 2e-3 and 0.05 < dt.max() < family.DT_MAX * 1.01
    assert (np.asarray(t["ssm_d"]) == 1.0).all()


def test_the_references_recurrence_is_the_recurrence_written_out(cfg, family, sample):
    """`_mamba_mixer` on one layer's arrays against numpy float64, a row at a
    time from S = 0, as the module's header writes it."""
    t = sample[0]
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 12, cfg["hidden_size"])).astype(np.float32)
    lw = family._planes(t, ("ssm_in", "ssm_x", "ssm_out"), 0)
    lw.update(rms=t["ssm_rms"][0], taps=t["ssm_taps"][0], dt_norm=t["ssm_dt_norm"][0],
              b_norm=t["ssm_b_norm"][0], c_norm=t["ssm_c_norm"][0], dt_proj=t["ssm_dt_proj"][0],
              dt_bias=t["ssm_dt_bias"][0], a_log=t["ssm_a_log"][0], d=t["ssm_d"][0],
              conv_bias=t["ssm_conv_bias"][0])
    n_state, rank, eps = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(family._mamba_mixer(jnp.asarray(x), lw, n_state=n_state, rank=rank, eps=eps))
    from harness.reference import dequant_q40

    f = lambda v: np.asarray(v, np.float64)
    norm = lambda v, g: v / np.sqrt(np.mean(v * v, -1, keepdims=True) + eps) * f(g)
    silu = lambda v: v / (1.0 + np.exp(-v))
    w_in, w_x, w_out = (f(dequant_q40(*lw[k])) for k in ("ssm_in", "ssm_x", "ssm_out"))
    h = f(x)[0]
    xz = norm(h, lw["rms"]) @ w_in
    xin, z = xz[:, :xz.shape[1] // 2], xz[:, xz.shape[1] // 2:]
    taps, k = f(lw["taps"]), lw["taps"].shape[0]
    pad = np.concatenate([np.zeros((k - 1, xin.shape[1])), xin])
    u = silu(sum(taps[j] * pad[j:j + len(xin)] for j in range(k)) + f(lw["conv_bias"]))
    dbc = u @ w_x
    dt, bm, cm = norm(dbc[:, :rank], lw["dt_norm"]), norm(dbc[:, rank:rank + n_state], lw["b_norm"]), \
        norm(dbc[:, rank + n_state:], lw["c_norm"])
    delta = np.log1p(np.exp(dt @ f(lw["dt_proj"]) + f(lw["dt_bias"])))
    a, s, ys = -np.exp(f(lw["a_log"])), np.zeros((n_state, u.shape[1])), []
    for i in range(len(u)):
        s = np.exp(delta[i] * a) * s + (delta[i] * u[i]) * bm[i][:, None]
        ys.append(cm[i] @ s + f(lw["d"]) * u[i])
    want = h + (np.stack(ys) * silu(z)) @ w_out
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-4)


def test_lane_state_covers_keys_values_and_the_whole_of_both_state_leaves(cfg, family, sample):
    from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

    config, t = family.program_config(cfg), sample[0]
    engine = InferenceEngine(config, family.assemble_params(config, t), n_lanes=4,
                             cache_dtype=jnp.float32)
    assert engine.cache.k.shape == (2, 4, 128, 32)  # the attention layers only
    assert engine.cache.ssm.shape == (6, 4, 8 * 256) and engine.cache.ssm_conv.shape == (6, 4, 3 * 256)
    prompt = list(range(2, 22))
    engine.prefill(0, prompt)
    engine.prefill(1, prompt)
    engine.prefill(2, prompt[:-1] + [99])
    assert family.lane_state_rel_err(engine, 0, 1, 20) == 0.0
    assert family.lane_state_rel_err(engine, 0, 2, 20) > 1e-3
    # rows [0, 19) of K and V agree; the running sums and the windows do not
    assert family.lane_state_rel_err(engine, 0, 2, 19) > 1e-3
    poked = engine.cache
    engine.cache = poked._replace(ssm=poked.ssm.at[5, 1, 7].add(1.0))
    assert family.lane_state_rel_err(engine, 0, 1, 20) > 1e-3  # the last layer's sum
    engine.cache = poked._replace(ssm_conv=poked.ssm_conv.at[0, 1, 700].add(1.0))
    assert family.lane_state_rel_err(engine, 0, 1, 20) > 1e-3  # the first layer's window
    # a cache without running sums is not this family's to compare
    toy = SimpleNamespace(cache=SimpleNamespace(k=jnp.zeros((1, 2, 4, 2)), v=jnp.zeros((1, 2, 4, 2))))
    assert family.lane_state_rel_err(toy, 0, 1, 2) is None


def test_the_rooflines_bytes_by_hand_at_the_published_shape():
    """E = 5120, N = 16: a lane's running sum in one layer is 16 x 5120 x 4 =
    327680 bytes, read and written once; a row moves 3 x 5120 + 2 x 16 numbers
    of two bytes."""
    c = SimpleNamespace(ssm_d_inner=5120, ssm_d_state=16)
    assert ssm_roofline.state_bytes(c) == 2 * 327680 == 655360
    assert ssm_roofline.row_bytes(c) == (15360 + 32) * 2 == 30784
    # a decode step of 100 live lanes over 26 layers
    assert ssm_roofline.decode_update_bytes(c, 2600) == 2600 * (655360 + 30784) == 1_783_974_400
    # a 1024-row chunk over 26 layers
    assert ssm_roofline.chunk_scan_bytes(c, 26 * 1024) == 26 * 1024 * 30784 == 819_593_216
    ctx = SimpleNamespace(peaks={"hbm_bytes_per_s": 819e9})
    assert ssm_roofline.hbm_share(ctx, 819e6, 2.0) == pytest.approx(50.0)
    assert ssm_roofline.hbm_share(ctx, 819e6, None) is None
    assert ssm_roofline.hbm_share(SimpleNamespace(peaks=None), 819e6, 2.0) is None


def test_the_four_readers_on_a_reduction_and_on_a_program_without_the_scopes(monkeypatch):
    from harness import stepclass

    read = {n: cells.load_module(os.path.join(BENCH_DIR, "metrics", n + ".py"), "m_" + n).read
            for n in ("ssm_mixer_step_ms", "ssm_state_decode_roofline", "ssm_scan_b1024_ms",
                      "ssm_scan_prefill_roofline")}
    config = SimpleNamespace(ssm_d_inner=5120, ssm_d_state=16)
    peaks = {"hbm_bytes_per_s": 819e9}
    # untraced, or a program from before the scopes and counters: nothing, no raise
    bare = SimpleNamespace(trace=None, counters={}, config=config, peaks=peaks)
    assert all(r(bare) is None for r in read.values())
    D, P = stepclass.DECODE, stepclass.PREFILL
    red = {"classes": {
        "dlstep.fused.b1024": {"executions": 2.0, "pair_ms": {
            (D, "dl.ssm"): 6.0, (D, "dl.ssm_scan"): 9.0, (P, "dl.ssm_scan"): 4.0, (P, "dl.ffn"): 30.0}},
        "dlstep.fused.b256": {"executions": 6.0, "pair_ms": {
            (D, "dl.ssm"): 5.0, (D, "dl.ssm_scan"): 8.0, (P, "dl.ssm_scan"): 1.0}},
    }}
    monkeypatch.setattr(stepclass, "for_ctx", lambda ctx: red)
    ctx = SimpleNamespace(
        trace={}, config=config, peaks=peaks,
        counters={"ssm_lane_steps": 26 * 100 * 50, "decode_steps": 50,
                  "ssm_rows_computed": 26 * (2 * 1024 + 6 * 256), "fused_steps": 8})
    # no pipelined decode step in the stretch: the most frequent fused class's decode half
    assert read["ssm_mixer_step_ms"](ctx) == pytest.approx(13.0)
    assert read["ssm_scan_b1024_ms"](ctx) == pytest.approx(4.0)
    need_ms = 1e3 * 2600 * (655360 + 30784) / 819e9
    assert read["ssm_state_decode_roofline"](ctx) == pytest.approx(100 * need_ms / 8.0)
    rows_a_step = 26 * (2 * 1024 + 6 * 256) / 8
    scan_ms = (2 * 4.0 + 6 * 1.0) / 8
    assert read["ssm_scan_prefill_roofline"](ctx) == pytest.approx(
        100 * (1e3 * rows_a_step * 30784 / 819e9) / scan_ms)
    monkeypatch.setattr(stepclass, "for_ctx", lambda ctx: {"classes": {
        "dlstep.fused.b256": {"executions": 3.0, "pair_ms": {(D, "dl.ffn"): 5.0}}}})
    assert all(r(ctx) is None for r in read.values())  # the parent's program: no such scope
