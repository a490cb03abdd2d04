"""Selective state-space layers in the layer-pattern block (models/hybrid.py,
ops/ssm_scan.py) through ``InferenceEngine`` and the scheduler at a toy size
on the CPU: prefill then decode through the cache against the benchmark's
plain reference, the fused and pipelined steps against the synchronous ones, no
rotation in the attention layers, the scanned layer loop against an unrolled
one, what is declined and counted, and 256 lanes. (The rule for a running sum
in every step family is tests/test_lane_state_contract.py's row ``jamba``.)

One engine for the file (``built``) and one warmed engine a lane count behind
``served``: a case builds an engine of its own only where the construction is
its subject (interpret mode, bfloat16, a monkeypatched layer loop)."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats import load_model_header
from distributed_llama_multiusers_tpu.formats.model_file import RopeType
from distributed_llama_multiusers_tpu.formats.synthetic import tiny_ssm_header, write_synthetic_model
from distributed_llama_multiusers_tpu.models import hybrid, llama, load_params_from_m
from distributed_llama_multiusers_tpu.models.hybrid import HybridCache, layer_periods
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

import latent_toy

CFG, FAMILY, CORRECT = latent_toy.toy("jamba")
SEQ = CFG["max_position_embeddings"]
PROMPT = [int(x) for x in np.random.default_rng(0).integers(2, CFG["vocab_size"], size=100)]


built = latent_toy.module_engine(FAMILY, CFG, seed=5, lanes=8)


@pytest.fixture(scope="module")
def eng(built):
    return built[0]


def test_the_cache_has_a_stack_a_kind_and_the_state_is_counted(eng):
    assert isinstance(eng.cache, HybridCache)
    assert eng.config.layer_kinds == (2, 2, 0, 2, 2, 2, 0, 2) and eng.config.rope_type == RopeType.NONE
    assert layer_periods(eng.config.layer_kinds) == (4, 2)
    assert eng.cache.k.shape == (2, 8, SEQ, 32) and eng.cache.conv.shape == (0, 8, 0)
    assert eng.cache.ssm.shape == (6, 8, 8 * 256) and eng.cache.ssm.dtype == jnp.float32
    assert eng.cache.ssm_conv.shape == (6, 8, 3 * 256)
    assert eng.lane_state_bytes == eng.cache.ssm.nbytes + eng.cache.ssm_conv.nbytes
    assert eng.stats.recurrent_state_bytes == eng.lane_state_bytes
    facts = eng.path_facts()
    assert facts["declined_for_recurrent_state"] == ["prefix_reuse", "speculation"]
    assert "mesh" in facts["refused_for_recurrent_state"] and not eng.supports_speculative
    assert eng.params.rope_cos is None and eng.params.ssm.a_log.dtype == jnp.float32


def test_engine_agrees_with_the_plain_reference_and_the_routes_read_zero(built):
    """Prefill, then decode through the cache, against the reference's full
    forward; the pipelined and fused programs against the synchronous ones on
    twins, the whole float32 state compared pair by pair."""
    e, tensors = built
    r = CORRECT.compare(FAMILY, CFG, tensors, e, 5)
    assert r["ok"], r
    assert r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5
    assert (r["route_greedy_gap"], r["route_nucleus_excess"], r["route_kv_rel_err"]) == (0, 0, 0)
    assert r["route_token_mismatches"] == 0 and r["route_tokens"] >= 20


def test_kernels_in_interpret_mode_agree_with_the_reference(pallas_interpret):
    """The one-row kernel (8 lanes, 256 channels) in every decode step; the
    chunk kernel does not tile 256 channels, so chunks take the scan over rows."""
    e, tensors = latent_toy.engine(FAMILY, CFG, 5)
    r = CORRECT.compare(FAMILY, CFG, tensors, e, 5)
    assert r["ok"], r
    assert r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5
    assert (r["route_greedy_gap"], r["route_nucleus_excess"], r["route_kv_rel_err"]) == (0, 0, 0)


def test_bfloat16_activations_keep_a_float32_state_and_are_told_apart():
    e, tensors = latent_toy.engine(FAMILY, CFG, 5, dtype=jnp.bfloat16)
    assert e.cache.ssm.dtype == jnp.float32 and e.cache.ssm_conv.dtype == jnp.bfloat16
    r = CORRECT.compare(FAMILY, CFG, tensors, e, 5)
    assert not r["ok"] and 1e-3 < r["prefill_rel_err"] < 0.1  # the f32 limits are 1e-3


def test_the_attention_layers_rotate_nothing(eng):
    """With no tables ``gqa_project`` hands the projections on as they are,
    and a prompt's logits do not depend on where in the lane's context it
    would sit for a rotation: the reference, which has no positional term at
    all, agrees (above)."""
    cfg = eng.config
    y = jnp.asarray(np.random.default_rng(1).standard_normal((1, 3, cfg.dim)), jnp.float32)
    w = lambda n: jnp.asarray(np.random.default_rng(n).standard_normal((cfg.dim, n)), jnp.float32)
    wq, wk, wv = w(cfg.dim), w(cfg.kv_dim), w(cfg.kv_dim + 0)
    pos = jnp.asarray([[5, 6, 7]], jnp.int32)
    q, k, v = llama.gqa_project(cfg, y, wq, wk, wv, pos, None, None)
    np.testing.assert_array_equal(np.asarray(q).reshape(1, 3, -1), np.asarray(y @ wq))
    np.testing.assert_array_equal(np.asarray(k).reshape(1, 3, -1), np.asarray(y @ wk))


def test_the_scanned_layer_loop_is_the_unrolled_one(eng, monkeypatch):
    """Two whole periods of ``M M A M`` in one scan (the file's engine) against
    the same eight layers unrolled (no period found: every layer in the odd
    tail; an engine of its own, the same seed's weights)."""
    scanned = eng
    row_s = np.asarray(scanned.prefill(0, PROMPT[:50])[0])
    monkeypatch.setattr(hybrid, "layer_periods", lambda kinds: (1, 0))
    unrolled, _ = latent_toy.engine(FAMILY, CFG, seed=5, lanes=4)
    row_u = np.asarray(unrolled.prefill(0, PROMPT[:50])[0])
    np.testing.assert_allclose(row_s, row_u, rtol=1e-5, atol=1e-5)
    assert FAMILY.lane_state_rel_err(scanned, 0, 0, 50) == 0.0
    np.testing.assert_allclose(np.asarray(scanned.cache.ssm[:, 0]), np.asarray(unrolled.cache.ssm[:, 0]),
                               rtol=1e-5, atol=1e-6)


def test_a_long_run_of_one_kind_is_a_scan_of_its_own_and_the_same_layers(tmp_path, monkeypatch):
    """``M M M M A`` twice: the four state-space layers of a period run as a
    scan inside the period's scan (``RUN_SCAN_MIN``), against the same ten
    layers with every run unrolled, and with no scan at all."""
    assert hybrid.kind_runs((2, 2, 2, 2, 0, 2)) == [(0, 4), (4, 1), (5, 1)]
    assert hybrid.kinds_after(2, (1, 0, 5)) == (1, 0, 6) and hybrid.kinds_after(0, (1, 0, 5)) == (2, 0, 5)
    header = tiny_ssm_header("MMMMAMMMMA", seq_len=128)
    write_synthetic_model(str(tmp_path / "m.m"), header, seed=5, scale=0.1)
    config, params = load_params_from_m(str(tmp_path / "m.m"), load_model_header(str(tmp_path / "m.m")),
                                        dtype=jnp.float32)
    assert layer_periods(config.layer_kinds) == (5, 2)

    def run():
        e = InferenceEngine(config, params, n_lanes=2, prefill_buckets=(16,))  # 16 + 16 + 8 of 16
        row = np.asarray(e.prefill(0, PROMPT[:40])[0])
        logits, _, _ = e.decode(np.asarray([7, 0], np.int32), np.asarray([40, 128], np.int32))
        return row, np.asarray(logits[0]), np.asarray(e.cache.ssm[:, 0]), np.asarray(e.cache.k[:, 0, :41])

    scanned = run()
    monkeypatch.setattr(hybrid, "RUN_SCAN_MIN", 99)
    unrolled = run()
    monkeypatch.setattr(hybrid, "layer_periods", lambda kinds: (1, 0))
    flat = run()
    for other in (unrolled, flat):
        for a, b in zip(scanned, other):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A synthetic ``M M A M`` checkpoint with dense FFNs through the real
    writer and loader."""
    return latent_toy.serving(tiny_ssm_header("MMAM", seq_len=128),
                              tmp_path_factory.mktemp("ssm"), scale=0.1)


def test_a_loaded_checkpoint_serves_and_admissions_reuse_lanes(served):
    """Six requests on two lanes: four are admitted into lanes that served
    before, by fused steps, and give the tokens they give alone (the same
    engine under a scheduler with the pipelined loop and fused admissions off,
    a request a run)."""
    shared = "the same long opening words of two requests, "
    prompts = [shared + "then one end", shared + "then another", "ab ab ab ab ab ab",
               "hello world hello", "lo lo lo world", shared + "and a third"]
    tokens, stats = served.serve(prompts)
    assert stats["state_zero_starts"] == 6 and stats["jit_compiles_after_warmup"] == 0
    assert stats["prefix_hits"] == 0 and stats["prefix_tokens_saved"] == 0
    assert stats["spec_steps"] == 0 and stats["pipeline_flushes"] == 0 and stats["fused_steps"] > 0
    # three state-space layers: the counters are a layer's rows times three
    assert stats["ssm_rows_scanned"] == 3 * stats["prefill_tokens"]
    assert stats["ssm_rows_computed"] == 3 * stats["prefill_bucket_rows"]
    assert stats["ssm_lane_steps"] > 0 and stats["ssm_lane_steps"] % 3 == 0
    for i, p in enumerate(prompts):
        alone, off = served.serve([p], pipelined=False, fused_prefill=False)
        assert alone[0] == tokens[i], (i, p)
        assert off["jit_compiles_after_warmup"] == 0 and off["fused_steps"] == 0


def test_256_lanes_serve_as_8_do(served):
    """The step programs, the sampler, the admitted lane's splice and the host
    loop at 256 lanes: 300 requests, every one the tokens it gives alone."""
    prompts = [f"request {i} says hello world {'ab ' * (i % 7)}" for i in range(300)]
    # (the pipelined loop takes the multi-step programs' place: none is warmed, none compiles)
    pipelined = latent_toy.Serving(served.config, served.params, served.tokenizer, multi_step=0)
    tokens, stats = pipelined.serve(prompts, lanes=256, max_tokens=6)
    assert stats["jit_compiles_after_warmup"] == 0 and stats["pipeline_flushes"] == 0
    assert stats["state_zero_starts"] == 300
    few, _ = pipelined.serve(prompts[:12], lanes=8, max_tokens=6)
    assert tokens[:12] == few
