"""Selective state-space layers in the layer-pattern block (models/hybrid.py,
ops/ssm_scan.py) through ``InferenceEngine`` and the scheduler at a toy size
on the CPU: prefill then decode through the cache against the benchmark's
plain reference, the fused and pipelined steps against the synchronous ones,
the rule for a running sum in every step family (a parked lane, a padded tail,
a second chunk, a start at position 0 in a lane that served before), no
rotation in the attention layers, the scanned layer loop against an unrolled
one, what is declined and counted, and 256 lanes."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats import load_model_header
from distributed_llama_multiusers_tpu.formats.model_file import RopeType
from distributed_llama_multiusers_tpu.formats.synthetic import (
    tiny_ssm_header,
    write_synthetic_model,
    write_synthetic_tokenizer,
)
from distributed_llama_multiusers_tpu.models import hybrid, llama, load_params_from_m
from distributed_llama_multiusers_tpu.models.hybrid import HybridCache, layer_periods
from distributed_llama_multiusers_tpu.ops import linear
from distributed_llama_multiusers_tpu.runtime import ContinuousBatchingScheduler, Request
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine, warmup_engine
from distributed_llama_multiusers_tpu.tokenizer import Tokenizer

import latent_toy

CFG, FAMILY, CORRECT = latent_toy.load("tiny_jamba.json")
SEQ = CFG["max_position_embeddings"]
PROMPT = [int(x) for x in np.random.default_rng(0).integers(2, CFG["vocab_size"], size=100)]


@pytest.fixture(scope="module")
def eng():
    return latent_toy.engine(FAMILY, CFG, seed=7, lanes=8)[0]


def _state(eng, lane):
    return np.concatenate([np.asarray(eng.cache.ssm[:, lane]).ravel(),
                           np.asarray(eng.cache.ssm_conv[:, lane], np.float32).ravel()])


def _park(eng, live: dict):
    tokens = np.zeros(eng.n_lanes, np.int32)
    positions = np.full(eng.n_lanes, SEQ, np.int32)
    for lane, (tok, pos) in live.items():
        tokens[lane], positions[lane] = tok, pos
    return tokens, positions


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


def test_engine_agrees_with_the_plain_reference_and_the_routes_read_zero():
    """Prefill, then decode through the cache, against the reference's full
    forward; the pipelined and fused programs against the synchronous ones on
    twins, the whole float32 state compared pair by pair."""
    e, tensors = latent_toy.engine(FAMILY, CFG, 5)
    r = CORRECT.compare(FAMILY, CFG, tensors, e, 5)
    assert r["ok"], r
    assert r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5
    assert (r["route_greedy_gap"], r["route_nucleus_excess"], r["route_kv_rel_err"]) == (0, 0, 0)
    assert r["route_token_mismatches"] == 0 and r["route_tokens"] >= 20


def test_kernels_in_interpret_mode_agree_with_the_reference():
    """The one-row kernel (8 lanes, 256 channels) in every decode step; the
    chunk kernel does not tile 256 channels, so chunks take the scan over rows."""
    linear.set_pallas_interpret(True)
    try:
        e, tensors = latent_toy.engine(FAMILY, CFG, 5)
        r = CORRECT.compare(FAMILY, CFG, tensors, e, 5)
    finally:
        linear.set_pallas_interpret(False)
    assert r["ok"], r
    assert r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5
    assert (r["route_greedy_gap"], r["route_nucleus_excess"], r["route_kv_rel_err"]) == (0, 0, 0)


def test_bfloat16_activations_keep_a_float32_state_and_are_told_apart():
    e, tensors = latent_toy.engine(FAMILY, CFG, 5, dtype=jnp.bfloat16)
    assert e.cache.ssm.dtype == jnp.float32 and e.cache.ssm_conv.dtype == jnp.bfloat16
    r = CORRECT.compare(FAMILY, CFG, tensors, e, 5)
    assert not r["ok"] and 1e-3 < r["prefill_rel_err"] < 0.1  # the f32 limits are 1e-3


@pytest.mark.parametrize("family", ["decode", "decode_nologits", "decode_multi", "decode_pl", "fused"])
def test_a_parked_lane_keeps_its_state_in_every_step_family(eng, family):
    eng.prefill(0, PROMPT[:20])
    eng.prefill(1, PROMPT[:30])
    before = _state(eng, 1)
    tokens, positions = _park(eng, {0: (5, 20)})
    if family == "decode":
        eng.decode(tokens, positions)
    elif family == "decode_nologits":
        eng.decode(tokens, positions, want_logits=False)
    elif family == "decode_multi":
        eng.decode_multi(tokens, positions, h=2)
    elif family == "decode_pl":
        eng.decode_pipelined(positions, tokens=tokens)
        eng.decode_pipelined(np.where(positions < SEQ, -1, positions).astype(np.int32))
        eng.pipeline_flush()
    else:
        eng.decode_prefill_fused(positions, p_lane=2, chunk=PROMPT[:10], tokens=tokens)
        eng.pipeline_flush()
    np.testing.assert_array_equal(_state(eng, 1), before)
    assert not np.array_equal(_state(eng, 0), before)


def test_a_padded_tail_is_ignored_and_token_by_token_is_the_same_state(eng):
    """20 tokens through the 64 bucket (44 rows of padding) against the same
    tokens one decode step each: other programs, the same running sum."""
    eng.prefill(0, PROMPT[:20])
    for i, tok in enumerate(PROMPT[:20]):
        eng.decode(*_park(eng, {1: (tok, i)}))
    assert FAMILY.lane_state_rel_err(eng, 0, 1, 20) < 1e-5
    eng.decode_prefill_fused(np.full(8, SEQ, np.int32), p_lane=2, chunk=PROMPT[:20],
                             tokens=np.zeros(8, np.int32))
    eng.pipeline_flush()
    assert FAMILY.lane_state_rel_err(eng, 0, 2, 20) < 1e-5
    # a state that absorbed the padding would differ in every channel
    eng.prefill(3, PROMPT[:20] + [0] * 44)
    assert FAMILY.lane_state_rel_err(eng, 0, 3, 20) > 1e-3


def test_a_second_chunk_continues_the_first(eng):
    eng.prefill(0, PROMPT)  # 64 + 36 through the 64 bucket
    eng.prefill(1, PROMPT[:30])
    eng.prefill(1, PROMPT[30:], start_pos=30)
    assert FAMILY.lane_state_rel_err(eng, 0, 1, 100) < 1e-5
    park = np.full(8, SEQ, np.int32)
    eng.decode_prefill_fused(park, p_lane=2, chunk=PROMPT[:16], tokens=np.zeros(8, np.int32))
    eng.decode_prefill_fused(park, p_lane=2, chunk=PROMPT[16:60], p_start=16)  # parked between
    eng.pipeline_flush()
    eng.prefill(3, PROMPT[:60])
    assert FAMILY.lane_state_rel_err(eng, 3, 2, 60) < 1e-5
    # a second chunk that restarted from zero is another state
    eng.prefill(4, PROMPT[16:60])
    assert FAMILY.lane_state_rel_err(eng, 3, 4, 1) > 1e-3


def test_position_zero_reads_zeros_in_a_lane_that_served_before(eng):
    eng.prefill(4, PROMPT[40:90])  # what an earlier request left behind
    dirty = _state(eng, 4).copy()
    zero_starts = eng.stats.state_zero_starts
    eng.prefill(4, PROMPT[:20])
    eng.prefill(5, PROMPT[60:70])
    eng.cache = eng.cache._replace(ssm=eng.cache.ssm.at[:, 5].set(0.0),
                                   ssm_conv=eng.cache.ssm_conv.at[:, 5].set(0.0))  # never used
    eng.prefill(5, PROMPT[:20])
    assert eng.stats.state_zero_starts == zero_starts + 3
    np.testing.assert_array_equal(_state(eng, 4), _state(eng, 5))
    assert not np.array_equal(_state(eng, 4), dirty)
    # a decode step at position 0 starts a sequence too
    eng.cache = eng.cache._replace(ssm=eng.cache.ssm.at[:, 6].set(3.0))
    eng.decode(*_park(eng, {6: (9, 0), 7: (9, 0)}))
    eng.cache = eng.cache._replace(ssm_conv=eng.cache.ssm_conv.at[:, 6].set(eng.cache.ssm_conv[:, 7]))
    np.testing.assert_array_equal(np.asarray(eng.cache.ssm[:, 6]), np.asarray(eng.cache.ssm[:, 7]))


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


def test_the_scanned_layer_loop_is_the_unrolled_one(monkeypatch):
    """Two whole periods of ``M M A M`` in one scan against the same eight
    layers unrolled (no period found: every layer in the odd tail)."""
    scanned, _ = latent_toy.engine(FAMILY, CFG, seed=9, lanes=4)
    row_s = np.asarray(scanned.prefill(0, PROMPT[:50])[0])
    monkeypatch.setattr(hybrid, "layer_periods", lambda kinds: (1, 0))
    unrolled, _ = latent_toy.engine(FAMILY, CFG, seed=9, lanes=4)
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
        e = InferenceEngine(config, params, n_lanes=2, prefill_buckets=(8, 16))
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


def test_verify_steps_and_lane_copies_are_refused(eng):
    n = eng.n_lanes
    z = np.zeros(n, np.int32)
    with pytest.raises(ValueError, match="without speculation"):
        eng.decode_spec(z, np.zeros((n, eng.SPEC_DRAFT), np.int32), z, z)
    with pytest.raises(RuntimeError, match="recurrent state"):
        eng.copy_lane(0, 1)
    config = FAMILY.program_config(CFG)
    params = FAMILY.assemble_params(config, FAMILY.device_weights(config, 3, jnp.float32))
    with pytest.raises(ValueError, match="6 state-space"):
        InferenceEngine(config, params, n_lanes=4, paged_kv=True)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A synthetic ``M M A M`` checkpoint with dense FFNs through the real
    writer and loader."""
    d = tmp_path_factory.mktemp("ssm")
    header = tiny_ssm_header("MMAM", seq_len=128)
    write_synthetic_model(str(d / "m.m"), header, seed=3, scale=0.1)
    write_synthetic_tokenizer(str(d / "t.t"), vocab_size=header.vocab_size)
    h = load_model_header(str(d / "m.m"))
    config, params = load_params_from_m(str(d / "m.m"), h, dtype=jnp.float32)
    return config, params, Tokenizer(str(d / "t.t"))


def _serve(served, prompts, lanes=2, max_tokens=8, **kw):
    config, params, tok = served
    engine = InferenceEngine(config, params, n_lanes=lanes, prefill_buckets=(8, 16))
    sched = ContinuousBatchingScheduler(engine, tok, **kw)
    warmup_engine(engine, spec=sched.speculative, multi_step=sched.multi_step)
    sched.start()
    try:
        reqs = [sched.submit(Request(prompt=p, max_tokens=max_tokens, temperature=0.0))
                for p in prompts]
        for r in reqs:
            r.future.result(timeout=600)
            assert r.error is None, r.error
    finally:
        sched.stop()
    return [list(r.generated_tokens) for r in reqs], engine.stats.snapshot()


def test_a_loaded_checkpoint_serves_and_admissions_reuse_lanes(served):
    """Six requests on two lanes: four are admitted into lanes that served
    before, by fused steps, and give the tokens they give alone."""
    shared = "the same long opening words of two requests, "
    prompts = [shared + "then one end", shared + "then another", "ab ab ab ab ab ab",
               "hello world hello", "lo lo lo world", shared + "and a third"]
    tokens, stats = _serve(served, prompts)
    assert stats["state_zero_starts"] == 6 and stats["jit_compiles_after_warmup"] == 0
    assert stats["prefix_hits"] == 0 and stats["prefix_tokens_saved"] == 0
    assert stats["spec_steps"] == 0 and stats["pipeline_flushes"] == 0 and stats["fused_steps"] > 0
    # three state-space layers: the counters are a layer's rows times three
    assert stats["ssm_rows_scanned"] == 3 * stats["prefill_tokens"]
    assert stats["ssm_rows_computed"] == 3 * stats["prefill_bucket_rows"]
    assert stats["ssm_lane_steps"] > 0 and stats["ssm_lane_steps"] % 3 == 0
    for i, p in enumerate(prompts):
        alone, _ = _serve(served, [p], pipelined=False, fused_prefill=False)
        assert alone[0] == tokens[i], (i, p)


def test_256_lanes_serve_as_8_do(served):
    """The step programs, the sampler, the admitted lane's splice and the host
    loop at 256 lanes: 300 requests, every one the tokens it gives alone."""
    prompts = [f"request {i} says hello world {'ab ' * (i % 7)}" for i in range(300)]
    tokens, stats = _serve(served, prompts, lanes=256, max_tokens=6)
    assert stats["jit_compiles_after_warmup"] == 0 and stats["pipeline_flushes"] == 0
    assert stats["state_zero_starts"] == 300
    few, _ = _serve(served, prompts[:12], lanes=8, max_tokens=6)
    assert tokens[:12] == few
