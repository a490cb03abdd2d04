"""What ``model_type: deepseek_v32`` adds to the latent-attention block
(models/deepseek.py), through ``InferenceEngine`` at a toy size on the CPU,
against the benchmark family's plain float32 reference: a query latent, an
indexer whose ``index_topk`` (16) is SMALLER than the toy's context (128), so
that the selection bites, expert groups, 8 of 16 experts held, YaRN. Prefill
then decode through the three-leaf cache, chunked against whole prefill, a
lane bit-identical whatever the other lanes hold, prefix reuse by lane copy,
and the faults the comparison must tell apart: a shorter list, the most
recent rows, dense attention.

One engine for the file (``sample``'s): every case that only reads and writes
lanes, counts or asks what is declined takes it and prefills the lanes it
reads first. A case builds an engine of its own only where the construction is
its subject (interpret mode, bfloat16, a monkeypatched selection, another
configuration)."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.models import deepseek
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

import latent_toy

CFG, FAMILY, CORRECT = latent_toy.toy("sparse")
TOPK = CFG["index_topk"]


@pytest.fixture(scope="module")
def sample():
    """The file's engine, its arrays, the sample sequences, its logits at them."""
    eng, tensors = latent_toy.engine(FAMILY, CFG, 21)
    prompts, forced = CORRECT.sample_sequences(CFG, 21)
    prefixes = [CORRECT.prefix_lengths(CFG, len(p)) for p in prompts]
    got = CORRECT.engine_logits(eng, prompts, forced, prefixes)
    return eng, tensors, (prompts, forced, prefixes), got


@pytest.fixture(scope="module")
def eng(sample):
    return sample[0]


def _reference(tensors, seqs, select):
    fam = type("F", (), {"reference_logits": staticmethod(
        functools.partial(FAMILY.reference_logits, select=select))})
    return CORRECT.plain_logits(fam, CFG, tensors, *seqs)


def test_kernels_in_interpret_mode_agree_with_the_reference(pallas_interpret):
    eng, tensors = latent_toy.engine(FAMILY, CFG, 5)
    r = CORRECT.compare(FAMILY, CFG, tensors, eng, 5)
    assert r["ok"], r
    assert r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5
    assert (r["route_greedy_gap"], r["route_nucleus_excess"], r["route_kv_rel_err"]) == (0, 0, 0)
    assert r["route_token_mismatches"] == 0
    assert max(CFG["correctness"]["prompt_tokens"]) > 4 * TOPK  # the selection bites


def test_bfloat16_stays_near_the_reference_and_the_routes_read_zero():
    eng, tensors = latent_toy.engine(FAMILY, CFG, 6, jnp.bfloat16)
    r = CORRECT.compare(FAMILY, CFG, tensors, eng, 6)
    assert r["prefill_rel_err"] < 0.15 and r["decode_rel_err"] < 0.15, r
    assert (r["route_greedy_gap"], r["route_nucleus_excess"], r["route_kv_rel_err"]) == (0, 0, 0)


@pytest.mark.parametrize("select,reads", [("indexer", "agrees"), ("recent", "differs"),
                                          ("all", "differs")])
def test_the_comparison_tells_a_wrong_selection_apart(sample, select, reads):
    """The engine against the reference as written, with the ``index_topk``
    most recent rows in the selection's place, and with the selection left
    out (dense attention)."""
    _eng, tensors, seqs, got = sample
    err = CORRECT.relative_errors(got, _reference(tensors, seqs, select))
    if reads == "agrees":
        assert err.max() < 1e-5
    else:
        assert np.sqrt(np.mean(err ** 2)) > 0.05, err


@pytest.mark.parametrize("fault", ["shorter_list", "approximate", "indexer_skipped_below_64"])
def test_a_program_that_departs_from_the_selection_fails_the_comparison(monkeypatch, fault, sample):
    _eng, tensors, seqs, _got = sample
    exact = deepseek.exact_topk
    if fault == "shorter_list":  # one row fewer than index_topk
        monkeypatch.setattr(deepseek, "exact_topk", lambda s, k, last: exact(s, k, last)[..., : k - 1])
    elif fault == "approximate":  # a recall under 1: every eighth chosen row dropped
        monkeypatch.setattr(
            deepseek, "exact_topk",
            lambda s, k, last: exact(s, k, last)[..., np.arange(k) % 8 != 7])
    else:  # positions under 64 attend the most recent rows: no scores there
        def skipping(qi, w, ik):
            s = deepseek_scores(qi, w, ik)
            recent = jnp.broadcast_to(jnp.arange(s.shape[-1], dtype=s.dtype), s.shape)
            return jnp.where(jnp.arange(s.shape[-1]) < 64, recent, s)
        deepseek_scores = deepseek.index_scores_block
        monkeypatch.setattr(deepseek, "index_scores_block", skipping)
    eng, _ = latent_toy.engine(FAMILY, CFG, 21)
    got = CORRECT.engine_logits(eng, *seqs)
    err = CORRECT.relative_errors(got, _reference(tensors, seqs, "indexer"))
    limits = CFG["correctness"]["limits"]
    assert np.sqrt(np.mean(err ** 2)) > 10 * limits["prefill_rel_err"], err


def test_no_step_program_holds_an_approximate_top_k(eng):
    cfg, n = eng.config, eng.n_lanes
    for b, t in ((n, 1), (1, 64)):
        cache = deepseek.init_latent_cache(cfg, b)
        tok = jnp.zeros((b, t), jnp.int32)
        jaxpr = str(jax.make_jaxpr(
            lambda tk, c: deepseek.deepseek_forward_counted(cfg, eng.params, tk, tk, c))(tok, cache))
        assert "approx_top_k" not in jaxpr
        # the exact top_k of index_topk, at one row a lane and in a chunk
        # (the router's own top_k are narrower)
        assert re.search(rf"top_k\[[^\]]*k={TOPK}\b", jaxpr)


@pytest.mark.parametrize("segment", [None, 32], ids=["one_sort", "segments_of_32"])
def test_the_exact_topk_is_the_stable_sorts_first_k_with_ties_and_short_rows(monkeypatch, segment):
    if segment:
        monkeypatch.setattr(deepseek, "TOPK_SEGMENT", segment)
    rng = np.random.default_rng(0)
    scores = rng.integers(0, 6, size=(5, 7, 96)).astype(np.float32)  # many equal scores
    scores[0, :, 40:] = -np.inf  # rows with fewer entries than k above -inf
    scores[1, :, 5:] = -np.inf
    last = jnp.int32(95)
    for k in (1, 16, 32):
        got = np.asarray(deepseek.exact_topk(jnp.asarray(scores), k, last))
        want = np.argsort(-scores, axis=-1, kind="stable")[..., :k]
        finite = np.take_along_axis(scores, want, axis=-1) > -np.inf
        np.testing.assert_array_equal(np.where(finite, got, -1), np.where(finite, want, -1))
        assert all(len(set(row.tolist())) == k for row in got.reshape(-1, k))  # no position twice
    # a segment past ``last`` is not sorted, and none of its positions can be chosen
    short = np.where(np.arange(96) <= 40, scores, -np.inf)
    got = np.asarray(deepseek.exact_topk(jnp.asarray(short), 16, jnp.int32(40)))
    want = np.argsort(-short, axis=-1, kind="stable")[..., :16]
    finite = np.take_along_axis(short, want, axis=-1) > -np.inf
    np.testing.assert_array_equal(np.where(finite, got, -1), np.where(finite, want, -1))


def test_prefill_in_chunks_gives_the_rows_of_a_prefill_in_one(eng):
    prompt = [int(x) for x in np.random.default_rng(0).integers(2, 250, size=120)]
    whole, _, _ = eng.prefill(0, prompt)
    eng.prefill(1, prompt[:64])
    parts, _, _ = eng.prefill(1, prompt[64:], start_pos=64)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), rtol=2e-4, atol=2e-4)
    assert FAMILY.lane_state_rel_err(eng, 0, 1, 120) < 1e-4


def test_a_lane_is_bit_identical_whatever_the_other_lanes_hold(eng):
    n, seq = eng.n_lanes, eng.config.seq_len
    rng = np.random.default_rng(1)
    prompt = [int(x) for x in rng.integers(2, 250, size=70)]
    rows = []
    for others in (None, 31, 90):  # alone, then beside lanes at other lengths
        eng.prefill(0, prompt)
        tokens, positions = np.full(n, 7, np.int32), np.full(n, seq, np.int32)
        positions[0] = len(prompt)
        if others is not None:
            for lane in range(1, n):
                eng.prefill(lane, [int(x) for x in rng.integers(2, 250, size=others + lane)])
                tokens[lane], positions[lane] = 11 + lane, others + lane
        logits, _, _ = eng.decode(tokens, positions, want_logits=True)
        rows.append(np.asarray(logits)[0])
    np.testing.assert_array_equal(rows[0], rows[1])
    np.testing.assert_array_equal(rows[0], rows[2])


def test_a_parked_lane_is_left_alone_in_all_three_leaves(eng):
    assert isinstance(eng.cache, deepseek.IndexedLatentCache)
    assert eng.cache.ik.shape == (3, 8, 128, CFG["index_head_dim"])
    n, seq = eng.n_lanes, eng.config.seq_len
    prompt = list(range(3, 43))
    eng.prefill(0, prompt)
    eng.prefill(1, prompt)
    before = [np.asarray(x) for x in eng.cache]
    positions = np.full(n, seq, np.int32)
    positions[0] = len(prompt)
    eng.decode(np.full(n, 9, np.int32), positions)
    for b, a in zip(before, [np.asarray(x) for x in eng.cache]):
        np.testing.assert_array_equal(b[:, 1:], a[:, 1:])
        assert (b[:, 0, len(prompt)] != a[:, 0, len(prompt)]).any()
        np.testing.assert_array_equal(b[:, 0, : len(prompt)], a[:, 0, : len(prompt)])


def test_prefix_reuse_by_lane_copy_carries_the_index_keys(eng):
    prompt = [int(x) for x in np.random.default_rng(2).integers(2, 250, size=110)]
    whole, _, _ = eng.prefill(0, prompt)
    eng.copy_lane(0, 2, prefix_len=64)
    tail, _, _ = eng.prefill(2, prompt[64:], start_pos=64)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(whole), rtol=2e-4, atol=2e-4)
    assert FAMILY.lane_state_rel_err(eng, 0, 2, 110) < 1e-4


def test_what_is_declined_is_declined_by_name_and_the_facts_are_said(eng):
    facts = eng.path_facts()
    assert facts["attention_path"] == "sparse_topk" and facts["index_topk"] == TOPK
    assert facts["sparse_rows"] == "gathered"
    assert facts["experts_held"] == "8/16"
    assert facts["declined_for_sparse_attention"] == ["speculation"]
    assert not eng.supports_speculative and not eng.supports_spec_pipelined
    with pytest.raises(ValueError, match="indexer"):
        eng._check_speculative()
    config, params = eng.config, eng.params
    for kw, what in ((dict(paged_kv=True), "paged KV pool"), (dict(kv_host_bytes=1 << 20), "host KV tier")):
        with pytest.raises(ValueError, match=what):
            InferenceEngine(config, params, n_lanes=4, **kw)


def test_the_counters_count_rows_scored_rows_chosen_and_pairs_unheld(eng):
    n, seq = eng.n_lanes, eng.config.seq_len
    lengths = [10, 40, 100]
    for lane, m in enumerate(lengths):
        eng.prefill(lane, list(range(2, 2 + m)))
    positions = np.full(n, seq, np.int32)
    positions[:3] = lengths
    z = np.zeros(n, np.float32)
    eng.stats.reset()
    eng.decode_pipelined(positions, z, z + 0.9, np.ones(n, np.uint32), tokens=np.full(n, 5, np.int32))
    eng.pipeline_consume()
    eng.pipeline_flush()
    s, layers = eng.stats.snapshot(), eng.config.n_layers
    assert s["indexer_rows_scored"] == layers * sum(m + 1 for m in lengths)
    assert s["sparse_rows_selected"] == layers * sum(min(m + 1, TOPK) for m in lengths)
    pairs = 3 * eng.config.n_active_experts * eng.config.n_routed_layers
    assert s["moe_assignments"] + s["moe_rows_unheld"] == pairs and s["moe_rows_unheld"] > 0
    assert s["moe_experts_held"] == 8
    assert s["moe_slabs_whole"] == eng.config.n_routed_layers * eng.config.n_experts


def test_the_fields_off_leave_the_latent_blocks_program_as_it_was():
    """Each mechanism engages by its own field: a configuration with none of
    them set traces the two-leaf program, count for count."""
    cfg3, family3, _ = latent_toy.load()
    eng, _ = latent_toy.engine(family3, cfg3, 3, lanes=4)
    assert type(eng.cache).__name__ == "KVCache" and eng._count_names == ("slabs", "assignments", "tiled_rows")
    assert eng.supports_speculative and "index_topk" not in eng.path_facts()
    off = dataclasses.replace(eng.config)
    assert not off.sparse_attention and off.experts_held == (0, off.n_experts)
    assert off.softmax_scale_factor == 1.0 and off.moe_norm_floor == 1e-20
