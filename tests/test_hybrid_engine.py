"""A block whose layers differ in their mixer (models/hybrid.py) through
``InferenceEngine`` and the scheduler at a toy size on the CPU: the rule for
a state overwritten in place by arithmetic, the pipelined overshoot of a
finished lane that harms no later request, the kernels in interpret mode and
the merged stack read in place; what is declined for such a model (prefix
reuse by lane copy, speculation) and what is refused by name. (The rule in
every step family, a parked lane, a padded tail, a second chunk, position 0,
is tests/test_lane_state_contract.py's row ``lfm2``.)

One engine for the file (``built``) and one warmed engine behind ``served``:
a case builds an engine of its own only where the construction is its subject
(interpret mode, another dtype or shape)."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats.synthetic import tiny_pattern_header
from distributed_llama_multiusers_tpu.models.hybrid import (
    HybridCache,
    layer_periods,
    window_state,
)
from distributed_llama_multiusers_tpu.ops import linear
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

import latent_toy
from latent_toy import park

CFG, FAMILY, CORRECT = latent_toy.toy("lfm2")
SEQ = CFG["max_position_embeddings"]
PROMPT = [int(x) for x in np.random.default_rng(0).integers(2, CFG["vocab_size"], size=100)]


built = latent_toy.module_engine(FAMILY, CFG, seed=7, lanes=8)


@pytest.fixture(scope="module")
def eng(built):
    return built[0]


def test_the_rule_itself():
    state = jnp.asarray([[[1.0], [2.0]], [[3.0], [4.0]], [[5.0], [6.0]]])  # K - 1 = 2
    u = jnp.asarray([[[10.0], [11.0], [12.0]]] * 3)
    window, new = window_state(state, u, jnp.asarray([0, 1, 3], jnp.int32))
    assert window.shape == (3, 5, 1)
    np.testing.assert_array_equal(new[..., 0], [[1, 2], [4, 10], [11, 12]])


@pytest.mark.parametrize("kinds,want", [
    ("AcccAcccAcccAcccAc", (4, 4)), ("AcccAc", (4, 1)), ("AAAA", (1, 4)), ("", (1, 0)),
    ("cAcAc", (2, 2)), ("ccA", (2, 1)),
])
def test_the_scan_runs_over_whole_periods_of_the_published_list(kinds, want):
    assert layer_periods(tuple(kinds)) == want


def test_the_cache_is_a_stack_a_kind(eng):
    assert isinstance(eng.cache, HybridCache)
    assert eng.cache.k.shape == (2, 8, SEQ, 64) and eng.cache.conv.shape == (6, 8, 2 * 128)
    assert eng.stats.recurrent_state_bytes == eng.cache.conv.nbytes
    facts = eng.path_facts()
    assert facts["attention_path"] == "xla_dense" and facts["expert_path"] == "xla_gathered_slabs"
    assert facts["declined_for_recurrent_state"] == ["prefix_reuse", "speculation"]
    assert eng.moe_slabs_per_step == 6 * 8 and not eng.supports_speculative


def test_the_pipelined_overshoot_of_a_finished_lane_harms_no_later_request(eng):
    """Two steps are in flight when the host learns a lane has finished: the
    lane absorbed a token it never emits. The lane's next request starts at
    position 0 and reads none of it."""
    eng.prefill(0, PROMPT[:20])
    tokens, positions = park(eng, {0: (5, 20)})
    eng.decode_pipelined(positions, tokens=tokens)
    eng.decode_pipelined(np.where(positions < SEQ, -1, positions).astype(np.int32))  # the overshoot
    eng.pipeline_flush()
    first, _, _ = eng.prefill(0, PROMPT[20:50])
    fresh, _, _ = eng.prefill(1, PROMPT[20:50])
    np.testing.assert_array_equal(np.asarray(first), np.asarray(fresh))
    np.testing.assert_array_equal(np.asarray(eng.cache.conv[:, 0]), np.asarray(eng.cache.conv[:, 1]))


def test_kernels_in_interpret_mode_agree_with_the_reference(pallas_interpret):
    e, tensors = latent_toy.engine(FAMILY, CFG, 5)
    assert e.path_facts()["expert_path"] == "q40_grouped_kernel"
    r = CORRECT.compare(FAMILY, CFG, tensors, e, 5)
    assert r["ok"], r
    assert r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5
    assert (r["route_greedy_gap"], r["route_nucleus_excess"], r["route_kv_rel_err"]) == (0, 0, 0)


def test_decode_steps_attend_the_merged_stack_in_place_where_the_kernel_takes_it(pallas_interpret):
    """A bfloat16 cache whose rows are whole 128-lane tiles and whose context
    is whole blocks, Pallas on (interpret mode): the engine says so, and the
    tokens of a pipelined chain, of a fused admission's decode half and of the
    synchronous replay are the same, lanes on both sides of a block's edge."""
    from distributed_llama_multiusers_tpu.ops import pallas_attention

    cfg, family, correct = latent_toy.wide_lfm2()
    e, tensors = latent_toy.engine(family, cfg, 5, dtype=jnp.bfloat16)
    assert e.cache.k.shape == (2, 8, 512, 128) and e.cache.k.dtype == jnp.bfloat16
    assert e.path_facts()["attention_path"] == "pallas_in_place"
    assert e.decode_attention_block == pallas_attention.BLOCK_ROWS
    r = correct.compare(family, cfg, tensors, e, 5, keep_rows=True)
    assert (r["route_greedy_gap"], r["route_nucleus_excess"], r["route_kv_rel_err"]) == (0, 0, 0)
    assert r["route_tokens"] >= 20 and r["route_token_mismatches"] == 0
    # bfloat16 against the float32 reference: a row reads 0.008-0.014, as the
    # dense path does (0.012), unless the router's choice at its position is a
    # near tie: the reference's margin between the last expert chosen and the
    # first left out is under one bfloat16 step of a score (2**-8 in [0.5, 1)),
    # so rounding decides the set, and which way it falls changes with the
    # order of any sum before it (PR 42's kernel sums the same products in
    # another order: rows 3 of sequences 0 and 1, margins 0.0032 and 0.0015,
    # read 0.009 before it and 0.082 / 0.063 since; seeds 6-8 flip other rows
    # on either side of that PR). Such a row, and the decode rows after it in
    # its lane, are the only ones allowed over the 0.02 every row was held to
    rows = np.asarray(r["row_errors"])
    near_tie = _router_margins(family, cfg, tensors, 5) < 2.0 ** -8
    prompt_row = rows.shape[1] - 1 - int(cfg["correctness"]["decode_steps"])
    after_tie = np.zeros_like(near_tie)  # a prefix row is prefilled alone
    after_tie[:, prompt_row:] = np.cumsum(near_tie[:, prompt_row:], axis=1) > 0
    clear = ~(near_tie | after_tie)
    assert rows.shape == near_tie.shape and clear.sum() >= 6, near_tie
    assert rows[clear].max() < 0.02, (rows, near_tie)
    assert r["prefill_rel_err"] < 0.02 and r["decode_rel_err"] < 0.04, r


def _router_margins(family, cfg, tensors, seed):
    """For each compared row ``[sequence, row]``, the least margin over the
    routed layers, in the float32 reference, between the last expert the
    router chooses at that row's position and the first it leaves out."""
    import jax

    prompts, forced = CORRECT.sample_sequences(cfg, seed)
    k = int(cfg["num_experts_per_tok"])
    real, seen = family._route, []

    def spy(m, gate, bias, **kw):
        scores = jnp.sort(jax.nn.sigmoid(m @ gate) + bias, axis=-1)
        seen.append(np.asarray(scores[0, :, -k] - scores[0, :, -k - 1]))
        return real(m, gate, bias, **kw)

    family._route = spy
    try:
        out = []
        for p, f in zip(prompts, forced):
            seen.clear()
            with jax.default_matmul_precision("highest"):
                family.reference_forward(cfg, tensors, np.asarray([p + f], np.int32))
            at = ([n - 1 for n in CORRECT.prefix_lengths(cfg, len(p))]
                  + list(range(len(p) - 1, len(p) + len(f))))
            out.append(np.stack(seen).min(axis=0)[at])
    finally:
        family._route = real
    return np.stack(out)


@pytest.mark.parametrize("dtype,seq,why", [
    (jnp.bfloat16, 512, "Pallas off"), (jnp.float32, 512, "a float32 cache"),
    (jnp.bfloat16, 384, "a context that is not whole blocks"),
])
def test_the_dense_path_is_said_where_the_kernel_does_not_take_the_cache(dtype, seq, why):
    cfg, family, _ = latent_toy.wide_lfm2()
    cfg["max_position_embeddings"] = seq
    linear.set_pallas_interpret(why != "Pallas off")
    try:
        e, _ = latent_toy.engine(family, cfg, 5, dtype=dtype, lanes=2)
        assert e.path_facts()["attention_path"] == "xla_dense", why
        assert e.decode_attention_block is None
    finally:
        linear.set_pallas_interpret(False)


def test_bfloat16_where_float32_is_stated_is_told_apart():
    e, tensors = latent_toy.engine(FAMILY, CFG, 5, dtype=jnp.bfloat16)
    r = CORRECT.compare(FAMILY, CFG, tensors, e, 5)
    assert not r["ok"] and r["prefill_rel_err"] > 1e-3  # the f32 limits are 1e-3


@pytest.mark.parametrize("kw,names", [
    (dict(paged_kv=True), "paged KV pool"),
    (dict(paged_kv=True, kv_host_bytes=1 << 20), "paged KV pool"),
    (dict(mesh=object()), "a mesh"),
])
def test_what_the_block_does_not_serve_is_refused_by_name(eng, kw, names):
    with pytest.raises(ValueError, match=names):
        InferenceEngine(eng.config, eng.params, n_lanes=4, **kw)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    # (both of its schedulers keep the pipelined loop: no multi-step program is
    # warmed; one bucket: a prompt is admitted in chunks of 16 with a padded tail)
    return latent_toy.serving(tiny_pattern_header(seq_len=128), tmp_path_factory.mktemp("pattern"),
                              buckets=(16,), multi_step=0)


def test_prefix_reuse_is_declined_and_speculation_too_with_the_same_tokens(served):
    """One after the other, so that the second finds the first resident: the
    defaults (prefix reuse at 16, speculation on), then the same warmed
    engine under a scheduler with both off."""
    shared = "the same long opening words of two requests, "
    prompts = [shared + "then one end", shared + "then another", "ab ab ab ab ab ab ab ab ab"]
    tokens, stats = served.serve(prompts, in_turn=True)
    assert stats["prefix_reuse_declined"] >= 1 and stats["prefix_hits"] == 0
    assert stats["prefix_tokens_saved"] == 0 and stats["spec_steps"] == 0
    assert stats["state_zero_starts"] == 3 and stats["jit_compiles_after_warmup"] == 0
    plain, off = served.serve(prompts, in_turn=True, prefix_min_tokens=0, speculative=False)
    assert tokens == plain and off["prefix_reuse_declined"] == 0
    assert off["state_zero_starts"] == 3 and off["jit_compiles_after_warmup"] == 0
