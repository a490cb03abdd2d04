"""A block whose layers differ in their mixer (models/hybrid.py) through
``InferenceEngine`` and the scheduler at a toy size on the CPU: the one rule
for a state overwritten in place, in every step family (a parked lane keeps
it, a bucket's padded tail is ignored, a second chunk continues the first,
position 0 reads zeros whatever the lane held, the pipelined overshoot of a
finished lane harms no later request); what is declined for such a model
(prefix reuse by lane copy, speculation) and what is refused by name."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats import load_model_header
from distributed_llama_multiusers_tpu.formats.synthetic import (
    tiny_pattern_header,
    write_synthetic_model,
    write_synthetic_tokenizer,
)
from distributed_llama_multiusers_tpu.models import load_params_from_m
from distributed_llama_multiusers_tpu.models.hybrid import (
    HybridCache,
    layer_periods,
    window_state,
)
from distributed_llama_multiusers_tpu.ops import linear
from distributed_llama_multiusers_tpu.runtime import ContinuousBatchingScheduler, Request
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine, warmup_engine
from distributed_llama_multiusers_tpu.tokenizer import Tokenizer

import latent_toy

CFG, FAMILY, CORRECT = latent_toy.load("tiny_lfm2.json")
SEQ = CFG["max_position_embeddings"]
PROMPT = [int(x) for x in np.random.default_rng(0).integers(2, CFG["vocab_size"], size=100)]


@pytest.fixture(scope="module")
def eng():
    return latent_toy.engine(FAMILY, CFG, seed=7, lanes=8)[0]


def _state(eng, lane):
    return np.asarray(eng.cache.conv[:, lane])


def _park(eng, live: dict):
    """Tokens and positions of a step in which only ``live`` lanes move."""
    tokens = np.zeros(eng.n_lanes, np.int32)
    positions = np.full(eng.n_lanes, SEQ, np.int32)
    for lane, (tok, pos) in live.items():
        tokens[lane], positions[lane] = tok, pos
    return tokens, positions


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
    assert not np.array_equal(_state(eng, 0), before)  # the live lane moved


def test_a_padded_tail_is_ignored_and_token_by_token_is_the_same_state(eng):
    """20 tokens through the 64 bucket (44 rows of padding) against the same
    tokens one decode step each: other programs, the same window."""
    eng.prefill(0, PROMPT[:20])
    for i, tok in enumerate(PROMPT[:20]):
        eng.decode(*_park(eng, {1: (tok, i)}))
    assert FAMILY.lane_state_rel_err(eng, 0, 1, 20) < 1e-5
    eng.decode_prefill_fused(np.full(8, SEQ, np.int32), p_lane=2, chunk=PROMPT[:20],
                             tokens=np.zeros(8, np.int32))
    eng.pipeline_flush()
    assert FAMILY.lane_state_rel_err(eng, 0, 2, 20) < 1e-5


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


def test_position_zero_reads_zeros_whatever_the_lane_held(eng):
    eng.prefill(4, PROMPT[40:90])  # what an earlier request left behind
    dirty = _state(eng, 4).copy()
    zero_starts = eng.stats.state_zero_starts
    eng.prefill(4, PROMPT[:20])
    eng.prefill(5, PROMPT[60:70])
    eng.cache = eng.cache._replace(conv=eng.cache.conv.at[:, 5].set(0.0))  # a lane never used
    eng.prefill(5, PROMPT[:20])
    assert eng.stats.state_zero_starts == zero_starts + 3
    np.testing.assert_array_equal(_state(eng, 4), _state(eng, 5))
    assert not np.array_equal(_state(eng, 4), dirty)
    # a decode step at position 0 starts a sequence too
    eng.cache = eng.cache._replace(conv=eng.cache.conv.at[:, 6].set(3.0))
    eng.decode(*_park(eng, {6: (9, 0), 7: (9, 0)}))
    np.testing.assert_array_equal(_state(eng, 6), _state(eng, 7))


def test_the_pipelined_overshoot_of_a_finished_lane_harms_no_later_request(eng):
    """Two steps are in flight when the host learns a lane has finished: the
    lane absorbed a token it never emits. The lane's next request starts at
    position 0 and reads none of it."""
    eng.prefill(0, PROMPT[:20])
    tokens, positions = _park(eng, {0: (5, 20)})
    eng.decode_pipelined(positions, tokens=tokens)
    eng.decode_pipelined(np.where(positions < SEQ, -1, positions).astype(np.int32))  # the overshoot
    eng.pipeline_flush()
    first, _, _ = eng.prefill(0, PROMPT[20:50])
    fresh, _, _ = eng.prefill(1, PROMPT[20:50])
    np.testing.assert_array_equal(np.asarray(first), np.asarray(fresh))
    np.testing.assert_array_equal(_state(eng, 0), _state(eng, 1))


def test_kernels_in_interpret_mode_agree_with_the_reference():
    linear.set_pallas_interpret(True)
    try:
        e, tensors = latent_toy.engine(FAMILY, CFG, 5)
        assert e.path_facts()["expert_path"] == "q40_grouped_kernel"
        r = CORRECT.compare(FAMILY, CFG, tensors, e, 5)
    finally:
        linear.set_pallas_interpret(False)
    assert r["ok"], r
    assert r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5
    assert (r["route_greedy_gap"], r["route_nucleus_excess"], r["route_kv_rel_err"]) == (0, 0, 0)


def test_decode_steps_attend_the_merged_stack_in_place_where_the_kernel_takes_it():
    """A bfloat16 cache whose rows are whole 128-lane tiles and whose context
    is whole blocks, Pallas on (interpret mode): the engine says so, and the
    tokens of a pipelined chain, of a fused admission's decode half and of the
    synchronous replay are the same, lanes on both sides of a block's edge."""
    from distributed_llama_multiusers_tpu.ops import pallas_attention

    cfg, family, correct = latent_toy.wide_lfm2()
    linear.set_pallas_interpret(True)
    try:
        e, tensors = latent_toy.engine(family, cfg, 5, dtype=jnp.bfloat16)
        assert e.cache.k.shape == (2, 8, 512, 128) and e.cache.k.dtype == jnp.bfloat16
        assert e.path_facts()["attention_path"] == "pallas_in_place"
        assert e.decode_attention_block == pallas_attention.BLOCK_ROWS
        r = correct.compare(family, cfg, tensors, e, 5, keep_rows=True)
    finally:
        linear.set_pallas_interpret(False)
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
        e, _ = latent_toy.engine(family, cfg, 5, dtype=dtype)
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
def test_what_the_block_does_not_serve_is_refused_by_name(kw, names):
    config = FAMILY.program_config(CFG)
    params = FAMILY.assemble_params(config, FAMILY.device_weights(config, 3, jnp.float32))
    with pytest.raises(ValueError, match=names):
        InferenceEngine(config, params, n_lanes=4, **kw)


def test_verify_steps_and_lane_copies_are_refused(eng):
    n = eng.n_lanes
    z = np.zeros(n, np.int32)
    with pytest.raises(ValueError, match="without speculation"):
        eng.decode_spec(z, np.zeros((n, eng.SPEC_DRAFT), np.int32), z, z)
    with pytest.raises(ValueError, match="without speculation"):
        eng.decode_spec_pipelined(z, np.zeros((n, eng.SPEC_DRAFT + 1), np.int32), z, tokens=z)
    with pytest.raises(RuntimeError, match="recurrent state"):
        eng.copy_lane(0, 1)
    eng.copy_lane(2, 2)  # nothing moves


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    d = tmp_path_factory.mktemp("pattern")
    header = tiny_pattern_header(seq_len=128)
    write_synthetic_model(str(d / "m.m"), header, seed=3)
    write_synthetic_tokenizer(str(d / "t.t"), vocab_size=header.vocab_size)
    h = load_model_header(str(d / "m.m"))
    config, params = load_params_from_m(str(d / "m.m"), h, dtype=jnp.float32)
    return config, params, Tokenizer(str(d / "t.t"))


def _serve(served, prompts, **kw):
    config, params, tok = served
    engine = InferenceEngine(config, params, n_lanes=2, prefill_buckets=(8, 16))
    sched = ContinuousBatchingScheduler(engine, tok, **kw)
    warmup_engine(engine, spec=sched.speculative, multi_step=sched.multi_step)
    sched.start()
    try:
        out = []
        for p in prompts:  # one after the other: the second finds the first resident
            r = sched.submit(Request(prompt=p, max_tokens=8, temperature=0.0))
            r.future.result(timeout=300)
            assert r.error is None, r.error
            out.append(list(r.generated_tokens))
    finally:
        sched.stop()
    return out, engine.stats.snapshot()


def test_prefix_reuse_is_declined_and_speculation_too_with_the_same_tokens(served):
    shared = "the same long opening words of two requests, "
    prompts = [shared + "then one end", shared + "then another", "ab ab ab ab ab ab ab ab ab"]
    tokens, stats = _serve(served, prompts)  # the defaults: prefix reuse at 16, speculation on
    assert stats["prefix_reuse_declined"] >= 1 and stats["prefix_hits"] == 0
    assert stats["prefix_tokens_saved"] == 0 and stats["spec_steps"] == 0
    assert stats["state_zero_starts"] == 3 and stats["jit_compiles_after_warmup"] == 0
    plain, off = _serve(served, prompts, prefix_min_tokens=0, speculative=False)
    assert tokens == plain and off["prefix_reuse_declined"] == 0
