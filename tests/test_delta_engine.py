"""Gated delta-rule layers beside gated NoPE GQA layers over a held share of
routed experts (models/hybrid.py ``LayerKind.DELTA``, ops/delta_rule.py) through
``InferenceEngine`` at a toy size on the CPU: eight layers ``G D D D G D D D``,
4 heads of 16, convs of 4 taps, gates of rank 8, 2 kv heads, 16 experts of
which 4 are chosen and 4 held, one shared. Against the benchmark's plain
reference on logits: prefill whole and in chunks with a padded tail, decode
through the cache, a fused admission beside decoding lanes, parked twins; the
rule for the matrix state AND the convs' windows in every step family; a
request that follows another on a lane; what is declined and counted."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats.model_file import LayerKind
from distributed_llama_multiusers_tpu.models.hybrid import HybridCache, layer_periods
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine
from distributed_llama_multiusers_tpu.telemetry import names

import latent_toy

CFG, FAMILY, CORRECT = latent_toy.load("tiny_solar_open2.json")
SEQ = CFG["max_position_embeddings"]
PROMPT = [int(x) for x in np.random.default_rng(0).integers(2, CFG["vocab_size"], size=120)]
G, D = LayerKind.ATTENTION, LayerKind.DELTA
LEAVES = ("k", "v", "delta", "delta_conv")


@pytest.fixture(scope="module")
def built():
    """One engine for the file and ONE bucket, 64 rows (its programs compile
    once: the tier-1 clock): a prompt of 100 is 64 + 36 of 64, one of 20 rides
    44 padded rows, and a chunk of the chunk form is 32 of them."""
    return latent_toy.engine(FAMILY, CFG, seed=5, lanes=8, prefill_buckets=(64,))


@pytest.fixture(scope="module")
def eng(built):
    return built[0]


def _lane(eng, lane):
    return {name: np.asarray(getattr(eng.cache, name)[:, lane]) for name in LEAVES}


def _park(eng, live: dict):
    tokens = np.zeros(eng.n_lanes, np.int32)
    positions = np.full(eng.n_lanes, SEQ, np.int32)
    for lane, (tok, pos) in live.items():
        tokens[lane], positions[lane] = tok, pos
    return tokens, positions


def test_engine_agrees_with_the_plain_reference_and_the_routes_read_zero(built):
    """Prompts of 20, 46 and 60 whole (one bucket each) and 100 in chunks of 64
    + 36 with a padded tail; four decode steps through the cache; the
    pipelined and fused programs (an admission whole, one in two chunks beside
    decoding lanes) against the synchronous ones on twins left parked, the whole
    state (planes, matrix state, conv windows) compared pair by pair."""
    e, tensors = built
    r = CORRECT.compare(FAMILY, CFG, tensors, e, 5)
    assert r["ok"], r
    assert r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5
    assert (r["route_greedy_gap"], r["route_nucleus_excess"]) == (0, 0)
    # every pair of lanes agrees to the bit; what is left of the number is the
    # share of a matrix state's words that a bfloat16 holds exactly
    assert r["route_kv_rel_err"] < 1e-3
    assert r["route_token_mismatches"] == 0 and r["route_tokens"] >= 20


def test_the_cache_has_two_new_leaves_and_the_state_is_counted(eng):
    assert isinstance(eng.cache, HybridCache)
    assert eng.config.layer_kinds == (G, D, D, D, G, D, D, D)
    assert layer_periods(eng.config.layer_kinds) == (4, 2)
    assert eng.cache.k.shape == eng.cache.v.shape == (2, 8, SEQ, 32)
    assert eng.cache.delta.shape == (6, 8, 4 * 16 * 16) and eng.cache.delta.dtype == jnp.float32
    assert eng.cache.delta_conv.shape == (6, 8, 3 * 3 * 64)
    assert eng.cache.lin is None and eng.cache.ssm is None and eng.cache.wk is None
    state = eng.cache.delta.nbytes + eng.cache.delta_conv.nbytes
    assert eng.lane_state_bytes == state == eng.stats.recurrent_state_bytes
    facts = eng.path_facts()
    assert facts["declined_for_recurrent_state"] == ["prefix_reuse", "speculation"]
    assert facts["delta_state_bytes"] == eng.cache.delta.nbytes
    assert facts["delta_conv_window_bytes"] == eng.cache.delta_conv.nbytes
    assert facts["delta_state_path"] == "xla" and facts["experts_held"] == "4/16"
    assert eng.config.recurrent_state and not eng.supports_speculative
    assert eng.config.n_attention_layers == 2 and eng.config.attn_output_gate == 1
    # chunk rows counted a delta layer, every row of the bucket (the compare above)
    assert eng.stats.delta_rows_computed > 0 and eng.stats.delta_rows_computed % 6 == 0


@pytest.mark.parametrize("family", ["decode", "decode_pl", "fused"])
def test_a_parked_lane_keeps_both_states_in_every_step_family(eng, family):
    eng.prefill(0, PROMPT[:20])
    eng.prefill(1, PROMPT[:30])
    before, live_before = _lane(eng, 1), _lane(eng, 0)
    tokens, positions = _park(eng, {0: (5, 20)})
    if family == "decode":
        eng.decode(tokens, positions)
    elif family == "decode_pl":
        eng.decode_pipelined(positions, tokens=tokens)
        eng.decode_pipelined(np.where(positions < SEQ, -1, positions).astype(np.int32))
        eng.pipeline_flush()
    else:
        eng.decode_prefill_fused(positions, p_lane=2, chunk=PROMPT[:10], tokens=tokens)
        eng.pipeline_flush()
    after = _lane(eng, 1)
    for name in LEAVES:
        np.testing.assert_array_equal(after[name], before[name], err_msg=name)
    for name in ("delta", "delta_conv"):
        assert not np.array_equal(_lane(eng, 0)[name], live_before[name]), name


def test_a_state_at_rest_in_bfloat16_is_told_though_both_lanes_agree(eng):
    """Two lanes of one engine agree whatever precision both keep their state
    in, and the logits do not show a state in bfloat16 (the cell's
    ``limits_from``): the family's state number also reads the share of a
    matrix state's words that a bfloat16 holds exactly, under 1e-3 as carried
    and 1 once rounded, over the cell's limit."""
    eng.prefill(0, PROMPT[:20])
    eng.prefill(1, PROMPT[:20])
    assert FAMILY.lane_state_rel_err(eng, 0, 1, 20) < 1e-3
    was = eng.cache.delta
    eng.cache = eng.cache._replace(delta=was.astype(jnp.bfloat16).astype(jnp.float32))
    try:
        assert FAMILY.lane_state_rel_err(eng, 0, 1, 20) == 1.0
    finally:
        eng.cache = eng.cache._replace(delta=was)


def test_a_padded_tail_is_ignored_and_token_by_token_is_the_same_state(eng):
    """20 tokens through the 64 bucket (44 rows of padding) against the same
    tokens one decode step each, and as a fused admission: other programs, the
    same matrix state and the same windows."""
    eng.prefill(0, PROMPT[:20])
    for i, tok in enumerate(PROMPT[:20]):
        eng.decode(*_park(eng, {1: (tok, i)}))
    assert FAMILY.lanes_rel_err(eng, 0, 1, 20) < 1e-5
    eng.decode_prefill_fused(np.full(8, SEQ, np.int32), p_lane=2, chunk=PROMPT[:20],
                             tokens=np.zeros(8, np.int32))
    eng.pipeline_flush()
    assert FAMILY.lanes_rel_err(eng, 0, 2, 20) < 1e-5
    # a state that absorbed the padding would differ in every head
    eng.prefill(3, PROMPT[:20] + [0] * 12)
    assert FAMILY.lanes_rel_err(eng, 0, 3, 20) > 1e-3


def test_a_second_chunk_continues_the_first(eng):
    eng.prefill(0, PROMPT[:100])  # 64 + 36 of 64
    eng.prefill(1, PROMPT[:29])   # an odd cut: inside a 32-row chunk of the chunk form
    eng.prefill(1, PROMPT[29:100], start_pos=29)
    assert FAMILY.lanes_rel_err(eng, 0, 1, 100) < 1e-5
    park = np.full(8, SEQ, np.int32)
    eng.decode_prefill_fused(park, p_lane=2, chunk=PROMPT[:15], tokens=np.zeros(8, np.int32))
    eng.decode_prefill_fused(park, p_lane=2, chunk=PROMPT[15:60], p_start=15)  # parked between
    eng.pipeline_flush()
    eng.prefill(3, PROMPT[:60])
    assert FAMILY.lanes_rel_err(eng, 3, 2, 60) < 1e-5
    # a second chunk that restarted from zero is another state
    eng.prefill(4, PROMPT[15:60])
    assert FAMILY.lanes_rel_err(eng, 3, 4, 1) > 1e-3


def test_a_request_that_follows_another_on_a_lane_reads_zeros(eng):
    eng.prefill(4, PROMPT[40:90])  # what an earlier request left behind
    dirty = _lane(eng, 4)
    zero_starts = eng.stats.state_zero_starts
    eng.prefill(4, PROMPT[:20])
    eng.prefill(5, PROMPT[60:70])
    eng.cache = eng.cache._replace(  # a lane never used
        delta=eng.cache.delta.at[:, 5].set(0.0),
        delta_conv=eng.cache.delta_conv.at[:, 5].set(0.0))
    eng.prefill(5, PROMPT[:20])
    assert eng.stats.state_zero_starts == zero_starts + 3
    for name in ("delta", "delta_conv"):
        np.testing.assert_array_equal(_lane(eng, 4)[name], _lane(eng, 5)[name], err_msg=name)
        assert not np.array_equal(_lane(eng, 4)[name], dirty[name])
    # a decode step at position 0 starts a sequence too
    eng.cache = eng.cache._replace(
        delta=eng.cache.delta.at[:, 6].set(3.0),
        delta_conv=eng.cache.delta_conv.at[:, 6].set(3.0))
    eng.decode(*_park(eng, {6: (9, 0), 7: (9, 0)}))
    for name in ("delta", "delta_conv"):
        np.testing.assert_array_equal(_lane(eng, 6)[name], _lane(eng, 7)[name], err_msg=name)


def test_a_lane_taken_out_and_put_back_carries_every_leaf(eng):
    """The fused step's splice of the admitted lane: lane 2's rows of every
    leaf after a fused admission are the rows a synchronous prefill writes,
    and no other lane's rows moved; a copy of a lane is refused by name."""
    eng.prefill(5, PROMPT[:50])
    others = _lane(eng, 5)
    eng.prefill(3, PROMPT[:60])
    eng.decode_prefill_fused(np.full(8, SEQ, np.int32), p_lane=2, chunk=PROMPT[:60],
                             tokens=np.zeros(8, np.int32))
    eng.pipeline_flush()
    assert FAMILY.lanes_rel_err(eng, 3, 2, 60) < 1e-5
    for name, was in others.items():
        np.testing.assert_array_equal(_lane(eng, 5)[name], was, err_msg=name)
    with pytest.raises(RuntimeError, match="recurrent state"):
        eng.copy_lane(0, 1)
    n = eng.n_lanes
    z = np.zeros(n, np.int32)
    with pytest.raises(ValueError, match="without speculation"):
        eng.decode_spec(z, np.zeros((n, eng.SPEC_DRAFT), np.int32), z, z)


def test_the_fused_step_carries_the_delta_scopes_in_both_halves(eng):
    """What the cell's four readers read: every heavy operation of the fused
    step sits under a scope, its class and one half, and the three delta
    scopes, the experts' and the shared expert's are among them (the lowering
    and the checks are tests/test_step_class.py's; the other toys' programs
    are held there)."""
    from test_step_class import LOC_DEF, check_classes_and_halves, heavy_op_names
    from test_tracing import lowered_with_debug_info
    import test_step_class

    text = lowered_with_debug_info(eng, "_decode_prefill_fn")
    paths = [names.scope_path(n) for n in dict(LOC_DEF.findall(text)).values()]
    for scope in names.DELTA_MIXER_SCOPES + (names.SCOPE_ATTENTION, names.SCOPE_EXPERTS,
                                             names.SCOPE_SHARED_EXPERT):
        assert any(scope in p for p in paths), scope
    assert not [n for n in heavy_op_names(text) if names.scope_of(n) is None]
    # the chunk [1, 2, 3] rides this engine's one bucket
    test_step_class.EXPECTED["_decode_prefill_fn"] = (
        names.step_class("fused", 64), set(names.HALVES))
    try:
        # every FFN of this family routes: no product lies directly under dl.ffn
        check_classes_and_halves(text, "_decode_prefill_fn",
                                 ffn_scopes=(names.SCOPE_FFN, names.SCOPE_EXPERTS))
    finally:
        test_step_class.EXPECTED["_decode_prefill_fn"] = (
            names.step_class("fused", test_step_class.BUCKET), set(names.HALVES))


def test_paged_kv_is_refused_by_name(built):
    config = FAMILY.program_config(CFG)
    params = FAMILY.assemble_params(config, built[1])
    with pytest.raises(ValueError, match="6 delta-rule"):
        InferenceEngine(config, params, n_lanes=4, paged_kv=True)
