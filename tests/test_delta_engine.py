"""Gated delta-rule layers beside gated NoPE GQA layers over a held share of
routed experts (models/hybrid.py ``LayerKind.DELTA``, ops/delta_rule.py) through
``InferenceEngine`` at a toy size on the CPU: eight layers ``G D D D G D D D``,
4 heads of 16, convs of 4 taps, gates of rank 8, 2 kv heads, 16 experts of
which 4 are chosen and 4 held, one shared. Against the benchmark's plain
reference on logits: prefill whole and in chunks with a padded tail, decode
through the cache, a fused admission beside decoding lanes, parked twins; a
state at rest in bfloat16 told apart; what is declined and counted. (The rule
for the matrix state AND the convs' windows in every step family, a request
that follows another on a lane and the fused step's splice of every leaf are
tests/test_lane_state_contract.py's row ``solar``.)"""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats.model_file import LayerKind
from distributed_llama_multiusers_tpu.models.hybrid import HybridCache, layer_periods
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine
from distributed_llama_multiusers_tpu.telemetry import names

import latent_toy

CFG, FAMILY, CORRECT = latent_toy.toy("solar")
SEQ = CFG["max_position_embeddings"]
PROMPT = [int(x) for x in np.random.default_rng(0).integers(2, CFG["vocab_size"], size=120)]
G, D = LayerKind.ATTENTION, LayerKind.DELTA


# One engine for the file and ONE bucket, 64 rows (its programs compile once:
# the tier-1 clock): a prompt of 100 is 64 + 36 of 64, one of 20 rides 44
# padded rows, and a chunk of the chunk form is 32 of them.
built = latent_toy.module_engine(FAMILY, CFG, seed=5, lanes=8, prefill_buckets=(64,))


@pytest.fixture(scope="module")
def eng(built):
    return built[0]


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
