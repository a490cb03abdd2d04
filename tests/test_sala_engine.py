"""Linear-attention and block-sparse layers in the layer-pattern block
(models/hybrid.py, ops/linear_attention.py, ops/block_sparse.py) through
``InferenceEngine`` and the scheduler at a toy size on the CPU: eight layers in
the order ``S L L L L S S L`` with sparse sizes small enough that the top-k
drops blocks. Prefill whole and in chunks with a padded tail, decode through
the cache across ``dense_len`` and across a kernel's end, fused admissions
beside decoding lanes and parked twins against the benchmark's plain
reference; the rule for the matrix state and for the compressed keys in every
step family; dense and sparse lanes in one decode step; the kernels in
interpret mode at a shape they tile; what is declined and counted
(tests/test_sala_serving.py: a checkpoint through the writer, the loader and
the scheduler; tests/test_lane_state_contract.py's row ``sala``: the rule for
the matrix state and the compressed keys in every step family).

One engine for the file (``built``, the default ladder: one bucket of 64): a
case builds an engine of its own only where the construction is its subject
(another ladder, another shape, a monkeypatched selection or layer loop)."""

import copy

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats.model_file import LayerKind
from distributed_llama_multiusers_tpu.models import hybrid
from distributed_llama_multiusers_tpu.models.hybrid import HybridCache, kind_runs, layer_periods
from distributed_llama_multiusers_tpu.ops import linear
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

import latent_toy
from latent_toy import park

CFG, FAMILY, CORRECT = latent_toy.toy("sala")
SEQ = CFG["max_position_embeddings"]
PROMPT = [int(x) for x in np.random.default_rng(0).integers(2, CFG["vocab_size"], size=120)]
L, S = LayerKind.LINEAR, LayerKind.SPARSE


built = latent_toy.module_engine(FAMILY, CFG, seed=5, lanes=8)


@pytest.fixture(scope="module")
def eng(built):
    return built[0]


def test_the_cache_has_four_kinds_of_leaf_and_the_state_is_counted(eng):
    assert isinstance(eng.cache, HybridCache)
    assert eng.config.layer_kinds == (S, L, L, L, L, S, S, L)
    # no period: a pass over five layers, its run of four a scan of its own, and a tail
    assert layer_periods(eng.config.layer_kinds) == (5, 1)
    assert kind_runs(eng.config.layer_kinds) == [(0, 1), (1, 4), (5, 2), (7, 1)]
    assert eng.cache.k.shape == eng.cache.v.shape == (3, 8, SEQ, 64)
    assert eng.cache.ck.shape == (3, 8, SEQ // 2, 64) and eng.cache.ck.dtype == eng.cache.k.dtype
    assert eng.cache.lin.shape == (5, 8, 4 * 32 * 32) and eng.cache.lin.dtype == jnp.float32
    assert eng.cache.ssm is None and eng.cache.wk is None and eng.cache.conv.shape == (0, 8, 0)
    # the matrix state is overwritten in place; the compressed keys are kept by position
    assert eng.lane_state_bytes == eng.cache.lin.nbytes == eng.stats.recurrent_state_bytes
    facts = eng.path_facts()
    assert facts["declined_for_recurrent_state"] == ["prefix_reuse", "speculation"]
    assert facts["linear_state_bytes"] == eng.cache.lin.nbytes
    assert facts["block_sparse_path"] == "xla_masked_key_blocks" and facts["sparse_blocks"] == "4x8@48"
    assert facts["compressed_key_bytes"] == eng.cache.ck.nbytes and not eng.supports_speculative
    assert eng.config.recurrent_state and eng.config.n_attention_layers == eng.config.n_sparse_layers == 3
    assert (eng.config.embed_scale, eng.config.logit_divisor) == (12.0, 2.0)
    np.testing.assert_allclose(eng.config.residual_scale, 1.4 / 8 ** 0.5)


@pytest.mark.parametrize("buckets", [None, (16, 32, 64)], ids=["whole", "chunks"])
def test_engine_agrees_with_the_plain_reference_and_the_routes_read_zero(built, buckets):
    """Prompts of 20, 46, 60 and 100 whole (one bucket each) and in chunks of
    at most 64 with a padded tail (100 = 64 + 32 + 4 of 16); four decode steps through the cache, the 46
    crossing dense_len (48) and a kernel's end while it decodes; the pipelined
    and fused programs against the synchronous ones on twins left parked, the
    whole state compared pair by pair."""
    # (the file's engine has the default ladder)
    e, tensors = built if buckets is None else latent_toy.engine(
        FAMILY, CFG, 5, prefill_buckets=buckets)
    r = CORRECT.compare(FAMILY, CFG, tensors, e, 5)
    assert r["ok"], r
    assert r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5
    assert (r["route_greedy_gap"], r["route_nucleus_excess"], r["route_kv_rel_err"]) == (0, 0, 0)
    assert r["route_token_mismatches"] == 0 and r["route_tokens"] >= 20


def test_the_scalars_of_the_parametrisation_are_told_apart(built):
    e, tensors = built
    for wrong in (dict(scale_emb=1), dict(scale_depth=1.0), dict(dim_model_base=128)):
        r = CORRECT.compare(FAMILY, dict(CFG, **wrong), tensors, e, 5)
        assert not r["ok"] and r["prefill_rel_err"] > 0.01, wrong


def _wide():
    """The toy at a shape the two decode kernels tile: heads of 128 (a matrix
    row is whole lane tiles; a kv head's columns are one), blocks of 16, a
    context of 256; served in bfloat16."""
    cfg = copy.deepcopy(CFG)
    cfg.update(hidden_size=512, head_dim=128, lightning_head_dim=128, dim_model_base=256,
               max_position_embeddings=256, mixer_types=["minicpm4", "lightning-attn"] * 2,
               num_hidden_layers=4)
    cfg["sparse_config"] = dict(kernel_size=8, kernel_stride=4, block_size=16, topk=4,
                                window_size=32, init_blocks=1, dense_len=96)
    cfg["correctness"]["prompt_tokens"] = [20, 94, 150, 200]
    return cfg


def test_kernels_in_interpret_mode_agree_with_the_masked_and_chunked_paths():
    """The one-row state kernel and the chosen-blocks kernel in every decode
    step (interpret mode), bfloat16, against the same engine without kernels:
    the same rows to the cache's rounding, lanes on both sides of dense_len. A
    row whose top-k flips on a near tie reads far off (the selection is
    discontinuous): the median row and the share of such rows are held."""
    cfg = _wide()
    prompts, forced = CORRECT.sample_sequences(cfg, 3)
    prefixes = [[] for _ in prompts]

    def run(interpret):
        linear.set_pallas_interpret(interpret)
        try:
            config = FAMILY.program_config(cfg)
            tensors = FAMILY.device_weights(config, 3, jnp.float32)
            e = InferenceEngine(config, FAMILY.assemble_params(config, tensors), n_lanes=8,
                                cache_dtype=jnp.bfloat16)
            facts = e.path_facts()
            return CORRECT.engine_logits(e, prompts, forced, prefixes), facts
        finally:
            linear.set_pallas_interpret(False)

    plain, facts = run(False)
    kernels, kfacts = run(True)
    assert facts["block_sparse_path"] == "xla_masked_key_blocks"
    assert kfacts["block_sparse_path"] == "pallas_chosen_blocks"
    err = CORRECT.relative_errors(kernels, plain)
    assert np.median(err) < 0.01 and (err > 0.1).mean() <= 0.1, err


def test_dense_and_sparse_lanes_decode_in_one_step_as_each_does_alone(eng):
    """Lane 0 at position 30 (under dense_len: every block it holds), lane 1
    at 100 (chooses 4 of 13): one step for both gives each lane the logits a
    step of its own gives it."""
    eng.prefill(0, PROMPT[:30])
    eng.prefill(1, PROMPT[:100])
    eng.prefill(2, PROMPT[:30])
    eng.prefill(3, PROMPT[:100])
    both, _, _ = eng.decode(*park(eng, {0: (7, 30), 1: (9, 100)}), want_logits=True)
    a, _, _ = eng.decode(*park(eng, {2: (7, 30)}), want_logits=True)
    b, _, _ = eng.decode(*park(eng, {3: (9, 100)}), want_logits=True)
    np.testing.assert_allclose(np.asarray(both[0]), np.asarray(a[2]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(both[1]), np.asarray(b[3]), rtol=1e-5, atol=1e-5)


def test_the_selection_is_in_the_logits(eng, monkeypatch):
    """With the selection left out of the program (every row takes every
    block it holds) a prompt past dense_len reads other logits and a prompt
    under it the same: what the reference's control shows from its side."""
    from distributed_llama_multiusers_tpu.ops import block_sparse

    short, long_ = np.asarray(eng.prefill(0, PROMPT[:40])[0]), np.asarray(eng.prefill(1, PROMPT[:110])[0])
    monkeypatch.setattr(block_sparse, "choose", lambda r, pos, sizes: jnp.broadcast_to(
        block_sparse.held_blocks(pos, r.shape[-1], sizes), r.shape))
    e2, _ = latent_toy.engine(FAMILY, CFG, 5, lanes=2)
    np.testing.assert_allclose(np.asarray(e2.prefill(0, PROMPT[:40])[0]), short, rtol=1e-5, atol=1e-5)
    other = np.asarray(e2.prefill(1, PROMPT[:110])[0])
    assert CORRECT.relative_errors(other[None], long_[None]).max() > 0.01


def test_the_layer_loop_with_its_runs_scanned_is_the_unrolled_one(eng, monkeypatch):
    """``S L L L L S S L`` has no period: its run of four linear layers is a
    scan of its own (the file's engine), against every layer unrolled (the
    same seed's weights); and a tail's long run scans too (``S S`` and nine
    ``L``)."""
    scanned = eng
    row_s = np.asarray(scanned.prefill(0, PROMPT[:70])[0])
    monkeypatch.setattr(hybrid, "layer_periods", lambda kinds: (1, 0))
    monkeypatch.setattr(hybrid, "RUN_SCAN_MIN", 99)
    unrolled, _ = latent_toy.engine(FAMILY, CFG, seed=5, lanes=2)
    row_u = np.asarray(unrolled.prefill(0, PROMPT[:70])[0])
    np.testing.assert_allclose(row_s, row_u, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(scanned.cache.lin[:, 0]), np.asarray(unrolled.cache.lin[:, 0]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(scanned.cache.ck[:, 0]), np.asarray(unrolled.cache.ck[:, 0]),
                               rtol=1e-4, atol=1e-4)
    monkeypatch.undo()
    cfg = dict(CFG, mixer_types=["minicpm4"] * 2 + ["lightning-attn"] * 9, num_hidden_layers=11)
    assert layer_periods(FAMILY.program_config(cfg).layer_kinds) == (6, 1)  # then a tail of five
    tail_scanned, _ = latent_toy.engine(FAMILY, cfg, seed=9, lanes=2)
    row_t = np.asarray(tail_scanned.prefill(0, PROMPT[:70])[0])
    monkeypatch.setattr(hybrid, "RUN_SCAN_MIN", 99)
    tail_unrolled, _ = latent_toy.engine(FAMILY, cfg, seed=9, lanes=2)
    np.testing.assert_allclose(row_t, np.asarray(tail_unrolled.prefill(0, PROMPT[:70])[0]),
                               rtol=1e-5, atol=1e-5)
