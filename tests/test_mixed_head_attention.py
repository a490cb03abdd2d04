"""Heads that differ by layer kind (``model_type: mimo_v2_flash``;
models/hybrid.py): full-context layers beside window layers with kv heads and
a rotation base of their own, keys wider than values, a head that rotates in
part, scaled values and a sink a query head. The ``.m`` header and walk, the
sink and the value width in every attention path against the dense form (the
decode kernel under both work lists in interpret mode, a key block at a time),
and the toy through ``InferenceEngine`` against the benchmark family's plain
reference in every step family."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats import model_file as mf
from distributed_llama_multiusers_tpu.formats.synthetic import (
    tiny_mixed_head_header,
    tiny_window_header,
    write_synthetic_model,
)
from distributed_llama_multiusers_tpu.models import deepseek, hybrid
from distributed_llama_multiusers_tpu.models.config import LlamaConfig
from distributed_llama_multiusers_tpu.models.llama import _dense_attention
from distributed_llama_multiusers_tpu.models.loader import (
    load_params_from_m,
    load_params_from_m_quantized,
)
from distributed_llama_multiusers_tpu.ops import blocked_attention as ba
from distributed_llama_multiusers_tpu.ops import pallas_attention as pa
from distributed_llama_multiusers_tpu.ops.rope import apply_rope, apply_rope_first

import latent_toy

CFG, FAMILY, CORRECT = latent_toy.toy("mimo")
SEQ = CFG["max_position_embeddings"]
NEW_KEYS = (mf.KEY_ROTARY_DIM, mf.KEY_WINDOW_N_KV_HEADS, mf.KEY_WINDOW_ROPE_THETA,
            mf.KEY_ATTN_VALUE_SCALE_E6, mf.KEY_WINDOW_SINK)


# -- the header and the walk ----------------------------------------------------


def test_the_header_round_trips_and_the_walk_has_a_stack_a_kind(tmp_path):
    h = tiny_mixed_head_header()
    path = str(tmp_path / "mixed.m")
    write_synthetic_model(path, h, seed=3, scale=0.1)
    back = mf.load_model_header(path)
    for name in (*mf.MIXED_HEAD_FIELDS, "v_head_dim", "head_dim", "n_dense_layers",
                 "moe_select_bias", "experts_held_count", "sliding_window"):
        assert getattr(back, name) == getattr(h, name), name
    assert (back.rotary_dim, back.window_n_kv_heads, back.window_rope_theta) == (8, 4, 10000.0)
    assert (back.attn_value_scale, back.window_sink, back.value_head_size) == (0.707, 1, 16)
    assert (back.q_dim, back.o_dim, back.kv_heads(), back.kv_heads(True)) == (192, 128, 2, 4)
    shapes = {(s.name, s.layer): s.shape for s in mf.model_tensor_specs(back)}
    assert shapes["block_matmul_k", 0] == (48, 64) and shapes["block_matmul_v", 0] == (32, 64)
    assert shapes["block_matmul_k", 1] == (96, 64) and shapes["block_matmul_v", 1] == (64, 64)
    assert shapes["block_matmul_wo", 1] == (64, 128) and shapes["block_attn_sink", 1] == (1, 8)
    assert ("block_attn_sink", 0) not in shapes and ("block_attn_sink", 5) not in shapes
    assert ("block_moe_gate", 0) not in shapes and shapes["block_moe_bias", 1] == (1, 16)
    for load in (load_params_from_m, load_params_from_m_quantized):
        config, params = load(path, back, dtype=jnp.float32)
        a = params.attn
        # a Q40 plane holds two rows a byte: [.., d_in / 2, d_out]
        shape = lambda w: (*w.packed.shape[:-2], 2 * w.packed.shape[-2], w.packed.shape[-1]) \
            if hasattr(w, "packed") else w.shape  # noqa: E731
        assert config.split_kv_kinds and config.kv_widths() == (48, 32)
        assert config.kv_widths(True) == (96, 64) and config.rope_dim == 8
        assert shape(a.wq) == (8, 64, 192) and shape(a.wo) == (8, 128, 64)
        assert shape(a.wk) == (2, 64, 48) and shape(a.wv) == (2, 64, 32)
        assert shape(a.wk_w) == (6, 64, 96) and shape(a.wv_w) == (6, 64, 64)
        assert a.sink.shape == (6, 8) and a.sink.dtype == jnp.float32
        # two tables of the rotated width, one a base
        assert params.rope_cos.shape == params.rope_cos_w.shape == (64, 4)
        assert not np.allclose(params.rope_cos[5], params.rope_cos_w[5])
    cache = hybrid.init_hybrid_cache(config, 3, jnp.float32, max_chunk=4)
    assert cache.k.shape == (2, 3, 64, 48) and cache.v.shape == (2, 3, 64, 32)
    assert cache.wk.shape == (6, 3, 12, 96) and cache.wv.shape == (6, 3, 12, 64)


def test_a_file_without_the_fields_is_written_and_read_as_before(tmp_path):
    h = tiny_window_header()
    keys = [k for k, _ in h.to_kv_pairs()]
    assert not set(keys) & set(NEW_KEYS)
    config = LlamaConfig.from_header(h)
    assert not config.split_kv_kinds and config.kv_widths() == config.kv_widths(True)
    assert (config.rope_dim, config.value_head_size, config.o_dim) == (16, 16, config.q_dim)
    assert config.kv_widths() == (config.kv_dim, config.kv_dim)


@pytest.mark.parametrize("wrong,match", [
    (dict(rotary_dim=7), "rotary_dim"), (dict(rotary_dim=32), "rotary_dim"),
    (dict(window_n_kv_heads=3), "window_n_kv_heads"),
    (dict(layer_kinds=(0,) * 8), "need a window layer"),
    (dict(layer_kinds=()), "layer-kind list"),
])
def test_what_the_fields_need_is_refused_by_name(wrong, match):
    config = LlamaConfig.from_header(tiny_mixed_head_header())
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(config, **wrong)


def test_the_first_numbers_of_a_head_rotate_and_the_rest_do_not():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 3, 4, 24)), jnp.float32)
    ang = rng.normal(size=(16, 4))
    cos, sin = jnp.asarray(np.cos(ang), jnp.float32), jnp.asarray(np.sin(ang), jnp.float32)
    pos = jnp.asarray([[0, 5, 9], [3, 3, 15]], jnp.int32)
    got = apply_rope_first(x, 8, cos, sin, pos)
    np.testing.assert_array_equal(np.asarray(got[..., 8:]), np.asarray(x[..., 8:]))
    np.testing.assert_array_equal(
        np.asarray(got[..., :8]), np.asarray(apply_rope(x[..., :8], cos, sin, pos)))
    whole = jnp.asarray(np.cos(rng.normal(size=(16, 12))), jnp.float32)
    np.testing.assert_array_equal(np.asarray(apply_rope_first(x, 24, whole, whole, pos)),
                                  np.asarray(apply_rope(x, whole, whole, pos)))


# -- the sink and the value width, path by path --------------------------------


def _dense(q, true_k, true_v, mask, scale, n_kv, sink):
    lanes, t, n_heads, hd = q.shape
    seq = true_k.shape[1]
    f32 = lambda a: jnp.asarray(a).astype(jnp.float32)  # noqa: E731
    out = _dense_attention(
        f32(q).reshape(lanes, t, n_kv, n_heads // n_kv, hd),
        f32(true_k).reshape(lanes, seq, n_kv, -1), f32(true_v).reshape(lanes, seq, n_kv, -1),
        jnp.asarray(mask), scale,
        None if sink is None else jnp.asarray(sink, jnp.float32).reshape(n_kv, -1))
    return np.asarray(out).reshape(lanes, t, n_heads, -1)


def _ring_of(true, last, ring):
    out = np.full((true.shape[0], ring, true.shape[2]), 7.5, np.float32)
    for b, hi in enumerate(last):
        for p in range(hi + 1):
            out[b, p % ring] = true[b, p]
    return out


def test_a_sink_takes_mass_and_gives_no_value():
    rng = np.random.default_rng(1)
    q = rng.normal(size=(1, 1, 2, 8)).astype(np.float32)
    k, v = rng.normal(size=(1, 6, 8)).astype(np.float32), rng.normal(size=(1, 6, 8)).astype(np.float32)
    mask = np.ones((1, 1, 6), bool)
    bare = _dense(q, k, v, mask, 0.5, 1, None)
    # a sink of -inf is no sink; a large one takes nearly all the mass
    np.testing.assert_allclose(_dense(q, k, v, mask, 0.5, 1, [-np.inf, -np.inf]), bare, rtol=1e-6)
    sunk = _dense(q, k, v, mask, 0.5, 1, [0.0, 30.0])
    scores = 0.5 * np.einsum("h,sh->s", q[0, 0, 0], k[0])
    share = np.exp(scores).sum() / (1.0 + np.exp(scores).sum())
    np.testing.assert_allclose(sunk[0, 0, 0], share * bare[0, 0, 0], rtol=1e-5)
    assert np.abs(sunk[0, 0, 1]).max() < 1e-6


@pytest.mark.parametrize("sunk", [False, True])
@pytest.mark.parametrize("ringed", [False, True])
def test_the_decode_kernel_takes_keys_wider_than_values_and_a_sink(ringed, sunk):
    """Interpret mode, merged rows of 2 kv heads: keys 192 wide (a head
    straddles 128-lane tiles), values 128, under ``lane_blocks`` over a plane
    and ``ring_blocks`` over a ring of three blocks for a window of 128 (half
    a block: a lane reads one block or two), lanes on both sides of a block's
    edge and of the wrap, a parked lane; against the dense form over the true
    rows, the sink a concatenated column there."""
    n_heads, n_kv, hd, vd, seq = 16, 2, 192, 128, 1024
    window, ring = (128, 768) if ringed else (0, seq)
    pos = np.asarray([3, 127, 128, 300, 511, 770, 1000, seq], np.int32)  # the last is parked
    lanes = len(pos)
    rng = np.random.default_rng(11 + ringed)
    true_k = rng.normal(size=(lanes, seq, n_kv * hd)).astype(np.float32)
    true_v = rng.normal(size=(lanes, seq, n_kv * vd)).astype(np.float32)
    last = [int(p) if p < seq else -1 for p in pos]
    k_all = jnp.asarray(np.stack([_ring_of(true_k, last, ring)] * 2), jnp.bfloat16)
    v_all = jnp.asarray(np.stack([_ring_of(true_v, last, ring)] * 2), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(lanes, n_heads, hd)), jnp.bfloat16)
    sink = rng.uniform(1.0, 4.0, size=n_heads).astype(np.float32) if sunk else None
    assert pa.supports(k_all, n_heads, n_kv, v_all)
    assert not pa.supports(k_all, n_heads, n_kv, v_all[:, :, :512])  # another context
    assert not pa.supports_prefill(k_all, n_heads, n_kv)  # a key straddles a column tile
    work = (pa.ring_blocks(jnp.asarray(pos), seq, window, ring) if ringed
            else pa.lane_blocks(jnp.asarray(pos), seq))
    scale = 0.3 / hd ** 0.5
    got = np.asarray(pa.decode_attention(
        q, k_all, v_all, 1, work, scale, interpret=True,
        sink=None if sink is None else jnp.asarray(sink)))
    assert got.shape == (lanes, n_heads, vd)
    s, at = np.arange(seq)[None, None, :], pos[:, None, None]
    mask = (s <= at) & ((s > at - window) if window else True)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731
    want = _dense(q[:, None], bf(true_k), bf(true_v), mask, scale, n_kv, sink)[:, 0]
    np.testing.assert_allclose(got[:-1], want[:-1], rtol=2e-2, atol=2e-2)
    assert (got[-1] == 0).all()  # the parked lane read nothing, sink or none
    if sunk:  # and the sink is no rounding: without it the rows read apart
        bare = _dense(q[:, None], bf(true_k), bf(true_v), mask, scale, n_kv, None)[:, 0]
        assert np.abs(bare[:-1] - want[:-1]).max() > 0.1


@pytest.mark.parametrize("ring,window,start,t,n_valid,block,sunk", [
    (64, 0, 20, 9, 6, 16, False),   # a plane, a second chunk with a padded tail
    (12, 8, 6, 4, 4, 5, True),      # crossing the window inside a chunk
    (12, 8, 21, 4, 3, 5, True),     # past the wrap, a padded tail
    (16, 8, 30, 8, 8, 3, True),
])
def test_a_key_block_at_a_time_takes_the_same_widths_and_the_same_column(
        ring, window, start, t, n_valid, block, sunk):
    rng = np.random.default_rng(ring * 1000 + start)
    n_heads, n_kv, hd, vd, lanes = 8, 2, 24, 16, 2
    true_k = rng.normal(size=(lanes, SEQ, n_kv * hd)).astype(np.float32)
    true_v = rng.normal(size=(lanes, SEQ, n_kv * vd)).astype(np.float32)
    last = [start + n_valid - 1] * lanes
    k_all = jnp.asarray(np.stack([_ring_of(true_k, last, ring)] * 2))
    v_all = jnp.asarray(np.stack([_ring_of(true_v, last, ring)] * 2))
    q = jnp.asarray(rng.normal(size=(lanes, t, n_heads, hd)).astype(np.float32))
    positions = jnp.asarray(np.tile(start + np.arange(t), (lanes, 1)), jnp.int32)
    sink = rng.uniform(0.0, 2.0, size=n_heads).astype(np.float32) if sunk else None
    got = np.asarray(ba.blocked_attention(
        q, k_all, v_all, 1, positions, jnp.full((lanes,), n_valid, jnp.int32), n_kv, 0.35,
        window=window, block=block, sink=None if sink is None else jnp.asarray(sink)))
    assert got.shape == (lanes, t, n_heads, vd)
    s, pos = np.arange(SEQ)[None, None, :], np.asarray(positions)[:, :, None]
    mask = (s <= pos) & ((s > pos - window) if window else True)
    want = _dense(q, true_k, true_v, mask, 0.35, n_kv, sink)
    np.testing.assert_allclose(got[:, :n_valid], want[:, :n_valid], rtol=2e-5, atol=2e-6)
    assert np.isfinite(got).all()  # a padded row reads junk, never NaN


# -- the toy through the engine, against the plain reference ---------------------


@pytest.fixture(scope="module")
def chunked():
    """(engine, tensors) with a ladder of 2 and 4, a ring of 12 rows: the
    file's engine, taken by the compare in chunks and by every case that only
    reads and writes lanes (tests/test_lane_state_contract.py's row ``mimo``
    holds the rings to the rule every per-lane state keeps)."""
    return latent_toy.engine(FAMILY, CFG, seed=5, lanes=10, prefill_buckets=(2, 4))


def _reference_rows(tensors, tokens, rows):
    return FAMILY.reference_logits(
        CFG, tensors, np.asarray([tokens], np.int32), np.asarray([rows], np.int32))[0]


def _tokens(n, seed=0):
    return [int(x) for x in np.random.default_rng(seed).integers(2, CFG["vocab_size"], size=n)]


def _rel(got, want):
    g, w = got - got.mean(-1, keepdims=True), want - want.mean(-1, keepdims=True)
    return float(np.linalg.norm(g - w) / np.linalg.norm(w))


@pytest.mark.parametrize("ladder", [(64,), (2, 4)])
def test_every_step_family_agrees_with_the_reference(chunked, ladder):
    """`correct.compare` at the toy's size: prompts under the window, one that
    crosses it while it decodes (6 + 6 steps over a window of 8), and prompts
    past the ring's wrap; prefilled whole (a ladder of one bucket as long as
    the context) and in chunks of 4 whose padded tails cross the window and
    wrap the ring of 12; then decoded through the cache, a pipelined chain and
    fused admissions (whole and in two chunks) beside decoding lanes, every
    twin parked while its other steps: logits at float32's noise, the chain's
    tokens the synchronous programs', and all four cache leaves of each pair
    of lanes the same."""
    eng, tensors = chunked if ladder == (2, 4) else latent_toy.engine(
        FAMILY, CFG, seed=5, lanes=10, prefill_buckets=ladder)
    assert eng.ring_rows == (12 if ladder == (2, 4) else SEQ)
    r = CORRECT.compare(FAMILY, CFG, tensors, eng, 5)
    assert r["ok"], r
    assert r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5
    assert (r["route_greedy_gap"], r["route_nucleus_excess"], r["route_kv_rel_err"]) == (0, 0, 0)
    assert r["route_token_mismatches"] == 0 and r["route_tokens"] >= 20


def test_a_second_chunk_continues_the_first_and_decode_crosses_the_windows_edge(chunked):
    eng, tensors = chunked
    toks = _tokens(21, 3)
    # 5 tokens in chunks 4 + 1 (a padded tail), then steps across position 8
    last, _, _ = eng.prefill(0, toks[:5])
    rows = [np.asarray(last, np.float32)]
    for j in range(5, 12):
        tok, pos = np.zeros(10, np.int32), np.full(10, SEQ, np.int32)
        tok[0], pos[0] = toks[j], j
        rows.append(np.asarray(eng.decode(tok, pos, want_logits=True)[0], np.float32)[0])
    want = _reference_rows(tensors, toks[:12], list(range(4, 12)))
    assert max(_rel(g, w) for g, w in zip(rows, want)) < 1e-5
    # past the wrap of the 12-row ring, in chunks with a padded tail
    last, _, _ = eng.prefill(1, toks)
    assert _rel(np.asarray(last, np.float32), _reference_rows(tensors, toks, [20])[0]) < 1e-5


def test_a_parked_lane_keeps_all_four_leaves_and_a_reused_lane_starts_from_nothing(chunked):
    eng, tensors = chunked
    toks = _tokens(30, 4)
    eng.prefill(2, toks[:17])
    before = [np.asarray(leaf[:, 2]) for leaf in (eng.cache.k, eng.cache.v, eng.cache.wk, eng.cache.wv)]
    eng.prefill(3, _tokens(9, 5))          # another lane's chunks
    tok, pos = np.zeros(10, np.int32), np.full(10, SEQ, np.int32)
    tok[3], pos[3] = 7, 9
    eng.decode(tok, pos)                   # a decode step in which lane 2 is parked
    after = [np.asarray(leaf[:, 2]) for leaf in (eng.cache.k, eng.cache.v, eng.cache.wk, eng.cache.wv)]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, a)
    # the lane goes on from where it stood, and a lane that held a longer
    # request before reads nothing of it
    last, _, _ = eng.prefill(2, toks[17:], start_pos=17)
    want = _reference_rows(tensors, toks, [29])[0]
    assert _rel(np.asarray(last, np.float32), want) < 1e-5
    eng.prefill(3, _tokens(40, 6))
    last, _, _ = eng.prefill(3, toks)
    assert _rel(np.asarray(last, np.float32), want) < 1e-5


def test_the_start_up_line_names_the_path_and_the_bytes_a_kind(chunked):
    eng, _ = chunked
    facts = eng.path_facts()
    assert facts["attention_path_by_kind"] == {"full": "xla_dense", "window": "xla_dense"}
    assert facts["kv_row_widths_by_kind"] == {"full": [48, 32], "window": [96, 64]}
    assert facts["window_sink"] is True and facts["kv_ring_rows"] == 12
    assert facts["kv_plane_bytes"] == 2 * 10 * 64 * (48 + 32) * 4
    assert facts["kv_ring_bytes"] == 6 * 10 * 12 * (96 + 64) * 4
    assert facts["declined_for_recurrent_state"] == ["prefix_reuse", "speculation"]
    assert facts["experts_held"] == "4/16"
    with pytest.raises(RuntimeError, match="recurrent state"):
        eng.copy_lane(0, 1)  # a ring is overwritten in place: no copy at another position


def test_the_kernels_in_interpret_mode_run_the_toy_at_the_dense_paths_numbers(pallas_interpret):
    """Keys of 192 and values of 128 on 2 and 4 kv heads of a 256-wide stream,
    a context of two blocks, a bfloat16 cache, Pallas in interpret mode: both
    kinds' decode steps read their stacks in place (the ring through the
    kernel with the sink), and the chain's tokens are the synchronous
    programs'; the logits stand where bfloat16 stands."""
    import copy

    cfg = copy.deepcopy(CFG)
    cfg.update(hidden_size=256, head_dim=192, swa_head_dim=192, v_head_dim=128,
               swa_v_head_dim=128, max_position_embeddings=512, sliding_window=128,
               num_hidden_layers=4, hybrid_layer_pattern=[0, 1, 1, 0],
               moe_layer_freq=[0, 1, 1, 1], num_attention_heads=8, swa_num_attention_heads=8)
    # across the window's edge while decoding (125 + 8 steps), and past the
    # wrap of the 256-row ring (256 + 8; admitted in chunks of 128 + 128)
    cfg["correctness"].update(prompt_tokens=[125, 256], decode_steps=8)
    eng, tensors = latent_toy.engine(
        FAMILY, cfg, 5, dtype=jnp.bfloat16, lanes=10, prefill_buckets=(64, 128))
    facts = eng.path_facts()
    assert facts["attention_path_by_kind"] == {
        "full": "pallas_in_place", "window": "pallas_in_place"}
    assert eng.cache.wk.shape == (2, 10, 256, 768) and eng.cache.wv.shape[-1] == 512
    assert eng.cache.k.shape == (2, 10, 512, 384) and eng.cache.v.shape[-1] == 256
    assert facts["window_attention_path"] == "pallas_in_place_ring"
    kernel = CORRECT.compare(FAMILY, cfg, tensors, eng, 5, keep_rows=True)
    assert (kernel["route_greedy_gap"], kernel["route_nucleus_excess"]) == (0, 0)
    assert kernel["route_kv_rel_err"] == 0 and kernel["route_token_mismatches"] == 0
    # bfloat16 against the float32 reference: a row reads 0.005-0.026, as on
    # the dense path (0.006-0.017 on the same seed), but for a row where
    # rounding decides a near tie of the router (tests/test_hybrid_engine.py
    # says how that reads: 0.06-0.1 there, up to 0.22 at this toy's four of
    # sixteen experts); a sink left out reads 0.3 on EVERY row
    rows = np.asarray(kernel["row_errors"])
    assert np.median(rows) < 0.012, rows
    assert (rows > 0.03).sum() <= 2 and rows.max() < 0.3, rows


# -- the held share ---------------------------------------------------------------


def test_the_four_shares_of_four_experts_add_up_to_the_uncut_layer():
    """The family's router (sigmoid scores, a bias that chooses, renormalised,
    no shared expert, no groups) through ``routed_ffn``: the routed parts of
    the 4 shares of 4 experts, summed, are the layer's that holds all 16."""
    whole = dataclasses.replace(FAMILY.program_config(CFG), experts_held_count=0)
    e, k, d, h = whole.n_experts, whole.n_active_experts, whole.dim, whole.moe_hidden_dim
    rng = np.random.default_rng(5)
    w = lambda *s: jnp.asarray(s[-2] ** -0.5 * rng.normal(size=s), jnp.float32)  # noqa: E731
    rp = deepseek.RoutedFfnParams(
        gate=w(d, e), bias=jnp.asarray(rng.uniform(-0.1, 0.1, size=e), jnp.float32),
        w1=w(e, d, h), w2=w(e, h, d), w3=w(e, d, h), s1=None, s2=None, s3=None,
        rms_ffn=jnp.ones(d))
    ops = deepseek.ffn_ops(whole, False)
    x = jnp.asarray(rng.normal(size=(2, 6, d)), jnp.float32)
    live = jnp.ones(12, bool)

    def run(cfg, params):
        out, _slabs, fetched, _, unheld = deepseek.routed_ffn(
            cfg, ops, x, params, jnp.int32(0), live)
        return np.asarray(out - x, np.float64), int(fetched), int(unheld)

    uncut, pairs, unheld = run(whole, rp)
    assert (pairs, unheld) == (12 * k, 0) and np.abs(uncut).max() > 0.01
    total, fetched_sum = np.zeros_like(uncut), 0
    for first in range(0, e, 4):
        cfg = dataclasses.replace(whole, experts_held_first=first, experts_held_count=4)
        out, fetched, unheld = run(cfg, rp._replace(
            w1=rp.w1[first:first + 4], w2=rp.w2[first:first + 4], w3=rp.w3[first:first + 4]))
        assert fetched + unheld == 12 * k
        total, fetched_sum = total + out, fetched_sum + fetched
    assert fetched_sum == 12 * k  # every chosen pair is some share's
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)


# -- the scheduler's arithmetic ----------------------------------------------------


def test_the_scheduler_counts_the_rows_either_kind_needs(chunked):
    from distributed_llama_multiusers_tpu.runtime.scheduler import ContinuousBatchingScheduler
    from distributed_llama_multiusers_tpu.utils.testing import StubStreamTokenizer

    eng, _ = chunked
    sched = ContinuousBatchingScheduler(
        eng, StubStreamTokenizer(CFG["vocab_size"]), speculative=False, prefix_min_tokens=0)
    before = eng.stats.snapshot()
    sched._count_attention_rows(np.asarray([2, 7, 30] + [SEQ] * 7), steps=2)
    stats = {k: v - before[k] for k, v in eng.stats.snapshot().items() if k.startswith("attn_")}
    # pos + 1 a live lane a step, and min(pos + 1, 8); a parked lane counts nothing
    assert stats["attn_full_rows_needed"] == (3 + 4) + (8 + 9) + (31 + 32)
    assert stats["attn_window_rows_needed"] == (3 + 4) + (8 + 8) + (8 + 8)
    assert stats["attn_full_rows_read"] == 10 * SEQ * 2  # the dense path reads whole planes
