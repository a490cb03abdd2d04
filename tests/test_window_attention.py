"""Window attention as a layer kind (models/hybrid.py, ``LayerKind.WINDOW``):
the ring's one rule in every step family, the ring against a plane under the
window's mask, attention a key block at a time against the dense form, the
decode kernel over a ring (interpret mode) against the dense masked form, the
counters, and a Llama block whose heads are not ``dim // n_heads`` wide."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats.model_file import load_model_header
from distributed_llama_multiusers_tpu.formats.synthetic import (
    tiny_header,
    tiny_window_header,
    write_synthetic_model,
)
from distributed_llama_multiusers_tpu.models import hybrid
from distributed_llama_multiusers_tpu.models.llama import _dense_attention
from distributed_llama_multiusers_tpu.models.loader import (
    load_params_from_m,
    load_params_from_m_quantized,
)
from distributed_llama_multiusers_tpu.ops import blocked_attention as ba
from distributed_llama_multiusers_tpu.ops import pallas_attention as pa
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

WINDOW, SEQ, LANES = 8, 64, 4


# -- the ring's rule, by arithmetic -------------------------------------------


@pytest.mark.parametrize("ring,window", [(12, 8), (64, 0), (64, 8), (9, 8)])
def test_held_position_is_the_newest_congruent_position_not_after_the_reader(ring, window):
    for t in (0, 5, 11, 12, 13, 40, 63):
        mask = np.asarray(ba.ring_mask(jnp.asarray([[t]], jnp.int32), ring, window))[0, 0]
        for r in range(ring):
            held = max((p for p in range(t + 1) if p % ring == r), default=-1)
            reads = held >= 0 and (not window or held > t - window)
            assert mask[r] == reads, (t, r)
        assert mask.sum() == (min(t + 1, window) if window else min(t + 1, ring))


def test_ring_rows_are_the_window_and_the_widest_chunk_in_whole_blocks():
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    cfg = LlamaConfig(dim=4096, hidden_dim=4096, n_layers=1, n_heads=128, n_kv_heads=8,
                      vocab_size=32, seq_len=32768, head_dim=128, sliding_window=4096,
                      layer_kinds=(3,))
    assert hybrid.ring_rows(cfg, 512) == 4608 == 18 * pa.BLOCK_ROWS
    assert hybrid.ring_rows(cfg, 64) == 4352 and hybrid.ring_rows(cfg, 1024) == 5120
    assert (cfg.head_size, cfg.q_dim, cfg.kv_dim) == (128, 16384, 1024)
    assert cfg.recurrent_state and (cfg.n_window_layers, cfg.n_attention_layers) == (1, 0)
    toy = LlamaConfig(dim=32, hidden_dim=64, n_layers=1, n_heads=4, n_kv_heads=2, vocab_size=32,
                      seq_len=64, head_dim=16, sliding_window=8, layer_kinds=(3,))
    assert hybrid.ring_rows(toy, 4) == 12 and hybrid.ring_rows(toy, 100) == 64


# -- a key block at a time against the dense form -------------------------------


def _true_rows(rng, lanes, n_pos, n_kv, hd):
    return rng.normal(size=(lanes, n_pos, n_kv * hd)).astype(np.float32)


def _ring_of(true, last, ring):
    """The ring after positions ``0..last[b]`` of ``true`` ``[B, P, w]`` were
    written at ``p mod ring``, junk in the rows nothing has reached."""
    out = np.full((true.shape[0], ring, true.shape[2]), 7.5, np.float32)
    for b, hi in enumerate(last):
        for p in range(hi + 1):
            out[b, p % ring] = true[b, p]
    return out


@pytest.mark.parametrize("ring,window,start,t,n_valid,block", [
    (64, 0, 0, 7, 7, 5),      # a plane, a chunk from 0, blocks that do not divide it
    (64, 0, 20, 9, 6, 16),    # a plane, a second chunk with a padded tail
    (12, 8, 0, 4, 4, 5),      # a ring under the window
    (12, 8, 6, 4, 4, 5),      # crossing the window inside a chunk
    (12, 8, 21, 4, 3, 5),     # past the wrap, a padded tail
    (12, 8, 45, 4, 4, 12),    # past it three times, one block
    (16, 8, 30, 8, 8, 3),
])
def test_blocked_attention_equals_dense_attention_under_the_same_mask(
        ring, window, start, t, n_valid, block):
    rng = np.random.default_rng(ring * 1000 + start)
    n_heads, n_kv, hd, lanes = 4, 2, 8, 2
    true_k, true_v = (_true_rows(rng, lanes, SEQ, n_kv, hd) for _ in range(2))
    last = [start + n_valid - 1] * lanes
    k_all = jnp.asarray(np.stack([_ring_of(true_k, last, ring)] * 2))  # layer 1 is read
    v_all = jnp.asarray(np.stack([_ring_of(true_v, last, ring)] * 2))
    q = jnp.asarray(rng.normal(size=(lanes, t, n_heads, hd)).astype(np.float32))
    positions = jnp.asarray(np.tile(start + np.arange(t), (lanes, 1)), jnp.int32)
    got = ba.blocked_attention(
        q, k_all, v_all, 1, positions, jnp.full((lanes,), n_valid, jnp.int32), n_kv, 0.35,
        window=window, block=block)
    s = np.arange(SEQ)[None, None, :]
    pos = np.asarray(positions)[:, :, None]
    mask = (s <= pos) & ((s > pos - window) if window else True)
    want = _dense_attention(
        q.reshape(lanes, t, n_kv, n_heads // n_kv, hd),
        jnp.asarray(true_k).reshape(lanes, SEQ, n_kv, hd),
        jnp.asarray(true_v).reshape(lanes, SEQ, n_kv, hd), jnp.asarray(mask), 0.35)
    np.testing.assert_allclose(
        np.asarray(got)[:, :n_valid], np.asarray(want).reshape(lanes, t, n_heads, hd)[:, :n_valid],
        rtol=2e-5, atol=2e-6)
    assert np.isfinite(np.asarray(got)).all()  # a padded row reads junk, never NaN


def test_blocked_attention_engages_by_shape_and_no_cell_of_2048_positions_meets_it():
    assert ba.engages(1, 512, 128, 32768) and ba.engages(1, 512, 128, 4608)
    assert ba.engages(1, 64, 128, 32768) and ba.engages(1, 256, 128, 4608)
    assert not ba.engages(16, 1, 128, 32768)                 # one row a lane: the kernel's
    assert not ba.engages(1, 1024, 32, 2048)                 # Mistral, LFM2, Jamba, Qwen (28)
    assert not ba.engages(1, 64, 128, 4608)


@pytest.mark.parametrize("start,n,bucket,ring,window", [
    (0, 512, 512, 32768, 0), (8192, 300, 512, 32768, 0), (0, 512, 512, 4608, 4096),
    (4000, 512, 512, 4608, 4096), (9000, 40, 64, 4608, 4096), (3, 9, 16, 12, 8)])
def test_chunk_block_counts_are_the_loop_and_the_least_the_mask_allows(
        start, n, bucket, ring, window):
    block = min(ba.BLOCK_KEYS, ring) if ring > 12 else 5
    visited, causal = ba.chunk_block_counts(start, n, bucket, ring, window, block)
    j0, j1 = (int(x) for x in ba.chunk_blocks(
        jnp.int32(start), jnp.int32(start + n - 1), ring, window, block))
    assert visited == bucket * (j1 - j0)
    want = 0
    for t in range(start, start + n):
        lo = max(t - window + 1, 0) if window else 0
        want += len({(p % ring) // block for p in range(lo, t + 1)})
    assert causal == want
    assert causal <= n * (j1 - j0)  # the loop leaves out no block a row reads


# -- the decode kernel over a ring ----------------------------------------------


def test_decode_kernel_over_a_ring_equals_the_dense_masked_form_wrap_included():
    """Interpret mode, a group of 16 query heads a kv head on merged rows of
    one 128-lane tile: lanes under the window, across it, past the wrap, past
    it many times, and a parked lane, against ``_dense_attention`` over the
    true rows with the window's mask."""
    window, ring, seq = 300, 768, 4096
    n_heads, n_kv, hd = 32, 2, 64
    pos = np.asarray([5, 299, 300, 700, 770, 3999, seq], np.int32)  # the last is parked
    lanes = len(pos)
    rng = np.random.default_rng(3)
    true_k, true_v = (_true_rows(rng, lanes, seq, n_kv, hd) for _ in range(2))
    last = [int(p) if p < seq else -1 for p in pos]
    k_all = jnp.asarray(np.stack([_ring_of(true_k, last, ring)] * 2), jnp.bfloat16)
    v_all = jnp.asarray(np.stack([_ring_of(true_v, last, ring)] * 2), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(lanes, n_heads, hd)), jnp.bfloat16)
    assert pa.supports(k_all, n_heads, n_kv)
    n_items, plan = pa.ring_blocks(jnp.asarray(pos), seq, window, ring)
    assert plan.shape[0] == 7
    plan_np, n_items = np.asarray(plan), int(n_items)
    # every live lane's items are the blocks that hold (pos - window, pos]
    for b, p in enumerate(pos[:-1]):
        mine = plan_np[:, :n_items][:, plan_np[0, :n_items] == b]
        lo = max(p - window + 1, 0)
        assert list(mine[5] // pa.BLOCK_ROWS) == list(range(lo // 256, p // 256 + 1))
        assert list(mine[2]) == [x % (ring // 256) for x in range(lo // 256, p // 256 + 1)]
        assert (mine[6] == lo).all() and (mine[3] == p).all()
    assert n_items == pa.ring_rows_read(pos, seq, window) // 256 + 1  # + the parked lane's
    got = pa.decode_attention(q, k_all, v_all, 1, (n_items, plan), 0.125, interpret=True)
    s = np.arange(seq)[None, None, :]
    at = pos[:, None, None]
    mask = (s <= at) & (s > at - window)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    want = _dense_attention(
        q.astype(jnp.float32).reshape(lanes, 1, n_kv, n_heads // n_kv, hd),
        bf(true_k).reshape(lanes, seq, n_kv, hd), bf(true_v).reshape(lanes, seq, n_kv, hd),
        jnp.asarray(mask), 0.125)
    want = np.asarray(want).reshape(lanes, n_heads, hd)
    np.testing.assert_allclose(np.asarray(got)[:-1], want[:-1], rtol=2e-2, atol=2e-2)
    assert (np.asarray(got)[-1] == 0).all()  # the parked lane read nothing


def test_a_full_context_plan_keeps_its_five_rows_and_its_kernel():
    _, plan = pa.lane_blocks(jnp.asarray([5, 600], jnp.int32), 1024)
    assert plan.shape[0] == 5


# -- the ring in every step family, on the toy ----------------------------------


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """(config, params) of the synthetic cohere2_moe toy: W W W F, window 8."""
    path = str(tmp_path_factory.mktemp("window") / "toy.m")
    write_synthetic_model(path, tiny_window_header(seq_len=SEQ), seed=5, scale=0.3)
    return load_params_from_m(path, load_model_header(path), dtype=jnp.float32)


def _engine(toy, buckets):
    config, params = toy
    return InferenceEngine(config, params, n_lanes=LANES, prefill_buckets=buckets,
                           cache_dtype=jnp.float32)


@pytest.fixture(scope="module")
def ringed(toy):
    return _engine(toy, (2, 4))  # a ring of 12 rows


@pytest.fixture(scope="module")
def planar(toy):
    return _engine(toy, (SEQ,))  # the ring is as long as the context: a plane


def _tokens(n, seed=0):
    return [int(x) for x in np.random.default_rng(seed).integers(2, 128, size=n)]


def _prefill_then_decode(engine, lane, tokens, n_prompt, steps=3):
    last, _, pos = engine.prefill(lane, tokens[:n_prompt])
    rows = [np.asarray(last, np.float32)]
    for j in range(steps):
        feed = np.zeros(LANES, np.int32)
        at = np.full(LANES, SEQ, np.int32)  # every other lane parks
        feed[lane], at[lane] = tokens[n_prompt + j], n_prompt + j
        logits, _, _ = engine.decode(feed, at, want_logits=True)
        rows.append(np.asarray(logits, np.float32)[lane])
    return np.stack(rows)


@pytest.mark.parametrize("n_prompt", [5, 7, 10, 20, 30, 45])
def test_ring_and_plane_give_the_same_logits(ringed, planar, n_prompt):
    """Under the window, crossing it while decoding and inside a chunk, past
    the ring's 12 rows, past 24, past 36; the ringed engine prefills in chunks
    of 4 (a padded tail where the length is odd), the planar one in one."""
    assert (ringed.ring_rows, planar.ring_rows) == (12, SEQ)
    toks = _tokens(n_prompt + 3, n_prompt)
    a = _prefill_then_decode(ringed, 1, toks, n_prompt)
    b = _prefill_then_decode(planar, 1, toks, n_prompt)
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_a_parked_lanes_ring_is_untouched_and_a_reused_lane_starts_from_nothing(ringed, planar):
    toks = _tokens(40, 1)
    ringed.prefill(0, toks[:30])  # lane 0 has wrapped twice
    before = [np.asarray(x[:, 0]) for x in hybrid.state_leaves(ringed.cache)]
    _prefill_then_decode(ringed, 2, _tokens(20, 2), 15, steps=5)  # five steps, lane 0 parked
    after = [np.asarray(x[:, 0]) for x in hybrid.state_leaves(ringed.cache)]
    assert all((x == y).all() for x, y in zip(before, after))
    # lane 0 again, for another request, from position 0: nothing is cleared,
    # and nothing of the old request is read
    fresh = _tokens(12, 9)
    a = _prefill_then_decode(ringed, 0, fresh, 9)
    b = _prefill_then_decode(planar, 3, fresh, 9)
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_the_blocked_path_inside_the_engine_and_its_counters(toy, planar, monkeypatch):
    """With the threshold at nothing every chunk's attention is computed a key
    block at a time (a ring of 12 and a plane of 64 are one block each): the
    same logits, and the two counters count by kind."""
    toks = _tokens(33, 4)
    b = _prefill_then_decode(planar, 1, toks, 30)
    assert planar.stats.snapshot()["prefill_attn_blocks_visited"] == 0  # dense there
    monkeypatch.setattr(ba, "DENSE_SCORE_BYTES", 0)
    engine = _engine(toy, (2, 4))
    a = _prefill_then_decode(engine, 1, toks, 30)
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    visited = causal = 0
    for start in range(0, 30, 4):
        n = min(4, 30 - start)
        bucket = 2 if n <= 2 else 4
        for layers, rows, window in ((1, SEQ, 0), (3, 12, WINDOW)):
            v, c = ba.chunk_block_counts(start, n, bucket, rows, window)
            visited, causal = visited + layers * v, causal + layers * c
    stats = engine.stats.snapshot()
    assert (stats["prefill_attn_blocks_visited"], stats["prefill_attn_blocks_causal"]) == (
        visited, causal)
    assert 0 < causal <= visited


def test_path_facts_name_the_ring_and_what_is_declined(ringed):
    facts = ringed.path_facts()
    assert facts["sliding_window"] == WINDOW and facts["kv_ring_rows"] == 12
    row = 2 * 16 * 4  # kv heads x head x float32
    assert facts["kv_ring_bytes"] == 2 * 3 * LANES * 12 * row
    assert facts["kv_plane_bytes"] == 2 * 1 * LANES * SEQ * row
    assert facts["recurrent_state_bytes"] == facts["kv_ring_bytes"] == ringed.lane_state_bytes
    assert facts["declined_for_recurrent_state"] == ["prefix_reuse", "speculation"]
    assert facts["window_attention_path"] == "xla_dense_ring"
    assert not ringed.supports_speculative
    with pytest.raises(RuntimeError, match="recurrent state"):
        ringed.copy_lane(0, 1, prefix_len=4)
    with pytest.raises(ValueError, match="does not serve"):
        InferenceEngine(ringed.config, ringed.params, n_lanes=2, paged_kv=True)


def test_the_scheduler_counts_window_rows_beside_the_full_context_kinds():
    from distributed_llama_multiusers_tpu.runtime.scheduler import ContinuousBatchingScheduler
    from distributed_llama_multiusers_tpu.utils.testing import MockAsyncEngine, StubStreamTokenizer

    seq = 8192
    engine = MockAsyncEngine(n_lanes=4, seq_len=seq, max_chunk=64)
    engine.decode_attention_block = engine.decode_ring_block = 256
    engine.ring_rows = 1280
    sched = ContinuousBatchingScheduler(
        engine, StubStreamTokenizer(engine.config.vocab_size, prompt_tokens=16),
        speculative=False, prefix_min_tokens=0, multi_step=0)
    sched._window = 1024
    positions = np.asarray([100, 1023, 5000, seq])  # under, at, past the window; parked
    sched._count_attention_rows(positions, steps=2)
    stats = engine.stats.snapshot()
    plane = sum(256 * ((p + s) // 256 + 1) for p in positions[:3] for s in range(2))
    ring = sum(256 * ((p + s) // 256 - max(p + s - 1023, 0) // 256 + 1)
               for p in positions[:3] for s in range(2))
    assert stats["attn_window_rows_plane"] == plane == stats["attn_full_rows_read"]
    assert stats["attn_kv_rows_read"] == plane  # the accepted pair: the full-context kind's
    assert stats["attn_window_rows_read"] == ring < plane
    assert stats["attn_kv_rows_whole"] == 2 * 4 * seq


# -- a chunk far into a long prompt takes the bucket under the largest --------------


@pytest.mark.parametrize("buckets,heads,plane,want", [
    ((64, 256, 512), 128, 32768, ba.TAPER_KEYS),   # the Command A+ cell
    ((64, 256, 512, 1024), 128, 32768, ba.TAPER_KEYS),  # a server's default ladder
    ((512,), 128, 32768, None),                    # one rung: nothing under it
    ((64, 256, 1024), 32, 2048, None),             # the 2048-position cells: dense scores
    ((64, 256, 512), 128, 8192, None),             # a plane no longer than the taper
    ((64, 256, 512), 16, 4096, None),
])
def test_the_taper_starts_where_a_long_plane_is_read_by_key_blocks(buckets, heads, plane, want):
    assert ba.taper_start(buckets, heads, plane) == want


def test_an_engine_tapers_its_chunks_by_start_and_gives_the_same_logits(toy, ringed, monkeypatch):
    """The toy's planes are too short for the rule, so the engine says no
    taper; with the start set by hand a prompt is cut 4 4 2 2 2 ... and the
    logits are the untapered engine's (a chunk's size changes no number)."""
    assert ringed.chunk_taper_start is None and "chunk_taper" not in ringed.path_facts()
    assert [ringed.max_chunk(s) for s in (0, 8, 40)] == [4, 4, 4]
    engine = _engine(toy, (2, 4))
    engine.chunk_taper_start = 8
    assert [engine.max_chunk(s) for s in (0, 4, 7, 8, 9, 40)] == [4, 4, 4, 2, 2, 2]
    assert engine.path_facts()["chunk_taper"] == "2@8"
    sizes = []
    plain = engine.prefill_chunk
    monkeypatch.setattr(engine, "prefill_chunk",
                        lambda lane, chunk, pos, **kw: (sizes.append((pos, len(chunk))),
                                                        plain(lane, chunk, pos, **kw))[1])
    toks = _tokens(24, 9)
    a = _prefill_then_decode(engine, 2, toks, 21)
    assert sizes == [(0, 4), (4, 4)] + [(p, 2) for p in range(8, 20, 2)] + [(20, 1)]
    np.testing.assert_allclose(a, _prefill_then_decode(ringed, 2, toks, 21), rtol=1e-4, atol=1e-5)


def test_the_scheduler_cuts_an_admissions_chunks_by_the_engines_taper():
    from distributed_llama_multiusers_tpu.runtime.scheduler import (
        ContinuousBatchingScheduler,
        Request,
    )
    from distributed_llama_multiusers_tpu.utils.testing import MockAsyncEngine, StubStreamTokenizer

    engine = MockAsyncEngine(n_lanes=2, seq_len=256, max_chunk=16)
    engine.max_chunk = lambda start=0: 8 if start >= 32 else 16
    cuts = {"sync": [], "fused": []}
    for name, key in (("prefill_chunk", "sync"), ("decode_prefill_fused", "fused")):
        plain = getattr(engine, name)

        def spy(*a, _plain=plain, _key=key, **kw):
            if _key == "sync":
                cuts[_key].append((a[2], len(a[1])))
            else:
                cuts[_key].append((kw["p_start"], len(kw["chunk"])))
            return _plain(*a, **kw)

        setattr(engine, name, spy)
    sched = ContinuousBatchingScheduler(
        engine, StubStreamTokenizer(engine.config.vocab_size, prompt_tokens=60),
        speculative=False, prefix_min_tokens=0, multi_step=0)
    sched.start()
    try:
        first = Request(prompt="a" * 60, max_tokens=40, temperature=0.0)
        sched.submit(first)
        while not first.generated_tokens:  # the second admission rides the chain
            pass
        second = Request(prompt="b" * 60, max_tokens=4, temperature=0.0)
        sched.submit(second)
        first.future.result(timeout=60)
        second.future.result(timeout=60)
    finally:
        sched.stop()
    want = [(0, 16), (16, 16), (32, 8), (40, 8), (48, 8), (56, 4)]
    assert cuts["sync"] + cuts["fused"] == want + want
    assert cuts["fused"], "the second admission did not ride a fused step"


# -- a Llama block whose heads are not dim // n_heads wide ------------------------


def test_a_llama_block_with_a_head_dim_through_the_m_round_trip_and_the_oracle(tmp_path):
    from distributed_llama_multiusers_tpu.models.oracle import OracleLlama, oracle_weights_from_m

    h = tiny_header(dim=64, n_heads=4, n_kv_heads=2)
    h.head_dim = 32  # 4 heads of 32 on a 64-wide stream
    path = str(tmp_path / "wide_heads.m")
    write_synthetic_model(path, h, seed=2, scale=0.1)
    back = load_model_header(path)
    assert (back.head_dim, back.head_size, back.q_dim, back.kv_dim) == (32, 32, 128, 64)
    config, params = load_params_from_m(path, back, dtype=jnp.float32)
    assert params.layers.wq.shape == (2, 64, 128) and params.layers.wo.shape == (2, 128, 64)
    assert params.rope_cos.shape[-1] == 16
    oracle = OracleLlama(config, oracle_weights_from_m(path, back), emulate_q80=False)
    prompt = _tokens(9, 7)
    want = [oracle.forward(tok, i) for i, tok in enumerate(prompt)][-1]
    for load in (load_params_from_m, load_params_from_m_quantized):
        config, params = load(path, back, dtype=jnp.float32)
        engine = InferenceEngine(config, params, n_lanes=2, cache_dtype=jnp.float32)
        assert engine.cache.k.shape[-2:] == (2, 32)
        last, _, _ = engine.prefill(0, prompt)
        np.testing.assert_allclose(np.asarray(last), want, rtol=2e-4, atol=2e-4)
    # a file without the key reads, and is written, as before
    from distributed_llama_multiusers_tpu.formats.model_file import KEY_HEAD_DIM

    assert KEY_HEAD_DIM not in [k for k, _ in tiny_header().to_kv_pairs()]
