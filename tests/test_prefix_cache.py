"""Prefix caching: requests sharing a prompt prefix with KV already
resident in some lane skip re-prefilling the prefix via a whole-lane HBM
copy (engine.copy_lane) + tail prefill.

No reference analogue — its lanes share a single KV cache (SURVEY.md §2
defect (c)), which makes per-lane prefix reuse impossible there. The
invariant under test is exactness: a prefix-cached request must produce
token streams identical to a cold prefill, because the copied KV slots are
the same values a fresh prefill would have written (prefill is
deterministic given tokens+positions).
"""

import numpy as np
import pytest
import jax.numpy as jnp

from distributed_llama_multiusers_tpu.formats.model_file import load_model_header
from distributed_llama_multiusers_tpu.models.loader import load_params_from_m
from distributed_llama_multiusers_tpu.runtime import (
    ContinuousBatchingScheduler,
    InferenceEngine,
    Request,
)
from distributed_llama_multiusers_tpu.tokenizer import Tokenizer

# char-level prompt-DEPENDENT tokenizer (shared text prefixes become
# shared token prefixes): one home in utils/testing.py
from distributed_llama_multiusers_tpu.utils.testing import (
    CharStreamTokenizer as _CharTokenizer,
)


@pytest.fixture(scope="module")
def loaded(tiny_model):
    h = load_model_header(tiny_model["model"])
    config, params = load_params_from_m(tiny_model["model"], h, dtype=jnp.float32)
    tok = Tokenizer(tiny_model["tokenizer"])
    return config, params, tok


def _engine(config, params, n_lanes=2):
    return InferenceEngine(config, params, n_lanes=n_lanes, prefill_buckets=(8,))


def test_copy_lane_then_tail_prefill_matches_cold_prefill(loaded):
    """copy_lane + tail prefill == full prefill, bit-for-bit logits."""
    config, params, _ = loaded
    full = [5, 9, 3, 17, 2, 11, 7, 4, 13, 6]
    split = 8

    cold = _engine(config, params)
    logits_cold, greedy_cold, _ = cold.prefill(1, full)

    warm = _engine(config, params)
    warm.prefill(0, full[:split])  # prefix resident in lane 0
    warm.copy_lane(0, 1)
    logits_warm, greedy_warm, _ = warm.prefill(1, full[split:], start_pos=split)

    assert int(greedy_warm) == int(greedy_cold)
    np.testing.assert_array_equal(
        np.asarray(logits_warm), np.asarray(logits_cold)
    )


def _run(engine, tok, reqs, **sched_kw):
    sched = ContinuousBatchingScheduler(engine, tok, **sched_kw)
    sched.start()
    try:
        for r in reqs:
            sched.submit(r)
        for r in reqs:
            r.future.result(timeout=300)
    finally:
        sched.stop()
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return [list(r.generated_tokens) for r in reqs]


def test_scheduler_prefix_hit_skips_prefill_and_keeps_stream(loaded):
    """Sequential requests with a shared long system prefix: the second
    admission reuses the first lane's KV (prefix_hits/prefix_tokens_saved
    count it, fewer prefill chunks run) and the generated stream is
    IDENTICAL to a prefix-cache-disabled scheduler."""
    config, params, tok = loaded
    system = "aa bb cc dd ee ff gg hh "  # long shared prefix (char-level tok)
    prompts = [system + "11", system + "22"]

    def reqs():
        return [Request(prompt=p, max_tokens=8, temperature=0.0) for p in prompts]

    engine = _engine(config, params)
    chunks = []
    real = engine.prefill_chunk

    def spy(lane, chunk, start_pos, **kw):
        chunks.append((lane, len(chunk), start_pos))
        return real(lane, chunk, start_pos, **kw)

    engine.prefill_chunk = spy

    def run_sequential(eng, **kw):
        sched = ContinuousBatchingScheduler(eng, tok, **kw)
        sched.start()
        out = []
        try:
            for r in reqs():
                sched.submit(r)
                r.future.result(timeout=300)
                assert r.error is None, r.error
                out.append(list(r.generated_tokens))
        finally:
            sched.stop()
        return out

    got_hit = run_sequential(engine)
    assert engine.stats.prefix_hits == 1
    # the second request's prompt processing started past the shared
    # prefix: no prefill chunk after the first request re-ran position 0
    first_prompt_chunks = -(-len(tok.encode(prompts[0])) // 8)  # ceil div
    assert all(c[2] > 0 for c in chunks[first_prompt_chunks:]), chunks
    n_shared = len(tok.encode(prompts[0][:-2]))
    assert engine.stats.prefix_tokens_saved >= n_shared - 8  # >= prefix - bucket

    plain_engine = _engine(config, params)
    got_plain = run_sequential(plain_engine, prefix_min_tokens=0)
    assert got_hit == got_plain
    assert plain_engine.stats.prefix_hits == 0
    # the cached run prefilled strictly fewer prompt tokens
    assert engine.stats.prefill_tokens < plain_engine.stats.prefill_tokens


@pytest.mark.slow  # tier-2: heavy; a faster sibling keeps this class covered in tier-1 (see pyproject markers)
def test_scheduler_prefix_concurrent_batch_identical_streams(loaded):
    """Two concurrent requests sharing a prefix (second admitted while the
    first may still be prefilling — only committed chunks are reusable):
    streams match the prefix-disabled scheduler exactly."""
    config, params, tok = loaded
    system = "aa bb cc dd ee ff "

    def reqs():
        return [
            Request(prompt=system + "xx", max_tokens=8, temperature=0.0),
            Request(prompt=system + "yy", max_tokens=8, temperature=0.0),
            Request(prompt="zz unrelated", max_tokens=6, temperature=0.0),
        ]

    got_hit = _run(_engine(config, params, n_lanes=4), tok, reqs())
    got_plain = _run(
        _engine(config, params, n_lanes=4), tok, reqs(), prefix_min_tokens=0
    )
    assert got_hit == got_plain


def test_pod_root_engine_broadcasts_copy_lane():
    """RootControlEngine.copy_lane must broadcast OP_COPY_LANE before the
    root-side call (a silent __getattr__ forward would desync the pod),
    and worker_loop must replay it."""
    from distributed_llama_multiusers_tpu.parallel.multihost import (
        OP_COPY_LANE,
        ControlPlane,
        RootControlEngine,
        worker_loop,
    )

    sent = []

    class _Plane(ControlPlane):
        def _bcast(self, pkt):
            sent.append(np.array(pkt))
            return pkt

    class _Inner:
        n_lanes = 2
        copied = None

        def copy_lane(self, src, dst):
            self.copied = (src, dst)

    inner = _Inner()
    root = RootControlEngine(inner, _Plane(n_lanes=2, chunk=8))
    root.copy_lane(0, 1)
    assert inner.copied == (0, 1)
    assert len(sent) == 1
    # header: [magic, version, op, lane, n, start_pos]
    assert list(sent[0][2:6]) == [OP_COPY_LANE, 0, 0, 1]
    root.copy_lane(1, 1)  # no-op: nothing broadcast, nothing dispatched
    assert len(sent) == 1

    # worker side replays the header operands
    class _WEngine:
        copied = None

        def copy_lane(self, src, dst):
            self.copied = (src, dst)

    from tests.test_multihost import _ScriptedPlane
    from distributed_llama_multiusers_tpu.parallel.multihost import OP_STOP

    weng = _WEngine()
    plane = _ScriptedPlane([OP_COPY_LANE, OP_STOP])
    # _ScriptedPlane packs (magic, version, op, 0, 2, 0); patch the copy
    # packet's operands (lane=src at header slot 3, start_pos=dst at 5)
    plane._pkts[0][3] = 1  # src
    plane._pkts[0][5] = 0  # dst
    worker_loop(weng, plane)
    assert weng.copied == (1, 0)


# ---------------------------------------------------------------------------
# Paged KV pool + ref-counted cross-request prefix tree (runtime/kvpool.py):
# prefix reuse becomes a refcount bump on SHARED physical pages (zero HBM
# copies — copy_lane is refused on paged engines), divergence is a single-
# page copy-on-write, finished sessions park so resident sessions exceed
# lanes, and the whole thing is pinned byte-identical to the contiguous
# layout. Pool bookkeeping is pure host/stdlib, so the unit tests below run
# without a backend; the byte-identity pins use the real engine.
# ---------------------------------------------------------------------------


def test_kvpool_cow_at_divergent_block():
    """Full shared blocks map to the SAME physical pages (refcount bump);
    the first divergent block is served by exactly one single-page COW
    into the new lane's private page."""
    from distributed_llama_multiusers_tpu.runtime.kvpool import KVPagePool

    pool = KVPagePool(n_pages=16, page_size=4, n_lanes=2)
    a = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]
    start, blocks, copies, _sw = pool.admit(0, a, reserve_tokens=12,
                                       min_share_tokens=4)
    assert (start, copies) == (0, [])
    pool.commit(0, a + [11, 12])  # 3 full blocks enter the tree
    pool.finish(0, park=True)  # parked: pages stay resident + refcounted

    # b shares block 0 exactly and diverges INSIDE block 1 (after 5, 6)
    b = [1, 2, 3, 4, 5, 6, 99, 100, 101]
    start, blocks2, copies, _sw = pool.admit(1, b, reserve_tokens=12,
                                        min_share_tokens=4)
    assert start == 6  # 4 tokens by refcount + 2 by copy-on-write
    assert blocks2[0] == blocks[0]  # full block: same physical page
    assert blocks2[1] != blocks[1]  # divergent block: private page
    assert copies == [(blocks[1], blocks2[1])]  # ONE single-page copy
    s = pool.stats()
    assert s["pool_cow_copies"] == 1
    assert s["pool_prefix_admits"] == 1
    assert s["pool_prefix_tokens_shared"] == 6


def test_kvpool_refcount_zero_page_reuse():
    """finish(park=False) drains every refcount: all pages return to the
    free list, their tree nodes die with them (no stale sharing), and the
    next admission recycles the same physical pages."""
    from distributed_llama_multiusers_tpu.runtime.kvpool import KVPagePool

    pool = KVPagePool(n_pages=4, page_size=4, n_lanes=2, max_parked=4)
    toks = [1, 2, 3, 4, 5, 6]
    _, blocks, _, _sw = pool.admit(0, toks, reserve_tokens=8)
    pool.commit(0, [1, 2, 3, 4, 5, 6, 7, 8])
    pool.finish(0, park=False)  # failure path: nothing parks
    assert pool.pages_free() == 4
    # the tree nodes died with their pages: identical content shares 0
    start2, blocks2, _, _sw = pool.admit(1, toks, reserve_tokens=8,
                                    min_share_tokens=1)
    assert start2 == 0
    assert sorted(blocks2) == sorted(blocks)  # same physical pages, reused
    pool.release(1)

    # park=True pins the registered blocks instead; drop_parked frees them
    pool.admit(0, toks, reserve_tokens=8)
    pool.commit(0, [1, 2, 3, 4, 5, 6, 7, 8])
    pool.finish(0, park=True)
    assert pool.parked_sessions() == 1
    assert pool.pages_free() == 2  # 2 registered blocks stay resident
    assert pool.drop_parked() == 1
    assert pool.pages_free() == 4


def test_kvpool_exhaustion_evicts_parked_then_sheds():
    """An admission the free list cannot serve first LRU-evicts parked
    sessions (drop-rebuild); only a pool pinned by ACTIVE lanes raises
    the typed PoolExhausted the scheduler maps to a retryable 429."""
    from distributed_llama_multiusers_tpu.runtime.kvpool import (
        KVPagePool,
        PoolExhausted,
    )

    pool = KVPagePool(n_pages=4, page_size=4, n_lanes=2, max_parked=4)
    pool.admit(0, [1, 2, 3, 4, 5], reserve_tokens=8)
    pool.commit(0, [1, 2, 3, 4])
    pool.finish(0, park=True)  # 1 registered page parked, tail freed
    assert pool.parked_sessions() == 1

    # needs 4 pages, 3 free: the parked session is evicted, not shed
    pool.admit(1, list(range(10, 25)), reserve_tokens=16)
    assert pool.parked_sessions() == 0
    assert pool.stats()["pool_parked_evicted"] == 1

    # pool now pinned by the ACTIVE lane 1: this one must shed, typed
    with pytest.raises(PoolExhausted) as ei:
        pool.admit(0, [1, 2, 3], reserve_tokens=16)
    assert ei.value.pages_needed == 4
    assert ei.value.pages_free == 0
    assert ei.value.pages_total == 4
    assert pool.stats()["pool_exhausted_sheds"] == 1


@pytest.mark.slow  # tier-2: heavy; a faster sibling keeps this class covered in tier-1 (see pyproject markers)
def test_paged_table_updates_keep_mesh_sharding(loaded):
    """Table replacements must carry the cache's replicated NamedSharding
    on a mesh: a bare jnp.asarray leaf changes the compiled programs'
    input aval — every warmed step family recompiles per admission on a
    single-host tp mesh, and a multi-process pod fails outright with
    incompatible devices. Streams must also match the mesh-free paged
    engine exactly."""
    from distributed_llama_multiusers_tpu.parallel import MeshPlan, make_mesh
    from distributed_llama_multiusers_tpu.runtime.engine import warmup_engine

    config, params, tok = loaded
    mesh = make_mesh(MeshPlan(tp=2))
    engine = InferenceEngine(config, params, n_lanes=2,
                             prefill_buckets=(8,), paged_kv=True,
                             kv_page_size=8, mesh=mesh)
    want_sh = engine.cache.table.sharding
    warmup_engine(engine, spec=False)  # includes the COW page copy
    ndim = engine.cache.table.ndim
    assert engine.cache.table.sharding.is_equivalent_to(want_sh, ndim)
    start = engine.paged_admit(0, list(range(2, 12)), 14)
    assert start == 0
    assert engine.cache.table.sharding.is_equivalent_to(want_sh, ndim)
    engine.paged_finish(0, park=False)
    assert engine.cache.table.sharding.is_equivalent_to(want_sh, ndim)

    plain = InferenceEngine(config, params, n_lanes=2,
                            prefill_buckets=(8,), paged_kv=True,
                            kv_page_size=8)
    streams = []
    for eng in (engine, plain):
        sched = ContinuousBatchingScheduler(eng, tok)
        sched.start()
        try:
            r = Request(prompt="mesh paged parity", max_tokens=6,
                        temperature=0.0)
            sched.submit(r)
            r.future.result(timeout=120)
            assert r.error is None, r.error
            streams.append(list(r.generated_tokens))
        finally:
            sched.stop()
    assert streams[0] == streams[1]


def test_warmup_compiles_paged_cow_program(loaded):
    """warmup_engine pre-compiles the single-page COW copy on paged
    engines: the first divergent-block admission runs mid-chain on the
    scheduler loop, where a lazy XLA compile would stall every lane
    behind the dispatch (the warmup contract every other step family
    already has)."""
    from distributed_llama_multiusers_tpu.runtime.engine import warmup_engine

    config, params, _ = loaded
    engine = InferenceEngine(config, params, n_lanes=2,
                             prefill_buckets=(8,), paged_kv=True,
                             kv_page_size=8)
    warmup_engine(engine, spec=False)
    assert engine._copy_page_fn._cache_size() == 1
    # and the warmup copy left lane 0's table in its initial unmapped
    # state (page 0 onto itself moved zeros over zeros)
    assert int(np.asarray(engine.cache.table).max()) == engine.kvpool.n_pages
    # releasing a lane that never mapped anything (the exhaustion-shed
    # reject path) dispatches NO device-side table update
    t0 = engine.cache.table
    engine.paged_finish(0)
    assert engine.cache.table is t0


def test_kvpool_unservable_reservation_is_not_retryable():
    """A reservation structurally larger than the whole pool (an
    explicitly undersized --kv-pool-pages) raises ValueError — the
    scheduler's request-scoped validation class — not the retryable
    PoolExhausted: a 429 would have the client back off and re-probe
    forever, each probe destructively evicting parked prefixes. The
    check fires BEFORE eviction, so parked sessions survive."""
    from distributed_llama_multiusers_tpu.runtime.kvpool import KVPagePool

    pool = KVPagePool(n_pages=3, page_size=4, n_lanes=2,
                      blocks_per_lane=8, max_parked=4)
    pool.admit(0, [1, 2, 3, 4, 5], reserve_tokens=8)  # 2 pages
    pool.commit(0, [1, 2, 3, 4])
    pool.finish(0, park=True)
    assert pool.parked_sessions() == 1

    # needs 4 pages, pool holds 3 total: no eviction could ever serve it
    with pytest.raises(ValueError, match="pool holds 3 total"):
        pool.admit(1, [9, 9, 9], reserve_tokens=16)
    # the probe evicted nothing and shed nothing (it is not load)
    assert pool.parked_sessions() == 1
    assert pool.stats()["pool_parked_evicted"] == 0
    assert pool.stats()["pool_exhausted_sheds"] == 0

    # a servable reservation still works afterwards, sharing the parked
    # prefix untouched by the failed probe
    start, _, _, _sw = pool.admit(1, [1, 2, 3, 4, 5], reserve_tokens=8,
                             min_share_tokens=4)
    assert start == 4

    # explicit invalid geometry dies in validation, never a silent
    # fallback: 0/negative pool_pages and non-positive page sizes
    with pytest.raises(ValueError):
        KVPagePool.for_seq_len(64, 2, pool_pages=0)
    with pytest.raises(ValueError):
        KVPagePool.for_seq_len(64, 2, page_size=0)


def test_kvpool_repark_identical_chain_occupies_one_lru_slot():
    """A client replaying the same prompt must not flood the parked LRU
    with duplicate holders of the same pages: each re-park refreshes
    the existing entry's recency, so other users' parked prefixes are
    not evicted by one repetitive session."""
    from distributed_llama_multiusers_tpu.runtime.kvpool import KVPagePool

    pool = KVPagePool(n_pages=16, page_size=4, n_lanes=2, max_parked=2)
    other = [9, 8, 7, 6, 5]
    pool.admit(0, other, reserve_tokens=8)
    pool.commit(0, [9, 8, 7, 6])
    pool.finish(0, park=True)  # the prefix a repeat client must not evict

    toks = [1, 2, 3, 4, 5]
    for _ in range(4):  # would overflow max_parked=2 without dedupe
        start, _, _, _sw = pool.admit(0, toks, reserve_tokens=8,
                                 min_share_tokens=4)
        pool.commit(0, [1, 2, 3, 4])
        pool.finish(0, park=True)
    s = pool.stats()
    assert s["pool_parked_sessions"] == 2  # other + ONE repeat slot
    assert s["pool_parked_evicted"] == 0
    assert s["pool_parked_pages"] == 2  # one page each, held once
    # both prefixes still serve copy-free
    start, _, _, _sw = pool.admit(1, other, reserve_tokens=8,
                             min_share_tokens=4)
    assert start == 4
    start, _, _, _sw = pool.admit(0, toks, reserve_tokens=8,
                             min_share_tokens=4)
    assert start == 4


def test_kvpool_eviction_skips_zero_yield_parked_sessions():
    """The eviction pass must not destroy park entries that can free
    nothing: an admission sharing session A's parked prefix pins those
    pages, so evicting A relieves zero pressure — and if the sharing
    request later failed (park=False), the hot prefix would vanish from
    the tree even though evicting only B sufficed."""
    from distributed_llama_multiusers_tpu.runtime.kvpool import KVPagePool

    pool = KVPagePool(n_pages=6, page_size=4, n_lanes=2, max_parked=4)
    a = list(range(1, 9))  # 2 full blocks
    pool.admit(0, a + [99], reserve_tokens=9)
    pool.commit(0, a)
    pool.finish(0, park=True)  # A (LRU-oldest): 2 pages parked
    b = list(range(11, 19))
    pool.admit(0, b + [99], reserve_tokens=9)
    pool.commit(0, b)
    pool.finish(0, park=True)  # B: 2 more pages parked; free = 2

    # shares A's 2 blocks and needs 3 fresh pages (free = 2): A is
    # pinned by this very admission (zero-yield), so the LRU pass must
    # skip it and evict only B
    start, _, _, _sw = pool.admit(1, a + list(range(30, 37)),
                             reserve_tokens=17, min_share_tokens=4)
    assert start == 8
    s = pool.stats()
    assert s["pool_parked_evicted"] == 1  # B only
    assert pool.parked_sessions() == 1  # A survives the pressure
    # and A still serves a copy-free hit afterwards
    pool.release(1)
    start, _, _, _sw = pool.admit(1, a + [99], reserve_tokens=9,
                             min_share_tokens=4)
    assert start == 8


def test_kvpool_shed_does_not_drain_parked_sessions():
    """An admission that would shed EVEN AFTER full parked eviction must
    shed without evicting: otherwise every retrying 429 client drains
    the parked prefix cache on each probe, holding the hit rate at zero
    for as long as the pool stays pinned by active lanes."""
    from distributed_llama_multiusers_tpu.runtime.kvpool import (
        KVPagePool,
        PoolExhausted,
    )

    pool = KVPagePool(n_pages=4, page_size=4, n_lanes=2, max_parked=4)
    # lane 0 stays ACTIVE pinning 2 pages
    pool.admit(0, [1, 2, 3, 4, 5], reserve_tokens=8)
    # lane 1 parks one sharable page (its tail frees)
    pool.admit(1, [9, 9, 9, 9, 9], reserve_tokens=8)
    pool.commit(1, [9, 9, 9, 9])
    pool.finish(1, park=True)
    assert pool.parked_sessions() == 1
    assert pool.pages_free() == 1

    # needs 4 pages; free(1) + evictable(1) = 2 < 4: must shed WITHOUT
    # touching the parked session
    with pytest.raises(PoolExhausted):
        pool.admit(1, [7, 7, 7], reserve_tokens=16)
    assert pool.parked_sessions() == 1
    assert pool.stats()["pool_parked_evicted"] == 0

    # an admission eviction CAN serve still evicts and succeeds
    pool.admit(1, [7, 7, 7], reserve_tokens=8)  # needs 2: 1 free + 1 evictable
    assert pool.parked_sessions() == 0
    assert pool.stats()["pool_parked_evicted"] == 1


def test_kvpool_duplicate_content_pages_freed_not_parked():
    """Two lanes admit the same novel prompt concurrently (neither
    committed yet, so no sharing): commit() keeps the FIRST lane's node
    for the duplicate chain, so the second lane's page backs no tree
    node and no future walk can reach it — finish(park=True) must free
    it, not park dead residency that LRU-evicts genuinely sharable
    sessions under pressure."""
    from distributed_llama_multiusers_tpu.runtime.kvpool import KVPagePool

    pool = KVPagePool(n_pages=8, page_size=4, n_lanes=2, max_parked=4)
    toks = [1, 2, 3, 4, 5]
    pool.admit(0, toks, reserve_tokens=8)
    pool.admit(1, toks, reserve_tokens=8)  # concurrent: nothing to share
    pool.commit(0, [1, 2, 3, 4])  # registers block 0
    pool.commit(1, [1, 2, 3, 4])  # duplicate: lane 0's node wins
    pool.finish(0, park=True)
    pool.finish(1, park=True)
    s = pool.stats()
    # lane 1 had nothing sharable to park: no session entry, its
    # duplicate page went back to the free list
    assert s["pool_parked_sessions"] == 1
    assert s["pool_parked_pages"] == 1
    assert pool.pages_free() == 7
    # and the survivor still serves copy-free follow-ups
    start, _, _, _sw = pool.admit(0, toks, reserve_tokens=8,
                             min_share_tokens=4)
    assert start == 4


def test_kvpool_parked_pages_count_distinct_pages():
    """pool_parked_pages is real pool occupancy: N parked sessions
    sharing the same physical prefix page pin it ONCE, not once per
    holder — otherwise pages per resident session could never show
    overlap."""
    from distributed_llama_multiusers_tpu.runtime.kvpool import KVPagePool

    pool = KVPagePool(n_pages=8, page_size=4, n_lanes=2)
    a = [1, 2, 3, 4, 5]
    pool.admit(0, a, reserve_tokens=8)
    pool.commit(0, [1, 2, 3, 4])
    pool.finish(0, park=True)
    # second session shares the SAME block-0 page then extends the
    # chain (an identical chain would dedupe into one LRU slot)
    b = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    pool.admit(1, b, reserve_tokens=12, min_share_tokens=4)
    pool.commit(1, [1, 2, 3, 4, 5, 6, 7, 8])
    pool.finish(1, park=True)
    s = pool.stats()
    assert s["pool_parked_sessions"] == 2
    # block-0's physical page has TWO park holders but counts once
    assert s["pool_parked_pages"] == 2
    assert pool.drop_parked() == 2
    assert pool.stats()["pool_parked_pages"] == 0
    assert pool.pages_free() == 8  # every ref drained back to the pool


def test_kvpool_eviction_cannot_free_matched_shared_pages():
    """Review-caught: admit() matched its shared prefix pages BEFORE
    taking refs on them, so the parked-session eviction an oversubscribed
    admission triggers could free (and re-pop as fresh!) the very pages
    the admission was about to share — one physical page mapped at two
    block indices of the same lane. The shared refs are now taken before
    eviction: the LRU pass skips pages the admission pinned and evicts
    the next session instead."""
    from distributed_llama_multiusers_tpu.runtime.kvpool import KVPagePool

    pool = KVPagePool(n_pages=4, page_size=4, n_lanes=2, max_parked=4)
    a = [1, 2, 3, 4, 5, 6, 7]  # 7 prompt + 1 reserved slot = 2 pages
    _, a_blocks, _, _sw = pool.admit(0, a, reserve_tokens=8)
    pool.commit(0, a + [8])  # both blocks full: both register + park
    pool.finish(0, park=True)  # LRU-oldest; sole holder of a's 2 pages
    b = [9, 10, 11, 12, 13, 14, 15]
    pool.admit(0, b, reserve_tokens=8)
    pool.commit(0, b + [16])
    pool.finish(0, park=True)  # pool now full: 2 parked sessions
    assert pool.pages_free() == 0

    # c shares a's both blocks and needs 2 fresh pages: eviction must
    # free b's pages (a's are pinned by this very admission), and the
    # mapping must stay one-physical-page-per-block
    c = a + [8, 17]
    start, c_blocks, _, _sw = pool.admit(1, c, reserve_tokens=16,
                                    min_share_tokens=4)
    assert start == 8
    assert c_blocks[:2] == a_blocks  # shared by refcount, still alive
    assert len(set(c_blocks)) == len(c_blocks)  # no page mapped twice
    assert not set(c_blocks) & set(pool._free)  # nothing mapped AND free


def test_kvpool_below_threshold_admit_resets_tree_tip():
    """Review-caught: the below-sharing-threshold reset cleared the
    matched pages but left the tree-walk key as the lane's registration
    tip, so commit() registered the lane's block 0 UNDER the matched
    chain — a later prompt genuinely starting chain+chain would then
    share a page whose KV was computed at the wrong positions. The tip
    must reset to root with the rest."""
    from distributed_llama_multiusers_tpu.runtime.kvpool import KVPagePool

    pool = KVPagePool(n_pages=16, page_size=4, n_lanes=2)
    blk = [1, 2, 3, 4]
    pool.admit(0, blk + [5], reserve_tokens=8)
    pool.commit(0, blk)  # chain root -> blk registered
    pool.finish(0, park=True)

    # matches blk (start would be 4) but 4 < min_share_tokens=6: admits
    # fully private — and must register its own blocks from the ROOT
    pool.admit(1, blk + [9], reserve_tokens=8, min_share_tokens=6)
    pool.commit(1, blk + [9, 9, 9, 9])
    pool.finish(1, park=True)

    # a prompt that REALLY starts blk+blk may share only the first blk:
    # with the stale tip, lane 1's block 0 (KV at positions 0..3) sat in
    # the tree as the chain's SECOND block and start came back 8
    start, _, copies, _sw = pool.admit(0, blk + blk + [7], reserve_tokens=12,
                                  min_share_tokens=4)
    assert start == 4
    assert copies == []  # blk's sibling run is below any COW win


def _mock_run(engine, prompts, max_tokens=8, sequential=True):
    """Drive the scheduler over the mock engine; returns token streams."""
    sched = ContinuousBatchingScheduler(
        engine, _CharTokenizer(engine.config.vocab_size),
        prefix_min_tokens=4,
    )
    sched.start()
    try:
        out = []
        reqs = [Request(prompt=p, max_tokens=max_tokens, temperature=0.0)
                for p in prompts]
        if sequential:
            for r in reqs:
                sched.submit(r)
                r.future.result(timeout=60)
        else:
            for r in reqs:
                sched.submit(r)
            for r in reqs:
                r.future.result(timeout=60)
        for r in reqs:
            assert r.error is None, r.error
            out.append(list(r.generated_tokens))
        return out
    finally:
        sched.stop()


def test_paged_oversubscription_parks_sessions_beyond_lanes():
    """Scheduler-level oversubscription without a backend (MockAsyncEngine
    paged + content_keyed mode drives the REAL pool bookkeeping): 6
    sessions over 2 lanes with a shared system prompt — streams are
    byte-identical to the non-paged mock, later admissions share the
    prefix by refcount (copy-free: the paged engine has no copy_lane at
    all), and every finished session parks, so resident sessions exceed
    2x the lane count."""
    from distributed_llama_multiusers_tpu.utils.testing import MockAsyncEngine

    system = "sys: answer tersely. "
    prompts = [system + f"user question {i}" for i in range(6)]

    plain = MockAsyncEngine(n_lanes=2, max_chunk=8, content_keyed=True)
    want = _mock_run(plain, prompts)

    paged = MockAsyncEngine(n_lanes=2, max_chunk=8, content_keyed=True,
                            paged=True, kv_page_size=4)
    got = _mock_run(paged, prompts)
    assert got == want  # byte-identical across the layout swap

    s = paged.kvpool.stats()
    assert s["pool_prefix_admits"] >= 5  # sessions 2..6 all shared
    assert s["pool_exhausted_sheds"] == 0
    # resident (parked) sessions exceed 2x lanes: the oversubscription
    # lever — bounded by journal bytes, not HBM
    assert s["pool_parked_sessions"] >= 4
    assert paged.stats.prefix_hits >= 5
    assert paged.stats.pipeline_flushes == 0


def test_paged_pool_exhaustion_sheds_typed_429():
    """A request whose reservation cannot be served even after parked
    eviction sheds with AdmissionRejected("pool_exhausted"): HTTP 429 +
    Retry-After, request-scoped (the other lane keeps serving and the
    breaker stays closed)."""
    from distributed_llama_multiusers_tpu.serving.qos import AdmissionRejected
    from distributed_llama_multiusers_tpu.utils.testing import MockAsyncEngine

    engine = MockAsyncEngine(n_lanes=2, max_chunk=8, content_keyed=True,
                             paged=True, kv_page_size=16, kv_pool_pages=4,
                             kv_max_parked=0)
    sched = ContinuousBatchingScheduler(
        engine, _CharTokenizer(engine.config.vocab_size),
        prefix_min_tokens=4,
    )
    sched.start()
    try:
        # A reserves the whole pool: 21 prompt + 42 + 1 tokens = 4 pages
        a = Request(prompt="x" * 21, max_tokens=42, temperature=0.0)
        b = Request(prompt="y" * 21, max_tokens=42, temperature=0.0)
        sched.submit(a)
        sched.submit(b)
        with pytest.raises(AdmissionRejected) as ei:
            b.future.result(timeout=60)
        assert ei.value.reason == "pool_exhausted"
        assert ei.value.http_status == 429
        assert ei.value.retry_after_s > 0
        # request-scoped containment: A is unaffected by B's shed
        a.future.result(timeout=60)
        assert a.error is None
        assert len(a.generated_tokens) == 42
    finally:
        sched.stop()
    assert engine.kvpool.stats()["pool_exhausted_sheds"] == 1


def test_paged_engine_refuses_copy_lane(loaded):
    """copy_lane is the contiguous layout's primitive; on a paged engine
    prefix sharing is a refcount bump and a whole-lane HBM copy must be
    impossible to reach."""
    config, params = loaded[0], loaded[1]
    eng = InferenceEngine(config, params, n_lanes=2, prefill_buckets=(8,),
                          paged_kv=True, kv_page_size=16)
    with pytest.raises(RuntimeError, match="paged"):
        eng.copy_lane(0, 1)


@pytest.mark.slow  # tier-2: heavy; a faster sibling keeps this class covered in tier-1 (see pyproject markers)
def test_paged_streams_byte_identical_vs_contiguous_churn(loaded):
    """THE paged pin: the same churn (sequential shared-prefix requests,
    then a concurrent mixed batch) over a paged engine and a contiguous
    engine produces byte-identical token streams, with the paged run
    serving the shared prefix copy-free by refcount plus one single-page
    COW at the divergent block, and zero pipeline flushes."""
    config, params, tok = loaded
    system = "aa bb cc dd ee ff gg hh "

    def drive(eng):
        sched = ContinuousBatchingScheduler(eng, tok)
        sched.start()
        try:
            out = []
            # sequential: B admits after A finished, sharing A's prefix
            for tail in ("11", "22"):
                r = Request(prompt=system + tail, max_tokens=8,
                            temperature=0.0)
                sched.submit(r)
                r.future.result(timeout=300)
                assert r.error is None, r.error
                out.append(list(r.generated_tokens))
            # churn: concurrent mixed batch (shared + unrelated)
            batch = [
                Request(prompt=system + "33", max_tokens=8, temperature=0.0),
                Request(prompt="zz unrelated", max_tokens=6, temperature=0.0),
            ]
            for r in batch:
                sched.submit(r)
            for r in batch:
                r.future.result(timeout=300)
                assert r.error is None, r.error
                out.append(list(r.generated_tokens))
            return out
        finally:
            sched.stop()

    cont = drive(_engine(config, params))
    paged_eng = InferenceEngine(config, params, n_lanes=2,
                                prefill_buckets=(8,), paged_kv=True,
                                kv_page_size=16)
    paged = drive(paged_eng)
    assert paged == cont  # byte-identical across the layout swap

    s = paged_eng.pool_stats()
    assert s["pool_prefix_admits"] >= 1  # shared prefix served copy-free
    assert s["pool_cow_copies"] >= 1  # divergence inside a shared block
    assert s["pool_exhausted_sheds"] == 0
    assert paged_eng.stats.prefix_hits >= 1
    assert paged_eng.stats.pipeline_flushes == 0  # steady churn: no flush


def test_paged_park_drop_journal_rebuild_byte_identical(loaded, tmp_path):
    """The drop-rebuild determinism pin (what makes parking safe): a
    finished session's pages are dropped under pressure and its next
    activity rebuilds by re-prefilling the journaled (prompt, resolved
    seed) — byte-identical to the never-dropped run. The journal's admit
    record carries everything the rebuild needs."""
    from distributed_llama_multiusers_tpu.serving import (
        RequestJournal,
        read_journal,
    )

    config, params, tok = loaded
    prompt = "aa bb cc dd ee ff gg hh 11"
    seed = 1234

    def one(sched):
        r = Request(prompt=prompt, max_tokens=8, temperature=0.8, seed=seed)
        sched.submit(r)
        r.future.result(timeout=300)
        assert r.error is None, r.error
        return list(r.generated_tokens)

    # reference: a fresh paged engine, no parking history
    ref_eng = InferenceEngine(config, params, n_lanes=2,
                              prefill_buckets=(8,), paged_kv=True,
                              kv_page_size=16)
    sched = ContinuousBatchingScheduler(ref_eng, tok)
    sched.start()
    try:
        ref = one(sched)
    finally:
        sched.stop()

    jpath = str(tmp_path / "journal.bin")
    journal = RequestJournal(jpath, fsync=False)
    eng = InferenceEngine(config, params, n_lanes=2, prefill_buckets=(8,),
                          paged_kv=True, kv_page_size=16)
    sched = ContinuousBatchingScheduler(eng, tok, journal=journal)
    sched.start()
    try:
        assert one(sched) == ref  # warm-up run; its session parks
        assert eng.kvpool.parked_sessions() >= 1
        # pressure: drop every parked session's pages (the LRU-eviction
        # path an oversubscribed admission takes)
        assert eng.kvpool.drop_parked() >= 1
        assert eng.pool_stats()["pool_parked_evicted"] >= 1
        # next activity rebuilds from scratch — byte-identical
        assert one(sched) == ref
    finally:
        sched.stop()
        journal.close()

    # the journal holds the rebuild inputs: resolved tokens + seed
    img = read_journal(jpath)
    entries = list(img.entries.values())
    assert len(entries) == 2
    for e in entries:
        assert e.prompt == prompt
        assert e.tokens == tok.encode(prompt)
        assert e.seed == seed
        assert e.finished


@pytest.mark.parametrize("warmed", [False, True])
def test_paged_three_tier_residency_byte_identical(loaded, warmed):
    """Tiered-residency determinism pin: one seeded request replayed
    with its prefix served from each residency tier — resident-parked
    (refcount bump), host-RAM swapped (batched host->device copy behind
    a sha256 re-verify), and dropped (re-prefill rebuild) — produces
    byte-identical streams, all equal to a contiguous engine that never
    paged at all. This is what makes the swap tier safe to enable: the
    tier only moves WHERE bytes live, never what they are.

    ``warmed``: the server's form of it. After ``warmup_engine`` the
    whole walk compiles NOTHING (the swap gather/scatter programs and
    the COW page copy are warm-up's to cover, or the first swapped
    admission stalls every lane behind a compile), and after ``stop()``
    nothing is still held: the lanes' pages are back at their count from
    before the first request, parked pages apart."""
    from distributed_llama_multiusers_tpu.runtime.engine import warmup_engine

    config, params, tok = loaded
    prompt = "aa bb cc dd ee ff gg hh 11"
    seed = 1234

    def one(sched):
        r = Request(prompt=prompt, max_tokens=8, temperature=0.8, seed=seed)
        sched.submit(r)
        r.future.result(timeout=300)
        assert r.error is None, r.error
        return list(r.generated_tokens)

    # contiguous reference: the layout-swap baseline
    ref_eng = _engine(config, params)
    sched = ContinuousBatchingScheduler(ref_eng, tok)
    sched.start()
    try:
        ref = one(sched)
    finally:
        sched.stop()

    eng = InferenceEngine(config, params, n_lanes=2, prefill_buckets=(8,),
                          paged_kv=True, kv_page_size=16,
                          kv_host_bytes=64 << 20)
    pre_pages = eng.pool_stats().get("pool_pages_in_use", 0)
    sched = ContinuousBatchingScheduler(eng, tok)
    if warmed:
        warmup_engine(eng, spec=True, multi_step=sched.multi_step)
    sched.start()
    try:
        assert one(sched) == ref  # cold prefill; the session parks
        assert eng.kvpool.parked_sessions() >= 1
        assert one(sched) == ref  # tier 0: resident-parked refcount reuse
        # tier 1: evict the parked pages to host RAM, then reactivate
        assert sched.run_device_op(lambda: eng.swap_out_parked()) >= 1
        s = eng.pool_stats()
        assert s["swap_outs"] >= 1 and s["pool_host_pages"] >= 1
        assert one(sched) == ref  # swap-in (hash-verified host copy)
        assert eng.pool_stats()["swap_ins"] >= 1
        # tier 2: drop everything, host tier included — rebuild path
        eng.kvpool.drop_parked()
        eng.kvpool.host_tier.clear()
        assert one(sched) == ref  # re-prefill rebuild
        assert eng.stats.pipeline_flushes == 0
    finally:
        sched.stop()
    if warmed:
        assert eng.stats.snapshot()["jit_compiles_after_warmup"] == 0
        held = sched.leak_counts()
        assert held.pop("kv_lane_pages") == pre_pages
        assert all(v == 0 for v in held.values()), held


@pytest.mark.slow  # tier-2: heavy; a faster sibling keeps this class covered in tier-1 (see pyproject markers)
def test_prefix_reuse_survives_idle_lane_decode_steps(loaded):
    """Round-5 code-review finding: every decode step scatters a KV write
    for EVERY lane; idle/finished lanes used to point at position 0,
    clobbering slot 0 of exactly the caches prefix admission wants to
    reuse. Idle lanes now write at seq_len (dropped). Scenario: A
    finishes, B keeps decoding (each step would have corrupted A's
    slot 0), then C reuses A's prefix — C's stream must equal a cold
    run's."""
    config, params, tok = loaded
    system = "aa bb cc dd ee ff gg hh "

    def make(mt, tail):
        return Request(prompt=system + tail, max_tokens=mt, temperature=0.0)

    def run(eng, **kw):
        sched = ContinuousBatchingScheduler(eng, tok, **kw)
        sched.start()
        try:
            a, b = make(2, "11"), make(30, "22")
            sched.submit(a)
            sched.submit(b)
            a.future.result(timeout=300)  # A done; B decodes on (idle A lane)
            c = make(8, "11")  # same prompt as A: prefix-hits A's lane
            sched.submit(c)
            c.future.result(timeout=300)
            b.future.result(timeout=300)
            assert all(r.error is None for r in (a, b, c))
            return list(c.generated_tokens)
        finally:
            sched.stop()

    warm_engine = _engine(config, params, n_lanes=2)
    got = run(warm_engine)
    assert warm_engine.stats.prefix_hits >= 1
    cold = run(_engine(config, params, n_lanes=2), prefix_min_tokens=0)
    assert got == cold
