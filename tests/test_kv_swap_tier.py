"""Tiered KV residency (runtime/kvpool.py HostTier + the engine's swap
programs): parked pages evicted under pool pressure swap their bytes to
a bounded host-RAM tier instead of dropping, and a later admission that
misses HBM but hits the tier reactivates by host->device copy instead of
re-prefill. The eviction ladder is resident-parked -> swap-to-host ->
drop-to-rebuild, and every rung must stay byte-identical: a swapped-in
prefix serves the same KV bytes a resident or rebuilt one would.

The integrity frame is disagg/kvtransfer.py's per-page sha256 (same
canonical framing, so the two serializers cannot drift); a failed
re-hash is REQUEST-scoped — typed :class:`HostTierCorrupt`, raised
before any pool mutation, entry dropped, tree never poisoned.

Pool/tier bookkeeping is pure host/stdlib, so most tests run without a
backend via MockAsyncEngine's paged mode (the REAL KVPagePool + a
content-canonical device half, shared with tests/test_disagg.py); the
real-engine three-tier byte-identity pin lives in
tests/test_prefix_cache.py's module for fixture reuse.
"""

import numpy as np
import pytest

from distributed_llama_multiusers_tpu.disagg.kvtransfer import page_hash
from distributed_llama_multiusers_tpu.runtime.kvpool import (
    HostTier,
    HostTierCorrupt,
    KVPagePool,
    PoolExhausted,
)
from distributed_llama_multiusers_tpu.utils.testing import MockAsyncEngine


def _paged_engine(pool_pages=32, max_parked=8, page_size=4, seq_len=64,
                  n_lanes=2, host_bytes=1 << 20):
    """A paged mock with the host swap tier armed: the REAL KVPagePool
    bookkeeping, device half mocked content-canonically (swap-outs and
    swap-ins are genuine byte round trips)."""
    return MockAsyncEngine(
        n_lanes=n_lanes, content_keyed=True, paged=True,
        kv_page_size=page_size, kv_pool_pages=pool_pages,
        kv_max_parked=max_parked, seq_len=seq_len,
        kv_host_bytes=host_bytes,
    )


def _park_chain(engine, lane, tokens):
    """Admit + commit + park one session's chain on ``engine``."""
    engine.paged_admit(lane, tokens, reserve_tokens=len(tokens))
    engine.paged_commit(lane, tokens)
    engine.paged_finish(lane, park=True)


# ---------------------------------------------------------------------------
# HostTier unit: bounded LRU byte budget + integrity frame
# ---------------------------------------------------------------------------


def test_host_tier_lru_byte_bound_eviction():
    """The byte budget is LRU-enforced at put, a get refreshes recency
    (the entry STAYS — one host copy serves N admissions), and an entry
    larger than the whole budget is refused, not stored truncated."""
    blk = (1, 2, 3, 4)
    pay = b"x" * 100
    tier = HostTier(budget_bytes=250, page_size=4)
    assert tier.enabled and not tier.full()

    assert tier.put(("a",), blk, pay)
    assert tier.put(("b",), blk, pay)
    # touch "a": now "b" is the LRU victim
    assert tier.get(("a",), blk) == pay
    assert tier.put(("c",), blk, pay)  # 300 bytes > 250: evicts "b"
    s = tier.stats()
    assert s["pool_host_pages"] == 2 and s["pool_host_bytes"] == 200
    assert s["pool_host_evicted"] == 1
    assert tier.get(("b",), blk) is None  # evicted
    assert tier.get(("a",), blk) == pay  # recency refresh kept it

    # oversize payload: refused whole (full_drops), nothing evicted for it
    assert not tier.put(("big",), blk, b"y" * 300)
    assert tier.stats()["pool_host_full_drops"] == 1
    assert tier.stats()["pool_host_pages"] == 2

    # budget 0 disables the tier outright (the --kv-host-bytes 0 hatch)
    off = HostTier(budget_bytes=0, page_size=4)
    assert not off.enabled
    assert not off.put(("a",), blk, pay)
    assert off.stats()["pool_host_pages"] == 0


def test_host_tier_rehash_failure_drops_entry_and_raises_typed():
    """A payload that no longer matches its stored hash dies with the
    typed :class:`HostTierCorrupt` (a ValueError — the scheduler's
    request-scoped class) and the entry is dropped, so the retry takes
    the rebuild path instead of re-hitting the corruption."""
    blk = (1, 2, 3, 4)
    tier = HostTier(budget_bytes=1 << 10, page_size=4)
    assert tier.put(("a",), blk, b"x" * 64)
    # corrupt the stored payload behind the hash's back
    with tier._lock:
        tier._swapped[("a",)] = (b"y" * 64, tier._swapped[("a",)][1])
    with pytest.raises(HostTierCorrupt) as ei:
        tier.get(("a",), blk)
    assert isinstance(ei.value, ValueError)  # request-scoped by class
    s = tier.stats()
    assert s["pool_host_corrupt"] == 1
    assert s["pool_host_pages"] == 0 and s["pool_host_bytes"] == 0
    assert tier.get(("a",), blk) is None  # dropped: clean miss now


def test_host_tier_hash_framing_matches_disagg():
    """The tier's integrity hash IS kvtransfer's page_hash framing —
    pinned so the two serializers can never drift apart."""
    blk = (7, 8, 9, 10)
    tier = HostTier(budget_bytes=1 << 10, page_size=4)
    tier.put(("k",), blk, b"payload-bytes")
    with tier._lock:
        _, stored_hash = tier._swapped[("k",)]
    assert stored_hash == page_hash(4, blk, b"payload-bytes")


# ---------------------------------------------------------------------------
# Pool + engine: the eviction ladder and swapped admission
# ---------------------------------------------------------------------------


def test_evicted_parked_pages_swap_to_host_and_readmit():
    """The tiered round trip: a parked chain evicted into the host tier
    reactivates on the next same-prefix admission — start covers the
    swapped blocks, the payloads land back byte-identically, and the
    re-registered pages serve from the prefix tree again."""
    eng = _paged_engine()
    tokens = list(range(2, 22))  # 20 tokens = 5 full blocks of 4
    _park_chain(eng, 0, tokens)
    # remember the content-canonical payloads the chain exported
    chain = eng.kvpool.chain_pages(tokens)
    assert len(chain) == 5
    before = [bytes(eng.export_kv_page(p)) for _, p in chain]

    assert eng.swap_out_parked() == 1
    s = eng.pool_stats()
    assert s["pool_host_pages"] == 5 and s["swap_outs"] == 5
    assert s["pool_swap_pending"] == 0  # the drain took everything
    assert eng.kvpool.parked_sessions() == 0
    assert not eng.kvpool.chain_pages(tokens)  # gone from the tree

    # same-prefix admission: 4 full blocks swap back in (the 5th holds
    # the prompt's final token — max_reuse = len-1 keeps one to prefill)
    start = eng.paged_admit(1, tokens, reserve_tokens=24)
    s = eng.pool_stats()
    assert start == 16
    assert s["swap_ins"] == 4 and s["pool_swap_in_admits"] == 1
    assert s["pool_host_pages_swapped_in"] == 4
    assert s["pool_host_hits"] == 4
    # byte identity through the tier: the reactivated pages export the
    # exact bytes the parked originals held
    after = [bytes(eng.export_kv_page(p))
             for _, p in eng.kvpool.chain_pages(tokens[:16])]
    assert after == before[:4]


def test_shared_swapped_prefix_two_sessions_one_host_copy():
    """One host copy serves N sessions: the first admission after the
    swap-out pays the swap-in, re-registers the chain, and the second
    admission shares it RESIDENT by refcount — zero extra swap-ins,
    zero extra host-tier hits."""
    eng = _paged_engine(n_lanes=2)
    prefix = list(range(2, 18))  # 16 tokens = 4 full blocks
    _park_chain(eng, 0, prefix + [30, 31])
    assert eng.swap_out_parked() == 1

    s0 = eng.pool_stats()
    start_a = eng.paged_admit(0, prefix + [40, 41], reserve_tokens=20)
    s1 = eng.pool_stats()
    assert start_a == 16
    assert s1["swap_ins"] - s0["swap_ins"] == 4  # A paid the swap-in
    eng.paged_commit(0, prefix + [40, 41])

    start_b = eng.paged_admit(1, prefix + [50, 51], reserve_tokens=20)
    s2 = eng.pool_stats()
    assert start_b == 16
    assert s2["swap_ins"] == s1["swap_ins"]  # B paid nothing
    assert s2["pool_host_hits"] == s1["pool_host_hits"]
    assert s2["pool_prefix_admits"] == s1["pool_prefix_admits"] + 1
    # and the tier still holds its copy (a hit never removes the entry)
    assert s2["pool_host_pages"] >= 4


def test_corrupt_swap_entry_fails_request_never_poisons_tree():
    """THE containment pin: a corrupt host-tier payload discovered
    during the admission walk raises the typed error BEFORE any pool
    mutation — no refcounts taken, no pages popped, no tree nodes
    registered — and the corrupt entry is dropped so the retry admits
    clean down the rebuild path."""
    eng = _paged_engine()
    tokens = list(range(2, 22))
    _park_chain(eng, 0, tokens)
    assert eng.swap_out_parked() == 1
    pool = eng.kvpool
    tier = pool.host_tier

    # corrupt EVERY entry's payload behind its hash (deposit order is
    # an eviction detail — whichever entry the walk probes first must
    # trip the re-hash)
    with tier._lock:
        for key in list(tier._swapped):
            data, h = tier._swapped[key]
            tier._swapped[key] = (b"\xff" * len(data), h)
    free_before = len(pool._free)
    nodes_before = dict(pool._nodes)
    with pytest.raises(HostTierCorrupt):
        eng.paged_admit(1, tokens, reserve_tokens=24)
    # pool untouched: same free pages, same tree, lane 1 unmapped
    assert len(pool._free) == free_before
    assert pool._nodes == nodes_before
    assert not pool._lane_blocks[1]
    assert eng.pool_stats()["pool_host_corrupt"] == 1

    # retry: the corrupt entry is gone, the walk misses, the request
    # rebuilds from scratch (start == 0) and completes
    start = eng.paged_admit(1, tokens, reserve_tokens=24)
    assert start == 0
    assert eng.pool_stats()["swap_ins"] == 0
    eng.paged_commit(1, tokens)
    eng.paged_finish(1, park=False)


def test_drop_parked_stays_drop_no_tier_deposit():
    """drop_parked() is the REBUILD lever (the third rung of
    test_paged_three_tier_residency_byte_identical): it must not stage
    swap-outs even with the tier enabled, or a 'rebuild' would quietly
    serve from host RAM."""
    eng = _paged_engine()
    _park_chain(eng, 0, list(range(2, 22)))
    assert eng.kvpool.drop_parked() == 1
    s = eng.pool_stats()
    assert s["pool_host_pages"] == 0 and s["swap_outs"] == 0
    assert s["pool_swap_pending"] == 0


def test_host_bytes_zero_restores_drop_to_rebuild_bitwise():
    """--kv-host-bytes 0 (the default): the tier never stores, admit
    never returns swapins, eviction deposits nothing — the PR 11
    drop-to-rebuild pool behavior, field-for-field."""
    on = _paged_engine(host_bytes=0)
    tokens = list(range(2, 22))
    _park_chain(on, 0, tokens)
    assert on.swap_out_parked() == 1  # evicts, but nothing to deposit
    s = on.pool_stats()
    assert s["pool_host_pages"] == 0 and s["swap_outs"] == 0
    assert s["pool_swap_pending"] == 0
    assert s["pool_host_budget_bytes"] == 0

    # the re-admission takes the rebuild path, exactly like a pool that
    # predates the tier: no sharing, no swap-ins, fresh pages. (The
    # stream-level bit-for-bit half of this hatch rides the existing
    # paged-vs-contiguous byte-identity pins — every one of them
    # constructs its engines with the default kv_host_bytes=0, so the
    # disabled-tier path IS the path they pin.)
    start = on.paged_admit(1, tokens, reserve_tokens=24)
    assert start == 0
    assert on.pool_stats()["swap_ins"] == 0


def test_pool_exhausted_reason_distinguishes_host_tier_full():
    """The typed shed carries host_tier_full so the scheduler can tell
    the operator which lever to pull (--kv-host-bytes vs
    --kv-pool-pages): False when the tier has headroom or is disabled,
    True when the shed fired with the tier at budget."""
    # tiny tier: one 4-token page payload (mock payloads are 64 bytes)
    # fills the 64-byte budget exactly
    eng = _paged_engine(pool_pages=6, max_parked=4, host_bytes=64)
    _park_chain(eng, 0, list(range(2, 12)))  # 2 committed pages parked
    eng.swap_out_parked()
    assert eng.pool_stats()["pool_host_bytes"] == 64  # LRU kept one
    assert eng.kvpool.host_tier.full()
    # pin lane 0 with an ACTIVE reservation (3 pages held, nothing
    # parked, so nothing is evictable) ...
    eng.paged_admit(0, list(range(50, 60)), reserve_tokens=12)
    # ... and a 4-page reservation against the 3 remaining free pages
    # sheds: structurally servable (4 <= 6 total) but unservable now,
    # with the tier reported FULL
    with pytest.raises(PoolExhausted) as ei:
        eng.paged_admit(1, list(range(100, 115)), reserve_tokens=16)
    assert ei.value.host_tier_full is True

    # same shed with the tier disabled: plain pool_exhausted
    off = _paged_engine(pool_pages=6, max_parked=4, host_bytes=0)
    off.paged_admit(0, list(range(50, 60)), reserve_tokens=12)
    with pytest.raises(PoolExhausted) as ei:
        off.paged_admit(1, list(range(100, 115)), reserve_tokens=16)
    assert ei.value.host_tier_full is False


def test_pool_reset_discards_pending_and_clears_tier():
    """Containment: reset() drops staged-but-undrained swap-outs (their
    bytes are untrusted after a failure) and clears the host tier — no
    stale payload can reactivate into a rebuilt pool."""
    eng = _paged_engine()
    _park_chain(eng, 0, list(range(2, 22)))
    # stage WITHOUT draining (reach under the engine: simulates a
    # failure between eviction and the drain)
    assert eng.kvpool.swap_out_parked() == 1
    assert eng.pool_stats()["pool_swap_pending"] > 0
    eng.paged_reset()
    s = eng.pool_stats()
    assert s["pool_swap_pending"] == 0
    assert s["pool_host_pages"] == 0


def test_swap_in_count_mismatch_is_typed():
    """The engine-side validation the pod replay path converts into a
    ReplayError: page/payload count mismatch is a ValueError before
    anything is recorded."""
    eng = _paged_engine()
    with pytest.raises(ValueError):
        eng.swap_in_pages([0, 1], [b"x"])


# ---------------------------------------------------------------------------
# OP_KV_SWAP: pod broadcast framing + worker replay
# ---------------------------------------------------------------------------


def _capture_plane(n_lanes=2, chunk=8):
    from distributed_llama_multiusers_tpu.parallel.multihost import (
        ControlPlane,
    )

    class _Plane(ControlPlane):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.sent = []

        def _bcast(self, pkt):
            self.sent.append(np.array(pkt))
            return pkt

    return _Plane(n_lanes=n_lanes, chunk=chunk)


class _FeedPlane:
    """Worker-side plane serving previously captured packets."""

    def __init__(self, plane, pkts):
        self._plane = plane
        self._pkts = list(pkts)

    def recv(self):
        from distributed_llama_multiusers_tpu.parallel.multihost import (
            ControlPlane,
        )

        pkt = self._pkts.pop(0)
        ControlPlane.validate(pkt)
        return pkt

    def slot(self, pkt, i, n):
        return self._plane.slot(pkt, i, n)


def test_send_kv_swap_frames_fragments_and_batch_flag():
    """send_kv_swap framing: per-page payload fragments with bit 0 on
    each page's final fragment and bit 1 only on the batch's last
    page's final fragment — and the pod-deadlock rule (empty batch /
    negative page id raise with ZERO packets broadcast)."""
    from distributed_llama_multiusers_tpu.parallel.multihost import (
        OP_KV_SWAP,
    )

    plane = _capture_plane(chunk=8)  # 32 payload bytes per fragment
    with pytest.raises(ValueError):
        plane.send_kv_swap([])
    with pytest.raises(ValueError):
        plane.send_kv_swap([(3, b"x"), (-1, b"y")])
    assert plane.sent == []  # nothing escaped pre-validation

    plane.send_kv_swap([(5, b"a" * 40), (9, b"b" * 8)])
    # page 5: 40 bytes -> fragments of 32 + 8; page 9: one 8-byte frag
    hdrs = [tuple(p[2:6]) for p in plane.sent]
    assert hdrs == [
        (OP_KV_SWAP, 0, 32, 5),  # mid fragment
        (OP_KV_SWAP, 1, 8, 5),  # final fragment of page 5
        (OP_KV_SWAP, 3, 8, 9),  # final fragment of final page: bits 0|1
    ]


def test_worker_replays_kv_swap_as_one_batched_dispatch():
    """The worker reassembles fragments per page, accumulates completed
    pages, and dispatches ONE engine.swap_in_pages for the whole batch
    (bit 1) — program counts identical to the root's."""
    from distributed_llama_multiusers_tpu.parallel.multihost import (
        worker_loop,
    )

    plane = _capture_plane(chunk=8)
    payload_a, payload_b = b"a" * 40, b"b" * 8
    plane.send_kv_swap([(5, payload_a), (9, payload_b)])
    plane.send_stop()

    class _WEng:
        kvpool = object()  # paged marker

        def __init__(self):
            self.calls = []

        def swap_in_pages(self, pages, payloads):
            self.calls.append((list(pages), [bytes(b) for b in payloads]))

    weng = _WEng()
    worker_loop(weng, _FeedPlane(plane, plane.sent))
    assert weng.calls == [([5, 9], [payload_a, payload_b])]


def test_worker_kv_swap_geometry_skew_is_replay_error():
    """A worker whose engine rejects the payload geometry (root and
    worker paged-KV flags skewed) classifies as ReplayError — the
    supervised worker resubscribes instead of dying — and a non-paged
    worker classifies the same way pre-dispatch."""
    from distributed_llama_multiusers_tpu.parallel.multihost import (
        ReplayError,
        worker_loop,
    )

    plane = _capture_plane(chunk=8)
    plane.send_kv_swap([(5, b"a" * 8)])

    class _SkewEng:
        kvpool = object()

        def swap_in_pages(self, pages, payloads):
            raise ValueError("payload 0 is 8 bytes, expected 4096")

    with pytest.raises(ReplayError) as ei:
        worker_loop(_SkewEng(), _FeedPlane(plane, plane.sent))
    assert "geometry" in str(ei.value)

    class _NonPaged:
        kvpool = None

    with pytest.raises(ReplayError) as ei:
        worker_loop(_NonPaged(), _FeedPlane(plane, plane.sent))
    assert "non-paged" in str(ei.value)


def test_pod_root_swap_in_validates_before_broadcast():
    """RootControlEngine.swap_in_pages: count/geometry skew dies ROOT-
    side with zero packets out (the pod-deadlock rule); a valid batch
    broadcasts exactly one OP_KV_SWAP batch then applies root-side."""
    from distributed_llama_multiusers_tpu.parallel.multihost import (
        OP_KV_SWAP,
        RootControlEngine,
    )

    # chunk >= the inner engine's blocks-per-lane (16) so OP_KV_TABLE
    # rows fit their packet slot; swap payloads fragment fine either way
    plane = _capture_plane(chunk=16)
    inner = _paged_engine(page_size=4)
    root = RootControlEngine(inner, plane)

    with pytest.raises(ValueError):
        root.swap_in_pages([0, 1], [b"x"])  # count mismatch
    assert plane.sent == []

    # a valid single-page batch rides the wire and lands on the inner
    # engine (the mock's device half records the payload)
    _park_chain(inner, 0, list(range(2, 22)))
    assert inner.swap_out_parked() == 1
    start = root.paged_admit(1, list(range(2, 22)), reserve_tokens=24)
    assert start == 16
    swap_pkts = [p for p in plane.sent if p[2] == OP_KV_SWAP]
    assert swap_pkts  # the host-tier hits rode OP_KV_SWAP
    assert any(p[3] & 2 for p in swap_pkts)  # batch-final flag present
