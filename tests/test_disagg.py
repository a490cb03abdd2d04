"""Disaggregated prefill tests (disagg/ — ISSUE 16).

Three layers, mirroring tests/test_fleet.py's shape:

- **kvtransfer units** — bundle export/import round trip over the REAL
  :class:`KVPagePool` (fleet-independent): integrity hashes verify
  before any mutation, adoption is refcount-correct (reused prefixes
  bump refs, only fresh pages import payloads), a COW-born block
  survives transfer, and exhaustion/corruption shed typed WITHOUT
  partial adoption.
- **replica surfaces** — the role field on /load, the
  ``GET /admin/kvpages/<id>`` export and ``POST /admin/kvimport``
  adopt endpoints with their typed refusals (404/400/409/422).
- **THE pin** — a long-classified request routed to a prefill-role
  replica hands its KV pages + session to a decode replica mid-stream,
  and the client stream is byte-identical to the single-replica run;
  every hand-off failure (no decode target, prefill death mid-transfer)
  degrades to a typed fallback, never a hung stream.

MockAsyncEngine in ``content_keyed + paged`` mode is the determinism
class under test: its page payloads are content-canonical (sha256 of
the tree-node key), so two replicas that committed the same prefix
export identical bytes and the integrity machinery is exercised for
real, not vacuously.
"""

from __future__ import annotations

import base64
import json
import threading
import urllib.error
import urllib.request

import pytest

from distributed_llama_multiusers_tpu.disagg import (
    HandoffAborted,
    KVTransferError,
    adopt_bundle,
    classify_prompt,
    decode_bundle,
    export_bundle,
    page_hash,
    prompt_chars,
)
from distributed_llama_multiusers_tpu.fleet import FleetRouter
from distributed_llama_multiusers_tpu.runtime.kvpool import PoolExhausted
from distributed_llama_multiusers_tpu.runtime.scheduler import (
    ContinuousBatchingScheduler,
)
from distributed_llama_multiusers_tpu.serving import StreamRegistry
from distributed_llama_multiusers_tpu.server import ApiServer
from distributed_llama_multiusers_tpu.tokenizer import TemplateType
from distributed_llama_multiusers_tpu.utils import faults
from distributed_llama_multiusers_tpu.utils.testing import (
    CharStreamTokenizer,
    MockAsyncEngine,
)


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.disarm()
    yield
    faults.disarm()


# ---------------------------------------------------------------------------
# kvtransfer units: bundle round trip over the real pool
# ---------------------------------------------------------------------------


def _paged_engine(pool_pages=32, max_parked=8, page_size=4, seq_len=64,
                  n_lanes=2):
    """A paged mock: the REAL KVPagePool bookkeeping, device half mocked
    content-canonically (export/import are genuine round trips)."""
    return MockAsyncEngine(
        n_lanes=n_lanes, content_keyed=True, paged=True,
        kv_page_size=page_size, kv_pool_pages=pool_pages,
        kv_max_parked=max_parked, seq_len=seq_len,
    )


def _commit_chain(engine, lane, tokens):
    """Admit + commit + park one session's chain on ``engine``."""
    engine.paged_admit(lane, tokens, reserve_tokens=len(tokens))
    engine.paged_commit(lane, tokens)
    engine.paged_finish(lane, park=True)


def test_bundle_export_import_round_trip():
    """THE unit pin: export a committed chain off pool A, adopt into
    pool B — pages + hashes verify, only fresh pages import, the
    adopted prefix is visible to B's admission (refcount-shared), and
    re-export off B reproduces the bundle byte-for-byte."""
    a = _paged_engine()
    tokens = list(range(2, 26))  # 24 tokens = 6 full blocks of 4
    _commit_chain(a, 0, tokens)

    bundle = export_bundle(a.kvpool, a, tokens)
    assert bundle["v"] == 1 and bundle["page_size"] == 4
    assert bundle["n_tokens"] == 24 and len(bundle["blocks"]) == 6
    for blk in bundle["blocks"]:
        payload = base64.b64decode(blk["p"])
        assert blk["h"] == page_hash(4, blk["t"], payload)

    b = _paged_engine()
    receipt = adopt_bundle(b.kvpool, b, bundle)
    assert receipt == {"pages": 6, "fresh": 6, "reused": 0}
    assert b.pages_imported == 6
    stats = b.kvpool.stats()
    assert stats["pool_adopts"] == 1
    assert stats["pool_adopted_pages_fresh"] == 6
    # the chain is registered: B's tree resolves every block in order
    assert len(b.kvpool.chain_pages(tokens)) == 6
    # round-trip fidelity: B re-exports the identical bundle
    assert export_bundle(b.kvpool, b, tokens) == bundle

    # idempotent re-adoption: all reused, zero new imports (reused
    # pages' bytes may be live read targets — skipping them is the rule)
    receipt2 = adopt_bundle(b.kvpool, b, bundle)
    assert receipt2 == {"pages": 6, "fresh": 0, "reused": 6}
    assert b.pages_imported == 6

    # refcount-correct adoption: a real admission on B shares the whole
    # adopted prefix copy-free (start = 24 of 25 prompt tokens)
    start = b.paged_admit(0, tokens + [50], reserve_tokens=26,
                          min_share_tokens=4)
    assert start == 24


def test_cow_block_survives_transfer():
    """A block born through the pool's copy-on-write path (divergence
    inside a shared block) exports and adopts like any committed block,
    and adoption dedups against the shared prefix it branched from."""
    a = _paged_engine()
    base = list(range(2, 26))  # 6 blocks
    _commit_chain(a, 0, base)
    # session 2 shares 5 full blocks + 2 tokens of block 6, then
    # diverges: admit serves the partial block copy-on-write
    forked = base[:22] + [91, 92]
    start, _blocks, copies, _sw = a.kvpool.admit(
        1, forked, reserve_tokens=len(forked) + 1, min_share_tokens=4
    )
    assert copies, "expected a COW copy at the divergent block"
    assert start == 22  # 5 shared blocks + 2 COW-served tokens
    a.kvpool.commit(1, forked)
    a.kvpool.finish(1, park=True)

    bundle = export_bundle(a.kvpool, a, forked)
    assert len(bundle["blocks"]) == 6  # the COW block is committed too

    b = _paged_engine()
    assert adopt_bundle(b.kvpool, b, bundle) \
        == {"pages": 6, "fresh": 6, "reused": 0}
    # adopting the ORIGINAL chain now moves only the divergent tail:
    # the 5 shared blocks dedup against the forked chain's prefix
    bundle_base = export_bundle(a.kvpool, a, base)
    assert adopt_bundle(b.kvpool, b, bundle_base) \
        == {"pages": 6, "fresh": 1, "reused": 5}


def test_integrity_failure_adopts_nothing():
    """A corrupted payload (or a payload attached to the wrong block)
    dies typed BEFORE any pool mutation — never a partial adoption."""
    a = _paged_engine()
    tokens = list(range(2, 26))
    _commit_chain(a, 0, tokens)
    bundle = export_bundle(a.kvpool, a, tokens)

    # flipped payload bytes on block 1
    evil = json.loads(json.dumps(bundle))
    evil["blocks"][1]["p"] = base64.b64encode(b"\x00" * 64).decode()
    b = _paged_engine()
    free_before = b.kvpool.pages_free()
    with pytest.raises(KVTransferError) as e:
        adopt_bundle(b.kvpool, b, evil)
    assert e.value.reason == "integrity"
    assert b.kvpool.pages_free() == free_before
    assert b.kvpool.stats()["pool_adopts"] == 0
    assert b.pages_imported == 0
    assert b.kvpool.chain_pages(tokens) == []

    # payload intact but re-attached to the WRONG block: the tokens are
    # part of the hash framing, so the mix-up is caught too
    swapped = json.loads(json.dumps(bundle))
    swapped["blocks"][0]["t"], swapped["blocks"][1]["t"] = \
        swapped["blocks"][1]["t"], swapped["blocks"][0]["t"]
    with pytest.raises(KVTransferError) as e:
        adopt_bundle(b.kvpool, b, swapped)
    assert e.value.reason == "integrity"


def test_bundle_geometry_and_shape_rejections():
    a = _paged_engine()
    tokens = list(range(2, 26))
    _commit_chain(a, 0, tokens)
    bundle = export_bundle(a.kvpool, a, tokens)
    b = _paged_engine()

    with pytest.raises(KVTransferError) as e:
        decode_bundle(b.kvpool, {**bundle, "v": 2})
    assert e.value.reason == "bundle_version"

    with pytest.raises(KVTransferError) as e:
        decode_bundle(b.kvpool, {**bundle, "page_size": 8})
    assert e.value.reason == "page_size_mismatch"

    short_payload = b"x" * 8
    partial = {**bundle, "blocks": [{
        "t": [1, 2, 3],
        "p": base64.b64encode(short_payload).decode(),
        "h": page_hash(4, [1, 2, 3], short_payload),
    }]}
    with pytest.raises(KVTransferError) as e:
        decode_bundle(b.kvpool, partial)
    assert e.value.reason == "partial_block"

    with pytest.raises(KVTransferError) as e:
        decode_bundle(b.kvpool, {**bundle, "blocks": [{"t": [1, 2, 3, 4]}]})
    assert e.value.reason == "malformed_block"

    # empty chain: a valid no-op, not an error (prompt under one block)
    assert adopt_bundle(b.kvpool, b, {**bundle, "blocks": []}) \
        == {"pages": 0, "fresh": 0, "reused": 0}
    assert b.kvpool.stats()["pool_adopts"] == 0


def test_adopt_exhausted_pool_sheds_without_mutation():
    """Adoption against a pool whose pages are pinned by LIVE lanes
    raises the typed PoolExhausted with the pool exactly as it was —
    the importing replica's 429 shed, never garbage state."""
    b = _paged_engine(pool_pages=32)
    # two live lanes pin 30 of 32 pages (not parked: nothing evictable)
    b.paged_admit(0, list(range(100, 156)), reserve_tokens=57)
    b.paged_admit(1, list(range(200, 256)), reserve_tokens=57)
    assert b.kvpool.pages_free() < 6

    a = _paged_engine()
    foreign = list(range(2, 26))
    _commit_chain(a, 0, foreign)
    bundle = export_bundle(a.kvpool, a, foreign)

    free_before = b.kvpool.pages_free()
    with pytest.raises(PoolExhausted):
        adopt_bundle(b.kvpool, b, bundle)
    assert b.kvpool.pages_free() == free_before
    assert b.kvpool.chain_pages(foreign) == []
    assert b.pages_imported == 0

    # a parkless pool cannot pin the adopted chain: typed refusal
    parkless = _paged_engine(max_parked=0)
    with pytest.raises(ValueError):
        adopt_bundle(parkless.kvpool, parkless, bundle)


# ---------------------------------------------------------------------------
# prompt-length classification
# ---------------------------------------------------------------------------


def test_prompt_chars_both_api_shapes():
    assert prompt_chars({"prompt": "abcd"}) == 4
    assert prompt_chars({"prompt": ["ab", "cd", 7]}) == 4
    assert prompt_chars({"messages": [
        {"role": "system", "content": "abc"},
        {"role": "user", "content": "de"},
        {"role": "user", "content": None},
    ]}) == 5
    assert prompt_chars({}) == 0


def test_classify_prompt_threshold_and_disable():
    assert classify_prompt({"prompt": "x" * 99}, 100) == "short"
    assert classify_prompt({"prompt": "x" * 100}, 100) == "long"
    # non-positive threshold disables disagg routing entirely
    assert classify_prompt({"prompt": "x" * 10_000}, 0) == "short"
    assert classify_prompt({"prompt": "x" * 10_000}, -1) == "short"


# ---------------------------------------------------------------------------
# replica surfaces: role on /load, kvpages export, kvimport adopt
# ---------------------------------------------------------------------------


class _Tok(CharStreamTokenizer):
    def decode(self, token):
        return f"[{token}]"


def _paged_replica(rid, role="mixed", grace_s=30.0, paged=True):
    engine = MockAsyncEngine(
        n_lanes=2, max_chunk=8, content_keyed=True, step_s=0.004,
        paged=paged, kv_page_size=16, kv_pool_pages=128, kv_max_parked=32,
    )
    sched = ContinuousBatchingScheduler(
        engine, _Tok(64, max_chars=96),
        speculative=False, prefix_min_tokens=16, multi_step=0,
    )
    sched.start()
    registry = StreamRegistry(grace_s=grace_s) if grace_s else None
    api = ApiServer(sched, _Tok(64, max_chars=96), model_name="disagg",
                    template_type=TemplateType.LLAMA2, resume=registry,
                    replica_id=rid, role=role)
    httpd = api.serve(host="127.0.0.1", port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return {"api": api, "engine": engine, "sched": sched,
            "registry": registry, "httpd": httpd,
            "base": f"127.0.0.1:{httpd.server_address[1]}", "rid": rid}


def _stop_replica(r):
    try:
        r["httpd"].shutdown()
    finally:
        if r["registry"] is not None:
            r["registry"].close()
        try:
            r["sched"].stop()
        except RuntimeError:
            pass


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return json.loads(resp.read())


def _post_json(url, body, timeout=20):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _open_stream(base, body, timeout=60):
    req = urllib.request.Request(
        f"http://{base}/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    resp = urllib.request.urlopen(req, timeout=timeout)
    rid = int(resp.headers["X-DLlama-Request"])
    # read to the first delta: admission (and the prompt's page
    # commits) are proven before the caller exports anything
    for line in resp:
        line = line.decode().strip()
        if line.startswith("data: ") and line != "data: [DONE]":
            break
    return resp, rid


def _drain(resp):
    for line in resp:
        pass
    resp.close()


def test_run_device_op_executes_on_loop_thread_and_relays_errors():
    """The donation-race fix (found by a live real-engine drive): page
    export/import must run on the batching-loop thread at its step
    boundary — the pipelined chain donates the cache pytree, so an
    admin-thread touch of ``engine.cache`` mid-chain hits a deleted
    buffer. Pins: (a) ops posted from another thread execute ON the
    loop thread, (b) exceptions re-raise to the caller with their
    original type, (c) a stopped loop runs ops inline (tests, drained
    servers), never hangs the caller."""
    engine = _paged_engine()
    sched = ContinuousBatchingScheduler(
        engine, CharStreamTokenizer(64), speculative=False, multi_step=0,
    )
    # (c) loop not running: inline on the calling thread
    here = threading.current_thread()
    assert sched.run_device_op(threading.current_thread) is here
    sched.start()
    try:
        # (a) posted from this (non-loop) thread, executed on the loop
        ran_on = sched.run_device_op(threading.current_thread)
        assert ran_on is sched._thread
        assert ran_on is not here

        # (b) original exception type crosses back to the caller
        class _Boom(RuntimeError):
            pass

        def _raise():
            raise _Boom("device op failed")

        with pytest.raises(_Boom, match="device op failed"):
            sched.run_device_op(_raise)
        # the loop survived the op's exception
        assert sched.run_device_op(lambda: 7) == 7
    finally:
        sched.stop()
    # (c) again after stop: inline, no hang
    assert sched.run_device_op(threading.current_thread) is here


def test_role_advertised_on_load_scrape():
    p = _paged_replica("pf", role="prefill")
    m = _paged_replica("mx")
    try:
        assert _get_json(f"http://{p['base']}/load")["role"] == "prefill"
        assert _get_json(f"http://{m['base']}/load")["role"] == "mixed"
    finally:
        _stop_replica(p)
        _stop_replica(m)


def test_kvpages_export_surface():
    r = _paged_replica("exp")
    try:
        prompt = "kv page export surface " * 4  # 92 chars -> 5 full pages
        resp, rid = _open_stream(
            r["base"], {"prompt": prompt, "max_tokens": 24, "stream": True}
        )
        bundle = _get_json(f"http://{r['base']}/admin/kvpages/{rid}")
        assert bundle["v"] == 1 and bundle["page_size"] == 16
        assert len(bundle["blocks"]) >= 5
        for blk in bundle["blocks"]:
            assert blk["h"] == page_hash(
                16, blk["t"], base64.b64decode(blk["p"])
            )
        _drain(resp)
        # unknown session: 404; non-numeric id: 400
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                f"http://{r['base']}/admin/kvpages/424242", timeout=10
            )
        assert e.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                f"http://{r['base']}/admin/kvpages/nope", timeout=10
            )
        assert e.value.code == 400
    finally:
        _stop_replica(r)


def test_kvimport_surface_and_typed_refusals():
    src = _paged_replica("isrc")
    dst = _paged_replica("idst")
    flat = _paged_replica("iflat", paged=False)
    try:
        prompt = "kv import surface round trip " * 3  # 87 chars
        resp, rid = _open_stream(
            src["base"], {"prompt": prompt, "max_tokens": 24, "stream": True}
        )
        bundle = _get_json(f"http://{src['base']}/admin/kvpages/{rid}")
        _drain(resp)
        status, receipt = _post_json(
            f"http://{dst['base']}/admin/kvimport", bundle
        )
        assert status == 200
        assert receipt["pages"] >= 5 and receipt["fresh"] == receipt["pages"]
        assert receipt["replica"] == "idst"
        assert dst["engine"].pages_imported == receipt["pages"]

        # corrupted in flight: typed 422, destination pool untouched
        evil = json.loads(json.dumps(bundle))
        evil["blocks"][0]["p"] = base64.b64encode(b"\x11" * 64).decode()
        adopts_before = dst["engine"].pool_stats()["pool_adopts"]
        with pytest.raises(urllib.error.HTTPError) as e:
            _post_json(f"http://{dst['base']}/admin/kvimport", evil)
        assert e.value.code == 422
        assert json.loads(e.value.read())["reason"] == "integrity"
        assert dst["engine"].pool_stats()["pool_adopts"] == adopts_before

        # a contiguous-cache replica cannot adopt pages: clear 409
        with pytest.raises(urllib.error.HTTPError) as e:
            _post_json(f"http://{flat['base']}/admin/kvimport", bundle)
        assert e.value.code == 409
    finally:
        for r in (src, dst, flat):
            _stop_replica(r)


# ---------------------------------------------------------------------------
# router: THE disagg pin + typed fallbacks
# ---------------------------------------------------------------------------


def _router(replicas, **kw):
    router = FleetRouter(
        {r["rid"]: r["base"] for r in replicas},
        scrape_interval_s=kw.pop("scrape_interval_s", 0.1),
        **kw,
    ).start()
    httpd = router.serve(host="127.0.0.1", port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    router.scrape_once()
    return router, httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


def _stream_via_router(rbase, body, timeout=120):
    req = urllib.request.Request(
        rbase + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    texts, ids, term = [], [], None
    cur_id = None
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        served = resp.headers.get("X-DLlama-Replica")
        for line in resp:
            line = line.decode().strip()
            if line.startswith("id: "):
                cur_id = int(line[4:])
                continue
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                break
            p = json.loads(line[6:])
            if "error" in p:
                term = p
                continue
            ch = p.get("choices", [{}])[0]
            if ch.get("finish_reason") is None:
                texts.append(ch.get("text", ""))
                if cur_id is not None:
                    ids.append(cur_id)
                cur_id = None
            else:
                term = p
    return "".join(texts), term, served, ids


def _oracle_text(body):
    """The single-replica reference stream off a STANDALONE replica
    (content_keyed: byte-identical wherever the prompt runs)."""
    r = _paged_replica("oracle")
    try:
        req = urllib.request.Request(
            f"http://{r['base']}/v1/completions",
            data=json.dumps({**body, "stream": False}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            return json.loads(resp.read())["generated_text"]
    finally:
        _stop_replica(r)


LONG_BODY = {"prompt": "disagg hand off pin prompt " * 10,  # 270 chars
             "max_tokens": 24, "stream": True}


def test_disagg_handoff_mid_stream_byte_identical():
    """THE pin (acceptance criterion): a long-classified request routed
    to the prefill-role replica hands off — pages adopted fresh on the
    decode replica, session injected, stream reattached — and the
    client sees the single-replica bytes with gapless SSE ids."""
    ref = _oracle_text(LONG_BODY)
    p = _paged_replica("p0", role="prefill")
    d = _paged_replica("d0", role="decode")
    # short sessions co-resident with the hand-off: they must not notice
    shorts = [{"prompt": f"coresident question {i}", "max_tokens": 16,
               "stream": True} for i in range(3)]
    short_refs = [_oracle_text(b) for b in shorts]
    router, rhttpd, rbase = _router([p, d], long_prompt_chars=120)
    try:
        got = {}

        def short_client(i):
            got[i] = _stream_via_router(rbase, shorts[i])

        threads = [threading.Thread(target=short_client, args=(i,))
                   for i in range(len(shorts))]
        for t in threads:
            t.start()
        text, term, served, ids = _stream_via_router(rbase, LONG_BODY)
        for t in threads:
            t.join(timeout=120)
        assert [got[i][0] for i in range(len(shorts))] == short_refs
        assert all("error" not in got[i][1] for i in range(len(shorts)))
        assert served == "p0"  # long -> the prefill-role replica
        assert text == ref
        assert term is not None and "error" not in term
        assert term["choices"][0]["finish_reason"] == "length"
        assert ids == list(range(1, len(ids) + 1))
        assert router.disagg_handoffs_ok == 1
        assert router.disagg_fallbacks == 0
        assert router.disagg_pages_fresh >= 1
        # the decode replica genuinely adopted + imported the pages
        assert d["engine"].pool_stats()["pool_adopts"] >= 1
        assert d["engine"].pages_imported >= 1
        assert "dllama_router_disagg_handoffs_total" \
            in router.handle_metrics()
        stats = router.handle_stats()
        assert stats["router_disagg_handoffs_ok"] == 1
        assert stats["router_long_prompt_chars"] == 120
        # export, import and adoption dispatched no new device program
        for r in (p, d):
            assert r["engine"].stats.snapshot()[
                "jit_compiles_after_warmup"] == 0, r["rid"]
    finally:
        router.close()
        rhttpd.shutdown()
        _stop_replica(p)
        _stop_replica(d)
    # adopted pages park or free with their session like native ones:
    # after stop() neither side holds a page, a mirror or a pending op
    for r in (p, d):
        assert all(v == 0 for v in r["sched"].leak_counts().values()), (
            r["rid"], r["sched"].leak_counts())


@pytest.mark.parametrize("fleet", ["prefill_only", "prefill_dead"])
def test_no_decode_target_falls_back_monolithic(fleet):
    """``prefill_only``: a fleet with ONLY the prefill replica: the
    hand-off has nowhere to go, so it falls back typed and the original
    stream finishes byte-identical — the monolithic path, never a hang.
    ``prefill_dead``: the prefill replica is gone before the long prompt
    arrives; with no prefill-role replica eligible the router serves it
    whole on the decode replica, byte-identical, with no hand-off tried."""
    ref = _oracle_text(LONG_BODY)
    p = _paged_replica("solo", role="prefill")
    alive = [p]
    if fleet == "prefill_dead":
        alive.append(_paged_replica("d0", role="decode"))
    router, rhttpd, rbase = _router(alive, long_prompt_chars=120)
    try:
        if fleet == "prefill_dead":
            alive.remove(p)
            _stop_replica(p)
            p["httpd"].server_close()  # connects refused: a dead process
            router.scrape_once()
        text, term, served, _ = _stream_via_router(rbase, LONG_BODY)
        assert served == ("solo" if fleet == "prefill_only" else "d0")
        assert text == ref
        assert term["choices"][0]["finish_reason"] == "length"
        assert router.disagg_handoffs_ok == 0
        assert router.disagg_fallbacks == (1 if fleet == "prefill_only" else 0)
    finally:
        router.close()
        rhttpd.shutdown()
        for r in alive:
            _stop_replica(r)


def test_prefill_death_mid_transfer_migrates_not_hangs(monkeypatch):
    """The nastiest failure mode: the prefill replica DIES in the
    middle of the transfer. The hand-off aborts typed (fallback), the
    resumed source stream breaks, and the normal migration path moves
    the session to the decode replica off the cached ticket — the
    client still sees the single-replica bytes, never a hung stream."""
    import distributed_llama_multiusers_tpu.fleet.router as router_mod

    ref = _oracle_text(LONG_BODY)
    p = _paged_replica("dies", role="prefill")
    d = _paged_replica("lives", role="decode")

    def deadly_hand_off(*args, **kw):
        # the source replica dies mid-transfer (scheduler force-cancel
        # + accept loop down, the orderly-death shape). stop() comes
        # FIRST and synchronously: the in-flight lanes must be
        # cancelled before the fallback resumes the source stream, so
        # the pump deterministically takes the migrate branch instead
        # of racing the short remaining generation to a natural finish
        # (httpd.shutdown() can block up to its serve-loop poll
        # interval, longer than the whole stream)
        p["sched"].stop()
        p["httpd"].shutdown()
        p["httpd"].server_close()
        raise HandoffAborted("src_died", "injected: source died mid-transfer")

    monkeypatch.setattr(router_mod, "hand_off", deadly_hand_off)
    router, rhttpd, rbase = _router([p, d], long_prompt_chars=120)
    try:
        text, term, served, ids = _stream_via_router(rbase, LONG_BODY)
        assert served == "dies"
        assert text == ref
        assert term is not None and "error" not in term
        assert term["choices"][0]["finish_reason"] == "length"
        assert ids == list(range(1, len(ids) + 1))
        assert router.disagg_fallbacks == 1
        assert router.disagg_handoffs_ok == 0
        assert router.migrations_ok == 1  # the rescue: ticket migration
    finally:
        router.close()
        rhttpd.shutdown()
        _stop_replica(d)
