"""Fleet front-end tests (fleet/ — ISSUE 12).

Three layers:

- **balancer units** — least-loaded wins, breaker-open/draining/dead
  replicas excluded, typed-shed Retry-After honored, prefix-key
  determinism, and the consistent-hash property: when a replica leaves,
  ONLY the keys it owned move (~1/N), everything else stays put.
- **replica surfaces** — the /load JSON (one scrape per routing
  decision), the X-DLlama-Replica attribution header + terminal-chunk
  field, and the /admin/session export + /admin/migrate inject pair.
- **THE pin** — a live SSE stream moved off a dying replica mid-flight
  resumes on another replica BYTE-IDENTICAL to the uninterrupted run,
  with zero lost and zero duplicated output. MockAsyncEngine in
  content_keyed mode is the determinism class the real engine pins
  (tokens are f(prompt content, pos), never f(lane, pos)), so two
  replicas regenerate the same stream from the same (prompt, seed) —
  exactly the property PR 10's replay recovery established and the
  migration primitive reuses.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from distributed_llama_multiusers_tpu.fleet import (
    FleetBalancer,
    FleetRouter,
    prefix_key,
)
from distributed_llama_multiusers_tpu.runtime.scheduler import (
    ContinuousBatchingScheduler,
    Request,
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
# balancer: load routing, eligibility, consistent hashing
# ---------------------------------------------------------------------------


def _loaded(b, rid, queue_depth=0, lanes_free=4, breaker="closed",
            draining=False):
    b.update_load(rid, {
        "queue_depth": queue_depth, "lanes_free": lanes_free,
        "lanes_total": 4, "breaker": breaker, "draining": draining,
    })


def test_least_loaded_wins():
    b = FleetBalancer(["h:1", "h:2", "h:3"])
    _loaded(b, "h:1", queue_depth=5, lanes_free=0)
    _loaded(b, "h:2", queue_depth=0, lanes_free=4)
    _loaded(b, "h:3", queue_depth=2, lanes_free=1)
    assert b.pick().rid == "h:2"
    # deeper queue loses even with free lanes equal
    _loaded(b, "h:2", queue_depth=9, lanes_free=4)
    assert b.pick().rid == "h:3"


def test_breaker_open_and_draining_replicas_excluded():
    b = FleetBalancer(["h:1", "h:2"])
    _loaded(b, "h:1", breaker="open")
    _loaded(b, "h:2")
    assert b.pick().rid == "h:2"
    # keyed picks walk the ring past the unhealthy replica too
    for key in range(0, 20000, 997):
        assert b.pick(key).rid == "h:2"
    _loaded(b, "h:2", draining=True)
    _loaded(b, "h:1", breaker="open")
    assert b.pick() is None  # nobody eligible: the router 503s
    assert not b.any_eligible()
    # recovery: a clean scrape restores eligibility
    _loaded(b, "h:1")
    assert b.any_eligible() and b.pick().rid == "h:1"


def test_shed_retry_after_honored_then_expires():
    b = FleetBalancer(["h:1", "h:2"])
    b.note_shed("h:1", retry_after_s=0.15)
    for _ in range(5):
        assert b.pick().rid == "h:2"
    assert b.min_retry_after_s() >= 1.0  # hint floor
    time.sleep(0.2)
    # horizon passed: h:1 is routable again (and, least-routed, wins)
    assert b.pick().rid == "h:1"


def test_dead_replica_backs_off_then_reprobes():
    b = FleetBalancer(["h:1", "h:2"], dead_backoff_s=0.1)
    b.note_dead("h:1")
    assert b.pick().rid == "h:2"
    time.sleep(0.15)
    # past the backoff the dead replica earns one inline probe
    assert {b.pick().rid for _ in range(4)} == {"h:1", "h:2"}


def test_prefix_key_same_leading_blocks_same_key():
    base = "system prompt block " * 100  # far beyond 4x256 chars
    k1 = prefix_key(base + "user question A")
    k2 = prefix_key(base + "a completely different user question B")
    assert k1 == k2  # leading blocks identical -> same key
    assert prefix_key("x" * 1024) != prefix_key("y" * 1024)
    assert prefix_key("short") is None  # no full block: no affinity
    # the chain folds earlier blocks: same block 1, different block 0
    a = ("A" * 256) + ("Z" * 256)
    bb = ("B" * 256) + ("Z" * 256)
    assert prefix_key(a) != prefix_key(bb)


def test_affinity_deterministic_and_ring_moves_one_over_n():
    """The consistent-hash property the warm-KV map depends on: removing
    one replica moves ONLY the keys it owned (~1/N), every other key
    keeps its replica — membership churn never reshuffles the fleet's
    prefix placement wholesale."""
    replicas = ["h:1", "h:2", "h:3", "h:4"]
    b1 = FleetBalancer(replicas)
    keys = [prefix_key(f"shared system prompt {i} " * 40)
            for i in range(400)]
    owners1 = {k: b1.ring_owner(k) for k in keys}
    # deterministic: a second balancer (fresh process stand-in) agrees
    assert {k: FleetBalancer(replicas).ring_owner(k) for k in keys} \
        == owners1
    # membership change: drop h:3 entirely
    b2 = FleetBalancer(["h:1", "h:2", "h:4"])
    owners2 = {k: b2.ring_owner(k) for k in keys}
    moved = [k for k in keys if owners1[k] != owners2[k]]
    was_on_removed = [k for k in keys if owners1[k] == "h:3"]
    # ONLY the removed replica's keys moved...
    assert set(moved) == set(was_on_removed)
    # ...and it owned roughly 1/N of the space (loose band: vnode noise)
    frac = len(was_on_removed) / len(keys)
    assert 0.10 < frac < 0.45, frac
    # failover (dead, not removed) keeps everyone else's keys too, and
    # the key comes back when the replica does
    b1.note_dead("h:3", backoff_s=60.0)
    for k in keys:
        got = b1.pick(k).rid
        if owners1[k] != "h:3":
            assert got == owners1[k]
        else:
            assert got != "h:3"


# ---------------------------------------------------------------------------
# replica surfaces: /load, attribution, session export + migrate inject
# ---------------------------------------------------------------------------


class TokenTextTokenizer(CharStreamTokenizer):
    """Prompt-dependent encoding + per-token distinct text: stream
    equality is a real assertion (CharStreamTokenizer home: the same
    prompt maps to the same tokens on every replica)."""

    def decode(self, token):
        return f"[{token}]"


def _replica(rid=None, n_lanes=2, grace_s=30.0, step_s=0.005,
             max_queue=0):
    """One in-process dllama-api stand-in: MockAsyncEngine in
    content_keyed mode (the replay-determinism class), resume registry
    (migration targets need one), ephemeral port."""
    from distributed_llama_multiusers_tpu.serving import QosQueue

    engine = MockAsyncEngine(n_lanes=n_lanes, max_chunk=8,
                             content_keyed=True, step_s=step_s)
    sched = ContinuousBatchingScheduler(
        engine, TokenTextTokenizer(64, max_chars=24),
        queue_=QosQueue(capacity=max_queue),
        speculative=False, prefix_min_tokens=0, multi_step=0,
    )
    sched.start()
    registry = StreamRegistry(grace_s=grace_s) if grace_s else None
    api = ApiServer(sched, TokenTextTokenizer(64, max_chars=24),
                    model_name="fleet", template_type=TemplateType.LLAMA2,
                    resume=registry, replica_id=rid)
    httpd = api.serve(host="127.0.0.1", port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"127.0.0.1:{httpd.server_address[1]}"
    return {"api": api, "sched": sched, "registry": registry,
            "httpd": httpd, "base": base, "rid": api.replica_id}


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
        return json.loads(resp.read()), dict(resp.headers)


def test_load_surface_one_scrape_json():
    r = _replica(rid="alpha")
    try:
        load, headers = _get_json(f"http://{r['base']}/load")
        assert load["status"] == "ok" and load["replica"] == "alpha"
        assert load["queue_depth"] == 0
        assert load["lanes_free"] == 2 and load["lanes_total"] == 2
        assert load["breaker"] == "closed" and load["draining"] is False
        assert headers["X-DLlama-Replica"] == "alpha"
        # /health carries the same machine fields (plus its status code)
        health, _ = _get_json(f"http://{r['base']}/health")
        assert health["queue_depth"] == 0 and health["breaker"] == "closed"
        # draining flips both: /load stays 200 (machine surface),
        # /health goes 503 (readiness surface)
        r["sched"]._draining.set()
        load, _ = _get_json(f"http://{r['base']}/load")
        assert load["status"] == "draining" and load["draining"] is True
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"http://{r['base']}/health", timeout=10)
        assert e.value.code == 503
        assert json.loads(e.value.read())["draining"] is True
        r["sched"]._draining.clear()
    finally:
        _stop_replica(r)


def test_replica_attribution_header_and_terminal_chunk():
    r = _replica(rid="attrib-1")
    try:
        req = urllib.request.Request(
            f"http://{r['base']}/v1/completions",
            data=json.dumps({"prompt": "attribution test prompt",
                             "max_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.headers["X-DLlama-Replica"] == "attrib-1"
            json.loads(resp.read())
        # streaming: the header AND the terminal chunk name the replica
        req = urllib.request.Request(
            f"http://{r['base']}/v1/completions",
            data=json.dumps({"prompt": "attribution test prompt",
                             "max_tokens": 4, "stream": True}).encode(),
            headers={"Content-Type": "application/json"},
        )
        term = None
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.headers["X-DLlama-Replica"] == "attrib-1"
            assert int(resp.headers["X-DLlama-Request"]) > 0
            for line in resp:
                line = line.decode().strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                p = json.loads(line[6:])
                if p.get("choices", [{}])[0].get("finish_reason"):
                    term = p
        assert term is not None and term["replica"] == "attrib-1"
    finally:
        _stop_replica(r)


def _stream_collect(url, body, timeout=60):
    """(delta texts, terminal payload, headers) for one SSE POST."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    texts, term = [], None
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        headers = dict(resp.headers)
        for line in resp:
            line = line.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            p = json.loads(line[6:])
            ch = p.get("choices", [{}])[0]
            if ch.get("finish_reason") is None:
                texts.append(ch.get("text", ""))
            else:
                term = p
    return texts, term, headers


def test_session_export_and_migrate_inject_round_trip():
    """The migration primitive end-to-end WITHOUT a router: export a
    live session's ticket from replica A, inject it into replica B,
    reattach from 0 — the regenerated stream is the same bytes."""
    a, b = _replica(rid="src"), _replica(rid="dst")
    try:
        # a slow-ish stream so the session is live while we export
        url = f"http://{a['base']}/v1/completions"
        body = {"prompt": "migration ticket round trip", "max_tokens": 24,
                "stream": True}
        req = urllib.request.Request(
            url, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        resp = urllib.request.urlopen(req, timeout=30)
        rid = int(resp.headers["X-DLlama-Request"])
        # first delta = admitted; the export has the resolved seed
        first = None
        for line in resp:
            line = line.decode().strip()
            if line.startswith("data: ") and line != "data: [DONE]":
                first = json.loads(line[6:])
                break
        assert first is not None
        ticket, _ = _get_json(f"http://{a['base']}/admin/session/{rid}")
        assert ticket["id"] == rid and ticket["k"] == "admit"
        assert isinstance(ticket["seed"], int) and ticket["tokens"]
        assert ticket["stream"] is True
        # finish the source stream; keep its bytes as the reference
        texts = [first["choices"][0].get("text", "")]
        for line in resp:
            line = line.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            p = json.loads(line[6:])
            if p.get("choices", [{}])[0].get("finish_reason") is None:
                texts.append(p["choices"][0].get("text", ""))
        resp.close()
        reference = "".join(texts)

        # inject into B: original id kept, stream path returned
        inj = urllib.request.Request(
            f"http://{b['base']}/admin/migrate",
            data=json.dumps(ticket).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(inj, timeout=30) as r2:
            out = json.loads(r2.read())
        assert out["request_id"] == rid
        # reattach from 0: the full regenerated stream replays
        req3 = urllib.request.Request(
            f"http://{b['base']}{out['stream_path']}",
            headers={"Last-Event-ID": "0"},
        )
        texts3 = []
        with urllib.request.urlopen(req3, timeout=60) as r3:
            for line in r3:
                line = line.decode().strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                p = json.loads(line[6:])
                if p.get("choices", [{}])[0].get("finish_reason") is None:
                    texts3.append(p["choices"][0].get("text", ""))
        assert "".join(texts3) == reference
    finally:
        _stop_replica(a)
        _stop_replica(b)


def test_migrate_inject_remaps_colliding_id():
    """Every replica numbers requests from 1, so an injected session's
    ORIGINAL id routinely names a LIVE request on the target — the
    endpoint must re-admit under a fresh id (the response's request_id
    is authoritative) instead of clobbering the live request's relay
    and session record."""
    a, b = _replica(rid="ca"), _replica(rid="cb")
    try:
        # a live stream on B whose id we will collide with
        url_b = f"http://{b['base']}/v1/completions"
        req_b = urllib.request.Request(
            url_b, data=json.dumps({"prompt": "the innocent bystander",
                                    "max_tokens": 40,
                                    "stream": True}).encode(),
            headers={"Content-Type": "application/json"},
        )
        resp_b = urllib.request.urlopen(req_b, timeout=30)
        live_rid = int(resp_b.headers["X-DLlama-Request"])

        # a finished session on A exported as a ticket, re-labelled
        # with B's live id (the cross-replica collision shape)
        texts, _, _ = _stream_collect(
            f"http://{a['base']}/v1/completions",
            {"prompt": "the migrating session", "max_tokens": 12,
             "stream": True},
        )
        # rebuild the ticket by hand (the session finished; a live
        # export is covered by the round-trip test above)
        ticket = {
            "k": "admit", "id": live_rid,
            "prompt": "the migrating session",
            "tokens": TokenTextTokenizer(64, max_chars=24).encode(
                "the migrating session"),
            "max_tokens": 12, "temp": 0.0, "topp": 0.9, "seed": 5,
            "stream": True, "kind": "completion",
        }
        inj = urllib.request.Request(
            f"http://{b['base']}/admin/migrate",
            data=json.dumps(ticket).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(inj, timeout=30) as r:
            out = json.loads(r.read())
        assert out["request_id"] != live_rid  # remapped, not clobbered
        # the bystander's relay survived: its stream drains to a
        # natural terminal under its ORIGINAL id
        got_terminal = False
        for line in resp_b:
            line = line.decode().strip()
            if line.startswith("data: ") and line != "data: [DONE]":
                p = json.loads(line[6:])
                ch = p.get("choices", [{}])[0]
                if ch.get("finish_reason"):
                    assert ch["finish_reason"] == "length"
                    got_terminal = True
            elif line == "data: [DONE]":
                break
        assert got_terminal
        # and the migrated session streams fully under its NEW id
        req3 = urllib.request.Request(
            f"http://{b['base']}{out['stream_path']}",
            headers={"Last-Event-ID": "0"},
        )
        texts3 = []
        with urllib.request.urlopen(req3, timeout=60) as r3:
            for line in r3:
                line = line.decode().strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                p = json.loads(line[6:])
                if p.get("choices", [{}])[0].get("finish_reason") is None:
                    texts3.append(p["choices"][0].get("text", ""))
        assert "".join(texts3)  # regenerated under the remapped id
    finally:
        _stop_replica(a)
        _stop_replica(b)


def test_migrate_endpoint_refusals():
    # no resume registry on the target: a clear 409, not a shed
    r = _replica(rid="nogrz", grace_s=0)
    try:
        ticket = {"k": "admit", "id": 12345, "prompt": "p",
                  "tokens": [1, 2, 3], "max_tokens": 4, "temp": 0.0,
                  "topp": 0.9, "seed": 7, "stream": True}
        req = urllib.request.Request(
            f"http://{r['base']}/admin/migrate",
            data=json.dumps(ticket).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 409
        # malformed record: 400
        req = urllib.request.Request(
            f"http://{r['base']}/admin/migrate",
            data=json.dumps({"k": "finish", "id": 1}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 400
        # unknown session export: 404
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                f"http://{r['base']}/admin/session/424242", timeout=10
            )
        assert e.value.code == 404
    finally:
        _stop_replica(r)


# ---------------------------------------------------------------------------
# router: routing + typed sheds + THE migration pin
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


def test_router_routes_around_draining_replica_and_gives_up_typed():
    a, b = _replica(rid="ra"), _replica(rid="rb")
    router, rhttpd, rbase = _router([a, b])
    try:
        a["sched"]._draining.set()
        router.scrape_once()  # the scrape sees the drain flag
        body = {"prompt": "routing probe " * 30, "max_tokens": 4}
        for _ in range(3):
            req = urllib.request.Request(
                rbase + "/v1/completions",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.headers["X-DLlama-Replica"] == "rb"
                json.loads(resp.read())
        # both gone: ONE aggregate typed 503 with a Retry-After hint
        b["sched"]._draining.set()
        router.scrape_once()
        req = urllib.request.Request(
            rbase + "/v1/completions", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code == 503
        payload = json.loads(e.value.read())
        assert payload["reason"] == "fleet_exhausted"
        assert int(e.value.headers["Retry-After"]) >= 1
        assert router.giveups >= 1
        a["sched"]._draining.clear()
        b["sched"]._draining.clear()
    finally:
        router.close()
        rhttpd.shutdown()
        _stop_replica(a)
        _stop_replica(b)


def test_router_retries_replica_shed_elsewhere():
    """A typed 429 (queue full) from one replica is retried on another;
    the shed replica's Retry-After becomes a routing backoff."""
    a = _replica(rid="full", n_lanes=1, max_queue=1)
    b = _replica(rid="roomy")
    router, rhttpd, rbase = _router([a, b])
    try:
        # saturate A directly: 1 lane busy + 1 queued (paced so the
        # first hold reaches its lane before the second one fills the
        # capacity-1 queue — pushing both at once would shed here)
        hold = [Request(prompt="hold the lane", max_tokens=400)
                for _ in range(2)]
        a["sched"].submit(hold[0])
        deadline = time.monotonic() + 10
        while not a["sched"].queue.empty() and time.monotonic() < deadline:
            time.sleep(0.005)
        a["sched"].submit(hold[1])
        # keyless short prompt -> least-loaded may pick A (scraped before
        # saturation); the 429 must bounce to B transparently
        router.scrape_once()
        deadline = time.monotonic() + 30
        saw_roomy = False
        while time.monotonic() < deadline and not saw_roomy:
            req = urllib.request.Request(
                rbase + "/v1/completions",
                data=json.dumps({"prompt": "x", "max_tokens": 2}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                saw_roomy = resp.headers["X-DLlama-Replica"] == "roomy"
                json.loads(resp.read())
        assert saw_roomy
        for h in hold:
            h.cancel()
    finally:
        router.close()
        rhttpd.shutdown()
        _stop_replica(a)
        _stop_replica(b)


def test_router_affinity_same_prefix_same_replica():
    a, b, c = _replica(rid="f1"), _replica(rid="f2"), _replica(rid="f3")
    router, rhttpd, rbase = _router([a, b, c])
    try:
        system = "you are a helpful assistant " * 40  # > 4 blocks
        served = set()
        for i in range(6):
            req = urllib.request.Request(
                rbase + "/v1/completions",
                data=json.dumps({
                    "prompt": system + f"user question {i}",
                    "max_tokens": 2,
                }).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                served.add(resp.headers["X-DLlama-Replica"])
        assert len(served) == 1  # same leading blocks -> same replica
        stats = router.handle_stats()
        assert stats["fleet_affinity_routes"] >= 6
        assert stats["fleet_affinity_hits"] >= 6
    finally:
        router.close()
        rhttpd.shutdown()
        for r in (a, b, c):
            _stop_replica(r)


def _stream_via_router(rbase, body, on_delta=None, timeout=120):
    """Stream through the router; returns (concatenated text, terminal
    payload, served-by header, router SSE ids)."""
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
                if on_delta is not None:
                    on_delta(len(texts))
            else:
                term = p
    return "".join(texts), term, served, ids


@pytest.mark.parametrize("how", ["kill", "drain_timeout"])
def test_live_migration_mid_stream_byte_identical(how):
    """THE pin (acceptance criterion): a streaming session moved off a
    dying replica resumes on another replica byte-identical to the
    uninterrupted run — zero lost, zero duplicated output — and the
    router's SSE ids stay gapless across the splice. ``kill`` is the
    orderly-death shape (accept loop down + scheduler stopped with the
    stream mid-flight); transport-level breaks land in the same migrate
    branch via the socket-error path. ``drain_timeout`` is a rolling
    restart that runs out of patience: the replica stays reachable,
    flips to draining, and force-cancels what is still streaming when
    its short window closes — that stream must migrate, not die."""
    a, b = _replica(rid="m1"), _replica(rid="m2")
    router, rhttpd, rbase = _router([a, b])
    killed = []
    try:
        body = {"prompt": "migration pin prompt " * 20, "max_tokens": 40,
                "stream": True}
        # reference: the uninterrupted run through the router (content
        # keyed: the same prompt regenerates the same stream anywhere)
        ref_text, ref_term, ref_served, _ = _stream_via_router(rbase, body)
        assert ref_term["choices"][0]["finish_reason"] == "length"
        assert len(ref_text) > 0
        source = ref_served  # affinity: the next run lands there too

        def kill_source(n_deltas):
            if n_deltas == 5 and not killed:
                victim = a if source == "m1" else b
                killed.append(victim)
                if how == "kill":
                    victim["httpd"].shutdown()
                    victim["sched"].stop()
                else:
                    threading.Thread(
                        target=lambda: victim["sched"].drain(timeout=0.03),
                        daemon=True,
                    ).start()

        text, term, served, ids = _stream_via_router(
            rbase, body, on_delta=kill_source
        )
        assert killed, "the kill never fired"
        assert served == source
        # byte-identical client view: nothing lost, nothing duplicated
        assert text == ref_text
        assert term is not None and "error" not in term
        assert term["choices"][0]["finish_reason"] == "length"
        # the router's re-stamped ids are gapless across the migration
        assert ids == list(range(1, len(ids) + 1))
        assert router.migrations_ok == 1 and router.migrations_failed == 0
        # the metric saw it too
        assert "dllama_router_migrations_total" in router.handle_metrics()
    finally:
        router.close()
        rhttpd.shutdown()
        for r in (a, b):
            if how != "kill" or r not in killed:
                _stop_replica(r)


def test_migration_rescues_engine_failure_terminal():
    """An engine-scoped failure on the source replica (contained by the
    supervised loop, PR 8 — the stream ends with a typed error) is
    migratable: the router moves the innocent session to a healthy
    replica instead of passing the failure through."""
    a, b = _replica(rid="e1"), _replica(rid="e2")
    router, rhttpd, rbase = _router([a, b])
    try:
        body = {"prompt": "engine failure rescue " * 20, "max_tokens": 30,
                "stream": True}
        ref_text, _, source, _ = _stream_via_router(rbase, body)

        fired = []

        def break_engine(n_deltas):
            if n_deltas == 4 and not fired:
                victim = a if source == "e1" else b
                fired.append(victim)
                # engine-scoped raise on the next dispatch: the
                # supervised loop contains it and fails the lane with
                # finish_reason="error"
                orig = victim["sched"].engine.decode_pipelined

                def boom(*args, **kw):
                    victim["sched"].engine.decode_pipelined = orig
                    raise RuntimeError("injected engine failure")

                victim["sched"].engine.decode_pipelined = boom

        text, term, served, _ = _stream_via_router(
            rbase, body, on_delta=break_engine
        )
        assert fired
        assert text == ref_text
        assert term["choices"][0]["finish_reason"] == "length"
        assert router.migrations_ok >= 1
    finally:
        router.close()
        rhttpd.shutdown()
        _stop_replica(a)
        _stop_replica(b)
