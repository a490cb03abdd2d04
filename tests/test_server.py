"""HTTP API server tests — multi-user path (reference: src/dllama-api.cpp),
including true concurrent requests, which the fork's serialized accept loop
could not do."""

import json
import threading
import urllib.request

import pytest
import jax.numpy as jnp

from distributed_llama_multiusers_tpu.formats import load_model_header
from distributed_llama_multiusers_tpu.models import load_params_from_m
from distributed_llama_multiusers_tpu.runtime import ContinuousBatchingScheduler, InferenceEngine
from distributed_llama_multiusers_tpu.server import ApiServer
from distributed_llama_multiusers_tpu.tokenizer import Tokenizer


@pytest.fixture(scope="module")
def server(tiny_model):
    h = load_model_header(tiny_model["model"])
    config, params = load_params_from_m(tiny_model["model"], h, dtype=jnp.float32)
    tok = Tokenizer(tiny_model["tokenizer"])
    engine = InferenceEngine(config, params, n_lanes=4, prefill_buckets=(16, 32))
    sched = ContinuousBatchingScheduler(engine, tok)
    sched.start()
    api = ApiServer(sched, tok, model_name="tiny-test")
    httpd = api.serve(host="127.0.0.1", port=0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{port}"
    httpd.shutdown()
    sched.stop()


def post(url, body, timeout=120):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def test_models_endpoint(server):
    with urllib.request.urlopen(server + "/v1/models", timeout=30) as r:
        body = json.loads(r.read())
    assert body["object"] == "list"
    assert body["data"][0]["id"] == "tiny-test"


def test_chat_completion(server):
    status, body = post(
        server + "/v1/chat/completions",
        {"messages": [{"role": "user", "content": "hello"}], "max_tokens": 6, "temperature": 0},
    )
    assert status == 200
    assert "generated_text" in body  # fork web-ui compat (web-ui/app.js:27-40)
    assert body["choices"][0]["message"]["content"] == body["generated_text"]
    assert body["usage"]["completion_tokens"] <= 6
    assert body["usage"]["prompt_tokens"] > 0


def test_concurrent_chat_completions(server):
    """4 simultaneous clients — all served through the shared batch."""
    results = {}
    errors = []

    def worker(i):
        try:
            results[i] = post(
                server + "/v1/chat/completions",
                {"messages": [{"role": "user", "content": "hello"}], "max_tokens": 5, "temperature": 0},
            )
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not errors
    assert len(results) == 4
    texts = {r[1]["generated_text"] for r in results.values()}
    assert len(texts) == 1  # same prompt, temp 0 -> identical outputs


def test_bad_request(server):
    req = urllib.request.Request(
        server + "/v1/chat/completions", data=b'{"messages": []}',
        headers={"Content-Type": "application/json"},
    )
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=30)
    assert e.value.code == 400


def test_unknown_route(server):
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(server + "/nope", timeout=30)
    assert e.value.code == 404


def test_streaming_sse(server):
    req = urllib.request.Request(
        server + "/v1/chat/completions",
        data=json.dumps(
            {"messages": [{"role": "user", "content": "hello"}], "max_tokens": 6,
             "temperature": 0, "stream": True}
        ).encode(),
        headers={"Content-Type": "application/json"},
    )
    chunks = []
    with urllib.request.urlopen(req, timeout=120) as r:
        assert r.headers["Content-Type"].startswith("text/event-stream")
        for line in r:
            line = line.decode().strip()
            if line.startswith("data: "):
                chunks.append(line[6:])
    assert chunks[-1] == "[DONE]"
    payloads = [json.loads(c) for c in chunks[:-1]]
    # truncated by max_tokens=6 -> accurate finish_reason
    assert payloads[-1]["choices"][0]["finish_reason"] in ("length", "stop")
    streamed = "".join(
        p["choices"][0]["delta"].get("content", "") for p in payloads
    )
    # must equal the non-streaming output for the same input
    _, full = post(
        server + "/v1/chat/completions",
        {"messages": [{"role": "user", "content": "hello"}], "max_tokens": 6, "temperature": 0},
    )
    assert streamed == full["generated_text"]


def test_cors_preflight(server):
    req = urllib.request.Request(server + "/v1/chat/completions", method="OPTIONS")
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.status == 204
        assert r.headers["Access-Control-Allow-Origin"] == "*"


def test_streaming_bad_request_gets_400(server):
    """Validation must happen before SSE headers commit."""
    req = urllib.request.Request(
        server + "/v1/chat/completions",
        data=json.dumps({"stream": True}).encode(),  # no messages
        headers={"Content-Type": "application/json"},
    )
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=30)
    assert e.value.code == 400


def test_stats_endpoint(server):
    """GET /stats reports engine counters + lane occupancy (beyond reference
    parity: the reference has no metrics endpoint, SURVEY §5.5)."""
    # generate something first so counters are non-zero
    post(
        server + "/v1/chat/completions",
        {"messages": [{"role": "user", "content": "hi"}], "max_tokens": 3,
         "temperature": 0},
    )
    with urllib.request.urlopen(server + "/stats", timeout=30) as r:
        body = json.loads(r.read())
    assert body["decode_steps"] >= 1
    assert body["lanes_total"] >= 1
    assert 0 <= body["lanes_busy"] <= body["lanes_total"]
    assert "spec_tokens_per_lane_step" in body
    assert "spec_lane_steps" in body
    # dequant attribution: every /stats payload names the configured dequant
    # mode, and no selection table beside it
    from distributed_llama_multiusers_tpu.ops.pallas_q40 import DEQUANT_MODES

    assert body["dequant_mode"] in DEQUANT_MODES
    assert [k for k in body if k.startswith("dequant_")] == ["dequant_mode"]


def test_text_completion(server):
    """/v1/completions (beyond parity): raw prompt, no chat template."""
    status, body = post(
        server + "/v1/completions",
        {"prompt": "hello world", "max_tokens": 6, "temperature": 0},
    )
    assert status == 200
    assert body["object"] == "text_completion"
    assert body["choices"][0]["text"] == body["generated_text"]
    assert body["usage"]["completion_tokens"] <= 6
    # 1-element list form is accepted; longer lists are a clean 400
    status2, body2 = post(
        server + "/v1/completions",
        {"prompt": ["hello world"], "max_tokens": 6, "temperature": 0},
    )
    assert status2 == 200 and body2["generated_text"] == body["generated_text"]
    import urllib.error

    with pytest.raises(urllib.error.HTTPError) as e3:
        post(server + "/v1/completions", {"prompt": ["a", "b"], "max_tokens": 4})
    assert e3.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e4:
        post(server + "/v1/completions", {"max_tokens": 4})
    assert e4.value.code == 400


def test_text_completion_streaming(server):
    import urllib.request

    req = urllib.request.Request(
        server + "/v1/completions",
        data=json.dumps(
            {"prompt": "hello world", "max_tokens": 6, "temperature": 0,
             "stream": True}
        ).encode(),
        headers={"Content-Type": "application/json"},
    )
    chunks = []
    with urllib.request.urlopen(req, timeout=120) as r:
        assert r.headers["Content-Type"].startswith("text/event-stream")
        for line in r:
            line = line.decode().strip()
            if line.startswith("data: "):
                chunks.append(line[6:])
    assert chunks[-1] == "[DONE]"
    payloads = [json.loads(c) for c in chunks[:-1]]
    assert payloads[-1]["choices"][0]["finish_reason"] in ("length", "stop")
    streamed = "".join(p["choices"][0]["text"] for p in payloads)
    _, full = post(
        server + "/v1/completions",
        {"prompt": "hello world", "max_tokens": 6, "temperature": 0},
    )
    assert streamed == full["generated_text"]
