"""A token is streamed at the readback that delivers it, and committed at the
readback of the step that was fed it (PR 57: ``scheduler.py`` ``_stream`` /
``_commit`` / ``_advance``).

What must NOT have moved is everything but the clock value a delta carries:
the tokens, the deltas and their order, the text, the ``finish_reason``, the
resident-KV map at park, the dispatches the loop makes and the step at which a
lane is released. Every case below is held to a PLAIN reference of the stream
(the mock engine's token function run through an ``EosDetector`` in a loop: no
scheduler code), on the synchronous ladder and on the pipelined chain alike.
"""

import pytest

from distributed_llama_multiusers_tpu.runtime import (
    ContinuousBatchingScheduler,
    Request,
)
from distributed_llama_multiusers_tpu.serving.journal import (
    entry_from_admit_record,
)
from distributed_llama_multiusers_tpu.telemetry import Telemetry
from distributed_llama_multiusers_tpu.tokenizer.eos import (
    EosDetector,
    EosResult,
)
from distributed_llama_multiusers_tpu.utils.testing import (
    ByteJsonTokenizer,
    CharStreamTokenizer,
    MockAsyncEngine,
)

PATHS = {
    # the synchronous ladder: prefill_chunk's first token, then _decode_once
    "ladder": dict(pipelined=False),
    # the pipelined chain with fused admissions (every benchmark cell)
    "chain": dict(pipelined=True, fused_prefill=True),
    # synchronous prefill, pipelined decode: every admission hands lanes
    # from the chain to the ladder and back with a streamed, uncommitted
    # next_token
    "chain_unfused": dict(pipelined=True, fused_prefill=False),
}
PROMPTS = ("the first prompt", "a second one", "and the third, longer, prompt")


class PieceTokenizer(CharStreamTokenizer):
    """Prompt-dependent tokens in, one letter a token out: deltas and stop
    strings have content to compare. ``eos`` makes one of the mock's tokens
    the end-of-sequence id."""

    def __init__(self, vocab_size=64, eos=None):
        super().__init__(vocab_size)
        if eos is not None:
            self.eos_token_ids = [eos]

    def decode(self, token):
        return chr(ord("a") + int(token) % 26)


def engine_tokens(tokenizer, prompt, n, vocab):
    """The first ``n`` tokens the mock engine generates after ``prompt``, by
    its own token function (content-keyed: whatever lane, whatever path)."""
    oracle = MockAsyncEngine(n_lanes=1, vocab=vocab, content_keyed=True)
    ids = tokenizer.encode(prompt)
    oracle._feed_key(0, ids, 0)
    return [oracle._tok(0, len(ids) - 1 + j) for j in range(n)]


def plain_stream(tokenizer, tokens, stops, max_tokens, padding=(2, 2)):
    """(tokens, deltas, finish_reason) of a stream, written out plainly."""
    eos = EosDetector(tokenizer.eos_token_ids, stops, *padding)
    out, deltas, reason = [], [], None
    for tok in tokens:
        out.append(tok)
        result = eos.append(tok, tokenizer.decode(tok))
        if result == EosResult.EOS:
            reason = "stop"
            break
        if result == EosResult.NOT_EOS:
            if eos.get_delta():
                deltas.append(eos.get_delta())
            eos.reset()
        if len(out) >= max_tokens:
            reason = "length"
            break
    if eos.get_delta():
        deltas.append(eos.get_delta())  # the held-back tail, at the finish
    return out, deltas, reason


def serve(path, specs, tokenizer=None, vocab=64, speculative=False,
          telemetry=None, engine=None, hooks=None):
    """Run ``specs`` (Request keywords) through a scheduler on ``path``: all
    submitted before the loop starts, on two lanes, so the first two are
    admitted synchronously and the third rides the live chain. ``hooks[k]``,
    if given, is called as ``hook(req, sched, n_deltas_so_far)`` inside the
    k-th request's ``on_delta``, on the loop's thread: between the token's
    stream and its commit. Returns (requests, deltas a request, scheduler)."""
    tokenizer = tokenizer or PieceTokenizer(vocab)
    engine = engine or MockAsyncEngine(
        n_lanes=2, vocab=vocab, step_s=0.0005, max_chunk=4,
        speculative=speculative, content_keyed=True)
    sched = ContinuousBatchingScheduler(
        engine, tokenizer, speculative=speculative, multi_step=0,
        prefix_min_tokens=0, telemetry=telemetry or Telemetry(), **PATHS[path])
    reqs, deltas = [], []
    for k, spec in enumerate(specs):
        got = []
        req = Request(**spec)

        def on_delta(d, _got=got, _req=req, _hook=(hooks or {}).get(k)):
            _got.append(d)
            if _hook is not None:
                _hook(_req, sched, len(_got))

        req.on_delta = on_delta
        reqs.append(req)
        deltas.append(got)
        sched.submit(req)
    sched.start()
    try:
        for r in reqs:
            try:
                r.future.result(timeout=60)
            except Exception:  # noqa: BLE001 — a case that fails a request reads req.error
                pass
    finally:
        sched.stop()
    return reqs, deltas, sched


# ---------------------------------------------------------------------------
# stream identity: tokens, deltas, text, finish_reason, against the plain
# reference, on every path
# ---------------------------------------------------------------------------

STREAMS = {
    # name -> (Request keywords, tokenizer keywords, engine vocabulary, speculative)
    "greedy": (dict(max_tokens=24, temperature=0.0), {}, 64, False),
    "sampled": (dict(max_tokens=24, temperature=0.8, seed=11), {}, 64, False),
    "eos_mid_stream": (dict(max_tokens=90, temperature=0.0), dict(eos=10), 64, False),
    "max_tokens_1": (dict(max_tokens=1, temperature=0.0), {}, 64, False),
    "max_tokens_2": (dict(max_tokens=2, temperature=0.0), {}, 64, False),
    # a vocabulary of 16 repeats every 14 positions: the n-gram index drafts
    # and a verify step accepts several tokens at one readback
    "spec_accepts_several": (dict(max_tokens=48, temperature=0.0), {}, 16, True),
    # ... and a stop string of 21 letters, held back from the first token
    # on, completes among the tokens of one accept
    "spec_stop_string": (dict(max_tokens=60, temperature=0.0), {}, 16, True),
}


def stops_of(name, tokenizer, prompt, vocab):
    """The stop strings of the two stop cases, cut from the request's own
    stream: one that is held back as MAYBE_EOS and then completes, one that
    is held back and then does not."""
    if name != "spec_stop_string" and not name.startswith("stop_"):
        return []
    p = [tokenizer.decode(t) for t in engine_tokens(tokenizer, prompt, 21, vocab)]
    if name == "spec_stop_string":
        return ["".join(p)]
    other = "!" if name == "stop_false_alarm" else p[7]
    # (p[3] + "!" never completes: held back at the fourth token, released at the fifth)
    return [p[3] + "!", p[6] + other]


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize(
    "name", sorted(STREAMS) + ["stop_held_back_then_eos", "stop_false_alarm"])
def test_stream_is_the_plain_stream(name, path):
    kw, tok_kw, vocab, speculative = STREAMS.get(
        name, (dict(max_tokens=24, temperature=0.0), {}, 64, False))
    tokenizer = PieceTokenizer(vocab, **tok_kw)
    specs = [dict(kw, prompt=p, stop=stops_of(name, tokenizer, p, vocab))
             for p in PROMPTS]
    reqs, deltas, sched = serve(path, specs, tokenizer, vocab, speculative)
    ended_by_stop = 0
    for req, got, spec in zip(reqs, deltas, specs):
        tokens, want_deltas, reason = plain_stream(
            tokenizer, engine_tokens(tokenizer, spec["prompt"], 100, vocab),
            spec["stop"] or sched._chat_stops.stops, spec["max_tokens"])
        assert req.error is None
        assert req.generated_tokens == tokens
        assert got == want_deltas
        assert req.generated_text == "".join(want_deltas) == req.future.result()
        assert req.finish_reason == reason
        ended_by_stop += reason == "stop"
    if "eos" in name or name == "spec_stop_string":
        assert ended_by_stop == len(reqs)  # the case is what its name says
    if name.startswith("spec_") and path != "ladder":
        stats = sched.engine.stats.snapshot()
        assert stats["spec_emitted"] > stats["spec_lane_steps"] > 0
        assert stats["pipeline_flushes"] == 0
    assert sched.leak_counts() == {k: 0 for k in sched.leak_counts()}


@pytest.mark.parametrize("path", ["ladder", "chain"])
def test_grammar_masked_lane_streams_as_on_the_ladder(path):
    """A constrained lane (the host mirror advances at the readback, inside
    the stream half) beside a plain one: the chain's streams are the
    ladder's, delta for delta, and the constrained text parses."""
    import json

    def run(p):
        tok = ByteJsonTokenizer()
        eng = MockAsyncEngine(n_lanes=2, vocab=258, step_s=0.0005, max_chunk=4,
                              speculative=True, content_keyed=True)
        eng.grammar_init(tok.token_table(), tok.eos_token_ids)
        specs = [
            dict(prompt="user 0 asks", max_tokens=800, seed=3,
                 response_format={"type": "json_object"}),
            dict(prompt="user 1 asks", max_tokens=40, seed=4, temperature=0.7),
            dict(prompt="user 2 asks", max_tokens=800, seed=5, temperature=0.7,
                 response_format={"type": "json_object"}),
        ]
        reqs, deltas, _ = serve(p, specs, tok, 258, True, engine=eng)
        return [(r.generated_tokens, d, r.generated_text, r.finish_reason, r.error)
                for r, d in zip(reqs, deltas)]

    got = run(path)
    assert got == run("chain_unfused")
    for tokens, deltas, text, reason, error in (got[0], got[2]):
        assert error is None and reason == "stop" and text == "".join(deltas)
        json.loads(text)


# ---------------------------------------------------------------------------
# between a token's stream and its commit: cancel, budget expiry, a raising
# on_delta. Nothing is dropped, reordered or streamed twice, and the
# resident-KV map holds only committed tokens
# ---------------------------------------------------------------------------


def _cancel(req, sched, n):
    if n == 5:
        req.cancel()


def _expire(req, sched, n):
    if n == 5:
        req.budget_s = 1e-9  # (0 would mean no budget)


def _raise(req, sched, n):
    if n == 5:
        raise RuntimeError("client went away")


BETWEEN = {
    # name -> (hook inside the 5th delta, finish_reason)
    "cancel": (_cancel, "cancelled"),
    "budget_expiry": (_expire, "timeout"),
    "raising_on_delta": (_raise, "error"),
}


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("name", sorted(BETWEEN))
def test_ending_between_stream_and_commit(name, path):
    hook, reason = BETWEEN[name]
    tokenizer = PieceTokenizer()
    # the THIRD request ends early. It takes lane 0 when the first request
    # frees it (riding the live chain where there is one), beside the second,
    # which must not notice; nothing claims lane 0 after it
    specs = [dict(prompt=p, max_tokens=n, temperature=0.0)
             for p, n in zip(PROMPTS, (12, 40, 20))]
    reqs, deltas, sched = serve(path, specs, tokenizer, hooks={2: hook})
    for k, (req, got, spec) in enumerate(zip(reqs, deltas, specs)):
        tokens, want_deltas, want_reason = plain_stream(
            tokenizer, engine_tokens(tokenizer, spec["prompt"], 100, 64),
            sched._chat_stops.stops, spec["max_tokens"])
        if k != 2:
            assert (req.generated_tokens, got, req.finish_reason, req.error) \
                == (tokens, want_deltas, want_reason, None)
            continue
        # the fifth token was streamed, the request ended before its commit:
        # five tokens, five deltas, no sixth, none twice
        assert req.finish_reason == reason
        assert req.generated_tokens == tokens[:5]
        assert got == want_deltas[:5]
        if reason == "error":
            assert "client went away" in req.error
            assert isinstance(req.future.exception(), RuntimeError)
            assert sched._lane_kv[0] == []  # a failed lane's map is discarded
        else:
            assert req.generated_text == "".join(want_deltas[:5]) == req.future.result()
            # parked: the prompt and the FOUR committed tokens: the fifth
            # was streamed and never committed
            assert sched._lane_kv[0] == tokenizer.encode(spec["prompt"]) + tokens[:4]
    assert sched.leak_counts() == {k: 0 for k in sched.leak_counts()}


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_parked_kv_is_the_committed_tokens(path, paged):
    """A request that runs to its end parks prompt + every generated token
    (its last token commits where the lane is released), as before; the
    paged pool's prefix tree is handed the same list."""
    tokenizer = PieceTokenizer()
    engine = MockAsyncEngine(n_lanes=2, vocab=64, step_s=0.0005, max_chunk=4,
                             content_keyed=True, paged=paged, kv_page_size=4)
    handed = {}
    commit = engine.paged_commit

    def paged_commit(lane, tokens):
        handed[lane] = list(tokens)
        return commit(lane, tokens)

    engine.paged_commit = paged_commit
    specs = [dict(prompt=p, max_tokens=9 + k, temperature=0.0)
             for k, p in enumerate(PROMPTS[:2])]
    reqs, _, sched = serve(path, specs, tokenizer, engine=engine)
    for lane, (req, spec) in enumerate(zip(reqs, specs)):
        want = tokenizer.encode(spec["prompt"]) + engine_tokens(
            tokenizer, spec["prompt"], spec["max_tokens"], 64)
        assert req.finish_reason == "length"
        assert sched._lane_kv[lane] == want
        if paged:
            assert handed[lane] == want


# ---------------------------------------------------------------------------
# the release of a lane did not move, and neither did a dispatch
# ---------------------------------------------------------------------------


class StepLog(Telemetry):
    """Telemetry that keeps the loop's step records in order, and the step
    being streamed when a request's prefill ended, its first token was
    stamped and it finished."""

    def __init__(self):
        super().__init__()
        self.records = []
        self.prefill_done = {}
        self.first_token = {}
        self.finished = {}

    def on_pipelined_step(self, *args, record, **kw):
        self.records.append(record)
        super().on_pipelined_step(*args, record=record, **kw)

    def on_prefill_done(self, req, now):
        self.prefill_done[req.id] = self.records[-1].step if self.records else 0
        super().on_prefill_done(req, now)

    def on_token(self, req, now=None):
        self.first_token.setdefault(req.id, self.records[-1].step if self.records else 0)
        super().on_token(req, now)

    def on_finish(self, req, lane, reason):
        self.finished[req.id] = self.records[-1].step
        super().on_finish(req, lane, reason)


@pytest.mark.parametrize("m", [1, 2, 3, 7])
def test_a_lane_is_held_for_as_many_steps_as_before(m):
    """A request of m tokens that rode the chain: its first token is
    streamed at the readback of the step that carried its last chunk (step
    F), and the lane is released at the readback of step F + m, the step
    that was FED its last token. That is where the parent released it
    (there the step after F emitted the first token, and step F + m the
    m-th): streaming a token earlier did not free a lane earlier."""
    log = StepLog()
    specs = [dict(prompt=PROMPTS[0], max_tokens=40, temperature=0.0),
             dict(prompt=PROMPTS[1], max_tokens=40, temperature=0.0),
             dict(prompt=PROMPTS[2], max_tokens=m, temperature=0.0)]
    reqs, _, _ = serve("chain", specs, telemetry=log)
    third = reqs[2]
    assert third.tel.fused_admitted and len(third.generated_tokens) == m
    assert log.first_token[third.id] == log.prefill_done[third.id]
    assert log.finished[third.id] - log.prefill_done[third.id] == m


def consumed_steps(log):
    out = []
    for r in log.records:
        key = (r.cls, r.lanes, r.chunk, r.final)
        if out and out[-1][0] == key:
            out[-1][1] += 1
        else:
            out.append([key, 1])
    return [(k, n) for k, n in out]


def seeded_run(speculative):
    log = StepLog()
    lengths = (9, 5, 1, 12, 2, 7)
    specs = [dict(prompt=PROMPTS[k % 3] + "." * k, max_tokens=n,
                  temperature=0.0 if k % 2 else 0.6, seed=k)
             for k, n in enumerate(lengths)]
    vocab = 16 if speculative else 64
    reqs, _, sched = serve("chain", specs, vocab=vocab, speculative=speculative,
                           telemetry=log)
    assert [len(r.generated_tokens) for r in reqs] == list(lengths)
    return consumed_steps(log), sched.engine.stats.snapshot()


# (class, live lanes, chunk tokens, final) of every consumed step of
# ``seeded_run``, run-length coded, as the PARENT commit (0fe6ea2) makes them:
# recorded there with these same functions. Timing cannot move it: every
# request is queued before the loop starts.
_D, _F, _S, _SF = "dlstep.decode", "dlstep.fused.b4", "dlstep.spec_pl", "dlstep.spec_fused.b4"
PARENT_STEPS = {
    False: [
        ((_F, 1, 1, True), 1), ((_D, 2, 0, False), 6), ((_F, 1, 4, False), 2),
        ((_F, 0, 4, False), 8), ((_F, 0, 3, True), 1), ((_F, 1, 4, False), 1),
        ((_F, 1, 3, True), 1), ((_D, 2, 0, False), 2), ((_F, 1, 4, False), 3),
        ((_F, 1, 4, True), 1), ((_D, 2, 0, False), 3), ((_F, 1, 4, False), 2),
        ((_F, 0, 4, False), 6), ((_F, 0, 2, True), 1), ((_D, 1, 0, False), 8),
    ],
    True: [
        ((_F, 1, 1, True), 1), ((_D, 2, 0, False), 4), ((_S, 2, 0, False), 1),
        ((_D, 2, 0, False), 1), ((_F, 1, 4, False), 2), ((_F, 0, 4, False), 8),
        ((_F, 0, 3, True), 1), ((_F, 1, 4, False), 1), ((_F, 1, 3, True), 1),
        ((_D, 2, 0, False), 2), ((_SF, 1, 4, False), 1), ((_F, 1, 4, False), 1),
        ((_SF, 1, 4, False), 1), ((_F, 1, 4, True), 1), ((_F, 1, 4, False), 3),
        ((_F, 0, 4, False), 5), ((_F, 0, 2, True), 1), ((_D, 1, 0, False), 4),
        ((_S, 1, 0, False), 1), ((_D, 1, 0, False), 1),
    ],
}


@pytest.mark.parametrize("speculative", [False, True], ids=["plain", "spec"])
def test_dispatches_are_the_parents(speculative):
    steps, stats = seeded_run(speculative)
    assert steps == PARENT_STEPS[speculative]
    assert stats["pipeline_flushes"] == 0


# ---------------------------------------------------------------------------
# a snapshot between a token's stream and its commit resumes without repeating
# or losing it
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path", sorted(PATHS))
def test_ticket_between_stream_and_commit_resumes_exactly(path):
    """The migration ticket's watermark counts STREAMED tokens. A ticket
    exported inside the 5th delta's callback (the 5th token is streamed and
    not committed) says 5; the target regenerates the whole stream from the
    ticket, and what the client had plus the replay past the watermark is the
    stream: the uncommitted token is neither repeated nor lost."""
    tokenizer = PieceTokenizer()
    tickets = []

    def export(req, sched, n):
        if n == 5:
            tickets.append((sched.export_session(req.id),
                            list(req.generated_tokens)))
            req.cancel()  # the source goes away mid-stream

    spec = dict(prompt=PROMPTS[0], max_tokens=20, temperature=0.7, seed=99)
    (src,), (had,), _ = serve(path, [spec], tokenizer, hooks={0: export})
    (ticket, streamed), = tickets
    assert ticket["watermark"] == len(streamed) == len(had) == 5

    target = ContinuousBatchingScheduler(
        MockAsyncEngine(n_lanes=2, vocab=64, step_s=0.0005, max_chunk=4,
                        content_keyed=True),
        tokenizer, speculative=False, multi_step=0, prefix_min_tokens=0,
        **PATHS[path])
    entry = entry_from_admit_record(ticket)
    assert entry.watermark == 5
    replay = target.build_recovered_request(entry)
    replayed = []
    replay.on_delta = replayed.append
    target.submit(replay)
    target.start()
    try:
        replay.future.result(timeout=60)
    finally:
        target.stop()
    tokens, deltas, reason = plain_stream(
        tokenizer, engine_tokens(tokenizer, spec["prompt"], 100, 64),
        target._chat_stops.stops, spec["max_tokens"])
    assert (replay.generated_tokens, replayed, replay.finish_reason) == (tokens, deltas, reason)
    assert streamed + replay.generated_tokens[entry.watermark:] == tokens
    assert had + replayed[entry.watermark:] == deltas
