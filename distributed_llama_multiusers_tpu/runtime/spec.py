"""Prompt-lookup draft index for speculative decoding.

Drafts come from the token stream itself: the previous occurrence of the
current suffix n-gram (3-gram, falling back to 2-gram) proposes the tokens
that followed it — no draft model. The index is maintained incrementally
(each committed token updates two dict entries), so a draft probe is O(1)
per step instead of a backward history scan.

Used by the continuous-batching scheduler (per lane) and the CLI inference
loop (single stream). The engine's verify program
(`InferenceEngine.decode_spec`) guarantees the speculative-verification
identity: greedy output streams are exactly the plain-decode streams.
"""

from __future__ import annotations

# drafts per speculative step (K = SPEC_DRAFT + 1 verified tokens); shared
# by the engine's verify program and the control plane's packet sizing
SPEC_DRAFT = 3


def pow2_floor(h: int) -> int:
    """Largest power of two <= h (0 for h < 1). The ONE bucketing rule for
    multi-step horizons: every dispatch site must land on these buckets so
    warmup_engine's compiled program is the one the serving loop uses."""
    return 1 << (h.bit_length() - 1) if h >= 1 else 0


class NgramDraftIndex:
    """Committed token history + n-gram -> last-start-position index."""

    GRAM_SIZES = (2, 3)

    def __init__(self, tokens=()):
        self.hist: list[int] = []
        self._last: dict = {}
        for t in tokens:
            self.append(t)

    def append(self, tok: int) -> None:
        self.hist.append(tok)
        for g in self.GRAM_SIZES:
            if len(self.hist) >= g:
                self._last[(g, tuple(self.hist[-g:]))] = len(self.hist) - g

    def draft(self, next_token: int, k: int) -> list[int]:
        """Up to k draft tokens continuing (hist + [next_token]). Each probe
        gram ends at a not-yet-committed token, so a hit is always a
        strictly earlier occurrence; the draft EXTENDS by re-probing over
        the virtual tail (hist + next_token + tokens drafted so far), so a
        short-period stream — whose last occurrence sits right at the end
        of history and offers at most period-1 continuation tokens in one
        lookup — still drafts the full k. (The pipelined chain needs this:
        its carry-alignment gate spends one candidate, so single-token
        probes could never accelerate a period-2 stream.)"""
        hist = self.hist
        nh = len(hist)
        # the VIRTUAL region: next_token + tokens drafted so far. Indexing
        # spans (hist ++ virt) WITHOUT copying the history — the probe is
        # O(k·gram), not O(history), and it runs per lane per dispatch.
        virt = [next_token]

        def at(i: int) -> int:
            return hist[i] if i < nh else virt[i - nh]

        # transient index over grams ending strictly before the current
        # tail (so a probe can never match itself): a period-p stream's
        # only earlier occurrence sits p tokens back, which is inside the
        # virtual region after the first few drafts
        overlay: dict = {}
        gmax = sorted(self.GRAM_SIZES, reverse=True)
        while len(virt) <= k:
            total = nh + len(virt)
            nxt = None
            for g in gmax:
                if total < g:
                    continue
                tail = tuple(at(total - g + j) for j in range(g))
                j = overlay.get((g, tail))
                if j is None:
                    j = self._last.get((g, tail))
                if j is not None and j + g < total:
                    nxt = at(j + g)
                    break
            if nxt is None:
                break
            # the tail's own grams become legal matches once a token
            # follows them — record them before appending
            for g in self.GRAM_SIZES:
                if total >= g:
                    overlay[(g, tuple(at(total - g + j) for j in range(g)))] = (
                        total - g
                    )
            virt.append(nxt)
        return virt[1:]


class SpecStream:
    """Single-stream speculative decode for the CLIs (inference AND chat):
    prompt-lookup drafts plus a pending-lookahead buffer, so greedy runs
    emit >1 token per forward when drafts hit while keeping the exact
    plain-decode token stream (speculative-verification identity).

    Per-stream analogue of the scheduler's per-lane spec path; near
    seq_len the draft length is clamped to the slots left (the cache
    scatter drops overshooting writes — models/llama.py KV append)."""

    def __init__(self, engine, config, enabled: bool, prompt_tokens=(),
                 multi_h: int = 0):
        """``multi_h`` > 1 enables the multi-step fallback for GREEDY
        streams: when no draft hits, chain up to that many decode steps in
        one device dispatch (engine.decode_multi) and serve the chained
        tokens from the same pending-lookahead buffer drafts use — one
        host round-trip per horizon instead of per token. Temperature>0
        callers must leave it 0 (they sample from last_logits every
        step)."""
        import numpy as np

        self.engine = engine
        self.config = config
        self.spec_k = getattr(engine, "SPEC_DRAFT", 0)
        self.enabled = (
            enabled
            and self.spec_k > 0
            and getattr(engine, "supports_speculative", False)
        )
        self.drafter = NgramDraftIndex(prompt_tokens) if self.enabled else None
        self.multi_h = (
            multi_h
            if multi_h > 1 and getattr(engine, "supports_multi_step", False)
            else 0
        )
        self.pending: list[int] = []  # produced-but-not-yet-emitted lookahead
        # whether `pending` came from a spec verify (counts toward the
        # speculation acceptance stats) or a multi-step horizon (must not)
        self._pending_spec = False
        # tokens already consumed from the CURRENT spec lookahead's verify
        # step (seq[0] counts at verify time): discard_pending() needs it
        # to retract a partially consumed step from the acceptance math
        self._pending_consumed = 0
        self._toks = np.zeros(engine.n_lanes, np.int32)
        self._poss = np.zeros(engine.n_lanes, np.int32)
        self.last_logits = None  # batch logits of the last real forward

    def extend_history(self, tokens) -> None:
        """Feed non-generated tokens (chat-turn prompts) to the draft index."""
        if self.drafter is not None:
            for t in tokens:
                self.drafter.append(int(t))

    def discard_pending(self) -> None:
        """Drop the unconsumed lookahead at a turn boundary (chat mode:
        spec tokens drafted past EOS are uncommitted cache scribble the
        next prefill overwrites — but the HOST-side buffer must go).

        Accounting: a spec verify whose lookahead is only PARTIALLY
        consumed is RETRACTED from the acceptance counters
        (``spec_lane_steps`` / ``spec_emitted``), not left dangling — the
        /stats acceptance ratio (emitted per drafted lane-step, class
        [1, K+1]) aggregates only fully realized steps, so a turn ending
        mid-lookahead can neither deflate it nor strand a lane-step whose
        emitted count no longer means anything. Counters never go below 0
        (a stats window reset between verify and discard clamps)."""
        if self.pending and self._pending_spec:
            stats = getattr(self.engine, "stats", None)
            if stats is not None:
                with stats.lock:
                    stats.spec_lane_steps = max(0, stats.spec_lane_steps - 1)
                    stats.spec_emitted = max(
                        0, stats.spec_emitted - self._pending_consumed
                    )
        self.pending.clear()
        self._pending_spec = False
        self._pending_consumed = 0

    def flush_pipeline(self) -> None:
        """Flush any live async-decode chain before a direct engine call:
        SpecStream's spec/multi/plain steps thread the same KV cache, and a
        device-fed chain still in flight would keep feeding tokens from a
        history this stream has moved past. No-op on engines without the
        pipelined family or with nothing in flight."""
        if getattr(self.engine, "pipeline_active", False):
            self.engine.pipeline_flush()

    def advance(self, cur: int, pos: int):
        """Commit ``cur`` at ``pos`` and return ``(next_token, used_forward)``.
        used_forward=False means the token came from the pending lookahead
        (its cache write already happened in the spec step that drafted it).
        For temperature>0 callers (spec disabled), sample from
        ``last_logits`` instead of the returned greedy token."""
        import numpy as np

        if self.pending:
            if self.drafter is not None:
                self.drafter.append(cur)
            stats = getattr(self.engine, "stats", None)
            if stats is not None and self._pending_spec:
                with stats.lock:
                    stats.spec_emitted += 1  # lookahead token consumed NOW
                self._pending_consumed += 1
            return self.pending.pop(0), False
        self.flush_pipeline()  # about to touch the engine directly
        draft: list[int] = []
        if self.drafter is not None:
            d_max = min(self.spec_k, self.config.seq_len - pos - 1)
            if d_max > 0:
                draft = self.drafter.draft(cur, self.spec_k)[:d_max]
            self.drafter.append(cur)
        self._toks[0] = cur
        self._poss[0] = pos
        if draft:
            drafts = np.zeros((self.engine.n_lanes, self.spec_k), np.int32)
            dlen = np.zeros(self.engine.n_lanes, np.int32)
            drafts[0, : len(draft)] = draft
            dlen[0] = len(draft)
            _, em, ne = self.engine.decode_spec(
                self._toks, drafts, dlen, self._poss
            )
            seq = [int(t) for t in em[0, : int(ne[0])]]
            self.pending = seq[1:]
            self._pending_spec = True
            self._pending_consumed = 1  # seq[0] is consumed below
            # consumed-only accounting, same semantics as the scheduler's
            # loop: the tokens still in `pending` count when popped (and
            # never count if a turn ends and discards them)
            stats = getattr(self.engine, "stats", None)
            if stats is not None:
                with stats.lock:
                    stats.spec_lane_steps += 1
                    stats.spec_emitted += 1  # seq[0], consumed now
            return seq[0], True
        if self.multi_h > 1:
            # no draft: chain a horizon of plain decode steps instead of
            # one. KV alignment matches the spec path: the scan feeds
            # cur, chosen[0..h-2] at pos..pos+h-1 (all written); the last
            # chosen token is fed by a later advance() forward.
            p = pow2_floor(min(self.multi_h, self.config.seq_len - pos))
            if p > 1:
                chosen = self.engine.decode_multi(self._toks, self._poss, h=p)
                seq = [int(chosen[j, 0]) for j in range(p)]
                self.pending = seq[1:]
                self._pending_spec = False
                return seq[0], True
        logits_b, greedy_b, _ = self.engine.decode(self._toks, self._poss)
        self.last_logits = logits_b
        return int(greedy_b[0]), True
