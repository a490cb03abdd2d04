"""Test/dev-environment helpers.

Multi-chip behavior is validated on a virtual CPU device mesh, the TPU
analogue of the reference's fake-synchronizer + local-process-cluster test
strategy (src/nn/nn-executor.cpp:6-8, examples/n-workers.sh): the same GSPMD
partitioner and collectives run, just over host devices.
"""

from __future__ import annotations

import os
import sys
import time


def force_cpu_mesh(n_devices: int = 8) -> None:
    """Force JAX onto `n_devices` virtual CPU devices
    (xla_force_host_platform_device_count makes one host look like a mesh).
    Call BEFORE any jax backend is initialized."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    import jax

    # jax may have been imported (and read JAX_PLATFORMS) before us
    jax.config.update("jax_platforms", "cpu")


class StubStreamTokenizer:
    """Minimal stream-decoder tokenizer for scheduler-only harnesses (the
    measurement/assertion is the scheduler loop, not BPE). EOS id =
    vocab_size, never produced, so requests run to max_tokens."""

    class _Vocab:  # TokenizerChatStops renders eos pieces from .vocab
        def __getitem__(self, i) -> bytes:
            return b"</s>"

    def __init__(self, vocab_size: int = 64, prompt_tokens: int = 8):
        self.vocab_size = vocab_size
        self.prompt_tokens = prompt_tokens
        self.eos_token_ids = [vocab_size]
        self.chat_template = None
        self.bos_id = 1
        self.vocab = self._Vocab()

    def encode(self, text, add_bos=True, add_special_tokens=True):
        n = max(1, min(len(text), self.prompt_tokens))
        return [(7 + i) % self.vocab_size for i in range(n)]

    def make_stream_decoder(self):
        return self

    def decode(self, token):  # stream-decoder protocol
        return "x"


class ByteJsonTokenizer(StubStreamTokenizer):
    """Byte-level tokenizer for grammar-constrained harnesses: id 0 =
    BOS (special), ids 1..256 = the raw bytes 0..255, id 257 = EOS —
    every byte is a token, so the grammar automaton's token closure is
    the character machine itself and constrained mock streams decode to
    REAL text the tests can ``json.loads``. ``token_table()`` feeds
    ``engine.grammar_init`` (None for the specials, bytes elsewhere)."""

    def __init__(self):
        super().__init__(vocab_size=258)
        self.eos_token_ids = [257]
        self.bos_id = 0
        # a recognizable template marker so ApiServer's chat route works
        # against this tokenizer (rendered text is plain bytes anyway)
        self.chat_template = "[INST]"

    def token_table(self):
        return [None] + [bytes([i]) for i in range(256)] + [None]

    def encode(self, text, add_bos=True, add_special_tokens=True):
        data = text.encode("utf-8", errors="replace") or b"?"
        out = [0] if add_bos else []
        return out + [1 + b for b in data]

    def decode(self, token):  # stream-decoder protocol
        # BOS/EOS yield nothing; ids past the byte range (model vocab
        # padding an UNCONSTRAINED lane can sample) render as nothing
        # too — only grammar-masked lanes are guaranteed in-range
        if not 1 <= int(token) <= 256:
            return None
        # latin-1 keeps the byte value verbatim, so the concatenated
        # stream text reconstructs the constrained byte stream exactly
        return bytes([int(token) - 1]).decode("latin-1")


class CharStreamTokenizer(StubStreamTokenizer):
    """Char-level, prompt-DEPENDENT encoding for prefix-sharing
    harnesses: shared text prefixes become shared token prefixes exactly
    as long as they are (the base stub maps every prompt to the same
    tokens, which would make any prefix probe a trivial full-prompt
    hit). One home for tests/test_prefix_cache.py, tests/test_fleet.py
    and tests/test_disagg.py, so the byte-identity tests pin one
    encoding. ``max_chars`` caps the prompt length in tokens (None =
    unbounded)."""

    def __init__(self, vocab_size: int = 64, max_chars: int | None = None):
        super().__init__(vocab_size)
        self.max_chars = max_chars

    def encode(self, text, add_bos=True, add_special_tokens=True):
        if self.max_chars is not None:
            text = text[: self.max_chars]
        return [2 + ord(c) % (self.vocab_size - 2) for c in text]


class MockAsyncEngine:
    """Engine stub modelling an ASYNC device for scheduler pipeline
    tests: dispatch is free and advances a simulated
    device busy-until timeline, consume blocks until the simulated step
    completes. The scheduler's pipelined loop runs against it unmodified,
    so the ``events`` log proves the lag structure (consume of step k runs
    while step k+1 is already dispatched) without accelerator timing noise.
    (tests/test_pipelined_decode.py holds that lag to it).

    Tokens are a pure function of (lane, position) — NOT of global step
    order — so the synchronous scheduler and the pipelined/fused one emit
    byte-identical streams for the same requests regardless of how
    admissions interleave: the property the fused-prefill churn tests pin.
    Supports the fused prefill+decode dispatch (``decode_prefill_fused``)
    with the real engine's packed-readback contract (an extra boundary
    column on fused steps).

    Carries the real engine's fault-injection hooks (utils/faults.py:
    ``engine.dispatch`` / ``engine.consume``) and its ``pipeline_abort``
    containment primitive, so the chaos suite (tests/test_failures.py)
    drives the supervised scheduler loop through deterministic failures
    without accelerator timing noise."""

    supports_multi_step = False
    supports_speculative = False
    supports_pipelined = True
    supports_fused_prefill = True
    supports_spec_pipelined = False
    SPEC_DRAFT = 3

    def __init__(self, n_lanes=4, vocab=64, seq_len=4096, step_s=0.002,
                 pipeline_depth=2, max_chunk=16, speculative=False,
                 content_keyed=False, paged=False, kv_page_size=16,
                 kv_pool_pages=None, kv_max_parked=8, kv_host_bytes=0):
        """``speculative=True`` opts this instance into the speculative
        families (``decode_spec`` + the in-chain
        ``decode_spec_pipelined`` / ``decode_spec_prefill_fused``),
        mirroring the real engine's verify semantics over the
        deterministic f(lane, pos) token function — drafts genuinely
        accept whenever the scheduler's n-gram index predicts the
        stream's own periodicity, so zero-flush speculation is testable
        without accelerator noise. Off by default: pre-existing mock
        tests pin non-speculative behavior.

        ``content_keyed=True`` makes tokens a pure function of
        (PROMPT CONTENT, position) instead of (lane, position): each
        prefill folds its chunk into a per-lane stream key, so the same
        request produces the same stream regardless of which lane it
        lands on. That is the real engine's replay-determinism class
        (sampling is per (seed, pos), greedy is per (model, prompt) —
        never per lane), which the crash-recovery chaos tests pin: a
        recovered request re-admitted onto a DIFFERENT lane must still
        regenerate byte-identically.

        ``paged=True`` mirrors the real engine's paged-KV contract
        (runtime/kvpool.py — a pure-host module, so no jax is needed):
        ``kvpool`` + ``paged_admit``/``paged_commit``/``paged_finish``/
        ``paged_reset``/``pool_stats`` drive the REAL pool bookkeeping
        (free list, refcounts, prefix tree, parking, exhaustion sheds)
        and maintain the host page-table mirror; the only thing mocked
        is the device half (table writes land in a numpy array, COW
        copies just count). Combined with ``content_keyed``, a shared
        prefix served by refcount reproduces the stream prefilling it
        would have produced: ``paged_admit`` folds the SKIPPED prefix's
        content into the lane stream key, so scheduler-level
        oversubscription tests assert byte-identity without a backend."""
        import numpy as np
        import types

        from ..runtime.engine import EngineStats

        self.n_lanes = n_lanes
        self.config = types.SimpleNamespace(seq_len=seq_len, vocab_size=vocab)
        self.stats = EngineStats()
        self.pipeline_depth = pipeline_depth
        self.step_s = step_s
        self._max_chunk = max_chunk
        self.supports_speculative = speculative
        self.supports_spec_pipelined = speculative
        self._content_keyed = content_keyed
        self._lane_key = np.zeros(n_lanes, np.int64)
        self._free_at = 0.0  # simulated device busy-until timestamp
        # None: pipeline_ready() goes by the simulated clock; True / False:
        # the answer a test wants (the dry-dispatch witness, step by step)
        self.ready_override = None
        # (ready_at, dispatched_at, step_idx, kind, payload): payload is
        # (toks, boundary|None) for "tok" steps, (emitted, n_emit) for
        # "spec" steps — computed AT DISPATCH (the sim is deterministic),
        # returned at consume like the real engine's lagged readback
        self._ring = []
        self._carry_live = False
        # simulated device carry: each lane's next feed token + write
        # position (the real engine's _pl_carry/_pl_carry_pos); a host
        # position >= 0 overrides, -1 reads the carry — same contract.
        # _sim_g is the grammar-state carry (absolute slab id, 0 = FREE)
        self._sim_tok = np.zeros(n_lanes, np.int64)
        self._sim_pos = np.zeros(n_lanes, np.int64)
        self._sim_g = np.zeros(n_lanes, np.int64)
        # grammar-constrained decoding: the REAL slab + compiler (pure
        # numpy — no jax needed); the mocked device half is the masked
        # token choice in _tok_g
        self.grammar_slab = None
        self._g_vocab = None
        self._g_eos = ()
        self._steps = 0
        self.events = []  # ("dispatch"|"consume", step_idx)
        # paged KV mirror (the real engine's host half, device half mocked)
        self.kvpool = None
        if paged:
            from ..runtime.kvpool import KVPagePool

            # the REAL engine's construction recipe (validation, shrink,
            # footprint default) — shared classmethod, so the mock's
            # pool geometry provably cannot drift from the engine's
            self.kvpool = KVPagePool.for_seq_len(
                seq_len, n_lanes, page_size=kv_page_size,
                pool_pages=kv_pool_pages, max_parked=kv_max_parked,
                host_bytes=kv_host_bytes,
            )
            self._host_tables = np.asarray(
                [self.kvpool.table_row([])] * n_lanes, np.int32
            )
            self.page_copies_applied = 0  # the mocked device COW half
            # tiered residency (host swap tier): the engine's traffic
            # counters, fed by the mocked device halves below
            self.swap_ins = 0
            self.swap_outs = 0
            self.swap_in_bytes = 0
            self.swap_out_bytes = 0
            self.swap_in_ms = 0.0
            # disagg transfer mock: imported payloads keyed by page, each
            # pinned to the tree node it was imported FOR (a reused page
            # re-registered with different content falls back to the
            # canonical derivation instead of replaying stale bytes)
            self._page_payloads = {}
            self.pages_imported = 0

    def max_chunk(self, start=0):
        return self._max_chunk

    def bucket_for(self, n):
        # one bucket: every chunk rides the largest (fused_bucket_hist's key)
        return self._max_chunk

    # -- grammar-constrained decoding (grammar/; REAL slab + compiler) -----

    @property
    def supports_grammar(self):
        return self._g_vocab is not None

    def grammar_init(self, token_table, eos_ids):
        from ..grammar.slab import GrammarSlab

        table = list(token_table)[: self.config.vocab_size]
        table += [None] * (self.config.vocab_size - len(table))
        self._g_vocab = table
        self._g_eos = tuple(int(e) for e in eos_ids)
        self.grammar_slab = GrammarSlab(self.config.vocab_size)

    def grammar_attach(self, rf):
        if self._g_vocab is None:
            raise ValueError(
                "structured output is disabled on this engine "
                "(--grammar off, or no tokenizer vocab registered)"
            )
        from ..grammar.automaton import compile_automaton

        auto = compile_automaton(rf, self._g_vocab, self._g_eos)
        handle = self.grammar_slab.attach(auto)
        with self.stats.lock:
            self.stats.grammar_lanes += 1
        return handle

    def grammar_detach(self, key):
        self.grammar_slab.detach(key)

    def grammar_stats(self):
        return (
            self.grammar_slab.stats() if self.grammar_slab is not None
            else {}
        )

    def _g_next_abs(self, g, tok):
        """Absolute-state transition (the device rule's mock twin)."""
        if g <= 0 or self.grammar_slab is None:
            return 0
        got = self.grammar_slab.resolve(int(g))
        if got is None:
            return 0
        auto, base = got
        return base + auto.next_state(int(g) - base, int(tok))

    def _tok_g(self, lane, pos, g):
        """The masked token choice: the deterministic base token function
        picks WHICH legal token (mod the legal count), so constrained
        streams stay pure functions of (content key, position) — the
        replay-determinism class — while always being grammar-legal
        (the real engine's masked-argmax analogue)."""
        t = self._tok(lane, pos)
        if g is None or g <= 0 or self.grammar_slab is None:
            return t
        got = self.grammar_slab.resolve(int(g))
        if got is None:
            return t
        auto, base = got
        legal = auto.legal_ids(int(g) - base)
        # choose among the LOWEST legal ids (structural bytes sort low):
        # a real model's masked argmax terminates values promptly; an
        # unbiased pick over ~250 legal string bytes would close a quote
        # once per ~250 tokens and every mock stream would hit max_tokens.
        # The index mixes (t, pos) NON-linearly: the raw token function is
        # linear in pos mod 256, and a linear pick resonates with
        # multi-token loop bodies (an array that never draws ']' runs to
        # max_tokens deterministically).
        cap = min(len(legal), 12)
        h = (t * 2654435761 + int(pos) * 0x9E3779B1) & 0xFFFFFFFF
        h ^= h >> 13
        return int(legal[h % cap])

    def _eff_g(self, g_states, reseed=False):
        """The grammar-state select: None defaults like the real engine
        (FREE on reseed, carry otherwise); -1 reads the simulated carry,
        >= 0 overrides."""
        n = self.n_lanes
        if g_states is None:
            if reseed:
                return [0] * n
            return [int(x) for x in self._sim_g]
        return [
            int(self._sim_g[i]) if int(g) < 0 else int(g)
            for i, g in enumerate(g_states)
        ]

    # -- paged KV (runtime/kvpool.py contract; device half mocked) ---------

    def paged_admit(self, lane, tokens, reserve_tokens,
                    min_share_tokens=1):
        """The real engine's paged admission over the REAL pool
        bookkeeping; raises the real :class:`~..runtime.kvpool.PoolExhausted`.
        The device half is a numpy table write + a COW counter bump; the
        tiered-residency ordering matches the engine's (drain staged
        swap-outs, apply host-tier swap-ins, then the table write)."""
        start, blocks, copies, swapins = self.kvpool.admit(
            lane, list(tokens), reserve_tokens, min_share_tokens
        )
        self.drain_kv_swapouts()
        if swapins:
            self.swap_in_pages([p for p, _ in swapins],
                               [b for _, b in swapins])
        self._host_tables[int(lane)] = self.kvpool.table_row(blocks)
        self.page_copies_applied += len(copies)
        if self._content_keyed and start > 0:
            # the shared prefix's KV is resident: fold its CONTENT into
            # the lane stream key exactly as prefilling it would have, so
            # a refcount-served prefix and a prefilled one are stream-
            # indistinguishable (the byte-identity property under test)
            self._lane_key[int(lane)] = 0
            self._feed_key(lane, list(tokens[:start]), 0)
        return start

    def _paged_table_row(self, blocks):
        """The pod control plane's table-row hook (mirrors the real
        engine): the pool's shared encoding as the int32 wire dtype."""
        import numpy as np
        return np.asarray(self.kvpool.table_row(list(blocks)), np.int32)

    def apply_paged_admit(self, lane, row, copies):
        """Device half of a pod admission replay on the mock: land the
        table row and apply COW copies to the payload shadow."""
        for src, dst in copies:
            got = self._page_payloads.get(int(src))
            if got is not None:
                self._page_payloads[int(dst)] = got
        self._host_tables[int(lane)] = row
        self.page_copies_applied += len(copies)

    def paged_commit(self, lane, tokens):
        self.kvpool.commit(lane, list(tokens))

    def paged_finish(self, lane, park=True):
        held = self.kvpool.finish(lane, park=park)
        self.drain_kv_swapouts()
        if held:
            self._host_tables[int(lane)] = self.kvpool.table_row([])

    def paged_reset(self):
        self.kvpool.reset()
        self._host_tables[:] = self.kvpool.table_row([])

    def pool_stats(self):
        if self.kvpool is None:
            return {}
        stats = self.kvpool.stats()
        stats["swap_ins"] = int(self.swap_ins)
        stats["swap_outs"] = int(self.swap_outs)
        stats["swap_in_bytes"] = int(self.swap_in_bytes)
        stats["swap_out_bytes"] = int(self.swap_out_bytes)
        stats["swap_in_ms"] = round(float(self.swap_in_ms), 3)
        return stats

    # -- host swap tier (runtime/engine.py contract; device half mocked) ---

    def drain_kv_swapouts(self):
        """Mocked device half of a swap-out drain: the 'device read' is
        the content-canonical payload rule shared with export_kv_page —
        imported bytes replay if the page still backs the staged node,
        otherwise the payload is the pure function of the node key. Same
        pool-side bookkeeping (take_pending_swapouts -> tier.put) as the
        real engine, so leak witnesses and tier stats are exercised."""
        import hashlib

        if self.kvpool is None or not self.kvpool.host_tier.enabled:
            return 0
        pending = self.kvpool.take_pending_swapouts()
        stored = 0
        for node_key, blk_tokens, page in pending:
            got = self._page_payloads.get(int(page))
            if got is not None and got[0] == node_key:
                payload = got[1]
            else:
                payload = hashlib.sha256(
                    repr(node_key).encode("utf-8")
                ).digest() * 2
            if self.kvpool.host_tier.put(node_key, blk_tokens, payload):
                stored += 1
            self.swap_outs += 1
            self.swap_out_bytes += len(payload)
        return stored

    def swap_in_pages(self, pages, payloads):
        """Mocked device half of a batched host->device swap-in: record
        each payload against the node its page now backs (admit()
        registered the chain just before this call), so a later export
        or re-swap-out round-trips the exact bytes."""
        if self.kvpool is None:
            raise RuntimeError("swap_in_pages needs a paged engine")
        if len(pages) != len(payloads):
            raise ValueError(
                f"swap_in_pages: {len(pages)} pages vs "
                f"{len(payloads)} payloads"
            )
        for page, payload in zip(pages, payloads):
            self._page_payloads[int(page)] = (
                self.kvpool.page_key(int(page)), bytes(payload)
            )
            self.swap_ins += 1
            self.swap_in_bytes += len(payload)

    def swap_out_parked(self):
        """Evict every parked chain into the host tier (tests' lever)."""
        if self.kvpool is None:
            return 0
        n = self.kvpool.swap_out_parked()
        self.drain_kv_swapouts()
        return n

    def reset_swap_stats(self):
        self.swap_ins = 0
        self.swap_outs = 0
        self.swap_in_bytes = 0
        self.swap_out_bytes = 0
        self.swap_in_ms = 0.0

    def _page_leaf_geometry(self):
        """One page's K (or V) leaf geometry under the mock's content-
        canonical payload convention: each half is the 32-byte sha256
        digest, so every canonical payload is exactly 2 * half — the
        same contract RootControlEngine's pre-broadcast validation
        checks on the real engine."""
        import numpy as np

        return (8,), np.dtype(np.float32)

    def export_kv_page(self, page):
        """The real engine's disagg export, mocked content-canonically:
        a committed page's payload is a pure function of its block-
        content chain (sha256 of the tree node key), so two replicas
        that committed the same prefix export IDENTICAL bytes and the
        kvtransfer integrity hashes are genuinely exercised end to end.
        Imported pages replay the imported bytes (round-trip fidelity),
        as long as the page still backs the node it was imported for."""
        import hashlib

        if self.kvpool is None:
            raise RuntimeError("export_kv_page needs a paged engine")
        key = self.kvpool.page_key(int(page))
        if key is None:
            raise ValueError(
                f"page {int(page)} backs no committed block — only "
                "immutable full blocks cross replicas"
            )
        got = self._page_payloads.get(int(page))
        if got is not None and got[0] == key:
            return got[1]
        return hashlib.sha256(repr(key).encode("utf-8")).digest() * 2

    def import_kv_page(self, page, payload):
        """Mocked device half of a page import: record the bytes against
        the node the page currently backs (adopt() registered it just
        before this call — the same ordering the real engine gets from
        the donated cache pytree)."""
        if self.kvpool is None:
            raise RuntimeError("import_kv_page needs a paged engine")
        self._page_payloads[int(page)] = (
            self.kvpool.page_key(int(page)), bytes(payload)
        )
        self.pages_imported += 1

    def reset_lane(self, lane):
        pass

    def _tok(self, lane, pos):
        # deterministic per (lane, position) — or per (prompt-content
        # key, position) in content_keyed mode: stream identity across
        # scheduler paths / lane placements is checkable by equality.
        # The keyed multiplier is 13, coprime to every small even
        # vocab-2 modulus (31 shares a factor with the default 62 and
        # would collapse the key to its parity).
        if self._content_keyed:
            key = int(self._lane_key[int(lane)])
            return 2 + (key * 13 + int(pos) * 7) % (self.config.vocab_size - 2)
        return 2 + (int(lane) * 31 + int(pos) * 7) % (self.config.vocab_size - 2)

    def _feed_key(self, lane, chunk, start_pos):
        """content_keyed mode: fold a prefill chunk into the lane's
        stream key (reset at a fresh prompt's first chunk), so the token
        function depends on WHAT was prefilled, not WHERE."""
        if not self._content_keyed:
            return
        k = 0 if start_pos == 0 else int(self._lane_key[int(lane)])
        for t in chunk:
            k = (k * 1000003 + int(t) + 1) & 0xFFFFFFFF
        self._lane_key[int(lane)] = k

    def prefill_chunk(self, lane, chunk, start_pos, temp=0.0, topp=0.9,
                      seed=0, g_state=0):
        from . import faults

        faults.fire("engine.dispatch")
        self._feed_key(lane, chunk, start_pos)
        # boundary token under the automaton's start-state mask (the
        # real engine's _prefill_half rule; g_state 0 = identity)
        t = self._tok_g(lane, start_pos + len(chunk) - 1, g_state)
        with self.stats.lock:
            self.stats.prefill_tokens += len(chunk)
            self.stats.prefill_bucket_rows += self._max_chunk
        return None, t, t

    def _toks_at(self, positions, g_states=None):
        import numpy as np

        return np.asarray(
            [
                self._tok_g(
                    i, positions[i],
                    0 if g_states is None else int(g_states[i]),
                )
                for i in range(self.n_lanes)
            ],
            np.int32,
        )

    def decode(self, tokens, positions, temps=None, topps=None, seeds=None,
               want_logits=True, g_states=None):
        from . import faults

        faults.fire("engine.dispatch")
        # synchronous fallback (admission iterations): dispatch + block
        now = time.monotonic()
        self._free_at = max(now, self._free_at) + self.step_s
        time.sleep(max(0.0, self._free_at - now))
        self._steps += 1
        with self.stats.lock:
            self.stats.decode_steps += 1
        t = self._toks_at(positions, g_states)
        return None, t, t

    def decode_spec(self, tokens, drafts, draft_len, positions, temps=None,
                    topps=None, seeds=None, g_states=None):
        """Synchronous speculative verify over the deterministic token
        function: the real engine's acceptance rule (longest draft prefix
        matching the model's own continuation) with greedy_j =
        f(lane, pos + j) — masked per position for constrained lanes."""
        import numpy as np

        from . import faults

        faults.fire("engine.dispatch")
        now = time.monotonic()
        self._free_at = max(now, self._free_at) + self.step_s
        time.sleep(max(0.0, self._free_at - now))
        self._steps += 1
        emitted, n_emit, _ = self._verify(
            np.asarray(tokens), np.asarray(drafts), np.asarray(draft_len),
            np.asarray(positions),
            None if g_states is None else [int(g) for g in g_states],
        )
        with self.stats.lock:
            self.stats.decode_steps += 1
            self.stats.spec_steps += 1
        return None, emitted, n_emit

    def _verify(self, tokens, drafts, draft_len, positions, g0=None):
        """The acceptance math shared by the sync and in-chain verify
        mocks. drafts here are the K continuation candidates (the real
        ``decode_spec`` layout). The grammar state walks the window
        exactly like the real verify core: each position's greedy is the
        MASKED choice under the state reached by the accepted prefix.
        Returns (emitted, n_emit, g_final) with g_final the per-lane
        state after the last emitted token."""
        import numpy as np

        n = self.n_lanes
        k = drafts.shape[1]
        emitted = np.zeros((n, k + 1), np.int64)
        n_emit = np.ones(n, np.int64)
        g_final = np.zeros(n, np.int64)
        seq_len = self.config.seq_len
        for i in range(n):
            pos = int(positions[i])
            g = 0 if g0 is None else int(g0[i])
            dlen = min(int(draft_len[i]), max(0, seq_len - pos - 1), k)
            j = 0
            while True:
                t = self._tok_g(i, pos + j, g)
                emitted[i, j] = t
                g = self._g_next_abs(g, t)
                if j < dlen and int(drafts[i, j]) == t:
                    j += 1
                    continue
                break
            n_emit[i] = j + 1
            g_final[i] = g
        return emitted, n_emit, g_final

    def pipeline_inflight(self):
        return len(self._ring)

    def pipeline_ready(self):
        """The real engine's poll: has the device finished everything in
        flight? The simulated clock answers; a test that wants the device
        dry (or busy) whatever the clock says sets ``ready_override``."""
        if not self._ring:
            return False
        if self.ready_override is not None:
            return self.ready_override
        return time.monotonic() >= self._ring[-1][0]

    @property
    def pipeline_active(self):
        return bool(self._ring) or self._carry_live

    def _eff_positions(self, positions):
        """The carried-position select: -1 reads the simulated device
        carry, >= 0 overrides from host metadata."""
        return [
            int(self._sim_pos[i]) if int(p) < 0 else int(p)
            for i, p in enumerate(positions)
        ]

    def _push(self, kind, payload):
        now = time.monotonic()
        self._free_at = max(now, self._free_at) + self.step_s
        s = self._steps
        self._steps += 1
        self._ring.append((self._free_at, now, s, kind, payload))
        self._carry_live = True
        self.events.append(("dispatch", s))
        with self.stats.lock:
            self.stats.pipeline_dispatches += 1
            d = len(self._ring)
            self.stats.pipeline_depth_hist[d] = (
                self.stats.pipeline_depth_hist.get(d, 0) + 1
            )

    def decode_pipelined(self, positions, temps=None, topps=None, seeds=None,
                         tokens=None, g_states=None):
        from . import faults

        faults.fire("engine.dispatch")
        eff = self._eff_positions(positions)
        effg = self._eff_g(g_states, reseed=tokens is not None)
        toks = [
            self._tok_g(i, eff[i], effg[i]) for i in range(self.n_lanes)
        ]
        for i in range(self.n_lanes):
            self._sim_tok[i] = toks[i]
            self._sim_pos[i] = min(eff[i] + 1, self.config.seq_len)
            self._sim_g[i] = self._g_next_abs(effg[i], toks[i])
        self._push("tok", (toks, None))

    def decode_prefill_fused(self, positions, temps=None, topps=None,
                             seeds=None, p_lane=0, chunk=None, p_start=0,
                             p_temp=0.0, p_topp=0.9, p_seed=0, tokens=None,
                             g_states=None, p_g=0):
        """Fused prefill+decode dispatch: one simulated device step that
        both advances the decode lanes and consumes one prompt chunk; the
        packed readback carries the chunk's boundary token in an extra
        column, like the real engine's [2, n+1] pack."""
        from . import faults

        if not chunk:
            raise ValueError("fused prefill needs a non-empty prompt chunk")
        if len(chunk) > self._max_chunk:
            raise ValueError(
                f"chunk of {len(chunk)} exceeds bucket {self._max_chunk}"
            )
        faults.fire("engine.dispatch")
        eff = self._eff_positions(positions)
        effg = self._eff_g(g_states, reseed=tokens is not None)
        toks = [
            self._tok_g(i, eff[i], effg[i]) for i in range(self.n_lanes)
        ]
        self._feed_key(p_lane, chunk, p_start)
        boundary = self._tok_g(p_lane, p_start + len(chunk) - 1, p_g)
        for i in range(self.n_lanes):
            self._sim_tok[i] = toks[i]
            self._sim_pos[i] = min(eff[i] + 1, self.config.seq_len)
            self._sim_g[i] = self._g_next_abs(effg[i], toks[i])
        # the joined lane's carry = the boundary pair (real-engine rule)
        self._sim_tok[p_lane] = boundary
        self._sim_pos[p_lane] = p_start + len(chunk)
        self._sim_g[p_lane] = self._g_next_abs(p_g, boundary)
        self._push("tok", (toks, boundary))
        with self.stats.lock:
            self.stats.fused_steps += 1
            self.stats.prefill_tokens += len(chunk)
            self.stats.prefill_bucket_rows += self._max_chunk
            self.stats.fused_bucket_hist[self._max_chunk] = (
                self.stats.fused_bucket_hist.get(self._max_chunk, 0) + 1
            )

    def _spec_payload(self, positions, drafts, draft_len, tokens,
                      g_states=None):
        """The in-chain verify sim: resolve carry tok/pos/grammar-state,
        apply the candidate-0 alignment gate, run the acceptance math,
        and advance the simulated carries by the per-lane emit counts."""
        import numpy as np

        n = self.n_lanes
        eff = self._eff_positions(positions)
        effg = self._eff_g(g_states, reseed=tokens is not None)
        carry = (
            [int(t) for t in tokens] if tokens is not None
            else [int(t) for t in self._sim_tok]
        )
        k1 = np.asarray(drafts).shape[1]  # SPEC_DRAFT + 1
        if k1 != self.SPEC_DRAFT + 1:
            raise ValueError(
                f"spec drafts shape {np.asarray(drafts).shape} != "
                f"{(n, self.SPEC_DRAFT + 1)}"
            )
        eff_drafts = np.asarray(drafts)[:, 1:]
        eff_len = np.zeros(n, np.int64)
        for i in range(n):
            if int(draft_len[i]) > 0 and int(drafts[i][0]) == carry[i]:
                eff_len[i] = int(draft_len[i]) - 1
        emitted, n_emit, g_final = self._verify(
            np.asarray(carry), eff_drafts, eff_len, np.asarray(eff), effg,
        )
        for i in range(n):
            cnt = int(n_emit[i])
            self._sim_tok[i] = int(emitted[i, cnt - 1])
            self._sim_pos[i] = min(eff[i] + cnt, self.config.seq_len)
            self._sim_g[i] = int(g_final[i])
        return emitted, n_emit

    def decode_spec_pipelined(self, positions, drafts, draft_len,
                              temps=None, topps=None, seeds=None,
                              tokens=None, g_states=None):
        from . import faults

        faults.fire("engine.dispatch")
        emitted, n_emit = self._spec_payload(
            positions, drafts, draft_len, tokens, g_states
        )
        self._push("spec", (emitted, n_emit))
        with self.stats.lock:
            self.stats.spec_steps += 1
            self.stats.spec_pipelined_steps += 1

    def decode_spec_prefill_fused(self, positions, drafts, draft_len,
                                  temps=None, topps=None, seeds=None,
                                  p_lane=0, chunk=None, p_start=0,
                                  p_temp=0.0, p_topp=0.9, p_seed=0,
                                  tokens=None, g_states=None, p_g=0):
        """An admitting chunk and a spec verify sharing one dispatch —
        the readback appends the boundary pair as an extra ROW
        (emitted[-1, :2]), the real engine's spec-pack layout."""
        import numpy as np

        from . import faults

        if not chunk:
            raise ValueError("fused prefill needs a non-empty prompt chunk")
        if len(chunk) > self._max_chunk:
            raise ValueError(
                f"chunk of {len(chunk)} exceeds bucket {self._max_chunk}"
            )
        faults.fire("engine.dispatch")
        emitted, n_emit = self._spec_payload(
            positions, drafts, draft_len, tokens, g_states
        )
        self._feed_key(p_lane, chunk, p_start)
        boundary = self._tok_g(p_lane, p_start + len(chunk) - 1, p_g)
        self._sim_tok[p_lane] = boundary
        self._sim_pos[p_lane] = p_start + len(chunk)
        self._sim_g[p_lane] = self._g_next_abs(p_g, boundary)
        brow = np.zeros((1, emitted.shape[1]), np.int64)
        brow[0, 0] = brow[0, 1] = boundary
        emitted = np.concatenate([emitted, brow])
        n_emit = np.concatenate([n_emit, np.ones(1, np.int64)])
        self._push("spec", (emitted, n_emit))
        with self.stats.lock:
            self.stats.spec_steps += 1
            self.stats.spec_pipelined_steps += 1
            self.stats.fused_steps += 1
            self.stats.prefill_tokens += len(chunk)
            self.stats.prefill_bucket_rows += self._max_chunk
            self.stats.fused_bucket_hist[self._max_chunk] = (
                self.stats.fused_bucket_hist.get(self._max_chunk, 0) + 1
            )

    def pipeline_consume(self):
        import numpy as np

        from . import faults

        faults.fire("engine.consume")
        ready_at, dispatched_at, s, kind, payload = self._ring.pop(0)
        t0 = time.monotonic()
        time.sleep(max(0.0, ready_at - t0))
        self.events.append(("consume", s))
        with self.stats.lock:
            self.stats.decode_steps += 1
            self.stats.decode_s += max(0.0, ready_at - t0)
            self.stats.overlap_s += max(0.0, t0 - dispatched_at)
        if kind == "spec":
            emitted, n_emit = payload
            return emitted, n_emit
        toks, boundary = payload
        t = np.asarray(toks, np.int32)
        if boundary is not None:
            t = np.concatenate([t, np.asarray([boundary], np.int32)])
        return t, t

    def pipeline_flush(self, count=True):
        n = len(self._ring)
        while self._ring:
            self.pipeline_consume()
        self._carry_live = False
        if n and count:
            with self.stats.lock:
                self.stats.pipeline_flushes += 1
        return n

    def pipeline_abort(self):
        """The real engine's containment primitive: drop the ring without
        consuming (a poisoned step's readback would re-raise)."""
        n = len(self._ring)
        self._ring.clear()
        self._carry_live = False
        if n:
            with self.stats.lock:
                self.stats.pipeline_flushes += 1
        return n

    def count_overlapped_consumes(self):
        """(consumed steps, consumes of step k that happened after step k+1
        was already dispatched) — the one-step-lag evidence."""
        seen = set()
        consumed = overlapped = 0
        for kind, s in self.events:
            if kind == "dispatch":
                seen.add(s)
            else:
                consumed += 1
                if s + 1 in seen:
                    overlapped += 1
        return consumed, overlapped


def greedy_rollout(engine, prompt, n):
    """Plain greedy decode of n tokens on lane 0 (other lanes idle);
    returns (produced tokens, final position). Shared by the speculative-
    decoding tests and the multichip dryrun's on-mesh acceptance check."""
    import numpy as np

    _, g, pos = engine.prefill(0, prompt)
    toks = [int(g)]
    tokens = np.zeros(engine.n_lanes, np.int32)
    positions = np.zeros(engine.n_lanes, np.int32)
    for _ in range(n - 1):
        tokens[0], positions[0] = toks[-1], pos
        _, greedy, _ = engine.decode(tokens, positions)
        toks.append(int(greedy[0]))
        pos += 1
    return toks, pos
