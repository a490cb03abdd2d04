"""Fleet trace context: one id per request, carried across every hop.

Since PR 12 (fleet router + live migration), PR 16 (disaggregated
prefill) and PR 19 (host-RAM swap tier) a single request routinely
crosses three or four processes — router, prefill-role replica, decode
replica, a migration target — and each process's span ring only knew its
own slice of the story. The ``TraceContext`` here is the thread that
stitches them back together: a 128-bit trace id plus the 64-bit span id
of the hop that forwarded the request, minted by ``dllama-router`` for
fresh traffic or accepted from a client ``X-DLlama-Trace`` header, and
propagated on every hop the fleet already makes (route/retry/failover,
migration ticket inject, disagg prefill→decode hand-off, journal admit
records so a crash-recovered stream rejoins its original trace).

Wire format (the ``X-DLlama-Trace`` header value)::

    <32 lowercase hex chars trace id>-<16 lowercase hex chars span id>

deliberately shaped like W3C traceparent's id fields without the
version/flags framing — two ids, one dash, trivially parseable by any
log pipeline. Invalid headers are *ignored* (a fresh context is minted),
never 400d: tracing must not be able to fail a request.

Pure stdlib like the rest of ``telemetry/`` (registered under dlint's
``host-sync`` scope): ids come from ``os.urandom``, no wall-clock reads
(the ``clock`` check covers this file), and the one stateful class
(``PhaseAccumulator``, the router-side aggregation state behind
``dllama_request_phase_seconds``) declares its lock discipline via
``_dlint_guarded_by`` like every other telemetry lock.
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass

from ..lockcheck import make_lock

TRACE_HEADER = "X-DLlama-Trace"

_WIRE_RE = re.compile(r"^([0-9a-f]{32})-([0-9a-f]{16})$")

# an all-zero id is the W3C-traceparent "invalid" convention; refuse it
_ZERO_TRACE = "0" * 32
_ZERO_SPAN = "0" * 16


@dataclass(frozen=True)
class TraceContext:
    """One request's fleet-wide identity: ``trace_id`` names the request
    for its whole life (across migration, hand-off, recovery), ``span_id``
    names the hop that forwarded it (re-minted per hop via ``child()``,
    so a replica can tell a retry from the original attempt)."""

    trace_id: str
    span_id: str

    @staticmethod
    def mint() -> "TraceContext":
        """A fresh context: 128-bit trace id, 64-bit span id, both from
        ``os.urandom`` (no wall clock, no PRNG state to guard)."""
        return TraceContext(
            trace_id=os.urandom(16).hex(), span_id=os.urandom(8).hex()
        )

    def child(self) -> "TraceContext":
        """Same trace, fresh span id — stamp one per forwarding hop
        (route attempt, retry, migration inject, disagg hand-off) so the
        merged timeline attributes each hop distinctly."""
        return TraceContext(trace_id=self.trace_id, span_id=os.urandom(8).hex())

    def to_header(self) -> str:
        return f"{self.trace_id}-{self.span_id}"

    @staticmethod
    def parse(value: str | None) -> "TraceContext | None":
        """Parse a wire value; ``None`` on anything malformed (callers
        mint a fresh context instead — tracing never fails a request)."""
        if not value or not isinstance(value, str):
            return None
        m = _WIRE_RE.match(value.strip().lower())
        if m is None:
            return None
        trace_id, span_id = m.group(1), m.group(2)
        if trace_id == _ZERO_TRACE or span_id == _ZERO_SPAN:
            return None
        return TraceContext(trace_id=trace_id, span_id=span_id)

    @staticmethod
    def accept(value: str | None) -> "TraceContext":
        """The router's ingress rule: honour a valid client header
        (clients correlating with their own telemetry), mint otherwise."""
        ctx = TraceContext.parse(value)
        return ctx if ctx is not None else TraceContext.mint()


def trace_id_of(wire: str | None) -> str | None:
    """The trace id of a wire value, or None — the one-liner span
    emitters use to stamp ``trace_id`` args without caring whether the
    request carried a context at all."""
    ctx = TraceContext.parse(wire)
    return None if ctx is None else ctx.trace_id


# phase keys every producer emits, in display order.
# The first four partition ``ttft_ms`` (RequestTrace.phases): queued,
# admitted but not yet dispatched, prompt on the device, and the boundary
# token held by the host until the next consumed step emits it.
PHASE_KEYS = (
    "queue_wait_ms", "dispatch_wait_ms", "prefill_ms", "first_token_hold_ms",
    "decode_ms", "itl_p50_ms", "itl_p99_ms",
    "migration_gap_ms", "swap_in_ms", "ttft_ms", "total_ms",
)


class PhaseAccumulator:
    """Router-side aggregation of per-request ``phases`` records.

    The router sees every completion's terminal payload (streaming
    terminal chunk or non-streaming body); folding the ``phases`` record
    there gives fleet-wide TTFT/ITL/phase distributions measured at the
    one vantage point that also knows about migrations — the artifact
    ROADMAP item 3(d)'s tail-latency curve reads. Kept deliberately
    small: per-phase count/sum under one short lock; the bucketed
    distribution lives in the caller's ``MetricsRegistry`` histogram
    (``dllama_request_phase_seconds``), fed from the same observe call.
    """

    # dlint guarded-by declaration (analysis/lock_check.py): aggregation
    # state only under `_phase_lock`. Machine-checked by `make lint`.
    _dlint_guarded_by = {
        ("_phase_lock",): ("_phase_counts", "_phase_sums_ms", "_phase_records"),
    }

    def __init__(self):
        # witness-wrappable (DLLAMA_LOCKCHECK=1), named for the
        # class-qualified declaration like every telemetry lock
        self._phase_lock = make_lock("PhaseAccumulator._phase_lock")
        self._phase_counts: dict[str, int] = {}
        self._phase_sums_ms: dict[str, float] = {}
        self._phase_records = 0

    def observe(self, phases: dict | None) -> dict | None:
        """Fold one ``phases`` record; returns the cleaned record (only
        known keys, numeric values) or None if there was nothing usable.
        Callers feed the same cleaned record into their histogram so the
        accumulator and ``/metrics`` cannot drift."""
        if not isinstance(phases, dict):
            return None
        clean = {}
        for key in PHASE_KEYS:
            v = phases.get(key)
            if isinstance(v, (int, float)) and v >= 0:
                clean[key] = float(v)
        if not clean:
            return None
        with self._phase_lock:
            self._phase_records += 1
            for key, v in clean.items():
                self._phase_counts[key] = self._phase_counts.get(key, 0) + 1
                self._phase_sums_ms[key] = (
                    self._phase_sums_ms.get(key, 0.0) + v
                )
        return clean

    def snapshot(self) -> dict:
        """{records, per-phase count + sum_ms} for /stats — dict-valued,
        so the stats bridge republishes it as labelled gauges."""
        with self._phase_lock:
            return {
                "phase_records": self._phase_records,
                "phase_counts": dict(self._phase_counts),
                "phase_sum_ms": {
                    k: round(v, 3) for k, v in self._phase_sums_ms.items()
                },
            }
