"""Runtime recompile witness (``DLLAMA_JITCHECK=1``).

The static surface model (``jitmodel.py`` + the ``warmup-coverage`` /
``jit-stability`` checks) proves what the SOURCE compiles at warmup;
this module proves what the PROCESS compiles after it — the
``lockcheck.make_lock`` pattern applied to compile stability. A
``jax.monitoring`` duration listener counts backend XLA compiles
(``/jax/core/compile/backend_compile_duration`` fires exactly once per
real compile and never on an executable-cache hit):

- ``warming()`` — ``warmup_engine`` wraps its body in this context, so
  warmup's own compiles (of ANY engine in the process — tests build
  several) never count against an armed witness;
- ``arm(stats)`` — called by ``warmup_engine`` as its last act: from
  here on, every backend compile bumps the engine's
  ``EngineStats.jit_compiles_after_warmup`` counter (under the stats
  lock — surfaced on ``/stats``, bridged to ``/metrics``, reported by
  the benchmark as ``jit_compiles_after_warmup``), and with the witness
  ENABLED (``DLLAMA_JITCHECK=1`` or :func:`force`) additionally raises
  :class:`RecompileAfterWarmup` out of the guilty dispatch — a stack
  trace at the exact call that changed an aval or hit an unwarmed
  family, instead of a latency graph three weeks later.

The counter is always on once armed (one listener call per compile —
compiles are the rare event being asserted absent — and zero per-step
overhead); only the RAISE is opt-in, mirroring the lock witness's
zero-production-overhead contract. Pure stdlib at import; jax is
imported lazily the first time a witness is armed, so ``make lint``'s
jax-free import surface is untouched.
"""

from __future__ import annotations

import contextlib
import os
import threading
import weakref

from ..lockcheck import make_lock

ENV_FLAG = "DLLAMA_JITCHECK"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# persistent-cache traffic of this process: a restart on the same tree must
# show hits, and the start-up lines (warmup_program, warmup_done) say so
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses",
}

_forced: bool | None = None
# guards the registry below (never held around a sink's stats lock or
# any jax call — the listener snapshots under it and bumps outside, so
# the package lock-order graph stays edge-free)
_lock = make_lock("jitcheck._lock")
_installed = False
_pause_depth = 0
_armed = False
_sinks: list = []  # weakrefs to EngineStats-like sinks
_total_compiles = 0  # process lifetime, diagnostics
_cache_counts = dict.fromkeys(CACHE_EVENTS.values(), 0)


class RecompileAfterWarmup(AssertionError):
    """XLA compiled a new program after ``warmup_engine`` returned.
    AssertionError on purpose (the lockcheck convention): the witness is
    a test-time oracle and a post-warmup compile is a failed invariant —
    an unwarmed (family, bucket) or an aval-changing operand — not an
    operational error to catch and retry."""


def enabled() -> bool:
    """Strict mode: raise on post-warmup compiles (the counter runs
    regardless once armed)."""
    if _forced is not None:
        return _forced
    return os.environ.get(ENV_FLAG, "") not in ("", "0")


def force(value: bool | None, fresh: bool = True) -> None:
    """Test hook: override the env flag (None restores it). ``fresh``
    disarms and drops registered sinks so the next ``arm`` starts
    clean; the process-global jax listener stays installed (it is
    inert while disarmed)."""
    global _forced, _armed
    _forced = value
    if fresh:
        with _lock:
            _armed = False
            _sinks.clear()


def _on_duration(event: str, duration: float, **kw) -> None:
    """The jax.monitoring listener — one call per backend compile."""
    global _total_compiles
    if event != COMPILE_EVENT:
        return
    with _lock:
        _total_compiles += 1
        if _pause_depth > 0 or not _armed:
            return
        sinks = [ref() for ref in _sinks]
    strict = enabled()
    for stats in sinks:
        if stats is None:
            continue
        # EngineStats discipline: the counter is declared in
        # _dlint_guarded_by, so the bump holds the stats lock
        with stats.lock:
            stats.jit_compiles_after_warmup += 1
    if strict:
        raise RecompileAfterWarmup(
            "XLA compiled a new program after warmup_engine returned — "
            "an unwarmed (family, bucket) or an aval-changing operand "
            "rebuild; the dispatch that triggered it is in this stack. "
            "Fix the warmup/leaf recipe (see docs/LINT.md, "
            "warmup-coverage / jit-stability) rather than disabling "
            f"{ENV_FLAG}."
        )


def _on_event(event: str, **kw) -> None:
    """The jax.monitoring listener for the persistent cache's events."""
    key = CACHE_EVENTS.get(event)
    if key is not None:
        with _lock:
            _cache_counts[key] += 1


def install() -> None:
    """Register the process-global listeners once (``arm`` does it too;
    ``warmup_engine`` and ``enable_compilation_cache`` call it earlier so
    the start-up's own compiles and cache loads are counted). Caller
    holds no lock; jax import happens here, lazily."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


@contextlib.contextmanager
def warming():
    """Suppress counting/raising for the duration (re-entrant):
    ``warmup_engine`` compiles on purpose, and one engine's warmup must
    not fire another engine's armed witness in the same process."""
    global _pause_depth
    with _lock:
        _pause_depth += 1
    try:
        yield
    finally:
        with _lock:
            _pause_depth -= 1


def arm(stats) -> None:
    """Start witnessing for ``stats`` (an ``EngineStats``: needs
    ``.lock`` and ``.jit_compiles_after_warmup``). Idempotent per
    object; sinks are weak so dead engines cost nothing."""
    install()
    with _lock:
        global _armed
        _armed = True
        _sinks[:] = [r for r in _sinks if r() is not None]
        if not any(r() is stats for r in _sinks):
            _sinks.append(weakref.ref(stats))


def armed() -> bool:
    with _lock:
        return _armed


def total_compiles() -> int:
    """Process-lifetime backend compile count (0 until ``install`` ran —
    the listener installs lazily)."""
    with _lock:
        return _total_compiles


def cache_counts() -> dict:
    """``{compile_cache_hits, compile_cache_misses}`` of this process
    since ``install`` (both 0 with no persistent cache configured)."""
    with _lock:
        return dict(_cache_counts)
