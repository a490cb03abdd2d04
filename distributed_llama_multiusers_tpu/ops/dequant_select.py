"""Per-site dequant mode selection for ``DLLAMA_DEQUANT=auto``.

The dequant arithmetic variant (ops/pallas_q40.DEQUANT_MODES) is a static
argument of the jitted Q40 matmul: switching it retraces every family that
touched it. So "auto" cannot mean "measure and switch live" — it means
resolve each matmul site's mode ONCE, deterministically, from a small
persisted selection table keyed by (d_in, d_out, m-class), before
``warmup_engine`` compiles the step families. The table is checked in
(ops/dequant_table.json) and has ONE writer: ``scripts/kernel_lab3.py
--adopt``, through ``record_win``.

Everything in this module is HOST state: rules are plain python dicts and
strings. No device arrays may ever be constructed into the table or the
resolution caches — this file is registered in the dlint jit-stability
scope (analysis/jit_surface_check.py) exactly like runtime/engine.py.

m-class: "decode" is m <= BLOCKDOT_MAX_M (the blockdot family's own cap),
"prefill" is everything wider. Resolution happens inside
``q40_matmul_pallas`` at trace time only, so a warmed family never
re-resolves; ``freeze_for_serving`` (called by warmup_engine) additionally
pins the loaded table so a mid-serving reload cannot change answers.
"""

from __future__ import annotations

import json
import os
import threading
import time

_TABLE_ENV = "DLLAMA_DEQUANT_TABLE"
_DEFAULT_TABLE = os.path.join(os.path.dirname(__file__), "dequant_table.json")

M_CLASSES = ("decode", "prefill")

# Conservative default when no table rule matches at all (the shipped table
# always matches via wildcards): the bf16 chain every mode falls back to.
FALLBACK_MODE = "bf16chain"


def m_class_of(m: int) -> str:
    from .pallas_q40 import BLOCKDOT_MAX_M

    return "decode" if m <= BLOCKDOT_MAX_M else "prefill"


class DequantTable:
    """The persisted (d_in, d_out, m-class) -> mode selection table.

    Rules match exact values or "*" wildcards; the most specific matching
    rule wins (each exact field scores one, ties keep the earlier row).
    Loading validates every rule against the known kernel-mode list and
    fails loudly — a stale or hand-edited table must never silently route
    a site to the wrong chain. PURE host state: ``rules`` holds the parsed
    JSON dicts as-is."""

    def __init__(self, path: str | None = None):
        from .pallas_q40 import DEQUANT_MODES

        self.path = path or os.environ.get(_TABLE_ENV) or _DEFAULT_TABLE
        with open(self.path) as f:
            data = json.load(f)
        rules = data.get("rules", [])
        for r in rules:
            if r.get("mode") not in DEQUANT_MODES:
                raise ValueError(
                    f"{self.path}: rule {r!r} has unknown mode "
                    f"{r.get('mode')!r}; one of {DEQUANT_MODES}"
                )
            if r.get("m_class", "*") not in M_CLASSES + ("*",):
                raise ValueError(
                    f"{self.path}: rule {r!r} has unknown m_class "
                    f"{r.get('m_class')!r}; one of {M_CLASSES + ('*',)}"
                )
        self.rules = rules
        self.provenance = {
            "path": self.path,
            "version": data.get("version"),
            "updated": data.get("updated"),
            "rows": len(rules),
            "provenance": data.get("provenance"),
        }

    def resolve(self, d_in: int, d_out: int, m_class: str) -> str:
        best, best_score = None, -1
        for r in self.rules:
            score = 0
            for key, val in (("d_in", d_in), ("d_out", d_out),
                             ("m_class", m_class)):
                rv = r.get(key, "*")
                if rv == "*":
                    continue
                if rv != val:
                    score = -1
                    break
                score += 1
            if score > best_score:
                best, best_score = r, score
        if best is None:
            return FALLBACK_MODE
        return best["mode"]


_lock = threading.Lock()
_table: DequantTable | None = None
_frozen = False
_sites: dict[str, str] = {}  # "d_inxd_out/m_class" -> resolved mode


def _get_table() -> DequantTable:
    global _table
    with _lock:
        if _table is None:
            _table = DequantTable()
        return _table


def resolve_mode(d_in: int, d_out: int, m: int) -> str:
    """The auto-mode hook q40_matmul_pallas calls at trace time: the
    table's answer for this site, recorded into the site map surfaced on
    /stats."""
    cls = m_class_of(m)
    mode = _get_table().resolve(d_in, d_out, cls)
    with _lock:
        _sites[f"{d_in}x{d_out}/{cls}"] = mode
    return mode


def resolved_sites() -> dict[str, str]:
    """Copy of the per-site resolution map (empty unless auto resolved
    something — fixed modes never consult the table)."""
    with _lock:
        return dict(_sites)


def freeze_for_serving() -> dict | None:
    """Load + pin the selection table before warmup compiles anything.
    After this, ``reload_table`` refuses: the mode is a static argname, so
    a live table change would retrace every warmed family mid-serving.
    Returns the table provenance under auto, None for fixed modes (the
    table is not even loaded then)."""
    from . import pallas_q40 as pq

    global _frozen
    prov = dict(_get_table().provenance) if pq.DEQUANT_MODE == "auto" else None
    with _lock:
        _frozen = True
    return prov


def reload_table(path: str | None = None) -> DequantTable:
    """Swap in a (possibly different) table file — measurement tooling and
    tests only. Refuses once frozen for serving."""
    global _table
    with _lock:
        if _frozen:
            raise RuntimeError(
                "dequant selection table is frozen after warmup — the mode "
                "is a static argname, a live switch recompiles every warmed "
                "family; restart to pick up table changes"
            )
        _table = DequantTable(path)
        _sites.clear()
        return _table


def _reset_for_tests() -> None:
    global _table, _frozen
    with _lock:
        _table = None
        _frozen = False
        _sites.clear()


def dequant_stats() -> dict:
    """The dequant attribution payload for /stats: the configured mode
    knob, the per-site resolutions (auto), and the selection-table
    provenance when a table is loaded."""
    from . import pallas_q40 as pq

    out = {"dequant_mode": pq.DEQUANT_MODE}
    with _lock:
        if _sites:
            out["dequant_sites"] = dict(_sites)
        if _table is not None:
            out["dequant_table"] = dict(_table.provenance)
    return out


def record_win(d_in, d_out, m_class: str, mode: str, source: str,
               path: str | None = None) -> str:
    """Feed a measured (shape -> mode) winner back into the persisted
    table (its one caller: scripts/kernel_lab3.py --adopt). Upserts the
    matching rule and rewrites the file atomically. Writes the FILE only:
    a live process's resolution stays whatever it froze at — the next
    serving start picks the row up."""
    from .pallas_q40 import DEQUANT_MODES

    if mode not in DEQUANT_MODES:
        raise ValueError(f"unknown dequant mode {mode!r}; one of {DEQUANT_MODES}")
    if m_class not in M_CLASSES + ("*",):
        raise ValueError(f"unknown m_class {m_class!r}; one of {M_CLASSES + ('*',)}")
    path = path or os.environ.get(_TABLE_ENV) or _DEFAULT_TABLE
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    else:
        data = {"version": 1, "provenance": "recorded by measurement loops",
                "rules": []}
    rules = data.setdefault("rules", [])
    for r in rules:
        if (r.get("d_in", "*"), r.get("d_out", "*"),
                r.get("m_class", "*")) == (d_in, d_out, m_class):
            r["mode"] = mode
            r["source"] = source
            break
    else:
        rules.append({"d_in": d_in, "d_out": d_out, "m_class": m_class,
                      "mode": mode, "source": source})
    data["updated"] = time.strftime("%Y-%m-%d")
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")
    os.replace(tmp, path)
    return path
