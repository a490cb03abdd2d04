"""From the profiler's ``.xplane.pb`` to the numbers the metrics read.

Two steps, kept apart so that the arithmetic can be checked on a small
recorded trace (tests/data/): `read_xplane` turns the file into plain lists of
events, and `reduce_trace` turns those lists into busy time, per-program and
per-operation durations and the idle gaps, labelled by what the host was
doing. Times are nanoseconds on the profiler's clock.

What a TPU trace holds (looked at by hand, PR 25): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per executed
program (``jit__decode_pl(<fingerprint>)``) and whose line ``XLA Ops`` has one
event per HLO operation, named by the instruction's whole text in the
optimized HLO (``%copy.200 = bf16[32,16,2048,8,128]{...} copy(...)``), a
``while`` or ``conditional`` spanning the events of its body. Host threads
are lines of the plane ``/host:CPU``; `jax.profiler.TraceAnnotation` spans
appear there under the name they were given.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

from .stats import percentile, union_seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def read_xplane(path: str) -> dict:
    """``{"device": {chip: {"ops": [...], "modules": [...]}}, "host": [...]}``;
    each event a dict ``name, start, dur`` (+ ``shape`` for operations)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"device": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    rec = {"name": ev.name, "start": float(ev.start_ns),
                           "dur": float(ev.duration_ns)}
                    if line.name == OPS_LINE:
                        rec["name"], rec["shape"], rec["opcode"] = parse_hlo_event(ev.name)
                        chip["ops"].append(rec)
                    else:
                        chip["modules"].append(rec)
            out["device"][int(m.group(1))] = chip
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        out["host"].append({
                            "name": ev.name, "start": float(ev.start_ns),
                            "dur": float(ev.duration_ns), "thread": line.name,
                        })
    return out


_TYPE_RE = re.compile(r"([a-z]+[0-9]*(?:e[0-9]m[0-9](?:fn)?)?)\[([0-9,]*)\]")
CONTAINERS = ("while", "conditional", "call")


def parse_hlo_event(text: str) -> tuple[str, str, str]:
    """An ``XLA Ops`` event's name is the instruction as the optimized HLO
    prints it: ``%copy.200 = bf16[32,16,2048,8,128]{4,3,...} copy(...)``.
    Returns (name, result type and dimensions without layout, opcode); for a
    tuple result, the first element's type. Falls back to (text, "", "")."""
    if " = " not in text:
        return text.lstrip("%"), "", ""
    name, rest = text.split(" = ", 1)
    name = name.strip().lstrip("%")
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        result, tail = rest[: i + 1], rest[i + 1:]
    else:
        result, _, tail = rest.partition(" ")
    m = _TYPE_RE.search(result)
    shape = f"{m.group(1)}[{m.group(2)}]" if m else ""
    opcode = tail.strip().split("(", 1)[0].strip()
    return name, shape, opcode


def program_family(module_name: str) -> str:
    """``jit__decode_pl(1234...)`` -> ``_decode_pl``."""
    name = module_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def reduce_trace(trace: dict, window: tuple[float, float] | None = None) -> dict:
    """Busy time, durations by program and by operation, and idle gaps.

    window: (start, end) on the profiler's clock; default is the span of the
    ``bench.traced_window`` host event, else of the device's own events.
    Events are clipped to it. Per chip first, then averaged over chips."""
    if window is None:
        marks = [h for h in trace["host"] if h["name"] == "bench.traced_window"]
        if marks:
            window = (marks[0]["start"], marks[0]["start"] + marks[0]["dur"])
    chips = trace["device"]
    if not chips:
        return {"chips": 0}
    if window is None:
        starts = [e["start"] for c in chips.values() for e in c["ops"]]
        ends = [e["start"] + e["dur"] for c in chips.values() for e in c["ops"]]
        window = (min(starts), max(ends))
    w0, w1 = window

    def clip(e):
        s, t = max(e["start"], w0), min(e["start"] + e["dur"], w1)
        return (s, t) if t > s else None

    busy, op_time, op_count = [], defaultdict(float), defaultdict(int)
    by_prog = defaultdict(lambda: defaultdict(lambda: [0.0, 0]))
    prog_durs = defaultdict(list)
    gaps = []
    for chip in chips.values():
        spans = [c for c in map(clip, chip["ops"]) if c]
        busy.append(union_seconds(spans) / 1e9)
        modules = sorted(chip["modules"], key=lambda m: m["start"])
        starts = [m["start"] for m in modules]
        for e in chip["ops"]:
            c = clip(e)
            # a while or a conditional spans the operations of its body, which
            # are events of their own: counted there, not twice
            if c and e.get("opcode") not in CONTAINERS:
                key = (e["name"], e["shape"])
                op_time[key] += (c[1] - c[0]) / 1e9
                op_count[key] += 1
                # the program execution the operation started in
                i = bisect.bisect_right(starts, e["start"]) - 1
                if i >= 0 and e["start"] < modules[i]["start"] + modules[i]["dur"]:
                    cell = by_prog[program_family(modules[i]["name"])][key]
                    cell[0] += (c[1] - c[0]) / 1e9
                    cell[1] += 1
        for e in chip["modules"]:
            # whole executions only: a clipped one would read short
            if e["start"] >= w0 and e["start"] + e["dur"] <= w1:
                prog_durs[program_family(e["name"])].append(e["dur"] / 1e6)
        gaps.extend(_idle_gaps(spans, w0, w1))
    n = len(chips)
    labelled = defaultdict(float)
    for g0, g1 in gaps:
        labelled[_host_label(trace["host"], g0, g1)] += (g1 - g0) / 1e9 / n
    return {
        "chips": n,
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n,
        "op_seconds": {k: v / n for k, v in op_time.items()},
        "op_calls": {k: v / n for k, v in op_count.items()},
        # program family -> operation -> (seconds, calls) inside its executions
        "program_ops": {f: {k: (v[0] / n, v[1] / n) for k, v in ops.items()}
                        for f, ops in by_prog.items()},
        "program_ms": dict(prog_durs),
        "idle_gaps": sorted(labelled.items(), key=lambda kv: -kv[1]),
    }


def _idle_gaps(spans, w0, w1):
    """The stretches of the window in which no operation ran."""
    out, cur = [], w0
    for s, t in sorted(spans):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if w1 > cur:
        out.append((cur, w1))
    return out


def _host_label(host, g0, g1) -> str:
    """The benchmark's host span that covers most of an idle gap."""
    best, best_cover = "host:unlabelled", 0.0
    for h in host:
        if h["name"] == "bench.traced_window":
            continue
        cover = min(g1, h["start"] + h["dur"]) - max(g0, h["start"])
        if cover > best_cover:
            best, best_cover = h["name"], cover
    return best


def program_median_ms(reduced: dict, families) -> float | None:
    durs = [d for f in families for d in reduced.get("program_ms", {}).get(f, [])]
    return percentile(durs, 50)


def top_device_ops(reduced: dict, n: int = 10) -> list:
    ops = sorted(reduced.get("op_seconds", {}).items(), key=lambda kv: -kv[1])[:n]
    return [[f"{name} {shape}".strip(), secs] for (name, shape), secs in ops]
