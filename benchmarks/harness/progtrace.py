"""The traced run read by the program's own names.

`xplane.py` reduces a trace by what XLA calls things (instruction names, result
shapes). This module reduces the same ``.xplane.pb`` by what the PROGRAM calls
them: the ``dl.*`` device scopes its step programs carry in every
instruction's ``op_name`` and the ``dl.loop.*`` host spans its batching loop
holds open (`distributed_llama_multiusers_tpu/telemetry/names.py`). A program
from before those names leaves nothing to read, and every reader built on this
module then returns None.

What a TPU trace holds (looked at by hand, PR 26): an ``XLA Ops`` event's name
is the instruction text WITHOUT its ``metadata={...}``, and
``jax.profiler.ProfileData`` shows only the event's own stats (offset,
duration). The ``op_name`` is the stat ``tf_op`` of the event's METADATA
(``jit(_decode_pl)/dl.layers/while/body/closed_call/dl.attention/dot_general:``),
which only the raw protobuf gives: one route, ``xplane_pb2``, as
``parallel/comm_stats.py`` reads it. A ``while`` carries no ``tf_op`` and spans
the events of its body. Times are nanoseconds on the profiler's clock
(line timestamp + event offset), the same for device and host lines.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import sys
import time
from collections import defaultdict

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
if __package__ in (None, ""):  # by hand: python3 benchmarks/harness/progtrace.py
    sys.path[:0] = [BENCH_DIR, ROOT]

from harness.stats import percentile, union_seconds  # noqa: E402
from harness.xplane import (  # noqa: E402
    CONTAINERS,
    DEVICE_PLANE,
    MODULES_LINE,
    OPS_LINE,
    _idle_gaps,
    parse_hlo_event,
    program_family,
)

try:
    from distributed_llama_multiusers_tpu.telemetry import names  # noqa: E402
except ImportError:  # a program from before the scopes: nothing to read
    names = None

OP_NAME_STAT = "tf_op"
LONG_GAP_NS = 1e5          # 0.1 ms: a gap the host could have prevented
LAUNCH_GAPS = "launch_gaps"  # the shorter ones, summed
NO_SPAN = "no dl.loop span"
WINDOW_MARK = "bench.traced_window"


def read(path: str) -> dict:
    """``{"device": {chip: {"ops": [...], "modules": [...]}}, "host": [...]}``:
    operations as dicts ``name, shape, opcode, op_name, start, dur``, program
    executions and host spans (``dl.*`` and ``bench.*``) as ``name, start,
    dur``."""
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {"device": {}, "host": []}
    for plane in space.planes:
        m = DEVICE_PLANE.match(plane.name)
        metas = plane.event_metadata
        if m:
            stat_id = next((k for k, v in plane.stat_metadata.items()
                            if v.name == OP_NAME_STAT), None)
            parsed: dict = {}   # metadata id -> (name, shape, opcode, op_name)
            chip = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                t_line = float(line.timestamp_ns)
                for ev in line.events:
                    start = t_line + ev.offset_ps / 1e3
                    dur = ev.duration_ps / 1e3
                    if line.name == MODULES_LINE:
                        chip["modules"].append({"name": metas[ev.metadata_id].name,
                                                "start": start, "dur": dur})
                        continue
                    rec = parsed.get(ev.metadata_id)
                    if rec is None:
                        md = metas[ev.metadata_id]
                        rec = parsed[ev.metadata_id] = (
                            *parse_hlo_event(md.name), _op_name(plane, md, stat_id))
                    chip["ops"].append({"name": rec[0], "shape": rec[1], "opcode": rec[2],
                                        "op_name": rec[3], "start": start, "dur": dur})
            out["device"][int(m.group(1))] = chip
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                t_line = float(line.timestamp_ns)
                for ev in line.events:
                    name = metas[ev.metadata_id].name
                    if name.startswith(("dl.", "bench.")):
                        out["host"].append({
                            "name": name, "start": t_line + ev.offset_ps / 1e3,
                            "dur": ev.duration_ps / 1e3, "thread": line.name})
    return out


def _xplane_pb2():
    """The XSpace protobuf module. It ships inside tensorflow, whose import
    costs a traced run 10-20 s; the one generated file needs nothing but
    ``google.protobuf``, so it is loaded by its path, and imported the long
    way (as ``parallel/comm_stats.py`` does) only where that fails."""
    import importlib.util

    try:
        pkg = importlib.util.find_spec("tensorflow")   # finds, does not import
        path = os.path.join(os.path.dirname(pkg.origin), "tsl", "profiler",
                            "protobuf", "xplane_pb2.py")
        spec = importlib.util.spec_from_file_location("_bench_xplane_pb2", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    except (AttributeError, OSError, ImportError):  # another layout: the documented import
        from tensorflow.tsl.profiler.protobuf import xplane_pb2

        return xplane_pb2


def _op_name(plane, md, stat_id) -> str:
    if stat_id is None:
        return ""
    for st in md.stats:
        if st.metadata_id == stat_id:
            if st.str_value:
                return st.str_value
            if st.ref_value:
                return plane.stat_metadata[st.ref_value].name
    return ""


def reduce(trace: dict, window: tuple[float, float] | None = None) -> dict | None:
    """Per program family and scope: seconds, self seconds, executions; the
    loop's host spans; the device's idle gaps by the span open at their start.

    Only operations inside WHOLE program executions within the window are
    counted per family, and each execution keeps its own sums: "per execution"
    is the MEDIAN over them, so an execution the profiler caught only in part
    (the first after the trace starts can lose its early operations) does not
    pull the figure. Returns None
    where the program's names are unknown or the trace has no device plane."""
    chips = trace["device"]
    if names is None or not chips:
        return None
    if window is None:
        marks = [h for h in trace["host"] if h["name"] == WINDOW_MARK]
        if marks:
            window = (marks[0]["start"], marks[0]["start"] + marks[0]["dur"])
        else:
            ops = [e for c in chips.values() for e in c["ops"]]
            window = (min(e["start"] for e in ops),
                      max(e["start"] + e["dur"] for e in ops))
    w0, w1 = window
    n = len(chips)

    def clip(e):
        s, t = max(e["start"], w0), min(e["start"] + e["dur"], w1)
        return (s, t) if t > s else None

    fam_self = defaultdict(lambda: defaultdict(float))   # family -> scope -> s
    fam_incl = defaultdict(lambda: defaultdict(float))
    fam_ops = defaultdict(lambda: defaultdict(lambda: defaultdict(float)))
    fam_ms = defaultdict(list)
    fam_exec = defaultdict(dict)   # family -> (chip, module index) -> scope -> s
    busy = unscoped = 0.0
    scoped_any = False
    gaps = []
    for chip_id, chip in chips.items():
        spans = [c for c in map(clip, chip["ops"]) if c]
        busy += union_seconds(spans) / 1e9 / n
        gaps.extend(_idle_gaps(spans, w0, w1))
        modules = sorted(chip["modules"], key=lambda m: m["start"])
        starts = [m["start"] for m in modules]
        for i, m in enumerate(modules):
            if m["start"] >= w0 and m["start"] + m["dur"] <= w1:
                fam_ms[program_family(m["name"])].append(m["dur"] / 1e6)
                fam_exec[program_family(m["name"])][chip_id, i] = defaultdict(float)
        for e in chip["ops"]:
            c = clip(e)
            # a while or a conditional spans the operations of its body, which
            # are events of their own: counted there, not twice
            if not c or e["opcode"] in CONTAINERS:
                continue
            secs = (c[1] - c[0]) / 1e9 / n
            path = names.scope_path(e["op_name"])
            scoped_any |= bool(path)
            if not path:
                unscoped += secs
            i = bisect.bisect_right(starts, e["start"]) - 1
            if i < 0 or e["start"] >= modules[i]["start"] + modules[i]["dur"]:
                continue
            mod = modules[i]
            if mod["start"] < w0 or mod["start"] + mod["dur"] > w1:
                continue
            fam = program_family(mod["name"])
            leaf = path[-1] if path else None
            fam_self[fam][leaf] += secs
            fam_exec[fam][chip_id, i][leaf] += c[1] - c[0]
            for scope in set(path):
                fam_incl[fam][scope] += secs
            fam_ops[fam][leaf][f"{e['name']} {e['shape']}".strip()] += secs
    if not scoped_any:
        # executables without a single scope: a program from before them, or a
        # compile cache filled by one (the cache's key ignores the names
        # unless jax_compilation_cache_include_metadata_in_key is set)
        scopes = None
    else:
        scopes = {
            fam: {"executions": len(fam_ms[fam]),
                  "median_ms": percentile(fam_ms[fam], 50),
                  "self_s": dict(fam_self[fam]), "seconds": dict(fam_incl[fam]),
                  # one dict a whole execution: scope -> ms of self time
                  "per_execution": [{k: v / 1e6 for k, v in ex.items()}
                                    for ex in fam_exec[fam].values()],
                  "ops": {k: dict(v) for k, v in fam_ops[fam].items()}}
            for fam in fam_ms}
    loop = [h for h in trace["host"] if h["name"].startswith(names.ANNOTATION_PREFIX + "loop.")]
    spans_s, counts = defaultdict(float), defaultdict(int)
    for h in loop:
        c = clip(h)
        if c:
            spans_s[h["name"]] += (c[1] - c[0]) / 1e9
            counts[h["name"]] += w0 <= h["start"] < w1
    idle = defaultdict(float)
    loop.sort(key=lambda h: h["start"])
    loop_starts = [h["start"] for h in loop]
    for g0, g1 in gaps:
        if g1 - g0 < LONG_GAP_NS:
            idle[LAUNCH_GAPS] += (g1 - g0) / 1e9 / n
            continue
        # the span that was open when the gap began: the last one started
        # before it that had not yet ended (the loop's spans do not nest)
        label = NO_SPAN
        i = bisect.bisect_right(loop_starts, g0) - 1
        if i >= 0 and g0 < loop[i]["start"] + loop[i]["dur"]:
            label = loop[i]["name"]
        idle[label] += (g1 - g0) / 1e9 / n
    return {
        "chips": n, "window_s": (w1 - w0) / 1e9, "busy_s": busy,
        "unscoped_s": unscoped if scoped_any else None,
        "scopes": scopes,
        "loop_s": dict(spans_s) if loop else None,
        "loop_count": dict(counts),
        "idle_by_span": dict(idle),
    }


# -- what the metrics ask -----------------------------------------------------

def scope_ms_per_execution(red: dict | None, family: str, scopes) -> float | None:
    """Self time of ``scopes`` (None: under no scope at all) in one execution
    of ``family``, in ms: the median over the whole executions."""
    fam = ((red or {}).get("scopes") or {}).get(family)
    if not fam or not fam["executions"]:
        return None
    return percentile([sum(ex.get(s, 0.0) for s in scopes)
                       for ex in fam["per_execution"]], 50)


def overhead_ms_per_execution(red: dict | None, family: str) -> float | None:
    """Time under none of the leaf scopes: ``dl.layers``' self time plus the
    operations under no scope."""
    return scope_ms_per_execution(red, family, (names.SCOPE_LAYERS, None) if names else ())


def loop_host_ms_per_step(red: dict | None) -> float | None:
    if not red or not red["loop_s"]:
        return None
    waits = red["loop_count"].get(names.ANNOTATION_PREFIX + names.LOOP_WAIT, 0)
    if not waits:
        return None
    host = sum(red["loop_s"].get(names.ANNOTATION_PREFIX + s, 0.0)
               for s in names.LOOP_HOST_SPANS)
    return 1e3 * host / waits


def idle_host_share(red: dict | None) -> float | None:
    """Percent of the window the device stood idle in gaps of 0.1 ms or more
    that began while the loop did its own work (not while it waited)."""
    if not red or not red["loop_s"] or not red["window_s"]:
        return None
    host = sum(red["idle_by_span"].get(names.ANNOTATION_PREFIX + s, 0.0)
               for s in names.LOOP_HOST_SPANS)
    return 100.0 * host / red["window_s"]


# -- the first token, by the program's stamps ---------------------------------

TTFT_STAMPS = ("submitted_at", "admitted_at", "first_dispatch_at",
               "prefill_done_at", "first_token_at")


def ttft_parts_ms(ctx) -> list[dict] | None:
    """Per request the window owes a first token (and that got one): queue
    wait, dispatch wait, prefill, hold, from the stamps the scheduler left on
    ``req.tel``, with the program's own ``ttft`` and the client's. None for an
    untraced run or a program without the stamps. Computed (and logged) once
    a run: the three readers share it through ``ctx``."""
    if getattr(ctx, "trace", None) is None:
        return None
    if hasattr(ctx, "_ttft_parts"):
        return ctx._ttft_parts
    from harness.window import owed_streams

    rows = []
    for s in owed_streams(ctx):
        tel = getattr(s.req, "tel", None)
        t = [getattr(tel, k, None) for k in TTFT_STAMPS]
        if any(v is None for v in t) or not s.delta_t:
            continue
        rows.append({
            "queue_wait": (t[1] - t[0]) * 1e3, "dispatch_wait": (t[2] - t[1]) * 1e3,
            "prefill": (t[3] - t[2]) * 1e3, "hold": (t[4] - t[3]) * 1e3,
            "program_ttft": tel.ttft_s * 1e3,
            "client_ttft": (s.delta_t[0] - s.start_t) * 1e3,
            "late": (s.req.submitted_at - s.start_t) * 1e3,
        })
    ctx._ttft_parts = rows or None
    if rows:
        med = {k: percentile([r[k] for r in rows], 50) for k in rows[0]}
        worst = max(abs(r["queue_wait"] + r["dispatch_wait"] + r["prefill"] + r["hold"]
                        - r["program_ttft"]) for r in rows)
        print(f"[progtrace] first token of {len(rows)} owed requests, medians in ms: "
              + ", ".join(f"{k} {v:.2f}" for k, v in med.items())
              + f"; largest |parts - program_ttft| {worst:.6f} ms", file=sys.stderr, flush=True)
    return ctx._ttft_parts


def ttft_part_p50_ms(ctx, part: str) -> float | None:
    rows = ttft_parts_ms(ctx)
    return percentile([r[part] for r in rows], 50) if rows else None


# -- one parse a process --------------------------------------------------------

_memo: dict = {}


def newest_trace(root: str = ROOT) -> str | None:
    files = glob.glob(os.path.join(root, ".bench_out", "trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def for_ctx(ctx) -> dict | None:
    """The reduction of the run's own trace (the newest under
    ``.bench_out/trace/``, which run.py has just written); None for an
    untraced run. Parsed once a process: every reader calls this."""
    if getattr(ctx, "trace", None) is None or names is None:
        return None
    path = newest_trace()
    if path is None:
        return None
    if path not in _memo:
        t0 = time.monotonic()
        trace = read(path)
        t1 = time.monotonic()
        red = _memo[path] = reduce(trace)
        print(f"[progtrace] {path}: read in {t1 - t0:.2f} s, reduced in {time.monotonic() - t1:.2f} s", file=sys.stderr, flush=True)
        log_table(red)
    return _memo[path]


def log_table(red: dict | None, out=sys.stderr) -> None:
    def p(msg):
        print(f"[progtrace] {msg}", file=out, flush=True)

    if red is None:
        p("nothing to read: no device plane, or a program without dl.* names")
        return
    p(f"window {red['window_s']:.4f} s, busy {red['busy_s']:.4f} s")
    if red["scopes"] is None:
        p("no operation carries a dl.* scope: executables from before the scopes "
          "(a stale compile cache?)")
    else:
        p(f"unscoped {red['unscoped_s']:.4f} s = {100 * red['unscoped_s'] / red['busy_s']:.2f} % of busy")
        for fam, d in sorted(red["scopes"].items()):
            if not d["executions"]:
                continue
            per = {k: percentile([ex.get(k, 0.0) for ex in d["per_execution"]], 50)
                   for k in d["self_s"]}
            total = percentile([sum(ex.values()) for ex in d["per_execution"]], 50)
            p(f"{fam}: {d['executions']} executions, median {d['median_ms']:.3f} ms; "
              f"operations sum to {total:.3f} ms an execution (medians)")
            for scope, ms in sorted(per.items(), key=lambda kv: -kv[1]):
                p(f"    {scope or 'unscoped':<14} {ms:9.3f} ms")
            for scope in (None, names.SCOPE_LAYERS):
                ops = sorted(d["ops"].get(scope, {}).items(), key=lambda kv: -kv[1])[:6]
                for name, s in ops:
                    p(f"      {scope or 'unscoped'}: {1e3 * s / d['executions']:8.3f} ms  {name[:90]}")
    if red["loop_s"]:
        p("loop spans in the window: " + ", ".join(
            f"{k} {v * 1e3:.2f} ms x{red['loop_count'].get(k, 0)}"
            for k, v in sorted(red["loop_s"].items())))
    p("device idle by the loop span open when the gap began: " + (", ".join(
        f"{k} {v * 1e3:.3f} ms" for k, v in sorted(red["idle_by_span"].items(),
                                                   key=lambda kv: -kv[1])) or "none"))


# -- by hand: look at a trace, record a stretch for the tests -----------------

def record_stretch(trace: dict, t0: float, t1: float, path: str, source: str) -> None:
    """Events that start in [t0, t1) as a compact json.gz (tests/data/)."""
    table, index = [], {}

    def key(e):
        k = (e["name"], e["shape"], e["opcode"], e["op_name"])
        if k not in index:
            index[k] = len(table)
            table.append(k)
        return index[k]

    keep = lambda e: t0 <= e["start"] < t1
    doc = {
        "source": source, "window": [t0, t1], "table": table,
        "device": {str(c): {
            "ops": [[key(e), e["start"], e["dur"]] for e in chip["ops"] if keep(e)],
            "modules": [m for m in chip["modules"] if keep(m)],
        } for c, chip in trace["device"].items()},
        "host": [h for h in trace["host"]
                 if h["start"] < t1 and h["start"] + h["dur"] > t0],
    }
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)


def load_stretch(path: str) -> tuple[dict, tuple[float, float]]:
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    table = doc["table"]
    device = {}
    for c, chip in doc["device"].items():
        ops = []
        for k, start, dur in chip["ops"]:
            name, shape, opcode, op_name = table[k]
            ops.append({"name": name, "shape": shape, "opcode": opcode,
                        "op_name": op_name, "start": start, "dur": dur})
        device[int(c)] = {"ops": ops, "modules": chip["modules"]}
    return {"device": device, "host": doc["host"]}, tuple(doc["window"])


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="print the table of one .xplane.pb")
    ap.add_argument("xplane", nargs="?", default=None, help="default: the newest traced run's")
    ap.add_argument("--record", default=None, help="write a stretch to this .json.gz")
    ap.add_argument("--ms", type=float, default=300.0, help="length of the recorded stretch")
    ap.add_argument("--source", default="", help="where the recording came from")
    args = ap.parse_args(argv)
    path = args.xplane or newest_trace()
    if path is None:
        print("no trace under .bench_out/trace/", file=sys.stderr)
        return 1
    trace = read(path)
    log_table(reduce(trace), out=sys.stdout)
    if args.record:
        marks = [h for h in trace["host"] if h["name"] == WINDOW_MARK]
        # from the middle of the traced stretch, aligned to a program's start
        mid = marks[0]["start"] + marks[0]["dur"] / 2 if marks else 0.0
        mods = sorted(m["start"] for c in trace["device"].values() for m in c["modules"])
        t0 = next((s for s in mods if s >= mid), mid)
        t1 = t0 + args.ms * 1e6
        ends = sorted(m["start"] for c in trace["device"].values() for m in c["modules"])
        t1 = next((s for s in ends if s >= t1), t1)   # ends where a program starts
        record_stretch(trace, t0, t1, args.record, args.source or path)
        rec, win = load_stretch(args.record)
        print(f"recorded {win[1] - win[0]:.0f} ns to {args.record}; it reduces to:")
        log_table(reduce(rec, win), out=sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
