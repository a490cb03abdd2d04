"""The window read by the batching loop's own record of each step.

`progtrace.py` and `stepclass.py` read the traced 3 s from the device's side.
Since PR 52 the loop leaves, for every pipelined step, one record made when
the step's readback returns (`telemetry/spans.py` ``StepRecord``: ``step``,
``cls`` (the device program's own ``dlstep.*`` class), ``chunk``, ``p_start``,
``final``, ``lanes``, ``dry``, ``dry_s``, ``interval_s`` from the readback
before to this one, ``wait_s`` inside ``engine.pipeline_consume``, ``host_s``
the rest, ``at`` on ``time.monotonic()``). This module reads it from the two
places a reader of the benchmark can reach:

- ``req.tel.chunks`` of every stream (``ctx.streams``): the records of the
  steps that carried a prompt chunk, over the WHOLE window, traced or not
  (`window_rows`, `class_table`, `late_share`, `top_rung_step_ms`);
- the profiler's copy of the loop's spans: ``dl.loop.dispatch`` carries
  ``step`` and ``dry``, ``dl.loop.stream`` the whole record, as the stats of
  the host plane's events (`read`). Device executions leave in dispatch order,
  so the first execution that starts after ``dl.loop.dispatch(step=n)`` opened
  is step n's and the ones after it count on from there (`join`): every
  execution of the stretch gets its record, and every idle gap the step
  before it, the step after it, the loop span open when it began and whether
  the host had seen the device run dry.

A program from before the record leaves nothing to read: every function here
then returns None (or an empty join) and raises nothing.
"""

from __future__ import annotations

import bisect
import os
import sys
import time
from collections import Counter, defaultdict

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
if __package__ in (None, ""):  # by hand: python3 benchmarks/harness/steplog.py
    sys.path[:0] = [BENCH_DIR, ROOT]

from harness import progtrace  # noqa: E402
from harness.progtrace import names  # noqa: E402
from harness.stats import percentile  # noqa: E402
from harness.xplane import CONTAINERS, _idle_gaps, program_family  # noqa: E402

FUSED = ("dlstep.fused.", "dlstep.spec_fused.")   # the classes that carry a chunk
LATE_FACTOR = 1.25    # a step is late past this many medians of its like
BAND = 4096           # start positions a like spans: a long context's step grows with its start
GAP_NS = 1e6          # an idle gap worth a line of its own: 1 ms
DISPATCH, STREAM = "dl.loop.dispatch", "dl.loop.stream"
# the step programs the pipelined loop dispatches, one execution a dispatch
PIPELINED = ("_decode_pl", "_decode_prefill", "_decode_spec_pl", "_decode_spec_prefill")
NO_SPAN = progtrace.NO_SPAN
QUEUED = "device idle with work queued"


# -- the whole window, from req.tel.chunks ------------------------------------

def window_rows(ctx) -> list | None:
    """The records of the steps that carried a prompt chunk and were read
    back inside the window, by time; None where no request keeps any (a
    program from before them)."""
    rows, known = [], False
    for s in ctx.streams:
        chunks = getattr(getattr(s.req, "tel", None), "chunks", None)
        if chunks is None:
            continue
        known = True
        rows.extend(r for r in chunks if ctx.t_open <= r.at < ctx.t_close)
    return sorted(rows, key=lambda r: r.at) if known else None


def fused_rows(rows) -> list:
    return [r for r in rows or () if r.cls.startswith(FUSED)]


def top_rung(ctx) -> int:
    """The largest rung of the cell's prefill ladder that its context holds
    (``serving.prefill_buckets``; ``default`` is the program's own)."""
    ladder = ctx.cfg["serving"].get("prefill_buckets", "default")
    if ladder == "default":
        from distributed_llama_multiusers_tpu.runtime.engine import DEFAULT_PREFILL_BUCKETS as ladder
    return max(b for b in ladder if b <= ctx.config.seq_len)


def top_rung_step_ms(ctx) -> float | None:
    """Median interval, in ms, of the window's steps whose chunk rode the
    ladder's largest rung; None where the window holds none."""
    rows = for_ctx(ctx)["rows"]
    if rows is None:
        return None
    cls = f"dlstep.fused.b{top_rung(ctx)}"
    return percentile([1e3 * r.interval_s for r in rows if r.cls == cls], 50)


def like_medians(rows) -> dict:
    """(class, band of start positions) -> median ``interval_s``, ``wait_s``
    and ``host_s`` of the fused steps of that like."""
    groups = defaultdict(list)
    for r in fused_rows(rows):
        groups[r.cls, r.p_start // BAND].append(r)
    return {k: tuple(percentile([getattr(r, f) for r in g], 50)
                     for f in ("interval_s", "wait_s", "host_s"))
            for k, g in groups.items()}


def late_rows(rows) -> list:
    """The fused steps whose interval passes LATE_FACTOR medians of their
    like, each with what its wait and its host time read over theirs."""
    med = like_medians(rows)
    out = []
    for r in fused_rows(rows):
        m = med[r.cls, r.p_start // BAND]
        if r.interval_s > LATE_FACTOR * m[0]:
            out.append((r, r.interval_s - m[0], r.wait_s - m[1], r.host_s - m[2]))
    return out


def late_share(ctx) -> float | None:
    rows = for_ctx(ctx)["rows"]
    if rows is None:
        return None
    fused = fused_rows(rows)
    return 100.0 * len(late_rows(rows)) / len(fused) if fused else 0.0


def counter_share(ctx, part: str, whole: float | None) -> float | None:
    """100 x a counter of the window over ``whole``; None where the program
    keeps no such counter, 0.0 where it did not move."""
    value = ctx.counters.get(part)
    if value is None:
        return None
    for_ctx(ctx)  # the table, once a run
    return 100.0 * value / whole if whole else 0.0


def class_table(rows) -> list[dict]:
    """One line a class of the window's chunk-carrying steps."""
    by = defaultdict(list)
    for r in rows or ():
        by[r.cls].append(r)
    out = []
    for cls, rs in sorted(by.items()):
        iv = [1e3 * r.interval_s for r in rs]
        out.append({
            "class": cls, "steps": len(rs), "median_ms": percentile(iv, 50),
            "p90_ms": percentile(iv, 90), "largest_ms": max(iv),
            "wait_ms": percentile([1e3 * r.wait_s for r in rs], 50),
            "host_ms": percentile([1e3 * r.host_s for r in rs], 50),
            "lanes": percentile([r.lanes for r in rs], 50),
            "dry": sum(1 for r in rs if r.dry),
        })
    return out


# -- the traced stretch: the profiler's copy of the loop's spans ---------------

def host_args(path: str) -> list[dict]:
    """The host plane's ``dl.*`` and ``bench.*`` events as `progtrace.read`
    gives them, each with ``args``: the keywords its annotation was handed
    (an event's stats), {} for a program that hands none."""
    space = progtrace._xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        if plane.name != "/host:CPU":
            continue
        metas, stat_names = plane.event_metadata, plane.stat_metadata
        for line in plane.lines:
            t_line = float(line.timestamp_ns)
            for ev in line.events:
                name = metas[ev.metadata_id].name
                if not name.startswith(("dl.", "bench.")):
                    continue
                args = {}
                for st in ev.stats:
                    kind = st.WhichOneof("value")
                    value = getattr(st, kind) if kind else None
                    if kind == "ref_value":   # a string, kept once a plane
                        value = stat_names[value].name
                    args[stat_names[st.metadata_id].name] = value
                out.append({"name": name, "start": t_line + ev.offset_ps / 1e3,
                            "dur": ev.duration_ps / 1e3, "thread": line.name,
                            "args": args})
    return out


def read(path: str) -> dict:
    """`progtrace.read`'s parse with the host events' ``args``; what
    `progtrace.record_stretch` writes of it keeps them."""
    trace = progtrace.read(path)
    trace["host"] = host_args(path)
    return trace


def _window(trace: dict, window):
    if window is not None:
        return window
    marks = [h for h in trace["host"] if h["name"] == progtrace.WINDOW_MARK]
    if marks:
        return marks[0]["start"], marks[0]["start"] + marks[0]["dur"]
    ops = [e for c in trace["device"].values() for e in c["ops"]]
    return min(e["start"] for e in ops), max(e["start"] + e["dur"] for e in ops)


def join(trace: dict, window: tuple[float, float] | None = None) -> dict | None:
    """Executions, records and idle gaps of the stretch, joined by the step's
    number. None where the trace has no device plane or nothing ran.

    ``executions``: the pipelined step programs' executions on the first chip
    in the order they ran, each ``start, dur, family, device_class`` (the
    ``dlstep.*`` class most of its operations carry), ``step`` (None where the
    trace holds no ``dl.loop.dispatch`` with a step: a program from before
    them), ``record`` (its ``dl.loop.stream``'s args, None where that span
    lies outside the trace) and ``whole`` (inside the window). ``gaps``: the
    window's idle stretches of GAP_NS or more. ``largest_gap_ms``: the longest
    of ANY length. The counts say how exact the join was."""
    chips = trace["device"]
    if not chips:
        return None
    chip = chips[min(chips)]
    if not chip["ops"]:
        return None
    w0, w1 = _window(trace, window)
    execs = sorted(
        ({"start": m["start"], "dur": m["dur"], "family": program_family(m["name"]),
          "classes": Counter(), "step": None, "record": None, "dry": None,
          "whole": m["start"] >= w0 and m["start"] + m["dur"] <= w1}
         for m in chip["modules"] if program_family(m["name"]) in PIPELINED),
        key=lambda e: e["start"])
    starts = [e["start"] for e in execs]
    spans = []
    class_of = getattr(names, "step_class_of", None)
    seen: dict = {}   # op_name -> class: a few hundred names, 1e5 events
    for e in chip["ops"]:
        s, t = max(e["start"], w0), min(e["start"] + e["dur"], w1)
        if t > s:
            spans.append((s, t))
        if e["opcode"] in CONTAINERS or class_of is None:
            continue
        op_name = e["op_name"]
        if op_name not in seen:
            seen[op_name] = class_of(op_name)
        cls = seen[op_name]
        if cls:
            i = bisect.bisect_right(starts, e["start"]) - 1
            if i >= 0 and e["start"] < execs[i]["start"] + execs[i]["dur"]:
                execs[i]["classes"][cls] += 1
    for ex in execs:
        ex["device_class"] = ex["classes"].most_common(1)[0][0] if ex["classes"] else None
        del ex["classes"]

    host = sorted(trace["host"], key=lambda h: h["start"])
    dispatches = [h for h in host if h["name"] == DISPATCH and "step" in h.get("args", {})]
    records = {h["args"]["step"]: h["args"] for h in host
               if h["name"] == STREAM and "interval_s" in h.get("args", {})}
    if dispatches and execs:
        # the first execution that starts after the first dispatch in the
        # trace opened is that dispatch's; the rest count on from it
        first = dispatches[0]
        i0 = bisect.bisect_left(starts, first["start"])
        by_step = {d["args"]["step"]: d for d in dispatches}
        for i, ex in enumerate(execs):
            ex["step"] = first["args"]["step"] + i - i0
            ex["record"] = records.get(ex["step"])
            d = by_step.get(ex["step"])
            ex["dry"] = None if d is None else bool(d["args"].get("dry"))
            ex["dispatched"] = None if d is None else d["start"]

    whole = [ex for ex in execs if ex["whole"]]
    matched = [ex for ex in whole if ex["record"] is not None]
    loop = [h for h in host if h["name"].startswith("dl.loop.")]
    loop_starts = [h["start"] for h in loop]
    ends = [ex["start"] + ex["dur"] for ex in execs]
    all_gaps = _idle_gaps(spans, w0, w1)
    gaps = []
    for g0, g1 in all_gaps:
        if g1 - g0 < GAP_NS:
            continue
        label = NO_SPAN
        i = bisect.bisect_right(loop_starts, g0) - 1
        if i >= 0 and g0 < loop[i]["start"] + loop[i]["dur"]:
            label = loop[i]["name"]
        # the last execution that had ended when the gap began and the first
        # to start once it was over; one between them spans the gap
        b = bisect.bisect_right(ends, g0 + 1e3) - 1
        a = bisect.bisect_left(starts, g1 - 1e3)
        inside = a - b == 2
        after = execs[b + 1] if inside else execs[a] if a < len(execs) else None
        gaps.append({
            "start_ms": (g0 - w0) / 1e6, "ms": (g1 - g0) / 1e6, "span": label,
            "before": execs[b] if b >= 0 else None, "after": after, "inside": inside,
            "dry": None if inside or after is None else after["dry"],
        })
    idle_before = defaultdict(float)   # execution -> ms idle just before it, of any length
    for g0, g1 in all_gaps:
        a = bisect.bisect_left(starts, g1 - 1e3)
        if a < len(execs) and bisect.bisect_right(ends, g0 + 1e3) == a:
            idle_before[a] += (g1 - g0) / 1e6
    dry = [i for i, ex in enumerate(execs) if ex["whole"] and ex["dry"]]
    dry_gap_ms = [idle_before.get(i, 0.0) for i in dry]
    return {
        "window_s": (w1 - w0) / 1e9, "executions": execs, "gaps": gaps,
        "largest_gap_ms": max(((g1 - g0) / 1e6 for g0, g1 in all_gaps), default=0.0),
        "whole": len(whole), "matched": len(matched),
        "mismatched": sum(1 for ex in matched if ex["record"]["class"] != ex["device_class"]),
        # an execution cannot start before its dispatch opened
        "out_of_order": sum(1 for ex in whole if ex.get("dispatched") is not None
                            and ex["start"] < ex["dispatched"]),
        "interval_s": sum(ex["record"]["interval_s"] for ex in matched),
        "stretch_s": ((matched[-1]["start"] + matched[-1]["dur"] - matched[0]["start"]) / 1e9
                      if matched else 0.0),
        "dry": len(dry),
        "dry_with_gap": sum(1 for ms in dry_gap_ms if ms > 0.0),
        "dry_s": sum((execs[i]["record"] or {}).get("dry_s", 0.0) for i in dry),
        "dry_gap_s": sum(dry_gap_ms) / 1e3,
        "gaps_dry": sum(1 for g in gaps if g["dry"]),
        "gaps_queued": sum(1 for g in gaps if g["dry"] is False),
    }


def largest_gap_ms(ctx) -> float | None:
    red = for_ctx(ctx)
    return None if red["join"] is None else red["join"]["largest_gap_ms"]


# -- one table a process ---------------------------------------------------------

def for_ctx(ctx) -> dict:
    """``{"rows": the window's records or None, "join": the traced stretch's
    join or None}``, computed and logged once a run. The join is a table for
    a reader of the log and one metric: a trace it cannot digest is said on
    stderr and costs the run's result line nothing."""
    if hasattr(ctx, "_steplog"):
        return ctx._steplog
    red = ctx._steplog = {"rows": window_rows(ctx), "join": None}
    log_rows(red["rows"], ctx.counters, ctx.lanes, ctx.t_open)
    path = progtrace.newest_trace() if getattr(ctx, "trace", None) is not None else None
    if path is not None:
        t0 = time.monotonic()
        try:
            red["join"] = join(read(path))
        except Exception:  # noqa: BLE001 - the boundary named above
            import traceback

            print(f"[steplog] {path}: not joined\n{traceback.format_exc()}",
                  file=sys.stderr, flush=True)
        else:
            print(f"[steplog] {path}: read and joined in {time.monotonic() - t0:.2f} s",
                  file=sys.stderr, flush=True)
            log_join(red["join"])
    return red


def _step_text(ex) -> str:
    if ex is None:
        return "none in the trace"
    rec = ex["record"] or {}
    text = ("" if ex["step"] is None else f"step {ex['step']} ") + (ex["device_class"] or ex["family"])
    if rec.get("chunk"):
        text += f" ({rec['chunk']} at {rec['p_start']}{', its last' if rec.get('final') else ''})"
    return text


def log_rows(rows, counters=None, lanes=None, t_open=0.0, out=sys.stderr) -> None:
    def p(msg):
        print(f"[steplog] {msg}", file=out, flush=True)

    if rows is None:
        p("no request keeps a record of its chunks: a program from before them")
    else:
        fused = fused_rows(rows)
        p(f"the window's steps that carried a chunk: {len(rows)} ({len(fused)} fused)")
        p(f"{'class':<26}{'steps':>6}{'median ms':>11}{'p90':>10}{'largest':>10}"
          f"{'wait ms':>10}{'host ms':>10}{'lanes':>7}{'dry':>5}")
        for d in class_table(rows):
            p(f"{d['class']:<26}{d['steps']:>6}{d['median_ms']:>11.3f}{d['p90_ms']:>10.3f}"
              f"{d['largest_ms']:>10.3f}{d['wait_ms']:>10.3f}{d['host_ms']:>10.3f}"
              f"{d['lanes']:>7g}{d['dry']:>5}")
        late = late_rows(rows)
        p(f"late (over {LATE_FACTOR} medians of their class within {BAND} start positions): "
          f"{len(late)} of {len(fused)} fused steps")
        for r, over, wait, host in late[:12]:
            p(f"    {r.at - t_open:7.3f} s into the window, step {r.step} {r.cls} {r.chunk} at "
              f"{r.p_start}: {1e3 * r.interval_s:.3f} ms, {1e3 * over:+.3f} over its like "
              f"(wait {1e3 * wait:+.3f}, host {1e3 * host:+.3f}){', dry' if r.dry else ''}")
    if counters and counters.get("pipeline_dry_dispatches") is not None:
        steps = counters.get("pipeline_dispatches", 0)
        p(f"whole window: {counters['pipeline_dry_dispatches']} dry of {steps} dispatches, "
          f"pipeline_dry_s {counters.get('pipeline_dry_s', 0.0):.6f}; live lanes a step "
          f"{counters.get('live_lane_steps', 0) / steps if steps else 0.0:.2f}"
          + (f" of {lanes}" if lanes else ""))


def log_join(j: dict | None, out=sys.stderr) -> None:
    def p(msg):
        print(f"[steplog] {msg}", file=out, flush=True)

    if j is None:
        p("nothing to join: no device plane, or nothing ran on it")
        return
    p(f"traced stretch {j['window_s']:.4f} s: {j['whole']} whole executions, {j['matched']} "
      f"matched to a step record, class mismatches {j['mismatched']}, executions that started "
      f"before their dispatch {j['out_of_order']}; matched interval_s sum {j['interval_s']:.4f} s "
      f"over {j['stretch_s']:.4f} s from the first matched execution to the last")
    p(f"dry dispatches in the stretch: {j['dry']}, with an idle gap before their execution "
      f"{j['dry_with_gap']}; their dry_s {1e3 * j['dry_s']:.3f} ms over gaps of "
      f"{1e3 * j['dry_gap_s']:.3f} ms; largest single gap {j['largest_gap_ms']:.3f} ms")
    p(f"idle gaps of {GAP_NS / 1e6:g} ms or more: {len(j['gaps'])} "
      f"({j['gaps_dry']} at a dry dispatch, {j['gaps_queued']} {QUEUED})")
    for g in j["gaps"][:16]:
        where = "inside" if g["inside"] else "before"
        verdict = ""
        if g["dry"]:
            # dry_s runs from the readback before the dispatch: a gap longer
            # than that began while the host was still inside the readback
            dry_ms = 1e3 * ((g["after"]["record"] or {}).get("dry_s") or 0.0)
            verdict = (f", the host had seen the device dry (dry_s {dry_ms:.3f} ms"
                       + (": the readback before it returned late)" if dry_ms < g["ms"] else ")"))
        elif g["dry"] is False:
            verdict = f", {QUEUED}"
        p(f"    at {g['start_ms']:9.3f} ms, {g['ms']:8.3f} ms under {g['span']}: after "
          f"{_step_text(g['before'])}, {where} {_step_text(g['after'])}{verdict}")


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="print the join of one trace")
    ap.add_argument("trace", nargs="?", default=None,
                    help=".xplane.pb, or a stretch recorded from `read`'s parse by "
                         "progtrace.record_stretch (.json.gz); default: the newest traced run's")
    ap.add_argument("--record", default=None, help="write a stretch to this .json.gz")
    ap.add_argument("--ms", type=float, default=300.0, help="length of the recorded stretch")
    args = ap.parse_args(argv)
    path = args.trace or progtrace.newest_trace()
    if path is None:
        print("no trace under .bench_out/trace/", file=sys.stderr)
        return 1
    if path.endswith(".json.gz"):
        log_join(join(*progtrace.load_stretch(path)), out=sys.stdout)
        return 0
    trace = read(path)
    log_join(join(trace), out=sys.stdout)
    if args.record:
        w0, w1 = _window(trace, None)
        mods = sorted(m["start"] for c in trace["device"].values() for m in c["modules"])
        t0 = next((s for s in mods if s >= (w0 + w1) / 2), w0)
        t1 = next((s for s in mods if s >= t0 + args.ms * 1e6), w1)
        progtrace.record_stretch(trace, t0, t1, args.record, path)
        print(f"recorded {t1 - t0:.0f} ns to {args.record}; it joins to:")
        log_join(join(*progtrace.load_stretch(args.record)), out=sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
