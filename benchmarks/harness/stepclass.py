"""The traced run read by what each step program says it is.

`progtrace.py` keys an execution by its program's family
(``jit__decode_prefill(<hash>)`` -> ``_decode_prefill``): the four prefill
buckets are one program there, and a median over them moves when the mix of
buckets moves. Since PR 37 every operation of a step program carries, beside
its ``dl.*`` scope, the program's CLASS (``dlstep.fused.b1024``: the family
and the static width it was compiled for) and the HALF of the step it belongs
to (``dlhalf.prefill``: the admitted chunk; ``dlhalf.decode``: the decode
batch; neither: what joins them), as components of its ``op_name``
(`distributed_llama_multiusers_tpu/telemetry/names.py`). This module reduces
the same parse (`progtrace.read`) by those: per class the executions, their
median duration, and their operations' self time by half and by (half,
scope). A program from before the names leaves nothing to read, and every
reader built on this module then returns None.
"""

from __future__ import annotations

import bisect
import os
import sys
import time
from collections import Counter, defaultdict

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
if __package__ in (None, ""):  # by hand: python3 benchmarks/harness/stepclass.py
    sys.path[:0] = [BENCH_DIR, ROOT]

from harness import progtrace  # noqa: E402
from harness.progtrace import names  # noqa: E402
from harness.stats import percentile, union_seconds  # noqa: E402
from harness.xplane import CONTAINERS, program_family  # noqa: E402

PREFILL, DECODE, JOIN, NO_OP_NAME = "prefill", "decode", "join", "no_op_name"
HALF_KEYS = (PREFILL, DECODE, JOIN, NO_OP_NAME)
UNCLASSED = "unclassed"
FUSED = "dlstep.fused."   # the classes of _decode_prefill, one a bucket


def known() -> bool:
    """Whether the program this process imports declares classes and halves."""
    return names is not None and hasattr(names, "step_class_of")


def labels(op_name: str) -> tuple:
    """(half, deepest ``dl.*`` scope, class) of an ``op_name``. In a step
    program and under neither half: what joins them. No class: no ``op_name``
    of the program's (none at all on a copy or a convert XLA added; a
    parameter's own name on a copy of it)."""
    cls = names.step_class_of(op_name)
    half = names.half_of(op_name)
    if half:
        half = half[len(names.HALF_PREFIX):]
    else:
        half = JOIN if cls else NO_OP_NAME
    return half, names.scope_of(op_name), cls


def reduce(trace: dict, window: tuple[float, float] | None = None) -> dict | None:
    """Per class of step program, over the WHOLE executions inside the window
    (as `progtrace.reduce` takes them): how many, the median duration of the
    ``XLA Modules`` event, the medians of the operations' self time by half
    and by (half, deepest ``dl.*`` scope), and the class's share of the
    device's busy time. ``while`` / ``conditional`` / ``call`` span their
    bodies' events and are left out, as there. An execution of a step program
    none of whose operations carries a class is ``unclassed:<family>``, and
    ``unclassed`` counts them; a program that is no step program (a lane
    copy) is ``other:<family>``. Several chips: medians over every chip's
    executions, counts and seconds averaged. None where the program's names
    are unknown, the trace has no device plane or nothing ran in the window."""
    chips = trace["device"]
    if not known() or not chips:
        return None
    if window is None:
        marks = [h for h in trace["host"] if h["name"] == progtrace.WINDOW_MARK]
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

    busy = 0.0
    half_s = dict.fromkeys(HALF_KEYS, 0.0)   # every operation in the window
    seen: dict = {}   # op_name -> its labels: a few hundred names, 1e5 events
    execs = []
    for chip in chips.values():
        clipped = [(e, c) for e in chip["ops"] if (c := clip(e))]
        busy += union_seconds([c for _, c in clipped]) / 1e9 / n
        modules = sorted(chip["modules"], key=lambda m: m["start"])
        starts = [m["start"] for m in modules]
        whole = {i: {"family": program_family(m["name"]), "ns": m["dur"],
                     "half": defaultdict(float), "pairs": defaultdict(float),
                     "classes": Counter(), "spans": []}
                 for i, m in enumerate(modules)
                 if m["start"] >= w0 and m["start"] + m["dur"] <= w1}
        for e, c in clipped:
            if e["opcode"] in CONTAINERS:
                continue
            op_name = e["op_name"]
            if op_name not in seen:
                seen[op_name] = labels(op_name)
            half, scope, cls = seen[op_name]
            half_s[half] += (c[1] - c[0]) / 1e9 / n
            i = bisect.bisect_right(starts, e["start"]) - 1
            ex = whole.get(i)
            if ex is None or e["start"] >= modules[i]["start"] + modules[i]["dur"]:
                continue
            ex["half"][half] += c[1] - c[0]
            ex["pairs"][half, scope] += c[1] - c[0]
            ex["spans"].append(c)
            if cls:
                ex["classes"][cls] += 1
        execs.extend(whole.values())
    if not busy:  # a device plane on which nothing ran inside the window
        return None

    by_class = defaultdict(list)
    unclassed = mixed = 0
    for ex in execs:
        if ex["classes"]:
            cls = ex["classes"].most_common(1)[0][0]
            mixed += len(ex["classes"]) > 1
        elif ex["family"] in names.STEP_PROGRAMS:
            cls = f"{UNCLASSED}:{ex['family']}"
            unclassed += 1
        else:
            cls = f"other:{ex['family']}"
        by_class[cls].append(ex)
    classes = {}
    for cls, exs in by_class.items():
        pairs = {k for ex in exs for k in ex["pairs"]}
        per = [{"ms": ex["ns"] / 1e6,
                "busy_ms": union_seconds(ex["spans"]) / 1e6,
                **{h: ex["half"].get(h, 0.0) / 1e6 for h in HALF_KEYS}} for ex in exs]
        classes[cls] = {
            "executions": len(exs) / n,
            "median_ms": percentile([p["ms"] for p in per], 50),
            # the time some operation ran (overlaps once): duration less idle
            "busy_ms": percentile([p["busy_ms"] for p in per], 50),
            "half_ms": {h: percentile([p[h] for p in per], 50) for h in HALF_KEYS},
            "pair_ms": {k: percentile([ex["pairs"].get(k, 0.0) / 1e6 for ex in exs], 50)
                        for k in pairs},
            "share_of_busy": 100.0 * sum(sum(ex["half"].values()) for ex in exs) / 1e9 / n / busy,
            "per_execution": per,
        }
    return {"chips": n, "window_s": (w1 - w0) / 1e9, "busy_s": busy,
            "half_s": half_s, "classes": classes,
            "unclassed": unclassed / n, "mixed": mixed / n}


# -- what the metrics ask -----------------------------------------------------

def class_median_ms(red: dict | None, cls: str) -> float | None:
    """Median duration of the whole executions of ``cls``; None where the
    stretch holds none."""
    return ((red or {}).get("classes", {}).get(cls) or {}).get("median_ms")


def fused_half_ms(red: dict | None, half: str) -> float | None:
    """Median, over the whole executions of EVERY fused class, of the time
    under ``half``."""
    per = [p[half] for cls, d in (red or {}).get("classes", {}).items()
           if cls.startswith(FUSED) for p in d["per_execution"]]
    return percentile(per, 50)


def half_share(red: dict | None, half: str) -> float | None:
    """Percent of the device's busy time in the stretch under ``half``,
    whatever the class."""
    if not red or not red["busy_s"]:
        return None
    return 100.0 * red["half_s"][half] / red["busy_s"]


# -- one parse a process --------------------------------------------------------

_memo: dict = {}


def for_ctx(ctx) -> dict | None:
    """The reduction of the run's own trace (`progtrace.newest_trace`); None
    for an untraced run or a program without the names. `progtrace.for_ctx`
    keeps its reduction and not the parse, so the file is read once more
    here (well under a second), once a process."""
    if getattr(ctx, "trace", None) is None or not known():
        return None
    path = progtrace.newest_trace()
    if path is None:
        return None
    if path not in _memo:
        t0 = time.monotonic()
        trace = progtrace.read(path)
        t1 = time.monotonic()
        red = _memo[path] = reduce(trace)
        print(f"[stepclass] {path}: read in {t1 - t0:.2f} s, reduced in "
              f"{time.monotonic() - t1:.2f} s", file=sys.stderr, flush=True)
        log_table(red)
    return _memo[path]


def log_table(red: dict | None, out=sys.stderr) -> None:
    def p(msg):
        print(f"[stepclass] {msg}", file=out, flush=True)

    if red is None:
        p("nothing to read: no device plane, or a program without dlstep.* names")
        return
    p(f"window {red['window_s']:.4f} s, busy {red['busy_s']:.4f} s; of busy: "
      + ", ".join(f"{h} {100 * red['half_s'][h] / red['busy_s']:.2f} %" for h in HALF_KEYS)
      + f"; unclassed executions of step programs {red['unclassed']:g}, "
      f"with operations of two classes {red['mixed']:g}")
    p(f"{'class':<28}{'execs':>7}{'median ms':>11}{'busy ms':>10}{'prefill':>10}"
      f"{'decode':>10}{'join':>9}{'no op_name':>11}{'% of busy':>10}   largest (half, scope) ms")
    for cls, d in sorted(red["classes"].items(), key=lambda kv: -kv[1]["share_of_busy"]):
        top = sorted(d["pair_ms"].items(), key=lambda kv: -kv[1])[:3]
        p(f"{cls:<28}{d['executions']:>7g}{d['median_ms']:>11.3f}{d['busy_ms']:>10.3f}"
          + "".join(f"{d['half_ms'][h]:>{w}.3f}" for h, w in zip(HALF_KEYS, (10, 10, 9, 11)))
          + f"{d['share_of_busy']:>10.2f}   "
          + ", ".join(f"{h}/{s or 'unscoped'} {ms:.3f}" for (h, s), ms in top))


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="print the per-class table of one trace")
    ap.add_argument("trace", nargs="?", default=None,
                    help=".xplane.pb, or a stretch recorded by progtrace.py --record "
                         "(.json.gz); default: the newest traced run's")
    args = ap.parse_args(argv)
    path = args.trace or progtrace.newest_trace()
    if path is None:
        print("no trace under .bench_out/trace/", file=sys.stderr)
        return 1
    if path.endswith(".json.gz"):
        log_table(reduce(*progtrace.load_stretch(path)), out=sys.stdout)
    else:
        log_table(reduce(progtrace.read(path)), out=sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
