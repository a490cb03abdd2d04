"""The benchmark's reading of the batching loop's step records, guarded in
tier-1.

``benchmarks/tests/test_steplog.py`` holds the cases (hand-counted events:
three dispatch annotations, three executions and one idle gap, under
``loop.wait`` with work queued and at a dry dispatch; an execution from before
the first dispatch; a class mismatch; a program from before the step numbers;
rows of ``req.tel.chunks`` by class and band; counters that stood still; a
stretch recorded from a real traced run of the change; a traced rehearsal of
the whole command on the CPU). They run with the harness's own tests, which
the tier-1 command does not reach; the readers go by what THIS package writes
(``telemetry/spans.py`` ``StepRecord``, the ``dl.loop.*`` annotations'
keywords, three ``EngineStats`` fields), so a change of the program that
breaks them would otherwise first show as a metric gone silent on the chip.
This file imports that module by path and re-exports its cases, as
``tests/test_bench_stepclass.py`` does: nothing is copied.
"""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
_spec = importlib.util.spec_from_file_location(
    "bench_steplog_cases",
    os.path.join(BENCH_DIR, "tests", "test_steplog.py"),
)
_cases = importlib.util.module_from_spec(_spec)
# only for the import (the harness's modules import each other as `harness`):
# they stay in sys.modules, and the path goes back as it was
_path = list(sys.path)
sys.path[:0] = [p for p in (BENCH_DIR, ROOT) if p not in sys.path]
try:
    _spec.loader.exec_module(_cases)
finally:
    sys.path[:] = _path

globals().update(
    {name: obj for name, obj in vars(_cases).items()
     if name.startswith("test_") or name == "recorded"}
)


def test_benchmark_json_lists_every_new_metric_with_its_cells():
    """The case of that name, which holds PR 52's six metrics to the LAST six
    entries of ``per_layer``: a later PR's metrics come after them (PR 54's
    four do: new entries go at the end of their lists), and a file under
    ``benchmarks/`` is a benchmark PR's to edit (PERF.md, open questions).
    Held here: the six are there, in their order and next to one another, with
    the lists the case asks of them."""
    from harness.cells import cell_metrics, load_benchmark

    bench = load_benchmark()
    six = ["top_rung_step_ms", "fused_step_late_share", "dry_dispatch_share",
           "device_starved_share", "lane_fill_share", "device_idle_largest_gap_ms"]
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(six[0])
    assert names[at:at + 6] == six
    new = {m["name"]: m for m in bench["per_layer"][at:at + 6]}
    every = [w["name"] for w in bench["workloads"]]
    saturated = [w for w in every if w.endswith("_saturated")]
    assert new["fused_step_late_share"]["workloads"] == every
    for name in six[2:]:
        assert new[name]["workloads"] == saturated and new[name]["moves"] == "tokens_per_s"
    steady = {m["name"] for m in cell_metrics(bench, "mistral7b_chat_steady", "per_layer")}
    assert steady & set(new) == {"fused_step_late_share"}
