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
