"""The benchmark's per-class reduction of a device trace, guarded in tier-1.

``benchmarks/tests/test_stepclass.py`` holds the cases (hand-counted events:
two classes of fused step and a decode step, both halves, a join, an operation
without ``op_name``, a ``while`` spanning its body, an execution clipped by the
window, two chips averaged; and a stretch recorded from a real traced run of
the change). They run with the harness's own tests, which the tier-1 command
does not reach; the reduction reads names THIS package declares
(``telemetry/names.py``: ``step_class_of``, ``half_of``, ``STEP_PROGRAMS``),
so a change of the program that breaks it would otherwise first show as a
metric gone silent on the chip. This file imports that module by path and
re-exports its cases, as ``tests/test_bench_family_seam.py`` does for the
family seam: nothing is copied.
"""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
_spec = importlib.util.spec_from_file_location(
    "bench_stepclass_cases",
    os.path.join(BENCH_DIR, "tests", "test_stepclass.py"),
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
     if name.startswith("test_") or name in ("by_hand", "recorded")}
)
