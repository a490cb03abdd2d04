"""The benchmark's minicpm_sala family, guarded in tier-1 the way
``tests/test_bench_jamba_family.py`` guards its sibling: the cases of
``benchmarks/tests/test_minicpm_sala_family.py`` imported by path and
re-exported, nothing copied."""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
_spec = importlib.util.spec_from_file_location(
    "bench_sala_family_cases",
    os.path.join(BENCH_DIR, "tests", "test_minicpm_sala_family.py"),
)
_cases = importlib.util.module_from_spec(_spec)
_path = list(sys.path)
sys.path[:0] = [p for p in (BENCH_DIR, ROOT) if p not in sys.path]
try:
    _spec.loader.exec_module(_cases)
finally:
    sys.path[:] = _path

globals().update(
    {name: obj for name, obj in vars(_cases).items()
     if name.startswith("test_") or name in ("cfg", "family", "sample")}
)
