"""The benchmark's solar_open2 family, guarded in tier-1 the way
``tests/test_bench_sala_family.py`` guards its sibling: cases of
``benchmarks/tests/test_solar_open2_family.py`` imported by path and
re-exported, nothing copied. The cases that build an engine or run the
reference once a fault stay with the benchmark's own tests (their ground is
held here by ``tests/test_delta_engine.py``, which compares the engine with the
same reference): the tier-1 run's clock has no room for them twice."""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
_spec = importlib.util.spec_from_file_location(
    "bench_solar_family_cases",
    os.path.join(BENCH_DIR, "tests", "test_solar_open2_family.py"),
)
_cases = importlib.util.module_from_spec(_spec)
_path = list(sys.path)
sys.path[:0] = [p for p in (BENCH_DIR, ROOT) if p not in sys.path]
try:
    _spec.loader.exec_module(_cases)
finally:
    sys.path[:] = _path

BENCH_ONLY = ("test_engine_agrees_with_the_reference_and_the_routes_read_zero",
              "test_every_fault_of_the_family_fails", "test_the_lower_precision_reference_fails",
              "test_a_reference_of_another_model_is_told_apart",
              "test_lane_state_covers_the_planes_the_matrices_and_the_windows")
globals().update(
    {name: obj for name, obj in vars(_cases).items()
     if (name.startswith("test_") and name not in BENCH_ONLY) or name in ("cfg", "family", "seeded", "sample")}
)
