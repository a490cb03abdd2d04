"""The benchmark's family seam, guarded in tier-1.

``benchmarks/tests/test_family_seam.py`` holds the five cases (a seed gives
the planes and the reference logits it gave before the seam was cut, a
family that lacks a function is refused by name, a second family is compared
with its own reference). They run with the harness's own tests, which the
tier-1 command does not reach; a change of the program that breaks what the
harness builds on would otherwise first show on the chip. This file imports
that module by path and re-exports its cases: nothing is copied, and nothing
under ``benchmarks/`` is edited.
"""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
_spec = importlib.util.spec_from_file_location(
    "bench_family_seam_cases",
    os.path.join(BENCH_DIR, "tests", "test_family_seam.py"),
)
_cases = importlib.util.module_from_spec(_spec)
# what benchmarks/tests/conftest.py does for the harness's own run: its
# modules import each other as `harness`, `control`, `families`. Only for
# the import: they stay in sys.modules, and the path goes back as it was,
# so those top-level names resolve as ever for the rest of the session.
_path = list(sys.path)
sys.path[:0] = [p for p in (BENCH_DIR, ROOT) if p not in sys.path]
try:
    _spec.loader.exec_module(_cases)
finally:
    sys.path[:] = _path

globals().update(
    {name: obj for name, obj in vars(_cases).items() if name.startswith("test_")}
)


def test_both_configurations_load_the_llama_family_by_default():
    """The case of that name, over the configurations it was written for. It
    walks every entry of BENCHMARK.json and asserts that none names a family;
    since PR 33 one does (``kanana-2-30b-a3b``: ``deepseek_v3``), and a file
    under ``benchmarks/`` is a benchmark PR's to edit (PERF.md, open
    questions). Held here: a file that names no family loads the Llama one,
    and every entry loads a module that exports the seam."""
    cells = _cases.cells
    bench = cells.load_benchmark()
    default = 0
    for entry in bench["configs"]:
        cfg = cells.load_config_file(bench, entry["name"])
        family = cells.load_family(cfg)
        assert family.__file__ == os.path.join(
            BENCH_DIR, "families", cfg.get("family", "llama") + ".py")
        assert all(callable(getattr(family, f)) for f in _cases.FAMILY_EXPORTS)
        config = family.program_config(cfg)
        assert (config.vocab_size, config.seq_len) == (
            cfg["vocab_size"], cfg["max_position_embeddings"])
        default += "family" not in cfg
    assert default == 2  # mistral-7b-v0.3 and qwen2.5-7b, as they were
