"""Cells, configurations, families and traffic mixes, found by the names in
BENCHMARK.json.

A cell is one entry of ``workloads``: it names a configuration (a file under
``benchmarks/configs/``, given by the configuration's ``file``) and a traffic
mix (``benchmarks/traffic/<traffic>.json``). A configuration file names its
architecture's family module (``benchmarks/families/<family>.py``), which is
all the harness knows of a model. Nothing here knows a cell, a configuration,
a family or a mix by name: adding one adds files and an entry, and edits no
file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

# the family of a configuration file that names none
DEFAULT_FAMILY = "llama"
# what a family module exports: all the harness asks of an architecture
FAMILY_EXPORTS = ("program_config", "device_weights", "assemble_params",
                  "reference_logits", "lane_state_rel_err")


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(
        f"no workload {name!r} in BENCHMARK.json "
        f"(have: {[c['name'] for c in bench['workloads']]})"
    )


def load_config_file(bench: dict, config_name: str, root: str = ROOT) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == config_name:
            with open(os.path.join(root, entry["file"])) as f:
                return json.load(f)
    raise SystemExit(f"no configuration {config_name!r} in BENCHMARK.json")


def load_traffic_file(traffic_name: str, traffic_dir: str | None = None) -> dict:
    """``traffic_dir`` (relative to the root) is a rehearsal file's own."""
    base = os.path.join(ROOT, traffic_dir) if traffic_dir else os.path.join(BENCH_DIR, "traffic")
    path = os.path.join(base, traffic_name + ".json")
    with open(path) as f:
        return json.load(f)


def cell_metrics(bench: dict, cell_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    that list it under ``workloads``, and those that list nothing where the
    cell reports what they move (every cell, for an end-to-end metric)."""
    e2e = [
        m for m in bench["end_to_end"]
        if "workloads" not in m or cell_name in m["workloads"]
    ]
    if kind == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [
        m for m in bench["per_layer"]
        if (cell_name in m["workloads"] if "workloads" in m
            else m["moves"] in reported)
    ]


def load_module(path: str, name: str):
    """A benchmark file (a family, a metric's reader) as a module, by path."""
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(cfg: dict, families_dir: str | None = None):
    """The module of the configuration's ``family``, loaded by its path:
    ``<families_dir>/<family>.py`` where a benchmark file gives that directory
    (relative to the root, as the rehearsal's does) and the file is there,
    else ``benchmarks/families/<family>.py``. It exports FAMILY_EXPORTS:

    - ``program_config(cfg)``: the object ``InferenceEngine`` takes; the
      harness reads its ``vocab_size`` and ``seq_len`` and nothing else;
    - ``device_weights(config, seed, dtype)``: name -> device array, made in
      one jitted program from the seed (any whole number up to 2**32);
    - ``assemble_params(config, tensors)``: the program's parameter tree
      around those arrays;
    - ``reference_logits(cfg, tensors, tokens, row_positions, lossy=None)``:
      float32 logits ``[B, R, vocab]`` of the plain reference, which imports
      nothing of the program; ``lossy`` names a type the control rounds to;
    - ``lane_state_rel_err(engine, lane_x, lane_y, n)``: both lanes have
      absorbed the same ``n`` tokens; the largest relative difference
      between them over every kind of per-lane state, rows ``[0, n)`` of
      what is kept by position and the whole of what is not, or None."""
    name = cfg.get("family", DEFAULT_FAMILY)
    dirs = [os.path.join(BENCH_DIR, "families")]
    if families_dir:
        dirs.insert(0, os.path.join(ROOT, families_dir))
    path = next((p for d in dirs if os.path.exists(p := os.path.join(d, name + ".py"))), None)
    if path is None:
        raise SystemExit(f"no family {name!r}: no {name}.py under {dirs}")
    mod = load_module(path, "bench_family_" + name)
    missing = [f for f in FAMILY_EXPORTS if not callable(getattr(mod, f, None))]
    if missing:
        raise SystemExit(f"family {name!r} ({path}) does not export {', '.join(missing)}")
    return mod
