"""Cells, configurations and traffic mixes, found by the names in BENCHMARK.json.

A cell is one entry of ``workloads``: it names a configuration (a file under
``benchmarks/configs/``, given by the configuration's ``file``) and a traffic
mix (``benchmarks/traffic/<traffic>.json``). Nothing here knows a cell, a
configuration or a mix by name: adding one adds files and an entry, and edits
no file that is there.
"""

from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

# activation functions the program's LlamaConfig knows (formats/model_file.py
# HiddenAct): the published ``hidden_act`` string -> that enum's value
_HIDDEN_ACT = {"gelu": 0, "silu": 1}


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


def llama_config(cfg: dict):
    """The program's LlamaConfig from a published ``config.json``'s keys, as
    the configuration file holds them (``max_position_embeddings`` is the
    serving context the file states)."""
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    head = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    if head * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise SystemExit(
            "the program derives the head size as hidden_size / heads; "
            f"head_dim {head} x {cfg['num_attention_heads']} heads is not "
            f"hidden_size {cfg['hidden_size']}"
        )
    return LlamaConfig(
        dim=cfg["hidden_size"],
        hidden_dim=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        vocab_size=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"],
        hidden_act=_HIDDEN_ACT[cfg["hidden_act"]],
        rope_theta=float(cfg["rope_theta"]),
        norm_epsilon=float(cfg["rms_norm_eps"]),
        qkv_bias=1 if cfg.get("attention_bias") else 0,
    )
