"""A later PR adds a cell, a configuration, a traffic mix and a per-layer
metric as files and entries, and edits no file that is there."""
import hashlib
import json
import os
import shutil

from harness.cells import BENCH_DIR, ROOT
from test_rehearsal import run_cell


def _hashes(base):
    out = {}
    for d, _dirs, files in os.walk(base):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, base)] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return out


def test_throw_away_fourth_cell(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, root / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "distributed_llama_multiusers_tpu"),
               root / "distributed_llama_multiusers_tpu")
    before = _hashes(root / "benchmarks")

    # the three new files ...
    reh = root / "benchmarks" / "tests" / "rehearsal"
    cfg = json.load(open(reh / "configs" / "tiny.json"))
    cfg.update(num_hidden_layers=3, num_key_value_heads=4)
    json.dump(cfg, open(reh / "configs" / "tiny_deep.json", "w"))
    shutil.copy(reh / "traffic" / "tiny_extract_greedy.json", reh / "traffic" / "tiny_fourth.json")
    (root / "benchmarks" / "metrics" / "fused_steps.train.py").write_text(
        '"""Fused prefill + decode dispatches inside the window."""\n\n\n'
        'def read(ctx):\n    return ctx.counters.get("fused_steps")\n'
    )
    (root / "benchmarks" / "metrics" / "spec_tokens_per_verify.py").write_text(
        '"""Tokens emitted per drafted lane and verify step (1 = no draft accepted)."""\n\n\n'
        'def read(ctx):\n    steps = ctx.counters.get("spec_lane_steps")\n'
        '    return ctx.counters["spec_emitted"] / steps if steps else None\n'
    )
    # ... and the entries, in a BENCHMARK.json of the PR's own
    bench = json.load(open(reh / "BENCHMARK.json"))
    bench["configs"].append({"name": "tiny_deep", "source": "none", "reduced": [], "why": "toy",
                             "file": "benchmarks/tests/rehearsal/configs/tiny_deep.json"})
    bench["workloads"].append({"name": "tiny_deep_fourth", "config": "tiny_deep",
                               "traffic": "tiny_fourth", "chips": 1, "why": "throw-away"})
    for m in bench["end_to_end"]:
        if m["name"] == "ttft_p50_ms":
            m["workloads"].append("tiny_deep_fourth")
    bench["per_layer"] += [
        {"name": "fused_steps.train", "unit": "count", "better": "higher", "source": "program_counter",
         "layer": "step scheduling", "moves": "ttft_p50_ms", "workloads": ["tiny_deep_fourth"]},
        {"name": "spec_tokens_per_verify", "unit": "tokens", "better": "higher",
         "source": "program_counter", "layer": "speculation", "moves": "itl_p50_ms",
         "workloads": ["tiny_deep_fourth"]},
    ]
    new_bench = root / "BENCHMARK.json"
    json.dump(bench, open(new_bench, "w"))

    p = run_cell("tiny_deep_fourth", 4, trace=1, bench_file=str(new_bench), root=str(root))
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    vals = res["rehearsal_values"]
    assert vals["fused_steps.train"]["value"] > 0
    # a greedy mix whose prompts repeat their own n-grams: the drafter works,
    # the verify programs were warmed, and nothing compiled in the window
    assert vals["spec_drafted_lane_steps"]["value"] > 0
    assert vals["spec_tokens_per_verify"]["value"] >= 1.0
    assert vals["jit_compiles_after_warmup"]["value"] == 0

    after = _hashes(root / "benchmarks")
    assert {k: v for k, v in after.items() if k in before} == before  # nothing edited
    assert sorted(set(after) - set(before)) == [
        "metrics/fused_steps.train.py",
        "metrics/spec_tokens_per_verify.py",
        "tests/rehearsal/configs/tiny_deep.json",
        "tests/rehearsal/traffic/tiny_fourth.json",
    ]
