"""A later PR adds a cell, a configuration, a traffic mix, a per-layer metric
and a whole architecture (a family module) as files and entries, and edits no
file that is there."""
import hashlib
import json
import os
import shutil

import pytest

from harness.cells import BENCH_DIR, ROOT
from test_rehearsal import run_cell

MOE_FILES = ("tests/rehearsal/families/tiny_moe.py", "tests/rehearsal/configs/tiny_moe.json")


def _hashes(base):
    out = {}
    for d, _dirs, files in os.walk(base):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, base)] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return out


def _checkout(tmp_path):
    """A copy of the benchmark's files as they were before the second family
    came (its module and configuration left out), beside the program."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tiny_moe*"))
    os.symlink(os.path.join(ROOT, "distributed_llama_multiusers_tpu"),
               root / "distributed_llama_multiusers_tpu")
    return root


def test_throw_away_fourth_cell(tmp_path):
    root = _checkout(tmp_path)
    before = _hashes(root / "benchmarks")

    # the three new files ...
    reh = root / "benchmarks" / "tests" / "rehearsal"
    cfg = json.load(open(reh / "configs" / "tiny.json"))
    cfg.update(num_hidden_layers=3, num_key_value_heads=4)
    json.dump(cfg, open(reh / "configs" / "tiny_deep.json", "w"))
    # the greedy mix, denser and with longer answers: at 20 requests a second a
    # 12-token answer is over before the next request is due, every admission
    # finds the lanes idle and no fused step falls inside the window
    mix = json.load(open(reh / "traffic" / "tiny_extract_greedy.json"))
    mix.update(rate_rps=40, max_tokens={"dist": "lognormal", "median": 48, "sigma": 0.1,
                                        "min": 40, "max": 56})
    json.dump(mix, open(reh / "traffic" / "tiny_fourth.json", "w"))
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


@pytest.mark.parametrize("top_k, correct", [(2, True), (1, False)])
def test_a_second_family_comes_as_files(tmp_path, top_k, correct):
    """An architecture the harness never heard of (a sparse mixture of
    experts): its family module, its configuration and its entries are added
    to a tree that has none of them, and the cell runs and is compared with
    the family's own reference. With that reference perturbed (one expert a
    token where the program routes to two) `correct` comes out false."""
    root = _checkout(tmp_path)
    before = _hashes(root / "benchmarks")
    assert not set(MOE_FILES) & set(before)

    for rel in MOE_FILES:
        os.makedirs((root / "benchmarks" / rel).parent, exist_ok=True)
        shutil.copy(os.path.join(BENCH_DIR, rel), root / "benchmarks" / rel)
    if top_k != 2:
        path = root / "benchmarks" / MOE_FILES[0]
        text = path.read_text()
        routed = 'top_k=int(cfg["num_experts_per_tok"])'
        assert text.count(routed) == 1  # the reference's, not the program's
        path.write_text(text.replace(routed, f"top_k={top_k}"))
    bench = json.load(open(root / "benchmarks" / "tests" / "rehearsal" / "BENCHMARK.json"))
    bench["families_dir"] = "benchmarks/tests/rehearsal/families"
    bench["configs"].append({"name": "tiny_moe", "source": "none", "reduced": [], "why": "toy",
                             "file": "benchmarks/" + MOE_FILES[1]})
    bench["workloads"].append({"name": "tiny_moe_saturated", "config": "tiny_moe",
                               "traffic": "tiny_saturated", "chips": 1, "why": "a second family"})
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s":
            m["workloads"].append("tiny_moe_saturated")
    new_bench = root / "BENCHMARK.json"
    json.dump(bench, open(new_bench, "w"))

    p = run_cell("tiny_moe_saturated", 2_900_000_041, bench_file=str(new_bench), root=str(root))
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is correct
    assert res["failed"] == 0 and res["attempted"] > 10 and res["compiles_in_window"] == 0
    compared = res["compared"]
    if correct:
        assert 0 < compared["prefill_rel_err"] < 1e-5 and 0 < compared["decode_rel_err"] < 1e-5
    else:
        assert compared["decode_rel_err"] > 10 * compared["decode_rel_err_limit"]
    # the window's own programs agree with the synchronous ones either way
    assert compared["route_kv_rel_err"] == compared["route_greedy_gap"] == 0.0
    assert "tokens_per_s" in res["rehearsal_values"]

    after = _hashes(root / "benchmarks")
    assert {k: v for k, v in after.items() if k in before} == before  # nothing edited
    assert sorted(set(after) - set(before)) == sorted(MOE_FILES)
