"""The command end to end on the CPU at a tiny size: counts only."""
import json
import os
import subprocess
import sys

import pytest

from harness.cells import BENCH_DIR, ROOT

REHEARSAL = os.path.join(BENCH_DIR, "tests", "rehearsal", "BENCHMARK.json")


def run_cell(workload, seed, trace=0, rehearse=True, bench_file=REHEARSAL, root=ROOT):
    cmd = [sys.executable, os.path.join(root, "benchmarks", "run.py"),
           "--workload", workload, "--seed", str(seed), "--seconds", "2",
           "--trace", str(trace)]
    if bench_file:
        cmd += ["--benchmark-file", bench_file]
    if rehearse:
        cmd.append("--rehearse")
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run(cmd, capture_output=True, text=True, env=env, cwd=root, timeout=600)


@pytest.fixture(scope="module")
def runs():
    out = {}
    for workload, seed, trace in [
        ("tiny_saturated", 1, 0), ("tiny_saturated", 3_000_000_001, 0),
        ("tiny_steady", 1, 0), ("tiny_steady", 2, 1), ("tiny_bias_saturated", 5, 1),
    ]:
        p = run_cell(workload, seed, trace)
        assert p.returncode == 0, p.stderr[-3000:]
        out[(workload, seed, trace)] = (json.loads(p.stdout.strip().splitlines()[-1]), p.stderr)
    return out


def test_result_line_is_a_rehearsal_and_never_a_device_number(runs):
    for (workload, _seed, trace), (res, err) in runs.items():
        assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
        assert res["rehearsal"] is True and res["metrics"] == {}
        assert res["device"]["platform"] == "cpu"
        assert res["device"]["memory_peak_bytes"] == "not measured"
        assert res["device"]["busy_s"] == "not measured"
        assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 10
        assert res["compiles_in_window"] == 0
        assert "0 with another count" in err  # every finished request: max_tokens
        assert "prefill_rel_err" in err and "limit" in err
        names = set(res["rehearsal_values"])
        if trace == 0:
            assert {"setup_s", "itl_p50_ms", "itl_p99_ms"} <= names
            assert ("ttft_p50_ms" in names) == (workload == "tiny_steady")
            assert ("tokens_per_s" in names) == (workload != "tiny_steady")
        else:
            assert {"pipeline_flushes", "jit_compiles_after_warmup",
                    "spec_drafted_lane_steps", "stream_overhead_p50_ms"} <= names
            assert ("gen_late_p95_ms" in names) == (workload == "tiny_steady")
            # no device plane in a CPU trace: the device readers find nothing
            assert "decode_step_device_ms" not in names


def test_two_seeds_do_the_same_work(runs):
    a = runs[("tiny_saturated", 1, 0)][0]
    b = runs[("tiny_saturated", 3_000_000_001, 0)][0]
    assert a["schedule"]["digest"] == b["schedule"]["digest"]
    c = runs[("tiny_steady", 1, 0)][0]
    d = runs[("tiny_steady", 2, 1)][0]
    assert c["schedule"] == d["schedule"]  # open loop: the count issued too


def test_no_accelerator_no_result():
    p = run_cell("tiny_saturated", 1, rehearse=False)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "no accelerator" in p.stderr
