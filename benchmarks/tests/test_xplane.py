"""The reduction from a trace to numbers: on events counted by hand, and on a
stretch recorded from a real TPU trace (tests/data/, my chip run, PR 25)."""
import gzip
import json
import os

import pytest

from harness import xplane
from harness.cells import BENCH_DIR

DATA = os.path.join(BENCH_DIR, "tests", "data")


def test_parse_hlo_event_names():
    p = xplane.parse_hlo_event
    assert p("%copy.200 = bf16[32,16,2048,8,128]{4,3,2,1,0:T(8,128)(2,1)} copy(bf16[32,16,2048,8,128]{4,3,2,1,0} %x)") == (
        "copy.200", "bf16[32,16,2048,8,128]", "copy")
    assert p("%while.7 = (s32[]{:T(128)}, bf16[16,1,4096]{2,0,1:T(8,128)(2,1)S(1)}) while((s32[], bf16[16,1,4096]) %t), condition=%c") == (
        "while.7", "s32[]", "while")
    assert p("%_q40_matmul_acts_impl.33 = bf16[16,14336]{1,0:T(8,128)(2,1)S(1)} custom-call(f32[16,2048]{1,0} %a)")[:2] == (
        "_q40_matmul_acts_impl.33", "bf16[16,14336]")
    assert p("%slice-start.36 = ((u8[2048,4096]{1,0}), u8[512,4096]{1,0:S(1)}, s32[]{:S(2)}) async-start(u8[2048,4096] %w)") == (
        "slice-start.36", "u8[2048,4096]", "async-start")
    assert p("dot_general.1") == ("dot_general.1", "", "")
    assert xplane.program_family("jit__decode_pl(16620646555647407974)") == "_decode_pl"


def _op(name, shape, start, dur, opcode="fusion"):
    return {"name": name, "shape": shape, "opcode": opcode, "start": start, "dur": dur}


def test_reduce_trace_counted_by_hand():
    trace = {
        "device": {0: {
            "ops": [
                _op("while.1", "s32[]", 100.0, 500.0, "while"),     # spans the next two
                _op("copy.1", "bf16[2,4]", 100.0, 200.0, "copy"),
                _op("mm.1", "bf16[4,8]", 350.0, 250.0, "custom-call"),
                _op("copy.1", "bf16[2,4]", 700.0, 100.0, "copy"),   # a gap of 100 before it
                _op("tail.1", "f32[1]", 950.0, 100.0),              # clipped at 1000
            ],
            "modules": [
                {"name": "jit_step(1)", "start": 100.0, "dur": 500.0},
                {"name": "jit_step(1)", "start": 700.0, "dur": 100.0},
                {"name": "jit_step(1)", "start": 950.0, "dur": 100.0},  # not whole: left out
            ],
        }},
        "host": [
            {"name": "bench.traced_window", "start": 0.0, "dur": 1000.0},
            {"name": "bench.wait_due", "start": 0.0, "dur": 90.0},
            {"name": "bench.consume", "start": 590.0, "dur": 100.0},
            {"name": "bench.submit", "start": 650.0, "dur": 20.0},
        ],
    }
    r = xplane.reduce_trace(trace)
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 600) + [700, 800) + [950, 1000) = 650 ns; the while is not added twice
    assert r["busy_s"] == pytest.approx(650e-9)
    assert r["op_seconds"][("copy.1", "bf16[2,4]")] == pytest.approx(300e-9)
    assert r["op_calls"][("copy.1", "bf16[2,4]")] == 2
    assert ("while.1", "s32[]") not in r["op_seconds"]
    assert r["op_seconds"][("tail.1", "f32[1]")] == pytest.approx(50e-9)
    assert r["program_ms"] == {"step": [500e-6, 100e-6]}
    # operations by the program execution they started in
    assert r["program_ops"]["step"][("copy.1", "bf16[2,4]")] == (pytest.approx(300e-9), 2)
    assert ("while.1", "s32[]") not in r["program_ops"]["step"]
    assert xplane.program_median_ms(r, ("step",)) == pytest.approx(300e-6)
    # idle: [0, 100) under wait_due (90 of it), [600, 700) under consume (90)
    # rather than submit (20), [800, 950) under nothing of the benchmark's
    assert dict(r["idle_gaps"]) == pytest.approx({
        "host:unlabelled": 150e-9, "bench.wait_due": 100e-9, "bench.consume": 100e-9})
    assert xplane.top_device_ops(r, 1) == [["copy.1 bf16[2,4]", pytest.approx(300e-9)]]


def test_no_device_plane_gives_nothing_to_read():
    assert xplane.reduce_trace({"device": {}, "host": []}) == {"chips": 0}


@pytest.fixture(scope="module")
def recorded():
    """0.35 s of the traced stretch of one `mistral7b_chat_steady` run on a TPU
    v5e (my chip run, PR 25), as `read_xplane` gave it: four pipelined decode
    steps and one fused prefill + decode step, with the benchmark's host spans."""
    path = os.path.join(DATA, "tpu_v5e_mistral7b_chat_steady.json.gz")
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    trace = {"device": {int(k): v for k, v in d["device"].items()}, "host": d["host"]}
    return trace, xplane.reduce_trace(trace, window=tuple(d["window"]))


def test_recorded_trace_programs_and_busy_time(recorded):
    trace, r = recorded
    assert r["chips"] == 1 and len(trace["device"][0]["ops"]) == 22334
    assert r["window_s"] == pytest.approx(0.349025209)
    assert r["busy_s"] == pytest.approx(0.348995912)
    assert r["busy_s"] <= r["window_s"]
    assert [round(x, 3) for x in r["program_ms"]["_decode_pl"]] == [63.457, 63.464, 63.391, 63.47]
    assert [round(x, 2) for x in r["program_ms"]["_decode_prefill"]] == [95.22]
    assert xplane.program_median_ms(r, ("_decode_pl",)) == pytest.approx(63.4605, abs=1e-3)
    # what ran is the programs, end to end: their sum is the busy time
    steps = sum(r["program_ms"]["_decode_pl"]) + sum(r["program_ms"]["_decode_prefill"])
    assert steps / 1e3 == pytest.approx(r["busy_s"], rel=1e-3)
    # the only gap: the host consuming a step's tokens before the next dispatch
    assert r["idle_gaps"] == [("bench.consume", pytest.approx(2.9297e-05, rel=1e-3))]


def test_recorded_trace_feeds_the_device_metrics(recorded):
    from types import SimpleNamespace

    import run

    _trace, r = recorded
    top = xplane.top_device_ops(r, 3)
    assert [name for name, _s in top] == [
        "bitcast_dynamic-update-slice_fusion.4 bf16[32,16,2048,8,128]",
        "copy.201 bf16[32,16,2048,8,128]", "copy.200 bf16[32,16,2048,8,128]"]
    # no operation is counted under a while: the parts sum to the busy time or less
    assert sum(r["op_seconds"].values()) <= r["busy_s"] * 1.001
    config = SimpleNamespace(dim=4096, hidden_dim=14336, n_layers=32, n_kv_heads=8,
                             head_size=128, seq_len=2048)
    ctx = SimpleNamespace(trace=r, config=config, lanes=16, kv_dtype="bfloat16",
                          padded_vocab=32768, peaks={"hbm_bytes_per_s": 819e9})
    # the whole-cache copies and updates: 0.1117 s of the 0.3490 s
    assert run.load_metric("kv_cache_copy_share")(ctx) == pytest.approx(32.0078, abs=1e-3)
    # the kernel's calls inside the four pipelined decode steps (the fused
    # step's are left out, its decode batch's too); 4.09 GB a step over
    # 819 GB/s = 4.99 ms; the calls took 11.5 ms a step
    calls = sum(n for (name, _s), (_t, n) in r["program_ops"]["_decode_pl"].items()
                if "q40_matmul" in name)
    assert calls == 4 * (7 * 32 + 1)
    roof = run.load_metric("q40_decode_roofline")(ctx)
    assert roof == pytest.approx(43.4, abs=0.2) and roof < 100
    assert run.load_metric("decode_step_device_ms")(ctx) == pytest.approx(63.4605, abs=1e-3)
    assert run.load_metric("fused_step_device_ms")(ctx) == pytest.approx(95.22, abs=0.01)
