"""The trace read by the program's own names: on events counted by hand, and on
a stretch recorded from a real TPU trace (tests/data/, my chip run, PR 26)."""
import os
from types import SimpleNamespace

import pytest

import run
from harness import progtrace
from harness.cells import BENCH_DIR

DATA = os.path.join(BENCH_DIR, "tests", "data")
PL = "jit(_decode_pl)/jit(main)/"


def _op(name, op_name, start, dur, opcode="fusion", shape="f32[1]"):
    return {"name": name, "shape": shape, "opcode": opcode, "op_name": op_name,
            "start": start, "dur": dur}


def _host(name, start, dur):
    return {"name": name, "start": start, "dur": dur, "thread": "scheduler"}


@pytest.fixture()
def by_hand():
    """Two executions of `_decode_pl` (the second cut by the window's end) and
    one of `_decode_prefill`, in a window of 2 ms = [0, 2e6) ns."""
    ops = [
        # execution 1 of _decode_pl: [100e3, 700e3)
        _op("gather.1", PL + "dl.embed/gather:", 100e3, 10e3),
        _op("while.1", "", 110e3, 500e3, "while"),                   # spans its body
        _op("fusion.1", PL + "dl.layers/while/body/closed_call/dl.qkv/dot_general:", 110e3, 100e3),
        # two scopes deep: counted under the deepest, and under both inclusively
        _op("fusion.2", PL + "dl.layers/while/body/closed_call/dl.attention/reduce_max:", 210e3, 150e3),
        # the scan's own update of the stacked cache: dl.layers' self time
        _op("dus.1", PL + "dl.layers/while/body/dynamic_update_slice:", 360e3, 200e3,
            shape="bf16[2,4]"),
        _op("copy.7", "", 560e3, 60e3, "copy", "bf16[2,4]"),          # no op_name at all
        _op("sort.1", PL + "dl.sampler/cond/branch_1_fun/vmap()/top_k:", 620e3, 60e3, "sort"),
        _op("where.1", PL + "dl.carry/jit(_where)/select_n:", 680e3, 20e3),
        # a fused step: [1000e3, 1300e3)
        _op("fusion.9", "jit(_decode_prefill)/jit(main)/dl.layers/while/body/closed_call/dl.ffn/mul:",
            1000e3, 300e3),
        # execution 2 of _decode_pl starts at 1800e3 and runs past the window
        _op("fusion.1", PL + "dl.layers/while/body/closed_call/dl.qkv/dot_general:", 1800e3, 300e3),
    ]
    modules = [
        {"name": "jit__decode_pl(1)", "start": 100e3, "dur": 600e3},
        {"name": "jit__decode_prefill(2)", "start": 1000e3, "dur": 300e3},
        {"name": "jit__decode_pl(1)", "start": 1800e3, "dur": 600e3},
    ]
    host = [
        _host("bench.traced_window", 0.0, 2000e3),
        _host("dl.loop.wait", 0.0, 705e3),          # the first gap [0, 100e3) begins under it
        _host("dl.loop.stream", 705e3, 200e3),      # gap [700e3, 1000e3) begins at 700e3: still wait
        _host("dl.loop.admit", 905e3, 50e3),
        _host("dl.loop.dispatch", 955e3, 40e3),
        _host("dl.loop.wait", 995e3, 300e3),
        _host("dl.loop.stream", 1295e3, 400e3),     # gap [1300e3, 1800e3) begins under it
        _host("dl.loop.admit", 1695e3, 10e3),
        _host("dl.loop.dispatch", 1705e3, 90e3),
        _host("dl.loop.wait", 1795e3, 600e3),       # clipped at the window's end
    ]
    return {"device": {0: {"ops": ops, "modules": modules}}, "host": host}


def test_scopes_self_time_and_executions_counted_by_hand(by_hand):
    r = progtrace.reduce(by_hand)
    assert r["window_s"] == pytest.approx(2e-3)
    # busy: [100, 700) + [1000, 1300) + [1800, 2000) us; the while is not added twice
    assert r["busy_s"] == pytest.approx(1100e-6)
    pl = r["scopes"]["_decode_pl"]
    # the second execution is not whole inside the window: not counted, nor its operations
    assert pl["executions"] == 1 and pl["median_ms"] == pytest.approx(0.6)
    assert pl["self_s"] == pytest.approx({
        "dl.embed": 10e-6, "dl.qkv": 100e-6, "dl.attention": 150e-6, "dl.layers": 200e-6,
        None: 60e-6, "dl.sampler": 60e-6, "dl.carry": 20e-6})
    # inclusive: dl.layers holds its children
    assert pl["seconds"]["dl.layers"] == pytest.approx(450e-6)
    assert pl["seconds"]["dl.attention"] == pytest.approx(150e-6)
    assert "while.1 f32[1]" not in pl["ops"].get(None, {})
    assert pl["ops"][None] == {"copy.7 bf16[2,4]": pytest.approx(60e-6)}
    assert r["scopes"]["_decode_prefill"]["self_s"] == {"dl.ffn": pytest.approx(300e-6)}
    # unscoped: copy.7 alone, of everything busy in the window (clipped ops included)
    assert r["unscoped_s"] == pytest.approx(60e-6)
    # what the metrics ask
    assert progtrace.overhead_ms_per_execution(r, "_decode_pl") == pytest.approx(0.26)
    assert progtrace.scope_ms_per_execution(r, "_decode_pl", ("dl.attention",)) == pytest.approx(0.15)
    assert progtrace.scope_ms_per_execution(r, "_decode_pl", ("dl.sampler",)) == pytest.approx(0.06)
    assert progtrace.scope_ms_per_execution(r, "_no_such_program", ("dl.ffn",)) is None
    # the leaf scopes and the overhead are all of the step's operations
    leaves = progtrace.scope_ms_per_execution(
        r, "_decode_pl", progtrace.names.LEAF_SCOPES)
    assert leaves + 0.26 == pytest.approx(0.60)   # the whole execution: no gap inside it


def test_an_execution_caught_in_part_does_not_pull_the_figure():
    """Three executions; the profiler lost the first one's operations but for
    the last (as the first execution after the trace starts can): the median
    over executions reads the whole ones."""
    ops, modules = [], []
    for k in range(3):
        t0 = 100e3 + k * 1000e3
        modules.append({"name": "jit__decode_pl(1)", "start": t0, "dur": 900e3})
        if k:
            ops.append(_op("fusion.1", PL + "dl.layers/while/body/closed_call/dl.attention/dot_general:", t0, 300e3))
            ops.append(_op("dus.1", PL + "dl.layers/while/body/dynamic_update_slice:", t0 + 300e3, 500e3))
        ops.append(_op("where.1", PL + "dl.carry/select_n:", t0 + 800e3, 100e3))
    r = progtrace.reduce({"device": {0: {"ops": ops, "modules": modules}},
                          "host": [_host("bench.traced_window", 0.0, 3100e3)]})
    assert r["scopes"]["_decode_pl"]["executions"] == 3
    assert progtrace.overhead_ms_per_execution(r, "_decode_pl") == pytest.approx(0.5)
    assert progtrace.scope_ms_per_execution(r, "_decode_pl", ("dl.attention",)) == pytest.approx(0.3)
    assert progtrace.scope_ms_per_execution(r, "_decode_pl", ("dl.carry",)) == pytest.approx(0.1)


def test_idle_gaps_go_to_the_span_open_when_they_began(by_hand):
    r = progtrace.reduce(by_hand)
    # [0, 100) and [700, 1000) began under a wait, [1300, 1800) under a stream
    assert r["idle_by_span"] == pytest.approx({"dl.loop.wait": 400e-6, "dl.loop.stream": 500e-6})
    assert progtrace.idle_host_share(r) == pytest.approx(100 * 500e-6 / 2e-3)
    # admit 60 + dispatch 130 + stream 600 us of the loop's own work; three waits began
    assert r["loop_count"]["dl.loop.wait"] == 3
    assert progtrace.loop_host_ms_per_step(r) == pytest.approx((60 + 130 + 600) / 3 / 1e3)
    # a short gap is a launch gap, whatever span was open
    by_hand["device"][0]["ops"].append(_op("late.1", PL + "dl.head/dot_general:", 1350e3, 400e3))
    r = progtrace.reduce(by_hand)
    assert r["idle_by_span"] == pytest.approx({"dl.loop.wait": 400e-6, "launch_gaps": 100e-6})
    # a gap under no span of the loop
    by_hand["host"] = [h for h in by_hand["host"] if h["name"] != "dl.loop.wait"]
    r = progtrace.reduce(by_hand)
    assert r["idle_by_span"] == pytest.approx({progtrace.NO_SPAN: 400e-6, "launch_gaps": 100e-6})
    assert progtrace.loop_host_ms_per_step(r) is None   # no wait, no step to divide by


def test_a_program_without_names_leaves_nothing_to_read(by_hand):
    for e in by_hand["device"][0]["ops"]:
        e["op_name"] = e["op_name"].replace("dl.", "xx.")
    by_hand["host"] = [h for h in by_hand["host"] if not h["name"].startswith("dl.")]
    r = progtrace.reduce(by_hand)
    assert r["scopes"] is None and r["unscoped_s"] is None and r["loop_s"] is None
    assert progtrace.overhead_ms_per_execution(r, "_decode_pl") is None
    assert progtrace.loop_host_ms_per_step(r) is None and progtrace.idle_host_share(r) is None
    assert progtrace.reduce({"device": {}, "host": []}) is None
    # every reader, on an untraced run and on a program without the stamps
    ctx = SimpleNamespace(trace=None, streams=[], t_open=0.0, t_close=1.0)
    for name in ("scan_overhead_step_ms", "attention_step_ms", "sampler_step_ms",
                 "unscoped_share", "loop_host_ms_per_step", "device_idle_host_share",
                 "ttft_dispatch_wait_p50_ms", "ttft_prefill_p50_ms", "ttft_hold_p50_ms"):
        assert run.load_metric(name)(ctx) is None
    old = SimpleNamespace(tel=SimpleNamespace(submitted_at=0.1, admitted_at=0.2,
                                              first_token_at=0.5, ttft_s=0.4),
                          submitted_at=0.1)
    ctx = SimpleNamespace(trace={"busy_s": 1.0}, t_open=0.0, t_close=1.0, streams=[
        SimpleNamespace(req=old, start_t=0.1, delta_t=[0.5])])
    assert run.load_metric("ttft_hold_p50_ms")(ctx) is None


def test_first_token_parts_from_the_programs_stamps():
    def stream(t):
        tel = SimpleNamespace(submitted_at=t[0], admitted_at=t[1], first_dispatch_at=t[2],
                              prefill_done_at=t[3], first_token_at=t[4], ttft_s=t[4] - t[0])
        return SimpleNamespace(req=SimpleNamespace(tel=tel, submitted_at=t[0]),
                               start_t=t[0] - 0.001, delta_t=[t[4] + 0.0002])

    ctx = SimpleNamespace(trace={"busy_s": 1.0}, t_open=1.0, t_close=9.0, streams=[
        stream((2.0, 2.03, 2.031, 2.21, 2.274)), stream((3.0, 3.05, 3.052, 3.24, 3.30)),
        stream((4.0, 4.01, 4.0105, 4.20, 4.265)),
        stream((0.5, 0.6, 0.7, 0.8, 0.9)),            # due before the window: not owed
    ])
    rows = progtrace.ttft_parts_ms(ctx)
    assert len(rows) == 3
    for r in rows:
        assert r["queue_wait"] + r["dispatch_wait"] + r["prefill"] + r["hold"] == \
            pytest.approx(r["program_ttft"])
        assert r["client_ttft"] == pytest.approx(r["program_ttft"] + 1.2)
    assert run.load_metric("ttft_dispatch_wait_p50_ms")(ctx) == pytest.approx(1.0)
    assert run.load_metric("ttft_prefill_p50_ms")(ctx) == pytest.approx(188.0)
    assert run.load_metric("ttft_hold_p50_ms")(ctx) == pytest.approx(64.0)


def test_record_and_load_a_stretch(by_hand, tmp_path):
    path = str(tmp_path / "stretch.json.gz")
    progtrace.record_stretch(by_hand, 0.0, 1500e3, path, "by hand")
    trace, window = progtrace.load_stretch(path)
    assert window == (0.0, 1500e3)
    assert len(trace["device"][0]["ops"]) == 9 and len(trace["device"][0]["modules"]) == 2
    r = progtrace.reduce(trace, window)
    assert r["scopes"]["_decode_pl"]["self_s"]["dl.layers"] == pytest.approx(200e-6)


@pytest.fixture(scope="module")
def recorded():
    """0.38 s of the traced stretch of one `mistral7b_chat_steady` run of the
    scoped program on a TPU v5e (my chip run, PR 26: seed 2600000001), as
    `progtrace.read` gave it: six pipelined decode steps with the loop's spans."""
    trace, window = progtrace.load_stretch(
        os.path.join(DATA, "tpu_v5e_mistral7b_chat_steady_scoped.json.gz"))
    return trace, progtrace.reduce(trace, window)


def test_recorded_stretch_reads_what_the_chip_run_printed(recorded):
    trace, r = recorded
    assert r["chips"] == 1 and r["window_s"] == pytest.approx(0.3807056)
    pl = r["scopes"]["_decode_pl"]
    assert pl["executions"] == 6 and pl["median_ms"] == pytest.approx(63.463, abs=1e-3)
    assert len(pl["per_execution"]) == 6
    per = {k: progtrace.scope_ms_per_execution(r, "_decode_pl", (k,)) for k in pl["self_s"]}
    printed = {"dl.layers": 21.208, None: 20.336, "dl.ffn": 13.468, "dl.attention": 3.986,
               "dl.qkv": 2.135, "dl.attn_out": 1.517, "dl.sampler": 0.455, "dl.head": 0.268,
               "dl.kv_write": 0.074, "dl.carry": 0.012, "dl.embed": 0.001}
    assert per == pytest.approx(printed, abs=6e-4)
    # the operations of a step come to the program's own duration: the
    # remainder is gaps inside an execution
    whole = [sum(ex.values()) for ex in pl["per_execution"]]
    assert sorted(whole)[3] == pytest.approx(63.460, abs=2e-3)
    assert max(whole) <= pl["median_ms"] * 1.0005 and min(whole) >= pl["median_ms"] * 0.998
    assert progtrace.overhead_ms_per_execution(r, "_decode_pl") == pytest.approx(41.543, abs=2e-3)
    assert progtrace.scope_ms_per_execution(r, "_decode_pl", ("dl.attention",)) == \
        pytest.approx(3.986, abs=1e-3)
    assert 100 * r["unscoped_s"] / r["busy_s"] == pytest.approx(32.05, abs=0.01)
    # what carries no scope: copies XLA inserted, with no op_name at all
    top = sorted(pl["ops"][None].items(), key=lambda kv: -kv[1])[:4]
    assert [n for n, _s in top] == [
        "copy.201 bf16[32,16,2048,8,128]", "copy.200 bf16[32,16,2048,8,128]",
        "copy-done.2 bf16[16,2048,8,128]", "copy-done.3 bf16[16,2048,8,128]"]
    assert all(e["op_name"] == "" for e in trace["device"][0]["ops"]
               if e["name"] in ("copy.200", "copy.201"))
    # and dl.layers' own time: the scan's rewrite and slices of the stacked cache
    assert max(pl["ops"]["dl.layers"], key=pl["ops"]["dl.layers"].get) == \
        "bitcast_dynamic-update-slice_fusion.4 bf16[32,16,2048,8,128]"


def test_recorded_stretch_loop_spans_and_idle_gaps(recorded):
    _trace, r = recorded
    ms = {k: round(v * 1e3, 2) for k, v in r["loop_s"].items()}
    assert ms == {"dl.loop.admit": 0.07, "dl.loop.dispatch": 12.03,
                  "dl.loop.stream": 1.46, "dl.loop.wait": 366.66}
    assert set(r["loop_count"].values()) == {6}
    assert progtrace.loop_host_ms_per_step(r) == pytest.approx((0.07 + 12.03 + 1.46) / 6, abs=2e-3)
    # the device never ran dry for 0.1 ms: launch gaps only
    assert r["idle_by_span"] == {"launch_gaps": pytest.approx(1.1e-5, rel=0.05)}
    assert progtrace.idle_host_share(r) == 0.0


def test_recorded_stretch_feeds_the_new_device_metrics(recorded, monkeypatch):
    _trace, r = recorded
    monkeypatch.setattr(progtrace, "for_ctx", lambda ctx: r)
    ctx = SimpleNamespace(trace={"busy_s": r["busy_s"]})
    assert run.load_metric("scan_overhead_step_ms")(ctx) == pytest.approx(41.543, abs=2e-3)
    assert run.load_metric("attention_step_ms")(ctx) == pytest.approx(3.986, abs=1e-3)
    assert run.load_metric("sampler_step_ms")(ctx) == pytest.approx(0.455, abs=1e-3)
    share = run.load_metric("unscoped_share")(ctx)
    assert share == pytest.approx(32.05, abs=0.01) and share < 100
    assert run.load_metric("loop_host_ms_per_step")(ctx) == pytest.approx(2.26, abs=0.01)
    assert run.load_metric("device_idle_host_share")(ctx) == 0.0
