"""The window read by the batching loop's own records (harness/steplog.py):
on events and rows counted by hand, and on a stretch recorded from a real TPU
trace of the change (tests/data/, my chip run, PR 52)."""
import io
import os
from collections import namedtuple
from types import SimpleNamespace

import pytest

from harness import progtrace, steplog
from harness.cells import BENCH_DIR

DATA = os.path.join(BENCH_DIR, "tests", "data")
STRETCH = os.path.join(DATA, "tpu_v5e_mistral7b_chat_saturated_steps.json.gz")
B256 = "jit(_decode_prefill)/jit(main)/dlstep.fused.b256/"
PL = "jit(_decode_pl)/jit(main)/dlstep.decode/"


def _exec(family, prefix, start, dur):
    """One execution: two operations that fill it end to end."""
    ops = [{"name": "fusion.1", "shape": "f32[1]", "opcode": "fusion",
            "op_name": prefix + "dlhalf.decode/dl.ffn/dot_general:", "start": start,
            "dur": dur / 2},
           {"name": "fusion.2", "shape": "f32[1]", "opcode": "fusion",
            "op_name": prefix + "dl.carry/select_n:", "start": start + dur / 2, "dur": dur / 2}]
    return ops, {"name": f"jit_{family}(7)", "start": start, "dur": dur}


def _span(name, start, dur, **args):
    return {"name": name, "start": start, "dur": dur, "thread": "loop", "args": args}


def _record(step, cls, interval_s, dry=0, **more):
    return dict(step=step, **{"class": cls}, lanes=15, dry=dry, interval_s=interval_s,
                wait_s=interval_s * 0.9, host_s=interval_s * 0.1, **more)


def hand_trace(late_host: bool, earlier: bool = False, args: bool = True):
    """A window of 7.5 ms on one chip: steps 5 (a 256-row fused step), 6 and
    7 (decode steps) run [0.4, 2.4), [2.4, 3.4) and [6.4, 7.4) ms: the device
    stands idle for 3 ms before step 7.

    ``late_host`` False: step 7 was dispatched at 2.7 ms, while step 6 ran,
    and the loop sat in ``loop.wait`` from 3.0 to 6.9 ms: the device idled
    with work queued. True: the loop's stream of step 6 ran from 3.35 to
    6.2 ms and step 7 was dispatched at 6.3 ms, dry. ``earlier``: an
    execution (step 4) that started before the first dispatch in the trace
    opened. ``args`` False: the host events of a program from before the
    step numbers."""
    parts = [_exec("_decode_prefill", B256, 400e3, 2000e3),
             _exec("_decode_pl", PL, 2400e3, 1000e3),
             _exec("_decode_pl", PL, 6400e3, 1000e3)]
    if earlier:
        parts.insert(0, _exec("_decode_pl", PL, 50e3, 330e3))
    fused = "dlstep.fused.b256"
    host = [
        _span("bench.traced_window", 0.0, 7500e3),
        _span("dl.loop.dispatch", 100e3, 200e3, step=5, dry=0),
        _span("dl.loop.dispatch", 500e3, 200e3, step=6, dry=0),
        _span("dl.loop.wait", 800e3, 1650e3, step=5),
        _span("dl.loop.stream", 2450e3, 150e3,
              **_record(5, fused, 0.002, chunk=200, p_start=512, final=1)),
    ]
    if late_host:
        host += [
            _span("dl.loop.wait", 2700e3, 650e3, step=6),
            _span("dl.loop.stream", 3350e3, 2850e3, **_record(6, "dlstep.decode", 0.001)),
            _span("dl.loop.dispatch", 6300e3, 80e3, step=7, dry=1),
            _span("dl.loop.wait", 6390e3, 1060e3, step=7),
            _span("dl.loop.stream", 7450e3, 40e3,
                  **_record(7, "dlstep.decode", 0.004, dry=1, dry_s=0.00303)),
        ]
    else:
        host += [
            _span("dl.loop.dispatch", 2700e3, 200e3, step=7, dry=0),
            _span("dl.loop.wait", 3000e3, 3900e3, step=6),
            _span("dl.loop.stream", 6900e3, 100e3, **_record(6, "dlstep.decode", 0.001)),
            _span("dl.loop.wait", 7100e3, 350e3, step=7),
            _span("dl.loop.stream", 7450e3, 40e3, **_record(7, "dlstep.decode", 0.004)),
        ]
    if not args:
        host = [dict(h, args={}) for h in host]
    return {"device": {0: {"ops": [o for ops, _ in parts for o in ops],
                           "modules": [m for _, m in parts]}},
            "host": host}


def test_three_dispatches_three_executions_one_gap_under_loop_wait():
    j = steplog.join(hand_trace(late_host=False))
    assert [(e["step"], e["device_class"], e["whole"]) for e in j["executions"]] == [
        (5, "dlstep.fused.b256", True), (6, "dlstep.decode", True), (7, "dlstep.decode", True)]
    assert (j["whole"], j["matched"], j["mismatched"], j["out_of_order"]) == (3, 3, 0, 0)
    assert [e["record"]["interval_s"] for e in j["executions"]] == [0.002, 0.001, 0.004]
    assert j["interval_s"] == pytest.approx(0.007) and j["stretch_s"] == pytest.approx(0.007)
    # one gap of 1 ms or more: 3 ms, begun under loop.wait, between steps 6
    # and 7, and step 7 had been handed over long before: not the host's
    (g,) = j["gaps"]
    assert (g["start_ms"], g["ms"], g["span"]) == (pytest.approx(3.4), pytest.approx(3.0),
                                                   "dl.loop.wait")
    assert (g["before"]["step"], g["after"]["step"], g["inside"], g["dry"]) == (6, 7, False, False)
    assert (j["gaps_dry"], j["gaps_queued"], j["dry"], j["dry_with_gap"]) == (0, 1, 0, 0)
    # the leading 0.4 ms and the trailing 0.1 ms are gaps too, and shorter
    assert j["largest_gap_ms"] == pytest.approx(3.0)
    assert j["window_s"] == pytest.approx(0.0075)


def test_a_dry_dispatch_has_its_gap_and_its_seconds_cover_it():
    j = steplog.join(hand_trace(late_host=True))
    (g,) = j["gaps"]
    assert g["span"] == "dl.loop.stream" and g["dry"] is True
    assert (g["before"]["step"], g["after"]["step"]) == (6, 7)
    assert (j["gaps_dry"], j["gaps_queued"], j["dry"], j["dry_with_gap"]) == (1, 0, 1, 1)
    assert j["dry_s"] == pytest.approx(0.00303) and j["dry_gap_s"] == pytest.approx(0.003)
    assert [e["dry"] for e in j["executions"]] == [False, False, True]
    out = io.StringIO()
    steplog.log_join(j, out=out)
    text = out.getvalue()
    assert "3 whole executions, 3 matched to a step record, class mismatches 0" in text
    assert ("under dl.loop.stream: after step 6 dlstep.decode, before step 7 dlstep.decode, "
            "the host had seen the device dry (dry_s 3.030 ms)") in text
    # a gap longer than dry_s began before the readback returned: said so
    late = hand_trace(late_host=True)
    for h in late["host"]:
        if h["name"] == "dl.loop.stream" and h["args"]["step"] == 7:
            h["args"]["dry_s"] = 0.0004
    out = io.StringIO()
    steplog.log_join(steplog.join(late), out=out)
    assert "(dry_s 0.400 ms: the readback before it returned late)" in out.getvalue()
    assert "idle gaps of 1 ms or more: 1 (1 at a dry dispatch, 0 device idle with work queued)" in text


def test_an_execution_from_before_the_first_dispatch_counts_back():
    j = steplog.join(hand_trace(late_host=False, earlier=True))
    assert [e["step"] for e in j["executions"]] == [4, 5, 6, 7]
    # its dispatch and its stream lie before the trace: a whole execution
    # without a record, and the other three as before
    assert (j["whole"], j["matched"], j["mismatched"]) == (4, 3, 0)
    assert j["executions"][0]["record"] is None and j["executions"][0]["dry"] is None
    assert j["gaps"][0]["before"]["step"] == 6


def test_a_class_the_device_does_not_carry_is_a_mismatch():
    trace = hand_trace(late_host=False)
    for h in trace["host"]:
        if h["name"] == "dl.loop.stream" and h["args"]["step"] == 6:
            h["args"]["class"] = "dlstep.fused.b1024"
    assert steplog.join(trace)["mismatched"] == 1


def test_a_program_from_before_the_step_numbers_joins_nothing_and_raises_nothing():
    j = steplog.join(hand_trace(late_host=False, args=False))
    assert [e["step"] for e in j["executions"]] == [None, None, None]
    assert (j["whole"], j["matched"], j["dry"]) == (3, 0, 0)
    (g,) = j["gaps"]
    assert g["span"] == "dl.loop.wait" and g["dry"] is None and g["ms"] == pytest.approx(3.0)
    assert j["largest_gap_ms"] == pytest.approx(3.0)
    out = io.StringIO()
    steplog.log_join(j, out=out)
    assert "after dlstep.decode, before dlstep.decode" in out.getvalue()
    assert steplog.join({"device": {}, "host": []}) is None
    out = io.StringIO()
    steplog.log_join(None, out=out)
    assert "nothing to join" in out.getvalue()


def test_a_gap_inside_an_execution_is_said_to_be_inside_it():
    trace = hand_trace(late_host=False)
    ops = trace["device"][0]["ops"]
    # step 5 runs [0.4, 2.4) ms; between its two operations a hole of 1.2 ms
    ops[0]["dur"] = 500e3
    ops[1]["start"], ops[1]["dur"] = 2100e3, 300e3
    j = steplog.join(trace)
    inside = [g for g in j["gaps"] if g["inside"]]
    assert len(inside) == 1 and inside[0]["after"]["step"] == 5 and inside[0]["dry"] is None
    assert inside[0]["ms"] == pytest.approx(1.2)


# -- the whole window, from req.tel.chunks ------------------------------------

Row = namedtuple("Row", "step cls chunk p_start final lanes dry dry_s interval_s wait_s host_s at")


def row(at, cls, interval_ms, p_start=0, dry=False, wait_ms=None, step=0):
    wait = interval_ms * 0.9 if wait_ms is None else wait_ms
    return Row(step, cls, 256, p_start, False, 15, dry, 0.0, interval_ms / 1e3, wait / 1e3,
               (interval_ms - wait) / 1e3, at)


def ctx_of(chunk_lists, counters=None, buckets="default", seq_len=2048, tel=True):
    streams = [SimpleNamespace(req=SimpleNamespace(
        tel=SimpleNamespace(chunks=rows) if tel else SimpleNamespace()))
        for rows in chunk_lists]
    return SimpleNamespace(
        streams=streams, t_open=100.0, t_close=140.0, seconds=40.0, trace=None,
        counters=counters or {}, lanes=16, cfg={"serving": {"prefill_buckets": buckets}},
        config=SimpleNamespace(seq_len=seq_len))


B1024, B512 = "dlstep.fused.b1024", "dlstep.fused.b512"


def test_top_rung_is_the_median_over_the_window_of_the_largest_rungs_steps():
    rows = [row(101.0, B1024, 134.0), row(110.0, B1024, 136.0), row(139.9, B1024, 171.0),
            row(99.9, B1024, 500.0), row(140.0, B1024, 500.0),      # outside the window
            row(105.0, B512, 80.0), row(106.0, "dlstep.prefill.b1024", 300.0)]
    ctx = ctx_of([rows[:3], rows[3:]])
    assert steplog.top_rung(ctx) == 1024
    assert steplog.top_rung_step_ms(ctx) == pytest.approx(136.0)
    # the ladder the configuration gives, cut to the context
    assert steplog.top_rung(ctx_of([], buckets=[64, 256, 512], seq_len=32768)) == 512
    assert steplog.top_rung(ctx_of([], seq_len=128)) == 64
    assert steplog.top_rung_step_ms(ctx_of([rows], buckets=[64, 256, 512])) == pytest.approx(80.0)
    # no such step in the window: nothing; a program without the rows: nothing
    assert steplog.top_rung_step_ms(ctx_of([[row(105.0, B512, 80.0)]])) is None
    assert steplog.top_rung_step_ms(ctx_of([rows], tel=False)) is None
    assert steplog.late_share(ctx_of([rows], tel=False)) is None


def test_late_share_goes_by_class_and_band_of_start_positions():
    # a long context's step grows with its start: 93 ms at 0, 118 at 28160
    early = [row(101.0 + i, B512, 93.0 + 0.1 * i) for i in range(5)]
    far = [row(111.0 + i, B512, 118.0 + 0.1 * i, p_start=28160) for i in range(5)]
    assert steplog.late_share(ctx_of([early + far])) == 0.0
    # one step of the early band read 40 % over its like: its wait grew
    late = row(120.0, B512, 131.0, wait_ms=125.0, step=77)
    ctx = ctx_of([early + far + [late]])
    assert steplog.late_share(ctx) == pytest.approx(100.0 / 11)
    (r, over, wait, host), = steplog.late_rows(steplog.window_rows(ctx))
    assert r.step == 77 and over == pytest.approx((131.0 - 93.25) / 1e3)
    assert wait > 0.03 and host < 0.003
    # the same interval in the far band is within a quarter of its like
    assert steplog.late_share(ctx_of([early + far + [late._replace(p_start=28672)]])) == 0.0
    # a chunk dispatched alone is no fused step; no fused step at all reads 0.0
    assert steplog.late_share(ctx_of([[row(101.0, "dlstep.prefill.b512", 300.0)]])) == 0.0
    out = io.StringIO()
    steplog.log_rows(steplog.window_rows(ctx), {"pipeline_dry_dispatches": 2,
                                                "pipeline_dispatches": 400,
                                                "pipeline_dry_s": 0.25,
                                                "live_lane_steps": 6000}, 16, ctx.t_open, out=out)
    text = out.getvalue()
    assert "late (over 1.25 medians of their class within 4096 start positions): 1 of 11" in text
    assert (" 20.000 s into the window, step 77 dlstep.fused.b512 256 at 0: 131.000 ms, "
            "+37.750 over its like") in text
    assert "2 dry of 400 dispatches, pipeline_dry_s 0.250000; live lanes a step 15.00 of 16" in text
    assert f"{B512:<26}{11:>6}" in text


def test_counter_shares_read_zero_where_the_counter_stood_still_and_nothing_without_it():
    moved = {"pipeline_dispatches": 2000, "pipeline_dry_dispatches": 3, "pipeline_dry_s": 0.4,
             "live_lane_steps": 24000}
    ctx = ctx_of([[]], counters=moved)
    assert steplog.counter_share(ctx, "pipeline_dry_dispatches", 2000) == pytest.approx(0.15)
    assert steplog.counter_share(ctx, "pipeline_dry_s", ctx.seconds) == pytest.approx(1.0)
    assert steplog.counter_share(ctx, "live_lane_steps", 2000 * 16) == pytest.approx(75.0)
    still = ctx_of([[]], counters=dict(moved, pipeline_dry_dispatches=0, pipeline_dry_s=0.0))
    assert steplog.counter_share(still, "pipeline_dry_dispatches", 2000) == 0.0
    assert steplog.counter_share(still, "pipeline_dry_s", 40.0) == 0.0
    # no dispatch in the window: 0.0, not a division
    assert steplog.counter_share(ctx_of([[]], counters={"live_lane_steps": 0}),
                                 "live_lane_steps", 0) == 0.0
    # the parent keeps no such counter: the reader finds nothing and raises nothing
    assert steplog.counter_share(ctx_of([[]], counters={"pipeline_dispatches": 9}),
                                 "pipeline_dry_dispatches", 9) is None
    assert steplog.largest_gap_ms(ctx_of([[]])) is None   # an untraced run


def test_the_readers_are_found_by_name_and_read_through_the_module():
    from harness.cells import load_module

    ctx = ctx_of([[row(101.0, B1024, 134.0)]],
                 counters={"pipeline_dispatches": 100, "pipeline_dry_dispatches": 1,
                           "pipeline_dry_s": 0.1, "live_lane_steps": 800})
    got = {}
    for name in ("top_rung_step_ms", "fused_step_late_share", "dry_dispatch_share",
                 "device_starved_share", "lane_fill_share", "device_idle_largest_gap_ms"):
        mod = load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"), "m_" + name)
        got[name] = mod.read(ctx)
    assert got == {"top_rung_step_ms": pytest.approx(134.0), "fused_step_late_share": 0.0,
                   "dry_dispatch_share": pytest.approx(1.0),
                   "device_starved_share": pytest.approx(0.25),
                   "lane_fill_share": pytest.approx(50.0), "device_idle_largest_gap_ms": None}


def test_benchmark_json_lists_every_new_metric_with_its_cells():
    from harness.cells import cell_metrics, load_benchmark

    bench = load_benchmark()
    new = {m["name"]: m for m in bench["per_layer"][-6:]}
    assert list(new) == ["top_rung_step_ms", "fused_step_late_share", "dry_dispatch_share",
                         "device_starved_share", "lane_fill_share", "device_idle_largest_gap_ms"]
    assert all("workloads" in m for m in new.values())
    every = [w["name"] for w in bench["workloads"]]
    saturated = [w for w in every if w.endswith("_saturated")]
    assert new["fused_step_late_share"]["workloads"] == every
    for name in ("dry_dispatch_share", "device_starved_share", "lane_fill_share",
                 "device_idle_largest_gap_ms"):
        assert new[name]["workloads"] == saturated and new[name]["moves"] == "tokens_per_s"
    steady = {m["name"] for m in cell_metrics(bench, "mistral7b_chat_steady", "per_layer")}
    assert steady & set(new) == {"fused_step_late_share"}


# -- a stretch of a real trace ---------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return progtrace.load_stretch(STRETCH)


def test_the_recorded_stretch_keeps_the_annotations_keywords(recorded):
    trace, _window = recorded
    dispatched = [h for h in trace["host"] if h["name"] == "dl.loop.dispatch"]
    streamed = [h for h in trace["host"] if h["name"] == "dl.loop.stream"]
    assert dispatched and streamed
    assert all(set(h["args"]) == {"step", "dry"} for h in dispatched)
    assert all({"step", "class", "lanes", "dry", "interval_s", "wait_s", "host_s"}
               <= set(h["args"]) for h in streamed)
    steps = [h["args"]["step"] for h in sorted(dispatched, key=lambda h: h["start"])]
    assert steps == list(range(steps[0], steps[0] + len(steps)))


def test_the_recorded_stretch_joins_exactly(recorded):
    j = steplog.join(*recorded)
    assert j["whole"] >= 8 and j["mismatched"] == 0 and j["out_of_order"] == 0
    # the last executions' readbacks lie past the stretch's end
    assert j["whole"] - 2 <= j["matched"] <= j["whole"]
    matched = [e for e in j["executions"] if e["whole"] and e["record"]]
    assert {e["record"]["class"] for e in matched} >= {"dlstep.decode"}
    assert all(e["record"]["class"] == e["device_class"] for e in matched)
    assert j["interval_s"] == pytest.approx(j["stretch_s"], rel=0.02)
    for e in matched:
        r = e["record"]
        assert r["interval_s"] == pytest.approx(r["wait_s"] + r["host_s"], abs=1e-9)
        assert 1 <= r["lanes"] <= 16
    out = io.StringIO()
    steplog.log_join(j, out=out)
    assert "class mismatches 0" in out.getvalue()


# -- the command end to end on the CPU ---------------------------------------------

def test_a_traced_rehearsal_reports_the_five_names_that_need_no_device_plane():
    """`run.py --rehearse --trace 1` at a tiny size: the records reach the
    readers through ``ctx.streams`` and ``ctx.counters``; the CPU's trace has
    no device plane, so the largest gap finds nothing to read."""
    import json
    import subprocess
    import sys

    from harness.cells import ROOT

    bench_file = os.path.join(BENCH_DIR, "tests", "rehearsal", "BENCHMARK_steplog.json")
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", "tiny_saturated", "--seed", "3000000011", "--seconds", "2",
           "--trace", "1", "--rehearse", "--benchmark-file", bench_file]
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    p = subprocess.run(cmd, capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["rehearsal"] is True and res["metrics"] == {}
    values = res["rehearsal_values"]
    assert set(values) == {"pipeline_flushes", "top_rung_step_ms", "fused_step_late_share",
                           "dry_dispatch_share", "device_starved_share", "lane_fill_share"}
    assert values["top_rung_step_ms"]["value"] > 0.0
    assert 0.0 < values["lane_fill_share"]["value"] <= 100.0
    for name in ("fused_step_late_share", "dry_dispatch_share"):
        assert 0.0 <= values[name]["value"] <= 100.0
    # over the NOMINAL seconds: a loaded CPU closes the window late, and on
    # the CPU nearly every dispatch is dry
    assert values["device_starved_share"]["value"] >= 0.0
    table = [line for line in p.stderr.splitlines() if line.startswith("[steplog]")]
    assert any("dlstep.fused.b64" in line for line in table)
    assert any("dry of" in line and "live lanes a step" in line for line in table)
