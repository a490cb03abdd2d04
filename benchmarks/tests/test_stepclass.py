"""The trace read by class of step program and by half: on events counted by
hand, and on a stretch recorded from a real TPU trace of the change
(tests/data/, my chip run, PR 37)."""
import io
import os
import re

import pytest

from harness import progtrace, stepclass
from harness.cells import BENCH_DIR
from harness.stepclass import DECODE, JOIN, NO_OP_NAME, PREFILL

DATA = os.path.join(BENCH_DIR, "tests", "data")
STRETCH = os.path.join(DATA, "tpu_v5e_mistral7b_chat_saturated_classed.json.gz")
B1024 = "jit(_decode_prefill)/jit(main)/dlstep.fused.b1024/"
B256 = "jit(_decode_prefill)/jit(main)/dlstep.fused.b256/"
PL = "jit(_decode_pl)/jit(main)/dlstep.decode/"
LAYER = "dl.layers/while/body/closed_call/"


def _op(name, op_name, start, dur, opcode="fusion", shape="f32[1]"):
    return {"name": name, "shape": shape, "opcode": opcode, "op_name": op_name,
            "start": start, "dur": dur}


def _fused(prefix, t0, prefill):
    """One fused execution from ``t0`` (ns): a prefill half of ``prefill`` ns
    in two scopes, a decode half of 90e3, a join of 10e3, a copy without
    ``op_name`` of 20e3, and 5e3 of idle before the join: the module lasts
    ``prefill + 125e3``."""
    t = t0
    ops = [_op("while.p", "", t, prefill, "while")]                  # spans the prefill's layers
    ops.append(_op("fusion.p1", prefix + "dlhalf.prefill/" + LAYER + "dl.ffn/dot_general:",
                   t, 0.75 * prefill))
    ops.append(_op("fusion.p2", prefix + "dlhalf.prefill/" + LAYER + "dl.attention/reduce_max:",
                   t + 0.75 * prefill, 0.25 * prefill))
    t += prefill
    ops.append(_op("copy.1", "", t, 20e3, "copy", "bf16[2,4]"))
    t += 20e3
    ops.append(_op("fusion.d1", prefix + "dlhalf.decode/" + LAYER + "dl.ffn/dot_general:", t, 60e3))
    ops.append(_op("fusion.d2", prefix + "dlhalf.decode/vmap(dl.sampler)/reduce_sum:", t + 60e3, 30e3))
    t += 90e3 + 5e3
    ops.append(_op("where.1", prefix + "dl.carry/jit(_where)/select_n:", t, 10e3))
    return ops, {"name": "jit__decode_prefill(7)", "start": t0, "dur": prefill + 125e3}


def _decode(t0):
    ops = [_op("fusion.d1", PL + "dlhalf.decode/" + LAYER + "dl.ffn/dot_general:", t0, 70e3),
           _op("fusion.d2", PL + "dlhalf.decode/dl.sampler/reduce_sum:", t0 + 70e3, 20e3),
           _op("where.1", PL + "dl.carry/select_n:", t0 + 90e3, 10e3)]
    return ops, {"name": "jit__decode_pl(1)", "start": t0, "dur": 100e3}


def _chip(parts):
    return {"ops": [o for ops, _ in parts for o in ops], "modules": [m for _, m in parts]}


@pytest.fixture()
def by_hand():
    """A window of 4 ms = [0, 4e6) ns on one chip: two 1024-bucket steps
    (prefill 800e3 and 1000e3), one 256-bucket step (prefill 200e3), two
    decode steps, a lane copy that is no step program, and a third
    1024-bucket step that runs past the window's end."""
    parts = [
        _fused(B1024, 100e3, 800e3),      # [100e3, 1025e3)
        _decode(1100e3),                  # [1100e3, 1200e3)
        _fused(B256, 1300e3, 200e3),      # [1300e3, 1625e3)
        _decode(1700e3),
        _fused(B1024, 1900e3, 1000e3),    # [1900e3, 3025e3)
        ([_op("copy.9", "jit(_copy_lane)/dynamic_update_slice:", 3100e3, 50e3)],
         {"name": "jit__copy_lane(3)", "start": 3100e3, "dur": 50e3}),
        _fused(B1024, 3500e3, 800e3),     # ends at 4425e3: clipped, not whole
    ]
    return {"device": {0: _chip(parts)},
            "host": [{"name": "bench.traced_window", "start": 0.0, "dur": 4000e3,
                      "thread": "main"}]}


def test_classes_halves_and_pairs_counted_by_hand(by_hand):
    r = stepclass.reduce(by_hand)
    assert r["window_s"] == pytest.approx(4e-3) and r["unclassed"] == 0 and r["mixed"] == 0
    # busy: each execution less its 5e3 of idle, and [3500e3, 4000e3) of the clipped one
    busy = (920e3 + 100e3 + 320e3 + 100e3 + 1120e3 + 50e3 + 500e3) / 1e9
    assert r["busy_s"] == pytest.approx(busy)
    assert set(r["classes"]) == {"dlstep.fused.b1024", "dlstep.fused.b256", "dlstep.decode",
                                 "other:_copy_lane"}
    big = r["classes"]["dlstep.fused.b1024"]
    # the clipped third execution is not counted; the median of two is their mean
    assert big["executions"] == 2 and big["median_ms"] == pytest.approx((0.925 + 1.125) / 2)
    assert big["busy_ms"] == pytest.approx((0.920 + 1.120) / 2)   # the while is not added twice
    assert big["half_ms"] == pytest.approx({PREFILL: 0.9, DECODE: 0.09, JOIN: 0.01,
                                            NO_OP_NAME: 0.02})
    assert big["pair_ms"] == pytest.approx({
        (PREFILL, "dl.ffn"): 0.675, (PREFILL, "dl.attention"): 0.225,
        (DECODE, "dl.ffn"): 0.06, (DECODE, "dl.sampler"): 0.03,
        (JOIN, "dl.carry"): 0.01, (NO_OP_NAME, None): 0.02})
    assert big["share_of_busy"] == pytest.approx(100 * (920e3 + 1120e3) / 1e9 / busy)
    small = r["classes"]["dlstep.fused.b256"]
    assert small["executions"] == 1 and small["median_ms"] == pytest.approx(0.325)
    assert small["half_ms"][PREFILL] == pytest.approx(0.2)
    dec = r["classes"]["dlstep.decode"]
    assert dec["executions"] == 2 and dec["median_ms"] == pytest.approx(0.1)
    assert dec["half_ms"] == pytest.approx({PREFILL: 0.0, DECODE: 0.09, JOIN: 0.01,
                                            NO_OP_NAME: 0.0})
    # the halves of a class add up to its busy time: nothing is counted twice or lost
    for d in (big, small, dec):
        assert sum(d["half_ms"].values()) == pytest.approx(d["busy_ms"])
    # every operation in the window, the clipped execution's 500e3 of prefill included
    assert r["half_s"] == pytest.approx({
        PREFILL: (800e3 + 200e3 + 1000e3 + 500e3) / 1e9, DECODE: (3 * 90e3 + 2 * 90e3) / 1e9,
        JOIN: (3 * 10e3 + 2 * 10e3) / 1e9, NO_OP_NAME: (3 * 20e3 + 50e3) / 1e9})


def test_what_the_metrics_ask(by_hand):
    r = stepclass.reduce(by_hand)
    assert stepclass.class_median_ms(r, "dlstep.fused.b1024") == pytest.approx(1.025)
    assert stepclass.class_median_ms(r, "dlstep.fused.b256") == pytest.approx(0.325)
    assert stepclass.class_median_ms(r, "dlstep.fused.b64") is None
    # over ALL fused executions, whatever their bucket: it cannot flip
    assert stepclass.fused_half_ms(r, DECODE) == pytest.approx(0.09)
    assert stepclass.fused_half_ms(r, PREFILL) == pytest.approx(0.8)   # 0.2, 0.8, 1.0
    assert stepclass.half_share(r, PREFILL) == pytest.approx(100 * 2500e3 / 1e9 / r["busy_s"])
    assert stepclass.class_median_ms(None, "dlstep.decode") is None
    assert stepclass.fused_half_ms(None, DECODE) is None
    assert stepclass.half_share(None, PREFILL) is None


def test_two_chips_are_averaged():
    """The same steps on two chips, the second chip's 1024-bucket step 100e3
    longer: counts and seconds are a chip's, medians go over both."""
    chip0 = _chip([_fused(B1024, 100e3, 800e3), _decode(1100e3)])
    chip1 = _chip([_fused(B1024, 100e3, 900e3), _decode(1200e3)])
    r = stepclass.reduce({"device": {0: chip0, 1: chip1}, "host": []},
                         window=(0.0, 2000e3))
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((1020e3 + 1120e3) / 2 / 1e9)
    big = r["classes"]["dlstep.fused.b1024"]
    assert big["executions"] == 1 and big["median_ms"] == pytest.approx((0.925 + 1.025) / 2)
    assert r["classes"]["dlstep.decode"]["executions"] == 1
    assert r["half_s"][PREFILL] == pytest.approx((800e3 + 900e3) / 2 / 1e9)
    assert sum(d["share_of_busy"] for d in r["classes"].values()) == pytest.approx(100.0)


def test_a_step_program_without_a_class_is_counted_unclassed():
    """Executables from a cache older than the classes: the module is a step
    program by its name, and none of its operations says which."""
    old = [_op("fusion.1", "jit(_decode_pl)/jit(main)/" + LAYER + "dl.ffn/dot_general:", 0.0, 90e3)]
    r = stepclass.reduce({"device": {0: {"ops": old, "modules": [
        {"name": "jit__decode_pl(1)", "start": 0.0, "dur": 100e3}]}}, "host": []},
        window=(0.0, 200e3))
    assert r["unclassed"] == 1 and set(r["classes"]) == {"unclassed:_decode_pl"}
    assert r["classes"]["unclassed:_decode_pl"]["half_ms"][NO_OP_NAME] == pytest.approx(0.09)
    assert stepclass.reduce({"device": {}, "host": []}) is None


def test_log_table_names_every_class(by_hand):
    out = io.StringIO()
    stepclass.log_table(stepclass.reduce(by_hand), out=out)
    stepclass.log_table(None, out=out)
    err = out.getvalue()
    for cls in ("dlstep.fused.b1024", "dlstep.fused.b256", "dlstep.decode", "other:_copy_lane"):
        assert re.search(rf"^\[stepclass\] {re.escape(cls)}\s", err, re.M), cls
    assert "prefill/dl.ffn 0.675" in err and "nothing to read" in err


# -- a stretch of a real traced run of the change -------------------------------


@pytest.fixture(scope="module")
def recorded():
    return progtrace.load_stretch(STRETCH)


def test_recorded_stretch_halves_add_up_to_the_duration_less_the_idle(recorded):
    trace, window = recorded
    r = stepclass.reduce(trace, window)
    assert r["unclassed"] == 0 and r["mixed"] == 0
    classes = {c: d for c, d in r["classes"].items() if c.startswith("dlstep.")}
    assert "dlstep.decode" in classes and any(c.startswith(stepclass.FUSED) for c in classes)
    for cls, d in classes.items():
        halves = sum(d["half_ms"].values())
        # the operations of a class, by half, are the time some operation ran
        # (the execution less the idle inside it), to 1 %
        assert halves == pytest.approx(d["busy_ms"], rel=0.01), cls
        assert d["busy_ms"] <= d["median_ms"] * 1.0001 and d["busy_ms"] > 0.95 * d["median_ms"], cls
        per = d["per_execution"]
        assert all(sum(p[h] for h in stepclass.HALF_KEYS) == pytest.approx(p["busy_ms"], rel=0.01)
                   for p in per), cls
        if cls.startswith(stepclass.FUSED):
            assert d["half_ms"][PREFILL] > 0 and d["half_ms"][DECODE] > 0 and d["half_ms"][JOIN] > 0
        else:
            assert d["half_ms"][PREFILL] == 0
    assert sum(d["share_of_busy"] for d in r["classes"].values()) == pytest.approx(100.0, rel=0.01)


STRIP = re.compile(r"(?<![\w.])dl(?:step|half)\.[a-z_]+(?:\.b\d+)?/")


def test_recorded_stretch_reads_the_same_by_scope_without_the_new_components(recorded):
    """What `progtrace` gives the accepted readers (attention_step_ms,
    sampler_step_ms, scan_overhead_step_ms, unscoped_share) from the change's
    trace is what the same operations give with the classes and halves
    stripped from their ``op_name``."""
    trace, window = recorded
    stripped = {"host": trace["host"], "device": {
        c: {"modules": chip["modules"],
            "ops": [{**e, "op_name": STRIP.sub("", e["op_name"])} for e in chip["ops"]]}
        for c, chip in trace["device"].items()}}
    assert any(e["op_name"] != s["op_name"] for c in trace["device"]
               for e, s in zip(trace["device"][c]["ops"], stripped["device"][c]["ops"]))
    assert not any("dlstep." in e["op_name"] or "dlhalf." in e["op_name"]
                   for chip in stripped["device"].values() for e in chip["ops"])
    a, b = progtrace.reduce(trace, window), progtrace.reduce(stripped, window)
    for scopes in (("dl.attention",), ("dl.sampler",)):
        assert (progtrace.scope_ms_per_execution(a, "_decode_pl", scopes)
                == progtrace.scope_ms_per_execution(b, "_decode_pl", scopes) > 0)
    assert (progtrace.overhead_ms_per_execution(a, "_decode_pl")
            == progtrace.overhead_ms_per_execution(b, "_decode_pl") > 0)
    assert a["unscoped_s"] == b["unscoped_s"] > 0 and a["busy_s"] == b["busy_s"]
    assert a["scopes"].keys() == b["scopes"].keys()
    for fam in a["scopes"]:
        assert a["scopes"][fam]["self_s"] == b["scopes"][fam]["self_s"], fam
