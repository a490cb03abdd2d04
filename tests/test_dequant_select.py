"""Selection-table semantics for ``DLLAMA_DEQUANT=auto``
(ops/dequant_select): load validation fails loudly, most-specific-match
precedence, the decode/prefill boundary rides the blockdot cap, measured
winners round-trip through record_win, and the table freezes at warmup.

Pure-host module under test: these tests run without touching a device.
"""

from __future__ import annotations

import json

import pytest

from distributed_llama_multiusers_tpu.ops import dequant_select as ds
from distributed_llama_multiusers_tpu.ops import pallas_q40 as pq
from distributed_llama_multiusers_tpu.ops.pallas_q40 import (
    BLOCKDOT_MAX_M,
    DEQUANT_MODES,
    SELECTABLE_MODES,
)


@pytest.fixture(autouse=True)
def fresh_state():
    ds._reset_for_tests()
    yield
    ds._reset_for_tests()


def _write_table(tmp_path, rules, **top):
    p = tmp_path / "table.json"
    p.write_text(json.dumps({"version": 1, "rules": rules, **top}))
    return str(p)


# -- table load + validation --------------------------------------------------


def test_shipped_table_loads_and_covers_both_classes():
    t = ds.DequantTable()  # the checked-in ops/dequant_table.json
    assert t.resolve(4096, 14336, "decode") == "i8blockdot"
    assert t.resolve(4096, 14336, "prefill") == "bf16chain"
    assert t.provenance["rows"] >= 2
    assert t.provenance["version"] is not None


def test_unknown_mode_in_table_fails_loudly(tmp_path):
    path = _write_table(tmp_path, [
        {"d_in": "*", "d_out": "*", "m_class": "*", "mode": "turbo9"},
    ])
    with pytest.raises(ValueError, match="turbo9"):
        ds.DequantTable(path)


def test_unknown_m_class_in_table_fails_loudly(tmp_path):
    path = _write_table(tmp_path, [
        {"d_in": "*", "d_out": "*", "m_class": "midfill", "mode": "v4"},
    ])
    with pytest.raises(ValueError, match="m_class"):
        ds.DequantTable(path)


# -- resolution ---------------------------------------------------------------


def test_most_specific_rule_wins(tmp_path):
    path = _write_table(tmp_path, [
        {"d_in": "*", "d_out": "*", "m_class": "decode", "mode": "i8blockdot"},
        {"d_in": 512, "d_out": "*", "m_class": "decode", "mode": "blockdot"},
        {"d_in": 512, "d_out": 1024, "m_class": "decode", "mode": "u8chain"},
    ])
    t = ds.DequantTable(path)
    assert t.resolve(128, 256, "decode") == "i8blockdot"
    assert t.resolve(512, 256, "decode") == "blockdot"
    assert t.resolve(512, 1024, "decode") == "u8chain"


def test_no_matching_rule_falls_back(tmp_path):
    path = _write_table(tmp_path, [
        {"d_in": "*", "d_out": "*", "m_class": "decode", "mode": "i8blockdot"},
    ])
    t = ds.DequantTable(path)
    assert t.resolve(128, 256, "prefill") == ds.FALLBACK_MODE


def test_m_class_boundary_is_the_blockdot_cap():
    assert ds.m_class_of(1) == "decode"
    assert ds.m_class_of(BLOCKDOT_MAX_M) == "decode"
    assert ds.m_class_of(BLOCKDOT_MAX_M + 1) == "prefill"


def test_resolve_mode_records_sites(tmp_path, monkeypatch):
    path = _write_table(tmp_path, [
        {"d_in": "*", "d_out": "*", "m_class": "decode", "mode": "blockdot"},
    ])
    monkeypatch.setenv(ds._TABLE_ENV, path)
    assert ds.resolve_mode(512, 1024, 4) == "blockdot"
    assert ds.resolved_sites() == {"512x1024/decode": "blockdot"}


# -- record_win round-trip ----------------------------------------------------


def test_record_win_round_trip_and_upsert(tmp_path, monkeypatch):
    path = str(tmp_path / "fresh.json")
    monkeypatch.setenv(ds._TABLE_ENV, path)
    ds.record_win(512, 1024, "decode", "blockdot", source="unit")
    t = ds.reload_table()
    assert t.resolve(512, 1024, "decode") == "blockdot"
    rows = t.provenance["rows"]
    # same key upserts in place — no duplicate rows accumulate
    ds.record_win(512, 1024, "decode", "u8chain", source="unit2")
    t = ds.reload_table()
    assert t.resolve(512, 1024, "decode") == "u8chain"
    assert t.provenance["rows"] == rows
    with open(path) as f:
        data = json.load(f)
    assert data["rules"][0]["source"] == "unit2"
    assert data["updated"]


def test_record_win_validates_mode_and_class(tmp_path, monkeypatch):
    monkeypatch.setenv(ds._TABLE_ENV, str(tmp_path / "t.json"))
    with pytest.raises(ValueError, match="unknown dequant mode"):
        ds.record_win("*", "*", "decode", "turbo9", source="unit")
    with pytest.raises(ValueError, match="unknown m_class"):
        ds.record_win("*", "*", "midfill", "v4", source="unit")


# -- freeze semantics ---------------------------------------------------------


def test_freeze_blocks_reload_and_reports_provenance(tmp_path, monkeypatch):
    path = _write_table(tmp_path, [
        {"d_in": "*", "d_out": "*", "m_class": "*", "mode": "i8blockdot"},
    ])
    monkeypatch.setenv(ds._TABLE_ENV, path)
    pq.set_dequant_mode("auto")
    try:
        prov = ds.freeze_for_serving()
        assert prov is not None and prov["rows"] == 1
        with pytest.raises(RuntimeError, match="frozen"):
            ds.reload_table()
        # record_win still writes the FILE — the live resolution is pinned,
        # the next serving start picks the row up
        ds.record_win(64, 128, "decode", "v4", source="unit")
    finally:
        pq.set_dequant_mode(None)


def test_freeze_under_fixed_mode_skips_table_load(tmp_path, monkeypatch):
    # a fixed mode never consults the table: freeze must not even load it
    # (a corrupt table file cannot take down a non-auto serving start)
    bad = tmp_path / "corrupt.json"
    bad.write_text("{not json")
    monkeypatch.setenv(ds._TABLE_ENV, str(bad))
    pq.set_dequant_mode("i8blockdot")
    try:
        assert ds.freeze_for_serving() is None
    finally:
        pq.set_dequant_mode(None)


# -- stats ---------------------------------------------------------------------


def test_dequant_stats_keys(tmp_path, monkeypatch):
    path = _write_table(tmp_path, [
        {"d_in": "*", "d_out": "*", "m_class": "*", "mode": "bf16chain"},
    ], updated="2026-08-07")
    monkeypatch.setenv(ds._TABLE_ENV, path)
    pq.set_dequant_mode("auto")
    try:
        ds.resolve_mode(256, 512, 8)
        stats = ds.dequant_stats()
        assert stats["dequant_mode"] == "auto"
        assert stats["dequant_sites"] == {"256x512/decode": "bf16chain"}
        assert stats["dequant_table"]["rows"] == 1
        assert stats["dequant_table"]["updated"] == "2026-08-07"
    finally:
        pq.set_dequant_mode(None)


# -- block geometry -------------------------------------------------------------


def test_block_geometry_constants_are_usable():
    """The kernel's block geometry is three constants (a chip measurement
    may move them, ROADMAP S2(b)(ii)). The widest block must be a positive
    multiple of 128, or no tile candidate divides any plane and every
    matmul silently takes the XLA fallback; and the block a plan aims for
    fits the VMEM bound."""
    from distributed_llama_multiusers_tpu.quants import packed

    assert packed.PALLAS_W_MAX > 0 and packed.PALLAS_W_MAX % 128 == 0
    assert 0 < pq.SINGLE_SLAB_BYTES <= pq.MAX_BLOCK_BYTES
    assert 0 < pq.TARGET_BLOCK_BYTES <= pq.MAX_BLOCK_BYTES


@pytest.mark.parametrize("d_in,d_out", [
    (4096, 4096), (4096, 1024), (4096, 14336), (14336, 4096),  # Mistral 7B
    (4096, 32768),  # its head
    (3584, 3584), (3584, 512), (3584, 18944), (18944, 3584),  # Qwen2.5 7B
    (3584, 152064),  # its head, which the loader pads
])
def test_packed_layout_and_block_plan_agree(d_in, d_out):
    """The two modules that share the geometry agree at the benchmark
    cells' widths: the width the packed layout pads a plane to is a width
    the kernel plans blocks for, inside its VMEM bound."""
    from distributed_llama_multiusers_tpu.quants import packed

    padded = packed.padded_d_out(d_out)
    w_tile = packed.pallas_wide_tile(padded)
    assert w_tile is not None and w_tile <= packed.PALLAS_W_MAX
    assert padded % w_tile == 0
    assert sum(packed.pallas_sub_tiles(w_tile)) == w_tile
    plan = pq._plan_blocks(d_in, padded)
    assert plan is not None and plan[0] == w_tile
    rows = plan[1]
    assert (d_in // 2) % rows == 0
    assert rows * w_tile <= pq.MAX_BLOCK_BYTES


# -- CLI pairing --------------------------------------------------------------


def test_args_dequant_choices_match_selectable_modes():
    """app/args.py stays jax-free, so its --dequant choices list is a
    hand-copied mirror of SELECTABLE_MODES — this pins the pairing."""
    from distributed_llama_multiusers_tpu.app.args import build_parser

    parser = build_parser("test")
    action = next(a for a in parser._actions if a.dest == "dequant")
    assert set(action.choices) == set(SELECTABLE_MODES)
    assert action.default is None  # None -> leave the env/default alone


def test_selectable_is_modes_plus_auto():
    assert SELECTABLE_MODES == DEQUANT_MODES + ("auto",)
