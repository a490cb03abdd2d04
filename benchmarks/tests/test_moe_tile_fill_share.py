"""`moe_tile_fill_share` (PR 48): nothing without the program's counters (the
parent has none), the quotient with them, and its entry in `BENCHMARK.json`."""

import os
from types import SimpleNamespace

import pytest

from harness import cells

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUTED_CELLS = ["kanana2_30b_chat_saturated", "lfm2_24b_chat_saturated",
                "deepseek_v32_longctx_saturated", "command_a_plus_longctx_saturated"]


@pytest.fixture(scope="module")
def read():
    path = os.path.join(BENCH_DIR, "metrics", "moe_tile_fill_share.py")
    return cells.load_module(path, "m_tile_fill").read


@pytest.mark.parametrize("counters", [
    {},  # a program without routed layers' counts
    {"moe_slabs_read": 1130, "moe_assignments": 4608},  # the parent: no count of tiles
    {"moe_assignments": 0, "moe_tile_pairs": 0, "moe_tile_rows": 0},  # no step in the window
])
def test_nothing_to_read_is_none(read, counters):
    assert read(SimpleNamespace(counters=counters)) is None


def test_the_share_is_the_tiles_pairs_over_their_rows(read):
    # the decode steps' assignments are not the numerator: a chunk's pairs count too
    got = read(SimpleNamespace(counters={"moe_assignments": 4608, "moe_tile_pairs": 20000,
                                         "moe_tile_rows": 36000}))
    assert got == pytest.approx(100.0 * 20000 / 36000) and got <= 100.0


def test_the_benchmark_names_it_in_the_four_routed_cells():
    bench = cells.load_benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "moe_tile_fill_share"]
    assert entry == {
        "name": "moe_tile_fill_share", "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "routed experts", "moves": "tokens_per_s", "workloads": ROUTED_CELLS,
    }
    for cell in ROUTED_CELLS:
        assert "moe_tile_fill_share" in [m["name"] for m in cells.cell_metrics(bench, cell, "per_layer")]
    assert "moe_tile_fill_share" not in [
        m["name"] for m in cells.cell_metrics(bench, "mistral7b_chat_saturated", "per_layer")]
