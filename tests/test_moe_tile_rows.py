"""The third routed count (PR 48): ``RoutePlan.tiled_rows`` = used tiles x
their height, by the path ``slabs`` and ``assignments`` take (out of
``routed_ffn``, named by ``count_names``, back with a step's tokens) and, with
the pairs that took a row, from a fused step's prompt chunk too: into
``EngineStats.moe_tile_pairs`` and ``moe_tile_rows``."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.models import deepseek
from distributed_llama_multiusers_tpu.ops import pallas_q40_grouped as g
from distributed_llama_multiusers_tpu.runtime.engine import EngineStats

import latent_toy

SLAB = 2048 * 768  # the weights of a slab as the rule hears them


@pytest.mark.parametrize("n,k,n_experts,parked", [
    (4, 2, 8, ()), (32, 3, 8, (0, 7)), (64, 2, 4, (3,)), (200, 2, 4, ()), (5, 2, 8, (0, 1, 2, 3, 4)),
])
def test_tiled_rows_is_the_used_tiles_by_their_height(n, k, n_experts, parked):
    rng = np.random.default_rng(n)
    topi = np.stack([rng.choice(n_experts, size=k, replace=False) for _ in range(n)])
    live = np.ones(n, bool)
    live[list(parked)] = False
    tm = g.tile_rows(n * k, n_experts, SLAB)
    plan = g.route_plan(jnp.asarray(topi, jnp.int32), jnp.asarray(live), n_experts, tm)
    groups = np.bincount(topi[live].ravel(), minlength=n_experts)
    assert int(plan.tiled_rows) == int(plan.n_used) * tm == int(np.sum(-(-groups // tm))) * tm
    assert int(plan.assignments) <= int(plan.tiled_rows) or not live.any()
    if not live.any():
        assert int(plan.tiled_rows) == 0


@pytest.mark.parametrize("toy,want", [
    ("tiny_latent.json", deepseek.ROUTED_COUNTS),
    ("tiny_deepseek_v32.json", deepseek.ROUTED_COUNTS + ("unheld", "scored", "selected")),
])
def test_count_names_carry_the_count_after_the_two_it_joins(toy, want):
    cfg, family, _ = latent_toy.load(toy)
    assert deepseek.ROUTED_COUNTS == ("slabs", "assignments", "tiled_rows")
    assert set(deepseek.TILE_COUNTS) < set(deepseek.ROUTED_COUNTS)
    assert deepseek.count_names(family.program_config(cfg)) == want


@pytest.mark.parametrize("field", ["moe_tile_pairs", "moe_tile_rows"])
def test_engine_stats_keep_snapshot_and_reset_the_counts(field):
    stats = EngineStats()
    setattr(stats, field, 48)
    assert stats.snapshot()[field] == 48
    stats.reset()
    assert stats.snapshot()[field] == 0


@pytest.fixture(scope="module")
def toy_engines():
    """One engine a toy for the file: both of its cases prefill the lanes they
    read and reset the counters before they count."""
    @functools.cache
    def get(toy: str):
        cfg, family, _ = latent_toy.load(toy)
        return latent_toy.engine(family, cfg, 4)[0]

    return get


@pytest.mark.parametrize("toy", ["tiny_latent.json", "tiny_deepseek_v32.json", "tiny_lfm2.json"])
def test_a_decode_step_brings_the_count_back_with_its_tokens(toy_engines, toy):
    """Three live lanes a step: no expert gets more than three rows, so every
    fetched slab is one tile of 8 rows, in each block that routes (the latent
    block, a held share beside an indexer, the layer pattern)."""
    eng = toy_engines(toy)
    assert eng._count_names[:3] == deepseek.ROUTED_COUNTS
    n, seq = eng.n_lanes, eng.config.seq_len
    for lane in range(3):
        eng.prefill(lane, list(range(5 + lane, 25 + lane)))
    pos = np.full(n, seq, np.int32)
    pos[:3] = 20
    feed = np.zeros(n, np.int32)
    feed[:3] = [7, 8, 9]
    z = np.zeros(n, np.float32)
    eng.stats.reset()
    for _ in range(2):
        eng.decode_pipelined(pos, z, z + 0.9, np.ones(n, np.uint32), tokens=feed)
        eng.pipeline_consume()
        pos[:3] += 1
    eng.pipeline_flush(count=False)
    s = eng.stats.snapshot()
    assert s["moe_slabs_read"] > 0
    assert s["moe_tile_rows"] == 8 * s["moe_slabs_read"]
    assert s["moe_tile_pairs"] == s["moe_assignments"] <= s["moe_tile_rows"]
    eng.stats.reset()
    assert eng.stats.snapshot()["moe_tile_rows"] == 0


@pytest.mark.parametrize("toy", ["tiny_latent.json", "tiny_deepseek_v32.json", "tiny_lfm2.json"])
def test_a_fused_steps_chunk_brings_its_tiles_back_too(toy_engines, toy):
    """The boundary column of a fused step carries the chunk's pairs and the
    rows of its tiles into the tile counters alone: the decode steps' counts
    (``moe_assignments``, ``moe_slabs_read`` and what reads them) stay the
    decode half's."""
    eng = toy_engines(toy)
    c = eng.config
    n, seq = eng.n_lanes, c.seq_len
    eng.prefill(0, list(range(5, 25)))
    pos = np.full(n, seq, np.int32)
    pos[0] = 20
    feed = np.zeros(n, np.int32)
    feed[0] = 7
    z = np.zeros(n, np.float32)
    chunk = list(range(30, 42))
    bucket = eng.bucket_for(len(chunk))
    eng.stats.reset()
    eng.decode_prefill_fused(pos, z, z + 0.9, np.ones(n, np.uint32), p_lane=1, chunk=chunk, tokens=feed)
    greedy, _ = eng.pipeline_consume()
    assert greedy.shape[-1] == n + 1
    eng.pipeline_flush(count=False)
    s = eng.stats.snapshot()
    per_row = c.n_active_experts * c.n_routed_layers
    decode_pairs = s["moe_assignments"]
    assert 0 < decode_pairs <= per_row  # one live lane
    # every row of the bucket routes, the padded tail too; a held share keeps its own
    chunk_pairs = s["moe_tile_pairs"] - decode_pairs
    assert chunk_pairs == bucket * per_row if not c.experts_held_count else 0 < chunk_pairs < bucket * per_row
    assert s["moe_tile_pairs"] <= s["moe_tile_rows"] and s["moe_tile_rows"] % 8 == 0
    assert s["moe_slabs_read"] <= decode_pairs  # the chunk's slabs are not counted
