"""The grouped Q40 kernel (ops/pallas_q40_grouped.py) in interpret mode: every
row by its expert's dequantized weights, a slab no row chose never addressed,
parked rows routed nowhere, static shapes that hold any routing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.ops import pallas_q40_grouped as g
from distributed_llama_multiusers_tpu.quants.packed import (
    PackedQ40,
    Q40Experts,
    unpack_q40,
    unpack_q40_slabs,
)

L, E, D_IN, D_OUT = 2, 8, 256, 128


@pytest.fixture(scope="module")
def stack():
    pk = jax.random.bits(jax.random.PRNGKey(0), (L, E, D_IN // 2, D_OUT), jnp.uint8)
    sc = jax.random.uniform(jax.random.PRNGKey(1), (L, E, D_IN // 32, D_OUT)) * 0.01 + 0.001
    packed = PackedQ40(pk, sc.astype(jnp.float16))
    return packed, Q40Experts.from_packed(packed)


def _routing(seed, n, k, n_experts=E, parked=()):
    rng = np.random.default_rng(seed)
    topi = np.stack([rng.choice(n_experts, size=k, replace=False) for _ in range(n)])
    live = np.ones(n, bool)
    live[list(parked)] = False
    return jnp.asarray(topi, jnp.int32), jnp.asarray(live)


# groups of about ten rows at every height the rule returns: several tiles an
# expert at 8 rows, most of one tile padding at 128
BY_HEIGHT = [pytest.param(40, 2, (1,), tm, id=f"tm{tm}") for tm in g.HEIGHTS]


def _addressed(plan):
    """(layer, expert) blocks the index map names over the whole grid."""
    meta = np.concatenate([[1, int(plan.n_used)], np.asarray(plan.tile_expert)])
    return [g.tile_block_index(i, meta) for i in range(plan.tile_expert.shape[0])]


def test_scale_bits_round_trip(stack):
    packed, experts = stack
    got = unpack_q40_slabs(experts, 1, jnp.arange(E))
    np.testing.assert_array_equal(got, unpack_q40(PackedQ40(packed.packed[1], packed.scales[1])))


@pytest.mark.parametrize("n,k,parked,tm", [
    (5, 2, (2,), 8), (32, 3, (), 128), (32, 3, (0, 7, 31), 128), (70, 2, (3,), 128), *BY_HEIGHT])
def test_every_row_is_multiplied_by_its_experts_dequantized_weights(stack, n, k, parked, tm):
    packed, experts = stack
    topi, live = _routing(n + k, n, k, parked=parked)
    plan = g.route_plan(topi, live, E, tm)
    x = jax.random.normal(jax.random.PRNGKey(2), (n, D_IN), jnp.float32)
    rows = jnp.concatenate([x, jnp.zeros((1, D_IN))])[plan.src]
    got = g.q40_grouped_pallas(rows, experts, 1, plan, interpret=True)
    xla = g.grouped_matmul_xla(rows, experts, 1, plan)
    dense = np.asarray(unpack_q40(PackedQ40(packed.packed[1], packed.scales[1])))
    p_rows = rows.shape[0]
    for i in range(n):
        for j in range(k):
            pos = int(plan.pos[i, j])
            if not live[i]:
                assert pos == p_rows  # routed nowhere: past the last row
                continue
            want = np.asarray(x[i]) @ dense[int(topi[i, j])]
            np.testing.assert_allclose(got[pos], want, rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(xla[pos], want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("seed", range(6))
def test_a_slab_no_row_chose_is_never_addressed(seed):
    n, k = 6, 2
    topi, live = _routing(seed, n, k, parked=(seed % n,))
    plan = g.route_plan(topi, live, E, 8)
    chosen = {int(e) for i in range(n) if live[i] for e in topi[i]}
    blocks = _addressed(plan)
    assert {b[1] for b in blocks} == chosen
    assert {b[0] for b in blocks} == {1} and all(b[2:] == (0, 0) for b in blocks)
    experts = [b[1] for b in blocks]
    # groups follow each other in expert order, so a slab is fetched once; the
    # tiles past the last used one repeat its expert and fetch nothing
    used = experts[: int(plan.n_used)]
    assert used == sorted(used) and set(experts[int(plan.n_used):]) <= {used[-1]}
    assert int(plan.slabs) == len(chosen)
    assert int(plan.assignments) == int(live.sum()) * k


def test_parked_rows_take_no_tile_row_and_all_parked_computes_nothing(stack):
    _, experts = stack
    topi, _ = _routing(3, 4, 2)
    plan = g.route_plan(topi, jnp.zeros(4, bool), E, 8)
    assert int(plan.n_used) == 0 and int(plan.slabs) == 0 and int(plan.assignments) == 0
    assert np.all(np.asarray(plan.src) == 4) and np.all(np.asarray(plan.pos) == plan.src.shape[0])


@pytest.mark.parametrize("n,k,n_experts,tm", [
    (1, 2, 8, 8), (32, 6, 128, 8), (160, 6, 128, 8), (1024, 6, 128, 128), (64, 2, 4, 128),
    *[pytest.param(256, 6, 128, tm, id=f"tm{tm}") for tm in g.HEIGHTS],
    # a held share: 16 experts of a router's many, a decode step's pairs and a chunk's
    pytest.param(16, 8, 16, 8, id="held16-tm8"), pytest.param(256, 8, 16, 128, id="held16-tm128"),
])
def test_the_static_tile_count_holds_the_worst_routing(n, k, n_experts, tm):
    a = n * k
    n_tiles = g.max_tiles(a, n_experts, tm)
    # the routings that need the most tiles: as many groups of one row past a
    # whole tile as there are experts, and everything on one expert (with a
    # held share: every pair on the held experts, which no router does)
    for topi in (
        np.stack([(np.arange(k) + i) % n_experts for i in range(n)]),
        np.tile(np.arange(k), (n, 1)),
    ):
        plan = g.route_plan(jnp.asarray(topi, jnp.int32), jnp.ones(n, bool), n_experts, tm)
        assert plan.tile_expert.shape[0] == n_tiles and plan.src.shape[0] == n_tiles * tm
        assert int(plan.n_used) <= n_tiles
        pos = np.asarray(plan.pos).ravel()
        assert len(set(pos)) == a and pos.max() < n_tiles * tm  # no token dropped
        owner = np.asarray(plan.tile_expert)[pos // tm]
        np.testing.assert_array_equal(owner, topi.ravel())
        # a group starts on a tile: an expert's first row is a tile's first
        first = {int(e): int(pos[topi.ravel() == e].min()) for e in np.unique(topi)}
        assert all(p % tm == 0 for p in first.values())
    # all parked, and every pair another chip's: no tile is used, no row taken
    for topi, live in ((topi, np.zeros(n, bool)), (np.full((n, k), n_experts), np.ones(n, bool))):
        plan = g.route_plan(jnp.asarray(topi, jnp.int32), jnp.asarray(live), n_experts, tm)
        assert int(plan.n_used) == 0 and int(plan.tiled_rows) == 0 and int(plan.slabs) == 0
        assert np.all(np.asarray(plan.pos) == n_tiles * tm) and np.all(np.asarray(plan.src) == n)


def test_supports_says_which_stacks_the_kernel_takes(stack):
    packed, experts = stack
    assert g.grouped_supports(experts)
    assert not g.grouped_supports(packed)  # float16 scales: the XLA form
    wide = Q40Experts(jnp.zeros((1, 2, 1024, 16384), jnp.uint8), jnp.zeros((1, 2, 64, 16384), jnp.int16))
    assert not g.grouped_supports(wide)  # a block narrower than the output width


@pytest.mark.parametrize("d_in,d_out,blocks", [
    (2048, 768, 1), (768, 2048, 1),    # Kanana's slabs: one block, one fetch, as before
    (2048, 1536, 2), (1536, 2048, 2),  # LFM2's: 1.5 MiB, walked in two
])
def test_a_slab_is_planned_in_whole_reduction_blocks(d_in, d_out, blocks):
    assert g.slab_blocks(d_in, d_out) == blocks
    w = Q40Experts(jnp.zeros((1, 2, d_in // 2, d_out), jnp.uint8),
                   jnp.zeros((1, 2, d_in // 32, d_out), jnp.int16))
    assert g.grouped_supports(w)


@pytest.mark.parametrize("d_in,d_out", [(2048, 1536), (1536, 2048)])
@pytest.mark.parametrize("n,k,tm", [
    (6, 2, 8), (70, 4, 128),  # tiles of 8 rows, and of 128
    *[pytest.param(40, 4, tm, id=f"tm{tm}") for tm in g.HEIGHTS[1:4]],
])
def test_a_slab_of_several_blocks_against_the_gathered_product(d_in, d_out, n, k, tm):
    n_experts = 4
    pk = jax.random.bits(jax.random.PRNGKey(3), (2, n_experts, d_in // 2, d_out), jnp.uint8)
    sc = jax.random.uniform(jax.random.PRNGKey(4), (2, n_experts, d_in // 32, d_out)) * 0.01 + 0.001
    experts = Q40Experts.from_packed(PackedQ40(pk, sc.astype(jnp.float16)))
    topi, live = _routing(n, n, k, n_experts=n_experts, parked=(1,))
    plan = g.route_plan(topi, live, n_experts, tm)
    x = jax.random.normal(jax.random.PRNGKey(5), (n, d_in), jnp.float32)
    rows = jnp.concatenate([x, jnp.zeros((1, d_in))])[plan.src]
    got = np.asarray(g.q40_grouped_pallas(rows, experts, 1, plan, interpret=True))
    want = np.asarray(g.grouped_matmul_xla(rows, experts, 1, plan))
    used = int(plan.tiled_rows)  # rows of unused tiles are not written
    np.testing.assert_allclose(got[:used], want[:used], rtol=2e-5, atol=2e-4)


def test_unused_tiles_of_a_walked_slab_stay_on_the_last_block():
    topi, live = _routing(0, 6, 2)
    plan = g.route_plan(topi, live, E, 8)
    meta = jnp.concatenate([jnp.asarray([1, plan.n_used], jnp.int32), plan.tile_expert])
    n_used, n_tiles = int(plan.n_used), plan.tile_expert.shape[0]
    assert n_used < n_tiles
    blocks = [tuple(int(v) for v in g.tile_block_index(i, k, meta, n_k=2))
              for i in range(n_tiles) for k in range(2)]
    assert [b[2] for b in blocks[: 2 * n_used]] == [0, 1] * n_used
    assert set(blocks[2 * n_used:]) == {blocks[2 * n_used - 1]}  # nothing more is fetched


# the benchmark's two configurations whose chip holds every expert: experts,
# experts a row, an expert's matrix, the lanes of a decode step and the height
# of each prefill rung (the lab's table, PERF.md section 6, PR 48); a held
# share's calls are not the rule's yet (models/deepseek.py routed_ffn)
CONFIGURATIONS = {
    "kanana-2-30b-a3b": (128, 6, 2048 * 768, 32, {64: 8, 256: 16, 512: 32, 1024: 32}),
    "lfm2-24b-a2b": (64, 4, 2048 * 1536, 64, {64: 8, 256: 32, 512: 32, 1024: 64}),
}


@pytest.mark.parametrize("name,rows", [
    pytest.param(name, rows, id=f"{name}-{'decode' if rows is None else rows}")
    for name, c in CONFIGURATIONS.items() for rows in (None, *c[4])
])
def test_the_rule_at_the_benchmark_configurations_widths(name, rows):
    """A decode step keeps its 8-row tiles (the program it was); a chunk gets
    the height the lab found cheapest for its rung."""
    n_experts, k, slab, lanes, chunks = CONFIGURATIONS[name]
    want = 8 if rows is None else chunks[rows]
    assert g.tile_rows((rows or lanes) * k, n_experts, slab) == want


@pytest.mark.parametrize("slab", [2048 * 768, 2048 * 1536, 4096 * 4096])
def test_the_rule_never_falls_as_the_pairs_grow(slab):
    """More pairs over the same experts never want a shorter tile, and the
    rule walks from 8 rows (a group under one row) to 128 (hundreds)."""
    got = [g.tile_rows(a, 64, slab) for a in (16, 64, 256, 1024, 4096, 16384, 65536)]
    assert got == sorted(got) and got[0] == 8 and got[-1] == 128
