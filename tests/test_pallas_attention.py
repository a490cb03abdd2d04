"""Attention in place (ops/pallas_attention.py: the decode kernel, its latent
form of PR 59, and at the end of the file the prefill kernel of PR 51) against
the dense path.

The kernel runs in interpret mode on the CPU, as tests/test_pallas_q40.py
runs its kernel: that proves its arithmetic and its work list, not that it
exists on the chip (tests/test_chip_compile_attention.py compiles it for a v5e).
`_dense_attention` on the same stacked cache is the reference; both take
bf16 keys and values, the kernel rounds its probabilities to bf16 for the
second product as the chip's default precision does, so they agree to a few
parts in a thousand and not to the bit.

Two forms of stack go through every case (ops/pallas_attention.py, "How the
stack goes in"): 128-wide heads on an axis of their own, and the merged rows
of narrower heads that models/hybrid.py keeps (8 kv heads of 64: rows of 512).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.models import llama
from distributed_llama_multiusers_tpu.models.config import LlamaConfig
from distributed_llama_multiusers_tpu.ops import linear, pallas_attention as pa

HD = pa.HEAD_SIZE
BLOCK = pa.BLOCK_ROWS
SEQ = 4 * BLOCK
LAYERS, LAYER = 3, 1  # a stack, read in the middle
# the configurations' head shapes, (n_kv, group, head): two stacks of 128-wide
# heads, and one whose 64-wide heads are merged into rows of n_kv * head
HEAD_SHAPES = [(8, 4, HD), (4, 7, HD), (8, 4, 64)]
shapes = pytest.mark.parametrize(
    "n_kv,group,hd", HEAD_SHAPES, ids=["8x4x128", "4x7x128", "8x4x64_merged"])
# position 0, a block's last row, a block's first row, the cache's last row,
# parked, somewhere inside a block
POSITIONS = np.array([0, BLOCK - 1, BLOCK, SEQ - 1, SEQ, 2 * BLOCK + 77],
                     np.int32)


def _stack(n_kv, group, lanes, seed, hd=HD):
    """Queries and a K/V stack in the form the block of that head size keeps:
    heads of ``HD`` on their own axis, narrower ones merged into the row."""
    rng = np.random.default_rng(seed)
    shape = (LAYERS, lanes, SEQ) + ((n_kv, hd) if hd == HD else (n_kv * hd,))
    k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((lanes, n_kv * group, hd)), jnp.bfloat16)
    return q, k, v


def _kernel(q, k, v, positions, layer=LAYER):
    work = pa.lane_blocks(jnp.asarray(positions), SEQ)
    return np.asarray(pa.decode_attention(q, k, v, layer, work, q.shape[-1] ** -0.5,
                                          interpret=True))


def _dense(q, k, v, positions, layer=LAYER):
    lanes, n_heads, hd = q.shape
    n_kv = k[layer].size // (lanes * SEQ * hd)
    qf = q.astype(jnp.float32).reshape(lanes, 1, n_kv, n_heads // n_kv, hd)
    mask = jnp.arange(SEQ)[None, None, :] <= jnp.asarray(positions)[:, None, None]
    plane = lambda c: c[layer].astype(jnp.float32).reshape(lanes, SEQ, n_kv, hd)
    out = llama._dense_attention(qf, plane(k), plane(v), mask, hd ** -0.5)
    return np.asarray(out).reshape(lanes, n_heads, hd)


@shapes
def test_kernel_matches_dense_attention_at_every_kind_of_position(n_kv, group, hd):
    q, k, v = _stack(n_kv, group, len(POSITIONS), seed=n_kv, hd=hd)
    got, want = _kernel(q, k, v, POSITIONS), _dense(q, k, v, POSITIONS)
    live = POSITIONS < SEQ
    for b in np.flatnonzero(live):
        np.testing.assert_allclose(
            got[b], want[b], rtol=0, atol=6e-3 * np.abs(want[b]).max(),
            err_msg=f"lane {b} at position {POSITIONS[b]}",
        )
    # one row of its own is a softmax over one score: the value row itself
    np.testing.assert_array_equal(
        got[0].reshape(n_kv, group, hd),
        np.broadcast_to(np.asarray(v[LAYER, 0, 0], np.float32).reshape(n_kv, 1, hd),
                        (n_kv, group, hd)),
    )
    # a parked lane: zeros, whatever its rows hold
    assert not got[~live].any()


@shapes
def test_a_lane_is_a_function_of_its_own_rows_and_position(n_kv, group, hd):
    """Bit for bit: every OTHER lane's position and rows change (parked,
    moved, refilled), and the lane's output does not."""
    q, k, v = _stack(n_kv, group, len(POSITIONS), seed=10 + n_kv, hd=hd)
    base = _kernel(q, k, v, POSITIONS)
    q2, k2, v2 = _stack(n_kv, group, len(POSITIONS), seed=20 + n_kv, hd=hd)
    for b in np.flatnonzero(POSITIONS < SEQ):
        others = np.roll(POSITIONS, 1 + b)
        others[b] = POSITIONS[b]
        keep = lambda a, a2: a2.at[:, b].set(a[:, b])
        got = _kernel(q2.at[b].set(q[b]), keep(k, k2), keep(v, v2), others)
        np.testing.assert_array_equal(got[b], base[b], err_msg=f"lane {b}")


@shapes
def test_rows_above_a_lanes_position_do_not_reach_its_output(n_kv, group, hd):
    """NaN in every row past each lane's position, in its last block and in
    the blocks it never fetches, and in every row of the parked lane."""
    q, k, v = _stack(n_kv, group, len(POSITIONS), seed=30 + n_kv, hd=hd)
    base = _kernel(q, k, v, POSITIONS)
    stale = (np.arange(SEQ)[None, :] > POSITIONS[:, None]).reshape(
        (1, len(POSITIONS), SEQ) + (1,) * (k.ndim - 3))
    got = _kernel(q, jnp.where(stale, jnp.nan, k), jnp.where(stale, jnp.nan, v),
                  POSITIONS)
    np.testing.assert_array_equal(got, base)


@pytest.mark.parametrize("hd", [HD, 64], ids=["128", "64_merged"])
def test_every_lane_parked_reads_nothing_and_returns_zeros(hd):
    q, k, v = _stack(8, 4, 4, seed=3, hd=hd)
    parked = np.full(4, SEQ, np.int32)
    n_items, plan = pa.lane_blocks(jnp.asarray(parked), SEQ)
    assert int(n_items) == 4 and not (np.asarray(plan)[4, :4] & (pa.FULL | pa.LAST)).any()
    assert not _kernel(q, jnp.full_like(k, jnp.nan), jnp.full_like(v, jnp.nan),
                       parked).any()


def test_work_list_walks_each_lanes_blocks_and_parks_on_the_held_block():
    positions = np.array([SEQ, BLOCK, SEQ, 5, SEQ], np.int32)
    n_items, plan = pa.lane_blocks(jnp.asarray(positions), SEQ)
    lane, src, block, pos, code = np.asarray(plan)[:, : int(n_items)]
    assert lane.tolist() == [0, 1, 1, 2, 3, 4]
    # a parked lane stays on the block the pipeline holds: ahead of the first
    # live lane its first block, behind a live lane that lane's last block
    assert src.tolist() == [1, 1, 1, 1, 3, 3] and block.tolist() == [0, 0, 1, 1, 0, 0]
    assert pos.tolist() == [SEQ, BLOCK, BLOCK, SEQ, 5, SEQ]
    first_final = pa.FIRST | pa.FINAL
    assert code.tolist() == [first_final, pa.FIRST | pa.FULL, pa.FINAL | pa.LAST,
                             first_final, first_final | pa.LAST, first_final]
    assert pa.rows_read(positions, SEQ) == 2 * BLOCK + BLOCK


@pytest.mark.parametrize("positions,want", [
    ([0], BLOCK), ([BLOCK - 1], BLOCK), ([BLOCK], 2 * BLOCK),
    ([SEQ - 1], SEQ), ([SEQ], 0), ([-1], 0), ([3, SEQ, BLOCK + 1], 3 * BLOCK),
])
def test_rows_read_is_whole_blocks_up_to_each_live_lanes_row(positions, want):
    assert pa.rows_read(np.array(positions), SEQ) == want


@pytest.mark.parametrize("shape,dtype,n_kv,want", [
    ((LAYERS, 2, SEQ, 8, HD), jnp.bfloat16, None, True),  # 128-wide heads say their own count
    ((LAYERS, 2, SEQ, 8, HD), jnp.bfloat16, 8, True),
    ((LAYERS, 2, SEQ, 8 * 64), jnp.bfloat16, 8, True),  # the merged stack
    ((LAYERS, 2, SEQ, 8 * 64), jnp.bfloat16, None, False),  # a merged row does not say
    ((LAYERS, 2, SEQ, 8 * 64), jnp.float32, 8, False),
    ((LAYERS, 2, SEQ, 8 * 64), jnp.float8_e4m3fn, 8, False),
    ((LAYERS, 2, SEQ, 7 * 64), jnp.bfloat16, 7, False),  # not whole 128-lane tiles
    ((LAYERS, 2, 2000, 8 * 64), jnp.bfloat16, 8, False),  # not whole blocks
    ((LAYERS, 2, SEQ, 8 * 64), jnp.bfloat16, 3, False),  # heads that do not divide the row
    ((LAYERS, 2, SEQ, 32 * 64), jnp.bfloat16, 32, False),  # wider than any compiled block
    ((LAYERS, 2, SEQ, 8, 64), jnp.bfloat16, 8, False),  # a 64-wide last axis
    ((2, SEQ, 8 * 64), jnp.bfloat16, 8, False),  # no stack
], ids=["heads128", "heads128_n_kv", "merged", "merged_no_n_kv", "merged_f32", "merged_f8",
        "merged_448", "merged_ctx2000", "merged_n_kv3", "merged_2048", "heads64", "rank3"])
def test_supports_says_which_stacks_are_taken(shape, dtype, n_kv, want):
    assert pa.supports(jax.ShapeDtypeStruct(shape, dtype), 32, n_kv) is want


def test_query_heads_that_are_no_multiple_of_the_kv_heads_are_declined():
    merged = jax.ShapeDtypeStruct((LAYERS, 2, SEQ, 8 * 64), jnp.bfloat16)
    assert pa.supports(merged, 24, 8) and not pa.supports(merged, 20, 8)


def test_merged_rows_pad_their_query_heads_to_whole_tiles():
    """24 heads on 8 kv heads of 64: the padding heads' zero queries score
    every row alike and are cut off; the real heads match the dense path."""
    q, k, v = _stack(8, 3, len(POSITIONS), seed=5, hd=64)
    got, want = _kernel(q, k, v, POSITIONS), _dense(q, k, v, POSITIONS)
    live = POSITIONS < SEQ
    assert got.shape == want.shape == (len(POSITIONS), 24, 64)
    np.testing.assert_allclose(got[live], want[live], rtol=0, atol=6e-3 * np.abs(want).max())
    assert not got[~live].any()


def _forward_setup(cache_dtype):
    cfg = LlamaConfig(dim=256, hidden_dim=512, n_layers=2, n_heads=2,
                      n_kv_heads=1, vocab_size=64, seq_len=2 * BLOCK)
    from distributed_llama_multiusers_tpu.models.loader import params_from_random

    params = params_from_random(cfg, seed=0, dtype=jnp.bfloat16, scale=0.05)
    lanes = 3
    cache = llama.init_kv_cache(cfg, lanes, dtype=cache_dtype)
    rng = np.random.default_rng(1)
    cache = llama.KVCache(*(
        jnp.asarray(rng.standard_normal(c.shape) * 0.5, cache_dtype) for c in cache
    ))
    tokens = jnp.asarray([[3], [9], [27]], jnp.int32)
    positions = jnp.asarray([[BLOCK + 4], [cfg.seq_len], [7]], jnp.int32)
    return cfg, params, tokens, positions, cache


def test_forward_takes_the_kernel_only_where_its_inputs_allow(monkeypatch):
    """`llama_forward` at one row a lane with the kernel on (interpret mode)
    agrees with the dense path, appends the same rows, and leaves every other
    width and cache to the dense path."""
    cfg, params, tokens, positions, cache = _forward_setup(jnp.bfloat16)
    fwd = jax.jit(lambda p, t, pos, c: llama.llama_forward(cfg, p, t, pos, c))
    dense_logits, dense_cache = fwd(params, tokens, positions, cache)
    assert not llama.decode_attention_engages(cache, None, cfg.n_heads)  # the CPU

    calls = []
    real = pa.decode_attention
    monkeypatch.setattr(pa, "decode_attention",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    linear.set_pallas_interpret(True)
    try:
        assert llama.decode_attention_engages(cache, None, cfg.n_heads)
        fwd = jax.jit(lambda p, t, pos, c: llama.llama_forward(cfg, p, t, pos, c))
        logits, new_cache = fwd(params, tokens, positions, cache)
        assert len(calls) == 1  # the scan's body, traced once
        # wider than one row; an f32 cache: the dense path as ever
        wide = jnp.tile(tokens, (1, 2))
        llama.llama_forward(cfg, params, wide, jnp.tile(positions, (1, 2)), cache)
        _, _, _, _, cache32 = _forward_setup(jnp.float32)
        assert not llama.decode_attention_engages(cache32, None, cfg.n_heads)
        assert len(calls) == 1
    finally:
        linear.set_pallas_interpret(False)
    live = np.array([0, 2])
    for got, want in zip(new_cache, dense_cache):  # the appends do not differ
        np.testing.assert_array_equal(
            np.asarray(got[0], np.float32), np.asarray(want[0], np.float32))
    got, want = np.asarray(logits)[live], np.asarray(dense_logits)[live]
    assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()


# ---- latent rows: one key row a position, its own value (PR 59) --------------

RANK, ROPE_LEAF, ROPE, LATENT_HEADS = 512, 128, 64, 32
LATENT_SCALE = (128 + ROPE) ** -0.5
LBLOCK = pa.block_rows(latent=True)  # taller than the heads' (PERF.md section 6, PR 59)
LSEQ = 4 * LBLOCK
# what a lane's position may be to its blocks: lanes of one step, by kind
LATENT_POSITIONS = {
    "inside_a_block": [77, LBLOCK + 5, 2 * LBLOCK + 77],
    "a_blocks_last_row": [LBLOCK - 1, 2 * LBLOCK - 1, 3 * LBLOCK - 1],
    "a_blocks_first_row": [0, LBLOCK, 3 * LBLOCK],
    "the_last_block": [LSEQ - 1, LSEQ - LBLOCK, LSEQ - 9],
    "parked_beside_live": [LSEQ, 300, LSEQ + 5],
    "every_lane_parked": [LSEQ, LSEQ, LSEQ],
}


def _latent_stack(lanes, seed):
    """The absorbed and rotated queries side by side, and the two leaves of a
    latent cache: the latent rows and their rope parts, zero past the rope
    width as ``models/deepseek.py`` pads them."""
    rng = np.random.default_rng(seed)
    c = jnp.asarray(rng.standard_normal((LAYERS, lanes, LSEQ, RANK)), jnp.bfloat16)
    r = jnp.asarray(rng.standard_normal((LAYERS, lanes, LSEQ, ROPE_LEAF)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((lanes, LATENT_HEADS, RANK + ROPE_LEAF)) * 0.3,
                    jnp.bfloat16)
    return q.at[..., RANK + ROPE:].set(0), c, r.at[..., ROPE:].set(0)


def _latent_kernel(q, c, r, positions, layer=LAYER):
    work = pa.lane_blocks(jnp.asarray(positions, jnp.int32), LSEQ, LBLOCK)
    return np.asarray(pa.decode_attention(q, c, r, layer, work, LATENT_SCALE,
                                          interpret=True, latent=True))


def _latent_planes(q, c, r, positions, layer=LAYER):
    from distributed_llama_multiusers_tpu.models import deepseek

    mask = jnp.arange(LSEQ)[None, None, :] <= jnp.asarray(positions)[:, None, None]
    return np.asarray(deepseek.latent_plane_attention(
        q[:, None, :, :RANK], q[:, None, :, RANK:], c[layer], r[layer], mask, LATENT_SCALE))[:, 0]


@pytest.mark.parametrize("kind", LATENT_POSITIONS, ids=list(LATENT_POSITIONS))
def test_latent_kernel_matches_the_plane_read_and_stale_rows_reach_nothing(kind):
    """`o~` of `models/deepseek.py`'s plane read on a bf16 cache at rank 512
    beside a rope leaf of 128, with NaN in every row past each lane's position
    (its last block's tail, the blocks it never fetches, every row of a parked
    lane): a live lane agrees, a parked lane is zeros."""
    positions = np.asarray(LATENT_POSITIONS[kind], np.int32)
    q, c, r = _latent_stack(len(positions), seed=len(kind))
    want = _latent_planes(q, c, r, positions)
    stale = (np.arange(LSEQ)[None, :] > positions[:, None])[None, :, :, None]
    got = _latent_kernel(q, jnp.where(stale, jnp.nan, c), jnp.where(stale, jnp.nan, r), positions)
    assert got.shape == (len(positions), LATENT_HEADS, RANK) and np.isfinite(got).all()
    live = positions < LSEQ
    for b in np.flatnonzero(live):
        np.testing.assert_allclose(
            got[b], want[b], rtol=0, atol=6e-3 * np.abs(want[b]).max(),
            err_msg=f"lane {b} at position {positions[b]}")
    assert not got[~live].any()


def test_a_latent_row_of_its_own_is_its_own_value():
    """Position 0: a softmax over one score, so `o~` is the latent row itself,
    bit for bit, for every head."""
    q, c, r = _latent_stack(2, seed=4)
    got = _latent_kernel(q, c, r, [0, 0])
    np.testing.assert_array_equal(
        got, np.broadcast_to(np.asarray(c[LAYER, :, 0], np.float32)[:, None, :], got.shape))


@pytest.mark.parametrize("k,v,dtype,want", [
    ((LAYERS, 2, SEQ, 512), (LAYERS, 2, SEQ, 128), jnp.bfloat16, True),  # Kanana's leaves
    ((LAYERS, 2, SEQ, 128), (LAYERS, 2, SEQ, 128), jnp.bfloat16, True),
    ((LAYERS, 2, SEQ, 64), (LAYERS, 2, SEQ, 128), jnp.bfloat16, False),  # tests/latent_toy.py's rank
    ((LAYERS, 2, SEQ, 512), (LAYERS, 2, SEQ, 64), jnp.bfloat16, False),  # an unpadded rope leaf
    ((LAYERS, 2, SEQ, 512), (LAYERS, 2, SEQ, 128), jnp.float32, False),
    ((LAYERS, 2, SEQ, 512), (LAYERS, 2, SEQ, 128), jnp.float8_e4m3fn, False),
    ((LAYERS, 2, 2000, 512), (LAYERS, 2, 2000, 128), jnp.bfloat16, False),  # not whole blocks
    ((LAYERS, 2, 3 * BLOCK, 512), (LAYERS, 2, 3 * BLOCK, 128), jnp.bfloat16, False),  # ... of 512
    ((LAYERS, 2, SEQ, 512), (LAYERS, 3, SEQ, 128), jnp.bfloat16, False),  # leaves of unlike lanes
    ((LAYERS, 2, SEQ, 1536), (LAYERS, 2, SEQ, 128), jnp.bfloat16, False),  # past the budgeted block
    ((LAYERS, 2, SEQ, 512), None, jnp.bfloat16, False),  # no rope leaf
    ((LAYERS, 2, SEQ, 4, 128), (LAYERS, 2, SEQ, 4, 128), jnp.bfloat16, False),  # heads, not rows
], ids=["rank512", "rank128", "rank64", "rope64", "f32", "f8", "ctx2000", "ctx768", "unlike_lanes",
        "rank1536", "no_rope_leaf", "rank5"])
def test_supports_says_which_latent_caches_are_taken(k, v, dtype, want):
    leaf = lambda shape: shape and jax.ShapeDtypeStruct(shape, dtype)
    assert pa.supports(leaf(k), LATENT_HEADS, None, leaf(v), latent=True) is want


def test_a_latent_cache_is_not_taken_for_merged_heads_nor_merged_heads_for_one():
    """A rank-4 leaf does not say what its row holds: Kanana's `[.., 512]` and
    `[.., 128]` leaves would pass for merged K / V rows of 32 heads 16 and 4
    wide. The caller says the form, and the engagement question keeps an
    indexer's cache (a third leaf: the rows are chosen, not read in place) out
    of the latent one."""
    from distributed_llama_multiusers_tpu.models import deepseek

    k = jax.ShapeDtypeStruct((LAYERS, 2, SEQ, 512), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((LAYERS, 2, SEQ, 128), jnp.bfloat16)
    assert pa.supports(k, 32, 32, v) and pa.supports(k, 32, None, v, latent=True)
    linear.set_pallas_interpret(True)
    try:
        assert llama.decode_attention_engages(llama.KVCache(k, v), None, 32, latent=True)
        assert not llama.decode_attention_engages(
            deepseek.IndexedLatentCache(k, v, v), None, 32, latent=True)
        assert not llama.decode_attention_engages(llama.KVCache(k, v), object(), 32, latent=True)
    finally:
        linear.set_pallas_interpret(False)
    assert not llama.decode_attention_engages(llama.KVCache(k, v), None, 32, latent=True)  # the CPU


# ---- more than one row a lane: the prefill kernel (PR 51) -------------------

def _chunk_stack(n_kv, group, hd, lanes, t, seed):
    rng = np.random.default_rng(seed)
    shape = (LAYERS, lanes, SEQ) + ((n_kv, hd) if hd == HD else (n_kv * hd,))
    k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((lanes, t, n_kv * group, hd)), jnp.bfloat16)
    return q, k, v


def _chunk_kernel(q, k, v, positions, n_valid, layer=LAYER):
    positions = jnp.asarray(positions, jnp.int32)
    work = pa.chunk_blocks(positions, jnp.asarray(n_valid, jnp.int32), SEQ,
                           pa.query_rows(q.shape[1]))
    out = pa.prefill_attention(q, k, v, layer, work, q.shape[-1] ** -0.5, interpret=True)
    assert out.shape == q.shape and out.dtype == q.dtype
    return np.asarray(out.astype(jnp.float32))


def _chunk_dense(q, k, v, positions, layer=LAYER):
    lanes, t, n_heads, hd = q.shape
    n_kv = k[layer].size // (lanes * SEQ * hd)
    qf = q.astype(jnp.float32).reshape(lanes, t, n_kv, n_heads // n_kv, hd)
    mask = jnp.arange(SEQ)[None, None, :] <= jnp.asarray(positions)[:, :, None]
    plane = lambda c: c[layer].astype(jnp.float32).reshape(lanes, SEQ, n_kv, hd)
    out = llama._dense_attention(qf, plane(k), plane(v), mask, hd ** -0.5)
    return np.asarray(out).reshape(lanes, t, n_heads, hd)


# (rows a lane, each lane's start, each lane's real rows)
CHUNKS = {
    "start_0": (64, [0], [64]),
    "start_inside_a_block": (64, [100], [64]),
    "across_a_block_edge": (64, [BLOCK - 20], [64]),
    "padded_rows_ignored": (64, [BLOCK + 30], [23]),
    "two_lanes_unlike_starts_two_query_blocks": (2 * pa.QUERY_ROWS[0], [3, 2 * BLOCK - 100],
                                                 [2 * pa.QUERY_ROWS[0], pa.QUERY_ROWS[0] + 9]),
    "a_lane_with_no_real_row": (64, [40, 2 * BLOCK, 7], [64, 0, 64]),
}


@pytest.mark.parametrize("chunk", CHUNKS, ids=list(CHUNKS))
@shapes
def test_prefill_kernel_matches_dense_attention(n_kv, group, hd, chunk):
    """Real rows agree with `_dense_attention` over the whole plane; a padded
    row holds finite values nobody reads (the next layer's K/V rows are made
    of them); a lane with no real row yields zeros."""
    t, starts, n_valid = CHUNKS[chunk]
    q, k, v = _chunk_stack(n_kv, group, hd, len(starts), t, seed=n_kv + t)
    positions = np.asarray(starts)[:, None] + np.arange(t)[None, :]
    got = _chunk_kernel(q, k, v, positions, n_valid)
    want = _chunk_dense(q, k, v, positions)
    assert np.isfinite(got).all()
    for b, n in enumerate(n_valid):
        if n == 0:
            assert not got[b].any()
            continue
        np.testing.assert_allclose(
            got[b, :n], want[b, :n], rtol=0, atol=6e-3 * np.abs(want[b, :n]).max(),
            err_msg=f"lane {b} from {starts[b]}, {n} real rows")


def test_prefill_kernel_takes_a_merged_row_of_one_128_wide_kv_head():
    """Jamba's attention layers: every query head on ONE kv head of 128, kept
    merged (a row IS the head): a unit of one head, no pairs to take apart."""
    t, n_kv, group = 64, 1, 5
    q, k, v = _chunk_stack(n_kv, group, HD, 2, t, seed=9)
    merge = lambda c: c.reshape(*c.shape[:3], n_kv * HD)
    positions = np.asarray([BLOCK - 9, 0])[:, None] + np.arange(t)[None, :]
    got = _chunk_kernel(q, merge(k), merge(v), positions, [t, 31])
    want = _chunk_dense(q, k, v, positions)
    for b, n in enumerate([t, 31]):
        np.testing.assert_allclose(
            got[b, :n], want[b, :n], rtol=0, atol=6e-3 * np.abs(want[b, :n]).max())


@shapes
def test_prefill_rows_do_not_see_rows_above_the_chunks_last_real_one(n_kv, group, hd):
    """NaN in every cache row past the last real position (a stale row of an
    earlier request, or nothing at all) reaches no real row: the rows of the
    last block are masked by position and its values zeroed."""
    t, start, n = 64, BLOCK + 10, 40
    q, k, v = _chunk_stack(n_kv, group, hd, 1, t, seed=5)
    positions = start + np.arange(t)[None, :]
    want = _chunk_kernel(q, k, v, positions, [n])[0, :n]
    poison = lambda c: c.at[:, :, start + n:].set(jnp.nan)
    got = _chunk_kernel(q, poison(k), poison(v), positions, [n])
    np.testing.assert_array_equal(got[0, :n], want)
    assert np.isfinite(got).all()


def test_chunk_work_list_visits_the_blocks_each_query_block_can_see():
    """Two lanes, two query blocks each: lane 0 from 3 (its second query block
    ends in key block 2), lane 1 with no real row in its second query block
    (one item that fetches nothing new and computes nothing)."""
    rows = pa.QUERY_ROWS[0]
    t = 2 * rows
    positions = jnp.asarray([[3], [BLOCK + 5]], jnp.int32) + jnp.arange(t, dtype=jnp.int32)[None, :]
    n_items, plan, row_positions = pa.chunk_blocks(
        positions, jnp.asarray([t, rows - 1], jnp.int32), SEQ, rows)
    n_items, plan = int(n_items), np.asarray(plan)[:, :int(n_items)]
    np.testing.assert_array_equal(
        np.asarray(row_positions), np.broadcast_to(np.asarray(positions)[:, :, None], (2, t, HD)))
    hi = [3 + rows - 1, 3 + t - 1, BLOCK + 5 + rows - 2]
    want = []  # (lane, query block, fetched lane, fetched block, code, hi)
    for (lane, qb), h, lo in zip([(0, 0), (0, 1), (1, 0)], hi, [3, 3 + rows, BLOCK + 5]):
        last = h // BLOCK
        for j in range(last + 1):
            code = pa.FULL if (j + 1) * BLOCK - 1 <= lo else pa.LAST
            code += pa.FIRST * (j == 0) + pa.FINAL * (j == last)
            want.append((lane, qb, lane, j, code, h))
    want.append((1, 1, 1, hi[2] // BLOCK, pa.FIRST + pa.FINAL, -1))  # held, idle
    assert n_items == len(want)
    np.testing.assert_array_equal(plan.T, np.asarray(want))


@pytest.mark.parametrize("shape,n_kv,want", [
    ((2, 3, SEQ, 4, HD), None, True),
    ((2, 3, SEQ, 1, HD), None, False),  # 128-wide heads leave a block in pairs
    ((2, 3, SEQ, 3, HD), None, False),
    ((2, 3, SEQ, 8 * 64), 8, True),  # two 64-wide heads a column tile
    ((2, 3, SEQ, 2 * 128), 2, True),  # merged rows of 128-wide heads
    ((2, 3, SEQ, 4 * 96), 4, False),  # a head would straddle two tiles
    ((2, 3, SEQ + 8, 4, HD), None, False),  # what `supports` declines
], ids=["4x128", "1x128", "3x128", "8x64_merged", "2x128_merged", "4x96_merged", "ragged_context"])
def test_supports_prefill_says_which_stacks_the_prefill_kernel_takes(shape, n_kv, want):
    k = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert pa.supports_prefill(k, 8, n_kv) is want


@pytest.mark.parametrize("t,want", [(1024, 256), (512, 256), (256, 256), (64, 64), (128, 128),
                                    (5, None), (17, None), (96, None)])
def test_query_rows_are_the_largest_block_that_divides_the_chunk(t, want):
    assert pa.query_rows(t) == want


def _chunk_forward_setup():
    cfg = LlamaConfig(dim=512, hidden_dim=512, n_layers=2, n_heads=4,
                      n_kv_heads=2, vocab_size=64, seq_len=2 * BLOCK)
    from distributed_llama_multiusers_tpu.models.loader import params_from_random

    params = params_from_random(cfg, seed=0, dtype=jnp.bfloat16, scale=0.05)
    cache = llama.init_kv_cache(cfg, 2, dtype=jnp.bfloat16)
    rng = np.random.default_rng(1)
    cache = llama.KVCache(*(
        jnp.asarray(rng.standard_normal(c.shape) * 0.5, jnp.bfloat16) for c in cache))
    t = 64
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, t)), jnp.int32)
    positions = jnp.asarray([[BLOCK - 30], [0]], jnp.int32) + jnp.arange(t, dtype=jnp.int32)[None, :]
    return cfg, params, tokens, positions, cache


def test_forward_takes_the_prefill_kernel_only_where_its_inputs_allow(monkeypatch):
    """`llama_forward` at a prefill bucket's rows with the kernel on (interpret
    mode) agrees with the dense path on the real rows, appends the same rows in
    the first layer, says nothing different with `n_valid`, and leaves a verify
    step's rows, a float32 cache, a mesh's and the paged pool's to the dense
    path."""
    cfg, params, tokens, positions, cache = _chunk_forward_setup()
    b, t = tokens.shape
    fwd = lambda **kw: jax.jit(
        lambda p, tok, pos, c: llama.llama_forward(cfg, p, tok, pos, c, **kw))
    dense_logits, dense_cache = fwd()(params, tokens, positions, cache)
    assert not llama.prefill_attention_engages(cache, None, b, t, cfg.n_heads)  # the CPU

    calls = []
    real = pa.prefill_attention
    monkeypatch.setattr(pa, "prefill_attention",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    before = pa.TRACE_STATS["prefill_kernel_traces"]
    linear.set_pallas_interpret(True)
    try:
        assert llama.prefill_attention_engages(cache, None, b, t, cfg.n_heads)
        logits, new_cache = fwd()(params, tokens, positions, cache)
        assert len(calls) == 1  # the scan's body, traced once
        assert pa.TRACE_STATS["prefill_kernel_traces"] == before + 1
        n_valid = jnp.asarray([t, 20], jnp.int32)
        short_logits, _ = fwd(n_valid=n_valid)(params, tokens, positions, cache)
        assert len(calls) == 2
        # a verify step's rows, one row, a float32 cache, a mesh: not this kernel
        assert not llama.prefill_attention_engages(cache, None, b, 5, cfg.n_heads)
        assert not llama.prefill_attention_engages(cache, None, b, 1, cfg.n_heads)
        assert not llama.prefill_attention_engages(cache, object(), b, t, cfg.n_heads)
        cache32 = llama.init_kv_cache(cfg, 2, dtype=jnp.float32)
        assert not llama.prefill_attention_engages(cache32, None, b, t, cfg.n_heads)
        paged = llama.init_paged_kv_cache(cfg, 2, n_pages=8, page_size=64, dtype=jnp.bfloat16)
        assert not llama.prefill_attention_engages(paged, None, b, t, cfg.n_heads)
        llama.llama_forward(cfg, params, tokens[:, :5], positions[:, :5], cache)
        assert len(calls) == 2
    finally:
        linear.set_pallas_interpret(False)
    # layer 0's appends are made of the embeddings alone
    for got, want in zip(new_cache, dense_cache):
        np.testing.assert_array_equal(
            np.asarray(got[0], np.float32), np.asarray(want[0], np.float32))
    got, want = np.asarray(logits), np.asarray(dense_logits)
    assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()
    short = np.asarray(short_logits)
    assert np.abs(short[0] - want[0]).max() <= 2e-2 * np.abs(want).max()
    assert np.abs(short[1, :20] - want[1, :20]).max() <= 2e-2 * np.abs(want).max()
    assert np.isfinite(short).all()
