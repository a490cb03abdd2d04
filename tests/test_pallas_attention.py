"""Decode attention in place (ops/pallas_attention.py) against the dense path.

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
