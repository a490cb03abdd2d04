"""The blocks a block-sparse layer's rows attend (ops/block_sparse.py) against
the benchmark's plain reference on the same queries and keys: the compressed
keys (whole, and appended across a chunk boundary and a row at a time), the
chosen sets of every row at more rows a lane and at one, the pooling by hand;
and attention over the chosen blocks: the decode kernel over a work list
(interpret mode) against the masked key-block path against the reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.ops import block_sparse as bs
from distributed_llama_multiusers_tpu.ops import blocked_attention, pallas_attention

import latent_toy

CFG, FAMILY, _ = latent_toy.load("tiny_minicpm_sala.json")
SIZES = bs.SparseSizes(4, 2, 8, 4, 16, 1, 48)
REF_SIZES = tuple(SIZES)
T, N_KV, GROUP, HD = 128, 2, 2, 32


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(4)
    q = jnp.asarray(2.0 * rng.standard_normal((T, N_KV, GROUP, HD)), jnp.float32)
    k = jnp.asarray(2.0 * rng.standard_normal((T, N_KV, HD)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((T, N_KV, HD)), jnp.float32)
    ck = FAMILY._compress(k, SIZES.kernel_size, SIZES.kernel_stride)
    with jax.default_matmul_precision("highest"):
        o, chosen = FAMILY._sparse_block(q, jnp.arange(T), k, v, ck, sizes=REF_SIZES, group=GROUP)
    return q, k, v, ck, np.asarray(o), np.asarray(chosen)


def _planes(k, v, lanes=1, layers=2, at=1):
    """Stacks ``[layers, lanes, T, n_kv * hd]`` holding k and v at layer ``at``
    of every lane, noise elsewhere."""
    noise = jnp.asarray(np.random.default_rng(0).standard_normal((layers, lanes, T, N_KV * HD)),
                        jnp.float32)
    put = lambda x: noise.at[at].set(jnp.broadcast_to(x.reshape(1, T, -1), (lanes, T, N_KV * HD)))  # noqa: E731
    return put(k), put(v)


def _ck_stack(lanes=1, layers=3):
    return jnp.full((layers, lanes, T // SIZES.kernel_stride, N_KV * HD), 9.0, jnp.float32)


def test_the_sizes_come_from_the_configuration():
    config = FAMILY.program_config(CFG)
    assert bs.SparseSizes.of(config) == SIZES
    assert SIZES.list_blocks(128) == 6 and SIZES.list_blocks(32) == 4
    assert bs.SparseSizes(32, 16, 64, 64, 2048, 1, 8192).list_blocks(32768) == 128


def test_blocks_attended_counts_what_a_row_reads():
    assert bs.blocks_attended(0, SIZES) == (1, 1) and bs.blocks_attended(47, SIZES) == (6, 6)
    assert bs.blocks_attended(48, SIZES) == (4, 7) and bs.blocks_attended(127, SIZES) == (4, 16)
    big = bs.SparseSizes(32, 16, 64, 64, 2048, 1, 8192)
    assert bs.blocks_attended(8191, big) == (128, 128) and bs.blocks_attended(8192, big) == (64, 129)


def test_pooling_takes_the_kernels_that_overlap_a_block():
    p = jnp.asarray(np.random.default_rng(1).random((3, 64)), jnp.float32)
    got = np.asarray(bs._pool_to_blocks(p, SIZES, 16))
    for b in range(16):
        over = [j for j in range(64) if 2 * j < 8 * (b + 1) and 2 * j + 4 > 8 * b]
        np.testing.assert_array_equal(got[:, b], np.asarray(p)[:, over].max(axis=1))
    assert [j for j in range(64) if 2 * j < 16 and 2 * j + 4 > 8] == [3, 4, 5, 6, 7]


@pytest.mark.parametrize("cuts", [(128,), (37, 91), (1,) * 128], ids=["whole", "chunks", "rows"])
def test_compressed_keys_are_the_means_however_the_rows_arrive(rows, cuts):
    """A kernel whose four rows arrive in two steps is written by the step
    that brings its last; a step's padded tail writes none."""
    _, k, v, ck, _, _ = rows
    k_all, _ = _planes(k, v)
    ck_all, start = _ck_stack(), 0
    for n in cuts:
        width = 1 if n == 1 else 64 * -(-n // 64)  # the bucket the chunk rides
        pos = (start + jnp.arange(width, dtype=jnp.int32))[None, :]
        ck_all = bs.append_compressed(
            ck_all, k_all, jnp.int32(2), jnp.int32(1), pos, jnp.asarray([n], jnp.int32), SIZES)
        start += n
        done = max((start - SIZES.kernel_size) // SIZES.kernel_stride + 1, 0)
        assert np.all(np.asarray(ck_all[2, 0, done:]) == 9.0)  # nothing ahead of its rows
    got = np.asarray(ck_all[2, 0, : ck.shape[0]]).reshape(-1, N_KV, HD)
    np.testing.assert_allclose(got, np.asarray(ck), rtol=1e-6, atol=1e-6)
    assert np.all(np.asarray(ck_all[:2]) == 9.0) and np.all(np.asarray(ck_all[2, 0, ck.shape[0]:]) == 9.0)


def _engine_sets(q, ck, positions):
    """The program's chosen sets for query rows at ``positions`` ``[B, T]``."""
    b, t = positions.shape
    ck_all = _ck_stack(lanes=b).at[1, :, : ck.shape[0]].set(ck.reshape(1, ck.shape[0], -1))
    with jax.default_matmul_precision("highest"):
        r = bs.block_scores(q.reshape(b, t, N_KV * GROUP, HD), ck_all, jnp.int32(1), positions,
                            N_KV, SIZES, HD ** -0.5)
    return np.asarray(bs.choose(r, positions, SIZES))


def test_the_chosen_sets_are_the_references_at_more_rows_a_lane(rows):
    q, _, _, ck, _, want = rows
    got = _engine_sets(q, ck, jnp.arange(T, dtype=jnp.int32)[None, :])[0]
    np.testing.assert_array_equal(got, want)
    # under dense_len every held block; past it topk of them, the first and
    # the window's two always, and the top-k DROPS blocks
    assert all(want[t].sum(-1).tolist() == [t // 8 + 1] * 2 for t in range(48))
    assert all(want[t].sum(-1).tolist() == [4, 4] for t in range(48, T))
    assert all(want[t, :, [0, t // 8 - 1, t // 8]].all() for t in range(48, T))
    assert any((want[t, 0] != want[t, 1]).any() for t in range(48, T))  # a set a kv head


def test_the_chosen_sets_are_the_references_at_one_row_a_lane(rows):
    """Eight lanes at eight positions in one step, on both sides of dense_len,
    one parked: the work list holds the reference's blocks in rising order."""
    q, _, _, ck, _, want = rows
    at = np.array([5, 47, 48, 63, 90, 127, 128, 31])
    positions = jnp.asarray(at, jnp.int32)[:, None]
    q_rows = q[np.minimum(at, T - 1)][:, None]
    got = _engine_sets(q_rows, ck, positions)
    count, blocks, pos = bs.chosen_list(jnp.asarray(got), positions, T, SIZES)
    count, blocks = np.asarray(count).reshape(8, N_KV), np.asarray(blocks).reshape(8, N_KV, -1)
    assert blocks.shape[-1] == 6
    for lane, t in enumerate(at):
        for h in range(N_KV):
            if t >= T:
                assert count[lane, h] == 0
                continue
            np.testing.assert_array_equal(got[lane, 0, h], want[t, h])
            mine = blocks[lane, h, : count[lane, h]]
            assert mine.tolist() == np.nonzero(want[t, h])[0].tolist() and mine[-1] == t // 8


def test_masked_key_blocks_attend_the_chosen_sets_as_the_reference_does(rows):
    q, k, v, _, want_o, chosen = rows
    k_all, v_all = _planes(k, v)
    positions = jnp.arange(T, dtype=jnp.int32)[None, :]
    with jax.default_matmul_precision("highest"):
        got = blocked_attention.blocked_attention(
            q.reshape(1, T, N_KV * GROUP, HD), k_all, v_all, jnp.int32(1), positions,
            jnp.asarray([T], jnp.int32), N_KV, HD ** -0.5, block=32,
            chosen=jnp.asarray(chosen)[None], chosen_block=8)
    np.testing.assert_allclose(np.asarray(got).reshape(T, N_KV, GROUP, HD), want_o,
                               rtol=2e-5, atol=2e-5)


def test_the_decode_kernel_reads_the_chosen_blocks_alone():
    """128-wide heads, 16-position blocks, bf16: eight lanes at positions on
    both sides of dense_len and one parked, against the masked key-block path
    over the same sets; garbage in the blocks nobody chose moves nothing."""
    sizes = bs.SparseSizes(8, 4, 16, 4, 32, 1, 96)
    seq, hd, n_kv, group, lanes = 256, 128, 2, 16, 8
    rng = np.random.default_rng(2)
    k_all = jnp.asarray(rng.standard_normal((2, lanes, seq, n_kv * hd)), jnp.bfloat16)
    v_all = jnp.asarray(rng.standard_normal((2, lanes, seq, n_kv * hd)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((lanes, n_kv * group, hd)), jnp.bfloat16)
    at = np.array([3, 95, 96, 130, 200, 255, 256, 17])
    positions = jnp.asarray(at, jnp.int32)[:, None]
    assert pallas_attention.supports_sparse(k_all, n_kv * group, n_kv, 16)
    assert not pallas_attention.supports_sparse(k_all.astype(jnp.float32), n_kv * group, n_kv, 16)
    assert not pallas_attention.supports_sparse(k_all, n_kv * group, n_kv, 8)
    r = jnp.asarray(rng.random((lanes, 1, n_kv, seq // 16)), jnp.float32)
    chosen = bs.choose(r, positions, sizes)
    work = bs.chosen_list(chosen, positions, seq, sizes)
    assert work[1].shape == (lanes * n_kv, 6)
    got = pallas_attention.sparse_decode_attention(
        q, k_all, v_all, jnp.int32(1), work, hd ** -0.5, 16, interpret=True)
    want = blocked_attention.blocked_attention(
        q[:, None], k_all, v_all, jnp.int32(1), positions,
        jnp.asarray(at < seq, jnp.int32), n_kv, hd ** -0.5, chosen=chosen, chosen_block=16)[:, 0]
    live = at < seq  # the masked path's row for a parked lane is nobody's to read
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(got[6]) == 0.0)  # the parked lane: zeros, no NaN
    # what nobody chose may hold anything
    unread = ~np.repeat(np.asarray(chosen[:, 0]).any(axis=1), 16, axis=-1)  # [lanes, seq]
    poison = jnp.where(jnp.asarray(unread)[None, :, :, None], jnp.nan, k_all.astype(jnp.float32))
    again = pallas_attention.sparse_decode_attention(
        q, poison.astype(jnp.bfloat16), v_all, jnp.int32(1), work, hd ** -0.5, 16, interpret=True)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))
