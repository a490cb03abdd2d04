"""On-device packed Q40 weights: pack/unpack exactness, quantized matmul,
full-model forward with quantized params, and quantized .m loading.

The reference analogue is matmul_Q80_Q40_F32 vs matmul_F32 equivalence in
src/nn/nn-cpu-ops-test.cpp:220-241 (tolerance there 4.0 on 4096-dim dots);
here dequantization is exact by construction, so the checks are tighter.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from distributed_llama_multiusers_tpu.quants.codec import (
    dequantize_q40,
    quantize_q40,
)
from distributed_llama_multiusers_tpu.quants.packed import (
    PackedQ40,
    pack_q40_from_blocks,
    pack_q40_host,
    q40_at_rest,
    q40_matmul_xla,
    scale_bits,
    unpack_q40,
)


def test_pack_unpack_matches_reference_dequant():
    rng = np.random.default_rng(0)
    d_out, d_in = 48, 64
    w = rng.standard_normal((d_out, d_in)).astype(np.float32)
    blocks = quantize_q40(w.reshape(-1))
    golden = dequantize_q40(blocks).reshape(d_out, d_in)  # reference dequant

    pk, sc = pack_q40_from_blocks(blocks, (d_out, d_in))
    assert pk.shape == (d_in // 2, d_out) and pk.dtype == np.uint8
    assert sc.shape == (d_in // 32, d_out) and sc.dtype == np.float16

    dev = unpack_q40(PackedQ40(jnp.asarray(pk), jnp.asarray(sc)), jnp.float32)
    np.testing.assert_array_equal(np.asarray(dev), golden.T)


def test_pack_q40_host_equals_pack_from_blocks():
    rng = np.random.default_rng(1)
    w = rng.standard_normal((2, 16, 64)).astype(np.float32)  # [L, d_out, d_in]
    pk, sc = pack_q40_host(w)
    assert pk.shape == (2, 32, 16) and sc.shape == (2, 2, 16)
    for layer in range(2):
        blocks = quantize_q40(w[layer].reshape(-1))
        pk1, sc1 = pack_q40_from_blocks(blocks, (16, 64))
        np.testing.assert_array_equal(pk[layer], pk1)
        np.testing.assert_array_equal(sc[layer], sc1)


@pytest.mark.parametrize("on", ["device", "host"])
def test_q40_at_rest_holds_the_scales_bits_once(on):
    """The one function that makes the form the program serves from: every
    ``PackedQ40`` leaf's scales as the int16 bits of their float16 values,
    nothing else in the tree touched, a tree at rest handed back as it is
    (the same leaves), a host plane viewed and not copied; ``unpack_q40``
    gives the same values from either form."""
    rng = np.random.default_rng(55)
    pk, sc = pack_q40_host(rng.standard_normal((2, 16, 64)).astype(np.float32))
    up = jnp.asarray if on == "device" else (lambda a: a)
    other = up(np.ones(3, np.float16))  # a float16 leaf that is no scale plane
    tree = {"w": PackedQ40(up(pk), up(sc)), "nested": [PackedQ40(up(pk[0]), up(sc[0]))],
            "other": other}
    rest = q40_at_rest(tree)
    assert rest["other"] is other and rest["w"].packed is tree["w"].packed
    for leaf, was in ((rest["w"], sc), (rest["nested"][0], sc[0])):
        assert isinstance(leaf, PackedQ40) and leaf.scales.dtype == jnp.int16
        np.testing.assert_array_equal(np.asarray(leaf.scales), was.view(np.int16))
    if on == "host":
        assert np.shares_memory(rest["w"].scales, sc)
    again = q40_at_rest(rest)
    assert again["w"].scales is rest["w"].scales
    assert again["nested"][0].scales is rest["nested"][0].scales
    np.testing.assert_array_equal(
        np.asarray(unpack_q40(PackedQ40(jnp.asarray(pk), jnp.asarray(rest["w"].scales)))),
        np.asarray(unpack_q40(PackedQ40(jnp.asarray(pk), jnp.asarray(sc)))))
    with pytest.raises(TypeError, match="float16 or their int16 bits"):
        scale_bits(np.ones((1, 4), np.float32))


@pytest.mark.parametrize("scales", ["f16", "bits"])
def test_q40_matmul_xla_matches_dense(scales):
    rng = np.random.default_rng(2)
    d_in, d_out, b = 128, 96, 4
    w = rng.standard_normal((d_out, d_in)).astype(np.float32)
    x = rng.standard_normal((b, d_in)).astype(np.float32)
    pk, sc = pack_q40_host(w)
    # either form of the scales: the float16 values, or their bits at rest
    pq = PackedQ40(jnp.asarray(pk), jnp.asarray(sc if scales == "f16" else sc.view(np.int16)))

    golden_w = dequantize_q40(quantize_q40(w.reshape(-1))).reshape(d_out, d_in)
    want = x @ golden_w.T
    got = np.asarray(q40_matmul_xla(jnp.asarray(x), pq))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_forward_quantized_close_to_dense():
    from distributed_llama_multiusers_tpu.models import (
        init_kv_cache,
        llama_forward,
        params_from_random,
        quantize_params,
    )
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    config = LlamaConfig(
        dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
        vocab_size=96, seq_len=32,
    )
    params = params_from_random(config, seed=3, dtype=jnp.float32)
    qparams = quantize_params(params)
    assert isinstance(qparams.layers.wq, PackedQ40)
    assert isinstance(qparams.wcls, PackedQ40)

    tokens = jnp.asarray(np.random.default_rng(4).integers(0, 96, (2, 8)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None], (2, 8))
    logits_d, _ = llama_forward(config, params, tokens, positions, init_kv_cache(config, 2))
    logits_q, _ = llama_forward(config, qparams, tokens, positions, init_kv_cache(config, 2))
    # 4-bit weights: expect small but nonzero drift vs dense
    diff = np.abs(np.asarray(logits_q) - np.asarray(logits_d))
    assert np.isfinite(np.asarray(logits_q)).all()
    assert diff.mean() < 0.5, diff.mean()


def test_forward_quantized_exact_vs_host_dequantized_weights():
    """Dequantizing on device inside the matmul must equal running the dense
    forward on host-dequantized weights — dequant itself is lossless."""
    from distributed_llama_multiusers_tpu.models import (
        init_kv_cache,
        llama_forward,
        params_from_random,
        quantize_params,
    )
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    config = LlamaConfig(
        dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
        vocab_size=96, seq_len=32,
    )
    params = params_from_random(config, seed=5, dtype=jnp.float32)
    qparams = quantize_params(params)

    def dq(w):
        if isinstance(w, PackedQ40):
            return unpack_q40(w, jnp.float32)
        return w

    dq_layers = qparams.layers._replace(
        **{k: dq(getattr(qparams.layers, k)) for k in ("wq", "wk", "wv", "wo", "w1", "w2", "w3")}
    )
    dq_params = qparams._replace(layers=dq_layers, wcls=dq(qparams.wcls))

    tokens = jnp.asarray(np.random.default_rng(6).integers(0, 96, (1, 4)), jnp.int32)
    positions = jnp.arange(4, dtype=jnp.int32)[None]
    logits_q, _ = llama_forward(config, qparams, tokens, positions, init_kv_cache(config, 1))
    logits_dq, _ = llama_forward(config, dq_params, tokens, positions, init_kv_cache(config, 1))
    np.testing.assert_allclose(np.asarray(logits_q), np.asarray(logits_dq), rtol=1e-6, atol=1e-6)


def test_load_params_from_m_quantized(tiny_model):
    from distributed_llama_multiusers_tpu.formats.model_file import load_model_header
    from distributed_llama_multiusers_tpu.models import (
        init_kv_cache,
        llama_forward,
        load_params_from_m,
        load_params_from_m_quantized,
    )

    header = tiny_model["header"]
    path = tiny_model["model"]
    header2 = load_model_header(path)
    config, qparams = load_params_from_m_quantized(path, header2, dtype=jnp.float32)
    _, dparams = load_params_from_m(path, header2, dtype=jnp.float32)
    assert isinstance(qparams.layers.wq, PackedQ40)
    # the loader's planes arrive as packed, float16: what rests as int16 bits
    # is the engine's to say, where it takes them (``q40_at_rest``)
    assert {w.scales.dtype for w in (qparams.layers.wq, qparams.layers.w2, qparams.wcls)} == {
        jnp.dtype(jnp.float16)}

    tokens = jnp.asarray([[1, 2, 3]], jnp.int32)
    positions = jnp.arange(3, dtype=jnp.int32)[None]
    logits_q, _ = llama_forward(config, qparams, tokens, positions, init_kv_cache(config, 1))
    logits_d, _ = llama_forward(config, dparams, tokens, positions, init_kv_cache(config, 1))
    # both paths dequantize the same Q40 bytes -> identical f32 weights
    np.testing.assert_allclose(
        np.asarray(logits_q), np.asarray(logits_d), rtol=1e-5, atol=1e-5
    )


def test_quantized_params_shard_and_forward_on_mesh():
    """PackedQ40 params must flow through shard_params + a TP forward (the
    reference runs Q40 weights sharded across nodes; here: across the mesh)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_llama_multiusers_tpu.models import (
        init_kv_cache,
        llama_forward,
        params_from_random,
        quantize_params,
    )
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig
    from distributed_llama_multiusers_tpu.parallel import MeshPlan, make_mesh
    from distributed_llama_multiusers_tpu.parallel.sharding import shard_params

    config = LlamaConfig(
        dim=64, hidden_dim=128, n_layers=2, n_heads=8, n_kv_heads=4,
        vocab_size=96, seq_len=32,
    )
    params = params_from_random(config, seed=7, dtype=jnp.float32)
    qparams = quantize_params(params)
    mesh = make_mesh(MeshPlan(dp=2, tp=2, sp=2))
    sharded = shard_params(qparams, mesh)
    assert isinstance(sharded.layers.wq, PackedQ40)

    tokens = jnp.asarray(np.random.default_rng(8).integers(0, 96, (2, 4)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32)[None], (2, 4))
    cache = init_kv_cache(config, 2)

    logits_sharded, _ = jax.jit(
        lambda p, t, pos, c: llama_forward(config, p, t, pos, c)
    )(sharded, tokens, positions, cache)
    logits_local, _ = llama_forward(config, qparams, tokens, positions, cache)
    np.testing.assert_allclose(
        np.asarray(logits_sharded), np.asarray(logits_local), rtol=2e-5, atol=2e-5
    )


def _q80_sync_fixture():
    import jax
    from distributed_llama_multiusers_tpu.models import (
        init_kv_cache,
        llama_forward,
        params_from_random,
    )
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig
    from distributed_llama_multiusers_tpu.parallel import MeshPlan, make_mesh
    from distributed_llama_multiusers_tpu.parallel.sharding import shard_params

    config = LlamaConfig(
        dim=128, hidden_dim=256, n_layers=2, n_heads=8, n_kv_heads=4,
        vocab_size=96, seq_len=32,
    )
    mesh = make_mesh(MeshPlan(tp=2))
    params = shard_params(params_from_random(config, seed=5, dtype=jnp.float32), mesh)
    tokens = jnp.asarray(np.random.default_rng(4).integers(0, 96, (2, 4)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32)[None], (2, 4))

    def fwd(q80_sync):
        return jax.jit(
            lambda p, t, pos, c: llama_forward(
                config, p, t, pos, c, mesh=mesh, q80_sync=q80_sync
            )
        )

    cache = init_kv_cache(config, 2)
    return fwd, params, tokens, positions, cache


def test_q80_sync_matmul_parity_and_payload_drop():
    """--buffer-float-type q80 on a tp mesh ships the wo/w2 sync as int8+
    scales — outputs stay within Q80 tolerance of the f32-sync forward and
    the compiled program's collective payload drops (the reference's
    ZQ-pipe bandwidth claim, ~4x on the gather half; src/llm.cpp:150,
    SURVEY.md §5.8). This test pins the LEGACY psum_scatter+all_gather
    transport (parallel/collectives.q80_sync_matmul), which since PR 7 is
    the --ring-sync off escape-hatch lowering — the default routes the
    same wire format through the ring (companion test below)."""
    from distributed_llama_multiusers_tpu.ops.ring_collective import (
        ring_sync_enabled,
        set_ring_sync,
    )
    from distributed_llama_multiusers_tpu.parallel.comm_stats import collective_stats_of

    prev = ring_sync_enabled()
    try:
        set_ring_sync(False)
        fwd, params, tokens, positions, cache = _q80_sync_fixture()
        ref, _ = fwd(False)(params, tokens, positions, cache)
        got, _ = fwd(True)(params, tokens, positions, cache)
        # Q80 rounding noise only (int8 blocks, f16 scales)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=0.15, rtol=0.05)
        assert not np.allclose(np.asarray(got), np.asarray(ref)), (
            "q80 path produced bit-identical logits — quantized sync not active?"
        )

        base = collective_stats_of(fwd(False), params, tokens, positions, cache)
        q80 = collective_stats_of(fwd(True), params, tokens, positions, cache)
        # the parser counts OUTPUT payload per op, which flatters all-reduce
        # (a ring all-reduce moves ~2x its payload on the wire, the rs+ag pair
        # exactly 1x each): f32 all-reduce 1.0 vs rs 0.5 + int8 ag ~0.27 = 0.77
        # measured here; on the wire the drop is ~(2.0 -> 0.77), ~2.6x
        assert q80["total_bytes"] < 0.8 * base["total_bytes"], (base, q80)
        # the int8 gather must be visible in the mix
        assert any(k.startswith("all-gather") for k in q80["bytes_by_kind"]), q80
    finally:
        set_ring_sync(prev)


def test_q80_sync_over_ring_parity_and_hlo_shape():
    """The PR-7 default: on a pure-TP mesh the q80 wire rides the RING
    (ops/ring_collective.ring_sync_matmul q80_wire) — same Q80 tolerance
    class vs the f32-sync forward, and the compiled program's collectives
    are chunk-sized collective-permutes (the overlappable hops), not one
    monolithic all-reduce, with int8 permutes visibly shrinking the
    payload vs the f32-wire ring."""
    from distributed_llama_multiusers_tpu.ops.ring_collective import (
        ring_sync_enabled,
        set_ring_sync,
    )
    from distributed_llama_multiusers_tpu.parallel.comm_stats import collective_stats_of

    prev = ring_sync_enabled()
    try:
        set_ring_sync(True)
        fwd, params, tokens, positions, cache = _q80_sync_fixture()
        ref, _ = fwd(False)(params, tokens, positions, cache)
        got, _ = fwd(True)(params, tokens, positions, cache)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=0.15, rtol=0.05)
        assert not np.allclose(np.asarray(got), np.asarray(ref)), (
            "q80 wire produced bit-identical logits — quantized sync not active?"
        )

        base = collective_stats_of(fwd(False), params, tokens, positions, cache)
        q80 = collective_stats_of(fwd(True), params, tokens, positions, cache)
        # ring lowering: hops only — no all-reduce/all-gather ops remain
        for stats in (base, q80):
            assert set(stats["bytes_by_kind"]) == {"collective-permute"}, stats
        # int8 wire on the gather hops: strictly fewer payload bytes than
        # the f32 wire (scales ride too, so the drop is < 4x, but real)
        assert q80["total_bytes"] < base["total_bytes"], (base, q80)
    finally:
        set_ring_sync(prev)


def test_pad_packed_d_out_caps_overhead():
    """Padding to wide slabs is only worth it when cheap: vocab-like widths
    (128256 -> 131072, +2.2%) pad; unlucky widths whose next 8192 multiple
    nearly doubles the bytes (8320 -> 16384) keep their natural layout and
    take the narrow-tile/XLA path instead (round-4 advisor finding)."""
    import numpy as np

    from distributed_llama_multiusers_tpu.quants.packed import (
        PAD_MAX_OVERHEAD, pad_packed_d_out,
    )

    def fake(d_out, d_in=64):
        packed = np.zeros((d_in // 2, d_out), np.uint8)
        scales = np.zeros((d_in // 32, d_out), np.float16)
        return packed, scales

    pk, sc = pad_packed_d_out(*fake(128256))
    assert pk.shape[-1] == 131072 and sc.shape[-1] == 131072

    pk, sc = pad_packed_d_out(*fake(8320))  # +97% > cap: unchanged
    assert pk.shape[-1] == 8320 and sc.shape[-1] == 8320
    assert 8192 * 2 - 8320 > 8320 * PAD_MAX_OVERHEAD  # the case is real
