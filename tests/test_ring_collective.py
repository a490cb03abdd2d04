"""Ring collectives (ops/ring_collective.py): parity vs the XLA
collectives they replace, on the virtual CPU mesh.

Gate classes (ISSUE 7 acceptance):
- f32 ring reduce-scatter / all-gather / all-reduce match
  lax.psum_scatter-style / all_gather / psum EXACTLY on integer-valued
  f32 (any summation order is exact there), and to fp tolerance on random
  values; odd AND even ring sizes.
- the Q80 wire matches the plain gather within the documented ~1e-2
  class, and matches the q80 qdq codec EXACTLY (same block rounding).
- DLLAMA_RING_SYNC=off (set_ring_sync(False)) restores the psum path:
  the partitioned Q40 matmul's col-sliced sync goes back to lax.psum
  bit-for-bit.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_llama_multiusers_tpu.ops import ring_collective as rc
from distributed_llama_multiusers_tpu.parallel import MeshPlan, make_mesh
from distributed_llama_multiusers_tpu.quants.jax_codec import qdq_q80
from distributed_llama_multiusers_tpu.quants.packed import (
    PackedQ40,
    pack_q40_host,
    q40_at_rest,
    q40_matmul_xla,
)

pytestmark = pytest.mark.usefixtures("cpu_devices")


@pytest.fixture
def cpu_devices():
    if len(jax.devices()) < 4:
        pytest.skip("needs the 8-virtual-device CPU mesh (tests/conftest.py)")


def _partials(tp: int, width: int, seed: int = 0, exact: bool = True):
    """[tp, 2, width] per-device partial sums; integer-valued when exact
    (fp addition of small ints is exact in any order)."""
    rng = np.random.default_rng(seed)
    if exact:
        return rng.integers(-8, 8, (tp, 2, width)).astype(np.float32)
    return rng.standard_normal((tp, 2, width)).astype(np.float32)


def _run_local(fn, mesh, x, out_spec):
    """Feed each tp shard its own partial (leading axis sharded over tp)."""
    xs = jax.device_put(x, NamedSharding(mesh, P("tp", None, None)))
    return np.asarray(
        shard_map(
            fn, mesh=mesh, in_specs=(P("tp", None, None),),
            out_specs=out_spec, check_vma=False,
        )(xs)
    )


@pytest.mark.parametrize("tp", [2, 3, 4])
def test_ring_reduce_scatter_matches_sum(tp):
    """Even AND odd ring sizes: device r ends with exactly the reduced
    chunk r (integer values -> order-independent exact sums)."""
    mesh = make_mesh(MeshPlan(tp=tp))
    x = _partials(tp, 12 * tp)
    got = _run_local(
        lambda xl: rc.ring_reduce_scatter(xl[0], "tp", tp),
        mesh, x, P(None, "tp"),
    )
    assert np.array_equal(got, x.sum(axis=0))


@pytest.mark.parametrize("tp", [2, 3, 4])
def test_ring_all_reduce_matches_psum(tp):
    mesh = make_mesh(MeshPlan(tp=tp))
    x = _partials(tp, 8 * tp, seed=1)
    got = _run_local(
        lambda xl: rc.ring_all_reduce(xl[0], "tp", tp),
        mesh, x, P(None, None),
    )
    want = _run_local(
        lambda xl: jax.lax.psum(xl[0], "tp"), mesh, x, P(None, None)
    )
    assert np.array_equal(got, want)  # integer-valued: exact either way


@pytest.mark.slow  # tier-2: heavy; a faster sibling keeps this class covered in tier-1 (see pyproject markers)
def test_ring_all_reduce_random_f32_tolerance():
    """Random f32: ring order vs XLA's reduction tree differ only in
    associativity — same f32 class."""
    tp = 4
    mesh = make_mesh(MeshPlan(tp=tp))
    x = _partials(tp, 32, seed=2, exact=False)
    got = _run_local(
        lambda xl: rc.ring_all_reduce(xl[0], "tp", tp),
        mesh, x, P(None, None),
    )
    want = x.sum(axis=0)
    assert np.allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tp", [2, 3, 4])
def test_ring_all_gather_matches_all_gather(tp):
    """Gather moves bits: exact vs lax.all_gather, any ring size."""
    mesh = make_mesh(MeshPlan(tp=tp))
    x = _partials(tp, 6, seed=3, exact=False)
    got = _run_local(
        lambda xl: rc.ring_all_gather(xl[0], "tp", tp),
        mesh, x, P(None, None),
    )

    def ref(xl):
        g = jax.lax.all_gather(xl[0], "tp", axis=0)  # [tp, 2, 6]
        return jnp.concatenate([g[i] for i in range(tp)], axis=-1)

    want = _run_local(ref, mesh, x, P(None, None))
    assert np.array_equal(got, want)


def test_ring_all_gather_q80_wire_class():
    """The compressed wire: within the documented ~1e-2 class of the f32
    gather, and EXACTLY the q80 qdq codec's block rounding per chunk (the
    wire IS the codec — parity with q80_all_gather semantics)."""
    tp = 4
    mesh = make_mesh(MeshPlan(tp=tp))
    x = _partials(tp, 64, seed=4, exact=False)  # chunk 64 % 32 == 0
    got = _run_local(
        lambda xl: rc.ring_all_gather_q80(xl[0], "tp", tp),
        mesh, x, P(None, None),
    )
    exact = _run_local(
        lambda xl: rc.ring_all_gather(xl[0], "tp", tp),
        mesh, x, P(None, None),
    )
    scale = np.abs(exact).max()
    assert np.abs(got - exact).max() <= 2e-2 * scale
    # bit-for-bit the codec's rounding: chunk k == qdq_q80(device k's data)
    want = np.concatenate(
        [np.asarray(qdq_q80(jnp.asarray(x[i]), mode="converter")) for i in range(tp)],
        axis=-1,
    )
    assert np.array_equal(got, want)


def test_ring_all_reduce_fallback_indivisible():
    """A width the ring cannot chunk falls back to psum inside
    ring_all_reduce — callers may substitute unconditionally."""
    tp = 4
    mesh = make_mesh(MeshPlan(tp=tp))
    x = _partials(tp, 30, seed=5)  # 30 % 4 != 0
    got = _run_local(
        lambda xl: rc.ring_all_reduce(xl[0], "tp", tp),
        mesh, x, P(None, None),
    )
    assert np.array_equal(got, x.sum(axis=0))


# ---------------------------------------------------------------------------
# The fused form: ring_sync_matmul.
# ---------------------------------------------------------------------------


def _packed_weight(d_in, d_out, seed=0):
    rng = np.random.default_rng(seed)
    return PackedQ40(*map(
        jnp.asarray, pack_q40_host(
            rng.standard_normal((d_out, d_in)).astype(np.float32) * 0.1
        )
    ))


@pytest.mark.parametrize("tp", [2, 4])
def test_ring_sync_matmul_dense(tp):
    mesh = make_mesh(MeshPlan(tp=tp))
    rng = np.random.default_rng(6)
    x = rng.standard_normal((3, 32 * tp)).astype(np.float32)
    w = rng.standard_normal((32 * tp, 16 * tp)).astype(np.float32)
    got = np.asarray(rc.ring_sync_matmul(jnp.asarray(x), jnp.asarray(w), mesh))
    want = x @ w
    assert np.allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.slow  # tier-2: heavy; a faster sibling keeps this class covered in tier-1 (see pyproject markers)
def test_ring_sync_matmul_packed_q40():
    """The serving form: col-sliced PackedQ40 planes, dequant-in-matmul
    per column chunk, ring-reduced — matches the unsharded Q40 matmul."""
    tp = 4
    mesh = make_mesh(MeshPlan(tp=tp))
    rng = np.random.default_rng(7)
    x = rng.standard_normal((3, 128)).astype(np.float32)
    w = _packed_weight(128, 128, seed=7)
    got = np.asarray(rc.ring_sync_matmul(jnp.asarray(x), w, mesh))
    want = np.asarray(q40_matmul_xla(jnp.asarray(x), w))
    scale = np.abs(want).max() + 1e-9
    assert np.abs(got - want).max() / scale < 1e-5


@pytest.mark.slow  # tier-2: heavy; a faster sibling keeps this class covered in tier-1 (see pyproject markers)
def test_ring_sync_matmul_q80_wire():
    """Q80 wire engages on the gather half only: within the reference
    transport's ~1e-2 class of the f32-wire result."""
    tp = 4
    mesh = make_mesh(MeshPlan(tp=tp))
    rng = np.random.default_rng(8)
    x = rng.standard_normal((3, 128)).astype(np.float32)
    w = _packed_weight(128, 256, seed=8)  # chunk 64: whole Q80 blocks
    f32 = np.asarray(rc.ring_sync_matmul(jnp.asarray(x), w, mesh))
    q80 = np.asarray(rc.ring_sync_matmul(jnp.asarray(x), w, mesh, q80_wire=True))
    scale = np.abs(f32).max() + 1e-9
    assert np.abs(q80 - f32).max() / scale < 2e-2
    assert not np.array_equal(q80, f32)  # the wire really quantized


def test_ring_sync_matmul_rejects_indivisible():
    tp = 4
    mesh = make_mesh(MeshPlan(tp=tp))
    w = _packed_weight(128, 96, seed=9)  # 96 % 4 == 0 but 24 % 32 != 0
    x = jnp.zeros((2, 128), jnp.float32)
    with pytest.raises(ValueError, match="whole Q80 blocks"):
        rc.ring_sync_matmul(x, w, mesh, q80_wire=True)
    w2 = _packed_weight(128, 30 * 2, seed=9)  # 60 % 4 == 0 -> ok f32
    assert rc.ring_sync_supported(60, 4) and not rc.ring_sync_supported(60, 4, True)
    with pytest.raises(ValueError, match="divisible"):
        rc.ring_sync_matmul(x, _packed_weight(128, 90, seed=9), mesh)  # 90 % 4


# ---------------------------------------------------------------------------
# Escape hatch + engagement predicate.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scales", ["f16", "bits"])
def test_escape_hatch_restores_psum_path(scales):
    """set_ring_sync(False): the partitioned Q40 matmul's col-sliced sync
    is lax.psum again — bit-for-bit the manual shard_map psum reference —
    and ring_sync_engages goes False everywhere. The scale plane passes
    through the mesh paths by shape, float16 or at rest as its int16 bits."""
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig
    from distributed_llama_multiusers_tpu.ops.pallas_q40 import (
        _q40_mm_impl,
        q40_matmul_partitioned,
    )

    tp = 4
    mesh = make_mesh(MeshPlan(tp=tp))
    rng = np.random.default_rng(10)
    x = rng.standard_normal((2, 128)).astype(np.float32)
    w = _packed_weight(128, 64, seed=10)
    if scales == "bits":
        w = q40_at_rest(w)

    # col-sliced layout: x last dim + packed plane rows sharded over tp.
    # interpret=True is the CPU convention for the partitioned kernel
    # (linear.matmul only routes here with pallas interpret on, as the
    # mesh tests do) — the escape-hatch contract is about the SYNC step.
    xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P(None, "tp")))
    wp = jax.device_put(w.packed, NamedSharding(mesh, P("tp", None)))
    ws = jax.device_put(w.scales, NamedSharding(mesh, P("tp", None)))
    shw = PackedQ40(wp, ws)

    def part_fn(a, b):
        # fresh jit per call: the ring flag is read at trace time, so a
        # shared cache would serve the first trace for both settings
        return jax.jit(
            lambda a_, b_: q40_matmul_partitioned(a_, b_, interpret=True)
        )(a, b)

    def manual_psum_ref():
        # EXACTLY the per-shard computation the partitioned path runs
        # (_q40_mm_impl), followed by a plain psum — the pre-ring lowering
        def inner(xl, pl_, sl):
            part = _q40_mm_impl(xl, pl_, sl, True, None)
            return jax.lax.psum(part, "tp")

        return np.asarray(shard_map(
            inner, mesh=mesh,
            in_specs=(P(None, "tp"), P("tp", None), P("tp", None)),
            out_specs=P(None, None), check_vma=False,
        )(xs, wp, ws))

    prev = rc.ring_sync_enabled()
    try:
        rc.set_ring_sync(False)
        assert not rc.ring_sync_engages(
            LlamaConfig(dim=64, hidden_dim=128, n_layers=1, n_heads=4,
                        n_kv_heads=4, vocab_size=64, seq_len=16),
            {"tp": 4},
        )
        off = np.asarray(part_fn(xs, shw))
        assert np.array_equal(off, manual_psum_ref())  # bit-for-bit psum
        rc.set_ring_sync(True)
        on = np.asarray(part_fn(xs, shw))
        # ring vs psum: same f32 class (exact at any tp for these magnitudes
        # is not guaranteed, but the class is)
        scale = np.abs(off).max() + 1e-9
        assert np.abs(on - off).max() / scale < 1e-5
    finally:
        rc.set_ring_sync(prev)


def test_ring_sync_engages_pure_tp_only():
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    cfg = LlamaConfig(dim=64, hidden_dim=128, n_layers=1, n_heads=4,
                      n_kv_heads=4, vocab_size=64, seq_len=16)
    prev = rc.ring_sync_enabled()
    try:
        rc.set_ring_sync(True)
        assert rc.ring_sync_engages(cfg, {"tp": 4})
        assert not rc.ring_sync_engages(cfg, {"tp": 1})
        assert not rc.ring_sync_engages(cfg, {"tp": 2, "sp": 2})
        assert not rc.ring_sync_engages(cfg, {"tp": 2, "dp": 2})
    finally:
        rc.set_ring_sync(prev)


def test_forward_ring_on_off_parity():
    """Pure-TP llama_forward: ring on vs off vs mesh-free all in the same
    f32 class (the serving-path integration, wo/w2 through the ring)."""
    from distributed_llama_multiusers_tpu.models import (
        init_kv_cache,
        llama_forward,
        params_from_random,
    )
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig
    from distributed_llama_multiusers_tpu.parallel import (
        validate_mesh_for_config,
    )
    from distributed_llama_multiusers_tpu.parallel.sharding import shard_params

    config = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=8,
                         n_kv_heads=4, vocab_size=128, seq_len=32)
    plan = MeshPlan(tp=4)
    validate_mesh_for_config(config, plan)
    mesh = make_mesh(plan)
    params = params_from_random(config, seed=0, dtype=jnp.float32)
    sp = shard_params(params, mesh)
    rng = np.random.default_rng(11)
    toks = jnp.asarray(rng.integers(0, 128, (2, 8)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None], (2, 8))

    def fwd(p, mesh_):
        logits, _ = jax.jit(
            lambda p_, t, q, c: llama_forward(config, p_, t, q, c, mesh=mesh_)
        )(p, toks, pos, init_kv_cache(config, 2))
        return np.asarray(logits)

    ref = fwd(params, None)
    prev = rc.ring_sync_enabled()
    try:
        rc.set_ring_sync(True)
        ring = fwd(sp, mesh)
        rc.set_ring_sync(False)
        psum = fwd(sp, mesh)
    finally:
        rc.set_ring_sync(prev)
    assert np.abs(ring - ref).max() < 1e-4
    assert np.abs(psum - ref).max() < 1e-4
    # greedy decisions identical: the serving stream-parity class
    assert np.array_equal(ring.argmax(-1), ref.argmax(-1))


@pytest.mark.parametrize("scales", ["f16", "bits"])
@pytest.mark.parametrize("ring", [True, False])
def test_pure_tp_packed_forward_needs_no_custom_partitioner(ring, scales, monkeypatch):
    """libtpu has no custom-call partitioner, so on a pure-TP mesh the
    Q40 forward must not contain the GSPMD kernel wrapper at all: every
    matmul runs the kernel per shard under shard_map (sliced: no sync;
    synced: ring, or shard-local psum with the ring off) — and still
    matches the dense mesh-free forward."""
    import distributed_llama_multiusers_tpu.ops.pallas_q40 as pq
    from distributed_llama_multiusers_tpu.models import (
        init_kv_cache,
        llama_forward,
        params_from_random,
    )
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig
    from distributed_llama_multiusers_tpu.models.loader import quantize_params
    from distributed_llama_multiusers_tpu.ops import linear
    from distributed_llama_multiusers_tpu.parallel.sharding import shard_params

    config = LlamaConfig(dim=256, hidden_dim=512, n_layers=2, n_heads=8,
                         n_kv_heads=4, vocab_size=512, seq_len=32)
    dense = params_from_random(config, seed=3, dtype=jnp.float32)
    packed = quantize_params(dense)
    if scales == "bits":  # as the engine serves it; shard_map takes it by shape
        packed = q40_at_rest(packed)
    mesh = make_mesh(MeshPlan(tp=2))
    toks = jnp.asarray([[3, 9, 27, 81]], jnp.int32)
    pos = jnp.arange(4, dtype=jnp.int32)[None]

    calls = {"n": 0}
    real_kernel = pq.q40_matmul_pallas

    def counting_kernel(x, w, interpret=False, **kw):
        calls["n"] += 1
        return real_kernel(x, w, interpret=interpret, **kw)

    monkeypatch.setattr(pq, "q40_matmul_pallas", counting_kernel)
    prev = rc.ring_sync_enabled()
    linear.set_pallas_interpret(True)
    try:
        rc.set_ring_sync(ring)
        # the reference dequantizes the SAME Q40 planes through XLA
        linear.set_pallas_enabled(False)
        ref, _ = llama_forward(config, packed, toks, pos, init_kv_cache(config, 1))
        linear.set_pallas_enabled(True)
        fwd = jax.jit(
            lambda p, c: llama_forward(config, p, toks, pos, c, mesh=mesh)[0]
        )
        args = (shard_params(packed, mesh), init_kv_cache(config, 1))
        hlo = fwd.lower(*args).as_text()
        got = fwd(*args)
    finally:
        linear.set_pallas_enabled(True)
        linear.set_pallas_interpret(False)
        rc.set_ring_sync(prev)
    assert "CustomSPMDPartitioning" not in hlo
    assert calls["n"] > 0, "the sharded forward never reached the kernel"
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=2e-3, rtol=2e-3
    )
