"""Pallas Q40 matmul kernel vs the XLA fallback (interpret mode on CPU).

The reference's kernel-equivalence analogue is matmul_Q80_Q40_F32 vs
matmul_F32 (src/nn/nn-cpu-ops-test.cpp:220-241); here the Pallas kernel and
q40_matmul_xla dequantize identically, so results must agree to float
rounding, not a quantization tolerance.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_llama_multiusers_tpu.ops.pallas_q40 import (
    _f16_bits_to_f32,
    q40_matmul_pallas,
)
from distributed_llama_multiusers_tpu.quants.packed import (
    PackedQ40,
    pack_q40_host,
    q40_matmul_xla,
)


def _pack(rng, d_out, d_in, scale=0.1):
    w = rng.standard_normal((d_out, d_in), dtype=np.float32) * scale
    packed, scales = pack_q40_host(w)
    return PackedQ40(packed=jnp.asarray(packed), scales=jnp.asarray(scales))


def test_f16_bit_conversion_exact():
    # every finite f16 bit pattern converts exactly (incl. denormals)
    bits = np.arange(65536, dtype=np.uint16)
    h = bits.view(np.float16)
    finite = np.isfinite(h)
    got = np.asarray(_f16_bits_to_f32(jnp.asarray(bits.astype(np.int16))))
    np.testing.assert_array_equal(got[finite], h[finite].astype(np.float32))


@pytest.mark.parametrize(
    "m,d_in,d_out",
    [
        (1, 64, 128),
        (5, 256, 384),
        (8, 2048, 512),
        (16, 128, 256),
        # d_in with no power-of-two chunk divisor (1376 = 43*32): the analogue
        # of Llama-2-7B's hidden_dim 11008 that crashed the halves layout
        (3, 1376, 128),
        # Llama-2-7B hidden_dim itself: d_out > 8192 with no 512-multiple
        # divisor — the wide-tile planner must fall back to 128-multiples
        # (5504 = 43*128), not reject the shape
        (2, 256, 11008),
        # and its tp=2 shard: d_out <= 8192, 512-multiple + 384 remainder
        (2, 256, 5504),
        # multi-chunk reduction (n_k > 1): half=2048 x W=2048 exceeds the
        # single-slab budget, exercising the k-axis accumulator
        (4, 4096, 2048),
        # wide-tile grid (j > 1): d_out 16384 tiles as 2 x 8192
        (2, 512, 16384),
        # multiple m tiles: m_pad 512 = 2 x 256 with full-extent checks on
        # the bsum lane dim
        (300, 64, 256),
    ],
)
def test_pallas_matches_xla(m, d_in, d_out):
    rng = np.random.default_rng(d_in + d_out)
    pw = _pack(rng, d_out, d_in)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    ref = q40_matmul_xla(x, pw)
    got = q40_matmul_pallas(x, pw, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4, rtol=2e-4)


def test_pallas_leading_batch_dims():
    rng = np.random.default_rng(0)
    pw = _pack(rng, 256, 128)
    x = jnp.asarray(rng.standard_normal((2, 3, 128), dtype=np.float32))
    ref = q40_matmul_xla(x, pw)
    got = q40_matmul_pallas(x, pw, interpret=True)
    assert got.shape == (2, 3, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4, rtol=2e-4)


def test_pallas_extreme_scales():
    # very small weights -> denormal f16 scales still convert exactly
    rng = np.random.default_rng(1)
    pw = _pack(rng, 128, 64, scale=1e-7)
    x = jnp.asarray(rng.standard_normal((4, 64), dtype=np.float32))
    ref = q40_matmul_xla(x, pw)
    got = q40_matmul_pallas(x, pw, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-10)


# ---------------------------------------------------------------------------
# GSPMD partitioning (q40_matmul_partitioned): the kernel under meshes.
# Round 1 disabled Pallas on any mesh; these pin the custom_partitioning rule
# that keeps dequant-in-matmul on every shard (the reference runs its
# quantized matmul on every node, src/nn/nn-cpu-ops.cpp:222-440).
# ---------------------------------------------------------------------------

from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from distributed_llama_multiusers_tpu.ops.pallas_q40 import q40_matmul_partitioned  # noqa: E402
from distributed_llama_multiusers_tpu.parallel import MeshPlan, make_mesh  # noqa: E402


def _sharded(arr, mesh, *spec):
    return jax.device_put(arr, NamedSharding(mesh, P(*spec)))


@pytest.mark.parametrize("w_spec,expect_out_tp", [
    ((None, "tp"), True),   # row-sliced: d_out sharded, output stays sharded
    (("tp", None), False),  # col-sliced: d_in sharded, psum -> replicated
])
def test_partitioned_matmul_parity(w_spec, expect_out_tp):
    rng = np.random.default_rng(7)
    pw = _pack(rng, 256, 128)
    x = jnp.asarray(rng.standard_normal((8, 128), dtype=np.float32))
    ref = q40_matmul_xla(x, pw)

    mesh = make_mesh(MeshPlan(tp=2, dp=2))
    w_sh = PackedQ40(
        packed=_sharded(pw.packed, mesh, *w_spec),
        scales=_sharded(pw.scales, mesh, *w_spec),
    )
    x_sh = _sharded(x, mesh, "dp", None)
    f = jax.jit(
        lambda a, p, s: q40_matmul_partitioned(a, PackedQ40(p, s), interpret=True)
    )
    got = f(x_sh, w_sh.packed, w_sh.scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4, rtol=2e-4)
    out_axes = set()
    for entry in got.sharding.spec:
        out_axes |= {entry} if isinstance(entry, str) else set(entry or ())
    assert ("tp" in out_axes) == expect_out_tp, got.sharding


def test_sharded_forward_takes_pallas_path(monkeypatch, tmp_path):
    """tp=2 quantized model forward routes through the Pallas kernel
    (interpret mode) and matches the dense single-device forward."""
    import distributed_llama_multiusers_tpu.ops.pallas_q40 as pq
    from distributed_llama_multiusers_tpu.formats.synthetic import (
        tiny_header,
        write_synthetic_model,
    )
    from distributed_llama_multiusers_tpu.formats.model_file import load_model_header
    from distributed_llama_multiusers_tpu.models import init_kv_cache, llama_forward
    from distributed_llama_multiusers_tpu.models.loader import (
        load_params_from_m,
        load_params_from_m_quantized,
    )
    from distributed_llama_multiusers_tpu.ops import linear
    from distributed_llama_multiusers_tpu.parallel.sharding import shard_params

    calls = {"n": 0}
    real_kernel = pq.q40_matmul_pallas

    def counting_kernel(x, w, interpret=False, **kw):
        calls["n"] += 1
        return real_kernel(x, w, interpret=interpret, **kw)

    monkeypatch.setattr(pq, "q40_matmul_pallas", counting_kernel)
    linear.set_pallas_interpret(True)
    try:
        path = str(tmp_path / "tiny.m")
        write_synthetic_model(path, tiny_header(), seed=11)
        h = load_model_header(path)
        config, dense_params = load_params_from_m(path, h, dtype=jnp.float32)
        _, qparams = load_params_from_m_quantized(path, h, dtype=jnp.float32)
        tokens = jnp.asarray([[3, 9, 27]], jnp.int32)
        positions = jnp.asarray([[0, 1, 2]], jnp.int32)
        ref, _ = llama_forward(
            config, dense_params, tokens, positions, init_kv_cache(config, 1)
        )

        mesh = make_mesh(MeshPlan(tp=2))
        q_sh = shard_params(qparams, mesh)
        got, _ = llama_forward(
            config, q_sh, tokens, positions, init_kv_cache(config, 1)
        )
    finally:
        linear.set_pallas_interpret(False)

    assert calls["n"] > 0, "sharded forward never reached the Pallas kernel"
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-3, rtol=2e-3)


def test_pallas_bf16_weight_tiles_close():
    """w_dtype=bf16 (the VMEM-bandwidth ablation knob) stays within bf16
    rounding of the exact f32 kernel — reachable via
    linear.set_pallas_w_dtype."""
    rng = np.random.default_rng(3)
    pw = _pack(rng, 256, 128)
    x = jnp.asarray(rng.standard_normal((4, 128), dtype=np.float32))
    exact = q40_matmul_pallas(x, pw, interpret=True)
    loose = q40_matmul_pallas(x, pw, interpret=True, w_dtype=jnp.bfloat16)
    # bf16 has 8 mantissa bits: ~0.4% relative error per product
    np.testing.assert_allclose(
        np.asarray(loose), np.asarray(exact), rtol=2e-2, atol=2e-2
    )
    assert not np.array_equal(np.asarray(loose), np.asarray(exact))


def test_dequant_mode_variants_close():
    """Every DEQUANT_MODE (the bf16-path arithmetic A/B: v4 f32-chain,
    bf16chain, repeat) stays within bf16 rounding of the exact f32 kernel,
    and the mode switch actually retraces (set_dequant_mode is a static
    arg of the jitted matmul)."""
    from distributed_llama_multiusers_tpu.ops.pallas_q40 import (
        DEQUANT_MODES,
        set_dequant_mode,
    )

    rng = np.random.default_rng(7)
    pw = _pack(rng, 256, 128)
    x = jnp.asarray(rng.standard_normal((4, 128), dtype=np.float32))
    exact = np.asarray(q40_matmul_pallas(x, pw, interpret=True))
    # per-mode error class: bf16-rounding-only chains sit at ~5e-3;
    # i8blockdot ALSO quantizes the activations (reference Q80 class,
    # ~1e-2 mean / 1.6e-2 max over seeds) so it gets the lab's bound
    bound = {"i8blockdot": 5e-2}
    try:
        for mode in DEQUANT_MODES:
            set_dequant_mode(mode)
            got = np.asarray(
                q40_matmul_pallas(x, pw, interpret=True, w_dtype=jnp.bfloat16)
            )
            # bf16 rounding error scales with the CONTRACTION magnitude,
            # not the output element (cancellation leaves small outputs
            # with proportionally larger error) — bound it vs max|y|
            rel = np.abs(got - exact).max() / (np.abs(exact).max() + 1e-9)
            assert rel < bound.get(mode, 2e-2), f"mode {mode}: max-rel {rel:.3e}"
            # exact-f32 dots ignore the mode knob entirely
            f32 = np.asarray(q40_matmul_pallas(x, pw, interpret=True))
            np.testing.assert_array_equal(f32, exact, err_msg=f"mode {mode}")
        # blockdot's post-scale cost scales with m: large-m calls
        # (prefill/training) must RESOLVE to bf16chain (observed via the
        # impl's mode argument — output closeness alone can't distinguish
        # a working fallback from blockdot incorrectly running at m=64)
        from distributed_llama_multiusers_tpu.ops import pallas_q40 as pq

        seen_modes = []
        real_impl = pq._q40_matmul_pallas_impl

        def spy(x_, w_, interpret_, w_dtype_, mode_):
            seen_modes.append(mode_)
            return real_impl(x_, w_, interpret_, w_dtype_, mode_)

        set_dequant_mode("blockdot")
        pq._q40_matmul_pallas_impl = spy
        try:
            x_big = jnp.asarray(
                rng.standard_normal((64, 128), dtype=np.float32)
            )
            exact_big = np.asarray(q40_matmul_pallas(x_big, pw, interpret=True))
            seen_modes.clear()
            got_big = np.asarray(
                q40_matmul_pallas(
                    x_big, pw, interpret=True, w_dtype=jnp.bfloat16
                )
            )
        finally:
            pq._q40_matmul_pallas_impl = real_impl
        assert seen_modes == ["bf16chain"], seen_modes
        rel = np.abs(got_big - exact_big).max() / (np.abs(exact_big).max() + 1e-9)
        assert rel < 2e-2, f"blockdot large-m fallback: max-rel {rel:.3e}"
    finally:
        set_dequant_mode(None)


def test_bf16_w_dtype_greedy_stream_model_scale(tiny_model):
    """End-to-end greedy stream with the SHIPPING TPU numeric default
    (w_dtype=bf16 dots, round-4 advisor finding: that path had no CI
    parity coverage — every other parity gate runs exact f32). On the
    synthetic tiny model the bf16 stream is token-identical to the exact
    f32 kernel stream for 32 tokens; per-step logits stay within bf16
    rounding. ``set_pallas_w_dtype(jnp.float32)`` restores exact-f32
    semantics (README/PERF document the default)."""
    from distributed_llama_multiusers_tpu.formats.model_file import load_model_header
    from distributed_llama_multiusers_tpu.models.loader import (
        load_params_from_m_quantized,
    )
    from distributed_llama_multiusers_tpu.ops import linear
    from distributed_llama_multiusers_tpu.runtime import InferenceEngine
    from distributed_llama_multiusers_tpu.utils.testing import greedy_rollout

    h = load_model_header(tiny_model["model"])
    config, qparams = load_params_from_m_quantized(
        tiny_model["model"], h, dtype=jnp.float32
    )
    prompt = [5, 9, 3, 17, 2]

    def rollout(w_dtype):
        linear.set_pallas_interpret(True)
        linear.set_pallas_w_dtype(w_dtype)
        try:
            engine = InferenceEngine(
                config, qparams, n_lanes=1, prefill_buckets=(8,)
            )
            toks, _ = greedy_rollout(engine, prompt, 32)
            logits, _, _ = engine.prefill(0, prompt)
            return toks, np.asarray(logits)
        finally:
            linear.set_pallas_w_dtype(None)
            linear.set_pallas_interpret(False)

    toks_bf16, logits_bf16 = rollout(jnp.bfloat16)
    toks_f32, logits_f32 = rollout(jnp.float32)
    np.testing.assert_allclose(logits_bf16, logits_f32, rtol=2e-2, atol=2e-2)
    assert toks_bf16 == toks_f32, (
        f"bf16-dot greedy stream diverged from exact f32: "
        f"{toks_bf16} vs {toks_f32}"
    )


# ---------------------------------------------------------------------------
# Shared Q80 activation operands (Q80Acts): one build per distinct input,
# every matmul sharing it consumes the prebuilt layouts.
# ---------------------------------------------------------------------------

from distributed_llama_multiusers_tpu.ops.pallas_q40 import (  # noqa: E402
    BLOCKDOT_MAX_M,
    DEQUANT_MODES,
    TRACE_STATS,
    make_q80_acts,
    reset_trace_stats,
    set_dequant_mode,
)


@pytest.mark.parametrize("mode", ["v4", "blockdot", "i8blockdot"])
def test_q80_acts_shared_vs_raw_parity(mode):
    """A prebuilt Q80Acts bundle and a raw activation run the SAME traced
    math per mode — only XLA fusion boundaries differ between the eager
    build and the in-jit build, so i8blockdot (the one mode with a
    reduction in operand prep) sits at ~1e-7 reduction-order wiggle.
    Covers the two acts-consuming modes plus the v4 chain standing in for
    the bf16-chain family (all chains unwrap the bundle via _raw_x on the
    same line, so one representative pins the passthrough)."""
    rng = np.random.default_rng(5)
    pw = _pack(rng, 256, 128)
    x = jnp.asarray(rng.standard_normal((4, 128), dtype=np.float32))
    set_dequant_mode(mode)
    try:
        raw = np.asarray(
            q40_matmul_pallas(x, pw, interpret=True, w_dtype=jnp.bfloat16)
        )
        acts = make_q80_acts(x)
        assert make_q80_acts(acts) is acts  # idempotent
        shared = np.asarray(
            q40_matmul_pallas(acts, pw, interpret=True, w_dtype=jnp.bfloat16)
        )
    finally:
        set_dequant_mode(None)
    np.testing.assert_allclose(shared, raw, rtol=1e-5, atol=1e-5)


def test_q80_acts_build_and_consume_counters():
    """Trace-time counters witness the sharing: one shared build feeds N
    consumes with zero per-site rebuilds."""
    rng = np.random.default_rng(6)
    weights = [_pack(rng, d_out, 128) for d_out in (128, 256, 384)]
    x = jnp.asarray(rng.standard_normal((4, 128), dtype=np.float32))
    reset_trace_stats()
    acts = make_q80_acts(x, shared=True)
    for pw in weights:
        q40_matmul_pallas(acts, pw, interpret=True)
    assert TRACE_STATS["acts_builds"] == 1, TRACE_STATS
    assert TRACE_STATS["shared_builds"] == 1, TRACE_STATS
    assert TRACE_STATS["shared_consumes"] == 3, TRACE_STATS


def test_shared_acts_build_counts_model_scale(tiny_model):
    """THE operand-sharing win at model scale: one llama_forward trace
    builds exactly TWO shared bundles (the normed x for wq/wk/wv; the
    FFN input for w1/w3) consumed at five matmul sites — the layer body
    traces once under lax.scan. The remaining builds are the unshared
    single-consumer sites (wo, w2 in the layer, wcls at the head)."""
    from distributed_llama_multiusers_tpu.formats.model_file import load_model_header
    from distributed_llama_multiusers_tpu.models import init_kv_cache, llama_forward
    from distributed_llama_multiusers_tpu.models.loader import (
        load_params_from_m_quantized,
    )
    from distributed_llama_multiusers_tpu.ops import linear

    h = load_model_header(tiny_model["model"])
    config, qparams = load_params_from_m_quantized(
        tiny_model["model"], h, dtype=jnp.float32
    )
    tokens = jnp.asarray([[3, 9, 27]], jnp.int32)
    positions = jnp.asarray([[0, 1, 2]], jnp.int32)
    linear.set_pallas_interpret(True)
    try:
        reset_trace_stats()
        llama_forward(
            config, qparams, tokens, positions, init_kv_cache(config, 1)
        )
        assert TRACE_STATS["shared_builds"] == 2, TRACE_STATS
        assert TRACE_STATS["shared_consumes"] == 5, TRACE_STATS
        # the only other builds come from the three unshared sites, each
        # at most once per kernel-family trace (0 on a warm jit cache) —
        # never one-per-consumer like the pre-sharing layout
        assert TRACE_STATS["acts_builds"] - 2 <= 3, TRACE_STATS
    finally:
        linear.set_pallas_interpret(False)


def test_blockdot_max_m_cap_routes_and_caches():
    """BLOCKDOT_MAX_M boundary (documented in PERF.md): m at/under the cap
    runs the selected blockdot-family mode, one past it falls back to
    bf16chain — observed via the impl's resolved mode argument — and
    repeated same-shape calls never re-trace the kernel core."""
    from distributed_llama_multiusers_tpu.ops import pallas_q40 as pq

    rng = np.random.default_rng(11)
    pw = _pack(rng, 128, 64)
    seen = []
    real_impl = pq._q40_matmul_pallas_impl

    def spy(x_, w_, interpret_, w_dtype_, mode_):
        seen.append(mode_)
        return real_impl(x_, w_, interpret_, w_dtype_, mode_)

    pq._q40_matmul_pallas_impl = spy
    try:
        for mode in ("blockdot", "i8blockdot"):
            set_dequant_mode(mode)
            for m, expect in [
                (BLOCKDOT_MAX_M - 1, mode),
                (BLOCKDOT_MAX_M, mode),
                (BLOCKDOT_MAX_M + 1, "bf16chain"),
            ]:
                seen.clear()
                x = jnp.asarray(
                    rng.standard_normal((m, 64), dtype=np.float32)
                )
                q40_matmul_pallas(x, pw, interpret=True, w_dtype=jnp.bfloat16)
                assert seen == [expect], (mode, m, seen)
        # auto resolves through the same boundary: the table's decode
        # class IS the blockdot cap, so the m-class flip and the kernel
        # fallback agree at m = BLOCKDOT_MAX_M + 1
        set_dequant_mode("auto")
        for m, expect in [
            (BLOCKDOT_MAX_M, "i8blockdot"),
            (BLOCKDOT_MAX_M + 1, "bf16chain"),
        ]:
            seen.clear()
            x = jnp.asarray(rng.standard_normal((m, 64), dtype=np.float32))
            q40_matmul_pallas(x, pw, interpret=True, w_dtype=jnp.bfloat16)
            assert seen == [expect], ("auto", m, seen)
        # no recompile churn: the second same-shape call is a jit cache
        # hit — the kernel core's python body does not run again
        set_dequant_mode("i8blockdot")
        x = jnp.asarray(
            rng.standard_normal((BLOCKDOT_MAX_M, 64), dtype=np.float32)
        )
        q40_matmul_pallas(x, pw, interpret=True, w_dtype=jnp.bfloat16)
        traces = TRACE_STATS["impl_traces"]
        q40_matmul_pallas(x, pw, interpret=True, w_dtype=jnp.bfloat16)
        assert TRACE_STATS["impl_traces"] == traces, TRACE_STATS
    finally:
        pq._q40_matmul_pallas_impl = real_impl
        set_dequant_mode(None)


# ---------------------------------------------------------------------------
# Q80xQ40 numerics pinning (make kernelcheck runs this grid standalone):
# interpret-mode i8blockdot vs the exact f32 chain across shapes.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "d_in,d_out,m",
    [
        # the (d_in, d_out) axis at the m extremes of the decode class,
        # plus the multi-chunk plane at the blockdot cap — the interpret
        # kernel is slow enough that tier-1 keeps the informative corners
        # and `make kernelcheck` + the slow stream pin carry the rest
        (128, 256, 1), (128, 256, 8), (128, 256, 32),
        (512, 256, 1),
        (512, 1024, 32),
    ],
)
def test_i8blockdot_parity_grid(d_in, d_out, m):
    rng = np.random.default_rng(d_in * 7 + d_out + m)
    pw = _pack(rng, d_out, d_in)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    exact = np.asarray(q40_matmul_pallas(x, pw, interpret=True))
    set_dequant_mode("i8blockdot")
    try:
        got = np.asarray(
            q40_matmul_pallas(x, pw, interpret=True, w_dtype=jnp.bfloat16)
        )
    finally:
        set_dequant_mode(None)
    rel = np.abs(got - exact).max() / (np.abs(exact).max() + 1e-9)
    assert rel <= 2e-2, f"({d_in}x{d_out}, m={m}): max-rel {rel:.3e}"


@pytest.mark.slow
def test_i8blockdot_greedy_stream_token_identity(tmp_path):
    """Decode-stream half of the numerics pin: >= 256 greedy tokens under
    the shipping bf16 dot are token-identical between the i8blockdot
    chain and the v4 chain on a seeded synthetic model, with bounded
    prefill-logit drift."""
    from distributed_llama_multiusers_tpu.formats.model_file import load_model_header
    from distributed_llama_multiusers_tpu.formats.synthetic import (
        tiny_header,
        write_synthetic_model,
    )
    from distributed_llama_multiusers_tpu.models.loader import (
        load_params_from_m_quantized,
    )
    from distributed_llama_multiusers_tpu.ops import linear
    from distributed_llama_multiusers_tpu.runtime import InferenceEngine
    from distributed_llama_multiusers_tpu.utils.testing import greedy_rollout

    path = str(tmp_path / "stream.m")
    write_synthetic_model(path, tiny_header(seq_len=320), seed=23)
    h = load_model_header(path)
    config, qparams = load_params_from_m_quantized(path, h, dtype=jnp.float32)
    prompt = [5, 9, 3, 17, 2]

    def rollout(mode):
        linear.set_pallas_interpret(True)
        linear.set_pallas_w_dtype(jnp.bfloat16)
        set_dequant_mode(mode)
        try:
            engine = InferenceEngine(
                config, qparams, n_lanes=1, prefill_buckets=(8,)
            )
            toks, _ = greedy_rollout(engine, prompt, 256)
            logits, _, _ = engine.prefill(0, prompt)
            return toks, np.asarray(logits)
        finally:
            set_dequant_mode(None)
            linear.set_pallas_w_dtype(None)
            linear.set_pallas_interpret(False)

    toks_i8, logits_i8 = rollout("i8blockdot")
    toks_v4, logits_v4 = rollout("v4")
    assert len(toks_i8) >= 256
    np.testing.assert_allclose(logits_i8, logits_v4, rtol=2e-2, atol=2e-2)
    assert toks_i8 == toks_v4, (
        f"i8blockdot greedy stream diverged from the v4 chain at "
        f"position {next(i for i, (a, b) in enumerate(zip(toks_i8, toks_v4)) if a != b)}"
    )


# ---------------------------------------------------------------------------
# Mode-knob validation (set_dequant_mode / DLLAMA_DEQUANT fail loudly).
# ---------------------------------------------------------------------------


def test_set_dequant_mode_rejects_unknown():
    with pytest.raises(ValueError, match="unknown dequant mode"):
        set_dequant_mode("q31wizard")
    # the knob is unchanged after the rejection
    from distributed_llama_multiusers_tpu.ops.pallas_q40 import DEQUANT_MODE

    assert DEQUANT_MODE in DEQUANT_MODES + ("auto",)


def test_env_dequant_rejects_unknown_on_import():
    import os
    import subprocess
    import sys

    env = dict(os.environ, DLLAMA_DEQUANT="q31wizard", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c",
         "import distributed_llama_multiusers_tpu.ops.pallas_q40"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert proc.returncode != 0
    assert "not a known dequant mode" in proc.stderr


# ---------------------------------------------------------------------------
# Stacked weights: the kernel reads layer ``l``'s tiles out of a [L, ...]
# stack itself (q40_matmul_pallas(layer=l)), so a layer scan never slices the
# plane into a buffer of its own. Same arithmetic, same bits: only where the
# weight blocks are fetched from differs.
# ---------------------------------------------------------------------------

STACK_L = 3


def _stack(rng, d_out, d_in, n=STACK_L):
    planes = [_pack(rng, d_out, d_in) for _ in range(n)]
    return PackedQ40(packed=jnp.stack([p.packed for p in planes]),
                     scales=jnp.stack([p.scales for p in planes]))


def _plane(stack, l):
    return PackedQ40(packed=stack.packed[l], scales=stack.scales[l])


@pytest.mark.parametrize("how", ["jit", "scan"])
@pytest.mark.parametrize("entry", ["raw_x", "shared_acts"])
@pytest.mark.parametrize("mode", DEQUANT_MODES)
def test_stacked_weight_equals_its_plane_bit_for_bit(mode, entry, how):
    """Layer ``l`` read out of the stack equals the 2-D kernel on plane ``l``
    to the bit, in every dequant mode and through both jitted entries, with
    ``l`` a traced scalar (an argument of a jit; the counter of a lax.scan)
    at the stack's first and last layer. Both sides are computed inside one
    traced program, so the operand builds are the same operations."""
    rng = np.random.default_rng(30)
    stack = _stack(rng, 256, 128)
    x = jnp.asarray(rng.standard_normal((4, 128), dtype=np.float32))
    kw = dict(interpret=True, w_dtype=jnp.bfloat16)

    def both(x, stack, l):
        xin = make_q80_acts(x) if entry == "shared_acts" else x
        return (q40_matmul_pallas(xin, stack, layer=l, **kw),
                q40_matmul_pallas(xin, _plane(stack, l), **kw))

    set_dequant_mode(mode)
    try:
        reset_trace_stats()
        if how == "jit":
            fn = jax.jit(both)
            pairs = {l: fn(x, stack, jnp.int32(l)) for l in (0, STACK_L - 1)}
        else:
            _, (got, want) = jax.lax.scan(
                lambda c, l: (c, both(x, stack, l)), 0,
                jnp.arange(STACK_L, dtype=jnp.int32))
            pairs = {l: (got[l], want[l]) for l in (0, STACK_L - 1)}
        # one trace of ``both``: one kernel call that indexes a stack
        assert TRACE_STATS["stacked_consumes"] == 1, TRACE_STATS
    finally:
        set_dequant_mode(None)
    for l, (got, want) in pairs.items():
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"layer {l}")
    assert not np.array_equal(np.asarray(pairs[0][0]),
                              np.asarray(pairs[STACK_L - 1][0]))


@pytest.mark.parametrize("m,d_in,d_out", [
    (4, 4096, 2048),   # n_k > 1: the k axis walks chunks of layer l's plane
    (2, 512, 16384),   # two wide tiles: the j axis
    (300, 64, 256),    # two m tiles
])
def test_stacked_weight_on_every_grid_axis(m, d_in, d_out):
    """The layer offset composes with each axis of the grid."""
    rng = np.random.default_rng(d_in + d_out)
    stack = _stack(rng, d_out, d_in, n=2)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    for l in (0, 1):
        got = q40_matmul_pallas(x, stack, interpret=True, layer=l)
        want = q40_matmul_pallas(x, _plane(stack, l), interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(q40_matmul_xla(x, _plane(stack, l))),
            atol=2e-4, rtol=2e-4)


# sha256[:16] of the output bytes the PARENT of PR 30 gives for the seeded
# call below (its kernel took 2-D planes only): a 2-D weight goes through the
# same pallas_call as a stack now, as the stack of one read at layer 0
PARENT_2D_DIGESTS = {
    "f32": "a00e1bb2e19dc500", "v4": "152c5bc3acc7695f",
    "bf16chain": "8cd3d3f237e607c8", "repeat": "8cd3d3f237e607c8",
    "u8chain": "8cd3d3f237e607c8", "blockdot": "2e41ab0e772529f9",
    "i8blockdot": "e537117c72b1fdf8",
}


@pytest.mark.parametrize("mode", list(PARENT_2D_DIGESTS))
def test_plain_weight_gives_what_it_gave_before_stacks(mode):
    import hashlib

    rng = np.random.default_rng(30)
    pw = _pack(rng, 384, 256)
    x = jnp.asarray(rng.standard_normal((5, 256), dtype=np.float32))
    kw = {} if mode == "f32" else {"w_dtype": jnp.bfloat16}
    set_dequant_mode(None if mode == "f32" else mode)
    try:
        got = np.asarray(q40_matmul_pallas(x, pw, interpret=True, **kw))
        as_stack = np.asarray(q40_matmul_pallas(
            x, PackedQ40(pw.packed[None], pw.scales[None]), interpret=True,
            layer=0, **kw))
    finally:
        set_dequant_mode(None)
    assert hashlib.sha256(got.tobytes()).hexdigest()[:16] == PARENT_2D_DIGESTS[mode]
    np.testing.assert_array_equal(as_stack, got)


def test_stack_and_layer_go_together():
    """A stack without a layer, or a layer with a 2-D plane, is an error and
    not a guess."""
    rng = np.random.default_rng(3)
    stack = _stack(rng, 128, 64, n=2)
    x = jnp.asarray(rng.standard_normal((2, 64), dtype=np.float32))
    with pytest.raises(ValueError, match="stack and its layer"):
        q40_matmul_pallas(x, stack, interpret=True)
    with pytest.raises(ValueError, match="stack and its layer"):
        q40_matmul_pallas(x, _plane(stack, 0), interpret=True, layer=0)
