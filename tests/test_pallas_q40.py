"""Pallas Q40 matmul kernel vs the XLA fallback (interpret mode on CPU).

The reference's kernel-equivalence analogue is matmul_Q80_Q40_F32 vs
matmul_F32 (src/nn/nn-cpu-ops-test.cpp:220-241); here the Pallas kernel and
q40_matmul_xla dequantize identically, so results must agree to float
rounding, not a quantization tolerance.
"""

from functools import partial

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
    q40_at_rest,
    q40_matmul_xla,
)

# the two forms a weight's scales arrive in (quants/packed.py): float16 as
# the packers make them, and their int16 bits as the engine serves from them.
# Through the kernel's entry a PLANE at rest is read in place and a stack as
# small as a test's has its layer's plane sliced out, as bits (XLA would stage
# it whole: ``pq.reads_scales_in_place``); a stack read in place is ``_in_place`` below
SCALE_FORMS = {"f16": lambda w: w, "bits": q40_at_rest}


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
        # rows above M_TILE: m_pad 512 = 2 x 256 (one block of rows since
        # PR 45, two m tiles before) with full-extent checks on
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
@pytest.mark.parametrize("scales", list(SCALE_FORMS))
def test_partitioned_matmul_parity(w_spec, expect_out_tp, scales):
    rng = np.random.default_rng(7)
    pw = _pack(rng, 256, 128)
    x = jnp.asarray(rng.standard_normal((8, 128), dtype=np.float32))
    ref = q40_matmul_xla(x, pw)
    # the partitioning rule passes the scale plane through by shape
    pw = SCALE_FORMS[scales](pw)

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
# One input, several consumers: every caller hands the kernel's one entry x
# as it is (PR 46: the activation bundle, its second jitted entry and the
# build / share counters went). Consumers of one x inside one program run the
# math they run alone.
# ---------------------------------------------------------------------------

from distributed_llama_multiusers_tpu.ops.pallas_q40 import (  # noqa: E402
    BLOCKDOT_MAX_M,
    DEQUANT_MODES,
    TRACE_STATS,
    reset_trace_stats,
    set_dequant_mode,
)


@pytest.mark.parametrize(
    "mode", ["v4", "blockdot", "i8blockdot", "bf16chain", "repeat", "u8chain"])
def test_consumers_of_one_input_match_their_standalone_calls(mode):
    """Three consumers of one x inside one ``jit`` (wq/wk/wv's shape: the
    program XLA may merge their pads and operand builds in) against each
    weight's standalone call — only XLA fusion boundaries differ, so
    i8blockdot (the one mode with a reduction in operand prep) sits at ~1e-7
    reduction-order wiggle. The slab chains (PR 42) are handed x itself
    either way, and nothing is prepared that a fusion boundary could move:
    to the bit."""
    rng = np.random.default_rng(5)
    weights = [_pack(rng, d_out, 128) for d_out in (256, 128, 384)]
    x = jnp.asarray(rng.standard_normal((4, 128), dtype=np.float32))
    call = partial(q40_matmul_pallas, interpret=True, w_dtype=jnp.bfloat16)
    set_dequant_mode(mode)
    try:
        alone = [np.asarray(call(x, pw)) for pw in weights]
        together = jax.jit(lambda x, ws: [call(x, pw) for pw in ws])(x, weights)
    finally:
        set_dequant_mode(None)
    for got, want in zip(together, alone):
        if mode in ("blockdot", "i8blockdot"):
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
        else:
            np.testing.assert_array_equal(np.asarray(got), want)


def test_kernel_body_trace_counters():
    """Trace-time counters: each kernel body traced in a slab chain was
    handed x as it is; the block-dot modes take the operands built for them;
    a plane is no stack read."""
    rng = np.random.default_rng(6)
    weights = [_pack(rng, d_out, 128) for d_out in (128, 256, 384)]
    x = jnp.asarray(rng.standard_normal((4, 128), dtype=np.float32))
    # the kernel bodies are traced here, whatever ran before in this process
    pq._q40_matmul_pallas_impl.clear_cache()
    reset_trace_stats()
    for pw in weights:
        q40_matmul_pallas(x, pw, interpret=True)
    # each traced kernel body (the f32 chain) was handed x as it is
    assert TRACE_STATS["impl_traces"] == 3, TRACE_STATS
    assert TRACE_STATS["natural_x_consumes"] == 3, TRACE_STATS
    assert TRACE_STATS["stacked_consumes"] == 0, TRACE_STATS
    assert TRACE_STATS["weight_passes_max"] == 1, TRACE_STATS
    # the block-dot modes take the operands built for them, not x itself
    set_dequant_mode("blockdot")
    try:
        pq._q40_matmul_pallas_impl.clear_cache()
        reset_trace_stats()
        q40_matmul_pallas(x, weights[0], interpret=True, w_dtype=jnp.bfloat16)
    finally:
        set_dequant_mode(None)
    assert TRACE_STATS["impl_traces"] == 1, TRACE_STATS
    assert TRACE_STATS["natural_x_consumes"] == 0, TRACE_STATS


def test_trace_counters_at_model_scale(tiny_model):
    """One llama_forward trace at model scale: the layer body traces once
    under lax.scan and its seven matmuls read their layer out of a stack
    (wq, wk, wv, wo, w1, w3, w2); the head's wcls is a plane. Whatever kernel
    body the trace made took x in its own order: the model has no other form
    to run, and no bundle stands between it and the kernel."""
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
        assert linear.reads_q40_stack(qparams.layers.wq)
        pq._q40_matmul_pallas_impl.clear_cache()
        reset_trace_stats()
        llama_forward(
            config, qparams, tokens, positions, init_kv_cache(config, 1)
        )
        assert TRACE_STATS["stacked_consumes"] == 7, TRACE_STATS
        # one body a distinct (rows, d_in, d_out, stacked): wq = wo, wk = wv
        # and w1 = w3 share theirs; then w2 and the head's plane
        assert 1 <= TRACE_STATS["impl_traces"] <= 8, TRACE_STATS
        assert (TRACE_STATS["natural_x_consumes"]
                == TRACE_STATS["impl_traces"]), TRACE_STATS
        assert TRACE_STATS["weight_passes_max"] == 1, TRACE_STATS
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

    assert DEQUANT_MODE in DEQUANT_MODES


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
@pytest.mark.parametrize("entry", ["raw_x", "rank3"])
@pytest.mark.parametrize("mode", DEQUANT_MODES)
def test_stacked_weight_equals_its_plane_bit_for_bit(mode, entry, how):
    """Layer ``l`` read out of the stack equals the 2-D kernel on plane ``l``
    to the bit, in every dequant mode, x two-dimensional or with leading axes
    ``[lanes, t, d_in]`` that the kernel merges, with
    ``l`` a traced scalar (an argument of a jit; the counter of a lax.scan)
    at the stack's first and last layer. Both sides are computed inside one
    traced program, so the operand builds are the same operations. (A stack
    at rest: ``test_stacked_weight_on_every_grid_axis`` and the ``in_place``
    tests below.)"""
    rng = np.random.default_rng(30)
    stack = _stack(rng, 256, 128)
    x = jnp.asarray(rng.standard_normal((4, 128), dtype=np.float32))
    kw = dict(interpret=True, w_dtype=jnp.bfloat16)

    def both(x, stack, l):
        xin = x.reshape(2, 2, 128) if entry == "rank3" else x
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
        # float16 scales are never read in place
        assert TRACE_STATS["scale_stack_reads"] == 0, TRACE_STATS
        # stack and plane alike: x itself in a slab chain, never in a
        # block-dot mode
        natural = mode not in ("blockdot", "i8blockdot")
        assert TRACE_STATS["natural_x_consumes"] == (
            TRACE_STATS["impl_traces"] if natural else 0), TRACE_STATS
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
    (300, 64, 256),    # rows above M_TILE, padded to 512
])
@pytest.mark.parametrize("scales", list(SCALE_FORMS))
def test_stacked_weight_on_every_grid_axis(m, d_in, d_out, scales):
    """The layer offset composes with each axis of the grid, for the nibbles
    and, where the scales rest as bits, for the scale tiles beside them
    (against the float16 plane's call)."""
    rng = np.random.default_rng(d_in + d_out)
    stack = _stack(rng, d_out, d_in, n=2)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    for l in (0, 1):
        got = q40_matmul_pallas(x, SCALE_FORMS[scales](stack), interpret=True, layer=l)
        want = q40_matmul_pallas(x, _plane(stack, l), interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(q40_matmul_xla(x, _plane(stack, l))),
            atol=2e-4, rtol=2e-4)


def _in_place(monkeypatch):
    """A call of the kernel's core over a stack at rest whose scale tiles are
    read IN PLACE, as a stack too large to stage is: ``reads_scales_in_place``
    says so for the trace, and the core is traced anew under a jit of its own
    (the entry's jit would serve a body traced before the patch)."""
    monkeypatch.setattr(pq, "reads_scales_in_place", lambda scales: scales.ndim == 3)

    def call(x, stack, layer, mode="v4", w_dtype=jnp.float32):
        return pq._q40_matmul_core(x, q40_at_rest(stack), True, w_dtype, mode, layer)

    return call


@pytest.mark.parametrize("how", ["jit", "scan"])
@pytest.mark.parametrize("m,d_in,d_out", [
    (4, 4096, 2048),    # n_k > 1: the k axis walks chunks of layer l's scale plane
    (2, 512, 16384),    # two wide tiles: the j axis
    (300, 64, 256),     # rows above M_TILE: the -8 subtracted, one block of 512
    (16, 2048, 1152),   # k chunks and sub tiles 512 + 512 + 128, the -8 folded
    (128, 2048, 1152),  # the same plan at the threshold: subtracted
])
def test_scale_tiles_read_in_place_equal_the_float16_planes_call(monkeypatch, m, d_in, d_out, how):
    """Layer l's scale tiles addressed inside the int16 stack by the index
    maps, ``l`` traced (a jit's argument; a scan's counter): the bit-identical
    result of the float16 plane's own call, on every grid axis, decode and
    prefill widths, fold and subtract."""
    rng = np.random.default_rng(d_in + d_out + m)
    stack = _stack(rng, d_out, d_in, n=3)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    call = _in_place(monkeypatch)
    reset_trace_stats()
    if how == "jit":
        fn = jax.jit(lambda x, s, l: call(x, s, l))
        got = {l: fn(x, stack, jnp.int32(l)) for l in (0, 2)}
    else:
        _, ys = jax.lax.scan(lambda c, l: (c, call(x, stack, l)), 0,
                             jnp.arange(3, dtype=jnp.int32))
        got = {l: ys[l] for l in (0, 2)}
    assert TRACE_STATS["scale_stack_reads"] == TRACE_STATS["impl_traces"] == 1, TRACE_STATS
    assert TRACE_STATS["scale_converts"] == 0
    for l, y in got.items():
        want = q40_matmul_pallas(x, _plane(stack, l), interpret=True)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(want), err_msg=f"layer {l}")
    assert not np.array_equal(np.asarray(got[0]), np.asarray(got[2]))


@pytest.mark.parametrize("mode", DEQUANT_MODES)
def test_scale_tiles_read_in_place_in_every_mode(monkeypatch, mode):
    """The block-dot modes' kernels take the scale operand by the same spec:
    in place out of the stack in each of the six, equal to the float16
    plane's call in that mode to the bit."""
    rng = np.random.default_rng(55)
    stack = _stack(rng, 256, 128)
    x = jnp.asarray(rng.standard_normal((4, 128), dtype=np.float32))
    call = _in_place(monkeypatch)
    set_dequant_mode(mode)
    try:
        got = jax.jit(lambda x, s, l: call(x, s, l, mode, jnp.bfloat16))(
            x, stack, jnp.int32(STACK_L - 1))
        want = q40_matmul_pallas(x, _plane(stack, STACK_L - 1), interpret=True,
                                 w_dtype=jnp.bfloat16)
    finally:
        set_dequant_mode(None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# sha256[:16] of the output bytes of the seeded call below. The two block-dot
# modes: what the PARENT of PR 30 gave (its kernel took 2-D planes only; a 2-D
# weight goes through the same pallas_call as a stack now, as the stack of one
# read at layer 0), untouched since. The slab chains: what PR 42 gives, whose
# one dot of depth 2 * rows sums the same products in another order than the
# two dots of depth rows it replaced, and whose block sums are of x as the
# dot sees it (this call hands an f32 x to a bf16 dot: the parent summed the
# unrounded f32 there; a bf16 x, as every cell's, reads the same either
# way). PR 30's parent gave 152c5bc3acc7695f for v4, 8cd3d3f237e607c8 for
# the bf16 chains, a00e1bb2e19dc500 in f32; how far the order moves a result
# is held against ``_two_dot_form`` below.
PARENT_2D_DIGESTS = {
    "f32": "4405722f4a618a94", "v4": "aab6fc6aa5b9fec8",
    "bf16chain": "b84f88086d153cd2", "repeat": "b84f88086d153cd2",
    "u8chain": "b84f88086d153cd2", "blockdot": "2e41ab0e772529f9",
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
        at_rest = np.asarray(q40_matmul_pallas(
            x, q40_at_rest(pw), interpret=True, **kw))
    finally:
        set_dequant_mode(None)
    assert hashlib.sha256(got.tobytes()).hexdigest()[:16] == PARENT_2D_DIGESTS[mode]
    np.testing.assert_array_equal(as_stack, got)
    np.testing.assert_array_equal(at_rest, got)  # the plane's scales as bits
    if mode in ("f32", "v4"):
        # the same products as the two-dot form, summed in another order
        want = np.asarray(_two_dot_form(
            x, pw, jnp.float32 if mode == "f32" else jnp.bfloat16))
        np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max(),
                                   rtol=0)


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


# ---------------------------------------------------------------------------
# The slab chains take x as it is (PR 42): its own column order, its own
# dtype, one BlockSpec. The kernel puts the dequantised nibble planes back in
# the input's order by whole 16-row tiles, multiplies in one dot and sums x's
# quant blocks itself. Before, the operand build split x's lane axis into
# [n_blk, 2, 16] in XLA ahead of every distinct input.
# ---------------------------------------------------------------------------

from distributed_llama_multiusers_tpu.ops import pallas_q40 as pq  # noqa: E402


def _two_dot_form(x, pw, w_dtype, sums_of=None):
    """What the kernel computed before PR 42, in plain jax.numpy on a whole
    plane: the pre-split halves of x against the low and the high nibble
    plane in two dots, the folded -8 against exact f32 block sums.
    ``sums_of``: the array whose blocks are summed. By default x as the dots
    see it, rounded to ``w_dtype``: what a bf16 x gave then and gives now.
    The parent summed the f32 it was handed (``sums_of=x`` for an f32 x,
    or the f32 a fused convert pair let through: see the norm-then-cast
    test)."""
    m, d_in = x.shape
    n_blk, half = d_in // 32, d_in // 2
    xf = x.astype(jnp.float32)
    xb = xf.reshape(m, n_blk, 2, 16)
    x_lo = xb[:, :, 0, :].reshape(m, half).astype(w_dtype)
    x_hi = xb[:, :, 1, :].reshape(m, half).astype(w_dtype)
    if sums_of is None:
        sums_of = x.astype(w_dtype)
    bsum = sums_of.astype(jnp.float32).reshape(m, n_blk, 32).sum(axis=2)
    p = pw.packed.astype(jnp.int32)
    s = pw.scales.astype(jnp.float32)
    planes = [
        (nib.astype(jnp.float32).reshape(n_blk, 16, -1) * s[:, None, :])
        .reshape(half, -1).astype(w_dtype)
        for nib in (p & 0x0F, p >> 4)
    ]
    dot = partial(jnp.dot, preferred_element_type=jnp.float32,
                  precision="highest")
    y = dot(x_lo, planes[0]) + dot(x_hi, planes[1]) - 8.0 * dot(bsum, s)
    return y.astype(x.dtype)


# every plan the cells' shapes take, at sizes interpret mode can carry
NATURAL_SHAPES = [
    # m = 8 (DeepSeek's decode width), 112 blocks (3584 / 32), one slab
    (8, 3584, 256),
    # m = 16, 112 blocks in two reduction chunks of 56 (rows 896)
    (16, 3584, 1024),
    # m = 32, 128 blocks in chunks, the f32 accumulator
    (32, 4096, 2048),
    # m = 64, two wide tiles
    (64, 512, 16384),
    # one whole m tile; 43 blocks
    (256, 1376, 128),
    # above M_TILE (one block of 512 rows), and rows that need padding
    (300, 64, 256),
    # rows that need padding under either dtype; 448 blocks (14336 / 32)
    (5, 14336, 128),
]


@pytest.mark.parametrize("weight", ["plane", "stack"])
@pytest.mark.parametrize("entry", ["raw_x", "rank3"])
@pytest.mark.parametrize("m,d_in,d_out", NATURAL_SHAPES)
def test_natural_operand_matches_xla_and_the_two_dot_form(m, d_in, d_out,
                                                          entry, weight):
    """The kernel handed x itself, in exact f32: against the XLA dequant to
    the tolerance this file has always had, against the two-dot form (the
    same products in another order) closer, and the four ways in (x in two
    dimensions or as ``[lanes, t, d_in]``, a plane or a layer of a stack under
    a traced index) equal to the bit."""
    rng = np.random.default_rng(d_in + d_out + m)
    stack = _stack(rng, d_out, d_in, n=2)
    pw = _plane(stack, 1)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))

    @jax.jit
    def run(x, stack, l):
        lanes = 2 if m % 2 == 0 else 1
        xin = x.reshape(lanes, m // lanes, d_in) if entry == "rank3" else x
        if weight == "stack":
            y = q40_matmul_pallas(xin, stack, interpret=True, layer=l)
        else:
            y = q40_matmul_pallas(xin, _plane(stack, 1), interpret=True)
        assert y.shape == xin.shape[:-1] + (d_out,)
        return y.reshape(m, d_out)

    got = np.asarray(run(x, stack, jnp.int32(1)))
    np.testing.assert_allclose(
        got, np.asarray(q40_matmul_xla(x, pw)), atol=2e-4, rtol=2e-4)
    want = np.asarray(_two_dot_form(x, pw, jnp.float32))
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max(),
                               rtol=0)
    base = np.asarray(q40_matmul_pallas(x, pw, interpret=True))
    np.testing.assert_array_equal(got, base)


@pytest.mark.parametrize("mode", ["v4", "bf16chain", "repeat", "u8chain"])
@pytest.mark.parametrize("m,d_in,d_out", [
    (8, 3584, 256), (16, 3584, 1024), (32, 512, 1024), (300, 64, 256)])
def test_natural_operand_in_bf16_as_the_cells_run_it(m, d_in, d_out, mode):
    """x in bf16 under the bf16 dot, every slab chain: rows padded to whole
    16-row tiles, the block sums a bf16 dot with f32 accumulation (every
    product exact). Against the two-dot form in the same precision the
    result differs by the summation order and the output's one rounding to
    bf16; v4 dequantises exactly as that form does, the bf16 chains also
    round the scale."""
    rng = np.random.default_rng(d_in + d_out + m)
    pw = _pack(rng, d_out, d_in)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32)
                    ).astype(jnp.bfloat16)
    set_dequant_mode(mode)
    try:
        got = q40_matmul_pallas(x, pw, interpret=True, w_dtype=jnp.bfloat16)
    finally:
        set_dequant_mode(None)
    assert got.dtype == jnp.bfloat16 and got.shape == (m, d_out)
    got = np.asarray(got, np.float32)
    want = np.asarray(_two_dot_form(x, pw, jnp.bfloat16), np.float32)
    top = np.abs(want).max()
    # one bf16 rounding of the output is 2**-8 of a value; the chains that
    # round the scale to bf16 as well stay inside this file's 2e-2 of max
    bound = 2 ** -7 if mode == "v4" else 2e-2
    assert np.abs(got - want).max() <= bound * top, (
        mode, np.abs(got - want).max() / top)


@pytest.mark.parametrize("made_by", ["norm", "gated_product"])
def test_x_rounded_once_feeds_both_terms_of_the_folded_minus_8(made_by):
    """x as the model makes it in f32 and casts to bf16: an RMS norm, and the
    FFN's gated product silu(a) * b of two bf16 arrays. The kernel handed the
    bf16 x agrees with the two-dot form on that x to the order of a sum and
    the output's one rounding. The parent's compiled decode step summed the
    blocks of the UNROUNDED product at w2's input (XLA removed the f32 ->
    bf16 -> f32 pair after the multiply: allow_excess_precision; the four
    other inputs of a layer were rounded, PERF.md section 6, PR 42), while
    its dots saw the rounded one; that form (``sums_of`` the f32) is the
    farther of the two from the f32 result, by the 8 * s * sum(x - bf16(x))
    the two terms then disagree by."""
    rng = np.random.default_rng(42)
    m, d_in, d_out = 16, 3584, 1024
    pw = _pack(rng, d_out, d_in)
    as_bf16 = lambda a: jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32)
    a = as_bf16(rng.standard_normal((m, d_in), dtype=np.float32))
    if made_by == "norm":
        g = jnp.asarray(1 + 0.1 * rng.standard_normal(d_in, dtype=np.float32))
        xf = a * jax.lax.rsqrt((a * a).mean(-1, keepdims=True) + 1e-5) * g
    else:
        b = as_bf16(rng.standard_normal((m, d_in), dtype=np.float32))
        xf = as_bf16(jax.nn.silu(a)) * b
    xb = xf.astype(jnp.bfloat16)
    exact = np.asarray(q40_matmul_xla(xf, pw), np.float32)
    rel = lambda y: (np.linalg.norm(np.asarray(y, np.float32) - exact)
                     / np.linalg.norm(exact))

    got = q40_matmul_pallas(xb, pw, interpret=True, w_dtype=jnp.bfloat16)
    same_x = _two_dot_form(xb, pw, jnp.bfloat16)
    sums_unrounded = _two_dot_form(xb, pw, jnp.bfloat16, sums_of=xf)
    gap = np.abs(np.asarray(got, np.float32) - np.asarray(same_x, np.float32))
    assert gap.max() <= 2 ** -7 * np.abs(exact).max()
    # 0.0048 against 0.0062 on this seed, either way x was made
    assert rel(got) < 0.9 * rel(sums_unrounded), (rel(got), rel(sums_unrounded))
    assert abs(rel(got) - rel(same_x)) < 0.01 * rel(same_x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,n", [(8, 512), (16, 3584), (5, 14336), (256, 64),
                                 (16, 7168), (8, 2048)])
def test_block_sums_equal_the_reshaped_sum(m, n, dtype):
    """The kernel's block sums (a dot against a 0/1 matrix: no lane of x is
    split) against ``x.reshape(m, n_blk, 32).sum(-1)`` in f32: the same 32
    numbers summed in f32 either way, so equal to the order of a sum."""
    rng = np.random.default_rng(m + n)
    x = jnp.asarray(rng.standard_normal((m, n), dtype=np.float32)).astype(dtype)
    pieces = pq._block_sums(x)
    # one 0/1 matrix of at most BSUM_SLICE columns, whatever the chunk's width
    assert len(pieces) == n // pq._sum_slice(n) and pq._sum_slice(n) <= 2048
    got = np.concatenate([np.asarray(p) for p in pieces], axis=1)
    assert got.dtype == np.float32 and got.shape == (m, n // 32)
    want = np.asarray(x.astype(jnp.float32).reshape(m, n // 32, 32).sum(-1))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("m,dtype,want", [
    (1, jnp.float32, (8, 8)), (8, jnp.float32, (8, 8)),
    (8, jnp.bfloat16, (16, 16)), (16, jnp.bfloat16, (16, 16)),
    (20, jnp.bfloat16, (32, 32)), (64, jnp.bfloat16, (64, 64)),
    (300, jnp.float32, (512, 256)), (300, jnp.bfloat16, (512, 256)),
    (1024, jnp.bfloat16, (1024, 256)),
])
def test_rows_pad_to_whole_tiles_of_their_dtype(m, dtype, want):
    """A bf16 block wants whole 16-row tiles (two rows a sublane), an f32
    one 8: decided by the input's dtype and static row count, nothing else."""
    assert pq._m_geometry(m, dtype) == want
    x = jnp.zeros((m, 64), dtype)
    assert pq._padded_rows(x).shape == (want[0], 64)
    assert pq._padded_rows(x).dtype == dtype


# --- the lowered-program witness -------------------------------------------

import re  # noqa: E402

WITNESS_SCOPES = ("dl.ffn", "dl.qkv", "dl.attn_out")


def _arrays_under(jaxpr, scopes, prefix=""):
    """(scope path, shape) of every array an equation makes under one of
    ``scopes``, through scans, jits and conditionals, NOT into a kernel: what
    a Pallas kernel does inside is not an XLA operation."""
    found = []
    for eqn in jaxpr.eqns:
        path = f"{prefix}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            continue
        if any(s in path for s in scopes):
            found += [(path, tuple(v.aval.shape)) for v in eqn.outvars
                      if hasattr(v.aval, "shape")]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _arrays_under(sub, scopes, path)
    return found


def _lane_splits(found):
    """Arrays of rank 3 and up whose last axis is 16: a quant block's half,
    split off the lane axis."""
    return sorted({shape for _, shape in found
                   if len(shape) >= 3 and shape[-1] == 16})


@pytest.fixture(scope="module")
def witness_engine(tmp_path_factory):
    """A two-layer quantised model no dimension of which is 16 (64-wide
    heads, 128 / 256-wide matmul inputs: 4 and 8 quant blocks), served by a
    real engine with the kernel in interpret mode."""
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

    path = str(tmp_path_factory.mktemp("witness") / "w.m")
    write_synthetic_model(path, tiny_header(
        dim=128, hidden_dim=256, n_heads=2, n_kv_heads=1, seq_len=32), seed=5)
    h = load_model_header(path)
    config, qparams = load_params_from_m_quantized(path, h, dtype=jnp.bfloat16)
    linear.set_pallas_interpret(True)
    linear.set_pallas_w_dtype(jnp.bfloat16)
    try:
        yield InferenceEngine(config, qparams, n_lanes=2, prefill_buckets=(8,))
    finally:
        linear.set_pallas_w_dtype(None)
        linear.set_pallas_interpret(False)


def _decode_step_forms(engine):
    """(StableHLO text, jaxpr) of the pipelined decode step program as the
    engine's own entry point dispatches it."""
    fn, seen = engine._decode_pl_fn, []

    def spy(*args, **kw):
        seen.append((fn.lower(*args, **kw).as_text(),
                     fn.trace(*args, **kw).jaxpr))
        return fn(*args, **kw)

    engine._decode_pl_fn = spy
    try:
        z = np.zeros(engine.n_lanes, np.int32)
        engine.decode_pipelined(z, tokens=z)
        engine.pipeline_flush()
    finally:
        engine._decode_pl_fn = fn
    assert seen, "the decode step program was not dispatched"
    return seen[0]


# an activation [rows, d_in] seen as [rows, d_in / 32, 2, 16]
SPLIT_RESHAPE = re.compile(r"tensor<\d+x\d+x2x16x(?:f32|bf16)>")


@pytest.mark.parametrize("mode,splits", [
    ("v4", False), ("bf16chain", False), ("repeat", False), ("u8chain", False),
    ("blockdot", True),
])
def test_decode_step_program_splits_no_activation_lane(witness_engine, mode,
                                                       splits):
    """There is no fallback whose hits could be counted, so the witness is
    the program: the lowered decode step of a quantised model holds no
    reshape of an activation to [.., d_in / 32, 2, 16] and, outside the
    kernels, no array whose last axis is 16 under the three scopes the dense
    Q40 matmuls run in. The control is the mode that still takes pre-split
    operands (blockdot), in which the same search finds both."""
    set_dequant_mode(mode)
    try:
        reset_trace_stats()
        jax.clear_caches()  # the step program is traced anew under this mode
        text, jaxpr = _decode_step_forms(witness_engine)
    finally:
        set_dequant_mode(None)
        jax.clear_caches()
    found = _arrays_under(jaxpr, WITNESS_SCOPES)
    assert any("dl.ffn" in p for p, _ in found), "no dl.ffn scope in the program"
    # the loader's tree is float16 and the engine leaves stacks this small so
    # (``reads_scales_in_place``): every kernel body of the step, in any mode,
    # was fed by a plane sliced out and converted, and says so
    assert TRACE_STATS["scale_converts"] == TRACE_STATS["impl_traces"], TRACE_STATS
    assert TRACE_STATS["scale_stack_reads"] == 0, TRACE_STATS
    if splits:
        assert SPLIT_RESHAPE.search(text)
        assert _lane_splits(found), found
        assert TRACE_STATS["natural_x_consumes"] == 0, TRACE_STATS
    else:
        assert not SPLIT_RESHAPE.search(text), SPLIT_RESHAPE.findall(text)
        assert _lane_splits(found) == [], _lane_splits(found)
        assert TRACE_STATS["natural_x_consumes"] == TRACE_STATS["impl_traces"] > 0


def test_the_witness_finds_the_split_the_kernel_took_before():
    """Control on the preparation itself: the operands every chain took
    before PR 42, and the block-dot modes still take, are built by that
    split; lowered alone under a scope it shows what both searches look for,
    and the rows the kernel pads (``_padded_rows``) hold none of it."""
    def prep(x):
        with jax.named_scope("dl.ffn"):
            return pq._block_dot_operands(pq._padded_rows(x), "blockdot")

    x = jax.ShapeDtypeStruct((16, 256), jnp.bfloat16)
    assert SPLIT_RESHAPE.search(jax.jit(prep).lower(x).as_text())
    found = _arrays_under(jax.make_jaxpr(prep)(x).jaxpr, WITNESS_SCOPES)
    assert (16, 8, 16) in _lane_splits(found), found


# ---------------------------------------------------------------------------
# PR 45: a weight slab is fetched and dequantised once for a BLOCK of rows,
# and the block is the call's rows up to M_BLOCK_MAX. A row's result does not
# depend on which other rows share its block: the same chain, the same dots
# over the same k chunks in the same order.
# ---------------------------------------------------------------------------

ROW_BLOCK_PLANS = {
    # (d_in, d_out): one slab (no k axis), several k chunks through the f32
    # accumulator (1024 x 1152 packed bytes: two chunks of 512 rows, sub
    # tiles 512 + 512 + 128), two wide tiles of 8192
    "one_slab": (64, 256),
    "k_chunks": (2048, 1152),
    "two_wide_tiles": (64, 16384),
}


@pytest.mark.parametrize("weight", ["plane", "stack"])
@pytest.mark.parametrize("plan", list(ROW_BLOCK_PLANS))
@pytest.mark.parametrize("m", [300, 512, 1024, 1300])
def test_row_blocks_do_not_change_a_rows_result(m, plan, weight):
    """The call's output equals, to the bit in interpret-mode f32, the
    outputs of the same rows sent 256 at a time (calls of M_TILE rows or
    fewer: the grid and the blocks they always had), and the XLA dequant to
    this file's tolerance."""
    d_in, d_out = ROW_BLOCK_PLANS[plan]
    w_tile, rows = pq._plan_blocks(d_in, d_out)
    assert ((d_in // 2) // rows, d_out // w_tile) == {
        "one_slab": (1, 1), "k_chunks": (2, 1), "two_wide_tiles": (1, 2)}[plan]
    rng = np.random.default_rng(m + d_in + d_out)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    if weight == "stack":
        w = _stack(rng, d_out, d_in, n=2)
        kw, plane = dict(interpret=True, layer=1), _plane(w, 1)
    else:
        w = plane = _pack(rng, d_out, d_in)
        kw = dict(interpret=True)
    got = np.asarray(q40_matmul_pallas(x, w, **kw))
    # the plan this call traced under (the trace itself may be another
    # test's, so the counter is not read here): one block up to 1024 rows
    m_pad, _ = pq._m_geometry(m, x.dtype)
    m_block, _ = pq._row_plan(m_pad, w_tile, rows, (d_in // 2) // rows, 4)
    assert m_pad // m_block == (1 if m <= 1024 else 2), (m_pad, m_block)
    # whole 256-row tiles, as the parent's grid cut the padded rows (XLA:CPU
    # sums a dot of 44 rows in another order than one of 256: the tail is
    # padded here as the kernel pads it)
    x_tiles = jnp.pad(x, ((0, -m % pq.M_TILE), (0, 0)))
    by_tile = np.concatenate([
        np.asarray(q40_matmul_pallas(x_tiles[r:r + pq.M_TILE], w, **kw))
        for r in range(0, m, pq.M_TILE)])[:m]
    np.testing.assert_array_equal(got, by_tile)
    np.testing.assert_allclose(got, np.asarray(q40_matmul_xla(x, plane)),
                               atol=2e-4, rtol=2e-4)


def _parent_m_pad(m, itemsize):
    """x rows as PR 44 padded them: whole sublane tiles, whole 256-row tiles
    above 256."""
    align = 8 * max(1, 4 // itemsize)
    m_pad = max(align, -(-m // align) * align)
    return m_pad if m_pad <= 256 else -(-m_pad // 256) * 256


# (d_in, d_out) of the benchmark's dense cells and of every 8192-wide tile in
# its six configurations (the heads, Jamba's MLP, DeepSeek's wide projections)
PLAN_SHAPES = [(4096, 14336), (14336, 4096), (4096, 1024), (3584, 18944),
               (18944, 3584), (4096, 32768), (3584, 152064), (2560, 8192),
               (2560, 65536), (1536, 24576), (7168, 128)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("d_in,d_out", PLAN_SHAPES)
def test_plan_from_shapes_one_pass_up_to_1024_rows(d_in, d_out, dtype):
    """From shapes alone: the padding is what it was for every m; every call
    of up to 256 rows keeps the grid, the wide tile and the VMEM ceiling it
    had; every call of up to 1024 rows is ONE block of rows, one pass over
    the plane, its pipelined blocks inside the 64 MiB the kernel always
    asked for (an 8192-wide tile narrows to 4096 at 1024 rows; f32 rows
    narrow a 7168-wide one too); longer calls are cut into equal blocks of
    whole 256-row tiles."""
    itemsize = jnp.dtype(dtype).itemsize
    w_plan, rows = pq._plan_blocks(d_in, d_out)
    n_k = (d_in // 2) // rows
    for m in (1, 7, 16, 32, 200, 256, 257, 300, 512, 777, 1000, 1024, 1025,
              1300, 2048, 2560, 4096):
        m_pad, _ = pq._m_geometry(m, dtype)
        assert m_pad == _parent_m_pad(m, itemsize), m
        m_block, w_tile = pq._row_plan(m_pad, w_plan, rows, n_k, itemsize)
        need = pq._block_bytes(m_block, w_tile, rows, n_k, itemsize)
        assert m_pad % m_block == 0 and m_block <= pq.M_BLOCK_MAX, (m, m_block)
        assert w_plan % w_tile == 0 and w_tile % 128 == 0
        assert w_tile == w_plan or w_tile >= pq.MIN_W_TILE
        limit = pq._vmem_limit(need)
        assert pq.VMEM_LIMIT_BYTES <= limit <= 80 << 20 < 128 << 20
        if m <= 256:
            # the parent's m tile, wide tile and compiler parameters
            assert (m_block, w_tile) == (min(256, m_pad), w_plan)
            assert limit == pq.VMEM_LIMIT_BYTES
        else:
            assert need <= pq.VMEM_LIMIT_BYTES, (m, m_block, w_tile)
            assert m_block % 256 == 0
            if m <= 1024:
                assert m_block == m_pad, (m, m_block)  # one pass
    at_1024 = pq._row_plan(1024, w_plan, rows, n_k, itemsize)
    if dtype == jnp.bfloat16:
        # what the cells send: only the 8192-wide tile gives way, by halving
        assert at_1024 == (1024, 4096 if w_plan == 8192 else w_plan)
        # 1300 rows: 1536 padded as before, two blocks of 768 (not 6 of 256)
        assert pq._row_plan(1536, w_plan, rows, n_k, 2)[0] == 768


def test_a_narrowed_wide_tile_does_not_change_a_result():
    """Jamba's MLP shape at a depth CPU interpret mode can afford: the plan
    is one 8192-wide tile, which 1024 rows meet as two tiles of 4096 with
    the k chunks as planned: every element sums the same products in the
    same order, so the call equals the same rows sent 256 at a time
    (against the 8192-wide tile) to the bit."""
    d_in, d_out, m = 512, 8192, 1024
    w_plan, rows = pq._plan_blocks(d_in, d_out)
    n_k = (d_in // 2) // rows
    assert (w_plan, n_k) == (8192, 2)
    assert pq._row_plan(m, w_plan, rows, n_k, 4) == (1024, 4096)
    assert pq._row_plan(256, w_plan, rows, n_k, 4) == (256, 8192)
    rng = np.random.default_rng(45)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    w = _pack(rng, d_out, d_in)
    got = np.asarray(q40_matmul_pallas(x, w, interpret=True))
    by_tile = np.concatenate([
        np.asarray(q40_matmul_pallas(x[r:r + 256], w, interpret=True))
        for r in range(0, m, 256)])
    np.testing.assert_array_equal(got, by_tile)
    np.testing.assert_allclose(got, np.asarray(q40_matmul_xla(x, w)),
                               atol=2e-4, rtol=2e-4)


def _trace_w1_call(m, mode="v4", scales=jnp.float16, layers=None):
    """Trace (nothing runs) a bf16 call of ``m`` rows at Mistral's w1: the
    plane, or a layer of a stack of ``layers``."""
    lead = () if layers is None else (layers,)
    w = PackedQ40(packed=jax.ShapeDtypeStruct(lead + (2048, 14336), jnp.uint8),
                  scales=jax.ShapeDtypeStruct(lead + (128, 14336), scales))
    x = jax.ShapeDtypeStruct((m, 4096), jnp.bfloat16)
    layer = None if layers is None else jax.ShapeDtypeStruct((), jnp.int32)
    jax.eval_shape(lambda x, w, l: pq._q40_matmul_core(
        x, w, True, jnp.bfloat16, mode, l), x, w, layer)


def test_weight_passes_witness_in_trace_stats_and_path_facts(witness_engine):
    """After tracing a 1024-row and a 16-row call the witness reads 1 (the
    parent's plan made 4 passes at 1024 rows: ``m_pad // 256``), and the
    engine's start-up facts carry it; a call past M_BLOCK_MAX says so."""
    trace = _trace_w1_call
    reset_trace_stats()
    assert witness_engine.path_facts()["q40_weight_passes"] == 0  # none traced
    trace(1024)
    trace(16)
    assert TRACE_STATS["weight_passes_max"] == 1, TRACE_STATS
    assert witness_engine.path_facts()["q40_weight_passes"] == 1
    trace(4096)
    assert TRACE_STATS["weight_passes_max"] == 4
    assert witness_engine.path_facts()["q40_weight_passes"] == 4
    reset_trace_stats()


# ---------------------------------------------------------------------------
# PR 49: where the nibbles' -8 goes is read off the block of rows. A block of
# SUBTRACT_MIN_ROWS rows and more takes it off the nibbles in the dequant
# chain and traces neither the block sums nor the correction dot; a smaller
# block traces the body it always did.
# ---------------------------------------------------------------------------

T = pq.SUBTRACT_MIN_ROWS
# (d_in, d_out) small enough for interpret mode, one of every class of plan
# the cells' shapes have: (k chunks?, wide tiles?, block-sum slices?)
OFFSET_PLANS = {
    "one_slab": (64, 256),
    "k_chunks": (2048, 1152),          # sub tiles 512 + 512 + 128
    "two_wide_tiles": (64, 16384),
    "k_chunks_and_wide_tiles": (512, 16384),
    "block_sum_slices": (7168, 128),   # the whole half one chunk: 4 slices
    "k_chunks_of_slices": (7168, 576),  # DeepSeek's wkva: two chunks of two
    # a head whose width only 128 divides (MiMo's vocabulary slice, 19072 =
    # 149 x 128, which padding to 8192s would grow by 29 %): 67 tiles here
    "wide_tiles_of_slices": (2304, 8576),
}


def _plan_class(d_in, d_out):
    w_tile, rows = pq._plan_blocks(d_in, d_out)
    return ((d_in // 2) // rows > 1, d_out // w_tile > 1,
            (2 * rows) // pq._sum_slice(2 * rows) > 1)


def test_offset_plans_cover_every_cell_shapes_plan():
    from chip_compile_util import CELL_SHAPES

    tested = {_plan_class(*shape) for shape in OFFSET_PLANS.values()}
    assert len(tested) == len(OFFSET_PLANS)
    assert {_plan_class(d_in, d_out) for d_in, d_out, _ in CELL_SHAPES} <= tested


def _kernel_dots(m, d_in, d_out, mode="v4", w_dtype=jnp.float32):
    """dot_general equations in the kernel body a call of ``m`` rows traces."""
    w = PackedQ40(packed=jax.ShapeDtypeStruct((d_in // 2, d_out), jnp.uint8),
                  scales=jax.ShapeDtypeStruct((d_in // 32, d_out), jnp.float16))
    x = jax.ShapeDtypeStruct((m, d_in), w_dtype)
    jaxpr = jax.make_jaxpr(lambda x, w: pq._q40_matmul_core(
        x, w, True, w_dtype, mode))(x, w)
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    return str(call.params["jaxpr"]).count("dot_general")


@pytest.mark.parametrize("plan", list(OFFSET_PLANS))
@pytest.mark.parametrize("m", [T - 8, T, T + 8], ids=["under", "at", "over"])
def test_offset_form_either_side_of_the_threshold(m, plan):
    """Both forms against the XLA dequant, at every class of plan the cells
    have; the witness counts the bodies without a correction dot and only
    those; the subtracting body holds one dot a sub-tile, the folding one two
    and a dot a block-sum slice."""
    d_in, d_out = OFFSET_PLANS[plan]
    rng = np.random.default_rng(49 + d_in + d_out)
    w = _pack(rng, d_out, d_in)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    pq._q40_matmul_pallas_impl.clear_cache()
    reset_trace_stats()
    got = np.asarray(q40_matmul_pallas(x, w, interpret=True))
    assert TRACE_STATS["impl_traces"] == 1, TRACE_STATS
    assert TRACE_STATS["offset_subtracted_traces"] == (m >= T), TRACE_STATS
    np.testing.assert_allclose(got, np.asarray(q40_matmul_xla(x, w)),
                               atol=2e-4, rtol=2e-4)
    w_tile, rows = pq._plan_blocks(d_in, d_out)
    m_block, w_tile = pq._row_plan(pq._m_geometry(m, x.dtype)[0], w_tile, rows,
                                   (d_in // 2) // rows, 4)
    n_sub = len(pq._sub_tiles(w_tile))
    slices = (2 * rows) // pq._sum_slice(2 * rows)
    assert _kernel_dots(m, d_in, d_out) == (
        n_sub if m >= T else slices + n_sub * (1 + slices))


@pytest.mark.parametrize("mode", ["v4", "bf16chain", "repeat", "u8chain"])
def test_offset_subtracted_in_every_slab_chain(mode):
    """The four slab chains in bf16, as the cells run v4: each takes the 8 off
    before the scale and holds one dot a sub-tile; the result is as close to
    the XLA dequant as the folded form's, and the three bf16 chains agree to
    the bit (the nibbles less 8 are exact in bf16 wherever they are taken)."""
    d_in, d_out = OFFSET_PLANS["k_chunks"]
    rng = np.random.default_rng(490)
    w = _pack(rng, d_out, d_in)
    x = jnp.asarray(rng.standard_normal((T, d_in), dtype=np.float32)).astype(jnp.bfloat16)
    want = np.asarray(q40_matmul_xla(x.astype(jnp.float32), w))
    got = {}
    for md in (mode, "bf16chain"):
        set_dequant_mode(md)
        try:
            got[md] = np.asarray(q40_matmul_pallas(
                x, w, interpret=True, w_dtype=jnp.bfloat16)).astype(np.float32)
        finally:
            set_dequant_mode(None)
    assert _kernel_dots(T, d_in, d_out, mode, jnp.bfloat16) == 3
    assert np.abs(got[mode] - want).max() <= 1e-2 * np.abs(want).max()
    if mode != "v4":
        np.testing.assert_array_equal(got[mode], got["bf16chain"])


@pytest.mark.parametrize("scales", list(SCALE_FORMS))
@pytest.mark.parametrize("m", [T - 8, T, 2 * T], ids=["under", "at", "two_tiles"])
def test_offset_form_on_a_stack_with_a_traced_layer(m, scales):
    """A layer read out of a stack under a traced index, the counter of a scan
    as the layer loop hands it, the stack's scales float16 or at rest as bits:
    equal to the float16 plane's own call to the bit on either side of the
    threshold (the -8 folded, the -8 subtracted), and to the XLA dequant."""
    d_in, d_out = OFFSET_PLANS["k_chunks"]
    rng = np.random.default_rng(m)
    stack = _stack(rng, d_out, d_in, n=2)
    served = SCALE_FORMS[scales](stack)
    x = jnp.asarray(rng.standard_normal((m, d_in), dtype=np.float32))
    _, got = jax.lax.scan(
        lambda c, l: (c, q40_matmul_pallas(x, served, interpret=True, layer=l)),
        0, jnp.arange(2, dtype=jnp.int32))
    for l in (0, 1):
        plane = _plane(stack, l)
        np.testing.assert_array_equal(
            np.asarray(got[l]), np.asarray(q40_matmul_pallas(x, plane, interpret=True)))
        np.testing.assert_allclose(np.asarray(got[l]), np.asarray(q40_matmul_xla(x, plane)),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("plan", ["k_chunks", "two_wide_tiles"])
def test_a_subtracted_rows_result_does_not_depend_on_its_block(plan):
    """Rows in a block of 2 T with other rows beside them, in a block of
    T alone, and beside different rows: the same bits each time."""
    d_in, d_out = OFFSET_PLANS[plan]
    rng = np.random.default_rng(4949)
    w = _pack(rng, d_out, d_in)
    a, b, c = (jnp.asarray(rng.standard_normal((T, d_in), dtype=np.float32))
               for _ in range(3))
    alone = np.asarray(q40_matmul_pallas(a, w, interpret=True))
    beside_b = np.asarray(q40_matmul_pallas(jnp.concatenate([a, b]), w, interpret=True))
    beside_c = np.asarray(q40_matmul_pallas(jnp.concatenate([c, a]), w, interpret=True))
    np.testing.assert_array_equal(beside_b[:T], alone)
    np.testing.assert_array_equal(beside_c[T:], alone)


def test_offset_witness_in_trace_stats_and_path_facts(witness_engine):
    """The engine's start-up facts carry the count of kernel bodies traced
    without the correction dot: 0 after decode-width calls alone, one more a
    prefill-width body."""
    trace = _trace_w1_call
    reset_trace_stats()
    for m in (8, 16, 32, 64, T - 16):
        trace(m)
    trace(16, "blockdot")
    assert TRACE_STATS["offset_subtracted_traces"] == 0, TRACE_STATS
    assert witness_engine.path_facts()["q40_offset_subtracted"] == 0
    for m in (T, 512, 1024):
        trace(m)
    assert TRACE_STATS["offset_subtracted_traces"] == 3, TRACE_STATS
    assert witness_engine.path_facts()["q40_offset_subtracted"] == 3
    reset_trace_stats()


def test_scale_stack_witness_in_trace_stats_and_path_facts(witness_engine):
    """The engine's start-up facts carry the Q40 kernel bodies traced whose
    scale tiles were read out of the weight's own int16 plane or stack in
    place and those fed by a float16 plane sliced out and converted (beside
    them here: all bodies traced), whatever the mode: a plane at rest and a 7B model's
    32-layer FFN stack are read in place, a stack XLA could stage whole has
    its plane sliced out as bits (neither count), float16 is converted."""
    def facts():
        f = witness_engine.path_facts()
        return f["q40_scales_in_stack"], f["q40_scale_converts"], TRACE_STATS["impl_traces"]

    reset_trace_stats()
    assert facts() == (0, 0, 0)
    for m in (16, T, 1024):
        _trace_w1_call(m, scales=jnp.int16)
    _trace_w1_call(16, "blockdot", scales=jnp.int16)
    assert facts() == (4, 0, 4)
    _trace_w1_call(16, layers=32, scales=jnp.int16)  # 117 MB of scales: in place
    assert facts() == (5, 0, 5)
    _trace_w1_call(16, layers=8, scales=jnp.int16)  # 29 MB: its plane sliced out
    assert facts() == (5, 0, 6)
    _trace_w1_call(16)  # float16: sliced and converted, and counted as such
    _trace_w1_call(16, layers=32)
    assert facts() == (5, 2, 8)
    reset_trace_stats()


def test_a_stack_is_read_in_place_only_where_xla_cannot_stage_it():
    """``reads_scales_in_place`` from shapes alone, in either form: the scale
    stacks of a 7B or 9B model's FFN (117-134 MB) cannot sit in fast memory
    beside the kernel's 64 MiB and are read in place (every configuration of
    the benchmark: tests/test_weight_residency.py); attention projections'
    (8-34 MB), a 9-layer 66 MB stack and every test's can, and XLA would copy
    them there whole a call (compiled for a v5e:
    tests/test_chip_compile_steps.py; what that costs on the chip: the
    predicate's docstring); a stack of one is its plane, and a plane (a
    head's) has no layer to slice out: the engine converts neither."""
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int16)
    in_place = [sds(32, 128, 14336), sds(32, 448, 4096), sds(28, 112, 18944),
                sds(32, 128, 16384), sds(32, 512, 4096)]  # the last two: MiniCPM-SALA's FFN
    sliced = [sds(32, 128, 4096), sds(32, 128, 1024), sds(28, 112, 3584),
              sds(9, 512, 7168), sds(8, 128, 16384), sds(2, 4, 256), sds(1, 592, 3584),
              sds(112, 152064)]
    f16 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float16)
    for form in (lambda s: s, f16):
        assert [pq.reads_scales_in_place(form(s)) for s in in_place] == [True] * len(in_place)
        assert [pq.reads_scales_in_place(form(s)) for s in sliced] == [False] * len(sliced)


# sha256[:16] of the output bytes of the seeded call below, 112 rows, on the
# PARENT of PR 49 (commit 0b69b2f), whose kernel folded the -8 at every row
# count
PARENT_FOLDED_DIGESTS = {"f32": "cfc4611c88a055e3", "v4": "438cc3ffae29ff8a"}


@pytest.mark.parametrize("dot", ["f32", "v4"])
def test_a_block_under_the_threshold_gives_what_the_parent_gave(dot):
    """The largest block that still folds the -8 (16 rows under
    SUBTRACT_MIN_ROWS: bf16 rows pad to whole 16-row tiles) traces the body
    the tree had before PR 49: the same bits, and no body counted as
    subtracting. (The traced programs of 16 such calls, the four slab chains
    at four cell shapes, were compared with the parent's equation by
    equation when this was written: identical.)"""
    import hashlib

    m = T - 16
    assert m == 112  # what the digests were taken at
    rng = np.random.default_rng(49)
    pw = _pack(rng, 1152, 2048)
    x = jnp.asarray(rng.standard_normal((m, 2048), dtype=np.float32))
    kw = {} if dot == "f32" else {"w_dtype": jnp.bfloat16}
    if dot == "v4":
        x = x.astype(jnp.bfloat16)
    pq._q40_matmul_pallas_impl.clear_cache()
    reset_trace_stats()
    got = np.asarray(q40_matmul_pallas(x, pw, interpret=True, **kw))
    assert TRACE_STATS["impl_traces"] == 1, TRACE_STATS
    assert TRACE_STATS["offset_subtracted_traces"] == 0, TRACE_STATS
    assert (hashlib.sha256(got.tobytes()).hexdigest()[:16]
            == PARENT_FOLDED_DIGESTS[dot])
