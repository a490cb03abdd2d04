"""Pallas Q40 matmul kernel vs the XLA fallback (interpret mode on CPU).

The reference's kernel-equivalence analogue is matmul_Q80_Q40_F32 vs
matmul_F32 (src/nn/nn-cpu-ops-test.cpp:220-241); here the Pallas kernel and
q40_matmul_xla dequantize identically, so results must agree to float
rounding, not a quantization tolerance.

Split by subject since PR 58 (same cases, same names; the file was the
longest of tier-1 on one worker): stacked weights and the natural operand are
tests/test_pallas_q40_stacks.py, the lowered-program witness, row blocks and
the offset forms tests/test_pallas_q40_rows.py.
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

from distributed_llama_multiusers_tpu.ops import pallas_q40 as pq  # noqa: E402
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
