"""Ahead-of-time compiles for a DESCRIBED TPU v5e (no chip attached): what the
``tests/test_chip_compile_*.py`` files share.

Interpret mode proves a Pallas kernel's arithmetic; only the chip's own
compiler (Mosaic, inside libtpu, which is installed here) proves the kernel
exists on the chip. PR 21 found three of the six dequant chains refused by
it while every interpret-mode test was green — an 8-bit-lane shift in
u8chain / i8blockdot, a gather in blockdot — and every chain refused at
prefill widths (a 256-row m tile against an 8192-wide slab overran the
default scoped-VMEM limit). These compiles guard every later PR at no chip
time: each mode `--dequant` offers must compile at the matmul shapes of
Llama-3.2-1B and Llama-3.1-8B, and at every shape the benchmark's cells hold.
A compile that passes is not a chip run — numerics on the chip are
chip_smoke.py's kernel phase.

One file was ten minutes on one xdist worker (``--dist loadfile`` keeps a
file together); since PR 46 the cases are split by kernel and row class into
sixteen files (the cases and their names are the same) of 11 to 210 s of worker
time each in a whole run under six workers (PR 58's reading: eleven of them over
a minute, 1200-1630 s together, a quarter of tier-1; a compile cannot be shared):

  test_chip_compile_q40_decode.py     the dense Q40 kernel, decode-width rows
  test_chip_compile_q40_prefill.py    ... at 1024 rows, the 1B / 8B shapes, planes
  test_chip_compile_q40_prefill_stacked.py   ... the same rows, a layer of a stack
  test_chip_compile_q40_rows256.py    ... one block of 256 rows, every cell's shapes
  test_chip_compile_q40_rows512.py    ... one block of 512 rows, every cell's stacks
  test_chip_compile_q40_rows1024.py   ... one block of 1024 rows, every cell's stacks
  test_chip_compile_q40_heads.py      ... 512 and 1024 rows, every cell's head
  test_chip_compile_consumers.py      consumers of one input: one pad, a call each
  test_chip_compile_grouped.py        the grouped expert kernel
  test_chip_compile_attention.py      decode attention in place
  test_chip_compile_sampler.py        the sampler's search
  test_chip_compile_steps.py          whole decode forwards and chunks

Each importing file names the fixtures it uses (``v5e``, ``v5e_devices``) in
its import: pytest finds a fixture in the module that asks for it.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep libtpu's logs out of /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_llama_multiusers_tpu.ops import linear, pallas_q40 as pq
from distributed_llama_multiusers_tpu.quants.packed import PackedQ40

# (d_in, d_out): 1B wq/wo, wk/wv, w1/w3, w2, wcls (vocab padded to the wide
# tile); 8B w1/w3, w2
SHAPES = [
    (2048, 2048), (2048, 512), (2048, 8192), (8192, 2048), (2048, 131072),
    (4096, 14336), (14336, 4096),
]
# one single-chunk plan (direct write, 64 unrolled quant blocks) and one
# multi-chunk, two-wide-tile plan (the f32 accumulator path)
TWO_SHAPES = [(2048, 512), (4096, 14336)]
DEFAULT_MODE = "v4"
OTHER_MODES = [m for m in pq.DEQUANT_MODES if m != DEFAULT_MODE]


@pytest.fixture(scope="module")
def v5e_devices():
    """The four described chips of a v5e 2x2 host, persistent compile cache
    off around the module: an AOT executable is written to the cache but
    cannot be read back without a chip, and the next compile would warn
    about it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e topology: {type(e).__name__}: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield list(topo.devices)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e(v5e_devices):
    return SingleDeviceSharding(v5e_devices[0])


def _routed_mode(mode: str, m: int) -> str:
    """The kernel mode q40_matmul_pallas would hand its jitted entry."""
    if mode in pq.BLOCK_DOT_MODES and m > pq.BLOCKDOT_MAX_M:
        mode = "bf16chain"
    return mode


# the form the engine makes, where it takes its weights, of a scale stack the
# kernel then reads in place (quants/packed.py ``q40_at_rest``): the int16 bits
# of the float16 values. Every other leaf stays as it arrived, float16 from the
# loaders and the benchmark's generator: ``engine_scales`` says which
AT_REST = jnp.int16


def engine_scales(shape):
    """The dtype an engine holds a scale plane or stack of ``shape`` in, built
    from a tree that arrives with float16 scales (a loader's, the
    benchmark's): ``InferenceEngine.__init__``'s own rule."""
    held = jax.ShapeDtypeStruct(shape, jnp.float16)
    return AT_REST if pq.reads_scales_in_place(held) else jnp.float16


def _compile(sharding, mode: str, d_in: int, d_out: int, m: int,
             x_dtype=jnp.bfloat16, w_dtype=jnp.bfloat16, scales=jnp.float16) -> str:
    """Compile the bf16-dot kernel the way q40_matmul_pallas routes it and
    return the optimized HLO text."""
    x = jax.ShapeDtypeStruct((m, d_in), x_dtype, sharding=sharding)
    w = PackedQ40(
        packed=jax.ShapeDtypeStruct((d_in // 2, d_out), jnp.uint8,
                                    sharding=sharding),
        scales=jax.ShapeDtypeStruct((d_in // 32, d_out), scales,
                                    sharding=sharding),
    )
    return pq._q40_matmul_pallas_impl.lower(
        x, w, interpret=False, w_dtype=w_dtype,
        mode=_routed_mode(mode, m),
    ).compile().as_text()


def _lane_splits(hlo: str) -> list[str]:
    """Arrays `[rows, n_blk, 16]` / `[rows, n_blk, 2, 16]` in the compiled
    program: an activation's lane axis split into quant-block halves, which
    XLA:TPU does by a physical relayout. Since PR 42 the slab chains take x
    as it is and no program of theirs makes one."""
    import re

    return sorted(set(re.findall(r"(?:f32|bf16)\[\d+,\d+,(?:2,)?16\]", hlo)))


def _is_slab_chain(mode: str, m: int) -> bool:
    return _routed_mode(mode, m) not in pq.BLOCK_DOT_MODES


# Stacked weights (PR 30): the kernel reads layer l's tiles out of a [L, ...]
# stack by a scalar-prefetch index. (d_in, d_out, decode m) of the seven
# planes of a layer at the benchmark's two configurations: Mistral-7B
# (16 lanes) wq/wo, wk/wv, w1/w3, w2; Qwen2.5-7B (32 lanes) the same
STACK_SHAPES = [
    (4096, 4096, 16), (4096, 1024, 16), (4096, 14336, 16), (14336, 4096, 16),
    (3584, 3584, 32), (3584, 512, 32), (3584, 18944, 32), (18944, 3584, 32),
]
STACK_LAYERS = 4


def _compile_stacked(sharding, mode: str, d_in: int, d_out: int, m: int,
                     scales=jnp.float16) -> str:
    """As `_compile`, the weight a stack and the layer a traced scalar (a
    stack of ``STACK_LAYERS`` is one an engine leaves float16)."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    x = sds((m, d_in), jnp.bfloat16)
    w = PackedQ40(
        packed=sds((STACK_LAYERS, d_in // 2, d_out), jnp.uint8),
        scales=sds((STACK_LAYERS, d_in // 32, d_out), scales),
    )
    return pq._q40_matmul_pallas_impl.lower(
        x, w, interpret=False, w_dtype=jnp.bfloat16,
        mode=_routed_mode(mode, m), layer=sds((), jnp.int32),
    ).compile().as_text()


def _scales_stack_converted_whole(hlo: str, d_in: int, d_out: int) -> bool:
    """Whether the program MAKES the whole stack's scale bit patterns (a
    parameter that arrives so, at rest, is not made; nor is a stack as small
    as these four layers that XLA stages in fast memory by a copy-start /
    copy-done of its own): for XLA:TPU f16 -> s16 is a pass over the data, so
    for a float16 stack the kernel converts one layer's slice
    (ops/pallas_q40.py); hoisted out of a layer loop, the stack's conversion
    was 441 MB of temporaries at 7B widths."""
    import re

    return bool(re.search(
        rf"= s16\[{STACK_LAYERS},{d_in // 32},{d_out}\]\S* (?!parameter\(|copy-done\()", hlo))


# PR 45: one block of rows a call. Every distinct (d_in, d_out) that the
# benchmark's ten configurations send through the slab kernel (the routed
# experts' [L, E, ...] slabs go through ops/pallas_q40_grouped.py), and
# whether the configuration holds it as a stack of layers (the kernel is
# handed the stack and a layer index) or as one plane (the heads). The list
# is held to the files by ``test_cell_shapes_are_what_the_config_files_give``.
CELL_SHAPES = [
    (1536, 2048, True), (1536, 8192, True), (1536, 24576, True),
    (2048, 512, True), (2048, 576, True), (2048, 1536, True),
    (2048, 2048, True), (2048, 6144, True), (2048, 7168, True),
    (2048, 11776, True), (2048, 65536, False), (2048, 131072, False),
    (2560, 128, True), (2560, 2560, True), (2560, 8192, True),
    (2560, 10240, True), (2560, 65536, False), (3584, 512, True),
    (3584, 3584, True), (3584, 18944, True), (3584, 152064, False),
    (4096, 256, True), (4096, 512, True), (4096, 768, True), (4096, 1024, True),
    (4096, 128, True), (4096, 1280, True),
    (4096, 1536, True), (4096, 2048, True), (4096, 4096, True), (4096, 8192, True),
    (4096, 12288, True),
    (4096, 14336, True), (4096, 16384, True), (4096, 19072, False), (4096, 24576, False),
    (4096, 32768, False),
    (4096, 73728, False), (1280, 4096, True),
    (5120, 192, True),
    (5120, 2560, True), (6144, 2048, True), (7168, 128, True),
    (7168, 576, True), (7168, 1536, True), (7168, 2048, True),
    (7168, 16384, False), (7168, 18432, True), (8192, 2560, True), (8192, 4096, True),
    (11776, 2048, True), (14336, 4096, True), (16384, 4096, True), (16384, 7168, True),
    (18432, 7168, True), (18944, 3584, True),
]


def check_one_row_block(v5e, d_in, d_out, stacked, m):
    """The default mode at a prefill bucket's rows, where the block of rows is
    the call's rows (PR 45) and, from SUBTRACT_MIN_ROWS up, the body takes
    the -8 off in the dequant chain (PR 49): Mosaic takes the x block, the
    f32 accumulator and the output block of ``m`` rows against every wide
    tile the cells have, under the ceiling the plan asks for; one kernel
    call, no lane of x split."""
    w_tile, rows = pq._plan_blocks(d_in, d_out)
    n_k = (d_in // 2) // rows
    assert pq._row_plan(m, w_tile, rows, n_k, 2)[0] == m  # one pass
    compile_ = _compile_stacked if stacked else _compile
    hlo = compile_(v5e, DEFAULT_MODE, d_in, d_out, m)
    assert hlo.count("tpu_custom_call") == 1
    assert _lane_splits(hlo) == []
    if stacked:
        assert not _scales_stack_converted_whole(hlo, d_in, d_out)


def _three_layer_decode_hlo(v5e, monkeypatch, lanes=16, n_heads=32, n_kv=8, rows=1, seq=256,
                            scales=None, layers=3):
    """The optimized HLO of a three-layer forward (one row a lane: a decode
    step; ``rows`` a lane: a prefill chunk; the cache donated) for a described
    v5e, and its dimensions: Mistral-7B's widths, or Qwen2.5-7B's at 28
    heads. The Q40 scales as an engine holds them (``engine_scales``: at a
    model's depth the FFN's stacks at rest, every other leaf float16), or all
    of them ``scales``."""
    from distributed_llama_multiusers_tpu.models import llama
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    monkeypatch.setattr(
        linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas
    )
    L, d, kv, vocab = layers, n_heads * 128, n_kv * 128, 8192
    h = {4096: 14336, 3584: 18944}[d]
    cfg = LlamaConfig(dim=d, hidden_dim=h, n_layers=L, n_heads=n_heads,
                      n_kv_heads=n_kv, vocab_size=vocab, seq_len=seq)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    q40 = lambda d_in, d_out, lead=(L,): PackedQ40(
        packed=sds(lead + (d_in // 2, d_out), jnp.uint8),
        scales=sds(lead + (d_in // 32, d_out),
                   scales or engine_scales(lead + (d_in // 32, d_out))))
    params = llama.LlamaParams(
        embedding=sds((vocab, d), jnp.bfloat16),
        layers=llama.LlamaLayerParams(
            wq=q40(d, d), wk=q40(d, kv), wv=q40(d, kv), wo=q40(d, d),
            w1=q40(d, h), w2=q40(h, d), w3=q40(d, h),
            rms_att=sds((L, d), jnp.float32), rms_ffn=sds((L, d), jnp.float32)),
        rms_final=sds((d,), jnp.float32), wcls=q40(d, vocab, ()),
        rope_cos=sds((seq, 64), jnp.float32), rope_sin=sds((seq, 64), jnp.float32))
    cache = llama.KVCache(*(sds((L, lanes, seq, n_kv, 128), jnp.bfloat16),) * 2)
    tok = sds((lanes, rows), jnp.int32)
    hlo = jax.jit(
        lambda p, t, c: llama.llama_forward(cfg, p, t, t, c), donate_argnums=(2,)
    ).lower(params, tok, cache).compile().as_text()
    return hlo, dict(L=L, d=d, h=h, kv=kv, lanes=lanes, seq=seq, n_kv=n_kv)


def _results_of_shape(hlo: str, shape: str) -> list[str]:
    """Instructions that MAKE an array of ``shape`` (a regex): a slice, a
    fusion, a copy or a conversion, in any layout. The in-place appends (a
    scatter fusion whose operand is the stack it returns) are what a decode
    step is allowed; parameters, tuple elements and bitcasts move nothing."""
    import re

    made = re.findall(
        rf"^\s*(?:ROOT )?(\S+) = {shape}\S* "
        r"(fusion|dynamic-slice|slice|copy|convert|transpose|copy-start)\((.*)$",
        hlo, flags=re.M)
    return [f"{name} = {op}" for name, op, rest in made
            if not (op == "fusion" and "dl.kv_write" in rest)]


def _cache_sized_results(hlo: str, L, lanes, seq, n_kv) -> list[str]:
    """What makes an array of the size of a K or V plane or of the stack, in
    the carry's shape or with (S, n_kv) merged (``_results_of_shape``)."""
    lead = rf"(?:{L},|1,)?{lanes},"
    return _results_of_shape(
        hlo, rf"(?:bf16|f32)\[{lead}(?:{seq},{n_kv}|{seq * n_kv}),128\]")


def _pattern_decode_hlo(v5e, monkeypatch, periods: int, seq: int):
    """The optimized HLO of the benchmark's layer-pattern block at its
    published widths, ``periods`` times ``c c A c`` (two dense layers, then
    whole periods ``A c c c`` of routed layers in the scan and an odd tail
    ``A c``), one row a lane at 64 lanes, the cache donated; and its
    dimensions."""
    from distributed_llama_multiusers_tpu.models import hybrid
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig
    from distributed_llama_multiusers_tpu.models.deepseek import DenseFfnParams, RoutedFfnParams
    from distributed_llama_multiusers_tpu.quants.packed import Q40Experts

    monkeypatch.setattr(linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas)
    kinds = (1, 1, 0, 1) * periods
    L, Ld, La, E, d, lanes, vocab = 4 * periods, 2, periods, 64, 2048, 64, 8192
    Lm, Lc = L - Ld, L - La
    cfg = LlamaConfig(
        dim=d, hidden_dim=11776, n_layers=L, n_heads=32, n_kv_heads=8, vocab_size=vocab,
        seq_len=seq, rope_theta=1e6, n_experts=E, n_active_experts=4, moe_hidden_dim=1536,
        n_dense_layers=Ld, moe_score_func=1, moe_select_bias=1, layer_kinds=kinds,
        conv_kernel=3, qk_norm=1)
    assert hybrid.layer_periods(kinds[Ld:]) == (4, periods - 1)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    q40 = lambda d_in, d_out, lead: PackedQ40(
        packed=sds(lead + (d_in // 2, d_out), jnp.uint8),
        scales=sds(lead + (d_in // 32, d_out), engine_scales(lead + (d_in // 32, d_out))))
    experts = lambda d_in, d_out: Q40Experts(
        sds((Lm, E, d_in // 2, d_out), jnp.uint8), sds((Lm, E, d_in // 32, d_out), jnp.int16))
    params = hybrid.HybridParams(
        embedding=sds((vocab, d), jnp.bfloat16),
        attn=hybrid.GqaParams(
            wq=q40(d, d, (La,)), wk=q40(d, 512, (La,)), wv=q40(d, 512, (La,)), wo=q40(d, d, (La,)),
            q_norm=sds((La, 64), jnp.float32), k_norm=sds((La, 64), jnp.float32),
            rms=sds((La, d), jnp.float32)),
        conv=hybrid.ConvParams(
            w_in=q40(d, 3 * d, (Lc,)), taps=sds((Lc, 3, d), jnp.float32),
            w_out=q40(d, d, (Lc,)), rms=sds((Lc, d), jnp.float32)),
        dense=DenseFfnParams(
            w1=q40(d, 11776, (Ld,)), w2=q40(11776, d, (Ld,)), w3=q40(d, 11776, (Ld,)),
            rms_ffn=sds((Ld, d), jnp.float32)),
        routed=RoutedFfnParams(
            gate=sds((Lm, d, E), jnp.float32), bias=sds((Lm, E), jnp.float32),
            w1=experts(d, 1536), w2=experts(1536, d), w3=experts(d, 1536),
            s1=None, s2=None, s3=None, rms_ffn=sds((Lm, d), jnp.float32)),
        rms_final=sds((d,), jnp.float32), wcls=q40(d, vocab, ()),
        rope_cos=sds((seq, 32), jnp.float32), rope_sin=sds((seq, 32), jnp.float32))
    cache = hybrid.HybridCache(
        sds((La, lanes, seq, 512), jnp.bfloat16), sds((La, lanes, seq, 512), jnp.bfloat16),
        sds((Lc, lanes, 2 * d), jnp.bfloat16))
    tok = sds((lanes, 1), jnp.int32)
    hlo = jax.jit(
        lambda p, t, c: hybrid.hybrid_forward_counted(cfg, p, t, t, c)[:2], donate_argnums=(2,)
    ).lower(params, tok, cache).compile().as_text()
    return hlo, dict(La=La, Lc=Lc, Lm=Lm, E=E, d=d, lanes=lanes, seq=seq)
