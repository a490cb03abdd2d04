"""Pallas TPU kernel: y = x @ dequant(W) for Q40-packed weights.

The TPU analogue of the reference's dequant-in-matmul kernels
(matmul_Q80_Q40_F32, src/nn/nn-cpu-ops.cpp:222-440, and the Vulkan shader
src/nn/vulkan/matmul-forward-q80-q40-f32.comp): weights stay int4+f16-scale
in HBM (~4.5 bits/element) and are expanded to f32 tile-by-tile in VMEM,
never materializing the dense weight in HBM. Decode-time matmuls are
HBM-bandwidth-bound, so reading 4.5 bits instead of 16 (bf16) per element is
the main single-chip throughput lever.

Layout (quants/packed.py): block-local nibble halves — each 32-input quant
block is 16 consecutive packed rows (low nibble = block inputs [0,16), high
nibble = [16,32)) + 1 scale row, so a chunk of whole blocks covers the same
contiguous input range in `packed`, `scales`, and `x`. The kernel takes the
scales as the int16 bits of their float16 values (Mosaic takes no f16
operand). Since PR 55 a stack that RESTS so (``q40_at_rest``) has layer l's
scale tiles read out of it by the index maps, exactly as the nibbles are,
wherever XLA cannot stage the stack whole (``reads_scales_in_place``: a 7B
model's FFN stacks, two thirds of the scales' bytes; the engine asks the same
predicate which leaves to convert, once, and is the one place that does).
Every other call has its layer's plane sliced out first, and converted where
it is float16, as every call had until then (the FFN's three converts were
0.55 ms of a 13.7 ms Mistral decode step).
``TRACE_STATS["scale_stack_reads"]`` counts the bodies that read in place,
``["scale_converts"]`` those fed by a converted plane.

Kernel formulation: the low/high nibble planes are split off the packed
bytes, interleaved by whole 16-row pieces into the input's own column order
and dequantised as one plane, so ONE dot against x as it is replaces the two
dots against pre-split halves of x that rounds 4 to PR 41 ran: splitting
x's lane axis into [n_blk, 2, 16] outside the kernel was five XLA
relayouts a layer, 3.65 ms of an 18.8 ms Mistral decode step (PERF.md
section 6, PR 42).

Where the nibbles' -8 goes is read off the call's block of rows, at trace
time, like the plan (PR 49). Under SUBTRACT_MIN_ROWS rows (every decode step
of 8-64 lanes) the kernel waits for the VPU's per-weight chain, so the
offset is FOLDED into one small correction dot against per-block x sums
instead of a per-weight subtract, and the kernel sums x's blocks itself, by
a dot (``_block_sums``): per packed byte the VPU does one
shift+mask+scale-mul, the rest is MXU work. From SUBTRACT_MIN_ROWS rows up
(the prefill buckets, a decode step of 128 lanes and more) it waits for the
MXU, and there the correction dot, ``[m, 8 or 16] @ [8 or 16, 512]`` a
sub-tile a k chunk, fills a 128-deep pass with 8 or 16 rows: a quarter of
the kernel's time at 1024 rows. So there the 8 is SUBTRACTED from the nibbles before they are
converted and scaled, ``w = (q - 8) * s`` as the reference's own
dequantisation writes it, and neither the block sums nor the correction dot
is traced: one dot a sub-tile a k chunk is left, and the VPU's extra
operation hides under it (PERF.md section 6, PR 49, has the lab's table).
``TRACE_STATS["offset_subtracted_traces"]`` counts such bodies. The two
block-dot modes keep the transposed (and Q80-quantised) operands,
built where their kernels are called (``_block_dot_operands``).

Block layout (round-4 rework, from pure-read measurements on a real v5e):
blocks span the FULL output width (or a wide 512-multiple tile
for very wide matmuls), so each DMA fetches one contiguous multi-hundred-KB
slab instead of the 512-BYTE strided rows of the old (chunk, 512) blocks —
which measured at 47 GB/s of the chip's 819 GB/s on pure reads. Dequant
happens in 512-lane sub-tiles INSIDE the kernel to bound VMEM transients.
Grid: (m blocks, d_out wide-tiles, d_in chunks); the d_in axis accumulates
into an f32 VMEM scratch. The m axis is outermost and the weight blocks'
index maps ignore it, so every m block fetches and dequantises the whole
plane again: the block of rows is therefore the CALL's rows, up to
M_BLOCK_MAX = 1024 (``_row_plan``, PR 45: the x block ``[m_block, 2 * rows]``,
the accumulator and the output block ``[m_block, w_tile]``), and a prefill
chunk of any bucket pays the VPU's pass over the weights once, where 256-row
blocks paid it four times at 1024 rows. A row's result does not depend on
the rows that share its block. Calls of 256 rows or fewer have the grid,
the blocks and the VMEM ceiling they always had.

On TPU the dot runs in bf16 by default: BOTH the dequantized weight planes
and the x operand are cast to bf16 (``w_dtype`` is the dot's compute
dtype), trading the MXU's multi-pass f32 emulation (~3x slower, ~f32
accuracy) for single-pass bf16. That rounds activations to 8 mantissa
bits — the same precision class as the reference's own Q80 activation
casts (8-bit, src/llm.cpp:232-239). Interpret mode (CPU tests) defaults
to exact f32; ``set_pallas_w_dtype(jnp.float32)`` restores multi-pass f32
on TPU.
"""

from __future__ import annotations

import math
import os as _os
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..quants.packed import (
    PALLAS_SUB as SUB_TILE,
    PackedQ40,
    pallas_sub_tiles as _sub_tiles,
    pallas_wide_tile as _pick_w,
)

# block geometry: every cell, test and server runs these values (ROADMAP
# S2(b)(ii) is where a chip measurement may change them)
SINGLE_SLAB_BYTES = 1 << 20  # planes up to this: one DMA, no k axis
TARGET_BLOCK_BYTES = 1 << 20  # k-chunk size target (DMA/compute overlap)

# Dequant arithmetic variant for the bf16 dot path (round-5 finding: the
# kernel is VPU-bound on the per-weight dequant chain — hbm_util ~0.26 on
# BOTH the 1B and the 8B, i.e. a per-byte cost with DMA hiding under it):
#   v4         f32 dequant (nib->f32, f32 scale mul) then bf16 cast
#   bf16chain  nib int->bf16 direct, one bf16 scale mul (no f32 round-trip)
#   repeat     bf16chain + jnp.repeat scale broadcast (no reshape dance)
#   u8chain    nibble masks on NATIVE 8-bit lanes (before any widening
#              relayout), int8->bf16 cast, bf16 scale mul — targets the
#              uint8->int32 expansion cost the other chains all pay
#   blockdot   per-quant-block MXU dots on RAW bf16 nibbles; the scale (and
#              the folded -8 offset) hit each block's [m, t] OUTPUT — the
#              per-weight VPU chain shrinks to mask + cast (~2 ops), with
#              the post-scale costing m/32 ops/weight (so decode-shaped m
#              only: m > 32 falls back to bf16chain)
#   i8blockdot blockdot with int8 MXU dots on Q80-QUANTIZED activations
#              (the reference's own activation format: per-block int8 +
#              f32 scale, src/llm.cpp:232-239): raw int8 nibbles feed the
#              MXU with NO per-weight cast or mul — the only chain with a
#              path to the DMA roofline. Numerics = reference Q80xQ40
#              class (activations quantized), bounded by the mode parity
#              test; same m cap as blockdot.
# Exact-f32 dots (w_dtype=f32: parity gate, interpret tests) always use the
# v4 f32 chain regardless of this knob.
DEQUANT_MODES = ("v4", "bf16chain", "repeat", "u8chain", "blockdot",
                 "i8blockdot")
# the two modes whose kernels take pre-split, transposed operands; the other
# four (the slab chains) take x as it is
BLOCK_DOT_MODES = ("blockdot", "i8blockdot")


def _env_dequant_default() -> str:
    """DLLAMA_DEQUANT, validated at READ time. A typo'd value must fail
    loudly here: the slab kernel's mode= else-branch would otherwise
    silently run the v4 chain under the wrong name."""
    mode = _os.environ.get("DLLAMA_DEQUANT", "v4")
    if mode not in DEQUANT_MODES:
        raise ValueError(
            f"DLLAMA_DEQUANT={mode!r} is not a known dequant mode; "
            f"one of {DEQUANT_MODES}"
        )
    return mode


DEQUANT_MODE = _env_dequant_default()
BLOCKDOT_MAX_M = 32  # above this, the post-scale FMA outweighs the savings

# Trace-time counters (host side: these python bodies run only while jax
# traces a NEW program, so steady-state jit-cache hits add nothing).
# `impl_traces` holding still across repeated calls is the no-recompile
# signal tests assert across the BLOCKDOT_MAX_M boundary,
# `natural_x_consumes` is the engagement witness of the slab chains'
# operand (PR 42): every kernel body traced in a slab chain was handed x in
# its own column order and dtype; there is no other form to fall back to,
# and `weight_passes_max` is the witness that a slab meets all of a call's
# rows (PR 45): `InferenceEngine.path_facts()` says it at start-up, so a
# run whose 1024-row calls still pay four passes is not silent; it says
# `offset_subtracted_traces` beside it (PR 49), so neither is a run whose
# 1024-row calls still carry the correction dot.
TRACE_STATS = {
    # q40_matmul_pallas calls that read their layer's tiles out of a stack
    "stacked_consumes": 0,
    "natural_x_consumes": 0,  # kernel-body traces handed x itself (slab chains)
    "impl_traces": 0,      # kernel-body traces (one per compiled family)
    # the most passes over its weight plane any traced kernel call makes
    # (m_pad // m_block: 1 for every call of up to M_BLOCK_MAX rows)
    "weight_passes_max": 0,
    # slab-chain kernel bodies traced with the -8 subtracted in the dequant
    # chain and NO correction dot (blocks of SUBTRACT_MIN_ROWS rows and more)
    "offset_subtracted_traces": 0,
    # kernel-body traces whose scale operand was the weight's own int16 plane
    # or stack, addressed in place (``reads_scales_in_place`` says which stacks)
    "scale_stack_reads": 0,
    # kernel-body traces fed by a FLOAT16 scale plane sliced out and converted
    # before the call (the parent's program for that call). An engine's every
    # body is one or the other; a small stack that a direct caller hands over
    # as bits all the same has its plane sliced out as bits, and is neither
    "scale_converts": 0,
}


def reset_trace_stats() -> None:
    for k in TRACE_STATS:
        TRACE_STATS[k] = 0

M_TILE = 256  # rows a call is padded to whole multiples of above this
# Blocks of this many rows and more take the nibbles' -8 off in the dequant
# chain; smaller ones fold it into a correction dot (``_q40_slab_kernel``).
# The smallest row count from which subtracting is no slower than folding on
# every shape of PR 49's lab (scripts/q40_offset_lab.py, the kernel alone on
# a v5e over fifteen of the cells' planes): -3 to -9 % at 128 rows, -10 to
# -18 % at 256, -12 to -27 % at 1024; +2 to +10 % at 16-64 rows.
SUBTRACT_MIN_ROWS = 128
# Rows one weight slab meets before the kernel moves to the next (PR 45): a
# slab is fetched and dequantised ONCE for a block of rows, so a call of up
# to this many rows (the widest prefill bucket) makes one pass over the
# plane; a longer call is cut into equal blocks and pays a pass a block.
M_BLOCK_MAX = 1024
MIN_W_TILE = 1024  # a wide tile narrowed for such a block keeps kilobyte rows
ROW_ALIGN = 8  # x rows padded to whole sublane tiles: 8 rows of 4-byte words
# Mosaic's default scoped-VMEM limit (16 MiB) refuses the prefill-shaped
# plans: a 256-row block against an 8192-wide slab needs 18.5 MiB (f32
# accumulator + double-buffered output block). The limit is a ceiling, not
# a reservation (the v5e has 128 MiB of VMEM). Every call whose pipelined
# blocks (``_block_bytes``) leave VMEM_HEADROOM under this one asks for
# exactly it: every call of 256 rows or fewer, so their compiler parameters
# are what they were. A call whose blocks need more (a 1024-row block
# against a 7168-wide tile: 59 MiB) asks for its blocks plus the headroom
# (``_vmem_limit``); the blocks themselves never pass this limit
# (``_row_plan``), so no call asks for more than 80 MiB.
VMEM_LIMIT_BYTES = 64 << 20
VMEM_BYTES = 128 << 20  # a TensorCore's fast memory (v5e; ``reads_scales_in_place``)
VMEM_HEADROOM = 16 << 20  # the body's transients beside the pipelined blocks


def _f16_bits_to_f32(h: jnp.ndarray) -> jnp.ndarray:
    """Exact f16 -> f32 from int16 bit patterns (Mosaic has no f16 type).

    Exact for all finite f16 values, which the Q40 encoder guarantees.
    Normals: rebias the exponent into f32 position. Denormals: mant * 2^-24
    as a float product — no denormal f32 intermediates, so flush-to-zero
    hardware (XLA:CPU, TPU) cannot corrupt them."""
    h32 = h.astype(jnp.int32) & 0xFFFF
    exp = (h32 >> 10) & 0x1F
    mant = h32 & 0x3FF
    normal = jax.lax.bitcast_convert_type(
        ((exp + 112) << 23) | (mant << 13), jnp.float32
    )
    denorm = mant.astype(jnp.float32) * jnp.float32(5.9604644775390625e-08)  # 2^-24
    mag = jnp.where(exp == 0, denorm, normal)
    return jnp.where(h32 >> 15 != 0, -mag, mag)


# A packed block (plus Mosaic's double buffer, the dequant transients, and
# the [m_tile, w_tile] f32 accumulator) must fit VMEM; blocks above this
# mean the shape has no supported tiling and callers take the XLA fallback.
MAX_BLOCK_BYTES = 4 << 20


def _pick_rows(half: int, w: int) -> int | None:
    """Packed rows per reduction step, or None when no VMEM-safe tiling
    exists. Small planes: the whole extent (one contiguous DMA). Larger:
    the biggest 128-multiple divisor of `half` whose slab is
    ~TARGET_BLOCK_BYTES, so Mosaic double-buffers multi-hundred-KB
    contiguous fetches."""
    if half * w <= SINGLE_SLAB_BYTES:
        return half
    best = None
    for rows in range(128, half + 1, 128):
        if half % rows == 0 and rows * w <= TARGET_BLOCK_BYTES:
            best = rows
    if best is None and half * w <= MAX_BLOCK_BYTES:
        return half  # e.g. half with no 128-multiple divisor, modest plane
    return best


def _plan_blocks(d_in: int, d_out: int) -> tuple[int, int] | None:
    """(w_tile, rows) for the slab kernel, or None when the shape has no
    supported VMEM-safe tiling (callers use q40_matmul_xla)."""
    if d_in % 32 != 0:
        return None
    w_tile = _pick_w(d_out)
    if w_tile is None:
        return None
    rows = _pick_rows(d_in // 2, w_tile)
    if rows is None:
        return None
    return w_tile, rows


def _acc_epilogue(part, off, t, k, n_k, out_ref, acc_ref):
    """Shared k-axis accumulation for one sub-tile's partial sum: direct
    write when the reduction has one chunk, else init/accumulate into the
    f32 VMEM scratch (finalized by ``_final_writeback``)."""
    if n_k == 1:
        out_ref[:, off:off + t] = part.astype(out_ref.dtype)
    else:
        @pl.when(k == 0)
        def _(part=part, off=off, t=t):
            acc_ref[:, off:off + t] = part

        @pl.when(k > 0)
        def _(part=part, off=off, t=t):
            acc_ref[:, off:off + t] = acc_ref[:, off:off + t] + part


def _final_writeback(k, n_k, out_ref, acc_ref):
    if n_k > 1:
        @pl.when(k == n_k - 1)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def set_dequant_mode(mode: str | None) -> None:
    """Select the bf16-path dequant variant (None -> env/default). The mode
    is a static argument of the jitted matmul, so switching retraces — set
    it before warmup_engine, never mid-serving."""
    global DEQUANT_MODE
    if mode is not None and mode not in DEQUANT_MODES:
        raise ValueError(
            f"unknown dequant mode {mode!r}; one of {DEQUANT_MODES}"
        )
    DEQUANT_MODE = mode or _env_dequant_default()


def _natural_order(lo, hi, n_blk, t):
    """The low and the high nibble plane ``[16 * n_blk, t]`` as one
    ``[n_blk, 32, t]`` in the input's own column order: block b's 16 low
    rows, then its 16 high rows. Done on the nibbles BEFORE they are
    converted and scaled, where the 16-row pieces are whole (8, 128) tiles
    of 4-byte words and the concatenation places registers: joining the
    dequantised bf16 planes instead cost the kernel a tenth of its time at
    decode width (PERF.md section 6, PR 42)."""
    return jnp.concatenate(
        [lo.reshape(n_blk, 16, t), hi.reshape(n_blk, 16, t)], axis=1
    )


# columns of x one block-sum dot takes: the 0/1 matrix it meets is built in
# the kernel every grid step and grows with the square of its width, so a
# chunk wider than this (a narrow d_out keeps the whole half as one slab:
# 7168 columns against the DeepSeek indexer's 128-wide planes) is summed in
# slices against one matrix of a slice's width
BSUM_SLICE = 2048


def _sum_slice(n: int) -> int:
    """Columns a block-sum dot takes of a chunk of ``n``: all of them up to
    BSUM_SLICE, else the largest divisor of n under it that is whole
    128-lane tiles of x and whole 8-row tiles of the scales it meets (a
    multiple of 256); n itself where there is none."""
    if n <= BSUM_SLICE:
        return n
    for c in range(BSUM_SLICE, 0, -256):
        if n % c == 0:
            return c
    return n


def _block_sums(x):
    """Per-quant-block sums of x's columns, ``[m, n]`` -> ``n / 32`` f32 sums
    a row, as dots: x against the 0/1 matrix that says which block a column
    is in, ``_sum_slice(n)`` columns a dot (the blocks repeat, so one matrix
    serves every slice). Returned a slice a piece, ``[m, c / 32]`` each, in
    column order: the caller meets them with the matching rows of the
    scales and never joins them on the lane axis. No lane of x is split.

    The products are exact and the accumulation is f32 in either dtype the
    kernel computes in: bf16 x against 0/1 in one MXU pass, f32 x at
    ``Precision.HIGHEST`` (asked for, not left to a default that may round
    the operand to bf16)."""
    n = x.shape[-1]
    c = _sum_slice(n)
    shape = (c, c // 32)
    col_blk = jax.lax.broadcasted_iota(jnp.int32, shape, 0) >> 5
    blk = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ind = (col_blk == blk).astype(x.dtype)
    exact = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    return [
        jnp.dot(x[:, j:j + c], ind, preferred_element_type=jnp.float32,
                precision=exact)
        for j in range(0, n, c)
    ]


def _q40_slab_kernel(x_ref, packed_ref, scales_ref, out_ref, acc_ref, *,
                     w_dtype, sub_tiles, n_k, mode, fold):
    """One (m block, d_out wide-tile, d_in chunk) step over a contiguous
    weight slab. ``x`` arrives as it is: this chunk's ``2 * rows`` columns in
    their own order and their own dtype.

    - the low/high nibble planes are interleaved by whole 16-row pieces
      into the input's order (``_natural_order``), dequantised as one plane
      and meet x in ONE dot of depth ``2 * rows``;
    - ``fold`` (a block under SUBTRACT_MIN_ROWS rows, where the VPU's chain
      sets the pace): NO per-weight -8 subtract: folded into one small
      correction dot, 8 * (per-block x sums) @ scales, subtracted from the
      partial sum. The block sums are dots too, once a grid step, BSUM_SLICE
      columns of x at most to a dot (``_block_sums``);
    - not ``fold`` (larger blocks, where the MXU does): 8 comes off the
      joined nibble plane before it is converted and scaled (u8chain: after
      its bf16 cast, exact there, Mosaic having no 8-bit-lane subtract), and
      no block sum and no correction dot is traced: the main dot alone. The
      weight is ``bf16((q - 8) * s)``, at most 8 s where the folded form
      rounds ``q * s`` up to 15 s;
    - dequant walks the slab in `sub_tiles`-lane slices to bound the VMEM
      transient (the slab itself can be megabytes wide).

    x: [mt, 2*rows]. packed: [rows, W] uint8 slab. scales: [rows/16, W]
    int16 (f16 bits). acc: [mt, W] f32 scratch (elided when n_k == 1: the
    block writes out_ref directly)."""
    rows, _ = packed_ref.shape
    n_blk = rows // 16
    k = pl.program_id(2)
    # the dot's compute dtype first (a no-op in every cell: x is bf16), so
    # both terms of a folded -8 see the same rounded x
    x = x_ref[...].astype(w_dtype)
    if fold:
        bsum = _block_sums(x)  # [mt, n_blk] f32, in slices of blk_c blocks
        blk_c = n_blk // len(bsum)

    off = 0
    for t in sub_tiles:
        s = _f16_bits_to_f32(scales_ref[:, off:off + t])  # [n_blk, t] f32
        if mode == "u8chain":
            # low-nibble mask on native 8-bit lanes BEFORE any widening
            # (the other chains pay a uint8->int32 expansion up front);
            # the high nibble shifts AFTER widening — Mosaic cannot
            # legalize arith.shrui on 8-bit lanes for the v5e. A 16-row
            # piece is half an 8-bit tile, so the planes are joined as bf16
            p8 = packed_ref[:, off:off + t]
            lo8 = (p8 & jnp.uint8(0x0F)).astype(jnp.int8)
            hi8 = (p8.astype(jnp.int32) >> 4).astype(jnp.int8)
            nib = _natural_order(lo8.astype(jnp.bfloat16),
                                 hi8.astype(jnp.bfloat16), n_blk, t)
            if not fold:
                # exact in bf16 (Mosaic has no 8-bit-lane subtract either)
                nib = nib - jnp.bfloat16(8)
        else:
            p = packed_ref[:, off:off + t].astype(jnp.int32)
            nib = _natural_order(p & 0x0F, p >> 4, n_blk, t)
            if not fold:
                nib = nib - 8
        if mode in ("bf16chain", "u8chain"):
            # dequant stays in bf16: nibbles (0..15, exact in bf16) cast
            # once, scales rounded to bf16 once per block (amortized /32),
            # ONE bf16 mul per weight — drops the f32 round-trip + downcast
            w = (nib.astype(jnp.bfloat16)
                 * s.astype(jnp.bfloat16)[:, None, :]).reshape(2 * rows, t)
        elif mode == "repeat":
            # bf16 chain with the scale broadcast as an explicit row repeat
            # (each block's scale row 32x consecutive) instead of the
            # reshape->broadcast->reshape dance — a relayout-cost A/B
            w = (nib.reshape(2 * rows, t).astype(jnp.bfloat16)
                 * jnp.repeat(s.astype(jnp.bfloat16), 32, axis=0))
        else:  # v4: f32 dequant, cast to the dot dtype at the end
            w = (nib.astype(jnp.float32) * s[:, None, :]).reshape(
                2 * rows, t).astype(w_dtype)

        if fold:
            # folded -8 offset: 8 * bsum_b @ s == sum_i x_i * 8 * s_block(i)
            corr = None
            for j, b in enumerate(bsum):
                c = jnp.dot(b, s[j * blk_c:(j + 1) * blk_c],
                            preferred_element_type=jnp.float32)
                corr = c if corr is None else corr + c
        part = jnp.dot(x, w, preferred_element_type=jnp.float32)
        if fold:
            part = part - 8.0 * corr
        _acc_epilogue(part, off, t, k, n_k, out_ref, acc_ref)
        off += t
    _final_writeback(k, n_k, out_ref, acc_ref)


def _q40_blockdot_kernel(xlt_ref, xht_ref, bsum_t_ref, packed_ref, scales_ref,
                        out_ref, acc_ref, *, sub_tiles, n_k):
    """blockdot mode: one (m tile, d_out wide-tile, d_in chunk) step where
    the MXU does the dequant scaling implicitly. Per quant block b, two
    small dots contract the RAW bf16 nibbles against the matching 16-row
    x slices (x arrives TRANSPOSED [rows, m] so the slices are sublane
    ranges, not sub-128 lane slices); the block's scale and the folded -8
    offset then hit the [m, t] block output once:

        y += (x_lo_b @ nib_lo_b + x_hi_b @ nib_hi_b - 8*bsum_b) * s_b

    Per-weight VPU work = mask + int->bf16 cast (~2 ops vs ~4.5 for the
    f32 chain); the post-scale FMA costs m/32 ops per weight, which is why
    callers cap m (BLOCKDOT_MAX_M). MXU pays n_blk small 16-deep dots per
    sub-tile — idle capacity at decode shapes (mfu ~0.002)."""
    rows, _ = packed_ref.shape
    n_blk = rows // 16
    k = pl.program_id(2)
    bs = bsum_t_ref[...]  # [n_blk, m_tile] f32
    xl = xlt_ref[...].astype(jnp.bfloat16)  # cast ONCE, slice per block
    xh = xht_ref[...].astype(jnp.bfloat16)
    dn = (((0,), (0,)), ((), ()))
    off = 0
    for t in sub_tiles:
        p = packed_ref[:, off:off + t].astype(jnp.int32)
        s = _f16_bits_to_f32(scales_ref[:, off:off + t])  # [n_blk, t]
        nib_lo = (p & 0x0F).astype(jnp.bfloat16)
        nib_hi = (p >> 4).astype(jnp.bfloat16)
        part = None
        for b in range(n_blk):
            lo = jax.lax.dot_general(
                xl[16 * b:16 * (b + 1), :],
                nib_lo[16 * b:16 * (b + 1), :], dn,
                preferred_element_type=jnp.float32,
            )
            hi = jax.lax.dot_general(
                xh[16 * b:16 * (b + 1), :],
                nib_hi[16 * b:16 * (b + 1), :], dn,
                preferred_element_type=jnp.float32,
            )
            contrib = (lo + hi - 8.0 * bs[b][:, None]) * s[b][None, :]
            part = contrib if part is None else part + contrib
        _acc_epilogue(part, off, t, k, n_k, out_ref, acc_ref)
        off += t
    _final_writeback(k, n_k, out_ref, acc_ref)


def _q40_i8blockdot_kernel(xlt_ref, xht_ref, aux_ref, packed_ref, scales_ref,
                           out_ref, acc_ref, *, sub_tiles, n_k):
    """i8blockdot mode: per-block int8 MXU dots on Q80-quantized
    activations. The raw int8 nibbles are the dot operand — the only
    per-weight VPU work is the 8-bit-lane mask. Per block b:

        y += s_b * (sx_b * (xq_lo_b @ nib_lo_b + xq_hi_b @ nib_hi_b)
                    - 8 * bsum_b)

    with sx the per-(lane, block) activation scale and bsum the EXACT f32
    per-block x sums (the folded -8 offset stays exact; only the nibble
    dot itself carries activation-quantization error — the reference's
    Q80xQ40 numerics, src/llm.cpp:232-239). aux interleaves bsum/sx on
    the sublane axis: aux[2b] = bsum[b], aux[2b+1] = sx[b]."""
    rows, _ = packed_ref.shape
    n_blk = rows // 16
    k = pl.program_id(2)
    m_t = xlt_ref.shape[1]
    aux = aux_ref[...].reshape(n_blk, 2, m_t)
    bs = aux[:, 0, :]  # [n_blk, m_tile] f32
    sx = aux[:, 1, :]
    xl = xlt_ref[...]  # [rows, m_tile] int8
    xh = xht_ref[...]
    dn = (((0,), (0,)), ((), ()))
    off = 0
    for t in sub_tiles:
        p8 = packed_ref[:, off:off + t]
        nib_lo = (p8 & jnp.uint8(0x0F)).astype(jnp.int8)
        # shift after widening: no 8-bit-lane arith.shrui on the v5e
        nib_hi = (p8.astype(jnp.int32) >> 4).astype(jnp.int8)
        s = _f16_bits_to_f32(scales_ref[:, off:off + t])  # [n_blk, t]
        part = None
        for b in range(n_blk):
            lo = jax.lax.dot_general(
                xl[16 * b:16 * (b + 1), :],
                nib_lo[16 * b:16 * (b + 1), :], dn,
                preferred_element_type=jnp.int32,
            )
            hi = jax.lax.dot_general(
                xh[16 * b:16 * (b + 1), :],
                nib_hi[16 * b:16 * (b + 1), :], dn,
                preferred_element_type=jnp.int32,
            )
            d = (lo + hi).astype(jnp.float32)
            contrib = (sx[b][:, None] * d - 8.0 * bs[b][:, None]) * s[b][None, :]
            part = contrib if part is None else part + contrib
        _acc_epilogue(part, off, t, k, n_k, out_ref, acc_ref)
        off += t
    _final_writeback(k, n_k, out_ref, acc_ref)


def pallas_supports(w: PackedQ40) -> bool:
    """True when the slab kernel handles these shapes; otherwise callers
    take the q40_matmul_xla fallback (ops/linear.py). d_in must cover whole
    quant blocks; d_out must give a valid wide tile (the loader pads wcls
    to a multiple of 8192 so vocab-width matmuls qualify); the fitted
    blocks must be VMEM-safe."""
    if w.packed.ndim != 2:
        return False
    return _plan_blocks(w.d_in, w.d_out) is not None


def pallas_supports_stack(w: PackedQ40) -> bool:
    """True when ``w`` is a stack of planes the slab kernel handles (exactly
    one leading axis, ``[L, d_in//2, d_out]``): the kernel then reads layer
    ``l``'s tiles out of the stack itself (``q40_matmul_pallas(layer=l)``)
    and nobody has to slice the plane out first."""
    return w.packed.ndim == 3 and _plan_blocks(w.d_in, w.d_out) is not None


def _resolve_w_dtype(w_dtype, interpret: bool):
    """None -> exact f32 in interpret mode (CPU parity tests), bf16 on TPU.
    w_dtype is the dot's COMPUTE dtype: the dequantized planes and the x
    operand are both cast to it (bf16 = single-pass MXU; f32 = slower
    multi-pass emulation with ~f32 accuracy)."""
    if w_dtype is not None:
        return w_dtype
    return jnp.float32 if interpret else jnp.bfloat16


def _m_geometry(m: int, dtype) -> tuple[int, int]:
    """(m_pad, m_tile): x rows padded to whole sublane tiles of ``dtype``
    (8 rows of f32, 16 of bf16: a narrower type packs two rows a sublane),
    and above M_TILE to whole tiles of M_TILE. The block of rows a weight
    slab meets is whole tiles of ``m_tile`` (``_row_plan``)."""
    align = ROW_ALIGN * max(1, 4 // jnp.dtype(dtype).itemsize)
    m_pad = max(align, ((m + align - 1) // align) * align)
    m_tile = min(M_TILE, m_pad)
    if m_pad % m_tile != 0:
        m_pad = ((m_pad + m_tile - 1) // m_tile) * m_tile
    return m_pad, m_tile


def _block_bytes(m_block: int, w_tile: int, rows: int, n_k: int,
                 x_itemsize: int) -> int:
    """VMEM the pipeline holds for one grid step of the slab kernel, from
    shapes alone: the f32 accumulator (none when the reduction is one
    chunk), and the double-buffered output, x, nibble and scale blocks."""
    acc = m_block * w_tile * 4 if n_k > 1 else 0
    out = m_block * w_tile * x_itemsize
    x = m_block * 2 * rows * x_itemsize
    weights = rows * w_tile + (rows // 16) * w_tile * 2
    return acc + 2 * (out + x + weights)


def _row_plan(m_pad: int, w_tile: int, rows: int, n_k: int,
              x_itemsize: int) -> tuple[int, int]:
    """(m_block, w_tile): the rows of x one fetched and dequantised weight
    slab meets, and the wide tile they meet it at.

    A call of up to M_TILE rows: all of them against the planned tile, the
    grid it always had. Above that the most whole M_TILE tiles, up to
    M_BLOCK_MAX rows, that divide ``m_pad`` (so the padding never grows: 300
    rows are 512 as before, in one block; 1300 are 1536 in two of 768).
    Where the pipelined blocks of that many rows would not fit
    VMEM_LIMIT_BYTES (1024 rows against an 8192-wide tile: 67 MiB, which
    the chip takes and runs SLOWER than four 256-row blocks, PERF.md
    section 6, PR 45), the wide tile gives way first: the largest
    128-multiple divisor of it, MIN_W_TILE lanes at least, that fits (8192
    -> 4096). The k chunk's rows stay as planned, so every element sums the
    same products in the same order at any tile. Only where no such divisor
    exists does the block of rows shrink."""
    if m_pad <= M_TILE:
        return m_pad, w_tile
    tiles = m_pad // M_TILE
    widths = [w_tile] + [w for w in range(w_tile - 128, MIN_W_TILE - 1, -128)
                         if w_tile % w == 0]
    for n in range(min(tiles, M_BLOCK_MAX // M_TILE), 1, -1):
        if tiles % n:
            continue
        for w in widths:
            if _block_bytes(n * M_TILE, w, rows, n_k,
                            x_itemsize) <= VMEM_LIMIT_BYTES:
                return n * M_TILE, w
    return M_TILE, w_tile


def _vmem_limit(block_bytes: int) -> int:
    """The scoped-VMEM ceiling a call asks for: VMEM_LIMIT_BYTES wherever
    the blocks leave VMEM_HEADROOM under it (every call of M_TILE rows or
    fewer: the compiler parameters they always had), else the blocks plus
    the headroom: 80 MiB at most, since ``_row_plan`` keeps the blocks
    under VMEM_LIMIT_BYTES."""
    return max(VMEM_LIMIT_BYTES, block_bytes + VMEM_HEADROOM)


def _padded_rows(x: jnp.ndarray) -> jnp.ndarray:
    """x ``[..., d_in]`` as the kernel takes it: the leading axes merged and
    the rows padded to whole tiles of x's dtype (``_m_geometry``), whatever
    the mode. No column moves and the dtype stays: the kernel takes x as it
    is and sums its blocks itself. Consumers of one input inside one program
    (wq/wk/wv, w1/w3) have their identical pads merged by XLA."""
    d_in = x.shape[-1]
    if d_in % 32 != 0:
        raise ValueError(f"d_in={d_in} must cover whole 32-wide quant blocks")
    x_rows = x.reshape(-1, d_in)
    m = x_rows.shape[0]
    m_pad, _ = _m_geometry(m, x.dtype)
    if m_pad != m:
        x_rows = jnp.pad(x_rows, ((0, m_pad - m), (0, 0)))
    return x_rows


def _block_dot_operands(x_rows: jnp.ndarray, mode: str):
    """The three operands a block-dot kernel takes, from ``[m_pad, d_in]``
    rows: the nibble halves of x's columns TRANSPOSED ``[half, m_pad]`` and
    an aux plane whose lane dim is m (Pallas lane-dim blocks must be
    multiples of 128 or the full extent, and m tiles are either all of
    m_pad or 256-wide). blockdot: f32 halves and the block sums
    ``[n_blk, m_pad]``; i8blockdot: the Q80 per-block int8 quantization and
    ``[2 * n_blk, m_pad]`` with aux[2b] = bsum[b], aux[2b+1] = sx[b].

    The split sees x's lane axis as [n_blk, 2, 16], which XLA:TPU does by a
    physical relayout: it cost 3.65 ms of an 18.8 ms Mistral decode step
    while the slab chains took their operands from it too (PERF.md section
    6, PR 42)."""
    m_pad, d_in = x_rows.shape
    half = d_in // 2
    n_blk = d_in // 32
    xq3 = x_rows.astype(jnp.float32).reshape(m_pad, n_blk, 32)
    bsum = xq3.sum(axis=2)  # EXACT f32 sums: the folded -8 stays exact
    if mode == "blockdot":
        halves, aux = xq3, bsum
    else:
        sx = jnp.maximum(jnp.abs(xq3).max(axis=2), 1e-8) / 127.0
        halves = jnp.clip(
            jnp.round(xq3 / sx[:, :, None]), -127, 127).astype(jnp.int8)
        aux = jnp.stack([bsum, sx], axis=2).reshape(m_pad, n_blk * 2)
    halves = halves.reshape(m_pad, n_blk, 2, 16)
    return (halves[:, :, 0, :].reshape(m_pad, half).T,
            halves[:, :, 1, :].reshape(m_pad, half).T,
            aux.T)


def q40_matmul_pallas(x: jnp.ndarray, w: PackedQ40, interpret: bool = False,
                      w_dtype=None, layer=None) -> jnp.ndarray:
    """y = x @ dequant(w). x: [..., d_in]; returns [..., d_out] in the
    input's dtype. The kernel's one entry: every caller hands it x as it is.

    ``layer``: with a STACKED weight (planes ``[L, d_in//2, d_out]`` and
    ``[L, d_in//32, d_out]``) the int32 index of the layer to multiply by,
    traced or not. The kernel's weight blocks (the nibbles', and the scales'
    where a stack too large to stage rests as int16 bits) are then addressed
    ``(layer, k, j)`` inside the stack, so a layer scan hands the whole
    stack over as a loop invariant and no copy of the plane is made for
    the call (a Pallas call is opaque to XLA and gets its operands
    materialised: sliced first, a 7B decode step copied all 4 GB of planes
    before the kernels read them again, PERF.md section 6, PR 30). A 2-D
    weight takes no ``layer``.

    ``w_dtype``: the dot's compute dtype — applied to the dequantized
    weight planes AND the x operand. None (the default) resolves to exact
    f32 under interpret and bf16 on TPU — see ``_resolve_w_dtype``.
    Explicit f32 on TPU restores multi-pass f32 MXU semantics (slower,
    more mantissa); explicit bf16 under interpret is the ablation/test
    knob. The bf16 path's dequant arithmetic variant comes from
    ``DEQUANT_MODE`` (env DLLAMA_DEQUANT / set_dequant_mode), read here so
    switching modes retraces. Exact-f32 dots always use the v4 f32 chain;
    blockdot's post-scale FMA scales with m, so large-m calls
    (prefill/training) fall back to bf16chain."""
    w_dtype_r = _resolve_w_dtype(w_dtype, interpret)
    m = math.prod(x.shape[:-1])
    mode = DEQUANT_MODE if w_dtype_r == jnp.bfloat16 else "v4"
    if mode in BLOCK_DOT_MODES and m > BLOCKDOT_MAX_M:
        mode = "bf16chain"
    at = ()  # a plain plane keeps the entry's five-argument call
    if layer is not None:
        TRACE_STATS["stacked_consumes"] += 1
        at = (jnp.asarray(layer, jnp.int32),)
    return _q40_matmul_pallas_impl(x, w, interpret, w_dtype_r, mode, *at)


@partial(jax.jit, static_argnames=("interpret", "w_dtype", "mode"))
def _q40_matmul_pallas_impl(x: jnp.ndarray, w: PackedQ40, interpret, w_dtype,
                            mode, layer=None) -> jnp.ndarray:
    """The one jitted entry: a device trace and a compiled program name
    every dense Q40 operation after it."""
    return _q40_matmul_core(x, w, interpret, w_dtype, mode, layer)


def reads_scales_in_place(scales) -> bool:
    """Whether a layer's kernel call addresses its scale tiles inside this
    ``[L, d_in//32, d_out]`` stack, once the stack rests as int16 bits, and so
    which leaves ``InferenceEngine`` converts when it takes its weights: a
    stack of more than one layer that XLA cannot hold whole in fast memory
    beside the kernel's own scoped VMEM. A Pallas call is opaque to XLA: its
    memory-space assignment sees the operand and not the index maps, and
    where the operand fits it copies ALL of it into fast memory ahead of the
    call, inside the layer loop, for the one plane the call reads
    (``pltpu.with_memory_space_constraint`` does not stop it, PR 30). That
    traffic is not free (chip, PR 55, every stack read in place against the
    parent: DeepSeek-V3.2's decode step 14.28 -> 14.81 ms, its grouped expert
    kernel 2.53 -> 3.21 ms beside 89 MB of staging a layer; Command A+'s
    expert kernel 6.60 -> 6.96 ms; it hid only where the kernels leave HBM
    bandwidth over, Qwen2.5-7B 16.35 -> 16.21). So such a stack's plane is
    sliced out a call whatever form the leaf is in (the slice is what costs,
    not the convert: the same 0.05-0.07 ms a 7B step for wq's plane either
    way, PERF.md section 5), the leaf stays as it arrived, and its programs
    are the ones they were. A stack over the line (the FFN scale stacks of a
    7B model are 117 MB each, MiniCPM-SALA's 134) is read in place. A call
    whose own limit is raised (``_vmem_limit``) leaves XLA less than the line
    assumes, so what is over it can be staged beside no call."""
    return (scales.ndim == 3 and scales.shape[0] > 1
            and scales.size * scales.dtype.itemsize > VMEM_BYTES - VMEM_LIMIT_BYTES)


def _q40_matmul_kernel(layer_ref, *refs, body):
    """Every mode's kernel behind the scalar-prefetch operand: the layer
    index is spent in the weight BlockSpecs' index maps, before the body."""
    del layer_ref
    body(*refs)


def _q40_matmul_core(x: jnp.ndarray, w: PackedQ40, interpret, w_dtype,
                     mode, layer=None) -> jnp.ndarray:
    TRACE_STATS["impl_traces"] += 1
    packed, scales = w.packed, w.scales
    if packed.ndim == 2 and layer is None:
        # one plane is a stack of one, read at layer 0: a free reshape, and
        # the same pallas_call as a layer of a real stack
        packed = packed[None]
        layer = jnp.zeros((), jnp.int32)
    elif packed.ndim != 3 or layer is None:
        raise ValueError(
            f"expected a 2D packed weight, or a [L, ...] stack and its layer "
            f"index; got {packed.shape} and layer={layer!r}"
        )
    d_in, d_out = w.d_in, w.d_out
    half = d_in // 2
    if x.shape[-1] != d_in:
        raise ValueError(f"operand d_in {x.shape[-1]} != weight d_in {d_in}")
    plan = _plan_blocks(d_in, d_out)
    if plan is None:
        raise ValueError(
            f"shape ({d_in}, {d_out}) unsupported; use q40_matmul_xla"
        )
    w_tile, rows = plan
    n_k = half // rows

    lead = x.shape[:-1]
    x_rows = _padded_rows(x)
    m_pad = x_rows.shape[0]
    x_itemsize = x.dtype.itemsize
    # the m axis is the grid's outermost and the weight blocks ignore it:
    # a slab is fetched and dequantised once for every m block, so the
    # block is the call's rows wherever they fit (one pass over the plane)
    m_block, w_tile = _row_plan(m_pad, w_tile, rows, n_k, x_itemsize)
    sub = _sub_tiles(w_tile)
    TRACE_STATS["weight_passes_max"] = max(
        TRACE_STATS["weight_passes_max"], m_pad // m_block)

    grid = (m_pad // m_block, d_out // w_tile, n_k)

    # Mosaic has no f16 type, so the kernel takes the scales' bit patterns.
    # A stack at rest (``quants.packed.q40_at_rest``) holds them so, and layer
    # l's scale tiles are addressed inside it like its nibbles: no slice, no
    # convert, no temporary. That holds for a stack XLA cannot hold in fast
    # memory (``reads_scales_in_place``); of any other the plane is sliced out,
    # and converted where it is still float16: for XLA:TPU f16 -> s16 is not
    # a relabelling but a pass over the data (on a whole stack it would be
    # hoisted out of the layer loop: 441 MB of temporaries at 7B widths,
    # PR 30), fused with the slice of ONE plane it costs that plane's read,
    # as it did for every call before PR 55. A 2-D plane is its own operand.
    at_rest = scales.dtype == jnp.int16
    in_stack = at_rest and reads_scales_in_place(scales)
    TRACE_STATS["scale_stack_reads"] += int(at_rest and (in_stack or scales.ndim == 2))
    TRACE_STATS["scale_converts"] += int(not at_rest)
    if in_stack:
        scale_spec = pl.BlockSpec((None, rows // 16, w_tile),
                                  lambda i, j, k, l: (l[0], k, j))
    else:
        if scales.ndim == 3:
            scales = jax.lax.dynamic_index_in_dim(scales, layer, 0, keepdims=False)
        if not at_rest:
            scales = jax.lax.bitcast_convert_type(scales, jnp.int16)
        scale_spec = pl.BlockSpec((rows // 16, w_tile), lambda i, j, k, l: (k, j))

    # index maps take the grid position and then the scalar-prefetch ref
    if mode in BLOCK_DOT_MODES:
        # x TRANSPOSED [rows, m]: the kernels slice 16-row (one quant
        # block) ranges, which must land on the sublane axis — sub-128
        # lane slices would relayout
        x_ops = _block_dot_operands(x_rows, mode)
        blockdot = mode == "blockdot"
        kernel = partial(
            _q40_blockdot_kernel if blockdot else _q40_i8blockdot_kernel,
            sub_tiles=sub, n_k=n_k)
        x_t_spec = pl.BlockSpec((rows, m_block), lambda i, j, k, l: (k, i))
        x_specs = [x_t_spec, x_t_spec, pl.BlockSpec(
            ((rows // 16) * (1 if blockdot else 2), m_block),
            lambda i, j, k, l: (k, i))]
    else:
        # x as it is: chunk k's 2 * rows columns, in its own dtype
        TRACE_STATS["natural_x_consumes"] += 1
        x_ops = (x_rows,)
        x_specs = [pl.BlockSpec((m_block, 2 * rows), lambda i, j, k, l: (i, k))]
        # where the -8 goes is read off the block of rows, like the plan
        fold = m_block < SUBTRACT_MIN_ROWS
        TRACE_STATS["offset_subtracted_traces"] += int(not fold)
        kernel = partial(_q40_slab_kernel, w_dtype=w_dtype, sub_tiles=sub,
                         n_k=n_k, mode=mode, fold=fold)

    out_dtype = x.dtype
    out = pl.pallas_call(
        partial(_q40_matmul_kernel, body=kernel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # the layer index, read by the index maps
            grid=grid,
            in_specs=[
                *x_specs,
                # layer l's nibble tiles, addressed inside the stack; the
                # leading block dimension is squeezed: the body sees [rows, W]
                pl.BlockSpec((None, rows, w_tile),
                             lambda i, j, k, l: (l[0], k, j)),
                scale_spec,  # its scale tiles: in the stack too, or its plane's
            ],
            out_specs=pl.BlockSpec((m_block, w_tile),
                                   lambda i, j, k, l: (i, j)),
            scratch_shapes=[
                pltpu.VMEM((m_block, w_tile if n_k > 1 else SUB_TILE),
                           jnp.float32)
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, d_out), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                _block_bytes(m_block, w_tile, rows, n_k, x_itemsize)),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m_pad * d_in * d_out,
            bytes_accessed=d_in * d_out // 2 + (d_in // 32) * d_out * 2
            + m_pad * d_in * out_dtype.itemsize
            + m_pad * d_out * out_dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(layer.reshape(1), *x_ops, packed, scales)

    return out[:math.prod(lead)].reshape(*lead, d_out)


# ---------------------------------------------------------------------------
# GSPMD integration: a partitioning rule for the kernel. VIRTUAL CPU DEVICES
# ONLY: libtpu implements no custom-call partitioner, so on real chips a mesh
# reaches the kernel through shard_map instead (ops/ring_collective.py, pure
# TP) and app/runtime_setup.py refuses the other layouts.
#
# Pallas calls are opaque to the SPMD partitioner, so without this a sharded
# forward would have to fall back to XLA dequant (round 1 disabled the kernel
# under any mesh). custom_partitioning teaches XLA to treat the quantized
# matmul like a dot: row-sliced weights (d_out sharded, reference
# sliceRowMatmul src/nn/nn-core.cpp:207-217) run the kernel per shard with a
# sharded output; col-sliced weights (d_in sharded, sliceColMatmul
# :219-230) run it per shard and psum the partial sums — the collective the
# reference realizes as its quantized TCP all-gather + merge_add.
# ---------------------------------------------------------------------------

from jax.experimental.custom_partitioning import custom_partitioning  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


def _q40_mm_impl(x, packed, scales, interpret, w_dtype):
    """Single-shard implementation: Pallas when the (local) shapes fit,
    XLA dequant otherwise. Runs unmodified on 1 device; partitioned, each
    shard re-evaluates `pallas_supports` on its local shapes."""
    from ..quants.packed import q40_matmul_xla

    w = PackedQ40(packed=packed, scales=scales)
    if pallas_supports(w):
        return q40_matmul_pallas(x, w, interpret=interpret, w_dtype=w_dtype)
    return q40_matmul_xla(x, w)


def _pad_spec(sharding, rank):
    spec = tuple(sharding.spec) if sharding.spec is not None else ()
    return spec + (None,) * (rank - len(spec))


def _spec_axes(entry):
    if entry is None:
        return set()
    return set(entry) if isinstance(entry, tuple) else {entry}


def _plan(mesh, arg_shapes):
    """(x_spec, packed_spec, scales_spec, out_spec, k_spec) — the canonical
    sharding layout nearest to what the operands arrived with."""
    x_s, p_s, _ = (a.sharding for a in arg_shapes)
    x_rank = len(arg_shapes[0].shape)
    x_spec = _pad_spec(x_s, x_rank)
    p_spec = _pad_spec(p_s, 2)

    k_spec = p_spec[0] if p_spec[0] is not None else x_spec[-1]
    n_spec = p_spec[1]
    if _spec_axes(k_spec) & _spec_axes(n_spec):
        k_spec = None  # conflicting proposal: replicate the contraction
    used = _spec_axes(k_spec) | _spec_axes(n_spec)
    lead = tuple(s if not (_spec_axes(s) & used) else None for s in x_spec[:-1])

    ns = lambda *spec: NamedSharding(mesh, P(*spec))
    return (
        ns(*lead, k_spec),
        ns(k_spec, n_spec),
        ns(k_spec, n_spec),
        ns(*lead, n_spec),
        k_spec,
    )


def _q40_mm_infer_sharding(interpret, w_dtype, mesh, arg_shapes, result_shape):
    del interpret, w_dtype, result_shape
    return _plan(mesh, arg_shapes)[3]


def _q40_mm_partition(interpret, w_dtype, mesh, arg_shapes, result_shape):
    del result_shape
    x_sh, p_sh, s_sh, out_sh, k_spec = _plan(mesh, arg_shapes)

    def lower(x, packed, scales):
        y = _q40_mm_impl(x, packed, scales, interpret, w_dtype)
        if k_spec is not None:
            y = _contraction_sync(y, k_spec, mesh)
        return y

    return mesh, lower, out_sh, (x_sh, p_sh, s_sh)


def _contraction_sync(y, k_spec, mesh):
    """The col-sliced partial-sum sync: a ring all-reduce (n-1 chunk-sized
    hops XLA overlaps with the surrounding compute — ops/ring_collective.py)
    when the ring engages, else the plain psum. DLLAMA_RING_SYNC=off (or
    set_ring_sync(False)) restores the psum path bit-for-bit; tuple axis
    specs and non-tiling widths fall back to psum inside ring_all_reduce."""
    from .ring_collective import ring_all_reduce, ring_sync_enabled

    if ring_sync_enabled() and isinstance(k_spec, str):
        return ring_all_reduce(y, k_spec, mesh.shape[k_spec])
    return jax.lax.psum(y, k_spec)


_q40_mm = custom_partitioning(_q40_mm_impl, static_argnums=(3, 4))
_q40_mm.def_partition(
    partition=_q40_mm_partition,
    infer_sharding_from_operands=_q40_mm_infer_sharding,
    # x [..., (b*32)], packed [(b*16), n], scales [b, n] -> [..., n]:
    # b = quant blocks of the contraction (reduction); the intra-block
    # subfactors must never be split across devices
    sharding_rule="... (b t), (b s) n, b n -> ... n",
    reduction_factors=("b",),
    need_replication_factors=("t", "s"),
    t=32,
    s=16,
)


def q40_matmul_partitioned(x: jnp.ndarray, w: PackedQ40, interpret: bool = False,
                           w_dtype=None) -> jnp.ndarray:
    """y = x @ dequant(w), partitionable under GSPMD meshes (TP/EP serving
    keeps dequant-in-matmul, closing round 1's 'Pallas disabled under any
    mesh' gap). Single device: identical to q40_matmul_pallas with XLA
    fallback for unsupported shapes."""
    return _q40_mm(x, w.packed, w.scales, interpret, w_dtype)
