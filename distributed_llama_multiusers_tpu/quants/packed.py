"""On-device packed Q40 weights: int4 nibbles + f16 block scales in HBM.

The reference keeps Q40 weights quantized at rest and dequantizes inside the
matmul kernel (src/nn/nn-cpu-ops.cpp:222-440 matmul_Q80_Q40_F32,
src/nn/vulkan/matmul-forward-q80-q40-f32.comp); the bf16 loader path instead
dequantizes on the host and ships 4x the bytes to HBM. Since TPU decode is
HBM-bandwidth-bound, keeping weights at 4 bit + 1/32 f16 scale (~4.5 bits/
element, exactly the .m Q40 footprint) is the main single-chip perf lever.

Device layout — block-local nibble halves, mirroring the .m Q40 block itself
(scale, 16 low-half bytes = inputs [0,16), high nibbles = inputs [16,32);
src/nn/nn-quants.hpp:64-67):

    packed: uint8 [..., d_in//2, d_out]
        row r = (b, j) with b = r // 16, j = r % 16:
        packed[r, o] = (v[32b + j, o] + 8) | ((v[32b + j + 16, o] + 8) << 4)
    scales: float16 [..., d_in//32, d_out], or the same bits as int16
        scales[b, o] covers input rows i in [32b, 32b+32)

Scales REST on the device as int16, the float16 values' bit patterns, where
the Pallas kernel reads a layer's scale tiles out of the stack in place: that
is the form it takes (Mosaic has no f16 type, and for XLA:TPU f16 -> s16 is a
pass over the data, not a relabelling). What makes them so is ``q40_at_rest``,
once, in ONE place: ``InferenceEngine.__init__``, for the stacks its
predicate names. The packers, the loaders and the generators make float16; a
leaf's dtype says which form it is in, and every reader here takes either
(``scale_values`` pays the bitcast back on the XLA paths).

i.e. the weight is stored transposed ([d_in, d_out], ready for y = x @ W)
and each 32-input quant block occupies 16 consecutive packed rows + 1 scale
row. Both planes are therefore CONTIGUOUS and PROPORTIONAL in the input
dimension: any slice of whole blocks — a TP shard of axis -2, or a Pallas
reduction chunk — covers the same input range in `packed`, `scales`, and
`x`, so identical PartitionSpecs shard both planes correctly (see
parallel/sharding.py) and kernels need no cross-chunk gather. Unpack is two
shifts + a block-local concat. Dequantization is (nibble - 8) * f16(scale),
bit-identical to src/nn/nn-quants.cpp:229-246.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .codec import Q40_BLOCK_SIZE, q40_to_planar, quantize_q40


class PackedQ40(NamedTuple):
    """A Q40-quantized matmul weight resident on device.

    Logical shape [..., d_in, d_out] for y = x @ W; ``logical_shape`` helpers
    below recover it from the stored planes.
    """

    packed: jnp.ndarray  # uint8 [..., d_in//2, d_out]
    # [..., d_in//32, d_out]: float16 as the packers make it, int16 (the
    # same bits) as the program serves from it (``q40_at_rest``)
    scales: jnp.ndarray

    @property
    def d_in(self) -> int:
        return self.packed.shape[-2] * 2

    @property
    def d_out(self) -> int:
        return self.packed.shape[-1]


def scale_bits(scales):
    """A scale plane as the kernels take it: the float16 values' bit
    patterns, int16. Bits pass through as they are; a host array is viewed,
    not copied; a device array pays one pass (``bitcast_convert_type``)."""
    if scales.dtype == jnp.int16:
        return scales
    if scales.dtype != jnp.float16:
        raise TypeError(f"Q40 scales are float16 or their int16 bits, not {scales.dtype}")
    if isinstance(scales, np.ndarray):
        return scales.view(np.int16)
    return jax.lax.bitcast_convert_type(scales, jnp.int16)


def scale_values(scales) -> jnp.ndarray:
    """A scale plane's float16 values, from either form: what the XLA paths
    multiply by (the bitcast back is theirs to pay)."""
    if scales.dtype != jnp.int16:
        return scales
    return jax.lax.bitcast_convert_type(scales, jnp.float16)


def q40_at_rest(tree, only=None):
    """``tree`` with its ``PackedQ40`` leaves' scales as int16 bits, the form
    the kernel reads a layer's scale tiles out of the stack in, by its index
    maps, like the nibbles (a float16 plane is sliced and converted before
    every call). ``only``: a predicate on a leaf's scale plane or stack
    (``ops.pallas_q40.reads_scales_in_place``: the engine's) that says which
    leaves; every leaf by default. The choice is read off each leaf's dtype,
    so a tree already at rest comes back as it is, and nothing else in the
    tree is touched. A host plane is viewed, a device plane costs one pass."""
    def rest(leaf):
        if not isinstance(leaf, PackedQ40) or (only is not None and not only(leaf.scales)):
            return leaf
        return leaf._replace(scales=scale_bits(leaf.scales))

    return jax.tree_util.tree_map(rest, tree, is_leaf=lambda x: isinstance(x, PackedQ40))


class Q40Layer(NamedTuple):
    """Layer ``layer`` of a stacked Q40 weight, still inside its stack: what
    a layer scan hands ``ops.linear.matmul`` where the Pallas kernel reads
    that layer's tiles out of the stack itself (``models/llama.py``), so that
    the plane is never sliced into a buffer of its own."""

    stack: PackedQ40  # planes [L, d_in//2, d_out] and [L, d_in//32, d_out]
    layer: jnp.ndarray  # int32 scalar, traced in a scan


def pack_q40_planar(values: np.ndarray, scales: np.ndarray):
    """Host-side repack: planar int8 values [..., d_out, d_in] (centered at 0,
    file orientation) + f16-exact scales [..., d_out, d_in//32] -> the device
    layout (packed uint8 [..., d_in//2, d_out], scales f16 [..., d_in//32, d_out])."""
    d_in = values.shape[-1]
    assert d_in % Q40_BLOCK_SIZE == 0, values.shape
    lead = values.shape[:-2]
    d_out = values.shape[-2]
    n_blk = d_in // Q40_BLOCK_SIZE
    half = Q40_BLOCK_SIZE // 2
    v = np.swapaxes(values, -1, -2)  # [..., d_in, d_out]
    vb = v.reshape(*lead, n_blk, Q40_BLOCK_SIZE, d_out)
    lo = (vb[..., :half, :].astype(np.int16) + 8).astype(np.uint8)
    hi = (vb[..., half:, :].astype(np.int16) + 8).astype(np.uint8)
    packed = ((lo & 0x0F) | ((hi & 0x0F) << 4)).reshape(*lead, d_in // 2, d_out)
    scales_t = np.swapaxes(scales, -1, -2).astype(np.float16)  # [..., d_in//32, d_out]
    return packed, scales_t


def pack_q40_from_blocks(raw_blocks: np.ndarray, shape: tuple[int, int]):
    """Packed .m Q40 block bytes (row-major over [d_out, d_in], blocks along
    d_in — src/llm.cpp:447-483 tensor layout) -> device layout, WITHOUT
    dequantizing. Returns (packed uint8 [d_in//2, d_out], scales f16
    [d_in//32, d_out])."""
    d_out, d_in = shape
    values, scales = q40_to_planar(raw_blocks)  # [(d_out*d_in/32), 32], f32 scales
    values = values.reshape(d_out, d_in)
    scales = scales.reshape(d_out, d_in // Q40_BLOCK_SIZE)
    return pack_q40_planar(values, scales)


def pack_q40_host(w: np.ndarray):
    """Quantize a float weight in file orientation [..., d_out, d_in] to the
    device layout (through the bit-exact Q40 encoder, codec.quantize_q40)."""
    lead = w.shape[:-2]
    d_out, d_in = w.shape[-2], w.shape[-1]
    blocks = quantize_q40(np.ascontiguousarray(w, np.float32).reshape(-1))
    values, scales = q40_to_planar(blocks)
    values = values.reshape(*lead, d_out, d_in)
    scales = scales.reshape(*lead, d_out, d_in // Q40_BLOCK_SIZE)
    return pack_q40_planar(values, scales)


# ---------------------------------------------------------------------------
# Slab-kernel geometry (shared with ops/pallas_q40): the Pallas kernel reads
# weights in full-width (or wide 512-multiple) contiguous slabs. These are
# pure-math helpers so the loader can pad without importing Pallas.
# ---------------------------------------------------------------------------

# widest output block of the slab kernel: a positive multiple of 128 (a
# plane's tile candidates are its 128-multiple divisors up to this; any
# other value would send every plane to the XLA fallback), pinned against
# the kernel's own block plan in tests/test_q40_geometry.py
PALLAS_W_MAX = 8192
PALLAS_SUB = 512  # in-kernel dequant sub-tile (lanes)


def pallas_sub_tiles(w: int) -> list[int] | None:
    """Static lane sub-tile sizes for a width-w kernel block: 512-lane
    tiles plus a 128-multiple remainder (slice offsets stay 128-aligned —
    e.g. Llama-2-7B's 5504-wide TP shard tiles as 10x512 + 384), a single
    tile for narrow test shapes, None when unsupported."""
    if w % 128 == 0:
        tiles = [PALLAS_SUB] * (w // PALLAS_SUB)
        if w % PALLAS_SUB:
            tiles.append(w % PALLAS_SUB)
        return tiles
    if w <= 4096:  # odd widths (e.g. 2752 = 11008/4 TP shard): one tile
        return [w]
    return None


def pallas_wide_tile(d_out: int) -> int | None:
    """Output-block width the slab kernel would use for this d_out, or None
    when unsupported (callers fall back to q40_matmul_xla, or pad — see
    pad_packed_d_out)."""
    if d_out <= PALLAS_W_MAX and pallas_sub_tiles(d_out) is not None:
        return d_out
    for cand in range(PALLAS_W_MAX, 127, -128):
        if d_out % cand == 0:
            return cand
    return None


PAD_MAX_OVERHEAD = 0.125  # never inflate a tensor's bytes by more than this


def padded_d_out(d_out: int) -> int:
    """The output width pad_packed_d_out would pad a tensor of width
    ``d_out`` to (shape-only: lets benchmarks draw padded planes directly
    on device without materializing the unpadded host tensor)."""
    tile = pallas_wide_tile(d_out)
    if d_out <= PALLAS_W_MAX or (tile is not None and tile >= 4096):
        return d_out
    pad = -d_out % PALLAS_W_MAX
    return d_out if pad > d_out * PAD_MAX_OVERHEAD else d_out + pad


def pad_packed_d_out(packed: np.ndarray, scales: np.ndarray):
    """Zero-pad a packed weight's OUTPUT dim to a multiple of 8192 when the
    slab kernel cannot tile it WELL (e.g. vocab 128256: best natural tile
    is a strided 768 — padding to 131072 buys full 8192-wide contiguous
    slabs for +2.2% bytes). Only valid for output-only tensors (wcls):
    consumers must slice the matmul result back to the true width
    (llama_forward slices logits to vocab_size). Zero scales make the pad
    columns exact zeros.

    Padding is capped at PAD_MAX_OVERHEAD of the tensor's bytes: an
    unlucky width like 8320 would round to 16384 (+97%), which costs more
    HBM than the wide tile saves — those widths keep their natural layout
    and take the narrow-tile or q40_matmul_xla path instead. Pads that do
    land are logged so the inflation is visible."""
    d_out = packed.shape[-1]
    target = padded_d_out(d_out)
    if target == d_out:
        return packed, scales
    pad = target - d_out
    import logging

    logging.getLogger(__name__).info(
        "padding packed d_out %d -> %d (+%.1f%% bytes) for wide slab tiles",
        d_out, d_out + pad, 100.0 * pad / d_out,
    )
    width = [(0, 0)] * (packed.ndim - 1) + [(0, pad)]
    return (
        np.pad(np.asarray(packed), width),
        np.pad(np.asarray(scales), width),
    )


def unpack_q40(w: PackedQ40, dtype=jnp.float32) -> jnp.ndarray:
    """Dequantize to a dense [..., d_in, d_out] array (XLA fallback path;
    the Pallas kernel in ops/pallas_q40.py does this tile-wise in VMEM)."""
    lead = w.packed.shape[:-2]
    d_in, d_out = w.d_in, w.d_out
    n_blk = d_in // Q40_BLOCK_SIZE
    half = Q40_BLOCK_SIZE // 2
    pb = w.packed.reshape(*lead, n_blk, half, d_out)
    lo = (pb & 0x0F).astype(jnp.int8) - 8
    hi = (pb >> 4).astype(jnp.int8) - 8
    vals = jnp.concatenate([lo, hi], axis=-2)  # [..., n_blk, 32, d_out]
    scales = scale_values(w.scales).astype(jnp.float32)[..., :, None, :]
    out = vals.astype(jnp.float32) * scales
    return out.reshape(*lead, d_in, d_out).astype(dtype)


def q40_matmul_xla(x: jnp.ndarray, w: PackedQ40, compute_dtype=None) -> jnp.ndarray:
    """y = x @ dequant(w) without a Pallas kernel. XLA fuses the unpack/scale
    into the matmul's weight-read loop where it can; correctness path for CPU
    tests and the fallback when Pallas is unavailable."""
    dtype = compute_dtype or x.dtype
    wd = unpack_q40(w, dtype)
    return jnp.matmul(x, wd, preferred_element_type=jnp.float32).astype(x.dtype)


class Q40Experts(NamedTuple):
    """The Q40 weights of every expert of every routed layer, one stack
    ``[L, E, ...]`` a matrix, as the grouped kernel reads them
    (ops/pallas_q40_grouped.py): by layer index and expert id, in place. The
    scales are held as their float16 BIT PATTERNS (``scale_bits``), as a
    ``PackedQ40`` at rest holds them: Mosaic has no f16 type, and for XLA:TPU
    f16 -> s16 is a pass over the data, which a call would pay on every
    expert's plane, chosen or not. ``from_packed`` pays it once, at load, and
    nothing where the stack arrives as bits already."""

    packed: jnp.ndarray  # uint8 [L, E, d_in//2, d_out]
    scale_bits: jnp.ndarray  # int16 [L, E, d_in//32, d_out]: float16 bits

    @property
    def d_in(self) -> int:
        return self.packed.shape[-2] * 2

    @property
    def d_out(self) -> int:
        return self.packed.shape[-1]

    @property
    def n_experts(self) -> int:
        return self.packed.shape[1]

    @staticmethod
    def from_packed(w: PackedQ40) -> "Q40Experts":
        if w.packed.ndim != 4:
            raise ValueError(f"expected [L, E, d_in//2, d_out] planes, got {w.packed.shape}")
        return Q40Experts(w.packed, scale_bits(w.scales))


def unpack_q40_slabs(w: Q40Experts, layer, experts, dtype=jnp.float32) -> jnp.ndarray:
    """Dequantize the slabs ``(layer, experts[i])`` to ``[n, d_in, d_out]``:
    the XLA form of the grouped kernel's read (the CPU, and what the kernel
    is tested against). Only the slabs named are touched."""
    packed = w.packed[layer, experts]  # [n, d_in//2, d_out]
    return unpack_q40(PackedQ40(packed, w.scale_bits[layer, experts]), dtype)
