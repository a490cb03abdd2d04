"""Ring collectives: TP activation sync overlapped with the dequant matmul.

The reference's TP sync is a quantized TCP all-gather after the wo/w2
row-parallel matmuls (SYNC_NODE_SLICES + merge_add, src/nn/nn-network.cpp:
537-569) — strictly sequential: every node finishes its whole partial
matmul, then the wire moves all the bytes, then decode continues. GSPMD
reproduces that schedule on ICI as one monolithic all-reduce at the matmul
output. This module replaces it with a RING schedule that the XLA scheduler
(and, on real TPU pods, a Pallas ``make_async_remote_copy`` hop — the JAX
distributed-Pallas idiom, SNIPPETS.md [1]) can overlap with compute:

- ``ring_reduce_scatter`` / ``ring_all_gather`` / ``ring_all_reduce``:
  shard-LOCAL ring collectives (call inside ``shard_map`` or a
  ``custom_partitioning`` lower). The payload moves as n-1 chunk-sized hops
  around the tp ring instead of one tensor-sized all-reduce, so each hop's
  ICI transfer is independent of the next chunk's accumulation add — XLA
  issues the collective-permutes async (start/done) and hides them under
  the arithmetic.

- ``ring_sync_matmul``: the fused form — a row-parallel (d_in-sharded)
  matmul whose OUTPUT is computed chunk-by-chunk interleaved with the ring:
  chip k streams its partial for chunk i to its right neighbor while the
  MXU computes chunk i+1's partial (the dequant-in-matmul kernel runs per
  column slice). The reduce half stays f32; the gather half optionally
  ships the Q80 wire format (int8 + f16 block scales — the reference's
  default transport, parallel/collectives.py) for ~4x fewer bytes.

- The per-hop shift is ``lax.ppermute`` (XLA's async collective-permute —
  the same ring schedule, testable on the virtual CPU mesh). On real TPU
  pods, ``DLLAMA_RING_RDMA=on`` opts the pure-TP shard_map paths into a
  Pallas hop built on ``pltpu.make_async_remote_copy`` (the ICI RDMA
  idiom of SNIPPETS.md [1]) that skips the HLO collective boundary;
  opt-in because it has never run on a chip; once opted in, a hop that
  fails to lower or compile raises instead of taking the ppermute ring.

Escape hatch: ``DLLAMA_RING_SYNC=off`` (or ``set_ring_sync(False)``)
disables every ring path and restores the plain ``lax.psum`` sync
bit-for-bit (the pre-ring behavior).

Numerics: the ring reduce adds partials in ring order instead of XLA's
reduction tree — same f32 class (bitwise-identical at tp=2, where both
orders are a single commutative add). The Q80 wire applies exactly the
block rounding of ``parallel/collectives.q80_all_gather`` (~1e-2 rel).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..quants.jax_codec import Q80_BLOCK, q80_decode_blocks, q80_encode_blocks

_ring_sync = os.environ.get("DLLAMA_RING_SYNC", "on").lower() not in (
    "off", "0", "false"
)


def set_ring_sync(enabled: bool | None) -> None:
    """Toggle the ring TP sync (None -> re-read DLLAMA_RING_SYNC). The flag
    is read at TRACE time and is not part of any jit cache key: it affects
    programs traced after the flip only — an already-compiled executable
    keeps its ring/psum lowering (tests build a fresh jit per setting for
    exactly this reason). Flip it before engine construction/warmup."""
    global _ring_sync
    if enabled is None:
        _ring_sync = os.environ.get("DLLAMA_RING_SYNC", "on").lower() not in (
            "off", "0", "false"
        )
    else:
        _ring_sync = bool(enabled)


def ring_sync_enabled() -> bool:
    return _ring_sync


def ring_sync_engages(config, mesh_shape: dict) -> bool:
    """Whether the shard_map ring sync replaces the wo/w2 activation
    all-reduce in ``llama_forward`` — the twin of ``q80_sync_engages``
    (same pure-TP requirement: the sync shard_map replicates activations
    over every non-tp axis) plus ring divisibility: both synced outputs
    are ``dim`` wide and must split into whole per-hop chunks."""
    if not _ring_sync:
        return False
    return pure_tp(mesh_shape) and config.dim % mesh_shape["tp"] == 0


# ---------------------------------------------------------------------------
# The per-hop shift primitive: ppermute everywhere; Pallas RDMA on real TPU.
# ---------------------------------------------------------------------------


def _ring_perm(n: int) -> list[tuple[int, int]]:
    return [(j, (j + 1) % n) for j in range(n)]


def _use_rdma() -> bool:
    """Pallas remote-DMA hop: OPT-IN (``DLLAMA_RING_RDMA=on``) and real TPU
    backends only. The HLO collective-permute ring is the shipping hop —
    same schedule, testable on the virtual CPU mesh; the RDMA kernel skips
    the HLO collective boundary but has never run on a chip. Once opted
    in, a hop that fails to lower or compile is an error (``_shift``)."""
    if os.environ.get("DLLAMA_RING_RDMA", "off").lower() not in ("on", "1", "true"):
        return False
    return jax.devices()[0].platform == "tpu"


# rdma_ok threading: ``device_id=(right,)`` addresses the neighbor by its
# coordinate along the ring axis, which equals the logical device id ONLY
# when every other mesh axis is trivial — the pure-TP meshes the shard_map
# sync engages on. Callers on possibly-multi-axis meshes (the
# custom_partitioning contraction sync) keep rdma_ok=False and hop via
# XLA's async collective-permute, the same ring schedule through HLO.


def _rdma_shift(x: jnp.ndarray, axis: str, n: int, chan: int) -> jnp.ndarray:
    """One ring hop over ICI RDMA: send the local buffer to the right
    neighbor via ``pltpu.make_async_remote_copy`` (SNIPPETS.md [1] / the
    JAX distributed-Pallas guide), return what the left neighbor sent.
    Must run inside shard_map on a real TPU mesh. ``chan`` is the Mosaic
    collective_id: hop chains with NO data dependency between them (the
    Q80 wire's int8-values and f16-scales chains run concurrently) must
    use distinct channels or their collective semaphores alias."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, o_ref, send_sem, recv_sem):
        my = jax.lax.axis_index(axis)
        right = jax.lax.rem(my + 1, n)
        copy = pltpu.make_async_remote_copy(
            src_ref=x_ref,
            dst_ref=o_ref,
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=(right,),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        copy.start()
        copy.wait()

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(collective_id=chan),
    )(x)


def _shift(x: jnp.ndarray, axis: str, n: int, rdma_ok: bool = False,
           chan: int = 0) -> jnp.ndarray:
    """Rotate ``x`` one hop rightward around the ring (device r receives
    device (r-1)'s buffer). ``chan``: see ``_rdma_shift`` — concurrent
    (data-independent) hop chains need distinct channels."""
    if rdma_ok and _use_rdma():
        # opted in (DLLAMA_RING_RDMA=on): a hop that fails to lower raises —
        # quietly taking the ppermute ring would time the wrong transport
        return _rdma_shift(x, axis, n, chan)
    return jax.lax.ppermute(x, axis, _ring_perm(n))


# ---------------------------------------------------------------------------
# Shard-local ring collectives (inside shard_map / custom_partitioning).
# ---------------------------------------------------------------------------


def _chunk_idx(chunks: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.dynamic_index_in_dim(chunks, idx, 0, keepdims=False)


def ring_reduce_scatter(x: jnp.ndarray, axis: str, n: int,
                        rdma_ok: bool = False) -> jnp.ndarray:
    """Ring reduce-scatter of the last dim: every device holds a full-width
    partial ``x`` [..., D]; device r returns the fully reduced chunk r
    [..., D/n]. n-1 hops, each carrying D/n elements — the accumulation add
    of hop s is independent of hop s+1's transfer, so the transfers hide
    under the arithmetic. Must run inside shard_map (or a
    custom_partitioning lower) with ``axis`` bound; D % n == 0."""
    if n <= 1:
        return x
    c = x.shape[-1] // n
    chunks = jnp.moveaxis(x.reshape(*x.shape[:-1], n, c), -2, 0)  # [n, ..., c]
    r = jax.lax.axis_index(axis)
    # invariant: after hop s, device r holds sum_{k=r-s..r} of every
    # device k's copy of chunk (r-1-s) mod n; s = n-1 lands chunk r reduced
    acc = _chunk_idx(chunks, (r - 1) % n)
    for s in range(1, n):
        acc = _shift(acc, axis, n, rdma_ok)
        acc = acc + _chunk_idx(chunks, (r - 1 - s) % n)
    return acc


def _reorder_arrivals(arrivals: list[jnp.ndarray], axis: str, n: int) -> jnp.ndarray:
    """Ring-arrival order -> chunk order: arrival j on device r originated
    on device (r-j) mod n, so output chunk k is arrival (r-k) mod n."""
    a = jnp.stack(arrivals)  # [n, ..., c]
    r = jax.lax.axis_index(axis)
    idx = (r - jnp.arange(n, dtype=jnp.int32)) % n
    b = jnp.take(a, idx, axis=0)  # b[k] = chunk k
    c = arrivals[0].shape[-1]
    return jnp.moveaxis(b, 0, -2).reshape(*arrivals[0].shape[:-1], n * c)


def ring_all_gather(x: jnp.ndarray, axis: str, n: int,
                    rdma_ok: bool = False) -> jnp.ndarray:
    """Ring all-gather of per-device chunks: device r holds chunk r
    [..., C]; returns [..., n*C] with chunk k = device k's data, identical
    on every device. Must run inside shard_map with ``axis`` bound."""
    if n <= 1:
        return x
    arrivals = [x]
    cur = x
    for _ in range(1, n):
        cur = _shift(cur, axis, n, rdma_ok)
        arrivals.append(cur)
    return _reorder_arrivals(arrivals, axis, n)


def ring_all_gather_q80(x: jnp.ndarray, axis: str, n: int,
                        rdma_ok: bool = False) -> jnp.ndarray:
    """``ring_all_gather`` shipping the Q80 wire format: the local chunk is
    encoded ONCE (int8 values + f16 block scales — the reference's ZQ-pipe
    transport, parallel/collectives.py) and the encoded pair rides all n-1
    hops; every arrival is decoded locally. ~25% of the f32 payload on the
    wire; the local chunk passes through the codec too, so all devices
    apply identical block rounding (the ``q80_all_gather`` contract).
    Needs C % 32 == 0."""
    if n <= 1:
        return x
    q, s = q80_encode_blocks(x.astype(jnp.float32), mode="converter")
    dec = lambda qq, ss: q80_decode_blocks(qq, ss, x.shape).astype(x.dtype)
    arrivals = [dec(q, s)]
    cq, cs = q, s
    for _ in range(1, n):
        # the two wire chains have no data dependency and may be scheduled
        # concurrently -> distinct RDMA channels (collective_ids)
        cq = _shift(cq, axis, n, rdma_ok, chan=0)
        cs = _shift(cs, axis, n, rdma_ok, chan=1)
        arrivals.append(dec(cq, cs))
    return _reorder_arrivals(arrivals, axis, n)


def ring_all_reduce(x: jnp.ndarray, axis: str, n: int) -> jnp.ndarray:
    """Ring all-reduce (reduce-scatter + all-gather): the drop-in for
    ``lax.psum`` over ``axis`` on a full-width partial. Falls back to psum
    when the ring cannot tile the payload (n does not divide the last dim)
    or the ring is degenerate — so callers can substitute unconditionally."""
    if n <= 1 or x.shape[-1] % n != 0:
        return jax.lax.psum(x, axis)
    return ring_all_gather(ring_reduce_scatter(x, axis, n), axis, n)


# ---------------------------------------------------------------------------
# Pure-TP matmuls on LOCAL shards (shard_map), for PackedQ40 weights.
#
# libtpu implements no custom-call partitioner ("Custom emitter for
# CustomSPMDPartitioning not found", seen on four real v5e chips, PR 21), so
# the GSPMD wrapper around the kernel (pallas_q40.q40_matmul_partitioned)
# compiles on virtual CPU devices only. On a pure-TP mesh every Q40 matmul of
# llama_forward therefore runs the kernel per shard under shard_map: these
# two, and ring_sync_matmul / q80_sync_matmul for the synced outputs.
# ---------------------------------------------------------------------------


def pure_tp(mesh_shape: dict) -> bool:
    """tp > 1 and every other mesh axis trivial: the reference's layout, and
    the one on which activations are replicated outside the matmuls."""
    return mesh_shape.get("tp", 1) > 1 and all(
        mesh_shape.get(ax, 1) == 1 for ax in ("dp", "sp", "ep", "pp")
    )


def tp_sliced_matmul(x: jnp.ndarray, w, mesh: Mesh,
                     axis: str = "tp") -> jnp.ndarray:
    """y = x @ dequant(w) for a row-sliced PackedQ40 weight (d_out sharded
    over ``axis``: wq/wk/wv/w1/w3/wcls). x replicated; each shard runs the
    local kernel on its column slice; the output stays sharded on d_out — no
    sync (reference sliceRowMatmul, src/nn/nn-core.cpp:207-217)."""
    from ..ops.linear import q40_matmul_local
    from ..quants.packed import PackedQ40

    return shard_map(
        lambda xl, pk, sc: q40_matmul_local(xl, PackedQ40(pk, sc)),
        mesh=mesh,
        in_specs=(P(), P(None, axis), P(None, axis)),
        out_specs=P(*([None] * (x.ndim - 1) + [axis])),
        check_vma=False,
    )(x, w.packed, w.scales)


def tp_reduced_matmul(x: jnp.ndarray, w, mesh: Mesh,
                      axis: str = "tp") -> jnp.ndarray:
    """y = x @ dequant(w) for a col-sliced PackedQ40 weight (d_in sharded:
    wo/w2) with the plain ``psum`` sync — what ``ring_sync_matmul`` replaces
    when the ring engages, and the path ``--ring-sync off`` restores."""
    from ..ops.linear import q40_matmul_local
    from ..quants.packed import PackedQ40

    nd = x.ndim
    return shard_map(
        lambda xl, pk, sc: jax.lax.psum(
            q40_matmul_local(xl, PackedQ40(pk, sc)), axis
        ),
        mesh=mesh,
        in_specs=(P(*([None] * (nd - 1) + [axis])), P(axis, None),
                  P(axis, None)),
        out_specs=P(*([None] * nd)),
        check_vma=False,
    )(x, w.packed, w.scales)


# ---------------------------------------------------------------------------
# The fused form: row-parallel matmul with the ring interleaved per chunk.
# ---------------------------------------------------------------------------


def ring_sync_supported(d_out: int, tp: int, q80_wire: bool = False) -> bool:
    """Whether a row-parallel output of width ``d_out`` can sync through
    the ring: whole chunks per hop, and whole Q80 blocks per chunk when the
    wire is compressed."""
    if tp <= 1 or d_out % tp != 0:
        return False
    return not q80_wire or (d_out // tp) % Q80_BLOCK == 0


def ring_sync_matmul(
    x: jnp.ndarray,
    w,
    mesh: Mesh,
    axis: str = "tp",
    q80_wire: bool = False,
) -> jnp.ndarray:
    """y = x @ w for a col-sliced (d_in-sharded) weight, with the TP sync
    RING-OVERLAPPED with the partial matmul instead of a sequential
    post-matmul all-reduce:

        for each of the n ring hops: compute the LOCAL partial for ONE
        d_out/n column chunk (dequant-in-matmul per column slice) and add
        the chunk partial that just arrived from the left neighbor; the
        hop transfer for chunk i is in flight WHILE chunk i+1's dot runs.

    After the reduce ring, device r holds reduced chunk r; a ring
    all-gather (Q80 wire when ``q80_wire`` — the reference's compressed
    transport) replicates the full output. Reduction is f32 regardless of
    the dot dtype (the reduce half of ``q80_sync_matmul`` has the same
    contract).

    x: [..., d_in] sharded over ``axis`` on its last dim; w: [d_in, d_out]
    dense or PackedQ40, sharded over ``axis`` on d_in. Returns [..., d_out]
    replicated over ``axis``. Needs ``ring_sync_supported(d_out, n,
    q80_wire)``."""
    from ..ops.linear import q40_matmul_local
    from ..quants.packed import PackedQ40

    n = mesh.shape[axis]
    packed = isinstance(w, PackedQ40)
    d_out = w.d_out if packed else w.shape[-1]
    if not ring_sync_supported(d_out, n, q80_wire):
        raise ValueError(
            f"ring_sync_matmul needs d_out ({d_out}) divisible by "
            f"mesh.shape[{axis!r}] ({n})"
            + (" with whole Q80 blocks per chunk" if q80_wire else "")
        )
    c = d_out // n
    nd = x.ndim

    def inner(xl, *wl):
        r = jax.lax.axis_index(axis)

        def part_chunk(idx):
            # local partial for output columns [idx*c, (idx+1)*c): column
            # chunking is exact (each output column reduces independently)
            if packed:
                pk = jax.lax.dynamic_slice_in_dim(wl[0], idx * c, c, axis=-1)
                sc = jax.lax.dynamic_slice_in_dim(wl[1], idx * c, c, axis=-1)
                part = q40_matmul_local(xl, PackedQ40(pk, sc))
            else:
                part = xl @ jax.lax.dynamic_slice_in_dim(wl[0], idx * c, c, axis=-1)
            return part.astype(jnp.float32)

        # ring reduce-scatter fused with the chunked matmul: the hop of
        # chunk s-1's accumulator and the dot for chunk s are independent,
        # so XLA runs the transfer concurrent with the MXU work
        acc = part_chunk((r - 1) % n)
        for s in range(1, n):
            # rdma_ok: this sync only engages on pure-TP meshes
            # (ring_sync_engages), where the tp coordinate IS the logical
            # device id the RDMA hop addresses
            acc = _shift(acc, axis, n, rdma_ok=True)
            acc = acc + part_chunk((r - 1 - s) % n)
        if q80_wire:
            out = ring_all_gather_q80(acc, axis, n, rdma_ok=True)
        else:
            out = ring_all_gather(acc, axis, n, rdma_ok=True)
        return out.astype(xl.dtype)

    x_spec = P(*([None] * (nd - 1) + [axis]))
    w_specs = (P(axis, None), P(axis, None)) if packed else (P(axis, None),)
    w_args = (w.packed, w.scales) if packed else (w,)
    return shard_map(
        inner,
        mesh=mesh,
        in_specs=(x_spec,) + w_specs,
        out_specs=P(*([None] * nd)),
        check_vma=False,
    )(x, *w_args)
