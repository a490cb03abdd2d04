"""Quantization-compressed collectives.

The reference cuts TP sync bandwidth ~4x by shipping Q80 (int8 + fp16 block
scale) instead of f32 over its TCP mesh (ZQ pipe, src/llm.cpp:150,
src/nn/nn-network.cpp:537-569). On ICI bandwidth is rarely the bottleneck,
but the same trick applies on DCN-spanning meshes — so the framework offers
an int8-compressed all-gather built from shard_map primitives.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..quants.jax_codec import Q80_BLOCK, q80_decode_blocks, q80_encode_blocks


def _gather_q80(local: jnp.ndarray, axis: str, n_shards: int) -> jnp.ndarray:
    """Shard-local half of the quantized gather: Q80-encode the owned slice,
    all_gather int8 values + f16 scales, decode, and concatenate the device
    slices along the last dim. Shared wire format of ``q80_all_gather`` and
    ``q80_sync_matmul``. Must run inside shard_map."""
    # converter-mode rounding (ties-to-even vectorizes as one jnp.round)
    q, s = q80_encode_blocks(local, mode="converter")
    qg = jax.lax.all_gather(q, axis, axis=0)  # [n, ..., blk, 32]
    sg = jax.lax.all_gather(s, axis, axis=0)
    full = q80_decode_blocks(qg, sg, (n_shards,) + local.shape)
    return jnp.concatenate([full[i] for i in range(n_shards)], axis=-1)


def q80_sync_supported(dim: int, tp: int) -> bool:
    """Whether a tp-sharded output of width ``dim`` can ship as Q80: each
    device slice must be whole 32-value blocks (for both the wire blocks and
    the packed/scale plane shard divisibility)."""
    return tp > 1 and dim % (Q80_BLOCK * tp) == 0


def q80_sync_engages(config, mesh_shape: dict) -> bool:
    """Single source of truth for whether the Q80 sync transport engages —
    used by both llama_forward (the compiled program) and the CLI startup
    log, so what is announced is what runs. Requires:

    - a PURE-TP mesh: the sync shard_map replicates its activations over
      every non-tp axis, so dp/sp/ep/pp > 1 would add per-layer gathers
      costing more than the f32 all-reduce saves (the reference's mesh is
      pure TP too, src/app.cpp:237-240);
    - whole Q80 blocks per tp shard of every synced output (wo -> dim;
      the dense-FFN w2 additionally needs hidden-sharded planes; MoE FFNs
      never route w2 through the wire sync)."""
    from ..ops.ring_collective import pure_tp

    if not pure_tp(mesh_shape):
        return False
    tp = mesh_shape["tp"]
    return q80_sync_supported(config.dim, tp) and (
        config.n_experts > 0 or q80_sync_supported(config.hidden_dim, tp)
    )


def q80_all_gather(x: jnp.ndarray, mesh: Mesh, axis: str = "tp") -> jnp.ndarray:
    """All-gather x's last dim across ``axis``, shipping int8+fp16 scales.

    x: sharded on its last axis over ``axis`` (each device holds its slice).
    Returns the full array, replicated over ``axis``; payload on the wire is
    ~25% of the f32 equivalent (34 bytes per 32 values, SURVEY.md §5.8).
    """
    n_axis_dims = x.ndim
    n_shards = mesh.shape[axis]
    if x.shape[-1] % (Q80_BLOCK * n_shards) != 0:
        raise ValueError(
            f"q80_all_gather needs last dim ({x.shape[-1]}) divisible by "
            f"{Q80_BLOCK} * mesh.shape[{axis!r}] ({n_shards}) so each device "
            f"slice is whole Q80 blocks"
        )

    def inner(local):
        return _gather_q80(local, axis, n_shards)

    in_spec = P(*([None] * (n_axis_dims - 1) + [axis]))
    out_spec = P(*([None] * n_axis_dims))
    return shard_map(
        inner, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec, check_vma=False
    )(x)


def q80_sync_matmul(x: jnp.ndarray, w, mesh: Mesh, axis: str = "tp") -> jnp.ndarray:
    """Row-parallel matmul whose TP sync ships Q80 instead of f32 — the
    serving wire-up of the reference's default transport (its wo/w2 outputs
    cross the node mesh as int8+scale, ZQ pipe src/llm.cpp:150,
    nn-network.cpp:537-569). GSPMD's plain all-reduce becomes:

        local partial matmul -> psum_scatter (f32, 1/tp of the payload)
        -> Q80-encode the owned slice -> all_gather int8+f16 -> decode

    Per-chip bytes drop from ~2N (ring all-reduce) to ~N + N/4. The gather
    half's quantization applies the same block-rounding the reference's
    transport does, so outputs match the f32 path within Q80 tolerance.

    x: [..., d_in] sharded over ``axis`` on its last dim; w: [d_in, d_out]
    (dense or PackedQ40) sharded over ``axis`` on d_in. Returns [..., d_out]
    replicated over ``axis``; needs d_out % (32 * mesh.shape[axis]) == 0.
    """
    from ..ops.linear import q40_matmul_local
    from ..quants.packed import PackedQ40

    n_shards = mesh.shape[axis]
    packed = isinstance(w, PackedQ40)
    d_out = w.d_out if packed else w.shape[-1]
    if d_out % (Q80_BLOCK * n_shards) != 0:
        raise ValueError(
            f"q80_sync_matmul needs d_out ({d_out}) divisible by "
            f"{Q80_BLOCK} * mesh.shape[{axis!r}] ({n_shards})"
        )
    nd = x.ndim

    def inner(xl, *wl):
        if packed:
            part = q40_matmul_local(xl, PackedQ40(*wl))
        else:
            part = xl @ wl[0]
        scat = jax.lax.psum_scatter(
            part, axis, scatter_dimension=nd - 1, tiled=True
        )  # [..., d_out / n] f32 — the reduce half stays full precision
        return _gather_q80(scat, axis, n_shards).astype(part.dtype)

    x_spec = P(*([None] * (nd - 1) + [axis]))
    w_specs = (
        (P(axis, None), P(axis, None)) if packed else (P(axis, None),)
    )
    w_args = (w.packed, w.scales) if packed else (w,)
    return shard_map(
        inner,
        mesh=mesh,
        in_specs=(x_spec,) + w_specs,
        out_specs=P(*([None] * nd)),
        check_vma=False,
    )(x, *w_args)
