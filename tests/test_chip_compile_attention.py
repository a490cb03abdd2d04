"""Attention in place (ops/pallas_attention.py: decode width, PR 32; the
layer-pattern block's merged stack, PR 36; prefill width, PR 51; latent rows,
PR 59), compiled for
a described v5e (tests/chip_compile_util.py)."""

import jax
import jax.numpy as jnp
import pytest

from chip_compile_util import (  # noqa: F401  (v5e, v5e_devices: the fixtures)
    STACK_LAYERS,
    _cache_sized_results,
    _pattern_decode_hlo,
    _results_of_shape,
    _three_layer_decode_hlo,
    v5e,
    v5e_devices,
)

# Decode attention in place (PR 32, ops/pallas_attention.py): (lanes, n_heads,
# n_kv) of the benchmark's two configurations at their cells' lanes, bf16,
# 2048 positions
ATTENTION_SHAPES = [(16, 32, 8), (32, 28, 4)]


@pytest.mark.parametrize("lanes,n_heads,n_kv", ATTENTION_SHAPES,
                         ids=["mistral7b", "qwen25_7b"])
def test_decode_attention_compiles_for_v5e(v5e, lanes, n_heads, n_kv):
    """Mosaic takes the kernel at both head shapes, the stack of a few layers
    as the carry holds it, the layer and the work list traced."""
    from distributed_llama_multiusers_tpu.ops import pallas_attention as pa

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    stack = sds((STACK_LAYERS, lanes, 2048, n_kv, 128), jnp.bfloat16)

    def attend(q, k, v, layer, positions):
        return pa.decode_attention(
            q, k, v, layer, pa.lane_blocks(positions, 2048), 128 ** -0.5)

    hlo = jax.jit(attend).lower(
        sds((lanes, n_heads, 128), jnp.bfloat16), stack, stack,
        sds((), jnp.int32), sds((lanes,), jnp.int32),
    ).compile().as_text()
    assert "tpu_custom_call" in hlo and "decode_attention" in hlo
    # merging (S, n_kv) for the kernel moved no byte of either stack
    assert not _cache_sized_results(hlo, STACK_LAYERS, lanes, 2048, n_kv)


def test_latent_decode_attention_compiles_for_v5e(v5e):
    """Mosaic takes the latent form (PR 59) at Kanana's cell shapes: 32 lanes
    of 2048 positions, 32 heads against a 512-wide latent row and its 128-wide
    rope leaf, the stacks of a few layers as the carry holds them, the layer
    and the work list traced; and neither stack is moved for it."""
    from distributed_llama_multiusers_tpu.ops import pallas_attention as pa

    lanes, n_heads, rank, rope_leaf = 32, 32, 512, 128
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    latent = sds((STACK_LAYERS, lanes, 2048, rank), jnp.bfloat16)
    rope = sds((STACK_LAYERS, lanes, 2048, rope_leaf), jnp.bfloat16)
    assert pa.supports(latent, n_heads, None, rope, latent=True)

    def attend(q, c, r, layer, positions):
        return pa.decode_attention(
            q, c, r, layer, pa.lane_blocks(positions, 2048), 192 ** -0.5, latent=True)

    hlo = jax.jit(attend).lower(
        sds((lanes, n_heads, rank + rope_leaf), jnp.bfloat16), latent, rope,
        sds((), jnp.int32), sds((lanes,), jnp.int32),
    ).compile().as_text()
    assert "tpu_custom_call" in hlo and "decode_attention" in hlo
    assert not _results_of_shape(
        hlo, rf"(?:bf16|f32)\[(?:{STACK_LAYERS},|1,)?{lanes},2048,(?:{rank}|{rope_leaf})\]")


@pytest.mark.parametrize("in_place", [True, False],
                         ids=["kernel_reads_in_place", "control_plane_reads"])
@pytest.mark.parametrize("lanes,n_heads,n_kv", ATTENTION_SHAPES,
                         ids=["mistral7b", "qwen25_7b"])
def test_decode_forward_reads_no_kv_plane_for_v5e(
        v5e, monkeypatch, lanes, n_heads, n_kv, in_place):
    """The optimized HLO of a three-layer decode forward: nothing has a K or V
    plane, or the stack, as its result but the two in-place appends: the
    kernel is handed the carry. The control patches the kernel's predicate
    off, as the program was before PR 32, and shows what the check looks
    for: each plane read out of the stack (and, at 4 kv heads, copied)."""
    from distributed_llama_multiusers_tpu.models import llama

    if not in_place:
        monkeypatch.setattr(llama, "decode_attention_engages",
                            lambda cache, mesh, n_heads: False)
    hlo, dims = _three_layer_decode_hlo(v5e, monkeypatch, lanes, n_heads, n_kv)
    assert hlo.count("decode_attention") >= int(in_place)
    made = _cache_sized_results(hlo, dims["L"], lanes, dims["seq"], n_kv)
    if in_place:
        assert made == [], made
    else:
        reads = [m for m in made if "dynamic-slice" in m or "fusion" in m]
        assert len(reads) >= 2, made  # K's plane and V's


def _merged_plane_results(hlo: str, La: int, lanes: int, seq: int, n_kv: int, hd: int) -> list[str]:
    """What makes an array of the size of one K or V plane of the merged
    stack (in the carry's shape or split by head, bf16 or float32) or of the
    stack itself (``_results_of_shape``)."""
    lead = rf"(?:{La},|1,)?{lanes},{seq},"
    return _results_of_shape(
        hlo, rf"(?:bf16|f32)\[{lead}(?:{n_kv * hd}|{n_kv},{hd}|{n_kv},1,{hd})\]")


@pytest.mark.parametrize("in_place", [True, False],
                         ids=["kernel_reads_in_place", "control_plane_reads"])
def test_pattern_decode_forward_reads_no_kv_plane_for_v5e(v5e, monkeypatch, in_place):
    """The layer-pattern block at the benchmark's depth and cache (20 layers,
    five of them attention; 64 lanes x 2048 positions x 8 heads of 64, merged
    to rows of 512): the optimized decode forward holds a ``decode_attention``
    kernel for each attention instance (the scan's period body and the odd
    tail) and nothing has a ``[64, 2048, 512]`` plane, a float32 plane or the
    ``[5, 64, 2048, 512]`` stack as its result but the in-place appends. The
    control patches the predicate off, as the program was before PR 36, and
    shows what the check looks for: each instance's K and V planes read out
    of the stack and converted."""
    import re

    from distributed_llama_multiusers_tpu.models import hybrid

    if not in_place:
        monkeypatch.setattr(hybrid, "decode_attention_engages", lambda *a: False)
    hlo, dims = _pattern_decode_hlo(v5e, monkeypatch, periods=5, seq=2048)
    kernels = len(re.findall(r'custom-call\(.*custom_call_target="tpu_custom_call".*decode_attention', hlo))
    made = _merged_plane_results(hlo, dims["La"], dims["lanes"], dims["seq"], 8, 64)
    if in_place:
        assert kernels == 2 and made == [], (kernels, made)
    else:
        assert kernels == 0
        reads = [m for m in made if "dynamic-slice" in m or "fusion" in m or "convert" in m]
        assert len(reads) >= 4, made  # K's plane and V's, in the body and in the tail


# ---- prefill width (PR 51) ---------------------------------------------------

PREFILL_ROWS = [64, 256, 512, 1024]


@pytest.mark.parametrize("rows", PREFILL_ROWS)
@pytest.mark.parametrize("n_heads,n_kv,hd,merged", [
    (32, 8, 128, False), (28, 4, 128, False), (32, 8, 64, True), (20, 1, 128, True)],
    ids=["mistral7b", "qwen25_7b", "lfm2_merged", "jamba_merged"])
def test_prefill_attention_compiles_for_v5e(v5e, n_heads, n_kv, hd, merged, rows):
    """Mosaic takes the prefill kernel at the cells' head shapes and at every
    prefill bucket: one lane of a stack of a few layers as the carry holds it
    (a layer-pattern block's with the heads merged into the row), the layer,
    the positions and the work list traced."""
    from distributed_llama_multiusers_tpu.ops import pallas_attention as pa

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    stack = sds((STACK_LAYERS, 1, 2048) + ((n_kv * hd,) if merged else (n_kv, hd)),
                jnp.bfloat16)
    assert pa.supports_prefill(stack, n_heads, n_kv)

    def attend(q, k, v, layer, positions, n_valid):
        work = pa.chunk_blocks(positions, n_valid, 2048, pa.query_rows(rows))
        return pa.prefill_attention(q, k, v, layer, work, hd ** -0.5)

    hlo = jax.jit(attend).lower(
        sds((1, rows, n_heads, hd), jnp.bfloat16), stack, stack,
        sds((), jnp.int32), sds((1, rows), jnp.int32), sds((1,), jnp.int32),
    ).compile().as_text()
    assert "tpu_custom_call" in hlo and "prefill_attention" in hlo


def _scores_and_planes(hlo: str, L: int, seq: int, n_kv: int, rows: int) -> tuple[list, list]:
    """(float32 arrays with both a ``rows`` and a ``seq`` axis, what makes an
    array of the size of one lane's K or V plane or of its stack) in a prefill
    forward of one lane."""
    import re

    scores = sorted(set(re.findall(
        rf"f32\[(?:\d+,)*(?:{rows},(?:\d+,)*{seq}|{seq},(?:\d+,)*{rows})(?:,\d+)*\]", hlo)))
    planes = _cache_sized_results(hlo, L, 1, seq, n_kv) + _results_of_shape(
        hlo, rf"(?:bf16|f32)\[(?:{L * seq * n_kv}|{seq * n_kv}),128\]")
    return scores, planes


@pytest.mark.parametrize("in_place", [True, False],
                         ids=["kernel_reads_in_place", "control_dense_scores"])
@pytest.mark.parametrize("n_heads,n_kv", [(32, 8), (28, 4)], ids=["mistral7b", "qwen25_7b"])
def test_prefill_forward_forms_no_dense_scores_for_v5e(v5e, monkeypatch, n_heads, n_kv, in_place):
    """The optimized HLO of a three-layer 1024-row prefill forward of one lane
    of a 2048-position configuration: no float32 tensor has both a ``T`` and an
    ``S`` axis and nothing has a K or V plane, or the stack, as its result but
    the in-place appends: the kernel is handed the carry. The control patches
    the predicate off, as the program was before PR 51, and shows what the
    check looks for: ``[T, heads, S]`` scores and each plane read out of the
    stack and converted."""
    from distributed_llama_multiusers_tpu.models import llama

    if not in_place:
        monkeypatch.setattr(llama, "prefill_attention_engages", lambda *a: False)
    rows, seq = 1024, 2048
    hlo, dims = _three_layer_decode_hlo(
        v5e, monkeypatch, lanes=1, n_heads=n_heads, n_kv=n_kv, rows=rows, seq=seq)
    scores, planes = _scores_and_planes(hlo, dims["L"], seq, n_kv, rows)
    assert hlo.count("prefill_attention") >= int(in_place)
    if in_place:
        assert scores == [] and planes == [], (scores, planes)
    else:
        assert "prefill_attention" not in hlo
        assert scores and len(planes) >= 2, (scores, planes)
