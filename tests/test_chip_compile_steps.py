"""Whole decode forwards and chunks compiled for a described v5e
(tests/chip_compile_util.py): what the chip's compiler makes of a layer loop
over Q40 stacks (no plane sliced out, no lane of x split) and of each block's
caches, stacks and states (no copy), at the benchmark's widths."""

import jax
import jax.numpy as jnp
import pytest

from distributed_llama_multiusers_tpu.ops import linear, pallas_q40 as pq
from distributed_llama_multiusers_tpu.quants.packed import PackedQ40

from chip_compile_util import (  # noqa: F401  (v5e, v5e_devices: the fixtures)
    DEFAULT_MODE,
    _lane_splits,
    _pattern_decode_hlo,
    _results_of_shape,
    _three_layer_decode_hlo,
    engine_scales,
    v5e,
    v5e_devices,
)


@pytest.mark.parametrize("reads_stack", [True, False],
                         ids=["kernel_reads_stack", "control_scanned_planes"])
def test_layer_loop_slices_no_q40_plane_for_v5e(v5e, monkeypatch, reads_stack):
    """The optimized HLO of a three-layer decode forward at (4096, 14336) and
    the other planes of that width: no slice, fusion or copy has a nibble
    plane's shape as its result. The control scans the planes as the program
    did before PR 30, and shows the slices this check looks for. (Stacks as
    small as three layers XLA may stage WHOLE in fast memory ahead of the
    loop, by `slice-start`s of its own; that is not what is looked for.)
    Nine kernel calls: seven Q40 matmuls a layer body and the head, and since
    PR 32 the decode attention that reads the cache in place."""
    import re

    from distributed_llama_multiusers_tpu.models import llama

    if not reads_stack:
        monkeypatch.setattr(llama, "reads_q40_stack", lambda w: False)
    hlo, dims = _three_layer_decode_hlo(v5e, monkeypatch)
    d, h, kv = dims["d"], dims["h"], dims["kv"]
    assert hlo.count("tpu_custom_call") == 9
    # a plane sliced out for a kernel call: the result of a slice fusion of
    # its own (`[1, d_in/2, d_out]`: the kernel takes a plane as a stack of one)
    planes = {(a // 2, b) for a, b in ((d, d), (d, kv), (d, h), (h, d))}
    sliced = {(int(r), int(w)) for r, w in re.findall(
        r"= u8\[(?:1,)?(\d+),(\d+)\]\S* (?:fusion|dynamic-slice|copy)\(", hlo)}
    sliced &= planes
    if reads_stack:
        assert sliced == set(), sliced
    else:
        assert sliced == planes, sliced


@pytest.mark.parametrize("mode,splits", [(DEFAULT_MODE, False), ("blockdot", True)],
                         ids=["x_as_it_is", "control_block_dot_operands"])
def test_decode_forward_splits_no_activation_lane_for_v5e(v5e, monkeypatch, mode, splits):
    """The compiled three-layer decode forward at Mistral-7B's widths holds
    no `[16, 128, 16]` / `[16, 448, 16]` / `[16, 448, 2, 16]` array: the
    operations that were 3.65 ms of an 18.8 ms decode step (PERF.md section
    6, PR 42). The control compiles the same forward in the mode that still
    takes pre-split operands and finds them."""
    import re

    monkeypatch.setattr(pq, "DEQUANT_MODE", mode)
    hlo, dims = _three_layer_decode_hlo(v5e, monkeypatch)
    assert hlo.count("tpu_custom_call") == 9
    if not splits:
        # x reaches each of the eight Q40 kernels as the bf16 the model made
        # (its second operand, after the layer index): rounded once, for the
        # dot and for the block sums alike
        made = dict(re.findall(r"(%[\w.\-]+) = (\w+\[[\d,]*\])", hlo))
        x_ops = [made[ops.split(",")[1].strip()] for ops in re.findall(
            r"%_q40_matmul_\w+\.\d+ = \S+ custom-call\(([^)]*)\)", hlo)]
        widths = {dims["d"], dims["h"]}
        assert len(x_ops) == 8 and all(
            re.fullmatch(rf"bf16\[{dims['lanes']},(\d+)\]", x)
            and int(x.split(",")[1][:-1]) in widths for x in x_ops), x_ops
    blocks = {dims["d"] // 32, dims["h"] // 32}
    found = [s for s in _lane_splits(hlo)
             if int(s.split(",")[1]) in blocks and s.split("[")[1].startswith(f"{dims['lanes']},")]
    assert bool(found) == splits, found


@pytest.mark.parametrize("scales", [None, jnp.float16], ids=["as_the_engine_holds", "control_float16"])
@pytest.mark.parametrize("widths", [
    dict(layers=32, lanes=16, n_heads=32, n_kv=8),
    dict(layers=28, lanes=32, n_heads=28, n_kv=4),
], ids=["mistral", "qwen"])
def test_decode_forward_reads_scale_tiles_out_of_the_stack_for_v5e(v5e, monkeypatch, widths, scales):
    """A decode forward at Mistral-7B's and Qwen2.5-7B's widths, depth and
    lanes over the tree AS AN ENGINE HOLDS IT (``engine_scales``: the FFN's
    three scale stacks, two thirds of the scales' bytes, at rest as int16
    bits; the attention projections' stacks and the head's plane float16, as
    they arrived): the scale operand of the FFN's three kernel calls is the
    loop's own ``[L, d_in/32, d_out]`` stack, which no instruction slices,
    copies, converts or stages (their three ``bitcast-convert`` fusions were
    0.55 ms of a 13.7 ms Mistral decode step, PERF.md section 6, PR 55); the
    other five calls are handed one plane, sliced out and converted beside
    the call as the parent's were. The control hands the same forward
    float16 scales throughout, as a direct caller may: it still compiles, and
    slices and converts one plane a kernel call."""
    import re

    hlo, dims = _three_layer_decode_hlo(v5e, monkeypatch, seq=2048, scales=scales, **widths)
    assert hlo.count("tpu_custom_call") == 9
    converts = hlo.count(" bitcast-convert(")
    if scales is not None:
        assert converts == 8, converts
        return
    assert converts == 5, converts
    L, d, h, kv = (dims[k] for k in ("L", "d", "h", "kv"))
    ffn = sorted(f"s16[{L},{a // 32},{b}]" for a, b in ((d, h), (d, h), (h, d)))
    planes = sorted([f"s16[{d // 32},{d}]"] * 2 + [f"s16[{d // 32},{kv}]"] * 2
                    + [f"s16[{d // 32},8192]"])
    made = {name: (shape, op) for name, shape, op in re.findall(
        r"(%[\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\(", hlo)}
    scale_ops = [made[ops.split(",")[3].strip()] for ops in re.findall(
        r"%_q40_matmul_\w+\.\d+ = \S+ custom-call\(([^)]*)\)", hlo)]
    assert sorted(shape for shape, op in scale_ops if op == "get-tuple-element") == ffn, scale_ops
    assert sorted(shape for shape, op in scale_ops if op != "get-tuple-element") == planes, scale_ops
    # nothing makes an array of an FFN scale stack's size or of one of its planes
    assert not re.findall(
        rf"= (?:s16|f16)\[(?:{L}|1),(?:{d // 32},{h}|{h // 32},{d})\]\S* "
        r"(?!parameter\(|get-tuple-element\(|bitcast\()\S+?\(", hlo)


def _latent_decode_hlo(v5e, monkeypatch, lanes=32, seq=512):
    """The optimized HLO of three layers (one dense, two routed) of the
    benchmark's latent block at its published widths, one row a lane, the
    cache donated; and its dimensions."""
    from distributed_llama_multiusers_tpu.models import deepseek
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig
    from distributed_llama_multiusers_tpu.models.llama import KVCache
    from distributed_llama_multiusers_tpu.quants.packed import Q40Experts

    monkeypatch.setattr(linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas)
    L, Lm, E, d, vocab = 3, 2, 128, 2048, 8192
    cfg = LlamaConfig(
        dim=d, hidden_dim=6144, n_layers=L, n_heads=32, n_kv_heads=32, vocab_size=vocab,
        seq_len=seq, norm_epsilon=1e-6, n_experts=E, n_active_experts=6, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, moe_hidden_dim=768,
        shared_hidden_dim=1536, n_dense_layers=1, moe_score_func=1, moe_select_bias=1,
        moe_routed_scale=2.448)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    q40 = lambda d_in, d_out, lead: PackedQ40(
        packed=sds(lead + (d_in // 2, d_out), jnp.uint8),
        scales=sds(lead + (d_in // 32, d_out), engine_scales(lead + (d_in // 32, d_out))))
    experts = lambda d_in, d_out: Q40Experts(
        sds((Lm, E, d_in // 2, d_out), jnp.uint8), sds((Lm, E, d_in // 32, d_out), jnp.int16))
    params = deepseek.DeepseekParams(
        embedding=sds((vocab, d), jnp.bfloat16),
        attn=deepseek.LatentAttnParams(
            wq=q40(d, 32 * 192, (L,)), wkva=q40(d, 576, (L,)),
            wuk=sds((L, 32, 128, 512), jnp.bfloat16), wuv=sds((L, 32, 512, 128), jnp.bfloat16),
            wo=q40(32 * 128, d, (L,)),
            rms_att=sds((L, d), jnp.float32), rms_kv=sds((L, 512), jnp.float32)),
        dense=deepseek.DenseFfnParams(
            w1=q40(d, 6144, (1,)), w2=q40(6144, d, (1,)), w3=q40(d, 6144, (1,)),
            rms_ffn=sds((1, d), jnp.float32)),
        routed=deepseek.RoutedFfnParams(
            gate=sds((Lm, d, E), jnp.float32), bias=sds((Lm, E), jnp.float32),
            w1=experts(d, 768), w2=experts(768, d), w3=experts(d, 768),
            s1=q40(d, 1536, (Lm,)), s2=q40(1536, d, (Lm,)), s3=q40(d, 1536, (Lm,)),
            rms_ffn=sds((Lm, d), jnp.float32)),
        rms_final=sds((d,), jnp.float32), wcls=q40(d, vocab, ()),
        rope_cos=sds((seq, 32), jnp.float32), rope_sin=sds((seq, 32), jnp.float32))
    cache = KVCache(sds((L, lanes, seq, 512), jnp.bfloat16), sds((L, lanes, seq, 128), jnp.bfloat16))
    tok = sds((lanes, 1), jnp.int32)
    hlo = jax.jit(
        lambda p, t, c: deepseek.deepseek_forward(cfg, p, t, t, c), donate_argnums=(2,)
    ).lower(params, tok, cache).compile().as_text()
    return hlo, dict(L=L, E=E, lanes=lanes, seq=seq, heads=32)


def test_latent_decode_forward_copies_no_cache_and_no_expert_stack_for_v5e(v5e, monkeypatch):
    """Three layers (one dense, two routed) of the benchmark's latent block at
    its published widths, one row a lane, the cache donated: the kernels are
    there (wq, wkva, wo, since PR 59 the decode attention that reads the
    latent rows in place, and the dense FFN or the grouped and shared experts;
    the head), the latent stack is the result of its in-place scatters alone
    (no copy, no relayout: a size-one head axis cost four whole-stack copies,
    PR 33), and no expert plane leaves its stack."""
    import re

    hlo, dims = _latent_decode_hlo(v5e, monkeypatch)
    L, E, lanes, seq = (dims[k] for k in ("L", "E", "lanes", "seq"))
    # layer 0: wq, wkva, the decode attention, wo, w1, w3, w2; the scan's body:
    # wq, wkva, the decode attention, wo, three grouped products, the shared
    # experts' three; the head
    assert hlo.count("tpu_custom_call") == 18
    stack = rf"bf16\[{L},{lanes},{seq},512\]"
    # (a stack this small XLA may stage whole in fast memory by copy-start /
    # copy-done of its own, as the Llama block's test above notes; a plain
    # copy of it is what is looked for)
    assert not re.search(rf"= {stack}\S* copy\(", hlo)
    assert f"= u8[{E},1024,768]" not in hlo and f"= u8[{E},384,2048]" not in hlo


def _latent_plane_results(hlo: str, L: int, lanes: int, seq: int, heads: int) -> tuple[list, list]:
    """(what makes an array of the size of one layer's latent or rope plane,
    bf16 or float32, or of a stack; the float32 ``[lanes, heads, S]`` score
    and probability tensors, in any order of their axes)."""
    import re

    planes = _results_of_shape(
        hlo, rf"(?:bf16|f32)\[(?:{L},|1,)?{lanes},{seq},(?:512|128)\]")
    scores = sorted(set(re.findall(
        rf"f32\[{lanes},(?:1,)?(?:{heads},(?:1,)?{seq}|{seq},(?:1,)?{heads})\]", hlo)))
    return planes, scores


@pytest.mark.parametrize("in_place", [True, False],
                         ids=["kernel_reads_in_place", "control_plane_reads"])
def test_latent_decode_forward_reads_no_latent_plane_for_v5e(v5e, monkeypatch, in_place):
    """The same three layers at the cell's cache (32 lanes of 2048 positions):
    a ``decode_attention`` kernel in layer 0 and in the scan's body, and
    nothing has a ``[32, 2048, 512]`` latent plane, a ``[32, 2048, 128]`` rope
    plane or a stack as its result but the in-place appends, nor is there a
    float32 ``[32, 32, 2048]`` score: the kernel is handed the carry. The
    control patches the predicate off, as the program was before PR 59, and
    shows what the check looks for: each layer's planes read out of the stack
    and scores over every position (5.45 of Kanana's 22.45 ms decode step on a
    v5e: PERF.md section 6, PR 59)."""
    import re

    from distributed_llama_multiusers_tpu.models import deepseek

    if not in_place:
        monkeypatch.setattr(deepseek, "decode_attention_engages", lambda *a, **kw: False)
    hlo, dims = _latent_decode_hlo(v5e, monkeypatch, seq=2048)
    kernels = len(re.findall(
        r'custom-call\(.*custom_call_target="tpu_custom_call".*decode_attention', hlo))
    planes, scores = _latent_plane_results(
        hlo, dims["L"], dims["lanes"], dims["seq"], dims["heads"])
    if in_place:
        assert kernels == 2 and planes == [] and scores == [], (kernels, planes, scores)
    else:
        assert kernels == 0 and scores, scores
        reads = [m for m in planes if "dynamic-slice" in m or "fusion" in m or "convert" in m]
        assert len(reads) >= 2, planes  # the latent plane, in layer 0 and in the body


def test_pattern_decode_forward_copies_no_cache_no_state_and_no_expert_stack_for_v5e(v5e, monkeypatch):
    """Eight layers of the benchmark's layer-pattern block at its published
    widths (two dense, then one whole period of routed layers and an odd tail
    of two; six conv and two attention layers), one row a lane, the cache
    donated: the kernels are there, the K/V stack and the conv state stack are
    the results of their in-place writes alone, and no expert plane leaves its
    stack. With the head's 64 as the K/V stack's last axis XLA gave the stack
    another layout inside the loop and copied it whole, in and out (PR 35):
    the stack keeps ``n_kv * head`` merged."""
    import re

    hlo, dims = _pattern_decode_hlo(v5e, monkeypatch, periods=2, seq=512)
    La, Lc, Lm, E, d, lanes, seq = (dims[k] for k in ("La", "Lc", "Lm", "E", "d", "lanes", "seq"))
    # two dense layers: conv_in, conv_out, w1, w3, w2 each; the scan's body, one
    # period: 3 conv layers of 2 + 1 attention layer of 4 and its decode
    # attention (PR 36), and 4 x 3 grouped products; the tail: an attention (4
    # and its decode attention) and a conv layer, 2 x 3 grouped; the head
    assert hlo.count("tpu_custom_call") == 10 + (6 + 5 + 12) + (5 + 2 + 6) + 1
    for stack in (rf"bf16\[{La},{lanes},{seq},512\]", rf"bf16\[{Lc},{lanes},{2 * d}\]"):
        assert not re.search(rf"= {stack}\S* copy\(", hlo), stack
    assert f"= u8[{E},1024,1536]" not in hlo and f"= u8[{E},768,2048]" not in hlo
    assert f"= u8[{Lm},{E},1024,1536]" not in hlo.split("ENTRY")[0]


def _deepseek_v32_cell_program(v5e, monkeypatch, b: int, t: int):
    """The optimized HLO of the benchmark's deepseek-v3.2 configuration at the
    cell's own depth, widths and cache (9 layers, 16 of 256 experts held, 8
    lanes of 32768 positions), ``b`` lanes of ``t`` rows, the cache donated;
    and its configuration. The arrays are the family generator's shapes."""
    import latent_toy
    from distributed_llama_multiusers_tpu.models import deepseek
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    path = list(__import__("sys").path)
    __import__("sys").path[:0] = [latent_toy.BENCH_DIR, latent_toy.ROOT]
    try:
        from harness import cells

        bench = cells.load_benchmark()
        cfg = cells.load_config_file(bench, "deepseek-v3.2")
        family = cells.load_family(cfg)
    finally:
        __import__("sys").path[:] = path
    config = family.program_config(cfg)
    monkeypatch.setattr(linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas)
    monkeypatch.setattr(linear, "pallas_kernel_active", lambda: True)
    monkeypatch.setattr(deepseek, "pallas_kernel_active", lambda: True)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), tree)
    arrays = jax.eval_shape(
        lambda k: family._generate(config, k, jnp.bfloat16, padded_d_out(config.vocab_size)),
        jax.random.PRNGKey(0))
    params = on_chip(jax.eval_shape(lambda a: family.assemble_params(config, a), arrays))
    cache = on_chip(jax.eval_shape(lambda: deepseek.init_latent_cache(config, b, jnp.bfloat16)))
    tok = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=v5e)
    hlo = jax.jit(
        lambda p, tk, c: deepseek.deepseek_forward_counted(config, p, tk, tk, c),
        donate_argnums=(2,),
    ).lower(params, tok, cache).compile().as_text()
    return hlo, config


def test_sparse_latent_decode_copies_no_cache_stack_and_reads_no_whole_plane_for_v5e(v5e, monkeypatch):
    """One row a lane at the cell's 8 lanes: none of the three cache stacks
    (latent, rope, index keys) is copied or re-laid (each is the result of its
    in-place scatters alone), attention gathers the chosen rows out of the
    stacks as they sit (no ``[lanes, S, 512]`` latent plane is sliced out to
    gather from, no ``[lanes, S, 640]`` float32 plane is made to attend), and
    the kernels are there."""
    import re

    hlo, c = _deepseek_v32_cell_program(v5e, monkeypatch, 8, 1)
    L, lanes, S = c.n_layers, 8, c.seq_len
    for width in (c.kv_lora_rank, 128, c.index_head_dim):
        assert not re.search(rf"= bf16\[{L},{lanes},{S},{width}\]\S* copy\(", hlo), width
    assert not re.search(rf"f32\[{lanes},{S},(640|512|576)\]", hlo)
    assert not re.search(rf"= bf16\[{lanes},{S},{c.kv_lora_rank}\]\S* (fusion|copy)\(", hlo)
    # the dense layer and the scan's body: q_a, q_b, kv_a, the indexer's two,
    # wo, and a dense or a routed-and-shared FFN; the head
    assert hlo.count("tpu_custom_call") == 22
    assert "approx" not in hlo.lower()


def test_sparse_latent_chunk_compiles_for_v5e_and_gathers_in_blocks(v5e, monkeypatch):
    """A 1024-row chunk against the cell's 32768-position lane: the chip's
    compiler takes it, the lane's three stacks are copied nowhere, no
    ``[1024, index_topk, 512]`` block of every query's rows exists at once (a
    block of queries at a time), and the selection is a sort, never the
    approximate top-k."""
    import re

    hlo, c = _deepseek_v32_cell_program(v5e, monkeypatch, 1, 1024)
    L, S = c.n_layers, c.seq_len
    for width in (c.kv_lora_rank, 128, c.index_head_dim):
        assert not re.search(rf"= bf16\[{L},1,{S},{width}\]\S* copy\(", hlo), width
    assert not re.search(rf"\[(1,)?1024,{c.index_topk},(512|128|640)\]", hlo)
    assert re.search(rf"\[(1,)?256,{c.index_topk},512\]", hlo)  # one block's gathered rows
    assert " sort(" in hlo and "approx" not in hlo.lower()

