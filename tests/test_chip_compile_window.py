"""The Command A+ cell's step programs, compiled ahead of time for a described
TPU v5e (tests/chip_compile_util.py says what such a compile proves): the
decode step at the cell's 16 lanes, whose window layers read their rings and
whose full-context layers read their planes in place, and a 512-row chunk
against a 32768-position lane, whose attention is computed a key block at a
time. Published widths: 128 query heads of 128 in groups of 16 on merged rows
of 1024 (``pallas_attention.MAX_ROW_WIDTH``), slabs of 4096 x 4096."""

import re

import jax
import jax.numpy as jnp
import pytest
from chip_compile_util import v5e, v5e_devices  # noqa: F401

from distributed_llama_multiusers_tpu.ops import linear, pallas_q40 as pq


def _command_a_cell_program(v5e, monkeypatch, b: int, t: int, n_valid=None):
    """The optimized HLO of the benchmark's command-a-plus-05-2026
    configuration at the cell's own depth, widths and cache (8 layers, 16 of
    128 experts held, lanes of 32768 positions, rings of 4608 rows), ``b``
    lanes of ``t`` rows, the cache donated; its configuration, and its
    compiled memory analysis."""
    import sys

    import latent_toy
    from distributed_llama_multiusers_tpu.models import deepseek, hybrid
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    path = list(sys.path)
    sys.path[:0] = [latent_toy.BENCH_DIR, latent_toy.ROOT]
    try:
        from harness import cells

        bench = cells.load_benchmark()
        cfg = cells.load_config_file(bench, "command-a-plus-05-2026")
        family = cells.load_family(cfg)
    finally:
        sys.path[:] = path
    config = family.program_config(cfg)
    monkeypatch.setattr(linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas)
    for mod in (linear, deepseek, hybrid):
        monkeypatch.setattr(mod, "pallas_kernel_active", lambda: True)
    from distributed_llama_multiusers_tpu.models import llama
    monkeypatch.setattr(llama, "pallas_kernel_active", lambda: True)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), tree)
    arrays = jax.eval_shape(
        lambda k: family._generate(config, k, jnp.bfloat16, padded_d_out(config.vocab_size)),
        jax.random.PRNGKey(0))
    params = on_chip(jax.eval_shape(lambda a: family.assemble_params(config, a), arrays))
    buckets = cfg["serving"]["prefill_buckets"]
    cache = on_chip(jax.eval_shape(
        lambda: hybrid.init_hybrid_cache(config, b, jnp.bfloat16, max_chunk=max(buckets))))
    tok = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=v5e)
    nv = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=v5e)
    compiled = jax.jit(
        lambda p, tk, c, n: hybrid.hybrid_forward_counted(config, p, tk, tk, c, n_valid=n),
        donate_argnums=(2,),
    ).lower(params, tok, cache, nv).compile()
    return compiled.as_text(), config, compiled.memory_analysis()


def test_command_a_decode_reads_rings_and_planes_in_place_for_v5e(v5e, monkeypatch):
    """One row a lane at the cell's 16 lanes: Mosaic takes the decode kernel at
    128 heads in groups of 16 on 1024-wide merged rows, with either work list;
    neither the planes' stack nor the rings' is copied or sliced out whole;
    the temporaries are a step's, not a cache's."""
    hlo, c, mem = _command_a_cell_program(v5e, monkeypatch, 16, 1)
    ring = 4608
    for stack in (rf"bf16\[{c.n_attention_layers},16,{c.seq_len},1024\]",
                  rf"bf16\[{c.n_window_layers},16,{ring},1024\]"):
        assert not re.search(rf"= {stack}\S* copy\(", hlo), stack
    assert not re.search(rf"= (bf16|f32)\[16,({c.seq_len}|{ring}),1024\]\S* "
                         r"(fusion|copy|dynamic-slice)\(", hlo)
    assert hlo.count("decode_attention") >= 2
    assert mem.temp_size_in_bytes < 1 << 30, mem.temp_size_in_bytes


def test_command_a_chunk_makes_no_scores_by_context_for_v5e(v5e, monkeypatch):
    """A 512-row chunk against the cell's lane: no tensor has the chunk's rows
    and a whole plane's or ring's keys (``[T, heads, S]`` scores would be 8.6
    GB in a full-context layer, 1.2 GB in a window layer); the lane's stacks
    are copied nowhere; the temporaries stay under a gigabyte."""
    hlo, c, mem = _command_a_cell_program(v5e, monkeypatch, 1, 512)
    # (the logits are [1, 512, 32768] too: the vocabulary slice is as long as
    # the context; a score tensor also has the heads)
    heads = "(8,16|16,8|128)"
    assert not re.search(rf"512,{heads},({c.seq_len}|4608)\]", hlo)
    assert not re.search(rf"{heads},512,({c.seq_len}|4608)\]", hlo)
    assert re.search(r"f32\[1,512,8,16,256\]", hlo)  # one block's scores
    for stack in (rf"bf16\[{c.n_attention_layers},1,{c.seq_len},1024\]",
                  rf"bf16\[{c.n_window_layers},1,4608,1024\]"):
        assert not re.search(rf"= {stack}\S* copy\(", hlo), stack
    assert mem.temp_size_in_bytes < 1 << 30, mem.temp_size_in_bytes
