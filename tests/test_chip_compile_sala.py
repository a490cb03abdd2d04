"""The MiniCPM-SALA cell's step programs, compiled ahead of time for a
described TPU v5e (tests/chip_compile_util.py says what such a compile
proves): the decode step at the cell's 16 lanes, whose lightning layers update
their float32 matrix state in place and whose sparse layers fetch the blocks
they chose out of their planes, and a 1024-row chunk against a 32768-position
lane, whose recurrence runs in chunks and whose rows choose row by row.
Published widths: 32 heads of 128 on 4096, 2 kv heads, 32 layers, a 16384 MLP."""

import re

import jax
import jax.numpy as jnp
import pytest
from chip_compile_util import v5e, v5e_devices  # noqa: F401

from distributed_llama_multiusers_tpu.ops import linear, pallas_q40 as pq


def _sala_cell_program(v5e, monkeypatch, b: int, t: int):
    """The optimized HLO of the benchmark's minicpm-sala configuration at the
    cell's own depth, widths and cache, ``b`` lanes of ``t`` rows, the cache
    donated; its configuration, and its compiled memory analysis."""
    import sys

    import latent_toy
    from distributed_llama_multiusers_tpu.models import deepseek, hybrid, llama
    from distributed_llama_multiusers_tpu.ops import linear_attention
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    path = list(sys.path)
    sys.path[:0] = [latent_toy.BENCH_DIR, latent_toy.ROOT]
    try:
        from harness import cells

        bench = cells.load_benchmark()
        cfg = cells.load_config_file(bench, "minicpm-sala")
        family = cells.load_family(cfg)
    finally:
        sys.path[:] = path
    config = family.program_config(cfg)
    monkeypatch.setattr(linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas)
    for mod in (linear, deepseek, hybrid, llama, linear_attention):
        monkeypatch.setattr(mod, "pallas_kernel_active", lambda: True)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), tree)
    arrays = jax.eval_shape(
        lambda k: family._generate(config, k, jnp.bfloat16, padded_d_out(config.vocab_size)),
        jax.random.PRNGKey(0))
    params = on_chip(jax.eval_shape(lambda a: family.assemble_params(config, a), arrays))
    cache = on_chip(jax.eval_shape(lambda: hybrid.init_hybrid_cache(config, b, jnp.bfloat16)))
    tok = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=v5e)
    nv = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=v5e)
    compiled = jax.jit(
        lambda p, tk, c, n: hybrid.hybrid_forward_counted(config, p, tk, tk, c, n_valid=n),
        donate_argnums=(2,),
    ).lower(params, tok, cache, nv).compile()
    return compiled.as_text(), config, compiled.memory_analysis()


def test_sala_decode_updates_the_state_and_reads_chosen_blocks_in_place_for_v5e(v5e, monkeypatch):
    """One row a lane at the cell's 16 lanes: Mosaic takes the one-row state
    kernel and the chosen-blocks kernel; neither the state's stack nor the
    planes' nor the compressed keys' is copied or sliced out whole; the
    temporaries are a step's, not a cache's."""
    hlo, c, mem = _sala_cell_program(v5e, monkeypatch, 16, 1)
    state = c.linear_n_heads * c.linear_head_dim ** 2
    for stack in (rf"f32\[{c.n_linear_layers},16,{state}\]",
                  rf"bf16\[{c.n_sparse_layers},16,{c.seq_len},256\]",
                  rf"bf16\[{c.n_sparse_layers},16,{c.seq_len // 16},256\]"):
        assert not re.search(rf"= {stack}\S* copy\(", hlo), stack
    assert not re.search(rf"= f32\[16,{state}\]\S* (fusion|copy|dynamic-slice)\(", hlo)
    assert not re.search(rf"= (bf16|f32)\[16,{c.seq_len},256\]\S* (fusion|copy|dynamic-slice)\(", hlo)
    assert "linear_attention_step" in hlo and "sparse_decode_attention" in hlo
    assert mem.temp_size_in_bytes < 1 << 30, mem.temp_size_in_bytes


def test_sala_chunk_holds_no_state_by_time_and_no_scores_by_context_for_v5e(v5e, monkeypatch):
    """A 1024-row chunk against the cell's lane: no tensor has the chunk's rows
    and the matrix state's axes (``[1024, 32, 128, 128]`` float32 is 2 GB), nor
    the chunk's rows, the heads and a whole plane's keys; the lane's stacks are
    copied nowhere; the temporaries stay under a gigabyte and a half."""
    hlo, c, mem = _sala_cell_program(v5e, monkeypatch, 1, 1024)
    assert not re.search(r"\[(1,)?(1024|8,128|128,8),32,128,128\]", hlo)
    assert not re.search(r"\[(1,)?32,(1024|8,128|128,8),128,128\]", hlo)
    heads = "(2,16|16,2|32)"
    assert not re.search(rf"1024,{heads},{c.seq_len}\]", hlo)
    assert not re.search(rf"{heads},1024,{c.seq_len}\]", hlo)
    state = c.linear_n_heads * c.linear_head_dim ** 2
    for stack in (rf"f32\[{c.n_linear_layers},1,{state}\]",
                  rf"bf16\[{c.n_sparse_layers},1,{c.seq_len},256\]"):
        assert not re.search(rf"= {stack}\S* copy\(", hlo), stack
    assert mem.temp_size_in_bytes < 3 << 29, mem.temp_size_in_bytes
