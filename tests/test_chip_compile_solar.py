"""The Solar-Open2 cell's step programs, compiled ahead of time for a described
TPU v5e (tests/chip_compile_util.py says what such a compile proves): the
decode step at the cell's 16 lanes, whose nine delta-rule layers update their
float32 matrix state in place through the one-row kernel beside three gated
NoPE planes read in place, and a 512-row chunk against a 32768-position lane,
whose recurrence runs in the chunk form. Published widths: 64 delta heads of
128, 64 query and 8 kv heads of 128 on 4096, 40 held experts of 320, 1280 wide."""

import re

import jax
import jax.numpy as jnp
from chip_compile_util import v5e, v5e_devices  # noqa: F401

from distributed_llama_multiusers_tpu.ops import linear, pallas_q40 as pq


def _solar_cell_program(v5e, monkeypatch, b: int, t: int):
    """The optimized HLO of the benchmark's solar-open2-250b configuration at
    the cell's own depth, widths and cache, ``b`` lanes of ``t`` rows, the
    cache donated; its configuration, and its compiled memory analysis."""
    import sys

    import latent_toy
    from distributed_llama_multiusers_tpu.models import deepseek, hybrid, llama
    from distributed_llama_multiusers_tpu.ops import delta_rule
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    path = list(sys.path)
    sys.path[:0] = [latent_toy.BENCH_DIR, latent_toy.ROOT]
    try:
        from harness import cells

        bench = cells.load_benchmark()
        cfg = cells.load_config_file(bench, "solar-open2-250b")
        family = cells.load_family(cfg)
    finally:
        sys.path[:] = path
    config = family.program_config(cfg)
    monkeypatch.setattr(linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas)
    for mod in (linear, deepseek, hybrid, llama, delta_rule):
        monkeypatch.setattr(mod, "pallas_kernel_active", lambda: True)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), tree)
    arrays = jax.eval_shape(
        lambda k: family._generate(config, k, jnp.bfloat16, padded_d_out(config.vocab_size)),
        jax.random.PRNGKey(0))
    params = on_chip(jax.eval_shape(lambda a: family.assemble_params(config, a), arrays))
    cache = on_chip(jax.eval_shape(
        lambda: hybrid.init_hybrid_cache(config, b, jnp.bfloat16, max_chunk=512)))
    tok = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=v5e)
    nv = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=v5e)
    compiled = jax.jit(
        lambda p, tk, c, n: hybrid.hybrid_forward_counted(config, p, tk, tk, c, n_valid=n),
        donate_argnums=(2,),
    ).lower(params, tok, cache, nv).compile()
    return compiled.as_text(), config, compiled.memory_analysis()


def test_solar_decode_updates_the_state_in_place_for_v5e(v5e, monkeypatch):
    """One row a lane at the cell's 16 lanes: Mosaic takes the one-row state
    kernel at 64 heads of 128; neither the state's stack, the conv windows'
    nor the planes' is copied or sliced out whole, no ``[128, 128]`` form of a
    lane's matrices is made beside the flat one; the temporaries are a step's,
    not a cache's."""
    hlo, c, mem = _solar_cell_program(v5e, monkeypatch, 16, 1)
    state = c.delta_n_heads * c.delta_head_dim ** 2
    window = (c.delta_conv_kernel - 1) * 3 * c.delta_dim
    for stack in (rf"f32\[{c.n_delta_layers},16,{state}\]",
                  rf"bf16\[{c.n_delta_layers},16,{window}\]",
                  rf"bf16\[{c.n_attention_layers},16,{c.seq_len},1024\]"):
        assert not re.search(rf"= {stack}\S* copy\(", hlo), stack
    assert not re.search(rf"= f32\[16,{state}\]\S* (fusion|copy|dynamic-slice)\(", hlo)
    assert not re.search(r"f32\[16,64,128,128\]", hlo)
    assert not re.search(rf"= (bf16|f32)\[16,{c.seq_len},1024\]\S* (fusion|copy|dynamic-slice)\(", hlo)
    assert "delta_rule_step" in hlo and "decode_attention" in hlo
    assert mem.temp_size_in_bytes < 1 << 30, mem.temp_size_in_bytes


def test_solar_chunk_holds_no_state_by_time_for_v5e(v5e, monkeypatch):
    """A 512-row chunk against the cell's lane: no tensor has the chunk's rows
    and the matrix state's axes (``[512, 64, 128, 128]`` float32 is 2 GB), the
    lane's stacks are copied nowhere, and the temporaries (a chunk's decayed
    scores among them) stay under a gigabyte and a half."""
    hlo, c, mem = _solar_cell_program(v5e, monkeypatch, 1, 512)
    assert not re.search(r"\[(1,)?(512|16,32|32,16),64,128,128\]", hlo)
    assert not re.search(r"\[(16,)?(1,)?64,(512|16,32|32),128,128\]", hlo)
    state = c.delta_n_heads * c.delta_head_dim ** 2
    for stack in (rf"f32\[{c.n_delta_layers},1,{state}\]",
                  rf"bf16\[{c.n_attention_layers},1,{c.seq_len},1024\]"):
        assert not re.search(rf"= {stack}\S* copy\(", hlo), stack
    assert mem.temp_size_in_bytes < 3 << 29, mem.temp_size_in_bytes
