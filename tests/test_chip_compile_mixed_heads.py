"""The MiMo-V2-Flash cell's step programs, compiled ahead of time for a
described TPU v5e (tests/chip_compile_util.py says what such a compile
proves): the decode step at the cell's 16 lanes, whose window layers read
their rings through the decode kernel with the sink and whose full-context
layers read their planes in place, keys 192 wide beside values of 128 on
merged rows of 768 / 512 and 1536 / 1024 (``pallas_attention.MAX_ROW_WIDTH``);
and a 512-row chunk against a 32768-position lane, a key block at a time."""

import re

import jax
import jax.numpy as jnp
from chip_compile_util import v5e, v5e_devices  # noqa: F401

from distributed_llama_multiusers_tpu.ops import linear, pallas_q40 as pq

RING = 768  # the window of 128 and the largest bucket, 512, in whole blocks of 256


def _mimo_cell_program(v5e, monkeypatch, b: int, t: int):
    """The optimized HLO of the benchmark's mimo-v2-flash configuration at the
    cell's own depth, widths and cache (16 layers, 16 of 256 experts held,
    lanes of 32768 positions, rings of 768 rows), ``b`` lanes of ``t`` rows,
    the cache donated; its configuration, and its compiled memory analysis."""
    import sys

    import latent_toy
    from distributed_llama_multiusers_tpu.models import deepseek, hybrid, llama
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    path = list(sys.path)
    sys.path[:0] = [latent_toy.BENCH_DIR, latent_toy.ROOT]
    try:
        from harness import cells

        bench = cells.load_benchmark()
        cfg = cells.load_config_file(bench, "mimo-v2-flash")
        family = cells.load_family(cfg)
    finally:
        sys.path[:] = path
    config = family.program_config(cfg)
    monkeypatch.setattr(linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas)
    for mod in (linear, deepseek, hybrid, llama):
        monkeypatch.setattr(mod, "pallas_kernel_active", lambda: True)
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), tree)
    arrays = jax.eval_shape(
        lambda k: family._generate(config, k, jnp.bfloat16, padded_d_out(config.vocab_size)),
        jax.random.PRNGKey(0))
    params = on_chip(jax.eval_shape(lambda a: family.assemble_params(config, a), arrays))
    buckets = cfg["serving"]["prefill_buckets"]
    cache = on_chip(jax.eval_shape(
        lambda: hybrid.init_hybrid_cache(config, b, jnp.bfloat16, max_chunk=max(buckets))))
    assert cache.wk.shape == (13, b, RING, 1536) and cache.wv.shape == (13, b, RING, 1024)
    assert cache.k.shape == (3, b, 32768, 768) and cache.v.shape == (3, b, 32768, 512)
    tok = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=v5e)
    nv = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=v5e)
    compiled = jax.jit(
        lambda p, tk, c, n: hybrid.hybrid_forward_counted(config, p, tk, tk, c, n_valid=n),
        donate_argnums=(2,),
    ).lower(params, tok, cache, nv).compile()
    return compiled.as_text(), config, compiled.memory_analysis()


def test_mimo_decode_reads_rings_and_planes_in_place_for_v5e(v5e, monkeypatch):
    """One row a lane at the cell's 16 lanes: Mosaic takes the decode kernel at
    64 heads on merged rows of 768 + 512 (groups of 16) and of 1536 + 1024
    (groups of 8, with the sink), under either work list; no stack is copied
    or sliced out whole, no float32 scores span a plane, and the temporaries
    are a step's, not a cache's."""
    hlo, c, mem = _mimo_cell_program(v5e, monkeypatch, 16, 1)
    for stack in (rf"bf16\[3,16,{c.seq_len},(768|512)\]", rf"bf16\[13,16,{RING},(1536|1024)\]"):
        assert not re.search(rf"= {stack}\S* copy\(", hlo), stack
    assert not re.search(rf"= (bf16|f32)\[16,({c.seq_len}|{RING}),(768|512|1536|1024)\]\S* "
                         r"(fusion|copy|dynamic-slice)\(", hlo)
    # no [lanes, S, heads] (in any order) float32 scores over a whole plane
    assert not re.search(rf"f32\[16,(\d+,)*{c.seq_len}(,\d+)*\]", hlo)
    assert hlo.count("decode_attention") >= 2
    assert mem.temp_size_in_bytes < 1 << 30, mem.temp_size_in_bytes


def test_mimo_chunk_makes_no_scores_by_context_for_v5e(v5e, monkeypatch):
    """A 512-row chunk against the cell's lane: no tensor has the chunk's rows
    and a whole plane's keys (a full-context layer's scores are one key
    block's, 4 kv heads of 16); a window layer's are dense over its ring of
    three blocks (101 MB, under ``blocked_attention.DENSE_SCORE_BYTES``: the
    rule's, and all three blocks are ones the chunk's rows read); the lane's
    stacks are copied nowhere; the temporaries stay under a gigabyte."""
    hlo, c, mem = _mimo_cell_program(v5e, monkeypatch, 1, 512)
    heads = "(4,16|16,4|8,8|64)"
    assert not re.search(rf"512,{heads},{c.seq_len}\]", hlo)
    assert not re.search(rf"{heads},512,{c.seq_len}\]", hlo)
    assert re.search(r"f32\[1,512,4,16,256\]", hlo) and re.search(rf"f32\[1,512,8,8,{RING}\]", hlo)
    for stack in (rf"bf16\[3,1,{c.seq_len},(768|512)\]", rf"bf16\[13,1,{RING},(1536|1024)\]"):
        assert not re.search(rf"= {stack}\S* copy\(", hlo), stack
    assert mem.temp_size_in_bytes < 1 << 30, mem.temp_size_in_bytes
