"""The scheduler's attn_kv_rows_* counters (EngineStats) against a hand count.

The host never reads a position back from a chain: a live lane's row on the
device is what the host has consumed plus its steps still in flight. The mock
engine simulates the device's position carry, so every dispatch's effective
positions can be recorded there and the counters recomputed from them.
"""

import time

import numpy as np
import pytest

from distributed_llama_multiusers_tpu.runtime.scheduler import (
    ContinuousBatchingScheduler,
    Request,
)
from distributed_llama_multiusers_tpu.utils.testing import (
    MockAsyncEngine,
    StubStreamTokenizer,
)

BLOCK, SEQ, LANES = 256, 1024, 4


def _hand_count(positions, block):
    """Rows a whole-block fetch up to each live lane's row brings in; a lane
    at `SEQ` (parked, idle, admitting) brings in nothing."""
    return sum(block * (p // block + 1) for p in positions if 0 <= p < SEQ)


def _run(block, pipeline_depth=2):
    engine = MockAsyncEngine(n_lanes=LANES, seq_len=SEQ, max_chunk=64,
                             pipeline_depth=pipeline_depth)
    engine.decode_attention_block = block
    seen = []  # one entry a decode step: every lane's row on the "device"

    def spy(name, positions_arg):
        real = getattr(engine, name)

        def wrapped(*a, **kw):
            seen.append(np.asarray(engine._eff_positions(a[positions_arg])))
            return real(*a, **kw)

        setattr(engine, name, wrapped)

    spy("decode_pipelined", 0)
    spy("decode_prefill_fused", 0)
    spy("decode", 1)
    sched = ContinuousBatchingScheduler(
        engine, StubStreamTokenizer(engine.config.vocab_size, prompt_tokens=512),
        speculative=False, prefix_min_tokens=0, multi_step=0,
    )
    # three requests on four lanes: one lane stays parked throughout, and the
    # long prompt's lane crosses a block boundary while it generates
    reqs = [Request(prompt="a" * n, max_tokens=m, temperature=0.0)
            for n, m in ((250, 24), (40, 30), (300, 12))]
    sched.start()
    try:
        sched.submit(reqs[0])
        deadline = time.monotonic() + 60
        while len(reqs[0].generated_tokens) < 2:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        for r in reqs[1:]:
            sched.submit(r)
        for r in reqs:
            r.future.result(timeout=60)
    finally:
        sched.stop()
    return engine.stats.snapshot(), seen


@pytest.mark.parametrize("pipeline_depth", [2, 0],
                         ids=["pipelined_and_fused", "synchronous"])
def test_counters_equal_the_hand_count_over_the_devices_positions(pipeline_depth):
    stats, seen = _run(BLOCK, pipeline_depth)
    assert len(seen) > 30
    assert any((at >= SEQ).any() and (at < SEQ).any() for at in seen)  # a parked lane
    assert any(BLOCK <= p < SEQ for at in seen for p in at)  # a second block
    assert stats["attn_kv_rows_whole"] == len(seen) * LANES * SEQ
    assert stats["attn_kv_rows_read"] == sum(_hand_count(at, BLOCK) for at in seen)
    assert 0 < stats["attn_kv_rows_read"] < stats["attn_kv_rows_whole"] // 4


def test_an_engine_that_reads_whole_planes_counts_whole_planes():
    """No in-place kernel (the CPU, a paged pool, a mesh): every lane's plane
    is read whole, parked or not, and the share reads 100 %."""
    stats, seen = _run(None)
    assert stats["attn_kv_rows_read"] == stats["attn_kv_rows_whole"] > 0
    assert stats["attn_kv_rows_whole"] == len(seen) * LANES * SEQ


def test_a_multi_step_dispatch_counts_each_of_its_steps():
    """`decode_multi` advances every live lane a row a step: three steps from
    rows 255 and 10, one lane parked."""
    engine = MockAsyncEngine(n_lanes=3, seq_len=SEQ)
    engine.decode_attention_block = BLOCK
    sched = ContinuousBatchingScheduler(
        engine, StubStreamTokenizer(engine.config.vocab_size))
    sched._count_attention_rows(np.array([255, SEQ, 10], np.int32), steps=3)
    stats = engine.stats.snapshot()
    assert stats["attn_kv_rows_whole"] == 3 * 3 * SEQ
    assert stats["attn_kv_rows_read"] == (BLOCK + 2 * BLOCK + 2 * BLOCK) + 3 * BLOCK


@pytest.mark.parametrize("pallas", [True, False], ids=["in_place", "pallas_off"])
@pytest.mark.parametrize("toy,block,planes,chunks", [
    ("wide_lfm2", BLOCK, "xla_dense", ("in_place_kernel", "dense")),
    # latent rows go by taller blocks, and a latent cache's chunks keep the
    # absorbed form over the plane (PR 59)
    ("wide_latent", 2 * BLOCK, "xla_dense_latent_absorbed", ("xla_dense_latent_absorbed",) * 2),
], ids=["layer_pattern", "latent"])
def test_an_engine_counts_what_its_decode_attention_fetches(toy, block, planes, chunks, pallas):
    """A real engine on the lfm2_moe toy and on the latent toy, each at a
    shape the kernel takes (bf16 rows of whole tiles, two blocks of context),
    served synchronously so that every step's positions are the host's own:
    with Pallas on (interpret mode) the engine says `pallas_in_place` and the
    counter is the hand count over those positions, under the whole planes;
    with Pallas off it names its plane read and counts whole planes."""
    import jax.numpy as jnp

    import latent_toy
    from distributed_llama_multiusers_tpu.ops import linear

    cfg, family, _ = getattr(latent_toy, toy)()
    seq, lanes = cfg["max_position_embeddings"], 4
    linear.set_pallas_interpret(pallas)
    try:
        engine, _ = latent_toy.engine(family, cfg, 5, dtype=jnp.bfloat16, lanes=lanes,
                                      pipeline_depth=0, prefill_buckets=(64,))
        facts = engine.path_facts()
        assert facts["attention_path"] == ("pallas_in_place" if pallas else planes)
        assert facts["prefill_attention_path"] == chunks[0 if pallas else 1]
        assert engine.decode_attention_block == (block if pallas else None)
        seen, real = [], engine.decode
        engine.decode = lambda tokens, positions, *a, **kw: (
            seen.append(np.asarray(positions).copy()) or real(tokens, positions, *a, **kw))
        sched = ContinuousBatchingScheduler(
            engine, StubStreamTokenizer(cfg["vocab_size"], prompt_tokens=512),
            speculative=False, prefix_min_tokens=0, multi_step=0)
        # one lane crosses the block's edge while it generates, one stays low,
        # two stay parked
        reqs = [Request(prompt="a" * n, max_tokens=m, temperature=0.0)
                for n, m in ((block - 6, 10), (30, 6))]
        sched.start()
        try:
            for r in reqs:
                sched.submit(r)
            for r in reqs:
                r.future.result(timeout=300)
                assert r.error is None, r.error
        finally:
            sched.stop()
    finally:
        linear.set_pallas_interpret(False)
    stats = engine.stats.snapshot()
    assert len(seen) >= 9 and any(block <= p < seq for at in seen for p in at)
    assert stats["attn_kv_rows_whole"] == len(seen) * lanes * seq
    hand = sum(block * (p // block + 1) for at in seen for p in at if 0 <= p < seq)
    if pallas:
        assert stats["attn_kv_rows_read"] == hand < stats["attn_kv_rows_whole"] // 2
    else:
        assert stats["attn_kv_rows_read"] == stats["attn_kv_rows_whole"]


def test_a_latent_engine_decodes_in_place_what_the_plane_read_decodes():
    """The latent toy widened to whole tiles, through the engine with the
    kernels in interpret mode: prompts on both sides of a block's edge go in
    by 64-row chunks (the absorbed form over the plane), then one decode step
    with two lanes parked reads the latent rows in place; its logits against
    the forward's plane read (Pallas off) over the same cache and weights."""
    import jax
    import jax.numpy as jnp

    import latent_toy
    from distributed_llama_multiusers_tpu.models import deepseek
    from distributed_llama_multiusers_tpu.ops import linear, pallas_attention

    cfg, family, _ = latent_toy.wide_latent()
    calls, real = [], pallas_attention.decode_attention
    linear.set_pallas_interpret(True)
    try:
        engine, _ = latent_toy.engine(family, cfg, 5, dtype=jnp.bfloat16, lanes=4,
                                      pipeline_depth=0, prefill_buckets=(64,))
        assert engine.decode_attention_block == pallas_attention.block_rows(latent=True)
        rng = np.random.default_rng(2)
        at = {0: 600, 2: 40}  # lane 0 holds two blocks, lane 2 one; 1 and 3 stand parked
        for lane, n in at.items():
            engine.prefill(lane, [int(x) for x in rng.integers(2, cfg["vocab_size"], size=n)])
        tokens, positions = latent_toy.park(engine, {lane: (7 + lane, n) for lane, n in at.items()})
        cache = jax.tree_util.tree_map(jnp.copy, engine.cache)
        deepseek.decode_attention = lambda *a, **kw: calls.append(kw) or real(*a, **kw)
        logits = np.asarray(engine.decode(tokens, positions)[0], np.float32)
    finally:
        deepseek.decode_attention = real
        linear.set_pallas_interpret(False)
    # layer 0 before the scan and the scan's body, each traced once
    assert len(calls) == 2 and all(kw["latent"] for kw in calls)
    want, _ = deepseek.deepseek_forward(
        engine.config, engine.params, jnp.asarray(tokens)[:, None], jnp.asarray(positions)[:, None],
        cache)
    want = np.asarray(want, np.float32)[:, 0]
    live = sorted(at)
    assert np.isfinite(logits[live]).all()
    assert np.abs(logits[live] - want[live]).max() <= 2e-2 * np.abs(want[live]).max()


def _llama_engine(**kw):
    """A Llama-block engine at a shape both in-place kernels take: two kv
    heads of 128, bfloat16, two blocks of context, one 64-row bucket."""
    import jax.numpy as jnp

    from distributed_llama_multiusers_tpu.models.config import LlamaConfig
    from distributed_llama_multiusers_tpu.models.loader import params_from_random
    from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

    cfg = LlamaConfig(dim=512, hidden_dim=512, n_layers=2, n_heads=4, n_kv_heads=2,
                      vocab_size=64, seq_len=2 * BLOCK)
    params = params_from_random(cfg, seed=0, dtype=jnp.bfloat16, scale=0.05)
    if kw.get("mesh") is not None:
        from distributed_llama_multiusers_tpu.parallel.sharding import shard_params

        params = shard_params(params, kw["mesh"])
    return InferenceEngine(cfg, params, n_lanes=2, prefill_buckets=(64,),
                           cache_dtype=jnp.bfloat16, **kw)


def _pattern_engine(**kw):
    import jax.numpy as jnp

    import latent_toy

    cfg, family, _ = latent_toy.wide_lfm2()
    return latent_toy.engine(family, cfg, 5, dtype=jnp.bfloat16, lanes=2,
                             prefill_buckets=(64,), **kw)[0]


def _mesh_engine():
    from distributed_llama_multiusers_tpu.parallel import MeshPlan, make_mesh

    return _llama_engine(mesh=make_mesh(MeshPlan(tp=2)), replicate_outputs=True)


@pytest.mark.parametrize("build,pallas,blocked,want", [
    (_llama_engine, True, False, "in_place_kernel"),
    (_pattern_engine, True, False, "in_place_kernel"),
    (_pattern_engine, True, True, "blocked"),
    (_llama_engine, False, False, "dense"),
    (_pattern_engine, False, False, "dense"),
    (lambda: _llama_engine(paged_kv=True), True, False, "dense"),
    (_mesh_engine, True, False, "dense"),
], ids=["llama_block", "layer_pattern", "layer_pattern_long_plane", "llama_block_pallas_off",
        "layer_pattern_pallas_off", "paged_pool", "mesh"])
def test_path_facts_say_how_a_prefill_chunk_reads_the_cache(monkeypatch, build, pallas,
                                                             blocked, want):
    """`prefill_attention_path` by the predicates the forwards ask: the kernel
    in place where both blocks' contiguous bf16 stacks meet Pallas on one
    device; the XLA walk over key blocks where a layer-pattern block's dense
    scores would pass the stated size (moved down here to the toy's); dense
    scores for Pallas off, the paged pool's gathered view and a mesh."""
    from distributed_llama_multiusers_tpu.ops import blocked_attention, linear

    if blocked:
        monkeypatch.setattr(blocked_attention, "DENSE_SCORE_BYTES", 0)
    linear.set_pallas_interpret(pallas)
    try:
        facts = build().path_facts()
    finally:
        linear.set_pallas_interpret(False)
    assert facts["prefill_attention_path"] == want
    assert "prefill_kernel_traces" in facts


def test_a_latent_cache_names_its_own_prefill_path():
    import latent_toy

    cfg, family, _ = latent_toy.load()
    facts = latent_toy.engine(family, cfg, lanes=2)[0].path_facts()
    assert facts["prefill_attention_path"] == facts["attention_path"] != "in_place_kernel"
