"""A toy latent-attention model with a routed FFN for the tier-1 tests: the
benchmark's deepseek_v3 family (its generator and its plain reference) at the
rehearsal's size, loaded by path as ``tests/test_bench_family_seam.py`` loads
the seam's cases. ``load("tiny_lfm2.json")`` is the lfm2_moe family's toy (conv
and attention mixers in a pattern) the same way."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")


def load(name: str = "tiny_latent.json"):
    """(cfg, family, correct): the toy configuration file's keys (``name``
    under the rehearsal's configs), its family module, and the harness's
    comparison."""
    path = list(sys.path)
    sys.path[:0] = [p for p in (BENCH_DIR, ROOT) if p not in sys.path]
    try:
        from harness import cells, correct

        with open(os.path.join(BENCH_DIR, "tests", "rehearsal", "configs", name)) as f:
            cfg = json.load(f)
        return cfg, cells.load_family(cfg), correct
    finally:
        sys.path[:] = path


def wide_lfm2():
    """(cfg, family, correct) of the lfm2_moe toy at a shape the in-place
    decode attention takes (ops/pallas_attention.py ``supports``): 4 heads of
    64 on 2 kv heads, so a merged K/V row is one whole 128-lane tile, and a
    context of two 256-row blocks, with sample prompts on both sides of the
    block's edge. Served in bfloat16 (``engine(..., dtype=jnp.bfloat16)``)."""
    import copy

    cfg, family, correct = load("tiny_lfm2.json")
    cfg = copy.deepcopy(cfg)
    cfg.update(hidden_size=256, max_position_embeddings=512)
    cfg["correctness"]["prompt_tokens"] = [20, 250, 300]
    return cfg, family, correct


def engine(family, cfg, seed=11, dtype=None, lanes=8, **kw):
    """(engine, tensors) as the benchmark builds them, at ``dtype``
    (activations and cache; float32 by default)."""
    import jax.numpy as jnp

    from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

    dtype = dtype or jnp.float32
    config = family.program_config(cfg)
    tensors = family.device_weights(config, seed, dtype)
    eng = InferenceEngine(config, family.assemble_params(config, tensors), n_lanes=lanes,
                          cache_dtype=dtype, **kw)
    return eng, tensors


def scale_dtypes(tree) -> set:
    """The dtypes of every Q40 scale plane in ``tree`` (``PackedQ40.scales``,
    ``Q40Experts.scale_bits``): int16 for an expert stack and for a
    ``PackedQ40`` at rest (``q40_at_rest``: of an engine's ``params``, the
    stacks the kernel reads in place), float16 for a leaf as the loaders and
    packers make it."""
    import jax
    import numpy as np

    from distributed_llama_multiusers_tpu.quants.packed import PackedQ40, Q40Experts

    q40 = (PackedQ40, Q40Experts)
    leaves = jax.tree_util.tree_leaves(tree, is_leaf=lambda x: isinstance(x, q40))
    return {np.dtype(w[1].dtype) for w in leaves if isinstance(w, q40)}
