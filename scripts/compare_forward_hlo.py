#!/usr/bin/env python3
"""A hash of the optimized HLO of every toy family's forward, to hold one tree
against another: that a change which adds a layer kind leaves the programs of
the blocks without it what they were.

    python3 scripts/compare_forward_hlo.py <checkout> > a.json   # in each tree
    diff a.json b.json

For each rehearsal configuration under ``benchmarks/tests/rehearsal/configs``
that the checkout's program can run, the block's forward is compiled on the CPU
at decode width (4 lanes of one row) and at a chunk (one lane of 16 rows), the
text stripped of what names a source line or a path, and hashed. A
configuration whose family the checkout lacks is left out of its list."""

import hashlib
import json
import os
import re
import sys

TOYS = ("tiny.json", "tiny_bias.json", "tiny_moe.json", "tiny_latent.json",
        "tiny_deepseek_v32.json", "tiny_lfm2.json", "tiny_jamba.json", "tiny_cohere2_moe.json",
        "tiny_minicpm_sala.json", "tiny_mimo_v2_flash.json", "tiny_solar_open2.json")


def _normalized(text: str) -> str:
    text = re.sub(r"metadata=\{[^}]*\}", "", text)
    text = re.sub(r"HloModule \S+", "HloModule m", text)
    text = re.sub(r"(?s)(FileNames|FunctionNames|FileLocations|StackFrames).*?\n\n", "", text)
    text = re.sub(r"stack_frame_id=\d+", "", text)
    return re.sub(r"/[\w/.-]*\.py", "", text)


def main() -> int:
    root = os.path.abspath(sys.argv[1] if len(sys.argv) > 1 else ".")
    sys.path[:0] = [root, os.path.join(root, "benchmarks")]
    import jax
    import jax.numpy as jnp
    from harness import cells

    from distributed_llama_multiusers_tpu.models import deepseek, hybrid, llama
    from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

    out = {}
    for name in TOYS:
        path = os.path.join(root, "benchmarks", "tests", "rehearsal", "configs", name)
        if not os.path.exists(path):
            continue
        with open(path) as f:
            cfg = json.load(f)
        try:
            family = cells.load_family(cfg)
            config = family.program_config(cfg)
        except SystemExit:
            continue  # a family this checkout's program cannot run
        tensors = family.device_weights(config, 3, jnp.float32)
        engine = InferenceEngine(config, family.assemble_params(config, tensors), n_lanes=4,
                                 cache_dtype=jnp.float32, prefill_buckets=(16,))
        if config.layer_kinds:
            fwd = lambda p, tk, ps, c: hybrid.hybrid_forward_counted(config, p, tk, ps, c)[:2]  # noqa: E731
        elif config.latent_attention:
            fwd = lambda p, tk, ps, c: deepseek.deepseek_forward_counted(config, p, tk, ps, c)[:2]  # noqa: E731
        else:
            fwd = lambda p, tk, ps, c: llama.llama_forward(config, p, tk, ps, c)  # noqa: E731
        for b, t in ((4, 1), (1, 16)):
            tokens = jnp.zeros((b, t), jnp.int32)
            positions = jnp.broadcast_to(jnp.arange(t)[None] + 5, (b, t)).astype(jnp.int32)
            cache = jax.tree_util.tree_map(lambda a: a[:, :b] if a.ndim > 1 else a, engine.cache)
            text = jax.jit(fwd).lower(engine.params, tokens, positions, cache).compile().as_text()
            out[f"{name}:{b}x{t}"] = hashlib.sha256(_normalized(text).encode()).hexdigest()[:16]
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
