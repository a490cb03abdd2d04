#!/usr/bin/env python3
"""A hash of step programs compiled for a described TPU v5e, kernels and all,
to hold one tree against another: that a change to ``ops/pallas_attention.py``
(or to a block's forward) leaves the programs it says it does not touch what
they were, operation for operation. ``scripts/compare_forward_hlo.py`` does
the same for the toys on the CPU, where no kernel is in the program.

    python3 scripts/compare_step_programs.py <checkout> > a.json   # each tree
    diff a.json b.json

The programs are the ones the checkout's own chip-compile tests build
(``tests/chip_compile_util.py`` and the cell programs beside it): one decode
forward of 128-wide heads on their own axis at each 7B shape and its 1024-row
chunk, the merged rows of the layer-pattern block, Command A+'s rings,
MiMo's sink and unlike K / V widths, and DeepSeek-V3.2's sparse latent decode
step and chunk. The text is stripped of what names a source line or a path;
a Mosaic kernel's payload (MLIR bytecode that carries its source locations)
is replaced by a hash of its text printed without them."""

import base64
import hashlib
import json
import os
import re
import sys


def _kernel_digest(match) -> str:
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(match.group(1)))
        text = module.operation.get_asm(enable_debug_info=False)
    return '"body":"' + hashlib.sha256(text.encode()).hexdigest() + '"'


def _normalized(text: str) -> str:
    from compare_forward_hlo import _normalized as without_source_names  # beside this file

    return without_source_names(re.sub(r'"body":"([A-Za-z0-9+/=]+)"', _kernel_digest, text))


def main() -> int:
    root = os.path.abspath(sys.argv[1] if len(sys.argv) > 1 else ".")
    sys.path[:0] = [root, os.path.join(root, "tests")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import pytest
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    v5e = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    import chip_compile_util as util
    import test_chip_compile_mixed_heads as mixed
    import test_chip_compile_steps as steps
    import test_chip_compile_window as window

    programs = {
        "heads128_decode_mistral": lambda mp: util._three_layer_decode_hlo(v5e, mp, seq=2048),
        "heads128_decode_qwen": lambda mp: util._three_layer_decode_hlo(
            v5e, mp, lanes=32, n_heads=28, n_kv=4, seq=2048),
        "heads128_chunk_1024": lambda mp: util._three_layer_decode_hlo(
            v5e, mp, lanes=1, rows=1024, seq=2048),
        "merged_pattern_decode": lambda mp: util._pattern_decode_hlo(
            v5e, mp, periods=2, seq=2048),
        "ring_command_a_decode": lambda mp: window._command_a_cell_program(v5e, mp, 16, 1),
        "sink_mimo_decode": lambda mp: mixed._mimo_cell_program(v5e, mp, 16, 1),
        "sparse_latent_decode": lambda mp: steps._deepseek_v32_cell_program(v5e, mp, 8, 1),
        "sparse_latent_chunk_1024": lambda mp: steps._deepseek_v32_cell_program(
            v5e, mp, 1, 1024),
    }
    out = {}
    for name, build in programs.items():
        with pytest.MonkeyPatch.context() as mp:
            text = build(mp)[0]
        out[name] = {"kernels": text.count("tpu_custom_call"),
                     "sha256": hashlib.sha256(_normalized(text).encode()).hexdigest()[:16]}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
