"""The sampler's threshold search (runtime/engine.py ``nucleus_keep``,
``sample_lanes``) compiled for a described v5e (tests/chip_compile_util.py)."""

import jax
import jax.numpy as jnp
import pytest

from chip_compile_util import v5e, v5e_devices  # noqa: F401  (the fixtures)


@pytest.mark.parametrize("lanes,vocab", [(32, 152064), (32, 128256), (16, 32768)])
def test_nucleus_search_compiles_with_no_sort(v5e, lanes, vocab):
    """The sampler's kept set at the cells' widths (Qwen, Kanana, Mistral), as
    the chip's compiler sees it: one `while` of 32 passes, no `sort` and no
    TopK custom call (PR 34; the sorted form it replaced was 13-16 s of every
    step program's compile and 6.6 ms of Qwen's decode step)."""
    import re

    from distributed_llama_multiusers_tpu.runtime.engine import nucleus_keep

    def keep(rows, topps):
        return jax.vmap(nucleus_keep)(rows / 0.7, topps)

    hlo = jax.jit(keep).lower(
        jax.ShapeDtypeStruct((lanes, vocab), jnp.float32, sharding=v5e),
        jax.ShapeDtypeStruct((lanes,), jnp.float32, sharding=v5e),
    ).compile().as_text()
    assert " while(" in hlo
    assert not re.search(r"\bsort[.(]|TopK|top_k|topk", hlo)


def test_grouped_sampler_holds_a_groups_rows_in_fast_memory(v5e):
    """Jamba's 256 lanes x 65536 through the sampler's entry (PR 44): four
    groups of 64, and the chip's compiler keeps BOTH operands of the 32 passes
    (the keys and the probabilities of a group) in memory space 1 across the
    inner `while`, where all 256 rows at once leave the keys in HBM and every
    pass streams them (5.5 ms of that cell's decode half). No sort either."""
    import re

    from distributed_llama_multiusers_tpu.runtime.engine import (
        _sample_lane, sample_lanes, sampler_group)

    lanes, vocab = 256, 65536
    assert sampler_group(lanes, vocab) == 64
    operands = [jax.ShapeDtypeStruct((lanes, vocab), jnp.float32, sharding=v5e)] + [
        jax.ShapeDtypeStruct((lanes,), d, sharding=v5e)
        for d in (jnp.float32, jnp.float32, jnp.int32, jnp.int32, jnp.int32)]

    def searches(fn):
        hlo = jax.jit(fn).lower(*operands).compile().as_text()
        assert not re.search(r"\bsort[.(]|TopK|top_k|topk", hlo)
        # the loops that carry a [rows, vocab] key: the 32 passes
        return [line for line in hlo.splitlines()
                if " while(" in line and re.search(r"u32\[\d+,65536\]", line)]

    (search,) = searches(sample_lanes)
    resident = r"\[64,65536\]\{1,0:T\(8,128\)S\(1\)\}"
    assert re.search("u32" + resident, search) and re.search("f32" + resident, search)
    (search,) = searches(jax.vmap(_sample_lane))   # the control: ungrouped
    assert re.search(r"u32\[256,65536\]\{1,0:T\(8,128\)\}", search)
