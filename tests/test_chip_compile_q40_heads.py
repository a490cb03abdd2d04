"""PR 45, one block of rows a call: the dense Q40 kernel's default mode at
512 and 1024 rows against the seven configurations' output heads, each one plane
of 8192-wide tiles or thereabouts (``CELL_SHAPES``' entries that are no stack,
tests/chip_compile_util.py), compiled for a described v5e; and, PR 53, what
became of those rows on the serving path: a 1024-row chunk's head over the
one row it keeps (``ops.linear.head`` with ``head_row``)."""

import re

import jax
import jax.numpy as jnp
import pytest

from distributed_llama_multiusers_tpu.ops import linear, pallas_q40 as pq
from distributed_llama_multiusers_tpu.ops.norm import rms_norm
from distributed_llama_multiusers_tpu.quants.packed import PackedQ40

from chip_compile_util import (  # noqa: F401  (v5e, v5e_devices: the fixtures)
    CELL_SHAPES,
    check_one_row_block,
    v5e,
    v5e_devices,
)


@pytest.mark.parametrize("m", [512, 1024])
@pytest.mark.parametrize("d_in,d_out,stacked", [s for s in CELL_SHAPES if not s[2]])
def test_one_row_block_compiles_for_v5e_at_every_cell_shape(
        v5e, d_in, d_out, stacked, m):
    check_one_row_block(v5e, d_in, d_out, stacked, m)


@pytest.mark.parametrize("d_in,d_out,stacked", [s for s in CELL_SHAPES if not s[2]])
def test_a_chunks_head_compiles_for_v5e_at_its_one_kept_row(
        v5e, monkeypatch, d_in, d_out, stacked):
    """PR 53: ``ops.linear.head`` over a 1024-row chunk with ``head_row``: the
    hidden row is cut out before the norm, the kernel is called once on a
    block of 16 rows (what a decode step of few lanes gives it), and no array
    of the program has the chunk's rows beside the vocabulary's columns."""
    monkeypatch.setattr(linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)  # noqa: E731
    wcls = PackedQ40(packed=sds((d_in // 2, d_out), jnp.uint8),
                     scales=sds((d_in // 32, d_out), jnp.float16))

    def chunk_head(x, gain, wcls, head_row):
        return linear.head(x, lambda x: rms_norm(x, gain, 1e-5), wcls, d_out - 8,
                           head_row=head_row)

    args = (sds((1, 1024, d_in), jnp.bfloat16), sds((d_in,), jnp.float32), wcls)
    hlo = jax.jit(chunk_head).lower(*args, sds((1,), jnp.int32)).compile().as_text()
    assert hlo.count("tpu_custom_call") == 1
    assert re.search(rf"f32\[1,1,{d_out - 8}\]", hlo)
    rows_by_vocab = re.compile(rf"(?:f32|bf16)\[(?:1,)?1024,{d_out}\]")
    assert not rows_by_vocab.search(hlo)
    # the control: every row through the head holds the chunk's rows by the vocabulary
    whole = jax.jit(chunk_head).lower(*args, None).compile().as_text()
    assert rows_by_vocab.search(whole)
