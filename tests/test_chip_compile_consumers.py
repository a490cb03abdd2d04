"""Consumers of one input, compiled for a described v5e (PR 46;
tests/chip_compile_util.py).

Until PR 46 a model built one operand bundle for the matmuls that share an
input (wq/wk/wv, w1/w3, a latent block's projections) and handed it to a
second jitted entry of the kernel. Since PR 42 the bundle was x with its rows
padded and nothing else, so it went: every consumer hands the one entry x as
it is, and pads the rows itself inside the step program. What that must not
cost is held here on the compiled program: for each of the benchmark's six
configurations, at its cell's lane count (one row a lane) and as one lane of
1024 rows, every group of consumers of one input holds ONE pad of x at most
(XLA merges the identical pads; none at all where the rows are whole tiles)
and one kernel call a consumer, each named after the one entry.

The groups are the forward's own: it is traced (shapes only) with a spy on the
kernel's entry, and the calls are grouped by the array they were handed.
"""

import collections
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from distributed_llama_multiusers_tpu.models.deepseek import forward_counted, init_latent_cache
from distributed_llama_multiusers_tpu.models.hybrid import init_hybrid_cache
from distributed_llama_multiusers_tpu.models.llama import init_kv_cache
from distributed_llama_multiusers_tpu.ops import linear, pallas_q40 as pq
from distributed_llama_multiusers_tpu.quants.packed import PackedQ40, Q40Layer

import latent_toy
from chip_compile_util import STACK_LAYERS, v5e, v5e_devices  # noqa: F401  (the fixtures)

CONFIGS = ["mistral-7b-v0.3", "qwen2.5-7b", "kanana-2-30b-a3b", "lfm2-24b-a2b",
           "deepseek-v3.2", "jamba2-3b"]
# the fewest groups of two or more consumers a configuration's forward has:
# wq/wk/wv and w1/w3, or the block's own projections and gated FFNs
MIN_GROUPS = 2


def _load(name: str):
    path = list(sys.path)
    sys.path[:0] = [latent_toy.BENCH_DIR, latent_toy.ROOT]
    try:
        from harness import cells

        cfg = cells.load_config_file(cells.load_benchmark(), name)
        return cfg, cells.load_family(cfg)
    finally:
        sys.path[:] = path


def _consumer_groups(monkeypatch, name: str, rows: str):
    """[(x's shape, [(d_in, d_out) of each consumer's stack])] for every array
    that two or more kernel calls of the configuration's forward were handed,
    at ``[lanes, 1]`` (``rows == "lanes"``) or ``[1, 1024]``."""
    from distributed_llama_multiusers_tpu.models import deepseek

    cfg, family = _load(name)
    config = family.program_config(cfg)
    b, t = (cfg["serving"]["lanes"], 1) if rows == "lanes" else (1, 1024)
    handed = collections.OrderedDict()  # id(x) -> (x, [weights])

    def spy(x, w, interpret=False, w_dtype=None, layer=None):
        if layer is not None:
            handed.setdefault(id(x), (x, []))[1].append((w.d_in, w.d_out))
        return jnp.zeros(x.shape[:-1] + (w.d_out,), x.dtype)

    tensors = jax.eval_shape(lambda: family.device_weights(config, 0, jnp.bfloat16))
    params = jax.eval_shape(lambda ts: family.assemble_params(config, ts), tensors)
    init = (init_hybrid_cache if config.layer_kinds
            else init_latent_cache if config.latent_attention else init_kv_cache)
    cache = jax.eval_shape(lambda: init(config, b, jnp.bfloat16))
    tok = jax.ShapeDtypeStruct((b, t), jnp.int32)
    forward = forward_counted(config)
    with monkeypatch.context() as patch:
        patch.setattr(linear, "pallas_kernel_active", lambda: True)
        patch.setattr(deepseek, "pallas_kernel_active", lambda: True)
        patch.setattr(pq, "q40_matmul_pallas", spy)
        jax.eval_shape(lambda p, tk, c: forward(config, p, tk, tk, c), params, tok, cache)
    return [(x.shape, ws) for x, ws in handed.values() if len(ws) > 1], b * t


@pytest.mark.parametrize("rows", ["lanes", "rows1024"])
@pytest.mark.parametrize("name", CONFIGS)
def test_consumers_of_one_input_hold_one_pad_and_a_call_each(v5e, monkeypatch, name, rows):
    groups, m = _consumer_groups(monkeypatch, name, rows)
    assert len(groups) >= MIN_GROUPS, groups
    monkeypatch.setattr(linear, "_pallas_q40_matmul", lambda: pq.q40_matmul_pallas)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
    xs = [sds(shape, jnp.bfloat16) for shape, _ in groups]
    stacks = [[PackedQ40(packed=sds((STACK_LAYERS, d_in // 2, d_out), jnp.uint8),
                         scales=sds((STACK_LAYERS, d_in // 32, d_out), jnp.float16))
               for d_in, d_out in ws] for _, ws in groups]

    def program(xs, stacks, layer):
        return [[linear.matmul(x, Q40Layer(w, layer)) for w in ws]
                for x, ws in zip(xs, stacks)]

    hlo = jax.jit(program).lower(xs, stacks, sds((), jnp.int32)).compile().as_text()
    consumers = sum(len(ws) for _, ws in groups)
    assert hlo.count("tpu_custom_call") == consumers
    called = re.findall(r"%(_q40_matmul_\w+?)\.\d+ = \S+ custom-call\(", hlo)
    assert len(called) == consumers and set(called) == {"_q40_matmul_pallas_impl"}, called
    # a pad of x makes [m_pad, d_in] out of [m, d_in]: one a group at most, and
    # none where the rows are whole tiles of bf16 already
    m_pad = pq._m_geometry(m, jnp.bfloat16)[0]
    pads = collections.Counter(
        int(d) for r, d in re.findall(r"= bf16\[(\d+),(\d+)\]\S* pad\(", hlo) if int(r) == m_pad)
    inputs = collections.Counter(shape[-1] for shape, _ in groups)
    if m_pad == m:
        assert not pads, pads
    else:
        assert pads and all(pads[d] <= inputs[d] for d in pads), (pads, inputs)
