"""One door into the Q40 kernel (PR 46).

``ops/pallas_q40.py`` has one jitted entry, ``_q40_matmul_pallas_impl``, and
every caller hands it x as it is: the activation bundle, the second jitted
entry that took it and the route a bundle used to mark went. Held here without a chip:

* the lowered pipelined decode step and 64-row fused step of each block
  (Llama, latent attention, layer pattern) at toy size, Q40 weights, the
  kernel in interpret mode: every ``_q40_matmul_*_impl`` they name is the one;
* what ``ops.linear.matmul`` routes by is the weight, whoever calls: a
  ``Q40Layer`` to the kernel, a 2-D plane with the kernel on through
  ``q40_matmul_partitioned`` (the kernel where it tiles the plane, the XLA
  dequant where not), anything else to the XLA dequant.
"""

import re

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.ops import linear, pallas_q40 as pq
from distributed_llama_multiusers_tpu.quants import packed as packed_mod
from distributed_llama_multiusers_tpu.quants.packed import PackedQ40, Q40Layer, pack_q40_host

import latent_toy
from test_tracing import lowered_with_debug_info

BUCKET = 64
# (block -> its row of the one table of toys, tests/latent_toy.py)
TOYS = {"llama": "llama", "latent": "latent", "pattern": "lfm2"}
ENTRY = re.compile(r"_q40_matmul_\w*_impl")


@pytest.fixture(scope="module")
def toy_engines():
    """Each block's toy engine as the benchmark builds it (Q40 planes from
    the family's generator), the kernel on in interpret mode while the
    programs are lowered."""
    made = {}
    linear.set_pallas_interpret(True)
    try:
        def get(block: str):
            if block not in made:
                cfg, family, _ = latent_toy.toy(TOYS[block])
                made[block] = latent_toy.engine(
                    family, cfg, seed=3, lanes=4, prefill_buckets=(BUCKET,))[0]
            return made[block]

        yield get
    finally:
        linear.set_pallas_interpret(False)


@pytest.mark.parametrize("program", ["_decode_pl_fn", "_decode_prefill_fn"],
                         ids=["pipelined_decode", "fused_b64"])
@pytest.mark.parametrize("block", sorted(TOYS))
def test_step_program_names_the_one_entry(toy_engines, block, program):
    engine = toy_engines(block)
    assert linear.pallas_kernel_active() and engine.prefill_buckets == (BUCKET,)
    text = lowered_with_debug_info(engine, program)
    if program == "_decode_prefill_fn":
        assert f"dlstep.fused.b{BUCKET}" in text
    found = ENTRY.findall(text)
    # a layer body's matmuls and the head's at least, under one name
    assert len(found) >= 8 and set(found) == {"_q40_matmul_pallas_impl"}, set(found)


def _plane(rng, d_out, d_in):
    packed, scales = pack_q40_host(rng.standard_normal((d_out, d_in), dtype=np.float32) * 0.1)
    return PackedQ40(packed=jnp.asarray(packed), scales=jnp.asarray(scales))


# (the weight handed to matmul, kernel on?) -> the functions that must run
ROUTES = {
    "q40_layer": ["kernel"],
    "plane_kernel_on": ["partitioned", "kernel"],
    "plane_the_kernel_does_not_tile": ["partitioned", "xla"],
    "plane_kernel_off": ["xla"],
    "stack_without_a_layer": ["xla"],
    "dense": [],
}


@pytest.mark.parametrize("scales", ["f16", "bits"])
@pytest.mark.parametrize("case", sorted(ROUTES))
def test_matmul_routes_by_what_the_weight_is(monkeypatch, case, scales):
    """The route rule of ``ops.linear.matmul``, by spies on the three
    functions it can reach; x is the same raw array in every case, and the
    result equals the XLA dequant's of the float16 plane. The form the scales
    arrive in (float16, or at rest as their int16 bits) moves no route: the
    kernel and the XLA dequant each take either."""
    rng = np.random.default_rng(46)
    ran = []

    def spy(name, fn):
        def wrapped(*a, **kw):
            ran.append(name)
            return fn(*a, **kw)
        wrapped.__wrapped__ = fn
        return wrapped

    monkeypatch.setattr(pq, "q40_matmul_pallas", spy("kernel", pq.q40_matmul_pallas))
    monkeypatch.setattr(pq, "q40_matmul_partitioned", spy("partitioned", pq.q40_matmul_partitioned))
    xla = spy("xla", packed_mod.q40_matmul_xla)  # by name in linear, by module in the wrapper
    monkeypatch.setattr(linear, "q40_matmul_xla", xla)
    monkeypatch.setattr(packed_mod, "q40_matmul_xla", xla)
    # 8224 = 32 * 257 outputs: over the widest block and no 128-multiple
    # divides them, so there is no plan
    d_out = 8224 if case == "plane_the_kernel_does_not_tile" else 128
    plane = _plane(rng, d_out, 64)
    assert (pq._plan_blocks(64, d_out) is None) == (d_out == 8224)
    stack = PackedQ40(packed=jnp.stack([plane.packed] * 2), scales=jnp.stack([plane.scales] * 2))
    x = jnp.asarray(rng.standard_normal((2, 3, 64), dtype=np.float32))
    form = packed_mod.q40_at_rest if scales == "bits" else (lambda w: w)
    w = {"q40_layer": Q40Layer(form(stack), jnp.int32(1)), "stack_without_a_layer": form(stack),
         "dense": jnp.ones((64, 128), jnp.float32)}.get(case, form(plane))
    linear.set_pallas_interpret(case != "plane_kernel_off")
    try:
        got = linear.matmul(x, w)
    finally:
        linear.set_pallas_interpret(False)
    assert ran == ROUTES[case], ran
    assert got.shape == (2, 3, d_out)
    if case not in ("dense", "stack_without_a_layer"):
        want = packed_mod.q40_matmul_xla.__wrapped__(x, plane)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)
