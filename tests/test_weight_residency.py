"""Weight residency: where the Pallas kernel runs on one device, the layer
scan closes over the stacked Q40 planes and the kernel reads layer ``l``'s
tiles out of the stack (models/llama.py, "How the weights move"). Scanned,
each plane was sliced into a buffer of its own for the kernel to read again.

Pins, like tests/test_kv_residency.py's: counts, shapes and bits, never a time.

* values: logits and caches equal a plain per-layer Python loop that slices
  every plane and runs the 2-D kernel on it, bit for bit;
* structure: no Q40 plane is a scanned input of the layer scan when the
  kernel engages, and every one is when it does not (XLA fallback, a mesh);
* the witness: one traced forward counts seven kernel calls that index a
  stack (a layer body; the head's ``wcls`` has no layer axis).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from distributed_llama_multiusers_tpu.formats import load_model_header
from distributed_llama_multiusers_tpu.formats.synthetic import (
    tiny_header,
    write_synthetic_model,
)
from distributed_llama_multiusers_tpu.models import init_kv_cache, llama_forward
from distributed_llama_multiusers_tpu.models.loader import (
    load_params_from_m_quantized,
)
from distributed_llama_multiusers_tpu.ops import linear
from distributed_llama_multiusers_tpu.ops.pallas_q40 import (
    TRACE_STATS,
    reset_trace_stats,
)
from distributed_llama_multiusers_tpu.parallel import MeshPlan, make_mesh
from distributed_llama_multiusers_tpu.parallel.sharding import shard_params
from distributed_llama_multiusers_tpu.quants.packed import PackedQ40

# the per-layer loop (every leaf sliced out of its stack), the caches and the
# jaxpr walk are the KV residency tests' own
from test_kv_residency import (
    N_LANES,
    N_LAYERS,
    SEQ_LEN,
    _cache,
    _layer_loop_forward,
    _scans,
)

Q40_FIELDS = ("wq", "wk", "wv", "wo", "w1", "w2", "w3")


@pytest.fixture(scope="module", params=[0, 1], ids=["plain", "qkv_bias"])
def loaded(request, tmp_path_factory):
    d = tmp_path_factory.mktemp("weight_residency")
    # hidden 192: six quant blocks, so that two tensor-parallel shards hold whole ones
    header = tiny_header(dim=64, hidden_dim=192, n_layers=N_LAYERS, n_heads=4,
                         n_kv_heads=2, vocab_size=128, seq_len=SEQ_LEN,
                         qkv_bias=request.param)
    path = str(d / "m.m")
    write_synthetic_model(path, header, seed=3)
    return load_params_from_m_quantized(path, load_model_header(path),
                                        dtype=jnp.float32)


@pytest.fixture
def kernel_on():
    linear.set_pallas_interpret(True)
    yield
    linear.set_pallas_interpret(False)


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_stack_read_equals_sliced_planes_bit_for_bit(loaded, kernel_on, layout):
    """A padded prefill bucket, then decode steps (t = 1) with a parked lane:
    logits and cache equal the per-layer loop's at every step."""
    config, params = loaded
    fwd = jax.jit(lambda p, t, q, c: llama_forward(config, p, t, q, c))
    ref = jax.jit(lambda p, t, q, c: _layer_loop_forward(config, p, t, q, c))
    got_c, ref_c = _cache(config, layout), _cache(config, layout)
    rng = np.random.default_rng(5)
    steps = [(rng.integers(0, 128, (N_LANES, 8)),
              np.stack([np.arange(8), np.arange(8), np.full(8, SEQ_LEN)]))]
    pos = np.array([8, 5, SEQ_LEN])
    for _ in range(3):
        steps.append((rng.integers(0, 128, (N_LANES, 1)), pos[:, None].copy()))
        pos = pos + np.array([1, 1, 0])
    reset_trace_stats()
    for tokens, positions in steps:
        tokens = jnp.asarray(tokens, jnp.int32)
        positions = jnp.asarray(positions, jnp.int32)
        got, got_c = fwd(params, tokens, positions, got_c)
        want, ref_c = ref(params, tokens, positions, ref_c)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got_c.k), np.asarray(ref_c.k))
        np.testing.assert_array_equal(np.asarray(got_c.v), np.asarray(ref_c.v))
    # the scan read stacks (two programs: the bucket and t = 1), the loop none
    assert TRACE_STATS["stacked_consumes"] == 2 * 7, TRACE_STATS
    assert float(jnp.abs(got_c.k).sum()) > 0.0


def _layer_scan_operands(config, params, cache, t, mesh=None):
    """(shapes of the consts, shapes of the scanned inputs) of the layer scan,
    uint8 operands only: the nibble planes are the program's only uint8."""
    tokens = jnp.zeros((N_LANES, t), jnp.int32)
    closed = jax.make_jaxpr(
        lambda p, c: llama_forward(config, p, tokens, tokens, c, mesh=mesh)
    )(params, cache)
    (eqn,) = [e for e in _scans(closed.jaxpr)
              if e.params["length"] == config.n_layers]
    n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
    u8 = lambda vs: sorted(v.aval.shape for v in vs if v.aval.dtype == jnp.uint8)
    return u8(eqn.invars[:n_consts]), u8(eqn.invars[n_consts + n_carry:])


def _plane_stacks(params):
    return sorted(getattr(params.layers, f).packed.shape for f in Q40_FIELDS)


@pytest.mark.parametrize("t", [1, 8], ids=["decode", "prefill_bucket"])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_no_q40_plane_is_scanned_when_the_kernel_reads_stacks(
        loaded, kernel_on, layout, t):
    config, params = loaded
    consts, scanned = _layer_scan_operands(config, params, _cache(config, layout), t)
    assert scanned == []
    assert consts == _plane_stacks(params)


@pytest.mark.parametrize("why", ["xla_fallback", "mesh"])
def test_q40_planes_stay_scanned_where_the_kernel_does_not_read_stacks(
        loaded, why):
    """With the kernel off every plane is dequantized by XLA from its scanned
    slice; on a pure-TP mesh the kernel runs on the local shard under
    shard_map, which takes a plane. Both scan the planes, as ever."""
    config, params = loaded
    mesh = None
    if why == "mesh":
        linear.set_pallas_interpret(True)
        mesh = make_mesh(MeshPlan(tp=2))
        params = shard_params(params, mesh)
    try:
        reset_trace_stats()
        consts, scanned = _layer_scan_operands(
            config, params, init_kv_cache(config, N_LANES), 1, mesh=mesh)
    finally:
        linear.set_pallas_interpret(False)
    assert consts == []
    assert scanned == _plane_stacks(params)
    assert TRACE_STATS["stacked_consumes"] == 0, TRACE_STATS


def test_one_traced_forward_counts_seven_stack_reads(loaded, kernel_on):
    """wq, wk, wv, wo, w1, w3, w2: the layer body traces once under the scan.
    The head's wcls is a plane of its own and is not among them."""
    config, params = loaded
    assert isinstance(params.wcls, PackedQ40) and params.wcls.packed.ndim == 2
    tokens = jnp.zeros((N_LANES, 1), jnp.int32)
    reset_trace_stats()
    jax.make_jaxpr(
        lambda p, c: llama_forward(config, p, tokens, tokens, c)
    )(params, init_kv_cache(config, N_LANES))
    assert TRACE_STATS["stacked_consumes"] == 7, TRACE_STATS
    # whatever kernel body this trace made was handed x as it is, and (the
    # loader's tree: float16 scales, stacks of two layers) a converted plane
    assert TRACE_STATS["natural_x_consumes"] == TRACE_STATS["impl_traces"], TRACE_STATS
    assert TRACE_STATS["scale_converts"] == TRACE_STATS["impl_traces"], TRACE_STATS
    assert TRACE_STATS["scale_stack_reads"] == 0, TRACE_STATS


@pytest.mark.parametrize("handed", ["float16_scales", "at_rest"])
def test_the_engine_rests_the_stacks_the_kernel_reads_in_place(loaded, handed, monkeypatch):
    """Where it takes its weights the engine makes the kernel's form of the
    scale stacks whose tiles the kernel will read in place
    (``reads_scales_in_place``: stacks XLA cannot stage whole; here every
    stack, the budget set to nothing), once, and holds no float16 copy of
    them; a leaf whose plane is sliced out a call (the head's plane; without
    the patch every stack of a model this small) stays as it arrived, as the
    loader made it, and so does a tree that is at rest already, leaf for
    leaf. The bits are the float16 values', so every product is the same."""
    from distributed_llama_multiusers_tpu.ops import pallas_q40 as pq
    from distributed_llama_multiusers_tpu.quants.packed import q40_at_rest
    from distributed_llama_multiusers_tpu.runtime import InferenceEngine

    import latent_toy

    config, params = loaded
    int16, float16 = np.dtype(np.int16), np.dtype(np.float16)
    assert latent_toy.scale_dtypes(params) == {float16}  # the loader's
    untouched = InferenceEngine(config, params, n_lanes=N_LANES, prefill_buckets=(8,))
    assert untouched.params.layers.w1.scales is params.layers.w1.scales  # 2 layers: sliced
    monkeypatch.setattr(pq, "VMEM_BYTES", pq.VMEM_LIMIT_BYTES)  # nothing can be staged
    if handed == "at_rest":
        params = q40_at_rest(params, only=pq.reads_scales_in_place)
    engine = InferenceEngine(config, params, n_lanes=N_LANES, prefill_buckets=(8,))
    stacks = {f: getattr(engine.params.layers, f).scales for f in Q40_FIELDS}
    assert {np.dtype(s.dtype) for s in stacks.values()} == {int16}
    assert engine.params.wcls.scales is params.wcls.scales  # a plane: as it arrived
    assert engine.params.wcls.scales.dtype == float16
    for f, s in stacks.items():
        if handed == "at_rest":
            assert s is getattr(params.layers, f).scales
        else:
            np.testing.assert_array_equal(
                np.asarray(s), np.asarray(getattr(loaded[1].layers, f).scales).view(np.int16))


# which Q40 scale stacks of each benchmark configuration the engine puts at
# rest, and so which cells' step programs differ from a float16 tree's
RESTED = {
    "mistral-7b-v0.3": {".layers.w1", ".layers.w2", ".layers.w3"},
    "qwen2.5-7b": {".layers.w1", ".layers.w2", ".layers.w3"},
    "minicpm-sala": {".dense.w1", ".dense.w2", ".dense.w3"},  # [32, 128, 16384]: 128 MiB each
    "kanana-2-30b-a3b": set(), "lfm2-24b-a2b": set(), "deepseek-v3.2": set(),  # wo: 63 MiB
    "jamba2-3b": set(), "command-a-plus-05-2026": set(), "mimo-v2-flash": set(),
    "solar-open2-250b": set(),  # the widest: nine layers' delta_q / k / v / out, 18 MiB each
}


@pytest.mark.parametrize("name", sorted(RESTED))
def test_which_stacks_of_a_benchmark_configuration_rest(name, monkeypatch):
    """The parameter tree of each of the benchmark's configurations, by shape
    alone (``eval_shape`` of its family's generator: nothing is made), through
    the engine's rule: the three FFN stacks of the dense 7B models and of
    MiniCPM-SALA (112-128 MiB each) are read in place; every other stack and
    every head is under the line, stays float16, and its cell's programs are
    a float16 tree's. A configuration that is added gets a line here."""
    import json
    import os
    import sys

    from distributed_llama_multiusers_tpu.ops.pallas_q40 import reads_scales_in_place

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    assert {c["name"] for c in bench["configs"]} == set(RESTED)
    cfg = json.load(open(os.path.join(
        root, next(c["file"] for c in bench["configs"] if c["name"] == name))))
    monkeypatch.setattr(sys, "path", [os.path.join(root, "benchmarks"), root] + sys.path)
    monkeypatch.setattr(jax, "block_until_ready", lambda t: t)  # tracers, here
    from harness import cells

    family = cells.load_family(cfg)
    config = family.program_config(cfg)
    tree = jax.eval_shape(lambda: family.assemble_params(
        config, family.device_weights(config, 55, jnp.bfloat16)))
    leaves = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, PackedQ40))[0]
    q40 = {jax.tree_util.keystr(path): w for path, w in leaves if isinstance(w, PackedQ40)}
    assert len(q40) >= 8 and all(w.scales.dtype == jnp.float16 for w in q40.values())
    assert {k for k, w in q40.items() if reads_scales_in_place(w.scales)} == RESTED[name]
