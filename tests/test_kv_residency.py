"""KV residency: the cache rides the layer scan as its CARRY and layer ``l``
is appended where it lies (models/llama.py, "How the cache moves").

Two kinds of pin, both counts, shapes and bits, never a time:

* structure — nothing of the cache's size is a scanned input or a stacked
  output of the layer scan (a donated buffer aliases through a loop only as
  its carry), and the engine's pipelined programs still alias the donated
  cache to their output;
* values — logits and the returned cache are bit-for-bit those of a plain
  per-layer Python loop (no scan) that writes each layer's plane the way the
  scan used to: slice the plane, scatter the rows, read it, stack the planes
  again. Parked lanes, sentinel pages and padded prefill tails included.
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
from distributed_llama_multiusers_tpu.models import (
    init_kv_cache,
    llama_forward,
    load_params_from_m,
)
from distributed_llama_multiusers_tpu.models.llama import (
    KVCache,
    PagedKVCache,
    _dense_attention,
    _maybe_bias,
    _to_cache_dtype,
    init_paged_kv_cache,
)
from distributed_llama_multiusers_tpu.ops.activations import silu
from distributed_llama_multiusers_tpu.ops.linear import matmul
from distributed_llama_multiusers_tpu.ops.norm import rms_norm
from distributed_llama_multiusers_tpu.ops.rope import apply_rope
from distributed_llama_multiusers_tpu.runtime import InferenceEngine

N_LAYERS, N_LANES, SEQ_LEN, PAGE, N_PAGES = 3, 3, 48, 8, 14
CACHE_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "f8": jnp.float8_e4m3fn}


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    d = tmp_path_factory.mktemp("kv_residency")
    header = tiny_header(dim=64, hidden_dim=160, n_layers=N_LAYERS, n_heads=4,
                         n_kv_heads=2, vocab_size=128, seq_len=SEQ_LEN)
    path = str(d / "m.m")
    write_synthetic_model(path, header, seed=3)
    return load_params_from_m(path, load_model_header(path), dtype=jnp.float32)


def _cache(config, layout: str, dtype=jnp.float32):
    """Contiguous lanes, or a page pool whose lane 0 maps scattered pages,
    lane 1 half its blocks and lane 2 none (all sentinels)."""
    if layout == "contiguous":
        return init_kv_cache(config, N_LANES, dtype=dtype)
    cache = init_paged_kv_cache(config, N_LANES, n_pages=N_PAGES,
                                page_size=PAGE, dtype=dtype)
    table = np.full((N_LANES, SEQ_LEN // PAGE), N_PAGES, np.int32)
    table[0] = [3, 1, 7, 9, 0, 2]
    table[1, :3] = [4, 5, 6]
    return cache._replace(table=jnp.asarray(table))


def _layer_loop_forward(config, params, tokens, positions, cache):
    """llama_forward for a dense-FFN model on one device as a Python loop over
    the layers: every leaf of layer ``l`` sliced out of its stack (Q40 planes
    too, tests/test_weight_residency.py) and the cache handled plane by plane
    (the scan's old form). Biases and shared operand builds as the scan has
    them: both are identities for a model without biases or with the kernel
    off."""
    b, t = tokens.shape
    n_heads, n_kv, hd = config.n_heads, config.n_kv_heads, config.head_size
    paged = isinstance(cache, PagedKVCache)
    lane_idx = jnp.arange(b)[:, None]
    mask = jnp.arange(config.seq_len)[None, None, :] <= positions[:, :, None]
    if paged:
        n_pages, page = cache.k.shape[1], cache.k.shape[2]
        blk = jnp.clip(positions // page, 0, cache.table.shape[1] - 1)
        w_page = jnp.take_along_axis(cache.table, blk, axis=1)
        w_page = jnp.where(positions < config.seq_len, w_page, n_pages)
        at = (w_page, positions % page)
        gather = (cache.table[:, :, None] * page
                  + jnp.arange(page, dtype=jnp.int32)[None, None, :]
                  ).reshape(b, -1)[:, : config.seq_len]
    else:
        at = (lane_idx, positions)
    x = params.embedding[tokens]
    planes_k, planes_v = [], []
    for l in range(config.n_layers):
        lp = jax.tree.map(lambda a: a[l], params.layers)
        y = rms_norm(x, lp.rms_att, config.norm_epsilon)
        q = _maybe_bias(matmul(y, lp.wq), lp.bq).reshape(b, t, n_heads, hd)
        k = _maybe_bias(matmul(y, lp.wk), lp.bk).reshape(b, t, n_kv, hd)
        v = _maybe_bias(matmul(y, lp.wv), lp.bv).reshape(b, t, n_kv, hd)
        q = apply_rope(q, params.rope_cos, params.rope_sin, positions)
        k = apply_rope(k, params.rope_cos, params.rope_sin, positions)
        k_plane = cache.k[l].at[at].set(_to_cache_dtype(k, cache.k.dtype), mode="drop")
        v_plane = cache.v[l].at[at].set(_to_cache_dtype(v, cache.v.dtype), mode="drop")
        planes_k.append(k_plane)
        planes_v.append(v_plane)
        if paged:
            k_plane = k_plane.reshape(n_pages * page, n_kv, hd)[gather]
            v_plane = v_plane.reshape(n_pages * page, n_kv, hd)[gather]
        qf = q.astype(jnp.float32).reshape(b, t, n_kv, n_heads // n_kv, hd)
        attn = _dense_attention(qf, k_plane.astype(jnp.float32),
                                v_plane.astype(jnp.float32), mask,
                                1.0 / float(hd) ** 0.5)
        x = x + matmul(attn.reshape(b, t, n_heads * hd).astype(x.dtype), lp.wo)
        y = rms_norm(x, lp.rms_ffn, config.norm_epsilon)
        x = x + matmul(silu(matmul(y, lp.w1)) * matmul(y, lp.w3), lp.w2)
    y = rms_norm(x, params.rms_final, config.norm_epsilon)
    logits = matmul(y, params.wcls).astype(jnp.float32)[..., : config.vocab_size]
    return logits, cache._replace(k=jnp.stack(planes_k), v=jnp.stack(planes_v))


def _bits(x):
    return np.asarray(jnp.asarray(x).astype(jnp.float32))


@pytest.mark.parametrize("kv_dtype", list(CACHE_DTYPES))
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_scan_equals_per_layer_loop_bit_for_bit(loaded, layout, kv_dtype):
    """A padded prefill bucket, then decode steps in which lane 1 overwrites
    its padded tail before reading it and lane 2 stays parked: logits and
    cache equal the per-layer loop's at every step, in every cache dtype."""
    config, params = loaded
    fwd = jax.jit(lambda p, t, q, c: llama_forward(config, p, t, q, c))
    ref = jax.jit(lambda p, t, q, c: _layer_loop_forward(config, p, t, q, c))
    got_c = _cache(config, layout, CACHE_DTYPES[kv_dtype])
    ref_c = _cache(config, layout, CACHE_DTYPES[kv_dtype])
    rng = np.random.default_rng(5)
    # lane 1's prompt is 5 tokens: slots 5..7 of the bucket are padding
    steps = [(rng.integers(0, 128, (N_LANES, 8)),
              np.stack([np.arange(8), np.arange(8), np.full(8, SEQ_LEN)]))]
    pos = np.array([8, 5, SEQ_LEN])
    for _ in range(4):
        steps.append((rng.integers(0, 128, (N_LANES, 1)), pos[:, None].copy()))
        pos = pos + np.array([1, 1, 0])
    for tokens, positions in steps:
        tokens = jnp.asarray(tokens, jnp.int32)
        positions = jnp.asarray(positions, jnp.int32)
        got, got_c = fwd(params, tokens, positions, got_c)
        want, ref_c = ref(params, tokens, positions, ref_c)
        np.testing.assert_array_equal(_bits(got), _bits(want))
        np.testing.assert_array_equal(_bits(got_c.k), _bits(ref_c.k))
        np.testing.assert_array_equal(_bits(got_c.v), _bits(ref_c.v))
    assert float(jnp.abs(got_c.k.astype(jnp.float32)).sum()) > 0.0


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_parked_lane_and_sentinel_pages_write_nothing(loaded, layout):
    """``positions == seq_len`` (a parked lane) drops the append in both
    layouts; so does a paged lane whose table holds only sentinels, whatever
    its position. Every other byte of the cache stays what it was."""
    config, params = loaded
    cache = _cache(config, layout)
    cache = cache._replace(k=cache.k + 1.0, v=cache.v - 1.0)
    tokens = jnp.asarray([[5], [9], [11]], jnp.int32)
    # lane 0 writes slot 2; lane 1 is parked; lane 2 decodes at slot 4 — a
    # real write on contiguous lanes, dropped through its all-sentinel table
    positions = jnp.asarray([[2], [SEQ_LEN], [4]], jnp.int32)
    _, new = llama_forward(config, params, tokens, positions, cache)
    changed = np.argwhere(np.any(_bits(new.k) != _bits(cache.k), axis=(-1, -2)))
    if layout == "contiguous":
        want = [[l, lane, slot] for l in range(N_LAYERS)
                for lane, slot in ((0, 2), (2, 4))]
    else:  # lane 0's block 0 is page 3
        want = [[l, 3, 2] for l in range(N_LAYERS)]
    assert changed.tolist() == want
    changed_v = np.argwhere(np.any(_bits(new.v) != _bits(cache.v), axis=(-1, -2)))
    assert changed_v.tolist() == want


@pytest.mark.parametrize("kv_dtype", list(CACHE_DTYPES))
def test_paged_equals_contiguous_through_the_carry(loaded, kv_dtype):
    """The page pool read back through its table holds the contiguous lanes'
    bytes, and the logits are the same bits, prefill and decode."""
    config, params = loaded
    dt = CACHE_DTYPES[kv_dtype]
    cont, paged = _cache(config, "contiguous", dt), _cache(config, "paged", dt)
    rng = np.random.default_rng(11)
    # lanes 0 and 1 only hold mapped pages for these positions; lane 2 parked
    feeds = [(rng.integers(0, 128, (N_LANES, 8)),
              np.stack([np.arange(8), np.arange(8), np.full(8, SEQ_LEN)])),
             (rng.integers(0, 128, (N_LANES, 1)), np.array([[8], [8], [SEQ_LEN]]))]
    for tokens, positions in feeds:
        tokens = jnp.asarray(tokens, jnp.int32)
        positions = jnp.asarray(positions, jnp.int32)
        lc, cont = llama_forward(config, params, tokens, positions, cont)
        lp, paged = llama_forward(config, params, tokens, positions, paged)
        np.testing.assert_array_equal(_bits(lc[:2]), _bits(lp[:2]))
    table = np.asarray(paged.table)
    for lane, n_tok in ((0, 9), (1, 9)):
        for s in range(n_tok):
            page = table[lane, s // PAGE]
            np.testing.assert_array_equal(
                _bits(paged.k[:, page, s % PAGE]), _bits(cont.k[:, lane, s]))
            np.testing.assert_array_equal(
                _bits(paged.v[:, page, s % PAGE]), _bits(cont.v[:, lane, s]))


def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@pytest.mark.parametrize("t", [1, 8], ids=["decode", "prefill_bucket"])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_cache_is_the_layer_scans_carry(loaded, layout, t):
    """No scanned input and no stacked output of the layer scan has the
    cache's ``[L, ...]`` shape: both stacks are carried, which is the only
    way a donated buffer aliases through the loop."""
    config, params = loaded
    cache = _cache(config, layout)
    tokens = jnp.zeros((N_LANES, t), jnp.int32)
    positions = jnp.zeros((N_LANES, t), jnp.int32)
    closed = jax.make_jaxpr(
        lambda p, c: llama_forward(config, p, tokens, positions, c)
    )(params, cache)
    layer_scans = [e for e in _scans(closed.jaxpr)
                   if e.params["length"] == config.n_layers]
    assert len(layer_scans) == 1
    eqn = layer_scans[0]
    n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
    shape = cache.k.shape
    carried = [v.aval.shape for v in eqn.invars[n_consts:n_consts + n_carry]]
    scanned = [v.aval.shape for v in eqn.invars[n_consts + n_carry:]]
    stacked = [v.aval.shape for v in eqn.outvars[n_carry:]]
    consts = [v.aval.shape for v in eqn.invars[:n_consts]]
    assert carried.count(shape) == 2, carried
    assert shape not in scanned and shape not in stacked and shape not in consts
    # nor one layer's plane: the body reads it out of the carry itself
    assert shape[1:] not in [s[1:] for s in scanned + stacked if len(s) == len(shape)]


@pytest.mark.parametrize("program", ["_decode_pl_fn", "_decode_prefill_fn"])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_pipelined_programs_alias_the_donated_cache(loaded, layout, program):
    """The tiny engine's compiled ``_decode_pl`` / ``_decode_prefill`` hand the
    donated K and V stacks back as their outputs (``input_output_alias``)."""
    config, params = loaded
    engine = InferenceEngine(config, params, n_lanes=2, prefill_buckets=(8,),
                             paged_kv=layout == "paged", kv_page_size=PAGE)
    fn, seen = getattr(engine, program), []

    def spy(*args):
        seen.append(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args))
        return fn(*args)

    setattr(engine, program, spy)
    z = np.zeros(2, np.int32)
    if program == "_decode_pl_fn":
        engine.decode_pipelined(z, tokens=z)
    else:
        park = np.full(2, config.seq_len, np.int32)
        engine.decode_prefill_fused(park, p_lane=0, chunk=[0] * 8, tokens=z)
    engine.pipeline_flush()
    text = fn.lower(*seen[0]).compile().as_text()
    header = next(line for line in text.splitlines() if "input_output_alias" in line)
    aliases = header.split("input_output_alias={", 1)[1].split("}, entry_computation", 1)[0]
    assert aliases.count("-alias") >= 2, header  # K and V
