"""No step program holds a sort of the vocabulary (PR 34).

The sampler decides the nucleus by a threshold search over the unsorted row
(``runtime/engine.py`` ``nucleus_keep``) and has no other path: there is no
fallback whose hits could be counted, so the witness that the mechanism is
the one that runs is that the lowered and the compiled form of every step
family that draws a token hold no ``sort`` and no ``top_k`` / ``TopK``. The
control lowers the sorted form the search replaced and finds both. Every
family is read twice: over an engine whose lanes the sampler takes at once,
and over one whose lanes it takes in two groups (PR 44, ``sample_lanes``).
"""

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from distributed_llama_multiusers_tpu.formats import load_model_header
from distributed_llama_multiusers_tpu.models import load_params_from_m
from distributed_llama_multiusers_tpu.runtime import InferenceEngine
from distributed_llama_multiusers_tpu.runtime import engine as engine_mod

# StableHLO / CHLO operations and HLO instructions or custom-call targets
SORTS = re.compile(
    r"stablehlo\.sort|chlo\.top_k|mhlo\.topk|\bsort\(|\bsort\.\d+|TopK|top_k|topk",
)

LANES = 4


def _z(e):
    return np.zeros(e.n_lanes, np.int32)


def _park(e):
    return np.full(e.n_lanes, e.config.seq_len, np.int32)


def _drafts(e, k):
    return np.zeros((e.n_lanes, k), np.int32)


# the jitted program's attribute -> how the engine's own entry points reach it
PROGRAMS = {
    "_prefill_fn": lambda e: e.prefill_chunk(0, [1, 2, 3], 0),
    "_decode_fn": lambda e: e.decode(_z(e), _z(e)),
    "_decode_nologits_fn": lambda e: e.decode(_z(e), _z(e), want_logits=False),
    "_decode_spec_fn": lambda e: e.decode_spec(
        _z(e), _drafts(e, e.SPEC_DRAFT), _z(e), _z(e)),
    "_decode_pl_fn": lambda e: (
        e.decode_pipelined(_z(e), tokens=_z(e)),
        e.decode_pipelined(_z(e) - 1),   # chained: positions from the carry
        e.pipeline_flush()),
    "_decode_spec_pl_fn": lambda e: (
        e.decode_spec_pipelined(_z(e), _drafts(e, e.SPEC_DRAFT + 1), _z(e),
                                tokens=_z(e)),
        e.pipeline_flush()),
    "_decode_prefill_fn": lambda e: (
        e.decode_prefill_fused(_park(e), p_lane=0, chunk=[1, 2, 3],
                               tokens=_z(e)),
        e.pipeline_flush()),
    "_decode_spec_prefill_fn": lambda e: (
        e.decode_spec_prefill_fused(_park(e), _drafts(e, e.SPEC_DRAFT + 1),
                                    _z(e), p_lane=0, chunk=[1, 2, 3],
                                    tokens=_z(e)),
        e.pipeline_flush()),
    "_sample_one": lambda e: e.sample_token(
        np.zeros(e.config.vocab_size, np.float32), 0.7, 0.9, 1, 0),
}


@pytest.fixture(scope="module", params=[1, 2], ids=["one_group", "two_groups"])
def engine(tiny_model, request):
    h = load_model_header(tiny_model["model"])
    config, params = load_params_from_m(tiny_model["model"], h,
                                        dtype=jnp.float32)
    # the budget is read when a step program traces, so it stays as set for
    # as long as this engine is the module's
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "SAMPLER_GROUP_BYTES",
                   8 * config.vocab_size * LANES // request.param)
        e = InferenceEngine(config, params, n_lanes=LANES, prefill_buckets=(4,))
        assert e.sampler_groups == request.param == e.path_facts()["sampler_groups"]
        yield e


def _spy(fn, seen):
    """``fn`` with the (StableHLO, optimized HLO) of each call's form kept."""

    def spy(*args, **kw):
        lowered = fn.lower(*args, **kw)
        seen.append((lowered.as_text(), lowered.compile().as_text()))
        return fn(*args, **kw)

    return spy


def _assert_no_sort(name, seen):
    assert seen, f"{name} was not dispatched"
    for texts in seen:
        for text in texts:
            found = sorted(set(SORTS.findall(text)))
            assert not found, f"{name}: {found}"


@pytest.mark.parametrize("attr", sorted(PROGRAMS))
def test_step_program_holds_no_sort(engine, attr):
    fn, seen = getattr(engine, attr), []
    setattr(engine, attr, _spy(fn, seen))
    try:
        PROGRAMS[attr](engine)
    finally:
        setattr(engine, attr, fn)
    _assert_no_sort(attr, seen)
    # the sampler is IN the program that was read, not beside it
    assert all("dl.sampler" in hlo for _, hlo in seen) or attr == "_sample_one"
    # and so is the loop over its groups: the lanes' rows as [groups, group,
    # V], a shape nothing else in a step program has (one row is never grouped)
    if attr not in ("_sample_one", "_prefill_fn"):
        rows = f"tensor<2x{LANES // 2}x{engine.config.vocab_size}xf32>"
        assert all((rows in shlo) == (engine.sampler_groups == 2) for shlo, _ in seen)


@pytest.mark.parametrize("h", [2, 4])
def test_multi_step_program_holds_no_sort(engine, h):
    z, seen = _z(engine), []
    fn = engine._make_decode_multi(h)
    engine._decode_multi_fns[h] = _spy(fn, seen)
    try:
        engine.decode_multi(z, z, h=h)
    finally:
        engine._decode_multi_fns[h] = fn
    _assert_no_sort(f"decode_multi[{h}]", seen)


def test_the_witness_finds_the_sort_it_replaced():
    """Control: the sorted form lowers to ``chlo.top_k`` and compiles to a
    sort, and the pattern above finds each."""

    def sorted_lane(row):
        vals, idx = jax.lax.top_k(row, row.shape[0])
        p = jax.nn.softmax(vals)
        return idx[jnp.argmax((jnp.cumsum(p) - p) < 0.9)]

    lowered = jax.jit(jax.vmap(sorted_lane)).lower(
        jax.ShapeDtypeStruct((LANES, 96), jnp.float32))
    assert SORTS.search(lowered.as_text())
    assert SORTS.search(lowered.compile().as_text())
