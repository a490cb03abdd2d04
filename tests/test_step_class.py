"""A step program says what it is (telemetry/names.py, PR 37).

(a) every heavy operation of every lowered step program carries exactly one
    ``dlstep.*`` class, the one of its family and static bucket, and the
    ``dlhalf.*`` of the half of the step it belongs to; the toy latent-routed
    and hybrid engines are lowered here too, with their own block scopes;
(b) the new components change nothing of what ``scope_path`` / ``scope_of``
    return, and parse through transforms;
(c) ``prefill_bucket_rows`` grows by the bucket where ``prefill_tokens``
    grows by the chunk, on the synchronous, the fused and the verify-fused
    dispatch;
(d) the host's ``step.fused`` / ``prefill.*`` slices say the bucket.
"""

import re

import numpy as np
import pytest
import jax.numpy as jnp

from distributed_llama_multiusers_tpu.formats import load_model_header
from distributed_llama_multiusers_tpu.formats.synthetic import (
    tiny_header,
    write_synthetic_model,
)
from distributed_llama_multiusers_tpu.models import load_params_from_m
from distributed_llama_multiusers_tpu.runtime import InferenceEngine
from distributed_llama_multiusers_tpu.telemetry import names

import latent_toy
from test_tracing import (  # the lowering, the models and the programs of the scope test
    HEAVY,
    LOC_DEF,
    LOC_USE,
    MODELS,
    PROGRAMS,
    engines,  # noqa: F401  (the module-scoped fixture, built again for this file)
    lowered_with_debug_info,
    run_scheduler,
    some_requests,
)

BUCKET = 4  # the one prefill bucket of every engine lowered here
# program -> (its class, the halves its heavy operations may sit under)
EXPECTED = {
    "_decode_pl_fn": (names.step_class("decode"), {names.HALF_DECODE}),
    "_decode_prefill_fn": (names.step_class("fused", BUCKET), set(names.HALVES)),
    "_decode_nologits_fn": (names.step_class("decode_sync_nologits"), {names.HALF_DECODE}),
    "_prefill_fn": (names.step_class("prefill", BUCKET), {names.HALF_PREFILL}),
}
# what closes a program after its halves: the carry and the packed readback,
# the logits row a synchronous program hands back
JOIN_SCOPES = {names.SCOPE_CARRY, names.SCOPE_HEAD}
# (its row of the one table of toys, tests/latent_toy.py; the scopes its block adds)
TOYS = {
    "latent": ("latent", names.LATENT_BLOCK_SCOPES),
    "hybrid": ("lfm2", names.CONV_MIXER_SCOPES + (names.SCOPE_ROUTER, names.SCOPE_EXPERTS)),
    # an indexer beside the latent block's attention: indexer_step_ms and
    # sparse_select_step_ms read these two
    "sparse": ("sparse", names.LATENT_BLOCK_SCOPES + names.SPARSE_ATTENTION_SCOPES),
    # linear-attention layers beside block-sparse ones: the six readers of the
    # minicpm_sala cell read these scopes
    "sala": ("sala", names.LINEAR_MIXER_SCOPES
             + (names.SCOPE_BLOCK_SCORES, names.SCOPE_SPARSE_SELECT, names.SCOPE_ATTENTION)),
}


FUNC = re.compile(r"^\s*func\.func (?:public|private) @([\w.]+)\(")
CALL = re.compile(r"\bcall @([\w.]+)\(")


def op_names(text: str, only=None) -> list[str]:
    """The whole ``op_name`` of every operation of a lowered program (of those
    whose line ``only`` matches), found as
    ``test_step_program_carries_every_scope`` finds them. An
    operation inside a private function (the layer scan's ``closed_call``
    body, a shared ``jit(_where)``) is located relative to that function, and
    XLA's call inliner puts the call site's ``op_name`` in front of it (a
    device trace shows ``jit(_decode_pl)/dl.layers/while/body/closed_call/
    dl.qkv/dot_general``): composed here the same way, once for every chain
    of call sites that reaches the function from ``main``."""
    locs = dict(LOC_DEF.findall(text))
    callers: dict = {}   # function -> [(calling function, the call site's name)]
    heavy = []           # (function, the operation's own name)
    fn = None
    for line in text.splitlines():
        m = FUNC.match(line)
        if m:
            fn = m.group(1)
            continue
        use = LOC_USE.search(line)
        if use is None:   # an op with a region closes it elsewhere
            continue
        name = locs.get(use.group(1), "")
        code = line.split(" loc(")[0]
        call = CALL.search(code)
        if call:
            callers.setdefault(call.group(1), []).append((fn, name))
        elif only is None or only.search(code):
            heavy.append((fn, name))

    def prefixes(f):
        if f == "main":
            return [""]
        return [p + site + "/" for g, site in callers[f] for p in prefixes(g)]

    return [p + name for f, name in heavy for p in prefixes(f)]


def heavy_op_names(text: str) -> list[str]:
    return op_names(text, HEAVY)


def check_classes_and_halves(text: str, attr: str, ffn_scopes=(names.SCOPE_FFN,)) -> None:
    cls, halves = EXPECTED[attr]
    heavy = heavy_op_names(text)
    assert len(heavy) >= 8
    seen = set()
    for op_name in heavy:
        assert names._STEP_RE.findall(op_name) == [cls], op_name
        found = names._HALF_RE.findall(op_name)
        assert len(found) <= 1 and set(found) <= halves, op_name
        if not found:
            # under neither half: only what joins them
            assert names.scope_of(op_name) in JOIN_SCOPES, op_name
        seen.update(found)
    assert seen == halves
    if attr == "_decode_prefill_fn":
        # each half does a forward of its own: both hold the layers' scopes
        for half in names.HALVES:
            under = {names.scope_of(n) for n in heavy if names.half_of(n) == half}
            assert {names.SCOPE_QKV, names.SCOPE_KV_WRITE} <= under, half
            # (``ffn_scopes``: a caller whose every FFN routes names the scope
            # its products lie one deeper under, beside dl.ffn)
            assert under & set(ffn_scopes), half
        # what joins them (no heavy operation among it: selects, a scatter of
        # one lane, the pack) is the closing dl.carry block and nothing else
        joins = [n for n in op_names(text)
                 if names.step_class_of(n) and names.half_of(n) is None]
        assert joins and all(names.scope_of(n) == names.SCOPE_CARRY for n in joins)


@pytest.mark.parametrize("attr", sorted(PROGRAMS))
@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_step_program_carries_its_class_and_halves(engines, model, paged, attr):  # noqa: F811
    check_classes_and_halves(lowered_with_debug_info(engines(model, paged), attr), attr)


@pytest.fixture(scope="module")
def toy_engines():
    made = {}

    def get(toy: str):
        if toy not in made:
            cfg, family, _ = latent_toy.toy(TOYS[toy][0])
            made[toy] = latent_toy.engine(family, cfg, 5, lanes=2,
                                          prefill_buckets=(BUCKET,))[0]
        return made[toy]

    return get


@pytest.mark.parametrize("attr", sorted(PROGRAMS))
@pytest.mark.parametrize("toy", sorted(TOYS))
def test_latent_and_hybrid_programs_carry_scopes_classes_and_halves(toy_engines, toy, attr):
    text = lowered_with_debug_info(toy_engines(toy), attr)
    paths = [names.scope_path(n) for n in dict(LOC_DEF.findall(text)).values()]
    # the scopes moe_experts_step_ms and conv_mixer_step_ms read, in every family
    for scope in names.ALL_SCOPES + TOYS[toy][1]:
        assert any(scope in p for p in paths), scope
    unscoped = [n for n in heavy_op_names(text) if names.scope_of(n) is None]
    assert not unscoped, unscoped[:5]
    check_classes_and_halves(text, attr)


def test_the_other_step_programs_say_their_class(engines):  # noqa: F811
    e = engines("dense", False)
    z = np.zeros(e.n_lanes, np.int32)
    drafts = np.zeros((e.n_lanes, e.SPEC_DRAFT + 1), np.int32)
    lowered = {
        "_decode_fn": e._decode_fn.lower(
            e.params, e.cache, z, z, z.astype(np.float32), z.astype(np.float32),
            z.astype(np.uint32), e._gtab(), z),
        "_decode_spec_fn": e._decode_spec_fn.lower(
            e.params, e.cache, z, drafts[:, 1:], z, z, z.astype(np.float32),
            z.astype(np.float32), z.astype(np.uint32), e._gtab(), z),
        "_decode_spec_pl_fn": e._decode_spec_pl_fn.lower(
            e.params, e.cache, z, z, z, drafts, z, z.astype(np.float32),
            z.astype(np.float32), z.astype(np.uint32), e._gtab(), z, z),
        "_decode_spec_prefill_fn": e._decode_spec_prefill_fn.lower(
            e.params, e.cache, z, z, z, drafts, z, z.astype(np.float32),
            z.astype(np.float32), z.astype(np.uint32), jnp.int32(0),
            np.zeros(BUCKET, np.int32), jnp.int32(0), jnp.int32(3), jnp.float32(0),
            jnp.float32(0.9), jnp.uint32(0), e._gtab(), z, z, jnp.int32(0)),
        "_decode_multi": e._make_decode_multi(3).lower(
            e.params, e.cache, z, z, z.astype(np.float32), z.astype(np.float32),
            z.astype(np.uint32), e._gtab(), z),
    }
    want = {
        "_decode_fn": ("dlstep.decode_sync", {names.HALF_DECODE}),
        "_decode_spec_fn": ("dlstep.spec", {names.HALF_DECODE}),
        "_decode_spec_pl_fn": ("dlstep.spec_pl", {names.HALF_DECODE}),
        "_decode_spec_prefill_fn": (f"dlstep.spec_fused.b{BUCKET}", set(names.HALVES)),
        "_decode_multi": ("dlstep.decode_multi.b3", {names.HALF_DECODE}),
    }
    assert set(names.STEP_PROGRAMS) == {k.removesuffix("_fn") for k in {**want, **EXPECTED}}
    for attr, low in lowered.items():
        heavy = heavy_op_names(low.as_text(debug_info=True))
        cls, halves = want[attr]
        assert len(heavy) >= 8
        assert {c for n in heavy for c in names._STEP_RE.findall(n)} == {cls}, attr
        assert all(names.step_class_of(n) == cls for n in heavy), attr
        assert {names.half_of(n) for n in heavy} - {None} == halves, attr


# ---------------------------------------------------------------------------
# (b) the names
# ---------------------------------------------------------------------------

OP_NAMES = [
    # (as the program writes it since PR 37, the same without the new components)
    ("jit(_decode_pl)/jit(main)/dlstep.decode/dlhalf.decode/dl.layers/while/body/closed_call/dl.attention/dot_general:",
     "jit(_decode_pl)/jit(main)/dl.layers/while/body/closed_call/dl.attention/dot_general:"),
    ("jit(_decode_prefill)/dlstep.fused.b1024/dlhalf.prefill/dl.sampler/cond/branch_1_fun/vmap()/top_k",
     "jit(_decode_prefill)/dl.sampler/cond/branch_1_fun/vmap()/top_k"),
    ("jit(_decode_prefill)/dlstep.fused.b256/dl.carry/concatenate",
     "jit(_decode_prefill)/dl.carry/concatenate"),
    ("jit(_decode_pl)/dlstep.decode/dlhalf.decode/vmap(dl.sampler)/sort",
     "jit(_decode_pl)/vmap(dl.sampler)/sort"),
    ("jit(_prefill)/dlstep.prefill.b512/dlhalf.prefill/dl.layers/while/body/dynamic_update_slice",
     "jit(_prefill)/dl.layers/while/body/dynamic_update_slice"),
    ("jit(_decode_pl)/dlstep.decode/convert_element_type", "jit(_decode_pl)/convert_element_type"),
    ("jit(model.embed)/gather", "jit(model.embed)/gather"),
    ("", ""),
]


@pytest.mark.parametrize("with_new,without", OP_NAMES)
def test_scopes_read_the_same_with_and_without_the_new_components(with_new, without):
    assert names.scope_path(with_new) == names.scope_path(without)
    assert names.scope_of(with_new) == names.scope_of(without)
    assert names.step_class_of(without) is None and names.half_of(without) is None
    for new in (names.STEP_PREFIX, names.HALF_PREFIX, *names.HALVES,
                names.step_class("fused", 1024), names.step_class("decode")):
        assert names.scope_path(new) == []


def test_classes_and_halves_parse_through_transforms():
    assert names.step_class("fused", 1024) == "dlstep.fused.b1024"
    assert names.step_class("decode") == "dlstep.decode"
    for op_name, cls, half in [
        (OP_NAMES[0][0], "dlstep.decode", names.HALF_DECODE),
        (OP_NAMES[1][0], "dlstep.fused.b1024", names.HALF_PREFILL),
        (OP_NAMES[2][0], "dlstep.fused.b256", None),
        ("jit(f)/vmap(dlstep.spec_fused.b64)/while/body/closed_call/vmap(dlhalf.decode)/dl.ffn/dot_general",
         "dlstep.spec_fused.b64", names.HALF_DECODE),
        ("jit(f)/dlstep.decode_multi.b8/dlhalf.decode/while/body/closed_call/dl.head/slice",
         "dlstep.decode_multi.b8", names.HALF_DECODE),
        ("jit(f)/mydlstep.decode/xdlhalf.decode/add", None, None),
        ("jit(_decode_pl)/dl.carry/select_n", None, None),
    ]:
        assert names.step_class_of(op_name) == cls, op_name
        assert names.half_of(op_name) == half, op_name
    # a class names its program: one family a jitted step program
    assert len(set(names.STEP_PROGRAMS.values())) == len(names.STEP_PROGRAMS)


# ---------------------------------------------------------------------------
# (c) the rows a prefill computes, counted beside the rows it was asked for
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def two_bucket_engine(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("step_class") / "dense.m")
    write_synthetic_model(path, tiny_header(), seed=5)
    config, params = load_params_from_m(path, load_model_header(path), dtype=jnp.float32)
    return InferenceEngine(config, params, n_lanes=2, prefill_buckets=(4, 8))


def dispatch_sync(e, chunk):
    e.prefill_chunk(0, chunk, 0)


def dispatch_fused(e, chunk):
    z = np.zeros(e.n_lanes, np.int32)
    e.decode_prefill_fused(np.full(e.n_lanes, e.config.seq_len, np.int32),
                           p_lane=0, chunk=chunk, tokens=z)
    e.pipeline_flush()


def dispatch_verify_fused(e, chunk):
    z = np.zeros(e.n_lanes, np.int32)
    e.decode_spec_prefill_fused(
        np.full(e.n_lanes, e.config.seq_len, np.int32),
        np.zeros((e.n_lanes, e.SPEC_DRAFT + 1), np.int32), z,
        p_lane=0, chunk=chunk, tokens=z)
    e.pipeline_flush()


@pytest.mark.parametrize("dispatch", [dispatch_sync, dispatch_fused, dispatch_verify_fused],
                         ids=["synchronous", "fused", "verify_fused"])
@pytest.mark.parametrize("chunk,bucket", [([1, 2, 3], 4), ([1, 2, 3, 4], 4), ([5, 4, 3, 2, 1], 8)])
def test_bucket_rows_grow_by_the_bucket_and_tokens_by_the_chunk(
        two_bucket_engine, dispatch, chunk, bucket):
    e = two_bucket_engine
    assert e.bucket_for(len(chunk)) == bucket
    before = e.stats.snapshot()
    dispatch(e, chunk)
    after = e.stats.snapshot()
    assert after["prefill_tokens"] - before["prefill_tokens"] == len(chunk)
    assert after["prefill_bucket_rows"] - before["prefill_bucket_rows"] == bucket
    snap = e.stats.reset()
    assert snap.prefill_bucket_rows == after["prefill_bucket_rows"] >= bucket
    cleared = e.stats.snapshot()
    assert cleared["prefill_bucket_rows"] == cleared["prefill_tokens"] == 0


# ---------------------------------------------------------------------------
# (d) the host's slices say the bucket
# ---------------------------------------------------------------------------


def test_fused_and_prefill_slices_hold_the_bucket():
    sched, tel = run_scheduler(some_requests())
    engine = sched.engine
    events = tel.tracer.snapshot()
    by_name = {n: [e for e in events if e.name == n]
               for n in ("step.fused", "prefill.fused", "prefill.sync", "step.pipelined")}
    assert by_name["step.fused"] and by_name["prefill.fused"] and by_name["prefill.sync"]
    for e in by_name["step.fused"]:
        assert e.args["bucket"] == engine.bucket_for(e.args["chunk"]), e.args
    for e in by_name["prefill.fused"] + by_name["prefill.sync"]:
        assert e.args["bucket"] == engine.bucket_for(e.args["tokens"]), e.args
    assert all("bucket" not in e.args for e in by_name["step.pipelined"])
    with engine.stats.lock:
        chunks = len(by_name["prefill.fused"]) + len(by_name["prefill.sync"])
        assert engine.stats.prefill_bucket_rows == chunks * engine.max_chunk()
