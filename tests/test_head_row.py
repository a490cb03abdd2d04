"""A prefill chunk's head meets the one row that is kept (ops/linear.py
``head``, the forwards' ``head_row``, PR 53).

(a) the three forwards with a row index a lane return ``[B, 1, vocab]``: that
    row of the all-rows call, to the bit, in the padded tail's neighbourhood,
    at the bucket's last row, and with unlike rows a lane; the cache they hand
    back is the all-rows call's;
(b) ``engine.prefill``'s row, greedy and sampled token are what the whole head
    gave (recomputed here as ``_prefill_half`` did before the cut: every row
    of the bucket, then the index) and the tokens the parent commit returned;
(c) the lowered ``_prefill`` and ``_decode_prefill`` programs of the largest
    bucket hold no ``bucket x vocab`` array (the all-rows forward, the
    control, does), ``path_facts()`` says ``prefill_head_rows: 1`` after
    warm-up and the bucket before it, and warm-up compiles a program a bucket
    as it did.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.formats import load_model_header
from distributed_llama_multiusers_tpu.formats.synthetic import tiny_header, write_synthetic_model
from distributed_llama_multiusers_tpu.models import load_params_from_m, load_params_from_m_quantized
from distributed_llama_multiusers_tpu.models.deepseek import forward_counted
from distributed_llama_multiusers_tpu.ops import linear
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine, warmup_engine

import latent_toy
from test_tracing import lowered_with_debug_info

BUCKETS = (8, 16)
ODD_BUCKET, ODD_VOCAB = 12, 160
FAMILIES = ("llama", "latent", "hybrid")
# (family -> its row of the one table of toys, tests/latent_toy.py)
TOYS = {"latent": "latent", "hybrid": "lfm2"}
PROMPT = [int(t) for t in np.random.default_rng(53).integers(2, 96, size=16)]
SAMPLER = dict(temp=0.8, topp=0.9, seed=1234)
# (greedy, sampled) of ``engine.prefill(0, PROMPT[:n], **SAMPLER)`` at the
# parent commit (02e8f00: every row of the bucket through wcls, then the index)
PARENT_TOKENS = {
    ("llama", 5): (45, 60), ("llama", 16): (61, 77),
    ("latent", 5): (171, 60), ("latent", 16): (6, 240),
    ("hybrid", 5): (115, 5), ("hybrid", 16): (52, 52),
}


def build(family: str, model_dir, lanes: int = 3, buckets=BUCKETS, quantized=False,
          vocab_size=None):
    """An engine on the tiny configuration the family's engine tests build
    (``vocab_size``: at another vocabulary)."""
    if family == "llama":
        path = str(model_dir / f"llama{vocab_size}.m")
        write_synthetic_model(
            path, tiny_header(**({"vocab_size": vocab_size} if vocab_size else {})), seed=0)
        load = load_params_from_m_quantized if quantized else load_params_from_m
        config, params = load(path, load_model_header(path), dtype=jnp.float32)
        return InferenceEngine(config, params, n_lanes=lanes, prefill_buckets=buckets)
    cfg, toy, _ = latent_toy.toy(TOYS[family])  # Q40 at rest
    if vocab_size:
        cfg = {**cfg, "vocab_size": vocab_size}
    return latent_toy.engine(toy, cfg, lanes=lanes, prefill_buckets=buckets)[0]


@pytest.fixture(scope="module")
def q40_engines(tmp_path_factory):
    """Engines whose every matmul, ``wcls`` among them, is a Q40 weight under
    the Pallas kernel in interpret mode at float32 where it tiles the plane
    (the XLA dequant where not): a row's result then does not depend on the
    rows beside it, which a float32 ``x @ w`` of the CPU does in its last bit."""
    made = {}

    def get(family):
        if family not in made:
            made[family] = build(family, tmp_path_factory.mktemp(family), quantized=True)
        return made[family]

    linear.set_pallas_interpret(True)
    yield get
    linear.set_pallas_interpret(False)


@pytest.fixture(scope="module")
def warmed(tmp_path_factory):
    """A family's engine on ``BUCKETS`` (three lanes), warmed: the engine
    itself, what ``path_facts()`` said of the head's rows before and after the
    warm-up, and how many programs either prefill family then held. One
    engine a family for the file (an engine a case traced and compiled each
    bucket's program anew: the tier-1 clock)."""
    @functools.cache
    def get(family):
        engine = build(family, tmp_path_factory.mktemp(family + "_warmed"))
        before = engine.path_facts()["prefill_head_rows"]
        warmup_engine(engine, spec=False)
        return (engine, before, engine.path_facts()["prefill_head_rows"],
                engine._prefill_fn._cache_size(), engine._decode_prefill_fn._cache_size())

    return get


@pytest.fixture(scope="module")
def odd_engines(tmp_path_factory):
    """A family's engine with one bucket and a vocabulary of sizes no other
    axis of the toys has: one a family, both of its prefill programs lowered
    from it."""
    return functools.cache(lambda family: build(
        family, tmp_path_factory.mktemp(family + "_odd"), lanes=2, buckets=(ODD_BUCKET,),
        vocab_size=ODD_VOCAB))


def whole_and_cut(engine, tokens, head_row):
    """(every row's logits, the cut call's logits, both caches) of one forward
    over ``tokens`` ``[B, T]`` from position 0 on the engine's first B lanes."""
    forward = _forward(engine, *tokens.shape)
    return forward(tokens, None), forward(tokens, head_row)


@functools.cache
def _forward(engine, b, t):
    """The jitted forward of ``engine`` over ``[b, t]`` tokens, one for the
    cases of one shape (a new ``jax.jit`` a case compiled both calls anew)."""
    cfg = engine.config
    cache = jax.tree_util.tree_map(lambda a: a[:, :b], engine.cache)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    return jax.jit(lambda tok, row: forward_counted(cfg)(
        cfg, engine.params, tok, positions, cache, head_row=row)[:2])


@pytest.mark.parametrize("rows", [
    pytest.param([4], id="padded_tail"),      # n_tokens 5 of a bucket of 8
    pytest.param([7], id="whole_bucket"),     # n_tokens == bucket
    pytest.param([6, 0, 3], id="unlike_rows_a_lane"),
])
@pytest.mark.parametrize("family", FAMILIES)
def test_forward_returns_the_named_row_of_the_whole_head(q40_engines, family, rows):
    engine = q40_engines(family)
    tokens = jnp.asarray(
        np.random.default_rng(len(rows)).integers(2, 96, size=(len(rows), 8)), jnp.int32)
    (whole, cache), (cut, cut_cache) = whole_and_cut(
        engine, tokens, jnp.asarray(rows, jnp.int32))
    assert whole.shape == (len(rows), 8, engine.config.vocab_size)
    assert cut.shape == (len(rows), 1, engine.config.vocab_size) and cut.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(cut[:, 0]), np.asarray(whole)[np.arange(len(rows)), rows])
    # every row is still written: the cache is the all-rows call's
    for a, b in zip(jax.tree_util.tree_leaves(cache), jax.tree_util.tree_leaves(cut_cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n_tokens", [5, 16], ids=["padded_tail", "whole_bucket"])
@pytest.mark.parametrize("family", FAMILIES)
def test_prefill_returns_what_the_whole_head_gave(warmed, family, n_tokens):
    engine = warmed(family)[0]
    cfg, prompt = engine.config, PROMPT[:n_tokens]
    bucket = next(b for b in BUCKETS if b >= n_tokens)
    # the chunk as _prefill_half ran it before the cut: the bucket's every row
    # through the head, then the row of the last real token
    lane_cache = jax.tree_util.tree_map(lambda a: a[:, :1], engine.cache)
    kw = {} if cfg.latent_attention and not cfg.layer_kinds else {
        "n_valid": jnp.asarray([n_tokens], jnp.int32)}
    logits = jax.jit(lambda tok: forward_counted(cfg)(
        cfg, engine.params, tok, jnp.arange(bucket, dtype=jnp.int32)[None], lane_cache,
        **kw)[0])(jnp.asarray([prompt + [0] * (bucket - n_tokens)], jnp.int32))
    want = np.asarray(logits[0, n_tokens - 1])

    row, greedy, pos = engine.prefill(0, prompt, **SAMPLER)
    assert pos == n_tokens and row.shape == (cfg.vocab_size,)
    # another program's float32 sums (a forward jitted alone): their last bits
    np.testing.assert_allclose(np.asarray(row), want, rtol=1e-5, atol=2e-5)
    assert greedy == int(np.argmax(want))
    sampled = int(engine.sample_token(
        want, SAMPLER["temp"], SAMPLER["topp"], SAMPLER["seed"], n_tokens - 1))
    assert int(engine.last_sampled) == sampled
    assert (greedy, sampled) == PARENT_TOKENS[family, n_tokens]


@pytest.mark.parametrize("attr", ["_prefill_fn", "_decode_prefill_fn"])
@pytest.mark.parametrize("family", FAMILIES)
def test_no_prefill_program_holds_a_bucket_of_logits(odd_engines, family, attr):
    bucket, vocab = ODD_BUCKET, ODD_VOCAB
    engine = odd_engines(family)
    assert engine.config.vocab_size == vocab
    # rows by the vocabulary, or by wcls's columns where the loader padded them
    wcls = engine.params.wcls
    cols = wcls.d_out if hasattr(wcls, "d_out") else wcls.shape[-1]
    seen = re.compile(rf"tensor<(?:\d+x)*{bucket}x(?:{vocab}|{cols})x\w+>")
    # the control: the forward that heads every row holds such an array
    lane_cache = jax.tree_util.tree_map(lambda a: a[:, :1], engine.cache)
    whole = jax.jit(lambda tok, pos: forward_counted(engine.config)(
        engine.config, engine.params, tok, pos, lane_cache)[0])
    zeros = jnp.zeros((1, bucket), jnp.int32)
    assert seen.search(whole.lower(zeros, zeros).as_text())
    # lowered_with_debug_info hands the program a chunk of 3 tokens
    text = lowered_with_debug_info(engine, attr)
    assert f"tensor<1x1x{vocab}xf32>" in text
    assert not seen.search(text)


@pytest.mark.parametrize("family", FAMILIES)
def test_warm_up_says_one_head_row_and_compiles_a_program_a_bucket(warmed, family):
    engine, before, after, prefills, fused = warmed(family)
    assert before == BUCKETS[-1]  # none traced yet
    assert after == engine.path_facts()["prefill_head_rows"] == 1
    # one program a bucket and family, as before the cut: no new program, at
    # the warm-up's end and after whatever the file's cases prefilled since
    assert prefills == engine._prefill_fn._cache_size() == len(BUCKETS)
    assert fused == engine._decode_prefill_fn._cache_size() == len(BUCKETS)
