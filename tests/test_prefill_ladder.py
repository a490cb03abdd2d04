"""The default prefill ladder, by counts (PR 40): (64, 256, 512, 1024).

A chunk rides the smallest rung that holds it and the step program computes
every row of the rung, so the ladder decides how many rows of a prefill half
are padding. Held here, on the CPU, so that a later change of the ladder or of
the benchmark's request list shows in tier-1 before it shows on the chip:

(a) ``bucket_for`` at every rung's edge;
(b) no length rides a wider rung than it rode before (a 16 rung, no 512);
(c) the ladder the constructor keeps for a context shorter than the rungs;
(d) the rows ``chat_saturated.json``'s list rides, and its padded share;
(e) ``warmup_engine`` warms one program a rung in each bucketed family, and
    nothing compiles after it;
(f) a context of 64 to 255 positions has the one rung 64 where it had 16 and
    64: a short chunk near the end of the context, whose padded tail runs past
    ``seq_len``, gives what the same tokens give under an explicit 16 rung.
"""

import importlib.util
import io
import json
import os
import sys
from collections import Counter

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
from distributed_llama_multiusers_tpu.runtime.engine import (
    DEFAULT_PREFILL_BUCKETS,
    warmup_engine,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LADDER = (64, 256, 512, 1024)
BEFORE = tuple(sorted({16, *LADDER} - {512}))   # the ladder until PR 40


@pytest.fixture(scope="module")
def make_engine(tmp_path_factory):
    """``make_engine(seq_len, **engine_kw)``: a tiny dense engine with that
    context; the default ladder unless ``prefill_buckets`` is given."""
    d = tmp_path_factory.mktemp("ladder_models")
    loaded = {}

    def make(seq_len, **kw):
        if seq_len not in loaded:
            path = str(d / f"s{seq_len}.m")
            write_synthetic_model(path, tiny_header(seq_len=seq_len), seed=0)
            loaded[seq_len] = load_params_from_m(
                path, load_model_header(path), dtype=jnp.float32)
        config, params = loaded[seq_len]
        return InferenceEngine(config, params, n_lanes=kw.pop("n_lanes", 2), **kw)

    return make


@pytest.fixture(scope="module")
def full_ladder_engine(make_engine):
    return make_engine(2048)


def bucket_under(ladder, n):
    return next((b for b in ladder if n <= b), ladder[-1])


def test_the_default_ladder_is_the_one_this_file_counts_with():
    assert DEFAULT_PREFILL_BUCKETS == LADDER


@pytest.mark.parametrize("n, rung", [
    (1, 64), (16, 64), (17, 64), (64, 64), (65, 256), (256, 256), (257, 512),
    (512, 512), (513, 1024), (1024, 1024),
])
def test_bucket_for_at_every_edge(full_ladder_engine, n, rung):
    assert full_ladder_engine.prefill_buckets == LADDER
    assert full_ladder_engine.max_chunk() == 1024
    assert full_ladder_engine.bucket_for(n) == rung == bucket_under(LADDER, n)


@pytest.mark.parametrize("lo, hi, before, now", [
    (17, 64, 64, 64), (65, 256, 256, 256), (257, 512, 1024, 512), (513, 1024, 1024, 1024),
])
def test_no_length_rides_a_wider_rung_than_before(full_ladder_engine, lo, hi, before, now):
    lengths = range(lo, hi + 1)
    assert {bucket_under(BEFORE, n) for n in lengths} == {before}
    assert {full_ladder_engine.bucket_for(n) for n in lengths} == {now}
    assert now <= before


@pytest.mark.parametrize("seq_len, ladder", [
    (16, (16,)), (32, (16,)), (48, (16,)), (64, (64,)), (300, (64, 256)),
    (2048, LADDER),
])
def test_the_ladder_a_context_keeps(make_engine, seq_len, ladder):
    engine = make_engine(seq_len, n_lanes=1)
    assert engine.prefill_buckets == ladder
    assert engine.max_chunk() == ladder[-1] <= max(16, seq_len)


def test_the_rows_the_benchmarks_chat_list_rides():
    """`benchmarks/traffic/chat_saturated.json` through the harness's own
    `_quantile_lengths`: 131,637 prompt tokens; 198,400 rows under the default
    ladder (263,888 under the one before), a padded share of 0.3365 (0.5012):
    what `prefill_pad_share` reads in the saturated cells."""
    spec = importlib.util.spec_from_file_location(
        "bench_traffic_for_the_ladder",
        os.path.join(ROOT, "benchmarks", "harness", "traffic.py"))
    traffic = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = traffic   # its dataclass looks its module up
    spec.loader.exec_module(traffic)
    with open(os.path.join(ROOT, "benchmarks", "traffic", "chat_saturated.json")) as f:
        params = json.load(f)
    lengths = traffic._quantile_lengths(params["prompt_tokens"], params["requests"])
    assert (len(lengths), sum(lengths), max(lengths)) == (512, 131637, 1024)
    assert max(lengths) <= DEFAULT_PREFILL_BUCKETS[-1]   # one chunk an admission

    def rides(ladder):
        return Counter(bucket_under(ladder, n) for n in lengths)

    now, before = rides(DEFAULT_PREFILL_BUCKETS), rides(BEFORE)
    assert now == {64: 44, 256: 284, 512: 128, 1024: 56}
    assert before == {16: 1, 64: 43, 256: 284, 1024: 184}
    rows = sum(b * k for b, k in now.items())
    assert rows == 198400
    assert sum(b * k for b, k in before.items()) == 263888
    assert round(1 - sum(lengths) / rows, 4) == 0.3365


def test_warmup_warms_one_program_a_rung_and_a_family(make_engine):
    from distributed_llama_multiusers_tpu.telemetry import logs

    engine = make_engine(1024)
    assert engine.prefill_buckets == LADDER
    stream = io.StringIO()
    old = logs.default_logger().stream
    logs.default_logger().stream = stream
    try:
        warmup_engine(engine, spec=False, multi_step=0)
    finally:
        logs.default_logger().stream = old
    lines = [json.loads(x) for x in stream.getvalue().splitlines()]
    programs = [x["program"] for x in lines if x["event"] == "warmup_program"]
    for family in ("prefill", "decode_prefill"):
        assert [p for p in programs if p.startswith(family + "[")] == [
            f"{family}[{b}]" for b in LADDER]
    # four rungs as before PR 40: no program more
    assert len(programs) == 2 * len(BEFORE) + 5
    assert lines[-1]["event"] == "warmup_engine"
    assert lines[-1]["buckets_warmed"] == list(LADDER)
    # every rung's synchronous and fused program is there: nothing compiles
    n = engine.n_lanes
    park = np.full(n, engine.config.seq_len, np.int32)
    for rows in (3, 64, 65, 256, 257, 512, 513, 1000):
        engine.prefill_chunk(0, [1] * rows, 0)
        engine.decode_prefill_fused(park, p_lane=0, chunk=[1] * rows,
                                    tokens=np.zeros(n, np.int32))
        engine.pipeline_flush()
    assert engine.stats.jit_compiles_after_warmup == 0
    assert engine.stats.fused_bucket_hist == {64: 2, 256: 2, 512: 2, 1024: 2}
    assert engine.stats.prefill_bucket_rows == 2 * 2 * sum(LADDER)


def test_a_padded_tail_past_the_context_is_dropped(make_engine):
    """seq_len 64: the default ladder is (64,), so 3 tokens at position 60
    ride 64 rows of which 60 lie past the context. Same logits, same cache
    rows as under (16,), whose tail (rows 63-75) also runs past it."""
    rng = np.random.default_rng(40)
    head = [int(t) for t in rng.integers(1, 128, 60)]
    tail = [int(t) for t in rng.integers(1, 128, 3)]
    got = []
    for kw, ladder, rows in (({}, (64,), 128), ({"prefill_buckets": (16,)}, (16,), 80)):
        engine = make_engine(64, **kw)
        assert engine.prefill_buckets == ladder
        engine.prefill(0, head)
        last, greedy, pos = engine.prefill(0, tail, start_pos=60)
        assert pos == 63 and engine.stats.prefill_bucket_rows == rows
        got.append((greedy, np.asarray(last), np.asarray(engine.cache.k[:, 0, :63]),
                    np.asarray(engine.cache.v[:, 0, :63])))
    (greedy_a, *arrays_a), (greedy_b, *arrays_b) = got
    assert greedy_a == greedy_b
    for x, y in zip(arrays_a, arrays_b):
        assert np.isfinite(x).all()
        np.testing.assert_allclose(x, y, rtol=2e-4, atol=2e-5)
