"""A toy latent-attention model with a routed FFN for the tier-1 tests: the
benchmark's deepseek_v3 family (its generator and its plain reference) at the
rehearsal's size, loaded by path as ``tests/test_bench_family_seam.py`` loads
the seam's cases. ``load("tiny_lfm2.json")`` is the lfm2_moe family's toy (conv
and attention mixers in a pattern) the same way.

An engine is a module's, not a case's: its step programs are ``jax.jit``
closures of the instance, so a new engine traces and compiles every program it
touches anew (12-17 s for a toy and one prefill, 30-50 s warmed).
``module_engine`` is the fixture a family's file takes its one engine from,
``Serving`` the one warmed engine a lane count that its schedulers take in
turn, ``TOYS`` the one table of toys: a row of it is what
``tests/test_lane_state_contract.py`` asks of a new family."""

import json
import os
import sys
from typing import NamedTuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")


def load(name: str = "tiny_latent.json"):
    """(cfg, family, correct): the toy configuration file's keys (``name``
    under the rehearsal's configs), its family module, and the harness's
    comparison."""
    path = list(sys.path)
    sys.path[:0] = [p for p in (BENCH_DIR, ROOT) if p not in sys.path]
    try:
        from harness import cells, correct

        with open(os.path.join(BENCH_DIR, "tests", "rehearsal", "configs", name)) as f:
            cfg = json.load(f)
        return cfg, cells.load_family(cfg), correct
    finally:
        sys.path[:] = path


def wide_lfm2():
    """(cfg, family, correct) of the lfm2_moe toy at a shape the in-place
    decode attention takes (ops/pallas_attention.py ``supports``): 4 heads of
    64 on 2 kv heads, so a merged K/V row is one whole 128-lane tile, and a
    context of two 256-row blocks, with sample prompts on both sides of the
    block's edge. Served in bfloat16 (``engine(..., dtype=jnp.bfloat16)``)."""
    import copy

    cfg, family, correct = load("tiny_lfm2.json")
    cfg = copy.deepcopy(cfg)
    cfg.update(hidden_size=256, max_position_embeddings=512)
    cfg["correctness"]["prompt_tokens"] = [20, 250, 300]
    return cfg, family, correct


def wide_latent():
    """(cfg, family, correct) of the latent toy at a shape the in-place decode
    attention takes (ops/pallas_attention.py ``supports(latent=True)``): a
    latent rank of one whole 128-lane tile beside the rope leaf's one, and a
    context of two of its 512-row blocks, with sample prompts on both sides
    of the block's edge. Served in bfloat16 (``engine(..., dtype=jnp.bfloat16)``)."""
    import copy

    cfg, family, correct = load()
    cfg = copy.deepcopy(cfg)
    cfg.update(kv_lora_rank=128, max_position_embeddings=1024)
    cfg["correctness"]["prompt_tokens"] = [20, 500, 600]
    return cfg, family, correct


def engine(family, cfg, seed=11, dtype=None, lanes=8, **kw):
    """(engine, tensors) as the benchmark builds them, at ``dtype``
    (activations and cache; float32 by default)."""
    import jax.numpy as jnp

    from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

    dtype = dtype or jnp.float32
    config = family.program_config(cfg)
    tensors = family.device_weights(config, seed, dtype)
    eng = InferenceEngine(config, family.assemble_params(config, tensors), n_lanes=lanes,
                          cache_dtype=dtype, **kw)
    return eng, tensors


STEP_FAMILIES = ("decode", "decode_nologits", "decode_multi", "decode_pl", "fused")


class Toy(NamedTuple):
    """A toy of the one table: its rehearsal configuration's file and, for a
    family with a per-lane state, what tests/test_lane_state_contract.py needs
    to hold that state to the rule (a toy without ``state`` has no row there).
    ``state``: the cache leaves a step overwrites in place; ``kept``: those
    kept by position, which a parked lane keeps too. ``ladder``: the row's
    engine's prefill buckets. ``lengths``: the prompts the cases cut (short,
    other, middle, long), ``cut`` / ``fused_cut`` where. ``pad``: the zero
    tokens behind a prompt in the padded tail's control (None: the state is a
    prompt's last rows only, and the control would pass). ``restart_differs``:
    a second chunk restarted from zero is another state. ``stale_rows``: an
    earlier request's rows stay in the leaf, never read (a ring): lanes are
    compared by what a step can read. ``families``: the step families the
    block serves. ``refuses_paged``: what its refusal of a paged pool names."""

    config: str
    state: tuple = ()
    kept: tuple = ("k", "v")
    ladder: tuple = (64,)
    lengths: tuple = (20, 30, 60, 100)
    cut: int = 30
    fused_cut: int = 16
    pad: int | None = None
    restart_differs: bool = True
    stale_rows: bool = False
    families: tuple = STEP_FAMILIES
    refuses_paged: str = "paged KV pool"


TOYS = {
    "llama": Toy("tiny.json"),
    "latent": Toy("tiny_latent.json"),
    "sparse": Toy("tiny_deepseek_v32.json"),
    # conv and attention mixers in a pattern: the window of a conv's last inputs
    "lfm2": Toy("tiny_lfm2.json", state=("conv",), restart_differs=False),
    # selective state-space layers: a float32 running sum and its conv's window
    "jamba": Toy("tiny_jamba.json", state=("ssm", "ssm_conv"), pad=44,
                 refuses_paged="6 state-space"),
    # linear-attention layers' matrix state beside block-sparse layers'
    # compressed keys (kept by position); the cut at 29 leaves a kernel's
    # rows on both sides
    "sala": Toy("tiny_minicpm_sala.json", state=("lin",), kept=("k", "v", "ck"), cut=29,
                fused_cut=15, pad=12, refuses_paged="5 linear-attention"),
    # gated delta-rule layers: a float32 matrix state and three convs' windows;
    # the cut at 29 falls inside a 32-row chunk of the chunk form
    "solar": Toy("tiny_solar_open2.json", state=("delta", "delta_conv"), cut=29, fused_cut=15,
                 pad=12, families=("decode", "decode_pl", "fused"),
                 refuses_paged="6 delta-rule"),
    # window layers' rings of 40 rows (a window of 8 and a chunk of 32) in a
    # context of 64: the long prompt wraps them
    "mimo": Toy("tiny_mimo_v2_flash.json", state=("wk", "wv"), ladder=(32,),
                lengths=(20, 30, 40, 61), cut=29, fused_cut=15, restart_differs=False,
                stale_rows=True, refuses_paged="does not serve"),
}


def toy(name: str):
    """(cfg, family, correct) of the table's row ``name``."""
    return load(TOYS[name].config)


def module_engine(family, cfg, **kw):
    """A module-scoped fixture ``(engine, tensors)``: the ONE engine of a
    family's file, taken by every case that only reads and writes lanes and by
    the float32 compare (a case that reads a lane prefills it first). A case
    keeps an engine of its own only where the construction is its subject."""
    import pytest

    @pytest.fixture(scope="module")
    def built():
        return engine(family, cfg, **kw)

    return built


def park(eng, live: dict):
    """(tokens, positions) of a step in which only ``live`` lanes move
    (``{lane: (token, position)}``): every other lane points past the context,
    where its cache writes are dropped, as the scheduler parks idle lanes."""
    import numpy as np

    tokens = np.zeros(eng.n_lanes, np.int32)
    positions = np.full(eng.n_lanes, eng.config.seq_len, np.int32)
    for lane, (tok, pos) in live.items():
        tokens[lane], positions[lane] = tok, pos
    return tokens, positions


class Serving:
    """One engine a lane count over ``(config, params, tokenizer)``, warmed
    once with the widest set of programs its schedulers ask for, serving each
    scheduler in turn. "Alone", "pipelined off", "prefix reuse off" are
    properties of the scheduler, not of the engine: ``serve`` starts one, runs
    the prompts, stops it, and hands back the tokens and what the engine
    counted meanwhile (the difference of two ``stats.snapshot()``s).
    ``multi_step``: the horizon warmed and handed to the schedulers; 0 where
    every one of them keeps the pipelined loop, which takes the multi-step
    programs' place."""

    def __init__(self, config, params, tokenizer, buckets=(8, 16), multi_step=8):
        self.config, self.params, self.tokenizer = config, params, tokenizer
        self.buckets, self.multi_step = buckets, multi_step
        self.engines = {}

    def engine(self, lanes: int):
        """The engine of ``lanes`` lanes, built and warmed at the first call."""
        from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine, warmup_engine

        if lanes not in self.engines:
            eng = InferenceEngine(self.config, self.params, n_lanes=lanes,
                                  prefill_buckets=self.buckets)
            warmup_engine(eng, spec=True, multi_step=self.multi_step)
            self.engines[lanes] = eng
        return self.engines[lanes]

    def serve(self, prompts, lanes=2, max_tokens=8, in_turn=False, **scheduler):
        """(tokens a prompt, the counters' growth). ``in_turn``: a request is
        submitted when the one before it has finished."""
        from distributed_llama_multiusers_tpu.runtime import ContinuousBatchingScheduler, Request

        eng = self.engine(lanes)
        scheduler.setdefault("multi_step", self.multi_step)
        sched = ContinuousBatchingScheduler(eng, self.tokenizer, **scheduler)
        before = eng.stats.snapshot()
        sched.start()
        try:
            reqs = []
            for p in prompts:
                reqs.append(sched.submit(Request(prompt=p, max_tokens=max_tokens, temperature=0.0)))
                if in_turn:
                    reqs[-1].future.result(timeout=600)
            for r in reqs:
                r.future.result(timeout=600)
                assert r.error is None, r.error
        finally:
            sched.stop()
        after = eng.stats.snapshot()
        grown = {k: v - before[k] if isinstance(v, (int, float)) else v for k, v in after.items()}
        return [list(r.generated_tokens) for r in reqs], grown


def serving(header, directory, scale=0.02, **kw) -> Serving:
    """A synthetic checkpoint of ``header`` through the real writer, the
    loader and the tokenizer's file, behind a ``Serving`` (``kw``: its ladder
    and horizon)."""
    import jax.numpy as jnp

    from distributed_llama_multiusers_tpu.formats import load_model_header, synthetic
    from distributed_llama_multiusers_tpu.models import load_params_from_m
    from distributed_llama_multiusers_tpu.tokenizer import Tokenizer

    model, tok = str(directory / "m.m"), str(directory / "t.t")
    synthetic.write_synthetic_model(model, header, seed=3, scale=scale)
    synthetic.write_synthetic_tokenizer(tok, vocab_size=header.vocab_size)
    config, params = load_params_from_m(model, load_model_header(model), dtype=jnp.float32)
    return Serving(config, params, Tokenizer(tok), **kw)


def scale_dtypes(tree) -> set:
    """The dtypes of every Q40 scale plane in ``tree`` (``PackedQ40.scales``,
    ``Q40Experts.scale_bits``): int16 for an expert stack and for a
    ``PackedQ40`` at rest (``q40_at_rest``: of an engine's ``params``, the
    stacks the kernel reads in place), float16 for a leaf as the loaders and
    packers make it."""
    import jax
    import numpy as np

    from distributed_llama_multiusers_tpu.quants.packed import PackedQ40, Q40Experts

    q40 = (PackedQ40, Q40Experts)
    leaves = jax.tree_util.tree_leaves(tree, is_leaf=lambda x: isinstance(x, q40))
    return {np.dtype(w[1].dtype) for w in leaves if isinstance(w, q40)}
