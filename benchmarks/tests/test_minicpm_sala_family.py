"""The minicpm_sala family (`families/minicpm_sala.py`) at a toy size on the
CPU: the engine against the family's plain reference through `correct.compare`
(prefill, decode through the planes, the compressed keys and the matrix
states across dense_len, a chunked prefill, fused admissions beside decoding
lanes, twins left parked), the five controls that must fail, the reference's
recurrence and selection against both written out, the comparison of two
lanes' state, the roofline's counts by hand at the published shape, and the
six readers this family's cell adds."""
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import control
import control_window
from harness import cells, correct, sala_roofline
from harness.cells import BENCH_DIR

REHEARSAL = os.path.join(BENCH_DIR, "tests", "rehearsal")
CELL = "minicpm_sala_longctx_saturated"
READERS = ("linear_attention_step_ms", "linear_state_decode_roofline",
           "linear_chunk_prefill_roofline", "block_scores_step_ms",
           "block_sparse_attention_decode_roofline", "attn_blocks_read_share")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(REHEARSAL, "configs", "tiny_minicpm_sala.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def family(cfg):
    return cells.load_family(cfg)


def test_the_real_configuration_is_whole_and_names_the_family():
    bench = cells.load_benchmark()
    cfg = cells.load_config_file(bench, "minicpm-sala")
    family = cells.load_family(cfg)
    assert family.__file__ == os.path.join(BENCH_DIR, "families", "minicpm_sala.py")
    assert all(callable(getattr(family, name)) for name in cells.FAMILY_EXPORTS)
    c = family.program_config(cfg)
    assert (c.n_layers, c.n_linear_layers, c.n_sparse_layers, c.n_attention_layers) == (32, 24, 8, 8)
    assert [l for l, k in enumerate(c.layer_kinds) if k == 5] == [0, 9, 16, 17, 22, 29, 30, 31]
    assert (c.dim, c.hidden_dim, c.n_heads, c.n_kv_heads, c.head_size) == (4096, 16384, 32, 2, 128)
    assert (c.linear_n_heads, c.linear_head_dim, c.vocab_size, c.seq_len) == (32, 128, 73448, 32768)
    assert (c.sparse_kernel_size, c.sparse_kernel_stride, c.sparse_block_size, c.sparse_topk,
            c.sparse_window, c.sparse_init_blocks, c.sparse_dense_len) == (32, 16, 64, 64, 2048, 1, 8192)
    assert c.embed_scale == 12.0 and c.logit_divisor == 16.0 and c.norm_epsilon == 1e-6
    assert c.residual_scale == pytest.approx(1.4 / 32 ** 0.5) and c.recurrent_state and c.n_experts == 0
    entry = next(e for e in bench["configs"] if e["name"] == "minicpm-sala")
    assert entry["reduced"] == list(cfg["reduced"]) == ["max_position_embeddings"]
    # every number of the catalog's config under its own key
    published = {"head_dim": 128, "hidden_size": 4096, "intermediate_size": 16384,
                 "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
                 "num_attention_heads": 32, "num_hidden_layers": 32, "num_key_value_heads": 2,
                 "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000, "scale_emb": 12,
                 "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 256}
    assert {k: cfg[k] for k in published} == published
    assert cfg["mixer_types"].count("lightning-attn") == 24 and len(cfg["mixer_types"]) == 32
    assert set(cfg["assumed"]) >= {"sparse_config", "decay", "dense_len_by_position",
                                   "output_norm_and_gate", "mup_denominator"}
    cell = cells.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("minicpm-sala", "longctx_saturated", 1)
    assert cfg["serving"]["lanes"] == 16
    lengths = cfg["correctness"]["prompt_tokens"]
    steps = cfg["correctness"]["decode_steps"]
    assert any(n < 4096 for n in lengths) and any(4096 < n < 8192 for n in lengths)
    assert any(n > 8192 for n in lengths) and any(n < 8192 <= n + steps for n in lengths)
    mine = [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == list(READERS)
    with pytest.raises(SystemExit, match="lightning_nkv"):
        family.program_config(dict(cfg, lightning_nkv=8))
    with pytest.raises(SystemExit, match="mixer_types"):
        family.program_config(dict(cfg, mixer_types=["mamba"] * 32))


def test_engine_agrees_with_the_reference_and_the_routes_read_zero(cfg, family):
    sound = control.readings(family, cfg, "as_configured", [3_000_000_033], log=lambda s: None)
    assert all(r["ok"] for r in sound), sound
    assert all(r["route_kv_rel_err"] == 0.0 and r["route_token_mismatches"] == 0 for r in sound)
    assert all(r["prefill_rel_err"] < 1e-5 and r["decode_rel_err"] < 1e-5 for r in sound)


def test_admissions_swapped_fail_by_the_state_and_by_the_tokens(cfg, family):
    swapped = control.readings(family, cfg, "admits_swapped", [31], log=lambda s: None)
    assert not swapped[0]["ok"] and swapped[0]["route_kv_rel_err"] > 0.1


def test_the_five_controls_fail(cfg, family):
    """The selection replaced by the newest blocks, the selection left out,
    lambda = 1 and the reference in f8, each in the reference's place
    (`control_window.py`, unedited); the f8 cache in the program."""
    got = control_window.readings(
        family, cfg, list(family.FAULTS) + [control_window.F8], [31], jnp.float32,
        log=lambda s: None)
    assert [r["variant"] for r in got] == ["newest_blocks", "no_selection", "no_decay",
                                           "float8_e4m3fn"]
    assert not any(r["ok"] for r in got), got
    # a sequence wholly under dense_len (20 tokens + 4) cannot show a selection
    assert got[0]["by_sequence"][0] == 0.0 and got[1]["by_sequence"][0] == 0.0
    assert got[0]["by_sequence"][-1] > 0.01 and got[1]["by_sequence"][-1] > 0.01
    assert min(got[2]["by_sequence"]) > 0.01  # the decay shows in every sequence
    f8 = control.readings(family, cfg, "f8_kv_cache", [31], log=lambda s: None)
    assert not f8[0]["ok"]


@pytest.fixture(scope="module")
def sample(cfg, family):
    """Seeded arrays, the sample sequences and the reference's logits."""
    t = family.device_weights(family.program_config(cfg), 31, jnp.float32)
    prompts, forced = correct.sample_sequences(cfg, 31)
    prefixes = [correct.prefix_lengths(cfg, len(p)) for p in prompts]
    return t, (prompts, forced, prefixes), correct.plain_logits(
        family, cfg, t, prompts, forced, prefixes)


@pytest.mark.parametrize("wrong", [
    dict(mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "lightning-attn", "minicpm4",
                      "lightning-attn", "minicpm4", "lightning-attn"]), dict(rms_norm_eps=1e-2),
    dict(scale_emb=6), dict(scale_depth=1.0), dict(dim_model_base=128), dict(rope_theta=100),
    dict(sparse_config=dict(kernel_size=4, kernel_stride=2, block_size=8, topk=3,
                            window_size=16, init_blocks=1, dense_len=48)),
    dict(sparse_config=dict(kernel_size=4, kernel_stride=2, block_size=8, topk=4,
                            window_size=8, init_blocks=1, dense_len=48)),
    dict(sparse_config=dict(kernel_size=4, kernel_stride=2, block_size=8, topk=4,
                            window_size=16, init_blocks=1, dense_len=64)),
], ids=["kinds", "eps", "scale_emb", "scale_depth", "dim_model_base", "theta", "topk", "window",
        "dense_len"])
def test_a_reference_of_another_model_is_told_apart(cfg, family, sample, wrong):
    t, seqs, want = sample
    got = correct.plain_logits(family, dict(cfg, **wrong), t, *seqs)
    assert correct._rms(correct.relative_errors(got, want)) > 0.003


@pytest.mark.parametrize("key", ["q_norm", "k_norm", "lin_q_norm", "lin_k_norm", "lin_o_norm",
                                 "attn_rms", "lin_rms"])
def test_a_reference_blind_to_a_new_tensor_is_told_apart(cfg, family, sample, key):
    t, (prompts, forced, prefixes), want = sample
    blind = dict(t, **{key: jnp.ones_like(t[key])})
    got = correct.plain_logits(family, cfg, blind, prompts, forced, prefixes)
    assert correct.relative_errors(got, want).max() > 0.003


def test_the_state_and_the_far_blocks_carry_their_share(cfg, family, sample):
    """With the gains as argued (`GAIN`, `QK_GAIN`) a sequence's last logits
    depend on its FIRST token, a hundred rows back: through the matrix states
    (its block, the first, is also always attended); and a lightning layer's
    output is mostly its state's part, a sparse row's mostly far blocks'."""
    t, (prompts, forced, prefixes), want = sample
    moved = [[p[0] ^ 1] + p[1:] for p in prompts]
    got = correct.plain_logits(family, cfg, t, moved, forced, prefixes)
    assert correct.relative_errors(got, want)[-1, -1] > 1e-3  # 100 + 4 rows later
    lam = family.decay_factors(32)
    np.testing.assert_allclose(lam[[0, 31]], [np.exp(-2.0 ** -0.25), np.exp(-2.0 ** -8)], rtol=1e-6)
    # o_t = q_t S_t: the row's own k_t^T v_t against the decayed sum before it
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((256, 32, 16)), jnp.float32) for _ in range(3))
    whole = np.asarray(family._recurrence(q, k, v, jnp.asarray(lam), scale=0.25))[-1]
    own = 0.25 * np.einsum("hd,hd->h", q[-1], k[-1])[:, None] * np.asarray(v[-1])
    rms = lambda x: float(np.sqrt(np.mean(np.square(x))))  # noqa: E731
    assert rms(whole - own) > 2.0 * rms(own)


def test_the_references_recurrence_and_selection_are_both_written_out(cfg, family):
    """`_recurrence` against numpy float64, a row at a time from S = 0; the
    chosen sets of `_sparse_block` against the definition with python loops."""
    rng = np.random.default_rng(1)
    q, k, v = (rng.standard_normal((40, 4, 8)) for _ in range(3))
    lam = family.decay_factors(4).astype(np.float64)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(family._recurrence(*(jnp.asarray(x, jnp.float32) for x in (q, k, v)),
                                            jnp.asarray(lam, jnp.float32), scale=0.5))
    s, want = np.zeros((4, 8, 8)), []
    for t in range(40):
        s = lam[:, None, None] * s + k[t][:, :, None] * v[t][:, None, :]
        want.append(0.5 * np.einsum("hd,hde->he", q[t], s))
    np.testing.assert_allclose(got, np.stack(want), rtol=1e-4, atol=1e-4)

    size, stride, block, topk, window, init, dense_len = sizes = (4, 2, 8, 4, 16, 1, 48)
    T, n_kv, g, d = 128, 2, 2, 8
    qs, ks = 2.0 * rng.standard_normal((T, n_kv, g, d)), 2.0 * rng.standard_normal((T, n_kv, d))
    vs = rng.standard_normal((T, n_kv, d))
    ck = np.asarray(family._compress(jnp.asarray(ks, jnp.float32), size, stride))
    for j in (0, 7, ck.shape[0] - 1):
        np.testing.assert_allclose(ck[j], ks[stride * j:stride * j + size].mean(0), rtol=1e-5, atol=1e-6)
    assert ck.shape[0] == (T - size) // stride + 1
    with jax.default_matmul_precision("highest"):
        _, chosen = family._sparse_block(
            *(jnp.asarray(x, jnp.float32) for x in (qs,)), jnp.arange(T),
            *(jnp.asarray(x, jnp.float32) for x in (ks, vs, ck)), sizes=sizes, group=g)
    chosen = np.asarray(chosen)
    for t in (5, 47, 48, 77, 127):
        for h in range(n_kv):
            own = t // block
            if t < dense_len:
                assert chosen[t, h].tolist() == [b <= own for b in range(T // block)]
                continue
            js = [j for j in range(ck.shape[0]) if stride * j + size - 1 <= t]
            p = np.zeros(ck.shape[0])
            for i in range(g):
                sc = np.array([qs[t, h, i] @ ck[j, h] for j in js]) / np.sqrt(d)
                e = np.exp(sc - sc.max())
                p[js] += e / e.sum()
            r = np.full(T // block, -np.inf)
            for b in range(own + 1):
                over = [j for j in js if stride * j < block * (b + 1) and stride * j + size > block * b]
                r[b] = max([p[j] for j in over], default=0.0)
                if b < init or b > own - window // block:
                    r[b] = np.inf
            want_set = sorted(np.argsort(-r, kind="stable")[:topk].tolist())
            assert np.nonzero(chosen[t, h])[0].tolist() == want_set, (t, h)


def test_lane_state_covers_planes_compressed_keys_and_the_whole_matrix_state(cfg, family, sample):
    from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

    config, t = family.program_config(cfg), sample[0]
    engine = InferenceEngine(config, family.assemble_params(config, t), n_lanes=4,
                             cache_dtype=jnp.float32)
    assert engine.cache.k.shape == (3, 4, 128, 64) and engine.cache.ck.shape == (3, 4, 64, 64)
    assert engine.cache.lin.shape == (5, 4, 4 * 32 * 32)
    prompt = list(range(2, 22))
    engine.prefill(0, prompt)
    engine.prefill(1, prompt)
    engine.prefill(2, prompt[:-1] + [99])
    assert family.lane_state_rel_err(engine, 0, 1, 20) == 0.0
    assert family.lane_state_rel_err(engine, 0, 2, 20) > 1e-3
    assert family.lane_state_rel_err(engine, 0, 2, 19) > 1e-3  # the matrix states differ
    poked = engine.cache
    engine.cache = poked._replace(lin=poked.lin.at[4, 1, 7].add(1.0))
    assert family.lane_state_rel_err(engine, 0, 1, 20) > 1e-3  # the last layer's state
    engine.cache = poked._replace(ck=poked.ck.at[0, 1, 8, 3].add(1.0))
    assert family.lane_state_rel_err(engine, 0, 1, 20) > 1e-3  # kernel 8 ends at row 19
    engine.cache = poked._replace(ck=poked.ck.at[0, 1, 9, 3].add(1.0))
    assert family.lane_state_rel_err(engine, 0, 1, 20) == 0.0  # kernel 9 ends past the rows
    toy = SimpleNamespace(cache=SimpleNamespace(k=jnp.zeros((1, 2, 4, 2)), v=jnp.zeros((1, 2, 4, 2))))
    assert family.lane_state_rel_err(toy, 0, 1, 2) is None


def test_the_rooflines_counts_by_hand_at_the_published_shape():
    """32 heads of 128: a row's q, k, v and o are 4 x 4096 numbers of two
    bytes; its recurrence 5 x 128^2 operations a head; a lane's state in one
    layer 32 x 128 x 128 x 4 = 2 MB, in and out."""
    c = SimpleNamespace(linear_n_heads=32, linear_head_dim=128, sparse_block_size=64, head_size=128,
                        n_kv_heads=2, n_sparse_layers=8)
    assert sala_roofline.linear_row_bytes(c) == 4 * 4096 * 2 == 32768
    assert sala_roofline.linear_row_ops(c) == 5 * 32 * 128 * 128 == 2_621_440
    a_step = 16 * 24 * 2 * 2_097_152  # 16 live lanes, 24 layers, in and out
    assert sala_roofline.decode_state_bytes(c, a_step, 16 * 24) == a_step + 384 * 32768
    # 16 lanes of 64 blocks: keys and values, two kv heads, eight layers
    assert sala_roofline.sparse_decode_bytes(c, 16 * 64) == 2 * 16 * 64 * 64 * 128 * 2 * 2 * 8
    ctx = SimpleNamespace(peaks={"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}, config=c)
    rows = 24 * 1024
    need = max(rows * 32768 / 819e9, rows * 2_621_440 / 197e12)
    assert need == rows * 32768 / 819e9  # the bytes bound the chunk form at this shape
    assert sala_roofline.chunk_share(ctx, rows, 2.0) == pytest.approx(100 * need / 2e-3)
    assert sala_roofline.chunk_share(ctx, rows, None) is None
    assert sala_roofline.chunk_share(SimpleNamespace(peaks=None, config=c), rows, 2.0) is None


def test_the_six_readers_on_a_reduction_and_on_a_program_without_the_scopes(monkeypatch):
    from harness import stepclass

    read = {n: cells.load_module(os.path.join(BENCH_DIR, "metrics", n + ".py"), "m_" + n).read
            for n in READERS}
    config = SimpleNamespace(linear_n_heads=32, linear_head_dim=128, sparse_block_size=64,
                             head_size=128, n_kv_heads=2, n_sparse_layers=8)
    peaks = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    # untraced, or a program from before the scopes and counters: nothing, no raise
    bare = SimpleNamespace(trace=None, counters={}, peaks=peaks, kv_dtype="bfloat16",
                           config=SimpleNamespace(head_size=128, n_kv_heads=8))
    assert all(r(bare) is None for r in read.values())
    D, P = stepclass.DECODE, stepclass.PREFILL
    red = {"classes": {
        "dlstep.fused.b1024": {"executions": 2.0, "pair_ms": {
            (D, "dl.linear_attention"): 3.0, (D, "dl.linear_state"): 2.5, (D, "dl.block_scores"): 0.5,
            (D, "dl.attention"): 1.5, (P, "dl.linear_state"): 6.0, (P, "dl.ffn"): 90.0}},
        "dlstep.fused.b512": {"executions": 6.0, "pair_ms": {
            (D, "dl.linear_attention"): 3.2, (D, "dl.linear_state"): 2.4, (D, "dl.block_scores"): 0.6,
            (D, "dl.attention"): 1.2, (P, "dl.linear_state"): 3.0}},
    }}
    monkeypatch.setattr(stepclass, "for_ctx", lambda ctx: red)
    state = 2 * 4 * 32 * 128 * 128
    ctx = SimpleNamespace(
        trace={}, config=config, peaks=peaks, kv_dtype="bfloat16",
        counters={"linear_state_bytes_moved": 15 * 24 * state * 50, "decode_steps": 50,
                  "linear_rows_computed": 24 * (2 * 1024 + 6 * 512), "fused_steps": 8,
                  "attn_blocks_read": 15 * 64 * 50, "attn_blocks_held": 15 * 200 * 50})
    # no pipelined decode step in the stretch: the most frequent fused class's decode half
    assert read["linear_attention_step_ms"](ctx) == pytest.approx(5.6)
    assert read["block_scores_step_ms"](ctx) == pytest.approx(0.6)
    need = 15 * 24 * (state + 32768) / 819e9
    assert read["linear_state_decode_roofline"](ctx) == pytest.approx(100 * need / 2.4e-3)
    rows = 24 * (2 * 1024 + 6 * 512) / 8
    ms = (2 * 6.0 + 6 * 3.0) / 8
    assert read["linear_chunk_prefill_roofline"](ctx) == pytest.approx(
        100 * (rows * 32768 / 819e9) / (ms / 1e3))
    blocks = 15 * 64 * 64 * 128 * 2 * 2 * 2 * 8
    assert read["block_sparse_attention_decode_roofline"](ctx) == pytest.approx(
        100 * (blocks / 819e9) / 1.2e-3)
    assert read["attn_blocks_read_share"](ctx) == pytest.approx(32.0)
    assert all(0 < read[n](ctx) < 100 for n in READERS if n.endswith("roofline"))
    monkeypatch.setattr(stepclass, "for_ctx", lambda ctx: {"classes": {
        "dlstep.fused.b256": {"executions": 3.0, "pair_ms": {(D, "dl.ffn"): 5.0}}}})
    gone = {n: r(ctx) for n, r in read.items()}  # the scopes gone, the counters there
    assert all(v is None for n, v in gone.items() if n != "attn_blocks_read_share")
