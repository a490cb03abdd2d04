"""Parts of models/deepseek.py held to plain forms: absorbed against expanded
attention on the same latents, the traced program's routed layer (no loop over
experts, no dequantized stack, at any width), the routed layer's counts
against a count on the host, and the refusals at start-up."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_multiusers_tpu.models import deepseek
from distributed_llama_multiusers_tpu.ops import linear
from distributed_llama_multiusers_tpu.runtime.engine import InferenceEngine

import latent_toy

CFG, FAMILY, _ = latent_toy.load()


def test_absorbed_attention_is_the_expanded_attention():
    b, t, s, h, nope, rope, vd, rank = 2, 3, 10, 4, 8, 4, 6, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q_nope = jax.random.normal(ks[0], (b, t, h, nope))
    q_pe = jax.random.normal(ks[1], (b, t, h, rope))
    wkvb = jax.random.normal(ks[2], (rank, h, nope + vd)) * 0.3
    c = jax.random.normal(ks[3], (b, s, rank))
    r = jax.random.normal(ks[4], (b, s, rope))
    positions = jnp.array([[4, 5, 6], [7, 8, 9]])
    mask = jnp.arange(s)[None, None, :] <= positions[:, :, None]
    scale = 1.0 / np.sqrt(nope + rope)
    q_abs = deepseek.absorb_queries(q_nope, jnp.transpose(wkvb[..., :nope], (1, 2, 0)))
    got = deepseek.expand_values(
        deepseek.latent_plane_attention(q_abs, q_pe, c, r, mask, scale),
        jnp.transpose(wkvb[..., nope:], (1, 0, 2)))
    # expanded, as published: every head's own keys and values
    kv = jnp.einsum("bsc,chx->bshx", c, wkvb)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(r[:, :, None, :], (b, s, h, rope))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    scores = jnp.einsum("bthx,bshx->bths", q, k) * scale
    probs = jax.nn.softmax(jnp.where(mask[:, :, None, :], scores, -jnp.inf), axis=-1)
    want = jnp.einsum("bths,bshv->bthv", probs, kv[..., nope:])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def _jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _jaxprs(inner)


@pytest.mark.parametrize("t", [1, 5, 64])
def test_the_traced_routed_layer_loops_over_no_expert_and_unpacks_no_stack(t):
    linear.set_pallas_interpret(True)
    try:
        eng, _ = latent_toy.engine(FAMILY, CFG, 3, lanes=2)
        c = eng.config
        tokens = jnp.zeros((2, t), jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (2, t))
        closed = jax.make_jaxpr(
            lambda p, cache: deepseek.deepseek_forward(c, p, tokens, positions, cache)
        )(eng.params, eng.cache)
    finally:
        linear.set_pallas_interpret(False)
    grouped = whole = 0
    # one layer's matrix dequantized for all experts: [E, d_in, d_out]
    stack = {(c.n_experts, c.dim, c.moe_hidden_dim), (c.n_experts, c.moe_hidden_dim, c.dim)}
    for jaxpr in _jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grouped += "q40_grouped" in str(eqn.params.get("name", eqn.params.get("name_and_src_info", "")))
            for v in eqn.outvars:
                aval = v.aval
                if getattr(aval, "shape", None) and jnp.issubdtype(aval.dtype, jnp.floating):
                    whole += tuple(aval.shape[-3:]) in stack
    # one scan body: three grouped products, whatever the expert count
    assert grouped == 3, grouped
    assert whole == 0


def test_slab_counts_are_a_count_on_the_host():
    eng, tensors = latent_toy.engine(FAMILY, CFG, 4)
    c, n, seq = eng.config, eng.n_lanes, eng.config.seq_len
    prompts = [list(range(5 + i, 25 + i)) for i in range(3)]
    for lane, p in enumerate(prompts):
        eng.prefill(lane, p)
    feed = np.zeros(n, np.int32)
    feed[:3] = [7, 8, 9]
    pos = np.full(n, seq, np.int32)
    pos[:3] = 20
    eng.decode_pipelined(pos, tokens=feed)
    eng.pipeline_consume()
    eng.pipeline_flush(count=False)
    # the host's count: the reference's chosen sets at the decoded row
    routes = []
    tokens = np.array([p + [f] for p, f in zip(prompts, feed[:3])])
    with jax.default_matmul_precision("highest"):
        FAMILY.reference_forward(CFG, tensors, tokens, routes=routes)
    want = sum(int(r[:, -1].any(axis=0).sum()) for r in routes)
    s = eng.stats.snapshot()
    assert s["moe_slabs_read"] == want
    assert s["moe_assignments"] == 3 * c.n_active_experts * (c.n_layers - c.n_dense_layers)
    assert s["moe_slabs_whole"] == (c.n_layers - c.n_dense_layers) * c.n_experts
    assert s["pipeline_flushes"] == 0


@pytest.mark.parametrize("kw,what", [
    (dict(paged_kv=True), "paged KV pool"),
    (dict(kv_host_bytes=1 << 20), "host KV tier"),
    (dict(mesh="any"), "a mesh"),
])
def test_what_the_latent_cache_does_not_serve_is_refused_at_start_up(kw, what):
    config = FAMILY.program_config(CFG)
    with pytest.raises(ValueError, match=f"latent-attention model.*does not serve.*{what}"):
        InferenceEngine(config, None, n_lanes=2, **kw)
