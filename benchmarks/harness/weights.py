"""Seeded random weights of the Llama family (`families/llama.py` exports
`device_weights` and `assemble_params` from here), made on the device in one
jitted call. `seed_key`, `q40_plane` and the reasoning behind `GAIN` serve any
family's generator that draws Q40 planes.

Copied from bench.py (`_weight_specs`, `_device_packed_params`,
`_assemble_params`) and changed in three ways: the nibbles are symmetric about
zero (see `_symmetric_nibbles`), each tensor's scales are sized to its place
in the block (see `GAIN`), the norm weights are not all ones, and the Qwen2
q/k/v biases are there. The Q40 planes are drawn directly in the layout the
program serves from (quants/packed.py: uint8 nibble pairs ``[d_in/2, d_out]``
and float16 block scales ``[d_in/32, d_out]``), so no dense tensor and no
``.m`` file exists at any point, and nothing but the RoPE tables crosses from
the host. The plain reference (reference.py) reads the same planes: they are
the benchmark's, made from ``--seed``, not the program's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def weight_specs(config) -> dict:
    """(d_in, d_out, lead) for every Q40 matmul weight of a dense model."""
    L, d, h = config.n_layers, config.dim, config.hidden_dim
    kv = config.n_kv_heads * config.head_size
    if config.n_experts > 0:
        raise SystemExit("the llama family's generator makes dense models only: "
                         "a configuration with experts names a family of its own")
    from distributed_llama_multiusers_tpu.quants.packed import padded_d_out

    return {
        "wq": (d, d, (L,)),
        "wk": (d, kv, (L,)),
        "wv": (d, kv, (L,)),
        "wo": (d, d, (L,)),
        "w1": (d, h, (L,)),
        "w2": (h, d, (L,)),
        "w3": (d, h, (L,)),
        # the loader pads the vocabulary where the kernel cannot tile it well
        "wcls": (d, padded_d_out(config.vocab_size), ()),
    }


def _symmetric_nibbles(bits):
    """Random bytes whose two nibbles dequantize to -7..7 with mean zero.

    A nibble n stands for n - 8, so uniform nibbles 0..15 have mean -1/2, and
    every weight matrix then has a rank-one part (all outputs moved by the
    sum of the inputs) whose gain, d_in/2 scales, dwarfs the random part's:
    after a few layers the hidden state is the all-ones direction and every
    position's logits are one vector up to sign (seen on the chip, PR 25,
    with bench.py's planes). Nibble 0 (-8) is redrawn as 8 (0)."""
    lo, hi = bits & 0x0F, bits >> 4
    lo = jnp.where(lo == 0, 8, lo)
    hi = jnp.where(hi == 0, 8, hi)
    return (lo | (hi << 4)).astype(jnp.uint8)


# Output rms of each matmul for an input of rms 1 ("gain"), chosen so that the
# random network behaves like a trained one where it matters to a comparison.
# The residual stream (embedding rms 1) is the sum of many small updates: the
# attention branch adds about 0.3 a layer and the FFN branch about 0.15, where
# one scale range for every tensor (bench.py's 0.001-0.011, a gain of 1.8 at
# d_in 4096 and 3.3 at 14336) lets each block overwrite the stream, makes the
# network a chaotic map and amplifies rounding layer by layer: bfloat16 then
# read 25-32 % from float32 at the logits and an f8 cache only 1.9 times that
# (chip, PR 25). Attention is sharp, as trained heads are: query and key gains
# of 2 spread the scores by about 4, so the few keys a query attends to have
# to be right. With a spread of 1 an f8 cache read 1.25 times the bfloat16
# engine (chip, PR 25) and no limit could have told them apart: the engine's
# own floor, about 2 %, is the rounding of its bfloat16 residual stream.
GAIN = {"wq": 2.0, "wk": 2.0, "wv": 1.0, "wo": 0.3,
        "w1": 1.0, "w3": 1.0, "w2": 0.2, "wcls": 1.78}
# rms of (nibble - 8) * scale for scale = a * uniform(1, 11), over a
_RMS_PER_A = (17.5 * (36.0 + 100.0 / 12.0)) ** 0.5


def q40_plane(kp, ks, lead, d_in: int, d_out: int, gain: float, live_out: int | None = None):
    """One seeded Q40 tensor in the layout the program serves from, stacked
    along ``lead``, whose product with an input of rms 1 has rms ``gain``.
    Columns from ``live_out`` on (the loader's padding of a vocabulary) get
    zero scales and dequantize to exact zeros."""
    from distributed_llama_multiusers_tpu.quants.packed import PackedQ40

    pk = _symmetric_nibbles(jax.random.bits(kp, (*lead, d_in // 2, d_out), jnp.uint8))
    sc = jax.random.uniform(ks, (*lead, d_in // 32, d_out), jnp.float32)
    sc = (sc * 10.0 + 1.0) * (gain / (_RMS_PER_A * d_in ** 0.5))
    if live_out is not None and d_out > live_out:
        sc = jnp.where(jnp.arange(d_out) < live_out, sc, 0.0)
    return PackedQ40(packed=pk, scales=sc.astype(jnp.float16))


def _generate(config, key, dtype):
    L, d = config.n_layers, config.dim
    kv = config.n_kv_heads * config.head_size
    out = {}
    for name, (d_in, d_out, lead) in weight_specs(config).items():
        key, kp, ks = jax.random.split(key, 3)
        out[name] = q40_plane(kp, ks, lead, d_in, d_out, GAIN[name],
                              live_out=config.vocab_size if name == "wcls" else None)
    key, ke, k1, k2, k3, kb = jax.random.split(key, 6)
    out["embedding"] = (
        jax.random.normal(ke, (config.vocab_size, d), jnp.float32)
    ).astype(dtype)
    out["rms_att"] = 1.0 + 0.1 * jax.random.normal(k1, (L, d), jnp.float32)
    out["rms_ffn"] = 1.0 + 0.1 * jax.random.normal(k2, (L, d), jnp.float32)
    out["rms_final"] = 1.0 + 0.1 * jax.random.normal(k3, (d,), jnp.float32)
    if config.qkv_bias:
        kq, kk, kv_ = jax.random.split(kb, 3)
        out["bq"] = 0.1 * jax.random.normal(kq, (L, d), jnp.float32)
        out["bk"] = 0.1 * jax.random.normal(kk, (L, kv), jnp.float32)
        out["bv"] = 0.1 * jax.random.normal(kv_, (L, kv), jnp.float32)
    return out


def seed_key(seed: int):
    """The key of ``--seed``, which may exceed 31 bits: folded in two halves."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def device_weights(config, seed: int, dtype=jnp.bfloat16) -> dict:
    """name -> device array (or PackedQ40 of two), all from one program."""
    t = jax.jit(lambda k: _generate(config, k, dtype))(seed_key(seed))
    jax.block_until_ready(t)
    return t


def assemble_params(config, t: dict):
    """The program's LlamaParams around the benchmark's arrays. The RoPE
    tables are the program's own (models/loader._rope_cache): they are part
    of the system under test, and the reference builds its own."""
    from distributed_llama_multiusers_tpu.models.llama import (
        LlamaLayerParams,
        LlamaParams,
    )
    from distributed_llama_multiusers_tpu.models.loader import _rope_cache

    cos, sin = _rope_cache(config)
    bias = {k: t[k] for k in ("bq", "bk", "bv") if k in t}
    layers = LlamaLayerParams(
        wq=t["wq"], wk=t["wk"], wv=t["wv"], wo=t["wo"],
        w1=t["w1"], w2=t["w2"], w3=t["w3"],
        rms_att=t["rms_att"], rms_ffn=t["rms_ffn"], **bias,
    )
    return LlamaParams(
        embedding=t["embedding"],
        layers=layers,
        rms_final=t["rms_final"],
        wcls=t["wcls"],
        rope_cos=jax.device_put(cos),
        rope_sin=jax.device_put(sin),
    )
