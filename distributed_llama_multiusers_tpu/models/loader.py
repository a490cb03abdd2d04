"""Weight loading: .m file -> LlamaParams pytree.

Replaces the reference's split-and-ship weight path (src/llm.cpp:447-483,
src/nn/nn-network.cpp:824-901): instead of slicing shards on the root and
streaming them to workers over TCP, tensors are dequantized host-side and
handed to jax.device_put with sharding annotations — PJRT does the
placement/transfer that NnRootWeightLoader did by hand.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
import jax
import jax.numpy as jnp

_BF16_NP = np.dtype(ml_dtypes.bfloat16)

from ..formats.model_file import ModelHeader, RopeType, iter_model_tensors
from ..ops.rope import build_rope_cache
from ..quants.codec import FloatType, dequantize_q40, dequantize_q80
from ..quants.packed import (
    PackedQ40,
    pack_q40_from_blocks,
    pack_q40_host,
    pad_packed_d_out,
)
from .config import LlamaConfig
from .llama import LlamaLayerParams, LlamaParams


def _decode_tensor(raw: np.ndarray, float_type: int, shape: tuple[int, int]) -> np.ndarray:
    from .. import native

    if float_type == FloatType.F32:
        x = raw.view("<f4").astype(np.float32)
    elif float_type == FloatType.F16:
        x = raw.view("<f2").astype(np.float32)
    elif float_type == FloatType.Q40:
        # threaded C++ dequant when built (native/quant_codec.cpp), numpy else
        x = native.dequantize_q40(raw)
        if x is None:
            x = dequantize_q40(raw)
    elif float_type == FloatType.Q80:
        x = native.dequantize_q80(raw)
        if x is None:
            x = dequantize_q80(raw)
    else:
        raise ValueError(f"unsupported float type {float_type}")
    return np.ascontiguousarray(x.reshape(shape))


_TENSOR_NAME_MAP = {
    "block_matmul_q": "wq",
    "block_matmul_k": "wk",
    "block_matmul_v": "wv",
    "block_matmul_wo": "wo",
    "block_matmul_w1": "w1",
    "block_matmul_w2": "w2",
    "block_matmul_w3": "w3",
    "block_rms_norm_0": "rms_att",
    "block_rms_norm_1": "rms_ffn",
    # Qwen2-family projection biases (header.qkv_bias; absent otherwise)
    "block_bias_q": "bq",
    "block_bias_k": "bk",
    "block_bias_v": "bv",
}

_BIAS_KEYS = ("bq", "bk", "bv")
# per-layer [n]-vector tensors (everything else under _TENSOR_NAME_MAP is a
# [d_out, d_in] matmul weight)
_VECTOR_KEYS = {"rms_att", "rms_ffn", *_BIAS_KEYS}


def read_m_tensors(path: str, header: ModelHeader) -> dict:
    """Read a .m file as dequantized f32 arrays in file orientation
    ([d_out, d_in] matmuls): embedding, rms_final, wcls plus per-layer lists
    wq,wk,wv,wo,w1,w2,w3,rms_att,rms_ffn (order: src/llm.cpp:447-483).

    MoE files additionally yield per-layer "moe_gate" [n_experts, dim] and
    w1/w2/w3 as per-layer [n_experts, d_out, d_in] stacks."""
    config = LlamaConfig.from_header(header)
    L, E = config.n_layers, config.n_experts
    w: dict = {k: [None] * L for k in _TENSOR_NAME_MAP.values()}
    if E > 0:
        w["moe_gate"] = [None] * L
        for key in ("w1", "w2", "w3"):
            w[key] = [[None] * E for _ in range(L)]
    for spec, raw in iter_model_tensors(path, header):
        x = _decode_tensor(raw, spec.float_type, spec.shape)
        if spec.name == "embedding":
            w["embedding"] = x
        elif spec.name == "final_rms_norm":
            w["rms_final"] = x.reshape(-1)
        elif spec.name == "final_matmul_logits":
            w["wcls"] = x
        elif spec.name == "block_moe_gate":
            w["moe_gate"][spec.layer] = x
        else:
            key = _TENSOR_NAME_MAP[spec.name]
            if spec.expert >= 0:
                w[key][spec.layer][spec.expert] = x
            else:
                w[key][spec.layer] = x.reshape(-1) if key in _VECTOR_KEYS else x
    if not header.qkv_bias:
        for key in _BIAS_KEYS:
            del w[key]
    if E > 0:
        for key in ("w1", "w2", "w3"):
            w[key] = [np.stack(mats) for mats in w[key]]  # [E, d_out, d_in] per layer
    return w


def _rope_cache(config: LlamaConfig, theta: float | None = None):
    """The rotation tables of ``config`` at its base, or at ``theta`` (a layer
    kind's own)."""
    return build_rope_cache(
        config.seq_len,
        config.rope_dim,
        theta or config.rope_theta,
        config.rope_scaling_factor,
        config.rope_scaling_low_freq_factor,
        config.rope_scaling_high_freq_factor,
        config.rope_scaling_orig_max_seq_len,
        yarn=config.rope_type == RopeType.YARN,
    )


def _cast_fn(dtype):
    """Host-side pre-cast where a numpy dtype exists; bf16 has no plain numpy
    dtype, so it casts at device_put time instead."""
    np_dtype = np.dtype(jnp.dtype(dtype).name) if jnp.dtype(dtype) != jnp.bfloat16 else None

    def cast(x: np.ndarray) -> np.ndarray:
        return x if np_dtype is None else x.astype(np_dtype)

    return cast


# the latent-attention walk (formats/model_file._latent_block_specs): tensor
# name -> the key models/deepseek.latent_params takes it by. w1/w2/w3 are the
# leading dense layers' (``dense_`` + key) where the tensor is no expert's
_LATENT_NAME_MAP = {
    "block_matmul_q": "wq",
    "block_matmul_kv_a": "wkva",
    "block_matmul_kv_b": "wkvb",
    "block_matmul_wo": "wo",
    "block_matmul_w1": "w1",
    "block_matmul_w2": "w2",
    "block_matmul_w3": "w3",
    "block_matmul_shared_w1": "shared_w1",
    "block_matmul_shared_w2": "shared_w2",
    "block_matmul_shared_w3": "shared_w3",
    "block_moe_gate": "moe_gate",
    "block_moe_bias": "moe_bias",
    "block_rms_norm_kv": "rms_kv",
    "block_rms_norm_0": "rms_att",
    "block_rms_norm_1": "rms_ffn",
    # a query latent and the indexer (header.q_lora_rank, header.index_topk)
    "block_matmul_q_a": "wqa",
    "block_rms_norm_q": "rms_q",
    "block_matmul_idx_q": "idx_wq",
    "block_matmul_idx_k": "idx_wk",
    "block_idx_k_norm_gain": "idx_k_gain",
    "block_idx_k_norm_bias": "idx_k_bias",
    "block_idx_weights": "idx_ww",
}
_LATENT_VECTORS = {"moe_bias", "rms_kv", "rms_att", "rms_ffn", "rms_q",
                   "idx_k_gain", "idx_k_bias"}
# keyed by layer, whatever the layer's FFN is
_LATENT_BY_LAYER = {"wq", "wkva", "wkvb", "wo", "rms_att", "rms_kv", "wqa", "rms_q",
                    "idx_wq", "idx_wk", "idx_k_gain", "idx_k_bias", "idx_ww"}


# float32 tensors the walk stores [d_out, d_in] (or [channels, taps]) and the
# blocks read the other way round
_TRANSPOSED_F32 = {"block_moe_gate", "block_conv_taps", "block_idx_weights",
                   "block_ssm_conv_taps", "block_ssm_dt_proj", "block_ssm_a_log",
                   "block_delta_conv_taps", "block_delta_f2", "block_delta_g2",
                   "block_delta_b"}


def _load_stacked(path: str, header: ModelHeader, dtype, put, quantized: bool,
                  place, f32_names) -> dict:
    """A ``.m`` whose block tensors ``place(spec)`` files under ``(key,
    index)`` (a layer, or a layer and an expert, counted as the block counts
    them), stacked by index: key -> array, matmul weights ``[d_in, d_out]``.
    ``quantized`` keeps Q40 matmul tensors packed (``PackedQ40``); the keys in
    ``f32_names`` stay float32. With ``embedding``, ``rms_final``, ``wcls``."""
    cast = _cast_fn(dtype)
    groups: dict = {}  # key -> {(layer[, expert]): array or (packed, scales)}
    top: dict = {}
    for spec, raw in iter_model_tensors(path, header):
        matmul = "matmul" in spec.name
        if matmul and quantized and spec.float_type == FloatType.Q40:
            x = pack_q40_from_blocks(raw, spec.shape)
            if spec.name == "final_matmul_logits":
                x = pad_packed_d_out(*x)
        else:
            x = _decode_tensor(raw, spec.float_type, spec.shape)
            x = x.T if matmul or spec.name in _TRANSPOSED_F32 else x
        if not spec.name.startswith("block_"):
            top[spec.name] = x
            continue
        key, index = place(spec)
        if not matmul and spec.shape[0] == 1:  # a vector, stored [1, n]
            x = x.reshape(-1)
        groups.setdefault(key, {})[index] = x

    def stack(name, entries: dict):
        shape = tuple(max(i[d] for i in entries) + 1 for d in range(len(next(iter(entries)))))
        picks = [entries[i] for i in np.ndindex(*shape)]
        if isinstance(picks[0], tuple):  # Q40, kept packed
            planes = [np.stack([p[j] for p in picks]) for j in (0, 1)]
            return PackedQ40(
                packed=put(name, planes[0].reshape(*shape, *planes[0].shape[1:])),
                scales=put(name + ".scales", planes[1].reshape(*shape, *planes[1].shape[1:])),
            )
        x = np.stack(picks)
        x = x.reshape(*shape, *x.shape[1:])
        if name in f32_names:
            return put(name, x).astype(jnp.float32)
        return put(name, cast(x)).astype(dtype)

    t = {name: stack(name, entries) for name, entries in groups.items()}
    t["embedding"] = put("embedding", cast(top["embedding"])).astype(dtype)
    t["rms_final"] = put("rms_final", top["final_rms_norm"].reshape(-1)).astype(jnp.float32)
    wcls = top["final_matmul_logits"]
    t["wcls"] = (
        PackedQ40(packed=put("wcls", wcls[0]), scales=put("wcls.scales", wcls[1]))
        if isinstance(wcls, tuple) else put("wcls", cast(wcls)).astype(dtype)
    )
    return t


def load_latent_params_from_m(path: str, header: ModelHeader, dtype=jnp.bfloat16,
                              device_put_fn=None, quantized: bool = False):
    """A latent-attention ``.m`` (``header.kv_lora_rank``) as the tree
    models/deepseek.py runs: tensors stacked by layer (the experts by routed
    layer and expert), matmul weights ``[d_in, d_out]``. ``quantized`` keeps
    Q40 matmul tensors packed (``PackedQ40``; the experts ``Q40Experts``);
    the router, its bias and the norms are float32 either way."""
    from .deepseek import latent_params

    config = LlamaConfig.from_header(header)
    put = device_put_fn or (lambda name, x: jnp.asarray(x))
    n_dense = config.n_dense_layers if config.n_experts else config.n_layers

    def place(spec):
        key = _LATENT_NAME_MAP[spec.name]
        index = (spec.layer,)
        if key in ("w1", "w2", "w3", "rms_ffn") and spec.expert < 0 and spec.layer < n_dense:
            key = "dense_" + key
        elif key not in _LATENT_BY_LAYER:
            index = (spec.layer - n_dense,) + ((spec.expert,) if spec.expert >= 0 else ())
        return key, index

    t = _load_stacked(path, header, dtype, put, quantized, place,
                      _LATENT_VECTORS | {"moe_gate", "dense_rms_ffn", "idx_ww"})
    cos, sin = _rope_cache(config)
    return config, latent_params(t, put("rope_cos", cos), put("rope_sin", sin), dtype, config)


# the layer-pattern walk (formats/model_file._pattern_block_specs): tensor
# name -> the key models/hybrid.hybrid_params takes it by
_PATTERN_NAME_MAP = {
    "block_matmul_conv_in": "conv_in",
    "block_conv_taps": "conv_taps",
    "block_matmul_conv_out": "conv_out",
    "block_matmul_q": "wq",
    "block_matmul_k": "wk",
    "block_matmul_v": "wv",
    "block_q_norm": "q_norm",
    "block_k_norm": "k_norm",
    "block_matmul_wo": "wo",
    "block_matmul_w1": "w1",
    "block_matmul_w2": "w2",
    "block_matmul_w3": "w3",
    "block_moe_gate": "moe_gate",
    "block_moe_bias": "moe_bias",
    "block_matmul_shared_w1": "shared_w1",
    "block_matmul_shared_w2": "shared_w2",
    "block_matmul_shared_w3": "shared_w3",
    "block_rms_norm_1": "rms_ffn",
    # a state-space layer (LayerKind.SSM)
    "block_matmul_ssm_in": "ssm_in",
    "block_ssm_conv_taps": "ssm_taps",
    "block_ssm_conv_bias": "ssm_conv_bias",
    "block_matmul_ssm_x": "ssm_x",
    "block_ssm_dt_norm": "ssm_dt_norm",
    "block_ssm_b_norm": "ssm_b_norm",
    "block_ssm_c_norm": "ssm_c_norm",
    "block_ssm_dt_proj": "ssm_dt_proj",
    "block_ssm_dt_bias": "ssm_dt_bias",
    "block_ssm_a_log": "ssm_a_log",
    "block_ssm_d": "ssm_d",
    "block_matmul_ssm_out": "ssm_out",
    # a block-sparse layer's output gate (LayerKind.SPARSE)
    "block_matmul_attn_gate": "attn_gate",
    # a linear-attention layer (LayerKind.LINEAR)
    "block_matmul_lin_q": "lin_q",
    "block_matmul_lin_k": "lin_k",
    "block_matmul_lin_v": "lin_v",
    "block_lin_q_norm": "lin_q_norm",
    "block_lin_k_norm": "lin_k_norm",
    "block_matmul_lin_gate": "lin_gate",
    "block_lin_o_norm": "lin_o_norm",
    "block_matmul_lin_out": "lin_out",
    # a window layer's learned sink a query head (header.window_sink)
    "block_attn_sink": "attn_sink",
    # a delta-rule layer (LayerKind.DELTA)
    "block_matmul_delta_q": "delta_q",
    "block_matmul_delta_k": "delta_k",
    "block_matmul_delta_v": "delta_v",
    "block_delta_conv_taps": "delta_taps",
    "block_matmul_delta_f1": "delta_f1",
    "block_delta_f2": "delta_f2",
    "block_delta_dt_bias": "delta_dt_bias",
    "block_delta_a_log": "delta_a_log",
    "block_delta_b": "delta_b",
    "block_matmul_delta_g1": "delta_g1",
    "block_delta_g2": "delta_g2",
    "block_delta_o_norm": "delta_o_norm",
    "block_matmul_delta_out": "delta_out",
}
_PATTERN_F32 = {"attn_sink", "conv_taps", "q_norm", "k_norm", "moe_gate", "moe_bias",
                "rms_ffn", "dense_rms_ffn", "attn_rms", "conv_rms", "ssm_rms", "ssm_taps",
                "ssm_conv_bias", "ssm_dt_norm", "ssm_b_norm", "ssm_c_norm",
                "ssm_dt_proj", "ssm_dt_bias", "ssm_a_log", "ssm_d",
                "lin_rms", "lin_q_norm", "lin_k_norm", "lin_o_norm",
                "delta_rms", "delta_taps", "delta_f2", "delta_dt_bias", "delta_a_log",
                "delta_b", "delta_g2", "delta_o_norm"}


def load_pattern_params_from_m(path: str, header: ModelHeader, dtype=jnp.bfloat16,
                               device_put_fn=None, quantized: bool = False):
    """A ``.m`` with a layer-kind list (``header.layer_kinds``) as the tree
    models/hybrid.py runs: each kind's tensors stacked by the count of that
    kind, the dense FFNs by layer, the routed ones by routed layer (and
    expert). The taps, the per-head norm gains, the router, its bias, the
    norms and what steers a state-space or a delta-rule layer's exponential
    are float32 whatever ``dtype`` is."""
    from ..formats.model_file import LayerKind
    from .hybrid import hybrid_params

    mixer_rms = {LayerKind.CONV: "conv_rms", LayerKind.SSM: "ssm_rms",
                 LayerKind.ATTENTION: "attn_rms", LayerKind.LINEAR: "lin_rms",
                 LayerKind.DELTA: "delta_rms"}
    config = LlamaConfig.from_header(header)
    put = device_put_fn or (lambda name, x: jnp.asarray(x))
    n_dense = config.n_dense_layers if config.n_experts else config.n_layers
    # a window layer's and a block-sparse layer's weights are stacked with the
    # full-context layers'
    kinds = tuple(LayerKind.ATTENTION if k in (LayerKind.WINDOW, LayerKind.SPARSE) else k
                  for k in config.layer_kinds)
    # a layer's index into its kind's stack
    nth = [sum(k == kinds[l] for k in kinds[:l]) for l in range(len(kinds))]
    # and into the stacks a window layer has to itself: its sink, and its K/V
    # projections where the kinds' kv heads differ (the full-context kind's
    # are then counted apart too)
    own = [sum(k == config.layer_kinds[l] for k in config.layer_kinds[:l])
           for l in range(len(kinds))]

    def place(spec):
        l = spec.layer
        if spec.name == "block_rms_norm_0":  # the mixer's norm, filed by kind
            return mixer_rms[kinds[l]], (nth[l],)
        key = _PATTERN_NAME_MAP[spec.name]
        if key == "attn_sink":
            return key, (own[l],)
        if key in ("wk", "wv") and config.split_kv_kinds:
            windowed = config.layer_kinds[l] == LayerKind.WINDOW
            return key + ("_w" if windowed else ""), (own[l],)
        if key in ("w1", "w2", "w3", "rms_ffn", "moe_gate", "moe_bias",
                   "shared_w1", "shared_w2", "shared_w3"):
            if spec.expert < 0 and l < n_dense:
                return "dense_" + key, (l,)
            return key, (l - n_dense,) + ((spec.expert,) if spec.expert >= 0 else ())
        return key, (nth[l],)

    t = _load_stacked(path, header, dtype, put, quantized, place, _PATTERN_F32)
    if config.rope_type == RopeType.NONE:  # nothing is rotated: no tables
        return config, hybrid_params(t, None, None)
    cos, sin = _rope_cache(config)
    window_rope = ()
    if config.window_rope_theta:  # the window kind rotates at a base of its own
        cos_w, sin_w = _rope_cache(config, config.window_rope_theta)
        window_rope = (put("rope_cos_w", cos_w), put("rope_sin_w", sin_w))
    return config, hybrid_params(t, put("rope_cos", cos), put("rope_sin", sin), *window_rope)


def load_params_from_m(
    path: str,
    header: ModelHeader,
    dtype=jnp.bfloat16,
    device_put_fn=None,
) -> tuple[LlamaConfig, LlamaParams]:
    """Load and dequantize all tensors; matmul weights are transposed to
    [d_in, d_out] (the .m stores [d_out, d_in], src/llm.cpp:447-483) and
    per-layer tensors stacked along a leading [n_layers] axis.

    ``device_put_fn(name, np_array) -> jax.Array`` lets callers control
    placement/sharding; defaults to plain jnp.asarray.
    """
    if header.kv_lora_rank:
        return load_latent_params_from_m(path, header, dtype, device_put_fn)
    if header.layer_kinds:
        return load_pattern_params_from_m(path, header, dtype, device_put_fn)
    config = LlamaConfig.from_header(header)
    put = device_put_fn or (lambda name, x: jnp.asarray(x))

    raw_w = read_m_tensors(path, header)
    embedding = raw_w["embedding"]
    rms_final = raw_w["rms_final"]
    wcls = raw_w["wcls"].T  # -> [dim, vocab]
    stacked = {}
    for key in _TENSOR_NAME_MAP.values():
        if key not in raw_w:
            continue  # bias keys absent on bias-free models
        mats = raw_w[key]
        if key in _VECTOR_KEYS:
            stacked[key] = np.stack(mats)
        else:
            # -> [L, d_in, d_out] (MoE ffn: [L, E, d_in, d_out])
            stacked[key] = np.stack([np.swapaxes(m, -1, -2) for m in mats])

    moe_gate = None
    if config.n_experts > 0:
        # [L, n_experts, dim] -> [L, dim, n_experts] for y @ gate
        moe_gate = np.swapaxes(np.stack(raw_w["moe_gate"]), -1, -2)

    cast = _cast_fn(dtype)
    cos, sin = _rope_cache(config)

    layers = LlamaLayerParams(
        moe_gate=(
            put("moe_gate", moe_gate).astype(jnp.float32) if moe_gate is not None else None
        ),
        wq=put("wq", cast(stacked["wq"])).astype(dtype),
        wk=put("wk", cast(stacked["wk"])).astype(dtype),
        wv=put("wv", cast(stacked["wv"])).astype(dtype),
        wo=put("wo", cast(stacked["wo"])).astype(dtype),
        w1=put("w1", cast(stacked["w1"])).astype(dtype),
        w2=put("w2", cast(stacked["w2"])).astype(dtype),
        w3=put("w3", cast(stacked["w3"])).astype(dtype),
        rms_att=put("rms_att", stacked["rms_att"]).astype(jnp.float32),
        rms_ffn=put("rms_ffn", stacked["rms_ffn"]).astype(jnp.float32),
        **{
            k: put(k, stacked[k]).astype(jnp.float32)
            for k in _BIAS_KEYS
            if k in stacked
        },
    )
    params = LlamaParams(
        embedding=put("embedding", cast(embedding)).astype(dtype),
        layers=layers,
        rms_final=put("rms_final", rms_final).astype(jnp.float32),
        wcls=put("wcls", cast(wcls)).astype(dtype),
        rope_cos=put("rope_cos", cos),
        rope_sin=put("rope_sin", sin),
    )
    return config, params


_MATMUL_KEYS = ("wq", "wk", "wv", "wo", "w1", "w2", "w3")


def load_params_from_m_quantized(
    path: str,
    header: ModelHeader,
    dtype=jnp.bfloat16,
    device_put_fn=None,
) -> tuple[LlamaConfig, LlamaParams]:
    """Load a Q40 .m keeping matmul weights quantized on device (PackedQ40:
    int4 nibbles + f16 block scales, quants/packed.py) — the TPU equivalent of
    the reference running Q40 weights at rest (src/nn/nn-cpu-ops.cpp:222-440).
    Non-Q40 matmul tensors (f32/f16 models) are loaded dense; embedding and
    norms are always dense (gather/elementwise ops want plain arrays)."""
    if header.kv_lora_rank:
        return load_latent_params_from_m(path, header, dtype, device_put_fn, quantized=True)
    if header.layer_kinds:
        return load_pattern_params_from_m(path, header, dtype, device_put_fn, quantized=True)
    config = LlamaConfig.from_header(header)
    put = device_put_fn or (lambda name, x: jnp.asarray(x))
    L, E = config.n_layers, config.n_experts

    def empty(key):
        if E > 0 and key in ("w1", "w2", "w3"):
            return [[None] * E for _ in range(L)]
        return [None] * L

    dense: dict = {}
    packed_w: dict = {k: empty(k) for k in _MATMUL_KEYS}
    for spec, raw in iter_model_tensors(path, header):
        is_matmul = spec.name.startswith("block_matmul_") or spec.name == "final_matmul_logits"
        if is_matmul and spec.float_type == FloatType.Q40:
            pk, sc = pack_q40_from_blocks(raw, spec.shape)
            if spec.name == "final_matmul_logits":
                # pad vocab width for the slab kernel's wide tiles; the
                # model slices logits back to vocab_size (llama_forward)
                dense["wcls"] = ("q40", *pad_packed_d_out(pk, sc))
            else:
                key = _TENSOR_NAME_MAP[spec.name]
                if spec.expert >= 0:
                    packed_w[key][spec.layer][spec.expert] = (pk, sc)
                else:
                    packed_w[key][spec.layer] = (pk, sc)
        else:
            x = _decode_tensor(raw, spec.float_type, spec.shape)
            if spec.name == "embedding":
                dense["embedding"] = x
            elif spec.name == "final_rms_norm":
                dense["rms_final"] = x.reshape(-1)
            elif spec.name == "final_matmul_logits":
                dense["wcls"] = ("dense", x.T)
            elif spec.name == "block_moe_gate":
                dense.setdefault("moe_gate", [None] * L)
                dense["moe_gate"][spec.layer] = x
            else:
                key = _TENSOR_NAME_MAP[spec.name]
                dense.setdefault(key, [None] * L if spec.expert < 0 else empty(key))
                if spec.expert >= 0:
                    dense[key][spec.layer][spec.expert] = x
                else:
                    dense[key][spec.layer] = x.reshape(-1) if key in _VECTOR_KEYS else x

    cast = _cast_fn(dtype)

    def _flatten(entries):
        """Per-layer entries, or per-layer-per-expert lists, flattened."""
        for m in entries:
            if isinstance(m, list):
                yield from m
            else:
                yield m

    def _stack_tree(entries, pick):
        """np.stack over layers (and experts for MoE nested lists)."""
        if isinstance(entries[0], list):
            return np.stack([np.stack([pick(m) for m in layer]) for layer in entries])
        return np.stack([pick(m) for m in entries])

    def stack_packed(key: str):
        mats = packed_w[key]
        flat = list(_flatten(mats))
        if all(m is not None for m in flat):
            return PackedQ40(
                packed=put(key, _stack_tree(mats, lambda m: m[0])),
                scales=put(key + ".scales", _stack_tree(mats, lambda m: m[1])),
            )
        if any(m is not None for m in flat):
            # float_type is per-tensor in the .m header, so this is encodable
            # but no converter emits it; fail clearly rather than stack holes
            raise ValueError(
                f"{key}: tensors mix Q40 and non-Q40 float types; "
                "mixed quantization is not supported"
            )
        # dense fallback (non-Q40 model): same path as load_params_from_m
        return put(
            key, cast(_stack_tree(dense[key], lambda m: np.swapaxes(m, -1, -2)))
        ).astype(dtype)

    cos, sin = _rope_cache(config)
    moe_gate = None
    if E > 0:
        moe_gate = put(
            "moe_gate", np.swapaxes(np.stack(dense["moe_gate"]), -1, -2)
        ).astype(jnp.float32)
    layers = LlamaLayerParams(
        **{k: stack_packed(k) for k in _MATMUL_KEYS},
        rms_att=put("rms_att", np.stack(dense["rms_att"])).astype(jnp.float32),
        rms_ffn=put("rms_ffn", np.stack(dense["rms_ffn"])).astype(jnp.float32),
        moe_gate=moe_gate,
        **{
            k: put(k, np.stack(dense[k])).astype(jnp.float32)
            for k in _BIAS_KEYS
            if k in dense
        },
    )
    wcls_entry = dense["wcls"]
    if wcls_entry[0] == "q40":
        wcls = PackedQ40(packed=put("wcls", wcls_entry[1]), scales=put("wcls.scales", wcls_entry[2]))
    else:
        wcls = put("wcls", cast(wcls_entry[1])).astype(dtype)
    params = LlamaParams(
        embedding=put("embedding", cast(dense["embedding"])).astype(dtype),
        layers=layers,
        rms_final=put("rms_final", dense["rms_final"]).astype(jnp.float32),
        wcls=wcls,
        rope_cos=put("rope_cos", cos),
        rope_sin=put("rope_sin", sin),
    )
    return config, params


def quantize_params(params: LlamaParams, to_device: bool = True) -> LlamaParams:
    """Quantize a dense params pytree to PackedQ40 layer matmuls + wcls
    (through the bit-exact Q40 encoder). Fully host-side for numpy inputs —
    combine with ``params_from_random(..., to_device=False)`` so multi-GB
    dense weights never cross the host<->device link; with
    ``to_device=False`` the packed planes also stay numpy for
    the caller to place (e.g. with mesh shardings)."""
    up = jnp.asarray if to_device else (lambda x: x)

    def q(w, pad: bool = False) -> PackedQ40:
        # w: [L?, d_in, d_out] device/numpy array -> file orientation then pack
        wf = np.swapaxes(np.asarray(w, np.float32), -1, -2)
        pk, sc = pack_q40_host(wf)
        if pad:  # wcls: widen vocab for the slab kernel (logits re-sliced)
            pk, sc = pad_packed_d_out(pk, sc)
        return PackedQ40(packed=up(pk), scales=up(sc))

    layers = params.layers._replace(**{k: q(getattr(params.layers, k)) for k in _MATMUL_KEYS})
    return params._replace(layers=layers, wcls=q(params.wcls, pad=True))


def params_from_random(
    config: LlamaConfig,
    seed: int = 0,
    dtype=jnp.bfloat16,
    scale: float = 0.02,
    to_device: bool = True,
) -> LlamaParams:
    """Random-weight params with the right shapes — used by benchmarks so that
    multi-GB models need not exist on disk. ``to_device=False`` keeps every
    leaf a host numpy array (bf16 via ml_dtypes) so nothing crosses the
    host->device link until the caller places it."""
    rng = np.random.default_rng(seed)
    L, dim, hidden, kv_dim, vocab = (
        config.n_layers,
        config.dim,
        config.hidden_dim,
        config.kv_dim,
        config.vocab_size,
    )

    np_dtype = (
        _BF16_NP if jnp.dtype(dtype) == jnp.bfloat16 else np.dtype(jnp.dtype(dtype).name)
    )

    def arr(x, d=None):
        return jnp.asarray(x, dtype=d) if to_device else np.asarray(x, dtype=d)

    def r(*shape):
        w = rng.standard_normal(shape, dtype=np.float32) * scale
        return jnp.asarray(w, dtype=dtype) if to_device else w.astype(np_dtype)

    cos, sin = _rope_cache(config)
    E = config.n_experts
    ffn_lead = (L, E) if E > 0 else (L,)
    layers = LlamaLayerParams(
        wq=r(L, dim, config.q_dim),
        wk=r(L, dim, kv_dim),
        wv=r(L, dim, kv_dim),
        wo=r(L, config.q_dim, dim),
        w1=r(*ffn_lead, dim, hidden),
        w2=r(*ffn_lead, hidden, dim),
        w3=r(*ffn_lead, dim, hidden),
        rms_att=arr(np.ones((L, dim), np.float32)),
        rms_ffn=arr(np.ones((L, dim), np.float32)),
        moe_gate=(
            arr(rng.standard_normal((L, dim, E), dtype=np.float32) * scale)
            if E > 0
            else None
        ),
    )
    return LlamaParams(
        embedding=r(vocab, dim),
        layers=layers,
        rms_final=arr(np.ones((dim,), np.float32)),
        wcls=r(dim, vocab),
        rope_cos=arr(cos),
        rope_sin=arr(sin),
    )
