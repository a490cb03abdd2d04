#!/usr/bin/env python
"""Convert a HuggingFace checkpoint folder to the `.m` format (model types
llama, mistral, mixtral, qwen2, deepseek_v3, deepseek_v32, lfm2_moe, jamba,
cohere2_moe, minicpm_sala, mimo_v2_flash).

Usage: python convert-hf.py <sourceFolderPath> <weightsFloatType> <name>

Reimplementation of the reference converter (converter/convert-hf.py):
- tensor order must match the runtime walk (src/llm.cpp:447-483 /
  formats/model_file.py model_tensor_specs)
- Q and K projections are permuted from HF half-rotation layout to the
  interleaved-rotary layout the runtime's RoPE expects
  (reference converter/convert-hf.py:11-14)
- embeddings/norms stay F32; lm_head falls back to the tied embedding
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from distributed_llama_multiusers_tpu.formats.model_file import (
    ArchType,
    HiddenAct,
    LayerKind,
    NormKind,
    ModelHeader,
    MoeScore,
    RopeType,
)
from distributed_llama_multiusers_tpu.quants.codec import FloatType
from writer import parse_float_type, write_header, write_tensor


def permute_rotary(w: "np.ndarray", n_heads: int) -> "np.ndarray":
    """HF half-rotation -> interleaved layout: row blocks [h, 2, d/2] -> [h, d/2, 2]."""
    d_out, d_in = w.shape
    return (
        w.reshape(n_heads, 2, d_out // n_heads // 2, d_in).swapaxes(1, 2).reshape(d_out, d_in)
    )


class SafetensorsIndex:
    """Lazy tensor lookup across sharded safetensors files, loading one file
    at a time (the reference's Processor.__loadModel memory discipline)."""

    def __init__(self, files: list[str]):
        from safetensors import safe_open

        self._open = safe_open
        self._key_to_file: dict[str, str] = {}
        for path in files:
            with safe_open(path, framework="pt", device="cpu") as f:
                for k in f.keys():
                    self._key_to_file[k] = path
        self._current_path: str | None = None
        self._current = None

    def __contains__(self, key: str) -> bool:
        return key in self._key_to_file

    def get(self, key: str) -> np.ndarray:
        import torch

        path = self._key_to_file[key]
        if path != self._current_path:
            self._current = self._open(path, framework="pt", device="cpu").__enter__()
            self._current_path = path
            print(f"💿 reading {os.path.basename(path)}")
        t = self._current.get_tensor(key)
        return t.to(torch.float32).numpy()


def load_config(folder: str, weight_type: int) -> tuple[ModelHeader, dict]:
    with open(os.path.join(folder, "config.json")) as f:
        cfg = json.load(f)
    # qwen2 is the llama graph + q/k/v projection biases (KEY_QKV_BIAS;
    # detected from the checkpoint tensors in convert())
    arch = {
        "llama": ArchType.LLAMA,
        "mistral": ArchType.LLAMA,
        "mixtral": ArchType.LLAMA,
        "qwen2": ArchType.LLAMA,
        # latent attention + routed FFN: the header's KEY_KV_LORA_RANK block
        # says so; the arch word stays the one every reader accepts
        "deepseek_v3": ArchType.LLAMA,
        # the same block with a query latent, an indexer, expert groups, YaRN
        "deepseek_v32": ArchType.LLAMA,
        # a per-layer pattern of conv and attention mixers: KEY_LAYER_KIND
        "lfm2_moe": ArchType.LLAMA,
        # selective state-space mixers beside attention without rotation:
        # KEY_LAYER_KIND with LayerKind.SSM, the KEY_SSM_* keys, RopeType.NONE
        "jamba": ArchType.LLAMA,
        # window attention with a ring beside full-context attention without
        # rotation, heads of head_dim, a parallel block under one layer norm,
        # shared experts averaged: LayerKind.WINDOW and the KEY_HEAD_DIM keys
        "cohere2_moe": ArchType.LLAMA,
        # lightning linear-attention layers beside block-sparse GQA layers
        # under scaled residuals: LayerKind.LINEAR / SPARSE, the
        # KEY_LINEAR_* / KEY_SPARSE_* keys and the three scalars
        "minicpm_sala": ArchType.LLAMA,
        # window layers with a sink and kv heads and a rotation base of their
        # own beside full-context layers, keys wider than values, a head that
        # rotates in part, scaled values: the KEY_ROTARY_DIM ... keys
        "mimo_v2_flash": ArchType.LLAMA,
    }.get(cfg["model_type"])
    if cfg["model_type"] == "solar_open2":
        # the program runs the family (models/hybrid.py LayerKind.DELTA, the
        # walk in formats/model_file.py) but no checkpoint, safetensors index
        # or modeling code of it is on this machine to read the names from
        raise ValueError(
            "Unsupported arch type: solar_open2: the checkpoint's tensor names are not known "
            "here (a delta-rule layer's q / k / v projections and their three conv1d weights, "
            "the decay's and the output gate's two low-rank factors, A_log, dt_bias, the b "
            "projection, the output norm; a GQA layer's gate; the router's selection bias): "
            "formats/synthetic.py tiny_delta_header and benchmarks/families/solar_open2.py "
            "make such a model, formats/model_file.py _pattern_block_specs says the walk")
    if arch is None:
        raise ValueError(f"Unsupported arch type: {cfg['model_type']}")
    # lfm2_moe publishes no hidden_act: its FFNs are SwiGLU
    hidden_act = cfg.get("hidden_act", "silu" if cfg["model_type"] == "lfm2_moe" else None)
    act = {"gelu": HiddenAct.GELU, "silu": HiddenAct.SILU}.get(hidden_act)
    if act is None:
        raise ValueError(f"Unsupported hidden act: {hidden_act}")
    h = ModelHeader(
        version=0,
        arch_type=arch,
        hidden_act=act,
        dim=cfg["hidden_size"],
        hidden_dim=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        weight_type=weight_type,
        seq_len=cfg["max_position_embeddings"],
        orig_seq_len=cfg["max_position_embeddings"],
        vocab_size=cfg["vocab_size"],
        rope_theta=float(
            (cfg.get("rope_parameters") or {}).get("rope_theta", cfg.get("rope_theta", 10000.0))),
    )
    if cfg["model_type"] in ("deepseek_v3", "deepseek_v32"):
        set_latent_header(h, cfg)
    if cfg["model_type"] == "lfm2_moe":
        set_pattern_header(h, cfg)
    if cfg["model_type"] == "jamba":
        set_ssm_header(h, cfg)
    if cfg["model_type"] == "cohere2_moe":
        set_window_header(h, cfg)
    if cfg["model_type"] == "minicpm_sala":
        set_sala_header(h, cfg)
    if cfg["model_type"] == "mimo_v2_flash":
        set_mixed_head_header(h, cfg)
    n_experts = cfg.get("num_local_experts")
    if n_experts:
        h.n_experts = int(n_experts)
        h.n_active_experts = int(
            cfg.get("num_active_local_experts") or cfg.get("num_experts_per_tok")
        )
    scaling = cfg.get("rope_scaling")
    if scaling is not None and scaling.get("type", scaling.get("rope_type")) == "yarn":
        # YaRN's factor, beta_slow, beta_fast and original context ride the
        # four scaling keys (formats/model_file.py RopeType.YARN)
        h.rope_type = RopeType.YARN
        h.rope_scaling_factor = float(scaling["factor"])
        h.rope_scaling_low_freq_factor = float(scaling.get("beta_slow", 1))
        h.rope_scaling_high_freq_factor = float(scaling.get("beta_fast", 32))
        h.rope_scaling_orig_max_seq_len = int(scaling["original_max_position_embeddings"])
        h.rope_yarn_mscale_all_dim = float(scaling.get("mscale_all_dim", 0.0))
        if float(scaling.get("mscale", h.rope_yarn_mscale_all_dim)) != h.rope_yarn_mscale_all_dim:
            # the runtime scales the softmax and not the rotation
            raise ValueError(f"Unsupported rope scaling: mscale differs from mscale_all_dim: {scaling}")
    elif scaling is not None and scaling.get("rope_type") in ("llama3",):
        h.rope_type = RopeType.LLAMA3_1
        h.rope_scaling_factor = float(scaling["factor"])
        h.rope_scaling_low_freq_factor = float(scaling["low_freq_factor"])
        h.rope_scaling_high_freq_factor = float(scaling["high_freq_factor"])
        h.rope_scaling_orig_max_seq_len = int(scaling["original_max_position_embeddings"])
    elif scaling is not None and scaling.get("rope_type") not in (None, "default"):
        raise ValueError(f"Unsupported rope scaling: {scaling}")
    return h, cfg


def set_latent_header(h: ModelHeader, cfg: dict) -> None:
    """The header keys of ``model_type: deepseek_v3`` (formats/model_file.py
    KEY_KV_LORA_RANK ...). What the runtime does not compute is refused here,
    not converted wrongly."""
    if cfg.get("moe_layer_freq", 1) != 1:
        raise ValueError(f"Unsupported deepseek_v3 setting: moe_layer_freq = {cfg['moe_layer_freq']!r}")
    scaling = cfg.get("rope_scaling")
    if scaling is not None and scaling.get("type", scaling.get("rope_type")) != "yarn":
        raise ValueError(f"Unsupported deepseek_v3 setting: rope_scaling = {scaling!r}")
    # each mechanism by its own key, whatever the model's name
    h.q_lora_rank = int(cfg.get("q_lora_rank") or 0)
    h.moe_n_group = int(cfg.get("n_group") or 1)
    h.moe_topk_group = int(cfg.get("topk_group") or 1)
    h.index_topk = int(cfg.get("index_topk") or 0)
    if h.index_topk:
        h.index_n_heads, h.index_head_dim = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    if "router_norm_floor" in cfg:  # no published key: a caller's own
        h.moe_norm_floor = float(cfg["router_norm_floor"])
    if not cfg.get("rope_interleave", True):
        raise ValueError("Unsupported deepseek_v3 setting: rope_interleave false")
    score = {"sigmoid": MoeScore.SIGMOID, "softmax": MoeScore.SOFTMAX}.get(cfg["scoring_func"])
    if score is None:
        raise ValueError(f"Unsupported scoring_func: {cfg['scoring_func']}")
    h.kv_lora_rank = cfg["kv_lora_rank"]
    h.qk_nope_head_dim = cfg["qk_nope_head_dim"]
    h.qk_rope_head_dim = cfg["qk_rope_head_dim"]
    h.v_head_dim = cfg["v_head_dim"]
    h.norm_epsilon = float(cfg["rms_norm_eps"])
    h.n_experts = int(cfg.get("n_routed_experts") or 0)
    if h.n_experts:
        h.n_active_experts = int(cfg["num_experts_per_tok"])
        h.moe_hidden_dim = cfg["moe_intermediate_size"]
        h.shared_hidden_dim = int(cfg.get("n_shared_experts") or 0) * cfg["moe_intermediate_size"]
        h.n_dense_layers = int(cfg.get("first_k_dense_replace", 0))
        h.moe_score_func = score
        h.moe_select_bias = int(cfg.get("topk_method") == "noaux_tc")
        h.moe_norm_topk = int(bool(cfg.get("norm_topk_prob", True)))
        h.moe_routed_scale = float(cfg.get("routed_scaling_factor", 1.0))


_LAYER_KINDS = {"conv": LayerKind.CONV, "full_attention": LayerKind.ATTENTION}


def set_pattern_header(h: ModelHeader, cfg: dict) -> None:
    """The header keys of ``model_type: lfm2_moe`` (formats/model_file.py
    KEY_LAYER_KIND ...): the layer kinds as published, the conv's taps, the
    per-head norm of queries and keys, and the routed FFN's keys. What the
    runtime does not compute is refused here, not converted wrongly."""
    unknown = sorted(set(cfg["layer_types"]) - set(_LAYER_KINDS))
    if unknown:
        raise ValueError(f"Unsupported lfm2_moe layer types: {unknown}")
    if cfg.get("conv_bias"):
        raise ValueError("Unsupported lfm2_moe setting: conv_bias true")
    if (cfg.get("rope_parameters") or {}).get("rope_type", "default") != "default":
        raise ValueError(f"Unsupported rope parameters: {cfg['rope_parameters']}")
    h.layer_kinds = [_LAYER_KINDS[k] for k in cfg["layer_types"]]
    h.conv_kernel = int(cfg["conv_L_cache"])
    h.qk_norm = 1
    h.norm_epsilon = float(cfg["norm_eps"])
    h.n_experts = int(cfg.get("num_experts") or 0)
    if h.n_experts:
        h.n_active_experts = int(cfg["num_experts_per_tok"])
        h.moe_hidden_dim = cfg["moe_intermediate_size"]
        h.n_dense_layers = int(cfg.get("num_dense_layers", 0))
        h.moe_score_func = MoeScore.SIGMOID
        h.moe_select_bias = int(bool(cfg.get("use_expert_bias")))
        h.moe_norm_topk = int(bool(cfg.get("norm_topk_prob", True)))
        h.moe_routed_scale = float(cfg.get("routed_scaling_factor", 1.0))


def set_ssm_header(h: ModelHeader, cfg: dict) -> None:
    """The header keys of ``model_type: jamba`` (formats/model_file.py
    KEY_SSM_D_INNER ...): a layer is attention where ``l % attn_layer_period
    == attn_layer_offset`` and a state-space mixer elsewhere, nothing is
    rotated, and every layer's FFN is a plain MLP. What the runtime does not
    compute is refused here, by name, not converted wrongly."""
    if int(cfg.get("num_experts", 1)) > 1:
        raise ValueError(
            f"Unsupported jamba setting: num_experts = {cfg['num_experts']} (the routed "
            "variant, an expert layer every expert_layer_period, is not converted)")
    for key in ("mamba_proj_bias", "sliding_window"):
        if cfg.get(key):
            raise ValueError(f"Unsupported jamba setting: {key} = {cfg[key]!r}")
    period, offset = int(cfg["attn_layer_period"]), int(cfg["attn_layer_offset"])
    h.layer_kinds = [LayerKind.ATTENTION if l % period == offset else LayerKind.SSM
                     for l in range(h.n_layers)]
    h.rope_type = RopeType.NONE
    h.norm_epsilon = float(cfg["rms_norm_eps"])
    rank = cfg.get("mamba_dt_rank", "auto")
    h.ssm_dt_rank = -(-h.dim // 16) if rank == "auto" else int(rank)
    h.ssm_d_inner = int(cfg["mamba_expand"]) * h.dim
    h.ssm_d_state = int(cfg["mamba_d_state"])
    h.ssm_conv_kernel = int(cfg["mamba_d_conv"])
    h.ssm_conv_bias = int(bool(cfg.get("mamba_conv_bias", True)))
    h.ssm_inner_norms = 1  # dt_layernorm, b_layernorm, c_layernorm: the family's


# the sparse sizes of the family's MiniCPM4 release (arXiv:2506.07900), which a
# minicpm_sala config.json does not repeat; a ``sparse_config`` in it wins
_SALA_SPARSE_DEFAULTS = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
                         "window_size": 2048, "init_blocks": 1, "dense_len": 8192}
_SALA_LAYER_KINDS = {"lightning-attn": LayerKind.LINEAR, "minicpm4": LayerKind.SPARSE}


def set_sala_header(h: ModelHeader, cfg: dict) -> None:
    """The header keys of ``model_type: minicpm_sala`` (formats/model_file.py
    KEY_LINEAR_N_HEADS ...): a layer is lightning linear attention or
    block-sparse GQA by ``mixer_types``, queries and keys are normed per head,
    the lightning layers rotate and the sparse ones do not, every FFN is a
    plain MLP, and the three scalars of the parametrisation ride the header.
    What the runtime does not compute is refused here, by name."""
    kinds = cfg["mixer_types"]
    unknown = sorted(set(kinds) - set(_SALA_LAYER_KINDS))
    if unknown or len(kinds) != h.n_layers:
        raise ValueError(f"Unsupported minicpm_sala mixer types: {unknown or len(kinds)}")
    wanted = {"qk_norm": True, "lightning_use_rope": True, "attn_use_rope": False,
              "use_output_gate": True, "use_output_norm": True, "attn_use_output_gate": True,
              "attention_bias": False}
    for key, want in wanted.items():
        if bool(cfg.get(key, want)) != want:
            raise ValueError(f"Unsupported minicpm_sala setting: {key} = {cfg.get(key)!r}")
    if int(cfg["lightning_nkv"]) != int(cfg["lightning_nh"]):
        raise ValueError(
            f"Unsupported minicpm_sala setting: lightning_nkv = {cfg['lightning_nkv']} "
            "(a key head a query head)")
    h.layer_kinds = [_SALA_LAYER_KINDS[k] for k in kinds]
    h.qk_norm, h.full_attention_nope = 1, 1
    h.head_dim = int(cfg.get("head_dim") or 0)
    h.norm_epsilon = float(cfg["rms_norm_eps"])
    h.linear_n_heads, h.linear_head_dim = int(cfg["lightning_nh"]), int(cfg["lightning_head_dim"])
    sparse = {**_SALA_SPARSE_DEFAULTS, **(cfg.get("sparse_config") or {})}
    h.sparse_kernel_size, h.sparse_kernel_stride = int(sparse["kernel_size"]), int(sparse["kernel_stride"])
    h.sparse_block_size, h.sparse_topk = int(sparse["block_size"]), int(sparse["topk"])
    h.sparse_window, h.sparse_init_blocks = int(sparse["window_size"]), int(sparse["init_blocks"])
    h.sparse_dense_len = int(sparse["dense_len"])
    h.embed_scale = float(cfg["scale_emb"])
    h.residual_scale = float(cfg["scale_depth"]) / float(h.n_layers) ** 0.5
    h.logit_divisor = float(cfg["hidden_size"]) / float(cfg["dim_model_base"])


def write_sala_layers(out, index, header: ModelHeader, wt: int) -> None:
    """The layers of a minicpm_sala checkpoint in the order of
    formats/model_file._pattern_block_specs, under the tensor names the
    family's modeling file is taken to give them (no checkpoint was read:
    ``self_attn.{q,k,v,o}_proj``, ``q_norm``, ``k_norm``, ``o_gate`` in both
    kinds, ``o_norm`` in a lightning layer). A lightning layer's q and k rows
    are permuted to the interleaved-pair layout and their per-head norm gains
    alike (they are rotated); a sparse layer's are not (nothing is rotated)."""
    from numpy import ascontiguousarray as contiguous

    def pairs(g):  # a head's gains in the interleaved-pair order of its rows
        return contiguous(permute_rotary(g.reshape(-1, 1), 1).reshape(-1))

    for l, kind in enumerate(header.layer_kinds):
        a = f"model.layers.{l}.self_attn"
        if kind == LayerKind.LINEAR:
            heads = header.linear_n_heads
            write_tensor(out, permute_rotary(index.get(f"{a}.q_proj.weight"), heads), wt)
            write_tensor(out, permute_rotary(index.get(f"{a}.k_proj.weight"), heads), wt)
            write_tensor(out, index.get(f"{a}.v_proj.weight"), wt)
            write_tensor(out, pairs(index.get(f"{a}.q_norm.weight")), FloatType.F32)
            write_tensor(out, pairs(index.get(f"{a}.k_norm.weight")), FloatType.F32)
            write_tensor(out, index.get(f"{a}.o_gate.weight"), wt)
            write_tensor(out, index.get(f"{a}.o_norm.weight"), FloatType.F32)
            write_tensor(out, index.get(f"{a}.o_proj.weight"), wt)
        else:
            for name in ("q_proj", "k_proj", "v_proj"):
                write_tensor(out, index.get(f"{a}.{name}.weight"), wt)
            write_tensor(out, index.get(f"{a}.q_norm.weight"), FloatType.F32)
            write_tensor(out, index.get(f"{a}.k_norm.weight"), FloatType.F32)
            write_tensor(out, index.get(f"{a}.o_gate.weight"), wt)
            write_tensor(out, index.get(f"{a}.o_proj.weight"), wt)
        mlp = f"model.layers.{l}.mlp"
        write_tensor(out, index.get(f"{mlp}.gate_proj.weight"), wt)  # w1
        write_tensor(out, index.get(f"{mlp}.down_proj.weight"), wt)  # w2
        write_tensor(out, index.get(f"{mlp}.up_proj.weight"), wt)  # w3
        write_tensor(out, index.get(f"model.layers.{l}.input_layernorm.weight"), FloatType.F32)
        write_tensor(out, index.get(f"model.layers.{l}.post_attention_layernorm.weight"),
                     FloatType.F32)


_WINDOW_LAYER_KINDS = {"sliding_attention": LayerKind.WINDOW,
                       "full_attention": LayerKind.ATTENTION}


def set_window_header(h: ModelHeader, cfg: dict) -> None:
    """The header keys of ``model_type: cohere2_moe`` (formats/model_file.py
    KEY_HEAD_DIM ...): the layer kinds as published (window layers rotate,
    full-context layers do not), the window, a head's width, the
    mean-subtracting norm, the parallel block, the routed FFN's keys and the
    shared experts as one gated FFN scaled by their average. What the runtime
    does not compute is refused here, by name, not converted wrongly."""
    unknown = sorted(set(cfg["layer_types"]) - set(_WINDOW_LAYER_KINDS))
    if unknown:
        raise ValueError(f"Unsupported cohere2_moe layer types: {unknown}")
    refused = {
        "use_qk_norm": bool(cfg.get("use_qk_norm")),
        "logit_scale": float(cfg.get("logit_scale", 1)) != 1.0,
        "first_k_dense_replace": int(cfg.get("first_k_dense_replace", 0)) > 0,
        "use_parallel_block": not cfg.get("use_parallel_block", False),
        "attention_bias": bool(cfg.get("attention_bias")),
        "rotary_pct": float(cfg.get("rotary_pct", 1)) != 1.0,
        "position_embedding_type": cfg.get("position_embedding_type", "rope_gptj") != "rope_gptj",
        "expert_selection_fn": cfg.get("expert_selection_fn") != "sigmoid",
        "shared_expert_combination_strategy":
            cfg.get("shared_expert_combination_strategy", "average") != "average",
        "use_gated_activation": not cfg.get("use_gated_activation", True),
    }
    for key, bad in refused.items():
        if bad:
            # (first_k_dense_replace > 0 would bring the prefix_dense_* layers)
            raise ValueError(f"Unsupported cohere2_moe setting: {key} = {cfg.get(key)!r}")
    if (cfg.get("rope_parameters") or {}).get("rope_type", "default") != "default":
        raise ValueError(f"Unsupported rope parameters: {cfg['rope_parameters']}")
    h.layer_kinds = [_WINDOW_LAYER_KINDS[k] for k in cfg["layer_types"]]
    h.sliding_window = int(cfg["sliding_window"])
    h.head_dim = int(cfg.get("head_dim") or 0)
    h.full_attention_nope, h.norm_kind, h.parallel_block = 1, NormKind.LAYER, 1
    h.norm_epsilon = float(cfg["layer_norm_eps"])
    h.n_experts = int(cfg["num_experts"])
    h.n_active_experts = int(cfg["num_experts_per_tok"])
    h.moe_hidden_dim = cfg["intermediate_size"]
    shared = int(cfg.get("num_shared_experts") or 0)
    h.shared_hidden_dim = shared * cfg["intermediate_size"]
    h.shared_expert_scale = 1.0 / shared if shared else 1.0
    h.moe_score_func = MoeScore.SIGMOID
    h.moe_norm_topk = int(bool(cfg.get("norm_topk_prob", True)))
    h.moe_norm_floor = 0.0  # sigmoid scores are positive: the family's sum has no floor


def write_window_layers(out, index, header: ModelHeader, wt: int) -> None:
    """The layers of a cohere2_moe checkpoint in the order of
    formats/model_file._pattern_block_specs, tensor names as the family's
    dense sibling (``cohere2``) and the routed families here name theirs. No
    row of q or k is permuted: ``rope_gptj`` turns adjacent pairs, the
    runtime's own convention. The shared experts are folded into ONE gated
    FFN (gate and up rows stacked, down columns side by side), which the
    header's ``shared_expert_scale`` turns into their average. One norm a
    layer; the router stays F32."""
    held = range(header.experts_held_first,
                 header.experts_held_first + (header.experts_held_count or header.n_experts))
    n_shared = header.shared_hidden_dim // header.moe_hidden_dim
    for l in range(header.n_layers):
        pre = f"model.layers.{l}"
        for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
            write_tensor(out, index.get(f"{pre}.self_attn.{name}.weight"), wt)
        write_tensor(out, index.get(f"{pre}.mlp.gate.weight"), FloatType.F32)
        for e in held:  # the experts this file holds (all of them unless told)
            epre = f"{pre}.mlp.experts.{e}"
            write_tensor(out, index.get(f"{epre}.up_proj.weight"), wt)  # w3
            write_tensor(out, index.get(f"{epre}.gate_proj.weight"), wt)  # w1
            write_tensor(out, index.get(f"{epre}.down_proj.weight"), wt)  # w2
        if n_shared:
            part = lambda name: [  # noqa: E731
                index.get(f"{pre}.mlp.shared_experts.{i}.{name}.weight") for i in range(n_shared)]
            write_tensor(out, np.concatenate(part("gate_proj"), axis=0), wt)  # w1
            write_tensor(out, np.concatenate(part("down_proj"), axis=1), wt)  # w2
            write_tensor(out, np.concatenate(part("up_proj"), axis=0), wt)  # w3
        write_tensor(out, index.get(f"{pre}.input_layernorm.weight"), FloatType.F32)


def permute_rotary_first(w: "np.ndarray", n_heads: int, rotary: int) -> "np.ndarray":
    """``permute_rotary`` on the first ``rotary`` rows of every head (the rows
    that rotate, published in the half-rotation pairing ``i, i + rotary / 2``),
    the head's other rows as they are."""
    d_out, d_in = w.shape
    heads = w.reshape(n_heads, d_out // n_heads, d_in)
    first = permute_rotary(heads[:, :rotary].reshape(n_heads * rotary, d_in), n_heads)
    return np.concatenate(
        [first.reshape(n_heads, rotary, d_in), heads[:, rotary:]], axis=1).reshape(d_out, d_in)


def set_mixed_head_header(h: ModelHeader, cfg: dict) -> None:
    """The header keys of ``model_type: mimo_v2_flash`` (formats/model_file.py
    KEY_ROTARY_DIM ...): the layer kinds as published (``hybrid_layer_pattern``:
    0 full context, 1 window), each kind's kv heads and rotation base, a key
    head's and a value head's width, the width that rotates, the value scale,
    the window layers' sink, the leading dense layers (``moe_layer_freq``) and
    the routed FFN's keys (sigmoid scores, a selection bias, no shared expert).
    What the runtime does not compute is refused here, by name, not converted
    wrongly."""
    kinds, freq = list(cfg["hybrid_layer_pattern"]), list(cfg["moe_layer_freq"])
    n_dense = next((i for i, f in enumerate(freq) if f), len(freq))
    refused = {
        "hybrid_layer_pattern": len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {0, 1},
        "moe_layer_freq": len(freq) != len(kinds) or any(f != 1 for f in freq[n_dense:]),
        "attention_bias": bool(cfg.get("attention_bias")),
        "add_full_attention_sink_bias": bool(cfg.get("add_full_attention_sink_bias")),
        "swa_num_attention_heads": cfg["swa_num_attention_heads"] != cfg["num_attention_heads"],
        "swa_head_dim": cfg["swa_head_dim"] != cfg["head_dim"],
        "swa_v_head_dim": cfg["swa_v_head_dim"] != cfg["v_head_dim"],
        "n_shared_experts": bool(cfg.get("n_shared_experts")),
        "routed_scaling_factor": cfg.get("routed_scaling_factor") not in (None, 1, 1.0),
        "n_group": cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1,
        "scoring_func": cfg.get("scoring_func") != "sigmoid",
        "topk_method": cfg.get("topk_method") not in ("noaux_tc", "greedy"),
    }
    for key, bad in refused.items():
        if bad:
            raise ValueError(f"Unsupported mimo_v2_flash setting: {key} = {cfg.get(key)!r}")
    h.layer_kinds = [LayerKind.WINDOW if k else LayerKind.ATTENTION for k in kinds]
    h.head_dim, h.v_head_dim = int(cfg["head_dim"]), int(cfg["v_head_dim"])
    # (the rounding of head_dim x partial_rotary_factor is not published: down)
    h.rotary_dim = int(cfg["head_dim"] * cfg.get("partial_rotary_factor", 1.0))
    h.sliding_window = int(cfg["sliding_window"])
    h.window_n_kv_heads = int(cfg["swa_num_key_value_heads"])
    h.window_rope_theta = float(cfg["swa_rope_theta"])
    h.attn_value_scale = float(cfg.get("attention_value_scale") or 1.0)
    h.window_sink = int(bool(cfg.get("add_swa_attention_sink_bias")))
    h.norm_epsilon = float(cfg["layernorm_epsilon"])
    h.n_dense_layers = n_dense
    h.n_experts = int(cfg["n_routed_experts"])
    h.n_active_experts = int(cfg["num_experts_per_tok"])
    h.moe_hidden_dim = int(cfg["moe_intermediate_size"])
    h.moe_score_func = MoeScore.SIGMOID
    h.moe_select_bias = int(cfg.get("topk_method") == "noaux_tc")
    h.moe_norm_topk = int(bool(cfg.get("norm_topk_prob", True)))
    h.moe_norm_floor = 0.0  # sigmoid scores are positive: the family's sum has no floor


def write_mixed_head_layers(out, index, header: ModelHeader, wt: int) -> None:
    """The layers of a mimo_v2_flash checkpoint in the order of
    formats/model_file._pattern_block_specs: q, k, v of the layer's kind (the
    first ``rotary_dim`` rows of every q and k head permuted from the
    published half-rotation pairing to adjacent pairs), wo, a window layer's
    ``attention_sink_bias`` (F32); then a dense FFN in the leading layers and
    in the others the router (F32), its ``e_score_correction_bias`` (F32) and
    the held experts' up, gate, down; then the two norms."""
    held = range(header.experts_held_first,
                 header.experts_held_first + (header.experts_held_count or header.n_experts))
    for l, kind in enumerate(header.layer_kinds):
        pre = f"model.layers.{l}"
        n_kv = header.kv_heads(kind == LayerKind.WINDOW)
        for name, heads in (("q_proj", header.n_heads), ("k_proj", n_kv)):
            write_tensor(out, permute_rotary_first(
                index.get(f"{pre}.self_attn.{name}.weight"), heads, header.rotary_dim), wt)
        write_tensor(out, index.get(f"{pre}.self_attn.v_proj.weight"), wt)
        write_tensor(out, index.get(f"{pre}.self_attn.o_proj.weight"), wt)
        if header.window_sink and kind == LayerKind.WINDOW:
            write_tensor(out, index.get(f"{pre}.self_attn.attention_sink_bias"), FloatType.F32)
        if l < header.n_dense_layers:
            write_tensor(out, index.get(f"{pre}.mlp.gate_proj.weight"), wt)  # w1
            write_tensor(out, index.get(f"{pre}.mlp.down_proj.weight"), wt)  # w2
            write_tensor(out, index.get(f"{pre}.mlp.up_proj.weight"), wt)  # w3
        else:
            write_tensor(out, index.get(f"{pre}.mlp.gate.weight"), FloatType.F32)
            if header.moe_select_bias:
                write_tensor(out, index.get(f"{pre}.mlp.gate.e_score_correction_bias"),
                             FloatType.F32)
            for e in held:  # the experts this file holds (all of them unless told)
                epre = f"{pre}.mlp.experts.{e}"
                write_tensor(out, index.get(f"{epre}.up_proj.weight"), wt)  # w3
                write_tensor(out, index.get(f"{epre}.gate_proj.weight"), wt)  # w1
                write_tensor(out, index.get(f"{epre}.down_proj.weight"), wt)  # w2
        write_tensor(out, index.get(f"{pre}.input_layernorm.weight"), FloatType.F32)
        write_tensor(out, index.get(f"{pre}.post_attention_layernorm.weight"), FloatType.F32)


def write_ssm_layers(out, index, header: ModelHeader, wt: int) -> None:
    """The layers of a jamba checkpoint in the order of
    formats/model_file._pattern_block_specs. ``mamba.in_proj`` (x, z) and
    ``mamba.x_proj`` (dt, B, C) are kept whole; the depthwise taps ``[E, 1,
    K]`` are written ``[E, K]``; what steers the state's exponential
    (``dt_proj`` and its bias, ``A_log``, ``D``), the conv's taps and bias and
    the three inner norms' gains stay F32. No row of q or k is permuted:
    nothing is rotated."""
    for l, kind in enumerate(header.layer_kinds):
        pre = f"model.layers.{l}"
        if kind == LayerKind.SSM:
            m = f"{pre}.mamba"
            write_tensor(out, index.get(f"{m}.in_proj.weight"), wt)
            taps = index.get(f"{m}.conv1d.weight")
            write_tensor(out, taps.reshape(header.ssm_d_inner, header.ssm_conv_kernel), FloatType.F32)
            if header.ssm_conv_bias:
                write_tensor(out, index.get(f"{m}.conv1d.bias"), FloatType.F32)
            write_tensor(out, index.get(f"{m}.x_proj.weight"), wt)
            for name in ("dt_layernorm", "b_layernorm", "c_layernorm"):
                write_tensor(out, index.get(f"{m}.{name}.weight"), FloatType.F32)
            write_tensor(out, index.get(f"{m}.dt_proj.weight"), FloatType.F32)
            write_tensor(out, index.get(f"{m}.dt_proj.bias"), FloatType.F32)
            write_tensor(out, index.get(f"{m}.A_log"), FloatType.F32)
            write_tensor(out, index.get(f"{m}.D"), FloatType.F32)
            write_tensor(out, index.get(f"{m}.out_proj.weight"), wt)
        else:
            for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
                write_tensor(out, index.get(f"{pre}.self_attn.{name}.weight"), wt)
        ffn = f"{pre}.feed_forward"
        write_tensor(out, index.get(f"{ffn}.gate_proj.weight"), wt)  # w1
        write_tensor(out, index.get(f"{ffn}.down_proj.weight"), wt)  # w2
        write_tensor(out, index.get(f"{ffn}.up_proj.weight"), wt)  # w3
        write_tensor(out, index.get(f"{pre}.input_layernorm.weight"), FloatType.F32)
        write_tensor(out, index.get(f"{pre}.pre_ff_layernorm.weight"), FloatType.F32)


def write_pattern_layers(out, index, header: ModelHeader, wt: int) -> None:
    """The layers of an lfm2_moe checkpoint in the order of
    formats/model_file._pattern_block_specs. ``conv.in_proj`` is kept whole
    (its thirds are B, C, x); the depthwise taps ``[dim, 1, K]`` are written
    ``[dim, K]``; q and k rows are permuted to the interleaved-pair layout as
    a Llama file's, and the per-head norm gains with them: the gain is
    applied per dimension BEFORE the rotation, so it must follow its row."""
    n_heads, n_kv = header.n_heads, header.n_kv_heads
    for l, kind in enumerate(header.layer_kinds):
        pre = f"model.layers.{l}"
        if kind == LayerKind.CONV:
            write_tensor(out, index.get(f"{pre}.conv.in_proj.weight"), wt)
            taps = index.get(f"{pre}.conv.conv.weight")
            write_tensor(out, taps.reshape(header.dim, header.conv_kernel), FloatType.F32)
            write_tensor(out, index.get(f"{pre}.conv.out_proj.weight"), wt)
        else:
            att = f"{pre}.self_attn"
            write_tensor(out, permute_rotary(index.get(f"{att}.q_proj.weight"), n_heads), wt)
            write_tensor(out, permute_rotary(index.get(f"{att}.k_proj.weight"), n_kv), wt)
            write_tensor(out, index.get(f"{att}.v_proj.weight"), wt)
            for name in ("q_layernorm", "k_layernorm"):
                gain = index.get(f"{att}.{name}.weight").reshape(-1, 1)
                write_tensor(out, permute_rotary(gain, 1).reshape(-1), FloatType.F32)
            write_tensor(out, index.get(f"{att}.out_proj.weight"), wt)
        ffn = f"{pre}.feed_forward"
        if l < header.n_dense_layers or header.n_experts == 0:
            write_tensor(out, index.get(f"{ffn}.w1.weight"), wt)  # gate
            write_tensor(out, index.get(f"{ffn}.w2.weight"), wt)  # down
            write_tensor(out, index.get(f"{ffn}.w3.weight"), wt)  # up
        else:
            write_tensor(out, index.get(f"{ffn}.gate.weight"), FloatType.F32)
            if header.moe_select_bias:
                write_tensor(out, index.get(f"{ffn}.expert_bias"), FloatType.F32)
            for e in range(header.n_experts):
                epre = f"{ffn}.experts.{e}"
                write_tensor(out, index.get(f"{epre}.w3.weight"), wt)  # up
                write_tensor(out, index.get(f"{epre}.w1.weight"), wt)  # gate
                write_tensor(out, index.get(f"{epre}.w2.weight"), wt)  # down
        write_tensor(out, index.get(f"{pre}.operator_norm.weight"), FloatType.F32)
        write_tensor(out, index.get(f"{pre}.ffn_norm.weight"), FloatType.F32)


def write_latent_layers(out, index, header: ModelHeader, wt: int) -> None:
    """The layers of a deepseek_v3 checkpoint in the order of
    formats/model_file._latent_block_specs. ``kv_a_proj_with_mqa`` and
    ``kv_b_proj`` are kept whole; no row is permuted: ``rope_interleave``
    checkpoints hold the rotary part in adjacent pairs, the runtime's own
    convention. The router and its selection bias stay F32."""
    held = range(header.experts_held_first,
                 header.experts_held_first + (header.experts_held_count or header.n_experts))
    for l in range(header.n_layers):
        pre = f"model.layers.{l}"
        if header.q_lora_rank:
            write_tensor(out, index.get(f"{pre}.self_attn.q_a_proj.weight"), wt)
            write_tensor(out, index.get(f"{pre}.self_attn.q_a_layernorm.weight"), FloatType.F32)
            write_tensor(out, index.get(f"{pre}.self_attn.q_b_proj.weight"), wt)
        else:
            write_tensor(out, index.get(f"{pre}.self_attn.q_proj.weight"), wt)
        if header.index_topk:
            ipre = f"{pre}.self_attn.indexer"
            write_tensor(out, index.get(f"{ipre}.wq_b.weight"), wt)
            write_tensor(out, index.get(f"{ipre}.wk.weight"), wt)
            write_tensor(out, index.get(f"{ipre}.k_norm.weight"), FloatType.F32)
            write_tensor(out, index.get(f"{ipre}.k_norm.bias"), FloatType.F32)
            write_tensor(out, index.get(f"{ipre}.weights_proj.weight"), FloatType.F32)
        write_tensor(out, index.get(f"{pre}.self_attn.kv_a_proj_with_mqa.weight"), wt)
        write_tensor(out, index.get(f"{pre}.self_attn.kv_a_layernorm.weight"), FloatType.F32)
        write_tensor(out, index.get(f"{pre}.self_attn.kv_b_proj.weight"), wt)
        write_tensor(out, index.get(f"{pre}.self_attn.o_proj.weight"), wt)
        if l < header.n_dense_layers or header.n_experts == 0:
            write_tensor(out, index.get(f"{pre}.mlp.gate_proj.weight"), wt)  # w1
            write_tensor(out, index.get(f"{pre}.mlp.down_proj.weight"), wt)  # w2
            write_tensor(out, index.get(f"{pre}.mlp.up_proj.weight"), wt)  # w3
        else:
            write_tensor(out, index.get(f"{pre}.mlp.gate.weight"), FloatType.F32)
            if header.moe_select_bias:
                write_tensor(out, index.get(f"{pre}.mlp.gate.e_score_correction_bias"), FloatType.F32)
            for e in held:  # the experts this file holds (all of them unless told)
                epre = f"{pre}.mlp.experts.{e}"
                write_tensor(out, index.get(f"{epre}.up_proj.weight"), wt)  # w3
                write_tensor(out, index.get(f"{epre}.gate_proj.weight"), wt)  # w1
                write_tensor(out, index.get(f"{epre}.down_proj.weight"), wt)  # w2
            if header.shared_hidden_dim:
                spre = f"{pre}.mlp.shared_experts"
                write_tensor(out, index.get(f"{spre}.gate_proj.weight"), wt)  # w1
                write_tensor(out, index.get(f"{spre}.down_proj.weight"), wt)  # w2
                write_tensor(out, index.get(f"{spre}.up_proj.weight"), wt)  # w3
        write_tensor(out, index.get(f"{pre}.input_layernorm.weight"), FloatType.F32)
        write_tensor(out, index.get(f"{pre}.post_attention_layernorm.weight"), FloatType.F32)


def convert(folder: str, weight_type: int, out_path: str, index=None,
            experts_held: tuple | None = None) -> None:
    """``index`` (tests): anything with ``get(key)`` and ``in`` over the
    checkpoint's tensor names, in place of the folder's safetensors.
    ``experts_held`` ``(first id, count)``: write one chip's share of the
    routed experts (the router keeps every output)."""
    header, cfg = load_config(folder, weight_type)
    if experts_held is not None:
        header.experts_held_first, header.experts_held_count = (int(x) for x in experts_held)
    if index is not None:
        return write_model(header, index, weight_type, out_path)
    files = sorted(
        os.path.join(folder, f)
        for f in os.listdir(folder)
        if f.endswith(".safetensors") and not f.startswith(".")
    )
    if not files:
        raise FileNotFoundError("No .safetensors files found")
    write_model(header, SafetensorsIndex(files), weight_type, out_path)


def write_model(header: ModelHeader, index, weight_type: int, out_path: str) -> None:
    wt = weight_type
    n_heads, n_kv = header.n_heads, header.n_kv_heads
    # Qwen2-family checkpoints (and llama-arch configs with
    # attention_bias=true) carry q/k/v projection biases
    header.qkv_bias = int("model.layers.0.self_attn.q_proj.bias" in index)

    def bias_permuted(key: str, heads: int) -> np.ndarray:
        # same head-dim rotary relayout as the weight, applied to the vector
        return permute_rotary(index.get(key).reshape(-1, 1), heads).reshape(-1)

    with open(out_path, "wb") as out:
        write_header(out, header)
        write_tensor(out, index.get("model.embed_tokens.weight"), FloatType.F32)
        if header.kv_lora_rank:
            write_latent_layers(out, index, header, wt)
        elif header.ssm_d_inner:
            write_ssm_layers(out, index, header, wt)
        elif header.linear_n_heads:
            write_sala_layers(out, index, header, wt)
        elif header.parallel_block:
            write_window_layers(out, index, header, wt)
        elif header.window_n_kv_heads or header.window_sink:
            write_mixed_head_layers(out, index, header, wt)
        elif header.layer_kinds:
            write_pattern_layers(out, index, header, wt)
        for l in range(0 if header.kv_lora_rank or header.layer_kinds else header.n_layers):  # a Llama block's layers
            pre = f"model.layers.{l}"
            write_tensor(out, permute_rotary(index.get(f"{pre}.self_attn.q_proj.weight"), n_heads), wt)
            if header.qkv_bias:
                write_tensor(out, bias_permuted(f"{pre}.self_attn.q_proj.bias", n_heads), FloatType.F32)
            write_tensor(out, permute_rotary(index.get(f"{pre}.self_attn.k_proj.weight"), n_kv), wt)
            if header.qkv_bias:
                write_tensor(out, bias_permuted(f"{pre}.self_attn.k_proj.bias", n_kv), FloatType.F32)
            write_tensor(out, index.get(f"{pre}.self_attn.v_proj.weight"), wt)
            if header.qkv_bias:
                write_tensor(out, index.get(f"{pre}.self_attn.v_proj.bias"), FloatType.F32)
            write_tensor(out, index.get(f"{pre}.self_attn.o_proj.weight"), wt)
            if header.n_experts > 0:
                # router (framework extension: the reference converter drops
                # the gate, leaving its MoE files unrunnable) + per-expert
                # w3/w1/w2 in the reference's expert order (convert-hf.py:66-73
                # upstream)
                write_tensor(
                    out, index.get(f"{pre}.block_sparse_moe.gate.weight"), FloatType.F32
                )
                for e in range(header.n_experts):
                    epre = f"{pre}.block_sparse_moe.experts.{e}"
                    write_tensor(out, index.get(f"{epre}.w3.weight"), wt)  # up
                    write_tensor(out, index.get(f"{epre}.w1.weight"), wt)  # gate
                    write_tensor(out, index.get(f"{epre}.w2.weight"), wt)  # down
            else:
                write_tensor(out, index.get(f"{pre}.mlp.gate_proj.weight"), wt)  # w1
                write_tensor(out, index.get(f"{pre}.mlp.down_proj.weight"), wt)  # w2
                write_tensor(out, index.get(f"{pre}.mlp.up_proj.weight"), wt)  # w3
            write_tensor(out, index.get(f"{pre}.input_layernorm.weight"), FloatType.F32)
            write_tensor(out, index.get(f"{pre}.post_attention_layernorm.weight"), FloatType.F32)
        # lfm2_moe names its final norm after the embedding, jamba after its place
        norm_key = ("model.final_layernorm.weight" if header.ssm_d_inner
                    else "model.norm.weight" if (header.parallel_block or header.linear_n_heads
                                                 or header.window_n_kv_heads or header.window_sink)
                    else "model.embedding_norm.weight" if header.layer_kinds
                    else "model.norm.weight")
        write_tensor(out, index.get(norm_key), FloatType.F32)
        head_key = "lm_head.weight" if "lm_head.weight" in index else "model.embed_tokens.weight"
        write_tensor(out, index.get(head_key), wt)
    print(f"✅ {out_path} created successfully")


def main() -> None:
    if len(sys.argv) < 4:
        print("Usage: python convert-hf.py <sourceFolderPath> <weightsFloatType> <name>")
        raise SystemExit(1)
    folder = sys.argv[1]
    weight_type = parse_float_type(sys.argv[2])
    name = sys.argv[3]
    convert(folder, weight_type, f"dllama_model_{name}_{sys.argv[2]}.m")


if __name__ == "__main__":
    main()
