"""The `.m` model file format — header + raw tensors in fixed order.

Format (reference src/llm.cpp:26-98, converter/writer.py:109-145):

    int32 magic = 0xA00ABCD
    int32 headerSize            # bytes of (magic, headerSize, kv...) == 8 + 8*nKv
    (int32 key, int32 value) * nKv
    raw tensor bytes...

Tensor order (src/llm.cpp:447-483):
    embedding (F32, [vocab, dim])
    per layer: q k v wo w1 w2 w3 (weightType), rms_att rms_ffn (F32, [dim])
    final: rms_final (F32, [dim]), wcls (weightType, [vocab, dim])

Matmul weights are stored row-major [d_out, d_in] (d_in contiguous), i.e. a
tensor that maps x[d_in] -> y[d_out] via y = W @ x. Q/K weights are stored
pre-permuted to the interleaved-rotary layout (converter/convert-hf.py:11-14).
"""

from __future__ import annotations

import math
import mmap
import os
import struct
from dataclasses import dataclass, field
from typing import BinaryIO, Iterator

import numpy as np

from ..quants.codec import FloatType, tensor_bytes

MODEL_MAGIC = 0xA00ABCD

# Header keys (src/llm.hpp:8-28)
KEY_VERSION = 0
KEY_ARCH_TYPE = 1
KEY_DIM = 2
KEY_HIDDEN_DIM = 3
KEY_N_LAYERS = 4
KEY_N_HEADS = 5
KEY_N_KV_HEADS = 6
KEY_N_EXPERTS = 7
KEY_N_ACTIVE_EXPERTS = 8
KEY_VOCAB_SIZE = 9
KEY_SEQ_LEN = 10
KEY_HIDDEN_ACT = 11
KEY_ROPE_THETA = 12
KEY_WEIGHT_FLOAT_TYPE = 13
KEY_ROPE_SCALING_FACTOR = 14
KEY_ROPE_SCALING_LOW_FREQ_FACTOR = 15
KEY_ROPE_SCALING_HIGH_FREQ_FACTORY = 16
KEY_ROPE_SCALING_ORIG_MAX_SEQ_LEN = 17
KEY_ROPE_TYPE = 18
# framework extension (reference enum src/llm.hpp:8-28 stops at 18):
# nonzero = per-layer q/k/v bias vectors follow each q/k/v matmul tensor
# (Qwen2-family checkpoints). Readers of bias-free files never see the key,
# so every pre-extension .m stays byte-identical.
KEY_QKV_BIAS = 19
# framework extension: the latent-attention block with a routed FFN
# (``model_type: deepseek_v3``; models/deepseek.py). Written only where
# KEY_KV_LORA_RANK is nonzero, so every file without them reads, and is
# written, as before. Two values are not whole numbers and are stored scaled:
# the routed experts' factor in millionths, the norm's epsilon in billionths
# (absent: the 1e-5 every Llama file has always been read with).
KEY_KV_LORA_RANK = 20
KEY_QK_NOPE_HEAD_DIM = 21
KEY_QK_ROPE_HEAD_DIM = 22
KEY_V_HEAD_DIM = 23
KEY_MOE_HIDDEN_DIM = 24
KEY_SHARED_HIDDEN_DIM = 25
KEY_N_DENSE_LAYERS = 26
KEY_MOE_SCORE_FUNC = 27
KEY_MOE_SELECT_BIAS = 28
KEY_MOE_NORM_TOPK = 29
KEY_MOE_ROUTED_SCALE_E6 = 30
KEY_NORM_EPSILON_E9 = 31
# framework extension: a block whose layers differ in their mixer
# (``model_type: lfm2_moe``; models/hybrid.py). KEY_LAYER_KIND is written once
# a layer, in layer order (a list, as published: no period is guessed from
# it); KEY_CONV_KERNEL is the short convolution's taps, KEY_QK_NORM says that
# an attention layer norms its queries and keys per head. Such a file also
# carries the routed FFN's keys above (kv_lora_rank 0). A file without a layer
# kind reads, and is written, as before.
KEY_LAYER_KIND = 32
KEY_CONV_KERNEL = 33
KEY_QK_NORM = 34
# framework extension: what ``model_type: deepseek_v32`` adds to the latent
# block (models/deepseek.py), each written only where it is set, so every
# file without them reads, and is written, as before. A query latent
# (KEY_Q_LORA_RANK: ``q_a``, its norm, ``q_b`` in the place of ``q``); the
# lightning indexer (its heads, their width and how many positions it keeps;
# three matrices and a layer norm's gain and bias a layer); the router's
# expert groups; the share of the routed experts this file holds (first id
# and count: the router keeps every output, the file only those experts'
# tensors; count 0: all of them); YaRN's scale on the softmax
# (``mscale_all_dim``, in millionths; its factor, beta_slow, beta_fast and
# original context ride the four KEY_ROPE_SCALING_* keys under
# RopeType.YARN); and the floor under the
# router's renormalising sum as a power of ten (20: 1e-20, what a file without
# the key is read with; -1: no floor).
KEY_Q_LORA_RANK = 35
KEY_INDEX_N_HEADS = 36
KEY_INDEX_HEAD_DIM = 37
KEY_INDEX_TOPK = 38
KEY_MOE_N_GROUP = 39
KEY_MOE_TOPK_GROUP = 40
KEY_EXPERTS_HELD_FIRST = 41
KEY_EXPERTS_HELD_COUNT = 42
KEY_ROPE_YARN_MSCALE_ALL_DIM_E6 = 43
KEY_MOE_NORM_FLOOR_EXP10 = 44
# framework extension: a selective state-space mixer (``LayerKind.SSM``;
# ``model_type: jamba``; models/hybrid.py, ops/ssm_scan.py), each key written
# only where the file has such a layer, so every file without one reads, and
# is written, as before. The mixer's inner width (expand x dim), the state a
# channel, the rank of the step size's projection, the taps of its causal
# depthwise conv, whether that conv has a bias, and whether dt, B and C are
# normed before use (the family's three inner norms).
KEY_SSM_D_INNER = 45
KEY_SSM_D_STATE = 46
KEY_SSM_DT_RANK = 47
KEY_SSM_CONV_KERNEL = 48
KEY_SSM_CONV_BIAS = 49
KEY_SSM_INNER_NORMS = 50
# framework extension: what ``model_type: cohere2_moe`` adds to a block of
# mixed layers, each key written only where it is set, so every file without
# them reads, and is written, as before. A head width that is not ``dim //
# n_heads`` (KEY_HEAD_DIM; 0: that quotient, in a Llama block's file too);
# window attention as a layer kind (``LayerKind.WINDOW``) and the window's
# size in positions (a query reads the ``sliding_window`` newest keys, itself
# among them); full-context layers that do not rotate where the window layers
# do (KEY_FULL_ATTENTION_NOPE); a norm that subtracts the mean
# (``NormKind.LAYER``: a gain and no bias); a block whose attention and FFN
# read ONE normed input and are added together (KEY_PARALLEL_BLOCK: one norm
# a layer in the file); the factor on the shared experts' output, in
# millionths (four experts averaged: one gated FFN of four times the width
# at 0.25).
KEY_HEAD_DIM = 51
KEY_SLIDING_WINDOW = 52
KEY_FULL_ATTENTION_NOPE = 53
KEY_NORM_KIND = 54
KEY_PARALLEL_BLOCK = 55
KEY_SHARED_EXPERT_SCALE_E6 = 56
# framework extension: what ``model_type: minicpm_sala`` adds to a block of
# mixed layers, each key written only where it is set, so every file without
# them reads, and is written, as before. A linear-attention layer
# (``LayerKind.LINEAR``): its heads and their width (a float32 matrix state
# ``[head, head]`` a head a lane; its decay a head is fixed by the head's
# number, models/hybrid.py ``linear_decay_slopes``). A block-sparse GQA layer
# (``LayerKind.SPARSE``): keys compressed by a mean over ``kernel_size``
# positions every ``kernel_stride``, blocks of ``block_size`` positions of
# which a query row at or past position ``dense_len`` attends the ``topk``
# its compressed keys' scores choose, the first ``init_blocks`` and the
# blocks of the newest ``window`` positions always among them. Three scalars
# of a width-independent parametrisation, in millionths: the factor on the
# embedding, the factor on every mixer's and FFN's term before it joins the
# stream, the divisor under the final norm's output before the head.
KEY_LINEAR_N_HEADS = 57
KEY_LINEAR_HEAD_DIM = 58
KEY_SPARSE_KERNEL_SIZE = 59
KEY_SPARSE_KERNEL_STRIDE = 60
KEY_SPARSE_BLOCK_SIZE = 61
KEY_SPARSE_TOPK = 62
KEY_SPARSE_WINDOW = 63
KEY_SPARSE_INIT_BLOCKS = 64
KEY_SPARSE_DENSE_LEN = 65
KEY_EMBED_SCALE_E6 = 66
KEY_RESIDUAL_SCALE_E6 = 67
KEY_LOGIT_DIVISOR_E6 = 68
# framework extension: what ``model_type: mimo_v2_flash`` adds to a block of
# full-context and window attention layers, each key written only where it is
# set, so every file without them reads, and is written, as before. A value
# head narrower than a key head rides KEY_V_HEAD_DIM (a layer-kind file wrote
# it as 0 before: the key head's width). The width of a head that rotates
# (its FIRST ``rotary_dim`` numbers; 0: the whole head); the window kind's
# own count of kv heads (0: ``n_kv_heads``) and rotation base (0:
# ``rope_theta``); the factor on every value, in millionths; and whether a
# window layer's softmax has a learned sink a query head (one F32 vector of
# ``n_heads`` a window layer, after its wo: a column of the softmax that takes
# mass and gives no value).
KEY_ROTARY_DIM = 69
KEY_WINDOW_N_KV_HEADS = 70
KEY_WINDOW_ROPE_THETA = 71
KEY_ATTN_VALUE_SCALE_E6 = 72
KEY_WINDOW_SINK = 73
# framework extension: what ``model_type: solar_open2`` adds to a block of
# mixed layers, each key written only where it is set. A gated delta-rule
# layer (``LayerKind.DELTA``, Kimi Delta Attention): its heads and their
# width (keys and values alike; a float32 matrix state ``[head, head]`` a head
# a lane under a decay a key CHANNEL), the taps of the three causal depthwise
# convs its q, k and v pass through, the rank of its two low-rank gates (the
# decay's and the output's), and whether ``b`` spans (0, 2) and not (0, 1).
# ``attn_output_gate``: a full-context layer's output is multiplied by
# ``sigmoid(W_g n)`` before wo, as a block-sparse layer's always is.
KEY_DELTA_N_HEADS = 74
KEY_DELTA_HEAD_DIM = 75
KEY_DELTA_CONV_KERNEL = 76
KEY_DELTA_GATE_RANK = 77
KEY_DELTA_NEG_EIGVAL = 78
KEY_ATTN_OUTPUT_GATE = 79


class ArchType:
    LLAMA = 0xABCD00


class HiddenAct:
    GELU = 0
    SILU = 1


class MoeScore:
    """How a router turns its logits into scores over all experts."""

    SOFTMAX = 0  # Mixtral: softmax, the chosen renormalised
    SIGMOID = 1  # DeepSeek-V3: independent sigmoids


class LayerKind:
    """A layer's mixer (KEY_LAYER_KIND)."""

    ATTENTION = 0  # GQA over the KV cache ("full_attention")
    CONV = 1  # gated short convolution over a window of inputs ("conv")
    SSM = 2  # selective state-space mixer: a running sum a channel ("mamba")
    # GQA over the newest ``sliding_window`` positions, kept in a ring
    # ("sliding_attention"); its weights are stacked with ATTENTION's
    WINDOW = 3
    # linear attention: a decayed float32 matrix state a head a lane, no
    # cache by position ("lightning-attn")
    LINEAR = 4
    # GQA over the blocks of the KV cache that its compressed keys choose,
    # with an output gate ("minicpm4"); its planes are stacked with ATTENTION's
    SPARSE = 5
    # gated delta rule: a float32 matrix state a head a lane under a decay a
    # key channel, q, k and v through short causal convs ("kda")
    DELTA = 6


class NormKind:
    """What a layer's norm divides by (KEY_NORM_KIND)."""

    RMS = 0  # the root mean square
    LAYER = 1  # the standard deviation, the mean subtracted first; no bias


class RopeType:
    LLAMA = 0
    FALCON = 1  # reserved in reference enum; unused
    LLAMA3_1 = 2
    YARN = 3  # frequencies blended over a correction range (ops/rope.py)
    NONE = 4  # no rotation and no other positional term (models/hybrid.py)


@dataclass
class ModelHeader:
    """Parsed .m header (mirror of LlmHeader, src/llm.hpp:39-67)."""

    version: int = 0
    arch_type: int = ArchType.LLAMA
    dim: int = 0
    hidden_dim: int = 0
    n_layers: int = 0
    n_heads: int = 0
    n_kv_heads: int = 0
    n_experts: int = 0
    n_active_experts: int = 0
    vocab_size: int = 0
    seq_len: int = 0
    orig_seq_len: int = 0
    hidden_act: int = HiddenAct.SILU
    rope_theta: float = 10000.0
    weight_type: int = -1
    rope_scaling_factor: float = 1.0
    rope_scaling_low_freq_factor: float = 0.0
    rope_scaling_high_freq_factor: float = 0.0
    rope_scaling_orig_max_seq_len: int = 0
    rope_type: int = RopeType.LLAMA
    qkv_bias: int = 0  # Qwen2-family q/k/v bias vectors (KEY_QKV_BIAS)
    norm_epsilon: float = 1e-5
    # the latent-attention block (KEY_KV_LORA_RANK ...); all zero elsewhere
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    moe_hidden_dim: int = 0
    shared_hidden_dim: int = 0
    n_dense_layers: int = 0
    moe_score_func: int = MoeScore.SOFTMAX
    moe_select_bias: int = 0
    moe_norm_topk: int = 1
    moe_routed_scale: float = 1.0
    # what deepseek_v32 adds (KEY_Q_LORA_RANK ...); unset elsewhere
    q_lora_rank: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    moe_n_group: int = 1
    moe_topk_group: int = 1
    experts_held_first: int = 0
    experts_held_count: int = 0  # 0: every expert
    rope_yarn_mscale_all_dim: float = 0.0
    moe_norm_floor: float = 1e-20
    # a block of mixed layers (KEY_LAYER_KIND ...); empty / zero elsewhere
    layer_kinds: list = field(default_factory=list)  # LayerKind a layer
    conv_kernel: int = 0
    qk_norm: int = 0
    # a selective state-space mixer (KEY_SSM_D_INNER ...); zero elsewhere
    ssm_d_inner: int = 0
    ssm_d_state: int = 0
    ssm_dt_rank: int = 0
    ssm_conv_kernel: int = 0
    ssm_conv_bias: int = 0
    ssm_inner_norms: int = 0
    # what cohere2_moe adds (KEY_HEAD_DIM ...); unset elsewhere
    head_dim: int = 0  # 0: dim // n_heads
    sliding_window: int = 0
    full_attention_nope: int = 0
    norm_kind: int = NormKind.RMS
    parallel_block: int = 0
    shared_expert_scale: float = 1.0
    # what minicpm_sala adds (KEY_LINEAR_N_HEADS ...); unset elsewhere
    linear_n_heads: int = 0
    linear_head_dim: int = 0
    sparse_kernel_size: int = 0
    sparse_kernel_stride: int = 0
    sparse_block_size: int = 0
    sparse_topk: int = 0
    sparse_window: int = 0
    sparse_init_blocks: int = 0
    sparse_dense_len: int = 0
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    # what mimo_v2_flash adds (KEY_ROTARY_DIM ...); unset elsewhere
    rotary_dim: int = 0  # 0: the whole head rotates
    window_n_kv_heads: int = 0  # 0: n_kv_heads
    window_rope_theta: float = 0.0  # 0: rope_theta
    attn_value_scale: float = 1.0
    window_sink: int = 0
    # what solar_open2 adds (KEY_DELTA_N_HEADS ...); unset elsewhere
    delta_n_heads: int = 0
    delta_head_dim: int = 0
    delta_conv_kernel: int = 0
    delta_gate_rank: int = 0
    delta_neg_eigval: int = 0
    attn_output_gate: int = 0
    header_size: int = 0
    file_size: int = 0

    @property
    def head_size(self) -> int:
        return self.head_dim or self.dim // self.n_heads

    @property
    def q_dim(self) -> int:
        """Width of a layer's queries."""
        return self.n_heads * self.head_size

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_size

    @property
    def value_head_size(self) -> int:
        """A GQA value head's width: the key head's unless the file says
        otherwise (``v_head_dim``, which a latent block reads as its own)."""
        return (0 if self.kv_lora_rank else self.v_head_dim) or self.head_size

    @property
    def o_dim(self) -> int:
        """Width of attention's output before wo."""
        return self.n_heads * self.value_head_size

    def kv_heads(self, windowed: bool = False) -> int:
        """The kv heads of a layer kind: the window kind's own where named."""
        return (self.window_n_kv_heads if windowed else 0) or self.n_kv_heads

    def to_kv_pairs(self) -> list[tuple[int, int]]:
        """Serializable (key, int-value) pairs, converter order (writer.py:109-130)."""
        return [
            (KEY_VERSION, self.version),
            (KEY_ARCH_TYPE, self.arch_type),
            (KEY_HIDDEN_ACT, self.hidden_act),
            (KEY_DIM, self.dim),
            (KEY_HIDDEN_DIM, self.hidden_dim),
            (KEY_N_LAYERS, self.n_layers),
            (KEY_N_HEADS, self.n_heads),
            (KEY_N_KV_HEADS, self.n_kv_heads),
            (KEY_WEIGHT_FLOAT_TYPE, self.weight_type),
            (KEY_SEQ_LEN, self.orig_seq_len or self.seq_len),
            (KEY_VOCAB_SIZE, self.vocab_size),
            (KEY_N_EXPERTS, self.n_experts),
            (KEY_N_ACTIVE_EXPERTS, self.n_active_experts),
            (KEY_ROPE_THETA, int(self.rope_theta)),
            (KEY_ROPE_SCALING_FACTOR, int(self.rope_scaling_factor)),
            (KEY_ROPE_SCALING_LOW_FREQ_FACTOR, int(self.rope_scaling_low_freq_factor)),
            (KEY_ROPE_SCALING_HIGH_FREQ_FACTORY, int(self.rope_scaling_high_freq_factor)),
            (KEY_ROPE_SCALING_ORIG_MAX_SEQ_LEN, self.rope_scaling_orig_max_seq_len),
            (KEY_ROPE_TYPE, self.rope_type),
        ] + ([(KEY_QKV_BIAS, self.qkv_bias)] if self.qkv_bias else []) + (
            [(key, getattr(self, name)) for key, name in _LATENT_INT_KEYS.items()]
            + [(KEY_MOE_ROUTED_SCALE_E6, int(round(self.moe_routed_scale * 1e6))),
               (KEY_NORM_EPSILON_E9, int(round(self.norm_epsilon * 1e9)))]
            if self.kv_lora_rank or self.layer_kinds else []
        ) + [
            (key, getattr(self, name)) for key, name in _SPARSE_INT_KEYS.items()
            if getattr(self, name) != _SPARSE_DEFAULTS.get(name, 0)
        ] + [
            (key, int(round(getattr(self, name) * 1e6)))
            for key, name in _YARN_E6_KEYS.items() if getattr(self, name)
        ] + (
            [(KEY_MOE_NORM_FLOOR_EXP10, _floor_to_exp10(self.moe_norm_floor))]
            if self.moe_norm_floor != 1e-20 else []
        ) + (
            [(KEY_LAYER_KIND, kind) for kind in self.layer_kinds]
            + [(KEY_CONV_KERNEL, self.conv_kernel), (KEY_QK_NORM, self.qk_norm)]
            if self.layer_kinds else []
        ) + (
            [(key, getattr(self, name)) for key, name in _SSM_INT_KEYS.items()]
            if self.ssm_d_inner else []
        ) + [
            (key, getattr(self, name)) for key, name in _WINDOW_INT_KEYS.items()
            if getattr(self, name)
        ] + (
            [(KEY_SHARED_EXPERT_SCALE_E6, int(round(self.shared_expert_scale * 1e6)))]
            if self.shared_expert_scale != 1.0 else []
        ) + [
            (key, getattr(self, name)) for key, name in _LINEAR_SPARSE_INT_KEYS.items()
            if getattr(self, name)
        ] + [
            (key, int(round(getattr(self, name) * 1e6)))
            for key, name in _SCALE_E6_KEYS.items() if getattr(self, name) != 1.0
        ] + [
            (key, int(getattr(self, name))) for key, name in _MIXED_HEAD_INT_KEYS.items()
            if getattr(self, name)
        ] + (
            [(KEY_ATTN_VALUE_SCALE_E6, int(round(self.attn_value_scale * 1e6)))]
            if self.attn_value_scale != 1.0 else []
        ) + [
            (key, int(getattr(self, name))) for key, name in _DELTA_INT_KEYS.items()
            if getattr(self, name)
        ]


_LATENT_INT_KEYS = {
    KEY_KV_LORA_RANK: "kv_lora_rank",
    KEY_QK_NOPE_HEAD_DIM: "qk_nope_head_dim",
    KEY_QK_ROPE_HEAD_DIM: "qk_rope_head_dim",
    KEY_V_HEAD_DIM: "v_head_dim",
    KEY_MOE_HIDDEN_DIM: "moe_hidden_dim",
    KEY_SHARED_HIDDEN_DIM: "shared_hidden_dim",
    KEY_N_DENSE_LAYERS: "n_dense_layers",
    KEY_MOE_SCORE_FUNC: "moe_score_func",
    KEY_MOE_SELECT_BIAS: "moe_select_bias",
    KEY_MOE_NORM_TOPK: "moe_norm_topk",
}
_SPARSE_INT_KEYS = {
    KEY_Q_LORA_RANK: "q_lora_rank",
    KEY_INDEX_N_HEADS: "index_n_heads",
    KEY_INDEX_HEAD_DIM: "index_head_dim",
    KEY_INDEX_TOPK: "index_topk",
    KEY_MOE_N_GROUP: "moe_n_group",
    KEY_MOE_TOPK_GROUP: "moe_topk_group",
    KEY_EXPERTS_HELD_FIRST: "experts_held_first",
    KEY_EXPERTS_HELD_COUNT: "experts_held_count",
}
_SPARSE_DEFAULTS = {"moe_n_group": 1, "moe_topk_group": 1}
_SSM_INT_KEYS = {
    KEY_SSM_D_INNER: "ssm_d_inner",
    KEY_SSM_D_STATE: "ssm_d_state",
    KEY_SSM_DT_RANK: "ssm_dt_rank",
    KEY_SSM_CONV_KERNEL: "ssm_conv_kernel",
    KEY_SSM_CONV_BIAS: "ssm_conv_bias",
    KEY_SSM_INNER_NORMS: "ssm_inner_norms",
}
_WINDOW_INT_KEYS = {
    KEY_HEAD_DIM: "head_dim",
    KEY_SLIDING_WINDOW: "sliding_window",
    KEY_FULL_ATTENTION_NOPE: "full_attention_nope",
    KEY_NORM_KIND: "norm_kind",
    KEY_PARALLEL_BLOCK: "parallel_block",
}
_LINEAR_SPARSE_INT_KEYS = {
    KEY_LINEAR_N_HEADS: "linear_n_heads",
    KEY_LINEAR_HEAD_DIM: "linear_head_dim",
    KEY_SPARSE_KERNEL_SIZE: "sparse_kernel_size",
    KEY_SPARSE_KERNEL_STRIDE: "sparse_kernel_stride",
    KEY_SPARSE_BLOCK_SIZE: "sparse_block_size",
    KEY_SPARSE_TOPK: "sparse_topk",
    KEY_SPARSE_WINDOW: "sparse_window",
    KEY_SPARSE_INIT_BLOCKS: "sparse_init_blocks",
    KEY_SPARSE_DENSE_LEN: "sparse_dense_len",
}
_SCALE_E6_KEYS = {
    KEY_EMBED_SCALE_E6: "embed_scale",
    KEY_RESIDUAL_SCALE_E6: "residual_scale",
    KEY_LOGIT_DIVISOR_E6: "logit_divisor",
}
_YARN_E6_KEYS = {KEY_ROPE_YARN_MSCALE_ALL_DIM_E6: "rope_yarn_mscale_all_dim"}
_MIXED_HEAD_INT_KEYS = {
    KEY_ROTARY_DIM: "rotary_dim",
    KEY_WINDOW_N_KV_HEADS: "window_n_kv_heads",
    KEY_WINDOW_ROPE_THETA: "window_rope_theta",
    KEY_WINDOW_SINK: "window_sink",
}
_DELTA_INT_KEYS = {
    KEY_DELTA_N_HEADS: "delta_n_heads",
    KEY_DELTA_HEAD_DIM: "delta_head_dim",
    KEY_DELTA_CONV_KERNEL: "delta_conv_kernel",
    KEY_DELTA_GATE_RANK: "delta_gate_rank",
    KEY_DELTA_NEG_EIGVAL: "delta_neg_eigval",
    KEY_ATTN_OUTPUT_GATE: "attn_output_gate",
}


def _floor_to_exp10(floor: float) -> int:
    """The renormalising floor as the header holds it: 1e-n as n, none as -1."""
    return -1 if floor <= 0.0 else int(round(-math.log10(floor)))


# every header field of the latent-attention block, as models/config.py takes them
LATENT_FIELDS = (
    *_LATENT_INT_KEYS.values(), "moe_routed_scale",
    *_SPARSE_INT_KEYS.values(), *_YARN_E6_KEYS.values(), "moe_norm_floor",
)
# every header field of a selective state-space mixer, as models/config.py takes them
SSM_FIELDS = tuple(_SSM_INT_KEYS.values())
# every header field cohere2_moe added, as models/config.py takes them
WINDOW_FIELDS = (*_WINDOW_INT_KEYS.values(), "shared_expert_scale")
# every header field minicpm_sala added, as models/config.py takes them
LINEAR_SPARSE_FIELDS = (*_LINEAR_SPARSE_INT_KEYS.values(), *_SCALE_E6_KEYS.values())
# every header field mimo_v2_flash added, as models/config.py takes them
MIXED_HEAD_FIELDS = (*_MIXED_HEAD_INT_KEYS.values(), "attn_value_scale")
# every header field solar_open2 added, as models/config.py takes them
DELTA_FIELDS = tuple(_DELTA_INT_KEYS.values())


def check_delta(h) -> None:
    """What a delta-rule layer and a gated full-context layer need of the
    header (or of a config: the fields have the same names)."""
    if LayerKind.DELTA in h.layer_kinds and not (
            h.delta_n_heads > 0 and h.delta_head_dim > 0 and h.delta_conv_kernel >= 2
            and h.delta_gate_rank > 0):
        raise ValueError(
            "a delta-rule layer needs delta_n_heads, delta_head_dim, "
            "delta_conv_kernel >= 2 and delta_gate_rank")
    if LayerKind.DELTA in h.layer_kinds and h.parallel_block:
        raise ValueError("a delta-rule layer belongs to a sequential block")
    if h.attn_output_gate and (
            LayerKind.ATTENTION not in h.layer_kinds or LayerKind.WINDOW in h.layer_kinds
            or LayerKind.SPARSE in h.layer_kinds or h.parallel_block):
        raise ValueError(
            "attn_output_gate gates the full-context layers of a sequential block "
            "without window or block-sparse layers")


def check_mixed_heads(h) -> None:
    """What the per-kind head fields need of the header (or of a config: the
    fields have the same names)."""
    named = h.rotary_dim or h.window_n_kv_heads or h.window_rope_theta or h.window_sink
    if named and not h.layer_kinds:
        raise ValueError("rotary_dim, window_n_kv_heads, window_rope_theta and "
                         "window_sink belong to a block with a layer-kind list")
    if h.rotary_dim and (h.rotary_dim % 2 or h.rotary_dim > h.head_size):
        raise ValueError("rotary_dim is an even width of at most a head")
    if h.window_n_kv_heads and h.n_heads % h.window_n_kv_heads:
        raise ValueError("the query heads are a multiple of window_n_kv_heads")
    if (h.window_n_kv_heads or h.window_rope_theta or h.window_sink) and (
            LayerKind.WINDOW not in h.layer_kinds):
        raise ValueError("window_n_kv_heads, window_rope_theta and window_sink "
                         "need a window layer")


def check_linear_sparse(h) -> None:
    """What a linear-attention or a block-sparse layer needs of the header
    (or of a config: the fields have the same names)."""
    kinds = h.layer_kinds
    if LayerKind.LINEAR in kinds and not (h.linear_n_heads > 0 and h.linear_head_dim > 0):
        raise ValueError("a linear-attention layer needs linear_n_heads and linear_head_dim")
    if LayerKind.SPARSE in kinds:
        size, stride, block = h.sparse_kernel_size, h.sparse_kernel_stride, h.sparse_block_size
        if not (stride > 0 and size >= stride and size % stride == 0 and block > 0
                and block % stride == 0 and h.sparse_topk > 0
                and h.sparse_window % block == 0 and h.sparse_dense_len % block == 0
                and h.seq_len % block == 0):
            raise ValueError(
                "a block-sparse layer needs sparse_kernel_size a multiple of "
                "sparse_kernel_stride, sparse_block_size a multiple of the stride, "
                "sparse_topk >= 1, and the window, dense_len and the context whole blocks")


def write_model_header(f: BinaryIO, header: ModelHeader) -> int:
    """Write magic + headerSize + KV pairs; returns bytes written."""
    data = b"".join(struct.pack("<ii", k, v) for k, v in header.to_kv_pairs())
    head = struct.pack("<i", MODEL_MAGIC)
    head += struct.pack("<i", 8 + len(data))
    f.write(head)
    f.write(data)
    return len(head) + len(data)


def load_model_header(path: str, max_seq_len: int = 0) -> ModelHeader:
    """Parse the .m KV header (src/llm.cpp:26-98). ``max_seq_len`` > 0 clamps
    seq_len the way --max-seq-len does (src/llm.cpp:89-91)."""
    h = ModelHeader()
    with open(path, "rb") as f:
        magic = struct.unpack("<i", f.read(4))[0]
        if magic in (0xABCD00, 0xABCD01):
            raise ValueError("Old model format is not supported")
        if magic != MODEL_MAGIC:
            raise ValueError(f"Unsupported magic number 0x{magic & 0xFFFFFFFF:X}")
        header_size = struct.unpack("<i", f.read(4))[0]
        n_kv = (header_size - 8) // 8
        buf = f.read(n_kv * 8)
        for i in range(n_kv):
            key, value = struct.unpack_from("<ii", buf, i * 8)
            if key == KEY_VERSION:
                h.version = value
            elif key == KEY_ARCH_TYPE:
                h.arch_type = value
            elif key == KEY_DIM:
                h.dim = value
            elif key == KEY_HIDDEN_DIM:
                h.hidden_dim = value
            elif key == KEY_N_LAYERS:
                h.n_layers = value
            elif key == KEY_N_HEADS:
                h.n_heads = value
            elif key == KEY_N_KV_HEADS:
                h.n_kv_heads = value
            elif key == KEY_N_EXPERTS:
                h.n_experts = value
            elif key == KEY_N_ACTIVE_EXPERTS:
                h.n_active_experts = value
            elif key == KEY_VOCAB_SIZE:
                h.vocab_size = value
            elif key == KEY_SEQ_LEN:
                h.seq_len = value
            elif key == KEY_HIDDEN_ACT:
                h.hidden_act = value
            elif key == KEY_ROPE_THETA:
                h.rope_theta = float(value)
            elif key == KEY_WEIGHT_FLOAT_TYPE:
                h.weight_type = value
            elif key == KEY_ROPE_SCALING_FACTOR:
                h.rope_scaling_factor = float(value)
            elif key == KEY_ROPE_SCALING_LOW_FREQ_FACTOR:
                h.rope_scaling_low_freq_factor = float(value)
            elif key == KEY_ROPE_SCALING_HIGH_FREQ_FACTORY:
                h.rope_scaling_high_freq_factor = float(value)
            elif key == KEY_ROPE_SCALING_ORIG_MAX_SEQ_LEN:
                h.rope_scaling_orig_max_seq_len = value
            elif key == KEY_ROPE_TYPE:
                h.rope_type = value
            elif key == KEY_QKV_BIAS:
                h.qkv_bias = value
            elif key in _LATENT_INT_KEYS:
                setattr(h, _LATENT_INT_KEYS[key], value)
            elif key == KEY_MOE_ROUTED_SCALE_E6:
                h.moe_routed_scale = value / 1e6
            elif key == KEY_NORM_EPSILON_E9:
                h.norm_epsilon = value / 1e9
            elif key in _SPARSE_INT_KEYS:
                setattr(h, _SPARSE_INT_KEYS[key], value)
            elif key in _YARN_E6_KEYS:
                setattr(h, _YARN_E6_KEYS[key], value / 1e6)
            elif key == KEY_MOE_NORM_FLOOR_EXP10:
                h.moe_norm_floor = 0.0 if value < 0 else 10.0 ** -value
            elif key == KEY_LAYER_KIND:
                h.layer_kinds.append(value)
            elif key == KEY_CONV_KERNEL:
                h.conv_kernel = value
            elif key == KEY_QK_NORM:
                h.qk_norm = value
            elif key in _SSM_INT_KEYS:
                setattr(h, _SSM_INT_KEYS[key], value)
            elif key in _WINDOW_INT_KEYS:
                setattr(h, _WINDOW_INT_KEYS[key], value)
            elif key == KEY_SHARED_EXPERT_SCALE_E6:
                h.shared_expert_scale = value / 1e6
            elif key in _LINEAR_SPARSE_INT_KEYS:
                setattr(h, _LINEAR_SPARSE_INT_KEYS[key], value)
            elif key in _SCALE_E6_KEYS:
                setattr(h, _SCALE_E6_KEYS[key], value / 1e6)
            elif key == KEY_WINDOW_ROPE_THETA:
                h.window_rope_theta = float(value)
            elif key in _MIXED_HEAD_INT_KEYS:
                setattr(h, _MIXED_HEAD_INT_KEYS[key], value)
            elif key == KEY_ATTN_VALUE_SCALE_E6:
                h.attn_value_scale = value / 1e6
            elif key in _DELTA_INT_KEYS:
                setattr(h, _DELTA_INT_KEYS[key], value)
            else:
                raise ValueError(f"Unsupported header key {key}")
        if h.weight_type == -1:
            raise ValueError("Model does not specify weight type")
        if h.layer_kinds and len(h.layer_kinds) != h.n_layers:
            raise ValueError(
                f"{len(h.layer_kinds)} layer kinds for {h.n_layers} layers")
        if LayerKind.SSM in h.layer_kinds and not (
                h.ssm_d_inner and h.ssm_d_state and h.ssm_dt_rank and h.ssm_conv_kernel >= 2):
            raise ValueError(
                "a state-space layer needs ssm_d_inner, ssm_d_state, ssm_dt_rank "
                "and ssm_conv_kernel >= 2")
        if LayerKind.WINDOW in h.layer_kinds and h.sliding_window < 1:
            raise ValueError("a window layer needs sliding_window >= 1")
        check_linear_sparse(h)
        check_mixed_heads(h)
        check_delta(h)
        h.header_size = header_size
        h.orig_seq_len = h.seq_len
        if max_seq_len > 0 and h.seq_len > max_seq_len:
            h.seq_len = max_seq_len
        h.file_size = os.path.getsize(path)
    return h


@dataclass
class TensorSpec:
    """One tensor in the fixed .m walk order."""

    name: str  # reference op-name it feeds, e.g. "block_matmul_q"
    layer: int
    float_type: int
    shape: tuple[int, int]  # (d_out, d_in) for matmuls; (1, n) for vectors
    offset: int  # byte offset in file
    n_bytes: int
    expert: int = -1  # expert index for MoE tensors, -1 for dense


def model_tensor_specs(h: ModelHeader) -> list[TensorSpec]:
    """The full tensor walk of a .m file (src/llm.cpp:447-483).

    MoE models (n_experts > 0): the FFN block per layer becomes a router
    tensor `block_moe_gate` (F32, [n_experts, dim]) followed by per-expert
    w3, w1, w2 in the reference converter's expert order
    (convert-hf.py:66-73 upstream). The router tensor is a FRAMEWORK
    EXTENSION: the reference converter emits expert weights but no gate, and
    its runtime only executes dense Llama (src/llm.cpp:21-24), so no
    reference-produced MoE file was ever runnable."""
    specs: list[TensorSpec] = []
    offset = h.header_size

    def add(name: str, layer: int, ftype: int, shape: tuple[int, int], expert: int = -1):
        nonlocal offset
        nb = tensor_bytes(ftype, shape[0] * shape[1])
        specs.append(TensorSpec(name, layer, ftype, shape, offset, nb, expert))
        offset += nb

    wt = h.weight_type
    dim, hidden, kv_dim, vocab = h.dim, h.hidden_dim, h.kv_dim, h.vocab_size
    q_dim = h.q_dim
    add("embedding", 0, FloatType.F32, (vocab, dim))
    if h.kv_lora_rank:
        _latent_block_specs(h, add)
    elif h.layer_kinds:
        _pattern_block_specs(h, add)
    for l in range(0 if h.kv_lora_rank or h.layer_kinds else h.n_layers):  # a Llama block's layers
        add("block_matmul_q", l, wt, (q_dim, dim))
        if h.qkv_bias:
            add("block_bias_q", l, FloatType.F32, (1, q_dim))
        add("block_matmul_k", l, wt, (kv_dim, dim))
        if h.qkv_bias:
            add("block_bias_k", l, FloatType.F32, (1, kv_dim))
        add("block_matmul_v", l, wt, (kv_dim, dim))
        if h.qkv_bias:
            add("block_bias_v", l, FloatType.F32, (1, kv_dim))
        add("block_matmul_wo", l, wt, (dim, q_dim))
        if h.n_experts > 0:
            add("block_moe_gate", l, FloatType.F32, (h.n_experts, dim))
            for e in range(h.n_experts):
                add("block_matmul_w3", l, wt, (hidden, dim), e)
                add("block_matmul_w1", l, wt, (hidden, dim), e)
                add("block_matmul_w2", l, wt, (dim, hidden), e)
        else:
            add("block_matmul_w1", l, wt, (hidden, dim))
            add("block_matmul_w2", l, wt, (dim, hidden))
            add("block_matmul_w3", l, wt, (hidden, dim))
        add("block_rms_norm_0", l, FloatType.F32, (1, dim))
        add("block_rms_norm_1", l, FloatType.F32, (1, dim))
    add("final_rms_norm", 0, FloatType.F32, (1, dim))
    add("final_matmul_logits", 0, wt, (vocab, dim))
    return specs


def _latent_block_specs(h: ModelHeader, add) -> None:
    """The layers of a latent-attention file (``model_type: deepseek_v3``), a
    framework extension like the router above. A layer: ``q`` (heads x
    (nope + rope) rows), ``kv_a`` (latent + rope rows, kept whole), the
    latent's norm, ``kv_b`` (heads x (nope + v) rows, kept whole), ``wo``;
    then a dense FFN in the first ``n_dense_layers`` layers, and in the
    others the router (F32), its selection bias (F32, where the header says
    so), every expert's w3, w1, w2, and the shared experts as one gated FFN;
    then the two norms. The rotary part of ``q`` and ``kv_a`` is in the
    interleaved-pair layout as published (``rope_interleave``)."""
    wt, dim = h.weight_type, h.dim
    qk = h.qk_nope_head_dim + h.qk_rope_head_dim
    for l in range(h.n_layers):
        if h.q_lora_rank:  # the query latent: q_a, its norm, q_b
            add("block_matmul_q_a", l, wt, (h.q_lora_rank, dim))
            add("block_rms_norm_q", l, FloatType.F32, (1, h.q_lora_rank))
        add("block_matmul_q", l, wt, (h.n_heads * qk, h.q_lora_rank or dim))
        if h.index_topk:
            # the indexer: queries from the query latent, one key a token
            # with a layer norm's gain and bias, the heads' weights (F32, as
            # the router is: 64 outputs are no Q40 plane)
            add("block_matmul_idx_q", l, wt,
                (h.index_n_heads * h.index_head_dim, h.q_lora_rank or dim))
            add("block_matmul_idx_k", l, wt, (h.index_head_dim, dim))
            add("block_idx_k_norm_gain", l, FloatType.F32, (1, h.index_head_dim))
            add("block_idx_k_norm_bias", l, FloatType.F32, (1, h.index_head_dim))
            add("block_idx_weights", l, FloatType.F32, (h.index_n_heads, dim))
        add("block_matmul_kv_a", l, wt, (h.kv_lora_rank + h.qk_rope_head_dim, dim))
        add("block_rms_norm_kv", l, FloatType.F32, (1, h.kv_lora_rank))
        add("block_matmul_kv_b", l, wt,
            (h.n_heads * (h.qk_nope_head_dim + h.v_head_dim), h.kv_lora_rank))
        add("block_matmul_wo", l, wt, (dim, h.n_heads * h.v_head_dim))
        if l < h.n_dense_layers or h.n_experts == 0:
            add("block_matmul_w1", l, wt, (h.hidden_dim, dim))
            add("block_matmul_w2", l, wt, (dim, h.hidden_dim))
            add("block_matmul_w3", l, wt, (h.hidden_dim, dim))
        else:
            _routed_ffn_specs(h, add, l)
            if h.shared_hidden_dim:
                add("block_matmul_shared_w1", l, wt, (h.shared_hidden_dim, dim))
                add("block_matmul_shared_w2", l, wt, (dim, h.shared_hidden_dim))
                add("block_matmul_shared_w3", l, wt, (h.shared_hidden_dim, dim))
        add("block_rms_norm_0", l, FloatType.F32, (1, dim))
        add("block_rms_norm_1", l, FloatType.F32, (1, dim))


def _routed_ffn_specs(h: ModelHeader, add, l: int) -> None:
    """A routed layer's FFN: the router (F32), its selection bias (F32, where
    the header says so), then every expert's w3, w1, w2."""
    wt, dim = h.weight_type, h.dim
    add("block_moe_gate", l, FloatType.F32, (h.n_experts, dim))
    if h.moe_select_bias:
        add("block_moe_bias", l, FloatType.F32, (1, h.n_experts))
    for e in range(h.experts_held_count or h.n_experts):  # the experts the file holds
        add("block_matmul_w3", l, wt, (h.moe_hidden_dim, dim), e)
        add("block_matmul_w1", l, wt, (h.moe_hidden_dim, dim), e)
        add("block_matmul_w2", l, wt, (dim, h.moe_hidden_dim), e)


def _pattern_block_specs(h: ModelHeader, add) -> None:
    """The layers of a file whose layers differ in their mixer
    (``model_type: lfm2_moe``), a framework extension. A conv layer:
    ``conv_in`` (3 x dim rows: the gate B, the gate C, the input x, in that
    order), the taps (F32, ``[dim, conv_kernel]``), ``conv_out``. An attention
    layer, full-context or window (``model_type: cohere2_moe``): q, k, v (rows
    permuted to the interleaved-pair layout, as a Llama file's; ``n_heads *
    head_size`` rows of q), their per-head norm gains (F32, permuted alike,
    where ``qk_norm``), wo. A state-space layer (``model_type: jamba``): ``ssm_in``
    (2 x ``ssm_d_inner`` rows: the input x, the gate z, in that order), the
    conv's taps (F32, ``[ssm_d_inner, ssm_conv_kernel]``) and its bias (F32,
    where the header says so), ``ssm_x`` (``ssm_dt_rank`` + 2 x
    ``ssm_d_state`` rows: dt, B, C, in that order), the gains of their three
    norms (F32, where the header says so), then what steers the state's
    exponential, all F32: ``dt_proj`` ``[ssm_d_inner, ssm_dt_rank]`` and its
    bias, ``A_log`` ``[ssm_d_inner, ssm_d_state]``, ``D``; then ``ssm_out``.
    Then a dense FFN in the first ``n_dense_layers`` layers (every layer
    where there are no experts) and a routed one in the others, with the
    shared experts as one gated FFN where the header has them; then the two
    norms (one where ``parallel_block``). A block-sparse layer (``model_type:
    minicpm_sala``): an attention layer's tensors with ``attn_gate`` (``n_heads
    * head_size`` rows) before wo. Where the file names them (``model_type:
    mimo_v2_flash``), k and v have the rows of THEIR layer kind's kv heads (k
    ``kv_heads(kind) * head_size``, v ``kv_heads(kind) * value_head_size``), wo
    reads ``n_heads * value_head_size`` columns, only the first ``rotary_dim``
    rows of every q and k head are permuted to pairs, and a window layer's
    ``attn_sink`` (F32, ``n_heads``) follows its wo. A linear-attention layer: ``lin_q``,
    ``lin_k``, ``lin_v`` (``linear_n_heads * linear_head_dim`` rows each, q
    and k permuted as an attention layer's), the per-head gains of q's and
    k's norms (F32, permuted alike), ``lin_gate``, the output norm's gain a
    head channel (F32), ``lin_out``. A full-context layer under
    ``attn_output_gate`` (``model_type: solar_open2``): ``attn_gate`` before
    wo, as a block-sparse layer's. A delta-rule layer, ``D = delta_n_heads *
    delta_head_dim``, ``r = delta_gate_rank``: ``delta_q``, ``delta_k``,
    ``delta_v`` (``D`` rows each, nothing permuted: nothing rotates), the
    three convs' taps in that order (F32, ``[3 D, delta_conv_kernel]``), the
    decay's low-rank gate ``delta_f1`` (``r`` rows) and ``delta_f2`` (F32,
    ``[D, r]``: it steers an exponential, and ``r`` need not be whole Q40
    blocks), ``delta_dt_bias`` (F32, ``D``), ``delta_a_log`` (F32, a number a
    head), ``delta_b`` (F32, ``[delta_n_heads, dim]``: the step a head steers
    the update, and 64 outputs are under the Q40 kernel's 128-wide tile), the
    output gate's ``delta_g1``
    (``r`` rows) and ``delta_g2`` (F32, ``[D, r]``), the output norm's gain a
    head channel (F32), ``delta_out``."""
    wt, dim, kv_dim = h.weight_type, h.dim, h.kv_dim
    e, n, r = h.ssm_d_inner, h.ssm_d_state, h.ssm_dt_rank
    for l, kind in enumerate(h.layer_kinds):
        if kind == LayerKind.CONV:
            add("block_matmul_conv_in", l, wt, (3 * dim, dim))
            add("block_conv_taps", l, FloatType.F32, (dim, h.conv_kernel))
            add("block_matmul_conv_out", l, wt, (dim, dim))
        elif kind == LayerKind.SSM:
            add("block_matmul_ssm_in", l, wt, (2 * e, dim))
            add("block_ssm_conv_taps", l, FloatType.F32, (e, h.ssm_conv_kernel))
            if h.ssm_conv_bias:
                add("block_ssm_conv_bias", l, FloatType.F32, (1, e))
            add("block_matmul_ssm_x", l, wt, (r + 2 * n, e))
            if h.ssm_inner_norms:
                add("block_ssm_dt_norm", l, FloatType.F32, (1, r))
                add("block_ssm_b_norm", l, FloatType.F32, (1, n))
                add("block_ssm_c_norm", l, FloatType.F32, (1, n))
            add("block_ssm_dt_proj", l, FloatType.F32, (e, r))
            add("block_ssm_dt_bias", l, FloatType.F32, (1, e))
            add("block_ssm_a_log", l, FloatType.F32, (e, n))
            add("block_ssm_d", l, FloatType.F32, (1, e))
            add("block_matmul_ssm_out", l, wt, (dim, e))
        elif kind in (LayerKind.ATTENTION, LayerKind.WINDOW):
            n_kv = h.kv_heads(kind == LayerKind.WINDOW)
            add("block_matmul_q", l, wt, (h.q_dim, dim))
            add("block_matmul_k", l, wt, (n_kv * h.head_size, dim))
            add("block_matmul_v", l, wt, (n_kv * h.value_head_size, dim))
            if h.qk_norm:
                add("block_q_norm", l, FloatType.F32, (1, h.head_size))
                add("block_k_norm", l, FloatType.F32, (1, h.head_size))
            if h.attn_output_gate:
                add("block_matmul_attn_gate", l, wt, (h.o_dim, dim))
            add("block_matmul_wo", l, wt, (dim, h.o_dim))
            if h.window_sink and kind == LayerKind.WINDOW:
                add("block_attn_sink", l, FloatType.F32, (1, h.n_heads))
        elif kind == LayerKind.SPARSE:
            add("block_matmul_q", l, wt, (h.q_dim, dim))
            add("block_matmul_k", l, wt, (kv_dim, dim))
            add("block_matmul_v", l, wt, (kv_dim, dim))
            if h.qk_norm:
                add("block_q_norm", l, FloatType.F32, (1, h.head_size))
                add("block_k_norm", l, FloatType.F32, (1, h.head_size))
            add("block_matmul_attn_gate", l, wt, (h.q_dim, dim))
            add("block_matmul_wo", l, wt, (dim, h.q_dim))
        elif kind == LayerKind.LINEAR:
            lin = h.linear_n_heads * h.linear_head_dim
            add("block_matmul_lin_q", l, wt, (lin, dim))
            add("block_matmul_lin_k", l, wt, (lin, dim))
            add("block_matmul_lin_v", l, wt, (lin, dim))
            add("block_lin_q_norm", l, FloatType.F32, (1, h.linear_head_dim))
            add("block_lin_k_norm", l, FloatType.F32, (1, h.linear_head_dim))
            add("block_matmul_lin_gate", l, wt, (lin, dim))
            add("block_lin_o_norm", l, FloatType.F32, (1, h.linear_head_dim))
            add("block_matmul_lin_out", l, wt, (dim, lin))
        elif kind == LayerKind.DELTA:
            dd, rank = h.delta_n_heads * h.delta_head_dim, h.delta_gate_rank
            add("block_matmul_delta_q", l, wt, (dd, dim))
            add("block_matmul_delta_k", l, wt, (dd, dim))
            add("block_matmul_delta_v", l, wt, (dd, dim))
            add("block_delta_conv_taps", l, FloatType.F32, (3 * dd, h.delta_conv_kernel))
            add("block_matmul_delta_f1", l, wt, (rank, dim))
            add("block_delta_f2", l, FloatType.F32, (dd, rank))
            add("block_delta_dt_bias", l, FloatType.F32, (1, dd))
            add("block_delta_a_log", l, FloatType.F32, (1, h.delta_n_heads))
            add("block_delta_b", l, FloatType.F32, (h.delta_n_heads, dim))
            add("block_matmul_delta_g1", l, wt, (rank, dim))
            add("block_delta_g2", l, FloatType.F32, (dd, rank))
            add("block_delta_o_norm", l, FloatType.F32, (1, h.delta_head_dim))
            add("block_matmul_delta_out", l, wt, (dim, dd))
        else:
            raise ValueError(f"layer {l}: unknown layer kind {kind}")
        if l < h.n_dense_layers or h.n_experts == 0:
            add("block_matmul_w1", l, wt, (h.hidden_dim, dim))
            add("block_matmul_w2", l, wt, (dim, h.hidden_dim))
            add("block_matmul_w3", l, wt, (h.hidden_dim, dim))
        else:
            _routed_ffn_specs(h, add, l)
            if h.shared_hidden_dim:
                add("block_matmul_shared_w1", l, wt, (h.shared_hidden_dim, dim))
                add("block_matmul_shared_w2", l, wt, (dim, h.shared_hidden_dim))
                add("block_matmul_shared_w3", l, wt, (h.shared_hidden_dim, dim))
        add("block_rms_norm_0", l, FloatType.F32, (1, dim))
        if not h.parallel_block:  # one norm feeds both halves there
            add("block_rms_norm_1", l, FloatType.F32, (1, dim))


def iter_model_tensors(path: str, header: ModelHeader) -> Iterator[tuple[TensorSpec, np.ndarray]]:
    """Yield (spec, raw bytes as uint8 array) for every tensor, via mmap.

    Verifies byte-exact file consumption like src/llm.cpp:477-479.
    """
    specs = model_tensor_specs(header)
    with open(path, "rb") as f:
        # The mmap is left to the GC: yielded arrays are zero-copy views into
        # it, so an explicit close() would invalidate buffers still in use.
        mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        end = specs[-1].offset + specs[-1].n_bytes
        if end != header.file_size:
            raise ValueError(
                f"Missing bytes in weight file: expected {end}, file has {header.file_size}"
            )
        for spec in specs:
            raw = np.frombuffer(mm, dtype=np.uint8, count=spec.n_bytes, offset=spec.offset)
            yield spec, raw
