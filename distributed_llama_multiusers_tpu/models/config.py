"""Model configuration, derived from the .m header (src/llm.hpp:39-67)."""

from __future__ import annotations

from dataclasses import dataclass

from ..formats.model_file import (
    LATENT_FIELDS,
    SSM_FIELDS,
    WINDOW_FIELDS,
    LINEAR_SPARSE_FIELDS,
    MIXED_HEAD_FIELDS,
    DELTA_FIELDS,
    check_delta,
    check_linear_sparse,
    check_mixed_heads,
    HiddenAct,
    LayerKind,
    ModelHeader,
    MoeScore,
    NormKind,
    RopeType,
)


@dataclass(frozen=True)
class LlamaConfig:
    dim: int
    hidden_dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    vocab_size: int
    seq_len: int
    hidden_act: int = HiddenAct.SILU
    rope_theta: float = 10000.0
    rope_type: int = RopeType.LLAMA
    rope_scaling_factor: float = 1.0
    rope_scaling_low_freq_factor: float = 0.0
    rope_scaling_high_freq_factor: float = 0.0
    rope_scaling_orig_max_seq_len: int = 0
    norm_epsilon: float = 1e-5
    n_experts: int = 0
    n_active_experts: int = 0
    qkv_bias: int = 0  # Qwen2-family: add per-layer q/k/v projection biases
    # Latent attention (the DeepSeek-V2/V3 block; models/deepseek.py):
    # kv_lora_rank > 0 selects it. Every head has its own sizes there (a
    # query/key head is qk_nope + qk_rope wide, a value head v_head_dim), none
    # of them dim // n_heads; the cache keeps one row of kv_lora_rank +
    # qk_rope_head_dim numbers a token a layer, shared by all heads.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # The routed FFN of that block: the first n_dense_layers layers keep a
    # dense gated FFN of hidden_dim; the others route n_active_experts of
    # n_experts experts of moe_hidden_dim each, beside one always-on gated FFN
    # of shared_hidden_dim (the shared experts, merged; 0: none).
    moe_hidden_dim: int = 0
    shared_hidden_dim: int = 0
    n_dense_layers: int = 0
    moe_score_func: int = MoeScore.SOFTMAX  # scores over all experts
    moe_select_bias: int = 0  # a per-expert bias added to choose, not to weigh
    moe_norm_topk: int = 1  # chosen scores renormalised to sum 1
    moe_routed_scale: float = 1.0  # factor on the routed experts' sum
    moe_norm_floor: float = 1e-20  # added to the chosen scores' sum before dividing
    # Expert groups in that router (moe_n_group > 1): the experts lie in
    # moe_n_group equal groups, a group scores the sum of its two largest
    # score + bias, and only the moe_topk_group best groups' experts can be
    # chosen.
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # The share of the routed experts this process holds (count 0: all): ids
    # [first, first + count). The router keeps all n_experts outputs and
    # chooses among them; a row's chosen expert outside the range fetches
    # nothing and adds nothing here (another chip's), the renormalisation is
    # over every chosen expert. The expert stacks hold `count` slabs a layer.
    experts_held_first: int = 0
    experts_held_count: int = 0
    # A query latent (q_lora_rank > 0): q = Wqb rmsnorm(Wqa n), and the
    # indexer's queries are made from the same normed latent.
    q_lora_rank: int = 0
    # Learned sparse attention (index_topk > 0; models/deepseek.py): an
    # indexer of index_n_heads heads of index_head_dim scores every cached
    # position for a query, and attention reads the index_topk best only.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # YaRN (rope_type YARN: the factor, beta_slow, beta_fast and original
    # context are the four rope_scaling_* fields above): mscale_all_dim
    # scales the softmax (the rotation is unscaled: a checkpoint whose mscale
    # differs from it is refused by the converter).
    rope_yarn_mscale_all_dim: float = 0.0
    # A block whose layers differ in their mixer (models/hybrid.py): the kind
    # of every layer as published (formats.model_file.LayerKind; a list, no
    # period is guessed from it), empty for a block of one kind. A conv layer
    # is a gated short convolution of conv_kernel taps, whose per-lane state
    # is its last conv_kernel - 1 inputs; qk_norm: an attention layer norms
    # its queries and keys per head before the rotation. The FFN is the routed
    # block's above (n_dense_layers, moe_*).
    layer_kinds: tuple = ()
    conv_kernel: int = 0
    qk_norm: int = 0
    # A selective state-space mixer among those kinds (LayerKind.SSM;
    # ops/ssm_scan.py): ssm_d_inner channels, each a running sum of
    # ssm_d_state numbers (float32 whatever the cache's type) fed through a
    # causal depthwise conv of ssm_conv_kernel taps (with a bias where
    # ssm_conv_bias), the step size projected from ssm_dt_rank numbers;
    # ssm_inner_norms: dt, B and C are normed before use. Its per-lane state
    # is the sum and the conv's last ssm_conv_kernel - 1 inputs. With
    # rope_type NONE the attention layers rotate nothing.
    ssm_d_inner: int = 0
    ssm_d_state: int = 0
    ssm_dt_rank: int = 0
    ssm_conv_kernel: int = 0
    ssm_conv_bias: int = 0
    ssm_inner_norms: int = 0
    # What ``model_type: cohere2_moe`` adds, each engaged by its own field.
    # head_dim: a head's width where it is not dim // n_heads (0: that
    # quotient), in either block. LayerKind.WINDOW among the kinds: GQA over
    # the newest sliding_window positions (the query's own among them), kept
    # in a ring (models/hybrid.py); full_attention_nope: the full-context
    # layers rotate nothing while the window layers do. norm_kind
    # (formats.model_file.NormKind): every norm of the block subtracts the
    # mean (a gain, no bias). parallel_block: attention and the routed FFN
    # read ONE normed input a layer and are added to the stream together.
    # shared_expert_scale: the factor on the shared experts' output.
    head_dim: int = 0
    sliding_window: int = 0
    full_attention_nope: int = 0
    norm_kind: int = NormKind.RMS
    parallel_block: int = 0
    shared_expert_scale: float = 1.0
    # What ``model_type: minicpm_sala`` adds, each engaged by its own field.
    # LayerKind.LINEAR among the kinds: linear attention, linear_n_heads heads
    # of linear_head_dim whose lane state is a float32 [head, head] matrix a
    # head, decayed by a factor a head every row (models/hybrid.py,
    # ops/linear_attention.py); its queries and keys are normed per head and
    # rotated. LayerKind.SPARSE: GQA whose rows at or past position
    # sparse_dense_len attend sparse_topk blocks of sparse_block_size
    # positions: the first sparse_init_blocks, the blocks of the newest
    # sparse_window positions, and those its compressed keys (a mean over
    # sparse_kernel_size positions every sparse_kernel_stride, cached beside
    # the planes) score highest; with an output gate; such a block has no
    # ATTENTION or WINDOW layer (one stack of attention weights, all gated).
    # embed_scale multiplies the embedding, residual_scale every mixer's and
    # FFN's term before it joins the stream, logit_divisor divides the final
    # norm's output before the head.
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
    # What ``model_type: mimo_v2_flash`` adds to a block of ATTENTION and
    # WINDOW layers, each engaged by its own field. A value head of
    # v_head_dim (above; 0: the key head's width) under GQA: keys and values
    # are cached at widths of their own and wo reads n_heads * v_head_dim.
    # rotary_dim: the FIRST rotary_dim numbers of every query and key head
    # rotate (0: the whole head). window_n_kv_heads / window_rope_theta: the
    # window kind's own kv heads and rotation base (0: the full-context
    # kind's), which give it K/V projections, rings and rotation tables of its
    # own. attn_value_scale multiplies every value. window_sink: a window
    # layer's softmax has one more column a query head, a learned logit that
    # takes mass and gives no value.
    rotary_dim: int = 0
    window_n_kv_heads: int = 0
    window_rope_theta: float = 0.0
    attn_value_scale: float = 1.0
    window_sink: int = 0
    # What ``model_type: solar_open2`` adds, each engaged by its own field.
    # LayerKind.DELTA among the kinds: a gated delta rule (ops/delta_rule.py),
    # delta_n_heads heads of delta_head_dim (keys and values alike) whose lane
    # state is a float32 [head, head] matrix a head under a decay a key
    # CHANNEL; q, k and v pass through causal depthwise convs of
    # delta_conv_kernel taps (a lane keeps their last delta_conv_kernel - 1
    # inputs), the decay and the output gate are low-rank of delta_gate_rank,
    # and b spans (0, 2) where delta_neg_eigval. attn_output_gate: a
    # full-context layer's output is gated by sigmoid(W_g n) before wo.
    delta_n_heads: int = 0
    delta_head_dim: int = 0
    delta_conv_kernel: int = 0
    delta_gate_rank: int = 0
    delta_neg_eigval: int = 0
    attn_output_gate: int = 0

    def __post_init__(self):
        if self.n_experts > 0 and not (1 <= self.n_active_experts <= self.n_experts):
            raise ValueError(
                f"MoE config needs 1 <= n_active_experts <= n_experts, got "
                f"n_active_experts={self.n_active_experts}, n_experts={self.n_experts}"
            )

        if self.kv_lora_rank > 0:
            if min(self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim) <= 0:
                raise ValueError(
                    "latent attention needs qk_nope_head_dim, qk_rope_head_dim "
                    "and v_head_dim"
                )
            if self.n_experts > 0 and not (
                self.moe_hidden_dim > 0 and 0 <= self.n_dense_layers <= self.n_layers
            ):
                raise ValueError(
                    "a routed latent-attention model needs moe_hidden_dim and "
                    "0 <= n_dense_layers <= n_layers"
                )

        if self.index_topk > 0 and not (
            self.latent_attention and self.index_n_heads > 0
            and self.index_head_dim >= self.qk_rope_head_dim
        ):
            raise ValueError(
                "an indexer (index_topk) belongs to latent attention and needs "
                "index_n_heads and index_head_dim >= qk_rope_head_dim")
        if self.moe_n_group > 1 and (
            self.n_experts % self.moe_n_group
            or not 1 <= self.moe_topk_group <= self.moe_n_group
            or self.n_experts // self.moe_n_group < 2
        ):
            raise ValueError(
                "expert groups need n_experts a multiple of moe_n_group, two "
                "experts a group and 1 <= moe_topk_group <= moe_n_group")
        if self.experts_held_count and not (
            0 <= self.experts_held_first
            and self.experts_held_first + self.experts_held_count <= self.n_experts
        ):
            raise ValueError("the held experts lie outside [0, n_experts)")

        if self.layer_kinds:
            if len(self.layer_kinds) != self.n_layers:
                raise ValueError(
                    f"{len(self.layer_kinds)} layer kinds for {self.n_layers} layers")
            if self.latent_attention:
                raise ValueError("a layer pattern's attention layers are GQA, not latent")
            if self.n_conv_layers and self.conv_kernel < 2:
                raise ValueError("a conv layer needs conv_kernel >= 2")
            if self.n_ssm_layers and not (
                self.ssm_d_inner > 0 and self.ssm_d_state > 0 and self.ssm_dt_rank > 0
                and self.ssm_conv_kernel >= 2
            ):
                raise ValueError(
                    "a state-space layer needs ssm_d_inner, ssm_d_state, "
                    "ssm_dt_rank and ssm_conv_kernel >= 2")
            if self.n_experts > 0 and not (
                self.moe_hidden_dim > 0 and 0 <= self.n_dense_layers <= self.n_layers
            ):
                raise ValueError(
                    "a routed layer pattern needs moe_hidden_dim and "
                    "0 <= n_dense_layers <= n_layers"
                )
            if self.n_window_layers and self.sliding_window < 1:
                raise ValueError("a window layer needs sliding_window >= 1")
            check_linear_sparse(self)
            if (self.value_head_size != self.head_size or self.rotary_dim) and (
                self.n_sparse_layers or self.n_linear_layers
            ):
                raise ValueError(
                    "a value head of its own width and a head that rotates in part "
                    "belong to a block of full-context and window layers")
            if self.n_sparse_layers and (
                self.n_sparse_layers != self.n_attention_layers or self.n_window_layers
            ):
                raise ValueError(
                    "a block with block-sparse layers has no other attention layers")
            if (self.n_linear_layers or self.n_sparse_layers) and (
                self.n_experts or self.parallel_block
            ):
                raise ValueError(
                    "a block with linear-attention or block-sparse layers has dense FFNs")
            if self.n_linear_layers and self.linear_head_dim != self.head_size:
                raise ValueError(
                    "a linear-attention layer's heads are as wide as the attention "
                    "layers' (one rotation table)")
        check_mixed_heads(self)
        check_delta(self)
        if self.residual_scale != 1.0 and (
            self.n_linear_layers + self.n_sparse_layers != self.n_layers or not self.layer_kinds
        ):
            raise ValueError(
                "residual_scale belongs to a block of linear-attention and "
                "block-sparse layers")
        if self.parallel_block and not (
            self.layer_kinds and self.n_routed_layers == self.n_layers
            and not self.n_conv_layers and not self.n_ssm_layers
        ):
            raise ValueError(
                "a parallel block is a layer pattern of attention layers "
                "(full-context or window) whose every layer routes (n_dense_layers 0)")

    @property
    def n_conv_layers(self) -> int:
        return sum(k == LayerKind.CONV for k in self.layer_kinds)

    @property
    def n_ssm_layers(self) -> int:
        return sum(k == LayerKind.SSM for k in self.layer_kinds)

    @property
    def n_window_layers(self) -> int:
        return sum(k == LayerKind.WINDOW for k in self.layer_kinds)

    @property
    def n_linear_layers(self) -> int:
        return sum(k == LayerKind.LINEAR for k in self.layer_kinds)

    @property
    def n_sparse_layers(self) -> int:
        return sum(k == LayerKind.SPARSE for k in self.layer_kinds)

    @property
    def n_delta_layers(self) -> int:
        return sum(k == LayerKind.DELTA for k in self.layer_kinds)

    @property
    def delta_dim(self) -> int:
        """Width of a delta-rule layer's queries, keys and values."""
        return self.delta_n_heads * self.delta_head_dim

    @property
    def linear_dim(self) -> int:
        """Width of a linear-attention layer's queries, keys and values."""
        return self.linear_n_heads * self.linear_head_dim

    @property
    def n_attention_layers(self) -> int:
        """Layers that keep every position's keys and values: what the KV
        stack holds (a window layer keeps a ring of its own; a block-sparse
        layer's planes are among them)."""
        return (self.n_layers - self.n_conv_layers - self.n_ssm_layers
                - self.n_window_layers - self.n_linear_layers - self.n_delta_layers)

    @property
    def recurrent_state(self) -> bool:
        """Whether a lane carries state that is overwritten in place, beside
        what the cache keeps by position: nothing that rewinds a lane or
        copies one at another position than its last holds for it. A window
        layer's ring is such a state."""
        return (self.n_conv_layers > 0 or self.n_ssm_layers > 0 or self.n_window_layers > 0
                or self.n_linear_layers > 0 or self.n_delta_layers > 0)

    @property
    def n_routed_layers(self) -> int:
        """Layers whose FFN routes rows to experts read by id (the grouped
        kernel's; 0 for a dense model and for models/llama.py's ``_moe_ffn``)."""
        if self.n_experts == 0 or self.moe_hidden_dim == 0:
            return 0
        return self.n_layers - self.n_dense_layers

    @property
    def experts_held(self) -> tuple:
        """(first id, count) of the routed experts held: every one unless a
        share is named."""
        return (self.experts_held_first, self.experts_held_count or self.n_experts)

    @property
    def sparse_attention(self) -> bool:
        """Whether attention reads only the positions an indexer selects."""
        return self.index_topk > 0

    @property
    def softmax_scale_factor(self) -> float:
        """YaRN's factor on the attention scores' scale (1 elsewhere)."""
        from ..ops.rope import yarn_mscale

        if self.rope_type != RopeType.YARN or not self.rope_yarn_mscale_all_dim:
            return 1.0
        return yarn_mscale(self.rope_scaling_factor, self.rope_yarn_mscale_all_dim) ** 2

    @property
    def latent_attention(self) -> bool:
        """Whether the block is models/deepseek.py's (latent attention, a
        one-row-a-token cache) and not models/llama.py's."""
        return self.kv_lora_rank > 0

    @property
    def head_size(self) -> int:
        return self.head_dim or self.dim // self.n_heads

    @property
    def q_dim(self) -> int:
        """Width of a layer's queries."""
        return self.n_heads * self.head_size

    @property
    def value_head_size(self) -> int:
        """A GQA value head's width: the key head's unless ``v_head_dim``
        says otherwise (a latent block reads that field as its own)."""
        return (0 if self.latent_attention else self.v_head_dim) or self.head_size

    @property
    def o_dim(self) -> int:
        """Width of GQA attention's output before wo."""
        return self.n_heads * self.value_head_size

    @property
    def rope_dim(self) -> int:
        """Width the rotary embedding turns: the whole head of a Llama block
        (its first ``rotary_dim`` numbers where that is named), the
        ``qk_rope_head_dim`` part of a latent-attention head."""
        if self.latent_attention:
            return self.qk_rope_head_dim
        return self.rotary_dim or self.head_size

    @property
    def kv_dim(self) -> int:
        """Width of a cached key row of the full-context kind (and of every
        kind, and of a value row, where no field says otherwise)."""
        return self.n_kv_heads * self.head_size

    def kv_heads(self, windowed: bool = False) -> int:
        """The kv heads of a layer kind: the window kind's own where named."""
        return (self.window_n_kv_heads if windowed else 0) or self.n_kv_heads

    def kv_widths(self, windowed: bool = False) -> tuple:
        """(key row, value row) widths a position of a layer kind's cache."""
        n_kv = self.kv_heads(windowed)
        return n_kv * self.head_size, n_kv * self.value_head_size

    @property
    def split_kv_kinds(self) -> bool:
        """Whether the window kind has K/V projections of its own beside the
        full-context kind's (another count of kv heads): then ``wk`` / ``wv``
        are a stack a kind, each indexed by the count of its kind."""
        return self.window_n_kv_heads not in (0, self.n_kv_heads)

    @staticmethod
    def from_header(h: ModelHeader) -> "LlamaConfig":
        return LlamaConfig(
            dim=h.dim,
            hidden_dim=h.hidden_dim,
            n_layers=h.n_layers,
            n_heads=h.n_heads,
            n_kv_heads=h.n_kv_heads,
            vocab_size=h.vocab_size,
            seq_len=h.seq_len,
            hidden_act=h.hidden_act,
            rope_theta=h.rope_theta,
            rope_type=h.rope_type,
            rope_scaling_factor=h.rope_scaling_factor,
            rope_scaling_low_freq_factor=h.rope_scaling_low_freq_factor,
            rope_scaling_high_freq_factor=h.rope_scaling_high_freq_factor,
            rope_scaling_orig_max_seq_len=h.rope_scaling_orig_max_seq_len,
            norm_epsilon=h.norm_epsilon,
            n_experts=h.n_experts,
            n_active_experts=h.n_active_experts,
            qkv_bias=h.qkv_bias,
            **{name: getattr(h, name) for name in LATENT_FIELDS},
            layer_kinds=tuple(h.layer_kinds),
            conv_kernel=h.conv_kernel,
            qk_norm=h.qk_norm,
            **{name: getattr(h, name) for name in SSM_FIELDS},
            **{name: getattr(h, name) for name in WINDOW_FIELDS},
            **{name: getattr(h, name) for name in LINEAR_SPARSE_FIELDS},
            **{name: getattr(h, name) for name in MIXED_HEAD_FIELDS},
            **{name: getattr(h, name) for name in DELTA_FIELDS},
        )
