"""Generate tiny random-weight `.m` / `.t` files for tests and benchmarks.

These go through the real writers, so every test exercises the same binary
path a converted HF checkpoint would (tensor order: src/llm.cpp:447-483).
"""

from __future__ import annotations

import numpy as np

from .. import native
from ..quants.codec import FloatType, quantize_q40, quantize_q80
from .model_file import (
    ArchType,
    HiddenAct,
    LayerKind,
    ModelHeader,
    MoeScore,
    NormKind,
    RopeType,
    model_tensor_specs,
    write_model_header,
)
from .tokenizer_file import TokenizerData, write_tokenizer_file


def tiny_header(
    dim: int = 64,
    hidden_dim: int = 128,
    n_layers: int = 2,
    n_heads: int = 4,
    n_kv_heads: int = 2,
    vocab_size: int = 128,
    seq_len: int = 64,
    weight_type: int = FloatType.Q40,
    rope_type: int = RopeType.LLAMA,
    rope_theta: float = 10000.0,
    n_experts: int = 0,
    n_active_experts: int = 0,
    qkv_bias: int = 0,
) -> ModelHeader:
    h = ModelHeader(
        qkv_bias=qkv_bias,
        version=0,
        arch_type=ArchType.LLAMA,
        dim=dim,
        hidden_dim=hidden_dim,
        n_layers=n_layers,
        n_heads=n_heads,
        n_kv_heads=n_kv_heads,
        n_experts=n_experts,
        n_active_experts=n_active_experts,
        vocab_size=vocab_size,
        seq_len=seq_len,
        orig_seq_len=seq_len,
        hidden_act=HiddenAct.SILU,
        rope_theta=rope_theta,
        weight_type=weight_type,
        rope_type=rope_type,
    )
    if rope_type == RopeType.LLAMA3_1:
        h.rope_scaling_factor = 8.0
        h.rope_scaling_low_freq_factor = 1.0
        h.rope_scaling_high_freq_factor = 4.0
        h.rope_scaling_orig_max_seq_len = seq_len
    return h


def tiny_pattern_header(
    pattern: str = "ccAcccAc",
    n_dense_layers: int = 2,
    n_experts: int = 8,
    n_active_experts: int = 2,
    moe_hidden_dim: int = 64,
    conv_kernel: int = 3,
    **kw,
) -> ModelHeader:
    """A toy of a block whose layers differ in their mixer (models/hybrid.py):
    ``pattern`` a letter a layer, ``c`` a gated short convolution and ``A``
    GQA attention with normed queries and keys; ``n_dense_layers`` dense FFNs,
    then routed ones (sigmoid scores, a selection bias)."""
    h = tiny_header(n_layers=len(pattern), **kw)
    h.layer_kinds = [LayerKind.CONV if c == "c" else LayerKind.ATTENTION for c in pattern]
    h.conv_kernel, h.qk_norm = conv_kernel, 1
    h.n_experts, h.n_active_experts = n_experts, n_active_experts
    h.moe_hidden_dim, h.n_dense_layers = moe_hidden_dim, n_dense_layers
    h.moe_score_func, h.moe_select_bias = MoeScore.SIGMOID, 1
    return h


def tiny_ssm_header(
    pattern: str = "MMAM",
    ssm_d_state: int = 8,
    ssm_dt_rank: int = 32,
    ssm_conv_kernel: int = 4,
    **kw,
) -> ModelHeader:
    """A toy of a block with selective state-space mixers (models/hybrid.py,
    ops/ssm_scan.py): ``pattern`` a letter a layer, ``M`` a state-space mixer
    of twice the stream's width, ``A`` GQA attention without rotation; every
    FFN dense."""
    kw.setdefault("n_kv_heads", 1)
    h = tiny_header(n_layers=len(pattern), rope_type=RopeType.NONE, **kw)
    h.layer_kinds = [LayerKind.SSM if c == "M" else LayerKind.ATTENTION for c in pattern]
    h.ssm_d_inner, h.ssm_d_state, h.ssm_dt_rank = 2 * h.dim, ssm_d_state, ssm_dt_rank
    h.ssm_conv_kernel, h.ssm_conv_bias, h.ssm_inner_norms = ssm_conv_kernel, 1, 1
    h.norm_epsilon = 1e-6
    return h


def tiny_window_header(
    pattern: str = "WWWF",
    sliding_window: int = 8,
    head_dim: int = 16,
    n_experts: int = 8,
    experts_held: tuple = (0, 0),
    **kw,
) -> ModelHeader:
    """A toy of what ``cohere2_moe`` adds (models/hybrid.py): ``pattern`` a
    letter a layer, ``W`` window attention that rotates, ``F`` full-context
    attention that does not; heads of ``head_dim`` on a narrower stream, a
    mean-subtracting norm, a parallel block, every layer routed (sigmoid
    scores) beside two shared experts averaged."""
    kw = {"dim": 32, "hidden_dim": 64, "n_heads": 4, "n_kv_heads": 2, **kw}
    h = tiny_header(n_layers=len(pattern), rope_theta=50000.0, **kw)
    h.layer_kinds = [LayerKind.WINDOW if c == "W" else LayerKind.ATTENTION for c in pattern]
    h.head_dim, h.sliding_window, h.full_attention_nope = head_dim, sliding_window, 1
    h.norm_kind, h.parallel_block = NormKind.LAYER, 1
    h.n_experts, h.n_active_experts, h.moe_hidden_dim = n_experts, 2, 32
    h.shared_hidden_dim, h.shared_expert_scale = 2 * 32, 0.5
    h.moe_score_func, h.moe_norm_topk = MoeScore.SIGMOID, 1
    h.experts_held_first, h.experts_held_count = experts_held
    return h


def tiny_mixed_head_header(
    pattern: str = "FWWWWFWW",
    sliding_window: int = 8,
    heads: tuple = (8, 2, 4),
    widths: tuple = (24, 16, 8),
    n_experts: int = 16,
    experts_held: tuple = (0, 4),
    **kw,
) -> ModelHeader:
    """A toy of what ``mimo_v2_flash`` adds (models/hybrid.py): ``pattern`` a
    letter a layer, ``F`` full-context attention of ``heads[1]`` kv heads at
    one rotation base, ``W`` window attention of ``heads[2]`` kv heads at
    another, with a sink a query head; ``widths``: a key head, a value head
    and the part of a key head that rotates; every value scaled; RMS norms, a
    sequential block, layer 0's FFN dense and the others routed (sigmoid
    scores, a selection bias, no shared expert), of which a share is held."""
    kw = {"dim": 64, "hidden_dim": 128, "n_heads": heads[0], "n_kv_heads": heads[1],
          "seq_len": 64, **kw}
    h = tiny_header(n_layers=len(pattern), rope_theta=5000000.0, **kw)
    h.layer_kinds = [LayerKind.WINDOW if c == "W" else LayerKind.ATTENTION for c in pattern]
    h.head_dim, h.v_head_dim, h.rotary_dim = widths
    h.sliding_window, h.window_n_kv_heads, h.window_rope_theta = sliding_window, heads[2], 10000.0
    h.attn_value_scale, h.window_sink = 0.707, 1
    h.n_dense_layers = 1
    h.n_experts, h.n_active_experts, h.moe_hidden_dim = n_experts, 4, 32
    h.moe_score_func, h.moe_select_bias, h.moe_norm_topk = MoeScore.SIGMOID, 1, 1
    h.experts_held_first, h.experts_held_count = experts_held
    return h


def tiny_sala_header(
    pattern: str = "SLLLLSSL",
    sizes: tuple = (4, 2, 8, 4, 16, 1, 48),
    **kw,
) -> ModelHeader:
    """A toy of what ``minicpm_sala`` adds (models/hybrid.py): ``pattern`` a
    letter a layer, ``L`` linear attention (a float32 matrix state a head,
    queries and keys normed and rotated), ``S`` block-sparse GQA that does
    not rotate, with an output gate; ``sizes``: the sparse layers' kernel
    size and stride, block size, top-k, window, leading blocks and the
    position from which a row chooses, small enough that the top-k drops
    blocks inside a 128-position context; every FFN dense; the three scalars
    of the width-independent parametrisation."""
    kw = {"dim": 64, "hidden_dim": 128, "n_heads": 4, "n_kv_heads": 2, "seq_len": 128, **kw}
    h = tiny_header(n_layers=len(pattern), **kw)
    h.layer_kinds = [LayerKind.LINEAR if c == "L" else LayerKind.SPARSE for c in pattern]
    h.qk_norm, h.full_attention_nope = 1, 1
    h.linear_n_heads, h.linear_head_dim = h.n_heads, h.dim // h.n_heads
    (h.sparse_kernel_size, h.sparse_kernel_stride, h.sparse_block_size, h.sparse_topk,
     h.sparse_window, h.sparse_init_blocks, h.sparse_dense_len) = sizes
    h.embed_scale, h.residual_scale, h.logit_divisor = 12.0, 1.4 / len(pattern) ** 0.5, 2.0
    h.norm_epsilon = 1e-6
    return h


def tiny_delta_header(
    pattern: str = "GDDDGDDD",
    n_experts: int = 16,
    experts_held: tuple = (0, 4),
    gate_rank: int = 8,
    **kw,
) -> ModelHeader:
    """A toy of what ``solar_open2`` adds (models/hybrid.py): ``pattern`` a
    letter a layer, ``D`` a gated delta-rule layer (a float32 matrix state a
    head under a decay a key channel, q, k and v through convs of 4 taps, low
    rank gates of ``gate_rank``, ``b`` in (0, 2)), ``G`` full-context GQA that
    rotates nothing and gates its output; every FFN routed (sigmoid scores, a
    selection bias, 4 chosen, one shared expert), of which a share is held."""
    kw = {"dim": 64, "hidden_dim": 128, "n_heads": 4, "n_kv_heads": 2, "seq_len": 128, **kw}
    h = tiny_header(n_layers=len(pattern), rope_type=RopeType.NONE, **kw)
    h.layer_kinds = [LayerKind.DELTA if c == "D" else LayerKind.ATTENTION for c in pattern]
    h.head_dim, h.attn_output_gate = h.dim // h.n_heads, 1
    h.delta_n_heads, h.delta_head_dim = h.n_heads, h.dim // h.n_heads
    h.delta_conv_kernel, h.delta_gate_rank, h.delta_neg_eigval = 4, gate_rank, 1
    h.n_experts, h.n_active_experts, h.moe_hidden_dim = n_experts, 4, 32
    h.shared_hidden_dim, h.n_dense_layers = 32, 0
    h.moe_score_func, h.moe_select_bias, h.moe_norm_topk = MoeScore.SIGMOID, 1, 1
    h.moe_norm_floor = 0.0
    h.experts_held_first, h.experts_held_count = experts_held
    return h


def ssm_steering_init(name: str, shape, rng) -> np.ndarray | None:
    """What steers a state-space layer's exponential, as the mixer's own
    published initialisation draws it (it decides how long the state
    remembers): ``A_log = log(1..N)`` a channel, the step's bias such that
    its softplus is log-uniform in ``[1e-3, 1e-1]``, ``D = 1``; and a
    delta-rule layer's: ``A_log = log(uniform(1, 16))`` a head, the same bias a
    channel. None for any other tensor."""
    if name == "block_ssm_a_log":
        return np.broadcast_to(np.log(np.arange(1, shape[1] + 1, dtype=np.float32)), shape)
    if name == "block_delta_a_log":
        return np.log(rng.uniform(1.0, 16.0, shape)).astype(np.float32)
    if name in ("block_ssm_dt_bias", "block_delta_dt_bias"):
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape)).astype(np.float32)
        return dt + np.log(-np.expm1(-dt))  # softplus^-1
    if name == "block_ssm_d":
        return np.ones(shape, np.float32)
    return None


def tiny_sparse_latent_header(
    n_layers: int = 3,
    n_experts: int = 16,
    experts_held: tuple = (0, 8),
    index_topk: int = 16,
    **kw,
) -> ModelHeader:
    """A toy of the latent-attention block with what ``deepseek_v32`` adds
    (models/deepseek.py): a query latent, an indexer that keeps ``index_topk``
    positions, expert groups, a held share of the routed experts, YaRN."""
    h = tiny_header(dim=128, hidden_dim=256, n_layers=n_layers, n_heads=4, n_kv_heads=4,
                    vocab_size=256, seq_len=128, **kw)
    h.kv_lora_rank, h.qk_nope_head_dim, h.qk_rope_head_dim, h.v_head_dim = 64, 32, 16, 32
    h.q_lora_rank = 64
    h.index_n_heads, h.index_head_dim, h.index_topk = 4, 32, index_topk
    h.n_experts, h.n_active_experts, h.moe_hidden_dim = n_experts, 3, 64
    h.shared_hidden_dim, h.n_dense_layers = 64, 1
    h.moe_score_func, h.moe_select_bias, h.moe_routed_scale = MoeScore.SIGMOID, 1, 2.5
    h.moe_n_group, h.moe_topk_group = 4, 2
    h.experts_held_first, h.experts_held_count = experts_held
    h.norm_epsilon, h.moe_norm_floor = 1e-6, 0.0
    h.rope_type = RopeType.YARN
    h.rope_scaling_factor, h.rope_scaling_orig_max_seq_len = 4.0, 32
    h.rope_scaling_low_freq_factor, h.rope_scaling_high_freq_factor = 1.0, 32.0
    h.rope_yarn_mscale_all_dim = 1.0
    return h


def _write_tensor(f, x: np.ndarray, float_type: int) -> None:
    x = np.ascontiguousarray(x, dtype=np.float32).reshape(-1)
    if float_type == FloatType.F32:
        f.write(x.astype("<f4").tobytes())
    elif float_type == FloatType.F16:
        f.write(x.astype("<f2").tobytes())
    elif float_type == FloatType.Q40:
        # threaded C++ encoder when built (bit-identical, ~30x faster: a
        # 1B-parameter model writes in a minute), the numpy oracle otherwise
        blocks = native.quantize_q40(x)
        f.write((quantize_q40(x) if blocks is None else blocks).tobytes())
    elif float_type == FloatType.Q80:
        f.write(quantize_q80(x, mode="converter").tobytes())
    else:
        raise ValueError(float_type)


def write_synthetic_model(path: str, header: ModelHeader, seed: int = 0, scale: float = 0.02) -> None:
    """Random-normal weights, written through the real quantizers."""
    rng = np.random.default_rng(seed)
    wt = header.weight_type
    dim, hidden, kv_dim, vocab = header.dim, header.hidden_dim, header.kv_dim, header.vocab_size

    def rand(shape):
        return rng.standard_normal(shape, dtype=np.float32) * scale

    if header.layer_kinds or header.kv_lora_rank or header.head_dim:
        # a layer pattern's or a latent block's file, or a Llama block's
        # whose heads are not dim // n_heads wide: the walk itself says what
        # to write
        with open(path, "wb") as f:
            header.header_size = write_model_header(f, header)
            for spec in model_tensor_specs(header):
                # a norm's gains sit about one
                gain = "norm" in spec.name and "bias" not in spec.name
                x = ssm_steering_init(spec.name, spec.shape, rng)
                _write_tensor(f, gain + rand(spec.shape) if x is None else x, spec.float_type)
        return

    with open(path, "wb") as f:
        write_model_header(f, header)
        _write_tensor(f, rand((vocab, dim)), FloatType.F32)
        for _ in range(header.n_layers):
            _write_tensor(f, rand((dim, dim)), wt)  # q
            if header.qkv_bias:
                _write_tensor(f, rand((dim,)), FloatType.F32)  # bq
            _write_tensor(f, rand((kv_dim, dim)), wt)  # k
            if header.qkv_bias:
                _write_tensor(f, rand((kv_dim,)), FloatType.F32)  # bk
            _write_tensor(f, rand((kv_dim, dim)), wt)  # v
            if header.qkv_bias:
                _write_tensor(f, rand((kv_dim,)), FloatType.F32)  # bv
            _write_tensor(f, rand((dim, dim)), wt)  # wo
            if header.n_experts > 0:
                _write_tensor(f, rand((header.n_experts, dim)), FloatType.F32)  # router
                for _ in range(header.n_experts):
                    _write_tensor(f, rand((hidden, dim)), wt)  # w3 up
                    _write_tensor(f, rand((hidden, dim)), wt)  # w1 gate
                    _write_tensor(f, rand((dim, hidden)), wt)  # w2 down
            else:
                _write_tensor(f, rand((hidden, dim)), wt)  # w1 gate
                _write_tensor(f, rand((dim, hidden)), wt)  # w2 down
                _write_tensor(f, rand((hidden, dim)), wt)  # w3 up
            _write_tensor(f, 1.0 + rand((dim,)), FloatType.F32)  # rms att
            _write_tensor(f, 1.0 + rand((dim,)), FloatType.F32)  # rms ffn
        _write_tensor(f, 1.0 + rand((dim,)), FloatType.F32)  # final rms
        _write_tensor(f, rand((vocab, dim)), wt)  # wcls


LLAMA3_CHAT_TEMPLATE = (
    "{% for message in messages %}<|start_header_id|>{{ message['role'] }}"
    "<|end_header_id|>\n\n{{ message['content'] }}<|eot_id|>{% endfor %}"
)


def write_synthetic_tokenizer(path: str, vocab_size: int = 128) -> TokenizerData:
    """A byte-level tokenizer: regular vocab = single bytes + a few merges,
    then BOS/EOS/header specials (regular/special split at bos_id, matching
    the reference's assumption, src/tokenizer.cpp:137-139)."""
    vocab: list[bytes] = []
    scores: list[float] = []
    # keep it small: printable ASCII + whitespace (real tokenizers carry all
    # 256 byte-fallback tokens; chat templates need \n)
    base = [b"\t", b"\n", b"\r"] + [bytes([b]) for b in range(32, 127)]
    merges = [b"he", b"ll", b"hell", b"hello", b"wo", b"rl", b"worl", b"world", b"lo "]
    for t in base:
        vocab.append(t)
        scores.append(0.0)
    for i, t in enumerate(merges):
        vocab.append(t)
        scores.append(float(i + 1))
    bos_id = len(vocab)
    vocab.append(b"<|begin_of_text|>")
    scores.append(0.0)
    eot_id = len(vocab)
    vocab.append(b"<|eot_id|>")
    scores.append(0.0)
    vocab.append(b"<|start_header_id|>")
    scores.append(0.0)
    vocab.append(b"<|end_header_id|>")
    scores.append(0.0)
    while len(vocab) < vocab_size:
        vocab.append(b"<|reserved_%d|>" % len(vocab))
        scores.append(0.0)
    data = TokenizerData(
        vocab=vocab[:vocab_size],
        scores=scores[:vocab_size],
        bos_id=bos_id,
        eos_token_ids=[eot_id],
        chat_template=LLAMA3_CHAT_TEMPLATE,
    )
    with open(path, "wb") as f:
        write_tokenizer_file(f, data)
    return data
