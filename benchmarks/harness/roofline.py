"""Bytes and operations a kernel call needs, computed from its shapes."""

from __future__ import annotations


def q40_matmul_bytes(m: int, d_in: int, d_out: int,
                     act_bytes: int = 2, out_bytes: int = 2) -> int:
    """HBM bytes one ``[m, d_in] @ Q40[d_in, d_out]`` call must move: the
    nibble plane (half a byte a weight), the float16 block scales (one per 32
    inputs per output), the activations in and the result out."""
    planes = d_in // 2 * d_out
    scales = d_in // 32 * d_out * 2
    return planes + scales + m * d_in * act_bytes + m * d_out * out_bytes


def q40_matmul_flops(m: int, d_in: int, d_out: int) -> int:
    return 2 * m * d_in * d_out


def decode_step_q40_calls(config, padded_vocab: int) -> list[tuple[int, int, int]]:
    """(d_in, d_out, calls) of every Q40 matmul in one decode step of a dense
    model: seven a layer and the output head."""
    d, h, L = config.dim, config.hidden_dim, config.n_layers
    kv = config.n_kv_heads * config.head_size
    return [
        (d, d, 2 * L),       # wq, wo
        (d, kv, 2 * L),      # wk, wv
        (d, h, 2 * L),       # w1, w3
        (h, d, L),           # w2
        (d, padded_vocab, 1),  # wcls
    ]


def decode_step_q40_bytes(config, padded_vocab: int, lanes: int) -> int:
    return sum(
        n * q40_matmul_bytes(lanes, d_in, d_out)
        for d_in, d_out, n in decode_step_q40_calls(config, padded_vocab)
    )
