"""Bytes and operations that attention with heads that differ by layer kind
MUST move and make, whatever implements it, counted from the configuration
FILE's published keys (``ctx.cfg``: ``hybrid_layer_pattern``, the two kinds'
kv head counts, ``head_dim``, ``v_head_dim``) and from what the program
counted (`EngineStats` `attn_full_rows_needed`, `attn_window_rows_needed`,
`prefill_attn_blocks_causal`, the routed counts: host counts). Beside
`harness/window_roofline.py`, whose `kv_row_bytes` takes one kv width for
every layer.

**A cached position.** A full-context layer keeps ``num_key_value_heads`` keys
of ``head_dim`` and values of ``v_head_dim`` a position, a window layer
``swa_num_key_value_heads`` of ``swa_head_dim`` / ``swa_v_head_dim``: 4 x (192
+ 128) x 2 = 2560 and 8 x 320 x 2 = 5120 bytes in bfloat16, whatever width the
program pads them to.

**The reads at decode width.** A live lane at ``pos`` needs ``pos + 1`` rows
of every full-context layer and ``min(pos + 1, W)`` of every window layer: the
scheduler's own arithmetic over the decode steps, in rows of one layer. No
operation is counted against the matrix units: least-bytes bounds.

**Prefill attention.** A counted (query row, key block) pair is ``BLOCK`` keys
against all query heads, once for the scores (``head_dim`` multiply-adds) and
once for the values (``v_head_dim``). The program counts the pairs of the
layers whose attention runs a key block at a time: here the full-context
layers (a window layer's ring of three blocks takes dense scores and is not
counted), so the time they are held against is the time whose deepest scope is
`dl.attention`, which a window layer's operations, one scope deeper, are not.
"""

from __future__ import annotations

from types import SimpleNamespace

BLOCK = 256  # ops/pallas_attention.py BLOCK_ROWS and ops/blocked_attention.py BLOCK_KEYS
WINDOW, FULL = 1, 0  # [hybrid_layer_pattern]'s entries


def n_layers_of(cfg: dict, kind: int) -> int:
    return sum(k == kind for k in cfg["hybrid_layer_pattern"])


def kv_row_bytes(cfg: dict, kind: int, kv_bytes: int = 2) -> int:
    """A position's keys and values in one layer of ``kind``."""
    if kind == WINDOW:
        return cfg["swa_num_key_value_heads"] * (cfg["swa_head_dim"] + cfg["swa_v_head_dim"]) * kv_bytes
    return cfg["num_key_value_heads"] * (cfg["head_dim"] + cfg["v_head_dim"]) * kv_bytes


def decode_read_bytes(cfg: dict, kind: int, rows_needed: float, kv_bytes: int = 2) -> float:
    """HBM bytes a decode step's layers of ``kind`` must read: ``rows_needed``
    rows of ONE such layer, every layer of the kind."""
    return rows_needed * n_layers_of(cfg, kind) * kv_row_bytes(cfg, kind, kv_bytes)


def prefill_attention_flops(cfg: dict, causal_pairs: float) -> float:
    """Floating-point operations of ``causal_pairs`` (query row, key block)
    pairs: scores over a key's width and values over a value's, a multiply
    and an add each."""
    return causal_pairs * BLOCK * cfg["num_attention_heads"] * (
        cfg["head_dim"] + cfg["v_head_dim"]) * 2


def routed_shape(cfg: dict) -> SimpleNamespace:
    """What `moe_roofline.routed_step_bytes` reads of a configuration."""
    return SimpleNamespace(dim=cfg["hidden_size"], moe_hidden_dim=cfg["moe_intermediate_size"])


def routed_steps_counted(cfg: dict, slabs_whole: float) -> float:
    """Decode steps behind `moe_slabs_whole`: routed layers x EVERY expert the
    router scores a step."""
    experts = cfg.get("deployment", {}).get("n_routed_experts_published", cfg["n_routed_experts"])
    return slabs_whole / (sum(cfg["moe_layer_freq"]) * experts)


def kv_bytes_of(ctx) -> int:
    return {"bfloat16": 2, "float32": 4}.get(ctx.kv_dtype, 2)
