"""Bytes and operations a decode step's learned sparse attention must move and
make, from what was scored and chosen (`EngineStats` `indexer_rows_scored`,
`sparse_rows_selected`: counted on the device, summed over layers). Beside
`harness/roofline.py` and `harness/moe_roofline.py`; whatever implements the
layer owes this work and no less."""

from __future__ import annotations

from harness.roofline import q40_matmul_bytes, q40_matmul_flops


def indexer_step_work(config, rows_scored: float, lanes: int, cache_bytes: int = 2):
    """(HBM bytes, operations) of ONE decode step's indexers over all layers:
    a layer projects its queries from the query latent (Q40), its key from the
    stream (Q40) and its heads' weights (float32 matrix), appends one key a
    lane, and scores every key a live lane holds: ``rows_scored`` rows of
    ``index_head_dim`` numbers read once, each against ``index_n_heads``
    queries (a multiply-add a number a head, then a relu and a weighted add)."""
    c = config
    L, hi, di = c.n_layers, c.index_n_heads, c.index_head_dim
    q_in = c.q_lora_rank or c.dim
    proj_bytes = L * (q40_matmul_bytes(lanes, q_in, hi * di) + q40_matmul_bytes(lanes, c.dim, di)
                      + c.dim * hi * 4)
    proj_flops = L * (q40_matmul_flops(lanes, q_in, hi * di) + q40_matmul_flops(lanes, c.dim, di)
                      + 2 * lanes * c.dim * hi)
    return (proj_bytes + rows_scored * di * cache_bytes,
            proj_flops + rows_scored * hi * (2 * di + 2))


def sparse_attention_step_work(config, rows_selected: float, lanes: int, cache_bytes: int = 2,
                               act_bytes: int = 2):
    """(HBM bytes, operations) of ONE decode step's attention over the chosen
    rows in the absorbed form, all layers: a chosen row is its latent and its
    rotated key part as the cache holds them (the rope leaf at its padded
    width), read once; every head scores it (``rank + rope`` multiply-adds)
    and adds it to its value (``rank``). A lane a layer also absorbs its query
    through ``Wuk`` and expands its result through ``Wuv`` (the dequantized
    halves of ``kv_b``, read once a layer)."""
    c = config
    L, h, rank, rope = c.n_layers, c.n_heads, c.kv_lora_rank, c.qk_rope_head_dim
    rope_leaf = -(-rope // 128) * 128
    kv_b = h * rank * (c.qk_nope_head_dim + c.v_head_dim)
    row_bytes = (rank + rope_leaf) * cache_bytes
    return (rows_selected * row_bytes + L * kv_b * act_bytes,
            rows_selected * h * 2 * (2 * rank + rope) + L * lanes * 2 * kv_b)


def roofline_share(ctx, scope: str, work) -> float | None:
    """Percent: the least time the chip needs for ``work``'s (bytes,
    operations) a step, whichever of its two peaks bounds, over the device
    time under ``scope`` a decode step (``decode_scope_ms``). None where the
    program has no such scope or counters (a program from before them)."""
    steps = ctx.counters.get("decode_steps")
    ms = decode_scope_ms(ctx, scope)
    if ctx.peaks is None or not steps or not ms:
        return None
    nbytes, flops = work(steps)
    need_s = max(nbytes / ctx.peaks["hbm_bytes_per_s"], flops / ctx.peaks["flops_per_s"])
    return 100.0 * need_s / (ms / 1e3)


def decode_scope_ms(ctx, scope: str) -> float | None:
    """Device time under ``scope`` in the decode batch of ONE step, in ms: the
    median over the stretch's pipelined decode steps where it holds any, else
    over the decode halves of its fused steps (the same batch less the
    admitting lane, which stands parked: in a cell whose window is mostly
    admissions a three-second stretch may hold no other step). None where the
    program has no such scope."""
    from harness import stepclass

    classes = (stepclass.for_ctx(ctx) or {}).get("classes", {})
    fused = sorted((d for cls, d in classes.items() if cls.startswith(stepclass.FUSED)),
                   key=lambda d: -d["executions"])
    for d in [classes.get("dlstep.decode")] + fused:
        if d and (stepclass.DECODE, scope) in d["pair_ms"]:
            return d["pair_ms"][stepclass.DECODE, scope]
    return None
