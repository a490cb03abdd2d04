"""The Llama family: dense decoder blocks of the Mistral / Qwen2 kind (GQA,
rotary embedding, gated SiLU or GELU FFN, optional q/k/v biases), served from
Q40. What `harness/cells.py` `load_family` asks of an architecture, over the
benchmark's own generator (`harness/weights.py`) and plain reference
(`harness/reference.py`, which imports nothing of the program)."""

from __future__ import annotations

import numpy as np
from harness.reference import reference_logits  # noqa: F401
from harness.weights import assemble_params, device_weights  # noqa: F401

# activation functions the program's LlamaConfig knows (formats/model_file.py
# HiddenAct): the published ``hidden_act`` string -> that enum's value
_HIDDEN_ACT = {"gelu": 0, "silu": 1}


def program_config(cfg: dict):
    """The program's LlamaConfig from a published ``config.json``'s keys, as
    the configuration file holds them (``max_position_embeddings`` is the
    serving context the file states)."""
    from distributed_llama_multiusers_tpu.models.config import LlamaConfig

    head = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    if head * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise SystemExit(
            "the program derives the head size as hidden_size / heads; "
            f"head_dim {head} x {cfg['num_attention_heads']} heads is not "
            f"hidden_size {cfg['hidden_size']}"
        )
    return LlamaConfig(
        dim=cfg["hidden_size"],
        hidden_dim=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        vocab_size=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"],
        hidden_act=_HIDDEN_ACT[cfg["hidden_act"]],
        rope_theta=float(cfg["rope_theta"]),
        norm_epsilon=float(cfg["rms_norm_eps"]),
        qkv_bias=1 if cfg.get("attention_bias") else 0,
    )


def lane_state_rel_err(engine, lane_x: int, lane_y: int, n: int):
    """Both lanes have absorbed the same n tokens. Largest difference
    between their rows ``[0, n)`` of keys and of values, over the largest
    magnitude there: the family's only per-lane state, all of it kept by
    position. None for a cache that is not the contiguous
    ``[layers, lanes, positions, heads, head size]`` pair."""
    import jax.numpy as jnp

    cache = engine.cache
    if getattr(cache, "table", None) is not None or cache.k.ndim != 5:
        return None
    worst = 0.0
    for plane in (cache.k, cache.v):
        x = np.asarray(plane[:, lane_x, :n].astype(jnp.float32))
        y = np.asarray(plane[:, lane_y, :n].astype(jnp.float32))
        worst = max(worst, float(np.abs(x - y).max() / max(np.abs(y).max(), 1e-30)))
    return worst
