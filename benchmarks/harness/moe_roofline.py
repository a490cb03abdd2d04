"""Bytes a routed FFN's decode step must move, from what was routed. Beside
`harness/roofline.py`, which counts a dense model's Q40 matmuls."""

from __future__ import annotations


def routed_step_bytes(config, slabs: float, assignments: float, act_bytes: int = 2) -> float:
    """HBM bytes the routed experts of ONE decode step must move, whatever
    implements the layer: for each of the ``slabs`` distinct (layer, expert)
    pairs some row chose, the expert's three Q40 matrices (half a byte a
    weight and a float16 scale per 32 inputs per output); for each of the
    ``assignments`` (row, expert) pairs, the activations in and the results
    out of the three products. A slab no row chose is owed nothing."""
    d, h = config.dim, config.moe_hidden_dim
    matrix = d * h // 2 + (d * h // 32) * 2  # w1, w3 and w2 hold d x h weights each
    return slabs * 3 * matrix + assignments * 3 * (d + h) * act_bytes


def routed_matmuls_per_step(config) -> int:
    """Grouped products a decode step makes: three a routed layer."""
    return 3 * (config.n_layers - config.n_dense_layers)
