"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Copied from bench.py's `_CHIP_SPECS`. Source: Google Cloud documentation,
"TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB), "TPU v4", "TPU v5p",
"TPU v6e" system architecture pages. A kind that is not here is an error,
never a default.
"""

from __future__ import annotations

# device_kind prefix -> (bf16 FLOP/s, HBM bytes/s, HBM bytes)
PEAKS = {
    "TPU v4": (275e12, 1228e9, 32e9),
    "TPU v5 lite": (197e12, 819e9, 16e9),
    "TPU v5e": (197e12, 819e9, 16e9),
    "TPU v5p": (459e12, 2765e9, 95e9),
    "TPU v6 lite": (918e12, 1640e9, 32e9),
    "TPU v6e": (918e12, 1640e9, 32e9),
}


def chip_peaks(device_kind: str) -> dict:
    for kind in sorted(PEAKS, key=len, reverse=True):
        if device_kind.lower().startswith(kind.lower()):
            flops, bw, mem = PEAKS[kind]
            return {"flops_per_s": flops, "hbm_bytes_per_s": bw, "hbm_bytes": mem}
    raise ValueError(
        f"device_kind {device_kind!r} is not in the peaks table "
        f"({sorted(PEAKS)}): add its published peaks, do not guess"
    )
