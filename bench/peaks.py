"""The chip's published peaks, keyed by ``device_kind`` (Google Cloud
documentation, "TPU v5e": per-chip peaks).  A kind missing here is an
error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}; bench/peaks.py has {sorted(PEAKS)}")
    return PEAKS[kind]
