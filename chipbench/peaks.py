"""Published per-chip peaks, keyed by ``jax.devices()[i].device_kind``.

Copied from ``src/repro/launch/roofline.py`` (``PEAKS`` / ``peaks()``) so
that a change to the program cannot move the benchmark's yardstick.

Source for "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e"
system architecture page -- 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
1,600 Gbit/s chip-to-chip interconnect (4 links of 50 GB/s).
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,      # FLOP/s
        "hbm_bw": 819e9,                # bytes/s
        "ici_link_bw": 50e9,            # bytes/s per link
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak rates of one chip of ``device_kind``; an unknown kind raises
    (there is no default chip)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
