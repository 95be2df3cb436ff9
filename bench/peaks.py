"""Published per-chip peaks, keyed by ``device.device_kind``.

Source: Google Cloud TPU documentation, one page per generation ("TPU v5e":
197 TFLOP/s bf16, 819 GB/s HBM, 16 GB).  A device that is not in the table
is an error: a share of a made-up peak is not a device metric.
"""

from __future__ import annotations

# (substring of device_kind, bf16 FLOP/s, HBM bytes/s)
PEAKS = (
    ("v6", 918e12, 1640e9),      # TPU v6e (Trillium)
    ("v5p", 459e12, 2765e9),
    ("v5 lite", 197e12, 819e9),  # v5e reports "TPU v5 lite"
    ("v5e", 197e12, 819e9),
    ("v4", 275e12, 1228e9),
)


def peak(device_kind: str) -> dict:
    """``{"flops_per_s", "bytes_per_s"}`` of one chip of ``device_kind``."""
    low = (device_kind or "").lower()
    for key, flops, membw in PEAKS:
        if key in low:
            return {"flops_per_s": flops, "bytes_per_s": membw}
    raise KeyError(f"no published peak for device kind {device_kind!r}")
