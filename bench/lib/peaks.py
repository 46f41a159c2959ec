"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A kind that is not in the table has no peaks: :func:`peaks` raises
rather than borrow another chip's numbers.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {  # TPU v5e
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, "
                  "1,600 Gbit/s ICI",
        "flops_bf16": 197e12,  # FLOP/s
        "hbm_bytes_per_s": 819e9,  # B/s
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/lib/peaks.py "
                       f"with their source") from None
