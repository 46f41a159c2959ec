"""Production mesh construction.

TPU v5e targets: a pod = 16 x 16 = 256 chips; the multi-pod dry-run uses
2 pods = 512 chips with a leading "pod" axis (pods talk over DCN — which is
exactly why the JJPF farm layer syncs across "pod" rarely or never, while
"data"/"model" live on intra-pod ICI).

Functions, not module constants: importing this module must never touch JAX
device state (jax locks the device count on first backend init).
"""

from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes=None):
    """Arbitrary (test-sized) meshes, e.g. (2, 2, 2) on 8 host devices."""
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):]
    return _mk(tuple(shape), tuple(axes))


#: Per-chip peaks, keyed by ``jax.Device.device_kind``.  A kind missing
#: here has no peaks: :func:`peaks` raises rather than borrow another
#: chip's numbers.
PEAKS = {
    "TPU v5 lite": {  # TPU v5e
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI "
                  "(4 links of 50 GB/s)",
        "peak_flops_bf16": 197e12,  # FLOP/s
        "hbm_bandwidth": 819e9,  # B/s
        "hbm_bytes": 16 * 2**30,
        "ici_link_bandwidth": 50e9,  # B/s per link
    },
}

#: The chip the dry-run grid and its roofline model (``make_production_mesh``).
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS with their "
                       f"source") from None
