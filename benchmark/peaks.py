"""The table of device peaks, and the bytes one engine step has to move.

Peaks are per chip and keyed by ``jax.Device.device_kind``; a device that is
not in the table is an error, never a default.
"""

from __future__ import annotations

import math

#: Google Cloud documentation, "TPU v5e" (system architecture): per chip
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
#: of chip-to-chip interconnect.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add a row "
                       f"with its source to benchmark/peaks.py")
    return PEAKS[device_kind]


def leaf_bytes_per_device(leaf) -> int:
    """Bytes of one array (or ShapeDtypeStruct) on ONE device: the shard's
    shape where the leaf is sharded, else the whole."""
    shape = leaf.shape
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None:
        shape = sharding.shard_shape(shape)
    return math.prod(shape) * leaf.dtype.itemsize


def step_bytes_per_device(arguments, outputs) -> int:
    """Least bytes one step moves through a device's memory: every argument
    read once and every output written once (the kernel updates the whole
    batched state each step; nothing is donated at depth 0).  The step does
    little arithmetic per byte, so memory bounds it, not compute."""
    import jax

    return sum(leaf_bytes_per_device(x)
               for x in jax.tree.leaves((arguments, outputs)))


def least_step_seconds(step_bytes: int, device_kind: str) -> float:
    return step_bytes / peaks_of(device_kind)["hbm_bytes_per_s"]
