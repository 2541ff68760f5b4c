"""Published peaks of one chip, keyed by jax's ``device_kind``.

One table, with its source.  A device that is not in it is an error, never
a default: a share of a peak that was guessed is not a measurement.
"""

from __future__ import annotations

from typing import Dict, NamedTuple


class Peak(NamedTuple):
    flops_per_s: float   # dense bf16 matrix FLOP/s
    bytes_per_s: float   # HBM bytes/s
    hbm_bytes: int
    source: str


PEAKS: Dict[str, Peak] = {
    "TPU v5 lite": Peak(197e12, 819e9, 16 * 2**30,
                        'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                        'bf16, 16 GB HBM2e at 819 GB/s per chip'),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}.  Add a row with its source, do not guess."
        ) from None
