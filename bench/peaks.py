"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s. JAX reports
the chip as "TPU v5 lite".

The networks run in float32, which the MXU executes in several bf16
passes, so a share of the bf16 peak (``mfu.*``) overstates what a float32
network can reach: it is the yardstick a bf16 path would be held to.
"""
from __future__ import annotations

_V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown chip is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py") from None
