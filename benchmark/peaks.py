"""Published peaks of the devices the benchmark runs on, keyed by JAX's
``device_kind``.  A device missing here is an error, never a default."""
from __future__ import annotations

#: peak HBM bandwidth, bytes/s.  Google Cloud documentation, "TPU v5e":
#: 16 GB of HBM at 819 GB/s per chip.
HBM_BPS = {
    "TPU v5 lite": 819e9,
}


def hbm_bps(device_kind: str) -> float:
    try:
        return HBM_BPS[device_kind]
    except KeyError:
        raise KeyError(f"no published HBM peak for device_kind "
                       f"{device_kind!r}: add it to benchmark/peaks.py "
                       "with its source") from None
