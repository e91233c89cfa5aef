"""Published per-chip peaks, keyed by `jax.devices()[0].device_kind`.

Copied from `src/repro/launch/mesh.py::CHIP_PEAKS`. Source: Google Cloud
documentation, "TPU v5e": per chip 197 TFLOP/s in bfloat16, 16 GB of HBM
at 819 GB/s, 1,600 Gbit/s of interconnect over 4 links. A kind missing
here is an error: a share of a guessed peak is not a measurement."""
from __future__ import annotations

CHIP_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s_per_link": 50e9},
}


def chip_peaks(device_kind: str) -> dict:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(CHIP_PEAKS)}") from None
