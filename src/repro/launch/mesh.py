"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state. The single-pod mesh is (16, 16) = 256 chips ("data", "model"); the
multi-pod mesh is (2, 16, 16) = 512 chips ("pod", "data", "model") — "pod"
is a pure data-parallel / FSDP axis (gradients all-reduce over it).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """jax.make_mesh with Auto axes: the GSPMD specs annotate with
    with_sharding_constraint, which Explicit axes (the make_mesh default
    in the installed JAX) do not accept."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int | None = None):
    """Small mesh for CI-style dry-run tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count >= data*model*pod)."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


# Published per-chip peaks, keyed by `jax.devices()[0].device_kind` — the
# one table every roofline share in this repo is computed against. Source:
# Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of interconnect over 4 links). A
# device kind missing here is an error, never a default: a share against
# a guessed peak is not a measurement.
CHIP_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s_per_link": 50e9},
}
# the device kind the dry-run meshes above stand for
DRY_RUN_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> dict:
    """Peaks of one chip of this kind (raises KeyError if unknown)."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}"
                       ) from None
