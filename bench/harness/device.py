"""The device the run is on, and the refusal to run anywhere else."""
from __future__ import annotations

import jax


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> None:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU found (JAX sees {devices[0].platform})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, found "
                            f"{len(devices)}")


def record(chips: int) -> dict:
    """Platform, kind and count as JAX reports them, and the peak bytes on
    the fullest of the chips used."""
    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    known = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": max(known) if known else None}
