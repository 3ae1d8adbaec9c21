"""Mesh construction and the persistent compilation cache.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  Every mesh is built by :func:`_auto_mesh`
with ``Auto`` axis types: ``constrain`` and the parameter shardings place
arrays through ``with_sharding_constraint`` / ``NamedSharding``, which only
refer to ``Auto`` axes (``jax.make_mesh`` defaults to ``Explicit`` ones).

* :func:`make_mesh` — the launchers' ``(1, N)`` ``("data", "model")`` mesh
  over the first N devices of the host (``--chips N``).
* :func:`make_production_mesh` — the 256/512-chip pod meshes of the
  dry-run, which runs under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=512``.
"""
from __future__ import annotations

import math
import os
from pathlib import Path

import jax
from jax.sharding import AxisType

# <repo>/.jax_cache: a fixed path, since the directory is part of the key
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def _auto_mesh(shape, axes, devices):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_mesh(chips: int = 1):
    """``(1, chips)`` data x model mesh over ``jax.devices()[:chips]``.

    Raises when the host has fewer devices; it never falls back to fewer.
    """
    devices = jax.devices()
    if chips < 1 or len(devices) < chips:
        raise RuntimeError(
            f"--chips {chips} needs {chips} devices, found {len(devices)} "
            f"({devices[0].platform})")
    return _auto_mesh((1, chips), ("data", "model"), devices[:chips])


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = one v5e pod slice; 2x16x16 = two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)}; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "for the dry-run")
    return _auto_mesh(shape, axes, devices[:need])


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads it
    itself); otherwise the cache lives at the fixed ``<repo>/.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
