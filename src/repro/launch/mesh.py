"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; callers control when devices are materialized.

Target hardware (roofline constants in benchmarks/roofline.py):
  TPU v5e, 197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s/link ICI.
  Single pod: 16x16 = 256 chips, axes (data, model).
  Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the EL programs place their
    data plane with ``lax.with_sharding_constraint``, which only accepts
    Auto axes (``jax.make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    import os
    if os.environ.get("REPRO_DEBUG_MESH"):        # tiny-mesh CI/debug mode
        d = int(os.environ["REPRO_DEBUG_MESH"])
        shape = (2, d, d) if multi_pod else (d, d)
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU multi-device tests (subprocesses set
    ``--xla_force_host_platform_device_count`` accordingly)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_debug_mesh_for(n_devices: int):
    """The debug mesh over a forced host-device fleet: shape
    ``(n_devices//2, 2)``, so 4 devices give a 2x2 (data, model) mesh
    and 8 a 4-wide ``data`` axis — the one sizing rule every launcher
    (``repro.launch.train``/``sweep``, ``scripts/bench_el.py``) shares."""
    d = max(n_devices // 2, 1)
    return make_debug_mesh(d, n_devices // d)
