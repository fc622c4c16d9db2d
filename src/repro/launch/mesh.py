"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run (launch/dryrun.py) sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import
to obtain placeholder devices for the production meshes.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axes: the sharding rules place arrays
    through constraints, not explicit-sharding types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (data, model); 2x16x16 = 512 chips across
    two pods (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many devices the host actually has."""
    return make_mesh((n_data, n_model), ("data", "model"))


def make_serve_mesh(tp_degree: int):
    """(1, tp_degree) serving mesh over the FIRST tp_degree devices.

    Unlike make_mesh (which spans every device), a serve mesh may be a
    strict subset of the host's devices - a TP replica under the fleet
    router owns tp_degree devices while other replicas own the rest, and
    on CPU CI the forced device count (4) exceeds the tp=2 test meshes.
    Axis names match sharding/rules.py: serving shards only "model" (the
    head axis); "data" stays 1 per replica (the router is the data axis).
    """
    import numpy as np

    devs = jax.devices()
    if tp_degree < 1:
        raise ValueError(f"tp_degree must be >= 1, got {tp_degree}")
    if len(devs) < tp_degree:
        raise ValueError(
            f"tp_degree={tp_degree} needs at least that many devices, have "
            f"{len(devs)} (on CPU, set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp_degree} "
            f"before jax imports)")
    arr = np.array(devs[:tp_degree]).reshape(1, tp_degree)
    return jax.sharding.Mesh(arr, ("data", "model"))
