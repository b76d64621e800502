"""Production mesh construction (multi-pod dry-run requirement).

Defined as functions (never module-level constants) so importing this module never
touches jax device state. The dry-run sets XLA_FLAGS host-device-count=512 before
any jax import; smoke tests and benches see the real single CPU device.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """Mesh whose axes are all Auto: the model code places data with
    sharding constraints, which JAX's default Explicit axes refuse."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(devices: int | None = None, model: int = 1):
    """Small mesh over available devices (for CPU integration tests)."""
    n = devices or len(jax.devices())
    return _auto_mesh((n // model, model), ("data", "model"))


def make_fleet_mesh(devices: int | None = None, *, processes: int | None = None):
    """1-D mesh over all (or the first N) devices for homogeneous fleet axes.

    Sweep fleets (app x policy x seed x config cells of identical shape) are
    embarrassingly parallel, so a single "fleet" axis is the whole layout;
    engine.fleet pads the fleet to a multiple of the mesh size.

    `processes=N` scales the fleet past one process: jax.distributed is
    brought up first (launch.distributed — worker env / cluster detection;
    must happen before jax touches its backends) and the mesh then spans the
    GLOBAL device set of all N connected processes. Every process must build
    the mesh and run the same plan (SPMD); engine.fleet gathers per-group
    results to all processes on retire.
    """
    if processes is not None:
        from repro.launch import distributed

        distributed.ensure_initialized(processes)
    n = devices or len(jax.devices())
    return _auto_mesh((n,), ("fleet",))


def mesh_dp_size(mesh) -> int:
    n = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            n *= mesh.shape[ax]
    return n


def mesh_tp_size(mesh) -> int:
    return mesh.shape.get("model", 1)
