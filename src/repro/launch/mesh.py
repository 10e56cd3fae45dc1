"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state -- jax locks the device count on first init, and
only launch/dryrun.py may set the 512-placeholder-device XLA flag.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh


def abstract_mesh(shape, axes) -> AbstractMesh:
    """Device-free mesh for shape/sharding reasoning (tests, dry-run)."""
    return AbstractMesh(tuple(shape), tuple(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single-pod (data=16, model=16) = 256 chips, or two pods = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh (tests / elastic re-shard / hillclimb variants)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def single_device_mesh() -> Mesh:
    return make_mesh((1,), ("data",))


def stage_submeshes(mesh: Mesh, n_stages: int):
    """Split a mesh's devices into ``n_stages`` submeshes for pipeline
    stages, preserving the trailing axes so partitioned streaming
    composes with tensor sharding.

    The leading axis is divided when it splits evenly (e.g. a
    ``(data=4, model=4)`` mesh into 2 stages of ``(data=2, model=4)``);
    otherwise the flat device list is divided and each group becomes a
    1-D ``("model",)`` submesh (tensor sharding inside the stage).  When
    the device count cannot be split K ways (notably 1-device CPU), all
    stages *share* the full mesh -- returned as K references with
    ``shared=True`` -- so callers can still place per-stage computations
    without special-casing.

    Returns ``(submeshes, shared)``.
    """
    import numpy as np

    devices = np.asarray(mesh.devices)
    lead = devices.shape[0]
    total = devices.size
    if n_stages <= 1:
        return [mesh] * max(n_stages, 1), False
    if lead % n_stages == 0 and lead >= n_stages:
        groups = np.split(devices, n_stages, axis=0)
        return (
            [Mesh(g, mesh.axis_names) for g in groups],
            False,
        )
    if total % n_stages == 0 and total >= n_stages:
        flat = devices.reshape(-1)
        groups = np.split(flat, n_stages)
        return [Mesh(g, ("model",)) for g in groups], False
    return [mesh] * n_stages, True
