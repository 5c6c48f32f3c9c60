"""Production mesh construction (function, not module-level constant, so
importing this module never touches jax device state).

Every mesh here has ``AxisType.Auto`` axes: the engine slices and pads its
sharded cohort outputs with plain indexing and leaves the partitioning to
XLA, which ``jax.make_mesh``'s default ``Explicit`` axes refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh (tests use small host-device meshes)."""
    return _auto_mesh(shape, axes)


def make_cohort_mesh(mesh_shape: tuple[int, ...] | None = None,
                     axis: str = "clients"):
    """1-D mesh over the federated cohort axis (fl.executors sharded backend).

    ``mesh_shape=None`` takes every visible device; an explicit shape must
    be 1-D (the cohort axis is the only thing sharded) and fit the visible
    device count — ``EngineConfig.validate`` checks both up front so bad
    shapes fail at Scenario registration, not mid-run.
    """
    if mesh_shape is None:
        mesh_shape = (len(jax.devices()),)
    if len(mesh_shape) != 1:
        raise ValueError(
            f"cohort mesh is 1-D (the client axis); got shape {mesh_shape!r}")
    return _auto_mesh(mesh_shape, (axis,))


def make_multihost_cohort_mesh(axis: str = "clients"):
    """1-D cohort mesh spanning every device of every process.

    After ``jax.distributed.initialize`` (``repro.dist.DistContext``),
    ``jax.devices()`` is the GLOBAL device list, so the full-device cohort
    mesh covers all hosts; this wrapper additionally asserts the mesh
    really spans the job (a worker that silently failed to join the
    coordination service would otherwise shard over its local devices only
    and diverge from the other processes).  Single-process jobs degrade to
    exactly :func:`make_cohort_mesh`'s all-local-devices mesh.
    """
    mesh = make_cohort_mesh(None, axis=axis)
    procs = {d.process_index for d in mesh.devices.flat}
    if len(procs) != jax.process_count():
        raise RuntimeError(
            f"multi-host cohort mesh covers processes {sorted(procs)} but "
            f"jax reports {jax.process_count()} processes — the "
            "coordination service is not fully joined")
    return mesh
