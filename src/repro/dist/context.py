"""Multi-process runtime context: ``jax.distributed`` init + cohort topology.

One :class:`DistContext` per process describes the process's place in a
``jax.distributed`` job: coordination-service endpoint, process index/count,
the global cohort mesh, and the host-collective helpers the cross-host
client-state store uses.  The context is deliberately tiny — the FL engine
stays a single SPMD program that every process runs identically (same PRNG
key sequence, same scheduler decisions, same byte accounting); only device
placement and client-state ownership differ per process.

Configuration comes from explicit :class:`DistConfig` or from environment
variables (the launcher contract — ``examples/multipod_train.py`` and
``scripts/dist_smoke.py`` spawn workers with these set):

  * ``REPRO_DIST_COORD``  — coordinator address, e.g. ``localhost:12345``
  * ``REPRO_DIST_NPROCS`` — total process count
  * ``REPRO_DIST_PID``    — this process's index (coordinator = 0)

A process with no ``REPRO_DIST_*`` environment (and no prior
``jax.distributed.initialize`` call) gets a degenerate single-process
context: ``process_count == 1``, the cohort mesh spans the local devices,
and every collective helper is an identity — so ``executor="dist"`` runs
anywhere the sharded backend does.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any

import jax
import numpy as np

ENV_COORD = "REPRO_DIST_COORD"
ENV_NPROCS = "REPRO_DIST_NPROCS"
ENV_PID = "REPRO_DIST_PID"


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """One process's slot in a ``jax.distributed`` job.

    ``num_processes == 1`` (the default) never touches the coordination
    service; >1 requires ``coordinator`` (``host:port`` — process 0 binds
    it, everyone connects).
    """
    coordinator: str | None = None
    num_processes: int = 1
    process_id: int = 0

    @classmethod
    def from_env(cls) -> "DistConfig | None":
        """The launcher contract; None when no REPRO_DIST_* vars are set."""
        if ENV_COORD not in os.environ and ENV_NPROCS not in os.environ:
            return None
        coord = os.environ.get(ENV_COORD)
        nprocs = int(os.environ.get(ENV_NPROCS, "1"))
        pid = int(os.environ.get(ENV_PID, "0"))
        return cls(coordinator=coord, num_processes=nprocs, process_id=pid)

    def validate(self) -> None:
        if self.num_processes < 1:
            raise ValueError(
                f"num_processes must be >= 1, got {self.num_processes}")
        if not 0 <= self.process_id < self.num_processes:
            raise ValueError(
                f"process_id {self.process_id} out of range for "
                f"{self.num_processes} processes")
        if self.num_processes > 1 and not self.coordinator:
            raise ValueError(
                "a multi-process job needs a coordinator address "
                f"({ENV_COORD} or DistConfig.coordinator, host:port)")


class DistContext:
    """The process's view of the distributed job (and the single-process
    degenerate case).

    Construction initializes the ``jax.distributed`` coordination service
    exactly once per process when the config is multi-process; afterwards
    ``jax.devices()`` is the GLOBAL device list, so the cohort mesh built
    here spans every host.  Collective helpers (``sum_across_processes``)
    are host-tree utilities over ``multihost_utils`` that degrade to
    identities at ``process_count == 1``.
    """

    def __init__(self, cfg: DistConfig | None = None):
        if cfg is None:
            cfg = DistConfig.from_env() or DistConfig()
        cfg.validate()
        self.cfg = cfg
        if cfg.num_processes > 1:
            _initialize_once(cfg)
        # read the topology back from jax — authoritative whether we
        # initialized, someone else did, or this is single-process
        self.process_index = int(jax.process_index())
        self.process_count = int(jax.process_count())

    @property
    def is_coordinator(self) -> bool:
        return self.process_index == 0

    @property
    def local_devices(self):
        return jax.local_devices()

    @property
    def global_devices(self):
        return jax.devices()

    def cohort_mesh(self):
        """1-D ``"clients"`` mesh over every device of every process."""
        from repro.launch.mesh import make_multihost_cohort_mesh
        return make_multihost_cohort_mesh()

    # -- host collectives --------------------------------------------------

    def sum_across_processes(self, tree: Any) -> Any:
        """Elementwise sum of each process's host pytree (identity at P=1).

        The cross-host state gather uses this as its handoff primitive:
        each process contributes real rows where it owns the client and
        zeros elsewhere, so the sum routes every row from its owning host
        to all hosts exactly (one non-zero contribution per row).
        """
        if self.process_count == 1:
            return tree
        from jax.experimental import multihost_utils
        gathered = multihost_utils.process_allgather(tree)  # (P, ...) leaves
        return jax.tree.map(lambda x: np.asarray(x).sum(axis=0), gathered)

    def barrier(self, name: str = "repro_dist_barrier") -> None:
        """Block until every process reaches the same point (no-op at P=1)."""
        if self.process_count == 1:
            return
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"DistContext(process {self.process_index}/"
                f"{self.process_count}, "
                f"{len(self.local_devices)} local / "
                f"{len(self.global_devices)} global devices)")


# --------------------------------------------------------------- singleton

_INITIALIZED = False
_CONTEXT: DistContext | None = None


def _initialize_once(cfg: DistConfig) -> None:
    """``jax.distributed.initialize`` exactly once per process, with an
    actionable error when the sandbox forbids the coordination socket."""
    global _INITIALIZED
    if _INITIALIZED:
        return
    # the default CPU client has no cross-process collectives ("Multiprocess
    # computations aren't implemented on the CPU backend"); jax ships a gloo
    # TCP implementation behind this flag.  Must be set before the backend
    # initializes — harmless for GPU/TPU jobs, which ignore it.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    try:
        jax.distributed.initialize(
            coordinator_address=cfg.coordinator,
            num_processes=cfg.num_processes,
            process_id=cfg.process_id)
    except Exception as e:  # noqa: BLE001 - re-raise with launch context
        raise RuntimeError(
            f"jax.distributed.initialize failed for process "
            f"{cfg.process_id}/{cfg.num_processes} "
            f"(coordinator {cfg.coordinator!r}): {e}. "
            "If this host cannot open the coordination-service socket, "
            "run single-process (drop the REPRO_DIST_* environment).") from e
    _INITIALIZED = True


def get_context() -> DistContext:
    """The process-wide context (created on first use, env-var driven).

    Call this BEFORE any other jax API in a worker process: the
    coordination service must initialize before the backend locks its
    device topology.
    """
    global _CONTEXT
    if _CONTEXT is None:
        _CONTEXT = DistContext()
    return _CONTEXT


def init_from_env() -> DistContext:
    """Explicit launcher entry point — same as :func:`get_context` but
    raises if REPRO_DIST_* is absent (a worker that expected to be
    distributed should not silently run single-process)."""
    cfg = DistConfig.from_env()
    if cfg is None:
        raise RuntimeError(
            f"init_from_env: no {ENV_COORD}/{ENV_NPROCS} in the "
            "environment; use get_context() for the single-process path")
    global _CONTEXT
    if _CONTEXT is None:
        _CONTEXT = DistContext(cfg)
    return _CONTEXT
