"""Cohort execution backends: HOW a batch of ``client_round`` calls runs.

The engine's hot path is running the per-client ``client_round`` over a
cohort.  *Who* trains *when* is scheduling policy (``repro.fl.rounds``);
*how the batch actually executes* is a :class:`ClientExecutor` backend:

  * :class:`SerialExecutor` — one jitted ``client_round`` call per client,
    outputs stacked on host order.  Lowest memory, easiest to debug, and
    the reference the equivalence tests pin the other backends against.
  * :class:`VmapExecutor` — the vmapped cohort path (the engine default):
    one ``jax.vmap`` call over the stacked client axis, exactly the
    compiled program the seed-parity byte pins were captured from.
  * :class:`ShardedExecutor` — the vmapped program with the cohort axis
    laid out across a 1-D device mesh (``jax.sharding.NamedSharding`` over
    the ``"clients"`` axis, mesh from ``repro.launch.mesh``).  Cohorts are
    padded to a multiple of the mesh size (``sampling.pad_clients``, last
    row repeated) and the padded rows are dropped from the output, so
    ragged cohorts (K not divisible by the device count) behave exactly
    like the single-device path.
  * :class:`DistExecutor` — the sharded program on a MULTI-PROCESS mesh:
    a ``jax.distributed`` job (``repro.dist.DistContext``) whose cohort
    mesh spans every host's devices.  Each process feeds only its local
    shard of the stacked client arrays
    (``jax.make_array_from_process_local_data``) and the outputs are
    all-gathered back to every host (the engine's uplink/aggregation is
    replicated SPMD), so the compiled per-row program — and therefore the
    seed-parity pins — is unchanged; only where rows live differs.

Every backend exposes the same two entry points and MUST be numerically
equivalent on the same inputs (tolerance-pinned in tests/test_executors.py):

  * ``run_shared(server, ...)`` — the whole batch trains against ONE
    server snapshot (the sync cohort barrier),
  * ``run_stacked(servers, ...)`` — each row trains against its OWN
    server snapshot stacked on the leading axis (async dispatch windows,
    where concurrently-finishing clients started from different versions).

``rounds.LocalTrain`` owns the data/persistent-state plumbing and
delegates both calls to the injected executor, so sync cohorts, async
windows, and every scenario in the registry scale through the same layer.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.fl.sampling import pad_clients
from repro.launch.mesh import make_cohort_mesh
from repro.obs import trace as obs_trace

COHORT_AXIS = "clients"

_VMAP_AXES = dict(in_axes=(None, 0, 0, 0, 0, 0, 0), out_axes=0)
_STACKED_AXES = dict(in_axes=(0, 0, 0, 0, 0, 0, 0), out_axes=0)


def _row(tree: Any, i: int) -> Any:
    return jax.tree.map(lambda x: x[i], tree)


def _stack(outs: list[Any]) -> Any:
    return jax.tree.map(lambda *ls: jnp.stack(ls), *outs)


def _named(client_round):
    """Wrap ``client_round`` in ``jax.named_scope`` at bind time so the
    compiled HLO (and any device profile) carries the stage name.  Pure
    trace-time metadata — numerically a no-op, so the seed-parity pins
    are unaffected."""
    def named_client_round(*args):
        with jax.named_scope("fl.client_round"):
            return client_round(*args)
    return named_client_round


class ClientExecutor:
    """Protocol: compile ``client_round`` once, then run cohort batches.

    ``bind`` receives the per-client round function
    ``client_round(server, persistent, cx, cy, cvx, cvy, batch_idx)``;
    ``run_shared``/``run_stacked`` receive client-stacked input trees
    (leading axis = cohort) and return the client-stacked output tree.
    """

    name: str = "?"

    def bind(self, client_round) -> None:
        raise NotImplementedError

    def run_shared(self, server, pers, cx, cy, cvx, cvy, bidx):
        """Batch vs ONE server snapshot (sync cohort barrier)."""
        raise NotImplementedError

    def run_stacked(self, servers, pers, cx, cy, cvx, cvy, bidx):
        """Batch vs per-row server snapshots (async dispatch window)."""
        raise NotImplementedError


class SerialExecutor(ClientExecutor):
    """One jitted ``client_round`` per client, host loop, outputs stacked.

    The pre-refactor async completion path; kept as a first-class backend
    because it compiles once for EVERY cohort size (the vmapped backends
    retrace per distinct batch size) and is the reference implementation
    the equivalence suite compares against.
    """

    name = "serial"

    def bind(self, client_round) -> None:
        self.jround = jax.jit(_named(client_round))

    def run_shared(self, server, pers, cx, cy, cvx, cvy, bidx):
        with obs_trace.span("executor.run_shared", backend=self.name,
                            n=int(cx.shape[0])):
            return _stack([self.jround(server, _row(pers, i), cx[i], cy[i],
                                       cvx[i], cvy[i], bidx[i])
                           for i in range(cx.shape[0])])

    def run_stacked(self, servers, pers, cx, cy, cvx, cvy, bidx):
        with obs_trace.span("executor.run_stacked", backend=self.name,
                            n=int(cx.shape[0])):
            return _stack([self.jround(_row(servers, i), _row(pers, i),
                                       cx[i], cy[i], cvx[i], cvy[i], bidx[i])
                           for i in range(cx.shape[0])])


class VmapExecutor(ClientExecutor):
    """The vmapped cohort path — the engine default.

    ``run_shared`` is bit-for-bit the program the seed-parity pins were
    captured from (server broadcast via ``in_axes=None``); ``run_stacked``
    maps the server axis too, so an async window of clients that started
    from different versions still executes as ONE call.
    """

    name = "vmap"

    def bind(self, client_round) -> None:
        named = _named(client_round)
        self.vround = jax.jit(jax.vmap(named, **_VMAP_AXES))
        self.vround_stacked = jax.jit(jax.vmap(named, **_STACKED_AXES))

    def run_shared(self, server, pers, cx, cy, cvx, cvy, bidx):
        with obs_trace.span("executor.run_shared", backend=self.name,
                            n=int(cx.shape[0])):
            return self.vround(server, pers, cx, cy, cvx, cvy, bidx)

    def run_stacked(self, servers, pers, cx, cy, cvx, cvy, bidx):
        with obs_trace.span("executor.run_stacked", backend=self.name,
                            n=int(cx.shape[0])):
            return self.vround_stacked(servers, pers, cx, cy, cvx, cvy, bidx)


class ShardedExecutor(VmapExecutor):
    """Vmapped cohort with the client axis sharded across a device mesh.

    The batch's client-stacked inputs are placed with
    ``NamedSharding(mesh, P("clients"))`` (leading axis split across the
    mesh, remaining axes replicated) and the server snapshot is replicated,
    so XLA partitions the vmapped program across devices — cohorts larger
    than one chip's memory/throughput run at ``cohort / mesh_size`` per
    device.  Cohorts are padded to a multiple of the mesh size by
    repeating the last client row (``sampling.pad_clients``); the padded
    rows compute a throwaway replica and are sliced off the output, so
    results are independent of the padding.
    """

    name = "sharded"

    def __init__(self, mesh=None, mesh_shape: tuple[int, ...] | None = None):
        self.mesh = mesh if mesh is not None else make_cohort_mesh(mesh_shape)
        self.mesh_size = int(math.prod(self.mesh.devices.shape))
        self._batch = NamedSharding(self.mesh, P(COHORT_AXIS))
        self._replicated = NamedSharding(self.mesh, P())

    # bind() is inherited: the compiled programs ARE VmapExecutor's; this
    # backend only changes where the inputs live.

    def _place(self, tree: Any, sharding: NamedSharding) -> Any:
        # one pytree-level device_put: JAX batches the per-leaf transfers
        return jax.device_put(tree, sharding)

    def _padded(self, trees: tuple, n: int) -> tuple:
        total = -(-n // self.mesh_size) * self.mesh_size
        return tuple(self._place(pad_clients(t, total), self._batch)
                     for t in trees)

    def run_shared(self, server, pers, cx, cy, cvx, cvy, bidx):
        n = cx.shape[0]
        with obs_trace.span("executor.run_shared", backend=self.name,
                            n=int(n)):
            batch = self._padded((pers, cx, cy, cvx, cvy, bidx), n)
            out = self.vround(self._place(server, self._replicated), *batch)
            return _row(out, slice(0, n))

    def run_stacked(self, servers, pers, cx, cy, cvx, cvy, bidx):
        n = cx.shape[0]
        with obs_trace.span("executor.run_stacked", backend=self.name,
                            n=int(n)):
            servers, *batch = self._padded(
                (servers, pers, cx, cy, cvx, cvy, bidx), n)
            out = self.vround_stacked(servers, *batch)
            return _row(out, slice(0, n))


class DistExecutor(ShardedExecutor):
    """The sharded cohort program on a ``jax.distributed`` multi-host mesh.

    Construction resolves the process's :class:`repro.dist.DistContext`
    (env-var driven ``jax.distributed.initialize``; degenerates to a
    single-process local-device mesh when no job is configured) and builds
    the cohort mesh over the GLOBAL device list.  Three things differ from
    :class:`ShardedExecutor`:

      * **input feed** — each process materialises only the rows its own
        devices address (``jax.make_array_from_process_local_data``); the
        server snapshot is fed replicated.  The stacked host arrays are
        identical on every process (deterministic SPMD engine), so the
        per-host slice is just a view of rows the host already computed.
      * **output fetch** — the sharded outputs are resharded to fully
        replicated (one compiled all-gather) and fetched to host numpy, so
        the host-side wire/aggregation path sees the full cohort on every
        process exactly like the single-process run.
      * **ownership** — :meth:`position_owners` exposes which process's
        mesh slice trained each cohort position (from the batch sharding's
        device index map), the contract
        :class:`repro.dist.CrossHostClientStore` partitions persistent
        client state by.

    The compiled per-row program is untouched (same vmapped HLO, rows just
    live on more hosts), so results — including the frozen seed byte pins —
    are bitwise identical to the single-process backends.
    """

    name = "dist"

    def __init__(self, ctx=None):
        if ctx is None:
            from repro.dist import get_context
            ctx = get_context()
        self.ctx = ctx
        super().__init__(mesh=ctx.cohort_mesh())
        self._rep_jit = jax.jit(lambda t: t, out_shardings=self._replicated)
        self._local_cache: dict[int, tuple[int, int]] = {}
        self._owner_cache: dict[int, Any] = {}

    def _place(self, tree: Any, sharding: NamedSharding) -> Any:
        if self.ctx.process_count == 1:
            return jax.device_put(tree, sharding)
        sharded_rows = bool(sharding.spec) and sharding.spec[0] == COHORT_AXIS

        def put(x):
            x = np.asarray(jax.device_get(x))
            gshape = x.shape
            if sharded_rows:
                lo, hi = self._local_rows(gshape[0])
                if (lo, hi) != (0, gshape[0]):
                    return jax.make_array_from_process_local_data(
                        sharding, np.ascontiguousarray(x[lo:hi]), gshape)
            return jax.make_array_from_process_local_data(sharding, x, gshape)

        return jax.tree.map(put, tree)

    def _local_rows(self, total: int) -> tuple[int, int]:
        """The contiguous [lo, hi) row block this process's devices address
        under the batch sharding; (0, total) when the device order is not a
        contiguous block (then the full replicated feed is used — always
        correct, just a larger host->device transfer)."""
        cached = self._local_cache.get(total)
        if cached is not None:
            return cached
        amap = self._batch.addressable_devices_indices_map((total,))
        bounds = sorted({(s[0].start or 0,
                          total if s[0].stop is None else s[0].stop)
                         for s in amap.values()})
        lo, hi = bounds[0][0], bounds[-1][1]
        if sum(b[1] - b[0] for b in bounds) != hi - lo:
            lo, hi = 0, total
        self._local_cache[total] = (lo, hi)
        return lo, hi

    def _fetch(self, out: Any) -> Any:
        """All-gather the row-sharded outputs and fetch to host numpy, so
        every process's wire path sees the full cohort."""
        if self.ctx.process_count == 1:
            return out
        return jax.device_get(self._rep_jit(out))

    def position_owners(self, n: int) -> Any:
        """Process index whose mesh slice trains each of ``n`` cohort rows
        (after padding) — the write-ownership contract of
        ``repro.dist.CrossHostClientStore``."""
        if n <= 0:
            return np.empty(0, np.int32)
        total = -(-n // self.mesh_size) * self.mesh_size
        owners = self._owner_cache.get(total)
        if owners is None:
            owners = np.empty(total, np.int32)
            for dev, index in self._batch.devices_indices_map(
                    (total,)).items():
                owners[index[0]] = dev.process_index
            self._owner_cache[total] = owners
        return owners[:n]

    def run_shared(self, server, pers, cx, cy, cvx, cvy, bidx):
        n = cx.shape[0]
        with obs_trace.span("executor.run_shared", backend=self.name,
                            n=int(n)):
            batch = self._padded((pers, cx, cy, cvx, cvy, bidx), n)
            out = self.vround(self._place(server, self._replicated), *batch)
            return _row(self._fetch(out), slice(0, n))

    def run_stacked(self, servers, pers, cx, cy, cvx, cvy, bidx):
        n = cx.shape[0]
        with obs_trace.span("executor.run_stacked", backend=self.name,
                            n=int(n)):
            servers, *batch = self._padded(
                (servers, pers, cx, cy, cvx, cvy, bidx), n)
            out = self.vround_stacked(servers, *batch)
            return _row(self._fetch(out), slice(0, n))


EXECUTORS: dict[str, type[ClientExecutor]] = {
    "serial": SerialExecutor,
    "vmap": VmapExecutor,
    "sharded": ShardedExecutor,
    "dist": DistExecutor,
}


def make_executor(name: str, *,
                  mesh_shape: tuple[int, ...] | None = None) -> ClientExecutor:
    """Build a backend by registry name (``EngineConfig.executor``)."""
    if name not in EXECUTORS:
        known = ", ".join(sorted(EXECUTORS))
        raise ValueError(f"unknown executor: {name!r} (known: {known})")
    if name == "sharded":
        return ShardedExecutor(mesh_shape=mesh_shape)
    if name == "dist":
        return DistExecutor()
    return EXECUTORS[name]()
