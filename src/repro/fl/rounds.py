"""Round-lifecycle stages: the paper's Algorithm 1 as composable objects.

One federated round is the same lifecycle regardless of *when* clients run:

    CohortPlan -> LocalTrain (vmapped client_round) -> Uplink -> Aggregate
               -> ServerStep -> Downlink -> Evaluate

This module provides each stage as a typed object plus the small dataclass
contracts between them (:class:`Contribution` — one decoded client message
with metadata, :class:`AggregatedRound` — the weighted means the server
consumes, :class:`RoundIntake` — what a scheduler hands the orchestrator
per aggregation).  ``repro.fl.engine.FederatedEngine`` builds ONE instance
of each stage and consumes a :class:`RoundScheduler` policy:

  * :class:`SyncScheduler` — per-round cohort barrier: everyone in the
    cohort trains against the same server snapshot, channel drops exclude
    stragglers from aggregation (their decoded mass is re-injected into the
    residual under error feedback, Eq. 5),
  * :class:`BufferedAsyncScheduler` — FedBuff-style buffer: M clients train
    concurrently against whatever server version each started from, the
    buffer aggregates with staleness weights once B updates land; clients
    whose simulated finish times fall in the same dispatch window run as
    ONE executor call (``LocalTrain.train_window``).

Sync vs. async is therefore a *scheduling policy*, not a forked code path —
both policies drive the identical ``Uplink``/``Aggregate``/``ServerStep``
stage instances (tested structurally in tests/test_rounds.py).  HOW a batch
of ``client_round`` calls executes — serial jit loop, vmapped, or
mesh-sharded over the cohort axis — is a third orthogonal axis, the
:class:`repro.fl.executors.ClientExecutor` backend injected into
``LocalTrain``.

``Uplink`` owns the host wire hot path: each cohort member's message is
encoded AND decoded (the server aggregates only what provably round-trips),
and because codec state (e.g. CABAC contexts) is per-message the per-client
round-trips are embarrassingly parallel — ``EngineConfig.uplink_workers``
fans them out across a ``ThreadPoolExecutor`` (numpy-dominated codecs
release the GIL) or a ``ProcessPoolExecutor`` (pure-Python entropy coders;
fork-based, results order-preserved).  Under wire schema v2 the client's BN
statistics travel inside the codec payload and :class:`Aggregate` sees them
only via the decoded message; under v1 (the PR-2 byte-pinned frame) the
uplink fills ``Contribution.bn_state`` from the device fetch instead.

PRNG-key discipline: each scheduler consumes splits in exactly the order
the PR-1/PR-2 engine did (sync: ``kb`` then — only when sampling — ``ks``;
async: ``kl`` latencies, ``ks`` first cohort, then one ``kb`` per windowed
completion in deterministic (finish, client) order followed by one
replacement ``ks`` per completion), which is what keeps the seed parity
pins bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import multiprocessing
import warnings
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import comms
from repro.comms import device as comms_device
from repro.comms.codecs import Int8BlockScaleCodec
from repro.core import delta as delta_lib
from repro.core import prand
from repro.core import quant as quant_lib
from repro.core import sparsify as sparsify_lib
from repro.core.protocol import ProtocolConfig, ServerState
from repro.data.federated import client_epoch_batches, epoch_batches
from repro.fl.executors import ClientExecutor, VmapExecutor
from repro.fl.async_buffer import (client_latencies, load_call_saving,
                                   normalized_staleness_weights,
                                   weighted_mean_trees)
from repro.fl.sampling import (EmptyCohortError, SamplingConfig,
                               sample_available, sample_cohort,
                               stream_cohort)
from repro.fl.server_opt import server_update
from repro.kernels import platform
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.optim import apply_updates

# ---------------------------------------------------------------- tree utils


def tree_mean0(tree: Any) -> Any:
    """Mean over the leading (client) axis."""
    return jax.tree.map(lambda x: jnp.mean(x, axis=0), tree)


def stack_trees(trees: list[Any]) -> Any:
    """Stack per-client trees along a new leading axis.

    Host (numpy) leaves stack on host — one transfer when the mean pushes
    the block to device, exactly the PR-2 wire path.  Device leaves stack
    on device so the no-wire fast path never syncs to host."""
    return jax.tree.map(
        lambda *ls: (np.stack(ls) if isinstance(ls[0], np.ndarray)
                     else jnp.stack(ls)), *trees)


def client_slice(tree: Any, i: int) -> Any:
    return jax.tree.map(lambda x: np.asarray(x[i]), tree)


def raw_bytes_per_client(params: Any) -> int:
    return 4 * sum(l.size for l in jax.tree.leaves(params))


# ---------------------------------------------------------------- contracts

@dataclasses.dataclass
class Contribution:
    """One decoded client message plus its metadata.

    ``delta_params``/``delta_scales``/``bn_state`` are host float32 pytrees:
    the DECODED wire reconstruction when the engine transmits (schema v2
    additionally sources ``bn_state`` from the payload's BN section), or the
    device-side reconstruction on the no-wire fast path.

    Under ``EngineConfig.ingest="streaming"`` the contribution is
    encode-only: ``payload`` holds the wire bytes, no decoded host tree
    exists (the scheduler folds survivors through ``repro.fl.ingest``),
    and ``delta_params``/``bn_state`` are lazy DEVICE row views — kept for
    Eq.-5 residual re-injection on drops/rejects and the v1 out-of-band BN
    mean, never fetched to host.
    """
    client: int
    delta_params: Any
    delta_scales: Any
    bn_state: Any
    payload_bytes: int = 0
    staleness: int = 0
    arrival_time: float = 0.0
    metrics: dict[str, float] | None = None
    payload: bytes | None = None


@dataclasses.dataclass
class AggregatedRound:
    """What one server step consumes: weighted means + the survivor set."""
    delta_params: Any
    delta_scales: Any
    bn_state: Any
    survivors: tuple[int, ...]
    weights: np.ndarray | None    # None = plain mean (sync cohort barrier)


@dataclasses.dataclass
class RoundIntake:
    """A scheduler's hand-off to the orchestrator for ONE aggregation.

    ``contributions`` are all charged uploads (byte accounting);
    ``survivors`` indexes the subset that aggregates (channel drops exclude
    clients without refunding their bytes).  ``weights`` is None for the
    sync plain mean, or the normalised FedBuff staleness weights.
    ``receivers`` is how many clients receive the following broadcast.

    ``preagg`` is the streaming-ingest hand-off: when a scheduler already
    folded the survivors through ``repro.fl.ingest`` (decode-and-
    accumulate, O(1) memory), it ships the finished
    :class:`AggregatedRound` here and the orchestrator skips the
    ``Aggregate`` stage (which would need the per-client decoded trees
    the streaming path never materialises).
    """
    contributions: list[Contribution]
    survivors: list[int]
    weights: np.ndarray | None
    sim_time: float
    receivers: int
    preagg: "AggregatedRound | None" = None


# ---------------------------------------------------------------- cohort plan

class CohortPlan:
    """Stage 1: who participates.  Two selection regimes:

      * **materialized** (legacy) — jax.random draws over the explicit
        index range, with the key-splitting discipline the parity pins rely
        on: full participation consumes NO sampling randomness.
      * **streaming** — active when the engine has a population axis or a
        traffic model: cohorts come from the hash-based
        :func:`repro.fl.sampling.stream_cohort` (a pure function of
        ``(stream_seed, round)``), optionally availability-masked by the
        traffic model's diurnal curve.  Consumes no jax keys at all, and
        never enumerates the population.
    """

    def __init__(self, sampling: SamplingConfig, num_clients: int, *,
                 streaming: bool = False, traffic=None):
        self.sampling = sampling
        self.num_clients = num_clients
        self.full = sampling.is_full(num_clients)
        self.streaming = streaming or traffic is not None
        self.traffic = traffic

    def select(self, key: jax.Array) -> tuple[np.ndarray, jax.Array]:
        """One sync round's cohort; returns (indices, advanced key)."""
        with obs_trace.span("cohort_plan.select", full=self.full):
            if self.full:
                return np.arange(self.num_clients), key
            key, ks = jax.random.split(key)
            return sample_cohort(ks, self.num_clients, self.sampling), key

    def select_stream(self, round_idx: int, now: float) -> np.ndarray:
        """Streaming-regime cohort: hash-drawn, availability-filtered.

        With a traffic model the draw is non-strict — a thin availability
        trough legitimately returns a short (possibly empty) cohort and the
        scheduler advances its clock and retries.
        """
        with obs_trace.span("cohort_plan.select_stream", round=round_idx):
            accept = None
            if self.traffic is not None:
                traffic, t = self.traffic, now
                accept = lambda ids: traffic.available(ids, t, round_idx)
            if self.full:
                ids = np.arange(self.num_clients, dtype=np.int64)
                if accept is not None:
                    ids = ids[np.asarray(accept(ids), bool)]
                return ids
            weight_fn = None
            if (self.sampling.strategy == "weighted"
                    and self.sampling.weights is not None):
                w = np.asarray(self.sampling.weights, np.float64)
                peak = w.max()
                weight_fn = lambda ids: w[ids] / peak
            return stream_cohort(
                self.sampling.stream_seed, round_idx, self.num_clients,
                self.sampling.effective_size(self.num_clients),
                weight_fn=weight_fn, accept_fn=accept,
                strict=accept is None)

    def select_available(self, key: jax.Array, available: np.ndarray,
                         k: int) -> tuple[np.ndarray, jax.Array]:
        """Async dispatch draw from the idle set (always consumes a split)."""
        with obs_trace.span("cohort_plan.select_available", k=k):
            key, ks = jax.random.split(key)
            return sample_available(ks, available, k, self.sampling), key


# ---------------------------------------------------------------- local train

class LocalTrain:
    """Stage 2: run ``client_round`` over a batch of clients.

    Per-client persistent state (residuals, optimizer states, schedule
    counters) lives in an injected
    :class:`repro.fl.population.ClientStateStore` — eager in-memory (the
    legacy client-stacked tree, bit-for-bit) or sharded + lazy with
    spill-to-disk for population-scale runs — and per-client data comes
    through a :class:`repro.fl.population.SplitsView` (identity over the
    real splits, or the hash-mapped virtual-population view).  HOW the
    batch executes is delegated to the injected
    :class:`~repro.fl.executors.ClientExecutor` (serial jit loop / vmapped
    / mesh-sharded — ``EngineConfig.executor``).  Channel-dropped decoded
    mass is re-injected here (``reinject_residual``) so Eq. 5 holds across
    drops.
    """

    def __init__(self, client_round, data, store, batch_size: int,
                 executor: ClientExecutor | None = None):
        self.executor = executor if executor is not None else VmapExecutor()
        self.executor.bind(client_round)
        self.splits = data          # SplitsView (attr passthrough preserved)
        self.store = store
        self.batch_size = batch_size
        self.n_train = data.n_train

    @property
    def persistent(self):
        """The whole client-stacked state (dense backends only)."""
        return self.store.state

    @persistent.setter
    def persistent(self, state) -> None:
        self.store.set_state(state)

    def train_cohort(self, kb: jax.Array, idx: np.ndarray, server: ServerState,
                     full: bool):
        """One barrier round over the cohort ``idx``; returns RoundOutput."""
        if len(idx) == 0:
            raise EmptyCohortError(
                "train_cohort received an empty cohort; schedulers should "
                "surface this as an all-drop round, not an executor call")
        with obs_trace.span("local_train.cohort", n=len(idx)):
            batch_idx = client_epoch_batches(kb, len(idx), self.n_train,
                                             self.batch_size)
            if full and self.store.dense:
                cx, cy, cvx, cvy = self.splits.all()
                pers_c = self.store.state
                out = self.executor.run_shared(server, pers_c, cx, cy,
                                               cvx, cvy, batch_idx)
                self.store.set_state(out.persistent)
            else:
                cx, cy, cvx, cvy = self.splits.gather(idx)
                pers_c = self.store.gather(idx)
                out = self.executor.run_shared(server, pers_c, cx, cy,
                                               cvx, cvy, batch_idx)
                self.store.scatter(idx, out.persistent)
            return out

    def train_window(self, kbs: list[jax.Array], clients: list[int],
                     servers: list[ServerState]):
        """One async dispatch window as ONE executor call.

        Each client carries its own batch-shuffle key and the server
        snapshot it was dispatched against (concurrently-finishing clients
        may straddle an aggregation), so the batch runs through the
        executor's stacked-server path — EXCEPT when every member was
        dispatched against the same snapshot (the common regime: a whole
        window of replacements issued after one aggregation), where the
        broadcast path avoids materialising one server copy per client.
        Returns the client-stacked RoundOutput in ``clients`` order.
        """
        if len(clients) == 0:
            raise EmptyCohortError(
                "train_window received an empty dispatch window; schedulers "
                "should surface this as an all-drop round, not an executor "
                "call")
        with obs_trace.span("local_train.window", n=len(clients)):
            idx = np.asarray(clients)
            bidx = jnp.stack([epoch_batches(kb, self.n_train, self.batch_size)
                              for kb in kbs])
            cx, cy, cvx, cvy = self.splits.gather(idx)
            args = (self.store.gather(idx), cx, cy, cvx, cvy, bidx)
            if all(s is servers[0] for s in servers[1:]):
                out = self.executor.run_shared(servers[0], *args)
            else:
                out = self.executor.run_stacked(stack_trees(servers), *args)
            self.store.scatter(idx, out.persistent)
            return out

    def reinject_residual(self, client: int, delta: Any) -> None:
        """A dropped upload must not break Eq. 5: put the lost (decoded)
        delta back into that client's residual so its mass is retransmitted
        (the scale-delta section has no residual and stays lost)."""
        if self.store.dense:
            state = self.store.state
            self.store.set_state(state._replace(
                residual=jax.tree.map(
                    lambda r, d: r.at[client].add(jnp.asarray(d)),
                    state.residual, delta)))
            return
        idx = np.asarray([client])
        row = self.store.gather(idx)
        self.store.scatter(idx, row._replace(
            residual=jax.tree.map(
                lambda r, d: r + np.asarray(d)[None].astype(r.dtype),
                row.residual, delta)))


# ---------------------------------------------------------------- uplink

# Fork-pool worker state: the codec/spec pair is shipped once per worker via
# the pool initializer instead of once per task (specs embed shape templates).
_POOL_CODEC: comms.Codec | None = None
_POOL_SPEC: comms.WireSpec | None = None


def _pool_init(codec: comms.Codec, spec: comms.WireSpec) -> None:
    # The accelerator belongs to the parent (one process per chip): pin
    # this worker's JAX to the CPU before any backend starts, so the
    # codecs that dispatch through jax (int8-blockscale) encode on the host
    jax.config.update("jax_platforms", "cpu")
    global _POOL_CODEC, _POOL_SPEC
    _POOL_CODEC, _POOL_SPEC = codec, spec


def _pool_roundtrip(upd: comms.ClientUpdate):
    payload = _POOL_CODEC.encode(upd, _POOL_SPEC)
    return len(payload), _POOL_CODEC.decode(payload, _POOL_SPEC)


def _pool_roundtrip_chunk(chunk: list[comms.ClientUpdate],
                          clients: list[int] | None):
    """One batched worker task: encode+decode a whole client chunk.

    Returns ``(payload_bytes, FlatDecoded)`` pairs — flat float32 arrays,
    NOT decoded pytrees: pickling one contiguous array per section back to
    the parent is what removes the per-leaf pickle tax that made the
    process-pool uplink pay for its parallelism.  The parent reassembles
    against its own spec (``comms.unflatten_decoded``)."""
    payloads = _POOL_CODEC.encode_batch(chunk, _POOL_SPEC, clients=clients)
    decs = _POOL_CODEC.decode_batch(payloads, _POOL_SPEC, clients=clients)
    return [(len(p), comms.flatten_decoded(d, _POOL_SPEC))
            for p, d in zip(payloads, decs)]


class Uplink:
    """Stage 3: the wire.  Encode each participant's update, decode it back.

    The engine aggregates the DECODED reconstructions, so ``payload_bytes``
    is the length of payloads that provably decode.  For level-lossless
    codecs the decode is bit-identical to the in-graph dequantization
    (parity with the seed); lossy wire codecs (fp16/int8) make the server
    honestly see the wire loss.

    Per-client round-trips share no codec state, so ``workers > 1`` fans
    them across an executor: ``"thread"`` for numpy-dominated codecs (GIL
    released), ``"process"`` for the entropy coders.  Results come back in
    submission order — parallelism cannot change bytes.

    With more than one worker, ``uplink_batch=True`` swaps the per-client
    dispatch for the codec **batch API**: the cohort splits into at most
    ``workers`` contiguous chunks, ONE pool task per chunk (``pool_tasks``
    counts submissions), all messages coded against one shared shapes
    view, and process workers return ``comms.FlatDecoded`` flat arrays
    instead of pickled pytrees — the host reassembles them against its own
    spec.  Payloads are byte-identical to the per-client path.  With at
    most one worker the intake always takes the batch API, in this thread.
    """

    def __init__(self, cfg: ProtocolConfig, engine_cfg, server: ServerState):
        self.transmit = engine_cfg.measure_bytes
        self.codec = comms.resolve_codec(engine_cfg.codec, cfg.quantize)
        if ("levels" in self.codec.needs and not cfg.quantize
                and cfg.method != "ternary"):
            # a level codec would put quantized levels on the wire while the
            # client's residual (Eq. 5) assumes the full-precision recon was
            # delivered — the same hazard resolve_codec's "auto" avoids
            raise ValueError(
                f"codec {self.codec.name!r} transmits integer levels but the "
                "protocol has quantize=False; use a float codec "
                "(raw-fp32/fp16/int8-blockscale) or enable quantization")
        send_mask = None
        if engine_cfg.up_predicate is not None:
            send_mask = comms.make_send_mask(server.params,
                                             engine_cfg.up_predicate)
        self.spec = comms.WireSpec(
            params=comms.shape_template(server.params),
            scales=comms.shape_template(server.scales),
            fine_mask=comms.path_fine_mask(server.params),
            step_size=cfg.step_size,
            fine_step_size=cfg.fine_step_size,
            ternary=(cfg.method == "ternary"),
            send_mask=send_mask,
            bn=(comms.shape_template(server.bn_state)
                if engine_cfg.wire_schema == 2 else None),
            version=engine_cfg.wire_schema)
        self.workers = engine_cfg.uplink_workers
        self.executor_kind = engine_cfg.uplink_executor
        self.batch = engine_cfg.uplink_batch
        # streaming ingest: intake is encode-only (payload bytes on the
        # Contribution), the decode+fold happens in repro.fl.ingest
        self.streaming = engine_cfg.ingest == "streaming"
        # device cohort encode: Codec.encode_cohort on the still-stacked
        # RoundOutput (ONE fused program per cohort); None => host fallback
        self.device_encode = engine_cfg.device_encode
        if (self.workers > 1 and self.executor_kind == "process"
                and not self.codec.fork_safe):
            raise ValueError(
                f"codec {self.codec.name!r} dispatches through jax/XLA and "
                "is not fork-safe; use uplink_executor='thread' (its numpy "
                "work releases the GIL) or a fork-safe codec")
        self._ex = None
        # cumulative executor task submissions (tests and benchmarks read
        # this: batched intake submits <= workers tasks per cohort, the
        # per-client path one per update)
        self.pool_tasks = 0

    # -- device -> host ----------------------------------------------------

    def fetch(self, out):
        """Pull the wire-relevant RoundOutput trees to host in ONE transfer
        (per-leaf slicing would sync the device once per leaf per client).
        Only the trees the codec reads are fetched — level codecs skip the
        float reconstructions (except ternary, which needs them for the
        magnitude tail) and float codecs skip the levels.  BN state is
        fetched only under schema v2, where it must be encoded; under v1
        it stays on device (contributions carry device rows and the BN
        mean never syncs to host, like the pre-redesign engine).  The
        scalar metrics ride along for the Contribution metadata.  The host
        first waits for exactly those trees (``uplink.fetch.wait``), then
        copies them (``uplink.fetch.copy``)."""
        with obs_trace.span("uplink.fetch"):
            return self._fetch(out)

    def _fetch(self, out):
        need_levels = "levels" in self.codec.needs
        need_recon = "recon" in self.codec.needs or self.spec.ternary
        trees = (out.levels_params if need_levels else None,
                 out.levels_scales if need_levels else None,
                 out.recon_delta_params if need_recon else None,
                 out.recon_delta_scales if need_recon else None,
                 out.bn_state if self.spec.version == 2 else None,
                 out.metrics)
        with obs_trace.span("uplink.fetch.wait"):
            jax.block_until_ready(trees)
        with obs_trace.span("uplink.fetch.copy"):
            lp, ls, rp, rs, bn, metrics = jax.device_get(trees)
        upd = comms.ClientUpdate(lp, ls, rp, rs, bn=bn)
        return upd, metrics

    # -- wire round-trips --------------------------------------------------

    def _account_payload(self, payload: bytes) -> None:
        """Per-section uplink byte counters (``uplink.section.<name>.bytes``)
        via the codec's :meth:`~repro.comms.Codec.payload_sections` parse.
        Registry-gated: telemetry off never re-parses the payload."""
        m = obs_metrics.get_registry()
        if not m.enabled:
            return
        m.count("uplink.payloads", 1)
        for sec, n in self.codec.payload_sections(payload, self.spec).items():
            m.count(f"uplink.section.{sec}.bytes", n)

    def _account_opaque(self, sizes: list[int]) -> None:
        """Process-pool results: workers live in another process and never
        see the parent registry, so only payload totals are accounted here
        (section splits would need a payload re-parse the hot path skips)."""
        m = obs_metrics.get_registry()
        if not m.enabled:
            return
        m.count("uplink.payloads", len(sizes))
        m.count("uplink.section.opaque.bytes", sum(sizes))

    def _roundtrip(self, upd: comms.ClientUpdate):
        with obs_trace.span("uplink.roundtrip"):
            payload = self.codec.encode(upd, self.spec)
            self._account_payload(payload)
            return len(payload), self.codec.decode(payload, self.spec)

    def _roundtrip_batch(self, chunk: list[comms.ClientUpdate],
                         clients: list[int] | None):
        with obs_trace.span("uplink.roundtrip_batch", n=len(chunk)):
            payloads = self.codec.encode_batch(chunk, self.spec,
                                               clients=clients)
            for p in payloads:
                self._account_payload(p)
            decs = self.codec.decode_batch(payloads, self.spec,
                                           clients=clients)
            return [(len(p), d) for p, d in zip(payloads, decs)]

    def _executor(self):
        if self._ex is None:
            if self.executor_kind == "thread":
                self._ex = ThreadPoolExecutor(self.workers)
            else:
                # forkserver, not fork: by uplink time the parent runs XLA
                # thread pools, and forking a multithreaded process can
                # deadlock the child.  The forkserver process is spawned
                # clean (fork+exec) and workers fork from IT; preloading
                # repro.comms there amortises the import across workers.
                if (isinstance(self.codec, Int8BlockScaleCodec)
                        and not platform.interpret()):
                    warnings.warn(
                        "uplink_executor='process' on a TPU: the workers "
                        "run JAX on the CPU (the chip belongs to this "
                        "process), so int8-blockscale encodes in the "
                        "Pallas interpreter there", RuntimeWarning,
                        stacklevel=2)
                ctx = multiprocessing.get_context("forkserver")
                ctx.set_forkserver_preload(["repro.comms"])
                self._ex = ProcessPoolExecutor(
                    self.workers, mp_context=ctx, initializer=_pool_init,
                    initargs=(self.codec, self.spec))
        return self._ex

    def roundtrip_all(self, upds: list[comms.ClientUpdate],
                      clients: list[int] | None = None):
        """Encode+decode every update; parallel across clients when
        configured (order-preserving either way).

        With at most one worker the whole cohort goes through the codec's
        batch API in this thread: every update encoded, then all payloads
        decoded in one ``decode_batch`` (the nnc-cabac codec parses the
        same section of every message together).  With more workers,
        ``uplink_batch=False`` is the per-client dispatch: one executor
        task per update.  ``uplink_batch=True`` routes through the codec's
        batch API — the cohort splits into at most ``workers`` contiguous
        chunks and ONE task is submitted per chunk, so a K-client cohort
        costs <= W submissions instead of K, and process workers return
        flat arrays instead of pickled pytrees.  Either way results come
        back in submission order — parallelism cannot change bytes."""
        if self.workers <= 1 or len(upds) <= 1:
            return self._roundtrip_batch(upds, clients)
        if not self.batch:
            fn = (self._roundtrip if self.executor_kind == "thread"
                  else _pool_roundtrip)
            self.pool_tasks += len(upds)
            results = list(self._executor().map(fn, upds))
            if self.executor_kind != "thread":
                self._account_opaque([n for n, _ in results])
            return results
        # enforce the cohort contract on the WHOLE batch: chunking must not
        # weaken the no-duplicate check (a duplicate pair could otherwise
        # land in different chunks and pass per-chunk validation)
        comms.check_batch_clients(clients, len(upds), "updates")
        nchunks = min(self.workers, len(upds))
        bounds = np.array_split(np.arange(len(upds)), nchunks)
        chunks = [([upds[i] for i in b],
                   None if clients is None else [clients[i] for i in b])
                  for b in bounds if len(b)]
        ex = self._executor()
        self.pool_tasks += len(chunks)
        if self.executor_kind == "thread":
            futs = [ex.submit(self._roundtrip_batch, ch, cl)
                    for ch, cl in chunks]
            return [r for f in futs for r in f.result()]
        futs = [ex.submit(_pool_roundtrip_chunk, ch, cl) for ch, cl in chunks]
        results = [(nbytes, comms.unflatten_decoded(flat, self.spec))
                   for f in futs for nbytes, flat in f.result()]
        self._account_opaque([n for n, _ in results])
        return results

    def close(self) -> None:
        if self._ex is not None:
            self._ex.shutdown()
            self._ex = None

    # -- device cohort encode ----------------------------------------------

    def _device_payloads(self, out, clients: list[int]):
        """Cohort payloads from the device fast path, or None.

        Calls ``Codec.encode_cohort`` on the still-stacked ``RoundOutput``
        — the fused kernels replace the fetch + per-client encode.  The
        ``uplink.kernel_dispatches`` counter records how many fused device
        programs the cohort cost (the K x leaves -> O(1) collapse is the
        point of the path, so it is observable in traces)."""
        before = comms_device.dispatch_count()
        with obs_trace.span("uplink.device_encode", n=len(clients),
                            codec=self.codec.name):
            payloads = self.codec.encode_cohort(out, self.spec,
                                                clients=clients)
        if payloads is None:
            comms_device.count_handover()
        m = obs_metrics.get_registry()
        if m.enabled:
            m.count("uplink.kernel_dispatches",
                    comms_device.dispatch_count() - before)
        return payloads

    # -- RoundOutput -> Contributions --------------------------------------

    def _metric_row(self, metrics, i: int | None) -> dict[str, float]:
        return {k: float(v if i is None else v[i])
                for k, v in metrics.items()}

    def intake(self, out, clients: list[int]) -> list[Contribution]:
        """Stacked cohort RoundOutput -> one Contribution per client."""
        with obs_trace.span("uplink.intake", n=len(clients),
                            transmit=self.transmit):
            return self._intake(out, clients)

    def _intake(self, out, clients: list[int]) -> list[Contribution]:
        if not self.transmit:
            # no-wire fast path: contributions carry DEVICE rows (lazy
            # slices), so aggregation stays on device with zero host
            # transfer — only the scalar metrics are fetched
            metrics = jax.device_get(out.metrics)

            def row(tree, i):
                return jax.tree.map(lambda x: x[i], tree)

            return [Contribution(
                client=c,
                delta_params=row(out.recon_delta_params, i),
                delta_scales=row(out.recon_delta_scales, i),
                bn_state=row(out.bn_state, i),
                metrics=self._metric_row(metrics, i))
                for i, c in enumerate(clients)]
        if self.streaming:
            return self._intake_streaming(out, clients)
        if self.device_encode:
            payloads = self._device_payloads(out, clients)
            if payloads is not None:
                # only the scalar metrics cross to host — the payloads
                # already did, inside encode_cohort's single device_get
                metrics = jax.device_get(out.metrics)
                for p in payloads:
                    self._account_payload(p)
                decs = self.codec.decode_batch(payloads, self.spec,
                                               clients=clients)
                return [Contribution(
                    client=c,
                    delta_params=dec.params,
                    delta_scales=dec.scales,
                    bn_state=(dec.bn if self.spec.version == 2
                              else jax.tree.map(lambda x: x[i],
                                                out.bn_state)),
                    payload_bytes=len(p),
                    metrics=self._metric_row(metrics, i))
                    for i, (c, p, dec) in enumerate(
                        zip(clients, payloads, decs))]
        host, metrics = self.fetch(out)
        upds = [comms.ClientUpdate(*(None if t is None else client_slice(t, i)
                                     for t in host))
                for i in range(len(clients))]
        results = self.roundtrip_all(upds, clients)
        return [Contribution(
            client=c,
            delta_params=dec.params,
            delta_scales=dec.scales,
            bn_state=(dec.bn if self.spec.version == 2
                      else jax.tree.map(lambda x: x[i], out.bn_state)),
            payload_bytes=nbytes,
            metrics=self._metric_row(metrics, i))
            for i, (c, (nbytes, dec)) in enumerate(zip(clients, results))]

    def _intake_streaming(self, out, clients: list[int]) -> list[Contribution]:
        """Encode-only intake for ``EngineConfig.ingest="streaming"``.

        Contributions carry the PAYLOAD, not a decoded tree — the
        scheduler folds survivors through ``repro.fl.ingest`` after
        drop/churn resolution, so per-client decoded pytrees never
        co-exist.  ``delta_params`` (and ``bn_state`` under v1) are lazy
        device row views into the stacked RoundOutput: the residual
        re-injection for drops and quarantined payloads (Eq. 5) uses the
        client-side reconstruction — bit-identical to the decoded tree
        for level-lossless codecs — and the v1 BN mean stays on device
        exactly like the gather path."""
        payloads = None
        if self.device_encode:
            payloads = self._device_payloads(out, clients)
        if payloads is not None:
            metrics = jax.device_get(out.metrics)
        else:
            host, metrics = self.fetch(out)
            upds = [comms.ClientUpdate(
                *(None if t is None else client_slice(t, i) for t in host))
                for i in range(len(clients))]
            with obs_trace.span("uplink.encode_batch", n=len(upds)):
                payloads = self.codec.encode_batch(upds, self.spec,
                                                   clients=clients)
        for p in payloads:
            self._account_payload(p)

        def row(tree, i):
            return jax.tree.map(lambda x: x[i], tree)

        return [Contribution(
            client=c,
            delta_params=row(out.recon_delta_params, i),
            delta_scales=None,
            bn_state=(None if self.spec.version == 2
                      else row(out.bn_state, i)),
            payload_bytes=len(p),
            payload=p,
            metrics=self._metric_row(metrics, i))
            for i, (c, p) in enumerate(zip(clients, payloads))]


# ---------------------------------------------------------------- aggregate

class Aggregate:
    """Stage 4: THE aggregation.  Both schedulers' contributions flow
    through this one instance — a plain mean for the sync cohort barrier
    (bitwise the seed loop's aggregation) or the FedBuff staleness-weighted
    combination (``weighted_mean_trees``) when the scheduler supplies
    weights.  There is no other aggregation math in the engine."""

    def __call__(self, contribs: list[Contribution],
                 weights: np.ndarray | None = None) -> AggregatedRound:
        if not contribs:
            raise ValueError("cannot aggregate zero contributions")
        with obs_trace.span("aggregate", n=len(contribs),
                            weighted=weights is not None):
            return self._aggregate(contribs, weights)

    def _aggregate(self, contribs: list[Contribution],
                   weights: np.ndarray | None) -> AggregatedRound:
        if weights is None:
            mdp = tree_mean0(stack_trees([c.delta_params for c in contribs]))
            mds = tree_mean0(stack_trees([c.delta_scales for c in contribs]))
            mbn = tree_mean0(stack_trees([c.bn_state for c in contribs]))
        else:
            mdp = weighted_mean_trees([c.delta_params for c in contribs],
                                      weights)
            mds = weighted_mean_trees([c.delta_scales for c in contribs],
                                      weights)
            mbn = weighted_mean_trees([c.bn_state for c in contribs],
                                      weights)
        return AggregatedRound(
            delta_params=mdp, delta_scales=mds, bn_state=mbn,
            survivors=tuple(c.client for c in contribs), weights=weights)


# ---------------------------------------------------------------- server step

class ServerStep:
    """Stage 5: fold one AggregatedRound into the server state.

    The aggregated delta acts as a pseudo-gradient for the server optimizer
    (``repro.fl.server_opt``); the resulting update is what Downlink may
    compress before it is applied (the broadcast quantity, §5.2)."""

    def __init__(self, opt):
        self.opt = opt
        self.state = None

    def init(self, params: Any) -> None:
        self.state = self.opt.init(params)

    def __call__(self, server: ServerState, agg: AggregatedRound,
                 downlink: "Downlink", receivers: int,
                 transmit: bool) -> tuple[ServerState, int]:
        with obs_trace.span("server_step"):
            updates, self.state = server_update(
                self.opt, self.state, agg.delta_params, server.params)
            down_bytes = 0
            # the downlink stage span fires even when broadcast compression
            # is inactive — the lifecycle always HAS a downlink leg, and the
            # trace should show all seven stages regardless of config
            with obs_trace.span("downlink", active=downlink.active):
                if downlink.active:
                    updates, down_bytes = downlink.compress(updates,
                                                            receivers,
                                                            transmit)
            server = ServerState(
                params=apply_updates(server.params, updates),
                scales=delta_lib.tree_add(server.scales, agg.delta_scales),
                bn_state=agg.bn_state)
            return server, down_bytes


# ---------------------------------------------------------------- downlink

class Downlink:
    """Stage 6: bidirectional server->clients compression with error
    feedback (§5.2).

    Operates on the server *update* (the quantity actually broadcast) and
    runs it through the wire codec as a params-only message: the engine
    applies the DECODED broadcast and ``down_bytes`` is
    ``receivers * len(payload)``.  For FedAvg(lr=1) the update equals the
    aggregated delta bitwise, matching the seed loop's pre-aggregation
    compression exactly.
    """

    def __init__(self, cfg: ProtocolConfig, step_size: float, params0: Any,
                 codec: comms.Codec, bidirectional: bool):
        self.active = bidirectional and cfg.method != "none"
        self.codec = codec
        self.q = quant_lib.QuantConfig(step_size=step_size,
                                       fine_step_size=cfg.fine_step_size)
        self.spars = sparsify_lib.SparsifyConfig(
            delta=cfg.delta, gamma=cfg.gamma, step_size=step_size,
            unstructured=cfg.unstructured, structured=cfg.structured,
            fixed_sparsity=cfg.fixed_sparsity)
        self.spec = comms.WireSpec(
            params=comms.shape_template(params0), scales=None,
            fine_mask=None, step_size=step_size,
            fine_step_size=cfg.fine_step_size)
        self.residual = jax.tree.map(jnp.zeros_like, params0)
        self.last_payload_bytes = 0

    def compress(self, updates: Any, receivers: int,
                 transmit: bool) -> tuple[Any, int]:
        with obs_trace.span("downlink.compress", receivers=receivers):
            carried = delta_lib.tree_add(updates, self.residual)
            sparse = sparsify_lib.sparsify_tree(carried, self.spars)
            lv = quant_lib.quantize_tree(sparse, self.q)
            if transmit:
                upd = comms.ClientUpdate(
                    levels_params=jax.tree.map(np.asarray, lv),
                    levels_scales=None,
                    recon_params=quant_lib.dequantize_tree(lv, self.q),
                    recon_scales=None)
                payload = self.codec.encode(upd, self.spec)
                recon = self.codec.decode(payload, self.spec).params
                self.last_payload_bytes = len(payload)
                down = receivers * len(payload)
                self._account_payload(payload)
            else:
                recon = quant_lib.dequantize_tree(lv, self.q)
                down = 0
            self.residual = delta_lib.tree_sub(carried, recon)
            return recon, down

    def _account_payload(self, payload: bytes) -> None:
        """Per-section broadcast bytes (one payload, before the receiver
        fan-out the engine's ``downlink.bytes`` counter applies)."""
        m = obs_metrics.get_registry()
        if not m.enabled:
            return
        m.count("downlink.payloads", 1)
        for sec, n in self.codec.payload_sections(payload, self.spec).items():
            m.count(f"downlink.section.{sec}.bytes", n)


# ---------------------------------------------------------------- evaluate

class Evaluate:
    """Stage 7: server-side test accuracy (jitted once per engine)."""

    def __init__(self, evaluate_fn, test_x, test_y):
        self._eval = jax.jit(evaluate_fn)
        self.test_x, self.test_y = test_x, test_y

    def __call__(self, server: ServerState) -> float:
        with obs_trace.span("evaluate"):
            acc = self._eval(server, self.test_x, self.test_y)
            with obs_trace.span("evaluate.wait"):
                acc.block_until_ready()
            return float(acc)


# ---------------------------------------------------------------- schedulers

class RoundScheduler:
    """Policy deciding who trains when and what one aggregation consumes.

    A scheduler is bound to a :class:`~repro.fl.engine.FederatedEngine`
    and drives the engine's OWN ``CohortPlan``/``LocalTrain``/``Uplink``
    stage instances; it never aggregates or steps the server itself — it
    returns a :class:`RoundIntake` and the orchestrator runs
    ``Aggregate``/``ServerStep``/``Downlink``/``Evaluate``.
    """

    mode: str = "?"

    def bind(self, engine, key: jax.Array) -> None:
        raise NotImplementedError

    def next_round(self) -> RoundIntake:
        raise NotImplementedError

    def log_fields(self, rec, intake: RoundIntake) -> dict[str, Any]:
        """Structured per-round log record.  Every value is sourced from
        the RoundRecord / intake the orchestrator just built, so the log
        can never disagree with the run's records (the satellite contract:
        byte and accuracy values match ``RoundRecord`` exactly)."""
        raise NotImplementedError

    def log_line(self, rec, intake: RoundIntake) -> str:
        """Human-readable formatting over :meth:`log_fields`."""
        return self._format(self.log_fields(rec, intake))

    def _format(self, fields: dict[str, Any]) -> str:
        raise NotImplementedError


class SyncScheduler(RoundScheduler):
    """Cohort barrier: one vmapped round per aggregation, channel drops.

    With a traffic model the cohort is availability-filtered (empty
    troughs advance the simulated clock and retry), per-dispatch churn
    coins can lose a participant mid-round (timeout semantics: the server
    still waits, the upload never arrives — treated exactly like a channel
    drop, EF re-injection included, but its bytes are NOT charged), and
    the round's duration gains each participant's simulated compute
    latency.
    """

    mode = "sync"

    def bind(self, engine, key: jax.Array) -> None:
        self.eng = engine
        self.key = key
        self.sim_clock = 0.0
        self.round_idx = 0
        self.churned_total = 0

    def _select_cohort(self) -> np.ndarray:
        """Streaming-regime selection; spins the clock through empty
        availability troughs (bounded)."""
        eng = self.eng
        day = (eng.traffic.cfg.day_s if eng.traffic is not None else 96.0)
        for _ in range(1000):
            idx = eng.cohort.select_stream(self.round_idx, self.sim_clock)
            if len(idx):
                return idx
            self.sim_clock += day / 96.0
        raise RuntimeError(
            "sync scheduler stalled: no client passed the availability "
            "filter after 1000 clock advances; the traffic trace is "
            "pathologically thin")

    def next_round(self) -> RoundIntake:
        eng = self.eng
        self.round_idx += 1
        self.key, kb = jax.random.split(self.key)
        if eng.cohort.streaming:
            idx = self._select_cohort()
        else:
            idx, self.key = eng.cohort.select(self.key)
        clients = [int(c) for c in idx]
        cohort = len(clients)

        try:
            out = eng.local_train.train_cohort(
                kb, idx, eng.server,
                full=eng.cohort.full and cohort == eng.num_clients)
        except EmptyCohortError:
            # nothing to execute (a zero-size cohort selection): surface an
            # all-drop round — no contributions, no server step — and
            # advance the simulated clock one availability-curve step so a
            # traffic-gated run keeps moving
            day = (eng.traffic.cfg.day_s if eng.traffic is not None else 96.0)
            self.sim_clock += day / 96.0
            return RoundIntake([], [], weights=None,
                               sim_time=self.sim_clock, receivers=0)
        contribs = eng.uplink.intake(out, clients)

        traffic = eng.traffic
        lost: list[int] = []
        if traffic is not None and traffic.cfg.churn_rate > 0.0:
            for i in range(cohort):
                if traffic.churned(clients[i], self.round_idx):
                    lost.append(i)
                    contribs[i].payload_bytes = 0  # never uploaded
            self.churned_total += len(lost)

        chan = eng.channel
        if eng.transmit and chan is not None:
            sizes = [c.payload_bytes for c in contribs]
            ref = eng.broadcast_ref_bytes()
            if traffic is None:
                self.sim_clock += chan.round_time(clients, sizes, ref,
                                                  self.round_idx)
            else:
                self.sim_clock += max(
                    (chan.down_time(c, ref, self.round_idx)
                     + traffic.latency(c)
                     + chan.up_time(c, n, self.round_idx)
                     for c, n in zip(clients, sizes)), default=0.0)
            lost.extend(i for i in range(cohort)
                        if i not in lost
                        and chan.dropped(self.round_idx, clients[i]))
        elif traffic is not None:
            # no channel: the barrier waits for the slowest computer
            self.sim_clock += max((traffic.latency(c) for c in clients),
                                  default=0.0)

        survivors = list(range(cohort))
        if lost:
            survivors = [i for i in range(cohort) if i not in lost]
            if eng.protocol_cfg.error_feedback:
                for i in lost:
                    eng.local_train.reinject_residual(
                        clients[i], contribs[i].delta_params)
        for c in contribs:
            c.arrival_time = self.sim_clock
        preagg = None
        if eng.streaming_ingest:
            preagg, survivors = self._fold_streaming(contribs, survivors,
                                                     clients)
        return RoundIntake(contribs, survivors, weights=None,
                           sim_time=self.sim_clock, receivers=cohort,
                           preagg=preagg)

    def _fold_streaming(self, contribs: list[Contribution],
                        survivors: list[int], clients: list[int]):
        """Decode-and-accumulate the surviving payloads (O(1) memory).

        Each survivor's payload folds into the running accumulators in
        cohort order — for the equal-weight sync mean this reproduces the
        gather path's stacked mean (float64 single-pass fold, see
        ``TreeAccumulator``).  Corrupt payloads are quarantined: excluded
        from the survivor set (bytes stay charged, like a drop) with
        their mass re-injected into the client residual under error
        feedback — Eq. 5 via the device-side reconstruction row, since
        the payload never decodes."""
        eng = self.eng
        ing = eng.make_ingest()
        for i in survivors:
            ing.submit(contribs[i].client, contribs[i].payload)
        res = ing.finish()
        if res.rejected:
            rej = {survivors[r.seq] for r in res.rejected}
            survivors = [i for i in survivors if i not in rej]
            if eng.protocol_cfg.error_feedback:
                for i in sorted(rej):
                    eng.local_train.reinject_residual(
                        clients[i], contribs[i].delta_params)
        if not survivors:
            return None, survivors
        if eng.uplink.spec.version == 2:
            mbn = res.bn
        else:
            # v1: BN rides out-of-band as device rows — same stacked mean
            # as the gather Aggregate, never fetched to host
            mbn = tree_mean0(stack_trees(
                [contribs[i].bn_state for i in survivors]))
        return AggregatedRound(
            delta_params=res.delta_params, delta_scales=res.delta_scales,
            bn_state=mbn, survivors=tuple(clients[i] for i in survivors),
            weights=None), survivors

    def log_fields(self, rec, intake: RoundIntake) -> dict[str, Any]:
        fields: dict[str, Any] = {
            "mode": self.mode,
            "round": rec.round,
            "test_acc": rec.test_acc,
            "survivors": len(intake.survivors),
            "cohort": len(intake.contributions),
            "up_bytes": rec.up_bytes,
            "down_bytes": rec.down_bytes,
            "update_sparsity": rec.update_sparsity,
        }
        if self.eng.channel is not None or self.eng.traffic is not None:
            fields["sim_time_s"] = rec.sim_time_s
        if self.churned_total:
            fields["churned_total"] = self.churned_total
        return fields

    def _format(self, f: dict[str, Any]) -> str:
        line = (f"round {f['round']:3d} acc={f['test_acc']:.3f} "
                f"cohort={f['survivors']}/{f['cohort']} "
                f"up={f['up_bytes']/1e6:.3f}MB "
                f"sparsity={f['update_sparsity']:.3f}")
        if "sim_time_s" in f:
            line += f" t_sim={f['sim_time_s']:.2f}s"
        if "churned_total" in f:
            line += f" churned={f['churned_total']}"
        return line


@dataclasses.dataclass
class _InFlight:
    client: int
    start_version: int
    server: ServerState
    finish: float
    seq: int = 0     # global dispatch counter (keys the churn coin, so a
                     # re-dispatched client draws a fresh one)


class BufferedAsyncScheduler(RoundScheduler):
    """FedBuff buffer: M concurrent clients, aggregate every B arrivals
    with staleness weights; heterogeneous latencies drive a simulated
    wall-clock.

    Completions are popped in **dispatch windows**: every in-flight client
    whose compute-finish time lands within ``AsyncConfig.dispatch_window``
    seconds of the earliest finisher trains in ONE executor call
    (``LocalTrain.train_window`` — each row against the server snapshot it
    started from).  ``dispatch_window=0`` (the default) pops exactly one
    completion at a time — the pre-batching behaviour, ties included.
    Contributions enter the buffer ordered by
    ``(arrival_time, client_id)`` — a total order, so async runs are
    reproducible across executor backends (arrival times are simulated,
    never wall-clock).  A window that overfills the buffer aggregates the
    whole buffer (the staleness weights renormalise).
    """

    mode = "async"

    def bind(self, engine, key: jax.Array) -> None:
        self.eng = engine
        acfg = engine.engine_cfg.async_cfg
        self.acfg = acfg
        self.traffic = engine.traffic
        self.stream = engine.cohort.streaming
        key, kl = jax.random.split(key)
        self.concurrency = min(acfg.concurrency, engine.num_clients)
        self.now = 0.0
        self.seq = 0       # dispatches issued (churn-coin keying)
        self.draws = 0     # stream_cohort invocations (sampling keying)
        self.churned_total = 0
        self.saving = 0.0
        if acfg.adaptive_window:
            self.saving = (acfg.call_saving_s
                           if acfg.call_saving_s is not None
                           else load_call_saving())
        self.in_flight: list[_InFlight] = []
        if self.stream:
            # streaming regime (population axis / traffic model): no
            # per-client arrays — replacements come from the hash-based
            # sampler excluding the in-flight set, latencies from the
            # traffic model or a hash-keyed lognormal.  kl is consumed
            # either way (it seeds the latency stream), keeping the key
            # discipline uniform.
            self.latency = None
            self.lat_seed = int(jax.random.randint(kl, (), 0, 2 ** 31 - 1))
            self.busy: set[int] = set()
            self.key = key
            for c in self._stream_draw(self.concurrency):
                self._launch(int(c))
        else:
            self.latency = client_latencies(kl, engine.num_clients, acfg)
            self.available = set(range(engine.num_clients))
            first, key = engine.cohort.select_available(
                key, np.array(sorted(self.available)), self.concurrency)
            self.key = key
            for c in first:
                self.available.discard(int(c))
                self.in_flight.append(_InFlight(
                    int(c), 0, engine.server,
                    self._dispatch_delay(int(c)) + float(self.latency[c]),
                    seq=self._next_seq()))
        # replacements for the window that triggered the last aggregation
        # are deferred until after the server step, so they train from the
        # newest version (otherwise every buffer-filling dispatch starts
        # one version stale)
        self.pending_dispatch = 0
        # executor-call batch sizes (benchmarks/cohort_scaling.py reads
        # this for the async batch-fill ratio)
        self.batch_sizes: list[int] = []

    # -- dispatch plumbing -------------------------------------------------

    def _next_seq(self) -> int:
        s = self.seq
        self.seq += 1
        return s

    def _dispatch_delay(self, client: int) -> float:
        """Model-download leg of a dispatch (channel mode only)."""
        if self.eng.channel is None:
            return 0.0
        return self.eng.channel.down_time(client,
                                          self.eng.broadcast_ref_bytes())

    def _lat(self, client: int) -> float:
        """Simulated compute seconds for one dispatch of ``client``."""
        if self.traffic is not None:
            return self.traffic.latency(client)
        if self.latency is not None:
            return float(self.latency[client])
        # streaming without traffic: the AsyncConfig lognormal, hash-keyed
        # per client so it never depends on population size or order
        if self.acfg.latency_sigma == 0.0:
            return self.acfg.latency_mean
        z = float(prand.normal(self.lat_seed, prand.TAG_LATENCY, client))
        return self.acfg.latency_mean * float(np.exp(
            self.acfg.latency_sigma * z))

    def _stream_draw(self, k: int) -> np.ndarray:
        """One streaming replacement draw (non-strict: an availability
        trough may return fewer than ``k``; the caller re-queues)."""
        if k <= 0:
            return np.empty(0, np.int64)
        eng = self.eng
        accept = None
        if self.traffic is not None:
            traffic, now, rd = self.traffic, self.now, self.draws
            accept = lambda ids: traffic.available(ids, now, rd)
        idx = stream_cohort(
            eng.engine_cfg.sampling.stream_seed, self.draws,
            eng.num_clients, k, accept_fn=accept, exclude=self.busy,
            strict=False)
        self.draws += 1
        return idx

    def _launch(self, client: int) -> None:
        self.busy.add(client)
        self.in_flight.append(_InFlight(
            client, self.eng.version, self.eng.server,
            self.now + self._dispatch_delay(client) + self._lat(client),
            seq=self._next_seq()))

    def _dispatch_one(self) -> None:
        eng = self.eng
        nxt, self.key = eng.cohort.select_available(
            self.key, np.array(sorted(self.available)), 1)
        nxt = int(nxt[0])
        self.available.discard(nxt)
        self.in_flight.append(_InFlight(
            nxt, eng.version, eng.server,
            self.now + self._dispatch_delay(nxt) + float(self.latency[nxt]),
            seq=self._next_seq()))

    def _dispatch(self, n: int) -> int:
        """Dispatch up to ``n`` replacements; returns how many launched
        (the legacy path always launches all ``n``)."""
        if n <= 0:
            return 0
        if not self.stream:
            for _ in range(n):
                self._dispatch_one()
            return n
        idx = self._stream_draw(n)
        for c in idx:
            self._launch(int(c))
        return len(idx)

    def _free(self, client: int) -> None:
        if self.stream:
            self.busy.discard(client)
        else:
            self.available.add(client)

    def _pop_window(self) -> list[_InFlight]:
        """Every in-flight client finishing within ``dispatch_window`` of
        the earliest finisher, in deterministic (finish, client) order.

        ``dispatch_window=0`` pops exactly ONE completion — the
        pre-batching FedBuff behaviour (buffer_size updates per
        aggregation) even when latencies tie exactly (latency_sigma=0
        would otherwise batch the whole in-flight set and silently bypass
        the buffer size); ties break deterministically by client id.

        ``adaptive_window`` replaces the fixed cutoff with greedy merging
        against the measured per-call saving: take finishers in (finish,
        client) order and keep extending the batch while the NEXT
        finisher's marginal wait (gap to the previous finisher) costs less
        simulated time than the executor call it saves — so the window
        tracks the observed arrival density instead of a constant."""
        if self.acfg.adaptive_window:
            order = sorted(self.in_flight, key=lambda f: (f.finish, f.client))
            window = [order[0]]
            for e in order[1:]:
                if e.finish - window[-1].finish > self.saving:
                    break
                window.append(e)
            for e in window:
                self.in_flight.remove(e)
            return window
        if self.acfg.dispatch_window <= 0.0:
            e = min(self.in_flight, key=lambda f: (f.finish, f.client))
            self.in_flight.remove(e)
            return [e]
        t0 = min(f.finish for f in self.in_flight)
        window = sorted(
            (f for f in self.in_flight
             if f.finish <= t0 + self.acfg.dispatch_window),
            key=lambda f: (f.finish, f.client))
        for e in window:
            self.in_flight.remove(e)
        return window

    def next_round(self) -> RoundIntake:
        eng = self.eng
        buffer: list[Contribution] = []
        stalls = 0
        churn_stalls = 0
        while True:
            self.pending_dispatch -= self._dispatch(self.pending_dispatch)
            if not self.in_flight:
                # every slot is waiting out an availability trough (only
                # reachable in the traffic-gated streaming regime):
                # advance the clock one curve step and redraw
                stalls += 1
                if stalls > 1000:
                    raise RuntimeError(
                        "async scheduler stalled: no client passed the "
                        "availability filter after 1000 clock advances")
                self.now += (self.traffic.cfg.day_s / 96.0
                             if self.traffic is not None else 1.0)
                continue
            stalls = 0
            # with a channel the upload leg is appended at pop time, so
            # arrival order approximates compute-finish order (documented
            # simplification)
            window = self._pop_window()
            if self.traffic is not None and self.traffic.cfg.churn_rate > 0.0:
                kept = []
                for e in window:
                    if self.traffic.churned(e.client, e.seq):
                        # mid-round churn: the dispatch vanishes without
                        # uploading — free the slot, re-queue a replacement
                        self.churned_total += 1
                        self._free(e.client)
                        self.pending_dispatch += 1
                    else:
                        kept.append(e)
                window = kept
                if not window:
                    # at churn_rate -> 1 every dispatch can vanish before
                    # uploading, which used to spin this loop forever;
                    # after a bounded number of fully-churned windows the
                    # round is surfaced as an all-drop intake (whatever the
                    # buffer holds, usually nothing) instead of hanging
                    churn_stalls += 1
                    if churn_stalls > 1000:
                        if buffer and eng.streaming_ingest:
                            return self._flush_streaming(buffer)
                        w = (normalized_staleness_weights(
                                [b.staleness for b in buffer],
                                self.acfg.staleness_exponent)
                             if buffer else None)
                        return RoundIntake(buffer,
                                           list(range(len(buffer))),
                                           weights=w, sim_time=self.now,
                                           receivers=self.concurrency)
                    continue
            churn_stalls = 0
            kbs = []
            for _ in window:
                self.key, kb = jax.random.split(self.key)
                kbs.append(kb)
            out = eng.local_train.train_window(
                kbs, [e.client for e in window], [e.server for e in window])
            self.batch_sizes.append(len(window))
            obs_metrics.observe("async.batch_size", len(window))
            contribs = eng.uplink.intake(out, [e.client for e in window])
            for e, c in zip(window, contribs):
                c.staleness = eng.version - e.start_version
                c.arrival_time = e.finish + (
                    eng.channel.up_time(e.client, c.payload_bytes)
                    if eng.channel is not None else 0.0)
                self._free(e.client)
            # deterministic intake order: (arrival_time, client_id) is a
            # total order, so ties (homogeneous latencies) cannot reorder
            # across runs or executor backends; the clock clamp keeps
            # recorded arrivals monotone when a heterogeneous upload leg
            # inverts the compute-finish order
            contribs.sort(key=lambda c: (c.arrival_time, c.client))
            for c in contribs:
                self.now = max(self.now, c.arrival_time)
                c.arrival_time = self.now
            buffer.extend(contribs)

            # replacements are deferred to the loop top (legacy: so the
            # post-aggregation batch trains from the newest version; the
            # streaming regime additionally re-tries short draws there)
            self.pending_dispatch += len(window)
            if len(buffer) >= self.acfg.buffer_size:
                if self.eng.streaming_ingest:
                    return self._flush_streaming(buffer)
                w = normalized_staleness_weights(
                    [b.staleness for b in buffer],
                    self.acfg.staleness_exponent)
                return RoundIntake(buffer, list(range(len(buffer))),
                                   weights=w, sim_time=self.now,
                                   receivers=self.concurrency)

    def _flush_streaming(self, buffer: list[Contribution]) -> RoundIntake:
        """Decode-at-flush: fold the buffered payloads in buffer order
        with the FedBuff staleness weights — the same weights, trees and
        fold order as the gather path's ``weighted_mean_trees``, so the
        aggregate is bit-identical when every payload decodes.

        A corrupt payload drops its entry (async has no residual to
        re-inject into — the bytes stay charged), the weights renormalise
        over the remainder and the fold re-runs; rejects are corruption-
        rare, so the re-decode costs less than holding decoded trees
        around to re-weight."""
        eng = self.eng
        keep = list(range(len(buffer)))
        while keep:
            w = normalized_staleness_weights(
                [buffer[i].staleness for i in keep],
                self.acfg.staleness_exponent)
            ing = eng.make_ingest()
            for j, i in enumerate(keep):
                ing.submit(buffer[i].client, buffer[i].payload,
                           weight=w[j])
            res = ing.finish()
            if not res.rejected:
                break
            rej = {keep[r.seq] for r in res.rejected}
            keep = [i for i in keep if i not in rej]
        if not keep:
            return RoundIntake(buffer, [], weights=None,
                               sim_time=self.now,
                               receivers=self.concurrency)
        if eng.uplink.spec.version == 2:
            mbn = res.bn
        else:
            # v1 BN: device rows through the SAME weighted_mean_trees
            # call the gather aggregate uses (device path, bit-identical)
            mbn = weighted_mean_trees(
                [buffer[i].bn_state for i in keep], w)
        preagg = AggregatedRound(
            delta_params=res.delta_params, delta_scales=res.delta_scales,
            bn_state=mbn, survivors=tuple(buffer[i].client for i in keep),
            weights=w)
        return RoundIntake(buffer, keep, weights=w, sim_time=self.now,
                           receivers=self.concurrency, preagg=preagg)

    def log_fields(self, rec, intake: RoundIntake) -> dict[str, Any]:
        fields: dict[str, Any] = {
            "mode": self.mode,
            "round": rec.round,
            "test_acc": rec.test_acc,
            "sim_time_s": rec.sim_time_s,
            "staleness": [c.staleness for c in intake.contributions],
            "up_bytes": rec.up_bytes,
            "down_bytes": rec.down_bytes,
        }
        if self.churned_total:
            fields["churned_total"] = self.churned_total
        return fields

    def _format(self, f: dict[str, Any]) -> str:
        line = (f"agg {f['round']:3d} acc={f['test_acc']:.3f} "
                f"t_sim={f['sim_time_s']:.2f}s staleness={f['staleness']} "
                f"up={f['up_bytes']/1e6:.3f}MB")
        if "churned_total" in f:
            line += f" churned={f['churned_total']}"
        return line


SCHEDULERS: dict[str, type[RoundScheduler]] = {
    "sync": SyncScheduler,
    "async": BufferedAsyncScheduler,
}
