"""One run of one cell: set-up, the measured window, the check.

Set-up makes the data from the seed on the device, builds one
``FederatedEngine`` and drives it through its first rounds (the rounds the
reference follows), then until a round compiles nothing.  The window runs
whole rounds of that same engine until ``seconds`` have passed, each
ending in ``Evaluate``'s host fetch.  A traced run instead records
``TRACE_ROUNDS`` rounds under ``jax.profiler`` with the program's spans on
(``record_spans``).
Once the window has closed and the peak memory is read, the engine is
freed and the plain reference (``bench/ref``) runs the same first rounds;
``bench/lib/check.py`` compares them.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

from bench.lib import cell as cell_lib
from bench.lib import check, data, flops
from bench.lib import trace as trace_lib

CHECK_ROUNDS = 2      # rounds the reference follows
TRACE_ROUNDS = 3      # rounds a traced run records
MAX_WARM = 6          # extra warm-up rounds while rounds still compile
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


class CompileClock:
    """Counts JAX's backend compiles (and their seconds) while registered."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


def _precision(cfg: dict):
    import jax

    p = cfg.get("matmul_precision", "default")
    return (contextlib.nullcontext() if p == "default"
            else jax.default_matmul_precision(p))


def server_state(engine) -> dict:
    import jax

    s = engine.server
    return jax.device_get({"params": s.params, "scales": s.scales,
                           "bn": s.bn_state})


def record_spans(engine) -> None:
    """Record the program's ``repro.obs`` spans without its metrics
    registry: ``telemetry="trace"`` also turns on per-round metrics that
    fetch every client's update and residual to the host
    (``local_train.metrics``, about 3 s a round for ResNet-18 on a TPU
    v5e), work the measured window does not do."""
    from repro import obs

    tel = obs.Telemetry("off")
    tel.recorder = obs.SpanRecorder()
    engine.telemetry = tel


def one_round(engine):
    return engine.run(1).records[0]


def first_rounds(engine, rounds: int = CHECK_ROUNDS, clock=None) -> dict:
    """Drive ``engine`` through its first rounds, keeping what the check
    compares (and, given a ``clock``, the compiles of the last round)."""
    out = {"loss": [], "acc": [], "bytes": [],
           "state0": server_state(engine)}
    for r in range(rounds):
        before = clock.count if clock else 0
        rec = one_round(engine)
        out["last_compiles"] = (clock.count if clock else 0) - before
        out["loss"].append(rec.train_loss)
        out["acc"].append(rec.test_acc)
        out["bytes"].append(rec.up_bytes)
        if r == 0:
            out["state1"] = server_state(engine)
    out["state_last"] = server_state(engine)
    return out


def require_chips(n: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices


def _load_reader(name: str):
    path = cell_lib.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class MetricContext:
    """What a per-layer reader (``bench/metrics/<name>.py``) may read."""

    def __init__(self, red, cell, device_kind, rounds):
        self.red = red
        self.cell = cell
        self.device_kind = device_kind
        self.rounds = rounds
        self.window_s = red.window_ns / 1e9
        self.busy_s = red.busy_ns() / 1e9
        self.peaks = json.loads((cell_lib.BENCH / "peaks.json").read_text())

    def peak(self) -> dict:
        return flops.peak(self.peaks, self.device_kind)

    def reference_model(self):
        from bench.ref import federated

        m = self.cell["config"]["model"]
        return federated.load("models", m["builder"]).build(**m["args"])

    def round_flops(self) -> int:
        cfg, mix = self.cell["config"], self.cell["mix"]
        init, apply = self.reference_model()
        t = cfg["data"]["task"]
        fwd = flops.forward_flops(init, apply,
                                  (t["size"], t["size"], t["channels"]))
        sizes = data.split_sizes(cfg["data"]["samples"], mix["clients"],
                                 cfg["data"]["train_frac"],
                                 cfg["data"]["val_frac"])
        return flops.round_flops(fwd, sizes, mix["clients"],
                                 cfg["protocol"]["batch_size"],
                                 cfg["protocol"]["scale_subepochs"])


def _window(engine, seconds: float):
    t = time.perf_counter()
    n = up = 0
    while True:
        up += one_round(engine).up_bytes
        n += 1
        if time.perf_counter() - t >= seconds:
            break
    return time.perf_counter() - t, n, up


def _traced(engine, trace_dir: Path):
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    marks = []
    # device activity and annotations only: the Python tracer would
    # record every call of the host stages and slow them several-fold
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        for _ in range(TRACE_ROUNDS):
            with jax.profiler.TraceAnnotation(trace_lib.ROUND_ANNOTATION):
                marks.append(time.perf_counter_ns())
                one_round(engine)
    finally:
        jax.profiler.stop_trace()
    spans = [(s.name, s.t0_ns, s.t0_ns + s.dur_ns)
             for s in engine.telemetry.recorder.snapshot()]
    return marks, spans


def _reduce(cell, trace_dir, marks, spans, device_kind, out_dir):
    events = trace_lib.load_xplane(trace_dir)
    offset = marks[0] - events["rounds"][0][1]
    spans = [(n, s - offset, e - offset) for n, s, e in spans]
    trace_lib.save_excerpt(events, spans, out_dir / "excerpt.json.gz")
    red = trace_lib.Reduction(events, spans)
    ctx = MetricContext(red, cell, device_kind, red.n_rounds)
    metrics = {}
    for m in cell["per_layer"]:
        value = _load_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = {"device_ops": trace_lib.top(red.top_ops()),
                 "idle_gaps": trace_lib.top(red.idle_by_span())}
    return metrics, ctx.busy_s, ctx.window_s, breakdown


def reference_readings(cell: dict, splits: dict, engine_key, **kw) -> dict:
    from bench.ref import federated

    return federated.run(cell["config"], cell["mix"], splits, engine_key,
                         CHECK_ROUNDS, **kw)


def run(cell: dict, seed: int, seconds: float, traced: bool, *,
        out_dir: Path, t0: float, log=sys.stderr) -> dict:
    """One run; returns the result line's object (``checks`` last)."""
    import jax

    dev = require_chips(cell["chips"])[0]
    out_dir.mkdir(parents=True, exist_ok=True)
    k_data, k_engine = jax.random.split(data.root_key(seed))
    splits = jax.block_until_ready(
        data.make_splits(k_data, cell["config"]["data"],
                         cell["mix"]["clients"]))
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        with _precision(cell["config"]):
            engine = cell_lib.build_engine(cell, splits, k_engine, "off")
            if traced:
                record_spans(engine)
            prog = first_rounds(engine, clock=clock)
            compiled = prog["last_compiles"]
            for _ in range(MAX_WARM):
                if not compiled:
                    break
                before = clock.count
                one_round(engine)
                compiled = clock.count - before
            setup_s = time.perf_counter() - t0
            before = clock.count
            if traced:
                marks, spans = _traced(engine, out_dir / "trace")
                n, elapsed, up = TRACE_ROUNDS, None, None
            else:
                elapsed, n, up = _window(engine, seconds)
            window_compiles = clock.count - before
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
    stats = dev.memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")
    del engine
    gc.collect()
    print(f"window: {n} rounds, {window_compiles} compiles; set-up "
          f"{setup_s:.3f} s, {clock.count} compiles in "
          f"{clock.seconds:.3f} s", file=log)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"], "memory_peak_bytes": peak_bytes}
    result = {"correct": False, "attempted": n, "failed": 0}
    if traced:
        metrics, busy_s, window_s, breakdown = _reduce(
            cell, out_dir / "trace", marks, spans, dev.device_kind, out_dir)
        device.update(busy_s=busy_s, window_s=window_s)
        result.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        values = {"setup_s": setup_s, "round_s": elapsed / n,
                  "up_bytes_per_round": up / n}
        result.update(metrics={m["name"]: {"value": values[m["name"]],
                                           "unit": m["unit"]}
                               for m in cell["end_to_end"]}, device=device)

    t_ref = time.perf_counter()
    ref = reference_readings(cell, splits, k_engine)
    print(f"reference: {time.perf_counter() - t_ref:.3f} s", file=log)
    numbers = check.compare(prog, ref)
    limits = cell["limits"]
    result["correct"] = (check.verdict(numbers, limits)
                         and all(b > 0 for b in prog["bytes"]))
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in limits}
    print(f"rounds compared: {CHECK_ROUNDS}; program loss {prog['loss']} "
          f"acc {prog['acc']}; reference loss {ref['loss']} acc "
          f"{ref['acc']}", file=log)
    print("not compared: " + ", ".join(
        f"{k} {v!r}" for k, v in numbers.items() if k not in limits),
        file=log)
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=log)
    if not all(math.isfinite(x) for x in prog["loss"]):
        result["correct"] = False
    return result
