"""End-to-end driver: federated training of the paper's thinned VGG11 on the
synthetic CIFAR task for a few hundred steps (paper §5.6 setting, scaled to
this container).

Runs the full Table-2 pipeline — Eqs. (2)+(3)/fixed-rate sparsification,
filter scaling with E sub-epochs + accept-if-improves, uniform quantization,
DeepCABAC byte measurement, FedAvg aggregation — and writes a checkpoint of
the final server model.

    PYTHONPATH=src python examples/federated_cifar.py [--rounds N]
    [--clients C] [--full]   (--full = paper-size thinned VGG11)
    [--scenario NAME]        (run a named engine scenario instead; see
                              `repro.fl.list_scenarios()` — adds client
                              sampling / server optimizers / async rounds)
    [--executor serial|vmap|sharded]  (cohort execution backend; "sharded"
                              lays the client axis across visible devices)
    [--population N]         (virtual population over the data shards; the
                              cohort streams through the client-state store)
    [--store memory|sharded] (eager vs. lazy/spill client-state backend)
    [--traffic PRESET]       (diurnal / churn traffic trace presets)
    [--telemetry MODE]       (off | metrics | trace round telemetry)
    [--trace-out FILE]       (Chrome trace-event JSON for Perfetto)
    [--metrics-out FILE]     (per-round metrics snapshots as JSONL)
"""
import argparse
import dataclasses

import jax

from repro import checkpoint
from repro.core.fsfl import run_federated
from repro.core.protocol import ProtocolConfig
from repro.data import federated, synthetic
from repro.fl import (TRAFFIC_PRESETS, get_scenario, list_scenarios,
                      run_scenario)
from repro.launch.cache import enable_compile_cache
from repro.models import cnn


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=None,
                    help="default: 10, or the scenario's registered rounds")
    ap.add_argument("--clients", type=int, default=None,
                    help="default: 4, or the scenario's client count")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--bidirectional", action="store_true")
    ap.add_argument("--scenario", choices=list_scenarios(), default=None)
    ap.add_argument("--wire-schema", type=int, choices=(1, 2), default=None,
                    help="2 = BN statistics travel inside every codec "
                         "payload (scenario runs only)")
    ap.add_argument("--uplink-workers", type=int, default=None,
                    help="parallel per-client wire encode+decode "
                         "(scenario runs only)")
    ap.add_argument("--uplink-batch", action="store_true",
                    help="cohort-batched uplink: code the whole cohort "
                         "through the codec batch API in <= workers pool "
                         "tasks (scenario runs only)")
    ap.add_argument("--executor", choices=("serial", "vmap", "sharded"),
                    default=None,
                    help="cohort execution backend: per-client jit loop, "
                         "one vmapped call (default), or the cohort axis "
                         "sharded across visible devices (scenario runs "
                         "only)")
    ap.add_argument("--population", type=int, default=None,
                    help="virtual population size: the scenario's --clients "
                         "data shards back this many hash-mapped clients; "
                         "per-client state lives in the configured store "
                         "(scenario runs only; sync scenarios need a "
                         "cohort_size)")
    ap.add_argument("--store", choices=("memory", "sharded"), default=None,
                    help="client-state backend: eager in-memory (legacy) or "
                         "sharded+lazy with LRU spill-to-disk (scenario "
                         "runs only)")
    ap.add_argument("--traffic", choices=sorted(TRAFFIC_PRESETS),
                    default=None,
                    help="trace-driven traffic preset: diurnal availability "
                         "curves / device-class latency / mid-round churn "
                         "(scenario runs only)")
    ap.add_argument("--telemetry", choices=("off", "metrics", "trace"),
                    default=None,
                    help="round-lifecycle telemetry: per-round metrics "
                         "snapshots, or full span tracing (scenario runs "
                         "only)")
    ap.add_argument("--trace-out", default=None,
                    help="write the recorded spans as Chrome trace-event "
                         "JSON (open at https://ui.perfetto.dev; implies "
                         "--telemetry trace)")
    ap.add_argument("--metrics-out", default=None,
                    help="stream per-round metrics snapshots to this JSONL "
                         "file (implies --telemetry metrics)")
    ap.add_argument("--out", default="/tmp/fsfl_server.ckpt")
    args = ap.parse_args()

    scenario = get_scenario(args.scenario) if args.scenario else None
    if scenario is None and (args.wire_schema is not None
                             or args.uplink_workers is not None
                             or args.uplink_batch
                             or args.executor is not None
                             or args.population is not None
                             or args.store is not None
                             or args.traffic is not None
                             or args.telemetry is not None
                             or args.trace_out is not None
                             or args.metrics_out is not None):
        ap.error("--wire-schema/--uplink-workers/--uplink-batch/--executor/"
                 "--population/--store/--traffic/--telemetry/--trace-out/"
                 "--metrics-out need --scenario")
    if args.trace_out is not None:
        args.telemetry = "trace"
    elif args.metrics_out is not None and args.telemetry is None:
        args.telemetry = "metrics"
    if args.clients is None:
        args.clients = scenario.num_clients if scenario else 4
    if args.rounds is None and scenario is None:
        args.rounds = 10  # scenario path: None defers to the registered rounds

    x, y = synthetic.make_image_dataset(jax.random.PRNGKey(0),
                                        synthetic.CIFAR_LIKE,
                                        1920 if args.full else 640)
    splits = federated.split_federated(jax.random.PRNGKey(1), x, y, args.clients)
    model = (cnn.vgg11_thinned(10) if args.full else
             cnn.make_vgg("vgg_small", [8, 16, 32], 10, 3, dense_width=16,
                          pool_after=(0, 1, 2)))

    if scenario is not None:
        if args.bidirectional:
            scenario = dataclasses.replace(scenario, bidirectional=True)
        if args.wire_schema is not None:
            scenario = dataclasses.replace(scenario,
                                           wire_schema=args.wire_schema)
        if args.uplink_workers is not None:
            scenario = dataclasses.replace(scenario,
                                           uplink_workers=args.uplink_workers)
        if args.uplink_batch:
            scenario = dataclasses.replace(scenario, uplink_batch=True)
        if args.executor is not None:
            scenario = dataclasses.replace(scenario, executor=args.executor)
        if args.population is not None:
            scenario = dataclasses.replace(scenario,
                                           population=args.population)
        if args.store is not None:
            scenario = dataclasses.replace(scenario, store=args.store)
        if args.traffic is not None:
            scenario = dataclasses.replace(
                scenario, traffic=TRAFFIC_PRESETS[args.traffic])
        if args.telemetry is not None:
            scenario = dataclasses.replace(scenario,
                                           telemetry=args.telemetry,
                                           metrics_out=args.metrics_out)
        res = run_scenario(scenario, rounds=args.rounds,
                           model=model, splits=splits, verbose=True)
        if args.trace_out is not None:
            n = res.telemetry.export_chrome_trace(args.trace_out)
            print(f"trace: {args.trace_out} ({n} events; open at "
                  "https://ui.perfetto.dev)")
    else:
        cfg = ProtocolConfig(
            name="fsfl", method="sparse", scaling=True, error_feedback=True,
            fixed_sparsity=0.96, structured=False, scale_subepochs=2,
            scale_lr=2e-2, scale_schedule="cawr", batch_size=32, local_lr=2e-3,
            total_rounds=args.rounds)
        res = run_federated(model, cfg, splits, args.rounds,
                            jax.random.PRNGKey(42), verbose=True,
                            bidirectional=args.bidirectional)
    final = res.records[-1]
    print(f"\nfinal acc={final.test_acc:.3f} "
          f"bytes={final.cum_bytes/1e6:.3f} MB "
          f"sparsity={final.update_sparsity:.3f}")
    # checkpoint the final server model (restore with repro.checkpoint)
    n = checkpoint.save(args.out, {
        "acc": final.test_acc,
        "params": res.server.params,
        "scales": res.server.scales,
        "bn_state": res.server.bn_state,
    })
    print(f"checkpoint: {args.out} ({n} bytes)")


if __name__ == "__main__":
    main()
