"""Engine throughput: per-round wall-clock + parallel-uplink speedups.

Two measurements, emitted together as ``BENCH_engine.json``:

(a) **Uplink encode+decode throughput** — the host wire hot path.  A
    synthetic cohort of N clients (default 8) is pushed through the REAL
    ``repro.fl.rounds.Uplink`` stage (codec registry payloads, both
    directions, order-preserving) serially and through thread/process
    pools.  Per-message codec state makes the round-trips embarrassingly
    parallel; what limits the win is the GIL: numpy-dominated codecs
    (fp16 casts, int8 kernel) release it and profit from threads, the
    pure-Python entropy coders (nnc-cabac bit loop) need the fork pool.

(b) **Per-round wall-clock** — a few rounds of representative scenarios
    (sync barrier, buffered async, schema v2) with mean seconds/round.

``--smoke`` shrinks the tensors and rounds for CI; the default sizes are
chosen so the headline number (``best_thread_speedup``) reflects a
realistic few-MB model update.  Scale knob: REPRO_BENCH_SCALE.
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

from repro.core.protocol import ProtocolConfig, ServerState
from repro.core import quant as quant_lib
from repro.fl import EngineConfig, Uplink, run_scenario
from repro.comms import ClientUpdate
from repro.launch.cache import enable_compile_cache

from _harness import time_best, write_report


# ------------------------------------------------------------- uplink bench

def _bench_shapes(smoke: bool):
    if smoke:
        return {"conv1": (16, 3, 3, 3), "conv2": (32, 16, 3, 3),
                "fc": (64, 512)}
    return {"conv1": (64, 3, 3, 3), "conv2": (128, 64, 3, 3),
            "fc": (256, 4096)}


def _synthetic_cohort(num_clients: int, smoke: bool, density: float = 0.05):
    """Stacked (levels, recon) updates consistent under the default step."""
    shapes = _bench_shapes(smoke)
    q = quant_lib.QuantConfig()
    rng = np.random.default_rng(0)
    lv = {k: (rng.integers(-40, 41, (num_clients,) + s)
              * (rng.random((num_clients,) + s) < density)).astype(np.int32)
          for k, s in shapes.items()}
    recon = {k: lv[k].astype(np.float32) * np.float32(q.step_size)
             for k in lv}
    s_lv = {"s0": rng.integers(-3, 4, (num_clients, 16)).astype(np.int32)}
    s_recon = {k: v.astype(np.float32) * np.float32(q.fine_step_size)
               for k, v in s_lv.items()}
    bn = {"bn": {"mean": rng.normal(size=(num_clients, 32))
                 .astype(np.float32),
                 "var": rng.random((num_clients, 32)).astype(np.float32)}}
    params0 = {k: np.zeros(s, np.float32) for k, s in shapes.items()}
    scales0 = {"s0": np.zeros((16,), np.float32)}
    bn0 = {"bn": {"mean": np.zeros((32,), np.float32),
                  "var": np.ones((32,), np.float32)}}
    server = ServerState(params=params0, scales=scales0, bn_state=bn0)
    return server, (lv, s_lv, recon, s_recon, bn)


def _client_updates(stacks, num_clients: int, with_bn: bool, needs):
    """Per-client updates carrying only the trees the codec reads — the
    same thinning Uplink.fetch applies, so pickle costs on the process
    path match the engine's."""
    lv, s_lv, recon, s_recon, bn = stacks
    want_lv = "levels" in needs
    want_rc = "recon" in needs

    def row(tree, i):
        import jax
        return jax.tree.map(lambda x: x[i], tree)

    return [ClientUpdate(row(lv, i) if want_lv else None,
                         row(s_lv, i) if want_lv else None,
                         row(recon, i) if want_rc else None,
                         row(s_recon, i) if want_rc else None,
                         bn=row(bn, i) if with_bn else None)
            for i in range(num_clients)]


def _make_uplink(server, codec: str, workers: int, executor: str,
                 wire_schema: int) -> Uplink:
    cfg = ProtocolConfig(name="bench", method="sparse", batch_size=32)
    ecfg = EngineConfig(codec=codec, uplink_workers=workers,
                        uplink_executor=executor, wire_schema=wire_schema)
    return Uplink(cfg, ecfg, server)


def _time_roundtrips(uplink: Uplink, upds, repeats: int):
    best, results = time_best(lambda: uplink.roundtrip_all(upds),
                              repeats=repeats, label="uplink.bench")
    assert all(n > 0 for n, _ in results)
    return best, results


def bench_uplink(num_clients: int, smoke: bool, workers: int,
                 codecs=("fp16", "int8-blockscale", "golomb", "nnc-cabac"),
                 wire_schema: int = 1, repeats: int = 2):
    server, stacks = _synthetic_cohort(num_clients, smoke)
    rows = []
    for codec in codecs:
        serial = _make_uplink(server, codec, 0, "thread", wire_schema)
        upds = _client_updates(stacks, num_clients,
                               with_bn=(wire_schema == 2),
                               needs=serial.codec.needs)
        t_serial, results = _time_roundtrips(serial, upds, repeats)
        row = {"codec": codec, "clients": num_clients,
               "payload_bytes": sum(n for n, _ in results),
               "serial_s": round(t_serial, 4)}
        kinds = ["thread"]
        if serial.codec.fork_safe:   # jax-dispatching codecs refuse fork
            kinds.append("process")
        for kind in kinds:
            pooled = _make_uplink(server, codec, workers, kind, wire_schema)
            try:
                t, _ = _time_roundtrips(pooled, upds, repeats)
            finally:
                pooled.close()
            row[f"{kind}_s"] = round(t, 4)
            row[f"{kind}_speedup"] = round(t_serial / t, 2)
        rows.append(row)
        print(f"# uplink {codec}: " + " ".join(
            f"{k}={row[f'{k}_s']}s"
            + (f" ({row[f'{k}_speedup']}x)" if k != "serial" else "")
            for k in ["serial"] + kinds), file=sys.stderr, flush=True)
    return rows


# ------------------------------------------------------- device encode bench

def _stacked_round_output(stacks):
    """The synthetic cohort as a device-resident stacked RoundOutput view
    (what Codec.encode_cohort reads)."""
    import jax
    import jax.numpy as jnp
    from types import SimpleNamespace

    lv, s_lv, recon, s_recon, bn = stacks
    dev = lambda t: jax.tree.map(jnp.asarray, t)
    return SimpleNamespace(
        levels_params=dev(lv), levels_scales=dev(s_lv),
        recon_delta_params=dev(recon), recon_delta_scales=dev(s_recon),
        bn_state=dev(bn))


def _host_encode(codec, spec, out, k):
    """The host path the device encode replaces: bulk device_get of the
    trees the codec reads, per-client slicing, encode_batch."""
    import jax

    need_lv = "levels" in codec.needs
    need_rc = "recon" in codec.needs or spec.ternary
    trees = jax.device_get((
        out.levels_params if need_lv else None,
        out.levels_scales if need_lv else None,
        out.recon_delta_params if need_rc else None,
        out.recon_delta_scales if need_rc else None))

    def row(tree, i):
        return (None if tree is None
                else jax.tree.map(lambda x: x[i], tree))

    upds = [ClientUpdate(*(row(t, i) for t in trees))
            for i in range(k)]
    return codec.encode_batch(upds, spec, clients=list(range(k)))


def bench_device_encode(num_clients: int, smoke: bool,
                        codecs=("int8-blockscale", "golomb", "nnc-cabac"),
                        repeats: int = 3):
    """Host encode_batch vs device encode_cohort on the same cohort.

    Payloads are asserted byte-identical in-bench before timing — the
    speedup column can never be bought with a bytes change."""
    from repro import comms

    server, stacks = _synthetic_cohort(num_clients, smoke)
    out = _stacked_round_output(stacks)
    rows = []
    for name in codecs:
        codec = comms.get_codec(name)
        spec = _make_uplink(server, name, 0, "thread", 1).spec
        host_payloads = _host_encode(codec, spec, out, num_clients)
        dev_payloads = codec.encode_cohort(out, spec,
                                           clients=list(range(num_clients)))
        assert dev_payloads is not None, f"{name}: no device fast path"
        assert [bytes(p) for p in dev_payloads] == \
            [bytes(p) for p in host_payloads], f"{name}: bytes diverged"
        t_host, _ = time_best(
            lambda: _host_encode(codec, spec, out, num_clients),
            repeats=repeats, label=f"host.{name}")
        t_dev, _ = time_best(
            lambda: codec.encode_cohort(out, spec,
                                        clients=list(range(num_clients))),
            repeats=repeats, label=f"device.{name}")
        rows.append({"codec": name, "clients": num_clients,
                     "payload_bytes": sum(len(p) for p in host_payloads),
                     "host_s": round(t_host, 4),
                     "device_s": round(t_dev, 4),
                     "device_speedup": round(t_host / t_dev, 2)})
        print(f"# device-encode {name}: host={t_host:.4f}s "
              f"device={t_dev:.4f}s ({rows[-1]['device_speedup']}x)",
              file=sys.stderr, flush=True)
    return rows


# ------------------------------------------------------------- round bench

def bench_rounds(rounds: int, scenarios=("sync_full_fedavg_fsfl",
                                         "async_b4_fsfl", "bnwire_v2_full")):
    rows = []
    for name in scenarios:
        res = run_scenario(name, rounds=rounds)
        walls = [r.wall_s for r in res.records]
        rows.append({
            "scenario": name, "rounds": len(res.records),
            "mean_round_s": round(float(np.mean(walls)), 3),
            "first_round_s": round(walls[0], 3),  # includes jit compile
            "steady_round_s": round(float(np.mean(walls[1:])), 3)
            if len(walls) > 1 else round(walls[0], 3),
            "total_up_bytes": res.records[-1].cum_bytes,
        })
        print(f"# rounds {name}: mean={rows[-1]['mean_round_s']}s "
              f"steady={rows[-1]['steady_round_s']}s",
              file=sys.stderr, flush=True)
    return rows


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small tensors + 1 round per scenario (CI)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--workers", type=int, default=None,
                    help="pool size (default: min(4, cpu count))")
    ap.add_argument("--out", default="BENCH_engine.json")
    ap.add_argument("--device-encode", choices=["off", "both"],
                    default="off",
                    help="'both': add host-vs-device encode_cohort rows")
    ap.add_argument("--guard", action="store_true",
                    help="fail unless the int8-blockscale device encode is "
                         ">=10x over the host path (needs --device-encode "
                         "both)")
    args = ap.parse_args()

    workers = args.workers or min(4, os.cpu_count() or 1)
    scale = float(os.environ.get("REPRO_BENCH_SCALE", "1"))
    rounds = 1 if args.smoke else max(2, int(3 * scale))

    uplink_rows = bench_uplink(args.clients, smoke=args.smoke,
                               workers=workers)
    best = max(uplink_rows, key=lambda r: r["thread_speedup"])
    best_proc = max((r for r in uplink_rows if "process_speedup" in r),
                    key=lambda r: r["process_speedup"])
    report = {
        "mode": "smoke" if args.smoke else "full",
        "clients": args.clients,
        "workers": workers,
        "uplink": uplink_rows,
        "best_thread_speedup": {"codec": best["codec"],
                                "speedup": best["thread_speedup"]},
        "best_process_speedup": {"codec": best_proc["codec"],
                                 "speedup": best_proc["process_speedup"]},
        "rounds": bench_rounds(rounds),
    }
    if args.device_encode != "off":
        report["device_encode"] = bench_device_encode(args.clients,
                                                      smoke=args.smoke)
    write_report(args.out, report)
    if not args.smoke and report["best_thread_speedup"]["speedup"] < 1.5:
        print("WARNING: thread-pooled uplink under 1.5x serial",
              file=sys.stderr)
    if args.guard:
        if args.device_encode == "off":
            sys.exit("--guard needs --device-encode both")
        int8 = next(r for r in report["device_encode"]
                    if r["codec"] == "int8-blockscale")
        if int8["device_speedup"] < 10.0:
            sys.exit(f"GUARD FAILED: int8-blockscale device encode "
                     f"{int8['device_speedup']}x < 10x over host at "
                     f"K={int8['clients']}")
        print(f"# guard OK: int8-blockscale device encode "
              f"{int8['device_speedup']}x (>=10x)", file=sys.stderr)


if __name__ == "__main__":
    main()
