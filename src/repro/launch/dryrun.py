"""Multi-pod dry-run: lower + compile every (arch x input-shape) combination
on the production meshes and record memory/cost/collective analysis.

MUST be run as its own process: ``main`` sets ``XLA_FLAGS`` (512 simulated
host devices, or ``REPRO_XLA_FLAGS``), which XLA reads at first backend
init.  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b \
        --shape train_4k [--multi-pod] [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all   # full sweep

Writes/updates a JSON results file (benchmarks/roofline reads it).
"""
import argparse
import json
import os
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of collective ops in the (post-SPMD) HLO."""
    sizes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
             "u16": 2}
    kinds = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    out = {k: 0 for k in kinds}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"^.*?%?[\w.-]+\s*=\s*(\([^)]*\)|[^ ]+)\s+([\w-]+)", ls)
        if not m:
            continue
        op = m.group(2)
        kind = next((k for k in kinds if op.startswith(k)), None)
        if kind is None:
            continue
        # output shape(s) ~ bytes moved (operand ~= result for these ops)
        nbytes = 0
        for dt, dims in shape_re.findall(m.group(1)):
            if dt not in sizes:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * sizes[dt]
        out[kind] += nbytes
    out["total"] = sum(out[k] for k in kinds)
    return out


def model_flops(cfg, shape_spec) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE) useful-FLOPs estimate."""
    from repro.dist.train_step import compute_specs  # noqa
    n = param_count(cfg)
    if cfg.n_experts:
        # active experts only
        dense_part = n - moe_param_count(cfg)
        n = dense_part + moe_param_count(cfg) * cfg.top_k / cfg.n_experts
    tokens = shape_spec.global_batch * (shape_spec.seq_len
                                        if shape_spec.kind == "train" else
                                        (shape_spec.seq_len
                                         if shape_spec.kind == "prefill" else 1))
    mult = 6 if shape_spec.kind == "train" else 2
    return mult * n * tokens


def param_count(cfg) -> int:
    import math
    import repro.models.transformer as tr
    a = jax.eval_shape(lambda k: tr.init_params(k, cfg, tr.SINGLE),
                       jax.ShapeDtypeStruct((2,), jnp.uint32))
    return sum(math.prod(l.shape) if l.shape else 1
               for l in jax.tree.leaves(a))


def moe_param_count(cfg) -> int:
    return cfg.n_layers * cfg.n_experts * 3 * cfg.d_model * cfg.d_ff


def run_one(arch: str, shape: str, multi_pod: bool, microbatches: int | None,
            compression: bool = True, scale_step: bool = True,
            block: int = 1024, clients_per_pod: int | None = None,
            parallel_block: bool = False, sp_int8: bool = False,
            moe_impl: str | None = None, decode_int8: bool = False,
            decode_resident: bool = False) -> dict:
    import dataclasses

    from repro.configs import base as cbase
    from repro.dist.collectives import MeshCompression
    from repro.dist.sharding import MeshLayout, choose_layout, make_plan
    from repro.dist import serve_step as serve_lib
    from repro.dist import train_step as train_lib
    from repro.launch.mesh import make_production_mesh
    from repro.models import decode as decode_lib

    cfg = cbase.get(arch)
    sspec = cbase.SHAPES[shape]
    if not cbase.supports_shape(cfg, shape):
        return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                "status": "skip", "reason": "long_500k needs sub-quadratic"}
    if shape == "long_500k":
        cfg = cbase.long_variant(cfg)
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16,
                              parallel_block=parallel_block, sp_int8=sp_int8)
    if moe_impl:
        cfg = dataclasses.replace(cfg, moe_impl=moe_impl)

    mesh = make_production_mesh(multi_pod=multi_pod)
    pod = 2 if multi_pod else 1
    n = param_count(cfg)
    layout = choose_layout(n, pod, 16, 16)
    if clients_per_pod:
        layout = MeshLayout(pod, 16, 16, clients_per_pod)

    t0 = time.time()
    rec = {"arch": arch, "shape": shape, "multi_pod": multi_pod,
           "params": n, "layout": dataclasses.asdict(layout),
           "compression": compression}

    if sspec.kind == "train":
        per_chip = sspec.global_batch // (pod * 16)
        mb = min(microbatches or default_microbatches(cfg), per_chip)
        comp = MeshCompression(enabled=compression, block=block)
        settings = train_lib.TrainSettings(microbatches=mb, compression=comp,
                                           scale_step=scale_step)
        plan = make_plan(cfg, 16)
        make, sds, sh, specs = train_lib.make_train_step(
            cfg, layout, plan, mesh, settings)
        batch_sds = cbase.input_specs(cfg, shape)
        fn = make(batch_sds)
        state_sh = jax.tree.map(lambda s: s, sh)
        batch_sh = train_lib.batch_shardings(cfg, layout, mesh, batch_sds)
        lowered = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                          out_shardings=(state_sh, None)).lower(sds, batch_sds)
        rec["microbatches"] = mb
    elif sspec.kind == "prefill":
        fn, in_sds, in_sh, plan = serve_lib.make_prefill_step(
            cfg, layout, mesh, sspec.global_batch, sspec.seq_len)
        (p_sds, batch_sds) = in_sds
        (p_sh, b_sh) = in_sh
        lowered = jax.jit(fn, in_shardings=(p_sh[0], p_sh[1], b_sh),
                          out_shardings=None).lower(
            p_sds[0], p_sds[1], batch_sds)
    else:  # decode
        cache_len = decode_lib.effective_cache_len(cfg, sspec.seq_len)
        if decode_resident:
            layout = MeshLayout(pod, 16, 16, clients_per_pod=16)  # fsdp = 1
            rec["layout"] = dataclasses.asdict(layout)
        fn, in_sds, in_sh, plan = serve_lib.make_decode_step(
            cfg, layout, mesh, sspec.global_batch, cache_len,
            quant_int8=decode_int8)
        (p_sds, c_sds, t_sds) = in_sds
        (p_sh, c_sh, t_sh) = in_sh
        lowered = jax.jit(fn, in_shardings=(p_sh + (c_sh, t_sh)),
                          out_shardings=None).lower(
            *(p_sds + (c_sds, t_sds)))
        rec["cache_len"] = cache_len
        rec["decode_int8"] = decode_int8

    rec["lower_s"] = round(time.time() - t0, 1)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 1)

    mem = compiled.memory_analysis()
    rec["memory"] = {
        "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
        "generated_code_size_bytes": getattr(mem, "generated_code_size_in_bytes", None),
    }
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    rec["cost"] = {k: float(v) for k, v in (cost or {}).items()
                   if isinstance(v, (int, float)) and (
                       k in ("flops", "bytes accessed") or
                       k.startswith("bytes accessed"))}
    hlo = compiled.as_text()
    rec["collectives"] = collective_bytes(hlo)
    rec["model_flops"] = model_flops(cfg, sspec)
    rec["status"] = "ok"
    return rec


def default_microbatches(cfg) -> int:
    # keep per-chip activation residency bounded; heuristics by d_model*layers
    big = cfg.d_model * cfg.n_layers
    if big >= 12288 * 80:
        return 16
    if big >= 4096 * 40:
        return 8
    if big >= 2048 * 24:
        return 4
    return 2


def main():
    os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_FLAGS")
                               or "--xla_force_host_platform_device_count=512")
    from repro.launch import require_dist
    require_dist()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int)
    ap.add_argument("--no-compression", action="store_true")
    ap.add_argument("--no-scale-step", action="store_true")
    ap.add_argument("--block", type=int, default=1024)
    ap.add_argument("--clients-per-pod", type=int)
    ap.add_argument("--parallel-block", action="store_true")
    ap.add_argument("--sp-int8", action="store_true")
    ap.add_argument("--moe-impl")
    ap.add_argument("--decode-int8", action="store_true")
    ap.add_argument("--decode-resident", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="benchmarks/dryrun_results.json")
    args = ap.parse_args()

    from repro.configs import base as cbase
    combos = ([(args.arch, args.shape, args.multi_pod)] if not args.all else
              [(a, s, mp) for a in cbase.ARCH_MODULES
               for s in cbase.SHAPES for mp in (False, True)])

    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    for arch_mod, shape, mp in combos:
        arch = arch_mod.replace("_", "-") if "-" not in arch_mod else arch_mod
        arch = {"internlm2-1-8b": "internlm2-1.8b",
                "qwen2-vl-72b": "qwen2-vl-72b"}.get(arch, arch)
        key = f"{arch}|{shape}|{'2pod' if mp else '1pod'}"
        if args.tag:
            key += f"|{args.tag}"
        try:
            rec = run_one(arch, shape, mp, args.microbatches,
                          compression=not args.no_compression,
                          scale_step=not args.no_scale_step,
                          block=args.block,
                          clients_per_pod=args.clients_per_pod,
                          parallel_block=args.parallel_block,
                          sp_int8=args.sp_int8, moe_impl=args.moe_impl,
                          decode_int8=args.decode_int8,
                          decode_resident=args.decode_resident)
        except Exception as e:  # noqa
            rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        results[key] = rec
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        status = rec["status"]
        extra = (f" compile={rec.get('compile_s')}s coll={rec.get('collectives', {}).get('total', 0)/1e9:.2f}GB"
                 if status == "ok" else rec.get("reason", rec.get("error", "")))
        print(f"[dryrun] {key}: {status}{extra}", flush=True)
        if status == "error":
            print(rec["trace"][-1500:], flush=True)


if __name__ == "__main__":
    main()
