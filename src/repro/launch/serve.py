"""Serving launcher.

Two front doors share this entry point:

* **FL ingest server** (default, no ``--arch``): delegates every argument
  to ``repro.launch.ingest_serve`` — the decode-and-accumulate uplink
  pipeline (``repro.fl.ingest``) serving a cohort of encoded payloads and
  reporting payloads/s and MB/s.

      PYTHONPATH=src python -m repro.launch.serve --k 32 --engine speculative

* **Transformer prefill+decode** (``--arch <id>``): batched prefill then
  step-wise decode for any config id, as before.

      PYTHONPATH=src python -m repro.launch.serve --arch mamba2-370m --steps 8
      [--trace-out FILE]   (span-trace the loop; Chrome trace-event JSON,
                            opens at https://ui.perfetto.dev)
"""
import argparse
import sys

import jax

from repro import obs
from repro.obs import trace as obs_trace


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if not any(a == "--arch" or a.startswith("--arch=") for a in argv):
        from repro.launch import ingest_serve
        return ingest_serve.main(argv)

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--trace-out", default=None,
                    help="write prefill/decode spans as Chrome trace-event "
                         "JSON")
    args = ap.parse_args(argv)

    from repro.configs import get, make_inputs
    from repro.models import decode as decode_lib
    from repro.models import transformer
    from repro.models.common import UNSHARDED
    from repro.models.transformer import SINGLE

    tel = obs.make_telemetry("trace" if args.trace_out else "off")
    cfg = get(args.arch).reduced()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg, SINGLE)
    extras = {}
    if cfg.family == "encdec":
        extras["enc_embeds"] = make_inputs(jax.random.PRNGKey(1), cfg,
                                           args.batch, args.prompt_len
                                           )["enc_embeds"]
    prompts = jax.random.randint(jax.random.PRNGKey(2),
                                 (args.batch, args.prompt_len), 0, cfg.vocab)
    with tel.activate():
        with obs_trace.span("serve.prefill", arch=args.arch,
                            batch=args.batch, prompt_len=args.prompt_len):
            nxt, cache = decode_lib.prefill(params, prompts, cfg, SINGLE,
                                            UNSHARDED,
                                            args.prompt_len + args.steps,
                                            **extras)
        step = jax.jit(lambda c, t: decode_lib.decode_step(
            params, c, t, cfg, SINGLE, UNSHARDED))
        toks = [nxt]
        for i in range(args.steps - 1):
            with obs_trace.span("serve.decode_step", step=i):
                nxt, cache = step(cache, nxt)
            toks.append(nxt)
    for b in range(args.batch):
        print(f"seq{b}:", [int(t[b]) for t in toks])
    if args.trace_out:
        n = tel.export_chrome_trace(args.trace_out)
        print(f"trace: {args.trace_out} ({n} events)")


if __name__ == "__main__":
    main()
