"""round_mfu, in percent: the useful operations of one round (``bench/lib/flops.py``
``round_flops``) over the traced round time times the chip's bf16 peak."""


def read(ctx):
    peak = ctx.peak()["bf16_flops_per_s"] * ctx.cell["chips"]
    return 100.0 * ctx.round_flops() * ctx.rounds / (ctx.window_s * peak)
