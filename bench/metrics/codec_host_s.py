"""codec_host_s: host seconds per round inside the codec's encode and
decode spans (their union, so nested calls count once)."""

SPANS = ("codec.encode", "codec.decode", "codec.encode_batch",
         "codec.decode_batch")


def read(ctx):
    ns = ctx.red.span_union_ns(SPANS)
    return ns / 1e9 / ctx.rounds if ns else None
