"""cabac_decode_host_s: host seconds per round inside the CABAC decoder's
``cabac.decode`` spans (``Decoder.decode_bits``; their union), read from
the profiler's host events."""
from bench.lib import profile_events

SPANS = ("cabac.decode",)


def read(ctx):
    d = profile_events.of(ctx)
    if d is None:
        return None
    return profile_events.per_round_s(d.span_union_ns(SPANS), ctx)
