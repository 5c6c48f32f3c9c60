"""golomb_decode_host_s: host seconds per round inside the exp-Golomb
section parse's ``golomb.decode`` spans (``golomb.decode_egk`` and
``decode_egk_jump``; their union), read from the profiler's host events."""
from bench.lib import profile_events

SPANS = ("golomb.decode",)


def read(ctx):
    d = profile_events.of(ctx)
    if d is None:
        return None
    return profile_events.per_round_s(d.span_union_ns(SPANS), ctx)
