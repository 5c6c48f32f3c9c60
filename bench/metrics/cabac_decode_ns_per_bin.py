"""cabac_decode_ns_per_bin: host nanoseconds per decoded context-coded bin:
the union of the ``cabac.decode`` spans over the sum of their ``bins``
attributes, read from the profiler's host events.  A decoder that is
faster per bin moves it; one that is handed fewer bins does not."""
from bench.lib import profile_events

SPANS = ("cabac.decode",)


def read(ctx):
    d = profile_events.of(ctx)
    bins = d.span_attr_sum(SPANS, "bins") if d is not None else 0
    return d.span_union_ns(SPANS) / bins if bins else None
