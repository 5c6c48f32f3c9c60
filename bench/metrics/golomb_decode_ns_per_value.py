"""golomb_decode_ns_per_value: host nanoseconds per decoded exp-Golomb
value: the union of the ``golomb.decode`` spans over the sum of their
``values`` attributes, read from the profiler's host events.  A parser
that is faster per codeword moves it; one that is handed fewer codewords
does not."""
from bench.lib import profile_events

SPANS = ("golomb.decode",)


def read(ctx):
    d = profile_events.of(ctx)
    values = d.span_attr_sum(SPANS, "values") if d is not None else 0
    return d.span_union_ns(SPANS) / values if values else None
