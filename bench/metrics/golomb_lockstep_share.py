"""golomb_lockstep_share: the share of the exp-Golomb codewords that were
parsed in lockstep with other messages' sections, in %: 100 x the sum of
the ``golomb.decode`` spans' ``lockstep_values`` over the sum of their
``values``, read from the profiler's host events.  A parse that advances
the same section of several messages together counts its values; a
section parsed alone counts none, and spans without the attribute (a
program that never parses in lockstep) read 0."""
from bench.lib import profile_events

SPANS = ("golomb.decode",)


def read(ctx):
    d = profile_events.of(ctx)
    values = d.span_attr_sum(SPANS, "values") if d is not None else 0
    if not values:
        return None
    return 100.0 * d.span_attr_sum(SPANS, "lockstep_values") / values
