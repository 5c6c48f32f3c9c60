"""host_wait_device_s: host seconds per round spent waiting for the device
(the union of the ``uplink.fetch.wait`` and ``evaluate.wait`` spans: the
client round before its fetch, the test accuracy before its read), read
from the profiler's host events."""
from bench.lib import profile_events

SPANS = ("uplink.fetch.wait", "evaluate.wait")


def read(ctx):
    d = profile_events.of(ctx)
    if d is None:
        return None
    return profile_events.per_round_s(d.span_union_ns(SPANS), ctx)
