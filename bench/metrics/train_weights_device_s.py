"""train_weights_device_s: device seconds per round of the operations
under the client round's ``train_weights`` scope (the weight epoch),
by self time."""
from bench.lib import profile_events

SCOPE = "train_weights"


def read(ctx):
    d = profile_events.of(ctx)
    if d is None:
        return None
    ns = d.scope_self_ns(profile_events.STAGES).get(SCOPE, 0.0)
    return profile_events.per_round_s(ns, ctx)
