"""fold_host_s: host seconds per round in the ``aggregate`` span (the
Aggregate stage's fold of the decoded cohort)."""


def read(ctx):
    ns = ctx.red.span_ns("aggregate")
    return ns / 1e9 / ctx.rounds if ns else None
