"""device_idle_share, in percent: 1 - busy / window over the traced rounds, busy being
the union of the device operations' intervals (averaged over the chips)."""


def read(ctx):
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
