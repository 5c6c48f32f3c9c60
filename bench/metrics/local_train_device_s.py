"""local_train_device_s: device seconds per round of the jitted cohort
client round (the vmapped ``client_round`` of ``fl/executors.py``, whose
compiled program is named ``jit_named_client_round``)."""

PROGRAM = "jit_named_client_round"


def read(ctx):
    ns = ctx.red.module_ns(PROGRAM)
    return ns / 1e9 / ctx.rounds if ns else None
