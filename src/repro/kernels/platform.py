"""Where the Pallas kernels run, decided in one place.

Mosaic compiles the kernels for a TPU only; on every other backend they
run in the Pallas interpreter (the tests' CPU runs).  Every kernel entry
point takes ``interpret=None`` and resolves it here, so a process whose
default backend is a TPU never interprets a kernel, and a run that asks
for the chip calls :func:`require_tpu` instead of falling back.
"""
from __future__ import annotations

import jax


def interpret() -> bool:
    """True unless JAX's default backend is a TPU."""
    return jax.default_backend() != "tpu"


def resolve(interpret_flag: bool | None) -> bool:
    """An explicit ``interpret`` wins; ``None`` means :func:`interpret`."""
    return interpret() if interpret_flag is None else interpret_flag


def require_tpu() -> list:
    """JAX's devices, which must be TPUs; raises ``RuntimeError`` where JAX
    found no TPU, so a chip run never carries on on the CPU."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU found: JAX's devices are {devices[0].platform} "
            f"({len(devices)} x {devices[0].device_kind})")
    return devices
