"""Where a Pallas kernel runs decides how: compiled by Mosaic on a TPU,
executed by the Pallas interpreter everywhere else.

Every kernel wrapper takes ``interpret=None`` and resolves it here, inside
its trace. The platform is the one the call is placed on: the device set
by ``jax.default_device`` when there is one, otherwise the default
backend. Both are part of jit's cache key, so one process can trace the
same kernel for the chip and, under ``jax.default_device(cpu)``, for the
host. An explicit ``True``/``False`` is honoured as given.
"""
from __future__ import annotations

import jax


def target_platform() -> str:
    """Platform name ('tpu', 'cpu', ...) that un-placed work runs on."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def interpret_mode(interpret: bool | None = None) -> bool:
    """True where the kernel must run in the interpreter (no TPU)."""
    if interpret is not None:
        return bool(interpret)
    return target_platform() != "tpu"
