"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis carries only data parallelism + cross-pod gradient reduction (DCN-ish
traffic), never tensor parallelism.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax use).
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["make_production_mesh", "make_host_mesh", "make_test_mesh"]

_HOST_COUNT_FLAG = "--xla_force_host_platform_device_count"


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes: the logical-axis rules place
    tensors with ``with_sharding_constraint``, which ``Explicit`` axes
    (the ``make_mesh`` default since JAX 0.7) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Whatever devices exist, flat on the "data" axis."""
    return _auto_mesh((len(jax.devices()),), ("data",))


def make_test_mesh(n: int = 8, axis: str = "cores") -> Mesh:
    """The first ``n`` devices of the default backend as a 1-axis ring,
    the layout ``exec.runtime`` executes period programs on.

    On the CPU backend it first asks for ``n`` host devices via XLA_FLAGS;
    that is only effective if jax has not initialized its backends yet, so
    call it as early as possible (tests/conftest.py forces 8 for the whole
    suite).  On a TPU host the flag is inert and the chips are the ring.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if _HOST_COUNT_FLAG not in flags:
        # No-op if a backend already exists, harmless either way.
        os.environ["XLA_FLAGS"] = f"{_HOST_COUNT_FLAG}={n} {flags}".strip()
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, found {len(devices)}; set "
            f"XLA_FLAGS={_HOST_COUNT_FLAG}={n} before the first jax call")
    return Mesh(np.asarray(devices[:n]), (axis,))
