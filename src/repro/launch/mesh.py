"""Production mesh builders (assignment: MULTI-POD DRY-RUN step 1).

Functions, not module-level constants — importing this module never
touches jax device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benches see the real single device.

Every axis is ``Auto``: the models place activations with
``with_sharding_constraint`` (:func:`repro.parallel.sharding.shard`),
which ``jax.make_mesh``'s default ``Explicit`` axes refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16×16 = 256 chips single-pod, 2×16×16 multi-pod.

    Axes: ``pod`` (DCN), ``data`` (DP/FSDP), ``model`` (TP/EP/SP).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for unit tests (requires forced host device count)."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
