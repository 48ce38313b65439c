"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — required because the dry-run
overrides the device count via XLA_FLAGS before first jax init, while
tests and benches must keep seeing 1 device.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.  Multi-pod: a leading
    pure-DP "pod" axis (2, 16, 16) = 512 chips across the DCN boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 2, pod: int | None = None):
    """Small mesh over however many host devices exist (tests)."""
    if pod:
        shape, axes = (pod, data, model), ("pod", "data", "model")
    else:
        shape, axes = (data, model), ("data", "model")
    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        raise ValueError(f"need {n} devices, have {len(jax.devices())}")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_flat_mesh(n_cores: int, name: str = "cores"):
    """1-D mesh used by the distributed SNN simulator (one neuron partition
    per device)."""
    return jax.make_mesh((n_cores,), (name,), axis_types=(AxisType.Auto,))
