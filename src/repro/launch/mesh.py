"""Production mesh builders.

Functions (never module-level constants) so importing this module touches no
jax device state -- the dry-run must set XLA_FLAGS before first jax init.

Every mesh built here has `AxisType.Auto` axes: the models, the store and
the sharded search are written for compiler-propagated shardings, while
`jax.make_mesh` on its own now defaults to Explicit axes.
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Sequence[jax.Device] | None = None) -> Mesh:
    """`jax.make_mesh` with every axis `AxisType.Auto`."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) data x model single pod; (2, 16, 16) pod x data x model for
    the 2-pod = 512-chip deployment."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} -- set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import (see launch/dryrun.py)")
    return make_mesh(shape, axes, devices=devices[:n])


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host has (tests/examples): (n/mp, mp) data x model."""
    devices = jax.devices()
    n = len(devices)
    mp = max(1, min(model_parallel, n))
    return make_mesh((n // mp, mp), ("data", "model"),
                     devices=devices[: (n // mp) * mp])
