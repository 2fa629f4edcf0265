"""Production mesh construction.

A FUNCTION (not module-level state) so importing this module never touches
jax device state.  Single pod = 256 chips (16 x 16, axes data x model);
multi-pod = 2 pods = 512 chips (2 x 16 x 16, axes pod x data x model).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis of type Auto: the one call site of
    ``jax.make_mesh`` and ``jax.sharding.AxisType`` (lint ECO501/502), so
    every mesh in the repo is built the same way."""
    # repro-lint: disable=ECO501 -- THE call site every other module uses
    auto = jax.sharding.AxisType.Auto
    # repro-lint: disable=ECO502 -- THE call site every other module uses
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh on the real local device (CPU tests/examples)."""
    return make_mesh((1, 1), ("data", "model"))
