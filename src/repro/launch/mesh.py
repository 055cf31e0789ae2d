"""Production mesh builders.

`make_production_mesh` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; callers decide when the
512 placeholder devices exist (see launch/dryrun.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    Axes: ``data`` is the batch/FSDP axis, ``model`` the tensor/expert
    parallel axis; ``pod`` (multi-pod only) is pure data parallelism whose
    collectives are the only cross-DCN traffic.  Axes are ``Auto``: the
    code places values with ``with_sharding_constraint`` hints, which
    ``Explicit`` axes (the ``make_mesh`` default) refuse.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for subprocess-based distributed tests (8 CPU devices)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
