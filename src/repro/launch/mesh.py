"""Production meshes.

A function (not a module constant) so importing never touches jax device
state — the dry-run sets XLA_FLAGS before any jax initialization.

Single pod: (data=16, model=16) = 256 chips (TPU v5e-256).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis joins
``data`` for batch/FSDP sharding — for the recommender this widens the
paper's user-group axis (``w = 16`` in its ``n_c = n_i^2 + w*n_i`` knob).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_cpu_mesh",
           "make_grid_mesh"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes, the sharding mode this repo's
    code is written for (propagation and ``with_sharding_constraint``).
    Since jax 0.9 ``make_mesh`` defaults to ``Explicit`` axes, under
    which the grid reshape in ``core/distributed.py`` and the constraints
    in ``sharding/ctx.py`` raise."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_cpu_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — used in tests."""
    return make_mesh((data, model), ("data", "model"))


def make_grid_mesh(grid):
    """A (data=g, model=n_i) mesh matching an S&R ``GridSpec``.

    One device per worker (``core/distributed.py`` maps item splits to
    ``model`` and user groups to ``data``). Raises if the host does not
    expose enough devices — start the process with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to simulate.
    """
    needed = grid.n_c
    have = len(jax.devices())
    if have < needed:
        raise ValueError(
            f"S&R grid needs {needed} devices ({grid.n_i}x{grid.g}); "
            f"only {have} available"
        )
    return make_mesh((grid.g, grid.n_i), ("data", "model"))
