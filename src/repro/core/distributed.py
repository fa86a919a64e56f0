"""The S&R worker grid on a real device mesh (shard_map).

``core/pipeline.py`` simulates workers with ``vmap``; this module places
them on mesh coordinates instead — item splits on ``model``, user groups on
``data`` (× ``pod`` when multi-pod, which widens the paper's user axis via
its ``w`` knob). Worker state lives device-resident across micro-batches;
the *only* cross-device communication in the whole update path is the
host-side bucketing of incoming events (the stream router in Figure 1 of
the paper) — the training itself is purely local, faithfully
shared-nothing.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import algorithm as algorithm_lib
from repro.core.pipeline import StreamConfig
from repro.core.routing import GridSpec

__all__ = [
    "flat_worker_sharding",
    "grid_axes",
    "grid_from_mesh",
    "make_grid_step",
    "make_flat_grid_worker",
    "init_grid_states",
    "grid_state_specs",
]


def grid_axes(mesh):
    """(item_axis, user_axes) mesh mapping for the S&R grid."""
    user_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return "model", user_axes


def grid_from_mesh(mesh) -> GridSpec:
    """The S&R ``GridSpec`` a device mesh realizes (item axis x user axes).

    The inverse of ``launch.mesh.make_grid_mesh``: configs built for an
    existing mesh should derive their grid from it rather than repeat the
    shape — and a rescale that re-carves the mesh gets its new ``GridSpec``
    from here.
    """
    item_ax, user_axes = grid_axes(mesh)
    n_i = mesh.shape[item_ax]
    g = int(np.prod([mesh.shape[a] for a in user_axes]))
    return GridSpec.rect(n_i, g)


def flat_worker_sharding(mesh):
    """Sharding of worker-major ``[n_c, ...]`` states on a grid mesh.

    Worker ``key = row * g + col`` lives at mesh coordinate
    ``(model=row, data=col)``: the flat axis splits over ``model`` then
    the user axes, which is the layout ``make_flat_grid_worker`` reshapes
    to ``(n_i, g)`` without moving data.
    """
    item_ax, user_axes = grid_axes(mesh)
    return jax.sharding.NamedSharding(mesh, P((item_ax,) + user_axes))


def init_grid_states(cfg: StreamConfig, mesh):
    """Stacked worker states shaped (n_i, g, ...) for the mesh grid."""
    n_i, g = grid_from_mesh(mesh).shape
    assert cfg.grid.shape == (n_i, g), (cfg.grid, n_i, g)
    one = algorithm_lib.get_algorithm(cfg.algorithm).init_state(
        cfg.resolved_hyper())
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_i, g) + x.shape), one
    )


def grid_state_specs(cfg: StreamConfig, mesh):
    item_ax, user_axes = grid_axes(mesh)
    user = user_axes if len(user_axes) > 1 else user_axes[0]
    states = init_grid_states(cfg, mesh)
    return jax.tree.map(lambda x: P(item_ax, user), states)


def _make_grid_step_unjitted(cfg: StreamConfig, mesh):
    """shard_map(worker_step) over the device grid (not jitted).

    Args (to the returned fn):
      states: stacked worker states (n_i, g, ...), sharded on the grid.
      ev_u, ev_i: int32[n_i, g, capacity] pre-bucketed events (-1 pad).
    Returns: (new_states, hits, evaluated) with the same grid layout.
    """
    item_ax, user_axes = grid_axes(mesh)
    user = user_axes if len(user_axes) > 1 else user_axes[0]
    state_spec = jax.tree.map(lambda _: P(item_ax, user),
                              init_grid_states(cfg, mesh))
    ev_spec = P(item_ax, user, None)

    one = algorithm_lib.get_algorithm(cfg.algorithm).make_worker_step(
        cfg.resolved_hyper(), jax.random.key(cfg.seed))

    def local(states, ev_u, ev_i):
        st = jax.tree.map(lambda x: x[0, 0], states)
        s2, hits, ev = one(st, (ev_u[0, 0], ev_i[0, 0]))
        return (
            jax.tree.map(lambda x: x[None, None], s2),
            hits[None, None],
            ev[None, None],
        )

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(state_spec, ev_spec, ev_spec),
        out_specs=(state_spec, ev_spec, ev_spec),
        check_vma=False,
    )


def make_grid_step(cfg: StreamConfig, mesh):
    """jit(shard_map(worker_step)) over the device grid."""
    return jax.jit(_make_grid_step_unjitted(cfg, mesh))


def make_flat_grid_worker(cfg: StreamConfig, mesh):
    """Engine adapter: worker-major [n_c, ...] <-> mesh grid (n_i, g, ...).

    The device-resident engine (``core/engine.py``) lays buckets out
    worker-major (``key = row * g + col``); this wraps the shard_map grid
    step so each S&R worker runs at its mesh coordinate while the engine
    scan stays layout-agnostic.
    """
    n_i, g = grid_from_mesh(mesh).shape
    assert cfg.grid.shape == (n_i, g), (cfg.grid, n_i, g)
    grid_step = _make_grid_step_unjitted(cfg, mesh)

    def worker(states, ev_u, ev_i):
        to_grid = lambda x: x.reshape((n_i, g) + x.shape[1:])
        states_g = jax.tree.map(to_grid, states)
        s2, hits, ev = grid_step(
            states_g, to_grid(ev_u), to_grid(ev_i)
        )
        flat = lambda x: x.reshape((n_i * g,) + x.shape[2:])
        return jax.tree.map(flat, s2), flat(hits), flat(ev)

    return worker
