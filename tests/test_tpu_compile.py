"""Compile the main-path kernels for a described TPU v5e at deployment widths.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described rather than attached, so what Mosaic or XLA
would refuse on the chip (misaligned tiles, VMEM overuse, a program that
does not fit HBM) fails here. Widths are the MovieLens-25M Table 1 shape
on a ``GridSpec(4)`` worker: ``u_cap = 38,784``, ``i_cap = 6,784``, 512
events per bucket.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library at once, and
every test worker imports this file.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.core.pipeline import StreamConfig
from repro.core.routing import GridSpec
from repro.kernels import ops

U_CAP, I_CAP, MB, K = 38_784, 6_784, 512, 10


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # A compile for a described chip cannot be read back from the cache.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, one_chip, *shapes, donate=()):
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    return compiled.as_text()


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _tables():
    i32 = jnp.int32
    return (_s((U_CAP,), i32), _s((I_CAP,), i32), _s((U_CAP,), i32),
            _s((I_CAP,), i32), _s((U_CAP,), i32), _s((I_CAP,), i32),
            _s((), i32))


@pytest.mark.parametrize("pairwise", [False, True],
                         ids=["isgd", "bpr_pairwise"])
def test_factor_update_compiles(one_chip, pairwise):
    def step(uv, iv, rated, tabs, ev_u, ev_i, us, is_, js, ini_u, ini_i):
        return ops.factor_update(
            uv, iv, rated, tabs,
            (ev_u, ev_i, us, is_, js if pairwise else None, ini_u, ini_i),
            eta=0.05, lam=0.01, interpret=False)

    ev = _s((MB,), jnp.int32)
    text = _compiled_text(
        step, one_chip, _s((U_CAP, K), jnp.float32),
        _s((I_CAP, K), jnp.float32), _s((U_CAP, I_CAP), bool), _tables(),
        ev, ev, ev, ev, ev, _s((MB, K), jnp.float32),
        _s((MB, K), jnp.float32))
    assert "tpu_custom_call" in text


def test_dics_update_compiles(one_chip):
    def step(co, cnt, rated, tabs, ev_u, ev_i, us, is_):
        return ops.dics_update(co, cnt, rated, tabs, (ev_u, ev_i, us, is_),
                               interpret=False)

    ev = _s((MB,), jnp.int32)
    text = _compiled_text(
        step, one_chip, _s((I_CAP, I_CAP), jnp.float32),
        _s((I_CAP,), jnp.float32), _s((U_CAP, I_CAP), bool), _tables(),
        ev, ev, ev, ev)
    assert "tpu_custom_call" in text


def test_fused_topn_compiles(one_chip):
    fn = partial(ops.fused_topn, top_n=10, interpret=False)
    text = _compiled_text(
        fn, one_chip, _s((64, K), jnp.float32), _s((I_CAP, K), jnp.float32),
        _s((64, I_CAP), bool), _s((I_CAP,), jnp.int32))
    assert "tpu_custom_call" in text


def test_dics_topn_compiles(one_chip):
    fn = partial(ops.dics_topn, top_n=10, k_nn=10, interpret=False)
    text = _compiled_text(
        fn, one_chip, _s((I_CAP, I_CAP), jnp.float32),
        _s((I_CAP,), jnp.float32), _s((64, I_CAP), bool), _s((64,), bool),
        _s((I_CAP,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("algorithm", ["disgd", "bpr", "dics"])
def test_engine_pallas_worker_compiles_for_16_workers(one_chip, monkeypatch,
                                                      algorithm):
    """The engine's vmapped worker step over a GridSpec(4) grid takes the
    kernel path (the dispatch is steered to the TPU branch here, since
    the process itself runs on the CPU)."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    from repro.core import algorithm as algorithm_lib

    algo = algorithm_lib.get_algorithm(algorithm)
    hyper = algo.default_hyper()._replace(u_cap=U_CAP, i_cap=I_CAP)
    cfg = StreamConfig(algorithm=algorithm, grid=GridSpec(4),
                       micro_batch=MB, capacity_factor=2.0, hyper=hyper,
                       backend="pallas")
    worker = engine.make_pallas_worker_fn(cfg)
    n_c, cap = cfg.grid.n_c, cfg.bucket_capacity
    states = jax.eval_shape(
        lambda: jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_c,) + x.shape),
            algo.init_state(cfg.resolved_hyper())))
    ev = _s((n_c, cap), jnp.int32)
    # States are donated, as the engine's scan carry updates them in place.
    text = _compiled_text(worker, one_chip, states, ev, ev, donate=(0,))
    assert "tpu_custom_call" in text
