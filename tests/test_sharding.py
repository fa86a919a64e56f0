"""Distribution tests on a real (8-way host) device mesh.

Run in subprocesses because XLA_FLAGS must be set before jax initializes —
and the rest of the suite needs the default single device.
"""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_sub(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # The child simulates its mesh on host devices and must never compete
    # with the parent for an accelerator.
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_grid_step_matches_vmap_pipeline():
    """shard_map workers on a 2x4 mesh == vmap-simulated workers."""
    run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core import distributed as dist, routing
        from repro.core.disgd import DisgdHyper
        from repro.core.pipeline import StreamConfig, make_worker_step, init_states
        from repro.launch.mesh import make_mesh

        # model axis = item splits (n_i=2), data axis = user groups (g=4).
        mesh = make_mesh((4, 2), ("data", "model"))
        n_i = mesh.shape["model"]; g = mesh.shape["data"]
        grid = routing.GridSpec(n_i, g - n_i)  # n_c = 8 workers
        cfg = StreamConfig(algorithm="disgd", grid=grid, micro_batch=256,
                           hyper=DisgdHyper(u_cap=64, i_cap=32))
        cap = cfg.bucket_capacity

        rng = np.random.default_rng(0)
        users = rng.integers(0, 200, 256); items = rng.integers(0, 100, 256)
        keys = (items % grid.n_i) * grid.g + (users % grid.g)
        buckets, kept, _ = routing.bucket_dispatch_np(keys, grid.n_c, cap)
        ev_u = np.where(buckets >= 0, users[np.clip(buckets, 0, None)], -1)
        ev_i = np.where(buckets >= 0, items[np.clip(buckets, 0, None)], -1)

        # vmap path (worker-major order: key = row*g + col)
        states_v = init_states(cfg)
        step_v = make_worker_step(cfg)
        sv, hits_v, eval_v = step_v(states_v,
                                    jnp.asarray(ev_u, jnp.int32),
                                    jnp.asarray(ev_i, jnp.int32))

        # shard_map path on the mesh grid (n_i, g) layout
        states_g = dist.init_grid_states(cfg, mesh)
        step_g = dist.make_grid_step(cfg, mesh)
        eg_u = jnp.asarray(ev_u.reshape(grid.n_i, grid.g, cap), jnp.int32)
        eg_i = jnp.asarray(ev_i.reshape(grid.n_i, grid.g, cap), jnp.int32)
        sg, hits_g, eval_g = step_g(states_g, eg_u, eg_i)

        np.testing.assert_array_equal(
            np.asarray(hits_v).reshape(grid.n_i, grid.g, cap),
            np.asarray(hits_g))
        for a, b in zip(jax.tree.leaves(sv), jax.tree.leaves(sg)):
            np.testing.assert_allclose(
                np.asarray(a).reshape(np.asarray(b).shape),
                np.asarray(b), rtol=1e-6, atol=1e-7)
        print("grid == vmap OK")
    """)


def test_small_mesh_train_step_runs_sharded():
    """A smoke arch trains on a real 2x4 mesh with the production specs."""
    run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.data.tokens import make_batch
        from repro.models.factory import build
        from repro.optim import adamw_init
        from repro.sharding import specs as specs_lib
        from repro.sharding.ctx import use_mesh
        from repro.launch.mesh import make_mesh

        cfg = get_smoke_config("olmoe_1b_7b")
        bundle = build(cfg)
        mesh = make_mesh((2, 4), ("data", "model"))
        with use_mesh(mesh):
            params = bundle.init(jax.random.key(0))
            shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 specs_lib.param_specs(bundle.decls, mesh),
                                 is_leaf=lambda x: isinstance(x, P))
            params = jax.device_put(params, shard)
            opt = adamw_init(params)
            batch = {k: jnp.asarray(v)
                     for k, v in make_batch(cfg, 4, 64, 0).items()}
            step = jax.jit(lambda p, o, b: bundle.train_step(p, o, b, 0))
            p2, o2, m = step(params, opt, batch)
            assert np.isfinite(float(m["loss"])), m
            # Params are actually distributed:
            leaves = jax.tree.leaves(p2)
            assert any(len(l.sharding.device_set) > 1 for l in leaves)
            print("sharded train OK, loss", float(m["loss"]))
    """)


def test_dryrun_machinery_on_8_devices():
    """The dry-run path itself (specs, lowering, roofline) on a tiny mesh."""
    run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.configs.shapes import InputShape
        from repro.models import module as mod
        from repro.models.factory import build
        from repro.roofline import analyze_compiled
        from repro.sharding import specs as specs_lib
        from repro.sharding.ctx import use_mesh
        from repro.launch.mesh import make_mesh

        cfg = get_smoke_config("h2o_danube_1p8b")
        bundle = build(cfg)
        mesh = make_mesh((2, 4), ("data", "model"))
        shape = InputShape("t", 64, 4, "prefill")
        with use_mesh(mesh):
            pshapes = mod.param_shapes(bundle.decls)
            pshard = jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                specs_lib.param_specs(bundle.decls, mesh),
                is_leaf=lambda x: isinstance(x, P))
            specs = bundle.input_specs(shape)
            lowered = jax.jit(bundle.prefill,
                              in_shardings=(pshard, None)).lower(
                pshapes, specs)
            compiled = lowered.compile()
            roof = analyze_compiled(compiled)
            assert roof.flops > 0
            print("dryrun-small OK flops", roof.flops,
                  "coll", roof.coll_bytes)
    """)


def test_multipod_mesh_shapes():
    run_sub("""
        from repro.launch.mesh import make_production_mesh
        m = make_production_mesh()
        assert dict(m.shape) == {"data": 16, "model": 16}
        m2 = make_production_mesh(multi_pod=True)
        assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
        print("mesh OK")
    """, devices=512)
