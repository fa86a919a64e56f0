"""Device-resident engine vs the host reference pipeline.

The engine (``core/engine.py``) must match the host loop event for event
whenever its bounded re-queue suffices: same routing, same prequential
bits, same end-of-stream drain. The one intentional divergence is
backpressure — the host carry queue is unbounded, the engine's is a
fixed device buffer whose overruns are *dropped and counted* (see
``test_bounded_requeue_counts_drops``). These tests pin both the
equivalence and the accounting.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.prop import given, settings, st

from repro.core import engine, routing, state as state_lib
from repro.core.disgd import DisgdHyper
from repro.core.forgetting import ForgettingConfig
from repro.core.pipeline import StreamConfig, init_states, run_stream
from repro.core.routing import GridSpec

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _stream(n=1500, seed=0):
    from repro.data.stream import MOVIELENS_25M, scaled, synth_stream

    users, items, _ = synth_stream(scaled(MOVIELENS_25M, 0.002), seed=seed)
    return users[:n], items[:n]


def _clean_bits(result):
    bits = result.recall.bits()
    return bits[~np.isnan(bits)]


# ---------------------------------------------------------------------------
# Dispatch parity: device bucket_dispatch == host bucket_dispatch_np
# ---------------------------------------------------------------------------


@given(
    st.lists(st.integers(0, 10**6), min_size=1, max_size=300),
    st.integers(1, 12),
    st.integers(1, 16),
)
@settings(max_examples=100, deadline=None)
def test_dispatch_parity_sets_kept_load(raw_keys, n_workers, capacity):
    """Per-worker bucket *sets*, kept mask, and load agree host/device."""
    keys = np.asarray(raw_keys) % n_workers
    b_np, kept_np, load_np = routing.bucket_dispatch_np(
        keys, n_workers, capacity)
    b_j, kept_j, load_j = routing.bucket_dispatch(
        jnp.asarray(keys, jnp.int32), n_workers, capacity)
    b_j = np.asarray(b_j)
    for w in range(n_workers):
        assert set(b_np[w][b_np[w] >= 0]) == set(b_j[w][b_j[w] >= 0])
    np.testing.assert_array_equal(kept_np, np.asarray(kept_j))
    np.testing.assert_array_equal(load_np, np.asarray(load_j))


# ---------------------------------------------------------------------------
# Backend equivalence on real streams
# ---------------------------------------------------------------------------


def test_scan_matches_host_bit_for_bit():
    users, items = _stream()
    cfg = StreamConfig(algorithm="disgd", grid=GridSpec(2), micro_batch=256,
                       hyper=DisgdHyper(u_cap=128, i_cap=32))
    host = run_stream(users, items, cfg)
    scan = run_stream(users, items, dataclasses.replace(cfg, backend="scan"))
    assert scan.events_processed == host.events_processed
    assert scan.dropped == host.dropped == 0
    np.testing.assert_array_equal(_clean_bits(scan), _clean_bits(host))


def test_scan_matches_host_with_overflow_carry():
    """Mild under-capacity: the re-queue is exercised, parity must hold."""
    users, items = _stream()
    cfg = StreamConfig(algorithm="disgd", grid=GridSpec(2), micro_batch=256,
                       capacity_factor=1.05,
                       hyper=DisgdHyper(u_cap=128, i_cap=32))
    host = run_stream(users, items, cfg)
    scan = run_stream(users, items, dataclasses.replace(cfg, backend="scan"))
    # The config must actually overflow, or this test is vacuous.
    assert max(int(l.max()) for l in host.load_history) > cfg.bucket_capacity
    assert scan.events_processed == host.events_processed
    np.testing.assert_array_equal(_clean_bits(scan), _clean_bits(host))


def test_scan_matches_host_with_forgetting():
    users, items = _stream()
    cfg = StreamConfig(
        algorithm="disgd", grid=GridSpec(2), micro_batch=256,
        hyper=DisgdHyper(u_cap=128, i_cap=32),
        forgetting=ForgettingConfig(policy="lru", trigger_every=512,
                                    lru_max_age=400),
    )
    host = run_stream(users, items, cfg)
    scan = run_stream(users, items, dataclasses.replace(cfg, backend="scan"))
    np.testing.assert_array_equal(_clean_bits(scan), _clean_bits(host))


def test_forgetting_trigger_not_aliased_to_micro_batch():
    """ISSUE 4 satellite: with ``trigger_every`` not a multiple of the
    micro-batch, the old reset-to-zero accumulator aliased the cadence to
    every ceil(te/mb)*mb events (triggers skipped). With the remainder
    carried, counts are exact on both backends and they agree."""
    users, items = _stream(n=960)
    cfg = StreamConfig(
        algorithm="disgd", grid=GridSpec(2), micro_batch=64,
        hyper=DisgdHyper(u_cap=128, i_cap=32),
        forgetting=ForgettingConfig(policy="gradual", trigger_every=96,
                                    gradual_gamma=0.999),
    )
    host = run_stream(users, items, cfg)
    scan = run_stream(users, items, dataclasses.replace(cfg, backend="scan"))
    assert host.dropped == scan.dropped == 0
    assert host.forgets == scan.forgets
    # Exact cadence: one trigger per trigger_every processed events.
    assert host.forgets == host.events_processed // 96
    np.testing.assert_array_equal(_clean_bits(scan), _clean_bits(host))


def test_scan_matches_host_dics():
    users, items = _stream(n=800)
    cfg = StreamConfig(algorithm="dics", grid=GridSpec(2), micro_batch=256,
                       hyper=None)
    from repro.core.dics import DicsHyper

    cfg = dataclasses.replace(cfg, hyper=DicsHyper(u_cap=128, i_cap=32))
    host = run_stream(users, items, cfg)
    scan = run_stream(users, items, dataclasses.replace(cfg, backend="scan"))
    np.testing.assert_array_equal(_clean_bits(scan), _clean_bits(host))


# ---------------------------------------------------------------------------
# End-of-stream drain (the former tail-overflow drop bug)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["host", "scan"])
def test_drain_flushes_tail_overflow(backend):
    """events_processed + dropped == n with dropped == 0 at sane capacity."""
    users, items = _stream()
    n = users.size
    cfg = StreamConfig(algorithm="disgd", grid=GridSpec(2), micro_batch=256,
                       capacity_factor=1.05, backend=backend,
                       hyper=DisgdHyper(u_cap=128, i_cap=32))
    res = run_stream(users, items, cfg)
    assert res.events_processed + res.dropped == n
    assert res.dropped == 0
    assert res.events_processed == n


def test_bounded_requeue_counts_drops():
    """Under-provisioned capacity: the engine's bounded re-queue drops
    events but never loses them from the accounting."""
    users, items = _stream()
    n = users.size
    cfg = StreamConfig(algorithm="disgd", grid=GridSpec(2), micro_batch=256,
                       capacity_factor=0.5, backend="scan",
                       hyper=DisgdHyper(u_cap=128, i_cap=32))
    res = run_stream(users, items, cfg)
    assert res.events_processed + res.dropped == n
    assert res.dropped > 0


# ---------------------------------------------------------------------------
# Pallas fast-path worker
# ---------------------------------------------------------------------------


def test_pallas_worker_states_match_reference():
    """No slot collisions => training is exact (scoring is batched, so only
    the recall bits may differ within a bucket)."""
    cfg = StreamConfig(algorithm="disgd", grid=GridSpec(1), micro_batch=64,
                       hyper=DisgdHyper(u_cap=32, i_cap=16, k=8))
    rng = np.random.default_rng(0)
    cap = 48
    ev_u = rng.integers(0, 32, cap)
    ev_i = rng.integers(0, 16, cap)
    pad = rng.random(cap) < 0.2
    ev_u[pad] = -1
    ev_i[pad] = -1
    ev_u = jnp.asarray(ev_u, jnp.int32)[None, :]
    ev_i = jnp.asarray(ev_i, jnp.int32)[None, :]

    states = init_states(cfg)
    ref_fn = jax.jit(engine.make_worker_fn(cfg))
    pal_fn = jax.jit(engine.make_pallas_worker_fn(cfg))
    s_ref, _, ev_ref = ref_fn(states, ev_u, ev_i)
    s_pal, _, ev_pal = pal_fn(states, ev_u, ev_i)

    np.testing.assert_array_equal(np.asarray(ev_ref), np.asarray(ev_pal))
    for name, a, b in zip(s_ref._fields, s_ref, s_pal):
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=1e-5, atol=1e-6,
                err_msg=f"field {name}")


def test_pallas_backend_end_to_end():
    users, items = _stream(n=600)
    cfg = StreamConfig(algorithm="disgd", grid=GridSpec(2), micro_batch=128,
                       backend="pallas",
                       hyper=DisgdHyper(u_cap=64, i_cap=16))
    res = run_stream(users, items, cfg)
    assert res.events_processed + res.dropped == users.size
    assert 0.0 <= res.recall.mean() <= 1.0


def test_pallas_backend_rejects_non_pallas_algorithm():
    """A direct fast-path request for an algorithm without one raises.

    All in-tree algorithms now ship a fast path, so the guard is pinned
    with a deliberately non-pallas stub registered just for this test.
    """
    from repro.core import algorithm as algorithm_lib

    class _ScanOnly(algorithm_lib.Algorithm):
        name = "_scanonly_engine"
        supports_pallas = False

        def default_hyper(self):
            return DisgdHyper(u_cap=16, i_cap=8)

        def init_state(self, hyper):
            from repro.core import state as state_lib
            return state_lib.init_disgd_state(
                hyper.u_cap, hyper.i_cap, hyper.k)

        def make_worker_step(self, hyper, key):
            from repro.core import disgd as disgd_lib

            def step(state, events):
                return disgd_lib.disgd_worker_step(state, events, hyper, key)

            return step

    algorithm_lib.register(_ScanOnly())
    try:
        with pytest.raises(ValueError):
            engine.make_pallas_worker_fn(
                StreamConfig(algorithm="_scanonly_engine", grid=GridSpec(1)))
    finally:
        algorithm_lib._REGISTRY.pop("_scanonly_engine", None)


# ---------------------------------------------------------------------------
# shard_map backend (workers on mesh coordinates; subprocess for devices)
# ---------------------------------------------------------------------------


def test_shard_map_backend_matches_scan():
    code = """
        import dataclasses
        import numpy as np
        from repro.core.disgd import DisgdHyper
        from repro.core.pipeline import StreamConfig, run_stream
        from repro.core.routing import GridSpec
        from repro.data.stream import MOVIELENS_25M, scaled, synth_stream

        users, items, _ = synth_stream(scaled(MOVIELENS_25M, 0.002), seed=0)
        users, items = users[:1000], items[:1000]
        cfg = StreamConfig(algorithm="disgd", grid=GridSpec(2),
                           micro_batch=256,
                           hyper=DisgdHyper(u_cap=128, i_cap=32))
        sm = run_stream(users, items,
                        dataclasses.replace(cfg, backend="shard_map"))
        sc = run_stream(users, items,
                        dataclasses.replace(cfg, backend="scan"))
        a, b = sm.recall.bits(), sc.recall.bits()
        a, b = a[~np.isnan(a)], b[~np.isnan(b)]
        np.testing.assert_array_equal(a, b)
        assert sm.events_processed == sc.events_processed == users.size
        # One worker per device: every state table is split over the 4
        # devices, one worker's slice on each.
        import jax
        for leaf in jax.tree.leaves(sm.final_states):
            shards = leaf.addressable_shards
            assert len({s.device for s in shards}) == 4, leaf.sharding
            assert all(s.data.shape[0] == 1 for s in shards)
        print("shard_map == scan OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    # Host devices only: the child must never compete with the parent
    # for an accelerator.
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"


def test_grid_mesh_requires_enough_devices():
    from repro.launch.mesh import make_grid_mesh

    with pytest.raises(ValueError):
        make_grid_mesh(GridSpec(8))  # 64 workers >> host devices
