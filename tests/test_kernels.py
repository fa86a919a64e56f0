"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode).

Off TPU the ops dispatch to the oracle by default (see kernels/ops.py),
so every call here forces ``interpret=True`` — the point is to validate
the KERNEL BODY against the oracle on any platform. The fused update /
serve-leaf kernels have their own parity suite in test_kernel_parity.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("b,i,k", [(8, 64, 4), (64, 300, 10), (128, 1024, 32),
                                   (17, 130, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_scores_sweep(b, i, k, dtype):
    u = jnp.asarray(RNG.normal(size=(b, k)), dtype)
    it = jnp.asarray(RNG.normal(size=(i, k)), dtype)
    mask = jnp.asarray(RNG.random((b, i)) > 0.3)
    got = ops.masked_scores(u, it, mask, interpret=True)
    want = ref.masked_scores(u, it, mask)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def _factor_tables(u_cap, i_cap, k):
    """A factor worker with random vectors, rated bits and live ids."""
    ids_u = RNG.integers(-1, 3 * u_cap, u_cap).astype(np.int32)
    ids_i = RNG.integers(-1, 3 * i_cap, i_cap).astype(np.int32)
    tabs = (jnp.asarray(ids_u), jnp.asarray(ids_i),
            jnp.asarray(RNG.integers(0, 5, u_cap), jnp.int32),
            jnp.asarray(RNG.integers(0, 5, i_cap), jnp.int32),
            jnp.asarray(RNG.integers(0, 50, u_cap), jnp.int32),
            jnp.asarray(RNG.integers(0, 50, i_cap), jnp.int32),
            jnp.asarray(50, jnp.int32))
    return (jnp.asarray(RNG.normal(size=(u_cap, k)) * 0.1, jnp.float32),
            jnp.asarray(RNG.normal(size=(i_cap, k)) * 0.1, jnp.float32),
            jnp.asarray(RNG.random((u_cap, i_cap)) < 0.2), tabs)


def _assert_factor_match(got, want):
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    for a, b in zip(got[3], want[3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("u_cap,i_cap,k,e", [(16, 16, 4, 10), (64, 48, 10, 100),
                                             (128, 64, 32, 257)])
def test_factor_update_sweep(u_cap, i_cap, k, e):
    uv, iv, rated, tabs = _factor_tables(u_cap, i_cap, k)
    ev_u = RNG.integers(0, 3 * u_cap, e).astype(np.int32)
    ev_i = RNG.integers(0, 3 * i_cap, e).astype(np.int32)
    pad = RNG.random(e) < 0.15
    ev_u[pad], ev_i[pad] = -1, -1
    ev_u, ev_i = jnp.asarray(ev_u), jnp.asarray(ev_i)
    events = (ev_u, ev_i, ev_u % u_cap, ev_i % i_cap, None,
              jnp.asarray(RNG.normal(size=(e, k)) * 0.1, jnp.float32),
              jnp.asarray(RNG.normal(size=(e, k)) * 0.1, jnp.float32))
    got = ops.factor_update(uv, iv, rated, tabs, events, eta=0.05,
                            lam=0.01, interpret=True)
    want = ref.factor_apply(uv, iv, rated, tabs, events, eta=0.05, lam=0.01)
    _assert_factor_match(got, want)


def test_factor_update_sequential_dependency():
    """Events touching the same rows must apply in order (not parallel)."""
    k, e = 4, 8
    uv = jnp.ones((4, k), jnp.float32) * 0.3
    iv = jnp.ones((4, k), jnp.float32) * 0.3
    rated = jnp.zeros((4, 4), bool)
    tabs = (jnp.asarray([0, -1, -1, -1], jnp.int32),
            jnp.asarray([0, -1, -1, -1], jnp.int32),
            jnp.ones((4,), jnp.int32), jnp.ones((4,), jnp.int32),
            jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
            jnp.asarray(0, jnp.int32))
    zeros = jnp.zeros((e,), jnp.int32)
    init = jnp.zeros((e, k), jnp.float32)
    events = (zeros, zeros, zeros, zeros, None, init, init)
    got = ops.factor_update(uv, iv, rated, tabs, events, eta=0.1, lam=0.0,
                            interpret=True)
    want = ref.factor_apply(uv, iv, rated, tabs, events, eta=0.1, lam=0.0)
    _assert_factor_match(got, want)
    assert int(got[3][2][0]) == 1 + e          # one LFU touch per event


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("window", [None, 32, 128])
def test_swa_attention_sweep(hq, hkv, window):
    b, s, d = 2, 256, 32
    q = jnp.asarray(RNG.normal(size=(b, hq, s, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    got = ops.swa_attention(q, k, v, window=window, block_q=64,
                            block_k=64, interpret=True)
    want = ref.swa_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_attention_dtype(dtype):
    b, hq, hkv, s, d = 1, 2, 1, 128, 64
    q = jnp.asarray(RNG.normal(size=(b, hq, s, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), dtype)
    got = ops.swa_attention(q, k, v, window=64, block_q=64, block_k=64,
                            interpret=True)
    want = ref.swa_attention(q, k, v, window=64)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_swa_small_sequence_fallback():
    """Short sequences use the oracle path (same results by construction)."""
    b, hq, hkv, s, d = 1, 2, 2, 16, 8
    q = jnp.asarray(RNG.normal(size=(b, hq, s, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    got = ops.swa_attention(q, k, v, window=4)
    want = ref.swa_attention(q, k, v, window=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
