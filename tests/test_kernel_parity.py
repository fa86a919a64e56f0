"""Kernel parity suite (ISSUE 8): fused Pallas fast paths vs references.

Three layers of pinning:

  * **Op level** — each fused kernel body (``interpret=True``) against
    its jnp oracle on realistic worker states: ``factor_update`` in both
    plain-ISGD and pairwise-BPR modes, ``dics_update`` bit-exact, and
    the two serve-leaf kernels (``fused_topn`` / ``dics_topn``).
  * **Worker level** — ``backend="pallas"`` vs ``backend="scan"`` final
    states for all three algorithms, *with eviction active* (capacities
    far below the id space), across forgetting and post-regrid
    continuation. Update ops are exact replicas of the reference scan
    bodies, so states match to float tolerance (int/bool leaves
    bit-exact); only the in-bucket recall bits may differ (the fast
    path scores at bucket start — the documented tolerance contract).
  * **Property level** — fused partial-topn equals score-then-
    ``topn_select`` on random tables with score ties, duplicate ids and
    empty (-1) slots, pinning the (score desc, id asc) merge contract
    that ``grid_topn`` invariance tests rely on.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algos.bpr import BprHyper
from repro.core import state as state_lib
from repro.core.dics import DicsHyper, dics_partial_topn, dics_scores
from repro.core.disgd import DisgdHyper
from repro.core.forgetting import ForgettingConfig
from repro.core.pipeline import StreamConfig, run_stream
from repro.core.routing import GridSpec
from repro.core.state import DicsState, Tables
from repro.drift.controller import DriftPolicy
from repro.kernels import ops, ref

ALGOS = ["disgd", "bpr", "dics"]


def _stream(n=1500, seed=0):
    from repro.data.stream import MOVIELENS_25M, scaled, synth_stream

    users, items, _ = synth_stream(scaled(MOVIELENS_25M, 0.002), seed=seed)
    return users[:n], items[:n]


# Capacities far below the synth id space => constant collisions, so
# every parity run below exercises the eviction branches.
_HYPERS = {
    "disgd": DisgdHyper(u_cap=48, i_cap=16, k=8),
    "bpr": BprHyper(u_cap=48, i_cap=16, k=8),
    "dics": DicsHyper(u_cap=48, i_cap=16, k_nn=5),
}


def _hyper(algorithm):
    return _HYPERS[algorithm]


def _cfg(algorithm, **over):
    return StreamConfig(algorithm=algorithm, grid=GridSpec(2),
                        micro_batch=128, backend="scan",
                        hyper=_hyper(algorithm), **over)


def _assert_states_close(a, b, msg=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        la, lb = np.asarray(la), np.asarray(lb)
        if la.dtype.kind in "fc":
            np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6,
                                       err_msg=msg)
        else:
            np.testing.assert_array_equal(la, lb, err_msg=msg)


def _worker0(states):
    return jax.tree.map(lambda x: x[0], states)


# -- worker-level parity: pallas vs scan backends -------------------------


@pytest.mark.parametrize("algorithm", ALGOS)
def test_pallas_states_match_scan_under_eviction(algorithm):
    users, items = _stream(n=1500)
    cfg = _cfg(algorithm)
    res_scan = run_stream(users, items, cfg)
    res_pal = run_stream(users, items,
                         dataclasses.replace(cfg, backend="pallas"))
    assert res_pal.events_processed == res_scan.events_processed
    assert res_pal.dropped == res_scan.dropped
    _assert_states_close(res_scan.final_states, res_pal.final_states,
                         msg=f"{algorithm} final states")


@pytest.mark.parametrize("algorithm", ALGOS)
def test_pallas_states_match_scan_with_forgetting(algorithm):
    users, items = _stream(n=1500, seed=3)
    cfg = _cfg(algorithm, forgetting=ForgettingConfig(
        policy="lru", trigger_every=256, lru_max_age=96))
    res_scan = run_stream(users, items, cfg)
    res_pal = run_stream(users, items,
                         dataclasses.replace(cfg, backend="pallas"))
    assert res_scan.forgets > 0          # the cadence actually fired
    assert res_pal.forgets == res_scan.forgets
    _assert_states_close(res_scan.final_states, res_pal.final_states,
                         msg=f"{algorithm} states after forgetting")


@pytest.mark.parametrize("algorithm", ALGOS)
def test_pallas_with_drift_detector_tracks_scan(algorithm):
    """Adaptive drift closes the loop on the recall *bits*, which the
    fast path computes at bucket start — detector firings may shift by
    a bucket, so this is the tolerance half of the contract: the run
    completes, processes the same events, and recall stays close."""
    users, items = _stream(n=1500, seed=5)
    cfg = _cfg(algorithm, drift=DriftPolicy())
    res_scan = run_stream(users, items, cfg)
    res_pal = run_stream(users, items,
                         dataclasses.replace(cfg, backend="pallas"))
    assert res_pal.events_processed == res_scan.events_processed

    def mean_recall(res):
        bits = res.recall.bits()
        bits = bits[~np.isnan(bits)]
        return float(bits.mean()) if bits.size else 0.0

    assert abs(mean_recall(res_pal) - mean_recall(res_scan)) < 0.15


@pytest.mark.parametrize("algorithm", ALGOS)
def test_pallas_matches_scan_after_regrid(algorithm):
    """Regrid mid-stream (2 -> 4 workers), then continue the stream on
    both backends from the *same* rebuilt states: post-regrid final
    states must still agree."""
    from repro.core import algorithm as algorithm_lib

    users, items = _stream(n=2000, seed=7)
    cfg = _cfg(algorithm)
    res = run_stream(users[:1000], items[:1000], cfg)

    algo = algorithm_lib.get_algorithm(algorithm)
    hyper = cfg.resolved_hyper()
    dst = GridSpec(4)
    logical = algo.extract_logical(res.final_states, cfg.grid)
    rebuilt = algo.build_states(logical, src=cfg.grid, dst=dst,
                                u_cap=hyper.u_cap, i_cap=hyper.i_cap,
                                merge="fresh")
    cfg2 = dataclasses.replace(cfg, grid=dst)

    res_scan = run_stream(users[1000:], items[1000:], cfg2,
                          initial_states=rebuilt)
    res_pal = run_stream(users[1000:], items[1000:],
                         dataclasses.replace(cfg2, backend="pallas"),
                         initial_states=rebuilt)
    assert res_pal.events_processed == res_scan.events_processed
    _assert_states_close(res_scan.final_states, res_pal.final_states,
                         msg=f"{algorithm} states after regrid")


# -- op-level parity: kernel bodies (interpret mode) vs oracles -----------


def _trained_worker(algorithm, n=600):
    users, items = _stream(n=n, seed=11)
    res = run_stream(users, items, _cfg(algorithm))
    return _worker0(res.final_states), _cfg(algorithm).resolved_hyper()


def _event_batch(hyper, n_ev=40, seed=13, pairwise=False):
    rng = np.random.default_rng(seed)
    ev_u = rng.integers(0, 300, n_ev).astype(np.int32)
    ev_i = rng.integers(0, 120, n_ev).astype(np.int32)
    pad = rng.random(n_ev) < 0.2
    ev_u[pad] = -1
    ev_i[pad] = -1
    ev_u = jnp.asarray(ev_u)
    ev_i = jnp.asarray(ev_i)
    u_slot = state_lib.slot_of(ev_u, hyper.g, hyper.u_cap)
    i_slot = state_lib.slot_of(ev_i, hyper.n_i, hyper.i_cap)
    j_slot = (jnp.asarray(rng.integers(0, hyper.i_cap, n_ev), jnp.int32)
              if pairwise else None)
    k = getattr(hyper, "k", 0)
    init_u = jnp.asarray(rng.normal(size=(n_ev, k)) * 0.1, jnp.float32)
    init_i = jnp.asarray(rng.normal(size=(n_ev, k)) * 0.1, jnp.float32)
    return (ev_u, ev_i, u_slot, i_slot, j_slot, init_u, init_i)


@pytest.mark.parametrize("pairwise", [False, True],
                         ids=["isgd", "bpr_pairwise"])
def test_factor_update_kernel_matches_oracle(pairwise):
    algorithm = "bpr" if pairwise else "disgd"
    st, hyper = _trained_worker(algorithm)
    tabs = tuple(st.tables)
    events = _event_batch(hyper, pairwise=pairwise)

    want = ref.factor_apply(st.user_vecs, st.item_vecs, st.rated, tabs,
                            events, eta=hyper.eta, lam=hyper.lam)
    got = ops.factor_update(st.user_vecs, st.item_vecs, st.rated, tabs,
                            events, eta=hyper.eta, lam=hyper.lam,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    for name, a, b in zip(
            ("user_ids", "item_ids", "user_freq", "item_freq",
             "user_ts", "item_ts", "clock"), got[3], want[3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"tables.{name}")


def test_dics_update_kernel_matches_oracle_bit_exact():
    st, hyper = _trained_worker("dics")
    tabs = tuple(st.tables)
    ev_u, ev_i, u_slot, i_slot, _, _, _ = _event_batch(hyper)
    events = (ev_u, ev_i, u_slot, i_slot)

    want = ref.dics_apply(st.co, st.item_cnt, st.rated, tabs, events)
    got = ops.dics_update(st.co, st.item_cnt, st.rated, tabs, events,
                          interpret=True)
    # Pure counter arithmetic: the kernel must be bit-identical.
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    for a, b in zip(got[3], want[3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dics_topn_kernel_matches_jnp_leaf():
    st, hyper = _trained_worker("dics")
    t = st.tables
    user_ids = jnp.asarray(np.arange(0, 60, 7), jnp.int32)
    want_ids, want_sc, want_known = dics_partial_topn(
        st, user_ids, top_n=8, k_nn=hyper.k_nn, g=hyper.g,
        u_cap=hyper.u_cap, use_kernel=False)

    slots = state_lib.slot_of(user_ids, hyper.g, hyper.u_cap)
    known = t.user_ids[slots] == user_ids
    hist = st.rated[slots] & known[:, None]
    got_ids, got_sc = ops.dics_topn(
        st.co, st.item_cnt, hist, known, t.item_ids,
        top_n=8, k_nn=hyper.k_nn, interpret=True)

    np.testing.assert_array_equal(np.asarray(known), np.asarray(want_known))
    np.testing.assert_array_equal(np.asarray(got_ids), np.asarray(want_ids))
    np.testing.assert_allclose(np.asarray(got_sc), np.asarray(want_sc),
                               rtol=1e-5, atol=1e-6)


def _warm_dics_worker(rng, u_cap, i_cap):
    """A DICS worker state with co-counts from its rated bits: small
    integer counts (so similarities tie), empty user and item slots, and
    users whose histories run from none to most of the items."""
    uid = np.arange(u_cap) + u_cap * rng.integers(0, 4, u_cap)
    iid = np.arange(i_cap) + i_cap * rng.integers(0, 4, i_cap)
    uid[rng.random(u_cap) < 0.1] = -1
    iid[rng.random(i_cap) < 0.15] = -1
    density = rng.choice([0.0, 0.05, 0.1, 0.5, 0.9], size=u_cap)
    rated = ((rng.random((u_cap, i_cap)) < density[:, None])
             & (uid >= 0)[:, None] & (iid >= 0)[None, :])
    r = rated.astype(np.float32)
    clock = 200
    tabs = Tables(
        jnp.asarray(uid, jnp.int32), jnp.asarray(iid, jnp.int32),
        jnp.asarray(rated.sum(1), jnp.int32),
        jnp.asarray(rated.sum(0), jnp.int32),
        jnp.asarray(rng.integers(0, clock, u_cap), jnp.int32),
        jnp.asarray(rng.integers(0, clock, i_cap), jnp.int32),
        jnp.asarray(clock, jnp.int32))
    return DicsState(tables=tabs, co=jnp.asarray(r.T @ r),
                     item_cnt=jnp.asarray(r.sum(0)),
                     rated=jnp.asarray(rated))


def _dics_bucket(rng, st, e, k_nn):
    """``e`` events on ``st``'s worker: known and unknown users and items
    (an unknown item evicts its slot's tenant), and padding. Most known
    users rate an item ranked near the top-N boundary of their scores."""
    uid = np.asarray(st.tables.user_ids)
    iid = np.asarray(st.tables.item_ids)
    u_cap, i_cap = uid.size, iid.size
    us = rng.integers(0, u_cap, e)
    scores = np.asarray(jax.vmap(lambda r: dics_scores(
        st.co, st.item_cnt, r, st.tables.item_ids, k_nn))(st.rated[us]))
    ranked = np.argsort(-scores, axis=1, kind="stable")
    its = np.where(rng.random(e) < 0.7,
                   ranked[np.arange(e), rng.integers(0, 16, e)],
                   rng.integers(0, i_cap, e))
    ev_u = np.where(uid[us] >= 0, uid[us], us)
    ev_i = np.where(iid[its] >= 0, iid[its], its)
    new_u, new_i = rng.random(e) < 0.1, rng.random(e) < 0.1
    ev_u[new_u] = us[new_u] + 7 * u_cap
    ev_i[new_i] = its[new_i] + 7 * i_cap
    pad = rng.random(e) < 0.15
    ev_u[pad], ev_i[pad] = -1, -1
    return jnp.asarray(ev_u, jnp.int32), jnp.asarray(ev_i, jnp.int32)


def _firm_hits(st, ev_u, ev_i, hyper, tol=1e-5):
    """Events whose hit the bucket-start scores decide outside a tie at
    the ``top_n``-th place (within ``tol``, relative, for float sums)."""
    t = st.tables
    u_slot = state_lib.slot_of(ev_u, hyper.g, hyper.u_cap)
    i_slot = np.asarray(state_lib.slot_of(ev_i, hyper.n_i, hyper.i_cap))
    known_u = t.user_ids[u_slot] == ev_u
    rows = st.rated[u_slot] & known_u[:, None]
    s = np.asarray(jax.vmap(lambda r: dics_scores(
        st.co, st.item_cnt, r, t.item_ids, hyper.k_nn))(rows))
    s = np.where(s > 0, s, -np.inf)
    target = s[np.arange(s.shape[0]), i_slot][:, None]
    above = np.sum(s > target * (1 + tol), axis=1)
    level = np.sum(s >= target * (1 - tol), axis=1)
    tied = np.isfinite(target[:, 0]) & (above < hyper.top_n) & (
        level > hyper.top_n)
    # Candidates sharing a score with another candidate of their row.
    same = (s[:, :, None] == s[:, None, :]) & np.isfinite(s)[:, :, None]
    return ~tied, int(np.sum(same.sum(-1) > 1))


def test_dics_pallas_worker_kernel_scoring_matches_jnp_oracle(monkeypatch):
    """The DICS Pallas worker step with its bucket-start scoring on the
    fused kernel (interpret mode), vmapped over two workers as the engine
    maps it, against the same step on the jnp oracle: the same hit bits
    wherever the top-N decision is no tie, and identical states (the
    update stays on its oracle on both sides)."""
    from repro.core import dics

    hyper = DicsHyper(k_nn=3, top_n=8, u_cap=24, i_cap=40)
    rng = np.random.default_rng(7)
    states = [_warm_dics_worker(rng, hyper.u_cap, hyper.i_cap)
              for _ in range(2)]
    buckets = [_dics_bucket(rng, st, 48, hyper.k_nn) for st in states]
    stacked = jax.tree.map(lambda *x: jnp.stack(x), *states)
    ev_u = jnp.stack([b[0] for b in buckets])
    ev_i = jnp.stack([b[1] for b in buckets])
    hist_len = np.asarray(jnp.sum(stacked.rated, axis=-1))
    assert hist_len.min() < hyper.k_nn < hist_len.max()

    def run():
        step = jax.jit(jax.vmap(dics.make_pallas_worker(hyper)))
        return step(stacked, (ev_u, ev_i))

    want_st, want_hits, want_valid = run()
    kernel_topn = ops.dics_topn
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "dics_topn", lambda *a, **k: kernel_topn(
        *a, **k, interpret=True))
    monkeypatch.setattr(ops, "dics_update", lambda *a: ref.dics_apply(*a))
    got_st, got_hits, got_valid = run()

    for a, b in zip(jax.tree.leaves(got_st), jax.tree.leaves(want_st)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(got_valid),
                                  np.asarray(want_valid))
    checked = hits = ties = 0
    for w, st in enumerate(states):
        firm, w_ties = _firm_hits(st, ev_u[w], ev_i[w], hyper)
        got, want = np.asarray(got_hits[w]), np.asarray(want_hits[w])
        np.testing.assert_array_equal(got[firm], want[firm])
        checked += int(firm.sum())
        hits += int(want[firm].sum())
        ties += w_ties
    assert checked >= 80 and hits > 0 and ties > 0


# -- property test: fused partial-topn == score-then-select ---------------


@pytest.mark.parametrize("seed", range(5))
def test_fused_topn_matches_select_on_tied_tables(seed):
    """Random tables with deliberate score ties (small-integer factors so
    dot products are exact in f32), duplicate global ids, empty (-1)
    slots and one fully-masked row: the fused kernel must reproduce
    ``masked_scores`` + ``topn_select`` exactly, ids and scores."""
    rng = np.random.default_rng(seed)
    b, i, k, n = 9, 37, 8, 7
    u_vecs = jnp.asarray(rng.integers(-2, 3, (b, k)), jnp.float32)
    item_vecs = jnp.asarray(rng.integers(-2, 3, (i, k)), jnp.float32)
    mask = np.asarray(rng.random((b, i)) < 0.7)
    mask[0, :] = False                     # nothing rated: all -inf
    mask = jnp.asarray(mask)
    # Duplicate ids (ties at equal scores) and -1 empty slots.
    ids = jnp.asarray(rng.choice([-1, 2, 3, 5, 5, 8, 13, 21], size=i),
                      jnp.int32)

    scores = ref.masked_scores(u_vecs, item_vecs, mask)
    ids_b = jnp.broadcast_to(ids[None, :], scores.shape)
    want_ids, want_sc = ops.topn_select(scores, ids_b, n)

    got_ids, got_sc = ops.fused_topn(u_vecs, item_vecs, mask, ids,
                                     top_n=n, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_ids), np.asarray(want_ids))
    np.testing.assert_array_equal(np.asarray(got_sc), np.asarray(want_sc))


def test_fused_topn_matches_select_on_trained_state():
    """Same equivalence on a real trained DISGD worker (float factors,
    eviction-active table with -1 slots)."""
    st, hyper = _trained_worker("disgd")
    t = st.tables
    user_ids = jnp.asarray(np.arange(0, 90, 11), jnp.int32)
    slots = state_lib.slot_of(user_ids, hyper.g, hyper.u_cap)
    known = t.user_ids[slots] == user_ids
    u_vecs = st.user_vecs[slots]
    occupied = t.item_ids >= 0
    mask = (~st.rated[slots] & known[:, None]) & occupied[None, :]

    scores = ref.masked_scores(u_vecs, st.item_vecs, mask)
    ids_b = jnp.broadcast_to(t.item_ids[None, :], scores.shape)
    want_ids, want_sc = ops.topn_select(scores, ids_b, 10)

    got_ids, got_sc = ops.fused_topn(u_vecs, st.item_vecs, mask,
                                     t.item_ids, top_n=10, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_ids), np.asarray(want_ids))
    np.testing.assert_allclose(np.asarray(got_sc), np.asarray(want_sc),
                               rtol=1e-5, atol=1e-6)


# -- op level on colliding buckets: random worker tables ------------------


def _random_worker_tables(rng, u_cap, i_cap):
    """Bookkeeping tables with live ids, empty slots and live tail slots
    (the slots padding events derive, ``slot_of(-1) = cap - 1``)."""
    uid = rng.integers(-1, 4 * u_cap, u_cap).astype(np.int32)
    iid = rng.integers(-1, 4 * i_cap, i_cap).astype(np.int32)
    uid[-1] = u_cap - 1
    iid[-1] = i_cap - 1
    return (jnp.asarray(uid), jnp.asarray(iid),
            jnp.asarray(rng.integers(0, 6, u_cap), jnp.int32),
            jnp.asarray(rng.integers(0, 6, i_cap), jnp.int32),
            jnp.asarray(rng.integers(0, 90, u_cap), jnp.int32),
            jnp.asarray(rng.integers(0, 90, i_cap), jnp.int32),
            jnp.asarray(90, jnp.int32))


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("mode", ["isgd", "bpr_pairwise", "dics"])
def test_update_kernels_match_oracle_on_colliding_buckets(mode, seed):
    """Buckets whose events share and steal slots (ids from a space 4x the
    capacity, repeats within the bucket) and padding that aliases live
    tail slots: every table the kernel path writes equals the oracle's."""
    rng = np.random.default_rng(100 + seed)
    u_cap, i_cap, k, e = 24, 136, 6, 48
    tabs = _random_worker_tables(rng, u_cap, i_cap)
    ev_u = rng.integers(0, 4 * u_cap, e).astype(np.int32)
    ev_i = rng.integers(0, 4 * i_cap, e).astype(np.int32)
    ev_u[5:9], ev_i[5:9] = ev_u[1], ev_i[2]     # repeats in one bucket
    pad = rng.random(e) < 0.2
    ev_u[pad], ev_i[pad] = -1, -1
    ev_u, ev_i = jnp.asarray(ev_u), jnp.asarray(ev_i)
    u_slot = state_lib.slot_of(ev_u, 1, u_cap)
    i_slot = state_lib.slot_of(ev_i, 1, i_cap)
    rated = jnp.asarray(rng.random((u_cap, i_cap)) < 0.3)
    if mode == "dics":
        co = jnp.asarray(rng.integers(0, 4, (i_cap, i_cap)), jnp.float32)
        cnt = jnp.asarray(rng.integers(0, 9, i_cap), jnp.float32)
        events = (ev_u, ev_i, u_slot, i_slot)
        want = ref.dics_apply(co, cnt, rated, tabs, events)
        got = ops.dics_update(co, cnt, rated, tabs, events, interpret=True)
        floats_exact = True
    else:
        j_slot = (jnp.asarray(rng.integers(0, i_cap, e), jnp.int32)
                  if mode == "bpr_pairwise" else None)
        events = (ev_u, ev_i, u_slot, i_slot, j_slot,
                  jnp.asarray(rng.normal(size=(e, k)) * 0.1, jnp.float32),
                  jnp.asarray(rng.normal(size=(e, k)) * 0.1, jnp.float32))
        uv = jnp.asarray(rng.normal(size=(u_cap, k)) * 0.1, jnp.float32)
        iv = jnp.asarray(rng.normal(size=(i_cap, k)) * 0.1, jnp.float32)
        want = ref.factor_apply(uv, iv, rated, tabs, events, eta=0.05,
                                lam=0.01)
        got = ops.factor_update(uv, iv, rated, tabs, events, eta=0.05,
                                lam=0.01, interpret=True)
        floats_exact = False
    for j in range(2):
        if floats_exact:
            np.testing.assert_array_equal(np.asarray(got[j]),
                                          np.asarray(want[j]))
        else:
            np.testing.assert_allclose(np.asarray(got[j]),
                                       np.asarray(want[j]),
                                       rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    for name, a, b in zip(
            ("user_ids", "item_ids", "user_freq", "item_freq",
             "user_ts", "item_ts", "clock"), got[3], want[3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"tables.{name}")


@pytest.mark.parametrize("batched", [False, True], ids=["one_worker", "vmap"])
def test_clear_columns_matches_column_scatter(batched):
    """``ops.clear_columns`` (the eviction clear of both the scan and the
    Pallas workers) zeroes exactly the columns a masked column scatter
    would, repeats and unflagged columns included."""
    rng = np.random.default_rng(0)
    n_w, u, i, e = 3, 16, 12, 5
    table = jnp.asarray(rng.random((n_w, u, i)) < 0.5)
    cols = jnp.asarray(rng.integers(0, i, (n_w, e)), jnp.int32)
    clear = jnp.asarray(rng.random((n_w, e)) < 0.6)

    def scatter(t, c, f):
        for k in range(e):
            t = t.at[:, jnp.where(f[k], c[k], i)].set(False, mode="drop")
        return t

    want = jax.vmap(scatter)(table, cols, clear)
    if batched:
        (got,) = jax.jit(jax.vmap(ops.clear_columns))(table, cols, clear)
    else:
        got = jnp.stack([ops.clear_columns(table[w], cols[w], clear[w])[0]
                         for w in range(n_w)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
