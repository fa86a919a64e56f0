"""LRU/LFU forgetting semantics."""

import jax.numpy as jnp
import numpy as np
import pytest

from tests.prop import given, settings, st

from repro.core import state as state_lib
from repro.core.forgetting import (ForgettingConfig, apply_forgetting,
                                   evict_to_budget)


def _populated(u_cap=8, i_cap=8, k=4):
    st = state_lib.init_disgd_state(u_cap, i_cap, k)
    t = st.tables._replace(
        user_ids=jnp.arange(u_cap, dtype=jnp.int32),
        item_ids=jnp.arange(i_cap, dtype=jnp.int32),
        user_freq=jnp.asarray([1, 1, 5, 5, 1, 5, 1, 5], jnp.int32),
        item_freq=jnp.asarray([5, 1, 5, 1, 5, 1, 5, 1], jnp.int32),
        user_ts=jnp.asarray([1, 2, 3, 4, 97, 98, 99, 100], jnp.int32),
        item_ts=jnp.asarray([100, 99, 98, 97, 4, 3, 2, 1], jnp.int32),
        clock=jnp.int32(100),
    )
    return st._replace(
        tables=t,
        user_vecs=jnp.ones_like(st.user_vecs),
        item_vecs=jnp.ones_like(st.item_vecs),
        rated=jnp.ones_like(st.rated),
    )


def test_lfu_evicts_below_frequency_threshold():
    st = apply_forgetting(_populated(), ForgettingConfig(
        policy="lfu", lfu_min_freq=2))
    uids = np.asarray(st.tables.user_ids)
    assert (uids >= 0).tolist() == [False, False, True, True, False, True,
                                    False, True]
    # Evicted entries are fully cleared.
    assert np.all(np.asarray(st.user_vecs)[uids < 0] == 0)
    assert np.all(~np.asarray(st.rated)[uids < 0, :])


def test_lru_evicts_stale_entries():
    st = apply_forgetting(_populated(), ForgettingConfig(
        policy="lru", lru_max_age=50))
    uids = np.asarray(st.tables.user_ids)
    # user_ts 1..4 are older than clock-50; 97..100 survive.
    assert (uids >= 0).tolist() == [False, False, False, False, True, True,
                                    True, True]


def test_none_policy_is_identity():
    st0 = _populated()
    st = apply_forgetting(st0, ForgettingConfig(policy="none"))
    for a, b in zip(np.asarray(st0.tables.user_ids),
                    np.asarray(st.tables.user_ids)):
        assert a == b


def test_dics_item_eviction_clears_co_rows():
    st = state_lib.init_dics_state(4, 4)
    t = st.tables._replace(
        item_ids=jnp.arange(4, dtype=jnp.int32),
        user_ids=jnp.arange(4, dtype=jnp.int32),
        item_freq=jnp.asarray([1, 9, 9, 9], jnp.int32),
        user_freq=jnp.full((4,), 9, jnp.int32),
        clock=jnp.int32(10),
    )
    st = st._replace(tables=t, co=jnp.ones((4, 4)), item_cnt=jnp.ones(4))
    out = apply_forgetting(st, ForgettingConfig(policy="lfu", lfu_min_freq=2))
    co = np.asarray(out.co)
    assert np.all(co[0, :] == 0) and np.all(co[:, 0] == 0)
    assert np.all(co[1:, 1:] == 1)
    assert float(out.item_cnt[0]) == 0.0


def test_evict_to_budget_bounds_occupancy():
    st = evict_to_budget(_populated(), user_budget=3, item_budget=2,
                         policy="lru")
    u_occ, i_occ = state_lib.occupancy(st.tables)
    assert int(u_occ) <= 3
    assert int(i_occ) <= 2


def _scored(u_scores, i_scores):
    """DISGD state with live entries carrying the given LRU timestamps."""
    u_cap, i_cap = len(u_scores), len(i_scores)
    s = state_lib.init_disgd_state(u_cap, i_cap, 4)
    t = s.tables._replace(
        user_ids=jnp.arange(u_cap, dtype=jnp.int32),
        item_ids=jnp.arange(i_cap, dtype=jnp.int32),
        user_ts=jnp.asarray(u_scores, jnp.int32),
        item_ts=jnp.asarray(i_scores, jnp.int32),
        user_freq=jnp.asarray(u_scores, jnp.int32),
        item_freq=jnp.asarray(i_scores, jnp.int32),
        clock=jnp.int32(1000),
    )
    return s._replace(tables=t)


def test_evict_to_budget_tie_break_keeps_strictly_better_entries():
    """ISSUE 4 regression: with ties at the k-th score, the old slot-order
    cumsum evicted an entry *strictly above* the threshold sitting in a
    late slot (budget=2, scores [9, 9, 10] evicted the 10)."""
    st = evict_to_budget(_scored([9, 9, 10], [9, 9, 10]), user_budget=2,
                         item_budget=2, policy="lru")
    uids = np.asarray(st.tables.user_ids)
    assert uids[2] == 2                       # the 10 must survive
    assert (uids >= 0).sum() == 2
    assert uids[0] == 0 and uids[1] < 0       # earliest tied slot wins
    iids = np.asarray(st.tables.item_ids)
    assert iids[2] == 2 and (iids >= 0).sum() == 2


def test_evict_to_budget_zero_budget_evicts_everything():
    """ISSUE 4 regression: budget=0 crashed on top_k(score, 0)[0][-1]."""
    st = evict_to_budget(_populated(), user_budget=0, item_budget=0,
                         policy="lru")
    u_occ, i_occ = state_lib.occupancy(st.tables)
    assert int(u_occ) == 0 and int(i_occ) == 0
    assert np.all(np.asarray(st.user_vecs) == 0)
    assert np.all(~np.asarray(st.rated))


@given(
    st.lists(st.integers(0, 30), min_size=1, max_size=12),
    st.integers(0, 14),
    st.sampled_from(["lru", "lfu"]),
)
@settings(max_examples=100, deadline=None)
def test_evict_to_budget_never_evicts_better_than_survivor(scores, budget,
                                                           policy):
    """Property (ISSUE 4): no evicted entry's score strictly exceeds any
    survivor's, and occupancy lands at min(budget, live)."""
    n = len(scores)
    state = _scored(scores, scores)
    out = evict_to_budget(state, user_budget=budget, item_budget=budget,
                          policy=policy)
    for ids in (out.tables.user_ids, out.tables.item_ids):
        ids = np.asarray(ids)
        arr = np.asarray(scores)
        kept, gone = arr[ids >= 0], arr[ids < 0]
        if kept.size and gone.size:
            assert gone.max() <= kept.min()
        assert (ids >= 0).sum() == min(budget, n)


def test_gradual_forgetting_decays_state():
    """Paper future work: gradual forgetting shrinks learned state smoothly
    instead of hard-evicting it."""
    st0 = _populated()
    st = apply_forgetting(st0, ForgettingConfig(policy="gradual",
                                                gradual_gamma=0.5))
    np.testing.assert_allclose(np.asarray(st.user_vecs),
                               0.5 * np.asarray(st0.user_vecs))
    np.testing.assert_allclose(np.asarray(st.item_vecs),
                               0.5 * np.asarray(st0.item_vecs))
    # Nothing is evicted: ids and history survive.
    np.testing.assert_array_equal(np.asarray(st.tables.user_ids),
                                  np.asarray(st0.tables.user_ids))
    np.testing.assert_array_equal(np.asarray(st.rated), np.asarray(st0.rated))


def test_gradual_forgetting_dics():
    st0 = state_lib.init_dics_state(4, 4)
    st0 = st0._replace(co=jnp.ones((4, 4)), item_cnt=2 * jnp.ones(4))
    st = apply_forgetting(st0, ForgettingConfig(policy="gradual",
                                                gradual_gamma=0.5))
    np.testing.assert_allclose(np.asarray(st.co), 0.5)
    np.testing.assert_allclose(np.asarray(st.item_cnt), 1.0)


# ---------------------------------------------------------------------------
# Forgetting x regrid: evictions survive resharding (ISSUE 3 satellite)
# ---------------------------------------------------------------------------


def _stacked(st):
    import jax

    return jax.tree.map(lambda x: x[None], st)


def test_evict_to_budget_keeps_best_and_clears_state():
    """Budget eviction keeps exactly the freshest/most-frequent entries
    and scrubs everything the evicted rows/columns owned."""
    st = evict_to_budget(_populated(), user_budget=3, item_budget=2,
                         policy="lru")
    uids = np.asarray(st.tables.user_ids)
    iids = np.asarray(st.tables.item_ids)
    # LRU keeps the 3 freshest users (ts 98, 99, 100) and 2 freshest items.
    assert np.flatnonzero(uids >= 0).tolist() == [5, 6, 7]
    assert np.flatnonzero(iids >= 0).tolist() == [0, 1]
    assert np.all(np.asarray(st.user_vecs)[uids < 0] == 0)
    assert np.all(np.asarray(st.item_vecs)[iids < 0] == 0)
    assert np.all(~np.asarray(st.rated)[uids < 0, :])
    assert np.all(~np.asarray(st.rated)[:, iids < 0])

    st_lfu = evict_to_budget(_populated(), user_budget=4, item_budget=4,
                             policy="lfu")
    assert int(state_lib.occupancy(st_lfu.tables)[0]) <= 4


def test_evicted_slots_stay_empty_after_regrid():
    """Resharding must not resurrect forgotten entries: ids evicted before
    a regrid are absent on every target grid, and their old slots carry
    -1, not stale tenants."""
    from repro.core.regrid import regrid
    from repro.core.routing import GridSpec

    st = evict_to_budget(_populated(), user_budget=3, item_budget=2,
                         policy="lru")
    live_u = {int(x) for x in np.asarray(st.tables.user_ids) if x >= 0}
    live_i = {int(x) for x in np.asarray(st.tables.item_ids) if x >= 0}
    src = GridSpec.rect(1, 1)
    for dst in (GridSpec.rect(1, 1), GridSpec.rect(2, 2),
                GridSpec.rect(1, 4)):
        out = regrid(_stacked(st), src, dst)
        uids = np.asarray(out.tables.user_ids).reshape(-1)
        iids = np.asarray(out.tables.item_ids).reshape(-1)
        assert {int(x) for x in uids if x >= 0} == live_u, dst
        assert {int(x) for x in iids if x >= 0} == live_i, dst
        # Evicted entries leave no orphaned payload anywhere: empty user
        # slots carry zero vectors and an all-False rated row.
        vecs = np.asarray(out.user_vecs).reshape(-1, out.user_vecs.shape[-1])
        assert np.all(vecs[uids < 0] == 0)
        dead_rows = np.asarray(out.tables.user_ids) < 0
        assert np.all(~np.asarray(out.rated)[dead_rows])


def test_gradual_forgetting_composes_with_regrid():
    """The gradual policy decays values without evicting; a regrid carries
    the decayed values verbatim (identity: bit-exact) and replica merges
    pick decayed replicas, never un-decayed ghosts."""
    import jax

    from repro.core.regrid import regrid
    from repro.core.routing import GridSpec

    st0 = _populated()
    st = apply_forgetting(st0, ForgettingConfig(policy="gradual",
                                                gradual_gamma=0.5))
    src = GridSpec.rect(1, 1)
    stacked = _stacked(st)
    ident = regrid(stacked, src, src)
    for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(ident)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    out = regrid(stacked, src, GridSpec.rect(2, 2))
    uids = np.asarray(out.tables.user_ids)
    for w in range(4):
        for s in np.flatnonzero(uids[w] >= 0):
            np.testing.assert_allclose(
                np.asarray(out.user_vecs[w, s]),
                0.5 * np.asarray(st0.user_vecs[int(uids[w, s])]))


def test_gradual_dics_decay_survives_coarsening():
    """DICS gradual decay then a split-coarsening regrid: the decayed co
    mass merges exactly (additivity is decay-agnostic). The source is a
    (2,1) grid — rows hold even/odd items — coarsened onto one worker."""
    import jax
    import jax.numpy as jnp

    from repro.core.regrid import regrid
    from repro.core.routing import GridSpec

    def worker(row):
        st = state_lib.init_dics_state(4, 4)
        return st._replace(
            tables=st.tables._replace(
                user_ids=jnp.arange(4, dtype=jnp.int32),
                item_ids=jnp.int32(row) + 2 * jnp.arange(4, dtype=jnp.int32),
                clock=jnp.int32(8)),
            co=jnp.full((4, 4), 2.0 + row), item_cnt=jnp.full((4,), 4.0))

    states = jax.tree.map(lambda *xs: jnp.stack(xs), worker(0), worker(1))
    decayed = apply_forgetting(states, ForgettingConfig(
        policy="gradual", gradual_gamma=0.5))
    out = regrid(decayed, GridSpec.rect(2, 1), GridSpec.rect(1, 1), i_cap=8)
    assert (float(np.asarray(out.co).sum())
            == float(np.asarray(decayed.co).sum()))
    assert (float(np.asarray(out.item_cnt).sum())
            == float(np.asarray(decayed.item_cnt).sum()))
    # All 8 items live on the merged worker, counts halved by the decay.
    iids = np.asarray(out.tables.item_ids).reshape(-1)
    assert sorted(iids[iids >= 0].tolist()) == list(range(8))
    assert np.all(np.asarray(out.item_cnt).reshape(-1)[iids >= 0] == 2.0)


# ---------------------------------------------------------------------------
# The fixed cadence follows the stream across ingest calls (scan backend)
# ---------------------------------------------------------------------------

CADENCE = 768          # not a multiple of the 512-event calls
CALL = 512


def _cadence_cfg(backend="scan"):
    """LRU on tables below the ids' count (collisions too); the bucket
    holds a whole micro-batch, so nothing is re-queued and calls of any
    size cut the stream into the same micro-batches."""
    from repro.core.disgd import DisgdHyper
    from repro.core.pipeline import StreamConfig
    from repro.core.routing import GridSpec

    return StreamConfig(
        algorithm="disgd", grid=GridSpec(2), micro_batch=64,
        capacity_factor=4.0, hyper=DisgdHyper(u_cap=64, i_cap=32),
        forgetting=ForgettingConfig(policy="lru", trigger_every=CADENCE,
                                    lru_max_age=200),
        backend=backend)


def _cadence_stream(n=7 * CALL):
    from repro.data.stream import MOVIELENS_25M, scaled, synth_stream

    users, items, _ = synth_stream(scaled(MOVIELENS_25M, 0.005), seed=3)
    return users[:n], items[:n]


def _ingest_calls(session, users, items):
    """Per-call triggers, the clean prequential bits of all calls and
    the cadence's count after the last."""
    forgets, bits = [], []
    for lo in range(0, users.size, CALL):
        res = session.ingest(users[lo:lo + CALL], items[lo:lo + CALL])
        forgets.append(res.forgets)
        b = res.recall.bits()
        bits.append(b[~np.isnan(b)])
    return forgets, np.concatenate(bits), res.events_since_trigger


def _assert_states_equal(a, b):
    import jax

    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_cadence_spans_ingest_calls():
    """Calls of 512 events with a cadence of 768 fire floor(N / 768)
    triggers in all, at the same steps as one call of N events, and end
    in bit-identical states."""
    from repro.session import StreamSession

    users, items = _cadence_stream()
    split = StreamSession(_cadence_cfg())
    forgets, bits, since = _ingest_calls(split, users, items)
    whole = StreamSession(_cadence_cfg())
    res = whole.ingest(users, items)

    assert res.dropped == 0 and res.events_processed == users.size
    assert sum(forgets) == res.forgets == users.size // CADENCE
    # 512, 1024, ..., 3584 events: a trigger at 768, 1536, 2304, 3072.
    assert forgets == [0, 1, 1, 0, 1, 1, 0]
    assert since == res.events_since_trigger == users.size % CADENCE
    whole_bits = res.recall.bits()
    np.testing.assert_array_equal(bits, whole_bits[~np.isnan(whole_bits)])
    _assert_states_equal(split.states, whole.states)
    assert int(split.metrics.counter("stream_forget_triggers_total",
                                     "").value) == sum(forgets)


def test_cadence_survives_checkpoint_and_restore(tmp_path):
    """A checkpoint taken between two triggers carries the cadence: the
    restored session fires where the uninterrupted one does. One
    written without the count restores 0."""
    from repro.core.pipeline import (restore_stream_checkpoint,
                                     save_stream_checkpoint)
    from repro.session import StreamSession

    users, items = _cadence_stream(n=2 * CALL)
    first, second = (slice(0, CALL), slice(CALL, 2 * CALL))
    live = StreamSession(_cadence_cfg())
    assert live.ingest(users[first], items[first]).forgets == 0
    live.checkpoint(str(tmp_path / "mid"))
    resumed = StreamSession.restore(str(tmp_path / "mid"), _cadence_cfg())
    a = live.ingest(users[second], items[second])
    b = resumed.ingest(users[second], items[second])
    assert a.forgets == b.forgets == 1
    assert a.events_since_trigger == b.events_since_trigger == 2 * CALL - CADENCE
    _assert_states_equal(live.states, resumed.states)

    save_stream_checkpoint(str(tmp_path / "old"), CALL, live.states)
    assert restore_stream_checkpoint(str(tmp_path / "old"),
                                     _cadence_cfg()).since == 0


def test_cadence_host_loop_agrees_with_scan():
    """The host pipeline carries the cadence across calls as the scan
    does: the same triggers per call and the same states."""
    from repro.session import StreamSession

    users, items = _cadence_stream(n=4 * CALL)
    host = StreamSession(_cadence_cfg(backend="host"))
    scan = StreamSession(_cadence_cfg())
    fh, bh, sh = _ingest_calls(host, users, items)
    fs, bs, ss = _ingest_calls(scan, users, items)
    assert fh == fs == [0, 1, 1, 0]
    np.testing.assert_array_equal(bh, bs)
    assert sh == ss == 4 * CALL - 2 * CADENCE
    _assert_states_equal(host.states, scan.states)


def test_collision_evictions_counted_as_the_reference_counts():
    """The telemetry's collision count is the number of live tenants a
    colliding id replaced, counted here by a plain hash-slot replay of
    each worker's events in stream order; host loop and scan agree, and
    the session folds it into ``stream_collision_evictions_total``."""
    import dataclasses

    from repro.core.pipeline import run_stream
    from repro.obs import telemetry_ints
    from repro.session import StreamSession

    cfg = dataclasses.replace(_cadence_cfg(), forgetting=ForgettingConfig())
    users, items = _cadence_stream(n=4 * CALL)
    g, n_i = cfg.grid.g, cfg.grid.n_i
    h = cfg.resolved_hyper()
    held_u, held_i, want = {}, {}, 0
    for u, i in zip(users.tolist(), items.tolist()):
        w = (i % n_i) * g + (u % g)
        for held, key, ident in ((held_u, (w, (u // g) % h.u_cap), u),
                                 (held_i, (w, (i // n_i) % h.i_cap), i)):
            want += held.get(key, ident) != ident
            held[key] = ident
    assert want > 0

    scan = StreamSession(cfg)
    res = scan.ingest(users, items)
    host = run_stream(users, items, dataclasses.replace(cfg, backend="host"))
    assert res.dropped == 0
    assert telemetry_ints(res.telemetry)["collisions"] == want
    assert telemetry_ints(host.telemetry)["collisions"] == want
    assert int(scan.metrics.counter("stream_collision_evictions_total",
                                    "").value) == want


def _random_state(kind: str, rng, u_cap=24, i_cap=16, k=4):
    """A stacked two-worker state with live, empty (-1) slots and
    arbitrary payloads."""
    ids = lambda n: np.where(rng.random((2, n)) < 0.8,   # noqa: E731
                             rng.integers(0, 1000, (2, n)), -1)
    t = state_lib.Tables(
        user_ids=jnp.asarray(ids(u_cap), jnp.int32),
        item_ids=jnp.asarray(ids(i_cap), jnp.int32),
        user_freq=jnp.asarray(rng.integers(0, 9, (2, u_cap)), jnp.int32),
        item_freq=jnp.asarray(rng.integers(0, 9, (2, i_cap)), jnp.int32),
        user_ts=jnp.asarray(rng.integers(0, 90, (2, u_cap)), jnp.int32),
        item_ts=jnp.asarray(rng.integers(0, 90, (2, i_cap)), jnp.int32),
        clock=jnp.asarray([90, 60], jnp.int32))
    rated = jnp.asarray(rng.random((2, u_cap, i_cap)) < 0.5)
    if kind == "disgd":
        return state_lib.DisgdState(
            tables=t, rated=rated,
            user_vecs=jnp.asarray(rng.normal(size=(2, u_cap, k)), jnp.float32),
            item_vecs=jnp.asarray(rng.normal(size=(2, i_cap, k)), jnp.float32))
    return state_lib.DicsState(
        tables=t, rated=rated,
        co=jnp.asarray(rng.integers(0, 5, (2, i_cap, i_cap)), jnp.float32),
        item_cnt=jnp.asarray(rng.integers(0, 9, (2, i_cap)), jnp.float32))


def _plain_eviction(state, u_ev, i_ev):
    """The semantics, one worker and one evicted entry at a time: id -1,
    freq and ts 0, vector or co row, column and count zeroed, rated row
    or column cleared."""
    out = {k: np.array(v) for k, v in state.tables._asdict().items()}
    rated = np.array(state.rated)
    payload = {k: np.array(v) for k, v in state._asdict().items()
               if k not in ("tables", "rated") and v is not None}
    for w in range(rated.shape[0]):
        for s in np.flatnonzero(u_ev[w]):
            out["user_ids"][w, s] = -1
            out["user_freq"][w, s] = out["user_ts"][w, s] = 0
            rated[w, s, :] = False
            if "user_vecs" in payload:
                payload["user_vecs"][w, s] = 0
        for s in np.flatnonzero(i_ev[w]):
            out["item_ids"][w, s] = -1
            out["item_freq"][w, s] = out["item_ts"][w, s] = 0
            rated[w, :, s] = False
            if "item_vecs" in payload:
                payload["item_vecs"][w, s] = 0
            if "co" in payload:
                payload["co"][w, s, :] = payload["co"][w, :, s] = 0
                payload["item_cnt"][w, s] = 0
    return out, rated, payload


@pytest.mark.parametrize("kind", ["disgd", "dics"])
@pytest.mark.parametrize("policy", ["lru", "lfu"])
@pytest.mark.parametrize("share", [0.0, 0.3, 1.0], ids=["none", "some", "all"])
def test_eviction_matches_plain_semantics(kind, policy, share):
    """``apply_forgetting`` over the worker axis (as the engine runs it)
    on random states, with thresholds that evict none, some or all of
    the live entries; empty slots (-1) stay as they are."""
    import jax

    rng = np.random.default_rng([7, int(share * 10), len(policy)])
    st0 = _random_state(kind, rng)
    t = st0.tables
    clock = np.asarray(t.clock)[:, None]
    if policy == "lru":
        age = {0.0: 1000, 0.3: 60, 1.0: -1000}[share]
        cfg = ForgettingConfig(policy="lru", lru_max_age=age)
        u_ev = clock - np.asarray(t.user_ts) > age
        i_ev = clock - np.asarray(t.item_ts) > age
    else:
        least = {0.0: 0, 0.3: 3, 1.0: 100}[share]
        cfg = ForgettingConfig(policy="lfu", lfu_min_freq=least)
        u_ev = np.asarray(t.user_freq) < least
        i_ev = np.asarray(t.item_freq) < least
    u_ev &= np.asarray(t.user_ids) >= 0
    i_ev &= np.asarray(t.item_ids) >= 0
    assert (u_ev.any() or i_ev.any()) == (share > 0)
    st = jax.vmap(lambda s: apply_forgetting(s, cfg))(st0)
    tables, rated, payload = _plain_eviction(st0, u_ev, i_ev)
    for k, v in tables.items():
        np.testing.assert_array_equal(np.asarray(getattr(st.tables, k)), v,
                                      err_msg=k)
    np.testing.assert_array_equal(np.asarray(st.rated), rated)
    for k, v in payload.items():
        np.testing.assert_array_equal(np.asarray(getattr(st, k)), v,
                                      err_msg=k)


def test_cadence_survives_rescale():
    """The cadence belongs to the stream, not to the grid: a regrid
    between two triggers leaves the count where it was."""
    from repro.core.routing import GridSpec
    from repro.session import StreamSession

    users, items = _cadence_stream(n=2 * CALL)
    session = StreamSession(_cadence_cfg())
    assert session.ingest(users[:CALL], items[:CALL]).forgets == 0
    session.rescale(GridSpec.rect(2, 1))
    res = session.ingest(users[CALL:], items[CALL:])
    assert res.forgets == 1
    assert res.events_since_trigger == 2 * CALL - CADENCE
