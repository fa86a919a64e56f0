"""DICS — Distributed Incremental Cosine Similarity (paper Alg. 3).

Item-based collaborative filtering with TencentRec's incremental cosine
similarity (paper Eq. 6), on the S&R worker grid. With the paper's
positive-only boolean feedback, the incremental statistics per worker are

  co[p, q]    — number of users who rated both p and q        (Eq. 6 numerator)
  item_cnt[p] — number of users who rated p                   (Eq. 6 denominator)

so ``sim(p, q) = co[p, q] / sqrt(item_cnt[p] * item_cnt[q])``.

Per event ``<u, i>`` on the routed worker:

  1. *Recommend first*: for every local unrated candidate ``p``, estimate
     ``r_hat(u, p)`` from the top-``k_nn`` most similar items among the
     user's rated history (Eq. 7). With boolean ratings Eq. 7's weighted
     average is identically 1 wherever defined, so — following TencentRec's
     practice — candidates are ranked by the *numerator mass*
     ``sum_{q in N^k(p) ∩ hist(u)} sim(p, q)``. Top-N list -> Recall@N bit.
  2. *Then update*: ``co[i, q] += 1`` for every ``q`` in the user's history,
     symmetrically; ``item_cnt[i] += 1``; mark ``rated[u, i]``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import state as state_lib
from repro.core.state import DicsState, Tables
from repro.kernels import ops
from repro.obs import trace as trace_lib

__all__ = ["DicsHyper", "dics_worker_step", "make_pallas_worker",
           "dics_scores", "dics_partial_topn", "similarity_matrix"]


class DicsHyper(NamedTuple):
    k_nn: int = 10      # neighborhood size in Eq. 7
    top_n: int = 10     # recommendation list size
    u_cap: int = 512
    i_cap: int = 512
    n_i: int = 1
    g: int = 1


def similarity_matrix(co, item_cnt):
    """Full local cosine similarity matrix (Eq. 6, boolean feedback)."""
    denom = jnp.sqrt(item_cnt[:, None] * item_cnt[None, :])
    sim = jnp.where(denom > 0, co / jnp.maximum(denom, 1e-12), 0.0)
    # An item is not its own neighbor.
    return sim * (1.0 - jnp.eye(co.shape[0], dtype=co.dtype))


def dics_scores(co, item_cnt, rated_row, item_ids, k_nn: int, *, sim=None):
    """Eq. 7 scores for every local candidate item.

    Returns f32[I_cap]; -inf on empty slots and already-rated items.
    ``sim`` lets batched callers (the serving leaf) precompute Eq. 6 once
    and share it across queries; the ranking rule itself lives only here.
    """
    if sim is None:
        sim = similarity_matrix(co, item_cnt)        # [I_cap, I_cap]
    # Restrict neighborhoods to the user's rated history.
    sim_hist = jnp.where(rated_row[None, :], sim, 0.0)
    # Top-k_nn neighbor mass per candidate (TencentRec ranking).
    top_vals, _ = jax.lax.top_k(sim_hist, min(k_nn, sim_hist.shape[-1]))
    scores = jnp.sum(top_vals, axis=-1)
    valid = (item_ids >= 0) & ~rated_row
    return jnp.where(valid, scores, -jnp.inf)


def dics_partial_topn(state: DicsState, user_ids, *, top_n: int = 10,
                      k_nn: int = 10, g: int = 1, u_cap: int = 1024,
                      use_kernel: bool = True, storage=None):
    """One worker's partial top-N (DICS): the Eq. 6/7 serving leaf.

    Read-only scoring of this worker's local item split (``co`` /
    ``item_cnt`` statistics) for a batch of query users — the DICS
    counterpart of ``serve.partial_topn``, merged across splits by
    ``repro.serve.plane``.

    On TPU (and ``use_kernel=True``) the whole leaf is one fused Pallas
    kernel (``ops.dics_topn``): similarity tiles, neighbor-mass top-k and
    the partial top-N merge never materialize the [I, I] similarity
    matrix in HBM. Elsewhere the jnp path below is the oracle: the
    similarity matrix (Eq. 6) is built once per call and shared by all
    queries in the batch.

    Candidates with no positive neighbor mass are excluded (score
    -inf), matching the training path's ``top_scores > 0`` hit rule: a
    zero-mass recommendation carries no collaborative signal.

    Returns:
      (item_ids i32[B, N] global, scores f32[B, N], known bool[B]).
    """
    t = state.tables
    slots = state_lib.slot_of(user_ids, g, u_cap)
    known = t.user_ids[slots] == user_ids
    if storage is None:
        co = state.co
        rated_rows = state.rated[slots]
    else:
        # Storage-policy decode: quantized co inflates to f32 once per
        # call; packed rated unpacks only the gathered query rows.
        from repro.core import storage as storage_lib

        co = storage_lib.decode_co(state.co, state.co_scale, storage)
        rated_rows = storage_lib.gather_rated(
            state.rated, slots, storage, t.item_ids.shape[-1])
    rated = rated_rows & known[:, None]                   # [B, I_cap]

    if use_kernel and ops.on_tpu():
        top_ids, top_scores = ops.dics_topn(
            co, state.item_cnt, rated, known, t.item_ids,
            top_n=top_n, k_nn=k_nn)
        return top_ids, top_scores, known

    sim = similarity_matrix(co, state.item_cnt)           # [I_cap, I_cap]

    def one(rated_row, is_known):
        scores = dics_scores(co, state.item_cnt, rated_row,
                             t.item_ids, k_nn, sim=sim)
        cand = is_known & (scores > 0)
        return jnp.where(cand, scores, -jnp.inf)

    scores = jax.vmap(one)(rated, known)                  # [B, I_cap]
    ids_b = jnp.broadcast_to(t.item_ids[None, :], scores.shape)
    top_ids, top_scores = ops.topn_select(scores, ids_b, top_n)
    return top_ids, top_scores, known


def dics_worker_step(state: DicsState, events, hyper: DicsHyper):
    """Process one micro-batch bucket on a single worker (cf. disgd)."""
    u_ids, i_ids = events

    def body(st: DicsState, ev):
        u_id, i_id = ev
        valid = u_id >= 0
        t = st.tables

        u_slot = state_lib.slot_of(u_id, hyper.g, hyper.u_cap)
        i_slot = state_lib.slot_of(i_id, hyper.n_i, hyper.i_cap)
        new_u = t.user_ids[u_slot] != u_id
        new_i = t.item_ids[i_slot] != i_id

        with trace_lib.layer("update"):
            # Collision eviction (no-op when capacity covers the id space).
            st = jax.lax.cond(
                new_u,
                lambda s: s._replace(rated=s.rated.at[u_slot, :].set(False)),
                lambda s: s,
                st,
            )
            st = jax.lax.cond(
                new_i,
                lambda s: s._replace(
                    rated=s.rated.at[:, i_slot].set(False),
                    co=s.co.at[i_slot, :].set(0.0).at[:, i_slot].set(0.0),
                    item_cnt=s.item_cnt.at[i_slot].set(0.0),
                ),
                lambda s: s,
                st,
            )

        with trace_lib.layer("score"):
            rated_row = st.rated[u_slot]

            # --- recommend, then evaluate ---
            scores = dics_scores(
                st.co, st.item_cnt, rated_row, st.tables.item_ids, hyper.k_nn
            )
            top_scores, top_idx = jax.lax.top_k(
                scores, min(hyper.top_n, scores.shape[-1])
            )
            hit = (
                jnp.any(
                    (st.tables.item_ids[top_idx] == i_id)
                    & jnp.isfinite(top_scores)
                    & (top_scores > 0)
                )
                & valid
                & ~new_i
            )

        # --- incremental update (Eq. 6 statistics) ---
        def write(st: DicsState) -> DicsState:
            t = st.tables
            clock = t.clock + 1
            hist = st.rated[u_slot].astype(st.co.dtype)
            co = st.co.at[i_slot, :].add(hist).at[:, i_slot].add(hist)
            t = t._replace(
                user_ids=t.user_ids.at[u_slot].set(u_id),
                item_ids=t.item_ids.at[i_slot].set(i_id),
                user_freq=t.user_freq.at[u_slot].set(
                    jnp.where(new_u, 1, t.user_freq[u_slot] + 1)
                ),
                item_freq=t.item_freq.at[i_slot].set(
                    jnp.where(new_i, 1, t.item_freq[i_slot] + 1)
                ),
                user_ts=t.user_ts.at[u_slot].set(clock),
                item_ts=t.item_ts.at[i_slot].set(clock),
                clock=clock,
            )
            return DicsState(
                tables=t,
                co=co,
                item_cnt=st.item_cnt.at[i_slot].add(1.0),
                rated=st.rated.at[u_slot, i_slot].set(True),
            )

        with trace_lib.layer("update"):
            st = jax.lax.cond(valid, write, lambda s: s, st)
        return st, (hit, valid)

    state, (hits, evaluated) = jax.lax.scan(body, state, (u_ids, i_ids))
    return state, hits, evaluated


def _bucket_topn(st: DicsState, rated_rows, known_u, hyper: DicsHyper):
    """Each bucket event's top-N (global item ids, scores) against the
    bucket-start statistics: the fused kernel on TPU, else the jnp
    oracle (Eq. 6 once per bucket, Eq. 7 batched in chunks that bound
    the [E, I, I] intermediate, then ``lax.top_k``)."""
    t = st.tables
    if ops.on_tpu():
        return ops.dics_topn(st.co, st.item_cnt, rated_rows, known_u,
                             t.item_ids, top_n=hyper.top_n, k_nn=hyper.k_nn)
    sim = similarity_matrix(st.co, st.item_cnt)       # [I, I]

    def score_chunk(rows):
        return jax.vmap(lambda r: dics_scores(
            st.co, st.item_cnt, r, t.item_ids, hyper.k_nn, sim=sim))(rows)

    n_ev, i_cap = rated_rows.shape
    chunk = max(1, min(n_ev, (1 << 22) // max(1, i_cap * i_cap)))
    while n_ev % chunk:
        chunk -= 1
    scores = jax.lax.map(
        score_chunk, rated_rows.reshape(n_ev // chunk, chunk, i_cap)
    ).reshape(n_ev, i_cap)
    top_scores, top_idx = jax.lax.top_k(
        scores, min(hyper.top_n, scores.shape[-1]))
    return t.item_ids[top_idx], top_scores


def make_pallas_worker(hyper: DicsHyper):
    """DICS worker step built on the fused kernels (fast path).

    The reference step rebuilds the full [I, I] similarity matrix from
    scratch INSIDE the per-event scan — O(I^2) work per event — because
    the co/cnt statistics change under it as the bucket proceeds. The
    fast path scores all events of the bucket against the bucket-start
    statistics, then the fused sequential update op (``ops.dics_update``
    -> ``kernels/dics_update.py``) applies the co-count scatters
    event-for-event — final states are EXACT against
    ``dics_worker_step``, unguarded eviction clears included; recall
    bits carry the same bucket-start tolerance contract as the factor
    fast paths.

    On TPU the bucket-start scoring is the serve leaf's fused kernel
    (``ops.dics_topn``): Eq. 6 tiles, the top-k_nn neighbour mass and
    the top-N list in VMEM, without the [E, I, I] neighbourhoods or a
    sort. Elsewhere the jnp path of ``_bucket_topn`` is the oracle. The
    two agree on every hit whose top-N decision is not a tie (the
    kernel breaks ties by global id, ``lax.top_k`` by slot).
    """
    u_cap, i_cap = hyper.u_cap, hyper.i_cap

    def step(st: DicsState, events):
        ev_u, ev_i = events
        valid = ev_u >= 0
        t = st.tables
        with trace_lib.layer("score"):
            u_slot = state_lib.slot_of(ev_u, hyper.g, u_cap)
            i_slot = state_lib.slot_of(ev_i, hyper.n_i, i_cap)
            known_u = t.user_ids[u_slot] == ev_u
            known_i = t.item_ids[i_slot] == ev_i
            rated_rows = st.rated[u_slot] & known_u[:, None]  # [E, I]

            # --- recommend (bucket-start Eq. 6/7 top-N) ---
            top_ids, top_scores = _bucket_topn(st, rated_rows, known_u,
                                               hyper)
            hits = jnp.any(
                (top_ids == ev_i[:, None])
                & jnp.isfinite(top_scores) & (top_scores > 0),
                axis=-1,
            ) & valid & known_i

        # --- update (fused sequential op: exact reference semantics) ---
        co, cnt, rated, tabs = ops.dics_update(
            st.co, st.item_cnt, st.rated, tuple(t),
            (ev_u, ev_i, u_slot, i_slot))
        new_st = DicsState(
            tables=Tables(*tabs), co=co, item_cnt=cnt, rated=rated)
        return new_st, hits, valid

    return step
