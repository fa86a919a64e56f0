"""DISGD — Distributed Incremental SGD matrix factorization (paper Alg. 2).

Per received rating ``<u, i, r>`` (positive-only, boolean), on the worker
selected by Algorithm 1:

  1. *Recommend first* (prequential evaluation, Alg. 4): score every local
     unrated item ``p`` as ``r_hat = U_u . I_p^T``, emit the top-N list, and
     record whether ``i`` is in it (online Recall@N).
  2. *Then train*: if ``u``/``i`` unseen locally, draw their vectors from
     N(0, 0.1); compute ``err = 1 - U_u . I_i^T`` and apply

        U_u <- U_u + eta * (err * I_i - lam * U_u)
        I_i <- I_i + eta * (err * U_u - lam * I_i)

ISGD (the central baseline of the paper) is exactly this machinery on a
1x1 grid — ``make_grid(n_i=1)`` — a single worker seeing every event.

Vector initialization is derived via ``fold_in(key, global_id)``: replicas
of the same user/item on different workers start identical (as if copied)
and then diverge through purely local training — the paper's
"replication of belonging".

The per-worker micro-batch is processed with ``lax.scan`` to preserve the
element-at-a-time incremental semantics of the Flink operator.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import state as state_lib
from repro.core.state import DisgdState, Tables

__all__ = ["DisgdHyper", "disgd_worker_step", "make_pallas_worker",
           "init_vector", "score_items"]


class DisgdHyper(NamedTuple):
    """Paper hyperparameters (Section 5.3.1)."""

    k: int = 10            # latent features
    eta: float = 0.05      # learning rate (paper's mu)
    lam: float = 0.01      # L2 regularization
    top_n: int = 10        # recommendation list size
    init_scale: float = 0.1
    u_cap: int = 1024
    i_cap: int = 1024
    n_i: int = 1           # item splits (for slot mapping)
    g: int = 1             # user groups


def init_vector(key: jax.Array, global_id, k: int, scale: float):
    """Deterministic N(0, scale) init shared by all replicas of an id."""
    return scale * jax.random.normal(
        jax.random.fold_in(key, global_id.astype(jnp.uint32)), (k,)
    )


def score_items(u_vec, item_vecs, item_ids, rated_row):
    """Scores for all local items, masking empties and already-rated."""
    scores = item_vecs @ u_vec  # [I_cap]
    valid = (item_ids >= 0) & ~rated_row
    return jnp.where(valid, scores, -jnp.inf)


def _recommend_hit(u_vec, item_vecs, item_ids, rated_row, i_id, top_n: int):
    """Prequential Recall@N for one event: is ``i_id`` in the top-N list?

    Computed as a rank count rather than a ``top_k`` sort — the target is
    in the top-N list iff fewer than N candidates outrank it (strictly
    greater score, or equal score at a smaller slot index, matching
    ``lax.top_k``'s index tie-breaking). O(I_cap) vector ops instead of a
    sort, which dominates the per-event cost of the worker scan.
    """
    scores = score_items(u_vec, item_vecs, item_ids, rated_row)
    i_cap = scores.shape[-1]
    t_slot = jnp.argmax(item_ids == i_id)
    s_t = jnp.where(item_ids[t_slot] == i_id, scores[t_slot], -jnp.inf)
    ahead = jnp.sum(scores > s_t) + jnp.sum(
        (scores == s_t) & (jnp.arange(i_cap) < t_slot)
    )
    return jnp.isfinite(s_t) & (ahead < min(top_n, i_cap))


def disgd_worker_step(state: DisgdState, events, hyper: DisgdHyper, key: jax.Array):
    """Process one micro-batch bucket of events on a single worker.

    Args:
      state: this worker's ``DisgdState``.
      events: ``(u_ids, i_ids)`` int32[capacity] with ``-1`` padding.
      hyper: ``DisgdHyper``.
      key: base PRNG key for replica-consistent vector init.

    Returns:
      (new_state, hits, evaluated): ``hits`` bool[capacity] prequential
      Recall@N bits, ``evaluated`` bool[capacity] False on padding.

    The per-event writes are expressed as masked row/element scatters
    rather than ``lax.cond`` over the whole state: under the pipeline's
    ``vmap`` over workers, ``cond`` lowers to a select that materializes
    both branches — i.e. a full copy of every factor table and the rated
    bitmap *per event*. Masked scatters keep each scan iteration O(rows
    touched), which is the difference between the step being copy-bound
    and compute-bound. The eviction's column clear is one in-place column
    update (``ops.clear_columns``), not a column scatter, for which XLA on
    the TPU transposes the whole rated table twice per event.
    """
    from repro.kernels import ops

    u_ids, i_ids = events
    # Replica-consistent init vectors for the whole bucket in one batched
    # PRNG pass (fold_in per id, so values are identical to per-event
    # computation; unused lanes are discarded by the masks below).
    init_us = jax.vmap(
        lambda ident: init_vector(key, ident, hyper.k, hyper.init_scale)
    )(u_ids)
    init_is = jax.vmap(
        lambda ident: init_vector(key, ident, hyper.k, hyper.init_scale)
    )(i_ids)

    def body(st: DisgdState, ev):
        u_id, i_id, init_u, init_i = ev
        valid = u_id >= 0
        t = st.tables

        u_slot = state_lib.slot_of(u_id, hyper.g, hyper.u_cap)
        i_slot = state_lib.slot_of(i_id, hyper.n_i, hyper.i_cap)

        new_u = t.user_ids[u_slot] != u_id
        new_i = t.item_ids[i_slot] != i_id

        u_vec = jnp.where(new_u, init_u, st.user_vecs[u_slot])
        i_vec = jnp.where(new_i, init_i, st.item_vecs[i_slot])
        # A reused slot may carry the previous tenant's history: mask it.
        rated_row = jnp.where(new_u, False, st.rated[u_slot])
        rated_row = rated_row.at[i_slot].set(
            jnp.where(new_i, False, rated_row[i_slot])
        )

        # --- recommend, then evaluate (Alg. 4 lines 1-5) ---
        hit = _recommend_hit(
            u_vec, st.item_vecs, t.item_ids, rated_row, i_id, hyper.top_n
        ) & valid & ~new_i  # a never-seen item cannot be recommended

        # --- incremental SGD update (Alg. 2) ---
        err = 1.0 - jnp.dot(u_vec, i_vec)
        u_new = u_vec + hyper.eta * (err * i_vec - hyper.lam * u_vec)
        i_new = i_vec + hyper.eta * (err * u_vec - hyper.lam * i_vec)

        # --- masked writes: padding events scatter out-of-bounds and are
        # skipped by mode="drop" (cheaper than gather + select + write) ---
        w = valid
        wu = jnp.where(w, u_slot, hyper.u_cap)    # drop target on padding
        wi = jnp.where(w, i_slot, hyper.i_cap)
        clock = t.clock + w.astype(t.clock.dtype)
        tables = t._replace(
            user_ids=t.user_ids.at[wu].set(u_id, mode="drop"),
            item_ids=t.item_ids.at[wi].set(i_id, mode="drop"),
            user_freq=t.user_freq.at[wu].set(
                jnp.where(new_u, 1, t.user_freq[u_slot] + 1), mode="drop"),
            item_freq=t.item_freq.at[wi].set(
                jnp.where(new_i, 1, t.item_freq[i_slot] + 1), mode="drop"),
            user_ts=t.user_ts.at[wu].set(clock, mode="drop"),
            item_ts=t.item_ts.at[wi].set(clock, mode="drop"),
            clock=clock,
        )
        # Collision eviction: clear the evicted item's column, then the
        # evicted user's row, then mark the rated pair (same order as the
        # hash-map semantics; no-ops when capacity covers the id space).
        (rated,) = ops.clear_columns(st.rated, i_slot[None],
                                     (w & new_i)[None])
        row = jnp.where(w & new_u, False, rated[u_slot])
        row = row.at[jnp.where(w, i_slot, hyper.i_cap)].set(True, mode="drop")
        rated = rated.at[wu].set(row, mode="drop")

        st = DisgdState(
            tables=tables,
            user_vecs=st.user_vecs.at[wu].set(u_new, mode="drop"),
            item_vecs=st.item_vecs.at[wi].set(i_new, mode="drop"),
            rated=rated,
        )
        return st, (hit, valid)

    state, (hits, evaluated) = jax.lax.scan(
        body, state, (u_ids, i_ids, init_us, init_is)
    )
    return state, hits, evaluated


def make_pallas_worker(hyper: DisgdHyper, key: jax.Array):
    """DISGD worker step built on the Pallas kernels (fast path).

    Scoring for the whole bucket is one masked-matmul kernel call against
    the state at bucket start (instead of ``capacity`` sequential top-k
    passes); training applies the fused complete-update op
    (``ops.factor_update`` -> ``kernels/factor_update.py``), which
    replicates the reference step's gather/update/eviction/bookkeeping
    sequence event-for-event — final states are EXACT against
    ``disgd_worker_step``, collisions and evictions included.
    *Recommendation* is evaluated against the state at bucket start, so
    recall bits may differ within a bucket when one user rates several
    items in the same micro-batch.

    Returns ``step(state, (ev_u, ev_i)) -> (state, hits, evaluated)`` —
    the same per-worker signature as ``disgd_worker_step`` partial-
    applied, which is what the engine vmaps over the worker axis.
    """
    from repro.kernels import ops

    u_cap, i_cap, k = hyper.u_cap, hyper.i_cap, hyper.k

    init_batch = jax.vmap(
        lambda ident: init_vector(key, ident, k, hyper.init_scale)
    )

    def step(st: DisgdState, events):
        ev_u, ev_i = events
        valid = ev_u >= 0
        t = st.tables
        u_slot = state_lib.slot_of(ev_u, hyper.g, u_cap)
        i_slot = state_lib.slot_of(ev_i, hyper.n_i, i_cap)
        # "Known at bucket start": the slot already holds this exact id.
        known_u = t.user_ids[u_slot] == ev_u
        known_i = t.item_ids[i_slot] == ev_i

        init_u = init_batch(ev_u)                       # [cap, k]
        init_i = init_batch(ev_i)

        # --- recommend (batched masked scoring) ---
        u_vecs_b = jnp.where(known_u[:, None], st.user_vecs[u_slot], init_u)
        rated_rows = jnp.where(known_u[:, None], st.rated[u_slot], False)
        cand = (t.item_ids >= 0)[None, :] & ~rated_rows & valid[:, None]
        scores = ops.masked_scores(u_vecs_b, st.item_vecs, cand)
        top_scores, top_idx = jax.lax.top_k(
            scores, min(hyper.top_n, scores.shape[-1])
        )
        hits = jnp.any(
            (t.item_ids[top_idx] == ev_i[:, None]) & jnp.isfinite(top_scores),
            axis=-1,
        ) & valid & known_i

        # --- train (fused complete-update op: exact reference semantics) ---
        uv, iv, rated, tabs = ops.factor_update(
            st.user_vecs, st.item_vecs, st.rated, tuple(t),
            (ev_u, ev_i, u_slot, i_slot, None, init_u, init_i),
            eta=hyper.eta, lam=hyper.lam,
        )
        new_st = DisgdState(
            tables=Tables(*tabs), user_vecs=uv, item_vecs=iv, rated=rated)
        return new_st, hits, valid

    return step
