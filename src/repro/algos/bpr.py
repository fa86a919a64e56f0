"""Streaming pairwise BPR-MF on the S&R grid — the third algorithm.

Bayesian Personalized Ranking (Rendle et al., 2009) adapted to the
paper's positive-only prequential stream, as surveyed for streaming
recommenders by Chang et al. (2016): per received rating ``<u, i>`` the
worker samples one *negative* item ``j`` from its local split (an item
the user has not rated here) and takes one SGD step on the pairwise
ranking objective ``ln sigmoid(x_ui - x_uj)``:

    s  = sigmoid(-(U_u . I_i - U_u . I_j))
    U_u <- U_u + eta * (s * (I_i - I_j) - lam * U_u)
    I_i <- I_i + eta * (s * U_u         - lam * I_i)
    I_j <- I_j + eta * (-s * U_u        - lam * I_j)

Recommendation (prequential, recommend-first) ranks candidates by the
raw score ``U_u . I_p`` — identical serving geometry to DISGD, so the
plugin reuses the public ``DisgdState`` container and the DISGD serving
leaf, and thereby inherits forgetting, elastic regrid, grid-portable
checkpoints and popularity stats with **zero** engine edits: this module
is written entirely against ``repro.core.algorithm.Algorithm``.

Negative sampling is drawn from ``fold_in(key, worker clock, user id)``,
so it is a pure function of the state — host, scan and shard_map
backends replay the identical sample sequence (bit-exact parity), and a
checkpoint resume continues the sequence where it left off. When the
sampled slot holds no usable negative (empty, the positive itself, or
already rated by ``u``) the pairwise update is skipped for that event —
the vectors are still seeded, so candidates accumulate and negatives
become available as the table fills.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import state as state_lib
from repro.core.algorithm import Algorithm, register
from repro.core.disgd import init_vector, score_items
from repro.core.serve import partial_topn
from repro.core.state import DisgdState, Tables
from repro.obs import trace as trace_lib

__all__ = ["BprHyper", "bpr_worker_step", "make_pallas_worker",
           "BprAlgorithm"]


class BprHyper(NamedTuple):
    """BPR-MF hyperparameters (shared fields match the runtime contract)."""

    k: int = 10            # latent features
    eta: float = 0.05      # SGD learning rate
    lam: float = 0.01      # L2 regularization
    top_n: int = 10        # recommendation list size
    init_scale: float = 0.1
    u_cap: int = 1024
    i_cap: int = 1024
    n_i: int = 1           # item splits (slot stride)
    g: int = 1             # user groups


def _rank_hit(u_vec, item_vecs, item_ids, rated_row, i_id, top_n: int):
    """Is ``i_id`` in the top-N by score? Rank count, as in DISGD."""
    scores = score_items(u_vec, item_vecs, item_ids, rated_row)
    i_cap = scores.shape[-1]
    t_slot = jnp.argmax(item_ids == i_id)
    s_t = jnp.where(item_ids[t_slot] == i_id, scores[t_slot], -jnp.inf)
    ahead = jnp.sum(scores > s_t) + jnp.sum(
        (scores == s_t) & (jnp.arange(i_cap) < t_slot)
    )
    return jnp.isfinite(s_t) & (ahead < min(top_n, i_cap))


def bpr_worker_step(state: DisgdState, events, hyper: BprHyper,
                    key: jax.Array):
    """Process one micro-batch bucket on a single worker (cf. disgd).

    Same recommend-first prequential contract and masked-scatter
    bookkeeping as ``disgd_worker_step`` — only the training rule
    differs (pairwise BPR step on a sampled local negative).
    """
    from repro.kernels import ops

    u_ids, i_ids = events
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

        # --- recommend, then evaluate (rank by score) ---
        hit = _rank_hit(
            u_vec, st.item_vecs, t.item_ids, rated_row, i_id, hyper.top_n
        ) & valid & ~new_i

        # --- sample a local negative; a function of (key, clock, u) so
        # every backend replays the identical sequence ---
        nkey = jax.random.fold_in(
            jax.random.fold_in(key, t.clock.astype(jnp.uint32)),
            u_id.astype(jnp.uint32))
        j_slot = jax.random.randint(nkey, (), 0, hyper.i_cap)
        neg_id = t.item_ids[j_slot]
        # j_slot != i_slot matters beyond skipping the positive itself:
        # when i evicts a previous tenant, that tenant still occupies
        # i_slot in the pre-write tables, and a negative update chained
        # onto the same slot would clobber i's freshly written vector.
        neg_ok = ((neg_id >= 0) & (neg_id != i_id) & (j_slot != i_slot)
                  & ~rated_row[j_slot])
        upd = valid & neg_ok
        j_vec = st.item_vecs[j_slot]

        # --- pairwise BPR-SGD step ---
        x = jnp.dot(u_vec, i_vec) - jnp.dot(u_vec, j_vec)
        s = jax.nn.sigmoid(-x)
        u_new = jnp.where(
            upd, u_vec + hyper.eta * (s * (i_vec - j_vec) - hyper.lam * u_vec),
            u_vec)
        i_new = jnp.where(
            upd, i_vec + hyper.eta * (s * u_vec - hyper.lam * i_vec), i_vec)
        j_new = j_vec + hyper.eta * (-s * u_vec - hyper.lam * j_vec)

        # --- masked writes (identical bookkeeping to disgd) ---
        w = valid
        wu = jnp.where(w, u_slot, hyper.u_cap)
        wi = jnp.where(w, i_slot, hyper.i_cap)
        wj = jnp.where(upd, j_slot, hyper.i_cap)  # sampling is not a touch
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
        (rated,) = ops.clear_columns(st.rated, i_slot[None],
                                     (w & new_i)[None])
        row = jnp.where(w & new_u, False, rated[u_slot])
        row = row.at[jnp.where(w, i_slot, hyper.i_cap)].set(True, mode="drop")
        rated = rated.at[wu].set(row, mode="drop")

        st = DisgdState(
            tables=tables,
            user_vecs=st.user_vecs.at[wu].set(u_new, mode="drop"),
            item_vecs=st.item_vecs.at[wi].set(i_new, mode="drop")
                                  .at[wj].set(j_new, mode="drop"),
            rated=rated,
        )
        return st, (hit, valid)

    state, (hits, evaluated) = jax.lax.scan(
        body, state, (u_ids, i_ids, init_us, init_is)
    )
    return state, hits, evaluated


def make_pallas_worker(hyper: BprHyper, key: jax.Array):
    """BPR worker step built on the Pallas kernels (fast path).

    Same structure as ``disgd.make_pallas_worker``: bucket scoring is one
    batched masked-matmul against the state at bucket start (recall bits
    tolerance-contract), training is the fused complete-update op in its
    pairwise mode — EXACT against ``bpr_worker_step``, negative-skip rule
    and eviction order included. The per-event negative slots are
    replayed batched: the event's clock is the bucket-start clock plus
    the number of valid events before it (exclusive cumsum), so
    ``fold_in(key, clock, u_id)`` reproduces the reference sequence
    bit-for-bit; slot *usability* is then re-checked inside the
    sequential op against the live tables, exactly where the reference
    checks it.
    """
    from repro.kernels import ops

    u_cap, i_cap = hyper.u_cap, hyper.i_cap

    init_batch = jax.vmap(
        lambda ident: init_vector(key, ident, hyper.k, hyper.init_scale)
    )

    def sample_neg(clock, u_id):
        nkey = jax.random.fold_in(
            jax.random.fold_in(key, clock.astype(jnp.uint32)),
            u_id.astype(jnp.uint32))
        return jax.random.randint(nkey, (), 0, i_cap)

    def step(st: DisgdState, events):
        ev_u, ev_i = events
        valid = ev_u >= 0
        t = st.tables
        with trace_lib.layer("score"):
            u_slot = state_lib.slot_of(ev_u, hyper.g, u_cap)
            i_slot = state_lib.slot_of(ev_i, hyper.n_i, i_cap)
            known_u = t.user_ids[u_slot] == ev_u
            known_i = t.item_ids[i_slot] == ev_i

            init_u = init_batch(ev_u)
            init_i = init_batch(ev_i)

            # --- recommend (batched masked scoring, bucket-start state) ---
            u_vecs_b = jnp.where(known_u[:, None], st.user_vecs[u_slot],
                                 init_u)
            rated_rows = jnp.where(known_u[:, None], st.rated[u_slot], False)
            cand = (t.item_ids >= 0)[None, :] & ~rated_rows & valid[:, None]
            scores = ops.masked_scores(u_vecs_b, st.item_vecs, cand)
            top_scores, top_idx = jax.lax.top_k(
                scores, min(hyper.top_n, scores.shape[-1])
            )
            hits = jnp.any(
                (t.item_ids[top_idx] == ev_i[:, None])
                & jnp.isfinite(top_scores),
                axis=-1,
            ) & valid & known_i

        # --- negative replay: the clock each event sees is bucket-start
        # clock + #valid events before it ---
        with trace_lib.layer("update"):
            vi = valid.astype(t.clock.dtype)
            clocks = t.clock + jnp.cumsum(vi) - vi
            j_slot = jax.vmap(sample_neg)(clocks, ev_u)

        # --- train (fused pairwise update: exact reference semantics) ---
        uv, iv, rated, tabs = ops.factor_update(
            st.user_vecs, st.item_vecs, st.rated, tuple(t),
            (ev_u, ev_i, u_slot, i_slot, j_slot, init_u, init_i),
            eta=hyper.eta, lam=hyper.lam,
        )
        new_st = DisgdState(
            tables=Tables(*tabs), user_vecs=uv, item_vecs=iv, rated=rated)
        return new_st, hits, valid

    return step


class BprAlgorithm(Algorithm):
    """Registry adapter: everything the runtime needs, nothing else."""

    name = "bpr"
    supports_pallas = True  # fused pairwise kernel (kernels/factor_update)
    supports_serve_kernel = True  # serving scores via the Pallas kernel

    def default_hyper(self):
        return BprHyper()

    def init_state(self, hyper):
        # Factor-model state: the public DISGD container fits verbatim,
        # which is what buys regrid/forgetting/checkpoints for free.
        return state_lib.init_disgd_state(hyper.u_cap, hyper.i_cap, hyper.k)

    def make_worker_step(self, hyper, key):
        def step(state, events):
            return bpr_worker_step(state, events, hyper, key)

        return step

    def make_pallas_worker_step(self, hyper, key):
        return make_pallas_worker(hyper, key)

    def make_serve_leaf(self, *, top_n, g, u_cap, k_nn, use_kernel,
                        storage=None):
        del k_nn  # neighborhood size is a DICS knob

        def leaf(state, user_ids):
            return partial_topn(state, user_ids, top_n=top_n, g=g,
                                u_cap=u_cap, use_kernel=use_kernel,
                                storage=storage)

        return leaf


register(BprAlgorithm())
