"""The DICS grid's state after a history of events, built in one jitted call.

A deployment that has been running holds co-rating counts in the
hundreds; a run that starts from empty tables would never reach them in
its window. So set-up folds the stream's first periods (the history)
into a state, hands it to the program as a checkpoint, and the reference
starts from the same state.

For one worker, events ``t = 1..T`` in stream order (the order the S&R
grid trains them), ``f_q(u)`` the time of user ``u``'s first event on
item ``q`` and ``R`` the rated bits, paper Algorithm 3 (as
``reference/dics.py`` states it event by event) leaves:

* ``co = C + B + B^T``: ``C = R^T R`` with a zero diagonal (each pair of
  items a user rated, counted once by the later of the two first
  events), and ``B[p, q]`` the number of repeat events ``(u, p, t)``
  (``u`` had rated ``p`` before) with ``f_q(u) < t``, ``q = p``
  included;
* ``item_cnt``, ``user_freq``, ``item_freq``: events per item, user and
  item; ``clock``: events per worker; ``user_ts``/``item_ts``: the
  1-based time of the id's last event; ids in their slots.

Capacities are collision-free, so no table evicts. The state is made on
the device, worker by worker (``lax.map``), so the temporaries of one
worker and not of the grid add to the chip's peak.
"""

from __future__ import annotations

import numpy as np

import cellspec
import srgrid

__all__ = ["build"]


def _pad(groups: list, fill: int) -> np.ndarray:
    n = max(1, max(g.size for g in groups))
    out = np.full((len(groups), n), fill, np.int32)
    for w, g in enumerate(groups):
        out[w, :g.size] = g
    return out


def _by_worker(w: np.ndarray, n_c: int, *cols):
    order = np.argsort(w, kind="stable")
    cuts = np.searchsorted(w[order], np.arange(n_c + 1))
    return [_pad([c[order[cuts[k]:cuts[k + 1]]] for k in range(n_c)], -1)
            for c in cols]


def build(doc: dict, users, items) -> dict:
    """``{leaf name: jax.Array}`` of the grid after ``users``/``items``
    (a deduplicated period may repeat a pair of an earlier one)."""
    import jax
    import jax.numpy as jnp

    users = np.asarray(users, np.int64)
    items = np.asarray(items, np.int64)
    n_i, g = doc["n_i"], doc["n_i"] + doc["w"]
    n_c = cellspec.n_workers(doc)
    u_cap, i_cap = cellspec.capacities(doc)
    w = srgrid.route(users, items, n_i, g)
    su, si = (users // g) % u_cap, (items // n_i) % i_cap
    t = srgrid._positions(w) + 1
    key = (w * u_cap + su) * i_cap + si
    first = np.zeros(users.size, bool)
    first[np.unique(key, return_index=True)[1]] = True
    ev = _by_worker(w, n_c, su, si, t, users, items)
    rw = w[~first]
    rep = _by_worker(rw, n_c, su[~first], si[~first], t[~first])
    return _build_jit(n_c, u_cap, i_cap)(
        *(jnp.asarray(a) for a in ev + rep))


def _build_jit(n_c: int, u_cap: int, i_cap: int):
    import jax
    import jax.numpy as jnp

    def one(args):
        su, si, t, uid, iid, rsu, rsi, rt = args
        ok, rok = su >= 0, rsu >= 0
        su = jnp.where(ok, su, u_cap)            # dropped by mode="drop"
        rsu = jnp.where(rok, rsu, 0)
        rated = jnp.zeros((u_cap, i_cap), bool).at[su, si].set(
            True, mode="drop")
        r = rated.astype(jnp.bfloat16)
        c = jnp.dot(r.T, r, preferred_element_type=jnp.float32)
        c = c * (1 - jnp.eye(i_cap, dtype=jnp.float32))
        f = jnp.full((u_cap, i_cap), jnp.iinfo(jnp.int32).max,
                     jnp.int32).at[su, si].min(t, mode="drop")
        q = (f[rsu] < rt[:, None]) & rok[:, None]
        e = jax.nn.one_hot(jnp.where(rok, rsi, -1), i_cap, dtype=jnp.bfloat16)
        b = jnp.dot(e.T, q.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
        ones = ok.astype(jnp.int32)
        zi, zu = jnp.zeros(i_cap, jnp.int32), jnp.zeros(u_cap, jnp.int32)
        return {
            "tables.user_ids": jnp.full(u_cap, -1, jnp.int32).at[su].set(
                uid, mode="drop"),
            "tables.item_ids": jnp.full(i_cap, -1, jnp.int32).at[
                jnp.where(ok, si, i_cap)].set(iid, mode="drop"),
            "tables.user_freq": zu.at[su].add(ones, mode="drop"),
            "tables.item_freq": zi.at[si].add(ones),
            "tables.user_ts": zu.at[su].max(t, mode="drop"),
            "tables.item_ts": zi.at[si].max(jnp.where(ok, t, 0)),
            "tables.clock": jnp.sum(ones),
            "co": c + b + b.T,
            "item_cnt": zi.at[si].add(ones).astype(jnp.float32),
            "rated": rated,
        }

    return jax.jit(lambda *a: jax.lax.map(one, a))
