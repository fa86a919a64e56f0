"""Plain DICS worker grid (paper Algorithm 3, Eq. 6-7), the check's reference.

Every worker keeps hash-slotted tables, as the configuration states
them (user ``u`` in slot ``(u // g) % u_cap``, item ``i`` in
``(i // n_i) % i_cap``), the co-rating counts ``co[p, q]``, the rating
counts ``cnt[p]`` and the rated bits. Per event ``<u, i>``: an id new to
its slot takes it (evicting any other tenant: its rated row or column,
and for an item its ``co`` row and column and its count); then, with
``H`` the user's rated bits on the worker before the event,
``co[i, :] += H`` and ``co[:, i] += H`` (in that order, so an item the
user rated before counts twice on the diagonal), ``cnt[i] += 1``, the
rated bit ``(u, i)`` is set, and the bookkeeping moves as in DISGD.

Prequential hit, against the state at the start of the micro-batch:
``sim(p, q) = co[p, q] / sqrt(cnt[p] cnt[q])`` (0 where a count is 0,
and on the diagonal); a known, unrated candidate ``p`` scores the sum of
the ``k_nn`` largest ``sim(p, q)`` over the user's rated ``q``; the hit
is the event's item among the ``top_n`` best candidates (ties to the
lower slot) with a score above 0.

Forgetting, where the configuration states it (``cellspec.forgetting``),
runs when ``srgrid.replay`` fires it: each evicted entry's bookkeeping
resets (``srgrid.forget_tables``), a user's rated row is cleared, and an
item's rated column, ``co`` row and column and count, at a cost that
grows with the entries evicted. ``evicted`` counts the tenants evicted
by a collision and by forgetting.

``dtype`` is the precision of the counts and of the similarity: the
configuration states float32; the output check's control runs the same
code in bfloat16. Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

import cellspec
import srgrid

__all__ = ["Worker", "FLOAT_LEAVES"]

#: Every DICS leaf holds integers (counts in float32): all compared exactly.
FLOAT_LEAVES = ()


class Worker:
    """All ``n_c`` workers of the grid, advanced in lockstep."""

    def __init__(self, doc: dict, dtype=np.float32):
        self.dtype = dtype
        self.n_i, self.g = doc["n_i"], doc["n_i"] + doc["w"]
        n_c = cellspec.n_workers(doc)
        self.u_cap, self.i_cap = cellspec.capacities(doc)
        self.forgetting = cellspec.forgetting(doc)
        self.evicted = {"collision": 0, "forgetting": 0}
        self.k_nn, self.top_n = doc["hyper"]["k_nn"], doc["hyper"]["top_n"]
        u, i = self.u_cap, self.i_cap
        self.user_ids = np.full((n_c, u), -1, np.int32)
        self.item_ids = np.full((n_c, i), -1, np.int32)
        self.user_freq = np.zeros((n_c, u), np.int32)
        self.item_freq = np.zeros((n_c, i), np.int32)
        self.user_ts = np.zeros((n_c, u), np.int32)
        self.item_ts = np.zeros((n_c, i), np.int32)
        self.clock = np.zeros(n_c, np.int32)
        self.co = np.zeros((n_c, i, i), dtype)
        self.item_cnt = np.zeros((n_c, i), dtype)
        self.rated = np.zeros((n_c, u, i), bool)

    def _slots(self, us, its):
        return (us // self.g) % self.u_cap, (its // self.n_i) % self.i_cap

    def train(self, ws, us, its):
        su, si = self._slots(us, its)
        old_u, old_i = self.user_ids[ws, su], self.item_ids[ws, si]
        new_u, new_i = old_u != us, old_i != its
        gone = new_u & (old_u >= 0)
        gone_i = new_i & (old_i >= 0)
        self.evicted["collision"] += int(gone.sum() + gone_i.sum())
        self.rated[ws[gone], su[gone], :] = False
        self._clear_items(ws[gone_i], si[gone_i])

        hist = self.rated[ws, su].astype(self.dtype)          # [m, I]
        self.co[ws, si, :] += hist
        self.co[ws[:, None], np.arange(self.i_cap)[None, :], si[:, None]] += hist
        self.item_cnt[ws, si] += self.dtype(1)

        self.clock[ws] += 1
        self.user_freq[ws, su] = np.where(new_u, 1, self.user_freq[ws, su] + 1)
        self.item_freq[ws, si] = np.where(new_i, 1, self.item_freq[ws, si] + 1)
        self.user_ids[ws, su] = us
        self.item_ids[ws, si] = its
        self.user_ts[ws, su] = self.clock[ws]
        self.item_ts[ws, si] = self.clock[ws]
        self.rated[ws, su, si] = True

    def _clear_items(self, ws, si):
        self.rated[ws, :, si] = False
        self.co[ws, si, :] = 0
        self.co[ws, :, si] = 0
        self.item_cnt[ws, si] = 0

    def forget(self):
        (uw, us), (iw, si) = srgrid.forget_tables(self, self.forgetting)
        self.rated[uw, us, :] = False
        self._clear_items(iw, si)
        self.evicted["forgetting"] += uw.size + iw.size

    def hits(self, w, us, its):
        d = self.dtype
        su, si = self._slots(us, its)
        known_u = self.user_ids[w, su] == us
        known_i = self.item_ids[w, si] == its
        rated = self.rated[w, su] & known_u[:, None]          # [E, I]
        cols = np.flatnonzero(rated.any(axis=0))
        cnt = self.item_cnt[w]
        # Eq. 6 for the columns some event's history holds.
        denom = np.sqrt(cnt[:, None] * cnt[None, cols])
        sim = np.where(denom > 0,
                       self.co[w][:, cols] / np.maximum(denom, d(1e-12)),
                       d(0)).astype(d)
        sim[cols, np.arange(cols.size)] = 0
        live = self.item_ids[w] >= 0
        s = np.full((us.size, self.i_cap), -np.inf, np.float32)
        for e in np.flatnonzero(known_i):
            h = np.searchsorted(cols, np.flatnonzero(rated[e]))
            # The k_nn largest, chosen among the same values in float32
            # (exact for a narrower type, and faster), summed in ``d``
            # from the largest.
            top = sim[:, h].astype(np.float32)
            if h.size > self.k_nn:
                top = np.partition(top, h.size - self.k_nn, axis=1)[
                    :, h.size - self.k_nn:]
            top = (-np.sort(-top, axis=1)).astype(d)
            s[e] = np.where(live & ~rated[e],
                            np.sum(top, axis=1, dtype=d), -np.inf)
        # No neighbour mass is no recommendation.
        s[s <= 0] = -np.inf
        return srgrid.top_n_hits(s, si, known_i, self.top_n)

    def load(self, leaves: dict) -> None:
        """Start from the state ``leaves`` (named as :meth:`states` names
        them), held in this worker's types."""
        for name, a in leaves.items():
            attr = name.rsplit(".", 1)[-1]
            setattr(self, attr, np.array(a, dtype=getattr(self, attr).dtype))

    def states(self) -> dict:
        """Leaves named as the program's state pytree names them."""
        t = {f"tables.{n}": getattr(self, n) for n in (
            "user_ids", "item_ids", "user_freq", "item_freq", "user_ts",
            "item_ts", "clock")}
        return {**t, "co": self.co, "item_cnt": self.item_cnt,
                "rated": self.rated}
