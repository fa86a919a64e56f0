"""Plain DISGD worker grid (paper Algorithm 2), the check's reference.

Every worker keeps hash-slotted tables, as the configuration states
them: user ``u`` lives in slot ``(u // g) % u_cap`` of its worker, item
``i`` in slot ``(i // n_i) % i_cap``. Per event ``<u, i>``: an id new to
its slot takes it (evicting any other tenant, with its rated row or
column) and starts from its replica-consistent vector, drawn from
``N(0, init_scale)`` by ``fold_in(key(model_seed), id)``; then

    err = 1 - U_u . I_i
    U_u <- U_u + eta * (err * I_i - lam * U_u)
    I_i <- I_i + eta * (err * U_u - lam * I_i)

and the bookkeeping moves: per-worker clock + 1, the slot's id, its
frequency (1 when new, else + 1) and its last-touch time (the clock),
and the rated bit ``(u, i)``. Prequential hit: the event's item is among
the ``top_n`` best-scored known, unrated items of the worker (ties to
the lower slot), scored against the state at the start of the
micro-batch with the user's vector (its initial vector when unknown).

Forgetting, where the configuration states it (``cellspec.forgetting``),
runs when ``srgrid.replay`` fires it: each evicted entry's bookkeeping
resets (``srgrid.forget_tables``), its vector is zeroed and its rated
row or column cleared, at a cost that grows with the entries evicted.
``evicted`` counts the tenants evicted by a collision and by forgetting.

``dtype`` is the precision the factor arithmetic runs in: the
configuration states float32; the output check's control runs the same
code in bfloat16. Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

import cellspec
import srgrid

__all__ = ["Worker", "FLOAT_LEAVES", "init_vectors"]

#: State leaves compared within a tolerance; every other leaf exactly.
FLOAT_LEAVES = ("user_vecs", "item_vecs")


def init_vectors(seed: int, n: int, k: int, scale: float) -> np.ndarray:
    """``scale * normal(fold_in(key(seed), id), (k,))`` for ids ``0..n-1``,
    computed by JAX's PRNG on the host's CPU."""
    import jax
    import jax.numpy as jnp

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        key = jax.random.key(seed)
        draw = jax.jit(jax.vmap(lambda i: jax.random.normal(
            jax.random.fold_in(key, i.astype(jnp.uint32)), (k,))))
        out = np.asarray(draw(jnp.arange(n, dtype=jnp.int32)))
    return out * np.float32(scale)


class Worker:
    """All ``n_c`` workers of the grid, advanced in lockstep."""

    def __init__(self, doc: dict, dtype=np.float32):
        self.dtype = dtype
        self.n_i, self.g = doc["n_i"], doc["n_i"] + doc["w"]
        n_c = cellspec.n_workers(doc)
        self.u_cap, self.i_cap = cellspec.capacities(doc)
        self.forgetting = cellspec.forgetting(doc)
        self.evicted = {"collision": 0, "forgetting": 0}
        h = doc["hyper"]
        self.k, self.top_n = h["k"], h["top_n"]
        self.eta, self.lam = dtype(h["eta"]), dtype(h["lam"])
        init = init_vectors(doc["model_seed"],
                            max(doc["n_users"], doc["n_items"]), self.k,
                            h["init_scale"]).astype(dtype)
        self.init_u = init[:doc["n_users"]]
        self.init_i = init[:doc["n_items"]]
        u, i = self.u_cap, self.i_cap
        self.user_ids = np.full((n_c, u), -1, np.int32)
        self.item_ids = np.full((n_c, i), -1, np.int32)
        self.user_freq = np.zeros((n_c, u), np.int32)
        self.item_freq = np.zeros((n_c, i), np.int32)
        self.user_ts = np.zeros((n_c, u), np.int32)
        self.item_ts = np.zeros((n_c, i), np.int32)
        self.clock = np.zeros(n_c, np.int32)
        self.user_vecs = np.zeros((n_c, u, self.k), dtype)
        self.item_vecs = np.zeros((n_c, i, self.k), dtype)
        self.rated = np.zeros((n_c, u, i), bool)

    def _slots(self, us, its):
        return (us // self.g) % self.u_cap, (its // self.n_i) % self.i_cap

    def train(self, ws, us, its):
        su, si = self._slots(us, its)
        old_u, old_i = self.user_ids[ws, su], self.item_ids[ws, si]
        new_u, new_i = old_u != us, old_i != its
        # Eviction of another tenant (a slot that held no id has no bits).
        gone_i = new_i & (old_i >= 0)
        self.rated[ws[gone_i], :, si[gone_i]] = False
        gone = new_u & (old_u >= 0)
        self.rated[ws[gone], su[gone], :] = False
        self.evicted["collision"] += int(gone.sum() + gone_i.sum())

        u = np.where(new_u[:, None], self.init_u[us], self.user_vecs[ws, su])
        i = np.where(new_i[:, None], self.init_i[its], self.item_vecs[ws, si])
        err = self.dtype(1) - np.sum(u * i, axis=1, dtype=self.dtype)
        self.user_vecs[ws, su] = u + self.eta * (err[:, None] * i - self.lam * u)
        self.item_vecs[ws, si] = i + self.eta * (err[:, None] * u - self.lam * i)

        self.clock[ws] += 1
        self.user_freq[ws, su] = np.where(new_u, 1, self.user_freq[ws, su] + 1)
        self.item_freq[ws, si] = np.where(new_i, 1, self.item_freq[ws, si] + 1)
        self.user_ids[ws, su] = us
        self.item_ids[ws, si] = its
        self.user_ts[ws, su] = self.clock[ws]
        self.item_ts[ws, si] = self.clock[ws]
        self.rated[ws, su, si] = True

    def forget(self):
        (uw, us), (iw, si) = srgrid.forget_tables(self, self.forgetting)
        self.user_vecs[uw, us] = 0
        self.item_vecs[iw, si] = 0
        self.rated[uw, us, :] = False
        self.rated[iw, :, si] = False
        self.evicted["forgetting"] += uw.size + iw.size

    def hits(self, w, us, its):
        su, si = self._slots(us, its)
        known_u = self.user_ids[w, su] == us
        known_i = self.item_ids[w, si] == its
        u = np.where(known_u[:, None], self.user_vecs[w, su], self.init_u[us])
        cand = (self.item_ids[w] >= 0)[None, :] & ~(
            self.rated[w, su] & known_u[:, None])
        # Products and sums in float32; the result rounded to ``dtype``.
        s = (u.astype(np.float32) @ self.item_vecs[w].astype(np.float32).T
             ).astype(self.dtype).astype(np.float32)
        s = np.where(cand, s, -np.inf)
        return srgrid.top_n_hits(s, si, known_i, self.top_n)

    def states(self) -> dict:
        """Leaves named as the program's state pytree names them."""
        t = {f"tables.{n}": getattr(self, n) for n in (
            "user_ids", "item_ids", "user_freq", "item_freq", "user_ts",
            "item_ts", "clock")}
        return {**t, "user_vecs": self.user_vecs, "item_vecs": self.item_vecs,
                "rated": self.rated}
