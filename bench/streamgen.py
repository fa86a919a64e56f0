"""Rating streams of the benchmark: a frozen generator and an endless feed.

``synth_stream`` and ``zipf_weights`` are copied from the program's
``repro.data.stream`` (``synth_stream`` and ``_zipf_weights``, as of the
commit that added this benchmark) and frozen here as the yardstick: a
later change to the program's generator does not change the benchmark's
traffic. Only the code paths the benchmark uses were kept (no drift
segments; global per-period dedupe), and one change was made: the
population (which users are active, which items popular, the taste
clusters) comes from the configuration's ``population_seed``, and only
the sampling of events from the run's seed. So every run serves the same
deployment, and seeds differ in the order and mix of its events, not in
its skew.

The stream has no end. Period ``p`` is ``synth_stream`` of the
configuration's profile with a sampling seed derived from ``p`` and the
run's seed; successive periods are drawn independently, so some pairs
recur across periods, as returning users re-rate. :class:`PeriodFeed`
makes period 0 at once and later periods on a producer thread, ahead of
the consumer.

Two ways to vary a stream by the run's seed (the mix's ``ids``):

* ``"sampled"``: the run's seed draws the events of every period;
* ``"relabeled"``: the events are drawn from the ``population_seed``, the
  same log for every run, and the run's seed relabels the ids by a
  permutation that keeps each user's ``u mod g`` and each item's
  ``i mod n_i`` (:func:`relabeling`). Every event then goes to the same
  S&R worker at the same place in its micro-batch, so every run does the
  same routing, re-queue and drain work on different ids and slots.
"""

from __future__ import annotations

import threading
import time

import numpy as np

__all__ = ["zipf_weights", "synth_stream", "period_seed", "relabeling",
           "PeriodFeed", "Starved"]


def zipf_weights(n: int, a: float, rng: np.random.Generator) -> np.ndarray:
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = ranks ** (-a)
    rng.shuffle(w)  # detach popularity from id order (ids are hash keys)
    return w / w.sum()


def synth_stream(profile: dict, seed: int):
    """One deduplicated ``(users, items)`` period matching ``profile``.

    ``profile`` holds ``n_users``, ``n_items``, ``n_ratings``,
    ``user_zipf`` and ``item_zipf`` (paper Table 1 shape statistics) and
    ``population_seed``. Users follow a Zipf activity law; each user
    belongs to one of a few taste clusters, each with its own Zipf item
    popularity. ``seed`` draws the events.
    """
    pop = np.random.default_rng(profile["population_seed"])
    rng = np.random.default_rng(seed)
    n = profile["n_ratings"]
    n_users, n_items = profile["n_users"], profile["n_items"]

    user_w = zipf_weights(n_users, profile["user_zipf"], pop)
    users = rng.choice(n_users, size=n, p=user_w)

    n_clusters = max(2, min(16, n_items // 64 or 2))
    user_cluster = pop.integers(0, n_clusters, size=n_users)
    cluster_weights = [zipf_weights(n_items, profile["item_zipf"], pop)
                       for _ in range(n_clusters)]

    seg = np.arange(n)
    items = np.empty(n, dtype=np.int64)
    for c in range(n_clusters):
        sel = seg[user_cluster[users] == c]
        if sel.size:
            items[sel] = rng.choice(n_items, size=sel.size,
                                    p=cluster_weights[c])

    pair = users.astype(np.int64) * n_items + items
    _, first = np.unique(pair, return_index=True)
    keep = np.zeros(n, dtype=bool)
    keep[first] = True
    return users[keep], items[keep]


def period_seed(seed: int, period: int) -> int:
    """Sampling seed of ``period`` of the stream of run seed ``seed``."""
    return int(np.random.SeedSequence([int(seed), int(period)])
               .generate_state(1, np.uint64)[0] >> 2)


def relabeling(n: int, classes: int, seed: int) -> np.ndarray:
    """A permutation of ``range(n)`` drawn from ``seed`` that keeps every
    id's residue ``mod classes`` (so its S&R worker and, with
    collision-free capacities, a slot of its own)."""
    rng = np.random.default_rng([int(seed), int(classes)])
    perm = np.empty(n, np.int64)
    for r in range(min(classes, n)):
        ids = np.arange(r, n, classes)
        perm[ids] = rng.permutation(ids)
    return perm


class Starved(RuntimeError):
    """The consumer asked for events the producer had not made yet."""


class PeriodFeed:
    """The endless stream of one run, handed out in order.

    Period 0 is made in the constructor (set-up). After :meth:`start`, a
    producer thread makes period ``p + 1`` as soon as fewer than half a
    period of events is left unconsumed, so the consumer never
    waits as long as the producer keeps up. Where :meth:`take` does
    wait, the wait is added to ``starved_s``, so that a run can say that
    it ran dry.
    """

    def __init__(self, profile: dict, seed: int, ids: str = "sampled",
                 classes: tuple[int, int] = (1, 1)):
        """``ids``: ``"sampled"`` or ``"relabeled"`` (module docstring);
        ``classes``: the residues a relabeling keeps, ``(g, n_i)``."""
        self.profile = profile
        self.seed = seed
        if ids == "sampled":
            self._log_seed, self._perm = seed, None
        elif ids == "relabeled":
            self._log_seed = profile["population_seed"]
            self._perm = (relabeling(profile["n_users"], classes[0], seed),
                          relabeling(profile["n_items"], classes[1], seed))
        else:
            raise ValueError(f"unknown ids {ids!r}")
        self._periods: list[tuple[np.ndarray, np.ndarray]] = [
            self._period(0)]
        self.low_water = self._periods[0][0].size // 2
        self._made = 1
        self._pos = 0           # events consumed from self._periods[0]
        self._cv = threading.Condition()
        self._thread: threading.Thread | None = None
        self._stop = False
        self.starved_s = 0.0
        self.error: BaseException | None = None

    def _period(self, p: int) -> tuple[np.ndarray, np.ndarray]:
        users, items = synth_stream(self.profile,
                                    period_seed(self._log_seed, p))
        if self._perm is None:
            return users, items
        return self._perm[0][users], self._perm[1][items]

    def _left(self) -> int:
        return sum(u.size for u, _ in self._periods) - self._pos

    def start(self) -> None:
        self._thread = threading.Thread(target=self._produce,
                                        name="period-producer", daemon=True)
        self._thread.start()

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join()

    def _produce(self) -> None:
        try:
            while True:
                with self._cv:
                    while not self._stop and self._left() >= self.low_water:
                        self._cv.wait()
                    if self._stop:
                        return
                    p = self._made
                nxt = self._period(p)
                with self._cv:
                    self._periods.append(nxt)
                    self._made += 1
                    self._cv.notify_all()
        except BaseException as e:  # reported by take(), never lost
            with self._cv:
                self.error = e
                self._cv.notify_all()

    def take_periods(self, k: int) -> tuple[np.ndarray, np.ndarray]:
        """The first ``k`` whole periods (the events a deployment took in
        before the run), made here; call before :meth:`start`, with
        nothing taken yet. The period after them is made too."""
        assert self._thread is None and self._pos == 0 and self._made == 1
        while self._made <= k:
            self._periods.append(self._period(self._made))
            self._made += 1
        got, self._periods = self._periods[:k], self._periods[k:]
        return (np.concatenate([u for u, _ in got]),
                np.concatenate([i for _, i in got]))

    def take(self, n: int) -> tuple[np.ndarray, np.ndarray]:
        """The next ``n`` events of the stream, in order."""
        us, its = [], []
        with self._cv:
            while n > 0:
                if self._left() == 0:
                    if self._thread is None:
                        raise Starved("period 0 is spent and no producer runs")
                    t0 = time.perf_counter()
                    while self._left() == 0 and self.error is None:
                        self._cv.wait()
                    self.starved_s += time.perf_counter() - t0
                    if self.error is not None:
                        raise self.error
                u, i = self._periods[0]
                m = min(n, u.size - self._pos)
                us.append(u[self._pos:self._pos + m])
                its.append(i[self._pos:self._pos + m])
                self._pos += m
                n -= m
                if self._pos == u.size:
                    self._periods.pop(0)
                    self._pos = 0
            self._cv.notify_all()
        return np.concatenate(us), np.concatenate(its)
