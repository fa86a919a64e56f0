"""Plain replay of the S&R grid: routing, micro-batches and re-queue.

The reference side of the output check. It walks the same ingest calls
the timed path was given and reproduces, event by event, where each
event goes and when it is trained:

* paper Algorithm 1: event ``<u, i>`` goes to worker
  ``(i mod n_i) * g + (u mod g)``;
* each ingest call is cut into micro-batches of ``micro_batch`` events;
  a worker takes at most ``capacity`` events per micro-batch, in stream
  order, and the rest are re-queued, in order, ahead of the next
  micro-batch (at most ``carry`` of them; any more are dropped);
* after the last micro-batch of a call, steps with no fresh events
  drain the re-queue; the call runs its steps in whole publish segments,
  and what is still queued at the end of the call is dropped;
* prequential evaluation: each event of a micro-batch is scored against
  its worker's state at the start of the micro-batch, then the worker
  trains on its events in order;
* forgetting every ``c`` events of the stream (``forget_every``): after
  each step that trained, the step's events are added to a count that
  carries across ingest calls; where it reaches ``c``, every worker
  forgets and ``c`` is taken off it, at most once a step.

A ``worker`` object supplies the algorithm: ``train(ws, us, its)``
applies one event on each of the distinct workers ``ws`` at once, and
``hits(w, us, its)`` scores a worker's events against its current state
and returns their hit bits and margins (:func:`top_n_hits`), and
``forget()`` runs the configuration's eviction on every worker. Nothing
here imports the program.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

__all__ = ["Layout", "Replay", "replay", "route", "top_n_hits",
           "forget_tables"]


class Layout(NamedTuple):
    n_i: int
    g: int
    micro_batch: int
    capacity: int
    carry: int
    publish_every: int

    @property
    def n_c(self) -> int:
        return self.n_i * self.g

    @property
    def row(self) -> int:
        """Slots of one step's row of prequential bits (carry + fresh)."""
        return self.carry + self.micro_batch


class Replay(NamedTuple):
    evaluated: np.ndarray   # bool[rows, row]: slot held an evaluated event
    hits: dict              # row index -> bool[row] (sampled rows only)
    margins: dict           # row index -> float[row], see top_n_hits
    dropped: int
    forgets: int            # forgetting triggers fired


def route(us, its, n_i: int, g: int):
    return (its % n_i) * g + (us % g)


def top_n_hits(scores: np.ndarray, target: np.ndarray, known: np.ndarray,
               top_n: int):
    """Prequential hits of a worker's events and how firmly each is decided.

    ``scores`` f32[E, I] (``-inf`` for a slot that is no candidate),
    ``target`` the event's item slot, ``known`` whether the worker knew
    the item. A hit is the target among the ``top_n`` best finite scores
    of its row, ties going to the lower slot. The margin is the absolute
    gap between the target's score and the score that decides it (the
    ``top_n``-th best for a miss, the next best after the list for a
    hit): ``inf`` where no finite score decides. Returns
    ``(hit bool[E], margin f64[E])``.
    """
    e, width = np.arange(scores.shape[0]), scores.shape[1]
    n = min(top_n, width)
    t = scores[e, target]
    ahead = (np.sum(scores > t[:, None], axis=1)
             + np.sum((scores == t[:, None])
                      & (np.arange(width)[None, :] < target[:, None]), axis=1))
    hit = known & np.isfinite(t) & (ahead < n)
    best = -np.partition(-scores, list(range(min(n + 1, width))), axis=1)
    cut = np.full(t.shape, -np.inf, np.float32)
    if n < width:
        cut = np.where(hit, best[:, n], best[:, n - 1])
    else:
        cut = np.where(hit, cut, best[:, n - 1])
    decided = known & np.isfinite(t) & np.isfinite(cut)
    with np.errstate(invalid="ignore"):
        margin = np.abs(t.astype(np.float64) - cut.astype(np.float64))
    return hit, np.where(decided, margin, np.inf)


def forget_tables(worker, policy: dict):
    """One forgetting trigger on the bookkeeping tables every reference
    keeps (paper Sec. 5.2; ``policy`` as ``cellspec.forgetting`` gives
    it). On each worker, LRU evicts the live entries with ``clock - ts >
    lru_max_age``, LFU those with ``freq < lfu_min_freq``; an evicted
    entry gets id -1, frequency and last-touch time 0. Returns the
    evicted ``(workers, slots)`` of users and of items, for the worker
    to clear what it learned of them."""
    out = []
    for ids, freq, ts in ((worker.user_ids, worker.user_freq, worker.user_ts),
                          (worker.item_ids, worker.item_freq, worker.item_ts)):
        if policy["policy"] == "lru":
            stale = worker.clock[:, None] - ts > policy["lru_max_age"]
        else:
            stale = freq < policy["lfu_min_freq"]
        w, s = np.nonzero((ids >= 0) & stale)
        ids[w, s] = -1
        freq[w, s] = 0
        ts[w, s] = 0
        out.append((w, s))
    return out


def _positions(keys: np.ndarray) -> np.ndarray:
    """Rank of each entry among the entries of its key, in order."""
    order = np.argsort(keys, kind="stable")
    ks = keys[order]
    pos = np.empty_like(order)
    pos[order] = np.arange(ks.size) - np.searchsorted(ks, ks, side="left")
    return pos


def replay(calls, lay: Layout, worker, sample_rows=(),
           forget_every: int | None = None) -> Replay:
    """Every ingest call of ``calls`` through the grid, in order;
    ``forget_every`` is the forgetting cadence ``c`` (``None``: none)."""
    sample_rows = set(int(r) for r in sample_rows)
    mb, cap, carry, n_c = lay.micro_batch, lay.capacity, lay.carry, lay.n_c
    evaluated, hits, margins = [], {}, {}
    dropped = forgets = since = 0
    for users, items in calls:
        users = np.asarray(users, np.int64)
        items = np.asarray(items, np.int64)
        n_batches = -(-users.size // mb)
        drain = -(-carry // cap) if n_batches else 0
        steps = n_batches + drain
        seg = lay.publish_every if lay.publish_every > 0 else max(steps, 1)
        steps = max(-(-steps // seg), 1) * seg
        cu = ci = np.empty(0, np.int64)
        for s in range(steps):
            fu = users[s * mb:(s + 1) * mb]
            fi = items[s * mb:(s + 1) * mb]
            if cu.size == 0 and fu.size == 0:
                continue                      # a step with no work
            bu = np.full(lay.row, -1, np.int64)
            bi = np.full(lay.row, -1, np.int64)
            bu[:cu.size], bi[:ci.size] = cu, ci
            bu[carry:carry + fu.size], bi[carry:carry + fi.size] = fu, fi
            valid = bu >= 0
            keys = np.where(valid, route(bu, bi, lay.n_i, lay.g), n_c)
            pos = _positions(keys)
            kept = valid & (pos < cap)
            over = np.flatnonzero(valid & ~kept)
            dropped += max(0, over.size - carry)
            over = over[:carry]
            slots = np.full((n_c, cap), -1, np.int64)
            at = np.flatnonzero(kept)
            slots[keys[at], pos[at]] = at

            row = len(evaluated)
            evaluated.append(kept)
            if row in sample_rows:
                h = np.zeros(lay.row, bool)
                m = np.full(lay.row, np.inf)
                for w in range(n_c):
                    idx = slots[w][slots[w] >= 0]
                    if idx.size:
                        h[idx], m[idx] = worker.hits(w, bu[idx], bi[idx])
                hits[row], margins[row] = h, m
            for p in range(cap):
                ws = np.flatnonzero(slots[:, p] >= 0)
                if ws.size == 0:
                    break
                idx = slots[ws, p]
                worker.train(ws, bu[idx], bi[idx])
            since += int(kept.sum())
            if forget_every is not None and since >= forget_every:
                worker.forget()
                since -= forget_every
                forgets += 1
            cu, ci = bu[over], bi[over]
        dropped += cu.size
    ev = (np.stack(evaluated) if evaluated
          else np.zeros((0, lay.row), bool))
    return Replay(evaluated=ev, hits=hits, margins=margins, dropped=dropped,
                  forgets=forgets)
