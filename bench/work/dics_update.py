"""Work the DICS update needs per event, whatever implements it.

For event ``<u, i>`` with ``h`` items already rated by ``u`` on its
worker, the algorithm reads that history (``h`` item ids, or the user's
rated bitmap row where that is smaller), adds 1 to ``h`` entries of
``co``'s row ``i`` and to ``h`` of its column ``i`` (each read and
written as float32), bumps ``cnt[i]`` and sets one rated bit: ``2 h + 1``
additions. ``h`` is counted over the warm start's history and the run's
events, per worker and user in stream order, which is the order each
worker sees them; only the window's events are summed. A lower bound
for any implementation.
"""

import numpy as np

import cellspec
import srgrid


def history_sizes(users, items, n_i: int, g: int) -> np.ndarray:
    """Per event, the distinct items its user rated on its worker before."""
    w = srgrid.route(users, items, n_i, g)
    _, wu = np.unique(np.stack([w, users]), axis=1, return_inverse=True)
    first = np.zeros(users.size, bool)
    first[np.unique(np.stack([w, users, items]), axis=1,
                    return_index=True)[1]] = True
    order = np.argsort(wu.ravel(), kind="stable")
    f = first[order].astype(np.int64)
    c = np.cumsum(f) - f                     # firsts before, in sort order
    start = np.searchsorted(wu.ravel()[order], wu.ravel()[order])
    h = np.empty(users.size, np.int64)
    h[order] = c - c[start]
    return h


def work(ctx) -> dict:
    doc = ctx.doc
    n_i, g = doc["n_i"], doc["n_i"] + doc["w"]
    _, i_cap = cellspec.capacities(doc)
    parts = list(ctx.calls)
    history = getattr(ctx, "history", None)
    if history is not None:
        parts.insert(0, history)
    skip = sum(u.size for u, _ in parts[:len(parts) - len(ctx.calls)
                                        + ctx.first_window_call])
    users = np.concatenate([np.asarray(u, np.int64) for u, _ in parts])
    items = np.concatenate([np.asarray(i, np.int64) for _, i in parts])
    h = history_sizes(users, items, n_i, g)[skip:].astype(np.float64)
    flops = float(np.sum(2 * h + 1))
    nbytes = float(np.sum(np.minimum(4 * h, i_cap / 8) + 16 * h + 8 + 1 / 8))
    return {"flops": flops, "bytes": nbytes}
