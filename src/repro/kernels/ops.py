"""Public entry points for the Pallas kernels.

Dispatch policy: on TPU every op runs its Pallas kernel (padding to
hardware-aligned shapes first); off TPU the op returns its jnp oracle
(``kernels/ref.py``) — compiled XLA, fast on CPU/GPU — rather than the
interpret-mode kernel, which emulates the grid step-by-step and is two
orders of magnitude slower than the oracle. Pass ``interpret=True`` to
force the interpret-mode kernel body anywhere (the parity tests do, so
the kernel semantics stay validated on every platform; the update
kernels run in TPU interpret mode, which emulates their DMAs), or
``interpret=False`` to force a real kernel launch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import custom_batching
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import bookkeeping, ref
from repro.kernels.dics_update import dics_update_pallas
from repro.kernels.factor_update import factor_update_pallas
from repro.kernels.scoring import masked_scores_pallas
from repro.kernels.swa_attention import swa_attention_pallas
from repro.kernels.topn import dics_topn_pallas, fused_topn_pallas, query_block
from repro.obs import trace as trace_lib

__all__ = ["on_tpu", "masked_scores", "clear_columns", "factor_update",
           "dics_update", "fused_topn", "dics_topn", "swa_attention",
           "topn_select", "topn_merge"]

_I32_MAX = np.iinfo(np.int32).max


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x, axis: int, multiple: int, value=0):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def masked_scores(u_vecs, item_vecs, mask, *, block_b: int = 128,
                  block_i: int = 512, interpret: bool | None = None):
    """Masked recommendation scoring: f32[B, I], -inf where masked."""
    if interpret is None:
        if not on_tpu():
            return ref.masked_scores(u_vecs, item_vecs, mask)
        interpret = False
    b, k = u_vecs.shape
    i = item_vecs.shape[0]
    block_b = min(block_b, max(8, 1 << (b - 1).bit_length()))
    block_i = min(block_i, max(128, 1 << (i - 1).bit_length()))

    up = _pad_to(_pad_to(u_vecs, 0, block_b), 1, 128)
    ip = _pad_to(_pad_to(item_vecs, 0, block_i), 1, 128)
    mp = _pad_to(_pad_to(mask, 0, block_b, value=False), 1, block_i, value=False)
    out = masked_scores_pallas(
        up, ip, mp, block_b=block_b, block_i=block_i, interpret=interpret
    )
    return out[:b, :i]


def _interpret_mode(interpret):
    """``interpret=True`` runs the kernel body in TPU interpret mode, which
    also emulates its DMAs and semaphores."""
    return pltpu.InterpretParams() if interpret is True else interpret


def _worker_batched(kernel_fn, n_out: int):
    """Per-worker view of a function written for a leading worker axis.

    Called unbatched it runs on a one-worker axis; under ``vmap`` (the
    engine maps the worker step over the S&R grid) the batching rule
    hands the whole grid to ONE call — for the kernels, one launch whose
    grid axis is the worker. Pallas' generic batching rule would instead
    loop over workers and copy every HBM-resident table per call.
    """

    @custom_batching.custom_vmap
    def one(*args):
        outs = kernel_fn(*(a[None] for a in args))
        return tuple(o[0] for o in outs)

    @one.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        return tuple(kernel_fn(*args)), (True,) * n_out

    return one


@functools.lru_cache(maxsize=64)
def _factor_kernel(eta: float, lam: float, pairwise: bool, interpret):
    return _worker_batched(functools.partial(
        factor_update_pallas, eta=eta, lam=lam, pairwise=pairwise,
        interpret=_interpret_mode(interpret)), 3)


@functools.lru_cache(maxsize=8)
def _dics_kernel(interpret):
    return _worker_batched(functools.partial(
        dics_update_pallas, interpret=_interpret_mode(interpret)), 2)


def _compact_rows(table, slots):
    """Gather the rows ``slots`` touch, one per distinct slot.

    Returns ``(rows, first, dest)``: ``rows[p]`` is ``table[slots[p]]``;
    ``first[p]`` is the position of the first event holding the same
    slot (every event of a slot works on that one row); ``dest`` is the
    scatter target for writing the rows back (the table size — dropped —
    for repeats).
    """
    first = bookkeeping.first_index(slots)
    own = first == jnp.arange(slots.shape[0], dtype=jnp.int32)
    return table[slots], first, jnp.where(own, slots, table.shape[0])


def _clear_columns_batched(table, cols, clear):
    """Zero column ``cols[w, e]`` of worker ``w``'s ``table`` [W, U, I]
    wherever ``clear[w, e]``, in event order. Each clear is one in-place
    column update: a column scatter next to the tables' row accesses
    makes XLA transpose the whole table around it on the TPU."""
    n_w, n_e = clear.shape
    zeros = jnp.zeros((1, table.shape[1], 1), table.dtype)

    def clear_one(t, r):
        w, e = t // n_e, t % n_e
        return jax.lax.cond(
            clear[w, e],
            lambda r: jax.lax.dynamic_update_slice(r, zeros,
                                                   (w, 0, cols[w, e])),
            lambda r: r, r)

    with trace_lib.layer("column_clear"):
        return (jax.lax.fori_loop(0, n_w * n_e, clear_one, table),)


clear_columns = _worker_batched(_clear_columns_batched, 1)


def _write_rated_batched(rated, cols, clear, dest, rows):
    """Write a bucket's rated-table changes back, for ``W`` workers.

    ``rated`` bool[W, U, I]; ``cols``/``clear`` [W, E] name the evicted
    item slots whose columns are zeroed, and ``dest``/``rows`` [W, E] /
    [W, E, I] the compact user rows to write back (which already carry
    those clears).
    """
    (rated,) = _clear_columns_batched(rated, cols, clear)
    w_idx = jnp.broadcast_to(jnp.arange(rated.shape[0])[:, None], dest.shape)
    return (rated.at[w_idx, dest].set(rows, mode="drop"),)


_write_rated = _worker_batched(_write_rated_batched, 1)


def factor_update(user_vecs, item_vecs, rated, tabs, events, *, eta: float,
                  lam: float, interpret: bool | None = None):
    """Complete factor-model micro-batch update (vectors + bookkeeping +
    rated bitmap), plain ISGD or pairwise BPR by the shape of ``events``.

    See ``ref.factor_apply`` for the full contract. On the kernel path
    the bookkeeping tables and per-event flags come from
    ``bookkeeping.plan_bucket``, the touched factor and rated rows are
    gathered into compact blocks, the kernel applies the events to them
    in order, and the rows are scattered back in place. Returns
    ``(user_vecs, item_vecs, rated, tabs)``.
    """
    with trace_lib.layer("update"):
        if interpret is None:
            if not on_tpu():
                return ref.factor_apply(user_vecs, item_vecs, rated, tabs,
                                        events, eta=eta, lam=lam)
            interpret = False
        ev_u, ev_i, u_slots, i_slots, j_slots, init_u, init_i = events
        pairwise = j_slots is not None
        if not pairwise:
            j_slots = i_slots
        plan = bookkeeping.plan_bucket(tuple(tabs), ev_u, ev_i, u_slots,
                                       i_slots, j_slots if pairwise else None)
        e = ev_u.shape[0]
        u_rows, ua, u_dest = _compact_rows(user_vecs, u_slots)
        cat = jnp.concatenate([i_slots, j_slots])
        i_rows, cat_first, i_dest = _compact_rows(item_vecs, cat)
        r_rows = rated[u_slots].astype(jnp.float32)
        scalars = (ua, cat_first[:e], cat_first[e:],
                   i_slots.astype(jnp.int32), j_slots.astype(jnp.int32),
                   plan.flags)
        kernel = _factor_kernel(eta, lam, pairwise, interpret)
        u_rows, i_rows, r_rows = kernel(*scalars, init_u, init_i, u_rows,
                                        i_rows, r_rows)
        (rated,) = _write_rated(rated, i_slots, (ev_u >= 0) & plan.new_i,
                                u_dest, r_rows != 0)
        user_vecs = user_vecs.at[u_dest].set(u_rows, mode="drop")
        item_vecs = item_vecs.at[i_dest].set(i_rows, mode="drop")
        return user_vecs, item_vecs, rated, plan.tables


def dics_update(co, item_cnt, rated, tabs, events, *,
                interpret: bool | None = None):
    """DICS co-occurrence micro-batch update (Eq. 6 statistics +
    bookkeeping). See ``ref.dics_apply``; returns
    ``(co, item_cnt, rated, tabs)``.

    On the kernel path ``co`` is updated in place by
    ``kernels/dics_update.py`` (its item width is padded to a multiple
    of 128 only when it is not one already), the support counts and
    bookkeeping come from ``bookkeeping.plan_bucket``, and the touched
    rated rows travel as compact blocks like in :func:`factor_update`.
    """
    with trace_lib.layer("update"):
        if interpret is None:
            if not on_tpu():
                return ref.dics_apply(co, item_cnt, rated, tabs, events)
            interpret = False
        ev_u, ev_i, u_slots, i_slots = events
        plan = bookkeeping.plan_bucket(tuple(tabs), ev_u, ev_i, u_slots,
                                       i_slots, item_cnt=item_cnt)
        i_cap = co.shape[0]
        r_rows, ua, u_dest = _compact_rows(rated, u_slots)
        r_rows = _pad_to(r_rows.astype(jnp.float32), 1, 128)
        co_p = _pad_to(_pad_to(co, 0, 128), 1, 128)
        scalars = (ua, i_slots.astype(jnp.int32), plan.flags)
        r_rows, co_p = _dics_kernel(interpret)(*scalars, r_rows, co_p)
        (rated,) = _write_rated(rated, i_slots, plan.new_i, u_dest,
                                r_rows[:, :i_cap] != 0)
        return co_p[:i_cap, :i_cap], plan.item_cnt, rated, plan.tables


def fused_topn(u_vecs, item_vecs, mask, item_ids, *, top_n: int,
               block_b: int = 128, block_i: int = 512,
               interpret: bool | None = None):
    """Fused serve leaf: masked scoring + partial top-N in one pass.

    Exactly equivalent to ``masked_scores`` followed by ``topn_select``
    over ``item_ids`` broadcast per row — including non-candidate
    entries surfacing their real ids at -inf (the property test in
    tests/test_kernel_parity.py pins the equivalence on tied tables).

    Args:
      u_vecs: f32[B, k]; item_vecs: f32[I, k]; mask: bool[B, I];
      item_ids: i32[I] global ids aligned with the item table rows.

    Returns (ids i32[B, top_n], scores f32[B, top_n]) in serving order.
    """
    if interpret is None:
        if not on_tpu():
            scores = ref.masked_scores(u_vecs, item_vecs, mask)
            ids_b = jnp.broadcast_to(item_ids[None, :], scores.shape)
            return topn_select(scores, ids_b, top_n)
        interpret = False
    b = u_vecs.shape[0]
    i = item_vecs.shape[0]
    block_b = min(block_b, max(8, 1 << (b - 1).bit_length()))
    block_i = min(block_i, max(128, 1 << (i - 1).bit_length()))
    up = _pad_to(_pad_to(u_vecs, 0, block_b), 1, 128)
    ip = _pad_to(_pad_to(item_vecs, 0, block_i), 1, 128)
    mp = _pad_to(_pad_to(mask, 0, block_b, value=False), 1, block_i,
                 value=False).astype(jnp.int8)
    # Padding ids sort after every real entry (-inf ties break id-asc).
    idp = _pad_to(item_ids.reshape(1, -1), 1, block_i, value=_I32_MAX)
    out_id, out_sc = fused_topn_pallas(
        up, ip, mp, idp.astype(jnp.int32), top_n=top_n,
        block_b=block_b, block_i=block_i, interpret=interpret,
    )
    return out_id[:b], out_sc[:b]


def dics_topn(co, item_cnt, hist, known, item_ids, *, top_n: int,
              k_nn: int, block_p: int = 128, interpret: bool | None = None):
    """DICS Eq. 6/7 kernel (similarity + neighbor mass + partial top-N
    in one pass): the serve leaf and training's bucket-start scoring.

    Unlike the other ops this has no oracle shortcut — the jnp paths
    live in ``core/dics`` (``dics_partial_topn``, ``_bucket_topn``),
    which are also the dispatch sites; ``interpret=None`` runs the
    interpret-mode kernel off TPU so the body stays exercisable
    everywhere.

    Args:
      co: f32[I, I]; item_cnt: f32[I]; hist: bool[B, I] known-masked
      rated rows; known: bool[B]; item_ids: i32[I].

    Returns (ids i32[B, top_n], scores f32[B, top_n]) in serving order.
    """
    if interpret is None:
        interpret = not on_tpu()
    b = hist.shape[0]
    i = co.shape[0]
    block_p = min(block_p, max(128, 1 << (i - 1).bit_length()))
    cop = _pad_to(_pad_to(co, 0, block_p), 1, block_p)
    cntp = _pad_to(item_cnt.reshape(1, -1), 1, block_p)
    # Queries come in sublane-aligned blocks; padded rows are unknown
    # users and are cut off below.
    block_q = query_block(b)
    histp = _pad_to(_pad_to(hist.astype(jnp.int8), 1, block_p), 0, block_q)
    knownp = _pad_to(known.astype(jnp.int32).reshape(-1, 1), 0, block_q)
    # Padded candidates carry cnt 0 -> zero neighbor mass -> excluded by
    # the score > 0 rule; id INT32_MAX keeps them after every real entry.
    idp = _pad_to(item_ids.reshape(1, -1), 1, block_p, value=_I32_MAX)
    out_id, out_sc = dics_topn_pallas(
        cop, cntp, histp, knownp, idp.astype(jnp.int32), top_n=top_n,
        k_nn=k_nn, block_p=block_p, block_q=block_q, interpret=interpret,
    )
    return out_id[:b], out_sc[:b]


def topn_select(scores, ids, top_n: int):
    """Deterministic top-N selection over the last axis.

    Ordering is (score descending, global id ascending on ties) — unlike
    ``lax.top_k``, whose tie-break is the *slot index*, this ordering is
    independent of where an item happens to live in a worker's table, so
    the same candidate set always yields the same list no matter which
    split/slot layout produced it. The serving plane relies on that for
    cross-split merges (``repro.serve.plane``); single-worker serving uses
    it too so grid-merged and local lists agree exactly.

    Args:
      scores: f32[..., C] candidate scores (-inf = not a candidate).
      ids:    i32[..., C] global ids aligned with ``scores``.
      top_n:  list length (clamped to C).

    Returns:
      (ids i32[..., N], scores f32[..., N]) in serving order.
    """
    n = min(top_n, scores.shape[-1])
    order = jnp.lexsort((ids, -scores), axis=-1)[..., :n]
    return (jnp.take_along_axis(ids, order, -1),
            jnp.take_along_axis(scores, order, -1))


def topn_merge(ids, scores, top_n: int):
    """Merge partial top-N lists along axis -2 into one list.

    ``ids``/``scores`` are [..., P, N] — P partial lists (one per item
    split in the serving plane). Splits partition the global item space,
    so the same id never appears in two partials and a flat re-selection
    over the P*N candidates is an exact merge. The P*N candidate set is
    tiny (n_i * top_n), so this is a jnp sort rather than a kernel; the
    FLOP-heavy part of serving is the fused scoring+selection leaf
    (``fused_topn``), which has the Pallas path.
    """
    flat_ids = ids.reshape(ids.shape[:-2] + (-1,))
    flat_scores = scores.reshape(scores.shape[:-2] + (-1,))
    return topn_select(flat_scores, flat_ids, top_n)


def swa_attention(q, k, v, *, window: int | None = None, causal: bool = True,
                  block_q: int = 128, block_k: int = 128,
                  interpret: bool | None = None):
    """Flash sliding-window attention. q:[B,Hq,S,D], k/v:[B,Hkv,S,D]."""
    if interpret is None:
        if not on_tpu():
            return ref.swa_attention(q, k, v, window=window, causal=causal)
        interpret = False
    s = q.shape[2]
    if s < block_q or s % block_q or s % block_k:
        # Small/ragged sequences: oracle is cheaper than a padded kernel.
        return ref.swa_attention(q, k, v, window=window, causal=causal)
    return swa_attention_pallas(
        q, k, v, window=window, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
