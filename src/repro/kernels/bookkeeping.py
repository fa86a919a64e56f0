"""Vectorized per-bucket bookkeeping for the fused update kernels.

The reference workers walk a bucket event by event and keep seven small
tables per worker (user/item ids, LFU counters, LRU timestamps, clock).
Everything those tables feed into the heavy update — "is this id new in
its slot?" (collision eviction), the BPR negative's live id — is a
function of the bucket's slot sequence alone, so it can be computed for
all events at once from ``[E, E]`` "earlier event in the same slot"
masks instead of a sequential scan. The update kernels then receive the
per-event answers as scalar-prefetched flags and never touch the 1-D
tables.

Every function here is exact against ``ref.factor_apply`` /
``ref.dics_apply``: a slot's live id before event ``e`` is the id of the
latest *valid* event ``e' < e`` in that slot (writes are gated on
validity), or the bucket-start id; LFU counters restart at the latest
reset and count valid touches since; timestamps are the clock after the
slot's last valid touch.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

__all__ = ["Plan", "plan_bucket", "first_index"]

# Per-event flag bits handed to the kernels.
VALID, NEW_U, NEW_I, NEG_OK = 1, 2, 4, 8


class Plan(NamedTuple):
    flags: jnp.ndarray     # i32[E] VALID | NEW_U | NEW_I | NEG_OK bits
    tables: tuple          # Tables fields after the bucket (clock 0-d)
    new_u: jnp.ndarray     # bool[E]
    new_i: jnp.ndarray     # bool[E]
    item_cnt: jnp.ndarray | None  # DICS support counts after the bucket


def first_index(slots):
    """For each position, the first position holding the same slot."""
    same = slots[:, None] == slots[None, :]
    return jnp.argmax(same, axis=1).astype(jnp.int32)


def _latest(mask):
    """Index of the last True per row (-1 when none)."""
    idx = jnp.arange(mask.shape[1], dtype=jnp.int32)
    return jnp.max(jnp.where(mask, idx[None, :], -1), axis=1)


def _live(table, slots, same, before_valid, values):
    """Table value at ``slots`` as seen by each event, before its own write."""
    last = _latest(same & before_valid)
    return jnp.where(last >= 0, values[jnp.maximum(last, 0)], table[slots])


def _counter_after(c0, slots, same, upto, resets, adds):
    """Counter after each event: reset (to 0) where ``resets``, +1 where
    ``adds``; ``resets`` apply before ``adds`` within an event."""
    scope = same & upto
    r = _latest(scope & resets[None, :])
    idx = jnp.arange(slots.shape[0], dtype=jnp.int32)
    since = scope & adds[None, :] & (idx[None, :] >= r[:, None])
    n = jnp.sum(since, axis=1, dtype=c0.dtype)
    return jnp.where(r >= 0, n, c0[slots] + n)


def _last_touch(same, after, touched):
    """True at the last ``touched`` event of each slot."""
    return touched & ~jnp.any(same & after & touched[None, :], axis=1)


def plan_bucket(tabs, ev_u, ev_i, u_slots, i_slots, j_slots=None,
                item_cnt=None) -> Plan:
    """Flags and post-bucket bookkeeping tables for one worker's bucket.

    ``tabs`` is the flattened ``Tables`` tuple (clock 0-d). ``j_slots``
    (BPR) adds the NEG_OK precondition — the sampled slot's live id is a
    real item other than the positive, in another slot; the kernel adds
    the rated-history half of the rule. ``item_cnt`` (DICS) returns the
    support counts after the bucket, with the reference's unguarded
    eviction clears (padding events clear too).
    """
    uid, iid, ufq, ifq, uts, its, clock = tabs
    e = ev_u.shape[0]
    u_cap, i_cap = uid.shape[0], iid.shape[0]
    valid = ev_u >= 0
    idx = jnp.arange(e, dtype=jnp.int32)
    before = idx[None, :] < idx[:, None]
    upto = idx[None, :] <= idx[:, None]
    after = idx[None, :] > idx[:, None]
    before_valid = before & valid[None, :]
    same_u = u_slots[:, None] == u_slots[None, :]
    same_i = i_slots[:, None] == i_slots[None, :]

    new_u = _live(uid, u_slots, same_u, before_valid, ev_u) != ev_u
    new_i = _live(iid, i_slots, same_i, before_valid, ev_i) != ev_i

    vi = valid.astype(clock.dtype)
    clocks = clock + jnp.cumsum(vi)
    ufq_e = _counter_after(ufq, u_slots, same_u, upto, valid & new_u, valid)
    ifq_e = _counter_after(ifq, i_slots, same_i, upto, valid & new_i, valid)
    wu = jnp.where(_last_touch(same_u, after, valid), u_slots, u_cap)
    wi = jnp.where(_last_touch(same_i, after, valid), i_slots, i_cap)
    tables = (
        uid.at[wu].set(ev_u, mode="drop"),
        iid.at[wi].set(ev_i, mode="drop"),
        ufq.at[wu].set(ufq_e, mode="drop"),
        ifq.at[wi].set(ifq_e, mode="drop"),
        uts.at[wu].set(clocks, mode="drop"),
        its.at[wi].set(clocks, mode="drop"),
        clock + jnp.sum(vi),
    )

    flags = (valid * VALID + new_u * NEW_U + new_i * NEW_I).astype(jnp.int32)
    if j_slots is not None:
        same_ji = j_slots[:, None] == i_slots[None, :]
        neg_id = _live(iid, j_slots, same_ji, before_valid, ev_i)
        neg_pre = (neg_id >= 0) & (neg_id != ev_i) & (j_slots != i_slots)
        flags = flags + (neg_pre * NEG_OK).astype(jnp.int32)

    cnt = None
    if item_cnt is not None:
        ones = jnp.ones((e,), bool)
        cnt_e = _counter_after(item_cnt, i_slots, same_i, upto, new_i, valid)
        last = jnp.where(_last_touch(same_i, after, ones), i_slots, i_cap)
        cnt = item_cnt.at[last].set(cnt_e, mode="drop")
    return Plan(flags, tables, new_u, new_i, cnt)
