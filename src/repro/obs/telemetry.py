"""Device-resident telemetry riding the engine scan carry.

The same trick the drift detector uses (``repro.drift.detector``): a
small NamedTuple of 0-d integer scalars lives in the ``lax.scan`` carry
and is folded forward every micro-batch with pure ``jnp`` integer
arithmetic — zero per-micro-batch host sync, and *bit-identical* values
whether the stream ran through the host reference loop or the scanned
engine (integer adds and max commute with nothing; both paths execute
the same :func:`telemetry_batch_update` expression on the same inputs).

The vector counts, cumulatively within one ``run_stream`` call:

  * ``events``     — kept events processed;
  * ``dropped``    — overflow events past the re-queue capacity;
  * ``requeued``   — overflow events re-queued for a later micro-batch;
  * ``evictions``  — table entries freed by forgetting / drift control
    (occupancy delta across the forgetting op);
  * ``collisions`` — live tenants replaced by an id that shares their
    slot (collision eviction; occupancy does not change);
  * ``triggers``   — forgetting passes fired (fixed cadence or the
    adaptive controller);
  * ``hits`` / ``evals`` — prequential recall numerator / denominator;
  * ``bucket_hwm`` — per-bucket dispatch-load high-water mark
    (``i32[n_c]``; the skew/pressure signal the ROADMAP's autoscaler
    wants).

The host loop's overflow queue is unbounded, so it folds with
``carry_cap = HOST_CARRY_CAP`` (nothing ever drops at the dispatch
boundary); the engine passes its fixed re-queue size. On streams whose
per-batch overflow never exceeds the engine's re-queue (the condition
for the two backends to train identically at all), the folds agree
exactly.

Host side, :class:`TelemetryFolder` turns cumulative vectors into
registry counters: ``fold`` syncs the device scalars *on the calling
thread* (the async publisher thread, for ``publish_sync=False`` runs —
observability costs the publisher, never the scan) and increments each
counter by the delta since the previous fold, so coalesced publishes
that skip intermediate boundaries fold to exactly the same totals.
"""

from __future__ import annotations

import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["TelemetryState", "telemetry_init", "telemetry_update",
           "telemetry_batch_update", "telemetry_ints", "TelemetryFolder",
           "effective_list_len", "collision_evictions", "HOST_CARRY_CAP"]

# The host reference loop re-queues overflow into an unbounded Python
# list; folding with this capacity makes "never drops, always requeues"
# fall out of the same arithmetic the engine uses.
HOST_CARRY_CAP = int(np.iinfo(np.int32).max)


class TelemetryState(NamedTuple):
    """Cumulative in-scan telemetry (0-d i32 scalars + one i32[n_c])."""

    events: jnp.ndarray      # kept events processed
    dropped: jnp.ndarray     # overflow past the re-queue capacity
    requeued: jnp.ndarray    # overflow re-queued (backpressure volume)
    evictions: jnp.ndarray   # table entries freed by forgetting
    hits: jnp.ndarray        # prequential recall hits
    evals: jnp.ndarray       # prequential recall evaluations
    bucket_hwm: jnp.ndarray  # i32[n_c] per-bucket load high-water mark
    occ_hwm: jnp.ndarray     # i32[n_c] per-worker occupancy high-water
                             # mark (user + item live entries)
    list_len: jnp.ndarray    # summed effective top-N list length — the
                             # precision@N denominator (min(top_n,
                             # unrated candidates) per kept event,
                             # measured at bucket start)
    collisions: jnp.ndarray  # live tenants evicted by a colliding id
    triggers: jnp.ndarray    # forgetting passes fired


def telemetry_init(n_c: int) -> TelemetryState:
    z = jnp.zeros((), jnp.int32)
    return TelemetryState(z, z, z, z, z, z, jnp.zeros((n_c,), jnp.int32),
                          jnp.zeros((n_c,), jnp.int32), z, z, z)


def telemetry_update(tel: TelemetryState, *, kept, overflow, carry_cap,
                     evicted, hits, evals, load,
                     occupancy=None, list_len=0, collisions=0,
                     triggers=0) -> TelemetryState:
    """Fold one micro-batch of scalar counts into the running vector.

    Pure integer arithmetic so host and scan backends produce
    bit-identical values; every argument is (convertible to) i32.
    ``occupancy`` (i32[n_c] live entries per worker, user + item) is
    optional — ``None`` leaves the occupancy high-water mark unchanged.
    ``list_len`` is the batch's summed effective recommendation-list
    length (:func:`effective_list_len`) — the precision@N denominator;
    callers without a precision head leave it at 0. ``collisions``
    (:func:`collision_evictions`) and ``triggers`` (forgetting passes
    fired) default to 0 likewise.
    """
    overflow = jnp.asarray(overflow, jnp.int32)
    carry_cap = jnp.asarray(carry_cap, jnp.int32)
    occ_hwm = tel.occ_hwm
    if occupancy is not None:
        occ_hwm = jnp.maximum(occ_hwm, jnp.asarray(occupancy, jnp.int32))
    return TelemetryState(
        events=tel.events + jnp.asarray(kept, jnp.int32),
        dropped=tel.dropped + jnp.maximum(overflow - carry_cap, 0),
        requeued=tel.requeued + jnp.minimum(overflow, carry_cap),
        evictions=tel.evictions + jnp.asarray(evicted, jnp.int32),
        hits=tel.hits + jnp.asarray(hits, jnp.int32),
        evals=tel.evals + jnp.asarray(evals, jnp.int32),
        bucket_hwm=jnp.maximum(tel.bucket_hwm,
                               jnp.asarray(load, jnp.int32)),
        occ_hwm=occ_hwm,
        list_len=tel.list_len + jnp.asarray(list_len, jnp.int32),
        collisions=tel.collisions + jnp.asarray(collisions, jnp.int32),
        triggers=tel.triggers + jnp.asarray(triggers, jnp.int32),
    )


def telemetry_batch_update(tel: TelemetryState, *, kept, overflow,
                           carry_cap, evicted, hits, evaluated,
                           load, occupancy=None, list_len=0,
                           collisions=0, triggers=0) -> TelemetryState:
    """:func:`telemetry_update` with the recall reduction inlined.

    ``hits`` / ``evaluated`` are the worker step's ``bool[n_c, cap]``
    masks; reducing them here (instead of at each call site) pins one
    expression for both backends — the parity contract.
    """
    return telemetry_update(
        tel, kept=kept, overflow=overflow, carry_cap=carry_cap,
        evicted=evicted,
        hits=jnp.sum((hits & evaluated).astype(jnp.int32)),
        evals=jnp.sum(evaluated.astype(jnp.int32)), load=load,
        occupancy=occupancy, list_len=list_len, collisions=collisions,
        triggers=triggers)


def effective_list_len(states, ev_u, *, top_n: int, g: int, storage):
    """Summed effective top-N list length for one dispatched micro-batch.

    The precision@N head's denominator, computed where the recall head
    computes its numerator — on device, from the bucket-start ``states``
    (BEFORE the worker step trains on the batch; the same bucket-start
    contract the pallas recall bits carry). For each kept event the
    serveable list is ``min(top_n, live unrated items on the worker)`` —
    shorter than ``top_n`` only while a worker's item table is still
    warming up or the user has rated nearly everything resident.

    ``states`` is the stacked ``[n_c, ...]`` worker pytree (in its
    resident encoding — only the gathered rated rows are decoded, via
    :func:`repro.core.storage.gather_rated`); ``ev_u`` is the dispatch's
    ``i32[n_c, cap]`` user-id layout (−1 = empty slot). Pure integer
    arithmetic on the same inputs in both backends, so host and scan
    fold bit-identical sums.
    """
    from repro.core import state as state_lib
    from repro.core import storage as storage_lib

    ev_u = jnp.asarray(ev_u, jnp.int32)

    def per_worker(st, eu):
        t = st.tables
        u_cap = t.user_ids.shape[-1]
        i_cap = t.item_ids.shape[-1]
        valid = eu >= 0
        u_slot = state_lib.slot_of(eu, g, u_cap)
        known_u = valid & (t.user_ids[u_slot] == eu)
        rated = storage_lib.gather_rated(st.rated, u_slot, storage, i_cap)
        cand = (t.item_ids >= 0)[None, :] & ~(rated & known_u[:, None])
        n_cand = jnp.sum(cand.astype(jnp.int32), axis=-1)
        return jnp.sum(jnp.where(valid, jnp.minimum(n_cand, top_n), 0))

    return jnp.sum(jax.vmap(per_worker)(states, ev_u)).astype(jnp.int32)


def collision_evictions(states, ev_u, ev_i, *, g: int, n_i: int):
    """Live tenants one dispatched micro-batch evicts by collision.

    An event evicts where its slot, as the event finds it, holds another
    live id (``kernels.bookkeeping.held_ids``, the workers' own "new in
    its slot" rule). Users and items both count; padding (``-1``)
    evicts nothing. ``states`` are the
    bucket-start ``[n_c, ...]`` states and ``ev_u``/``ev_i`` the
    dispatch's ``i32[n_c, cap]`` layout, as for
    :func:`effective_list_len`.
    """
    from repro.core import state as state_lib
    from repro.kernels.bookkeeping import held_ids

    def replaced(table, ids, slots, valid):
        held = held_ids(table, ids, slots, valid)
        return jnp.sum((valid & (held >= 0) & (held != ids))
                       .astype(jnp.int32))

    def per_worker(st, eu, ei):
        t = st.tables
        valid = eu >= 0
        u_slot = state_lib.slot_of(eu, g, t.user_ids.shape[-1])
        i_slot = state_lib.slot_of(ei, n_i, t.item_ids.shape[-1])
        return (replaced(t.user_ids, eu, u_slot, valid)
                + replaced(t.item_ids, ei, i_slot, valid))

    return jnp.sum(jax.vmap(per_worker)(
        states, jnp.asarray(ev_u, jnp.int32),
        jnp.asarray(ev_i, jnp.int32))).astype(jnp.int32)


def telemetry_ints(tel: TelemetryState) -> dict:
    """Host-int view of a telemetry vector (blocks on device reads)."""
    return {
        "events": int(tel.events),
        "dropped": int(tel.dropped),
        "requeued": int(tel.requeued),
        "evictions": int(tel.evictions),
        "hits": int(tel.hits),
        "evals": int(tel.evals),
        "bucket_hwm": [int(v) for v in np.asarray(tel.bucket_hwm)],
        "occ_hwm": [int(v) for v in np.asarray(tel.occ_hwm)],
        "list_len": int(tel.list_len),
        "collisions": int(tel.collisions),
        "triggers": int(tel.triggers),
    }


class TelemetryFolder:
    """Folds cumulative telemetry vectors into a metrics registry.

    The vector restarts from zero at every ``run_stream`` call, so the
    owner (``StreamSession.ingest``) calls :meth:`rebase` at the start
    of each segment; ``fold`` then increments the ``stream_*`` counters
    by the delta against the previously folded vector. Because the
    vector is cumulative, folding only the freshest of several pending
    publishes (the snapshot store's coalescing) loses nothing.
    """

    _SCALARS = ("events", "dropped", "requeued", "evictions", "hits",
                "evals", "list_len", "collisions", "triggers")

    def __init__(self, registry):
        self.registry = registry
        self._lock = threading.Lock()
        self._last: dict | None = None
        self._counters = {
            "events": registry.counter(
                "stream_events_total", "Events processed (kept) by the "
                "streaming engine"),
            "dropped": registry.counter(
                "stream_dropped_total", "Overflow events dropped past "
                "the re-queue capacity"),
            "requeued": registry.counter(
                "stream_requeued_total", "Overflow events re-queued into "
                "a later micro-batch"),
            "evictions": registry.counter(
                "stream_evictions_total", "Table entries freed by "
                "forgetting / drift control"),
            "hits": registry.counter(
                "stream_recall_hits_total", "Prequential recall hits"),
            "evals": registry.counter(
                "stream_recall_evals_total", "Prequential recall "
                "evaluations"),
            "list_len": registry.counter(
                "stream_list_len_total", "Summed effective top-N list "
                "length (precision@N denominator)"),
            "collisions": registry.counter(
                "stream_collision_evictions_total", "Live table entries "
                "evicted by an id that shares their slot"),
            "triggers": registry.counter(
                "stream_forget_triggers_total", "Forgetting passes fired "
                "(fixed cadence or drift controller)"),
        }
        self._hwm = registry.gauge(
            "stream_bucket_hwm", "Per-bucket dispatch-load high-water "
            "mark (events)", labels=("bucket",))
        self._occ_frac = registry.gauge(
            "bucket_occupancy_frac", "Per-worker occupancy high-water "
            "mark as a fraction of table capacity (user + item entries)",
            labels=("bucket",))
        self._capacity: int | None = None

    def set_capacity(self, entries: int) -> None:
        """Per-worker entry capacity (u_cap + i_cap) for the occupancy
        fraction gauge; owner calls this at init and after a rescale."""
        with self._lock:
            self._capacity = int(entries) if entries else None

    def rebase(self) -> None:
        """Mark the start of a new stream segment (counters reset to 0)."""
        with self._lock:
            self._last = None

    def fold(self, tel) -> dict | None:
        """Sync ``tel`` (on this thread) and fold deltas into counters."""
        if tel is None:
            return None
        vals = telemetry_ints(tel)
        with self._lock:
            last = self._last if self._last is not None else {}
            for f in self._SCALARS:
                delta = vals[f] - last.get(f, 0)
                if delta > 0:
                    self._counters[f].inc(delta)
            for b, v in enumerate(vals["bucket_hwm"]):
                self._hwm.labels(bucket=str(b)).set_max(v)
            if self._capacity:
                for b, v in enumerate(vals.get("occ_hwm", ())):
                    self._occ_frac.labels(bucket=str(b)).set(
                        v / self._capacity)
            self._last = vals
        return vals
