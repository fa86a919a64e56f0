"""Snapshot double-buffering: serve a consistent grid while training runs.

The engine publishes worker states at micro-batch boundaries
(``engine.run_stream_device(publish_every=..., on_publish=...)``); this
store is the subscriber. It keeps a small ring of snapshot buffers
(double-buffered by default): ``publish`` writes the incoming state tree
into the back buffer and then atomically rotates it to the front, so
``acquire`` always returns a complete snapshot taken exactly at a
micro-batch boundary — a query can never observe partial state from a
later micro-batch. JAX arrays are immutable, so a published tree costs
no copy and stays valid however long a reader holds it while training
keeps producing new buffers.

Two publish paths share the rotation:

  * ``publish``       — synchronous: popularity aggregation + rotation
    complete before the call returns (deterministic; what tests of exact
    boundary state use).
  * ``publish_async`` — the trainer's hot path: enqueue the device-ready
    buffer and return immediately. A background publisher thread
    aggregates the popularity head, syncs the progress scalars, and
    performs the same atomic rotation — all off the scan's critical
    path. Under load the queue coalesces to the freshest buffer
    (intermediate publishes are counted in the
    ``snapshot_coalesced_total`` metric — ``stats_snapshot()
    ["coalesced"]`` — the production-correct backpressure: serve the
    newest state, never queue up stale rotations). ``flush()`` blocks
    until the queue drains — call it before asserting on the front
    snapshot.

Post-rotation listeners (``subscribe``) fire after every rotation,
outside the store lock — the hook serving loops use to react to fresh
state (e.g. a query burst per snapshot).

Bounded staleness: the trainer (or driver) reports stream progress via
``report_progress`` — publishes do this implicitly — and ``acquire``
raises ``StaleSnapshotError`` when the front snapshot has fallen more
than ``max_staleness_events`` processed events behind that progress.
The knob maps onto the publish cadence: publishing every ``k``
micro-batches of size ``mb`` bounds staleness by ``k * mb`` events
(``PublishPolicy.staleness_bound_events``).

Each snapshot also carries the grid-wide popularity head
(``popularity_topn`` over the paper's frequency statistics), the
front-end's fallback answer for unknown users.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Any, Callable

import numpy as np

from repro.core import state as state_lib
from repro.obs import metrics as metrics_lib
from repro.obs import trace as trace_lib

__all__ = ["Snapshot", "SnapshotStore", "StaleSnapshotError",
           "popularity_topn"]


class StaleSnapshotError(RuntimeError):
    """The front snapshot violates the caller's staleness bound."""


def popularity_topn(states, top_n: int):
    """Grid-wide most-popular items from a (stacked) state tree.

    Aggregates per-worker item rating mass (``state_lib.item_stats``) by
    global id — an item replicated across the ``g`` workers of its row
    contributes all replicas' local counts — and returns the ``top_n``
    head ordered by (mass desc, id asc).

    Returns:
      (ids int64[top_n] (-1 padded), mass float64[top_n]).
    """
    ids, weight = state_lib.item_stats(states)
    ids = np.asarray(ids).reshape(-1)
    weight = np.asarray(weight, np.float64).reshape(-1)
    live = ids >= 0
    ids, weight = ids[live], weight[live]
    out_ids = np.full(top_n, -1, np.int64)
    out_mass = np.zeros(top_n, np.float64)
    if ids.size:
        uniq, inverse = np.unique(ids, return_inverse=True)
        mass = np.zeros(uniq.size, np.float64)
        np.add.at(mass, inverse, weight)
        order = np.lexsort((uniq, -mass))[:top_n]
        out_ids[:order.size] = uniq[order]
        out_mass[:order.size] = mass[order]
    return out_ids, out_mass


@dataclasses.dataclass(frozen=True)
class Snapshot:
    """One published, read-only grid state at a micro-batch boundary."""

    states: Any               # [n_c, ...] worker-state pytree (immutable)
    version: int              # monotonically increasing publish counter
    events_processed: int     # stream position of the boundary
    forgets: int              # forgetting triggers fired up to the boundary
    popular_ids: np.ndarray   # popularity-fallback head (global ids)
    popular_mass: np.ndarray  # its rating mass (fallback "scores")


class SnapshotStore:
    """Double-buffered snapshot exchange between trainer and servers.

    Thread-safe; the rotation is a single front-index assignment under a
    lock, so readers either get the old complete snapshot or the new
    complete one, never a mix.
    """

    def __init__(self, slots: int = 2, fallback_n: int = 100,
                 registry: metrics_lib.MetricsRegistry | None = None):
        if slots < 2:
            raise ValueError("double-buffering needs at least 2 slots")
        self._slots: list[Snapshot | None] = [None] * slots
        self._front = -1
        self._version = 0
        self._progress = 0
        self._fallback_n = fallback_n
        self._lock = threading.Lock()
        self._listeners: list[Callable[[Snapshot], None]] = []
        # Async publish machinery: pending device-ready buffers drained by
        # a lazily-started daemon thread; ``_idle`` is set whenever the
        # queue is empty and no rotation is in flight. ``_draining`` is
        # the spawn gate: it flips true when a drain thread is started
        # and false only in the same critical section where that thread
        # decides to exit, so an enqueue can never observe a thread that
        # is alive but already past its exit decision.
        self._pending: collections.deque = collections.deque()
        self._publisher: threading.Thread | None = None
        self._draining = False
        self._idle = threading.Event()
        self._idle.set()
        # Publish-plane instruments. The registry is shared with whoever
        # passed it in (StreamSession wires one registry through store,
        # front-end and telemetry folder); a store constructed bare gets
        # its own.
        self.metrics = (registry if registry is not None
                        else metrics_lib.MetricsRegistry())
        self._c_rotations = self.metrics.counter(
            "snapshot_rotations_total", "Snapshot rotations by publish "
            "path", labels=("mode",))
        self._c_coalesced = self.metrics.counter(
            "snapshot_coalesced_total", "Async publishes coalesced away "
            "under backlog")
        self._g_front_version = self.metrics.gauge(
            "snapshot_front_version", "Version of the front snapshot")
        self._g_front_events = self.metrics.gauge(
            "snapshot_front_events", "Stream position of the front "
            "snapshot (events)")
        self._g_staleness = self.metrics.gauge(
            "snapshot_staleness_events", "Events the front snapshot "
            "trails reported stream progress")
        # Device-telemetry hand-off: StreamSession points this at its
        # TelemetryFolder.fold so publish boundaries carrying a telemetry
        # vector fold it into the registry — on the publisher thread for
        # the async path (observability costs the publisher, not the
        # scan).
        self._telemetry_sink: Callable[[Any], Any] | None = None

    # -- the rotation (shared by both publish paths) ----------------------

    def _rotate(self, states, events_processed: int, forgets: int,
                mode: str) -> Snapshot:
        popular_ids, popular_mass = popularity_topn(states, self._fallback_n)
        with self._lock:
            self._version += 1
            snap = Snapshot(
                states=states,
                version=self._version,
                events_processed=int(events_processed),
                forgets=int(forgets),
                popular_ids=popular_ids,
                popular_mass=popular_mass,
            )
            back = (self._front + 1) % len(self._slots)
            self._slots[back] = snap
            self._front = back                     # the atomic rotation
            self._progress = max(self._progress, snap.events_processed)
            listeners = list(self._listeners)
            self._c_rotations.labels(mode=mode).inc()
            self._g_front_version.set(snap.version)
            self._g_front_events.set(snap.events_processed)
            self._g_staleness.set(self._progress - snap.events_processed)
        for fn in listeners:    # outside the lock: listeners may acquire()
            fn(snap)
        return snap

    def publish(self, states, events_processed: int, forgets: int = 0,
                telemetry=None) -> Snapshot:
        """Synchronous publish: write, aggregate, rotate, then return.

        ``telemetry`` (a device ``TelemetryState`` from the publish
        boundary) is folded into the registry inline via the session's
        sink (:meth:`set_telemetry_sink`), after the rotation.
        """
        snap = self._rotate(states, events_processed, forgets, mode="sync")
        if telemetry is not None and self._telemetry_sink is not None:
            self._telemetry_sink(telemetry)
        return snap

    # -- async publish ----------------------------------------------------

    def publish_async(self, states, events_processed, forgets=0,
                      telemetry=None) -> None:
        """Enqueue a device-ready buffer; rotation happens off-thread.

        The call is the trainer's publish boundary, so it must cost
        next to nothing: one deque append. ``events_processed`` /
        ``forgets`` may be device scalars — the publisher thread syncs
        them (that host-blocking read is exactly what moves off the
        scan's critical path). Pending buffers coalesce: only the
        freshest enqueued state rotates when the publisher is behind —
        lossless for ``telemetry`` too, since the vector is cumulative.
        """
        with self._lock:
            self._pending.append((states, events_processed, forgets,
                                  telemetry))
            self._idle.clear()
            if not self._draining:
                self._draining = True
                self._publisher = threading.Thread(
                    target=self._drain_forever, name="snapshot-publisher",
                    daemon=True)
                self._publisher.start()

    def _drain_forever(self) -> None:
        try:
            while True:
                with self._lock:
                    if not self._pending:
                        # Exit decision and spawn-gate clear are one
                        # critical section (see __init__): an enqueue
                        # serialized after this sees _draining False and
                        # spawns a fresh thread — no stranded buffers.
                        self._draining = False
                        self._idle.set()
                        return
                    # Coalesce: rotate only the freshest pending buffer.
                    skipped = len(self._pending) - 1
                    states, events, forgets, telemetry = self._pending[-1]
                    self._pending.clear()
                    self._c_coalesced.inc(skipped)
                # int() here is THE deferred host sync of the non-blocking
                # publish boundary — it runs on this thread, so the scan
                # never waited for it. Same for the telemetry fold below.
                with trace_lib.span("publish_async", self.metrics):
                    self._rotate(states, int(events), int(forgets),
                                 mode="async")
                    if (telemetry is not None
                            and self._telemetry_sink is not None):
                        self._telemetry_sink(telemetry)
        except BaseException:
            # A failing rotation (e.g. a raising listener) must not wedge
            # the store: reopen the spawn gate so the next enqueue
            # restarts draining, and don't leave flush() hanging on an
            # empty queue.
            with self._lock:
                self._draining = False
                if not self._pending:
                    self._idle.set()
            raise

    def flush(self, timeout: float | None = None) -> bool:
        """Block until every pending async publish has rotated."""
        return self._idle.wait(timeout)

    def set_telemetry_sink(self, fn: Callable[[Any], Any] | None) -> None:
        """Install the fold target for publish-boundary telemetry
        vectors (e.g. ``TelemetryFolder(registry).fold``). The sink runs
        on the publisher thread for async publishes and inline for sync
        ones, always outside the store lock."""
        self._telemetry_sink = fn

    def stats_snapshot(self) -> dict[str, int]:
        """The publish counters as plain ints (registry-backed).

        Safe from any thread while the publisher is live. The legacy
        keys (``async_rotations``, ``coalesced``) keep their pre-registry
        meaning; the counters themselves live in ``self.metrics`` as
        ``snapshot_rotations_total{mode=}`` / ``snapshot_coalesced_total``.
        """
        a = int(self._c_rotations.labels(mode="async").value)
        s = int(self._c_rotations.labels(mode="sync").value)
        return {"async_rotations": a, "sync_rotations": s,
                "rotations": a + s,
                "coalesced": int(self._c_coalesced.value)}

    # -- subscribers ------------------------------------------------------

    def subscriber(self, mode: str = "sync"):
        """Adapter for the engine hook: ``on_publish=store.subscriber()``.

        ``mode="async"`` routes through :meth:`publish_async` (the
        non-blocking path); default is the synchronous rotation.
        """
        pub = self.publish_async if mode == "async" else self.publish

        def _on_publish(ev):
            pub(ev.states, ev.events_processed, ev.forgets,
                telemetry=getattr(ev, "telemetry", None))
        return _on_publish

    def subscribe(self, fn: Callable[[Snapshot], None]) -> None:
        """Call ``fn(snapshot)`` after every rotation (outside the lock).

        Sync publishes run listeners inline on the publishing thread;
        async publishes run them on the publisher thread — a listener
        serving queries therefore never blocks the trainer either way.
        """
        with self._lock:
            self._listeners.append(fn)

    # -- readers ----------------------------------------------------------

    def acquire(self, max_staleness_events: int | None = None) -> Snapshot:
        """The front snapshot; optionally enforce a staleness bound."""
        with self._lock:
            snap = self._slots[self._front] if self._front >= 0 else None
            progress = self._progress
        if snap is None:
            raise LookupError("no snapshot published yet")
        if (max_staleness_events is not None
                and progress - snap.events_processed > max_staleness_events):
            raise StaleSnapshotError(
                f"snapshot v{snap.version} is {progress - snap.events_processed}"
                f" events behind the stream (bound {max_staleness_events});"
                " publish more often or loosen the bound")
        return snap

    def report_progress(self, events_processed: int) -> None:
        """Advance the trainer's stream position (drives the staleness check)."""
        with self._lock:
            self._progress = max(self._progress, int(events_processed))

    def staleness(self) -> int:
        """Processed events the front snapshot is behind reported progress."""
        with self._lock:
            if self._front < 0:
                return 0
            return self._progress - self._slots[self._front].events_processed

    @property
    def progress(self) -> int:
        """Latest reported stream position (events processed)."""
        with self._lock:
            return self._progress

    @property
    def latest_version(self) -> int:
        with self._lock:
            return self._version
