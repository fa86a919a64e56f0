"""StreamSession — one public facade over train / serve / rescale / drift.

The runtime grew organically: ``run_stream`` / ``run_stream_device`` for
training, ``SnapshotStore`` + ``QueryFrontend`` + ``grid_topn`` for
serving, ``regrid`` + ``retarget`` for elasticity, a growing positional
tuple out of ``restore_stream_checkpoint``, and detector state threaded
by hand for closed-loop drift. This module collapses those entry points
into one object with a five-verb lifecycle:

    cfg = repro.StreamConfig(algorithm="disgd", grid=repro.GridSpec(2))
    session = repro.StreamSession(
        cfg, publish=repro.PublishPolicy(every=8, mode="async"))
    session.ingest(users, items)        # incremental; call repeatedly
    session.recommend(user_ids)         # snapshot-backed grid top-N
    session.checkpoint(directory)       # grid-portable, detector included
    session = repro.StreamSession.restore(directory, cfg)
    session.rescale(repro.GridSpec.rect(4, 2))   # elastic regrid + serve

Everything underneath stays available for power users; the facade only
owns the *plumbing* — carrying states, the overflow re-queue, the drift
detector, and the serving snapshot across calls — never the math.
Algorithms resolve through the registry (``repro.core.algorithm``), so a
session drives any registered plugin (e.g. ``algorithm="bpr"``)
identically to the paper's pair.

Publishing is governed by one :class:`~repro.serve.policy.PublishPolicy`
owned by the session: cadence (``every`` micro-batches), sync vs async
rotation, and the read-side staleness bound.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from repro.core import algorithm as algorithm_lib
from repro.core import pipeline as pipeline_lib
from repro.core import storage as storage_lib
from repro.core.pipeline import (RestoredCheckpoint, StreamConfig,
                                 StreamResult, restore_stream_checkpoint,
                                 run_stream, save_stream_checkpoint)
from repro.core.routing import GridSpec
from repro.obs import metrics as metrics_lib
from repro.obs import telemetry as telemetry_lib
from repro.obs import trace as trace_lib
from repro.serve import (PublishPolicy, QueryFrontend, ServeConfig,
                         ServeResponse, SnapshotStore)

__all__ = ["StreamSession", "RestoredCheckpoint"]


class StreamSession:
    """A live streaming-recommender: state + serving plane + drift loop.

    Construction is cheap (zero states for ``cfg.grid``); all heavy work
    happens in the verbs. The session is single-writer: ``ingest`` /
    ``rescale`` mutate it, ``recommend`` reads the last published
    snapshot (so it can safely run from other threads between writes,
    the same contract as ``SnapshotStore``).
    """

    def __init__(self, cfg: StreamConfig, *, serve: ServeConfig | None = None,
                 publish: PublishPolicy | None = None,
                 snapshot_slots: int = 2,
                 metrics: metrics_lib.MetricsRegistry | None = None):
        self.cfg = cfg
        self.algorithm = algorithm_lib.get_algorithm(cfg.algorithm)
        # One registry spans the whole session — engine telemetry,
        # snapshot store, query front-end and stage spans all land here.
        # Pass a shared registry to aggregate several sessions into one
        # scrape; export via metrics.to_prometheus() / write_json().
        self.metrics = (metrics if metrics is not None
                        else metrics_lib.MetricsRegistry())
        self.store = SnapshotStore(slots=snapshot_slots,
                                   registry=self.metrics)
        # Device-telemetry fold path: publish boundaries carry the
        # in-scan TelemetryState; the store hands it to this folder (on
        # the publisher thread under async policies).
        self._telemetry = telemetry_lib.TelemetryFolder(self.metrics)
        self.store.set_telemetry_sink(self._telemetry.fold)
        # One policy governs both halves: the session's ingest cadence
        # and the front-end's staleness bound. An explicit ``publish``
        # wins; otherwise adopt the ServeConfig's (or the default).
        if serve is None:
            serve = ServeConfig.from_stream(cfg)
        if publish is None:
            publish = serve.publish
        else:
            serve = dataclasses.replace(serve, publish=publish)
        self.publish_policy = publish
        # The frontend owns the serving config (`self._frontend.cfg`);
        # retarget/recommend mutate it there, never a mirror here.
        self._frontend = QueryFrontend(self.store, serve)
        self._states = pipeline_lib.init_states(cfg)
        self._carry: tuple = (None, None)
        self._detector: Any = None
        # The fixed forgetting cadence's count: it follows the stream
        # across ingest calls, checkpoints and regrids.
        self._since = 0
        self.events_processed = 0
        self.forgets = 0
        hyper = cfg.resolved_hyper()
        self._telemetry.set_capacity(hyper.u_cap + hyper.i_cap)
        self._table_bytes = self.metrics.gauge(
            "table_bytes", "Exact resident bytes of a live state table",
            labels=("algorithm", "table", "dtype"))
        self._update_table_bytes()

    def _update_table_bytes(self) -> None:
        # Array metadata only (shape x itemsize) — no device sync.
        for table, (dtype, nbytes) in storage_lib.state_nbytes(
                self._states).items():
            self._table_bytes.labels(
                algorithm=self.cfg.algorithm, table=table,
                dtype=dtype).set(nbytes)

    # -- introspection ----------------------------------------------------

    @property
    def states(self):
        """Current stacked ``[n_c, ...]`` worker-state pytree (read-only)."""
        return self._states

    @property
    def grid(self) -> GridSpec:
        return self.cfg.grid

    @property
    def frontend(self) -> QueryFrontend:
        """The session's query front-end (read path; shares the store)."""
        return self._frontend

    # -- train ------------------------------------------------------------

    def ingest(self, users, items, *, verbose: bool = False) -> StreamResult:
        """Stream a batch of ``<user, item>`` events through the engine.

        Incremental and resumable: each call continues from the states,
        overflow carry, and drift-detector baseline the previous call
        (or ``restore``) left behind. Mid-run snapshot publishing
        follows the session's :class:`PublishPolicy`: with
        ``policy.every = k > 0`` the engine publishes into this
        session's store every ``k`` micro-batches (bounding serving
        staleness by ``k * micro_batch`` events), asynchronously when
        ``policy.mode == "async"`` so rotation never blocks the scan.
        The final state is always published (synchronously — the stream
        has ended, and ``recommend`` right after ``ingest`` must see
        it). Returns the segment's ``StreamResult``.
        """
        policy = self.publish_policy

        hook = None
        if policy.every > 0:
            base = self.events_processed
            base_forgets = self.forgets
            publish = (self.store.publish_async if policy.is_async
                       else self.store.publish)

            def hook(ev):
                publish(ev.states, base + ev.events_processed,
                        base_forgets + ev.forgets, telemetry=ev.telemetry)

        # The telemetry vector restarts from zero each run_stream call;
        # the previous segment's folds are complete (ingest ends with a
        # flush inside _publish), so rebasing here is race-free.
        self._telemetry.rebase()
        with trace_lib.span("ingest", self.metrics):
            res = run_stream(
                np.asarray(users), np.asarray(items), self.cfg,
                verbose=verbose,
                publish_every=policy.every,
                on_publish=hook,
                publish_sync=not policy.is_async,
                initial_states=self._states, initial_carry=self._carry,
                initial_detector=self._detector, initial_since=self._since)
        self._states = res.final_states
        self._since = res.events_since_trigger
        # run_stream drains the re-queue before returning (flushed or
        # counted in res.dropped), so the carry is consumed.
        self._carry = (None, None)
        if res.final_detector is not None:
            self._detector = res.final_detector
        self.events_processed += res.events_processed
        self.forgets += res.forgets
        self._publish(telemetry=res.telemetry)
        return res

    def _publish(self, telemetry=None) -> None:
        # Drain in-flight async rotations first: a mid-stream buffer
        # rotating after this final sync publish would regress the front
        # snapshot to an older stream position, breaking the "recommend
        # right after ingest sees the final state" guarantee.
        with trace_lib.span("publish", self.metrics):
            with trace_lib.span("flush"):
                self.store.flush()
            with trace_lib.span("rotate"):
                self.store.publish(self._states, self.events_processed,
                                   self.forgets)
            self._update_table_bytes()
            # Final fold of an ingest: the end-of-run vector covers any
            # tail past the last publish boundary (or the whole run when
            # publishing was off). After the flush, no async fold is in
            # flight.
            if telemetry is not None:
                with trace_lib.span("fold"):
                    self._telemetry.fold(telemetry)

    # -- serve ------------------------------------------------------------

    def recommend(self, user_ids, n: int | None = None) -> ServeResponse:
        """Grid-wide top-N for a batch of users, from the last snapshot.

        Runs the full serving plane: column fan-out + cross-split merge
        (``grid_topn``), LRU response cache, and the popularity fallback
        for unknown users. ``n`` overrides the list length (a new jit
        signature, so prefer a fixed ``n``); default is the serving
        config's ``top_n``.
        """
        if self.store.latest_version == 0:
            self._publish()     # cold session: serve the zero state
        if n is not None and n != self._frontend.cfg.top_n:
            # The fresh frontend shares the store's registry (idempotent
            # get-or-create), so the serve counters keep accumulating.
            self._frontend = QueryFrontend(
                self.store, dataclasses.replace(self._frontend.cfg, top_n=n))
        with trace_lib.span("serve", self.metrics):
            return self._frontend.serve(user_ids)

    # -- checkpoint / restore ---------------------------------------------

    def checkpoint(self, directory: str) -> str:
        """Write a grid-portable checkpoint (detector state and the
        forgetting cadence included)."""
        return save_stream_checkpoint(
            directory, self.events_processed, self._states,
            carry=self._carry, grid=self.cfg.grid,
            algorithm=self.cfg.algorithm, detector=self._detector,
            storage=self.cfg.storage, since=self._since)

    @classmethod
    def restore(cls, directory: str, cfg: StreamConfig,
                step: int | None = None, *,
                serve: ServeConfig | None = None,
                publish: PublishPolicy | None = None,
                snapshot_slots: int = 2,
                metrics: metrics_lib.MetricsRegistry | None = None,
                ) -> "StreamSession":
        """Resume a session from ``checkpoint`` output, at ``cfg.grid``.

        Grid-portable checkpoints regrid to the configured shape on the
        fly, so restoring at a different ``(n_i, g)`` than the save IS
        the scale-out path (see also :meth:`rescale` for live states).
        ``metrics`` lets the restored session join a shared (possibly
        scoped) registry — the ensemble restore path relies on this.
        """
        ck: RestoredCheckpoint = restore_stream_checkpoint(directory, cfg, step)
        session = cls(cfg, serve=serve, publish=publish,
                      snapshot_slots=snapshot_slots, metrics=metrics)
        session._states = ck.states
        session._carry = ck.carry
        session._detector = ck.detector
        session._since = ck.since
        session.events_processed = int(ck.events_processed)
        session._publish()
        return session

    # -- elasticity -------------------------------------------------------

    def rescale(self, grid: GridSpec, *, u_cap: int | None = None,
                i_cap: int | None = None, merge: str = "fresh",
                storage=None) -> None:
        """Reshape the live worker grid to ``grid`` (elastic S&R).

        Runs the algorithm's regrid hooks (logical extract + rebuild),
        swaps the session config to the new shape (optionally with new
        per-worker capacities), publishes the resharded snapshot, and
        retargets the query front-end — queries served right after this
        call already answer from the new grid, before any retraining.

        ``storage`` migrates the resident encoding in the same pass (the
        logical form is policy-portable): pass a new
        :class:`~repro.core.storage.StoragePolicy` to re-encode every
        table while regridding; default keeps the current policy.
        """
        hyper = self.cfg.resolved_hyper()
        new_u = u_cap if u_cap is not None else hyper.u_cap
        new_i = i_cap if i_cap is not None else hyper.i_cap
        new_storage = storage if storage is not None else self.cfg.storage
        with trace_lib.span("regrid", self.metrics):
            logical = self.algorithm.extract_logical(
                self._states, self.cfg.grid, storage=self.cfg.storage)
            self._states = self.algorithm.build_states(
                logical, src=self.cfg.grid, dst=grid,
                u_cap=new_u, i_cap=new_i, merge=merge, storage=new_storage)
            self.cfg = dataclasses.replace(
                self.cfg, grid=grid, storage=new_storage,
                hyper=hyper._replace(u_cap=new_u, i_cap=new_i))
            self._telemetry.set_capacity(new_u + new_i)
            self._publish()
            self._frontend.retarget(grid, u_cap=u_cap, storage=new_storage)
