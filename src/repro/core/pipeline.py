"""End-to-end S&R streaming pipeline (paper Figure 1/2).

Ties together routing (Alg. 1), the per-worker incremental algorithms
(Alg. 2 / Alg. 3), forgetting, and prequential evaluation (Alg. 4) into a
micro-batched streaming loop. ``run_stream`` is a thin dispatcher over
execution backends (``StreamConfig.backend``):

  * ``"host"`` — the interpretable reference loop in this module:
    host-side bucketing (Alg. 1) -> device worker steps -> host scatter of
    recall bits; states round-trip host<->device every micro-batch.
  * ``"scan"`` / ``"pallas"`` / ``"shard_map"`` — the device-resident
    engine (``repro.core.engine``): the whole prequential loop is one
    jitted ``lax.scan`` with on-device dispatch, in-scan forgetting and
    overflow re-queue; states never leave the device. See the engine
    module docstring for the worker execution modes.

Workers are simulated on CPU with ``vmap`` over the worker axis; the same
step functions run under ``shard_map`` on the production mesh
(``core/distributed.py``, each mesh coordinate = one worker).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import algorithm as algorithm_lib
from repro.core import forgetting as forgetting_lib
from repro.core import routing, state as state_lib
from repro.core import storage as storage_lib
from repro.core.evaluator import RecallAccumulator
from repro.core.regrid import CheckpointShapeError
from repro.core.storage import StoragePolicy, StoragePolicyError

__all__ = ["StreamConfig", "StreamResult", "RestoredCheckpoint", "run_stream",
           "make_worker_step", "init_states",
           "save_stream_checkpoint", "restore_stream_checkpoint",
           "CheckpointShapeError", "StoragePolicyError", "LOGICAL_FORMAT"]


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    # Registry key into repro.core.algorithm ("disgd", "dics", plugins…).
    algorithm: str = "disgd"
    grid: routing.GridSpec = routing.GridSpec(1, 0)
    micro_batch: int = 2048
    capacity_factor: float = 2.0             # bucket capacity vs fair share
    forgetting: forgetting_lib.ForgettingConfig = forgetting_lib.ForgettingConfig()
    hyper: Any = None                        # DisgdHyper | DicsHyper (caps etc.)
    seed: int = 0
    record_every: int = 4                    # occupancy snapshot cadence
    backend: str = "host"                    # "host"|"scan"|"pallas"|"shard_map"
    carry_slots: int = 0                     # overflow re-queue size (0 = micro_batch)
    # Opt-in closed-loop concept-drift policy (repro.drift.DriftPolicy).
    # When its mode is "adaptive", the on-device detector + controller
    # replace the fixed `forgetting.trigger_every` cadence entirely.
    drift: Any = None
    # In-scan observability counters (repro.obs.telemetry) riding the
    # carry; off buys back the few extra reductions per micro-batch
    # (benchmarks/bench_obs.py gates the overhead at 3%).
    telemetry: bool = True
    # Per-table resident encoding of worker state (repro.core.storage):
    # every layer that touches state decodes -> computes in f32/bool ->
    # encodes at micro-batch boundaries. The default is bit-identical to
    # the pre-policy code (identity codecs).
    storage: StoragePolicy = StoragePolicy()

    def resolved_hyper(self):
        h = self.hyper
        if h is None:
            h = algorithm_lib.get_algorithm(self.algorithm).default_hyper()
        return h._replace(n_i=self.grid.n_i, g=self.grid.g)

    @property
    def bucket_capacity(self) -> int:
        fair = self.micro_batch / self.grid.n_c
        return max(8, int(np.ceil(fair * self.capacity_factor)))


@dataclasses.dataclass
class StreamResult:
    """What one ``run_stream`` call measured and produced.

    ``events_processed`` / ``dropped`` / ``forgets`` are always plain
    Python ints here, in both publish modes — the engine syncs them once
    at end of stream. The 0-d *device* scalars that exist mid-run under
    ``publish_sync=False`` are never on this object; they ride on each
    boundary's :class:`~repro.core.engine.PublishEvent` (resolve them
    with ``PublishEvent.as_ints()``).
    """

    recall: RecallAccumulator
    user_occupancy: list      # [(events_processed, np[n_c])]
    item_occupancy: list
    events_processed: int
    dropped: int
    wall_seconds: float
    load_history: list        # per-batch worker loads (skew diagnostics)
    # Final worker states [n_c, ...] (device-resident pytree) — the input
    # to the serving plane (`repro.serve`): publish via SnapshotStore or
    # query directly with `serve.plane.grid_topn`.
    final_states: Any = None
    # Forgetting passes fired (fixed cadence or adaptive controller).
    forgets: int = 0
    # Events folded since the fixed cadence's last trigger: the count the
    # next call continues from (``run_stream(initial_since=...)``), so
    # the cadence follows the stream, not the calls. 0 without a cadence.
    events_since_trigger: int = 0
    # Per-micro-batch detector flags (i32[steps]) when the adaptive drift
    # policy is active, else None.
    drift_flags: Any = None
    # Final DetectorState (host arrays) under the adaptive policy — pass
    # to save_stream_checkpoint(detector=...) for closed-loop resume.
    final_detector: Any = None
    # End-of-run observability vector (repro.obs.telemetry.TelemetryState
    # of host arrays; None when cfg.telemetry is off). Cumulative over
    # this call only; host and scan backends fold bit-identical values.
    telemetry: Any = None

    @property
    def throughput(self) -> float:
        return self.events_processed / max(self.wall_seconds, 1e-9)

    @property
    def precision_at_n(self) -> float:
        """Micro-averaged prequential precision@N for this segment.

        Hits over summed *effective* list length (``min(top_n, live
        unrated candidates)`` per evaluated event — short lists while
        tables warm up don't get charged for slots they could not fill).
        Both terms ride the scan carry
        (:class:`repro.obs.telemetry.TelemetryState`), bit-identical
        between host and scan backends. ``nan`` when telemetry is off or
        nothing was evaluated.
        """
        if self.telemetry is None:
            return float("nan")
        denom = int(self.telemetry.list_len)
        return int(self.telemetry.hits) / denom if denom else float("nan")

    def occupancy_summary(self):
        """Mean per-worker live entries at end of stream (paper's metric)."""
        u = self.user_occupancy[-1][1] if self.user_occupancy else np.zeros(1)
        i = self.item_occupancy[-1][1] if self.item_occupancy else np.zeros(1)
        return {
            "user_mean": float(np.mean(u)), "user_max": int(np.max(u)),
            "item_mean": float(np.mean(i)), "item_max": int(np.max(i)),
            "user_total": int(np.sum(u)), "item_total": int(np.sum(i)),
        }


def make_worker_step(cfg: StreamConfig) -> Callable:
    """vmapped + jitted micro-batch step over all workers.

    Memoized on the (hashable, frozen) config so repeated runs — e.g.
    benchmark repeats — reuse the compiled executable instead of
    re-tracing.
    """
    return _make_worker_step_cached(cfg)


@functools.lru_cache(maxsize=32)
def _make_worker_step_cached(cfg: StreamConfig) -> Callable:
    from repro.core import engine

    worker = engine.make_worker_fn(cfg)

    @jax.jit
    def step(states, ev_u, ev_i):
        return worker(states, ev_u, ev_i)

    return step


def init_states(cfg: StreamConfig):
    one = algorithm_lib.get_algorithm(cfg.algorithm).init_state(
        cfg.resolved_hyper())
    # Algorithms init (and compute) in f32/bool; the resident encoding is
    # applied here, once, before the broadcast over workers.
    one = storage_lib.encode_state(one, cfg.storage)
    n_c = cfg.grid.n_c

    def stack(one):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n_c,) + x.shape),
                            one)

    if cfg.backend != "shard_map":
        return stack(one)
    # One worker per device of the grid mesh, built in place there.
    from repro.core import distributed
    from repro.launch.mesh import make_grid_mesh

    sharding = distributed.flat_worker_sharding(make_grid_mesh(cfg.grid))
    return jax.jit(stack, out_shardings=sharding)(one)


def run_stream(users: np.ndarray, items: np.ndarray, cfg: StreamConfig,
               verbose: bool = False, publish_every: int = 0,
               on_publish=None, publish_sync: bool = True,
               initial_states=None,
               initial_carry=(None, None),
               initial_detector=None,
               initial_since: int = 0) -> StreamResult:
    """Run the full prequential stream; returns curves + paper metrics.

    Thin dispatcher: ``cfg.backend`` selects the host reference loop below
    or the device-resident engine (``repro.core.engine``).

    ``publish_every``/``on_publish`` expose state snapshots at micro-batch
    boundaries for the serving plane (``repro.serve.snapshot``): every
    ``publish_every`` micro-batch steps, ``on_publish(PublishEvent)``
    fires with the immutable worker-state tree at that boundary.
    ``publish_sync=False`` makes the device engine's boundary
    non-blocking (device scalars handed to an async subscriber — see
    ``engine.run_stream_device``); the host reference loop is
    synchronous by construction and ignores it.

    ``initial_states``/``initial_carry`` resume mid-stream from a
    checkpoint or a regridded state (``repro.core.regrid``): the states
    must be shaped for ``cfg.grid`` — restore with
    ``restore_stream_checkpoint`` (which regrids portable checkpoints to
    the configured grid) or call ``regrid.regrid`` first.
    ``events_processed``/recall in the result cover the resumed segment.
    ``initial_since`` continues the fixed forgetting cadence from the
    previous call's ``StreamResult.events_since_trigger``.
    """
    # Backend selection negotiates against the algorithm's capability
    # flags (e.g. pallas without a fast path degrades to scan, with one
    # warning) instead of raising mid-run.
    if algorithm_lib.negotiated_backend(cfg) != "host":
        from repro.core import engine

        return engine.run_stream_device(
            users, items, cfg, verbose=verbose,
            publish_every=publish_every, on_publish=on_publish,
            publish_sync=publish_sync,
            initial_states=initial_states, initial_carry=initial_carry,
            initial_detector=initial_detector, initial_since=initial_since)

    assert users.shape == items.shape
    n = users.shape[0]
    grid = cfg.grid
    cap = cfg.bucket_capacity
    step = make_worker_step(cfg)
    states = initial_states if initial_states is not None else init_states(cfg)

    # Closed-loop drift policy replaces the fixed cadence when configured.
    adaptive = cfg.drift is not None and cfg.drift.mode == "adaptive"
    # Storage-policy codecs: forgetting and drift control compute on the
    # decoded (f32/bool) form, exactly like the worker step (wrapped
    # inside engine.make_worker_fn). Identity under the default policy.
    dec_s, enc_s = storage_lib.state_codecs(cfg.storage)
    forget = None
    det = det_update = controller = boost = None
    if adaptive:
        from repro.drift import controller as controller_lib
        from repro.drift import detector as detector_lib

        det_update = jax.jit(partial(detector_lib.detector_update,
                                     cfg=cfg.drift.detector))
        raw_controller = controller_lib.make_controller(cfg.drift)

        def _controller(s, fired, boost):
            s2, b2 = raw_controller(dec_s(s), fired, boost)
            return enc_s(s2), b2

        controller = jax.jit(_controller)
        det = (detector_lib.DetectorState(
                   *(jnp.asarray(l) for l in initial_detector))
               if initial_detector is not None
               else detector_lib.detector_init())
        boost = controller_lib.controller_init()
    elif cfg.forgetting.policy != "none":
        raw_forget = jax.vmap(
            partial(forgetting_lib.apply_forgetting, cfg=cfg.forgetting))
        forget = jax.jit(lambda s: enc_s(raw_forget(dec_s(s))))

    acc = RecallAccumulator()
    user_occ, item_occ, loads = [], [], []
    drift_flags = []
    dropped = 0
    processed = 0
    carry_u, carry_i = (np.asarray(c, np.int64) if c is not None
                        else np.empty(0, np.int64) for c in initial_carry)
    events_since_trigger = initial_since
    forgets = 0
    published_steps = 0

    occ_fn = jax.jit(jax.vmap(lambda s: state_lib.occupancy(s.tables)))

    # In-scan telemetry, host edition: the same pure-jnp fold the engine
    # runs inside its scan (repro.obs.telemetry), executed once per
    # micro-batch here — bit-identical values by construction. The host
    # re-queue is unbounded, hence HOST_CARRY_CAP (nothing drops at the
    # dispatch boundary).
    tel = tel_step = occ_total = list_fn = None
    if cfg.telemetry:
        from repro.obs import telemetry as telemetry_lib

        tel = telemetry_lib.telemetry_init(grid.n_c)
        tel_step = jax.jit(partial(telemetry_lib.telemetry_batch_update,
                                   carry_cap=telemetry_lib.HOST_CARRY_CAP))
        # Precision@N denominator on bucket-start states — the same
        # expression the engine folds in-scan (bit-parity contract).
        list_fn = jax.jit(partial(telemetry_lib.effective_list_len,
                                  top_n=cfg.resolved_hyper().top_n,
                                  g=grid.g, storage=cfg.storage))
        collision_fn = jax.jit(partial(telemetry_lib.collision_evictions,
                                       g=grid.g, n_i=grid.n_i))
        occ_total = jax.jit(
            lambda s: sum(jnp.sum(o) for o in
                          jax.vmap(lambda w: state_lib.occupancy(w.tables))(s)
                          ).astype(jnp.int32))

    def _publish_event(states, processed, dropped, forgets, segment, steps):
        from repro.core.engine import PublishEvent

        return PublishEvent(states=states, events_processed=processed,
                            dropped=dropped, forgets=forgets,
                            segment=segment, steps_done=steps,
                            detector=det if adaptive else None,
                            telemetry=tel)

    # Warm the jitted steps so the wall clock measures streaming, not
    # compilation — the engine backends AOT-compile before their timer,
    # and throughput comparisons must be symmetric.
    dummy = jnp.full((grid.n_c, cap), -1, jnp.int32)
    jax.block_until_ready(step(states, dummy, dummy))
    jax.block_until_ready(occ_fn(states))
    if forget is not None:
        jax.block_until_ready(forget(states))
    if adaptive:
        dummy_b = jnp.zeros((grid.n_c, cap), bool)
        jax.block_until_ready(det_update(det, dummy_b, dummy_b))
        jax.block_until_ready(controller(states, det.fired, boost)[0])
    if tel is not None:
        dummy_b = jnp.zeros((grid.n_c, cap), bool)
        zero = jnp.zeros((), jnp.int32)
        jax.block_until_ready(tel_step(
            tel, kept=zero, overflow=zero, evicted=zero, hits=dummy_b,
            evaluated=dummy_b, load=jnp.zeros((grid.n_c,), jnp.int32),
            occupancy=jnp.zeros((grid.n_c,), jnp.int32), list_len=zero,
            collisions=zero, triggers=zero))
        jax.block_until_ready(list_fn(states, dummy))
        jax.block_until_ready(collision_fn(states, dummy, dummy))
        jax.block_until_ready(occ_total(states))

    t0 = time.perf_counter()
    publish_time = 0.0
    n_batches = int(np.ceil(n / cfg.micro_batch))
    empty = np.empty(0, dtype=np.int64)
    b = 0
    max_drain = None
    while True:
        if b < n_batches:
            lo, hi = b * cfg.micro_batch, min((b + 1) * cfg.micro_batch, n)
            fresh_u, fresh_i = users[lo:hi], items[lo:hi]
        elif carry_u.size == 0:
            break
        else:
            # End-of-stream drain: flush the re-queue through empty
            # batches so overflow is processed, not dropped. Worst case
            # (every carried event targets one worker) needs
            # ceil(carry / capacity) passes; anything left after that
            # bound is counted as dropped.
            if max_drain is None:
                max_drain = n_batches + int(np.ceil(carry_u.size / cap)) + 1
            if b >= max_drain:
                dropped += carry_u.size
                break
            fresh_u, fresh_i = empty, empty
        bu = np.concatenate([carry_u, fresh_u])
        bi = np.concatenate([carry_i, fresh_i])
        keys = (bi % grid.n_i) * grid.g + (bu % grid.g)
        buckets, kept, load = routing.bucket_dispatch_np(
            keys.astype(np.int64), grid.n_c, cap
        )
        # Overflow events re-queue into the next micro-batch (not lost).
        carry_u, carry_i = bu[~kept], bi[~kept]

        ev_u = np.where(buckets >= 0, bu[np.clip(buckets, 0, None)], -1)
        ev_i = np.where(buckets >= 0, bi[np.clip(buckets, 0, None)], -1)
        ev_u_j = jnp.asarray(ev_u, jnp.int32)
        # Precision@N denominator from the pre-step states (the engine
        # computes it at the same point inside its scan body).
        ev_i_j = jnp.asarray(ev_i, jnp.int32)
        lens = collisions = None
        if tel is not None:
            lens = list_fn(states, ev_u_j)
            collisions = collision_fn(states, ev_u_j, ev_i_j)
        states, hits, evaluated = step(states, ev_u_j, ev_i_j)

        # Stream-order scatter needs bucket indices relative to this batch.
        acc.add_batch(buckets, np.asarray(hits), np.asarray(evaluated), bu.shape[0])
        processed += int(kept.sum())
        loads.append(load)

        evicted = triggers = 0
        if adaptive:
            det = det_update(det, hits, evaluated)
            occ_before = occ_total(states) if tel is not None else None
            states, boost = controller(states, det.fired, boost)
            if tel is not None:
                evicted = max(int(occ_before) - int(occ_total(states)), 0)
            fired = bool(det.fired)
            drift_flags.append(fired)
            triggers = int(fired)
            forgets += triggers
        elif forget is not None:
            events_since_trigger += int(kept.sum())
            if events_since_trigger >= cfg.forgetting.trigger_every:
                occ_before = occ_total(states) if tel is not None else None
                states = forget(states)
                if tel is not None:
                    evicted = int(occ_before) - int(occ_total(states))
                # Carry the remainder (see engine._make_batch_step):
                # resetting to zero would alias the cadence onto
                # micro-batch boundaries.
                events_since_trigger -= cfg.forgetting.trigger_every
                triggers = 1
                forgets += 1
        if tel is not None:
            u_o, i_o = occ_fn(states)
            tel = tel_step(tel, kept=jnp.asarray(int(kept.sum()), jnp.int32),
                           overflow=jnp.asarray(carry_u.size, jnp.int32),
                           evicted=jnp.asarray(evicted, jnp.int32),
                           hits=hits, evaluated=evaluated,
                           load=jnp.asarray(load, jnp.int32),
                           occupancy=u_o + i_o, list_len=lens,
                           collisions=collisions,
                           triggers=jnp.asarray(triggers, jnp.int32))

        if publish_every and on_publish is not None and (b + 1) % publish_every == 0:
            # Sync in-flight device work (async forgetting dispatch) before
            # the publish timer starts, then exclude only subscriber time
            # from the training wall clock — the same accounting as the
            # device engine's boundary.
            jax.block_until_ready(states)
            tp = time.perf_counter()
            on_publish(_publish_event(states, processed, dropped, forgets,
                                      (b + 1) // publish_every - 1, b + 1))
            publish_time += time.perf_counter() - tp
            published_steps = b + 1

        if b % cfg.record_every == 0:
            u_occ, i_occ = occ_fn(states)
            user_occ.append((processed, np.asarray(u_occ)))
            item_occ.append((processed, np.asarray(i_occ)))
        if verbose and b % 16 == 0:
            print(f"[stream] batch {b}/{n_batches} recall so far: {acc.mean():.4f}")
        b += 1

    # Final occupancy snapshot, unless the last loop iteration already
    # recorded this exact point.
    if n_batches and (not user_occ or user_occ[-1][0] != processed):
        u_occ, i_occ = occ_fn(states)
        user_occ.append((processed, np.asarray(u_occ)))
        item_occ.append((processed, np.asarray(i_occ)))

    # Tail publish: the device engine publishes after its final segment,
    # so the host path must too — otherwise micro-batches after the last
    # cadence boundary would never be snapshotted and the end-of-stream
    # staleness would be unbounded.
    if (publish_every and on_publish is not None and n_batches
            and published_steps != b):
        jax.block_until_ready(states)
        tp = time.perf_counter()
        on_publish(_publish_event(states, processed, dropped, forgets,
                                  published_steps // publish_every, b))
        publish_time += time.perf_counter() - tp

    jax.block_until_ready(states)
    wall = time.perf_counter() - t0 - publish_time
    return StreamResult(
        recall=acc,
        user_occupancy=user_occ,
        item_occupancy=item_occ,
        events_processed=processed,
        dropped=dropped,
        wall_seconds=wall,
        load_history=loads,
        final_states=states,
        forgets=forgets,
        events_since_trigger=events_since_trigger,
        drift_flags=(np.asarray(drift_flags, np.int32) if adaptive else None),
        final_detector=(jax.tree.map(np.asarray, det) if adaptive else None),
        telemetry=(jax.tree.map(np.asarray, tel) if tel is not None else None),
    )


# ---------------------------------------------------------------------------
# Fault tolerance: checkpoint/resume of the streaming state
# ---------------------------------------------------------------------------

# Version tag of the grid-portable checkpoint payload. v1: LogicalState
# records + (algorithm, grid shape, carry). Legacy fixed-shape checkpoints
# have no "format" key and restore only at their original grid.
LOGICAL_FORMAT = "sr-logical-v1"


def save_stream_checkpoint(directory: str, events_processed: int, states,
                           carry=(None, None), grid=None, algorithm=None,
                           detector=None, storage: StoragePolicy = None,
                           since: int = 0):
    """Persist worker states (+ the re-queue carry) mid-stream.

    With ``grid`` (the ``GridSpec`` the states are shaped for), the
    checkpoint is written in the grid-portable *logical* format
    (``repro.core.regrid.LogicalState``, version-tagged): it restores at
    ANY ``(n_i, g)`` — ``restore_stream_checkpoint`` rebuilds worker
    tables for the configured grid. Without ``grid``, the legacy
    fixed-shape format is written (restorable only at the same grid).

    ``storage`` is the :class:`~repro.core.storage.StoragePolicy` the
    live ``states`` are encoded under (default: the identity policy).
    The policy descriptor is stamped into the payload, and the logical
    format persists the heavy leaves *in the policy's encoding* — the
    generalization of the checkpointer's bf16 view trick: quantized
    ``co`` rides with its per-row scales (``co_scale``), packed
    ``rated`` with its bit width (``rated_bits``), bf16 factors as bf16.
    Restoring requires the same policy (``StoragePolicyError`` otherwise
    — migrate via ``rescale(..., storage=...)``, not at restore time).

    ``detector`` (a ``repro.drift.DetectorState``, e.g.
    ``StreamResult.final_detector`` or ``PublishEvent.detector``) rides
    along in either format — the detector's scalars are grid-agnostic —
    so a closed-loop run resumes without re-warming drift detection.
    ``since`` (``StreamResult.events_since_trigger``) does the same for
    the fixed forgetting cadence: it belongs to the stream, not the grid.
    """
    from repro.checkpoint import save_checkpoint

    if storage is None:
        storage = StoragePolicy()
    carry_u, carry_i = carry
    tree = {
        "carry_u": np.asarray(carry_u if carry_u is not None else
                              np.empty(0, np.int64)),
        "carry_i": np.asarray(carry_i if carry_i is not None else
                              np.empty(0, np.int64)),
        "storage": storage.describe(),
        "since": np.asarray(since, np.int64),
    }
    if detector is not None:
        tree["detector"] = jax.tree.map(np.asarray, detector)
    if grid is None:
        tree["states"] = jax.tree.map(np.asarray, states)
    else:
        if algorithm is None:
            # Best-effort: state containers are shared across algorithms,
            # so callers that know the registry key (StreamSession does)
            # pass it explicitly.
            algorithm = algorithm_lib.infer_algorithm(states)
        logical = algorithm_lib.get_algorithm(algorithm).extract_logical(
            states, grid, storage=storage)
        # Re-encode the heavy logical leaves per the policy so the bytes
        # on disk match the resident footprint (extract_logical hands
        # back the decoded f32/bool compute form).
        if storage.factors == "bf16":
            logical = logical._replace(
                u_vec=logical.u_vec.astype(jnp.bfloat16),
                i_vec=logical.i_vec.astype(jnp.bfloat16))
        if storage.co in ("uint16", "int8"):
            q, scale = storage_lib.quantize_rows(logical.co, storage.co)
            logical = logical._replace(co=q)
            tree["co_scale"] = np.asarray(scale)
        elif storage.co == "bf16":
            logical = logical._replace(co=logical.co.astype(jnp.bfloat16))
        if storage.rated == "packed":
            tree["rated_bits"] = int(logical.rated.shape[-1])
            logical = logical._replace(
                rated=storage_lib.pack_bits(logical.rated))
        tree.update({
            "format": LOGICAL_FORMAT,
            "algorithm": algorithm,
            "grid": np.asarray([grid.n_i, grid.g], np.int64),
            "logical": jax.tree.map(np.asarray, logical),
        })
    return save_checkpoint(directory, events_processed, tree)


@dataclasses.dataclass
class RestoredCheckpoint:
    """What ``restore_stream_checkpoint`` hands back, by name.

    ``states`` are shaped for the restoring config's grid; ``carry`` is
    the ``(carry_u, carry_i)`` overflow re-queue; ``detector`` is the
    saved drift ``DetectorState`` (a tuple of host arrays for
    ``run_stream(initial_detector=...)``) or ``None`` for checkpoints
    written without one. ``since`` is the fixed forgetting cadence's
    count (0 for checkpoints written without one).

    The legacy ``(events_processed, states, carry, detector)`` 4-tuple
    iteration shipped for one release of back-compat (PR 5) and is now
    removed: tuple-unpacking a ``RestoredCheckpoint`` raises
    ``TypeError`` — use the named fields (or the
    ``StreamSession.restore`` facade).
    """

    events_processed: int
    states: Any
    carry: tuple
    detector: Any = None
    since: int = 0


def restore_stream_checkpoint(directory: str, cfg: StreamConfig,
                              step: int | None = None) -> RestoredCheckpoint:
    """Restore worker states shaped like ``init_states(cfg)``.

    Grid-portable (logical-format) checkpoints restore at whatever grid
    ``cfg`` configures, regridding on the fly through the algorithm's
    ``build_states`` hook; legacy fixed-shape checkpoints must match the
    configured grid (validated against the algorithm's
    ``state_template`` schema) or raise ``CheckpointShapeError``.

    Returns a :class:`RestoredCheckpoint` (named fields only — the
    legacy 4-tuple iteration was removed after its one deprecation
    release).
    """
    from repro.checkpoint import restore_checkpoint
    from repro.core import regrid as regrid_lib

    events_processed, tree = restore_checkpoint(directory, step)
    carry = (tree["carry_u"], tree["carry_i"])
    detector = tree.get("detector")
    since = int(tree.get("since", 0))
    hyper = cfg.resolved_hyper()
    algo = algorithm_lib.get_algorithm(cfg.algorithm)

    # Policy gate: restoring under a different resident encoding than
    # the checkpoint was written with would scatter garbage into tables
    # (or silently drop precision). Fail loudly, naming both policies;
    # migration is a live-rescale concern (rescale(..., storage=...)).
    saved_policy = StoragePolicy.from_descriptor(tree.get("storage"))
    if saved_policy != cfg.storage:
        raise StoragePolicyError(saved_policy, cfg.storage)

    fmt = tree.get("format")
    if fmt is not None:
        if fmt != LOGICAL_FORMAT:
            raise ValueError(f"unknown checkpoint format {fmt!r}")
        if tree["algorithm"] != cfg.algorithm:
            raise ValueError(
                f"checkpoint holds {tree['algorithm']!r} state but the "
                f"config asks for {cfg.algorithm!r}")
        n_i, g = (int(x) for x in np.asarray(tree["grid"]))
        src = routing.GridSpec.rect(n_i, g)
        logical = regrid_lib.LogicalState(
            *(jnp.asarray(leaf) for leaf in tree["logical"]))
        # Decode the policy-encoded heavy leaves back to the f32/bool
        # compute form build_states expects (inverse of the save path).
        if saved_policy.factors == "bf16":
            logical = logical._replace(
                u_vec=logical.u_vec.astype(jnp.float32),
                i_vec=logical.i_vec.astype(jnp.float32))
        if saved_policy.co in ("uint16", "int8"):
            logical = logical._replace(co=storage_lib.dequantize_rows(
                logical.co, jnp.asarray(tree["co_scale"])))
        elif saved_policy.co == "bf16":
            logical = logical._replace(co=logical.co.astype(jnp.float32))
        if saved_policy.rated == "packed":
            logical = logical._replace(rated=storage_lib.unpack_bits(
                logical.rated, int(tree["rated_bits"])))
        states = algo.build_states(
            logical, src=src, dst=cfg.grid,
            u_cap=hyper.u_cap, i_cap=hyper.i_cap, storage=cfg.storage)
        return RestoredCheckpoint(events_processed, states, carry, detector,
                                  since)

    # Legacy fixed-shape payload: validate against the algorithm's
    # checkpoint schema (single-worker template stacked over the grid,
    # in the configured policy's resident encoding).
    one = algo.state_template(hyper)
    one = jax.eval_shape(
        partial(storage_lib.encode_state, policy=cfg.storage), one)
    n_c = cfg.grid.n_c
    flat_one, treedef = jax.tree.flatten(one)
    flat_t = [jax.ShapeDtypeStruct((n_c,) + s.shape, s.dtype)
              for s in flat_one]
    flat_s = jax.tree.leaves(tree["states"])
    ckpt_workers = flat_s[0].shape[0] if flat_s and flat_s[0].ndim else "?"
    if len(flat_t) != len(flat_s):
        raise regrid_lib.CheckpointShapeError(
            ckpt_workers, cfg.grid,
            f"leaf count {len(flat_s)} != expected {len(flat_t)} "
            f"(algorithm mismatch?)")
    for s, t in zip(flat_s, flat_t):
        if tuple(s.shape) != tuple(t.shape):
            raise regrid_lib.CheckpointShapeError(
                ckpt_workers, cfg.grid,
                f"leaf shape {tuple(s.shape)} != expected {tuple(t.shape)}")
    states = jax.tree.unflatten(
        treedef,
        [jnp.asarray(s, t.dtype) for s, t in zip(flat_s, flat_t)],
    )
    return RestoredCheckpoint(events_processed, states, carry, detector, since)
