"""Device-resident S&R streaming engine.

``pipeline.run_stream``'s host path routes every micro-batch through a
Python ``bucket_dispatch_np`` loop and round-trips worker states
host<->device once per batch — exactly the single-machine bottleneck the
paper's Splitting & Replication architecture exists to remove. This module
runs the *entire* prequential loop as one jitted ``lax.scan`` over
micro-batches:

  * routing + capacity bucketing on device (``routing.bucket_dispatch``);
  * overflow events carried in a fixed-size on-device re-queue, with
    static drain iterations appended so the end of the stream is flushed
    (unlike the host loop's unbounded Python queue, buffer overruns are
    dropped and counted in ``StreamResult.dropped`` — backpressure, not
    silent loss);
  * forgetting triggers evaluated inside the scan (``lax.cond``) — the
    fixed cadence, or, with ``StreamConfig.drift``, the closed-loop
    drift detector + adaptive controller (``repro.drift``) whose scalar
    state rides in the scan carry (no per-micro-batch host sync);
  * recall bits scattered back to stream order on device and returned as
    one ``[steps, slots]`` array.

Worker states never leave the device between micro-batches. Three worker
execution modes share the loop:

  * ``"reference"`` — ``vmap`` over the worker axis of the per-event
    ``lax.scan`` step (bit-identical to the host path; the interpretable
    reference).
  * ``"pallas"`` — kernel fast path for algorithms that advertise
    ``supports_pallas``. All three in-tree algorithms do: DISGD and
    BPR-MF share the fused complete factor update
    (``kernels/factor_update.py``, plain vs pairwise mode), DICS uses
    the fused co-count update (``kernels/dics_update.py``); each pairs
    it with batched bucket-start scoring. Fast-path FINAL STATES are
    exact against the reference workers (collision eviction and
    bookkeeping included); recall bits carry a bucket-start tolerance
    contract. Algorithms without a fast path negotiate down to
    ``"scan"`` with a warning (``algorithm.negotiated_backend``)
    instead of failing mid-run.
  * ``"shard_map"`` — each S&R worker placed at a mesh coordinate
    (``core/distributed.py``) instead of a ``vmap`` lane.

``pipeline.run_stream`` selects between the host loop and this engine via
``StreamConfig.backend``.
"""

from __future__ import annotations

import functools
import time
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import algorithm as algorithm_lib
from repro.core import forgetting as forgetting_lib
from repro.core import routing, state as state_lib
from repro.core import storage as storage_lib
from repro.core.evaluator import RecallAccumulator
from repro.drift import controller as controller_lib
from repro.drift import detector as detector_lib
from repro.obs import telemetry as telemetry_lib
from repro.obs import trace as trace_lib

__all__ = ["make_worker_fn", "make_pallas_worker_fn", "run_stream_device",
           "PublishEvent", "scan_steps", "compiled_scan"]


def make_worker_fn(cfg) -> Callable:
    """vmapped (unjitted) micro-batch step over all workers.

    Returns ``worker(states, ev_u, ev_i) -> (states, hits, evaluated)``
    with everything laid out ``[n_c, ...]``. ``pipeline.make_worker_step``
    jits this directly; the engine inlines it into its scan body. The
    per-worker step comes from the registered :class:`~repro.core.
    algorithm.Algorithm` — the engine never dispatches on names.
    """
    algo = algorithm_lib.get_algorithm(cfg.algorithm)
    one = algo.make_worker_step(cfg.resolved_hyper(), jax.random.key(cfg.seed))

    stepped = jax.vmap(one, in_axes=(0, 0))
    # Storage-policy boundary: decode the resident encoding, compute in
    # f32/bool, re-encode (identity traces under the default policy).
    dec, enc = storage_lib.state_codecs(cfg.storage)

    def worker(states, ev_u, ev_i):
        out, hits, evaluated = stepped(dec(states), (ev_u, ev_i))
        return enc(out), hits, evaluated

    return worker


def make_pallas_worker_fn(cfg) -> Callable:
    """Pallas fast-path worker for algorithms that advertise one.

    An explicit request for an impossible fast path raises (the
    ``supports_pallas`` capability flag is the contract); backend
    *negotiation* (``algorithm.negotiated_backend``) checks the flag
    first and silently degrades to the reference scan worker instead.
    """
    algo = algorithm_lib.get_algorithm(cfg.algorithm)
    if not algo.supports_pallas:
        raise ValueError(
            f"backend='pallas' is not supported by algorithm "
            f"{cfg.algorithm!r} (supports_pallas=False)")
    one = algo.make_pallas_worker_step(cfg.resolved_hyper(),
                                       jax.random.key(cfg.seed))
    stepped = jax.vmap(one, in_axes=(0, 0))
    dec, enc = storage_lib.state_codecs(cfg.storage)

    def worker(states, ev_u, ev_i):
        out, hits, evaluated = stepped(dec(states), (ev_u, ev_i))
        return enc(out), hits, evaluated

    return worker


# ---------------------------------------------------------------------------
# The scanned streaming loop
# ---------------------------------------------------------------------------


def _resolve_worker_fn(cfg, mesh=None) -> Callable:
    backend = algorithm_lib.negotiated_backend(cfg)
    if backend in ("scan", "host"):
        return make_worker_fn(cfg)
    if backend == "pallas":
        return make_pallas_worker_fn(cfg)
    if backend == "shard_map":
        from repro.core import distributed

        if mesh is None:
            from repro.launch.mesh import make_grid_mesh

            mesh = make_grid_mesh(cfg.grid)
        return distributed.make_flat_grid_worker(cfg, mesh)
    raise ValueError(f"unknown backend {backend!r}")


def _adaptive(cfg) -> bool:
    return cfg.drift is not None and cfg.drift.mode == "adaptive"


def _make_batch_step(cfg, worker_fn):
    grid = cfg.grid
    n_c, g, n_i = grid.n_c, grid.g, grid.n_i
    cap = cfg.bucket_capacity
    mb = cfg.micro_batch
    carry_cap = cfg.carry_slots or mb
    layout = carry_cap + mb

    # Closed-loop drift policy replaces the fixed forgetting cadence when
    # configured (``StreamConfig.drift``, mode "adaptive"). Both forget
    # and the drift controller compute on the decoded form, mirroring the
    # host loop and the worker step (identity under the default policy).
    adaptive = _adaptive(cfg)
    dec_s, enc_s = storage_lib.state_codecs(cfg.storage)
    controller = None
    if adaptive:
        raw_controller = controller_lib.make_controller(cfg.drift)

        def controller(s, fired, boost):
            s2, b2 = raw_controller(dec_s(s), fired, boost)
            return enc_s(s2), b2

    forget = None
    if not adaptive and cfg.forgetting.policy != "none":
        raw_forget = jax.vmap(
            partial(forgetting_lib.apply_forgetting, cfg=cfg.forgetting)
        )
        forget = lambda s: enc_s(raw_forget(dec_s(s)))  # noqa: E731
    occ_fn = jax.vmap(lambda s: state_lib.occupancy(s.tables))
    tel_on = cfg.telemetry

    def _occ_total(s):
        with trace_lib.layer("telemetry"):
            u, i = occ_fn(s)
            return (jnp.sum(u) + jnp.sum(i)).astype(jnp.int32)

    def live(carry, fresh):
        (states, cu, ci, since, processed, dropped, forgets, det, boost,
         tel) = carry
        fu, fi = fresh
        with trace_lib.layer("route"):
            bu = jnp.concatenate([cu, fu])
            bi = jnp.concatenate([ci, fi])
            valid = bu >= 0
            # Invalid slots route to key n_c: out of range, so they occupy
            # no bucket capacity and contribute no load.
            keys = jnp.where(valid, (bi % n_i) * g + (bu % g), n_c)
            buckets, kept, load = routing.bucket_dispatch(
                keys.astype(jnp.int32), n_c, cap
            )
            kept = kept & valid

            ev_u = jnp.where(buckets >= 0, bu[jnp.clip(buckets, 0, None)], -1)
            ev_i = jnp.where(buckets >= 0, bi[jnp.clip(buckets, 0, None)], -1)
        # Precision@N denominator, measured on the bucket-start states
        # (before this batch trains) — the same expression the host loop
        # folds, so the two backends stay bit-identical.
        list_len = collisions = 0
        if tel_on:
            with trace_lib.layer("telemetry"):
                list_len = telemetry_lib.effective_list_len(
                    states, ev_u.astype(jnp.int32),
                    top_n=cfg.resolved_hyper().top_n, g=g,
                    storage=cfg.storage)
                collisions = telemetry_lib.collision_evictions(
                    states, ev_u, ev_i, g=g, n_i=n_i)
        states, hits, evaluated = worker_fn(
            states, ev_u.astype(jnp.int32), ev_i.astype(jnp.int32)
        )

        with trace_lib.layer("route"):
            # Stream-order recall bits for this step (NaN = no evaluation).
            flat_idx = buckets.reshape(-1)
            sel = (flat_idx >= 0) & evaluated.reshape(-1)
            bits = jnp.full((layout,), jnp.nan, jnp.float32).at[
                jnp.where(sel, flat_idx, layout)
            ].set(jnp.where(sel, hits.reshape(-1).astype(jnp.float32), 0.0),
                  mode="drop")

            # Overflow re-queue (order-preserving compaction into the carry
            # buffer); anything past the buffer is dropped and counted.
            overflow = valid & ~kept
            ovf_idx = jnp.nonzero(overflow, size=carry_cap,
                                  fill_value=layout)[0]
            bu_ext = jnp.concatenate([bu, jnp.full((1,), -1, bu.dtype)])
            bi_ext = jnp.concatenate([bi, jnp.full((1,), -1, bi.dtype)])
            cu_new = bu_ext[jnp.minimum(ovf_idx, layout)]
            ci_new = bi_ext[jnp.minimum(ovf_idx, layout)]
            n_overflow = jnp.sum(overflow.astype(jnp.int32))
            dropped = dropped + jnp.maximum(0, n_overflow - carry_cap)

            kept_n = jnp.sum(kept.astype(jnp.int32))
            processed = processed + kept_n
        fired = jnp.zeros((), jnp.int32)
        triggers = evicted = jnp.zeros((), jnp.int32)
        if adaptive:
            with trace_lib.layer("forget"):
                det = detector_lib.detector_update(
                    det, hits, evaluated, cfg.drift.detector)
                if tel_on:
                    occ_before = _occ_total(states)
                states, boost = controller(states, det.fired, boost)
                if tel_on:
                    # Controller decay shrinks weights without freeing
                    # rows; only the net occupancy drop counts as
                    # evictions.
                    evicted = jnp.maximum(occ_before - _occ_total(states), 0)
                fired = triggers = det.fired.astype(jnp.int32)
                forgets = forgets + fired
        elif forget is not None:
            with trace_lib.layer("forget"):
                since = since + kept_n
                trigger = since >= cfg.forgetting.trigger_every
                if tel_on:
                    def _forget_counted(s):
                        before = _occ_total(s)
                        s2 = forget(s)
                        return s2, before - _occ_total(s2)

                    states, evicted = jax.lax.cond(
                        trigger, _forget_counted,
                        lambda s: (s, jnp.zeros((), jnp.int32)), states)
                else:
                    states = jax.lax.cond(trigger, forget, lambda s: s,
                                          states)
                # Carry the remainder instead of resetting to zero: a reset
                # aliases the cadence onto micro-batch boundaries whenever
                # ``trigger_every`` is not a multiple of the micro-batch
                # (triggers fire every ceil(te/mb)*mb events instead of
                # every te) — with the remainder carried, trigger counts
                # match floor(processed / trigger_every) exactly for
                # mb <= te.
                since = jnp.where(trigger,
                                  since - cfg.forgetting.trigger_every, since)
                triggers = trigger.astype(jnp.int32)
                forgets = forgets + triggers

        if tel_on:
            with trace_lib.layer("telemetry"):
                u_o, i_o = occ_fn(states)
                tel = telemetry_lib.telemetry_batch_update(
                    tel, kept=kept_n, overflow=n_overflow,
                    carry_cap=carry_cap, evicted=evicted, hits=hits,
                    evaluated=evaluated, load=load, occupancy=u_o + i_o,
                    list_len=list_len, collisions=collisions,
                    triggers=triggers)

        carry = (states, cu_new, ci_new, since, processed, dropped, forgets,
                 det, boost, tel)
        return carry, (bits, load, kept_n, fired)

    def dead(carry, fresh):
        del fresh
        return carry, (
            jnp.full((layout,), jnp.nan, jnp.float32),
            jnp.zeros((n_c,), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
        )

    def batch_step(carry, fresh):
        fu, _ = fresh
        cu = carry[1]
        has_work = jnp.any(fu >= 0) | jnp.any(cu >= 0)
        carry, outs = jax.lax.cond(has_work, live, dead, carry, fresh)
        with trace_lib.layer("telemetry"):
            u_occ, i_occ = occ_fn(carry[0])
        return carry, outs + (u_occ, i_occ)

    return batch_step, carry_cap, cap


def _scan_program(batch_step):
    """The jitted scan over micro-batches; named, so its module reads
    ``jit_engine_scan`` in device traces and compiled text."""
    def engine_scan(carry, xs):
        return jax.lax.scan(batch_step, carry, xs)

    return jax.jit(engine_scan)


def init_scan_carry(cfg, states=None, carry=(None, None), detector=None,
                    since: int = 0):
    """Initial scan carry; ``states``/``carry``/``detector`` resume from a
    checkpoint (``detector`` is a ``DetectorState``-shaped tuple), and
    ``since`` continues the fixed forgetting cadence: the events folded
    since its last trigger, in this call or an earlier one."""
    from repro.core import pipeline

    if states is None:
        states = pipeline.init_states(cfg)
    carry_cap = cfg.carry_slots or cfg.micro_batch
    cu = jnp.full((carry_cap,), -1, jnp.int32)
    ci = jnp.full((carry_cap,), -1, jnp.int32)
    carry_u, carry_i = carry
    lost = 0
    if carry_u is not None and np.asarray(carry_u).size:
        size = int(np.asarray(carry_u).size)
        m = min(size, carry_cap)
        # A checkpoint written by the host pipeline (unbounded queue) can
        # exceed the engine's buffer; the truncated tail is accounted as
        # dropped, never silently lost.
        lost = size - m
        cu = cu.at[:m].set(jnp.asarray(carry_u, jnp.int32)[:m])
        ci = ci.at[:m].set(jnp.asarray(carry_i, jnp.int32)[:m])
    det = detector_lib.detector_init()
    if detector is not None:
        det = detector_lib.DetectorState(
            *(jnp.asarray(leaf) for leaf in detector))
    zero = jnp.zeros((), jnp.int32)
    # The telemetry slot rides along even with cfg.telemetry=False (zeros,
    # never updated) so the carry structure is config-independent.
    return (states, cu, ci, jnp.asarray(since, jnp.int32), zero,
            jnp.asarray(lost, jnp.int32), zero,
            det, controller_lib.controller_init(),
            telemetry_lib.telemetry_init(cfg.grid.n_c))


def scan_steps(cfg, n_events: int, resumed_carry: bool = False) -> int:
    """Scan steps one ``run_stream_device`` call takes for ``n_events``:
    the micro-batches plus a static drain tail (worst case, every carried
    event targets one worker)."""
    n_batches = int(np.ceil(n_events / cfg.micro_batch)) if n_events else 0
    carry_cap = cfg.carry_slots or cfg.micro_batch
    drain = (int(np.ceil(carry_cap / cfg.bucket_capacity))
             if (n_batches or resumed_carry) else 0)
    return n_batches + drain


@functools.lru_cache(maxsize=16)
def compiled_scan(cfg, steps: int):
    """AOT-compiled scan executable for (config, step count) — the one
    ``run_stream_device`` runs (without an explicit mesh). Lowered from
    shapes only, so compiling allocates no state."""
    worker_fn = _resolve_worker_fn(cfg)
    batch_step, _, _ = _make_batch_step(cfg, worker_fn)
    carry0 = jax.eval_shape(lambda: init_scan_carry(cfg))
    mb = cfg.micro_batch
    xs = (jax.ShapeDtypeStruct((steps, mb), jnp.int32),) * 2
    compiled = _scan_program(batch_step).lower(carry0, xs).compile()
    trace_lib.register_program(compiled)
    return compiled


class PublishEvent(NamedTuple):
    """Snapshot-boundary payload handed to ``on_publish``.

    ``states`` is the device-resident worker-state pytree at a
    micro-batch boundary — immutable jax arrays, so holding a reference
    IS a consistent snapshot: later training builds new buffers and can
    never mutate what the subscriber holds. ``forgets`` counts forgetting
    triggers fired so far (serving caches invalidate when it advances).

    The progress scalars come in two modes:

    * ``publish_sync=True`` (the default, blocking boundary):
      ``events_processed`` / ``dropped`` / ``forgets`` are Python ints —
      the boundary blocked on the segment's compute to read them.
    * ``publish_sync=False`` (non-blocking boundary): they are 0-d
      device arrays still attached to the in-flight scan — the
      subscriber (e.g. ``SnapshotStore.publish_async``) syncs them on
      its own thread so the trainer never waits at the boundary. Call
      :meth:`as_ints` to resolve them (this blocks until the segment's
      compute has finished — exactly the wait the mode exists to move
      off the trainer).

    ``telemetry`` is the in-scan observability vector
    (:class:`repro.obs.telemetry.TelemetryState`, cumulative for the
    run) — always device arrays in both modes; ``None`` when
    ``StreamConfig.telemetry`` is off. The host reference loop hands the
    equivalent host-folded vector (bit-identical values). The recall head
    (``hits``/``evals``) and the precision@N head (``hits``/``list_len``)
    both ride here, so boundary subscribers (the ensemble weigher,
    ``TelemetryFolder``) read ranking quality without a device sync on
    the trainer.
    """

    states: Any
    events_processed: Any  # int, or 0-d device array when publish_sync=False
    dropped: Any
    forgets: Any
    segment: int          # 0-based index of the segment just finished
    steps_done: int       # scan steps completed so far
    detector: Any = None  # DetectorState at the boundary (adaptive drift
                          # policy only) — checkpointable alongside states
    telemetry: Any = None  # TelemetryState at the boundary (device arrays)

    def as_ints(self) -> "PublishEvent":
        """Resolve device scalars to host values (blocks on the scan).

        Returns a copy with ``events_processed`` / ``dropped`` /
        ``forgets`` as Python ints and ``telemetry`` as host (numpy)
        arrays — the ergonomic bridge for ``publish_sync=False``
        subscribers that want plain numbers. A no-op-shaped copy when
        the scalars are already ints.
        """
        return self._replace(
            events_processed=int(self.events_processed),
            dropped=int(self.dropped),
            forgets=int(self.forgets),
            telemetry=(jax.tree.map(np.asarray, self.telemetry)
                       if self.telemetry is not None else None))


def run_stream_device(users: np.ndarray, items: np.ndarray, cfg,
                      verbose: bool = False, mesh=None,
                      publish_every: int = 0, on_publish=None,
                      publish_sync: bool = True,
                      initial_states=None, initial_carry=(None, None),
                      initial_detector=None, initial_since: int = 0):
    """Run the whole prequential stream as a jitted scan on device.

    With ``publish_every == 0`` (default) the stream is one scan call.
    With ``publish_every = k > 0`` the scan runs in segments of ``k``
    micro-batch steps and ``on_publish(PublishEvent)`` fires after each
    segment — the hook the serving plane's snapshot double-buffer
    subscribes to (``repro.serve.snapshot``). Worker states stay
    device-resident across segments; the only extra cost per boundary is
    the host sync of two scalars plus whatever the callback does.

    ``publish_sync=False`` removes even that: the boundary hands the
    0-d device scalars to the subscriber un-synced, so the host loop can
    dispatch the next segment immediately instead of blocking until the
    finished segment's compute completes — segments pipeline through the
    async dispatch queue while an async subscriber (e.g.
    ``SnapshotStore.publish_async``) syncs and rotates on its own
    thread. Use only with subscribers that tolerate device scalars.

    ``initial_states``/``initial_carry`` resume from a checkpoint or a
    regridded state; shapes must match ``cfg`` (the compiled scan is
    shape-polymorphic in values only), so regrid to ``cfg.grid`` first.
    ``initial_since`` is the forgetting cadence's count carried from the
    previous call (``StreamResult.events_since_trigger``).
    """
    from repro.core.pipeline import StreamResult

    assert users.shape == items.shape
    n = users.shape[0]
    mb = cfg.micro_batch

    resumed_carry = (initial_carry[0] is not None
                     and np.asarray(initial_carry[0]).size > 0)
    n_batches = int(np.ceil(n / mb)) if n else 0
    steps = scan_steps(cfg, n, resumed_carry)

    seg = publish_every if publish_every > 0 else max(steps, 1)
    n_segments = int(np.ceil(steps / seg))
    steps_padded = max(n_segments, 1) * seg

    # Host spans: inside a session's ``ingest`` span these read
    # ``ingest/prepare``, ``ingest/scan`` (with ``ingest/scan/boundary``
    # per mid-call publish) and ``ingest/readback``.
    with trace_lib.span("prepare"):
        fu = np.full((steps_padded, mb), -1, np.int64)
        fi = np.full((steps_padded, mb), -1, np.int64)
        flat_u = fu[:n_batches].reshape(-1)
        flat_i = fi[:n_batches].reshape(-1)
        flat_u[:n] = users
        flat_i[:n] = items

        carry0 = init_scan_carry(cfg, states=initial_states,
                                 carry=initial_carry,
                                 detector=initial_detector,
                                 since=initial_since)
        xs = (jnp.asarray(fu, jnp.int32), jnp.asarray(fi, jnp.int32))

        # AOT-compile so the wall clock measures steady-state streaming,
        # not tracing (the host path warms its jit before its timer for
        # the same reason). Memoized on the frozen config so benchmark
        # repeats reuse the executable; mesh objects are unhashable, so
        # explicit-mesh shard_map runs compile per call.
        if mesh is None and cfg.backend != "shard_map":
            compiled = compiled_scan(cfg, seg)
        else:
            worker_fn = _resolve_worker_fn(cfg, mesh=mesh)
            batch_step, _, _ = _make_batch_step(cfg, worker_fn)
            xs_seg = jax.tree.map(lambda x: x[:seg], xs)
            compiled = _scan_program(batch_step).lower(carry0,
                                                       xs_seg).compile()
            trace_lib.register_program(compiled)

    t0 = time.perf_counter()
    publish_time = 0.0
    carry = carry0
    seg_outs = []
    with trace_lib.span("scan"):
        for s in range(max(n_segments, 1)):
            xs_seg = jax.tree.map(lambda x: x[s * seg:(s + 1) * seg], xs)
            carry, outs = compiled(carry, xs_seg)
            seg_outs.append(outs)
            if on_publish is not None:
                # Publish boundary. Sync mode: read the progress scalars
                # (states stay on device) and hand the immutable state
                # tree to the subscriber. The scalar reads block until the
                # segment's (async-dispatched) compute finishes — they
                # must complete BEFORE the publish timer starts, or
                # segment compute would be misattributed to the
                # subscriber. Only subscriber work (e.g. a serving burst)
                # is excluded from the training wall clock, keeping
                # throughput comparable to non-publishing runs. Async mode
                # (publish_sync=False): hand the un-synced device scalars
                # over and keep dispatching — the subscriber thread pays
                # the sync instead of this loop.
                ev = PublishEvent(
                    states=carry[0],
                    events_processed=(int(carry[4]) if publish_sync
                                      else carry[4]),
                    dropped=int(carry[5]) if publish_sync else carry[5],
                    forgets=int(carry[6]) if publish_sync else carry[6],
                    segment=s,
                    steps_done=(s + 1) * seg,
                    detector=carry[7] if _adaptive(cfg) else None,
                    telemetry=carry[9] if cfg.telemetry else None,
                )
                tp = time.perf_counter()
                with trace_lib.span("boundary"):
                    on_publish(ev)
                publish_time += time.perf_counter() - tp
        states, cu, ci, since, processed, dropped, forgets, det, _, tel = carry
        jax.block_until_ready(states)
    wall = time.perf_counter() - t0 - publish_time

    with trace_lib.span("readback"):
        bits, loads, kept_n, fired, u_occ, i_occ = (
            np.concatenate([np.asarray(o[j]) for o in seg_outs])
            for j in range(6)
        )
        processed = int(processed)
        dropped = int(dropped) + int(np.sum(np.asarray(cu) >= 0))

        acc = RecallAccumulator()
        active = [s for s in range(bits.shape[0])
                  if loads[s].sum() > 0 or s < n_batches]
        for s in active:
            acc.add_raw(bits[s])
        load_history = [loads[s] for s in active]
        drift_flags = (np.asarray([fired[s] for s in active], np.int32)
                       if _adaptive(cfg) else None)

        cum = np.cumsum(kept_n)
        user_occ, item_occ = [], []
        for j, s in enumerate(active):
            if j % cfg.record_every == 0 or j == len(active) - 1:
                user_occ.append((int(cum[s]), u_occ[s]))
                item_occ.append((int(cum[s]), i_occ[s]))
            if verbose and j % 16 == 0:
                print(f"[engine] step {j}/{len(active)}")
        final_detector = (jax.tree.map(np.asarray, det) if _adaptive(cfg)
                          else None)
        telemetry = (jax.tree.map(np.asarray, tel) if cfg.telemetry
                     else None)

    return StreamResult(
        recall=acc,
        user_occupancy=user_occ,
        item_occupancy=item_occ,
        events_processed=processed,
        dropped=dropped,
        wall_seconds=wall,
        load_history=load_history,
        final_states=states,
        forgets=int(forgets),
        events_since_trigger=int(since),
        drift_flags=drift_flags,
        final_detector=final_detector,
        telemetry=telemetry,
    )
