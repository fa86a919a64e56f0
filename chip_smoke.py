#!/usr/bin/env python3
"""Drive the S&R streaming recommender once on a TPU at MovieLens-25M scale.

    python chip_smoke.py             # one chip: DISGD, BPR-MF and DICS
    python chip_smoke.py --chips 4   # four chips: the shard_map S&R grid

One chip. For each algorithm the stream is ``synth_stream(MOVIELENS_25M,
seed)`` (paper Table 1: 155,002 users, 27,133 items) on a ``GridSpec(4)``
grid, 16 workers, with collision-free per-worker capacities. A
``StreamSession`` with ``backend="pallas"`` ingests 3 windows of 65,536
events and answers 256 ``recommend`` queries; the same windows then run
through the reference ``backend="scan"`` engine, and the final states
are compared (integer tables exactly, float tables within a stated
tolerance). DICS runs at ``DICS_SCALE`` of the user and item counts: its
reference worker rebuilds the dense item-item similarity for every event
and selects the whole state under ``vmap``, so at full width it neither
fits next to its snapshots nor finishes within the run's time limit.

Four chips (``--chips 4``). DISGD on ``GridSpec(2)``, one worker per
chip, through ``backend="shard_map"``, against ``backend="scan"`` for
the same grid on one chip: recall bits and integer tables must be
equal, factor tables within ``FLOAT_ATOL``, and every device must hold
exactly one worker's tables.

The script fails (non-zero exit, no result line) when the repository is
not next to it, when JAX finds no TPU, when the Pallas path is not the
one negotiated or its kernels are absent from the compiled programs, or
when any check fails. Its last stdout line on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
import warnings
from pathlib import Path

SEED = 0
WINDOWS, WINDOW = 3, 65_536
MICRO_BATCH, CAPACITY_FACTOR = 512, 1.5
N_QUERIES, N_UNKNOWN = 256, 16
ALGORITHMS = ("disgd", "bpr", "dics")
# Users and items of the DICS stream, as a fraction of Table 1 (Zipf
# skews kept); see the module docstring.
DICS_SCALE = 0.2
# Float state tolerance (DISGD and BPR-MF factor tables). Both paths
# update the factors in f32 (XLA turns the reference's [k]-vector dots
# into f32 multiply-reduce, the kernel sums lanes on the VPU), but in
# different summation orders, and SGD chains each event's rounding
# difference into the next. The bound sits well above that drift and
# below what rounding the final factors to bf16 alone moves them (about
# 1e-3 at the factors' scale); every run checks that this bf16 control
# exceeds it, so a path that lost f32 precision would fail.
FLOAT_ATOL = 1e-4


def log(record: dict) -> None:
    print(json.dumps(record, default=float), flush=True)


def fail(msg: str, code: int = 1):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def require(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def caps(n_users: int, n_items: int, grid) -> tuple[int, int]:
    """Collision-free per-worker capacities, rounded up to 128."""
    up = lambda n, k: int(math.ceil(math.ceil(n / k) / 128) * 128)  # noqa: E731
    return up(n_users, grid.g), up(n_items, grid.n_i)


def make_stream(scale: float):
    from repro.data.stream import MOVIELENS_25M, scaled, synth_stream

    prof = MOVIELENS_25M if scale == 1.0 else scaled(MOVIELENS_25M, scale)
    t0 = time.perf_counter()
    users, items, _ = synth_stream(prof, seed=SEED)
    n = WINDOWS * WINDOW
    require(users.size >= n, f"stream has {users.size} < {n} events")
    return prof, users[:n], items[:n], time.perf_counter() - t0


def windows(users, items):
    for w in range(WINDOWS):
        sl = slice(w * WINDOW, (w + 1) * WINDOW)
        yield users[sl], items[sl]


def config(algorithm: str, grid, prof, backend: str):
    from repro.core.algorithm import get_algorithm
    from repro.core.pipeline import StreamConfig

    u_cap, i_cap = caps(prof.n_users, prof.n_items, grid)
    hyper = get_algorithm(algorithm).default_hyper()._replace(
        u_cap=u_cap, i_cap=i_cap)
    return StreamConfig(algorithm=algorithm, grid=grid,
                        micro_batch=MICRO_BATCH,
                        capacity_factor=CAPACITY_FACTOR, hyper=hyper,
                        backend=backend, seed=SEED)


def host_tree(tree):
    import jax
    import numpy as np

    return jax.tree.map(np.asarray, tree)


def compare_states(a, b, what: str, exact_floats: bool = False) -> dict:
    """Integer/bool leaves identical; float leaves within FLOAT_ATOL, or
    identical when ``exact_floats``. Logs, per float leaf, the largest
    |a - b| and the same reading with ``a`` rounded to bf16 (a control
    the tolerance has to reject), then checks; returns the readings."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    diffs, control = {}, {}
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree.leaves(b)):
        name = jax.tree_util.keystr(path)
        require(x.shape == y.shape and x.dtype == y.dtype,
                f"{what}{name}: {x.shape}/{x.dtype} vs {y.shape}/{y.dtype}")
        if x.dtype.kind == "f":
            require(np.all(np.isfinite(x)), f"{what}{name}: non-finite")
            maxabs = lambda d: float(np.max(np.abs(d))) if d.size else 0.0  # noqa: E731
            diffs[name] = maxabs(x - y)
            control[name] = maxabs(
                x.astype(jnp.bfloat16).astype(x.dtype) - y)
        else:
            require(np.array_equal(x, y), f"{what}{name}: tables differ")
    limit = 0.0 if exact_floats else FLOAT_ATOL
    log({"phase": "state_compare", "what": what, "float_atol": limit,
         "float_max_abs_diff": diffs, "bf16_control_max_abs_diff": control})
    for name, d in diffs.items():
        require(d <= limit, f"{what}{name}: max |diff| {d} > {limit}")
    if diffs and not exact_floats:
        require(max(control.values()) > limit,
                f"{what}: float_atol {limit} does not reject the bf16 control")
    return diffs


def check_recommendations(resp, qids, n_known_max: int, n_items: int,
                          rated: dict) -> dict:
    """Lists are well formed: shape, id range, no repeats, scores sorted,
    never an item the user already rated; unknown users fall back."""
    import numpy as np

    ids, scores, known = resp.ids, resp.scores, resp.known
    require(ids.shape[0] == len(qids) and scores.shape == ids.shape,
            f"response shape {ids.shape}")
    for row, (uid, sc, kn) in enumerate(zip(ids, scores, known)):
        q = int(qids[row])
        real = uid[uid >= 0]
        require(np.all(real < n_items), f"query {q}: id out of range")
        require(real.size == np.unique(real).size, f"query {q}: repeats")
        require(np.all(uid[:real.size] >= 0), f"query {q}: -1 inside list")
        fin = sc[:real.size]
        require(np.all(np.isfinite(fin)), f"query {q}: non-finite score")
        require(np.all(np.diff(fin) <= 0), f"query {q}: scores not sorted")
        if q >= n_known_max:
            require(not kn, f"unknown user {q} reported known")
        elif kn:
            seen = rated.get(q, set())
            require(not seen.intersection(real.tolist()),
                    f"query {q}: recommends an item it already rated")
    return {"known": int(known.sum()), "filled": int((ids >= 0).sum())}


def strided_cost(algorithm: str, hyper, states, n_events: int) -> dict:
    """Upper bound on the bytes the strided (column) accesses move.

    Each eviction of an item slot clears its rated column in place,
    rewriting at most the 128-lane strip of every user row; with
    collision-free capacities the evictions are the first sightings of
    each item on each worker, i.e. the live item slots at the end. DICS
    also reads and writes the 128-lane ``co`` column strip per event.
    """
    import numpy as np

    clears = int(np.sum(states.tables.item_ids >= 0))
    per_clear = 2 * hyper.u_cap * 128
    out = {"column_clears": clears, "clear_bytes_each_max": per_clear,
           "clear_bytes_max": clears * per_clear}
    if algorithm == "dics":
        per_event = 2 * 4 * hyper.i_cap * 128
        out["co_column_bytes_each"] = per_event
        out["co_column_bytes_max"] = n_events * per_event
    return out


def serve_program_text(session, qids) -> str:
    """The compiled serve step the front-end dispatches."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import plane

    cfg = session.frontend.cfg
    arr = np.full(cfg.batch_size, -1, np.int64)
    arr[:min(len(qids), cfg.batch_size)] = qids[:cfg.batch_size]
    return plane.grid_topn.lower(
        session.states, jnp.asarray(arr), algorithm=cfg.algorithm,
        grid=cfg.grid, top_n=cfg.top_n, u_cap=cfg.u_cap, qcap=cfg.qcap,
        k_nn=cfg.k_nn, use_kernel=cfg.use_kernel,
        storage=cfg.storage).compile().as_text()


def run_reference(cfg, users, items):
    """The same windows through ``run_stream`` (no snapshot store)."""
    from repro.core.pipeline import run_stream

    import jax

    states, bits, walls = None, [], []
    for u, i in windows(users, items):
        t0 = time.perf_counter()
        res = run_stream(u, i, cfg, initial_states=states)
        states = res.final_states
        jax.block_until_ready(states)
        walls.append(time.perf_counter() - t0)
        require(res.events_processed + res.dropped == u.size,
                f"{cfg.backend}: processed+dropped != {u.size}")
        require(res.dropped == 0, f"{cfg.backend}: dropped {res.dropped}")
        bits.append(res.recall.bits())
        del res
    return states, bits, walls


def nanmean(bits) -> float:
    import numpy as np

    b = np.concatenate(bits)
    b = b[~np.isnan(b)]
    return float(b.mean()) if b.size else float("nan")


# Persistent compilation cache events (requests, misses, hits) seen by
# this process, counted from the start of the run.
CACHE_EVENTS: dict[str, int] = {}
HITS = "/jax/compilation_cache/cache_hits"


def count_cache_event(event: str, **_) -> None:
    if event.startswith("/jax/compilation_cache/"):
        CACHE_EVENTS[event] = CACHE_EVENTS.get(event, 0) + 1


def cache_probe(cfg, steps: int, first_seconds: float,
                first_events: dict) -> None:
    """Compile the ingest step again after dropping every in-memory
    cache. A hit on the first compile means an earlier process's entry
    was found (a cold run after a warm one); a hit here means this
    process's own entry was."""
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.core import engine

    written = sum(1 for _ in Path(enable_compile_cache()).glob("*"))
    CACHE_EVENTS.clear()
    engine.compiled_scan.cache_clear()
    jax.clear_caches()
    t0 = time.perf_counter()
    engine.compiled_scan(cfg, steps)
    log({"phase": "compile_cache", "first_compile_s": first_seconds,
         "first_compile_events": first_events,
         "hit_first_compile": bool(first_events.get(HITS)),
         "second_compile_s": time.perf_counter() - t0,
         "second_compile_events": dict(CACHE_EVENTS),
         "hit_second_compile": bool(CACHE_EVENTS.get(HITS)),
         "cache_entries": written})


def one_chip_algorithm(algorithm: str, scale: float, device, stream,
                       probe_cache: bool) -> None:
    import jax
    import numpy as np

    from repro import StreamSession
    from repro.core import algorithm as algorithm_lib
    from repro.core import engine, storage
    from repro.core.routing import GridSpec

    prof, users, items, gen_s = stream
    grid = GridSpec(4)
    cfg = config(algorithm, grid, prof, "pallas")
    hyper = cfg.resolved_hyper()
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        backend = algorithm_lib.negotiated_backend(cfg)
    require(backend == "pallas", f"{algorithm}: negotiated {backend}")

    # The ingest step, compiled exactly as StreamSession.ingest runs it.
    steps = engine.scan_steps(cfg, WINDOW)
    t0 = time.perf_counter()
    exe = engine.compiled_scan(cfg, steps)
    compile_s = time.perf_counter() - t0
    first_events = dict(CACHE_EVENTS)
    require("tpu_custom_call" in exe.as_text(),
            f"{algorithm}: no Pallas kernel in the compiled ingest step")
    mem = exe.memory_analysis()
    state_bytes = sum(b for _, b in storage.state_nbytes(
        jax.eval_shape(lambda: engine.init_scan_carry(cfg)[0])).values())
    limit = device.memory_stats().get("bytes_limit", 0)
    # In flight during ingest: the step's arguments, outputs and temps,
    # plus the previous snapshot the serving store double-buffers.
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + state_bytes)
    require(not limit or need <= limit,
            f"{algorithm}: needs {need} B of {limit} B device memory")
    if probe_cache:
        cache_probe(cfg, steps, compile_s, first_events)

    session = StreamSession(cfg)
    walls, bits = [], []
    rated: dict[int, set] = {}
    for u, i in windows(users, items):
        t0 = time.perf_counter()
        res = session.ingest(u, i)
        jax.block_until_ready(session.states)
        walls.append(time.perf_counter() - t0)
        require(res.events_processed + res.dropped == u.size,
                f"{algorithm}: processed+dropped != {u.size}")
        require(res.dropped == 0, f"{algorithm}: dropped {res.dropped}")
        bits.append(res.recall.bits())
        del res

    rng = np.random.default_rng(SEED)
    qids = np.concatenate([
        rng.choice(users, N_QUERIES - N_UNKNOWN),           # Zipf activity
        prof.n_users + np.arange(N_UNKNOWN)])                # never seen
    asked = np.isin(users, qids)
    for u, i in zip(users[asked].tolist(), items[asked].tolist()):
        rated.setdefault(u, set()).add(i)
    text = serve_program_text(session, qids)
    require("tpu_custom_call" in text,
            f"{algorithm}: no Pallas kernel in the compiled serve step")
    t0 = time.perf_counter()
    resp = session.recommend(qids)
    serve_s = time.perf_counter() - t0
    served = check_recommendations(resp, qids, prof.n_users, prof.n_items,
                                   rated)
    pallas_states = host_tree(session.states)
    peak = device.memory_stats().get("peak_bytes_in_use")
    del session, resp
    gc.collect()

    ref_states, ref_bits, ref_walls = run_reference(
        config(algorithm, grid, prof, "scan"), users, items)
    ref_states = host_tree(ref_states)
    gc.collect()
    diffs = compare_states(pallas_states, ref_states, f"{algorithm} states",
                           exact_floats=algorithm == "dics")
    log({"phase": "one_chip", "algorithm": algorithm, "scale": scale,
         "users": prof.n_users, "items": prof.n_items,
         "u_cap": hyper.u_cap, "i_cap": hyper.i_cap,
         "workers": grid.n_c, "events": users.size,
         "stream_gen_s": gen_s, "compile_s": compile_s,
         "window_wall_s": walls, "reference_window_wall_s": ref_walls,
         "recall_pallas": nanmean(bits), "recall_scan": nanmean(ref_bits),
         "state_bytes": state_bytes, "step_bytes": {
             "argument": mem.argument_size_in_bytes,
             "output": mem.output_size_in_bytes,
             "temp": mem.temp_size_in_bytes},
         "peak_bytes_in_use": peak, "serve_s": serve_s, **served,
         "float_max_abs_diff": max(diffs.values(), default=0.0),
         **strided_cost(algorithm, hyper, pallas_states, users.size)})


def four_chip_grid(devices) -> None:
    import jax
    import numpy as np

    from repro import StreamSession
    from repro.core.routing import GridSpec

    require(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
            f"{len(devices)}")
    grid = GridSpec(2)
    prof, users, items, _ = make_stream(1.0)
    cfg = config("disgd", grid, prof, "shard_map")
    session = StreamSession(cfg)
    walls, bits = [], []
    for u, i in windows(users, items):
        # shard_map runs compile per call (the engine memoizes only
        # mesh-free executables), so each window includes its compile.
        t0 = time.perf_counter()
        res = session.ingest(u, i)
        jax.block_until_ready(session.states)
        walls.append(time.perf_counter() - t0)
        require(res.events_processed + res.dropped == u.size
                and res.dropped == 0, "shard_map: events lost")
        bits.append(res.recall.bits())
        del res
    placement = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(session.states):
        shards = leaf.addressable_shards
        devs = sorted(s.device.id for s in shards)
        per = [int(s.data.nbytes) for s in shards]
        require(len(set(devs)) == grid.n_c and all(
            s.data.shape[0] == 1 for s in shards),
            f"{jax.tree_util.keystr(path)} not one worker per device: "
            f"{leaf.sharding}")
        placement[jax.tree_util.keystr(path)] = {
            "sharding": str(leaf.sharding.spec), "devices": devs,
            "bytes_per_device": per}
    # Device memory while the shard_map session's states are live.
    per_device = {str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices[:grid.n_c]}
    sm_states = host_tree(session.states)
    del session
    gc.collect()

    ref_states, ref_bits, ref_walls = run_reference(
        config("disgd", grid, prof, "scan"), users, items)
    ref_states = host_tree(ref_states)
    a, b = np.concatenate(bits), np.concatenate(ref_bits)
    require(np.array_equal(np.isnan(a), np.isnan(b))
            and np.array_equal(a[~np.isnan(a)], b[~np.isnan(b)]),
            "shard_map recall bits differ from scan")
    # Integer tables identical. The factor tables may differ in the last
    # bits: the same per-event SGD step, compiled for one worker per
    # device or batched over the grid's workers in one program, rounds
    # its row dot differently (one f32 ulp on the CPU backend from the
    # first micro-batch on, with identical inputs and no sharding).
    diffs = compare_states(sm_states, ref_states, "shard_map states")
    log({"phase": "four_chip", "algorithm": "disgd", "grid": "2x2",
         "u_cap": cfg.resolved_hyper().u_cap,
         "i_cap": cfg.resolved_hyper().i_cap,
         "window_wall_s": walls, "reference_window_wall_s": ref_walls,
         "recall": nanmean(bits), "recall_scan": nanmean(ref_bits),
         "float_max_abs_diff": max(diffs.values(), default=0.0),
         "placement": placement,
         "bytes_in_use_per_device": per_device})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "__init__.py").is_file():
        fail(f"the repository's sources are not next to this script "
             f"({src})", 2)
    sys.path.insert(0, str(src))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform} devices", 3)
    from jax import monitoring

    from repro.compile_cache import enable_compile_cache

    monitoring.register_event_listener(count_cache_event)
    log({"phase": "setup", "compile_cache_dir": enable_compile_cache(),
         "devices": len(devices), "kind": devices[0].device_kind,
         "jax": jax.__version__})

    if args.chips == 4:
        four_chip_grid(devices)
        count = len(devices)
    else:
        full, failed = None, []
        for k, algorithm in enumerate(ALGORITHMS):
            scale = DICS_SCALE if algorithm == "dics" else 1.0
            if scale == 1.0:
                full = full or make_stream(1.0)
                stream = full
            else:
                stream = make_stream(scale)
            # A failed check has printed its reason; the other algorithms
            # still run so that one run reports every failure.
            try:
                one_chip_algorithm(algorithm, scale, devices[0], stream,
                                   probe_cache=k == 0)
            except SystemExit:
                failed.append(algorithm)
            gc.collect()
        if failed:
            fail(f"checks failed for {', '.join(failed)}")
        count = len(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
