"""Kernel microbenchmarks + execution-tile autotune + CI smoke gate.

Three layers, matching how the fused kernels actually ship:

  * ``rows()`` — analytic rooflines per kernel (FLOPs / bytes /
    intensity at the configured tile sizes) alongside measured oracle
    wall-times. Off TPU the ops dispatch to their jnp oracles (the
    production path there); the derived column reports what the real
    kernel costs on TPU hardware.
  * ``update_rows()`` / ``serve_rows()`` — measured events/s (resp.
    µs/call) of the fused update and serve-leaf entry points on
    realistic worker shapes, via the same ``ops.*`` dispatch the engine
    uses.
  * ``engine_rows()`` / ``autotune()`` / ``smoke()`` — end-to-end
    engine throughput at the cached execution tiles
    (``repro.kernels.tiles``). ``--autotune`` sweeps micro-batch x
    per-bucket capacity factor per (algorithm, backend), prefers
    zero-drop winners, records them in the tile registry and persists
    ``BENCH_tiles.json``. ``--smoke`` appends ``kernels/`` rows to
    ``BENCH_smoke.json`` and enforces absolute floors — a regression
    gate separate from the end-to-end ``throughput/`` rows.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.roofline.analysis import HW

# Execution-tile sweep grid (``--autotune``).
TUNE_MB = (128, 256, 512)
TUNE_CF = (1.0, 1.25, 2.0)

# Stream-length divisor per algorithm (data, not dispatch): DICS's
# O(i_cap^2) co updates run at roughly half the factor models' rate.
EVENT_DIVISOR = {"dics": 2}

# Smoke-gate floors (conservative absolutes, CPU container). The engine
# floor is the pre-tuning scan baseline this PR had to beat (ISSUE 8);
# the op floors sit far below healthy measurements so only a real
# regression (not CI jitter) trips them.
ENGINE_FLOOR_EV_S = 157_000.0      # best kernels/engine row must beat this
UPDATE_FLOOR_EV_S = {"disgd": 20_000.0, "bpr": 20_000.0, "dics": 15_000.0}
SERVE_CEIL_US = {"disgd": 20_000.0, "dics": 50_000.0}


def _time(fn, *args, iters: int = 5) -> float:
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def rows():
    from repro.kernels import ref

    hw = HW()
    rng = np.random.default_rng(0)
    out = []

    # Scoring kernel: B users x I items shard, k latent.
    b, i, k = 256, 2048, 32
    u = jnp.asarray(rng.normal(size=(b, k)), jnp.float32)
    it = jnp.asarray(rng.normal(size=(i, k)), jnp.float32)
    mask = jnp.asarray(rng.random((b, i)) > 0.2)
    us = _time(jax.jit(ref.masked_scores), u, it, mask)
    flops = 2 * b * i * k
    bytes_ = 4 * (b * k + i * k + b * i) + b * i
    out.append({
        "name": f"kernel/scoring/B{b}xI{i}xk{k}",
        "us_per_call": us,
        "derived": (
            f"tpu_compute_us={flops / hw.peak_flops * 1e6:.2f}"
            f" tpu_hbm_us={bytes_ / hw.hbm_bw * 1e6:.2f}"
            f" intensity={flops / bytes_:.1f}"
        ),
    })

    # SWA flash attention: prefill tile.
    b2, hq, hkv, s, d = 1, 8, 2, 2048, 128
    window = 512
    q = jnp.asarray(rng.normal(size=(b2, hq, s, d)), jnp.bfloat16)
    kk = jnp.asarray(rng.normal(size=(b2, hkv, s, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(b2, hkv, s, d)), jnp.bfloat16)
    ref_fn = jax.jit(lambda a, b3, c: ref.swa_attention(a, b3, c,
                                                        window=window))
    us = _time(ref_fn, q, kk, v)
    flops = 4 * b2 * hq * s * window * d  # qk + pv within window
    bytes_full = 2 * (b2 * hq * s * d * 2 + b2 * hq * s * s)  # materialized
    bytes_flash = 2 * (b2 * hq * s * d * 3)
    out.append({
        "name": f"kernel/swa_attn/S{s}_w{window}_h{hq}",
        "us_per_call": us,
        "derived": (
            f"tpu_compute_us={flops / hw.peak_flops * 1e6:.2f}"
            f" hbm_saving_vs_materialized="
            f"{bytes_full / bytes_flash:.1f}x"
        ),
    })
    return out


# -- fused update / serve-leaf ops on realistic worker shapes -------------


def _zero_worker(algorithm: str, u_cap: int = 1024, i_cap: int = 128):
    from repro.core.algorithm import get_algorithm
    from repro.core.pipeline import StreamConfig, init_states
    from repro.core.routing import GridSpec

    cfg = StreamConfig(
        algorithm=algorithm, grid=GridSpec(1), micro_batch=256,
        backend="scan",
        hyper=get_algorithm(algorithm).default_hyper()._replace(
            u_cap=u_cap, i_cap=i_cap))
    st = jax.tree.map(lambda x: x[0], init_states(cfg))
    return st, cfg.resolved_hyper()


def _update_events(hyper, n_ev: int, pairwise: bool, seed: int = 0):
    from repro.core import state as state_lib

    rng = np.random.default_rng(seed)
    ev_u = jnp.asarray(rng.integers(0, 4096, n_ev), jnp.int32)
    ev_i = jnp.asarray(rng.integers(0, 512, n_ev), jnp.int32)
    u_slot = state_lib.slot_of(ev_u, hyper.g, hyper.u_cap)
    i_slot = state_lib.slot_of(ev_i, hyper.n_i, hyper.i_cap)
    if not hasattr(hyper, "k"):
        return (ev_u, ev_i, u_slot, i_slot)
    j_slot = (jnp.asarray(rng.integers(0, hyper.i_cap, n_ev), jnp.int32)
              if pairwise else None)
    init_u = jnp.asarray(rng.normal(size=(n_ev, hyper.k)) * 0.1, jnp.float32)
    init_i = jnp.asarray(rng.normal(size=(n_ev, hyper.k)) * 0.1, jnp.float32)
    return (ev_u, ev_i, u_slot, i_slot, j_slot, init_u, init_i)


def update_rows(n_ev: int = 2048):
    """Fused micro-batch update ops (``ops.factor_update`` /
    ``ops.dics_update``) in events/s — the number the engine's per-bucket
    cost is made of. DICS runs a smaller batch: its per-event cost is
    O(i_cap^2) counters, not O(k)."""
    from repro.kernels import ops

    out = []
    for algorithm, pairwise in (("disgd", False), ("bpr", True)):
        st, hyper = _zero_worker(algorithm)
        events = _update_events(hyper, n_ev, pairwise)
        fn = jax.jit(lambda uv, iv, r, t, ev: ops.factor_update(
            uv, iv, r, t, ev, eta=hyper.eta, lam=hyper.lam))
        us = _time(fn, st.user_vecs, st.item_vecs, st.rated,
                   tuple(st.tables), events)
        out.append({
            "name": f"kernels/update/{algorithm}",
            "events": n_ev,
            "us_per_call": us,
            "events_per_sec": n_ev / (us * 1e-6),
        })

    n_dics = n_ev // 4
    st, hyper = _zero_worker("dics")
    events = _update_events(hyper, n_dics, pairwise=False)
    fn = jax.jit(lambda co, cnt, r, t, ev: ops.dics_update(co, cnt, r, t, ev))
    us = _time(fn, st.co, st.item_cnt, st.rated, tuple(st.tables), events)
    out.append({
        "name": "kernels/update/dics",
        "events": n_dics,
        "us_per_call": us,
        "events_per_sec": n_dics / (us * 1e-6),
    })
    return out


def serve_rows(batch: int = 64):
    """One-kernel serve leaves: fused score+mask+partial-topn
    (``ops.fused_topn``) and the DICS Eq. 6/7 leaf, µs per query batch."""
    from repro.core.dics import dics_partial_topn
    from repro.kernels import ops

    rng = np.random.default_rng(1)
    out = []

    st, hyper = _zero_worker("disgd")
    u_vecs = jnp.asarray(rng.normal(size=(batch, hyper.k)), jnp.float32)
    mask = jnp.asarray(rng.random((batch, hyper.i_cap)) > 0.2)
    fn = jax.jit(lambda u, iv, m, ids: ops.fused_topn(
        u, iv, m, ids, top_n=10))
    us = _time(fn, u_vecs, st.item_vecs, mask, st.tables.item_ids)
    out.append({
        "name": "kernels/serve_leaf/disgd",
        "batch": batch,
        "us_per_call": us,
        "queries_per_sec": batch / (us * 1e-6),
    })

    st, hyper = _zero_worker("dics")
    user_ids = jnp.asarray(rng.integers(0, 4096, batch), jnp.int32)
    fn = jax.jit(lambda s, q: dics_partial_topn(
        s, q, top_n=10, k_nn=hyper.k_nn, g=hyper.g, u_cap=hyper.u_cap))
    us = _time(fn, st, user_ids)
    out.append({
        "name": "kernels/serve_leaf/dics",
        "batch": batch,
        "us_per_call": us,
        "queries_per_sec": batch / (us * 1e-6),
    })
    return out


# -- end-to-end engine throughput at the cached execution tiles -----------


def engine_rows(events: int = 6144, repeats: int = 2):
    """Engine throughput per (algorithm, backend) at the tile registry's
    winners — the rows the smoke gate floors."""
    from benchmarks.common import run
    from repro.kernels import tiles

    platform = jax.default_backend()
    out = []
    for algorithm in ("disgd", "bpr", "dics"):
        ev = events // EVENT_DIVISOR.get(algorithm, 1)
        for backend in ("scan", "pallas"):
            tile = tiles.best_tile("engine", algorithm, backend, platform)
            mb = int(tile["micro_batch"])
            cf = float(tile["capacity_factor"])
            res = run(algorithm, "movielens", 4, ev, backend=backend,
                      micro_batch=mb, capacity_factor=cf, repeats=repeats)
            out.append({
                "name": f"kernels/engine/{algorithm}/{backend}",
                "backend": backend,
                "micro_batch": mb,
                "capacity_factor": cf,
                "events": int(res.events_processed),
                "dropped": int(res.dropped),
                "events_per_sec": res.throughput,
                "recall": res.recall.mean(),
            })
    return out


def autotune(out_path: str = "BENCH_tiles.json", events: int = 6144,
             algorithms=("disgd", "bpr", "dics"),
             backends=("scan", "pallas")):
    """Sweep micro-batch x capacity-factor per (algorithm, backend),
    record zero-drop throughput winners in the tile registry, persist
    them to ``out_path``. Returns the full sweep table."""
    from benchmarks.common import run
    from repro.kernels import tiles

    platform = jax.default_backend()
    table = []
    for algorithm in algorithms:
        ev = events // EVENT_DIVISOR.get(algorithm, 1)
        for backend in backends:
            best = None
            for mb in TUNE_MB:
                for cf in TUNE_CF:
                    res = run(algorithm, "movielens", 4, ev, backend=backend,
                              micro_batch=mb, capacity_factor=cf, repeats=1)
                    cand = {
                        "algorithm": algorithm, "backend": backend,
                        "micro_batch": mb, "capacity_factor": cf,
                        "events_per_sec": res.throughput,
                        "dropped": int(res.dropped),
                        "recall": res.recall.mean(),
                    }
                    table.append(cand)
                    # Zero-drop beats any dropping config; throughput
                    # breaks ties (dropping events is shedding load, not
                    # processing it faster).
                    key = (cand["dropped"] == 0, cand["events_per_sec"])
                    if best is None or key > best[0]:
                        best = (key, cand)
            win = best[1]
            tiles.record("engine", algorithm, backend, platform, {
                "micro_batch": win["micro_batch"],
                "capacity_factor": win["capacity_factor"],
            })
            print(f"# winner engine/{algorithm}/{backend}/{platform}: "
                  f"mb={win['micro_batch']} cf={win['capacity_factor']} "
                  f"({win['events_per_sec']:,.0f} ev/s, "
                  f"dropped={win['dropped']})", file=sys.stderr)
    tiles.save(out_path)
    print(f"# wrote {out_path}", file=sys.stderr)
    return table


def smoke(out_path: str = "BENCH_smoke.json", events: int = 6144) -> int:
    """Append ``kernels/`` rows to the smoke artifact and enforce the
    kernel-level floors (returns exit status). This gate is deliberately
    separate from the end-to-end ``throughput/`` rows: it pins the fused
    ops and the tuned-tile engine configs, so an engine regression can't
    hide behind an unrelated end-to-end win (or vice versa)."""
    from benchmarks.common import smoke_update

    t0 = time.perf_counter()
    new_rows = engine_rows(events) + update_rows() + serve_rows()
    smoke_update(out_path, "kernels/", new_rows,
                 wall_seconds=time.perf_counter() - t0)

    status = 0
    best = 0.0
    for r in new_rows:
        if "events_per_sec" in r:
            print(f"{r['name']},{r['us_per_call']:.2f}"
                  if "us_per_call" in r else f"{r['name']}", end="")
            print(f",events/s={r['events_per_sec']:,.0f}"
                  + (f",dropped={r['dropped']}" if r.get("dropped") else ""))
        else:
            print(f"{r['name']},{r['us_per_call']:.2f},"
                  f"qps={r['queries_per_sec']:,.0f}")
        tail = r["name"].rsplit("/", 2)
        if r["name"].startswith("kernels/engine/"):
            best = max(best, r["events_per_sec"])
        elif r["name"].startswith("kernels/update/"):
            floor = UPDATE_FLOOR_EV_S[tail[-1]]
            if r["events_per_sec"] < floor:
                print(f"# FAIL: {r['name']} at "
                      f"{r['events_per_sec']:,.0f} ev/s < floor "
                      f"{floor:,.0f}", file=sys.stderr)
                status = 2
        elif r["name"].startswith("kernels/serve_leaf/"):
            ceil = SERVE_CEIL_US[tail[-1]]
            if r["us_per_call"] > ceil:
                print(f"# FAIL: {r['name']} at {r['us_per_call']:,.0f}µs "
                      f"> ceiling {ceil:,.0f}µs", file=sys.stderr)
                status = 2
    if best < ENGINE_FLOOR_EV_S:
        print(f"# FAIL: best kernels/engine row {best:,.0f} ev/s does not "
              f"beat the pre-tuning floor {ENGINE_FLOOR_EV_S:,.0f}",
              file=sys.stderr)
        status = 2
    print(f"# appended kernel rows to {out_path} "
          f"(best engine {best:,.0f} ev/s)")
    return status


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: append kernels/ rows + enforce floors")
    ap.add_argument("--smoke-out", default="BENCH_smoke.json")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep execution tiles, write BENCH_tiles.json")
    ap.add_argument("--tiles-out", default="BENCH_tiles.json")
    ap.add_argument("--events", type=int, default=6144)
    args = ap.parse_args()
    if args.autotune:
        print("algorithm,backend,micro_batch,capacity_factor,"
              "events_per_sec,dropped,recall")
        for c in autotune(args.tiles_out, args.events):
            print(f"{c['algorithm']},{c['backend']},{c['micro_batch']},"
                  f"{c['capacity_factor']},{c['events_per_sec']:,.0f},"
                  f"{c['dropped']},{c['recall']:.3f}")
        return
    if args.smoke:
        raise SystemExit(smoke(args.smoke_out, args.events))
    print("name,us_per_call,derived")
    for row in rows():
        print(f"{row['name']},{row['us_per_call']:.2f},{row['derived']}")
    for row in update_rows() + serve_rows():
        extra = (f"events/s={row['events_per_sec']:,.0f}"
                 if "events_per_sec" in row
                 else f"qps={row['queries_per_sec']:,.0f}")
        print(f"{row['name']},{row['us_per_call']:.2f},{extra}")


if __name__ == "__main__":
    main()
