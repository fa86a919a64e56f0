"""Benchmark runner. One module per paper table/figure; prints
``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--only recall,kernels] [--fast]
  PYTHONPATH=src python -m benchmarks.run --smoke   # CI: BENCH_smoke.json
"""

from __future__ import annotations

import argparse
import json
import platform
import sys
import time

SUITES = ("kernels", "recall", "memory", "forgetting", "throughput", "skew",
          "serve", "service", "regrid", "drift", "obs", "ensemble")


def smoke(out_path: str = "BENCH_smoke.json", events: int = 4096) -> int:
    """Tiny host-vs-engine throughput check emitted as a JSON artifact so
    CI runs leave a perf trajectory behind. Also appends the kernel-level
    ``kernels/`` rows (fused ops + tuned-tile engine configs) and the
    ``memory/`` capacity rows (``bench_memory.smoke``) and the
    ``ensemble/`` rows (``bench_ensemble.smoke``); the combined return
    carries every gate — kernel floors, the compressed-policy
    capacity/recall floor, and the ensemble hold-best-single /
    explored-on-drift gates — enforced separately from these end-to-end
    rows."""
    import jax

    from benchmarks import (bench_ensemble, bench_kernels, bench_memory,
                            bench_throughput)
    from benchmarks.common import SMOKE_SCHEMA_VERSION

    t0 = time.perf_counter()
    rows = bench_throughput.smoke_rows(events)
    total = time.perf_counter() - t0
    for row in rows:
        # throughput rows already carry their own run wall; anything
        # without one gets the batch wall, same rule as smoke_update().
        row.setdefault("wall_seconds", round(total, 3))
    payload = {
        "suite": "smoke",
        "schema_version": SMOKE_SCHEMA_VERSION,
        "events": events,
        "backend": jax.default_backend(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "rows": rows,
        "total_seconds": total,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    for row in rows:
        print(f"{row['name']},{1e6 / max(row['events_per_sec'], 1e-9):.2f},"
              f"events/s={row['events_per_sec']:,.0f}")
    print(f"# wrote {out_path} in {payload['total_seconds']:.1f}s",
          file=sys.stderr)
    status = bench_kernels.smoke(out_path)
    status = bench_memory.smoke(out_path, events=events) or status
    return bench_ensemble.smoke(out_path) or status


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(SUITES))
    ap.add_argument("--fast", action="store_true",
                    help="quarter-size streams (CI mode)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny throughput check, writes BENCH_smoke.json")
    ap.add_argument("--smoke-out", default="BENCH_smoke.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        raise SystemExit(smoke(args.smoke_out))
    only = set(args.only.split(",")) if args.only else set(SUITES)

    from benchmarks import (bench_drift, bench_ensemble, bench_forgetting,
                            bench_kernels, bench_memory, bench_obs,
                            bench_recall, bench_regrid, bench_serve,
                            bench_service, bench_skew, bench_throughput)

    scale = 4 if args.fast else 1
    plans = {
        "kernels": lambda: bench_kernels.rows(),
        "recall": lambda: bench_recall.rows(16_384 // scale, 6_144 // scale),
        "memory": lambda: bench_memory.rows(16_384 // scale),
        "forgetting": lambda: bench_forgetting.rows(12_288 // scale),
        "throughput": lambda: bench_throughput.rows(12_288 // scale),
        "skew": lambda: bench_skew.rows(12_288 // scale),
        "serve": lambda: bench_serve.rows(4_096 // scale),
        "service": lambda: bench_service.rows(4_096 // scale),
        "regrid": lambda: bench_regrid.rows(8_192 // scale),
        "drift": lambda: bench_drift.rows(32_768 // scale),
        "obs": lambda: bench_obs.rows(8_192 // scale),
        "ensemble": lambda: bench_ensemble.rows(8_192 // scale),
    }

    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    for suite in SUITES:
        if suite not in only:
            continue
        t1 = time.perf_counter()
        for row in plans[suite]():
            print(f"{row['name']},{row['us_per_call']:.2f},{row['derived']}")
        print(f"# suite {suite} done in {time.perf_counter()-t1:.1f}s",
              file=sys.stderr)
    print(f"# total {time.perf_counter()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
