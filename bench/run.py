#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix come from
``BENCHMARK.json`` at the root of the checkout and the files it names:
``configs/<config>.json`` (the deployment), ``traffic/<traffic>.json``
(the mix, whose ``kind`` names its runner in ``kinds/``), and for each
per-layer metric ``metrics/<name>.py``. The configuration names its
plain reference (``reference/<name>.py``), the builder of its warm
start where it asks for one (``warm/<reference>.py``), and its worker
kernels, whose needed work ``work/<kernel>.py`` counts. Nothing else is
per cell, so a cell, a configuration, a mix or a metric is added by
adding files.

A kind's module has ``run(h)``, which sets up and drives the window
through the :class:`Run` ``h``, and may have ``check(h, states,
control)``, its own comparison with the reference (the default,
:func:`output_check`, replays the ingest calls). A per-layer reader has
``read(ctx)`` and gets a :class:`MetricContext`: the trace, the
:class:`Run` (``ctx.run``: calls, counts and whatever the kind set on
it) and the session's metrics registry at the window's open and close
(``ctx.counters``).

The run sets up (stream, session, warm-up: ``setup_s``), measures for
``--seconds``, reads the device's peak memory, frees the program's
state and checks what the timed path produced against the plain
reference (``check.py``). Its last stdout line is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``check``, the numbers
compared beside their limits (also the last lines of stderr). With
``--trace 0`` the metrics are the cell's end-to-end ones, with
``--trace 1`` its per-layer ones, read from a profiler trace of the
window's last ``TRACE_SECONDS`` and the run's counts.

``--control 1`` also runs the reference in the precision below the one
the configuration states and prints, on an earlier line, the numbers
that control reads against the reference (the check's upper readings).

Exit codes: 0 with a result line; 2 for a cell, file or program that is
not there, or a configuration that states what no reference replays
(``cellspec.validate``); 3 where JAX finds no TPU or fewer chips than
the cell asks for; 4 where the stream producer made the trainer wait.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE / "kinds"))      # a kind may build on another

import numpy as np  # noqa: E402

import cellspec  # noqa: E402
import check  # noqa: E402
import deploy  # noqa: E402
import devtrace  # noqa: E402
import srgrid  # noqa: E402

GIB = 2 ** 30

#: Seconds at the end of a ``--trace 1`` run's window that the profiler
#: records: it starts at the call boundary before the last
#: ``TRACE_SECONDS`` of the window and stops when the window closes, and
#: the per-layer metrics read the calls it recorded. (A DISGD window of
#: 30 s holds millions of device events; stopping a trace of all of them
#: takes minutes.)
TRACE_SECONDS = 8.0


def log(record: dict) -> None:
    print(json.dumps(record, default=float), file=sys.stderr, flush=True)


def fail(msg: str, code: int):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def load(path: Path):
    """A module of the benchmark, found by its file name."""
    if not path.is_file():
        fail(f"{path} is not there", 2)
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names."""

    def __init__(self, root: Path, name: str):
        path = root / "BENCHMARK.json"
        if not path.is_file():
            fail(f"{path} is not there", 2)
        man = json.loads(path.read_text())
        cells = {w["name"]: w for w in man["workloads"]}
        if name not in cells:
            fail(f"no cell {name!r} in BENCHMARK.json", 2)
        self.name = name
        self.spec = cells[name]
        conf = {c["name"]: c for c in man["configs"]}[self.spec["config"]]
        self.doc = json.loads((root / conf["file"]).read_text())
        try:
            cellspec.validate(self.doc)
        except ValueError as e:
            fail(f"{conf['file']}: {e}", 2)
        self.traffic = json.loads(
            (HERE / "traffic" / f"{self.spec['traffic']}.json").read_text())
        self.end_to_end = [m for m in man["end_to_end"]
                           if name in m.get("workloads", cells)]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in man["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in e2e)]


class Run:
    """What a traffic kind's runner is handed, and what it hands back."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool):
        self.doc, self.traffic = cell.doc, cell.traffic
        self.profile = cellspec.profile(cell.doc)
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.calls: list = []      # (users, items) of every ingest call
        self.bits: list = []       # the program's prequential bits per call
        self.forgets: list = []    # the program's forgetting triggers per call
        self.history = None        # (users, items) folded into a warm start
        self.first_window_call = 0
        self.e2e: dict = {}
        self.session = None
        self.counters: dict = {}   # registry snapshots: "start", "end"
        self.processed = self.live_steps = self.attempted = self.failed = 0
        self.fresh_steps = 0
        self.marks: dict = {}      # set-up stages a kind timed (perf_counter)
        self.window_calls: list = []   # per window call: what it did
        self.first_traced = None   # first window call the profiler recorded
        self.starved_s = 0.0
        self.setup_s = self.window_s = 0.0
        self.trace_dir: str | None = None
        self.timings: dict = {}    # seconds of the trace's stop and reading
        self.gc2_s: list = []      # full garbage collections in the window
        self.compiles = {"backend_compiles": 0, "cache_hits": 0}
        self.setup_compiles = {"backend_compiles": 0, "cache_hits": 0}
        self._counting = self._tracing = False
        self._annotation = None
        self._trace_from = self._gc_t0 = 0.0

    def count_compile(self, event: str, *_, **__) -> None:
        counts = self.compiles if self._counting else self.setup_compiles
        if event == "/jax/core/compile/backend_compile_duration":
            counts["backend_compiles"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1

    def count_gc(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._counting:
            self.gc2_s.append(time.perf_counter() - self._gc_t0)

    def call_done(self, **counts) -> None:
        """A kind reports each call of the window when it returns, with
        what it did (``processed``, ``live`` and ``fresh`` steps, ...)."""
        self.window_calls.append(counts)
        now = time.perf_counter()
        if (self.trace and self.first_traced is None
                and now + counts.get("s", 0.0) >= self._trace_from):
            self._begin_trace()

    @property
    def traced_calls(self) -> list:
        """What the calls the profiler recorded did."""
        if self.first_traced is None:
            return []
        return self.window_calls[self.first_traced:]

    def _begin_trace(self) -> None:
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation(devtrace.WINDOW)
        self._annotation.__enter__()
        self._tracing = True
        self.first_traced = len(self.window_calls)

    def _end_trace(self) -> None:
        import jax

        self._annotation.__exit__(None, None, None)
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.timings["trace_stop_s"] = time.perf_counter() - t
        self._tracing = False

    def warm_state(self) -> dict:
        """The state after ``history`` (``warm/<reference>.py``), as
        device arrays named like the program's state leaves."""
        builder = load(HERE / "warm" / f"{self.doc['reference']}.py")
        return builder.build(self.doc, *self.history)

    def _snapshot(self, key: str) -> None:
        if self.session is not None:
            self.counters[key] = self.session.metrics.snapshot()

    @contextlib.contextmanager
    def window(self):
        """The measured window; yields its deadline (perf_counter)."""
        self._snapshot("start")
        self._counting = True
        try:
            t0 = time.perf_counter()
            self.setup_s = t0 - T_START
            self._trace_from = t0 + self.seconds - TRACE_SECONDS
            if self.trace and self._trace_from <= t0:
                self._begin_trace()
            yield t0 + self.seconds
            self.window_s = time.perf_counter() - t0
        finally:
            self._counting = False
            self._snapshot("end")
            if self._tracing:
                self._end_trace()


class MetricContext:
    """What a per-layer reader (``metrics/<name>.py``) reads."""

    def __init__(self, run: Run, trace, peak):
        self.run, self.counters = run, run.counters
        self.doc, self.trace, self.peak = run.doc, trace, peak
        # The traced calls: the window's from ``run.first_traced`` on;
        # ``calls`` holds every call, those before them as history.
        traced = run.traced_calls
        self.first_window_call = run.first_window_call + (run.first_traced
                                                          or 0)
        self.calls = run.calls
        self.history = run.history
        self.processed = sum(c.get("processed", 0) for c in traced)
        self.live_steps = sum(c.get("live", 0) for c in traced)
        self.window_s = (trace.window[1] - trace.window[0]) / 1e9
        self.busy_s = devtrace.busy_s(trace)
        self._work: dict = {}

    def work(self, kernel: str) -> dict:
        if kernel not in self._work:
            self._work[kernel] = load(HERE / "work" / f"{kernel}.py").work(self)
        return self._work[kernel]


def bit_rows(bits) -> np.ndarray:
    return np.concatenate(bits) if bits else np.zeros(0)


def replay_rows(rep, width: int) -> np.ndarray:
    """A reference replay's bits in the program's form (NaN where no
    event was evaluated; hits of the sampled rows, 0 elsewhere)."""
    rows = np.where(rep.evaluated, 0.0, np.nan)
    for r, h in rep.hits.items():
        rows[r][rep.evaluated[r]] = h[rep.evaluated[r]]
    return rows.reshape(-1, width)


def output_check(run: Run, states: dict, control: bool) -> tuple[bool, dict]:
    """The default comparison: ``srgrid.replay`` of every ingest call of
    the run with ``reference/<reference>.py``, from the warm start where
    there is one (``check.py``)."""
    doc = run.doc
    ref_mod = load(HERE / "reference" / f"{doc['reference']}.py")
    lay = srgrid.Layout(
        n_i=doc["n_i"], g=doc["n_i"] + doc["w"],
        micro_batch=doc["micro_batch"],
        capacity=cellspec.bucket_capacity(doc), carry=doc["micro_batch"],
        publish_every=run.traffic["publish"]["every"])
    rows = check.program_rows(bit_rows(run.bits), lay.row)
    rng = np.random.default_rng([int(run.seed), 1])
    n = min(doc["check"]["hit_rows"], rows.shape[0])
    sample = rng.choice(rows.shape[0], size=n, replace=False)

    t0 = time.perf_counter()
    tol = doc["check"]["tie_tolerance"]
    warm = None
    if run.history is not None:
        warm = {k: np.asarray(v) for k, v in run.warm_state().items()}
    ref = ref_mod.Worker(doc)
    if warm is not None:
        ref.load(warm)
    forget = cellspec.forgetting(doc)
    every = forget["trigger_every"] if forget else None
    rep = srgrid.replay(run.calls, lay, ref, sample, every)
    numbers = check.compare(states, ref.states(), ref_mod.FLOAT_LEAVES,
                            rows, rep, tol)
    log({"reference_s": time.perf_counter() - t0,
         "reference_dropped": rep.dropped,
         "reference_forgets": rep.forgets,
         "program_forgets": sum(run.forgets),
         "reference_evicted": ref.evicted, **numbers})
    if control:
        import ml_dtypes

        t0 = time.perf_counter()
        low = ref_mod.Worker(doc, dtype=ml_dtypes.bfloat16)
        if warm is not None:
            low.load(warm)
        low_rep = srgrid.replay(run.calls, lay, low, sample, every)
        readings = check.compare(low.states(), ref.states(),
                                 ref_mod.FLOAT_LEAVES,
                                 replay_rows(low_rep, lay.row), rep, tol)
        log({"control": "bfloat16", "control_s": time.perf_counter() - t0,
             **readings})
    return check.verdict(numbers, doc["check"]["limits"])


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             control: bool = False) -> dict:
    """Set up, measure, check; returns the result object. Needs the
    program importable and JAX initialised on the devices to use."""
    import jax
    from jax import monitoring

    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    t_jax = time.perf_counter()
    peaks = json.loads((HERE / "peaks.json").read_text())
    peak = peaks.get(devices[0].device_kind)
    if peak is None and devices[0].platform == "tpu":
        fail(f"no peaks for device kind {devices[0].device_kind!r}", 2)
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    run = Run(cell, seed, seconds, trace)
    run.marks["jax"] = t_jax
    monitoring.register_event_listener(run.count_compile)
    monitoring.register_event_duration_secs_listener(run.count_compile)
    runner = load(HERE / "kinds" / f"{cell.traffic['kind']}.py")
    gc.callbacks.append(run.count_gc)
    try:
        runner.run(run)
    finally:
        gc.callbacks.remove(run.count_gc)
    log({"cell": cell.name, "seed": seed, "setup_s": run.setup_s,
         "window_s": run.window_s, "calls": len(run.calls),
         "window_calls": len(run.calls) - run.first_window_call,
         "processed": run.processed, "live_steps": run.live_steps,
         "compiles_in_window": run.compiles, "compile_cache": cache_dir,
         "setup_compiles": run.setup_compiles,
         "setup_marks_s": {k: v - T_START for k, v in run.marks.items()},
         "first_traced": run.first_traced, "gc2_s": run.gc2_s,
         "call_s": [c.get("s") for c in run.window_calls],
         "starved_s": run.starved_s})
    if run.starved_s > 0:
        fail(f"the trainer waited {run.starved_s:.3f} s for the stream "
             f"producer", 4)

    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
    states = deploy.host_states(run.session.states)
    run.session = None
    gc.collect()
    check_fn = getattr(runner, "check", output_check)

    metrics: dict = {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    breakdown = None
    if trace:
        if run.trace_dir is None:
            fail("the window closed before the profiler started", 2)
        t = time.perf_counter()
        path = next(Path(run.trace_dir).rglob("*.xplane.pb"))
        tr = devtrace.load(path)
        run.timings["trace_load_s"] = time.perf_counter() - t
        t = time.perf_counter()
        ctx = MetricContext(run, tr, peak)
        device["busy_s"], device["window_s"] = ctx.busy_s, ctx.window_s
        for m in cell.per_layer:
            v = load(HERE / "metrics" / f"{m['name']}.py").read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": devtrace.top_ops(tr),
                     "idle_gaps": devtrace.idle_gaps(tr)}
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        run.timings["trace_read_s"] = time.perf_counter() - t
        log({"trace": run.timings, "traced_calls": len(run.traced_calls)})
    else:
        have = dict(run.e2e, setup_s=run.setup_s,
                    peak_hbm_gib=peak_bytes / GIB)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": have[m["name"]], "unit": m["unit"]}

    correct, numbers = check_fn(run, states, control)
    for name, entry in numbers.items():
        print(f"check {name}: {entry}", file=sys.stderr)
    sys.stderr.flush()
    result = {"correct": bool(correct), "attempted": int(run.attempted),
              "failed": int(run.failed), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(ROOT, args.workload)
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        fail(f"the program is not next to the benchmark ({src})", 2)
    sys.path.insert(0, str(src))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.spec["chips"]:
        fail(f"cell {cell.name} needs {cell.spec['chips']} TPU chip(s); "
             f"JAX found {len(devices)} {devices[0].platform} device(s)", 3)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      control=bool(args.control))
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
