"""The harness: manifest, trace reduction, window rate, work counts, and
adding a cell, a configuration, a mix and a metric from files alone."""

import json
import math
import re
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import devtrace
from conftest import BENCH, ROOT, checkout, load_run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_manifest_keeps_the_contract():
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["command"] == ["python3", "bench/run.py"]
    assert man["paths"] == ["bench"]
    r = man["run_seconds"]
    assert isinstance(r, int) and 1 <= r <= 51
    assert (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 + 1200 <= 43200

    configs = {c["name"]: c for c in man["configs"]}
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        doc = json.loads((ROOT / c["file"]).read_text())
        assert (BENCH / "reference" / f"{doc['reference']}.py").is_file()
        for k in doc["kernels"]:
            assert (BENCH / "work" / f"{k}.py").is_file()
    cells = {w["name"]: w for w in man["workloads"]}
    pairs = {(w["config"], w["traffic"]) for w in man["workloads"]}
    assert len(pairs) == len(cells) == len(man["workloads"])
    assert {w["config"] for w in man["workloads"]} == set(configs)
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()

    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= set(cells)
    layers = {}
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and (BENCH / "metrics" /
                                      f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        layers.setdefault(m["layer"], m["layer"])
        for w in m.get("workloads", cells):
            assert m["moves"] in e2e and w in e2e[m["moves"]].get(
                "workloads", cells)
    for w in cells:
        have = [m for m in man["end_to_end"] if w in m.get("workloads", cells)]
        assert "setup_s" in [m["name"] for m in have] and len(have) >= 2
        assert any(w in m.get("workloads", cells) for m in man["per_layer"])
    assert len(json.dumps(man)) <= 64 * 1024


def test_trace_reduction_on_a_small_trace():
    planes = [
        ("/host:CPU", [("main", [("bench.window", 100, 1000),
                                 ("ingest", 100, 500), ("publish", 600, 300)])]),
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_scan(1)", 150, 300), ("jit_other(2)", 690, 80)]),
            ("XLA Ops", [("%pre = f32[2] add(...)", 0, 150),
                         ("%while.1 = (s32[]) while(...)", 190, 210),
                         ("%factor_update.3 = f32[8] custom-call(...)", 200, 100),
                         ("%fusion.1 = f32[8] fusion(...)", 300, 60),
                         ("%copy = f32[8] copy(...)", 700, 50)])]),
    ]
    tr = devtrace.from_planes(planes)
    assert tr.window == (100, 1100)
    assert [o[0] for o in tr.ops["/device:TPU:0"]][:2] == ["pre", "while.1"]
    # [100,150) + [190,400) + [700,750); the while holds its body's ops.
    assert devtrace.busy_s(tr) == pytest.approx(310e-9)
    match = __import__("roofline").kernel_match("factor_update")
    assert devtrace.op_ns(tr, match) == 100
    assert dict(devtrace.top_ops(tr)) == pytest.approx(
        {"factor_update.3": 100e-9, "fusion.1": 60e-9, "while.1": 50e-9,
         "copy": 50e-9})
    gaps = dict(devtrace.idle_gaps(tr, short_ns=10))
    assert gaps == pytest.approx({"ingest": 340e-9, "(no host event)": 350e-9})

    run = load_run(ROOT)
    ctx = SimpleNamespace(trace=tr, live_steps=3, doc={"kernels": ["factor_update"]})
    step = run.load(BENCH / "metrics" / "scan_step_ms.py").read(ctx)
    assert step == pytest.approx(300 / 1e6 / 3)


def test_trace_reduction_on_a_recorded_chip_trace():
    """15 ms of a v5e trace of ``ml25m-dics.ingest``, around the end of a
    scan segment, reduced here and counted again by brute force."""
    rec = json.loads((Path(__file__).parent / "data" /
                      "trace_v5e.json").read_text())
    tr = devtrace.from_planes(rec["planes"])
    lo, hi = tr.window
    busy = np.zeros(hi - lo, bool)
    for _, s, d in tr.ops["/device:TPU:0"]:
        busy[max(s, lo) - lo:min(s + d, hi) - lo] = True
    assert devtrace.busy_s(tr) == pytest.approx(busy.sum() / 1e9)
    assert 0 < busy.sum() < busy.size
    idle = sum(v for _, v in devtrace.idle_gaps(tr, n=100))
    assert idle == pytest.approx((busy.size - busy.sum()) / 1e9)
    kernel = [d for n, s, d in tr.ops["/device:TPU:0"]
              if n.startswith("dics_update.") and lo <= s < hi]
    match = __import__("roofline").kernel_match("dics_update")
    assert kernel and devtrace.op_ns(tr, match) == sum(kernel)
    assert all("=" not in n and not n.startswith("%")
               for n, _, _ in tr.ops["/device:TPU:0"])


def test_drain_step_share():
    run = load_run(ROOT)
    read = run.load(BENCH / "metrics" / "drain_step_pct.py").read
    ctx = SimpleNamespace(run=SimpleNamespace(live_steps=33, fresh_steps=32))
    assert read(ctx) == pytest.approx(100 / 33)
    assert read(SimpleNamespace(run=SimpleNamespace(live_steps=0,
                                                    fresh_steps=0))) is None


def test_kernel_work_by_hand():
    run = load_run(ROOT)
    fu = run.load(BENCH / "work" / "factor_update.py")
    assert fu.work(SimpleNamespace(doc={"hyper": {"k": 10}}, processed=3)) == {
        "flops": 3 * 121, "bytes": 3 * 160.125}

    du = run.load(BENCH / "work" / "dics_update.py")
    doc = {"n_i": 1, "w": 0, "n_users": 8, "n_items": 1024,
           "capacity": "collision-free"}
    # One worker; user 0 rates items 1, 2, 3, then 2 again; user 1 rates 1.
    calls = [(np.array([0, 0]), np.array([1, 2])),
             (np.array([0, 1, 0]), np.array([3, 1, 2]))]
    w = du.work(SimpleNamespace(doc=doc, calls=calls, first_window_call=1))
    # Window events: h = 2 (user 0 before item 3), 0 (user 1), 3.
    hs = [2, 0, 3]
    assert w["flops"] == sum(2 * h + 1 for h in hs)
    assert w["bytes"] == pytest.approx(sum(min(4 * h, 1024 / 8) + 16 * h
                                           + 8.125 for h in hs))
    # A warm start's history counts towards h, and is not summed itself.
    w = du.work(SimpleNamespace(doc=doc, calls=calls[1:], first_window_call=0,
                                history=calls[0]))
    assert w["flops"] == sum(2 * h + 1 for h in hs)


def test_window_rate_counts_a_stall(tiny, monkeypatch):
    run, root = tiny
    import deploy

    class Session:
        def __init__(self, stall):
            self.calls, self.stall, self.states = 0, stall, np.zeros(())
            self.metrics = SimpleNamespace(snapshot=lambda: {"calls": self.calls})

        def ingest(self, users, items):
            self.calls += 1
            time.sleep(0.005 + (self.stall if self.calls == 3 else 0.0))
            return SimpleNamespace(
                events_processed=users.size, dropped=0, load_history=[1],
                forgets=0,
                recall=SimpleNamespace(bits=lambda: np.zeros(0)))

    def rate(stall):
        monkeypatch.setattr(deploy, "session",
                            lambda doc, pub: Session(stall))
        cell = run.Cell(root, "tiny-disgd.ingest")
        h = run.Run(cell, 5, 1.0, False)
        run.load(root / "bench" / "kinds" / "backlog.py").run(h)
        return h.e2e["events_per_s"], h.window_s

    fast, _ = rate(0.0)
    slow, span = rate(0.6)
    # The stall lies inside the window, and the window runs to the end of
    # the call in flight at its deadline.
    assert slow < 0.7 * fast
    assert span >= 1.0


@pytest.mark.parametrize("config", ["ml25m-disgd", "ml25m-dics"])
def test_accepted_configurations_build_the_same_program(config):
    """The accepted configurations state no forgetting and collision-free
    tables, and build the ``StreamConfig`` they built before either
    could be stated."""
    import cellspec
    import deploy
    from repro.core.algorithm import get_algorithm
    from repro.core.pipeline import StreamConfig
    from repro.core.routing import GridSpec

    doc = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    assert cellspec.forgetting(doc) is None
    u_cap, i_cap = cellspec.capacities(doc)
    hyper = get_algorithm(doc["algorithm"]).default_hyper()._replace(
        **doc["hyper"], u_cap=u_cap, i_cap=i_cap)
    want = StreamConfig(algorithm=doc["algorithm"],
                        grid=GridSpec(doc["n_i"], doc["w"]),
                        micro_batch=doc["micro_batch"],
                        capacity_factor=doc["capacity_factor"], hyper=hyper,
                        backend=doc["backend"], seed=doc["model_seed"])
    assert deploy.stream_config(doc) == want


@pytest.mark.parametrize("setting", [
    {"forgetting": {"policy": "gradual", "trigger_every": 512}},
    {"drift": {"mode": "adaptive"}},
    {"storage": {"co": "uint16"}},
    {"warm_periods": 2, "forgetting": {"policy": "lfu", "trigger_every": 512,
                                       "lfu_min_freq": 2}},
    {"forgetting": {"policy": "lru", "trigger_every": 8, "lru_max_age": 9}},
    {"forgetting": {"policy": "lru", "trigger_every": 512}},
    {"capacity": {"u_cap": 100, "i_cap": 128}},
    {"capacity": {"u_cap": 128, "i_cap": 512}},
    {"capacity": "unbounded"},
], ids=["gradual", "drift", "storage", "forgetting-warm", "cadence-short",
        "threshold-missing", "capacity-unaligned", "capacity-too-large",
        "capacity-rule"])
def test_a_setting_no_reference_replays_is_refused(tmp_path, setting):
    """A configuration that states what no reference replays exits with
    code 2 before a run starts."""
    root = checkout(tmp_path, {"tiny-new": "tiny-disgd.json"},
                    [{"name": "tiny-new.ingest", "config": "tiny-new",
                      "traffic": "tiny-backlog", "chips": 1, "why": "test"}])
    path = root / "bench" / "configs" / "tiny-new.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), **setting}))
    run = load_run(root)
    with pytest.raises(SystemExit) as e:
        run.Cell(root, "tiny-new.ingest")
    assert e.value.code == 2


@pytest.mark.parametrize("config", ["tiny-disgd.json", "tiny-disgd-lru.json"])
def test_a_cell_and_a_metric_from_files_alone(tmp_path, config):
    metric = ('"""Events folded in per live step (test metric)."""\n\n'
              "def read(ctx):\n"
              "    return ctx.processed / ctx.live_steps\n")
    root = checkout(
        tmp_path, {"tiny-new": config},
        [{"name": "tiny-new.ingest", "config": "tiny-new",
          "traffic": "tiny-backlog", "chips": 1, "why": "test"}],
        extra={"bench/metrics/events_per_step.py": metric})
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["per_layer"].append({
        "name": "events_per_step", "unit": "events", "better": "higher",
        "source": "program_counter", "layer": "engine scan program",
        "moves": "events_per_s", "workloads": ["tiny-new.ingest"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    run = load_run(root)
    cell = run.Cell(root, "tiny-new.ingest")
    assert [m["name"] for m in cell.per_layer][-1] == "events_per_step"
    res = run.run_cell(cell, 2 ** 31 + 3, 1.0, trace=True)
    assert res["correct"] is True
    v = res["metrics"]["events_per_step"]["value"]
    assert 0 < v <= 64 and math.isfinite(v)
    assert list(res)[-1] == "check"


def test_a_kind_with_its_own_check_from_files_alone(tmp_path):
    """A traffic kind that brings its own comparison, and a metric read
    from the session's counters, added as files."""
    kind = ('"""Backlog with a comparison of its own (test kind)."""\n\n'
            "import backlog\n\n"
            "run = backlog.run\n\n\n"
            "def check(h, states, control):\n"
            "    n = sum(u.size for u, _ in h.calls)\n"
            "    return n > 0, {'events_seen': {'value': n, 'min': 1}}\n")
    metric = ('"""Ingest spans in the window, from the counters (test)."""\n\n'
              "def _count(snap):\n"
              "    for s in snap.get('span_seconds', {}).get('series', []):\n"
              "        if s['labels'].get('stage') == 'ingest':\n"
              "            return s['count']\n"
              "    return 0\n\n\n"
              "def read(ctx):\n"
              "    c = ctx.counters\n"
              "    return _count(c['end']) - _count(c['start'])\n")
    root = checkout(
        tmp_path, {"tiny-new": "tiny-disgd.json"},
        [{"name": "tiny-new.own", "config": "tiny-new",
          "traffic": "tiny-own", "chips": 1, "why": "test"}],
        extra={"bench/kinds/own.py": kind,
               "bench/metrics/ingest_spans.py": metric,
               "bench/traffic/tiny-own.json": json.dumps(
                   {"kind": "own", "chunk_events": 512, "ids": "relabeled",
                    "publish": {"every": 4, "mode": "async"}})})
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["per_layer"].append({
        "name": "ingest_spans", "unit": "calls", "better": "lower",
        "source": "program_span", "layer": "session facade",
        "moves": "events_per_s", "workloads": ["tiny-new.own"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    run = load_run(root)
    res = run.run_cell(run.Cell(root, "tiny-new.own"), 2 ** 31 + 5, 1.0,
                       trace=True)
    assert res["correct"] is True
    assert list(res["check"]) == ["events_seen"]
    assert res["metrics"]["ingest_spans"]["value"] >= 1


def test_a_traced_run_reads_the_traced_calls(tiny):
    """The profiler records the last ``TRACE_SECONDS`` of the window, from
    a call boundary; the readers count those calls only, the earlier
    ones being history to them."""
    run, root = tiny
    run.TRACE_SECONDS = 0.2
    metric = ('"""Traced events per traced live step (test)."""\n\n'
              "def read(ctx):\n"
              "    assert len(ctx.calls) - ctx.first_window_call == "
              "len(ctx.run.traced_calls) > 0\n"
              "    assert ctx.run.first_traced > 0\n"
              "    return ctx.processed / ctx.live_steps\n")
    (root / "bench" / "metrics" / "traced_per_step.py").write_text(metric)
    cell = run.Cell(root, "tiny-dics.ingest")
    cell.per_layer = [{"name": "traced_per_step", "unit": "events"}]
    res = run.run_cell(cell, 2 ** 31 + 9, 1.0, trace=True)
    assert res["correct"] is True
    assert 0.2 <= res["device"]["window_s"] < 0.9
    assert 0 < res["metrics"]["traced_per_step"]["value"] <= 64
