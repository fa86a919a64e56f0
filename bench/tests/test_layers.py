"""The per-layer split of a traced window: the scan program's op time by
layer (``step_ms.*``, ``step_unscoped_pct``) and the device's idle time
by program span (``idle_ms.*``), on synthetic traces and a fake layer
map of the program."""

import json
from types import SimpleNamespace

import pytest

import devtrace
import layers
from conftest import BENCH, ROOT, load_run

#: The program's map for the module ``jit_engine_scan``, as
#: ``repro.obs.trace.op_layers()`` gives it.
OP_LAYERS = {"sort.2": "score", "while.11": "column_clear",
             "dynamic_slice.23": "column_clear", "fusion.15": "update",
             "fusion.3": "route", "reduce.7": "telemetry", "copy.9": "",
             "copy.84": "score"}


def _trace(scan_module="jit_engine_scan(77)"):
    host = [("bench.window", 0, 10_000),
            ("ingest", 100, 4_850),
            ("ingest/prepare", 100, 400),
            ("ingest/scan", 500, 3_500),
            ("ingest/scan/boundary", 2_000, 300),
            ("ingest/readback", 4_000, 900),
            ("PjitFunction(add)", 4_100, 200),
            ("publish", 5_100, 1_000),
            ("publish/rotate", 5_200, 500)]
    ops = [("%sort.2 = (f32[8]) sort(...)", 600, 1_000),
           ("%while.11 = (s32[]) while(...)", 1_700, 500),
           ("%dynamic_slice.23 = pred[1] dynamic-slice(...)", 1_800, 100),
           ("%fusion.15 = f32[8] fusion(...)", 2_300, 300),
           ("%copy.9 = f32[8] copy(...)", 2_700, 200),
           ("%fusion.3 = s32[8] fusion(...)", 2_950, 20),
           ("%reduce.7 = s32[] reduce(...)", 2_970, 30),
           # Another module's op, named like one of the scan program's.
           ("%copy.84 = f32[8] copy(...)", 5_300, 100)]
    return devtrace.from_planes([
        ("/host:CPU", [("main", host)]),
        ("/device:TPU:0", [
            ("XLA Modules", [(scan_module, 600, 2_400),
                             ("jit_add(5)", 5_300, 100)]),
            ("XLA Ops", ops)]),
    ])


def _ctx(tr, live_steps=2):
    return SimpleNamespace(trace=tr, live_steps=live_steps)


@pytest.fixture
def program_map(monkeypatch):
    """The program's layer map, as the readers find it after a run."""
    from repro.obs import trace

    monkeypatch.setattr(trace, "op_layers",
                        lambda: {"jit_engine_scan": dict(OP_LAYERS),
                                 "jit_add": {"copy.84": ""}})


def _read(name, ctx):
    run = load_run(ROOT)
    return run.load(BENCH / "metrics" / f"{name}.py").read(ctx)


def test_step_ms_by_layer_adds_up_to_the_scan_op_time(program_map, capsys):
    ctx = _ctx(_trace())
    # Self time: the while holds the dynamic slice (400 + 100 ns).
    want_ns = {"route": 20, "score": 1_000, "update": 300,
               "column_clear": 500, "telemetry": 30}
    got = {k: _read(f"step_ms.{k}", ctx) for k in layers.STEP_LAYERS}
    assert got == pytest.approx({k: ns / 1e6 / 2
                                 for k, ns in want_ns.items()})
    split = layers.scan_split(ctx)
    assert split["ns"][""] == 200
    assert split["total_ns"] == sum(want_ns.values()) + 200 == 2_050
    assert _read("step_unscoped_pct", ctx) == pytest.approx(
        100 * 200 / 2_050)
    # The run prints the split and its sum once, on stderr.
    lines = [json.loads(x) for x in capsys.readouterr().err.splitlines()
             if "scan_layers_ms_per_step" in x]
    assert len(lines) == 1
    assert lines[0]["layers_plus_unscoped_ms_per_step"] == pytest.approx(
        lines[0]["scan_op_self_ms_per_step"]) == pytest.approx(2_050 / 2e6)


def test_other_modules_ops_are_not_counted(program_map):
    # copy.84 maps to score in the scan program, but runs in a jit_add.
    ctx = _ctx(_trace())
    assert layers.scan_split(ctx)["ns"]["score"] == 1_000
    # With no scan program in the trace (as on a program that does not
    # name it), the readers read nothing.
    ctx = _ctx(_trace(scan_module="jit__lambda(77)"))
    assert _read("step_ms.score", ctx) is None
    assert _read("step_unscoped_pct", ctx) is None


def test_a_program_without_a_layer_map_reads_nothing(monkeypatch):
    from repro.obs import trace

    monkeypatch.delattr(trace, "op_layers")
    assert _read("step_ms.update", _ctx(_trace())) is None


def test_innermost_segments():
    spans = [("ingest", 0, 100), ("ingest/scan", 10, 50),
             ("ingest/scan/boundary", 20, 10), ("ingest/readback", 70, 20),
             ("publish", 120, 10)]
    assert layers.innermost_segments(spans) == [
        (0, 10, "ingest"), (10, 20, "ingest/scan"),
        (20, 30, "ingest/scan/boundary"), (30, 60, "ingest/scan"),
        (60, 70, "ingest"), (70, 90, "ingest/readback"), (90, 100, "ingest"),
        (120, 130, "publish")]


def test_idle_time_goes_to_the_innermost_program_span(capsys):
    ctx = _ctx(_trace())
    # Gaps between the device's ops, cut by the program's spans; the
    # host event inside the readback is not the program's, the boundary
    # counts to the scan and publish/rotate to publish.
    want = {"prepare": 400, "scan": 100 + 100 + 100 + 100 + 50 + 1_000,
            "readback": 900, "publish": 100 + 100 + 300 + 400}
    got = {k: _read(f"idle_ms.{k}", ctx) for k in layers.IDLE_SPANS}
    assert got == pytest.approx({k: ns / 1e6 for k, ns in want.items()})
    split = layers.idle_split(ctx)
    assert split["calls"] == 1
    assert split["idle_ns"] == 10_000 - 2_050 - 100 == 7_850
    # Inside the ingest call, after its readback.
    assert split["ns"]["(other)"] == 50
    err = capsys.readouterr().err
    assert err.count("idle_ms_per_call_by_span") == 1


def test_idle_readers_need_the_programs_spans():
    tr = _trace()
    # A program with only the session's top spans: idle under ``publish``
    # is read, the ingest call's parts are not.
    tr = tr._replace(spans=[x for x in tr.spans
                            if x[0] in ("ingest", "publish")])
    ctx = _ctx(tr)
    assert _read("idle_ms.prepare", ctx) is None
    assert _read("idle_ms.publish", ctx) == pytest.approx(
        (100 + 100 + 300 + 400) / 1e6)
    assert _read("idle_ms.scan", _ctx(tr._replace(ops={}))) is None
