"""The output check: the plain references agree with the program on the
CPU at a tiny size, with forgetting and colliding tables too, and the
check refuses the control (the reference in bfloat16 in the program's
place) and a broken timed path. Strict xfails pin the program's faults
that the references show."""

import json

import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import cellspec
import check
import srgrid
import streamgen
from conftest import BENCH

SEED = 2 ** 31 + 7          # larger than 32 signed bits hold


def real_limits(algorithm: str) -> dict:
    return json.loads((BENCH / "configs" / f"ml25m-{algorithm}.json")
                      .read_text())["check"]["limits"]


#: A padding slot of a DICS bucket (id -1) maps to the last table slot
#: and clears the tenant there as a collision would, keeping its id.
DICS_PADDING_FAULT = pytest.mark.xfail(
    strict=True, raises=AssertionError,
    reason="program fault: dics_worker_step and ops.dics_update run the "
           "collision eviction for padding events (id -1, slot cap - 1), "
           "clearing a live tenant's rated row or co row, column and count")


def reference_log(capsys) -> dict:
    """The reference's record on stderr (``run.output_check``)."""
    lines = [json.loads(ln) for ln in capsys.readouterr().err.splitlines()
             if ln.startswith('{"reference_s"')]
    assert len(lines) == 1
    return lines[0]


@pytest.mark.parametrize("config", [
    "disgd", "dics", "disgd-lru", "dics-lfu",
    pytest.param("dics-lfu-collide", marks=DICS_PADDING_FAULT)])
def test_reference_agrees_with_the_program(tiny, capsys, config):
    """The tiny cells come out correct. The forgetting ones fire several
    triggers in each ingest call of 512 events, a multiple of their
    ``trigger_every``, and drop nothing, so each call ends where a
    trigger fell: there the program, which counts the cadence per call,
    agrees with the stream's cadence that the reference replays."""
    run, root = tiny
    cell = run.Cell(root, f"tiny-{config}.ingest")
    res = run.run_cell(cell, SEED, 1.0, trace=False)
    log = reference_log(capsys)
    assert res["correct"] is True, res["check"]
    c = res["check"]
    assert c["state_mismatch"]["value"] == 0
    assert c["eval_mismatch"]["value"] == 0
    assert c["hit_mismatch"]["value"] == 0
    assert c["factor_max_abs_diff"]["value"] <= 1e-6
    assert c["hits_checked"]["value"] > 0
    assert res["failed"] == 0 and res["attempted"] > 0
    forget = cellspec.forgetting(cell.doc)
    if forget is not None:
        assert 512 % forget["trigger_every"] == 0
        assert log["reference_forgets"] >= 2
        assert log["program_forgets"] == log["reference_forgets"]
        assert log["reference_evicted"]["forgetting"] > 0
    if cell.doc["capacity"] != "collision-free":
        assert log["reference_evicted"]["collision"] > 0


@pytest.mark.xfail(
    strict=True, raises=AssertionError,
    reason="program fault: engine.init_scan_carry zeroes the forgetting "
           "cadence counter (since) on every ingest call, so calls shorter "
           "than trigger_every never forget")
def test_forgetting_cadence_spans_ingest_calls(tiny, capsys):
    """Every ``trigger_every`` events of the stream, across calls: with
    calls of 512 events and a cadence of 768, the reference forgets
    after the second call, and the program has to as well."""
    run, root = tiny
    cell = run.Cell(root, "tiny-disgd-lru.ingest")
    cell.doc["forgetting"]["trigger_every"] = 768
    res = run.run_cell(cell, SEED, 1.0, trace=False)
    log = reference_log(capsys)
    if log["reference_forgets"] < 2:
        pytest.fail(f"the reference fired {log['reference_forgets']} "
                    "triggers: too few to show the cadence")
    assert log["program_forgets"] == log["reference_forgets"]
    assert res["correct"] is True, res["check"]


def tiny_doc(config: str) -> dict:
    return json.loads((BENCH / "tests" / "data" /
                       f"tiny-{config}.json").read_text())


def fold(mod, doc, users, items, dtype=np.float32):
    """The reference after ``users``/``items``, one event at a time in
    each worker's stream order."""
    g = doc["n_i"] + doc["w"]
    w = srgrid.route(users, items, doc["n_i"], g)
    pos = srgrid._positions(w)
    order = np.lexsort((w, pos))
    ref, at = mod.Worker(doc, dtype=dtype), 0
    for c in np.bincount(pos):
        idx = order[at:at + c]
        at += c
        ref.train(w[idx], users[idx], items[idx])
    return ref


def replays(config: str, dtype):
    doc = tiny_doc(config)
    g = doc["n_i"] + doc["w"]
    feed = streamgen.PeriodFeed(cellspec.profile(doc), SEED,
                                ids="relabeled", classes=(g, doc["n_i"]))
    history = (feed.take_periods(doc["warm_periods"])
               if doc.get("warm_periods") else None)
    feed.start()
    try:
        calls = [feed.take(512) for _ in range(8)]
    finally:
        feed.close()
    lay = srgrid.Layout(n_i=doc["n_i"], g=g,
                        micro_batch=doc["micro_batch"],
                        capacity=cellspec.bucket_capacity(doc),
                        carry=doc["micro_batch"], publish_every=4)
    run = __import__("run")
    mod = run.load(BENCH / "reference" / f"{doc['reference']}.py")
    ref, low = mod.Worker(doc), mod.Worker(doc, dtype=dtype)
    if history is not None:
        warm = run.load(BENCH / "warm" / f"{doc['reference']}.py").build(
            doc, *history)
        warm = {k: np.asarray(v) for k, v in warm.items()}
        ref.load(warm)
        low.load(warm)
    rows = range(0, 200, 3)
    forget = cellspec.forgetting(doc)
    every = forget["trigger_every"] if forget else None
    rep = srgrid.replay(calls, lay, ref, rows, every)
    low_rep = srgrid.replay(calls, lay, low, rows, every)
    return mod, ref, rep, low, low_rep, lay, run, doc


def test_warm_start_is_the_fold():
    """The warm start (one jitted call) is the state the reference
    reaches event by event, re-rated pairs of later periods included."""
    doc = tiny_doc("dics")
    feed = streamgen.PeriodFeed(cellspec.profile(doc), SEED,
                                ids="relabeled",
                                classes=(doc["n_i"] + doc["w"], doc["n_i"]))
    users, items = feed.take_periods(doc["warm_periods"])
    run = __import__("run")
    mod = run.load(BENCH / "reference" / "dics.py")
    want = fold(mod, doc, users, items).states()
    got = run.load(BENCH / "warm" / "dics.py").build(doc, users, items)
    assert set(got) == set(want)
    for name, w in want.items():
        g = np.asarray(got[name])
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_array_equal(g, w, err_msg=name)
    # Repeats across periods exist, and counts pass what bfloat16 holds.
    assert np.asarray(got["co"]).diagonal(axis1=1, axis2=2).max() > 0
    assert np.asarray(got["item_cnt"]).max() > 256


def test_relabeling_keeps_the_routing():
    doc = tiny_doc("dics")
    g = doc["n_i"] + doc["w"]
    prof = cellspec.profile(doc)
    a, b = (streamgen.PeriodFeed(prof, s, ids="relabeled",
                                 classes=(g, doc["n_i"])).take_periods(1)
            for s in (SEED, SEED + 1))
    assert not np.array_equal(a[0], b[0])
    np.testing.assert_array_equal(srgrid.route(*a, doc["n_i"], g),
                                  srgrid.route(*b, doc["n_i"], g))
    np.testing.assert_array_equal(a[0] % g, b[0] % g)
    np.testing.assert_array_equal(a[1] % doc["n_i"], b[1] % doc["n_i"])


@pytest.mark.parametrize("config", ["disgd", "dics", "disgd-lru",
                                    "dics-lfu"])
def test_control_is_refused(config):
    mod, ref, rep, low, low_rep, lay, run, doc = replays(config,
                                                    ml_dtypes.bfloat16)
    numbers = check.compare(low.states(), ref.states(), mod.FLOAT_LEAVES,
                            run.replay_rows(low_rep, lay.row), rep,
                            doc["check"]["tie_tolerance"])
    ok, _ = check.verdict(numbers, real_limits(doc["algorithm"]))
    assert not ok, numbers
    if cellspec.forgetting(doc) is not None:
        assert rep.forgets >= 2 and ref.evicted["forgetting"] > 0


@pytest.mark.parametrize("config", ["disgd", "dics", "disgd-lru",
                                    "dics-lfu", "dics-lfu-collide"])
def test_reference_against_itself_passes(config):
    mod, ref, rep, same, same_rep, lay, run, doc = replays(config, np.float32)
    numbers = check.compare(same.states(), ref.states(), mod.FLOAT_LEAVES,
                            run.replay_rows(same_rep, lay.row), rep,
                            doc["check"]["tie_tolerance"])
    ok, _ = check.verdict(numbers, real_limits(doc["algorithm"]))
    assert ok and numbers["state_mismatch"] == 0, numbers


def _unchanged(worker):
    def step(states, u, i):
        _, hits, evaluated = worker(states, u, i)
        return states, hits, evaluated
    return step


def _half(worker):
    def step(states, u, i):
        keep = jnp.arange(u.shape[-1]) < u.shape[-1] // 2
        return worker(states, jnp.where(keep, u, -1), jnp.where(keep, i, -1))
    return step


def _altered(worker):
    def step(states, u, i):
        out, hits, evaluated = worker(states, u, i)
        return out, hits ^ evaluated, evaluated
    return step


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered],
                         ids=["state-unchanged", "half-the-batch",
                              "answer-altered"])
@pytest.mark.parametrize("algorithm", ["disgd", "dics"])
def test_broken_timed_path_is_refused(tiny, monkeypatch, algorithm, fault):
    from repro.core import engine

    run, root = tiny
    real = engine.make_pallas_worker_fn
    monkeypatch.setattr(engine, "make_pallas_worker_fn",
                        lambda cfg: fault(real(cfg)))
    engine.compiled_scan.cache_clear()
    cell = run.Cell(root, f"tiny-{algorithm}.ingest")
    cell.doc["check"]["limits"] = real_limits(algorithm)
    try:
        res = run.run_cell(cell, SEED, 1.0, trace=False)
    finally:
        engine.compiled_scan.cache_clear()
    assert res["correct"] is False, res["check"]
