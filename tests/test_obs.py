"""Observability layer (ISSUE 7): metrics registry, device telemetry,
spans, and the rebuilt serve-layer instrumentation.

Four contracts under test:

  * the registry — histogram buckets are fixed and log-spaced so
    snapshots merge exactly; percentiles are exact while the raw-sample
    cap holds; all instruments survive a concurrent-increment stress
    with exact final counts; Prometheus/JSON exports are well-formed;
  * device telemetry — the in-scan vector folds bit-identically through
    the host reference loop and the scanned engine (events, drops,
    requeues, forgetting evictions, recall hits/evals, bucket HWM), and
    ``PublishEvent.as_ints`` syncs the device scalars of async runs;
  * the serve layer on the registry — ``stats_snapshot()`` replaces the
    ad-hoc dicts (the one-release ``.stats`` shims are now gone —
    pinned as AttributeError), and
    ``ServiceReport.summary()`` computes its percentiles from registry
    histograms, matching the former inline ``np.percentile`` math;
  * spans — nest into "/"-joined stage paths and observe wall time into
    ``span_seconds``.
"""

import dataclasses
import threading

import numpy as np
import pytest

import repro
from repro.core.forgetting import ForgettingConfig
from repro.core.pipeline import StreamConfig, run_stream
from repro.core.routing import GridSpec
from repro.obs import (HOST_CARRY_CAP, MetricsRegistry, TelemetryFolder,
                       current_span, default_buckets, merge_histograms,
                       span, telemetry_ints)

G2 = GridSpec(2)


def _stream(n=1200, seed=0):
    from repro.data.stream import MOVIELENS_25M, scaled, synth_stream

    users, items, _ = synth_stream(scaled(MOVIELENS_25M, 0.002), seed=seed)
    return users[:n], items[:n]


def _cfg(algorithm="disgd", grid=G2, u_cap=128, i_cap=32, **over):
    hyper = repro.get_algorithm(algorithm).default_hyper()._replace(
        u_cap=u_cap, i_cap=i_cap)
    return StreamConfig(algorithm=algorithm, grid=grid, micro_batch=256,
                        hyper=hyper, **over)


# ---------------------------------------------------------------------------
# Registry: histograms, merging, thread safety, exports
# ---------------------------------------------------------------------------


def test_histogram_buckets_fixed_and_counts_exact():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "x")
    bounds = default_buckets()
    # Log-spaced: constant ratio between consecutive bounds.
    ratios = np.diff(np.log10(np.asarray(bounds)))
    np.testing.assert_allclose(ratios, ratios[0], rtol=1e-9)
    # One observation per bucket midpoint lands exactly one count there.
    mids = [bounds[0] / 2] + [
        (bounds[i] + bounds[i + 1]) / 2 for i in range(len(bounds) - 1)]
    for m in mids:
        h.observe(m)
    snap = h.snapshot()
    assert list(snap.counts[:len(mids)]) == [1] * len(mids)
    assert snap.count == len(mids)


def test_histogram_percentiles_exact_until_sample_cap():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "x", keep_samples=100)
    rng = np.random.default_rng(3)
    xs = rng.lognormal(-5, 1, 100)
    for x in xs:
        h.observe(float(x))
    snap = h.snapshot()
    assert snap.exact
    for q in (0, 25, 50, 95, 99, 100):
        assert np.isclose(snap.percentile(q), np.percentile(xs, q),
                          rtol=1e-12)
    # One past the cap: degrades (flagged) to bucket interpolation.
    h.observe(float(xs[0]))
    over = h.snapshot()
    assert not over.exact
    assert over.count == 101


def test_histogram_merge_is_exact():
    reg = MetricsRegistry()
    a = reg.histogram("a_seconds", "x")
    b = reg.histogram("b_seconds", "x")
    both = reg.histogram("both_seconds", "x")
    rng = np.random.default_rng(7)
    xs, ys = rng.lognormal(-5, 1, 200), rng.lognormal(-3, 1, 300)
    for x in xs:
        a.observe(float(x))
        both.observe(float(x))
    for y in ys:
        b.observe(float(y))
        both.observe(float(y))
    merged = merge_histograms(a.snapshot(), b.snapshot())
    ref = both.snapshot()
    assert list(merged.counts) == list(ref.counts)
    assert merged.count == ref.count == 500
    assert np.isclose(merged.sum, ref.sum, rtol=1e-12)
    for q in (50, 90, 99):
        assert np.isclose(merged.percentile(q), ref.percentile(q),
                          rtol=1e-12)


def test_registry_thread_safety_exact_counts():
    reg = MetricsRegistry()
    c = reg.counter("hits_total", "x")
    g = reg.gauge("hwm", "x", labels=("k",))
    h = reg.histogram("lat_seconds", "x", labels=("stage",))
    n_threads, per_thread = 8, 2000

    def work(tid):
        child = h.labels(stage=f"s{tid % 2}")
        for i in range(per_thread):
            c.inc()
            g.labels(k=str(tid % 4)).set_max(i)
            child.observe(1e-4)

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert int(c.value) == n_threads * per_thread
    total = sum(child.snapshot().count for _, child in h.series())
    assert total == n_threads * per_thread
    for _, child in g.series():
        assert int(child.value) == per_thread - 1


def test_registry_get_or_create_idempotent_and_kind_checked():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x")
    assert reg.counter("x_total", "x") is not None
    a.inc(3)
    assert int(reg.counter("x_total", "x").value) == 3
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x")       # same name, different kind


def test_prometheus_and_json_exports(tmp_path):
    reg = MetricsRegistry()
    reg.counter("events_total", "Events", labels=("mode",)).labels(
        mode="scan").inc(7)
    reg.gauge("front_version", "v").set(3)
    reg.histogram("lat_seconds", "L").observe(0.5)
    text = reg.to_prometheus()
    assert '# TYPE events_total counter' in text
    assert 'events_total{mode="scan"} 7' in text
    assert "front_version 3" in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_count 1" in text

    import json
    out = tmp_path / "m.json"
    reg.write_json(str(out))
    payload = json.loads(out.read_text())
    assert payload["schema_version"] == 1
    assert "events_total" in payload["metrics"]


# ---------------------------------------------------------------------------
# Device telemetry: host/scan parity, as_ints, folder semantics
# ---------------------------------------------------------------------------


def test_telemetry_host_scan_bit_parity_plain():
    users, items = _stream()
    cfg = _cfg()
    host = run_stream(users, items, cfg)
    scan = run_stream(users, items, dataclasses.replace(cfg, backend="scan"))
    assert host.dropped == scan.dropped == 0
    assert telemetry_ints(host.telemetry) == telemetry_ints(scan.telemetry)
    tel = telemetry_ints(host.telemetry)
    assert tel["events"] == users.size
    assert tel["evals"] == users.size
    assert len(tel["bucket_hwm"]) == cfg.grid.n_c


def test_telemetry_host_scan_bit_parity_with_forgetting_and_requeue():
    users, items = _stream(n=2400)
    cfg = _cfg(forgetting=ForgettingConfig(
        policy="lru", trigger_every=300, lru_max_age=200),
        capacity_factor=1.2)
    host = run_stream(users, items, cfg)
    scan = run_stream(users, items, dataclasses.replace(cfg, backend="scan"))
    assert host.dropped == scan.dropped == 0   # parity's precondition
    th, ts = telemetry_ints(host.telemetry), telemetry_ints(scan.telemetry)
    assert th == ts
    assert th["evictions"] > 0                 # forgetting actually fired
    assert host.forgets == scan.forgets > 0


def test_precision_head_parity_and_surfacing():
    """The precision@N head (hits / effective list length) rides the
    same scan-carry vector as the recall head: bit-parity host vs scan,
    surfaced on ``StreamResult.precision_at_n``, on publish boundaries,
    and as ``stream_list_len_total`` in a session's registry."""
    users, items = _stream()
    cfg = _cfg()
    host = run_stream(users, items, cfg)
    scan = run_stream(users, items, dataclasses.replace(cfg, backend="scan"))
    th, ts = telemetry_ints(host.telemetry), telemetry_ints(scan.telemetry)
    assert th["list_len"] == ts["list_len"] > 0
    # precision@N is well-formed: hits bound by both denominators.
    assert th["hits"] <= th["list_len"]
    assert 0.0 < scan.precision_at_n < 1.0
    assert scan.precision_at_n == th["hits"] / th["list_len"]
    assert host.precision_at_n == scan.precision_at_n
    # The head rides publish boundaries (the ensemble weigher's read).
    boundary = []
    run_stream(users, items, dataclasses.replace(cfg, backend="scan"),
               publish_every=2, on_publish=lambda ev: boundary.append(ev))
    assert telemetry_ints(boundary[-1].telemetry)["list_len"] > 0
    # Telemetry off: the property degrades to NaN, not a crash.
    off = run_stream(users, items,
                     dataclasses.replace(cfg, telemetry=False))
    assert np.isnan(off.precision_at_n)
    # Session fold: the denominator lands as a registry counter.
    s = repro.StreamSession(_cfg(backend="scan"))
    s.ingest(users, items)
    assert (s.metrics.counter("stream_list_len_total").value
            == th["list_len"])


def test_telemetry_off_yields_none_and_identical_training():
    users, items = _stream(n=600)
    cfg = _cfg(backend="scan")
    on = run_stream(users, items, cfg)
    off = run_stream(users, items,
                     dataclasses.replace(cfg, telemetry=False))
    assert on.telemetry is not None and off.telemetry is None
    import jax
    for a, b in zip(jax.tree.leaves(on.final_states),
                    jax.tree.leaves(off.final_states)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_publish_event_as_ints_under_async_publish():
    users, items = _stream(n=1024)
    cfg = _cfg(backend="scan")
    events = []
    run_stream(users, items, cfg, publish_every=2, publish_sync=False,
               on_publish=lambda ev: events.append(ev))
    assert events
    last = events[-1].as_ints()
    assert isinstance(last.events_processed, int)
    assert last.events_processed > 0
    tel = telemetry_ints(last.telemetry)
    assert isinstance(tel["events"], int) and tel["events"] > 0


def test_telemetry_folder_deltas_and_coalescing():
    reg = MetricsRegistry()
    folder = TelemetryFolder(reg)
    from repro.obs import telemetry_init, telemetry_update

    tel = telemetry_init(2)
    for k in (10, 20, 30):
        tel = telemetry_update(tel, kept=k, overflow=0,
                               carry_cap=HOST_CARRY_CAP, evicted=0,
                               hits=1, evals=k, load=[k, k // 2])
    # Coalesced fold: only the final cumulative vector arrives.
    folder.fold(tel)
    assert int(reg.counter("stream_events_total", "").value) == 60
    # Re-folding the same vector is a no-op (delta 0).
    folder.fold(tel)
    assert int(reg.counter("stream_events_total", "").value) == 60
    # A new segment rebases, then adds from zero again.
    folder.rebase()
    tel2 = telemetry_update(telemetry_init(2), kept=5, overflow=0,
                            carry_cap=HOST_CARRY_CAP, evicted=0,
                            hits=0, evals=5, load=[1, 1])
    folder.fold(tel2)
    assert int(reg.counter("stream_events_total", "").value) == 65


def test_session_folds_telemetry_into_registry():
    users, items = _stream(n=1024)
    s = repro.StreamSession(_cfg(backend="scan"),
                            publish=repro.PublishPolicy(every=2,
                                                        mode="async"))
    res = s.ingest(users, items)
    assert int(s.metrics.counter("stream_events_total", "").value) \
        == telemetry_ints(res.telemetry)["events"] == users.size
    # Second segment keeps accumulating (rebase, not reset).
    s.ingest(users, items)
    assert int(s.metrics.counter("stream_events_total", "").value) \
        == 2 * users.size


# ---------------------------------------------------------------------------
# Serve layer on the registry: snapshots, shims, report percentiles
# ---------------------------------------------------------------------------


def test_store_and_frontend_stats_snapshot_and_shim_removed():
    users, items = _stream(n=512)
    s = repro.StreamSession(_cfg(backend="scan"))
    s.ingest(users, items)
    s.recommend(users[:8])
    st = s.store.stats_snapshot()
    assert st["sync_rotations"] >= 1
    assert st["rotations"] == st["sync_rotations"] + st["async_rotations"]
    fe = s.frontend.stats_snapshot()
    assert fe["queries"] == 8
    # The one-release deprecation window for the `.stats` dict shims is
    # over: the attribute is gone, not warning. Pin the removal so the
    # shim can't silently come back.
    with pytest.raises(AttributeError):
        s.store.stats
    with pytest.raises(AttributeError):
        s.frontend.stats


def test_frontend_latency_and_staleness_histograms_populate():
    users, items = _stream(n=512)
    s = repro.StreamSession(_cfg(backend="scan"))
    s.ingest(users, items)
    for i in range(3):
        s.recommend(users[8 * i:8 * (i + 1)])
    lat = s.metrics.histogram("serve_latency_seconds", "").snapshot()
    stale = s.metrics.histogram("serve_staleness_events", "").snapshot()
    assert lat.count == 3 and stale.count == 3
    assert lat.sum > 0


def test_service_report_percentiles_from_registry_match_inline():
    import math

    from repro.serve.loadgen import LoadConfig
    from repro.serve.service import ServiceConfig, run_service

    users, items = _stream(n=2048)
    s = repro.StreamSession(
        _cfg(backend="scan"),
        publish=repro.PublishPolicy(every=2, mode="async"))
    report = run_service(
        s, users, items, LoadConfig(query_batch=8, n_users=200),
        ServiceConfig(mode="interleaved", query_batches=10))
    assert report.metrics is not None
    got = report.summary()
    ref = dataclasses.replace(report, metrics=None).summary()
    for k in ("p50_ms", "p99_ms", "max_ms", "staleness_mean"):
        assert math.isclose(got[k], ref[k], rel_tol=1e-9, abs_tol=1e-9), k
    for k in ("staleness_p95", "staleness_max"):
        assert got[k] == ref[k]


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


def test_span_nesting_paths_and_histogram():
    reg = MetricsRegistry()
    with span("ingest", reg):
        assert current_span() == "ingest"
        with span("flush", reg):
            assert current_span() == "ingest/flush"
    assert current_span() == ""
    fam = reg.get("span_seconds")
    stages = {labels["stage"] for labels, _ in fam.series()}
    assert stages == {"ingest", "ingest/flush"}


def test_session_verbs_record_spans():
    users, items = _stream(n=512)
    s = repro.StreamSession(_cfg(backend="scan"))
    s.ingest(users, items)
    s.recommend(users[:4])
    s.rescale(GridSpec.rect(1, 4))
    stages = {labels["stage"]
              for labels, _ in s.metrics.get("span_seconds").series()}
    assert {"ingest", "publish", "serve", "regrid"} <= stages


def _stages(reg):
    fam = reg.get("span_seconds")
    return {labels["stage"] for labels, _ in fam.series()} if fam else set()


def test_nested_span_records_into_its_parents_registry():
    reg, own = MetricsRegistry(), MetricsRegistry()
    with span("ingest", reg):
        with span("scan") as path:
            assert path == "ingest/scan"
            with span("boundary"):
                pass
        with span("readback", own):
            with span("fold"):
                pass
    with span("alone"):
        pass
    assert _stages(reg) == {"ingest", "ingest/scan", "ingest/scan/boundary"}
    # A span given a registry records there, and so do its children.
    assert _stages(own) == {"ingest/readback", "ingest/readback/fold"}


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_session_ingest_records_engine_and_publish_spans(mode, monkeypatch):
    import collections

    from repro.core import engine
    from repro.obs import trace

    monkeypatch.setattr(trace, "_programs", collections.deque(maxlen=16))
    engine.compiled_scan.cache_clear()
    users, items = _stream(n=1200)
    s = repro.StreamSession(_cfg(backend="scan"),
                            publish=repro.PublishPolicy(every=2, mode=mode))
    s.ingest(users, items)
    stages = _stages(s.metrics)
    assert {"ingest", "ingest/prepare", "ingest/scan",
            "ingest/scan/boundary", "ingest/readback", "publish",
            "publish/flush", "publish/rotate", "publish/fold"} <= stages
    # The publisher thread's rotations land in the store's registry.
    assert ("publish_async" in stages) == (mode == "async")
    # The scan program the call ran is registered and mapped.
    assert "route" in trace.op_layers()["jit_engine_scan"].values()


def test_layer_accepts_only_the_engines_layers():
    from repro.obs import trace

    assert trace.LAYERS == ("route", "score", "update", "column_clear",
                            "telemetry", "forget")
    with pytest.raises(ValueError, match="unknown layer"):
        trace.layer("scoring")


_HLO = """HloModule jit_fake_scan, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %negate.0 = f32[8]{0} negate(%param_0), metadata={op_name="jit(engine_scan)/update/neg"}
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %sort.2 = f32[8]{0} sort(%p), dimensions={0}, metadata={op_name="jit(engine_scan)/while/body/vmap(score)/top_k" source_file="x.py" source_line=3}
  %copy.1 = f32[8]{0} copy(%sort.2)
  %copy.3 = f32[8]{0} copy(%p)
  %while.11 = f32[8]{0} while(%copy.3), condition=%c, body=%b, metadata={op_name="jit(engine_scan)/vmap(update)/column_clear/while"}
  %fusion.4 = f32[8]{0} fusion(%copy.1, %while.11), kind=kLoop, calls=%fused_computation, metadata={op_name="update/reduce_sum"}
  %add.5 = f32[8]{0} add(%fusion.4, %p), metadata={op_name="jit(engine_scan)/jit(update)/add"}
  ROOT %add.6 = f32[8]{0} add(%add.5, %add.5)
}
"""


def test_op_layers_maps_instructions_to_their_innermost_layer(monkeypatch):
    import collections
    from types import SimpleNamespace

    from repro.obs import trace

    monkeypatch.setattr(trace, "_programs", collections.deque(maxlen=16))
    trace.register_program(SimpleNamespace(as_text=lambda: _HLO))
    got = trace.op_layers()["jit_fake_scan"]
    assert got["sort.2"] == "score"              # through vmap(...)
    assert got["while.11"] == "column_clear"     # innermost of two
    assert got["fusion.4"] == "update"           # a shortened path
    assert got["negate.0"] == "update"
    assert got["add.5"] == ""                    # jit(update) is a function
    # No metadata: the layer its users share, else none.
    assert got["copy.3"] == "column_clear" and got["copy.1"] == "update"
    assert got["p"] == "" and got["add.6"] == ""
    # Two programs of one module that disagree leave the op unscoped.
    trace.register_program(SimpleNamespace(
        as_text=lambda: _HLO.replace("vmap(score)", "route")))
    got = trace.op_layers()["jit_fake_scan"]
    assert got["sort.2"] == "" and got["while.11"] == "column_clear"
