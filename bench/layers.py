"""Device time and idle time of a traced window, split by the program's layers.

Two splits, each made once per run and printed on stderr:

* :func:`scan_split`: the self time (``devtrace.self_ns``) of the device
  ops that run inside executions of the engine's scan program (``XLA
  Modules`` events named ``jit_engine_scan(...)``), summed by the layer
  the program gives each op's instruction. The program provides that
  map (``repro.obs.trace.op_layers()``, read after the window); an
  instruction it places in no layer is unscoped (``""``). An op of
  another module is not counted, even where its name is one the scan
  program also has (``copy.84`` in a ``jit_add``).
* :func:`idle_split`: the device's idle time in the window (the gaps
  between its ops), summed by the innermost program span of the
  window's thread around each part of a gap. The program's spans are
  ``ingest`` and ``publish`` and the spans below them
  (``ingest/prepare``, ``publish/rotate``, ...); the split keys a span
  by :data:`IDLE_SPANS`, which takes each span with the spans below it.

A reader returns ``None`` where the trace holds none of what it reads
(a program without the scan's name, its layer map or the spans).
"""

from __future__ import annotations

import bisect
import json
import sys
import time

import devtrace

__all__ = ["SCAN_MODULE", "STEP_LAYERS", "IDLE_SPANS", "scan_split",
           "step_ms", "unscoped_pct", "innermost_segments", "idle_split",
           "idle_ms"]

SCAN_MODULE = "jit_engine_scan"
#: The layers with a ``step_ms.<layer>`` metric (``forget`` has no cell).
STEP_LAYERS = ("route", "score", "update", "column_clear", "telemetry")
#: ``idle_ms.<key>``: the span path that key takes, with its sub-spans.
IDLE_SPANS = {"prepare": "ingest/prepare", "scan": "ingest/scan",
              "readback": "ingest/readback", "publish": "publish"}


def _log(record: dict) -> None:
    print(json.dumps(record), file=sys.stderr, flush=True)


def _is_scan(module: str) -> bool:
    return module == SCAN_MODULE or module.startswith(SCAN_MODULE + "(")


def _program_op_layers():
    """The scan program's ``{instruction: layer}``, or None where the
    program does not provide one."""
    from repro.obs import trace

    op_layers = getattr(trace, "op_layers", None)
    return None if op_layers is None else op_layers().get(SCAN_MODULE)


def scan_split(ctx):
    """``{"ns": {layer: self ns}, "total_ns", "module_ns"}`` over the
    window's scan-program executions, summed over devices; None where
    the trace holds no such execution or the program no layer map."""
    if "scan_split" in vars(ctx):
        return ctx.scan_split
    tr = ctx.trace
    out = None
    runs = {}
    if tr is not None:
        lo, hi = tr.window
        for dev, mods in tr.modules.items():
            r = sorted((s, s + d) for n, s, d in mods
                       if _is_scan(n) and lo <= s < hi)
            if r:
                runs[dev] = r
    parse_s = None
    if runs:
        t = time.perf_counter()
        op_layers = _program_op_layers()
        parse_s = time.perf_counter() - t
        if op_layers is not None:
            out = _split_ops(tr, runs, op_layers)
    ctx.scan_split = out
    if out is not None and getattr(ctx, "live_steps", 0):
        per = {k or "(unscoped)": v / 1e6 / ctx.live_steps
               for k, v in sorted(out["ns"].items())}
        _log({"scan_layers_ms_per_step": per, "op_layers_s": parse_s,
              "scan_op_self_ms_per_step": out["total_ns"] / 1e6
              / ctx.live_steps,
              "layers_plus_unscoped_ms_per_step": sum(per.values()),
              "scan_module_ms_per_step": out["module_ns"] / 1e6
              / ctx.live_steps})
    return out


def _split_ops(tr, runs: dict, op_layers: dict) -> dict:
    lo, hi = tr.window
    ns: dict = {}
    module_ns = 0
    for dev, r in runs.items():
        module_ns += sum(e - s for s, e in r)
        starts = [s for s, _ in r]
        for name, s, d in devtrace.self_ns(
                devtrace.within(tr.ops.get(dev, ()), lo, hi)):
            j = bisect.bisect_right(starts, s) - 1
            if j >= 0 and s < r[j][1]:
                where = op_layers.get(name, "")
                ns[where] = ns.get(where, 0) + d
    return {"ns": ns, "total_ns": sum(ns.values()), "module_ns": module_ns}


def step_ms(ctx, layer: str):
    """Self time of ``layer``'s ops in the scan program per live step, ms."""
    split = scan_split(ctx)
    if split is None or not ctx.live_steps:
        return None
    return split["ns"].get(layer, 0) / 1e6 / ctx.live_steps


def unscoped_pct(ctx):
    """Share of the scan program's op self time in no layer, in %."""
    split = scan_split(ctx)
    if split is None or not split["total_ns"]:
        return None
    return 100.0 * split["ns"].get("", 0) / split["total_ns"]


def _is_program_span(name: str) -> bool:
    return any(name == root or name.startswith(root + "/")
               for root in ("ingest", "publish"))


def innermost_segments(spans):
    """``[(start, end, name)]``, disjoint and in order: the innermost of
    the nested ``(name, start, dur)`` spans at each time one covers."""
    segs, stack, t = [], [], None
    for s, e, name in sorted(((s, s + d, n) for n, s, d in spans),
                             key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, outer = stack.pop()
            if end > t:
                segs.append((t, end, outer))
                t = end
        if stack:
            if s > t:
                segs.append((t, s, stack[-1][1]))
            e = min(e, stack[-1][0])
        t = s
        stack.append((e, name))
    while stack:
        end, outer = stack.pop()
        if end > t:
            segs.append((t, end, outer))
            t = end
    return segs


def _idle_key(span: str):
    for key, path in IDLE_SPANS.items():
        if span == path or span.startswith(path + "/"):
            return key
    return None


def idle_split(ctx):
    """``{"ns": {key: idle ns}, "idle_ns", "calls", "seen": keys whose
    spans the trace holds}``, averaged over devices; None where the
    trace holds no device op or no program span."""
    if "idle_split" in vars(ctx):
        return ctx.idle_split
    tr = ctx.trace
    out = None
    spans = ([x for x in tr.spans if _is_program_span(x[0])]
             if tr is not None else [])
    if tr is not None and tr.ops and spans:
        out = _split_idle(tr, spans)
    ctx.idle_split = out
    if out is not None and out["calls"]:
        per = {k: v / 1e6 / out["calls"] for k, v in sorted(out["ns"].items())}
        _log({"idle_ms_per_call_by_span": per, "ingest_calls": out["calls"],
              "idle_ms_per_call": out["idle_ns"] / 1e6 / out["calls"],
              "idle_in_the_four_pct": 100.0 * sum(
                  out["ns"].get(k, 0) for k in IDLE_SPANS)
              / max(out["idle_ns"], 1)})
    return out


def _split_idle(tr, spans) -> dict:
    lo, hi = tr.window
    segs = innermost_segments(spans)
    seg_starts = [s for s, _, _ in segs]
    ns: dict = {}
    idle = 0
    for evs in tr.ops.values():
        ivs = sorted((max(s, lo), min(s + d, hi)) for _, s, d in evs
                     if s < hi and s + d > lo)
        t = lo
        for s, e in ivs + [(hi, hi)]:
            if s > t:
                idle += s - t
                j = max(bisect.bisect_right(seg_starts, t) - 1, 0)
                while j < len(segs) and segs[j][0] < s:
                    a, b = max(segs[j][0], t), min(segs[j][1], s)
                    if b > a:
                        key = _idle_key(segs[j][2]) or "(other)"
                        ns[key] = ns.get(key, 0) + (b - a)
                    j += 1
            t = max(t, e)
    n_dev = len(tr.ops)
    return {"ns": {k: v / n_dev for k, v in ns.items()},
            "idle_ns": idle / n_dev,
            "calls": sum(1 for n, s, _ in spans
                         if n == "ingest" and lo <= s < hi),
            "seen": {k for k in IDLE_SPANS
                     if any(_idle_key(n) == k for n, _, _ in spans)}}


def idle_ms(ctx, key: str):
    """Device idle time inside the ``key`` program span per traced
    ingest call, ms."""
    split = idle_split(ctx)
    if split is None or not split["calls"] or key not in split["seen"]:
        return None
    return split["ns"].get(key, 0) / 1e6 / split["calls"]
