"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is the ``.xplane.pb`` file ``jax.profiler`` writes. From it:

* ``window``: the host span ``WINDOW`` the harness opens around the
  measured window, as ``(start_ns, end_ns)``;
* ``ops``: per device plane (``/device:...``), the events of its
  ``XLA Ops`` line as ``(op name, start_ns, duration_ns)``; ops nest
  there (a ``while`` spans its body's ops); ``modules`` likewise from
  its ``XLA Modules`` line, one event per program execution;
* ``spans``: host events of the thread that ran the window, as
  ``(name, start_ns, duration_ns)``.

Busy time is the union of a device's op intervals inside the window;
the idle gaps between them are labelled by the innermost host event of
that thread around the gap's middle. All times are in nanoseconds, as
the trace has them.
"""

from __future__ import annotations

from typing import NamedTuple

__all__ = ["WINDOW", "Trace", "load", "from_planes", "op_name", "union_ns",
           "busy_s", "op_ns", "within", "self_ns", "idle_gaps", "top_ops"]

WINDOW = "bench.window"


class Trace(NamedTuple):
    window: tuple
    ops: dict          # device plane name -> [(name, start, dur)]
    modules: dict      # device plane name -> [(name, start, dur)]
    spans: list        # [(name, start, dur)] on the window's thread


def op_name(text: str) -> str:
    """``"%sort.6 = f32[...] sort(...)"`` -> ``"sort.6"``: the TPU trace
    names an op by its HLO instruction; keep the instruction's name."""
    return text.split(" = ", 1)[0].lstrip("%")


def from_planes(planes) -> Trace:
    """``planes``: ``[(plane_name, [(line_name, [(name, start, dur)])])]``."""
    ops, modules, window, spans = {}, {}, None, []
    for pname, lines in planes:
        if pname.startswith("/device:"):
            for lname, evs in lines:
                if lname == "XLA Ops":
                    ops.setdefault(pname, []).extend(
                        (op_name(n), s, d) for n, s, d in evs)
                elif lname == "XLA Modules":
                    modules.setdefault(pname, []).extend(evs)
        elif pname.startswith("/host:"):
            for _, evs in lines:
                w = [e for e in evs if e[0] == WINDOW]
                if w and window is None:
                    window = (w[0][1], w[0][1] + w[0][2])
                    spans = [e for e in evs if e[0] != WINDOW]
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    return Trace(window=window, ops=ops, modules=modules, spans=spans)


def load(path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(e.name, int(e.start_ns),
                                       int(e.duration_ns))
                                      for e in line.events]))
        planes.append((plane.name, lines))
    return from_planes(planes)


def within(evs, lo: int, hi: int):
    """The events that start inside ``[lo, hi)``."""
    return [e for e in evs if lo <= e[1] < hi]


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``(start, end)`` intervals, clipped."""
    total, end = 0, lo
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            total += e - s
            end = e
    return total


def busy_s(tr: Trace) -> float:
    """Seconds in the window in which an op ran, averaged over devices."""
    lo, hi = tr.window
    if not tr.ops:
        return 0.0
    per = [union_ns([(s, s + d) for _, s, d in evs], lo, hi)
           for evs in tr.ops.values()]
    return sum(per) / len(per) / 1e9


def op_ns(tr: Trace, match) -> int:
    """Device time of the ops whose name ``match(name)`` accepts, summed
    over devices, in the window."""
    lo, hi = tr.window
    return sum(d for evs in tr.ops.values() for n, s, d in within(evs, lo, hi)
               if match(n))


def self_ns(evs):
    """``[(name, start, self_ns)]``: each op's time less the time of the
    ops nested in it (a ``while`` holds its body's ops on the same line)."""
    out, stack = [], []          # stack of indexes into out, with ends
    for name, s, d in sorted(evs, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            n, ps, pself = out[parent]
            out[parent] = (n, ps, pself - min(d, stack[-1][1] - s))
        out.append((name, s, d))
        stack.append((len(out) - 1, s + d))
    return out


def top_ops(tr: Trace, n: int = 10):
    """``[[name, seconds], ...]``: the ops with the most self time on the
    device in the window, averaged over devices."""
    lo, hi = tr.window
    acc: dict = {}
    for evs in tr.ops.values():
        for name, s, d in self_ns(within(evs, lo, hi)):
            acc[name] = acc.get(name, 0) + d
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / max(len(tr.ops), 1)] for k, v in best]


def idle_gaps(tr: Trace, n: int = 10, short_ns: int = 20_000):
    """``[[host activity, seconds], ...]``: idle device time in the
    window, summed by what the window's thread was doing, most first and
    averaged over devices. Gaps under ``short_ns`` between two ops are
    summed as one entry of their own."""
    import bisect

    lo, hi = tr.window
    spans = sorted(tr.spans, key=lambda x: x[1])
    starts = [x[1] for x in spans]

    def doing(t):
        # Innermost host event around t: the latest-starting one that
        # still covers it (host events nest).
        j = bisect.bisect_right(starts, t) - 1
        for x in spans[max(0, j - 256):j + 1][::-1]:
            if x[1] + x[2] > t:
                return x[0]
        return "(no host event)"

    acc: dict = {}
    for evs in tr.ops.values():
        ivs = sorted((max(s, lo), min(s + d, hi)) for _, s, d in evs
                     if s < hi and s + d > lo)
        t = lo
        for s, e in ivs + [(hi, hi)]:
            if s > t:
                name = (f"(op gaps under {short_ns // 1000} us)"
                        if s - t < short_ns and t > lo and s < hi
                        else doing((s + t) // 2))
                acc[name] = acc.get(name, 0) + (s - t)
            t = max(t, e)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / max(len(tr.ops), 1)] for k, v in best]
