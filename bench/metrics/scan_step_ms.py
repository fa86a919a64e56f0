"""Device time of the engine's scan program per live scan step, in ms.

The scan program is each program execution (``XLA Modules`` event) in
the traced window that runs one of the configuration's worker kernels
(``"kernels"``); live steps are the micro-batch steps that trained
events, summed over the window's ingest calls.
"""

import bisect

import roofline


def read(ctx):
    if ctx.trace is None or not ctx.live_steps:
        return None
    lo, hi = ctx.trace.window
    matches = [roofline.kernel_match(k) for k in ctx.doc["kernels"]]
    total = 0
    for dev, mods in ctx.trace.modules.items():
        starts = sorted(s for n, s, _ in ctx.trace.ops.get(dev, ())
                        if any(m(n) for m in matches))
        for _, s, d in mods:
            if lo <= s < hi:
                j = bisect.bisect_left(starts, s)
                if j < len(starts) and starts[j] < s + d:
                    total += d
    if total == 0:
        return None
    return total / 1e6 / ctx.live_steps
