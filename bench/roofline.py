"""A kernel's share of its roofline, from the trace and its work function.

The least time the chip could take for the work the algorithm needs
(``work/<kernel>.py``) is the larger of operations over peak operations
per second and bytes over peak HBM bytes per second (``peaks.json``,
keyed by the device kind); the share is that time over the kernel's
device time in the traced window. The bound that applies is printed on
an earlier line of the run.
"""

from __future__ import annotations

import json
import sys

import devtrace


def kernel_match(kernel: str):
    """Trace op names of a kernel launched as ``pallas_call(name=kernel)``:
    the name itself, or it with a ``.<n>`` suffix of a repeated op."""
    def match(name: str) -> bool:
        return name == kernel or (name.startswith(kernel + ".")
                                  and name[len(kernel) + 1:].isdigit())
    return match


def share(ctx, kernel: str):
    if ctx.trace is None:
        return None
    t = devtrace.op_ns(ctx.trace, kernel_match(kernel)) / 1e9
    if t <= 0:
        return None
    need = ctx.work(kernel)
    peak = ctx.peak
    t_flops = need["flops"] / peak["flops_per_s"]
    t_bytes = need["bytes"] / peak["hbm_bytes_per_s"]
    print(json.dumps({"roofline": kernel, "kernel_s": t, **need,
                      "bound": "bytes" if t_bytes >= t_flops else "flops",
                      "least_s": max(t_flops, t_bytes)}), file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / t
