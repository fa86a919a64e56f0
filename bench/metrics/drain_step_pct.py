"""Share of the window's live scan steps that only drain the re-queue, in %.

An ingest call runs one step per micro-batch of fresh events; what a
hot worker could not take in a micro-batch is re-queued, and after the
last micro-batch the call runs further steps until the re-queue is
empty. Those drain steps cost as much as a full step on the device.
Counted from the program's per-call step loads (``load_history``).
"""


def read(ctx):
    run = ctx.run
    if not run.live_steps:
        return None
    return 100.0 * (run.live_steps - run.fresh_steps) / run.live_steps
