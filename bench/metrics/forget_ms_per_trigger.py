"""Self time of the scan program's ops in the ``forget`` layer in the
traced calls, per forgetting trigger those calls fired, in ms: the cost
of one eviction pass (``layers.py``; the triggers are each call's
``StreamResult.forgets``)."""

import layers


def read(ctx):
    split = layers.scan_split(ctx)
    triggers = sum(ctx.run.forgets[ctx.first_window_call:])
    if split is None or not triggers:
        return None
    return split["ns"].get("forget", 0) / 1e6 / triggers
