"""Device idle time per traced ingest call inside the program span
``ingest/scan`` and ``ingest/scan/boundary`` (segment dispatch, mid-call
publish hooks, the final wait), in ms (innermost program span;
``layers.py``)."""

import layers


def read(ctx):
    return layers.idle_ms(ctx, "scan")
