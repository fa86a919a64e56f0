"""Device idle time per traced ingest call inside the program span
``ingest/readback`` (step outputs to the host, recall and occupancy
folds), in ms (innermost program span; ``layers.py``)."""

import layers


def read(ctx):
    return layers.idle_ms(ctx, "readback")
