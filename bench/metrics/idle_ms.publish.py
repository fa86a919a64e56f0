"""Device idle time per traced ingest call inside the program span
``publish`` and ``publish/*`` (flush, rotation with the popularity head,
telemetry fold), in ms (innermost program span; ``layers.py``)."""

import layers


def read(ctx):
    return layers.idle_ms(ctx, "publish")
