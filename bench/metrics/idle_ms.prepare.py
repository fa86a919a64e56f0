"""Device idle time per traced ingest call inside the program span
``ingest/prepare`` (padding, the scan carry, the chunk's transfer), in
ms (innermost program span; ``layers.py``)."""

import layers


def read(ctx):
    return layers.idle_ms(ctx, "prepare")
