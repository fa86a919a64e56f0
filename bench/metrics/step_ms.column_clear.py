"""Self time of the scan program's ops in the ``column_clear`` layer per
live step, in ms: rated-column clears (the per-event clear loop inside
the update) (``layers.py``)."""

import layers


def read(ctx):
    return layers.step_ms(ctx, "column_clear")
