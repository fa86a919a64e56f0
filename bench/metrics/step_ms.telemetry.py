"""Self time of the scan program's ops in the ``telemetry`` layer per
live step, in ms: telemetry (list lengths, occupancy reductions, the
telemetry vector) (``layers.py``)."""

import layers


def read(ctx):
    return layers.step_ms(ctx, "telemetry")
