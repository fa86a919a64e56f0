"""Self time of the scan program's ops in the ``forget`` layer per live
step, in ms: the fixed forgetting cadence's count and test, and on the
steps where it fires, the eviction (``layers.py``)."""

import layers


def read(ctx):
    return layers.step_ms(ctx, "forget")
