"""Self time of the scan program's ops in the ``score`` layer per live
step, in ms: bucket-start scoring (slot lookup, init vectors, scores,
top-k, hits) (``layers.py``)."""

import layers


def read(ctx):
    return layers.step_ms(ctx, "score")
