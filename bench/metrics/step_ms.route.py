"""Self time of the scan program's ops in the ``route`` layer per live
step, in ms: routing (keys, bucket dispatch, event gather, recall-bits
scatter, re-queue compaction) (``layers.py``)."""

import layers


def read(ctx):
    return layers.step_ms(ctx, "route")
