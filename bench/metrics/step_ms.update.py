"""Self time of the scan program's ops in the ``update`` layer per live
step, in ms: worker update (bucket plan, row compaction, update kernel,
scatters back) (``layers.py``)."""

import layers


def read(ctx):
    return layers.step_ms(ctx, "update")
