"""Share of the scan program's op self time that the program places in
no layer, in % (``layers.py``)."""

import layers


def read(ctx):
    return layers.unscoped_pct(ctx)
