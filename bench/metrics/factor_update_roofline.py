"""Share of its roofline the ``factor_update`` kernel reaches, in %."""

import roofline


def read(ctx):
    return roofline.share(ctx, "factor_update")
