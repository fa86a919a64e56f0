"""Share of its roofline the ``dics_update`` kernel reaches, in %."""

import roofline


def read(ctx):
    return roofline.share(ctx, "dics_update")
