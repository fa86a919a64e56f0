"""Work the DISGD update needs per event, whatever implements it.

Per event the algorithm reads the user's and the item's factor rows and
writes both back (``4 * k`` float32 values) and sets one rated bit. The
arithmetic is one ``k``-dot, the error, and two ``k``-vector updates of
five operations per element: ``12 k + 1`` operations. Whole-table
copies, padding and bookkeeping an implementation adds are not needed
and not counted, so the time this gives is a lower bound for any
implementation.
"""


def work(ctx) -> dict:
    k = ctx.doc["hyper"]["k"]
    n = ctx.processed
    return {"flops": n * (12 * k + 1), "bytes": n * (4 * k * 4 + 1 / 8)}
