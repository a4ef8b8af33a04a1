"""Mean of a histogram's observations made inside the window."""
from lib.prom import delta


def read(ctx, metric, over="window", scale=1.0):
    n = delta(ctx, over, metric + "_count")
    total = delta(ctx, over, metric + "_sum")
    if not n or total is None:
        return None
    return scale * total / n
