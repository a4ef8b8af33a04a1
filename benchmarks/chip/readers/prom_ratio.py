"""Increase of one counter over the increase of another, between the
scrapes at the ends of the window or of the traced span. None where either
counter is missing (a worker from before it) or the divisor did not move."""
from lib.prom import delta


def read(ctx, num, den, num_labels="", den_labels="", over="span",
         scale=1.0):
    if over not in ctx:
        return None
    a = delta(ctx, over, num, num_labels)
    b = delta(ctx, over, den, den_labels)
    if a is None or not b:
        return None
    return scale * a / b
