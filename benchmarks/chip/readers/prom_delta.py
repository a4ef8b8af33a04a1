"""A counter's increase over the window (or the traced span)."""
from lib.prom import delta


def read(ctx, metric, labels="", over="window", scale=1.0):
    if over not in ctx:
        return None
    d = delta(ctx, over, metric, labels)
    return 0.0 if d is None else d * scale
