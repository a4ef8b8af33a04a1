"""100 * a / (a + b) of two counters' increases."""
from lib.prom import delta


def read(ctx, part, rest, labels="", over="window"):
    a, b = delta(ctx, over, part, labels), delta(ctx, over, rest, labels)
    if a is None or b is None or a + b <= 0:
        return None
    return 100.0 * a / (a + b)
