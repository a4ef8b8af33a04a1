"""A percentile of the client's samples of the window (its TTFT tail): the
same arithmetic as the end-to-end metrics, recorded without a bound."""
from lib.endtoend import percentile


def read(ctx, samples, p, scale=1e3):
    values = ctx["client"][samples]
    if not values:
        return None
    return scale * percentile(values, p)
