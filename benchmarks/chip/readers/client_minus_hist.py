"""What a request spends outside the engine: the client's mean time from
sending to the first token, minus the engine's own mean from enqueue to
first token over the same window."""
from lib.prom import delta


def read(ctx, metric, client="ttft_from_send_s", scale=1e3):
    samples = ctx["client"][client]
    n, total = delta(ctx, "window", metric + "_count"), \
        delta(ctx, "window", metric + "_sum")
    if not samples or not n:
        return None
    return scale * (sum(samples) / len(samples) - total / n)
