"""A counter's increase over the traced span per device second of the
programs that did the work (tokens prefilled per second of prefill)."""
from lib.prom import delta
from lib.trace import program_time


def read(ctx, metric, module):
    _, seconds = program_time(ctx["trace"], module)
    done = delta(ctx, "span", metric)
    if not seconds or not done:
        return None
    return done / seconds
