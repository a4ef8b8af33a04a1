"""Mean of the observations made inside the window under chosen values of
one label of a histogram family, the values' means added up: consecutive
stages of one request's way, each observed once a request
(`prom_hist_mean` reads a family whole, every label set in one sum).
None where a value has no observation in the window: a stage the program
does not stamp is missing, not 0 ms."""
from lib.prom import delta


def read(ctx, metric, label, values, over="window", scale=1.0):
    total = 0.0
    for value in values:
        tag = f'{label}="{value}"'
        n = delta(ctx, over, metric + "_count", tag)
        seconds = delta(ctx, over, metric + "_sum", tag)
        if not n or seconds is None:
            return None
        total += seconds / n
    return scale * total
