"""What is left of `outside_engine_ms` (the client's mean time from its
send to the first token, minus the engine's own mean from enqueue to first
token) once the stages the worker's scrape holds are taken out of it: the
client's send to the handler's first statement, and the worker's socket to
the client's first frame. The parts and the rest add up to
`outside_engine_ms` of the same run, as the four `idle_*` add up to
`device_idle`. None where either side is missing."""
from readers.client_minus_hist import read as outside_engine
from readers.prom_hist_label_mean import read as stages_mean


def read(ctx, metric, stages_metric, label, values, scale=1e3):
    outside = outside_engine(ctx, metric, scale=scale)
    inside = stages_mean(ctx, stages_metric, label, values, scale=scale)
    if outside is None or inside is None:
        return None
    return outside - inside
