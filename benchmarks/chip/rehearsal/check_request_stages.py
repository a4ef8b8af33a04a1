"""Drives the two readers of the request stage family on a hand-made
context: `readers/prom_hist_label_mean.py` selects label values of a family
with three label sets (a stage with no sample in the window gives None, not
0), `readers/outside_rest.py` computes what is left, and the four metrics of
`layer_metrics/` add up to `outside_engine_ms` of the same context."""
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

FAMILY = "dynamo_request_stage_seconds"
PARTS = ("frontend_in_ms", "worker_in_ms", "worker_out_ms",
         "outside_rest_ms")


def metric(name):
    """A layer metric as run.py reads it: its spec's reader on its args."""
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    mod_spec = importlib.util.spec_from_file_location(
        "reader_" + spec["reader"],
        os.path.join(HERE, "readers", spec["reader"] + ".py"))
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return lambda ctx: mod.read(ctx, **spec.get("args", {}))


def scrape(count, seconds_by_stage):
    """One scrape of the worker: the stage family, every stage observed
    `count` times, and the engine's own histogram."""
    out = {"dynamo_engine_ttft_seconds_count": float(count),
           "dynamo_engine_ttft_seconds_sum": 0.150 * count}
    for stage, seconds in seconds_by_stage.items():
        out[f'{FAMILY}_count{{stage="{stage}"}}'] = float(count)
        out[f'{FAMILY}_sum{{stage="{stage}"}}'] = seconds * count
        out[f'{FAMILY}_bucket{{le="+Inf",stage="{stage}"}}'] = float(count)
    return out


def main() -> int:
    # three label sets, 10 requests before the window and 30 by its end;
    # the window's 20 took 2, 3 and 5 ms a stage
    three = {"route": 0.002, "transport_in": 0.003, "worker_in": 0.005}
    start = scrape(10, {k: 0.001 for k in three})
    stop = scrape(30, {k: (0.001 * 10 + v * 20) / 30
                       for k, v in three.items()})
    ctx = {"window": {"prom_start": start, "prom_stop": stop},
           "client": {"ttft_from_send_s": [0.2, 0.22, 0.24]}}
    spec = importlib.util.spec_from_file_location(
        "label_mean", os.path.join(HERE, "readers",
                                   "prom_hist_label_mean.py"))
    label_mean = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(label_mean)

    def mean(values):
        return label_mean.read(ctx, FAMILY, "stage", values, scale=1e3)

    assert abs(mean(["route"]) - 2.0) < 1e-9
    assert abs(mean(["transport_in", "worker_in"]) - 8.0) < 1e-9
    assert abs(mean(list(three)) - 10.0) < 1e-9
    # a stage nobody stamped is missing, not 0 ms; so is one the window
    # did not observe
    assert mean(["route", "preprocess"]) is None
    assert label_mean.read(
        {"window": {"prom_start": stop, "prom_stop": stop}},
        FAMILY, "stage", ["route"]) is None
    # as run.py reads them: with only three stages in the scrape two of the
    # parts and the rest are left out of the line
    assert abs(metric("worker_in_ms")(ctx) - 8.0) < 1e-9
    for name in ("frontend_in_ms", "worker_out_ms", "outside_rest_ms"):
        assert metric(name)(ctx) is None, name
    # the worker's six stages in the scrape: the four add up to
    # `outside_engine_ms`, 220 - 150 = 70 ms
    six = {"http_parse": 0.001, "preprocess": 0.004, "route": 0.002,
           "transport_in": 0.003, "worker_in": 0.005, "worker_out": 0.009}
    ctx["window"] = {"prom_start": scrape(0, six),
                     "prom_stop": scrape(20, six)}
    got = {name: metric(name)(ctx) for name in PARTS}
    want = {"frontend_in_ms": 7.0, "worker_in_ms": 8.0, "worker_out_ms": 9.0,
            "outside_rest_ms": 46.0}
    for name in PARTS:
        assert abs(got[name] - want[name]) < 1e-9, (name, got)
    outside = metric("outside_engine_ms")(ctx)
    assert abs(sum(got.values()) - outside) < 1e-9 and abs(outside - 70) < 1e-9
    # a program without the family (the parent of PR 43): nothing to read
    ctx["window"] = {
        mark: {k: v for k, v in seen.items() if not k.startswith(FAMILY)}
        for mark, seen in ctx["window"].items()}
    assert all(metric(name)(ctx) is None for name in PARTS)
    assert abs(metric("outside_engine_ms")(ctx) - 70) < 1e-9
    with open(os.path.join(os.path.dirname(HERE), os.pardir,
                           "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in PARTS:
        assert listed[name]["moves"] == "ttft_p50_ms"
        assert "workloads" not in listed[name]
    return 0


if __name__ == "__main__":
    sys.exit(main())
