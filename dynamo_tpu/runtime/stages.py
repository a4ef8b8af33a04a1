"""What is made of a request's stage clock (`Context.stages`).

One histogram family, `dynamo_request_stage_seconds{stage}`, always on like
`dynamo_engine_ttft_seconds`: an interval is named by the stamp that ends
it and starts at the stamp before it on the clock, so the intervals of one
request telescope: the eight behind `http_parse` add up to
`dynamo_http_time_to_first_token_seconds`, which is observed from the same
two stamps. A process observes when a leg ends in it: the worker at
`worker_out` (`WORKER_LEG`: not `engine`, which `dynamo_engine_ttft_seconds`
owns there between the same two instants), the frontend at `frontend_out`
(`FRONTEND_LEG`: all nine, it has no other record of `engine`). An interval
that reads negative (the clocks of two hosts) is observed as 0 and counted
in `dynamo_request_stage_clock_skew_total`.

Under DYN_TRACE the same stamps become children of the spans that exist
(`Tracer.stage`, explicit timestamps, as the engine's stages are emitted).

The cost is per REQUEST: ten clock reads, one header (`HEADER`, beside
`traceparent`), one envelope field (`FIELD`, on the one data frame that
carries the first emission). Nothing per token.
"""

from __future__ import annotations

from dynamo_tpu.runtime.context import (
    FRONTEND_OUT,
    HTTP_PARSE,
    PREPROCESS,
    ROUTE,
    STAGES,
    TRANSPORT_BACK,
    TRANSPORT_IN,
    WORKER_IN,
    WORKER_OUT,
)
from dynamo_tpu.runtime.metrics import (
    Counter,
    LabeledHistogram,
    MetricsRegistry,
)
from dynamo_tpu.runtime.tracing import Span, tracer

HEADER = "x-dyn-stages"   # request headers: the clock so far, stage -> ns
FIELD = "stages"          # data-frame envelope: the clock back, once

WORKER_LEG = (HTTP_PARSE, PREPROCESS, ROUTE, TRANSPORT_IN, WORKER_IN,
              WORKER_OUT)
FRONTEND_LEG = STAGES[1:]

# a span a stage, where the clock's name and the catalog's differ by a dot
# (`engine` is the engine's own `engine.request` tree)
SPAN_NAMES = {HTTP_PARSE: "http.parse", PREPROCESS: "preprocess",
              ROUTE: "route", TRANSPORT_IN: "transport.in",
              WORKER_IN: "worker.in", WORKER_OUT: "worker.out",
              TRANSPORT_BACK: "transport.back",
              FRONTEND_OUT: "frontend.out"}
# the worker emits the two it can parent under `serve <subject>`; the
# frontend the rest, under `http <endpoint>`, when its leg ends
WORKER_SPANS = (TRANSPORT_IN, WORKER_IN)
FRONTEND_SPANS = (HTTP_PARSE, PREPROCESS, ROUTE, WORKER_OUT, TRANSPORT_BACK,
                  FRONTEND_OUT)

_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
            0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0)


def from_wire(obj: object) -> dict[str, int]:
    """The clock a peer sent (a header's or an envelope field's value):
    known stages with whole-number stamps, in the order sent; {} for
    anything else, so a sender without a clock is served as before."""
    if not isinstance(obj, dict):
        return {}
    return {k: v for k, v in obj.items()
            if k in STAGES and isinstance(v, int)}


def intervals(stages: dict[str, int], leg: tuple[str, ...]
              ) -> list[tuple[str, int, int]]:
    """(stage, start_ns, end_ns) of every interval of `leg` the clock
    holds: from the stamp before it on the clock to its own."""
    out = []
    prev = None
    for name, ns in stages.items():
        if prev is not None and name in leg:
            out.append((name, prev, ns))
        prev = ns
    return out


class StageMetrics:
    """The stage family of one process (`DistributedRuntime.stage_metrics`)."""

    def __init__(self, registry: MetricsRegistry) -> None:
        self.seconds = LabeledHistogram(
            "dynamo_request_stage_seconds", "stage",
            "a request's way to its first token, by the stage that ends "
            "the interval", _BUCKETS)
        self.skew = Counter(
            "dynamo_request_stage_clock_skew_total",
            "stage intervals that read negative and were observed as 0")
        registry.register(self.seconds)
        registry.register(self.skew)

    def leg_ended(self, stages: dict[str, int], leg: tuple[str, ...],
                  parent: Span | None, spans: tuple[str, ...]) -> None:
        """Observe every interval of `leg` the clock holds; under
        DYN_TRACE emit those of `spans` as children of `parent`."""
        tr = tracer()
        traced = parent is not None and tr.enabled
        for name, start_ns, end_ns in intervals(stages, leg):
            if end_ns < start_ns:
                self.skew.inc()
                end_ns = start_ns
            self.seconds.observe(name, (end_ns - start_ns) / 1e9)
            if traced and name in spans:
                tr.stage(parent, SPAN_NAMES[name], start_ns, end_ns)
