"""The request's stage clock (runtime/stages.py), end to end on the CPU:
the mocker behind the real frontend and a real TCP transport hop (the
fixture of tests/test_trace_smoke.py). The clock rides from the HTTP
handler's first statement to the first SSE frame; the worker observes its
leg into `dynamo_request_stage_seconds{stage}` when the first emission's
frame goes out, the frontend when the first content chunk's write returns.
"""

import asyncio
import contextlib
import statistics
import time

import aiohttp
import pytest

from dynamo_tpu.runtime import codec, stages
from dynamo_tpu.runtime.context import (
    ENGINE,
    FRONTEND_OUT,
    HTTP_PARSE,
    HTTP_RECV,
    PREPROCESS,
    ROUTE,
    STAGES,
    TRANSPORT_BACK,
    TRANSPORT_IN,
    WORKER_IN,
    WORKER_OUT,
    Context,
)
from dynamo_tpu.runtime.metrics import MetricsRegistry
from dynamo_tpu.runtime.recorder import Recorder
from dynamo_tpu.runtime.tracing import Tracer, set_tracer

pytestmark = pytest.mark.tier0

MODEL = "mock-model"
FAMILY = "dynamo_request_stage_seconds"
SKEW = "dynamo_request_stage_clock_skew_total"
HTTP_TTFT = "dynamo_http_time_to_first_token_seconds"


class Stack:
    """Frontend and worker as two runtimes over a TCP store, so a request
    crosses a real transport hop; `clocks` holds every clock whose
    frontend leg ended, `frames` every frame either side read."""

    def __init__(self, fe, rt_f, rt_w) -> None:
        self.fe, self.rt_f, self.rt_w = fe, rt_f, rt_w
        self.clocks: list[dict] = []
        self.frames: list[dict] = []

    def family(self, rt):
        return rt.metrics.collect()[FAMILY]

    def metric(self, rt, name):
        return rt.metrics.collect()[name]

    async def stream(self, session, text="hello there", max_tokens=6
                     ) -> list[str]:
        """One streamed chat completion; its SSE data lines."""
        async with session.post(
                f"{self.fe.url}/v1/chat/completions",
                json={"model": MODEL, "max_tokens": max_tokens,
                      "stream": True,
                      "messages": [{"role": "user", "content": text}]}
        ) as r:
            assert r.status == 200, await r.text()
            body = await r.text()
        lines = [ln for ln in body.splitlines() if ln.startswith("data: ")]
        assert lines[-1] == "data: [DONE]"
        return lines


@contextlib.asynccontextmanager
async def stack(monkeypatch):
    from dynamo_tpu.llm.entrypoint import (
        serve_engine,
        start_frontend,
        wire_engine_events,
    )
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.mocker.engine import MockEngine, MockEngineConfig
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.store_net import StoreServer

    store_server = StoreServer()
    host, port = await store_server.start()
    store_url = f"tcp://{host}:{port}"
    rt_w = await DistributedRuntime.create(RuntimeConfig(store_url=store_url))
    rt_f = await DistributedRuntime.create(RuntimeConfig(store_url=store_url))
    card = ModelDeploymentCard(
        name=MODEL, namespace="ns", component="mock",
        tokenizer_kind="word", tokenizer_path=MODEL,
        router_mode="round_robin")
    ev_sink, m_sink = wire_engine_events(rt_w, card)
    eng = MockEngine(
        MockEngineConfig(block_size=card.kv_block_size, worker_id=1,
                         speedup=200.0, default_max_tokens=8),
        event_sink=ev_sink, metrics_sink=m_sink)
    handle = await serve_engine(rt_w, eng, card, instance_id=1)
    fe = await start_frontend(rt_f)
    st = Stack(fe, rt_f, rt_w)

    ended = rt_f.stage_metrics.leg_ended

    def spy(clock, leg, parent, spans):
        st.clocks.append(dict(clock))
        return ended(clock, leg, parent, spans)

    monkeypatch.setattr(rt_f.stage_metrics, "leg_ended", spy)
    read_frame = codec.read_frame

    async def tap(reader):
        msg = await read_frame(reader)
        st.frames.append(msg)
        return msg

    monkeypatch.setattr(codec, "read_frame", tap)
    try:
        for _ in range(200):
            if MODEL in fe.manager.model_names():
                break
            await asyncio.sleep(0.01)
        yield st
    finally:
        await fe.stop()
        await handle.stop()
        await eng.close()
        await rt_f.close()
        await rt_w.close()
        await store_server.stop()


async def test_counts_by_process(monkeypatch):
    """N streamed requests: N observations of each of the worker's six
    stages (no `engine` series there: `dynamo_engine_ttft_seconds` owns
    it) and of each of the frontend's nine; nothing negative."""
    n = 5
    async with stack(monkeypatch) as st:
        async with aiohttp.ClientSession() as s:
            for _ in range(n):
                await st.stream(s)
        worker, front = st.family(st.rt_w), st.family(st.rt_f)
        assert worker.values() == sorted(stages.WORKER_LEG)
        assert ENGINE not in worker.values()
        assert front.values() == sorted(STAGES[1:])
        for fam in (worker, front):
            for stage in fam.values():
                total, count = fam.stats(stage)
                assert count == n, (stage, count)
                assert total >= 0.0
        for rt in (st.rt_w, st.rt_f):
            assert st.metric(rt, SKEW).get() == 0
        # the engine's own histogram holds the interval the worker left out
        assert st.metric(st.rt_w, "dynamo_engine_ttft_seconds").count == n
    assert len(st.clocks) == n
    for clock in st.clocks:
        assert list(clock) == list(STAGES)
        stamps = list(clock.values())
        assert stamps == sorted(stamps)


async def test_stages_add_up_to_http_ttft(monkeypatch):
    """`dynamo_http_time_to_first_token_seconds` is read off the clock's
    own stamps: the frontend's eight intervals behind `http_parse` add up
    to its sum, and each request's to `frontend_out - http_parse`."""
    async with stack(monkeypatch) as st:
        async with aiohttp.ClientSession() as s:
            for _ in range(4):
                await st.stream(s)
        front = st.family(st.rt_f)
        behind = sum(front.stats(stage)[0] for stage in STAGES[2:])
        ttft = st.metric(st.rt_f, HTTP_TTFT)
        assert ttft.count == 4
        assert abs(behind - ttft.sum) < 1e-6
        by_clock = sum(c[FRONTEND_OUT] - c[HTTP_PARSE] for c in st.clocks)
        assert abs(by_clock / 1e9 - ttft.sum) < 1e-6
        # the debug view's first_token_s is the same reading
        recent = list(st.fe.http._dbg_recent)
        assert abs(sum(r["first_token_s"] for r in recent) - ttft.sum) < 1e-5


async def test_slow_tokenizer_moves_preprocess_alone(monkeypatch,
                                                     frozen_heap):
    """A tokenizer that sleeps 20 ms moves `preprocess` by 20 ms and no
    other stage by more than 2 (medians over the requests' own clocks)."""
    async with stack(monkeypatch) as st:
        async with aiohttp.ClientSession() as s:
            for _ in range(3):          # connections, vocabulary, caches
                await st.stream(s)
            del st.clocks[:]
            for _ in range(9):
                await st.stream(s)
            before, st.clocks = st.clocks, []
            tok = st.fe.manager.get(MODEL).engine.tokenizer
            encode = tok.encode

            def slow_encode(text):
                time.sleep(0.02)
                return encode(text)

            monkeypatch.setattr(tok, "encode", slow_encode)
            for _ in range(9):
                await st.stream(s)
            after = st.clocks

    def medians(clocks):
        return {stage: statistics.median(
            end - start for c in clocks
            for name, start, end in stages.intervals(c, STAGES[1:])
            if name == stage) / 1e6 for stage in STAGES[1:]}

    was, now = medians(before), medians(after)
    assert 19.5 < now[PREPROCESS] - was[PREPROCESS] < 24.0, (was, now)
    for stage in STAGES[1:]:
        if stage != PREPROCESS:
            assert abs(now[stage] - was[stage]) < 2.0, (stage, was, now)


async def test_request_without_the_key(monkeypatch):
    """A direct caller of the worker that keeps no clock (an old sender):
    served as before, no envelope field on any frame, and the worker
    observes from its own first stamp on: `worker_in` (from
    `transport_in`) and `worker_out`, nothing it has no start for."""
    from dynamo_tpu.protocols import PreprocessedRequest, StopConditions

    async with stack(monkeypatch) as st:
        inst = st.fe.manager.get(MODEL).client.instances()[0]
        payload = PreprocessedRequest(
            token_ids=[5, 6, 7], model=MODEL,
            stop=StopConditions(max_tokens=4)).to_dict()
        ctx = Context()
        assert not ctx.stages
        out = [item async for item in st.rt_f.transport_client.request(
            inst.address, inst.subject, payload, ctx)]
        assert sum(len(o["token_ids"]) for o in out) == 4
        assert not ctx.stages
        worker = st.family(st.rt_w)
        assert worker.values() == sorted([WORKER_IN, WORKER_OUT])
        assert worker.stats(WORKER_IN)[1] == worker.stats(WORKER_OUT)[1] == 1
    data = [f for f in st.frames if f.get("t") == "data"]
    assert len(data) == 4 and not any(stages.FIELD in f for f in data)


async def test_envelope_field_on_one_frame(monkeypatch):
    """The clock comes back once a request: on the data frame of the first
    emission, with the worker's four stamps; absent on every other."""
    async with stack(monkeypatch) as st:
        async with aiohttp.ClientSession() as s:
            for _ in range(3):
                await st.stream(s, max_tokens=5)
    reqs = [f for f in st.frames if f.get("t") == "req"
            and stages.HEADER in (f.get("headers") or {})]
    assert len(reqs) == 3
    for req in reqs:
        assert list(req["headers"][stages.HEADER]) == [
            HTTP_RECV, HTTP_PARSE, PREPROCESS, ROUTE]
        data = [f for f in st.frames
                if f.get("t") == "data" and f.get("rid") == req["rid"]]
        assert len(data) == 5
        assert [stages.FIELD in f for f in data] == [True] + [False] * 4
        back = data[0][stages.FIELD]
        assert list(back) == [HTTP_RECV, HTTP_PARSE, PREPROCESS, ROUTE,
                              TRANSPORT_IN, WORKER_IN, ENGINE, WORKER_OUT,
                              TRANSPORT_BACK]


async def test_spans_under_the_spans_that_exist(monkeypatch, tmp_path):
    """DYN_TRACE: the eight stage spans in the request's one trace,
    `transport.in` / `worker.in` under `serve <subject>`, the other six
    under `http <endpoint>`, each from the stamp before it to its own."""
    path = tmp_path / "trace.jsonl"
    tracer = Tracer(enabled=True, path=str(path))
    set_tracer(tracer)
    try:
        async with stack(monkeypatch) as st:
            async with aiohttp.ClientSession() as s:
                await st.stream(s)
    finally:
        set_tracer(None)
    await tracer.close()
    (clock,) = st.clocks
    rows = [e for _, e in Recorder.iter_events(path)]
    http = next(r for r in rows if r["name"].startswith("http "))
    ours = [r for r in rows if r["traceId"] == http["traceId"]]
    serve = next(r for r in ours if r["name"].startswith("serve "))
    by_name = {r["name"]: r for r in ours}
    assert set(stages.SPAN_NAMES.values()) <= set(by_name)
    assert len(stages.SPAN_NAMES) == 8
    order = list(STAGES)
    for stage, name in stages.SPAN_NAMES.items():
        span = by_name[name]
        parent = serve if stage in stages.WORKER_SPANS else http
        assert span["parentSpanId"] == parent["spanId"], name
        assert span["startTimeUnixNano"] == clock[
            order[order.index(stage) - 1]], name
        assert span["endTimeUnixNano"] == clock[stage], name
    # the handler's span opens at the clock's first stamp, so they nest
    assert http["startTimeUnixNano"] == clock[HTTP_RECV]
    assert by_name["engine.request"]["parentSpanId"] == serve["spanId"]


def test_negative_interval_is_skew():
    """Two hosts' clocks: an interval that reads negative is observed as
    0 and counted; a peer's clock that is no clock is an empty one."""
    reg = MetricsRegistry("dynamo")
    sm = stages.StageMetrics(reg)
    clock = {ROUTE: 2_000_000, TRANSPORT_IN: 1_000_000,
             WORKER_IN: 1_500_000}
    sm.leg_ended(clock, stages.WORKER_LEG, None, ())
    assert sm.skew.get() == 1
    assert sm.seconds.stats(TRANSPORT_IN) == (0.0, 1)
    assert sm.seconds.stats(WORKER_IN) == (0.0005, 1)
    assert stages.from_wire(None) == {} == stages.from_wire("x")
    assert stages.from_wire({ROUTE: 5, "nope": 1, ENGINE: "7"}) == {ROUTE: 5}
    text = reg.render()
    assert f'{FAMILY}_count{{stage="worker_in"}} 1' in text
    assert f'{FAMILY}_bucket{{le="+Inf",stage="transport_in"}} 1' in text


def test_first_stamp_wins_and_children_share():
    """A stage is stamped once (a retry does not move it) and a child
    context's stamps are the request's."""
    ctx = Context()
    first = ctx.stamp(ROUTE)
    child = ctx.child()
    assert child.stamp(ROUTE) >= first and ctx.stages[ROUTE] == first
    child.stamp(ENGINE, 7)
    assert ctx.stages[ENGINE] == 7 and list(ctx.stages) == [ROUTE, ENGINE]
