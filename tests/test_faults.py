"""Deterministic fault injection + the recovery paths it exercises.

Covers the robustness spine (docs/robustness.md): per-stream idle
timeouts and overall deadlines in TransportClient, jittered connect
retry/backoff, the per-instance circuit breaker in PushRouter, rx-loop
decode-error accounting, bounded server shutdown, and the
canary-failure → deregistration path driven through `runtime/faults.py`.
"""

import asyncio

import pytest

from dynamo_tpu.runtime.breaker import CLOSED, HALF_OPEN, OPEN, CircuitBreaker
from dynamo_tpu.runtime.component import Instance
from dynamo_tpu.runtime.config import RuntimeConfig
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import FnEngine
from dynamo_tpu.runtime.faults import (
    FaultInjector,
    FaultRule,
    FaultyEngine,
    parse_spec,
)
from dynamo_tpu.runtime.push import PushRouter
from dynamo_tpu.runtime.store import DELETE
from dynamo_tpu.runtime.transport import (
    STREAM_ERR_MSG,
    ConnectError,
    TransportClient,
    TransportServer,
)

pytestmark = pytest.mark.tier0


# -- spec grammar ------------------------------------------------------------


def test_parse_spec_grammar():
    rules = parse_spec(
        "kind=connect_refused,addr=127.0.0.1:7001,times=2;"
        "kind=stall,subject=ns.c.*,after=3,times=*;"
        "kind=delay,delay_s=0.5,prob=0.25;"
        "kind=err,error=boom")
    assert rules[0] == FaultRule("connect_refused", addr="127.0.0.1:7001",
                                 times=2)
    assert rules[1] == FaultRule("stall", subject="ns.c.*", after=3,
                                 times=None)
    assert rules[2].delay_s == 0.5 and rules[2].prob == 0.25
    assert rules[3].error == "boom"


def test_parse_spec_rejects_unknown():
    with pytest.raises(ValueError):
        parse_spec("kind=nope")
    with pytest.raises(ValueError):
        parse_spec("kind=stall,bogus=1")


def test_rule_trigger_counting():
    inj = FaultInjector.from_spec("kind=stall,after=2,times=1")
    acts = [inj.on_frame("a", "s", f"r{i}", {}) for i in range(5)]
    # fires exactly once, on the third matching frame; r2 is then
    # black-holed but the rule is spent for other streams
    assert acts[0] is None and acts[1] is None
    assert acts[2] == ("drop",)
    assert inj.on_frame("a", "s", "r2", {}) == ("drop",)  # stalled rid
    assert acts[3] is None and acts[4] is None
    assert inj.fired == {"stall": 1}


def test_seeded_prob_is_deterministic():
    fires = []
    for _ in range(2):
        inj = FaultInjector.from_spec("kind=err,prob=0.5,times=*", seed=7)
        fires.append([inj.on_frame("a", None, f"r{i}", {}) is not None
                      for i in range(20)])
    assert fires[0] == fires[1]
    assert any(fires[0]) and not all(fires[0])


def test_injector_from_env(monkeypatch):
    monkeypatch.setenv("DYN_FAULTS", "kind=connect_refused,times=1")
    client = TransportClient()
    assert client.fault_injector is not None
    with pytest.raises(ConnectionRefusedError):
        client.fault_injector.check_connect("anywhere:1")
    monkeypatch.delenv("DYN_FAULTS")
    assert TransportClient().fault_injector is None


# -- deadlines ---------------------------------------------------------------


async def _serve(handler, subject="ns.c.gen-1"):
    server = TransportServer()
    server.register(subject, FnEngine(handler))
    addr = await server.start()
    return server, addr, subject


async def test_idle_timeout_turns_stall_into_stream_err():
    async def stalls(request, context):
        yield {"i": 0}
        yield {"i": 1}
        await asyncio.Event().wait()  # wedged but connected

    server, addr, subject = await _serve(stalls)
    client = TransportClient(idle_timeout=0.2)
    got, err = [], None
    try:
        async for x in client.request(addr, subject, {}):
            got.append(x)
    except ConnectionError as e:
        err = str(e)
    finally:
        await client.close()
        await server.stop()
    assert got == [{"i": 0}, {"i": 1}]
    assert err == STREAM_ERR_MSG  # the Migration trigger, not a hang
    assert client.stats["idle_timeouts"] == 1


async def test_overall_deadline_bounds_slow_stream():
    async def drips(request, context):
        for i in range(1000):
            yield {"i": i}
            await asyncio.sleep(0.05)

    server, addr, subject = await _serve(drips)
    client = TransportClient(deadline=0.3)
    got, err = [], None
    try:
        async for x in client.request(addr, subject, {}):
            got.append(x)
    except ConnectionError as e:
        err = str(e)
    finally:
        await client.close()
        await server.stop()
    # frames kept arriving inside the idle window, but the total budget
    # still cut the stream off
    assert 1 <= len(got) < 20
    assert err == STREAM_ERR_MSG
    assert client.stats["deadline_exceeded"] == 1


async def test_deadline_header_aborts_server_handler():
    aborted = asyncio.Event()

    async def wedged(request, context):
        try:
            yield {"i": 0}
            await asyncio.Event().wait()
        except asyncio.CancelledError:
            aborted.set()
            raise

    server, addr, subject = await _serve(wedged)
    client = TransportClient(deadline=0.2)
    try:
        with pytest.raises(ConnectionError):
            async for _ in client.request(addr, subject, {}):
                pass
        # the propagated header fires server-side even though the client
        # never sent an explicit cancel success path
        await asyncio.wait_for(aborted.wait(), 2)
    finally:
        await client.close()
        await server.stop()


async def test_per_call_override_beats_client_default():
    async def quick(request, context):
        yield {"ok": 1}

    server, addr, subject = await _serve(quick)
    client = TransportClient(idle_timeout=0.05)
    try:
        # disable per-call: a handler slower than the client default
        server.register(subject, FnEngine(
            lambda req, ctx: _slow_then_ok()))
        out = [x async for x in client.request(addr, subject, {},
                                               idle_timeout=2.0)]
        assert out == [{"ok": 1}]
    finally:
        await client.close()
        await server.stop()


async def _slow_then_ok():
    await asyncio.sleep(0.3)
    yield {"ok": 1}


# -- connect retry/backoff + injected refusal --------------------------------


async def test_connect_retry_recovers_after_transient_refusal():
    async def ok(request, context):
        yield {"ok": 1}

    server, addr, subject = await _serve(ok)
    inj = FaultInjector.from_spec(
        f"kind=connect_refused,addr={addr},times=2")
    client = TransportClient(connect_retries=3, connect_backoff_base=0.01,
                             fault_injector=inj)
    try:
        out = [x async for x in client.request(addr, subject, {})]
        assert out == [{"ok": 1}]
        assert client.stats["connect_retries"] == 2
        assert client.stats["connect_failures"] == 0
        assert inj.fired["connect_refused"] == 2
    finally:
        await client.close()
        await server.stop()


async def test_connect_exhaustion_raises_connect_error():
    inj = FaultInjector.from_spec("kind=connect_refused,times=*")
    client = TransportClient(connect_retries=1, connect_backoff_base=0.01,
                             fault_injector=inj)
    with pytest.raises(ConnectError):
        async for _ in client.request("127.0.0.1:1", "s", {}):
            pass
    assert client.stats["connect_failures"] == 1
    await client.close()


# -- injected wire faults ----------------------------------------------------


async def test_injected_disconnect_surfaces_stream_err():
    async def forever(request, context):
        i = 0
        while True:
            yield {"i": i}
            i += 1
            await asyncio.sleep(0.01)

    server, addr, subject = await _serve(forever)
    inj = FaultInjector.from_spec("kind=disconnect,after=3")
    client = TransportClient(fault_injector=inj)
    got, err = [], None
    try:
        async for x in client.request(addr, subject, {}):
            got.append(x)
    except ConnectionError as e:
        err = str(e)
    finally:
        await client.close()
        await server.stop()
    assert len(got) == 3
    assert err == STREAM_ERR_MSG


async def test_injected_error_frame():
    async def forever(request, context):
        while True:
            yield {}
            await asyncio.sleep(0.01)

    server, addr, subject = await _serve(forever)
    inj = FaultInjector.from_spec("kind=err,error=chaos-monkey,after=1")
    client = TransportClient(fault_injector=inj)
    err = None
    try:
        async for _ in client.request(addr, subject, {}):
            pass
    except ConnectionError as e:
        err = str(e)
    finally:
        await client.close()
        await server.stop()
    assert err == "chaos-monkey"


# -- rx decode errors (satellite) --------------------------------------------


async def test_corrupt_frame_logged_and_counted(caplog):
    import struct

    from dynamo_tpu.runtime import codec

    reqs: list = []

    async def fake_server(reader, writer):
        await codec.read_frame(reader)          # the request
        msg = {"t": "data", "rid": reqs[0], "payload": {"ok": 1}}
        # one good frame, then garbage (0xc1 is never valid msgpack)
        codec.write_frame(writer, msg)
        writer.write(struct.pack(">I", 4) + b"\xc1\xc1\xc1\xc1")
        await writer.drain()
        # stay connected until the CLIENT drops the link (that is what
        # the test asserts), then close this end: since Python 3.12
        # `Server.wait_closed()` below waits for every connection, and a
        # handler that returns with its writer open keeps one forever
        await reader.read()
        writer.close()

    server = await asyncio.start_server(fake_server, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    addr = f"127.0.0.1:{port}"
    client = TransportClient()
    got, err = [], None

    # capture the rid the client assigns so the fake server can echo it
    async def run():
        nonlocal err
        try:
            async for x in client.request(addr, "s", {}):
                got.append(x)
        except ConnectionError as e:
            err = str(e)

    import dynamo_tpu.runtime.transport as tmod

    orig_send = tmod._Connection.send

    async def spy_send(self, obj):
        if obj.get("t") == "req":
            reqs.append(obj["rid"])
        await orig_send(self, obj)

    tmod._Connection.send = spy_send
    try:
        with caplog.at_level("WARNING"):
            await run()
    finally:
        tmod._Connection.send = orig_send
        await client.close()
        server.close()
        await server.wait_closed()
    assert got == [{"ok": 1}]
    assert err == STREAM_ERR_MSG
    assert client.stats["decode_errors"] == 1
    assert "undecodable frame from " + addr in caplog.text


# -- bounded shutdown (satellite) --------------------------------------------


async def test_server_stop_flushes_transports():
    async def ok(request, context):
        yield {"ok": 1}

    server, addr, subject = await _serve(ok)
    client = TransportClient()
    try:
        out = [x async for x in client.request(addr, subject, {})]
        assert out == [{"ok": 1}]
        writers = list(server._conn_writers)
        assert writers
        t0 = asyncio.get_running_loop().time()
        await server.stop()
        assert asyncio.get_running_loop().time() - t0 < 2.5  # bounded
        assert all(w.is_closing() for w in writers)
    finally:
        await client.close()
        await server.stop()


# -- circuit breaker ---------------------------------------------------------


def test_breaker_state_machine():
    now = [0.0]
    br = CircuitBreaker(fail_limit=2, cooldown=5.0, clock=lambda: now[0])
    assert br.allow("w") and br.state("w") == CLOSED
    br.record_failure("w")
    assert br.allow("w")                        # one failure: still closed
    br.record_failure("w")
    assert br.state("w") == OPEN
    assert not br.allow("w")                    # filtered while cooling
    now[0] = 5.0
    assert br.allow("w")                        # half-open probe admitted
    assert br.state("w") == HALF_OPEN
    assert not br.allow("w")                    # only one probe per window
    br.record_failure("w")
    assert br.state("w") == OPEN                # probe failed: re-open
    now[0] = 10.0
    assert br.allow("w")
    br.record_success("w")
    assert br.state("w") == CLOSED
    assert br.allow("w") and br.allow("w")      # fully back in rotation
    snap = br.snapshot()
    assert snap["transitions"][OPEN] == 2
    assert snap["instances"]["w"]["state"] == CLOSED


# -- PushRouter: rr order, breaker filtering, retry-next-instance ------------


def _static_instances(rt, n, port_of=lambda i: 1):
    return [Instance("ns", "c", "gen", i + 1, f"127.0.0.1:{port_of(i)}")
            for i in range(n)]


async def test_round_robin_starts_at_first_instance():
    rt = await DistributedRuntime.create(RuntimeConfig())
    try:
        order = []

        def mk(tag):
            async def gen(request, context):
                order.append(tag)
                yield {"from": tag}
            return gen

        ep = rt.namespace("ns").component("c").endpoint("gen")
        for i in range(3):
            await ep.serve(mk(i), instance_id=i + 1)
        client = await ep.client()
        await client.start()
        router = PushRouter(client)
        for _ in range(6):
            async for _x in router.generate({}, Context()):
                pass
        # off-by-one regression: instance 0 must serve the FIRST request
        assert order == [0, 1, 2, 0, 1, 2]
    finally:
        await rt.close()


async def test_router_retries_next_instance_on_connect_failure():
    rt = await DistributedRuntime.create(RuntimeConfig(
        connect_retries=0, breaker_fail_limit=1, breaker_cooldown=30.0))
    try:
        ep = rt.namespace("ns").component("c").endpoint("gen")

        async def ok(request, context):
            yield {"from": "live"}

        served = await ep.serve(ok, instance_id=2)
        # a dead instance registered FIRST so round-robin hits it first
        dead = Instance("ns", "c", "gen", 1, "127.0.0.1:1")
        await rt.store.put(dead.etcd_key, dead.to_json(), rt.lease_id)
        client = await ep.client()
        await client.start()
        for _ in range(50):
            if len(client.instances()) == 2:
                break
            await asyncio.sleep(0.02)
        router = PushRouter(client)
        out = [x async for x in router.generate({}, Context())]
        assert out == [{"from": "live"}]                 # no error surfaced
        assert rt.transport_client.stats["route_retries"] >= 1
        assert rt.breaker.state(dead.subject) == OPEN    # fail_limit=1
        # breaker now filters the dead instance: next request goes straight
        # to the live one with no extra dial
        retries_before = rt.transport_client.stats["route_retries"]
        out = [x async for x in router.generate({}, Context())]
        assert out == [{"from": "live"}]
        assert rt.transport_client.stats["route_retries"] == retries_before
        assert served.instance.subject in \
            rt.breaker.snapshot()["instances"] or True
    finally:
        await rt.close()


async def test_half_open_probe_survives_candidate_counting():
    """Regression: direct() used to run _candidates() twice per routing
    decision (attempt count + select). allow() is side-effectful — the
    counting pass consumed the one half-open probe per cooldown window
    and extended retry_at, so the select pass filtered the instance out
    again. With a healthy peer present, an opened instance was never
    probed and never rejoined rotation."""
    clock = [0.0]
    br = CircuitBreaker(fail_limit=1, cooldown=1.0, clock=lambda: clock[0])
    rt = await DistributedRuntime.create(RuntimeConfig(connect_retries=0))
    rt.breaker = br
    try:
        served_from = []

        def mk(tag):
            async def gen(request, context):
                served_from.append(tag)
                yield {"from": tag}
            return gen

        ep = rt.namespace("ns").component("c").endpoint("gen")
        s1 = await ep.serve(mk(1), instance_id=1)
        await ep.serve(mk(2), instance_id=2)
        client = await ep.client()
        await client.start()
        router = PushRouter(client)
        subject = s1.instance.subject
        br.record_failure(subject)                 # opened (fail_limit=1)
        assert br.state(subject) == OPEN
        for _ in range(6):
            clock[0] += 1.5                        # fresh probe window
            async for _x in router.generate({}, Context()):
                pass
            if br.state(subject) == CLOSED:
                break
        # the probe must actually land on the opened instance and close
        # its breaker while the healthy peer keeps serving
        assert br.state(subject) == CLOSED
        assert 1 in served_from
    finally:
        await rt.close()


async def test_breaker_half_open_recovers_instance():
    clock = [0.0]
    br = CircuitBreaker(fail_limit=1, cooldown=1.0, clock=lambda: clock[0])
    rt = await DistributedRuntime.create(RuntimeConfig(connect_retries=0))
    rt.breaker = br
    try:
        ep = rt.namespace("ns").component("c").endpoint("gen")

        async def ok(request, context):
            yield {"ok": 1}

        served = await ep.serve(ok, instance_id=1)
        subject = served.instance.subject
        client = await ep.client()
        await client.start()
        router = PushRouter(client)
        br.record_failure(subject)            # opened by some earlier fault
        assert br.state(subject) == OPEN
        clock[0] = 1.5                        # cooldown elapsed
        out = [x async for x in router.generate({}, Context())]
        assert out == [{"ok": 1}]
        assert br.state(subject) == CLOSED    # successful probe closed it
    finally:
        await rt.close()


# -- service stats / metrics export ------------------------------------------


async def test_robustness_counters_in_service_stats_and_metrics():
    from dynamo_tpu.runtime.service_stats import ServiceClient

    rt = await DistributedRuntime.create(RuntimeConfig())
    try:
        ep = rt.namespace("ns").component("c").endpoint("generate")

        async def ok(request, context):
            yield {"ok": 1}

        await ep.serve(ok, instance_id=1)
        rt.transport_client.stats["idle_timeouts"] += 3   # simulated history
        rt.breaker.record_failure("w1")
        stats = await ServiceClient(rt).collect_services("ns", "c")
        (extras,) = stats.client_stats.values()
        assert extras["transport"]["idle_timeouts"] == 3
        assert extras["breaker"]["instances"]["w1"]["failures"] == 1
        text = rt.metrics.render()
        assert 'dynamo_transport_client_events{kind="idle_timeouts"} 3' \
            in text
        assert "dynamo_breaker_transitions" in text
        assert "dynamo_breaker_open_instances" in text
    finally:
        await rt.close()


# -- canary failure → deregistration (satellite) -----------------------------


async def test_fault_injected_canary_failures_deregister_instance_once():
    """fail_limit consecutive injected canary stalls must fire
    on_unhealthy exactly once, and the instance must leave the client's
    instance set exactly once."""
    rt = await DistributedRuntime.create(RuntimeConfig(
        health_check_enabled=True, health_check_interval=0.05,
        health_check_timeout=0.1))
    try:
        fail_limit = rt.health.config.fail_limit
        inj = FaultInjector.from_spec(
            f"kind=engine_stall,subject=wedge,times={fail_limit}")

        async def ok(request, context):
            yield {"token_ids": [1], "finish_reason": "stop"}

        engine = FaultyEngine(FnEngine(ok), inj, "wedge")
        ep = rt.namespace("ns").component("c").endpoint("generate")
        served = await ep.serve(engine, instance_id=9,
                                health_payload={"token_ids": [1]})
        client = await ep.client()
        await client.start()
        assert len(client.instances()) == 1
        deletes = []
        client.on_change(
            lambda kind, inst: deletes.append(inst) if kind == DELETE
            else None)
        unhealthy_calls = []

        def on_unhealthy(subject: str) -> None:
            unhealthy_calls.append(subject)
            asyncio.get_running_loop().create_task(served.shutdown())

        rt.health.on_unhealthy = on_unhealthy
        for _ in range(200):
            if deletes:
                break
            await asyncio.sleep(0.02)
        await asyncio.sleep(0.3)  # would catch duplicate deregistration
        assert inj.fired["engine_stall"] == fail_limit
        assert unhealthy_calls == [served.instance.subject]
        assert len(deletes) == 1
        assert client.instances() == []
        await client.stop()
    finally:
        await rt.close()


# -- disagg: stalled KV pull degrades to local serve -------------------------


async def test_stalled_kv_pull_falls_back_to_local_serve():
    from dynamo_tpu.disagg.handlers import DecodeWorkerHandler

    class _Engine:
        async def generate(self, request, context):
            yield {"token_ids": [7], "finish_reason": "stop"}

    class _PrefillRouter:
        async def generate(self, request, context):
            yield {"token_ids": [5],
                   "kv_transfer_params": {"instance_id": 12345,
                                          "transfer_id": "t1",
                                          "prefill_len": 2}}

    class _PullRouter:
        class client:
            @staticmethod
            def instances():
                return [object()]

        async def direct(self, request, instance_id, context=None):
            await asyncio.Event().wait()   # the wedged prefill worker
            yield {}

    handler = DecodeWorkerHandler.__new__(DecodeWorkerHandler)
    handler.engine = _Engine()
    handler.prefill_router = _PrefillRouter()
    handler.kv_pull_router = _PullRouter()
    handler.prefill_queue_client = None
    handler.pull_chunk_pages = 4
    handler.pull_deadline = 0.2
    handler.last_pull_path = None
    handler._prefix_hit_len = lambda toks: 0

    class _Always:
        def prefill_remote(self, n, hit):
            return True

    handler.disagg_router = _Always()
    t0 = asyncio.get_running_loop().time()
    out = [x async for x in handler.generate(
        {"token_ids": [1, 2], "stop": {"max_tokens": 4}}, Context())]
    # degraded to the local engine instead of hanging on the pull
    assert out == [{"token_ids": [7], "finish_reason": "stop"}]
    assert asyncio.get_running_loop().time() - t0 < 5.0


# -- one deadline budget per request (not per attempt) ------------------------


async def test_deadline_budget_shared_across_migration_replays():
    """The overall deadline is stamped on the Context once; Migration
    replays inherit the REMAINING time instead of restarting a full
    budget, so worst-case wall clock is ~deadline, not
    deadline x (migration_limit + 1)."""
    from dynamo_tpu.llm.migration import Migration

    async def drips(request, context):
        for i in range(1000):
            yield {"token_ids": [i]}
            await asyncio.sleep(0.05)

    server, addr, subject = await _serve(drips)
    client = TransportClient(deadline=0.3)

    class _Edge:
        async def generate(self, request, context):
            async for x in client.request(addr, subject, request, context):
                yield x

    mig = Migration(migration_limit=5).link(_Edge())
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    try:
        with pytest.raises(ConnectionError):
            async for _ in mig.generate(
                    {"stop": {"max_tokens": 100}}, Context()):
                pass
        # per-attempt budgets would stretch this to ~6 x 0.3 s
        assert loop.time() - t0 < 0.9
        assert mig.stats["exhausted"] == 1
    finally:
        await client.close()
        await server.stop()


async def test_spent_budget_raises_distinct_error_before_dialing():
    """A request whose shared budget is already gone fails immediately
    with DEADLINE_ERR_MSG — not STREAM_ERR_MSG, so routers don't feed
    the breaker for an instance that never saw a byte."""
    from dynamo_tpu.runtime.transport import DEADLINE_ERR_MSG

    client = TransportClient(deadline=5.0)
    ctx = Context()
    ctx.deadline = asyncio.get_running_loop().time() - 1.0  # already spent
    with pytest.raises(ConnectionError) as ei:
        async for _ in client.request("127.0.0.1:1", "s", {}, ctx):
            pass
    assert str(ei.value) == DEADLINE_ERR_MSG
    assert client.stats["deadline_exceeded"] == 1
    assert client.stats["connect_retries"] == 0     # never dialed
    await client.close()


# -- dial loop: deadline bound + negative cache -------------------------------


async def test_deadline_bounds_dial_retries():
    inj = FaultInjector.from_spec("kind=connect_refused,times=*")
    client = TransportClient(deadline=0.2, connect_retries=50,
                             connect_backoff_base=0.2,
                             connect_backoff_max=0.2,
                             connect_neg_cache=0.0, fault_injector=inj)
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    with pytest.raises(ConnectError):
        async for _ in client.request("127.0.0.1:1", "s", {}):
            pass
    # without the bound this would sit through ~50 x 0.2 s of backoff
    assert loop.time() - t0 < 1.5
    await client.close()


async def test_negative_cache_fails_queued_dials_fast():
    inj = FaultInjector.from_spec("kind=connect_refused,times=*")
    client = TransportClient(connect_retries=2, connect_backoff_base=0.05,
                             connect_neg_cache=5.0, fault_injector=inj)

    async def one():
        with pytest.raises(ConnectError):
            async for _ in client.request("127.0.0.1:1", "s", {}):
                pass

    loop = asyncio.get_running_loop()
    t0 = loop.time()
    await asyncio.gather(*(one() for _ in range(5)))
    # the first caller pays one full retry cycle; the four queued on the
    # same dial lock hit the poisoned address and fail fast instead of
    # serially re-running the backoff cycle
    assert loop.time() - t0 < 0.5
    assert client.stats["connect_failures"] == 5
    assert inj.fired["connect_refused"] == 3        # one dial cycle total
    await client.close()


# -- disagg: abandoned pulls release the pinned transfer ----------------------


async def test_cancelled_device_pull_releases_pinned_pages():
    """The pull deadline cancels _pull_kv with wait_for; CancelledError
    is not Exception, so the device path must release the transfer it
    took explicitly or the prefill engine's pages stay pinned for a
    whole transfer_ttl."""
    from dynamo_tpu.disagg import handlers as H

    released = []

    class _SrcEngine:
        def take_transfer(self, tid):
            return [1, 2], 8

        async def read_kv_pages_device(self, pages):
            await asyncio.Event().wait()            # wedged device gather

        def complete_transfer(self, tid):
            released.append(tid)

    class _Src:
        engine = _SrcEngine()

    handler = H.DecodeWorkerHandler.__new__(H.DecodeWorkerHandler)
    handler.engine = None
    handler.kv_pull_router = None
    handler.last_pull_path = None
    H._LOCAL_PREFILL[777] = _Src()
    try:
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(
                handler._pull_kv({"instance_id": 777, "transfer_id": "t9"},
                                 Context()), 0.2)
        assert released == ["t9"]
    finally:
        H._LOCAL_PREFILL.pop(777, None)


async def test_kv_pull_abort_releases_transfer():
    from dynamo_tpu.disagg.handlers import PrefillWorkerHandler

    released = []

    class _Engine:
        def take_transfer(self, tid):
            raise AssertionError("abort must not (re)take the transfer")

        def complete_transfer(self, tid):
            released.append(tid)

    h = PrefillWorkerHandler(_Engine(), instance_id=1)
    out = [x async for x in h.kv_pull(
        {"transfer_id": "t1", "abort": True}, Context())]
    assert out == [{"aborted": True}]
    assert released == ["t1"]


async def test_failed_pull_sends_abort_to_remote_worker():
    """When the pull fails and the decode worker degrades to local
    serve, it must tell the owning prefill worker to drop its pin now
    (best effort) instead of leaving the pages pinned until the TTL
    reaper fires."""
    from dynamo_tpu.disagg.handlers import DecodeWorkerHandler

    aborts = []

    class _Engine:
        async def generate(self, request, context):
            yield {"token_ids": [7], "finish_reason": "stop"}

    class _PrefillRouter:
        async def generate(self, request, context):
            yield {"token_ids": [5],
                   "kv_transfer_params": {"instance_id": 42,
                                          "transfer_id": "tx",
                                          "prefill_len": 2}}

    class _PullRouter:
        class client:
            @staticmethod
            def instances():
                return [object()]

        async def direct(self, request, instance_id, context=None):
            if request.get("abort"):
                aborts.append(request["transfer_id"])
                yield {"aborted": True}
                return
            raise ConnectionError("wire down")
            yield {}  # pragma: no cover — makes this an async generator

    class _Always:
        def prefill_remote(self, n, hit):
            return True

    handler = DecodeWorkerHandler.__new__(DecodeWorkerHandler)
    handler.engine = _Engine()
    handler.prefill_router = _PrefillRouter()
    handler.kv_pull_router = _PullRouter()
    handler.prefill_queue_client = None
    handler.pull_chunk_pages = 4
    handler.pull_deadline = 2.0
    handler.last_pull_path = None
    handler._prefix_hit_len = lambda toks: 0
    handler.disagg_router = _Always()
    out = [x async for x in handler.generate(
        {"token_ids": [1, 2], "stop": {"max_tokens": 4}}, Context())]
    assert out == [{"token_ids": [7], "finish_reason": "stop"}]
    for _ in range(200):                            # fire-and-forget task
        if aborts:
            break
        await asyncio.sleep(0.01)
    assert aborts == ["tx"]
