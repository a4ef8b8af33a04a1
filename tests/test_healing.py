"""Self-healing worker lifecycle (docs/robustness.md "Watchdog &
self-healing" / "Degraded control plane") — the `make heal-smoke` body.

Layers, cheapest first:

- off-by-default pins: with no env knobs set, none of the healing
  machinery exists — no watchdog thread, no store fault seam, no
  revalidation task, no gap resync. Unarmed must mean byte-identical.
- watchdog: a wedged dispatch (seeded `dispatch_wedge` fault) trips the
  monitor thread exactly once, with the diagnosis on the event plane
  and in `dynamo_watchdog_trips_total{cause}`; an idle engine never
  accrues silence into a trip.
- quarantine: deregister → abort streams → flag engine; the instance
  leaves every client's snapshot and its breaker entry dies with it.
- supervisor: quarantined workers are reaped + respawned with backoff,
  crash loops hit the budget and give up loudly, and scale-downs drain
  corpses before healthy replicas.
- degraded control plane: seeded `store_outage` makes store ops raise;
  the lease reaper pauses; routers serve from the stale snapshot while
  the revalidation loop measures staleness and repairs missed deletes;
  KV-event gaps escalate to a full per-worker index resync.
- doctor preflight: --json verdicts and per-kind exit codes.

The end-to-end wedge-a-worker-mid-stream scenario lives in
tests/test_chaos.py (real sockets, Migration replay).
"""

import asyncio
import json

import pytest

from dynamo_tpu.engine.watchdog import (
    WATCHDOG_EVENTS_SUBJECT,
    DispatchWatchdog,
    watchdog_from_env,
)
from dynamo_tpu.mocker.engine import MockEngine, MockEngineConfig
from dynamo_tpu.runtime.breaker import CircuitBreaker
from dynamo_tpu.runtime.config import RuntimeConfig
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.faults import FaultInjector
from dynamo_tpu.runtime.store import MemoryStore
from dynamo_tpu.worker.quarantine import QUARANTINE_EXIT_CODE, quarantine_worker

pytestmark = pytest.mark.tier0

BS = 16


async def make_rt(**kw) -> DistributedRuntime:
    return await DistributedRuntime.create(
        RuntimeConfig(store_url="memory", **kw))


def make_request(tokens, max_tokens=4):
    return {"token_ids": tokens, "model": "m",
            "stop": {"max_tokens": max_tokens}, "sampling": {}}


def make_mock(worker_id=1, speedup=200.0):
    return MockEngine(MockEngineConfig(
        block_size=BS, worker_id=worker_id, speedup=speedup,
        total_kv_blocks=64))


async def noop_engine(request, context):
    yield {"token_ids": [0]}


# -- off-by-default pins -----------------------------------------------------


def test_healing_machinery_off_by_default(monkeypatch):
    """Unarmed ⇒ byte-identical: no watchdog, no fault seams, no
    revalidation task, no gap resync. Every healing path must be opted
    into explicitly."""
    from dynamo_tpu.engine.watchdog import ENV_STALL
    from dynamo_tpu.router.kv_router import KvRouterConfig
    from dynamo_tpu.runtime.faults import ENV_SPEC

    monkeypatch.delenv(ENV_STALL, raising=False)
    monkeypatch.delenv(ENV_SPEC, raising=False)
    eng = make_mock()
    assert watchdog_from_env(eng) is None
    monkeypatch.setenv(ENV_STALL, "0")
    assert watchdog_from_env(eng) is None
    monkeypatch.setenv(ENV_STALL, "banana")
    assert watchdog_from_env(eng) is None
    assert eng.fault_injector is None           # no DYN_FAULTS
    assert MemoryStore().fault_injector is None
    assert KvRouterConfig().gap_resync is False
    assert RuntimeConfig().instance_revalidate_s == 0.0
    monkeypatch.setenv(ENV_STALL, "2.5")
    wd = watchdog_from_env(eng, instance="x")
    assert wd is not None and wd.stall_s == 2.5 and wd._thread is None


# -- watchdog ----------------------------------------------------------------


async def test_watchdog_trips_on_wedged_dispatch():
    """Seeded dispatch_wedge parks the mock scheduler with work pending;
    the watchdog must trip once, publish to `watchdog_events`, bump the
    cause-labelled counter, and invoke on_trip on the event loop."""
    rt = await make_rt()
    eng = make_mock(worker_id=1)
    eng.fault_injector = FaultInjector.from_spec("kind=dispatch_wedge")
    sub = await rt.events.subscribe(WATCHDOG_EVENTS_SUBJECT)
    trips: list[dict] = []
    wd = DispatchWatchdog(eng, 0.25, runtime=rt, instance="1",
                          on_trip=trips.append)
    consume = None
    try:
        wd.start()

        async def _consume():
            async for _ in eng.generate(make_request(list(range(BS))),
                                        Context()):
                pass

        consume = asyncio.get_running_loop().create_task(_consume())
        for _ in range(200):
            if wd.tripped is not None and trips:
                break
            await asyncio.sleep(0.05)
        assert wd.tripped is not None, "watchdog never tripped"
        assert eng.fault_injector.fired["dispatch_wedge"] == 1
        ev = wd.tripped
        assert ev["instance"] == "1"
        assert ev["pending"] >= 1
        assert ev["stalled_s"] >= 0.25
        assert "dispatch watchdog" in ev["detail"]
        # published on the event plane for fleet observers
        msg = await asyncio.wait_for(sub.queue.get(), 2.0)
        assert msg["payload"] == ev
        # on_trip ran on the loop with the same event
        assert trips == [ev]
        # cause-labelled counter renders on /metrics
        assert wd._counter.get(cause=ev["cause"]) == 1
        assert "dynamo_watchdog_trips_total" in rt.metrics.render()
    finally:
        wd.stop()
        if consume is not None:
            consume.cancel()
        await eng.close()
        await rt.close()


async def test_watchdog_idle_engine_never_trips():
    """No work pending ⇒ silence is idleness, not a wedge."""
    eng = make_mock()
    wd = DispatchWatchdog(eng, 0.1, instance="idle")
    try:
        wd.start()
        await asyncio.sleep(0.5)   # many stall windows, zero work
        assert wd.tripped is None
    finally:
        wd.stop()
        await eng.close()


# -- quarantine --------------------------------------------------------------


async def test_quarantine_deregisters_and_flags_engine():
    rt = await make_rt()
    try:
        ep = rt.namespace("ns").component("c").endpoint("gen")
        eng = make_mock(worker_id=7)
        served = await ep.serve(eng, instance_id=7)
        client = await ep.client()
        await client.start()
        await client.wait_ready()
        assert len(client.instances()) == 1
        await quarantine_worker(rt, served, eng, reason="test",
                                exit_process=False)
        assert getattr(eng, "_quarantined", False) is True
        for _ in range(100):
            if not client.instances():
                break
            await asyncio.sleep(0.02)
        assert client.instances() == []   # instance key deleted
        assert QUARANTINE_EXIT_CODE == 44
        await client.stop()
    finally:
        await rt.close()


# -- supervisor: respawn / giveup / drain ordering ---------------------------


def _sup_config(**kw):
    from dynamo_tpu.planner.supervisor import SupervisorConfig

    base = dict(mock_speedup=200.0, drain_grace_s=0.2,
                health_poll_s=0.03, respawn_backoff_base=0.01,
                respawn_backoff_max=0.05)
    base.update(kw)
    return SupervisorConfig(**base)


async def _scale(sup, n, revision):
    assert await sup.apply({"revision": revision, "targets": [
        {"component": "backend", "sub_component_type": "decode",
         "desired_replicas": n}]})


async def test_supervisor_respawns_quarantined_worker():
    from dynamo_tpu.planner.supervisor import FleetSupervisor

    rt = await make_rt()
    sup = await FleetSupervisor(rt, _sup_config()).start()
    pool = ("backend", "decode")
    try:
        await _scale(sup, 1, 1)
        old = sup.pools[pool][0]
        # the watchdog's task-mode endgame: engine flagged _quarantined
        old.engine._quarantined = True
        for _ in range(200):
            ws = sup.pools.get(pool, [])
            if len(ws) == 1 and ws[0].instance_id != old.instance_id:
                break
            await asyncio.sleep(0.02)
        ws = sup.pools[pool]
        assert len(ws) == 1 and ws[0].instance_id != old.instance_id
        respawns = [e for e in sup.scale_events
                    if e.get("direction") == "respawn"]
        assert respawns and respawns[0]["cause"] == "quarantined"
        assert respawns[0]["dead_instance"] == old.instance_id
        assert respawns[0]["new_instance"] == ws[0].instance_id
        assert sup._c_events.get(direction="respawn") >= 1
    finally:
        await sup.stop()
        await rt.close()


async def test_supervisor_crash_loop_budget_gives_up():
    """A worker that wedges instantly on every respawn needs an
    operator, not a supervisor hammering it: after `crash_loop_budget`
    respawns inside the window the pool is written off, loudly."""
    from dynamo_tpu.planner.supervisor import FleetSupervisor

    rt = await make_rt()
    sup = await FleetSupervisor(rt, _sup_config(
        crash_loop_budget=2, crash_loop_window_s=60.0,
        respawn_backoff_base=0.0)).start()
    pool = ("backend", "decode")
    try:
        await _scale(sup, 1, 1)
        for _ in range(400):
            if any(e.get("direction") == "giveup"
                   for e in sup.scale_events):
                break
            ws = sup.pools.get(pool, [])
            if ws:
                ws[0].engine._quarantined = True
            await asyncio.sleep(0.02)
        giveups = [e for e in sup.scale_events
                   if e.get("direction") == "giveup"]
        assert giveups, sup.scale_events
        assert giveups[0]["respawns_in_window"] >= 2
        assert sup._c_events.get(direction="giveup") == 1
        # written off: the pool stays empty, no further respawns
        await asyncio.sleep(0.2)
        assert sup.replicas("backend", "decode") == 0
        assert len(giveups) == 1   # logged/recorded once, not per poll
    finally:
        await sup.stop()
        await rt.close()


async def test_scale_down_drains_dead_replicas_before_healthy():
    """Regression for the drain-ordering bug: scaling 2→1 with a
    quarantined corpse in the pool must collect the corpse and keep the
    healthy replica — never tear down a live worker while a dead one
    still holds a slot."""
    from dynamo_tpu.planner.supervisor import FleetSupervisor

    rt = await make_rt()
    # respawn off so the health loop doesn't race the scale-down
    sup = await FleetSupervisor(rt, _sup_config(respawn=False)).start()
    pool = ("backend", "decode")
    try:
        await _scale(sup, 2, 1)
        dead, healthy = sup.pools[pool]
        dead.engine._quarantined = True
        await _scale(sup, 1, 2)
        ws = sup.pools[pool]
        assert len(ws) == 1
        assert ws[0].instance_id == healthy.instance_id
        assert not getattr(ws[0].engine, "_quarantined", False)
    finally:
        await sup.stop()
        await rt.close()


# -- breaker ↔ quarantine ----------------------------------------------------


def test_breaker_reset_unit():
    t = [0.0]
    b = CircuitBreaker(fail_limit=1, cooldown=100.0, clock=lambda: t[0])
    b.record_failure("w")
    assert b.state("w") == "open" and not b.allow("w")
    assert b.reset("w") is True
    assert b.state("w") == "closed" and b.allow("w")
    assert b.reset("w") is False        # entry really gone
    # lifetime transition counters survive the reset
    assert b.snapshot()["transitions"]["open"] == 1


async def test_breaker_entry_purged_on_deregistration_then_respawn():
    """A respawned worker under the same subject must start closed —
    not inherit the corpse's open breaker and wait out a half-open
    probe cooldown it never earned."""
    rt = await make_rt(breaker_cooldown=300.0)   # cooldown ≫ test
    try:
        ep = rt.namespace("ns").component("c").endpoint("gen")
        served = await ep.serve(noop_engine, instance_id=5)
        subject = served.instance.subject
        client = await ep.client()
        await client.start()
        await client.wait_ready()
        assert len(client.instances()) == 1
        for _ in range(5):
            rt.breaker.record_failure(subject)
        assert rt.breaker.state(subject) == "open"
        assert not rt.breaker.allow(subject)     # cooldown not elapsed
        # quarantine/scale-down endgame: deregistration purges the entry
        await served.shutdown()
        for _ in range(100):
            if rt.breaker.state(subject) == "closed" \
                    and not client.instances():
                break
            await asyncio.sleep(0.02)
        assert rt.breaker.state(subject) == "closed"
        # respawn under the same subject: admitted immediately, no
        # half-open probe gate
        await ep.serve(noop_engine, instance_id=5)
        assert rt.breaker.allow(subject)
        assert rt.breaker.state(subject) == "closed"
        await client.stop()
    finally:
        await rt.close()


# -- degraded control plane --------------------------------------------------


async def test_store_outage_faults_and_reaper_pause():
    store = MemoryStore()
    lease = await store.create_lease(0.25)
    await store.put("k", b"v", lease)
    store.fault_injector = FaultInjector.from_spec(
        "kind=store_outage,times=2")
    assert store.fault_injector.outage_active()
    with pytest.raises(ConnectionError):
        await store.put("k2", b"v")
    with pytest.raises(ConnectionError):
        await store.get("k")
    # rules exhausted: the store heals
    assert not store.fault_injector.outage_active()
    assert (await store.get("k")).value == b"v"

    # unlimited outage: the reaper must NOT expire leases (a down
    # coordinator expires nothing — keepalives simply never arrive)
    store.fault_injector = FaultInjector.from_spec(
        "kind=store_outage,times=*")
    await asyncio.sleep(0.6)           # well past the 0.25 s ttl
    assert "k" in store._data
    store.fault_injector = None        # coordinator back: reaping resumes
    for _ in range(100):
        if "k" not in store._data:
            break
        await asyncio.sleep(0.05)
    assert "k" not in store._data


async def test_store_outage_rule_targets_keyspace():
    store = MemoryStore()
    store.fault_injector = FaultInjector.from_spec(
        "kind=store_outage,addr=v1/instances/*,times=1")
    await store.put("v1/models/x", b"v")     # other keyspaces untouched
    with pytest.raises(ConnectionError):
        await store.put("v1/instances/ns/c/gen/1", b"v")


async def test_stale_while_revalidate_degradation_and_recovery():
    """Store down ⇒ the snapshot keeps serving, the runtime flags
    DEGRADED with a growing staleness clock (gauges included); store
    back ⇒ one recovery log and the flag clears."""
    rt = await make_rt(instance_revalidate_s=0.03)
    try:
        ep = rt.namespace("ns").component("c").endpoint("gen")
        await ep.serve(noop_engine, instance_id=3)
        client = await ep.client()
        await client.start()
        await client.wait_ready()
        assert len(client.instances()) == 1
        assert rt.store_staleness_s() == 0.0
        assert "dynamo_store_degraded 0" in rt.metrics.render()
        # outage only on the revalidation read path; watches stay up
        rt.store.fault_injector = FaultInjector.from_spec(
            "kind=store_outage,subject=store.get_prefix,times=*")
        for _ in range(100):
            if rt._store_degraded_since is not None:
                break
            await asyncio.sleep(0.02)
        assert rt._store_degraded_since is not None
        assert rt.store_staleness_s() > 0.0
        # the request path never touched the store: snapshot still serves
        assert len(client.instances()) == 1
        render = rt.metrics.render()
        assert "dynamo_store_degraded 1" in render
        assert "dynamo_store_staleness_seconds" in render
        stats = rt._robustness_stats()["store"]
        assert stats["degraded"] is True and stats["staleness_s"] > 0
        # coordinator returns
        rt.store.fault_injector = None
        for _ in range(100):
            if rt._store_degraded_since is None:
                break
            await asyncio.sleep(0.02)
        assert rt._store_degraded_since is None
        assert "dynamo_store_degraded 0" in rt.metrics.render()
        await client.stop()
    finally:
        await rt.close()


async def test_revalidation_repairs_missed_delete_and_purges_breaker():
    """The revalidation loop reconciles the snapshot against the store:
    a DELETE the watch never delivered (dead watch, lossy reconnect) is
    applied on the next tick, breaker purge included."""
    rt = await make_rt(instance_revalidate_s=0.03)
    try:
        ep = rt.namespace("ns").component("c").endpoint("gen")
        served = await ep.serve(noop_engine, instance_id=9)
        subject = served.instance.subject
        client = await ep.client()
        await client.start()
        await client.wait_ready()
        assert len(client.instances()) == 1
        rt.breaker.record_failure(subject)
        client._watch.cancel()                 # watch goes dark
        await rt.store.delete(served.instance.etcd_key)
        for _ in range(100):
            if not client.instances():
                break
            await asyncio.sleep(0.02)
        assert client.instances() == []        # revalidation caught it
        assert rt.breaker.state(subject) == "closed"
        await client.stop()
    finally:
        await rt.close()


async def test_kv_event_gap_escalates_to_index_resync():
    """gap_resync=True: a jump in a worker's event_id drops that
    worker's slice of the prefix index and rebuilds it from the bus's
    retained tail — counted in dynamo_router_index_resyncs_total."""
    from dynamo_tpu.protocols import KV_STORED, KvCacheEvent, StoredBlock
    from dynamo_tpu.router.kv_router import (
        KvPushRouter,
        KvRouterConfig,
        kv_events_subject,
    )
    from dynamo_tpu.tokens import SEED_HASH

    rt = await make_rt()
    kv_push = None
    try:
        ep = rt.namespace("ns").component("c").endpoint("gen")
        client = await ep.client()
        kv_push = await KvPushRouter(
            client, rt.events,
            KvRouterConfig(block_size=BS, gap_resync=True)).start()
        subject = kv_events_subject("ns", "c")
        worker = (7, 0)

        def stored(eid, parent, seq, local):
            return KvCacheEvent(
                kind=KV_STORED, worker_id=7, dp_rank=0, event_id=eid,
                parent_seq_hash=parent,
                blocks=[StoredBlock(seq, local)]).to_dict()

        rt.events.publish_nowait(subject, stored(1, SEED_HASH, 101, 201))
        rt.events.publish_nowait(subject, stored(2, 101, 102, 202))
        # events 3 and 4 lost by the bus: gap of 2 on event 5
        rt.events.publish_nowait(subject, stored(5, 102, 103, 203))
        idx = kv_push.router.indexer
        for _ in range(200):
            if kv_push.router.metrics.index_resyncs.get(
                    worker="7:0") >= 1 and not kv_push._resyncing:
                break
            await asyncio.sleep(0.02)
        assert kv_push.router.metrics.index_resyncs.get(worker="7:0") >= 1
        assert idx.gaps.get(worker, 0) >= 2
        # the rebuild replayed the retained tail: the worker's blocks
        # are back in the tree (not left dropped)
        for _ in range(100):
            if any(w[0] == 7 for w in idx.tree.workers()):
                break
            await asyncio.sleep(0.02)
        assert any(w[0] == 7 for w in idx.tree.workers())
        assert "dynamo_router_index_resyncs_total" in rt.metrics.render()
    finally:
        if kv_push is not None:
            await kv_push.stop()
        await rt.close()


# -- doctor preflight: --json + exit codes -----------------------------------


def test_preflight_json_and_exit_codes(monkeypatch, capsys):
    from dynamo_tpu.doctor import preflight

    # healthy: rc 0, machine-readable verdict
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    monkeypatch.setattr(preflight, "probe_device",
                        lambda attempts, timeout_s: (cpu, None))
    assert preflight.main(["--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["ok"] is True and out["kind"] == "ok"
    assert out["exit_code"] == 0 and out["device"] == cpu

    # each diagnosis kind maps to its own exit code
    cases = [
        ("device preflight ran on 'cpu', not on 'tpu': the backend "
         "fell back", "other", 5),
        ("device preflight timed out", "timeout", 3),
        ("RESOURCE_EXHAUSTED: out of memory", "oom", 4),
        ("something else entirely", "other", 5),
    ]
    for verdict, kind, rc in cases:
        monkeypatch.setattr(preflight, "probe_device",
                            lambda a, t, v=verdict: (None, v))
        assert preflight.main(["--json"]) == rc, kind
        out = json.loads(capsys.readouterr().out)
        assert out["ok"] is False
        assert out["kind"] == kind and out["exit_code"] == rc
        # text mode returns the same rc
        assert preflight.main([]) == rc
        assert kind in capsys.readouterr().out
