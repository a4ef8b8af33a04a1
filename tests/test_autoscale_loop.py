"""The closed autoscaling loop (docs/autoscaling.md).

Three layers, cheapest first:

- schedule layer: the trafficgen artifact is DETERMINISTIC — same seed
  + config must serialize to byte-identical JSONL (the acceptance gate
  for replayable load tests), and every arrival pattern must produce a
  sane open-loop schedule.
- supervisor layer: targets written through the VirtualConnector are
  applied exactly once per revision (stale/duplicate revisions are
  no-ops, planner restarts resume rather than reset), scale-downs drain
  gracefully, and fleet state is observable.
- loop layer (`make autoscale-smoke`): frontend + supervisor + planner
  on live telemetry + trafficgen replaying a diurnal day — the planner
  must scale the mock fleet up on the ramp and back down after, the
  TTFT/ITL SLOs must never fast-burn after warmup, and every
  non-abandoned stream must complete with tokens identical to an
  unscaled reference replay (scale events may migrate streams, never
  corrupt them).
"""

import asyncio
import hashlib

import pytest

from dynamo_tpu.trafficgen import (
    TrafficConfig,
    build_schedule,
    prompt_text,
    schedule_from_jsonl,
    schedule_to_jsonl,
)
from dynamo_tpu.trafficgen.schedule import PATTERNS

# -- schedule layer ----------------------------------------------------------


def _md5(text: str) -> str:
    return hashlib.md5(text.encode()).hexdigest()


@pytest.mark.tier0
def test_schedule_bytes_deterministic():
    cfg = TrafficConfig(pattern="bursty", duration_s=30.0, base_rps=3.0,
                        seed=1234, prefix_fraction=0.4,
                        abandon_fraction=0.2)
    a = schedule_to_jsonl(cfg, build_schedule(cfg))
    b = schedule_to_jsonl(cfg, build_schedule(cfg))
    assert _md5(a) == _md5(b)          # byte-identical, not just equal
    other = TrafficConfig(pattern="bursty", duration_s=30.0, base_rps=3.0,
                          seed=1235, prefix_fraction=0.4,
                          abandon_fraction=0.2)
    assert _md5(schedule_to_jsonl(other, build_schedule(other))) != _md5(a)


@pytest.mark.tier0
def test_schedule_roundtrip_and_reserialize():
    cfg = TrafficConfig(pattern="diurnal", duration_s=20.0, base_rps=5.0,
                        seed=9, prefix_fraction=0.5, abandon_fraction=0.3)
    reqs = build_schedule(cfg)
    text = schedule_to_jsonl(cfg, reqs)
    cfg2, reqs2 = schedule_from_jsonl(text)
    assert cfg2 == cfg
    assert reqs2 == reqs
    assert schedule_to_jsonl(cfg2, reqs2) == text


@pytest.mark.tier0
def test_every_pattern_produces_sane_schedules():
    for pattern in PATTERNS:
        cfg = TrafficConfig(pattern=pattern, duration_s=30.0,
                            base_rps=4.0, seed=5,
                            prefix_fraction=1.0, abandon_fraction=1.0)
        reqs = build_schedule(cfg)
        assert len(reqs) > 10, pattern
        ats = [r.at for r in reqs]
        assert ats == sorted(ats), pattern
        assert 0 < ats[0] and ats[-1] <= cfg.duration_s, pattern
        for r in reqs:
            assert 1 <= r.isl <= cfg.isl_max
            assert 1 <= r.osl <= cfg.osl_max
            assert 0 <= r.prefix_id < cfg.num_prefixes   # fraction 1.0
            assert 1 <= r.abandon_after <= max(r.osl // 2, 1)
    with pytest.raises(ValueError):
        TrafficConfig(pattern="nope")


@pytest.mark.tier0
def test_bursty_pattern_actually_bursts():
    """The MMPP must visit both states: windows of storm-rate arrivals
    amid calm stretches (otherwise the autoscale gate isn't exercising
    scale-up at all)."""
    cfg = TrafficConfig(pattern="bursty", duration_s=120.0, base_rps=1.0,
                        burst_rps=20.0, burst_start_rate=0.1,
                        burst_stop_rate=0.5, seed=3)
    reqs = build_schedule(cfg)
    # per-second arrival counts: some seconds must be storm-dense while
    # the median second stays calm
    counts = [0] * 121
    for r in reqs:
        counts[int(r.at)] += 1
    assert max(counts) >= 8
    assert sorted(counts)[len(counts) // 2] <= 3


@pytest.mark.tier0
def test_prompt_text_shares_prefixes_exactly():
    cfg = TrafficConfig(prefix_len=16)
    reqs = build_schedule(TrafficConfig(
        pattern="constant", duration_s=10.0, base_rps=2.0,
        prefix_fraction=1.0, num_prefixes=1, prefix_len=16, seed=0))
    texts = [prompt_text(r, cfg) for r in reqs[:4]]
    prefixes = {" ".join(t.split()[:16]) for t in texts}
    assert len(prefixes) == 1          # byte-identical shared prefix
    for r, t in zip(reqs[:4], texts):
        assert len(t.split()) == 16 + r.isl
    solo = prompt_text(type(reqs[0])(index=0, at=0.0, isl=3, osl=1), cfg)
    assert solo.split() == ["u0w0", "u0w1", "u0w2"]


# -- supervisor layer --------------------------------------------------------


async def _mk_runtime(**kw):
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    return await DistributedRuntime.create(
        RuntimeConfig(store_url="memory", **kw))


@pytest.mark.tier0
async def test_supervisor_applies_targets_once_per_revision():
    from dynamo_tpu.planner.connector import TargetReplica, VirtualConnector
    from dynamo_tpu.planner.supervisor import FleetSupervisor, SupervisorConfig

    rt = await _mk_runtime()
    sup = await FleetSupervisor(rt, SupervisorConfig(
        mock_speedup=100.0, drain_grace_s=0.2)).start()
    conn = VirtualConnector(rt, "dynamo")
    try:
        await conn.set_component_replicas([
            TargetReplica("backend", "decode", 2),
            TargetReplica("backend_prefill", "prefill", 1)])
        for _ in range(200):
            if sup.replicas("backend", "decode") == 2 \
                    and sup.replicas("backend_prefill", "prefill") == 1:
                break
            await asyncio.sleep(0.02)
        assert sup.replicas("backend", "decode") == 2
        assert sup.replicas("backend_prefill", "prefill") == 1
        # a stale revision must be rejected without touching the pools
        assert not await sup.apply({
            "revision": 1, "targets": [
                {"component": "backend", "sub_component_type": "decode",
                 "desired_replicas": 9}]})
        assert sup.replicas("backend", "decode") == 2
        # replaying the CURRENT revision is a no-op too (watch replay
        # after a coordinator reset must not double-apply)
        cur = await conn.read_targets()
        assert not await sup.apply(cur)
        # scale down drains to the target
        await conn.set_component_replicas([
            TargetReplica("backend", "decode", 1),
            TargetReplica("backend_prefill", "prefill", 0)])
        for _ in range(200):
            if sup.replicas("backend", "decode") == 1 \
                    and sup.replicas("backend_prefill", "prefill") == 0:
                break
            await asyncio.sleep(0.02)
        assert sup.replicas("backend", "decode") == 1
        assert sup.replicas("backend_prefill", "prefill") == 0
        dirs = [e["direction"] for e in sup.scale_events]
        assert dirs.count("up") == 2 and dirs.count("down") == 2
        state = sup.fleet_state()
        assert state["applied_revision"] == 2
        assert len(state["pools"]["backend/decode"]) == 1
        # fleet state rides the _sys.stats scrape
        assert "supervisor" in rt.transport_server.extra_stats()
    finally:
        await sup.stop()
        await rt.close()


@pytest.mark.tier0
async def test_supervisor_survives_planner_restart():
    """VirtualConnector revisions RESUME after a planner restart (seeded
    from the store, never reset to zero) — so a supervisor that de-dupes
    on 'revision increased' keeps applying targets from the reborn
    planner instead of dropping them all as stale."""
    from dynamo_tpu.planner.connector import TargetReplica, VirtualConnector
    from dynamo_tpu.planner.supervisor import FleetSupervisor, SupervisorConfig

    rt = await _mk_runtime()
    sup = await FleetSupervisor(rt, SupervisorConfig(
        mock_speedup=100.0, drain_grace_s=0.2)).start()
    try:
        first = VirtualConnector(rt, "dynamo")
        await first.set_component_replicas([
            TargetReplica("backend", "decode", 2)])
        for _ in range(200):
            if sup.replicas("backend", "decode") == 2:
                break
            await asyncio.sleep(0.02)
        assert sup.applied_revision == 1
        # planner dies; its replacement starts with no in-memory state
        reborn = VirtualConnector(rt, "dynamo")
        await reborn.set_component_replicas([
            TargetReplica("backend", "decode", 3)])
        assert reborn.revision == 2    # resumed, not reset
        for _ in range(200):
            if sup.replicas("backend", "decode") == 3:
                break
            await asyncio.sleep(0.02)
        assert sup.replicas("backend", "decode") == 3
        assert sup.applied_revision == 2
    finally:
        await sup.stop()
        await rt.close()


# -- loop layer: the SLA gate ------------------------------------------------

# weak synthetic profile surfaces so single-digit RPS crosses replica
# thresholds: prefill 120 tok/s/chip flat; decode 20..60 tok/s/chip as
# kv_usage rises, itl 10..50 ms
_WEAK_PREFILL = {
    "isl": [8, 32, 128, 512],
    "ttft_ms": [8.0, 10.0, 14.0, 30.0],
    "thpt_per_chip": [120.0, 120.0, 120.0, 120.0],
}
_wx, _wy, _witl, _wthpt = [], [], [], []
for _ctx in (16.0, 64.0, 256.0):
    for _kv in (0.0, 0.25, 0.5, 0.75, 1.0):
        _wx.append(_kv)
        _wy.append(_ctx)
        _witl.append(10.0 + 40.0 * _kv)
        _wthpt.append(20.0 + 40.0 * _kv)
_WEAK_DECODE = {
    "x_kv_usage": _wx, "y_context_length": _wy, "z_itl_ms": _witl,
    "z_thpt_per_chip": _wthpt, "max_kv_tokens": 100000,
}


async def _run_autoscale_gate(duration_s: float, base_rps: float) -> None:
    """The full loop under a compressed diurnal day. Used by the smoke
    (short) and the soak (slow-marked, longer)."""
    import aiohttp  # noqa: F401  (replay needs it; fail fast if absent)

    from dynamo_tpu.llm.entrypoint import start_frontend
    from dynamo_tpu.planner.connector import TargetReplica, VirtualConnector
    from dynamo_tpu.planner.interpolation import (
        DecodeInterpolator,
        PrefillInterpolator,
    )
    from dynamo_tpu.planner.planner_core import Planner, SlaPlannerConfig
    from dynamo_tpu.planner.supervisor import FleetSupervisor, SupervisorConfig
    from dynamo_tpu.planner.telemetry_source import TelemetrySource
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.store_net import StoreServer
    from dynamo_tpu.trafficgen.runner import (
        STATUS_ABANDONED,
        STATUS_OK,
        replay,
    )

    store_server = StoreServer()
    host, port = await store_server.start()
    store_url = f"tcp://{host}:{port}"
    # frontend runtime: HTTP metrics publish once from here (generous
    # SLOs — the mock fleet is fast; the gate is "never fast_burn", not
    # "latency under X")
    rt_f = await DistributedRuntime.create(RuntimeConfig(
        store_url=store_url, telemetry_interval=0.05,
        slo_ttft=1.0, slo_itl=0.5, slo_check_interval=0.2,
        slo_fast_window=3.0, slo_slow_window=10.0))
    # worker runtime: supervisor + its spawned engines
    rt_w = await DistributedRuntime.create(RuntimeConfig(
        store_url=store_url, telemetry_interval=0.05))
    sup = await FleetSupervisor(rt_w, SupervisorConfig(
        mock_speedup=100.0, drain_grace_s=0.5)).start()
    fe = await start_frontend(rt_f, port=0)
    planner = None
    slo_states: list[str] = []
    warmed = asyncio.Event()
    stop_watch = asyncio.Event()

    async def slo_watch():
        while not stop_watch.is_set():
            if warmed.is_set() and fe.slo is not None:
                slo_states.extend(
                    v["state"] for v in fe.slo.status().values())
            await asyncio.sleep(0.1)

    try:
        # bootstrap a 1/1 fleet through the same connector path the
        # planner uses, then wait for the model to be routable
        boot = VirtualConnector(rt_f, "dynamo")
        await boot.set_component_replicas([
            TargetReplica("backend_prefill", "prefill", 1),
            TargetReplica("backend", "decode", 1)])
        for _ in range(300):
            if fe.manager.model_names() \
                    and sup.replicas("backend", "decode") == 1:
                break
            await asyncio.sleep(0.05)
        assert fe.manager.model_names() == ["mock-model"]

        cfg = TrafficConfig(
            pattern="diurnal", duration_s=duration_s, base_rps=base_rps,
            diurnal_amplitude=0.9, diurnal_period_s=duration_s, seed=42,
            isl_mean=16, isl_max=64, osl_mean=8, osl_max=32,
            prefix_fraction=0.3, abandon_fraction=0.1)
        schedule = build_schedule(cfg)
        assert len(schedule) > 30

        # reference replay on the unscaled 1/1 fleet: arrivals squeezed
        # together (not concurrent-all — still a valid open-loop run)
        ref = await replay(fe.url, "mock-model", schedule, cfg,
                           time_scale=0.02)

        # close the loop: planner on live event-plane telemetry
        planner = Planner(
            SlaPlannerConfig(adjustment_interval=1.0, max_chip_budget=8,
                             min_endpoint=1, no_correction=True),
            PrefillInterpolator(raw_data=_WEAK_PREFILL),
            DecodeInterpolator(raw_data=_WEAK_DECODE),
            TelemetrySource(fe.collector),
            connector=VirtualConnector(rt_f, "dynamo"))
        planner.start()
        watcher = asyncio.get_running_loop().create_task(slo_watch())

        async def warm():
            await asyncio.sleep(2.0)
            warmed.set()

        warm_task = asyncio.get_running_loop().create_task(warm())
        main = await replay(fe.url, "mock-model", schedule, cfg,
                            time_scale=1.0)
        # let the planner see the post-replay trough and scale down
        for _ in range(100):
            if sup.replicas("backend", "decode") <= 1 \
                    and sup.replicas("backend_prefill", "prefill") <= 1:
                break
            await asyncio.sleep(0.1)
        stop_watch.set()
        await watcher
        warm_task.cancel()

        # 1. the planner scaled the fleet up on the ramp AND back down
        ups = [e for e in sup.scale_events if e["direction"] == "up"]
        downs = [e for e in sup.scale_events if e["direction"] == "down"]
        assert len(ups) >= 2, sup.scale_events
        assert len(downs) >= 2, sup.scale_events
        peak = max(e["to"] for e in ups)
        assert peak >= 2, sup.scale_events
        # 2. SLOs held through every scale event after warmup
        assert slo_states, "slo watcher never sampled"
        assert not any(s in ("fast_burn", "breach") for s in slo_states), \
            sorted(set(slo_states))
        # 3. zero non-abandoned streams dropped, token-identical to the
        # unscaled reference (migrations may move streams, never corrupt)
        for r_main, r_ref in zip(main, ref):
            if r_main.status == STATUS_ABANDONED \
                    or r_ref.status == STATUS_ABANDONED:
                continue
            assert r_main.status == STATUS_OK, \
                (r_main.index, r_main.status)
            assert r_main.text == r_ref.text, r_main.index
            assert r_main.tokens == r_ref.tokens
    finally:
        stop_watch.set()
        if planner is not None:
            planner.stop()
        await fe.stop()
        await sup.stop()
        await rt_f.close()
        await rt_w.close()
        await store_server.stop()


async def test_autoscale_loop_smoke(frozen_heap):
    """`make autoscale-smoke` body: the full closed loop in ~20 s."""
    await _run_autoscale_gate(duration_s=12.0, base_rps=15.0)


@pytest.mark.slow
async def test_autoscale_loop_soak(frozen_heap):
    """Longer diurnal day, same gate — catches slow drifts (leaked
    workers, revision stalls) the smoke's single cycle can miss."""
    await _run_autoscale_gate(duration_s=40.0, base_rps=12.0)
