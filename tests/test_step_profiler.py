"""Step flight recorder (engine/profiler.py): ring semantics, zero-cost
off path, MockEngine parity, analytic padding math, doctor profile
rendering, and the /debug/profile surface (host spans:
tests/test_host_spans.py)."""

import asyncio
import json

import pytest

from dynamo_tpu.engine.profiler import (
    StepRecorder,
    profile_payload,
    recorder_from_env,
    step_profile_summary,
)
from dynamo_tpu.mocker.engine import MockEngine, MockEngineConfig, _pow2
from dynamo_tpu.protocols import PreprocessedRequest
from dynamo_tpu.runtime.context import Context


def make_req(tokens, max_tokens=8, model="m"):
    r = PreprocessedRequest(token_ids=list(tokens), model=model)
    r.stop.max_tokens = max_tokens
    return r.to_dict()


async def run_to_completion(eng, tokens, max_tokens):
    out = []
    async for chunk in eng.generate(make_req(tokens, max_tokens),
                                    Context()):
        out.extend(chunk.get("token_ids") or [])
    return out


# -- ring semantics ---------------------------------------------------------


@pytest.mark.tier0
def test_ring_bound_and_eviction():
    rec = StepRecorder(capacity=16)
    for i in range(40):
        rec.record("decode_burst", (8, 1), 0.001,
                   good_tokens=5, work_tokens=8, lanes=5, width=8,
                   tokens=5)
    s = rec.summary()
    assert s["recorded"] == 40
    assert s["in_ring"] == 16
    assert s["capacity"] == 16
    assert s["evicted"] == 24
    # cumulative totals survive eviction: exact over all 40 records
    assert s["totals"]["good_tokens"] == 40 * 5
    assert s["totals"]["padded_tokens"] == 40 * 3
    assert len(rec.snapshot()) == 16
    assert len(rec.snapshot(limit=4)) == 4
    rec.clear()
    assert rec.recorded == 0
    assert rec.summary()["totals"]["work_tokens"] == 0


@pytest.mark.tier0
def test_capacity_floor_and_env_gate(monkeypatch):
    assert StepRecorder(capacity=1).capacity == 16
    monkeypatch.delenv("DYN_STEP_PROFILE", raising=False)
    assert recorder_from_env() is None
    monkeypatch.setenv("DYN_STEP_PROFILE", "0")
    assert recorder_from_env() is None
    monkeypatch.setenv("DYN_STEP_PROFILE", "1")
    monkeypatch.setenv("DYN_STEP_PROFILE_RING", "64")
    rec = recorder_from_env()
    assert rec is not None and rec.capacity == 64


@pytest.mark.tier0
def test_gap_chain_and_synced_accounting():
    rec = StepRecorder()
    rec.record("prefill", (1, 64), 0.002, good_tokens=50,
               work_tokens=64, synced=False)
    rec.record("decode_burst", (4, 1), 0.001, good_tokens=3,
               work_tokens=4)
    recs = rec.snapshot()
    assert recs[0]["gap_s"] is None          # first record: no gap
    assert recs[1]["gap_s"] is not None and recs[1]["gap_s"] >= 0.0
    s = rec.summary()
    # device-time share counts only synced host time: the unsynced
    # prefill dispatch contributes zero
    assert s["entries"]["prefill"]["device_share_pct"] == 0.0
    assert s["entries"]["decode_burst"]["device_share_pct"] == 100.0
    assert s["dispatch_gap"]["count"] == 1


# -- zero-cost off path -----------------------------------------------------


@pytest.mark.tier0
async def test_off_by_default_zero_cost(monkeypatch):
    monkeypatch.delenv("DYN_STEP_PROFILE", raising=False)
    published = []
    eng = MockEngine(MockEngineConfig(speedup=1000.0),
                     metrics_sink=published.append)
    assert eng.step_recorder is None
    toks = await run_to_completion(eng, [7, 8, 9], 4)
    assert len(toks) == 4
    eng._publish_metrics()
    await eng.close()
    # scheduler_stats stays absent — the published payload is
    # byte-identical to the pre-profiler one
    assert published and published[-1].scheduler_stats is None
    assert step_profile_summary(eng) is None
    assert profile_payload(eng)["enabled"] is False


# -- MockEngine parity + analytic padding math ------------------------------


async def test_mock_engine_analytic_padding(monkeypatch):
    monkeypatch.setenv("DYN_STEP_PROFILE", "1")
    published = []
    eng = MockEngine(MockEngineConfig(speedup=1000.0),
                     metrics_sink=published.append)
    assert eng.step_recorder is not None
    # scripted sequential mix: distinct prompts (no prefix reuse), one
    # request in flight at a time, so the mocker's _pow2 bucketing model
    # makes the padded share exactly computable:
    #   prefill work  = _pow2(L) per request (good = L)
    #   decode  work  = 1 per emitted token  (single lane, width 1)
    mix = [(5, 4), (100, 7), (33, 9)]
    base = 1000
    for i, (plen, mtok) in enumerate(mix):
        prompt = list(range(base * (i + 1), base * (i + 1) + plen))
        toks = await run_to_completion(eng, prompt, mtok)
        assert len(toks) == mtok
    eng._publish_metrics()
    await eng.close()

    good = sum(plen + mtok for plen, mtok in mix)
    work = sum(_pow2(plen) + mtok for plen, mtok in mix)
    expect_pct = 100.0 * (work - good) / work
    s = eng.step_recorder.summary()
    assert s["totals"]["good_tokens"] == good
    assert s["totals"]["work_tokens"] == work
    assert abs(s["totals"]["padded_pct"] - expect_pct) < 1.0
    # decode goodput == tokens emitted (make profile-smoke's invariant)
    emitted = sum(mtok for _plen, mtok in mix)
    assert s["entries"]["decode_burst"]["good_tokens"] == emitted
    assert eng.metrics.goodput_tokens.get(entry="decode_burst") == emitted
    assert eng.metrics.padded_tokens.get(entry="prefill") == \
        sum(_pow2(plen) - plen for plen, _mtok in mix)
    # the gated scheduler_stats block is present and agrees
    stats = published[-1].scheduler_stats
    assert stats is not None
    assert stats["goodput_tokens"] == good
    assert stats["padded_tokens"] == work - good
    # bench summary block mirrors the same totals
    sp = step_profile_summary(eng)
    assert sp is not None and sp["goodput_tokens"] == good
    assert abs(sp["padded_pct"] - round(expect_pct, 3)) < 1e-9


# -- doctor ------------------------------------------------------------------


@pytest.mark.tier0
def test_doctor_profile_renders(tmp_path, capsys):
    from dynamo_tpu.doctor.profile import main as profile_main

    rec = StepRecorder()
    rec.record("prefill", (2, 128), 0.010, good_tokens=200,
               work_tokens=256, lanes=2, width=2, compiled=True)
    rec.record("decode_burst", (8, 1), 0.002, good_tokens=6,
               work_tokens=8, lanes=6, width=8, tokens=6)

    class _E:
        step_recorder = rec

    src = tmp_path / "profile.json"
    src.write_text(json.dumps(
        {"enabled": True, "engines": [profile_payload(_E())]}))
    assert profile_main([str(src)]) == 0
    out = capsys.readouterr().out
    assert "goodput" in out
    assert "padding waste by bucket shape" in out
    assert "top compile stalls" in out
    # recorder-off payload exits nonzero
    off = tmp_path / "off.json"
    off.write_text(json.dumps(
        {"enabled": False, "engines": [{"enabled": False,
                                        "hint": "off"}]}))
    assert profile_main([str(off)]) == 1


@pytest.mark.tier0
def test_doctor_subcommand_dispatch(tmp_path, capsys):
    from dynamo_tpu.doctor.__main__ import main as doctor_main

    bad = tmp_path / "missing.json"
    assert doctor_main(["profile", str(bad)]) == 1
    assert "cannot read" in capsys.readouterr().out


# -- fleet plane ------------------------------------------------------------


@pytest.mark.tier0
def test_fleet_status_goodput(monkeypatch):
    from dynamo_tpu.runtime.telemetry import TelemetryCollector

    col = TelemetryCollector(bus=None)

    def payload(at, good, padded):
        return {"component": "mock", "instance": "w1", "role": "worker",
                "at": at,
                "metrics": {
                    "dynamo_engine_goodput_tokens_total": {
                        "type": "counter",
                        "values": [[{"entry": "decode_burst"}, good]]},
                    "dynamo_engine_padded_tokens_total": {
                        "type": "counter",
                        "values": [[{"entry": "prefill"}, padded]]},
                }}

    import time as _time
    now = _time.time()
    col.ingest(payload(now - 10.0, 100, 25))
    col.ingest(payload(now, 300, 75))   # +200 tok over 10 s
    status = col.fleet_status()
    gp = status["components"][0]["goodput"]
    assert gp["goodput_tokens"] == 300
    assert gp["padded_tokens"] == 75
    assert gp["padded_pct"] == 20.0
    assert abs(gp["goodput_tok_s"] - 20.0) < 1e-6
    fleet_gp = status["fleet"]["goodput"]
    assert fleet_gp["goodput_tokens"] == 300
    assert abs(fleet_gp["goodput_tok_s"] - 20.0) < 1e-6
    # unprofiled workers keep the pre-profiler payload shape
    col2 = TelemetryCollector(bus=None)
    col2.ingest({"component": "mock", "instance": "w2",
                 "role": "worker", "at": now, "metrics": {}})
    st2 = col2.fleet_status()
    assert "goodput" not in st2["components"][0]
    assert "goodput" not in st2["fleet"]


@pytest.mark.tier0
def test_doctor_fleet_renders_goodput(tmp_path, capsys):
    from dynamo_tpu.doctor.fleet import main as fleet_main

    status = {"components": [{"component": "mock", "instance": "w1",
                              "role": "worker", "age_s": 1.0,
                              "latency": {},
                              "goodput": {"goodput_tokens": 300,
                                          "padded_tokens": 75,
                                          "padded_pct": 20.0,
                                          "goodput_tok_s": 20.0}}],
              "fleet": {"latency": {}}}
    f = tmp_path / "status.json"
    f.write_text(json.dumps(status))
    assert fleet_main([str(f)]) == 0
    out = capsys.readouterr().out
    assert "goodput=300tok" in out
    assert "(20.0tok/s)" in out
    assert "padded=20.0%" in out


# -- /debug/profile surface -------------------------------------------------


async def test_debug_profile_endpoint(monkeypatch):
    monkeypatch.setenv("DYN_STEP_PROFILE", "1")
    import aiohttp

    from dynamo_tpu.llm.entrypoint import (
        serve_engine,
        start_frontend,
        wire_engine_events,
    )
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    rt = await DistributedRuntime.create(RuntimeConfig(store_url="memory"))
    card = ModelDeploymentCard(
        name="mock-model", namespace="ns", component="mock",
        tokenizer_kind="word", tokenizer_path="mock-model",
        router_mode="round_robin", migration_limit=1)
    ev_sink, m_sink = wire_engine_events(rt, card)
    eng = MockEngine(
        MockEngineConfig(block_size=card.kv_block_size, worker_id=1,
                         speedup=200.0, default_max_tokens=16),
        event_sink=ev_sink, metrics_sink=m_sink)
    handle = await serve_engine(rt, eng, card, instance_id=1)
    fe = await start_frontend(rt)
    try:
        for _ in range(100):
            if "mock-model" in fe.manager.model_names():
                break
            await asyncio.sleep(0.01)
        async with aiohttp.ClientSession() as s:
            body = {"model": "mock-model", "max_tokens": 8,
                    "messages": [{"role": "user",
                                  "content": "profile me please"}]}
            async with s.post(f"{fe.url}/v1/chat/completions",
                              json=body) as r:
                assert r.status == 200
            async with s.get(f"{fe.url}/debug/profile") as r:
                assert r.status == 200
                data = await r.json()
            assert data["enabled"] is True
            summary = data["engines"][0]["summary"]
            assert summary["totals"]["good_tokens"] > 0
            assert data["engines"][0]["records"]
            async with s.get(f"{fe.url}/debug/profile?capture_s=nope") as r:
                assert r.status == 400
            # openapi advertises the route
            async with s.get(f"{fe.url}/openapi.json") as r:
                spec = await r.json()
            assert "/debug/profile" in spec["paths"]
    finally:
        await fe.stop()
        await handle.stop()
        await eng.close()
        await rt.close()


# -- doctor preflight -------------------------------------------------------


def test_device_preflight_ok_on_cpu():
    from dynamo_tpu.doctor.preflight import device_preflight

    assert device_preflight(attempts=1, timeout_s=120.0) is None


@pytest.mark.tier0
def test_device_preflight_failure_diagnosis(monkeypatch):
    import sys

    from dynamo_tpu.doctor import preflight

    # probe child that exits nonzero with a diagnostic on stderr
    monkeypatch.setattr(
        preflight, "_PROBE",
        "import sys; sys.stderr.write('relay down'); sys.exit(3)")
    verdict = preflight.device_preflight(attempts=1, timeout_s=60.0)
    assert verdict is not None and "relay down" in verdict
    assert sys.executable  # silence unused-import style checkers
