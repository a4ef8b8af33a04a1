"""Perf ledger + deterministic gate + request correlation
(docs/observability.md "Perf ledger & regression gate").

Pins: the BENCH_*.json schema fields `doctor bench` consumes (round-trip
over one record of every historical shape — flat, rich, partial with
nested phase errors, outage), the
byte-determinism of the chip-free perf phase, the checked-in baseline
matching the unmodified tree, the seeded-regression failure path
(bucket-floor knob → gate must fail), the doctor dispatch table, the
GET /debug index, and the `doctor request` four-source join.
"""

import asyncio
import json
import pathlib

import pytest

from dynamo_tpu.bench.ledger import (
    GATE_THRESHOLDS,
    RunRecord,
    flatten_metrics,
    gate_compare,
    is_perf_record,
    load_run,
    normalize_run,
    trajectory_deltas,
)
from dynamo_tpu.bench.perf import PerfConfig, record_to_json, run_perf
from dynamo_tpu.doctor.bench import main as bench_main
from dynamo_tpu.doctor.preflight import classify
from dynamo_tpu.doctor.request import correlate, gather_sources
from dynamo_tpu.doctor.request import main as request_main

pytestmark = pytest.mark.tier0

REPO = pathlib.Path(__file__).resolve().parent.parent

HUNG = "device preflight timed out after 240s"
_CMD = "if [ -f bench.py ]; then python bench.py; else exit 0; fi"
_HEAD = {"metric": "engine_output_tokens_per_sec_per_chip",
         "unit": "tok/s/chip"}
# one driver record per historical shape; the partial round (nested
# per-phase errors) is the checked-in BENCH_r03.json itself
_ROUNDS = {
    1: {**_HEAD, "value": 471.8, "vs_baseline": 0.778},        # flat
    2: {**_HEAD, "value": 1953.7, "vs_baseline": 3.224,        # rich
        "effective_ms_per_step": 8.19, "device_loop_tok_s": 2434.3,
        "vs_device_loop": 0.803, "device_ms_per_step": 6.57,
        "hbm_util_pct": 24.9, "isl": 96, "osl": 64, "n_requests": 32,
        "batch": 16, "quantize": "int8", "ttft_ms_unloaded_p50": 306.0,
        "phase_tok_s": [1798.2, 1953.7], "kv_transfer_mb": 268.4,
        "kv_host_gbps": 0.01, "kv_device_gbps": 0.51},
    4: {**_HEAD, "value": 0.0, "vs_baseline": 0.0, "error": HUNG},
    5: {**_HEAD, "value": 0.0, "vs_baseline": 0.0, "error": HUNG},
}


@pytest.fixture(scope="module")
def bench_files(tmp_path_factory):
    """BENCH_r01..r05.json as the driver wrote them ({n, cmd, rc, tail,
    parsed}), in a directory of their own."""
    d = tmp_path_factory.mktemp("bench_rounds")
    paths = []
    for n in range(1, 6):
        p = d / f"BENCH_r0{n}.json"
        if n == 3:
            p.write_text((REPO / "BENCH_r03.json").read_text())
        else:
            p.write_text(json.dumps({
                "n": n, "cmd": _CMD, "rc": 0,
                "tail": json.dumps(_ROUNDS[n])[:160],
                "parsed": _ROUNDS[n]}))
        paths.append(p)
    return paths


def _small_cfg(**kw) -> PerfConfig:
    cfg = PerfConfig(**kw)
    cfg.max_requests = 60
    cfg.traffic.duration_s = 12.0
    return cfg


# -- historical BENCH schema round-trip -------------------------------------


def test_bench_schema_roundtrip_r01_to_r05(bench_files):
    recs = [load_run(str(p)) for p in bench_files]
    assert [r.round for r in recs] == [1, 2, 3, 4, 5]
    assert [r.status for r in recs] == ["ok", "ok", "partial",
                                        "outage", "outage"]
    # the fields doctor bench consumes
    assert recs[0].value == 471.8
    assert recs[1].value == 1953.7
    assert recs[1].metrics["vs_device_loop"] == 0.803
    assert recs[1].metrics["ttft_ms"] == 306.0
    assert recs[2].value == 2104.0
    # r03's nested phase errors classify as OOM; r04/r05 as a hung probe
    assert recs[2].diagnosis["kind"] == "oom"
    assert len(recs[2].errors) == 3
    for r in recs[3:]:
        assert r.value is None
        assert r.diagnosis["kind"] == "timeout"
        assert r.metrics.get("tok_s_chip") is None


def test_normalize_current_outage_shape():
    # the shape bench.py writes TODAY: value null + skipped + the
    # machine-readable preflight block (which wins over re-classifying)
    rec = normalize_run({
        "metric": "engine_output_tokens_per_sec_per_chip",
        "unit": "tok/s/chip", "value": None, "vs_baseline": None,
        "skipped": True, "error": HUNG,
        "preflight": {"kind": "timeout", "detail": HUNG},
    }, label="r06")
    assert rec.status == "outage"
    assert rec.value is None
    assert rec.diagnosis == {"kind": "timeout", "detail": HUNG}


def test_wrapper_and_bare_parsed_normalize_identically(bench_files):
    data = json.loads(bench_files[1].read_text())
    wrapped = normalize_run(data, label="r02")
    bare = normalize_run(data["parsed"], label="r02")
    assert wrapped.metrics == bare.metrics
    assert wrapped.status == bare.status == "ok"
    assert wrapped.round == 2 and bare.round is None


def test_classify_kinds():
    assert classify(HUNG)["kind"] == "timeout"
    assert classify("device preflight ran on 'cpu', not on 'tpu': the "
                    "backend fell back")["kind"] == "other"
    assert classify("JaxRuntimeError: RESOURCE_EXHAUSTED: TPU backend "
                    "error")["kind"] == "oom"
    assert classify("RecursionError: maximum recursion depth "
                    "exceeded")["kind"] == "other"
    assert classify("")["kind"] == "other"


def test_trajectory_render_over_real_files(capsys, bench_files):
    assert bench_main([str(p) for p in bench_files]) == 0
    out = capsys.readouterr().out
    # honest outage rows with their diagnosis, not silent holes
    assert out.count("OUTAGE") == 2
    assert "timeout" in out
    assert "oom" in out
    assert "1953.7 tok/s/chip" in out
    assert "deltas" in out


def test_trajectory_deltas_respect_noise_bounds():
    mk = lambda label, m: RunRecord(label=label, round=None, status="ok",
                                    value=m.get("tok_s_chip"), metrics=m)
    rows = trajectory_deltas([
        mk("a", {"tok_s_chip": 1000.0, "ttft_ms": 100.0}),
        mk("b", {"tok_s_chip": 1050.0, "ttft_ms": 140.0}),   # +5% / +40%
        mk("c", {"tok_s_chip": 1500.0}),                     # +43%
    ])
    by = {(r["metric"], r["to"]): r for r in rows}
    assert by[("tok_s_chip", "b")]["verdict"] == "noise"     # inside 10%
    assert by[("tok_s_chip", "c")]["verdict"] == "better"
    assert by[("ttft_ms", "b")]["verdict"] == "worse"        # beyond 15%
    # an outage round must not break the comparison chain
    rows2 = trajectory_deltas([
        mk("a", {"tok_s_chip": 1000.0}),
        RunRecord(label="out", round=None, status="outage", value=None),
        mk("c", {"tok_s_chip": 2000.0}),
    ])
    assert [(r["from"], r["to"]) for r in rows2] == [("a", "c")]


# -- deterministic perf phase ------------------------------------------------


def test_perf_two_runs_byte_identical():
    a = record_to_json(run_perf(_small_cfg()))
    b = record_to_json(run_perf(_small_cfg()))
    assert a == b


def test_perf_record_carries_no_wall_clock():
    rec = json.loads(record_to_json(run_perf(_small_cfg())))
    # control_sim action stamps ("at") are virtual-clock ticks, not wall
    # time — byte-identity across runs pins that; scan everything else
    scan = dict(rec)
    scan.pop("control_sim", None)
    text = record_to_json(scan)
    for leak in ('"at"', "wall_span", "dispatch_gap", "goodput_tok_s",
                 "mean_s", "residency"):
        assert leak not in text
    assert is_perf_record(rec)
    m = rec["metrics"]
    assert m["engine"]["goodput_tokens"] > 0
    assert m["engine"]["padded_tokens"] >= 0
    assert m["kv"]["hits"] > 0
    # prefix reuse is the same phenomenon on both planes
    assert m["router"]["tokens_saved"] == m["kv"]["tokens_saved"] > 0
    assert m["router"]["decisions"] == rec["requests"]
    assert rec["completed"] == rec["requests"]


def test_perf_seed_changes_the_record():
    a = run_perf(_small_cfg(seed=11))
    cfg = _small_cfg(seed=12)
    cfg.traffic.seed = 12
    b = run_perf(cfg)
    assert record_to_json(a) != record_to_json(b)


def test_checked_in_baseline_matches_unmodified_tree():
    baseline = json.loads((REPO / "benchmarks" /
                           "perf_baseline.json").read_text())
    current = run_perf(PerfConfig())
    rows, failed = gate_compare(baseline, current)
    assert not failed, rows
    # stronger: the default-config record is byte-identical to the
    # committed baseline, so `make perf-gate` shows all-zero deltas
    assert record_to_json(current) == record_to_json(baseline)


# -- the gate ----------------------------------------------------------------


def test_gate_fails_on_seeded_padding_regression(tmp_path, capsys):
    good = run_perf(_small_cfg())
    bad = run_perf(_small_cfg(bucket_floor=64))
    rows, failed = gate_compare(good, bad)
    assert failed
    flagged = {r["metric"] for r in rows if not r["ok"]}
    assert "engine.padded_pct" in flagged
    # goodput is unchanged — the knob inflates padding, not work done
    assert flatten_metrics(bad["metrics"])["engine.goodput_tokens"] == \
        flatten_metrics(good["metrics"])["engine.goodput_tokens"]
    # end to end through doctor bench --gate: rc 1 + rendered table
    bp, cp = tmp_path / "base.json", tmp_path / "cur.json"
    bp.write_text(record_to_json(good))
    cp.write_text(record_to_json(bad))
    assert bench_main(["--gate", str(bp), str(cp)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "GATE FAILED" in out


def test_gate_missing_metric_fails():
    good = run_perf(_small_cfg())
    pruned = json.loads(record_to_json(good))
    del pruned["metrics"]["kv"]["tokens_saved"]
    rows, failed = gate_compare(good, pruned)
    assert failed
    row = next(r for r in rows if r["metric"] == "kv.tokens_saved")
    assert row["cur"] is None and not row["ok"]


def test_gate_rejects_non_perf_records(tmp_path, capsys):
    p = tmp_path / "not_perf.json"
    p.write_text(json.dumps({"value": 1.0}))
    assert bench_main(["--gate", str(p), str(p)]) == 2
    assert "not a perf record" in capsys.readouterr().out
    # every gated key exists in a real record
    m = flatten_metrics(run_perf(_small_cfg())["metrics"])
    for key in GATE_THRESHOLDS:
        assert key in m, key


# -- doctor dispatch table ---------------------------------------------------


def test_doctor_dispatch_table(capsys):
    import importlib

    from dynamo_tpu.doctor.__main__ import SUBCOMMANDS
    from dynamo_tpu.doctor.__main__ import main as doctor_main

    for name in ("bench", "request", "profile", "router", "kv",
                 "trace", "fleet", "preflight"):
        assert name in SUBCOMMANDS
        module, help_line = SUBCOMMANDS[name]
        mod = importlib.import_module(f"dynamo_tpu.doctor.{module}")
        assert callable(mod.main)
        assert help_line
    assert doctor_main([]) == 0
    out = capsys.readouterr().out
    assert "bench" in out and "request" in out and "check" in out
    assert doctor_main(["no-such-subcommand"]) == 2


# -- GET /debug index --------------------------------------------------------


async def test_debug_index_endpoint(monkeypatch):
    monkeypatch.setenv("DYN_STEP_PROFILE", "1")
    import aiohttp

    from dynamo_tpu.llm.entrypoint import serve_engine, start_frontend
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.mocker.engine import MockEngine, MockEngineConfig
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    rt = await DistributedRuntime.create(
        RuntimeConfig(store_url="memory"))
    card = ModelDeploymentCard(
        name="mock-model", namespace="ns", component="mock",
        tokenizer_kind="word", tokenizer_path="mock-model",
        router_mode="round_robin", migration_limit=1)
    eng = MockEngine(MockEngineConfig(speedup=200.0))
    handle = await serve_engine(rt, eng, card, instance_id=1)
    fe = await start_frontend(rt)
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{fe.url}/debug") as r:
                assert r.status == 200
                surfaces = (await r.json())["surfaces"]
            assert set(surfaces) == {"/debug/requests", "/debug/profile",
                                     "/debug/router", "/debug/kv",
                                     "/debug/control", "/debug/memory",
                                     "/debug/mesh", "/debug/tenants",
                                     "/debug/classes", "/debug/prefixes"}
            # always-on ring vs env-armed recorders, with the knob named
            assert surfaces["/debug/requests"]["armed"] is True
            assert surfaces["/debug/requests"]["arm"] is None
            assert surfaces["/debug/profile"]["armed"] is True
            assert surfaces["/debug/profile"]["arm"] == \
                "DYN_STEP_PROFILE=1"
            assert surfaces["/debug/kv"]["armed"] is False  # not armed
            assert surfaces["/debug/kv"]["arm"] == "DYN_KV_LIFECYCLE=1"
            assert surfaces["/debug/control"]["armed"] is False
            assert surfaces["/debug/control"]["arm"].startswith("DYN_CONTROL")
            assert surfaces["/debug/memory"]["armed"] is False
            assert surfaces["/debug/memory"]["arm"] == "DYN_MEM_LEDGER=1"
            assert surfaces["/debug/mesh"]["armed"] is False
            assert surfaces["/debug/mesh"]["arm"] == "DYN_MESH_RECORDER=1"
            assert surfaces["/debug/tenants"]["armed"] is False
            assert surfaces["/debug/tenants"]["arm"].startswith("DYN_TENANCY")
            # round-robin model → no kv router on this frontend
            assert surfaces["/debug/router"]["available"] is False
            async with s.get(f"{fe.url}/openapi.json") as r:
                assert "/debug" in (await r.json())["paths"]
    finally:
        await fe.stop()
        await handle.stop()
        await eng.close()
        await rt.close()


# -- doctor request: the four-source join -----------------------------------


def _request_sources(tmp_path):
    trace_id = "ab" * 16
    t0 = 1_000_000.0
    ns = int(t0 * 1e9)
    requests_dump = {"in_flight": [], "recent": [{
        "request_id": "req-1", "endpoint": "chat", "model": "m",
        "stream": True, "received_at": t0, "trace_id": trace_id,
        "status": "ok", "first_token_s": 0.012, "last_token_s": 0.050,
        "duration_s": 0.055,
        "usage": {"prompt_tokens": 64, "completion_tokens": 8}}]}
    router_dump = {"models": [{"model": "m", "records": [{
        "request_id": "req-1", "mode": "route", "at": t0 + 0.001,
        "worker": "1:0", "overlap_blocks": 3, "total_blocks": 4,
        "prefix_hit_ratio": 0.75, "prefill_tokens": 16,
        "tokens_saved": 48, "n_tokens": 64, "logit_margin": 0.5,
        "ties": 1, "draw": None,
        "candidates": [{"worker": "1:0", "overlap_blocks": 3,
                        "logit": 4.0},
                       {"worker": "2:0", "overlap_blocks": 0,
                        "logit": 4.5}]}]}]}
    kv_dump = {"engines": [{"enabled": True, "tiers": {},
                            "records": [
        {"ev": "hit", "at": t0 + 0.002, "tokens_saved": 48},
        {"ev": "allocate", "at": t0 + 0.003, "page": 7},
        {"ev": "allocate", "at": t0 + 99.0, "page": 8},  # outside window
    ]}]}
    profile_dump = {"engines": [{"enabled": True, "summary": {},
                                 "records": [
        {"entry": "prefill", "at": t0 + 0.004, "host_s": 0.003,
         "good_tokens": 16, "work_tokens": 16},
        {"entry": "decode_burst", "at": t0 + 0.02, "host_s": 0.004,
         "good_tokens": 6, "work_tokens": 8},
    ]}]}
    spans = [
        {"traceId": trace_id, "spanId": "s1" * 4, "parentSpanId": "",
         "name": "engine.request", "startTimeUnixNano": ns,
         "endTimeUnixNano": ns + 55_000_000,
         "attributes": [{"key": "request.id",
                         "value": {"stringValue": "req-1"}}],
         "events": [{"name": "first_token",
                     "timeUnixNano": ns + 12_000_000}],
         "status": {"code": "OK"}},
        {"traceId": trace_id, "spanId": "s2" * 4,
         "parentSpanId": "s1" * 4, "name": "engine.prefill",
         "startTimeUnixNano": ns + 1_000_000,
         "endTimeUnixNano": ns + 9_000_000, "attributes": [],
         "events": [], "status": {"code": "OK"}},
        {"traceId": "ff" * 16, "spanId": "s3" * 4, "parentSpanId": "",
         "name": "other.request", "startTimeUnixNano": ns,
         "endTimeUnixNano": ns + 1, "attributes": [], "events": [],
         "status": {"code": "OK"}},
    ]
    paths = []
    for name, body in (("requests.json", requests_dump),
                       ("router.json", router_dump),
                       ("kv.json", kv_dump),
                       ("profile.json", profile_dump)):
        p = tmp_path / name
        p.write_text(json.dumps(body))
        paths.append(str(p))
    tp = tmp_path / "trace.jsonl"
    tp.write_text("\n".join(json.dumps(s) for s in spans) + "\n")
    paths.append(str(tp))
    return trace_id, paths


def test_doctor_request_joins_all_four_sources(tmp_path, capsys):
    trace_id, paths = _request_sources(tmp_path)
    assert request_main([trace_id] + paths) == 0
    out = capsys.readouterr().out
    assert "req-1" in out
    assert "router → 1:0" in out and "saved=48 tok" in out
    assert "engine.request" in out and "engine.prefill" in out
    assert "first_token" in out
    assert "kv lifecycle in window: 2 events" in out   # 99s-later excluded
    assert "engine dispatches in window: 2" in out


def test_doctor_request_correlates_by_either_id(tmp_path):
    trace_id, paths = _request_sources(tmp_path)
    srcs = gather_sources(paths)
    by_trace = correlate(srcs, trace_id)
    by_req = correlate(srcs, "req-1")
    assert by_trace["request_id"] == by_req["request_id"] == "req-1"
    assert by_trace["decision"]["worker"] == "1:0"
    assert len(by_trace["spans"]) == 2        # the foreign trace excluded
    # trace-only: no requests dump; request id recovered from span attrs
    spans_only = gather_sources([p for p in paths
                                 if p.endswith("trace.jsonl")
                                 or p.endswith("router.json")])
    j = correlate(spans_only, trace_id)
    assert j["request_id"] == "req-1"
    assert j["decision"] is not None


def test_doctor_request_no_match(tmp_path, capsys):
    _, paths = _request_sources(tmp_path)
    assert request_main(["deadbeef" * 4] + paths) == 1
    assert "no source matched" in capsys.readouterr().out


# -- trafficgen token-id plane ----------------------------------------------


def test_prompt_token_ids_share_prefix_plane():
    from dynamo_tpu.trafficgen.schedule import (
        ScheduledRequest,
        TrafficConfig,
        prompt_token_ids,
        prompt_text,
    )

    cfg = TrafficConfig(prefix_fraction=1.0, num_prefixes=2,
                        prefix_len=8, isl_max=64)
    a = ScheduledRequest(index=0, at=0.0, isl=5, osl=4, prefix_id=1)
    b = ScheduledRequest(index=1, at=0.1, isl=7, osl=4, prefix_id=1)
    c = ScheduledRequest(index=2, at=0.2, isl=5, osl=4, prefix_id=0)
    ia, ib, ic = (prompt_token_ids(r, cfg) for r in (a, b, c))
    # same prefix id ⇒ identical leading ids; different ⇒ disjoint
    assert ia[:8] == ib[:8]
    assert ia[:8] != ic[:8]
    # tails unique per (request, position); lengths mirror prompt_text
    assert len(set(ia) | set(ib) | set(ic)) == len(ia + ib + ic) - 8
    assert len(ia) == len(prompt_text(a, cfg).split())
