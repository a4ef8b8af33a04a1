"""SLA planner: replica math vs reference semantics, predictors,
interpolators, profiler-on-mocker, and a scaling e2e where a supervisor
acts on the virtual connector's targets.

Reference tests: `tests/planner/test_replica_calculation.py`,
`tests/planner/test_scaling_e2e.py`.
"""

import asyncio
import os
import math

import pytest

from dynamo_tpu.planner import (
    ConstantPredictor,
    DecodeInterpolator,
    EwmaPredictor,
    IntervalMetrics,
    LinearTrendPredictor,
    Planner,
    PrefillInterpolator,
    SlaPlannerConfig,
    TargetReplica,
    VirtualConnector,
)

# -- fixtures: synthetic profile surfaces -----------------------------------
# prefill: ttft grows linearly with isl; thpt/chip flat 10_000 tok/s
PREFILL_RAW = {
    "isl": [64, 256, 1024, 4096],
    "ttft_ms": [10.0, 30.0, 110.0, 430.0],
    "thpt_per_chip": [10000.0, 10000.0, 10000.0, 10000.0],
}
# decode: itl rises with kv_usage; thpt/chip rises with kv_usage
_x, _y, _itl, _thpt = [], [], [], []
for ctx in (128.0, 512.0, 2048.0):
    for kv in (0.0, 0.25, 0.5, 0.75, 1.0):
        _x.append(kv)
        _y.append(ctx)
        _itl.append(10.0 + 40.0 * kv)          # ms: 10..50
        _thpt.append(100.0 + 900.0 * kv)       # tok/s/chip: 100..1000
DECODE_RAW = {
    "x_kv_usage": _x, "y_context_length": _y, "z_itl_ms": _itl,
    "z_thpt_per_chip": _thpt, "max_kv_tokens": 100000,
}


def make_planner(connector=None, **cfg_kw):
    defaults = dict(adjustment_interval=10.0, ttft_sla=0.5,
                    itl_sla=0.05, max_chip_budget=16)
    defaults.update(cfg_kw)
    cfg = SlaPlannerConfig(**defaults)

    class NullSource:
        async def interval_metrics(self):
            return IntervalMetrics()

    return Planner(cfg, PrefillInterpolator(raw_data=PREFILL_RAW),
                   DecodeInterpolator(raw_data=DECODE_RAW),
                   NullSource(), connector=connector)


# -- predictors -------------------------------------------------------------


def test_constant_predictor_and_idle_skip():
    p = ConstantPredictor()
    p.add_data_point(0)          # leading idle skipped
    assert p.predict_next() == 0
    p.add_data_point(5)
    p.add_data_point(7)
    assert p.predict_next() == 7


def test_linear_trend_extrapolates_ramp():
    p = LinearTrendPredictor(minimum_data_points=3)
    for v in (10, 20, 30, 40):
        p.add_data_point(v)
    assert p.predict_next() == pytest.approx(50, rel=0.01)
    # constant series stays constant
    p2 = LinearTrendPredictor(minimum_data_points=3)
    for _ in range(5):
        p2.add_data_point(8)
    assert p2.predict_next() == 8


def test_ewma_smooths():
    p = EwmaPredictor(alpha=0.5)
    for v in (10, 10, 30):
        p.add_data_point(v)
    assert 10 < p.predict_next() < 30


# -- interpolators -----------------------------------------------------------


def test_prefill_interpolator_exact_and_clamped():
    pi = PrefillInterpolator(raw_data=PREFILL_RAW)
    assert pi.interpolate_ttft(256) == pytest.approx(0.030, abs=1e-3)
    assert pi.interpolate_thpt_per_chip(9999999) == pytest.approx(10000.0)
    assert pi.interpolate_ttft(1) == pytest.approx(0.010, abs=2e-3)


def test_decode_interpolator_surfaces_and_best_thpt():
    di = DecodeInterpolator(raw_data=DECODE_RAW)
    # kv=0.5 at ctx 512: itl ≈ 30ms
    itl = di.interpolate_itl(concurrency=0.5 * 100000 / 512,
                             context_length=512)
    assert itl == pytest.approx(0.030, abs=0.004)
    # best thpt under a 30ms SLA must pick kv_usage ≈ 0.5 → thpt ≈ 550
    thpt, kv, achieved = di.find_best_throughput_per_chip(
        itl=0.030, context_length=512)
    assert achieved <= 0.0305
    assert thpt == pytest.approx(100 + 900 * kv, rel=0.05)
    assert 0.4 < kv < 0.6
    # unmeetable SLA falls back to the least-bad point
    thpt2, kv2, achieved2 = di.find_best_throughput_per_chip(
        itl=0.001, context_length=512)
    assert kv2 == pytest.approx(0.0, abs=0.05)


# -- replica math (reference planner_core.py:313-407 semantics) -------------


def test_replica_requirements_basic():
    pl = make_planner()
    # 100 req / 10s interval, isl 1000, osl 100
    # prefill: 100*1000/10 = 10_000 tok/s / 10_000 per chip = 1 chip
    # decode: 100*100/10 = 1000 tok/s; itl sla 50ms ⇒ kv=1.0 usable,
    #   thpt/chip = 1000 ⇒ 1 chip
    num_p, num_d = pl.compute_replica_requirements(100, 1000, 100)
    assert num_p == 1 and num_d == 1


def test_replica_requirements_scale_with_load():
    pl = make_planner(max_chip_budget=64)
    num_p, num_d = pl.compute_replica_requirements(1000, 1000, 100)
    # prefill: 100_000 tok/s / 10_000 = 10 chips
    assert num_p == 10
    assert num_d >= 10


def test_prefill_correction_factor_caps_at_one():
    pl = make_planner(max_chip_budget=64)
    pl.p_correction_factor = 0.25   # heavy queueing headroom
    num_p, _ = pl.compute_replica_requirements(1000, 1000, 100)
    assert num_p == math.ceil(1000 * 1000 / 10.0 * 0.25 / 10000)
    pl.p_correction_factor = 4.0    # worse than profiled: min(1, f)
    num_p2, _ = pl.compute_replica_requirements(1000, 1000, 100)
    assert num_p2 == 10


def test_decode_correction_tightens_itl():
    pl = make_planner()
    pl.d_correction_factor = 2.0    # observed itl 2x the surface
    # corrected sla = 25ms ⇒ kv ≈ 0.375 ⇒ thpt/chip ≈ 437 < 1000
    _, num_d = pl.compute_replica_requirements(100, 1000, 100)
    base_pl = make_planner()
    _, num_d_base = base_pl.compute_replica_requirements(100, 1000, 100)
    assert num_d >= num_d_base


def test_chip_budget_clamp_prefers_min_endpoint():
    pl = make_planner(max_chip_budget=4)
    num_p, num_d = pl.compute_replica_requirements(1000, 1000, 100)
    assert num_p * 1 + num_d * 1 <= 4 + 1  # round() slack, ref semantics
    assert num_p >= 1 and num_d >= 1


def test_min_endpoint_floor():
    pl = make_planner(min_endpoint=2)
    num_p, num_d = pl.compute_replica_requirements(1, 64, 4)
    assert num_p == 2 and num_d == 2


# -- profiler on the mocker --------------------------------------------------


async def test_profile_sla_on_mocker(tmp_path):
    from dynamo_tpu.mocker.engine import MockEngine, MockEngineConfig
    from dynamo_tpu.planner.profile_sla import profile_engine

    eng = MockEngine(MockEngineConfig(speedup=500.0,
                                      default_max_tokens=64))
    try:
        path = str(tmp_path / "profile.json")
        profile = await profile_engine(
            eng, isls=[32, 64, 128], context_lengths=[64, 128],
            concurrencies=[1, 4], max_kv_tokens=1024 * 16,
            output_path=path)
        pi = PrefillInterpolator(profile_path=path)
        di = DecodeInterpolator(profile_path=path)
        assert pi.interpolate_thpt_per_chip(64) > 0
        assert di.interpolate_itl(1, 96) >= 0
        # longer prompts must not be *faster* to prefill end-to-end
        assert pi.interpolate_ttft(128) >= pi.interpolate_ttft(32) * 0.5
    finally:
        await eng.close()


# -- e2e: planner scales mocker workers through the virtual connector -------


async def test_planner_scaling_e2e_with_mockers():
    """Synthetic load ramps up then down; a supervisor coroutine applies
    the virtual connector's targets by starting/stopping in-proc mocker
    workers; live instance counts must follow."""
    from dynamo_tpu.llm.entrypoint import serve_engine
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.mocker.engine import MockEngine, MockEngineConfig
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    rt = await DistributedRuntime.create(RuntimeConfig(store_url="memory"))
    connector = VirtualConnector(rt, "dynamo")

    class Source:
        """Scripted load: low → high → low."""

        def __init__(self):
            self.script = [
                IntervalMetrics(10, 1000, 100, 0.05, 0.02, 2.0),
                IntervalMetrics(1000, 1000, 100, 0.3, 0.04, 5.0),
                IntervalMetrics(1000, 1000, 100, 0.3, 0.04, 5.0),
                IntervalMetrics(10, 1000, 100, 0.05, 0.02, 2.0),
            ]
            self.i = 0

        async def interval_metrics(self):
            m = self.script[min(self.i, len(self.script) - 1)]
            self.i += 1
            return m

    cfg = SlaPlannerConfig(adjustment_interval=10.0, max_chip_budget=32,
                           no_correction=True)
    planner = Planner(cfg, PrefillInterpolator(raw_data=PREFILL_RAW),
                      DecodeInterpolator(raw_data=DECODE_RAW),
                      Source(), connector=connector)

    # supervisor: reconcile decode-pool mocker workers to the target
    card = ModelDeploymentCard(name="mock-model", namespace="dynamo",
                               component="backend", tokenizer_kind="word",
                               tokenizer_path="mock-model")
    workers: list = []

    async def reconcile():
        targets = await connector.read_targets()
        want = {t["component"]: t["desired_replicas"]
                for t in targets["targets"]}
        n = want.get("backend", 0)
        while len(workers) < n:
            eng = MockEngine(MockEngineConfig(worker_id=len(workers) + 1,
                                              speedup=200.0))
            h = await serve_engine(rt, eng, card,
                                   instance_id=len(workers) + 1)
            workers.append((eng, h))
        while len(workers) > n:
            eng, h = workers.pop()
            await h.stop()
            await eng.close()

    try:
        # interval 1: low load → minimal pools
        await planner.step()
        await reconcile()
        low_n = len(workers)
        assert low_n >= 1
        # interval 2-3: high load → scale up
        await planner.step()
        await reconcile()
        await planner.step()
        await reconcile()
        high_n = len(workers)
        assert high_n > low_n
        assert planner.last_targets[0] >= 1  # prefill pool sized too
        # interval 4: load drops → scale back down
        await planner.step()
        await reconcile()
        assert len(workers) < high_n
        # live instance count matches the reconciled worker set
        assert await connector.current_replicas("backend") == len(workers)
    finally:
        for eng, h in workers:
            await h.stop()
            await eng.close()
        await rt.close()


def test_predictor_skips_nan_samples():
    # review regression: idle intervals report NaN isl/osl; coercing them
    # to 0.0 collapsed EWMA/trend forecasts after traffic gaps
    from dynamo_tpu.planner.load_predictor import EwmaPredictor

    p = EwmaPredictor(alpha=0.5)
    for _ in range(10):
        p.add_data_point(1000.0)
    for _ in range(5):
        p.add_data_point(float("nan"))   # idle: undefined ISL
    assert p.predict_next() > 900        # forecast unharmed by the gap
    p.add_data_point(0.0)                # a true zero IS a sample
    assert p.predict_next() < 1000


def test_constant_predictor_honors_window_size():
    from dynamo_tpu.planner.load_predictor import ConstantPredictor

    p = ConstantPredictor(window_size=3)
    assert p.window_size == 3
    for v in [1, 2, 3, 4, 5]:
        p.add_data_point(v)
    assert p.data_buffer == [3.0, 4.0, 5.0]


async def test_virtual_connector_revision_survives_restart():
    from dynamo_tpu.planner.connector import TargetReplica, VirtualConnector
    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    rt = await DistributedRuntime.create(RuntimeConfig(store_url="memory"))
    try:
        c1 = VirtualConnector(rt, "ns")
        t = [TargetReplica("backend", "decode", 2)]
        await c1.set_component_replicas(t)
        await c1.set_component_replicas(t)
        assert (await c1.read_targets())["revision"] == 2
        # a fresh connector (planner restart) must continue, not reset
        c2 = VirtualConnector(rt, "ns")
        await c2.set_component_replicas(t)
        assert (await c2.read_targets())["revision"] == 3
    finally:
        await rt.close()


async def test_profiler_normalizes_per_chip(tmp_path):
    from dynamo_tpu.mocker.engine import MockEngine, MockEngineConfig
    from dynamo_tpu.planner.profile_sla import profile_prefill

    eng = MockEngine(MockEngineConfig(block_size=16, worker_id=1,
                                      speedup=500.0, default_max_tokens=4))
    try:
        four = await profile_prefill(eng, [64], reps=1, num_chips=4)
        # internal consistency (wall-clock independent): the recorded
        # throughput must equal isl / ttft / num_chips for the SAME run
        ttft_s = four["ttft_ms"][0] / 1000
        assert four["thpt_per_chip"][0] == pytest.approx(
            64 / ttft_s / 4, rel=1e-6)
        assert four["num_chips"] == 4
    finally:
        await eng.close()


def test_pre_swept_sizing_no_engine_boot():
    """The planner sizes p/d pools from a COMMITTED
    pre-swept table alone — no engine, no live profiling."""
    import json
    import subprocess
    import sys

    from dynamo_tpu.planner.pre_swept import (
        load_pre_swept,
        size_from_pre_swept,
    )

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    table = os.path.join(repo, "deploy", "pre_swept", "mocker_v0.json")
    profile = load_pre_swept(table)
    out = size_from_pre_swept(profile, ttft_ms=500, itl_ms=50,
                              req_per_s=4.0, isl=1024, osl=256)
    assert out["prefill_replicas"] >= 1
    assert out["decode_replicas"] >= 1
    assert out["total_chips"] == (out["prefill_replicas"]
                                  + out["decode_replicas"])
    assert out["expected_ttft_ms"] > 0
    # heavier load must not shrink the pools
    heavy = size_from_pre_swept(profile, ttft_ms=500, itl_ms=50,
                                req_per_s=40.0, isl=1024, osl=256)
    assert heavy["prefill_replicas"] >= out["prefill_replicas"]
    assert heavy["decode_replicas"] >= out["decode_replicas"]

    # the CLI path end to end (still no engines)
    proc = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.planner.pre_swept", table,
         "--ttft-ms", "500", "--itl-ms", "50", "--req-per-s", "4",
         "--isl", "1024", "--osl", "256"],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr
    cli = json.loads(proc.stdout)
    assert cli["prefill_replicas"] == out["prefill_replicas"]


def test_pre_swept_rejects_malformed_table(tmp_path):
    import json

    import pytest as _pytest

    from dynamo_tpu.planner.pre_swept import load_pre_swept

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"prefill": {"isl": [1]}}))
    with _pytest.raises(ValueError):
        load_pre_swept(str(bad))


def test_holtwinters_tracks_seasonal_load():
    """The seasonal predictor must forecast a sinusoidal load with the
    upcoming phase, where EWMA/linear lag it (the Prophet/ARIMA
    planning role)."""
    from dynamo_tpu.planner.load_predictor import (
        EwmaPredictor,
        HoltWintersPredictor,
    )

    period = 12
    series = [100 + 80 * math.sin(2 * math.pi * t / period)
              for t in range(1, 5 * period)]
    hw = HoltWintersPredictor(period=period)
    ew = EwmaPredictor()
    for v in series:
        hw.add_data_point(v)
        ew.add_data_point(v)
    t_next = len(series) + 1
    truth = 100 + 80 * math.sin(2 * math.pi * t_next / period)
    hw_err = abs(hw.predict_next() - truth)
    ew_err = abs(ew.predict_next() - truth)
    assert hw_err < 15, (hw.predict_next(), truth)
    assert hw_err < ew_err / 2, (hw_err, ew_err)
    # trend + season: a ramping sinusoid stays tracked
    series2 = [t * 2 + 50 * math.sin(2 * math.pi * t / period)
               for t in range(1, 5 * period)]
    hw2 = HoltWintersPredictor(period=period)
    for v in series2:
        hw2.add_data_point(v)
    t2 = len(series2) + 1
    truth2 = t2 * 2 + 50 * math.sin(2 * math.pi * t2 / period)
    assert abs(hw2.predict_next() - truth2) < 20, \
        (hw2.predict_next(), truth2)
    # planner integration: a holtwinters Planner forecasts seasonal
    # request load into its replica math
    pl = make_planner(load_predictor="holtwinters",
                      load_predictor_period=period)
    for v in series:
        pl.num_req_predictor.add_data_point(v)
        pl.isl_predictor.add_data_point(64)
        pl.osl_predictor.add_data_point(16)
    num_req, isl, osl = pl.predict_load()
    assert abs(num_req - truth) < 15, (num_req, truth)
    assert pl.compute_replica_requirements(num_req, isl, osl)[0] >= 1


def test_holtwinters_gap_keeps_seasonal_phase():
    """NaN (idle) samples must carry forward, not be dropped — a
    dropped interval would phase-shift every later forecast."""
    from dynamo_tpu.planner.load_predictor import HoltWintersPredictor

    period = 8
    hw = HoltWintersPredictor(period=period)
    for t in range(1, 4 * period):
        hw.add_data_point(100 + 50 * math.sin(2 * math.pi * t / period))
        if t == 2 * period:
            # an idle stretch reports NaN isl/osl for 3 intervals
            for _ in range(3):
                hw.add_data_point(float("nan"))
    # without gap placeholders the 3 dropped samples would shift the
    # phase by 3/8 of a period (~2.7x the tolerance below)
    t_next = 4 * period + 3 + 1
    truth = 100 + 50 * math.sin(2 * math.pi * t_next / period)
    assert abs(hw.predict_next() - truth) < 25, \
        (hw.predict_next(), truth)


def test_holtwinters_rejects_window_smaller_than_two_periods():
    from dynamo_tpu.planner.load_predictor import HoltWintersPredictor

    with pytest.raises(ValueError, match="window"):
        HoltWintersPredictor(period=12, window_size=20)


def test_holtwinters_short_series_falls_back():
    from dynamo_tpu.planner.load_predictor import HoltWintersPredictor

    hw = HoltWintersPredictor(period=12)
    for v in (10, 20, 30, 40, 50):
        hw.add_data_point(v)
    # < 2 periods: linear-trend fallback, not a crash
    assert 50 <= hw.predict_next() <= 70


# -- sizing math at the clamp edges (autoscale-loop satellite) ---------------


def test_budget_exhausted_sizes_prefill_first():
    """When demand overruns the chip budget, the clamp scales prefill
    first and decode gets whatever chips REMAIN — never a proportional
    share that would overshoot the budget."""
    pl = make_planner(max_chip_budget=6)
    # unclamped: prefill 1000*1000/10 / 10000 = 10 chips; decode
    # 1000*20/10 = 2000 tok/s / 1000 per chip = 2 chips; total 12 > 6
    num_p, num_d = pl.compute_replica_requirements(1000, 1000, 20)
    assert num_p == 5                  # round(10 * 6/12)
    assert num_d == 1                  # budget - prefill, floored
    assert num_p + num_d <= 6


def test_budget_exhausted_min_endpoint_floor_wins():
    """min_endpoint outranks the budget clamp on BOTH pools (reference
    semantics: a pool is never scaled to zero by the clamp)."""
    pl = make_planner(max_chip_budget=3, min_endpoint=2)
    num_p, num_d = pl.compute_replica_requirements(1000, 1000, 100)
    assert num_p == 2 and num_d == 2   # floor holds even over budget


async def test_invalid_interval_skips_adjustment():
    """An interval with no (or NaN) traffic must produce NO adjustment:
    make_adjustments returns None, targets stay untouched, and the
    connector sees no new revision — the supervisor keeps the current
    fleet instead of collapsing it on a telemetry gap."""

    class Recorder:
        def __init__(self):
            self.calls = 0

        async def set_component_replicas(self, targets):
            self.calls += 1

    rec = Recorder()
    pl = make_planner(connector=rec)
    # no observe yet: last_metrics is all-NaN
    assert await pl.make_adjustments() is None
    # zero-request interval is invalid too (is_valid needs num_req > 0)
    pl.last_metrics = IntervalMetrics(0, 100, 10, 0.1, 0.01, 1.0)
    assert await pl.make_adjustments() is None
    assert rec.calls == 0
    assert pl.last_targets == (0, 0)
    # a valid interval immediately resumes publishing
    pl.last_metrics = IntervalMetrics(100, 1000, 100, 0.1, 0.01, 1.0)
    pl.num_req_predictor.add_data_point(100)
    pl.isl_predictor.add_data_point(1000)
    pl.osl_predictor.add_data_point(100)
    assert await pl.make_adjustments() == (1, 1)
    assert rec.calls == 1
