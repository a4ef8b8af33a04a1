"""Host spans of the scheduler on the profiler's clock (engine/profiler.py
`span`, docs/observability.md "Step profiler"): what an armed engine leaves
in a `jax.profiler` trace and on /metrics, that no span crosses an `await`,
what the benchmark's reduction rebuilds from it, that an unarmed engine is
untouched, and the worker's device-trace route."""

import asyncio
import glob
import importlib.util
import os
import time

import jax
import pytest

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.engine.profiler import HOST_PHASES
from dynamo_tpu.models.llama import LlamaConfig
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.metrics import MetricsRegistry

set_attention_impl("xla")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KIND = dict(HOST_PHASES)
K_STEPS = 4
PROMPTS = ([7, 8, 9, 10, 11, 12, 13, 14, 15],
           list(range(40, 60)), list(range(90, 101)))
MAX_TOKENS = 22


def reduction():
    """benchmarks/chip/lib/host_spans.py, as the benchmark's reader runs it."""
    path = os.path.join(ROOT, "benchmarks", "chip", "lib", "host_spans.py")
    spec = importlib.util.spec_from_file_location("bench_host_spans", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def idle_reader():
    """benchmarks/chip/readers/idle_by_kind.py, imported as run.py does:
    with benchmarks/chip on the path."""
    import sys

    bench = os.path.join(ROOT, "benchmarks", "chip")
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            "bench_idle_by_kind",
            os.path.join(bench, "readers", "idle_by_kind.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(bench)
        for name in [m for m in sys.modules
                     if m == "lib" or m.startswith("lib.")]:
            del sys.modules[name]
    return mod


def make_engine() -> TpuEngine:
    return TpuEngine(TpuEngineConfig(
        model=LlamaConfig.tiny(), num_pages=64, max_batch_size=4,
        prefill_chunk=32, min_prefill_bucket=8, default_max_tokens=8,
        decode_steps_per_sync=K_STEPS))


async def tokens_of(eng: TpuEngine, prompt: list[int]) -> list[int]:
    request = {"token_ids": prompt, "model": "m",
               "sampling": {"temperature": 0.0, "seed": None},
               "stop": {"max_tokens": MAX_TOKENS, "stop_token_ids": []}}
    return [t async for o in eng.generate(request, Context())
            for t in o.get("token_ids", ())]


async def serve(eng: TpuEngine) -> list[list[int]]:
    """One request alone, a pause with the engine empty, then two at once."""
    first = await tokens_of(eng, PROMPTS[0])
    await asyncio.sleep(0.06)
    rest = await asyncio.gather(*(tokens_of(eng, p) for p in PROMPTS[1:]))
    return [first, *rest]


def metric_names(eng: TpuEngine) -> set[str]:
    reg = MetricsRegistry()
    eng.metrics.register(reg)
    return {line.split()[2] for line in reg.render().splitlines()
            if line.startswith("# TYPE")}


def counted(eng: TpuEngine) -> dict:
    m, rec = eng.metrics, eng.step_recorder.summary()
    return {
        "seconds": {lab["phase"]: v for lab, v in m.host_seconds.items()},
        "spans": {lab["phase"]: v for lab, v in m.host_spans.items()},
        "kinds": {lab["phase"]: lab["kind"]
                  for lab, _ in m.host_spans.items()},
        "prefill_tokens": m.prefill_new_tokens.get(),
        "decode_goodput": m.goodput_tokens.get(entry="decode_burst"),
        "decode_records": rec["entries"].get(
            "decode_burst", {"count": 0})["count"],
        "pipelined": m.pipelined_bursts.get(),
    }


@pytest.fixture(scope="module")
def armed(tmp_path_factory):
    """An armed toy engine, warmed up, then traced while it serves: the
    trace's `engine.*` events by thread line, and what the engine counted
    meanwhile."""
    from jax.profiler import ProfileData

    out = str(tmp_path_factory.mktemp("trace"))

    async def scenario():
        eng = make_engine()
        try:
            await serve(eng)                      # every program compiled
            await asyncio.sleep(0.02)             # the loop is in `wait`
            before = counted(eng)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(out, profiler_options=opts)
            t0 = time.perf_counter()
            try:
                tokens = await serve(eng)
                await asyncio.sleep(0.02)
            finally:
                wall = time.perf_counter() - t0
                jax.profiler.stop_trace()
            return {"tokens": tokens, "wall": wall, "before": before,
                    "after": counted(eng), "names": metric_names(eng),
                    "labels": {(e["entry"], e["shape"])
                               for e in eng.metrics.compile.events}}
        finally:
            await eng.close()

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DYN_STEP_PROFILE", "1")
        got = asyncio.run(scenario())
    path = glob.glob(os.path.join(
        out, "plugins", "profile", "*", "*.xplane.pb"))[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                    ev.name[len("engine."):], dict(ev.stats))
                   for ev in line.events if ev.name.startswith("engine.")]
            if evs:
                lines.append(sorted(evs, key=lambda e: (e[0], -e[1])))
    got["lines"] = lines
    got["path"] = path

    def delta(key):
        a, b = got["after"][key], got["before"][key]
        if isinstance(a, dict):
            return {k: v - b.get(k, 0.0) for k, v in a.items()
                    if v != b.get(k, 0.0)}
        return a - b
    got["delta"] = delta
    return got


def events_of(armed, *phases):
    return [ev for line in armed["lines"] for ev in line if ev[2] in phases]


def test_phase_table_is_the_reductions():
    """The benchmark's copy of phase -> kind is the program's."""
    assert reduction().PHASE_KIND == KIND
    assert set(KIND.values()) == {"sched", "device", "idle"}


def test_every_phase_passed_through_is_in_the_trace(armed):
    spans = armed["delta"]("spans")
    assert {"admit", "prefill_prep", "decode_prep", "sample_first", "emit",
            "dispatch", "sync", "wait"} <= set(spans)
    in_trace = {ev[2].split(".")[0] for line in armed["lines"] for ev in line}
    assert in_trace == set(spans)
    for phase, n in spans.items():
        if KIND[phase] == "idle":       # a marker pair, perhaps cut by an end
            pairs = (len(events_of(armed, phase + ".begin")),
                     len(events_of(armed, phase + ".end")))
            assert n in range(min(pairs), max(pairs) + 1), (phase, n, pairs)
        else:
            assert len(events_of(armed, phase)) == n, phase
    assert armed["after"]["kinds"] == {p: KIND[p]
                                       for p in armed["after"]["kinds"]}


def test_spans_nest_on_their_line_and_none_crosses_an_await(armed):
    for line in armed["lines"]:
        stack = []
        for s, e, name, _ in line:
            while stack and stack[-1] <= s:
                stack.pop()
            assert not stack or e <= stack[-1], f"{name} straddles a span"
            stack.append(e)
    # the scheduler's own phases and the markers share ONE line, the event
    # loop's thread; what runs in a dispatch closure is never on it
    loop_phases = {p for p, k in KIND.items() if k != "device"} - {
        "prefill_prep", "sample_first", "decode_prep"}
    on = [i for i, line in enumerate(armed["lines"])
          if any(ev[2].split(".")[0] in loop_phases for ev in line)]
    assert len(on) == 1, on
    loop_line = armed["lines"][on[0]]
    assert not [ev for ev in loop_line if ev[2] in ("dispatch", "sync")]
    # a span held across an await would contain the dispatch it awaited
    # (the other way round is the landing: the loop admits, and builds a
    # held successor, under the `sync` of the burst it waits for)
    device = events_of(armed, "dispatch", "sync")
    assert device
    for s, e, name, _ in loop_line:
        inside = [d for d in device if s <= d[0] and d[1] <= e]
        assert not inside, f"engine.{name} holds {inside[0][2]}"


def test_dispatch_carries_the_compile_trackers_labels(armed):
    dispatches = events_of(armed, "dispatch")
    assert {(st["entry"], st["shape"]) for *_, st in dispatches} \
        <= armed["labels"]

    def tokens(entry):
        return sum(int(st["tokens"]) for *_, st in dispatches
                   if st["entry"] == entry)

    # the warm-up left the prompts' whole pages in the prefix cache
    assert 0 < tokens("prefill") == armed["delta"]("prefill_tokens") \
        < sum(len(p) for p in PROMPTS)
    assert tokens("decode_burst") == armed["delta"]("decode_goodput")
    assert tokens("sample_first") == len(PROMPTS)


def test_pipelined_burst_is_under_a_dispatch_span(armed):
    bursts = [st for *_, st in events_of(armed, "dispatch")
              if st["entry"] == "decode_burst"]
    assert armed["delta"]("pipelined") > 0
    # every decode burst the recorder saw, the speculative ones included
    assert len(bursts) == armed["delta"]("decode_records")
    assert {st["shape"] for st in bursts} == {f"4x{K_STEPS}x0"}


def test_host_seconds_fit_the_wall_clock(armed):
    seconds = armed["delta"]("seconds")
    assert all(v >= 0.0 for v in seconds.values())
    # spans of one engine never overlap: the loop awaits its closures
    assert 0.0 < sum(seconds.values()) <= armed["wall"]
    assert seconds["wait"] >= 0.05        # the pause with the engine empty


def test_wait_is_rebuilt_and_idle_adds_up(armed):
    mod = reduction()
    planes = mod.load_planes(armed["path"])
    w0, w1 = mod.trace_window(planes)
    waits = [(s, e) for s, e, ph in mod.host_spans(planes, w0, w1)
             if ph == "wait"]
    assert waits and max(e - s for s, e in waits) >= 0.05
    assert all(w0 <= s <= e <= w1 for s, e in waits)
    out = mod.reduce_planes(planes, mod.load_stats(armed["path"]))
    assert out["spans"] == sum(len(line) for line in armed["lines"]) \
        - len(events_of(armed, "wait.begin", "wait.end", "yield.begin",
                        "yield.end")) + out["phases"]["wait"]["count"] \
        + out["phases"].get("yield", {"count": 0})["count"]
    assert abs(sum(out["idle_by_kind"].values()) - out["idle_s"]) < 1e-9
    assert abs(sum(out["idle_by_phase"].values()) + out["unattributed_s"]
               - out["idle_s"]) < 1e-9
    assert out["idle_by_kind"]["idle"] >= 0.04      # nothing ran in `wait`
    assert set(out["phases"]) == set(armed["delta"]("spans"))
    # the reduction reads the dispatch spans' attributes back: tokens
    # counted where the round ran
    by_entry = out["dispatch_tokens"]
    assert by_entry["prefill"]["tokens"] == armed["delta"]("prefill_tokens")
    assert by_entry["decode_burst"]["tokens"] \
        == armed["delta"]("decode_goodput")
    assert sum(e["count"] for e in by_entry.values()) \
        == len(events_of(armed, "dispatch"))
    # a CPU trace has no device plane to set the host's clock against:
    # the readers leave the idle shares out rather than print them
    assert out["stand_in"] and not out["clock"]["ok"]


@pytest.mark.parametrize("spans,ok,want", [
    (0, True, None),        # a worker from before the spans
    (40, False, None),      # the clock offset could not be measured
    (40, True, 1.5),        # 0.03 s of 2 s
])
def test_idle_reader_prints_no_share_without_a_measured_clock(
        monkeypatch, spans, ok, want):
    mod = idle_reader()
    monkeypatch.setattr(mod, "summary", lambda: {
        "spans": spans, "clock": {"ok": ok},
        "idle_by_kind": {"sched": 0.02, "device": 0.01, "idle": 0.5}})
    got = mod.read({"trace": {"window_s": 2.0}}, ["sched", "device"])
    assert got == want if want is None else abs(got - want) < 1e-9


async def test_unarmed_engine_is_untouched(armed, monkeypatch):
    from dynamo_tpu.engine import profiler

    def refuse(*a, **kw):
        raise AssertionError("a TraceAnnotation was constructed unarmed")

    monkeypatch.delenv("DYN_STEP_PROFILE", raising=False)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(profiler, "_TraceAnnotation", None)
    eng = make_engine()
    try:
        assert eng.step_recorder is None
        assert eng.metrics.host_seconds is None
        assert await serve(eng) == armed["tokens"]
        names = metric_names(eng)
    finally:
        await eng.close()
    new = {"dynamo_engine_host_seconds_total",
           "dynamo_engine_host_spans_total"}
    assert not names & new
    assert armed["names"] == names | new


@pytest.mark.parametrize("query,status", [
    ("?capture_s=0.2", 200), ("?capture_s=nope", 400), ("", 400)])
async def test_worker_system_port_traces_its_process(tmp_path, query,
                                                     status):
    import aiohttp

    from dynamo_tpu.runtime.config import RuntimeConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    rt = await DistributedRuntime.create(RuntimeConfig(
        store_url="memory", system_port=0))
    if status == 200:
        query += f"&dir={tmp_path}"
    try:
        url = f"http://127.0.0.1:{rt._status_server.port}/debug/profile"
        async with aiohttp.ClientSession() as s:
            async with s.get(url + query) as r:
                assert r.status == status
                body = await r.json()
    finally:
        await rt.close()
    if status == 200:
        assert body["out_dir"] == str(tmp_path)
        assert glob.glob(os.path.join(
            body["out_dir"], "plugins", "profile", "*", "*.xplane.pb"))
    else:
        assert "capture_s" in body["error"]
