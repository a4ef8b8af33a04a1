"""Check the split of device idle by host span (`lib/host_spans.py`),
without a chip.

1. Hand-made planes in the TPU's layout: a gap covered by one span, by
   nested spans on two threads, by a `wait` marker pair and by nothing come
   out as computed by hand, and add up to `lib/trace.py`'s idle; the same
   with the device's clock set off against the host's and launches paired
   with their programs by `run_id` to find the offset by; with fewer than
   `MIN_TIGHT` tight pairs `clock.ok` is false.
2. The recorded `small_tpu_spans.xplane.pb` (record_small_spans.py on a TPU
   v5 lite: 12 dispatches, each under an `engine.dispatch` span, then 1 ms
   under `engine.emit`, 2 ms between a `wait` pair, 0.5 ms under nothing):
   the spans are found, the idle adds up to `lib/trace.py`'s, the sleeps
   land under `wait`. Host and device planes do NOT share one timeline as
   recorded: the device's events sit 1.2 ms early. With the offset that
   `lib/host_spans.py` measures from the runtime's launch events, every
   device program starts inside its host `dispatch` span; offset and lag
   are printed. The `dispatch` spans' `entry` / `tokens` attributes are read
   back.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearsal/check_host_spans.py
"""

import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from lib import host_spans, trace  # noqa: E402


def near(a: float, b: float, tol: float = 1e-9) -> bool:
    return abs(a - b) <= tol


def idle_of_trace(planes: list[dict]) -> float:
    out = trace.reduce_planes(planes)
    return out["window_s"] - out["busy_s"]


def check_by_hand() -> None:
    ops = [(0.0, 1.0, "%fusion.1 = f()"), (2.0, 3.0, "%fusion.2 = f()"),
           (5.0, 6.0, "%fusion.3 = f()"), (8.0, 9.0, "%copy.4 = copy()")]
    mods = [(s, e, f"jit_step({i})") for i, (s, e, _) in enumerate(ops)]
    loop = [(0.0, 10.0, "thread"),                    # the window: 0..10
            (0.9, 2.1, "engine.emit"),                # gap 1..2: one span
            (3.0, 5.0, "engine.admit"),               # gap 3..5: nested
            (6.2, 6.2, "engine.wait.begin"),          # gap 6..8: a pair
            (7.5, 7.5, "engine.wait.end"),
            (9.5, 9.5, "engine.yield.begin")]         # open at the end
    closure = [(4.0, 5.2, "engine.dispatch")]         # started last: wins
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/host:CPU", "lines": [
            {"name": "loop", "events": loop},
            {"name": "closure", "events": closure}]}]
    out = host_spans.reduce_planes(planes)
    assert near(out["window_s"], 10.0) and near(out["idle_s"], 6.0), out
    assert near(out["idle_s"], idle_of_trace(planes)), out
    want = {"emit": 1.0, "admit": 1.0, "dispatch": 1.0, "wait": 1.3,
            "yield": 0.5}
    assert set(out["idle_by_phase"]) == set(want), out
    for phase, sec in want.items():
        assert near(out["idle_by_phase"][phase], sec), (phase, out)
    # 6..6.2 and 7.5..8 of the third gap, 9..9.5 after the last operation
    assert near(out["unattributed_s"], 1.2), out
    assert near(out["idle_by_kind"]["sched"], 2.0), out
    assert near(out["idle_by_kind"]["device"], 1.0), out
    assert near(out["idle_by_kind"]["idle"], 1.8), out
    assert out["phases"]["wait"]["count"] == 1, out
    assert near(out["phases"]["admit"]["self_s"], 1.0), out   # 2 - nested 1
    assert near(out["phases"]["dispatch"]["self_s"], 1.2), out
    assert not out["clock"]["ok"], out["clock"]      # nothing to pair
    # the device's clock 12 ms behind the host's: the runtime's launches
    # (2 ms before each program starts), paired by run_id, give the offset
    # back but for that latency, and the split comes out the same to
    # within it; a program queued before the trace began has no launch in
    # it and is left out
    skewed = [dict(p, lines=[dict(ln, events=[
        (s - 0.012, e - 0.012, n) for s, e, n in ln["events"]])
        for ln in p["lines"]]) for p in planes[:1]] + planes[1:]
    stats = {"launches": {(0, i): s - 0.002 for i, (s, _, _)
                          in enumerate(mods)},
             "programs": {(0, i): s - 0.012 for i, (s, _, _)
                          in enumerate(mods)} | {(0, 99): -0.5},
             "dispatches": [("step", 3), ("step", 4), ("other", 1)]}
    got = host_spans.reduce_planes(skewed, stats)
    assert near(got["clock"]["offset_s"], 0.010), got["clock"]
    assert got["clock"]["pairs"] == got["clock"]["tight_pairs"] == len(mods)
    assert got["clock"]["ok"], got["clock"]
    assert got["dispatch_tokens"] == {
        "other": {"count": 1, "tokens": 1},
        "step": {"count": 2, "tokens": 7}}, got["dispatch_tokens"]
    # one launch on an idle device, the rest queued behind a busy one:
    # a single tight pair is not enough to trust
    late = dict(stats, launches={k: t - (0.0 if k == (0, 0) else 0.005)
                                 for k, t in stats["launches"].items()})
    lone = host_spans.clock_offset(late["launches"], late["programs"])
    assert lone["tight_pairs"] == 1 and not lone["ok"], lone
    for phase in ("emit", "admit", "dispatch", "wait"):     # to the latency
        assert near(got["idle_by_phase"][phase], want[phase], 0.0021), got
    # a trace that starts inside a wait: the first end has no begin
    planes[1]["lines"][0]["events"] = [
        (0.0, 10.0, "thread"), (1.5, 1.5, "engine.wait.end")]
    planes[1]["lines"][1]["events"] = []
    cut = host_spans.reduce_planes(planes)
    assert near(cut["idle_by_phase"]["wait"], 0.5), cut       # gap 1..1.5
    # no span at all: a worker from before them
    planes[1]["lines"][0]["events"] = [(0.0, 10.0, "thread")]
    bare = host_spans.reduce_planes(planes)
    assert bare["spans"] == 0 and near(bare["unattributed_s"], 6.0), bare


def check_recorded() -> None:
    from record_small_spans import BARE_S, EMIT_S, WAIT_S
    from record_small_trace import DISPATCHES as n

    path = os.path.join(HERE, "small_tpu_spans.xplane.pb")
    planes = trace.load_planes(path)
    out = host_spans.reduce_planes(planes, host_spans.load_stats(path))
    assert not out["stand_in"] and out["devices"] == 1, out
    assert near(out["idle_s"], idle_of_trace(planes), 1e-6), out
    for phase in ("dispatch", "emit", "wait"):
        assert out["phases"][phase]["count"] == n, (phase, out["phases"])
    by = out["idle_by_phase"]
    # the device does nothing while the host sleeps or spins
    assert by["wait"] >= n * WAIT_S and by["emit"] >= 0.9 * n * EMIT_S, by
    assert out["unattributed_s"] >= 0.9 * (n - 1) * BARE_S, out
    assert near(sum(out["idle_by_kind"].values()), out["idle_s"], 1e-9), out
    # one timeline once the offset is taken out: each device program
    # starts inside its dispatch span. As recorded, none does
    off = out["clock"]["offset_s"]
    assert out["clock"]["tight_pairs"] >= n - 1 and 0.0005 < off < 0.003, \
        out["clock"]
    assert out["clock"]["pairs"] == n and out["clock"]["ok"], out["clock"]
    assert out["dispatch_tokens"] == {"small_step": {
        "count": n, "tokens": sum(range(n))}}, out["dispatch_tokens"]
    w0, w1 = host_spans.trace_window(planes)
    spans = sorted((s - off, e - off) for s, e, ph in host_spans.host_spans(
        planes, w0, w1) if ph == "dispatch")
    device = next(p for p in planes if p["name"].startswith("/device:TPU"))
    mods = sorted(ev for ln in device["lines"] if ln["name"] == "XLA Modules"
                  for ev in ln["events"])
    assert len(mods) == len(spans) == n, (len(mods), len(spans))
    lags = []
    for (m0, m1, name), (s0, s1) in zip(mods, spans):
        assert s0 <= m0 and m1 <= s1, (name, m0 - s0, s1 - m1)
        lags.append(m0 - s0)
    print(f"recorded trace: window {out['window_s']:.4f}s idle "
          f"{out['idle_s']:.4f}s = wait {by['wait']:.4f} + emit "
          f"{by['emit']:.4f} + dispatch {by['dispatch']:.4f} + unattributed "
          f"{out['unattributed_s']:.4f}; the device's clock sits "
          f"{1e6 * off:.0f} us behind the host's ({out['clock']}); with "
          f"that taken out a device program starts {1e6 * min(lags):.0f} / "
          f"{1e6 * statistics.median(lags):.0f} / {1e6 * max(lags):.0f} us "
          f"(min / median / max) after its host dispatch span opens")


if __name__ == "__main__":
    check_by_hand()
    check_recorded()
    print("host span reduction: ok")
