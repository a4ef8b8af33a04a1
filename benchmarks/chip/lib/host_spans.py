"""Split the device's idle time of a traced run by what the host was doing.

    python lib/host_spans.py <trace dir> <out.json>

The worker, armed with DYN_STEP_PROFILE, wraps the scheduler's host work in
`jax.profiler.TraceAnnotation`s named `engine.<phase>` (engine/profiler.py):
they land in the host planes of the same `.xplane.pb`, on the clock of the
device planes. This reduction takes

- the idle time exactly as `device_idle` has it (lib/trace.py): per device,
  the gaps of the union of its `XLA Ops`, plus the stretch of the window
  (first to last event over every plane) before its first and after its
  last operation; averaged over devices;
- the `engine.*` events of every host thread line. The two awaited phases
  are not spans in the trace (an annotation cannot cross an `await`): a
  marker pair `engine.wait.begin` / `engine.wait.end` on one line becomes
  the span between them, cut at the window's ends where one of the pair
  fell outside the trace;

and splits each idle stretch among the spans that cover it. Where spans
nest or overlap across threads the innermost wins: the one that started
last. Idle time under no span is `unattributed`: the loop's thread in other
tasks, the hop to and from a dispatch thread, a wait for the GIL.

**The two clocks.** The device planes' timestamps come from the chip's
clock, converted once per profiling session; against the host planes they
sit off by a constant that differs from session to session (0.6, 1.2, 1.7
and 2.2 ms in four traces of PR 26: every program *starts* that long before
the host launches it), which is the size of the gaps being split. So the
offset is measured in each trace. The runtime's host event
`DoEnqueueProgram` carries the `run_id` of the launch it makes, and the
device's `XLA Modules` event of that program carries the same `run_id`:
the pairs are exact, whatever was queued before the trace began and
whatever the programs are called. A program never starts before it is
enqueued, and one enqueued on an idle device starts a launch latency after
it, so the offset is the largest (enqueue - start) over the pairs; the
pairs within 0.2 ms of it are counted as `tight_pairs`. The spans are moved
onto the device's clock by the offset; what remains is that launch latency
(tens of microseconds), by which device events still sit early. `clock` in
the output says what was found. `clock.ok` is false where fewer than
MIN_TIGHT pairs are tight (one pair alone could be anything) or where host
lines stood in for a device: the readers then leave their metrics out
rather than print a split made with an unknown offset.

Out: `window_s`, `idle_s`, `idle_by_phase`, `idle_by_kind`,
`unattributed_s`, `clock`, per phase `count` and `self_s` (the seconds in
which the phase was the innermost span) within the trace, and
`dispatch_tokens`: per `entry` attribute of the `engine.dispatch` spans,
their count and the sum of their `tokens` attribute, the token positions
counted where the round ran. `spans` is the
number of `engine.*` spans found: 0 for a worker without them (a parent
commit), and then the readers leave their metrics out of the line.
"""

from __future__ import annotations

import bisect
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from lib.trace import find_xplane, load_planes, union_seconds  # noqa: E402

PREFIX = "engine."
# phase -> kind, as engine/profiler.py HOST_PHASES has them (pinned by
# tests/test_host_spans.py); a phase this file does not know counts under
# its own name and under the kind "other"
PHASE_KIND = {
    "admit": "sched", "prefill_prep": "sched", "decode_prep": "sched",
    "sample_first": "sched", "emit": "sched", "publish": "sched",
    "dispatch": "device", "sync": "device",
    "wait": "idle", "yield": "idle",
}
UNATTRIBUTED = "unattributed"
LAUNCH = "DoEnqueueProgram"         # the runtime's host event at a launch
TIGHT_S, MIN_TIGHT = 200e-6, 3


def trace_window(planes: list[dict]) -> tuple[float, float]:
    every = [ev for p in planes for ln in p["lines"] for ev in ln["events"]]
    if not every:
        raise ValueError("the trace holds no event")
    return min(s for s, _, _ in every), max(e for _, e, _ in every)


def device_idle(planes: list[dict], w0: float, w1: float
                ) -> tuple[list[list[tuple[float, float]]], bool]:
    """Idle stretches (start, end) of each device inside the window, by
    lib/trace.py's rule, and whether host lines stood in for a device."""
    devices = [p for p in planes if p["name"].startswith("/device:TPU")]
    stand_in = not devices
    if stand_in:
        devices = [p for p in planes if p["name"].startswith("/host:")]
    out = []
    for plane in devices:
        if stand_in:
            ops = [ev for ln in plane["lines"] for ev in ln["events"]
                   if not ev[2].startswith(PREFIX)]
        else:
            ops = [ev for ln in plane["lines"] if ln["name"] == "XLA Ops"
                   for ev in ln["events"]]
        if not ops:
            continue
        _, gaps = union_seconds([(s, e) for s, e, _ in ops])
        first, last = min(s for s, _, _ in ops), max(e for _, e, _ in ops)
        out.append([(w0, first)] + gaps + [(last, w1)])
    if not out:
        raise ValueError("no device operation in the trace")
    return out, stand_in


def load_stats(path: str) -> dict:
    """What `load_planes` leaves out, the events' attributes: `launches`
    and `programs` as {(device, run_id): start_s}, and `dispatches` as
    (entry, tokens) of every `engine.dispatch` span."""
    from jax.profiler import ProfileData

    launches, programs, dispatches = {}, {}, []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:TPU")
        if not (device or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            if device and line.name != "XLA Modules":
                continue
            for ev in line.events:
                if device:
                    key = (int(plane.name.rsplit(":", 1)[1]),
                           dict(ev.stats).get("run_id"))
                    programs[key] = ev.start_ns * 1e-9
                elif ev.name == LAUNCH:
                    st = dict(ev.stats)
                    key = (int(st.get("device_ordinal", 0)), st.get("run_id"))
                    launches[key] = ev.start_ns * 1e-9
                elif ev.name == PREFIX + "dispatch":
                    st = dict(ev.stats)
                    dispatches.append((str(st.get("entry", "?")),
                                       int(st.get("tokens", 0))))
    return {"launches": launches, "programs": programs,
            "dispatches": dispatches}


def clock_offset(launches: dict, programs: dict) -> dict:
    """Host clock minus device clock from the launches and the programs
    that share a (device, run_id) (see the module's docstring)."""
    lead = [t - programs[key] for key, t in launches.items()
            if key[1] is not None and key in programs]
    found = {"offset_s": 0.0, "launches": len(launches),
             "programs": len(programs), "pairs": len(lead),
             "tight_pairs": 0, "ok": False}
    if lead:
        found["offset_s"] = max(lead)
        found["tight_pairs"] = sum(1 for d in lead
                                   if max(lead) - d < TIGHT_S)
        found["ok"] = found["tight_pairs"] >= MIN_TIGHT
    return found


def host_spans(planes: list[dict], w0: float, w1: float
               ) -> list[tuple[float, float, str]]:
    """(start, end, phase) of every `engine.*` span on the host planes,
    the awaited phases rebuilt from their markers line by line."""
    spans = []
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            opened: dict[str, float] = {}
            for s, e, name in sorted(line["events"]):
                if not name.startswith(PREFIX):
                    continue
                phase = name[len(PREFIX):]
                if phase.endswith(".begin"):
                    opened[phase[:-6]] = s
                elif phase.endswith(".end"):
                    # an end without a begin: the phase was entered
                    # before the trace started
                    phase = phase[:-4]
                    spans.append((opened.pop(phase, w0), e, phase))
                else:
                    spans.append((s, e, phase))
            for phase, start in opened.items():   # still in it at the end
                spans.append((start, w1, phase))
    return spans


def innermost_cover(spans: list[tuple[float, float, str]]
                    ) -> list[tuple[float, float, str]]:
    """Disjoint, sorted (start, end, phase): at each instant the covering
    span that started last."""
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    by_start = sorted(spans)
    cover, live, nxt = [], [], 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while nxt < len(by_start) and by_start[nxt][0] <= t0:
            live.append(by_start[nxt])
            nxt += 1
        live = [sp for sp in live if sp[1] > t0]
        if not live:
            continue
        phase = max(live, key=lambda sp: (sp[0], -sp[1]))[2]
        if cover and cover[-1][2] == phase and cover[-1][1] == t0:
            cover[-1] = (cover[-1][0], t1, phase)
        else:
            cover.append((t0, t1, phase))
    return cover


def split_idle(gaps: list[tuple[float, float]],
               cover: list[tuple[float, float, str]]) -> dict[str, float]:
    """Seconds of `gaps` by the phase covering them."""
    out: dict[str, float] = {}
    starts = [c[0] for c in cover]
    for g0, g1 in gaps:
        if g1 <= g0:
            continue
        left = g1 - g0
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(cover) and cover[i][0] < g1:
            c0, c1, phase = cover[i]
            under = min(c1, g1) - max(c0, g0)
            if under > 0:
                out[phase] = out.get(phase, 0.0) + under
                left -= under
            i += 1
        out[UNATTRIBUTED] = out.get(UNATTRIBUTED, 0.0) + max(left, 0.0)
    return out


def reduce_planes(planes: list[dict], stats: dict | None = None) -> dict:
    """`planes` as lib/trace.py `load_planes` gives them, `stats` as
    `load_stats` gives them for the same file."""
    stats = stats or {"launches": {}, "programs": {}, "dispatches": []}
    w0, w1 = trace_window(planes)
    per_device, stand_in = device_idle(planes, w0, w1)
    spans = host_spans(planes, w0, w1)
    clock = clock_offset(stats["launches"], stats["programs"])
    clock["ok"] = clock["ok"] and not stand_in
    off = clock["offset_s"]
    cover = innermost_cover([(s - off, e - off, ph) for s, e, ph in spans])
    n = len(per_device)
    by_phase: dict[str, float] = {}
    for gaps in per_device:
        for phase, sec in split_idle(gaps, cover).items():
            by_phase[phase] = by_phase.get(phase, 0.0) + sec / n
    unattributed = by_phase.pop(UNATTRIBUTED, 0.0)
    by_kind: dict[str, float] = {}
    for phase, sec in by_phase.items():
        kind = PHASE_KIND.get(phase, "other")
        by_kind[kind] = by_kind.get(kind, 0.0) + sec
    by_kind[UNATTRIBUTED] = unattributed
    phases: dict[str, dict] = {}
    for _, _, phase in spans:
        phases.setdefault(phase, {"count": 0, "self_s": 0.0})["count"] += 1
    for c0, c1, phase in cover:
        phases[phase]["self_s"] += c1 - c0
    tokens: dict[str, dict] = {}
    for entry, n_tok in stats["dispatches"]:
        t = tokens.setdefault(entry, {"count": 0, "tokens": 0})
        t["count"] += 1
        t["tokens"] += n_tok
    return {
        "window_s": w1 - w0, "devices": n, "stand_in": stand_in,
        "spans": len(spans), "clock": clock,
        "idle_s": sum(by_phase.values()) + unattributed,
        "idle_by_phase": dict(sorted(by_phase.items())),
        "idle_by_kind": dict(sorted(by_kind.items())),
        "unattributed_s": unattributed,
        "phases": dict(sorted(phases.items())),
        "dispatch_tokens": dict(sorted(tokens.items())),
    }


if __name__ == "__main__":
    xplane = find_xplane(sys.argv[1])
    summary = reduce_planes(load_planes(xplane), load_stats(xplane))
    with open(sys.argv[2], "w") as f:
        json.dump(summary, f, indent=1)
