"""Reduce a JAX profiler trace (`.xplane.pb`) to what the metrics read.

    python lib/trace.py <trace dir> <out.json>

Runs in a child pinned to the CPU after the worker has exited (reading the
file imports JAX). On a TPU each chip is a plane `/device:TPU:<n>` whose
line `XLA Modules` has one event per dispatched program and whose line
`XLA Ops` has the operations inside them (a `while` spans its body's ops).

- busy: the union of the ops' intervals, per device, averaged over devices;
- window: first to last event over every plane of the trace, host threads
  included, so a device that sat idle at either end still counts the time;
- modules: count and device seconds of each program, by its name without
  the run id;
- device_ops: self time of each operation (its own span minus the
  operations nested inside it), summed by kind (the HLO name without its
  index), largest first;
- idle_gaps: the time in which no operation ran, summed by the program that
  ended each gap (`before <program>`) or that the device paused inside. What the host was doing in a gap needs spans inside the
  worker on the trace's clock: the `tracing` issue (PERF.md).

Where no TPU plane exists (a CPU rehearsal) the host planes' XLA lines
stand in, so that the path runs; such numbers are never reported.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

_RUN_ID = re.compile(r"\(\d+\)$")
_OP_INDEX = re.compile(r"[.\d]+$")


def op_kind(name: str) -> str:
    """`%paged_attention.424 = (f32[...]) custom-call(...)` ->
    `paged_attention`: the trace names an operation by its whole HLO line."""
    return _OP_INDEX.sub("", name.split(" = ")[0].lstrip("%")) or name


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union_seconds(spans: list[tuple[float, float]]) -> tuple[float, list]:
    """Length of the union of (start, end) spans and the gaps between its
    pieces, as (gap start, gap end)."""
    busy, gaps, cur_s, cur_e = 0.0, [], None, None
    for s, e in sorted(spans):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
                gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def self_times(events: list[tuple[float, float, str]]) -> dict[str, float]:
    """Self seconds by name for properly nested (start, end, name) events."""
    out: dict[str, float] = {}
    stack: list[list] = []          # [end, name, self seconds]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


def reduce_planes(planes: list[dict]) -> dict:
    """`planes`: [{"name", "lines": [{"name", "events": [(start_s, end_s,
    name)]}]}], already in seconds."""
    every = [ev for p in planes for ln in p["lines"] for ev in ln["events"]]
    if not every:
        raise ValueError("the trace holds no event")
    window = max(e for _, e, _ in every) - min(s for s, _, _ in every)
    devices = [p for p in planes if p["name"].startswith("/device:TPU")]
    stand_in = not devices
    if stand_in:
        devices = [p for p in planes if p["name"].startswith("/host:")]
    busy, modules, ops_self, idle = [], {}, {}, {}
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if stand_in:
            ops = [ev for evs in lines.values() for ev in evs]
            mods = [ev for ev in ops if "jit_" in ev[2].lower()
                    or "pjit" in ev[2].lower()]
        else:
            ops = lines.get("XLA Ops", [])
            mods = lines.get("XLA Modules", [])
        if not ops:
            continue
        b, gaps = union_seconds([(s, e) for s, e, _ in ops])
        busy.append(b)
        for s, e, name in mods:
            m = modules.setdefault(_RUN_ID.sub("", name),
                                   {"count": 0, "seconds": 0.0})
            m["count"] += 1
            m["seconds"] += e - s
        for name, sec in self_times(ops).items():
            kind = op_kind(name)
            ops_self[kind] = ops_self.get(kind, 0.0) + sec
        spans = sorted((s, e, _RUN_ID.sub("", name)) for s, e, name in mods)
        starts = [s for s, _, _ in spans]
        for g0, g1 in gaps:
            # the program whose operation ended the gap: the last to start
            # by then. It began inside the gap, or the device paused in it.
            i = bisect.bisect_right(starts, g1 + 1e-9) - 1
            if i < 0:
                continue
            s, _, name = spans[i]
            label = ("before " if s >= g0 else "inside ") + name
            idle[label] = idle.get(label, 0.0) + g1 - g0
    if not busy:
        raise ValueError("no device operation in the trace")
    n = len(busy)
    top = sorted(ops_self.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window, "busy_s": sum(busy) / n, "devices": n,
        "stand_in": stand_in,
        "modules": {k: {"count": v["count"] / n, "seconds": v["seconds"] / n}
                    for k, v in modules.items()},
        "device_ops": [[k, v / n] for k, v in top[:10]],
        "idle_gaps": [[k, v / n] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:10]],
    }


def program_time(summary: dict, pattern: str) -> tuple[float, float]:
    """(dispatches, device seconds) of the summary's programs whose name
    matches `pattern`: what the readers of a traced run divide by."""
    hit = [m for name, m in summary["modules"].items()
           if re.search(pattern, name)]
    return sum(m["count"] for m in hit), sum(m["seconds"] for m in hit)


def load_planes(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [(ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def describe(planes: list[dict]) -> list[str]:
    """Planes, lines and event counts: the first thing to read by hand."""
    out = []
    for p in planes:
        for ln in p["lines"]:
            names = sorted({ev[2][:60] for ev in ln["events"]})[:6]
            out.append(f"{p['name']} | {ln['name']} | "
                       f"{len(ln['events'])} events | {names}")
    return out


if __name__ == "__main__":
    planes_ = load_planes(find_xplane(sys.argv[1]))
    summary = reduce_planes(planes_)
    summary["layout"] = describe(planes_)[:60]
    with open(sys.argv[2], "w") as f:
        json.dump(summary, f, indent=1)
