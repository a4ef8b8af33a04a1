"""Share of the traced span in which the device sat idle under host spans
of the given kinds (lib/host_spans.py): `sched`, `device`, `idle`, or
`unattributed` for idle time under no span. The shares of all four add up
to `device_idle` of the same run.

The reader's `ctx` carries no path: the trace is the one this process's
`--workload` wrote under `.bench_chip/<workload>/trace`. It is reduced in a
child pinned to the CPU, as run.py reduces it for `trace_summary.json`
(reading it imports JAX), and `host_spans.json` beside that file is reused
while it is newer than the trace, so the four metrics cost one read. None
(the metric is left out of the line) where the trace holds no `engine.*`
event (a worker from before the spans), and where the reduction could not
measure the offset between the host's clock and the device's (`clock.ok`
false: too few launches paired tightly with their programs, or no device
plane): a split made with an unknown offset is not a metric. The `clock`
block is printed beside the shares on the first read."""
import json
import os
import subprocess
import sys

from lib.deploy import ROOT, BenchFailure
from lib.trace import find_xplane

LIB = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "lib")


def _workload() -> str:
    argv = sys.argv
    for i, a in enumerate(argv):
        if a == "--workload" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--workload="):
            return a.split("=", 1)[1]
    raise BenchFailure("idle_by_kind: no --workload on the command line")


def summary() -> dict:
    log_dir = os.path.join(ROOT, ".bench_chip", _workload())
    trace_dir, out = (os.path.join(log_dir, "trace"),
                      os.path.join(log_dir, "host_spans.json"))
    xplane = find_xplane(trace_dir)
    if not (os.path.exists(out)
            and os.path.getmtime(out) >= os.path.getmtime(xplane)):
        proc = subprocess.run(
            [sys.executable, os.path.join(LIB, "host_spans.py"), trace_dir,
             out], env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=300)
        if proc.returncode != 0:
            raise BenchFailure(
                f"host span reduction failed: {proc.stderr[-800:]}")
        with open(out) as f:
            made = json.load(f)
        print(f"[bench] host spans: {made['spans']} spans, clock "
              f"{json.dumps(made['clock'])}, idle by kind (s) "
              f"{json.dumps(made['idle_by_kind'])}, dispatch tokens "
              f"{json.dumps(made['dispatch_tokens'])}", flush=True)
        return made
    with open(out) as f:
        return json.load(f)


def read(ctx, kinds):
    spans = summary()
    if not spans["spans"] or not spans["clock"]["ok"]:
        return None
    idle = sum(spans["idle_by_kind"].get(k, 0.0) for k in kinds)
    return 100.0 * idle / ctx["trace"]["window_s"]
