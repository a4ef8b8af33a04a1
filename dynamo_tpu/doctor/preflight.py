"""Device preflight: prove the accelerator backend can run a trivial op
before anything expensive trusts it.

Lifted from bench.py (which now imports it) so operators can run the
same check standalone: `python -m dynamo_tpu.doctor preflight`. It opens
the backend in a process of its own, so it cannot run beside a live
worker: a chip belongs to one process at a time.

The probe reports the platform it ran on and the check holds it to the
one that was asked for — the first entry of JAX_PLATFORMS, or `tpu`
where that is unset. JAX falls back to the CPU when the TPU fails to
open; a probe that only added two arrays would pass there.

Discipline preserved from the bench version:
  * the probe runs in a CHILD process — a hung backend must not hang
    the caller;
  * retried (default twice): one transient failure must not record a
    broken round;
  * a hung child gets SIGTERM + a grace period before SIGKILL.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional

DEFAULT_TIMEOUT_S = 1200.0
_GRACE_S = 30.0

# the honest probe: backend init + one op + a host round-trip, and the
# device the result came from
_PROBE = ("import jax, json, numpy; "
          "x = jax.numpy.ones(4) + 1; numpy.asarray(x); "
          "d = next(iter(x.devices())); "
          "print('DEV_OK ' + json.dumps({'platform': d.platform, "
          "'kind': d.device_kind, 'count': len(jax.devices())}))")


def expected_platform() -> str:
    """What the probe must land on: the caller's first JAX_PLATFORMS
    entry (tests and rehearsals ask for the CPU by name), else `tpu`."""
    asked = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    return asked.lower() or "tpu"


def classify(error: str) -> dict:
    """Machine-readable diagnosis of a preflight/bench error string:
    {"kind", "detail"} where kind is one of "timeout", "oom", "other".
    bench.py attaches this to outage records and the perf ledger uses
    it to tell an OOM round from a hung backend."""
    s = (error or "").strip()
    low = s.lower()
    if "timed out" in low or "timeout" in low:
        kind = "timeout"
    elif "resource_exhausted" in low or "out of memory" in low \
            or "oom" in low:
        kind = "oom"
    else:
        kind = "other"
    return {"kind": kind, "detail": s[:200]}


def probe_device(attempts: int = 2,
                 timeout_s: float = DEFAULT_TIMEOUT_S
                 ) -> tuple[Optional[dict], Optional[str]]:
    """(device, error): device is {"platform", "kind", "count"} as the
    child's backend reported it, or None when no child got that far;
    error is None only when the device is on the expected platform."""
    want = expected_platform()
    device, last = None, "device preflight never ran"
    for _ in range(max(1, attempts)):
        proc = subprocess.Popen(
            [sys.executable, "-c", _PROBE],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        try:
            out_s, err_s = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            proc.terminate()
            try:
                out_s, err_s = proc.communicate(timeout=_GRACE_S)
            except subprocess.TimeoutExpired:
                proc.kill()
                out_s = err_s = ""
            last = f"device preflight timed out after {timeout_s:.0f}s"
            continue
        ok_line = next((ln for ln in (out_s or "").splitlines()
                        if ln.startswith("DEV_OK ")), None)
        if ok_line is None:
            last = ("device preflight failed: "
                    f"{(err_s or out_s or '')[-200:]}")
            continue
        device = json.loads(ok_line[len("DEV_OK "):])
        if device["platform"] == want:
            return device, None
        # a fallback is deterministic: retrying cannot change it
        return device, (
            f"device preflight ran on {device['platform']!r}, not on "
            f"{want!r}: the backend fell back")
    return device, last


def device_preflight(attempts: int = 2,
                     timeout_s: float = DEFAULT_TIMEOUT_S
                     ) -> Optional[str]:
    """None when a child process can init the expected backend and
    round-trip a trivial op; otherwise a diagnosis string."""
    return probe_device(attempts, timeout_s)[1]


# distinct exit codes per classify() kind, so wrapper scripts (bench
# orchestration, supervisor hooks) can branch without parsing output:
# 0 = healthy, then one code per diagnosis; 1 stays reserved for
# argparse/usage errors.
EXIT_OK = 0
EXIT_CODES = {"timeout": 3, "oom": 4, "other": 5}


def main(argv: list[str]) -> int:
    """`python -m dynamo_tpu.doctor preflight [--attempts N]
    [--timeout S] [--json]` — exit 0 healthy; on failure the exit code
    encodes the classify() kind (timeout=3, oom=4, other=5)."""
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m dynamo_tpu.doctor preflight",
        description="probe the accelerator backend from a child process")
    p.add_argument("--attempts", type=int, default=2)
    p.add_argument("--timeout", type=float, default=DEFAULT_TIMEOUT_S,
                   help="seconds before a probe child is declared hung")
    p.add_argument("--json", action="store_true",
                   help="machine-readable verdict on stdout (one object: "
                        "ok, kind, detail, device, elapsed_s, exit_code)")
    args = p.parse_args(argv)
    t0 = time.perf_counter()
    device, verdict = probe_device(args.attempts, args.timeout)
    dt = time.perf_counter() - t0
    if verdict is None:
        if args.json:
            print(json.dumps({"ok": True, "kind": "ok", "detail": "",
                              "device": device,
                              "elapsed_s": round(dt, 3),
                              "exit_code": EXIT_OK}))
        else:
            print(f"device preflight OK on {device} ({dt:.1f}s)")
        return EXIT_OK
    diag = classify(verdict)
    rc = EXIT_CODES.get(diag["kind"], EXIT_CODES["other"])
    if args.json:
        print(json.dumps({"ok": False, "kind": diag["kind"],
                          "detail": diag["detail"], "device": device,
                          "elapsed_s": round(dt, 3), "exit_code": rc}))
    else:
        print(f"device preflight FAILED ({dt:.1f}s) "
              f"[{diag['kind']}]: {verdict}")
    return rc
