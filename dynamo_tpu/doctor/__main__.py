"""`python -m dynamo_tpu.doctor` — environment + deployment health check.

Reference: `deploy/dynamo_check.py` — one command that tells an operator
what's broken: python deps, device backend, native toolchain, control-
plane reachability, frontend health. Exit code = number of failures.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import urllib.request


def check(name: str, fn) -> tuple[bool, str]:
    try:
        detail = fn() or "ok"
        return True, str(detail)
    except Exception as e:
        return False, repr(e)


def _deps():
    import aiohttp  # noqa: F401
    import jax
    import numpy  # noqa: F401

    return f"jax {jax.__version__}"


def _devices():
    import jax

    devs = jax.devices()
    return f"{len(devs)}x {devs[0].platform}:{devs[0].device_kind}"


def _native():
    from dynamo_tpu.native.radix import native_radix_available

    return ("C++ radix built" if native_radix_available()
            else "fallback to Python tree (no g++?)")


def _grpc():
    from dynamo_tpu.grpc_frontend import grpc_available

    if not grpc_available():
        raise RuntimeError("grpcio/protoc unavailable")
    return "kserve pb2 compiled"


def _store(url: str):
    async def ping():
        from dynamo_tpu.runtime.store import connect_store

        store = await connect_store(url)
        lease = await store.create_lease(2.0)
        await store.revoke_lease(lease)
        close = getattr(store, "close", None)
        if close is not None:
            await close()
        return f"lease roundtrip ok @ {url}"

    return asyncio.run(asyncio.wait_for(ping(), 10))


def _frontend(url: str):
    with urllib.request.urlopen(f"{url}/health", timeout=5) as r:
        body = json.loads(r.read())
    models = body.get("models", [])
    return f"healthy, models={models}"


# Subcommand table: name -> (module under dynamo_tpu.doctor, help line).
# Each module exposes `main(argv) -> int`; dispatch imports lazily so a
# broken optional dep in one analyzer can't take down the others. Bare
# `doctor` (no args) prints this list; `doctor check [...]` (or any
# `--flag` start) runs the legacy environment health check below.
SUBCOMMANDS: dict[str, tuple[str, str]] = {
    "trace": ("trace",
              "analyze a DYN_TRACE span JSONL file"),
    "fleet": ("fleet",
              "render the merged telemetry view from /fleet/status"),
    "profile": ("profile",
                "step flight-recorder ring from /debug/profile"),
    "router": ("router",
               "explain KV-aware placement from /debug/router, or "
               "replay a KvRecorder capture"),
    "kv": ("kv",
           "KV-cache memory plane from /debug/kv: tiers, evictions, "
           "reuse distance, hotness"),
    "memory": ("memory",
               "HBM memory ledger from /debug/memory (or an OOM crash "
               "file): occupancy by class, headroom, workspace "
               "shapes, unattributed residual"),
    "mesh": ("mesh",
             "mesh/collective flight recorder from /debug/mesh: "
             "per-entry collective bytes by axis, reshard warnings, "
             "device skew, link-tier topology"),
    "preflight": ("preflight",
                  "probe the device backend from a child process and "
                  "hold it to the expected platform"),
    "bench": ("bench",
              "perf-ledger trajectory over BENCH_*.json; --gate "
              "compares perf records against thresholds"),
    "request": ("request",
                "join trace spans + router decision + step/KV "
                "recorder windows for one request"),
    "control": ("control",
                "flight-control knob changes from /debug/control or an "
                "events JSONL: timeline, trajectories, evidence"),
    "tenants": ("tenants",
                "per-tenant quotas, fair-share deficits, and goodput "
                "from /debug/tenants"),
    "classes": ("classes",
                "serving-class objectives, deadline admission, and "
                "brownout stage from /debug/classes"),
    "prefixes": ("prefixes",
                 "fleet prefix plane from /debug/prefixes: duplication "
                 "by depth, tier-blind misses, shadow routing "
                 "counterfactual"),
}


def _print_subcommands() -> None:
    print("python -m dynamo_tpu.doctor <subcommand> [...]\n")
    for name in sorted(SUBCOMMANDS):
        print(f"  {name:<10} {SUBCOMMANDS[name][1]}")
    print(f"  {'check':<10} environment health check "
          "(--store/--frontend; also the default with flags)")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        _print_subcommands()
        return 0
    if argv[0] in SUBCOMMANDS:
        import importlib

        module, _ = SUBCOMMANDS[argv[0]]
        mod = importlib.import_module(f"dynamo_tpu.doctor.{module}")
        return mod.main(argv[1:])
    if argv[0] == "check":
        argv = argv[1:]
    elif not argv[0].startswith("-"):
        print(f"unknown subcommand {argv[0]!r}\n")
        _print_subcommands()
        return 2
    p = argparse.ArgumentParser(prog="python -m dynamo_tpu.doctor")
    p.add_argument("--store", default=None,
                   help="control-plane url to ping (tcp://host:port)")
    p.add_argument("--frontend", default=None,
                   help="frontend base url to health-check")
    args = p.parse_args(argv)

    checks: list[tuple[str, object]] = [
        ("python deps", _deps),
        ("jax devices", _devices),
        ("native radix", _native),
        ("grpc/kserve", _grpc),
    ]
    if args.store:
        checks.append(("store", lambda: _store(args.store)))
    if args.frontend:
        checks.append(("frontend", lambda: _frontend(args.frontend)))

    failures = 0
    for name, fn in checks:
        ok, detail = check(name, fn)
        mark = "OK " if ok else "FAIL"
        print(f"[{mark}] {name:<14} {detail}")
        failures += 0 if ok else 1
    print(f"doctor: {failures} failure(s)")
    return failures


if __name__ == "__main__":
    sys.exit(main())
