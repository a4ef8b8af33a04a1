"""Start the worker exactly as `python -m dynamo_tpu.worker` does, plus a
side thread for what only the process that holds the chip can do.

The worker's system port serves /live /health /metrics /config only and
has no route that traces the device, so the benchmark asks through files
in $BENCH_CONTROL_DIR: a file named `trace` (JSON: seconds, dir) brackets
that many seconds with `jax.profiler.start_trace/stop_trace` and scrapes
the worker's own /metrics just inside the bracket; a file named `memory`
reads the devices' peak memory. Each answer is `<request>.json`. A trace
route on the worker itself belongs to the `tracing` issue (PERF.md).
"""

import json
import os
import threading
import time
import urllib.request


def _scrape(port: str) -> str:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
        return r.read().decode()


def _trace(req: dict, port: str) -> dict:
    import jax

    # device planes and the runtime's own host events; no Python frames,
    # which would make most of the file and slow the scheduler's thread
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(req["dir"], profiler_options=opts)
    try:
        before, t0 = _scrape(port), time.time()
        time.sleep(req["seconds"])
        after, t1 = _scrape(port), time.time()
    finally:
        jax.profiler.stop_trace()
    return {"scrape_start": before, "scrape_stop": after,
            "t_start": t0, "t_stop": t1}


def _memory() -> dict:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return {"peak_bytes": max(peaks, default=0)}


def _serve(control: str, port: str) -> None:
    while True:
        for name in ("trace", "memory"):
            path = os.path.join(control, name)
            if not os.path.exists(path):
                continue
            try:
                with open(path) as f:
                    text = f.read()
                os.remove(path)
                answer = (_trace(json.loads(text), port)
                          if name == "trace" else _memory())
            except Exception as e:    # the parent reads the failure
                answer = {"error": f"{type(e).__name__}: {e}"}
            tmp = os.path.join(control, name + ".tmp")
            with open(tmp, "w") as f:
                json.dump(answer, f)
            os.replace(tmp, os.path.join(control, name + ".json"))
        time.sleep(0.05)


if __name__ == "__main__":
    from dynamo_tpu.worker.main import main

    threading.Thread(
        target=_serve, daemon=True,
        args=(os.environ["BENCH_CONTROL_DIR"],
              os.environ["BENCH_SYS_PORT"])).start()
    main()
