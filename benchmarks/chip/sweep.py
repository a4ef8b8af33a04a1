#!/usr/bin/env python3
"""Find an open-loop cell's knee: one deployment, a few fixed rates.

    python3 benchmarks/chip/sweep.py --workload <name> --seed <n> \
        --rates 1.0,1.4,1.8 --seconds 30

Brings the cell's deployment up once, warms it up as a run does, then
offers the cell's traffic at each rate in turn for --seconds and lets it
drain. A rate is sustained when the backlog does not grow: the requests
still in flight when the offering stops are no more than a few, and the
last third's TTFT is not far above the first third's. The knee is the
highest sustained rate; the cell then runs at 0.8 of it (traffic file).
Not part of a benchmark run and it prints no result line: a builder's tool.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench_run                                          # noqa: E402
from lib import ckpt                                             # noqa: E402
from lib.client import Client                                    # noqa: E402
from lib.deploy import ROOT, Deployment, MODEL_NAME              # noqa: E402
from lib.schedule import Prompts, open_phase                     # noqa: E402


async def sweep(dep, config, traffic, seed, rates, seconds):
    prompts = Prompts(traffic, config["vocab_size"], seed)
    async with Client(dep.url, MODEL_NAME, traffic["sampling"]) as client:
        await bench_run.warm_up(client, dep, traffic, prompts)
        for rate in rates:
            mix = dict(traffic, arrivals=dict(traffic["arrivals"], rps=rate))
            reqs = open_phase(mix, seconds, seed, f"sweep{rate}")
            texts = [prompts.text(r) for r in reqs]
            t0 = time.perf_counter() + 0.05
            n_before = len(client.results)
            tasks = await client.open_loop("window", reqs, texts, t0)
            t_stop = time.perf_counter()
            in_flight = sum(1 for t in tasks if not t.done())
            await asyncio.wait(tasks, timeout=bench_run.DRAIN_S * 2)
            drain_s = time.perf_counter() - t_stop
            res = client.results[n_before:]
            ok = [r for r in res if r.ok]
            ttft = [r.frames[0][0] - r.due for r in ok]
            third = max(1, len(ttft) // 3)
            tpot = [(r.frames[-1][0] - r.frames[0][0]) / (r.tokens - 1)
                    for r in ok if r.tokens > 1]
            print(f"[sweep] rate {rate:.2f} req/s: sent {len(res)} ok "
                  f"{len(ok)} in flight at stop {in_flight} drain "
                  f"{drain_s:.1f}s | ttft ms p50 "
                  f"{1e3 * statistics.median(ttft):.0f} p90 "
                  f"{1e3 * bench_run.endtoend.percentile(ttft, 90):.0f} "
                  f"first third {1e3 * statistics.median(ttft[:third]):.0f} "
                  f"last third {1e3 * statistics.median(ttft[-third:]):.0f}"
                  f" | tpot ms p50 {1e3 * statistics.median(tpot):.1f} | "
                  f"completed {len(ok) / (seconds + drain_s):.2f} req/s",
                  flush=True)
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--bench-file",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    _, _, config, traffic = bench_run.load_cell(args.bench_file,
                                                args.workload)
    log_dir = os.path.join(ROOT, ".bench_chip", args.workload + ".sweep")
    os.makedirs(log_dir, exist_ok=True)
    ckpt_dir = bench_run.checkpoint_dir()
    ckpt.write_checkpoint(ckpt_dir, config, args.seed)
    with Deployment(config, ckpt_dir, log_dir, {}) as dep:
        print(f"[sweep] worker up in {dep.load_s:.1f}s: {dep.device}",
              flush=True)
        asyncio.run(sweep(dep, config, traffic, args.seed,
                          [float(r) for r in args.rates.split(",")],
                          args.seconds))
    return 0


if __name__ == "__main__":
    sys.exit(main())
