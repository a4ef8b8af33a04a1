"""aiperf-style load sweep against any OpenAI-compatible endpoint.

Reference: `benchmarks/` (aiperf wrapper + sweep configs,
`benchmarks/README.md:17-40`): drive a served deployment across a
concurrency ladder with synthetic prompts of a given ISL/OSL, and report
per-level TTFT/ITL percentiles + aggregate throughput — the numbers the
SLA planner's interpolators and the Pareto plots consume.

Load SHAPES (reference `benchmarks/sin_load_generator/`,
`benchmarks/burstgpt_loadgen/`, `benchmarks/prefix_data_generator/`):
- `--arrival closed` (default): concurrency-ladder closed loop.
- `--arrival poisson --qps R`: open loop, exponential inter-arrivals.
- `--arrival sin --qps R --sin-period S --sin-amplitude A`: open loop,
  rate(t) = R·(1 + A·sin(2πt/S)) — the planner's predictors see a
  seasonal signal.
- `--arrival burst --qps R --burst-size N`: open loop, N requests land
  together every N/R seconds (BurstGPT-style clumping).
- `--prefix-ratio F --prefix-pool K`: the first F·ISL words of each
  prompt come from one of K shared system-prompt-style prefixes —
  exercises the KV router's overlap scoring and the radix prefix cache
  (the default prompts are deliberately prefix-disjoint).

Usage:
    python -m benchmarks.sweep --url http://HOST:8080 --model NAME \
        --isl 96 --osl 64 --concurrency 1,4,16 --requests 32
Prints one JSON line per level and a final summary line.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import random
import sys
import time


def make_prompt(rng: random.Random, isl: int,
                prefix_ratio: float = 0.0, prefix_pool: int = 4,
                seed: int = 0) -> str:
    """Word-ish prompt; with prefix_ratio > 0 the head words come from
    one of `prefix_pool` deterministic shared prefixes (chosen by this
    prompt's rng) so requests overlap the way system-prompt traffic
    does. Default prompts stay prefix-disjoint (worst case)."""
    n_prefix = int(isl * prefix_ratio)
    words = []
    if n_prefix > 0:
        pool_id = rng.randrange(prefix_pool)
        prng = random.Random(1_000_003 * (seed + 1) + pool_id)
        words += [f"p{prng.randrange(1 << 20):x}"
                  for _ in range(n_prefix)]
    words += [f"w{rng.randrange(1 << 20):x}"
              for _ in range(isl - n_prefix)]
    return " ".join(words)


def arrival_times(kind: str, n: int, qps: float, sin_period: float,
                  sin_amplitude: float, burst_size: int,
                  rng: random.Random) -> list[float]:
    """Request launch offsets (seconds from window start) for the open-
    loop shapes. Deterministic given the rng."""
    if kind == "poisson":
        t, out = 0.0, []
        for _ in range(n):
            t += rng.expovariate(qps)
            out.append(t)
        return out
    if kind == "sin":
        # thinning-free piecewise draw: local exponential at rate(t)
        t, out = 0.0, []
        for _ in range(n):
            rate = qps * (1.0 + sin_amplitude
                          * math.sin(2 * math.pi * t / sin_period))
            rate = max(rate, qps * 0.05)
            t += rng.expovariate(rate)
            out.append(t)
        return out
    if kind == "burst":
        gap = burst_size / qps
        return [(i // burst_size) * gap for i in range(n)]
    raise ValueError(f"unknown arrival kind {kind!r}")


async def one_request(session, url: str, model: str, prompt: str,
                      osl: int) -> dict:
    """Streamed completion; returns timing + token counts."""
    t0 = time.perf_counter()
    first = None
    deltas: list[float] = []
    last = None
    n_chunks = 0
    body = {"model": model, "prompt": prompt, "stream": True,
            "max_tokens": osl, "ignore_eos": True}
    finish = None
    async with session.post(f"{url}/v1/completions", json=body) as resp:
        if resp.status != 200:
            return {"error": resp.status}
        async for raw in resp.content:
            line = raw.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            now = time.perf_counter()
            chunk = json.loads(line[6:])
            for c in chunk.get("choices", ()):
                finish = c.get("finish_reason") or finish
            if first is None:
                # first data event = first token(s), aiperf semantics —
                # byte-level tokenizers can hold partial UTF-8 so the
                # first VISIBLE text may lag the first token
                first = now
            if any(c.get("text") for c in chunk.get("choices", ())):
                if last is not None:
                    deltas.append(now - last)
                last = now
                n_chunks += 1
    if finish not in ("length", "stop", "eos"):
        # a stream that ended on an error frame (or never finished) is
        # a FAILED request, even though HTTP said 200 — counting it ok
        # would inflate output_tok_s exactly when the backend drops
        return {"error": f"finish_reason={finish}"}
    return {"ttft": (first - t0) if first else None,
            "itls": deltas, "duration": time.perf_counter() - t0,
            "chunks": n_chunks}


def pct(xs: list[float], p: float):
    """Percentile, or None when the sample is empty (e.g. the whole
    output arrived in one SSE frame — the engine emits one frame per
    fused burst, so short OSLs can yield zero inter-token deltas).
    None, not NaN: NaN would make the output line invalid JSON."""
    if not xs:
        return None
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(p * len(xs)))]


def ms(x, nd=2):
    return None if x is None else round(x * 1e3, nd)


async def run_level(url: str, model: str, concurrency: int,
                    n_requests: int, isl: int, osl: int,
                    seed: int = 0, arrival: str = "closed",
                    qps: float = 4.0, sin_period: float = 30.0,
                    sin_amplitude: float = 0.8, burst_size: int = 8,
                    prefix_ratio: float = 0.0,
                    prefix_pool: int = 4) -> dict:
    import aiohttp

    rng = random.Random(seed)
    prompts = [make_prompt(rng, isl, prefix_ratio, prefix_pool, seed)
               for _ in range(n_requests)]
    results: list[dict] = []
    offsets: list[float] = []

    async with aiohttp.ClientSession() as session:
        t0 = time.perf_counter()
        if arrival == "closed":
            sem = asyncio.Semaphore(concurrency)

            async def bounded(p):
                async with sem:
                    results.append(await one_request(
                        session, url, model, p, osl))

            await asyncio.gather(*(bounded(p) for p in prompts))
        else:
            # open loop: requests launch at their arrival offsets
            # regardless of completions — the shape the router/planner
            # actually face
            offsets = arrival_times(arrival, n_requests, qps,
                                    sin_period, sin_amplitude,
                                    burst_size, rng)

            async def timed(p, at):
                delay = at - (time.perf_counter() - t0)
                if delay > 0:
                    await asyncio.sleep(delay)
                results.append(await one_request(
                    session, url, model, p, osl))

            await asyncio.gather(
                *(timed(p, at) for p, at in zip(prompts, offsets)))
        wall = time.perf_counter() - t0

    ok = [r for r in results if "error" not in r and r["ttft"]]
    errors = len(results) - len(ok)
    error_statuses = sorted({str(r["error"]) for r in results
                             if "error" in r})
    ttfts = [r["ttft"] for r in ok]
    itls = [d for r in ok for d in r["itls"]]
    total_tokens = len(ok) * osl
    row = {
        "arrival": arrival,
        "concurrency": concurrency if arrival == "closed" else None,
        "requests": n_requests,
        "errors": errors, "isl": isl, "osl": osl,
        "output_tok_s": round(total_tokens / wall, 1),
        "req_s": round(len(ok) / wall, 2),
        "ttft_p50_ms": ms(pct(ttfts, 0.5), 1),
        "ttft_p95_ms": ms(pct(ttfts, 0.95), 1),
        "itl_p50_ms": ms(pct(itls, 0.5)),
        "itl_p95_ms": ms(pct(itls, 0.95)),
        "duration_s": round(wall, 2),
    }
    if error_statuses:
        row["error_statuses"] = error_statuses
    if arrival != "closed":
        row["target_qps"] = qps
        # offered rate comes from the ARRIVAL span, not the wall (which
        # stretches to the last completion — at saturation, exactly
        # where open-loop load matters, completion rate ≠ offered rate)
        span = offsets[-1] if offsets and offsets[-1] > 0 else None
        row["offered_qps"] = (round(n_requests / span, 2)
                              if span else None)
        row["completed_req_s"] = round(len(ok) / max(wall, 1e-9), 2)
    if prefix_ratio > 0:
        row["prefix_ratio"] = prefix_ratio
        row["prefix_pool"] = prefix_pool
    return row


async def sweep(url: str, model: str, levels: list[int], n_requests: int,
                isl: int, osl: int, **kw) -> list[dict]:
    out = []
    for i, conc in enumerate(levels):
        row = await run_level(url, model, conc, n_requests, isl, osl,
                              seed=i, **kw)
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m benchmarks.sweep")
    p.add_argument("--url", required=True)
    p.add_argument("--model", required=True)
    p.add_argument("--isl", type=int, default=96)
    p.add_argument("--osl", type=int, default=64)
    p.add_argument("--concurrency", default="1,4,16",
                   help="comma-separated ladder (closed loop)")
    p.add_argument("--requests", type=int, default=32,
                   help="requests per level")
    p.add_argument("--arrival", default="closed",
                   choices=("closed", "poisson", "sin", "burst"))
    p.add_argument("--qps", type=float, default=4.0,
                   help="mean request rate for open-loop arrivals")
    p.add_argument("--sin-period", type=float, default=30.0)
    p.add_argument("--sin-amplitude", type=float, default=0.8)
    p.add_argument("--burst-size", type=int, default=8)
    p.add_argument("--prefix-ratio", type=float, default=0.0,
                   help="fraction of ISL drawn from a shared prefix")
    p.add_argument("--prefix-pool", type=int, default=4,
                   help="number of distinct shared prefixes")
    p.add_argument("--output", default=None, help="write JSONL here too")
    args = p.parse_args(argv)
    levels = ([int(x) for x in args.concurrency.split(",") if x]
              if args.arrival == "closed" else [0])
    kw = dict(arrival=args.arrival, qps=args.qps,
              sin_period=args.sin_period,
              sin_amplitude=args.sin_amplitude,
              burst_size=args.burst_size,
              prefix_ratio=args.prefix_ratio,
              prefix_pool=args.prefix_pool)
    rows = asyncio.run(sweep(args.url, args.model, levels, args.requests,
                             args.isl, args.osl, **kw))
    best = max(rows, key=lambda r: r["output_tok_s"])
    print(json.dumps({"summary": "best_throughput", **best}), flush=True)
    if args.output:
        with open(args.output, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    return 0 if all(r["errors"] == 0 for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
