#!/usr/bin/env python3
"""How steady is each way of reading the TTFT tail, over runs already made?

    python3 benchmarks/chip/tails.py <client_samples.json> [...]

Each file is one run's kept samples (`.bench_chip/<workload>/
client_samples.json`, copied aside by whoever made the runs). For each
workload it prints, a run a row, the statistics `lib/endtoend.py` can take
from the window's TTFT samples, then each statistic's spread over the runs
both ways the records use it: IQR / median (`statistics.quantiles(n=4)`)
and (max - min) / median with the run farthest from the median left out.
With 8 runs or more it also prints what a check that makes two sets of runs
of the same tree may read: over every split of the runs into two halves, the
mean of the halves' IQR / median, each half without its run farthest from its
median (a bound under twice that is too tight), and how far the second
half's median lies from the first's.
Not part of a benchmark run and it prints no result line: a builder's tool.
"""

from __future__ import annotations

import itertools
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from lib import endtoend                                         # noqa: E402

NAMED = ["ttft_p50_ms", "ttft_p80_ms", "ttft_p90_ms", "ttft_slow20_ms",
         "ttft_slow10_ms", "tpot_p50_ms", "out_tok_s"]
CANDIDATES = NAMED + ["ttft_mean_ms"]


def iqr_share(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_range_share(values: list[float]) -> float:
    kept = nearest(values, set(range(len(values))))
    return (max(kept) - min(kept)) / statistics.median(values)


def nearest(col: list[float], half: set[int]) -> list[float]:
    """A half's values without the one farthest from their median."""
    values = [col[i] for i in half]
    med = statistics.median(values)
    return sorted(values, key=lambda v: abs(v - med))[:-1]


def main(paths: list[str]) -> int:
    runs: dict[str, list] = {}
    for path in paths:
        with open(path) as f:
            kept = json.load(f)
        cli = endtoend.reduce(kept["requests"], kept["window_s"],
                              kept["loop"])
        values = endtoend.compute(
            [{"name": n, "unit": ""} for n in NAMED], cli, kept["setup_s"])
        runs.setdefault(kept["workload"], []).append(
            (kept["seed"], len(cli["ttft_s"]), path,
             [values[n]["value"] for n in NAMED]
             + [1e3 * statistics.fmean(cli["ttft_s"])]))
    for workload, rows in runs.items():
        print(f"## {workload}: {len(rows)} runs")
        print("| seed | n | " + " | ".join(CANDIDATES) + " | file |")
        for seed, n, path, vals in rows:
            print(f"| {seed} | {n} | " + " | ".join(f"{v:.3f}" for v in vals)
                  + f" | {os.path.basename(path)} |")
        if len(rows) < 3:
            continue
        for label, spread in (("median", statistics.median),
                              ("IQR / median", iqr_share),
                              ("trimmed range / median",
                               trimmed_range_share)):
            cols = [spread([r[3][i] for r in rows])
                    for i in range(len(CANDIDATES))]
            print(f"| {label} | | " + " | ".join(
                f"{c:.3f}" if label == "median" else f"{c:.5f}"
                for c in cols) + " | |")
        if len(rows) < 8:
            continue
        halves = [(set(a), set(range(len(rows))) - set(a))
                  for a in itertools.combinations(range(len(rows)),
                                                  len(rows) // 2) if 0 in a]
        cols = [[r[3][i] for r in rows] for i in range(len(CANDIDATES))]
        reads = {
            "two halves' IQR / median, farthest run out": lambda col, a, b: (
                iqr_share(nearest(col, a)) + iqr_share(nearest(col, b))) / 2,
            "second half's median off the first's": lambda col, a, b: abs(
                statistics.median(col[i] for i in b)
                / statistics.median(col[i] for i in a) - 1)}
        for what, read in reads.items():
            got = [[read(col, a, b) for a, b in halves] for col in cols]
            for label, pick in (("median", statistics.median),
                                ("widest", max)):
                print(f"| {what}, {label} of {len(halves)} splits | | "
                      + " | ".join(f"{pick(g):.5f}" for g in got) + " | |")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
