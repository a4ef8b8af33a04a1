"""Check the client-side reduction (`lib/endtoend.py`), without a chip or JAX.

The tail mean on hand-made samples, `percentile` against numpy's, names
`compute` knows and does not know, the window's bookkeeping on hand-made
records of both loops, the records' round trip through JSON, and that
`rehearsal/cells.json` mirrors `BENCHMARK.json`'s end-to-end entries.
`CASES` is a list of (name, function) so that a test file can take each as
a parametrised case.

    python3 benchmarks/chip/rehearsal/check_endtoend.py
"""

import json
import os
import sys
from types import SimpleNamespace

import numpy

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, os.path.dirname(HERE))

from lib import endtoend  # noqa: E402


def load(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def close(a: float, b: float) -> bool:
    return abs(a - b) <= 1e-9 * max(1.0, abs(a), abs(b))


# -- the tail mean -----------------------------------------------------------


def slow_72_takes_15() -> None:
    xs = [float(i) for i in range(72)]           # slowest 15: 57..71
    assert endtoend.slowest_mean(xs[::-1], 20) == sum(range(57, 72)) / 15
    assert endtoend.slowest_mean(xs, 10) == sum(range(64, 72)) / 8   # ceil 7.2


def slow_5_takes_1() -> None:
    assert endtoend.slowest_mean([3.0, 9.0, 1.0, 4.0, 2.0], 20) == 9.0
    assert endtoend.slowest_mean([3.0, 9.0, 1.0, 4.0, 2.0, 8.0], 20) == 8.5


def slow_ties() -> None:
    assert endtoend.slowest_mean([2.0] * 9 + [1.0], 20) == 2.0
    assert endtoend.slowest_mean([1.0] * 8 + [5.0, 5.0, 5.0], 20) == 5.0


def slow_one_sample() -> None:
    assert endtoend.slowest_mean([0.25], 20) == 0.25
    assert endtoend.slowest_mean([0.25], 1) == 0.25


def slow_never_under_percentile() -> None:
    rng = numpy.random.default_rng(7)
    for n in (2, 5, 11, 72, 134):
        xs = rng.lognormal(size=n).tolist()
        for share in (10, 20, 50):
            assert (endtoend.slowest_mean(xs, share)
                    >= endtoend.percentile(xs, 100 - share)), (n, share)


def percentile_is_numpys() -> None:
    rng = numpy.random.default_rng(11)
    for n in (1, 2, 3, 72, 134, 1000):
        xs = rng.exponential(size=n).tolist()
        for p in (0, 1, 50, 80, 90, 99):
            assert close(endtoend.percentile(xs, p),
                         float(numpy.percentile(xs, p))), (n, p)


# -- names ---------------------------------------------------------------------

CLI = {"ttft_s": [0.1 * i for i in range(1, 11)], "tpot_s": [0.02, 0.04],
       "window_tokens": 500, "window_s": 50.0}


def value(name: str, cli: dict = CLI) -> float:
    return endtoend.compute([{"name": name, "unit": "x"}], cli, 12.5)[
        name]["value"]


def names_computed() -> None:
    assert close(value("ttft_slow20_ms"), 950.0)          # (0.9 + 1.0) / 2
    assert close(value("ttft_slow5_ms"), 1000.0)
    assert close(value("ttft_p50_ms"), 550.0)
    assert close(value("tpot_slow50_ms"), 40.0)
    assert value("out_tok_s") == 10.0 and value("setup_s") == 12.5
    assert endtoend.compute([{"name": "ttft_slow20_ms", "unit": "ms"}],
                            {**CLI, "ttft_s": []}, 1.0) == {}


def unknown_name_raises() -> None:
    for name in ("ttft_mean_ms", "ttft_slow100_ms", "ttft_slow_ms",
                 "xttft_slow20_ms", "ttft_p100_ms", "goodput"):
        try:
            value(name)
        except KeyError:
            continue
        raise AssertionError(f"{name} was computed")


def benchmark_names_computable() -> None:
    for m in load("BENCHMARK.json")["end_to_end"]:
        assert value(m["name"]) > 0, m


def rehearsal_cells_mirror_benchmark() -> None:
    """Same end-to-end entries, cell names apart: a metric's `workloads`
    name the same traffic mixes on both sides."""
    bench, cells = load("BENCHMARK.json"), load(
        "benchmarks", "chip", "rehearsal", "cells.json")

    def entries(b: dict) -> list:
        mix = {w["name"]: w["traffic"] for w in b["workloads"]}
        return [{**m, "workloads": sorted({mix[w] for w in m["workloads"]})}
                if "workloads" in m else m for m in b["end_to_end"]]

    assert entries(bench) == entries(cells), (entries(bench), entries(cells))
    assert cells["run_seconds"] == bench["run_seconds"]


# -- records -----------------------------------------------------------------


def result(phase, due, sent, frames, done, max_tokens, finish="length",
           error=None) -> SimpleNamespace:
    tokens = sum(n for _, n in frames)
    return SimpleNamespace(
        phase=phase, due=due, sent=sent, frames=frames, done=done,
        prompt_tokens=256, max_tokens=max_tokens, tokens=tokens, error=error,
        finish=finish,
        ok=error is None and finish == "length" and tokens == max_tokens)


W0 = 1000.0
RESULTS = [
    result("warmup", 900.0, 900.0, [(901.0, 4)], 901.0, 4),
    result("ramp", 998.0, 998.001, [(999.5, 1), (1000.5, 3)], 1000.5, 4),
    result("window", 1001.0, 1001.002, [(1001.3, 1), (1001.9, 4)], 1001.9, 5),
    result("window", 1002.0, 1002.004, [(1002.8, 1), (1003.4, 2)], 1003.4, 3),
    # refused: counts as failed and in no latency
    result("window", 1003.0, 1003.0, [], 1003.1, 4, finish=None,
           error="http 503"),
    # due inside the window, ends after it
    result("window", 1009.0, 1009.0, [(1009.6, 1), (1010.5, 1)], 1010.5, 2),
    # cut by the end of a closed-loop window
    result("window", 1009.5, 1009.5, [(1009.9, 1)], 1010.0, 8, finish=None,
           error="cancelled"),
]


def records_leave_out_other_phases() -> None:
    recs = endtoend.samples(RESULTS, W0)
    assert [r["phase"] for r in recs] == ["ramp"] + ["window"] * 5
    assert close(recs[0]["due"], -2.0) and close(recs[1]["first"], 1.3)
    assert recs[3]["first"] is None and recs[3]["error"] == "http 503"
    assert json.loads(json.dumps(recs)) == recs


def open_loop_window() -> None:
    cli = endtoend.reduce(endtoend.samples(RESULTS, W0), 10.0, "open")
    assert (cli["attempted"], cli["failed"], cli["completed"]) == (5, 2, 3)
    assert cli["failures"] == ["cancelled", "http 503"]
    for got, want in zip(cli["ttft_s"], (0.3, 0.8, 0.6, 0.4)):
        assert close(got, want), cli["ttft_s"]
    for got, want in zip(cli["tpot_s"], (0.15, 0.3, 0.9)):
        assert close(got, want), cli["tpot_s"]
    # the ramp's second frame and the straddler's first lie inside
    assert cli["window_tokens"] == 3 + 5 + 3 + 1 + 1
    assert close(cli["late_ms_max"], 4.0)


def closed_loop_window() -> None:
    cli = endtoend.reduce(endtoend.samples(RESULTS, W0), 10.0, "closed")
    # ended inside: the ramp's request, two served, the refused one
    assert (cli["attempted"], cli["failed"], cli["completed"]) == (4, 1, 3)
    assert len(cli["ttft_s"]) == 4 and len(cli["tpot_s"]) == 3
    assert close(cli["tpot_s"][0], 1.0 / 3)


def kept_file_gives_the_line() -> None:
    """What `run.py` writes, read back, gives the numbers of the line."""
    recs = endtoend.samples(RESULTS, W0)
    kept = json.loads(json.dumps({"loop": "open", "window_s": 10.0,
                                  "setup_s": 140.0, "requests": recs}))
    names = [{"name": n, "unit": "x"} for n in (
        "ttft_p50_ms", "ttft_slow20_ms", "tpot_p50_ms", "out_tok_s",
        "setup_s")]
    again = endtoend.compute(names, endtoend.reduce(
        kept["requests"], kept["window_s"], kept["loop"]), kept["setup_s"])
    first = endtoend.compute(names, endtoend.reduce(recs, 10.0, "open"),
                             140.0)
    assert again == first
    assert close(again["ttft_slow20_ms"]["value"], 800.0)   # 1 of 4
    assert close(again["out_tok_s"]["value"], 1.3)


CASES = [(f.__name__, f) for f in (
    slow_72_takes_15, slow_5_takes_1, slow_ties, slow_one_sample,
    slow_never_under_percentile, percentile_is_numpys, names_computed,
    unknown_name_raises, benchmark_names_computable,
    rehearsal_cells_mirror_benchmark, records_leave_out_other_phases,
    open_loop_window, closed_loop_window, kept_file_gives_the_line)]


if __name__ == "__main__":
    for name, case in CASES:
        case()
        print(f"ok   {name}")
    print("end-to-end reduction: ok")
