"""End-to-end metrics from the client's samples, by name.

`<ttft|tpot>_p<NN>_ms` is the NNth percentile over the window's requests:
TTFT from the instant a request was due to its first token-carrying frame,
TPOT (last frame - first frame) / (tokens - 1) per completed request.
`<ttft|tpot>_slow<NN>_ms` is the mean of the slowest NN% of the same samples
(the `ceil(n * NN / 100)` largest): a tail that every slow request moves,
where a percentile reads the two samples beside one rank.
`out_tok_s` is every output token received inside the window over the
window's seconds. `setup_s` is process start to the opening of the window. A later PR can name another
percentile or another share in BENCHMARK.json without touching this file.

The samples themselves are records, one a request (`samples`), on the
client's clock with the window's opening as 0; `reduce` makes of them what
the metrics and the readers read. A run keeps its records as
`client_samples.json`, so any statistic can be taken from a finished run.
"""

from __future__ import annotations

import re

_PCT = re.compile(r"^(ttft|tpot)_p(\d{1,2})_ms$")
_SLOW = re.compile(r"^(ttft|tpot)_slow(\d{1,2})_ms$")


def percentile(values: list[float], p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def slowest_mean(values: list[float], share: int) -> float:
    """Mean of the slowest `share` percent: the ceil(n * share / 100)
    largest values, at least one."""
    n = max(1, -(-len(values) * share // 100))
    return sum(sorted(values)[-n:]) / n


def samples(results: list, w0: float) -> list[dict]:
    """One record a request of ramp and window, times in seconds since the
    window opened (`lib/client.py:Result` holds them on perf_counter)."""
    return [{
        "phase": r.phase, "due": r.due - w0, "sent": r.sent - w0,
        "first": r.frames[0][0] - w0 if r.frames else None,
        "done": r.done - w0, "prompt_tokens": r.prompt_tokens,
        "max_tokens": r.max_tokens, "tokens": r.tokens, "ok": r.ok,
        "error": r.error, "finish": r.finish,
        "frames": [[t - w0, n] for t, n in r.frames],
    } for r in results if r.phase in ("ramp", "window")]


def reduce(records: list[dict], window_s: float, loop: str) -> dict:
    """Samples of the window."""
    in_win = [r for r in records if 0.0 <= r["due"] < window_s]
    if loop == "closed":
        # callers come back only when a request ends: the attempts of the
        # window are the requests that ended in it (those cut by its end
        # are neither completed nor failed)
        ended = [r for r in records
                 if 0.0 <= r["done"] < window_s and r["error"] != "cancelled"]
    else:
        ended = in_win
    failed = [r for r in ended if not r["ok"]]
    firsts = [r for r in in_win if r["frames"]
              and (r["ok"] or r["error"] == "cancelled")]
    done = [r for r in ended if r["ok"] and r["tokens"] > 1]
    late = sorted(r["sent"] - r["due"] for r in in_win)
    return {
        "attempted": len(ended), "failed": len(failed),
        "failures": sorted({str(r["error"] or r["finish"])
                            for r in failed})[:5],
        "ttft_s": [r["first"] - r["due"] for r in firsts],
        "ttft_from_send_s": [r["first"] - r["sent"] for r in firsts],
        "tpot_s": [(r["frames"][-1][0] - r["first"]) / (r["tokens"] - 1)
                   for r in done],
        "window_tokens": sum(n for r in records for t, n in r["frames"]
                             if 0.0 <= t < window_s),
        "completed": len(done), "window_s": window_s,
        "frames_per_request": (sum(len(r["frames"]) for r in done)
                               / max(1, len(done))),
        "late_ms_p50": 1e3 * late[len(late) // 2] if late else 0.0,
        "late_ms_max": 1e3 * late[-1] if late else 0.0,
    }


def compute(metrics: list[dict], cli: dict, setup_s: float) -> dict:
    out = {}
    for m in metrics:
        name = m["name"]
        pct, slow = _PCT.match(name), _SLOW.match(name)
        if pct or slow:
            kind, share = (pct or slow).groups()
            values = cli[kind + "_s"]
            if not values:
                continue
            value = 1e3 * (percentile(values, float(share)) if pct
                           else slowest_mean(values, int(share)))
        elif name == "out_tok_s":
            value = cli["window_tokens"] / cli["window_s"]
        elif name == "setup_s":
            value = setup_s
        else:
            raise KeyError(f"no end-to-end metric named {name!r}")
        out[name] = {"value": value, "unit": m["unit"]}
    return out
