"""End-to-end metrics from the client's samples, by name.

`<ttft|tpot>_p<NN>_ms` is the NNth percentile over the window's requests:
TTFT from the instant a request was due to its first token-carrying frame,
TPOT (last frame - first frame) / (tokens - 1) per completed request.
`out_tok_s` is every output token received inside the window over the
window's seconds. `setup_s` is process start to the opening of the window. A later PR can name another
percentile in BENCHMARK.json without touching this file.
"""

from __future__ import annotations

import re

_PCT = re.compile(r"^(ttft|tpot)_p(\d{1,2})_ms$")


def percentile(values: list[float], p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def compute(metrics: list[dict], cli: dict, setup_s: float) -> dict:
    out = {}
    for m in metrics:
        name, match = m["name"], _PCT.match(m["name"])
        if match:
            samples = cli[match.group(1) + "_s"]
            if not samples:
                continue
            value = 1e3 * percentile(samples, float(match.group(2)))
        elif name == "out_tok_s":
            value = cli["window_tokens"] / cli["window_s"]
        elif name == "setup_s":
            value = setup_s
        else:
            raise KeyError(f"no end-to-end metric named {name!r}")
        out[name] = {"value": value, "unit": m["unit"]}
    return out
