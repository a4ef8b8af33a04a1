"""Deltas of Prometheus samples between two scrapes of the worker."""

from __future__ import annotations


def delta(ctx: dict, over: str, metric: str, labels: str = "") -> float | None:
    """Sum over the samples of `metric` (exact name, or name + `{`) whose
    label text contains `labels`, stop minus start. None where the later
    scrape has no such sample."""
    start, stop = ctx[over]["prom_start"], ctx[over]["prom_stop"]
    keys = [k for k in stop
            if (k == metric or k.startswith(metric + "{")) and labels in k]
    if not keys:
        return None
    return sum(stop[k] - start.get(k, 0.0) for k in keys)
