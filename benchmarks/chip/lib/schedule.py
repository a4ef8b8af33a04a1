"""Seeded traffic from a traffic file: arrivals, lengths, prompts.

Adapted from `dynamo_tpu/trafficgen/schedule.py` (pure, one seeded RNG,
Poisson / bursty arrivals, lognormal lengths, shared prefixes). What the
benchmark changes, and why:

- *Stratified draws.* A window holds one or two hundred requests; free
  draws of a heavy-tailed length make the work of a run depend on its
  seed by several per cent, which would have to be paid for in the
  bounds. With `"draws": "stratified"` (the default) the n values of a
  distribution are its quantiles at (i + 0.5) / n, and the seed only
  chooses their order: every seed offers the same set of sizes and the
  same set of gaps between arrivals, in another order. `"random"` keeps
  the original free draws (the only way to make `bursty` arrivals).
- `"schedule_seed"` in a traffic file fixes the order too: the schedule is
  then a replay, the same in every run, and `--seed` chooses only the
  prompts' words (and the weights). For an open loop near its knee, where
  the order of arrivals alone moves a window's median TTFT by a quarter.
- Lengths may be `fixed`, a weighted `choice`, or a clipped `lognormal`
  (the median is the parameter, as in the original).
- A prompt is words of the benchmark's own tokenizer (`lib/ckpt.py`):
  n words are exactly n tokens.

Pure: no clock, no network, no global RNG.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass
from statistics import NormalDist

from lib.ckpt import WORD


@dataclass
class Request:
    at: float               # seconds after the phase starts (open loop)
    prompt_tokens: int
    max_tokens: int
    prefix_id: int = -1


def _quantiles(n: int) -> list[float]:
    return [(i + 0.5) / n for i in range(n)]


def _length_at(spec: dict, q: float) -> int:
    kind = spec["kind"]
    if kind == "fixed":
        return int(spec["value"])
    if kind == "choice":
        total = float(sum(spec["weights"]))
        acc = 0.0
        for value, weight in zip(spec["values"], spec["weights"]):
            acc += weight / total
            if q < acc:
                return int(value)
        return int(spec["values"][-1])
    if kind == "lognormal":
        z = NormalDist().inv_cdf(min(max(q, 1e-9), 1 - 1e-9))
        v = math.exp(math.log(spec["median"]) + spec["sigma"] * z)
        return int(min(max(v, spec["min"]), spec["max"]))
    raise ValueError(f"unknown length kind {kind!r}")


def lengths(spec: dict, n: int, rng: random.Random,
            stratified: bool) -> list[int]:
    """n lengths of one distribution: its quantiles in seeded order, or
    free draws."""
    if stratified:
        out = [_length_at(spec, q) for q in _quantiles(n)]
        rng.shuffle(out)
        return out
    return [_length_at(spec, rng.random()) for _ in range(n)]


def _bursty_times(arr: dict, duration: float,
                  rng: random.Random) -> list[float]:
    """Two-state Markov-modulated Poisson process (as the original)."""
    out, t, storm = [], 0.0, False
    while True:
        rate = arr["burst_rps"] if storm else arr["rps"]
        flip = arr["burst_stop_rate"] if storm else arr["burst_start_rate"]
        dt_arrival = rng.expovariate(rate)
        dt_flip = rng.expovariate(flip) if flip > 0 else float("inf")
        if dt_flip < dt_arrival:
            t += dt_flip
            storm = not storm
        else:
            t += dt_arrival
            if t <= duration:
                out.append(t)
        if t > duration:
            return out


def arrival_times(arr: dict, duration: float, rng: random.Random,
                  stratified: bool) -> list[float]:
    """Arrival offsets in (0, duration]."""
    pattern, rps = arr["pattern"], float(arr["rps"])
    n = max(1, round(rps * duration))
    if pattern == "constant":
        return [(i + 1) / rps for i in range(n)]
    if pattern == "poisson" and stratified:
        # the n gaps are the exponential's quantiles, scaled so that they
        # fill the phase exactly; the seed orders them
        gaps = [-math.log(1.0 - q) for q in _quantiles(n)]
        rng.shuffle(gaps)
        scale = duration / sum(gaps)
        out, t = [], 0.0
        for g in gaps:
            t += g * scale
            out.append(min(t, duration))
        return out
    if pattern == "poisson":
        out, t = [], 0.0
        while True:
            t += rng.expovariate(rps)
            if t > duration:
                return out
            out.append(t)
    if pattern == "bursty":
        return _bursty_times(arr, duration, rng)
    raise ValueError(f"unknown arrival pattern {pattern!r}")


def _prefix_ids(sharing: dict | None, n: int, rng: random.Random,
                stratified: bool) -> list[int]:
    if not sharing or sharing.get("fraction", 0.0) <= 0:
        return [-1] * n
    pool = int(sharing["num_prefixes"])
    if stratified:
        k = round(sharing["fraction"] * n)
        ids = [i % pool for i in range(k)] + [-1] * (n - k)
        rng.shuffle(ids)
        return ids
    return [rng.randrange(pool) if rng.random() < sharing["fraction"]
            else -1 for _ in range(n)]


def open_phase(traffic: dict, duration: float, seed: int,
               salt: str) -> list[Request]:
    """The requests of one open-loop phase (`salt` tells the ramp from the
    window, so that they are two orders of the same kind of traffic)."""
    rng = random.Random(f"{traffic.get('schedule_seed', seed)}:{salt}")
    strat = traffic.get("draws", "stratified") == "stratified"
    times = arrival_times(traffic["arrivals"], duration, rng, strat)
    n = len(times)
    isl = lengths(traffic["prompt_tokens"], n, rng, strat)
    osl = lengths(traffic["output_tokens"], n, rng, strat)
    pids = _prefix_ids(traffic.get("sharing"), n, rng, strat)
    return [Request(times[i], isl[i], osl[i], pids[i]) for i in range(n)]


class ClosedSource:
    """Requests of a closed loop, handed out in the order clients ask.
    Lengths come in blocks of `block` stratified values, so that any run
    of `block` consecutive requests is the same set whatever the seed."""

    def __init__(self, traffic: dict, seed: int, block: int = 256) -> None:
        self.traffic, self.block = traffic, block
        self.rng = random.Random(
            f"{traffic.get('schedule_seed', seed)}:closed")
        self.strat = traffic.get("draws", "stratified") == "stratified"
        self._buf: list[tuple[int, int, int]] = []

    def next(self) -> Request:
        if not self._buf:
            n = self.block
            self._buf = list(zip(
                lengths(self.traffic["prompt_tokens"], n, self.rng,
                        self.strat),
                lengths(self.traffic["output_tokens"], n, self.rng,
                        self.strat),
                _prefix_ids(self.traffic.get("sharing"), n, self.rng,
                            self.strat)))
        return Request(0.0, *self._buf.pop())


class Prompts:
    """Prompt text for a request: `prompt_tokens` words in all, the first
    `prefix_tokens` of them a shared system prompt when the request has a
    prefix. Unshared words are random ids, so two prompts share no prefix
    (a collision over a whole 16-token page has odds below 1e-60)."""

    def __init__(self, traffic: dict, vocab_size: int, seed: int) -> None:
        self.vocab = vocab_size
        self.rng = random.Random(f"{seed}:prompts")
        sharing = traffic.get("sharing") or {}
        prefix_rng = random.Random(f"{seed}:prefixes")
        self.prefixes = [
            self._words(prefix_rng, int(sharing["prefix_tokens"]))
            for _ in range(int(sharing.get("num_prefixes", 0)))]

    def _words(self, rng: random.Random, n: int) -> list[str]:
        return [WORD.format(rng.randrange(self.vocab)) for _ in range(n)]

    def fresh(self, n: int) -> str:
        """n words shared with nothing: warm-up, pacer and probe prompts."""
        return " ".join(self._words(self.rng, n))

    def text(self, req: Request) -> str:
        head = self.prefixes[req.prefix_id] if req.prefix_id >= 0 else []
        head = head[:req.prompt_tokens]
        return " ".join(head + self._words(
            self.rng, req.prompt_tokens - len(head)))
