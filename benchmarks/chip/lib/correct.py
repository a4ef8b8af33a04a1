"""The parent's side of the comparison with the plain reference: choose the
sequences, hand them to the child (`lib/refcheck.py`) once the worker has
exited, and hold the two numbers it reads to the configuration's limits.
No JAX here: the parent never imports it.

The limits are the configuration's (`deployment.correct`): `logprob_dev_max`
and `served_gap_max`, each with the readings it was set from in words.
`"in_correct": false` there prints the comparison and leaves it out of
`correct`: for a configuration whose program is known to disagree, until an
issue of its own repairs the program (README.md).
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys

from lib.ckpt import WORD
from lib.deploy import HERE, ROOT, BenchFailure, child_env, worker_env

SAMPLE = 3              # requests of the window the reference is run over
CHILD_TIMEOUT_S = 900.0


def ids_of(text: str) -> list[int]:
    """The benchmark's tokenizer makes every id one fixed-width word."""
    width = len(WORD.format(0))
    words = text.split()
    if any(len(w) != width for w in words):
        raise BenchFailure("a served word is not of the tokenizer's shape")
    return [int(w[1:]) for w in words]


def sequences(run: dict, traffic: dict, seed: int) -> list[dict]:
    """Both probes, with the log-probabilities they reported, and a sample
    drawn from the seed of the requests the window finished, the longest
    (prompt and served tokens together) always in it. Each says whether its
    tokens were chosen greedily: only then does `served_gap` read it."""
    def greedy(sampling: dict) -> bool:
        return float(sampling.get("temperature", 1.0)) <= 0.0

    probe_sampling = {**traffic["sampling"],
                      **(traffic["warmup"].get("probe_sampling") or {})}
    out = [{"kind": "probe", "prompt": ids_of(r.prompt),
            "served": ids_of("".join(r.text)), "logprobs": r.logprobs,
            "greedy": greedy(probe_sampling)} for r in run["probes"]]
    done = [r for r in run["results"] if r.phase in ("ramp", "window")
            and r.ok and run["w0"] <= r.done < run["w1"]]
    if done:
        done.sort(key=lambda r: (r.prompt_tokens + r.tokens, r.due))
        longest = done.pop()
        rng = random.Random(f"{seed}:correct")
        picked = [longest] + rng.sample(done, min(SAMPLE - 1, len(done)))
        out += [{"kind": "window", "prompt": ids_of(r.prompt),
                 "served": ids_of("".join(r.text)),
                 "greedy": greedy(traffic["sampling"])} for r in picked]
    return out


def compare(config: dict, ckpt_dir: str, log_dir: str, seqs: list[dict],
            control: str | None) -> dict:
    """Run the child on the device the worker had, and read its answer."""
    job = os.path.join(log_dir, "reference_job.json")
    answer = os.path.join(log_dir, "reference.json")
    with open(job, "w") as f:
        json.dump({"config": config, "checkpoint": ckpt_dir,
                   "sequences": seqs, "control": control,
                   "compile_cache": os.path.join(ROOT, ".jax_cache")}, f)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "lib", "refcheck.py"), job,
         answer], env=worker_env(child_env(), 1, {}), capture_output=True,
        text=True, timeout=CHILD_TIMEOUT_S)
    if proc.returncode != 0:
        raise BenchFailure(f"the reference failed: {proc.stderr[-1500:]}")
    with open(answer) as f:
        return json.load(f)


def judge(config: dict, got: dict) -> tuple[bool, list[str]]:
    """(within the limits, one line a number compared beside its limit)."""
    limits = config["deployment"]["correct"]
    lines, ok = [], True
    for name, count in (("logprob_dev", "logprob_n"),
                        ("served_gap", "served_n")):
        value, limit = got[name], float(limits[name + "_max"])
        good = value is not None and value <= limit
        ok = ok and good
        lines.append(
            f"{name} {value if value is None else format(value, '.6f')} "
            f"(limit {limit:g}, {got[count]} tokens compared)"
            + ("" if good else "  <-- BEYOND THE LIMIT: "
               + json.dumps(got["worst"].get(name))))
    if not limits.get("in_correct", True):
        lines.append("this configuration's comparison is printed and left "
                     "out of `correct`: " + str(limits.get("why_not", "")))
        ok = True
    return ok, lines
