"""The comparison with the plain reference, in a child of its own.

    python lib/refcheck.py <job.json> <out.json>

Runs once the worker has exited and the chip is free (a chip belongs to one
process). The job names the run's checkpoint, its configuration and the
sequences to compare: the probe (prompt, the ids the system emitted, the
log-probabilities it reported for them) and a sample of the requests the
window finished (prompt and served ids). The family's reference
(`reference/<family>.py`) runs once over each prompt with its served
tokens, teacher-forced, and two numbers come out:

- `logprob_dev`: the largest |reported - reference| log-probability over
  the probe's emitted tokens. Values, never the choice of token. Position
  0 checks prefill, the rest decode through the paged cache.
- `served_gap`: the widest gap by which a served token's logit lies below
  the reference's best at its position, over every sequence whose tokens
  were chosen greedily: a sound system then serves the reference's best or
  a near tie. (A probe that draws its tokens is left out.)

With `control` in the job (`int4`: the nearest precision below the stated
one) the reference is computed a second time at that precision and put in
the program's place: its log-probabilities at the same ids, and at each
position the gap of the token it puts first. A builder's reading, from
which the limits were set; the benchmark's own runs do not ask for it.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def compare(job: dict) -> dict:
    import jax
    import numpy as np

    from lib import family, refio

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", job["compile_cache"])
    config = job["config"]
    ref = family.load("reference", config)
    read = refio.Checkpoint(job["checkpoint"])
    seqs = job["sequences"]
    ids = [s["prompt"] + s["served"] for s in seqs]
    starts = [len(s["prompt"]) for s in seqs]

    def log_softmax(z):
        z = z - z.max(axis=-1, keepdims=True)
        return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))

    t0 = time.monotonic()
    ref_logits = ref.logits(read, config, ids, starts,
                            refio.bits_of(config, None))
    out = {"device": jax.devices()[0].platform,
           "logprob_dev": None, "logprob_n": 0, "served_gap": 0.0,
           "served_n": 0, "worst": {}}
    for i, (s, z) in enumerate(zip(seqs, ref_logits)):
        served = np.asarray(s["served"])
        at = z[np.arange(len(served)), served]
        gap = z.max(axis=-1) - at if s["greedy"] else np.zeros(1)
        out["served_n"] += len(served) if s["greedy"] else 0
        j = int(gap.argmax())
        if s["greedy"] and gap[j] >= out["served_gap"]:
            out["served_gap"] = float(gap[j])
            out["worst"]["served_gap"] = {
                "sequence": i, "kind": s["kind"], "position": j,
                "served_id": int(served[j]), "served_logit": float(at[j]),
                "reference_id": int(z[j].argmax()),
                "reference_logit": float(z[j].max())}
        if s.get("logprobs"):
            lp = log_softmax(z)[np.arange(len(served)), served]
            dev = np.abs(lp - np.asarray(s["logprobs"]))
            out["logprob_n"] += len(dev)
            j = int(dev.argmax())
            if out["logprob_dev"] is None or dev[j] >= out["logprob_dev"]:
                out["logprob_dev"] = float(dev[j])
                out["worst"]["logprob_dev"] = {
                    "sequence": i, "kind": s["kind"], "position": j,
                    "id": int(served[j]), "reported": s["logprobs"][j],
                    "reference": float(lp[j])}
    out["seconds"] = time.monotonic() - t0

    if job.get("control"):
        t0 = time.monotonic()
        low = ref.logits(read, config, ids, starts,
                         refio.bits_of(config, job["control"]))
        c = {"precision": job["control"], "logprob_dev": 0.0,
             "served_gap": 0.0}
        for s, z, zl in zip(seqs, ref_logits, low):
            first = zl.argmax(axis=-1)
            rows = np.arange(len(first))
            if s["greedy"]:
                c["served_gap"] = max(c["served_gap"], float(
                    (z.max(axis=-1) - z[rows, first]).max()))
            if s.get("logprobs"):
                served = np.asarray(s["served"])
                c["logprob_dev"] = max(c["logprob_dev"], float(np.abs(
                    log_softmax(zl)[rows, served]
                    - log_softmax(z)[rows, served]).max()))
        c["seconds"] = time.monotonic() - t0
        out["control"] = c
    return out


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        job_ = json.load(f)
    result = compare(job_)
    with open(sys.argv[2], "w") as f:
        json.dump(result, f, indent=1)
