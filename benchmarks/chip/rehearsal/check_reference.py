"""The comparison with the reference holds the sound program and fails what
it is there to catch, at toy size on the CPU, through the served path.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearsal/check_reference.py

Each case drives a run (`run.measure`: everything but the look for a chip)
on seeded weights, prefill then decode through the paged cache:

- the toy Mistral served in bf16 and the toy Qwen served in int8 agree with
  `reference/llama.py`: `correct` true;
- the control with the program's own path: the toy Mistral served with
  `--quantize int4` under a configuration that states int8: `correct` false;
- the timed path broken underneath (`broken_worker_launch.py`: tokens
  altered where they are sampled): `correct` false, though the stream keeps
  its shape and the probe repeats;
- the comparison is tight on the reference's side too: over the sound Qwen
  run's own sequences, a reference with another `rope_theta` and a reference
  that leaves out the q/k/v biases are each beyond the limits.
"""

import argparse
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run                                                       # noqa: E402
from lib import correct, deploy                                  # noqa: E402

SEED = 2147483999
SCRATCH = os.path.join(deploy.ROOT, ".bench_chip", "check_reference")


def load(rel: str) -> dict:
    with open(os.path.join(HERE, rel)) as f:
        return json.load(f)


def serve(name: str, config: dict, traffic: str, launcher: str | None = None
          ) -> dict:
    """One run of a scratch cell over `config`; the result line."""
    os.makedirs(SCRATCH, exist_ok=True)
    conf_file = os.path.join(SCRATCH, name + ".json")
    cells_file = os.path.join(SCRATCH, name + ".cells.json")
    with open(conf_file, "w") as f:
        json.dump(config, f)
    with open(cells_file, "w") as f:
        json.dump({"configs": [{"name": name, "file": os.path.relpath(
            conf_file, deploy.ROOT)}], "workloads": [{
                "name": name, "config": name, "traffic": traffic,
                "chips": 1}]}, f)
    sound = deploy.WORKER_LAUNCH
    if launcher:
        deploy.WORKER_LAUNCH = os.path.join(HERE, "rehearsal", launcher)
    try:
        line = run.measure(argparse.Namespace(
            workload=name, seed=SEED, seconds=4.0, trace=0,
            bench_file=cells_file, control=None))[0]
    finally:
        deploy.WORKER_LAUNCH = sound
    print(f"check_reference: {name}: correct {line['correct']} "
          f"{json.dumps(line['reference'])}", flush=True)
    return line


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAIL {what}")


def served_cases() -> None:
    mistral, qwen = (load(f"rehearsal/tiny-{m}.json")
                     for m in ("mistral", "qwen"))
    bf16 = copy.deepcopy(mistral)
    del bf16["deployment"]["worker_flags"]["quantize"]
    bf16["deployment"]["weight_bytes"].update(layers=2, lm_head=2)
    check(serve("sound-bf16", bf16, "decode-saturated")["correct"],
          "the toy Mistral in bf16 against the reference")
    int4 = copy.deepcopy(mistral)
    int4["deployment"]["worker_flags"]["quantize"] = "int4"
    check(not serve("control-int4", int4, "decode-saturated")["correct"],
          "a worker serving int4 under a configuration that states int8 "
          "passed the comparison")
    check(not serve("broken-sampler", mistral, "decode-saturated",
                    "broken_worker_launch.py")["correct"],
          "tokens altered where they are sampled passed the comparison")
    # last: `reference_cases` reads this run's checkpoint, and the
    # directory holds one
    check(serve("sound-int8-biases", qwen, "long-prompt")["correct"],
          "the toy Qwen in int8 against the reference")


def reference_cases() -> None:
    """Over the sound Qwen run's own job, in this process."""
    from lib import refcheck, refio

    with open(os.path.join(deploy.ROOT, ".bench_chip", "sound-int8-biases",
                           "reference_job.json")) as f:
        job = json.load(f)
    config = job["config"]
    check(correct.judge(config, refcheck.compare(job))[0],
          "the saved job does not pass as it is")
    other = copy.deepcopy(job)
    other["config"]["rope_theta"] = config["rope_theta"] / 100
    ok, lines = correct.judge(config, refcheck.compare(other))
    print("check_reference: another rope_theta:", lines, flush=True)
    check(not ok, "a reference with another rope_theta passed")
    has = refio.Checkpoint.__contains__
    refio.Checkpoint.__contains__ = (
        lambda self, name: not name.endswith(".bias") and has(self, name))
    try:
        ok, lines = correct.judge(config, refcheck.compare(job))
    finally:
        refio.Checkpoint.__contains__ = has
    print("check_reference: no q/k/v biases:", lines, flush=True)
    check(not ok, "a reference without the q/k/v biases passed")


if __name__ == "__main__":
    served_cases()
    reference_cases()
    print("check_reference: sound runs pass; int4 under int8, altered "
          "tokens, another rope_theta and missing biases each fail")
