#!/usr/bin/env python3
"""One run of one benchmark cell on the served path.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

One process tree per run: this parent (which never imports JAX) writes the
checkpoint from --seed, starts coordinator -> worker -> frontend
(`--router-mode kv`) as `chip_smoke.py` does, warms up the shapes the cell's
traffic can reach, measures for --seconds at the HTTP client, tears down and
prints one JSON line last. With --trace 1 the worker runs with
DYN_STEP_PROFILE=1 and a few seconds in the middle of the window are
profiled; the line then carries the cell's per-layer metrics.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by its name in BENCHMARK.json (README.md).
No chip is an error and exit 1, never a CPU number: a CPU rehearsal serves
everything and fails at the platform check, on purpose.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import asyncio
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from lib import ckpt, correct, endtoend, prom                   # noqa: E402
from lib.client import Client                                   # noqa: E402
from lib.deploy import (ROOT, BenchFailure, Deployment, MODEL_NAME, http,
                        parse_prom, scrape)                      # noqa: E402
from lib.schedule import ClosedSource, Prompts, open_phase       # noqa: E402

TRACE_SPAN_S = 3.0          # traces are large; this much of the window
MAX_LATE_MS = 50.0
PROBE_TOKENS = 24
DRAIN_S = 60.0


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


# -- phases of a run ---------------------------------------------------------


async def warm_up(client: Client, dep: Deployment, traffic: dict,
                  prompts: Prompts) -> None:
    """Touch every program the mix can reach. A pacer request keeps the
    engine in decode bursts, so the n requests of a group, sent together,
    queue behind one burst and are prefilled as one round; a group whose
    program did not show up on the worker's compile counters is sent
    again."""
    warm = traffic["warmup"]
    pace = warm["pacer"]

    async def pacing() -> None:
        while True:
            await client.complete(
                "pacer", prompts.fresh(pace["prompt_tokens"]),
                pace["prompt_tokens"], pace["max_tokens"],
                time.perf_counter())

    async def pacer_decoding() -> None:
        while True:
            last = [r for r in client.results if r.phase == "pacer"][-1:]
            if last and last[0].frames and not last[0].done:
                return
            if last and last[0].error:
                raise BenchFailure(f"pacer request failed: {last[0].error}")
            await asyncio.sleep(0.02)

    pacer = asyncio.create_task(pacing())
    try:
        for group in warm["groups"]:
            label = 'entry="%s",shape="%s"' % tuple(
                group["expect"].split(":"))
            n, plen = group["simultaneous"], group["prompt_tokens"]
            for attempt in range(4):
                await pacer_decoding()
                texts = [prompts.fresh(plen) for _ in range(n)]
                now = time.perf_counter()
                got = await asyncio.gather(*(client.complete(
                    "warmup", t, plen, group["max_tokens"], now)
                    for t in texts))
                bad = [r.error or r.finish for r in got if not r.ok]
                if bad:
                    raise BenchFailure(f"warm-up request failed: {bad[:3]}")
                seen = await asyncio.to_thread(scrape, dep.sys_port)
                if any(k.startswith("dynamo_compile_total{") and label in k
                       for k in seen):
                    break
                say(f"warm-up {group['expect']} not reached "
                    f"(attempt {attempt + 1}), again")
            else:
                raise BenchFailure(
                    f"warm-up never reached {group['expect']}")
    finally:
        pacer.cancel()
        await asyncio.gather(pacer, return_exceptions=True)


async def quiet(dep: Deployment) -> None:
    """Wait until the engine emits and prefills nothing: a request cut a
    moment ago is still in the engine, and a probe prefilled in one round
    with it runs through another program than a probe prefilled alone."""
    def work() -> float:
        seen = scrape(dep.sys_port)
        return sum(seen.get(k, 0.0) for k in (
            "dynamo_engine_tokens_emitted_total",
            "dynamo_engine_prefill_new_tokens_total"))

    last = -1.0
    for _ in range(60):
        now = await asyncio.to_thread(work)
        if now == last:
            return
        last = now
        await asyncio.sleep(0.25)
    raise BenchFailure("the engine did not come to rest before the probe")


async def probe(client: Client, dep: Deployment, text: str, n: int,
                sampling: dict | None):
    """A fixed request through the same programs, alone in the engine: the
    reusable prefix cache is dropped first, so before and after the window
    it is prefilled whole. It reports the log-probability of each token it
    emits, for the comparison with the reference. `sampling`
    (`warmup.probe_sampling` of the mix: a temperature and a seed) makes it
    draw its tokens, the same draws both times: a greedy stream of noise
    weights settles on one id whose probability is all but 1, and a
    log-probability of 0 deviates by nothing whatever the precision."""
    await quiet(dep)
    await asyncio.to_thread(http, "POST", dep.url + "/clear_kv_blocks", {})
    r = await client.complete("probe", text, n, PROBE_TOKENS,
                              time.perf_counter(), logprobs=True,
                              sampling=sampling)
    if not r.ok:
        raise BenchFailure(f"probe request failed: {r.error or r.finish}")
    if len(r.logprobs) != PROBE_TOKENS:
        raise BenchFailure(f"probe reported {len(r.logprobs)} "
                           f"log-probabilities for {PROBE_TOKENS} tokens")
    return r


async def marks(dep: Deployment, w0: float, w1: float, trace_dir: str | None
                ) -> dict:
    """Scrape the worker at both ends of the window and, in a traced run,
    have it profile a span in the middle."""
    out = {}
    await asyncio.sleep(max(0.0, w0 - time.perf_counter()))
    out["prom_start"] = await asyncio.to_thread(scrape, dep.sys_port)
    if trace_dir:
        span = min(TRACE_SPAN_S, (w1 - w0) / 2)
        await asyncio.sleep(max(0.0, w0 + (w1 - w0 - span) * 0.4
                                - time.perf_counter()))
        offset = time.time() - time.perf_counter()
        ans = await asyncio.to_thread(
            dep.ask_worker, "trace", {"seconds": span, "dir": trace_dir})
        out["span"] = {
            "prom_start": parse_prom(ans["scrape_start"]),
            "prom_stop": parse_prom(ans["scrape_stop"]),
            "t0": ans["t_start"] - offset, "t1": ans["t_stop"] - offset}
    await asyncio.sleep(max(0.0, w1 - time.perf_counter()))
    out["prom_stop"] = await asyncio.to_thread(scrape, dep.sys_port)
    return out


async def drive(dep: Deployment, config: dict, traffic: dict, seed: int,
                seconds: float, trace_dir: str | None) -> dict:
    prompts = Prompts(traffic, config["vocab_size"], seed)
    probe_tokens = traffic["warmup"]["probe_prompt_tokens"]
    probe_text = Prompts(traffic, config["vocab_size"], seed + 1).fresh(
        probe_tokens)
    ramp = traffic["ramp"]
    async with Client(dep.url, MODEL_NAME, traffic["sampling"]) as client:
        await warm_up(client, dep, traffic, prompts)
        compile_s = sum(
            v for k, v in (await asyncio.to_thread(
                scrape, dep.sys_port)).items()
            if k.startswith("dynamo_compile_seconds_total"))
        probe_sampling = traffic["warmup"].get("probe_sampling")
        before = await probe(client, dep, probe_text, probe_tokens,
                             probe_sampling)
        phases = []
        if traffic["loop"] == "open":
            for name, length in (("ramp", ramp["seconds"]),
                                 ("window", seconds)):
                reqs = open_phase(traffic, length, seed, name)
                phases.append((name, reqs, [prompts.text(r) for r in reqs]))
        r0 = time.perf_counter() + 0.05
        w0 = r0 + ramp["seconds"]
        w1 = w0 + seconds
        marker = asyncio.create_task(marks(dep, w0, w1, trace_dir))
        cut = 0
        if traffic["loop"] == "closed":
            await client.closed_loop(
                ClosedSource(traffic, seed), prompts, traffic["clients"],
                r0, w0, w1, ramp.get("stagger_s", 0.0), seed)
        else:
            tasks = []
            for (name, reqs, texts), start in zip(phases, (r0, w0)):
                tasks += await client.open_loop(name, reqs, texts, start)
            _, late = await asyncio.wait(tasks, timeout=DRAIN_S)
            cut = len(late)
            for t in late:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
        got = await marker
        after = await probe(client, dep, probe_text, probe_tokens,
                            probe_sampling)
        return {"results": client.results, "w0": w0, "w1": w1, **got,
                "probes": [before, after], "undrained": cut,
                "probe_same": "".join(before.text) == "".join(after.text),
                "compile_s_after_warmup": compile_s,
                "setup_s": w0 - T_PROCESS}


# -- reduction ---------------------------------------------------------------


def reduce_client(run: dict, loop: str, log_dir: str, about: dict) -> dict:
    """Samples of the window, by the clock of this process. The records
    they are taken from are kept beside the run's logs: every request of
    ramp and window, so that any statistic can be taken from a finished
    run (`lib/endtoend.py`)."""
    window_s = run["w1"] - run["w0"]
    records = endtoend.samples(run["results"], run["w0"])
    with open(os.path.join(log_dir, "client_samples.json"), "w") as f:
        json.dump({**about, "loop": loop, "window_s": window_s,
                   "setup_s": run["setup_s"], "requests": records}, f)
    return endtoend.reduce(records, window_s, loop)


def live_at(results: list, t: float) -> tuple[int, int]:
    """(sequences in flight, tokens of KV they hold) at time t, as the
    client knows them: prompt plus the tokens received so far."""
    live = [r for r in results if r.sent and r.sent <= t
            and (not r.done or r.done > t)]
    return len(live), sum(r.prompt_tokens + r.tokens_before(t) for r in live)


def reduce_trace(trace_dir: str, log_dir: str) -> dict:
    """The trace is read in a child pinned to the CPU, after the worker has
    exited: reading it imports JAX, and a chip belongs to one process."""
    out = os.path.join(log_dir, "trace_summary.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "lib", "trace.py"), trace_dir,
         out], env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        raise BenchFailure(f"trace reduction failed: {proc.stderr[-800:]}")
    return load_json(out)


def read_layer_metrics(names: list[dict], ctx: dict) -> dict:
    out = {}
    for m in names:
        spec = load_json(os.path.join(HERE, "layer_metrics",
                                      m["name"] + ".json"))
        path = os.path.join(HERE, "readers", spec["reader"] + ".py")
        mod_spec = importlib.util.spec_from_file_location(
            "reader_" + spec["reader"], path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        value = mod.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# -- the run -----------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--bench-file", default=os.path.join(
        ROOT, "BENCHMARK.json"), help="for the rehearsals' own cell list")
    ap.add_argument("--control", default=None, choices=("int4",),
                    help="builder's reading: the reference once more at "
                         "this lower precision, in the program's place")
    args = ap.parse_args()
    try:
        return run(args)
    except BenchFailure as e:
        say(f"FAILED: {e}")
    except (OSError, subprocess.SubprocessError, KeyError, ValueError) as e:
        say(f"FAILED: {type(e).__name__}: {e}")
    return 1


def load_cell(bench_file: str, workload: str) -> tuple:
    """(benchmark, cell, configuration, traffic) of one workload, each from
    the file that BENCHMARK.json names."""
    # a rehearsal's cell list may bring only `configs` and `workloads`: what
    # it leaves out is BENCHMARK.json's
    bench = {**load_json(os.path.join(ROOT, "BENCHMARK.json")),
             **load_json(bench_file)}
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise BenchFailure(f"no workload {workload!r} in {bench_file}")
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, conf_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def checkpoint_dir() -> str:
    """One directory, outside the checkout (14 GB), under TMPDIR: it holds
    the last (configuration, seed) written and is reused when they match."""
    return os.path.join(tempfile.gettempdir(), "dynamo-bench-chip", "ckpt")


def run(args) -> int:
    line, device, cell, peaks = measure(args)
    if device["platform"] != "tpu":
        raise BenchFailure(
            f"the worker's arrays sit on {device['platform']!r}, not on a "
            "TPU: no result")
    if device["count"] != cell["chips"]:
        raise BenchFailure(f"worker used {device['count']} devices, the "
                           f"cell asks for {cell['chips']}")
    if device["kind"] not in peaks:
        raise BenchFailure(f"device kind {device['kind']!r} is not in "
                           "peaks.json")
    print(json.dumps(line), flush=True)
    return 0


def measure(args) -> tuple:
    """Everything of a run but the look at the platform: (result line, the
    worker's device report, cell, peaks)."""
    if not os.path.isdir(os.path.join(ROOT, "dynamo_tpu")):
        raise BenchFailure(f"no dynamo_tpu package in {ROOT}: nothing to "
                           "measure")
    bench, cell, config, traffic = load_cell(args.bench_file, args.workload)
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    seconds = args.seconds or bench["run_seconds"]
    platforms = os.environ.get("JAX_PLATFORMS", "tpu")
    if "tpu" not in platforms and not config.get("rehearsal"):
        raise BenchFailure(
            f"JAX_PLATFORMS={platforms}: {cell['config']} is a full-size "
            "configuration and runs on the chip only")
    if int(config["deployment"]["chips"]) != cell["chips"]:
        raise BenchFailure("the cell and its configuration disagree on chips")

    log_dir = os.path.join(ROOT, ".bench_chip", args.workload)
    os.makedirs(log_dir, exist_ok=True)
    trace_dir = os.path.join(log_dir, "trace") if args.trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ckpt_dir = checkpoint_dir()
    t = time.monotonic()
    wrote = ckpt.write_checkpoint(ckpt_dir, config, args.seed)
    say(f"checkpoint {'written' if wrote else 'reused'} in "
        f"{time.monotonic() - t:.1f}s at {ckpt_dir}")

    extra = {"DYN_STEP_PROFILE": "1"} if args.trace else {}
    with Deployment(config, ckpt_dir, log_dir, extra) as dep:
        say(f"worker up in {dep.load_s:.1f}s: {json.dumps(dep.device)}")
        run_ = asyncio.run(drive(dep, config, traffic, args.seed, seconds,
                                 trace_dir))
        memory = dep.ask_worker("memory")
        device = dep.device
        load_s = dep.load_s
    cli = reduce_client(run_, traffic["loop"], log_dir, {
        "workload": args.workload, "seed": args.seed, "trace": args.trace})
    # the worker has exited and its memory peak is read: the chip is free
    # for the reference
    t = time.monotonic()
    ref = correct.compare(config, ckpt_dir, log_dir,
                          correct.sequences(run_, traffic, args.seed),
                          args.control)
    say(f"reference ({ref['device']}) over {ref['served_n']} served tokens "
        f"in {ref['seconds']:.1f}s, {time.monotonic() - t:.1f}s with the "
        "child's start and compiles")
    if ref.get("control"):
        say(f"control {json.dumps(ref['control'])}")
    ref_ok, ref_lines = correct.judge(config, ref)
    say("probe ids " + " ".join(str(i) for i in correct.ids_of(
        "".join(run_["probes"][0].text))))

    window = {"prom_start": run_["prom_start"],
              "prom_stop": run_["prom_stop"]}
    compiles = prom.delta({"window": window}, "window",
                          "dynamo_compile_total") or 0.0
    say(f"set-up {run_['setup_s']:.1f}s (worker load {load_s:.1f}s, compile "
        f"seconds after warm-up {run_['compile_s_after_warmup']:.1f}); "
        f"compiles in window {compiles:.0f}"
        + ("  <-- A PROGRAM COMPILED INSIDE THE WINDOW: " + ", ".join(
            k for k, v in window["prom_stop"].items()
            if k.startswith("dynamo_compile_total")
            and v > window["prom_start"].get(k, 0.0)) if compiles else ""))
    say(f"generator lateness p50 {cli['late_ms_p50']:.2f} ms, max "
        f"{cli['late_ms_max']:.2f} ms"
        + ("  <-- GENERATOR RAN LATE" if cli["late_ms_max"] > MAX_LATE_MS
           else ""))
    say(f"window {cli['window_s']:.1f}s: attempted {cli['attempted']} "
        f"failed {cli['failed']} {cli['failures']} completed "
        f"{cli['completed']} ({cli['frames_per_request']:.1f} token frames "
        f"each) undrained {run_['undrained']}"
        + ("  <-- FEWER THAN 100 REQUESTS" if cli["completed"] < 100
           else ""))
    checks = [
        f"failed {cli['failed']} (limit 0) of attempted "
        f"{cli['attempted']} (at least 1)",
        f"probe repeated identically {run_['probe_same']} (must)",
        f"compiles in window {compiles:.0f} (limit 0)",
        f"undrained {run_['undrained']} (limit 0)", *ref_lines]
    is_correct = (cli["failed"] == 0 and cli["attempted"] > 0
                  and run_["probe_same"] and compiles == 0
                  and run_["undrained"] == 0 and ref_ok)
    checks.append(f"correct: {is_correct}")
    for text in checks:
        say(text)

    out_device = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"],
                  "memory_peak_bytes": memory["peak_bytes"]}
    line = {"correct": is_correct, "attempted": cli["attempted"],
            "failed": cli["failed"], "device": out_device,
            "reference": {k: ref[k] for k in (
                "logprob_dev", "logprob_n", "served_gap", "served_n",
                "seconds")}}
    if not args.trace:
        line["metrics"] = endtoend.compute(
            [m for m in bench["end_to_end"] if applies(m, args.workload)],
            cli, run_["setup_s"])
    else:
        trace = reduce_trace(trace_dir, log_dir)
        span = run_["span"]
        lanes0, kv0 = live_at(run_["results"], span["t0"])
        lanes1, kv1 = live_at(run_["results"], span["t1"])
        ctx = {"window": window,
               "span": {**span, "seconds": span["t1"] - span["t0"],
                        "lanes": (lanes0 + lanes1) / 2,
                        "kv_tokens": (kv0 + kv1) / 2},
               "trace": trace, "client": cli, "config": config,
               "deployment": {"load_s": load_s}, "traffic": traffic,
               "peaks": peaks.get(device["kind"], {})}
        # what the readers read, kept beside the trace: a later reduction
        # of this run reads the same numbers
        with open(os.path.join(log_dir, "layer_ctx.json"), "w") as f:
            json.dump(ctx, f)
        line["metrics"] = read_layer_metrics(
            [m for m in bench["per_layer"] if applies(m, args.workload)], ctx)
        out_device["busy_s"] = trace["busy_s"]
        out_device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"][:10],
                             "idle_gaps": trace["idle_gaps"][:10]}
    say(f"metrics computed: {sorted(line['metrics'])}")
    # each number compared beside its limit: the last lines on standard
    # error too, where the driver's record keeps them
    print("\n".join(f"[bench] {text}" for text in checks), file=sys.stderr,
          flush=True)
    return line, device, cell, peaks


if __name__ == "__main__":
    sys.exit(main())
