#!/usr/bin/env python3
"""Chip smoke: the main serving path, once, on the accelerator.

    python chip_smoke.py                 # one chip, Llama-3-8B widths
    python chip_smoke.py --chips 4       # tensor-parallel worker vs one chip
    JAX_PLATFORMS=cpu python chip_smoke.py --model tiny   # CPU rehearsal

Drives the entry points a user would: a synthetic HF checkpoint (weights
from --seed) is written and loaded through the real loader, then
`python -m dynamo_tpu.coordinator`, one `python -m dynamo_tpu.worker` and
`python -m dynamo_tpu.frontend --router-mode kv` run as three processes
and answer OpenAI requests over HTTP. This parent, the coordinator and
the frontend never import JAX (a chip belongs to one process); the
device block of the result comes from the worker's own report.

The last stdout line is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`;
the exit code is 0 only when every phase passed AND the worker's arrays
sit on a TPU. The CPU rehearsal therefore serves every request and still
ends `"ok": false`.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, ".chip_smoke")
MODEL_NAME = "smoke"
# first-token logprobs of the tp worker vs the one-chip worker: same
# weights, same int8 quantisation, different reduction order (psum over
# tp) in bf16 — agreement, not bit-equality, is the contract
LOGPROB_TOL = 0.25
HEAD_GAIN = 16.0
# the deployment: weight-only int8 (8B bf16 alone would fill the chip),
# the worker's default KV pool, a prefill chunk short enough that the
# long prompt crosses it
QUANTIZE = "int8"
NUM_PAGES, MAX_BATCH, PREFILL_CHUNK, CONTEXT = 2048, 8, 128, 2048
LOAD_TIMEOUT_S = 900.0


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: str, n: int = 60) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<no log: {e}>"


def maps_hold(pid: int, needle: str) -> bool:
    """True when a shared object whose path contains `needle` is mapped
    into process `pid` — how the smoke sees, from outside, whether a
    process loaded jaxlib or the native radix library."""
    try:
        with open(f"/proc/{pid}/maps") as f:
            return any(needle in line for line in f)
    except OSError:
        return False


class Proc:
    """One child of the deployment, its output in LOG_DIR/<name>.log."""

    def __init__(self, name: str, argv: list[str], env: dict) -> None:
        self.name = name
        self.log = os.path.join(LOG_DIR, f"{name}.log")
        self._f = open(self.log, "w")
        self.p = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=self._f,
                                  stderr=subprocess.STDOUT)

    def wait_line(self, marker: str, timeout: float) -> str:
        """Block until a log line starts with `marker`; fail if the
        process dies or the time runs out."""
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            with open(self.log, errors="replace") as f:
                for line in f:
                    if line.startswith(marker):
                        return line.strip()
            if self.p.poll() is not None:
                raise SmokeFailure(
                    f"{self.name} exited rc={self.p.returncode} before "
                    f"{marker}\n--- {self.name} log tail ---\n"
                    f"{tail(self.log)}")
            time.sleep(0.25)
        raise SmokeFailure(
            f"{self.name}: no {marker} within {timeout:.0f}s\n"
            f"--- {self.name} log tail ---\n{tail(self.log)}")

    def stop(self, grace: float = 30.0) -> None:
        if self.p.poll() is None:
            self.p.send_signal(signal.SIGTERM)
            try:
                self.p.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                self.p.kill()
                self.p.wait()
        self._f.close()


def http(method: str, url: str, body=None, timeout: float = 600.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read().decode()


def chat(url: str, prompt: str, max_tokens: int, stream: bool) -> dict:
    """One greedy seeded chat completion with per-token logprobs (the
    byte tokenizer decodes most of a 128k vocab to empty text, so the
    logprob list is the token stream's fingerprint). Returns
    {n, logprobs, finish_reason, text, ttft_s, wall_s}, and for a
    streamed one the arrival of each token-carrying frame."""
    body = {"model": MODEL_NAME,
            "messages": [{"role": "user", "content": prompt}],
            "max_tokens": max_tokens, "temperature": 0.0, "seed": 7,
            "logprobs": True, "stream": stream}
    t0 = time.monotonic()
    if not stream:
        out = json.loads(http("POST", url + "/v1/chat/completions", body))
        ch = out["choices"][0]
        lps = [e["logprob"] for e in (ch.get("logprobs") or {})
               .get("content", [])]
        return {"n": out["usage"]["completion_tokens"], "logprobs": lps,
                "finish_reason": ch.get("finish_reason"),
                "text": ch["message"].get("content") or "",
                "ttft_s": None, "wall_s": time.monotonic() - t0}
    req = urllib.request.Request(
        url + "/v1/chat/completions", data=json.dumps(body).encode(),
        headers={"content-type": "application/json"})
    lps, text, finish, ttft, frames, arrivals = [], "", None, None, 0, []
    with urllib.request.urlopen(req, timeout=600.0) as r:
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            payload = line[5:].strip()
            if payload == "[DONE]":
                break
            frames += 1
            for ch in json.loads(payload).get("choices", []):
                got = [e["logprob"] for e in (ch.get("logprobs") or {})
                       .get("content", [])]
                if got and ttft is None:
                    ttft = time.monotonic() - t0
                if got:
                    arrivals.append(
                        (round((time.monotonic() - t0) * 1e3), len(got)))
                lps += got
                text += (ch.get("delta") or {}).get("content") or ""
                finish = ch.get("finish_reason") or finish
    return {"n": len(lps), "logprobs": lps, "finish_reason": finish,
            "text": text, "ttft_s": ttft, "frames": frames,
            "arrivals": arrivals, "wall_s": time.monotonic() - t0}


def scrape(port: int) -> dict[str, float]:
    """Prometheus text of the worker's system port → {sample: value}."""
    out = {}
    for line in http("GET", f"http://127.0.0.1:{port}/metrics",
                     timeout=60).splitlines():
        if line and not line.startswith("#"):
            k, _, v = line.rpartition(" ")
            try:
                out[k] = float(v)
            except ValueError:
                pass
    return out


def cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.startswith("."))
    except OSError:
        return 0


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def worker_env(args, base: dict) -> dict:
    """JAX falls back to the CPU when the TPU fails to open. Where the
    caller left JAX_PLATFORMS unset that must be an error; where the
    caller set it (the CPU rehearsal, the chip tool) it passes through
    and the platform check at the end decides."""
    env = dict(base)
    env.setdefault("JAX_PLATFORMS", "tpu")
    if args.chips > 1 and "tpu" not in env["JAX_PLATFORMS"]:
        # rehearsal of the sharded path on virtual CPU devices
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.chips}").strip()
    return env


def preflight(env: dict) -> dict:
    """`python -m dynamo_tpu.doctor preflight` in a child that exits
    before the worker starts (doctor's device checks open the backend
    themselves and cannot run beside a live worker)."""
    proc = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.doctor", "preflight", "--json",
         "--attempts", "1", "--timeout", "300"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)
    try:
        verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError):
        raise SmokeFailure(
            f"preflight printed no verdict (rc={proc.returncode}): "
            f"{(proc.stderr or proc.stdout)[-400:]}") from None
    return verdict


def write_checkpoint(args, env: dict) -> tuple[str, float]:
    """Synthetic HF checkpoint at a fixed path under the temp dir —
    outside the checkout (16 GB at full depth) and reused when a run
    before this one already wrote it."""
    path = os.path.join(
        tempfile.gettempdir(), "dynamo-chip-smoke",
        f"{args.model}-L{args.layers or 'full'}-s{args.seed}")
    # head_gain: noise weights give nearly flat logits, where every
    # greedy choice is a near-tie and no comparison of two token
    # streams means anything; a scaled lm_head gives a peaked one
    code = ("import sys; from dynamo_tpu.models.synth_ckpt import "
            "write_synthetic_hf_checkpoint as w; "
            "w(sys.argv[1], sys.argv[2], seed=int(sys.argv[3]), "
            f"layers=int(sys.argv[4]), head_gain={HEAD_GAIN})")
    t0 = time.monotonic()
    # the writer is numpy-only but its package imports jax: keep that
    # child off the chip
    proc = subprocess.run(
        [sys.executable, "-c", code, path, args.model, str(args.seed),
         str(args.layers)],
        cwd=ROOT, env={**env, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True)
    check(proc.returncode == 0,
          f"checkpoint write failed: {proc.stderr[-800:]}")
    return path, time.monotonic() - t0


class Deployment:
    """coordinator -> worker -> frontend, each its own process."""

    def __init__(self, args, env: dict, ckpt: str, tp: int,
                 tag: str) -> None:
        self.tag = tag
        self.procs: list[Proc] = []
        try:
            self._bring_up(args, env, ckpt, tp)
        except BaseException:
            self.stop()     # a half-started deployment leaves nothing behind
            raise

    def _bring_up(self, args, env: dict, ckpt: str, tp: int) -> None:
        store_port, self.sys_port = free_port(), free_port()
        http_port = free_port()
        self.url = f"http://127.0.0.1:{http_port}"
        store = f"tcp://127.0.0.1:{store_port}"
        py = [sys.executable, "-m"]
        coord = self._start("coordinator", py + [
            "dynamo_tpu.coordinator", "--port", str(store_port)], env)
        coord.wait_line("COORDINATOR_READY", 60)
        wenv = worker_env(args, env)
        if tp > 1:
            wenv["DYN_MESH_RECORDER"] = "1"
        wargs = py + [
            "dynamo_tpu.worker", "--model", ckpt, "--store", store,
            "--served-model-name", MODEL_NAME,
            "--quantize", QUANTIZE, "--num-pages", str(NUM_PAGES),
            "--max-batch-size", str(MAX_BATCH),
            "--prefill-chunk", str(PREFILL_CHUNK),
            "--context-length", str(CONTEXT),
            "--system-port", str(self.sys_port)]
        if tp > 1:
            wargs += ["--tensor-parallel-size", str(tp)]
        t0 = time.monotonic()
        self.worker = self._start("worker", wargs, wenv)
        line = self.worker.wait_line("WORKER_DEVICE", LOAD_TIMEOUT_S)
        self.device = json.loads(line.split(" ", 1)[1])
        self.worker.wait_line("WORKER_READY", 120)
        self.load_s = time.monotonic() - t0
        self.frontend = self._start("frontend", py + [
            "dynamo_tpu.frontend", "--host", "127.0.0.1", "--port",
            str(http_port), "--store", store, "--router-mode", "kv"], env)
        self.frontend.wait_line("FRONTEND_READY", 120)
        self.coordinator = coord
        end = time.monotonic() + 60
        while MODEL_NAME not in http("GET", self.url + "/v1/models",
                                     timeout=30):
            check(time.monotonic() < end,
                  "frontend never listed the worker's model")
            time.sleep(0.25)

    def _start(self, name: str, argv: list[str], env: dict) -> Proc:
        p = Proc(f"{self.tag}{name}", argv, env)
        self.procs.append(p)
        return p

    def stop(self) -> None:
        # frontend first, coordinator last: the reverse of start-up
        for p in reversed(self.procs):
            p.stop()

    def __enter__(self) -> "Deployment":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            say(f"--- {self.worker.name} log tail ---\n"
                f"{tail(self.worker.log)}")
        self.stop()


def long_prompt(n_chars: int, salt: str) -> str:
    words = ("pack my box with five dozen liquor jugs and " + salt + " ")
    return (words * (n_chars // len(words) + 1))[:n_chars]


def serve_requests(dep: Deployment, args) -> dict:
    """The traffic of the one-chip smoke. Returns facts for the report;
    raises SmokeFailure on the first broken promise."""
    chunk = PREFILL_CHUNK
    # long enough to cross a prefill-chunk boundary (the byte tokenizer
    # makes one token per character) and several 8-step decode bursts
    p_long = long_prompt(chunk + chunk // 2, "alpha")
    t0 = time.monotonic()
    first = chat(dep.url, p_long, 40, stream=True)
    cold_s = time.monotonic() - t0
    check(first["n"] >= 40 or first["finish_reason"] == "stop",
          f"streamed request ended early: {first}")
    check(first["finish_reason"] in ("length", "stop"),
          f"streamed request has no finish_reason: {first}")
    check(first["n"] > 0 and first["frames"] > 1,
          f"streamed request carried no token frames: {first}")
    # two concurrent requests, prompts that share no prefix
    with concurrent.futures.ThreadPoolExecutor(2) as ex:
        futs = [ex.submit(chat, dep.url,
                          long_prompt(chunk // 2 + 7 * i, salt), 24, False)
                for i, salt in enumerate(("bravo", "charlie"))]
        pair = [f.result() for f in futs]
    for r in pair:
        check(r["n"] > 0 and r["finish_reason"] in ("length", "stop"),
              f"concurrent request failed: {r}")
    check(pair[0]["logprobs"] != pair[1]["logprobs"],
          "two different prompts produced one token stream")
    # the seeded greedy request again, through the same programs (the
    # reusable prefix cache dropped first): the same tokens, bit for bit
    http("POST", dep.url + "/clear_kv_blocks", {})
    again = chat(dep.url, p_long, 40, stream=True)
    check(again["logprobs"] == first["logprobs"]
          and again["text"] == first["text"],
          "seeded greedy request repeated gave different tokens:\n"
          f"{first['logprobs']}\n{again['logprobs']}")
    # and once more with its prefix cached: only the tail is prefilled,
    # through another program, so agreement is to rounding, not to the bit
    cached = chat(dep.url, p_long, 40, stream=True)
    check(cached["n"] == again["n"]
          and abs(cached["logprobs"][0] - again["logprobs"][0])
          <= LOGPROB_TOL,
          f"cached-prefix repeat disagrees on the first token: "
          f"{cached['logprobs'][0]} vs {again['logprobs'][0]}")
    every = first["logprobs"] + pair[0]["logprobs"] + pair[1]["logprobs"]
    for lp in every:
        check(lp == lp and -1e4 < lp <= 0.0, f"logprob not finite: {lp}")
    return {
        "requests": 5, "tokens": first["n"] + again["n"] + cached["n"]
        + sum(r["n"] for r in pair),
        "chosen_logprob_range": [round(min(every), 3),
                                 round(max(every), 3)],
        "prompt_tokens_long": len(p_long),
        # wall-clock at the HTTP client; the engine's own first-token
        # and inter-token times are on the worker-scrape line
        "cold_first_request_s": round(cold_s, 3),
        "warm_first_token_s": round(again["ttft_s"] or -1, 4),
        "warm_request_s": round(again["wall_s"], 4),
        # (ms since the request was sent, tokens in the frame)
        "warm_token_frames": again["arrivals"],
        "cached_prefix_request_s": round(cached["wall_s"], 4),
    }


def check_worker_scrape(dep: Deployment, need_kernels: bool) -> dict:
    m = scrape(dep.sys_port)
    fallbacks = {k: v for k, v in m.items()
                 if k.startswith("dynamo_attention_fallback_total")}
    compiles = {k: v for k, v in m.items()
                if k.startswith("dynamo_compile_total")}
    info = [k for k in m if k.startswith("dynamo_engine_device_info{")]
    check(info, "worker scrape carries no dynamo_engine_device_info")
    check(f'platform="{dep.device["platform"]}"' in info[0],
          f"scrape and start-up line disagree on the device: {info}")
    check(compiles, "worker scrape shows no compiled entry: no request "
                    "reached the engine")
    if need_kernels:
        check(all(v == 0 for v in fallbacks.values()),
              f"attention kernels declined on this model: {fallbacks}")
        check(dep.device["attention_kernels"],
              "worker reports the attention kernel path is off")
    label = re.compile(r'entry="([^"]*)".*shape="([^"]*)"')

    def mean(hist: str) -> float:
        return round(m.get(hist + "_sum", 0.0)
                     / max(1.0, m.get(hist + "_count", 0.0)), 4)

    return {"engine_ttft_s_mean": mean("dynamo_engine_ttft_seconds"),
            "engine_itl_ms_mean": mean("dynamo_engine_itl_ms"),
            "attention_fallbacks": fallbacks or
            {"dynamo_attention_fallback_total": 0.0},
            "compiled": sorted(":".join(label.search(k).groups())
                               for k in compiles),
            "compile_seconds": round(sum(
                v for k, v in m.items()
                if k.startswith("dynamo_compile_seconds_total")), 2)}


def processes_off_jax(dep: Deployment) -> dict:
    held = {"parent": "jax" in sys.modules,
            "coordinator": maps_hold(dep.coordinator.p.pid, "jaxlib"),
            "frontend": maps_hold(dep.frontend.p.pid, "jaxlib")}
    check(maps_hold(dep.worker.p.pid, "jaxlib"),
          "the jaxlib probe cannot see the worker's own jaxlib")
    check(not any(held.values()), f"a chip-free process loaded jax: {held}")
    return held


def run_one_chip(args, env: dict, ckpt: str) -> dict:
    with Deployment(args, env, ckpt, tp=1, tag="") as dep:
        say(f"worker device report: {json.dumps(dep.device)}")
        say(f"worker up in {dep.load_s:.1f}s (import + load + KV cache)")
        facts = serve_requests(dep, args)
        say(f"requests answered over HTTP: {json.dumps(facts)}")
        on_tpu = dep.device["platform"] == "tpu"
        say("worker scrape: " + json.dumps(
            check_worker_scrape(dep, need_kernels=on_tpu)))
        say(f"jax loaded: {json.dumps(processes_off_jax(dep))}")
        say("kv router index: " + (
            "native radix (libradix.so)"
            if maps_hold(dep.frontend.p.pid, "libradix")
            else "python radix tree"))
        return dep.device


TP_PROMPTS = [(96, "delta"), (150, "echo"), (200, "foxtrot"),
              (260, "golf")]
TP_TOKENS = 24      # three 8-step bursts: the pipelined dispatch runs too


def tp_answers(dep: Deployment) -> list[dict]:
    return [chat(dep.url, long_prompt(n, salt), TP_TOKENS, stream=False)
            for n, salt in TP_PROMPTS]


def run_tensor_parallel(args, env: dict, ckpt: str) -> dict:
    """The sharded path and what it is compared with, nothing else: one
    worker on one chip answers seeded prompts, then a worker with
    --tensor-parallel-size N answers the same prompts. They run one
    after the other — a TPU process claims every chip of the host."""
    with Deployment(args, env, ckpt, tp=1, tag="ref-") as ref:
        say(f"one-chip worker: {json.dumps(ref.device)} "
            f"(up in {ref.load_s:.1f}s)")
        want = tp_answers(ref)
    with Deployment(args, env, ckpt, tp=args.chips, tag="tp-") as dep:
        say(f"tp={args.chips} worker: {json.dumps(dep.device)} "
            f"(up in {dep.load_s:.1f}s)")
        got = tp_answers(dep)
        m = scrape(dep.sys_port)
    # sharding: every chip holds a share, none holds (nearly) all of it
    by_dev = dep.device["bytes_by_device"]
    total = sum(by_dev.values())
    check(dep.device["count"] == args.chips and len(by_dev) == args.chips,
          f"engine arrays sit on {by_dev.keys()}, wanted {args.chips}")
    shares = {d: round(b / total, 3) for d, b in by_dev.items()}
    # the embedding table and norms replicate, so a share is somewhat
    # above 1/N; (nearly) everything on one chip is the failure
    check(max(shares.values()) < 1.6 / args.chips,
          f"shards are not spread over the chips: {shares}")
    say(f"engine bytes by device: {json.dumps(by_dev)} shares {shares}")
    hbm = {k: v for k, v in m.items()
           if k.startswith("dynamo_mesh_device_bytes{")}
    say(f"HBM bytes_in_use by device (mesh recorder): {json.dumps(hbm)}")
    if dep.device["platform"] == "tpu":
        check(len(hbm) == args.chips,
              f"mesh recorder saw {len(hbm)} devices in use: {hbm}")
        check(max(hbm.values()) < 1.6 * sum(hbm.values()) / args.chips,
              f"HBM use is lopsided across the chips: {hbm}")
    coll = {k: v for k, v in m.items()
            if k.startswith("dynamo_collective_bytes_total{")
            and 'axis="tp"' in k and 'entry="decode_burst"' in k}
    check(coll and all(v > 0 for v in coll.values()),
          "the decode step's collective manifest on the tp axis is empty")
    say(f"decode_burst collectives on tp: {json.dumps(coll)}")
    piped = m.get("dynamo_engine_pipelined_bursts_total", 0.0)
    check(piped > 0, "no decode burst was dispatched through the pipeline")
    say(f"pipelined decode bursts on the tp worker: {piped:.0f}")
    # agreement with the one-chip answers
    worst, matched = 0.0, []
    for (n, salt), a, b in zip(TP_PROMPTS, want, got):
        check(a["n"] > 0 and b["n"] > 0, f"empty answer for {salt}")
        d0 = abs(a["logprobs"][0] - b["logprobs"][0])
        worst = max(worst, d0)
        k = 0
        for x, y in zip(a["logprobs"], b["logprobs"]):
            if abs(x - y) > LOGPROB_TOL:
                break
            k += 1
        matched.append(k)
    say(f"tp vs one chip: first-token |dlogprob| max {worst:.4f} "
        f"(tolerance {LOGPROB_TOL}); tokens agreeing before the first "
        f"divergence per prompt {matched} of {TP_TOKENS}")
    check(worst <= LOGPROB_TOL,
          f"first-token logprobs disagree by {worst:.4f}")
    check(sum(matched) >= len(matched) * TP_TOKENS // 2,
          f"tp and one-chip streams part too early: {matched}")
    return dep.device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="llama3-8b",
                    help="synth_ckpt preset; `tiny` for the CPU rehearsal")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut depth only (0 = the preset's published depth)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the tensor-parallel path vs one chip")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic weights")
    args = ap.parse_args()

    device = {"platform": "none", "kind": "none", "count": 0}
    ok = False
    try:
        check(os.path.isdir(os.path.join(ROOT, "dynamo_tpu")),
              f"no dynamo_tpu package beside {__file__}")
        os.makedirs(LOG_DIR, exist_ok=True)
        env = child_env()
        wenv = worker_env(args, env)
        say(f"model {args.model} depth "
            f"{args.layers or 'as published'} quantize {QUANTIZE} "
            f"pages {NUM_PAGES} batch {MAX_BATCH} "
            f"prefill-chunk {PREFILL_CHUNK} chips {args.chips} "
            f"JAX_PLATFORMS={wenv['JAX_PLATFORMS']}")
        verdict = preflight(wenv)
        say(f"preflight: {json.dumps(verdict)}")
        seen = (verdict.get("device") or {}).get("platform")
        if args.model == "llama3-8b":
            # never write and load 16 GB for a backend that is not there
            check(verdict.get("ok") and seen == "tpu",
                  f"no TPU for the full-width model: {verdict}")
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
            os.path.join(ROOT, ".jax_cache")
        before = cache_entries(cache_dir)
        ckpt, write_s = write_checkpoint(args, env)
        with open(os.path.join(ckpt, "config.json")) as f:
            cfg = json.load(f)
        say("checkpoint %s (%.1fs): hidden %d intermediate %d layers %d "
            "heads %d kv_heads %d head_dim %d vocab %d" % (
                ckpt, write_s, cfg["hidden_size"],
                cfg["intermediate_size"], cfg["num_hidden_layers"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], cfg["vocab_size"]))
        run = run_one_chip if args.chips == 1 else run_tensor_parallel
        device = run(args, env, ckpt)
        after = cache_entries(cache_dir)
        # jax caches only what took a second or more to compile: a run
        # that adds nothing to a cache that held entries found them all
        say(f"compile cache {cache_dir}: {before} entries before, "
            f"{after} after ({after - before} written by this run)")
        check("jax" not in sys.modules, "chip_smoke.py itself imported jax")
        check(device["platform"] == "tpu",
              f"the worker's arrays sit on {device['platform']!r}, "
              "not on a TPU")
        check(device["count"] == args.chips,
              f"worker used {device['count']} devices, wanted {args.chips}")
        ok = True
    except SmokeFailure as e:
        say(f"FAILED: {e}")
    except (urllib.error.URLError, OSError, subprocess.SubprocessError,
            KeyError, ValueError) as e:
        say(f"FAILED: {type(e).__name__}: {e}")
    print(json.dumps({"ok": ok, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
