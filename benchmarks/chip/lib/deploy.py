"""coordinator -> worker -> frontend as three processes, as a user starts them.

`Proc`, `Deployment` and `scrape` are copied from `chip_smoke.py` (PR 23 ran
them on the chip); the deployment's flags come from the configuration file
instead of constants, and the worker starts through `lib/worker_launch.py`,
which runs the worker's own `main()` and adds the two things only the
process that holds the chip can do: trace it, and read its memory peak.
This parent, the coordinator and the frontend never import JAX.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

MODEL_NAME = "bench"
LOAD_TIMEOUT_S = 900.0
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))       # the checkout
WORKER_LAUNCH = os.path.join(HERE, "lib", "worker_launch.py")


class BenchFailure(Exception):
    pass


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


class Proc:
    """One child of the deployment, its output in <log_dir>/<name>.log."""

    def __init__(self, name: str, argv: list[str], env: dict,
                 log_dir: str) -> None:
        self.name = name
        self.log = os.path.join(log_dir, f"{name}.log")
        self._f = open(self.log, "w")
        self.started = time.monotonic()
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
                raise BenchFailure(
                    f"{self.name} exited rc={self.p.returncode} before "
                    f"{marker}\n--- {self.name} log tail ---\n"
                    f"{tail(self.log)}")
            time.sleep(0.1)
        raise BenchFailure(
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


def http(method: str, url: str, body=None, timeout: float = 600.0) -> str:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read().decode()


def parse_prom(text: str) -> dict[str, float]:
    """Prometheus text -> {sample with labels: value}."""
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            k, _, v = line.rpartition(" ")
            try:
                out[k] = float(v)
            except ValueError:
                pass
    return out


def scrape(port: int) -> dict[str, float]:
    return parse_prom(http("GET", f"http://127.0.0.1:{port}/metrics",
                           timeout=60))


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def worker_env(base: dict, chips: int, extra: dict) -> dict:
    """JAX falls back to the CPU when the TPU fails to open. Where the
    caller left JAX_PLATFORMS unset that must be an error; where the
    caller set it (a CPU rehearsal) it passes through and the platform
    check at the end of the run decides."""
    env = dict(base)
    env.setdefault("JAX_PLATFORMS", "tpu")
    if chips > 1 and "tpu" not in env["JAX_PLATFORMS"]:
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{chips}").strip()
    env.update(extra)
    return env


class Deployment:
    """The three processes of one run; `control` is the directory through
    which the parent talks to the worker's launcher."""

    def __init__(self, config: dict, ckpt: str, log_dir: str,
                 worker_extra_env: dict) -> None:
        self.log_dir = log_dir
        self.control = os.path.join(log_dir, "control")
        os.makedirs(self.control, exist_ok=True)
        for name in os.listdir(self.control):
            os.remove(os.path.join(self.control, name))
        self.procs: list[Proc] = []
        try:
            self._bring_up(config, ckpt, worker_extra_env)
        except BaseException:
            self.stop()     # a half-started deployment leaves nothing behind
            raise

    def _bring_up(self, config: dict, ckpt: str, extra: dict) -> None:
        dep = config["deployment"]
        tp = int(dep.get("tensor_parallel", 1))
        # DYN_* settings of the deployment, for all three processes
        env = {**child_env(), **dep.get("env", {})}
        store_port, self.sys_port = free_port(), free_port()
        http_port = free_port()
        self.url = f"http://127.0.0.1:{http_port}"
        store = f"tcp://127.0.0.1:{store_port}"
        py = [sys.executable, "-m"]
        self._start("coordinator", py + [
            "dynamo_tpu.coordinator", "--port", str(store_port)], env
        ).wait_line("COORDINATOR_READY", 60)
        wargs = [sys.executable, WORKER_LAUNCH,
                 "--model", ckpt, "--store", store,
                 "--served-model-name", MODEL_NAME,
                 "--system-port", str(self.sys_port)]
        for flag, value in dep["worker_flags"].items():
            wargs += [f"--{flag}", str(value)]
        if tp > 1:
            wargs += ["--tensor-parallel-size", str(tp)]
        wenv = worker_env(env, int(dep["chips"]), {
            **extra, "BENCH_CONTROL_DIR": self.control,
            "BENCH_SYS_PORT": str(self.sys_port)})
        self.worker = self._start("worker", wargs, wenv)
        line = self.worker.wait_line("WORKER_DEVICE", LOAD_TIMEOUT_S)
        self.device = json.loads(line.split(" ", 1)[1])
        self.worker.wait_line("WORKER_READY", 120)
        self.load_s = time.monotonic() - self.worker.started
        self.frontend = self._start("frontend", py + [
            "dynamo_tpu.frontend", "--host", "127.0.0.1", "--port",
            str(http_port), "--store", store, "--router-mode", "kv"], env)
        self.frontend.wait_line("FRONTEND_READY", 120)
        end = time.monotonic() + 60
        while MODEL_NAME not in http("GET", self.url + "/v1/models",
                                     timeout=30):
            if time.monotonic() > end:
                raise BenchFailure("frontend never listed the worker's model")
            time.sleep(0.1)

    def _start(self, name: str, argv: list[str], env: dict) -> Proc:
        p = Proc(name, argv, env, self.log_dir)
        self.procs.append(p)
        return p

    def ask_worker(self, request: str, payload: dict | None = None,
                   timeout: float = 120.0) -> dict:
        """Hand the launcher a request file (`trace`, `memory`) and wait
        for its answer, a JSON file of the same name."""
        answer = os.path.join(self.control, request + ".json")
        tmp = os.path.join(self.control, request + ".req")
        with open(tmp, "w") as f:
            json.dump(payload or {}, f)
        os.replace(tmp, os.path.join(self.control, request))
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if os.path.exists(answer):
                with open(answer) as f:
                    out = json.load(f)
                if "error" in out:
                    raise BenchFailure(f"worker {request}: {out['error']}")
                return out
            if self.worker.p.poll() is not None:
                raise BenchFailure(
                    f"worker died during {request}\n{tail(self.worker.log)}")
            time.sleep(0.05)
        raise BenchFailure(f"worker did not answer {request}")

    def stop(self) -> None:
        # frontend first, coordinator last: the reverse of start-up
        for p in reversed(self.procs):
            p.stop()

    def __enter__(self) -> "Deployment":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None and self.procs:
            print(f"--- {self.procs[-1].name} log tail ---\n"
                  f"{tail(self.procs[-1].log)}", flush=True)
        self.stop()
