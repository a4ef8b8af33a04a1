"""Shared CLI plumbing for the `python -m dynamo_tpu.*` components.

Reference: every L4 component is a `python -m dynamo.<comp>` argparse CLI
(`components/src/dynamo/frontend/main.py:4-16`, `vllm/main.py`); flags
layer over `RuntimeConfig` env (`DYN_*`) the way figment does in
`lib/runtime/src/config.rs:214-226`.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import signal
from typing import Optional

from dynamo_tpu.runtime.config import RuntimeConfig


def add_runtime_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("--store", default=None,
                   help="control-plane store url: memory | tcp://host:port "
                        "(default: DYN_STORE_URL env or memory)")
    p.add_argument("--namespace", default="dynamo")
    p.add_argument("--system-port", type=int, default=None,
                   help="system status server port (health/metrics)")
    p.add_argument("--lease-ttl", type=float, default=None)
    p.add_argument("--health-check", action="store_true",
                   help="enable canary health probes on served endpoints")
    p.add_argument("--health-check-interval", type=float, default=None,
                   help="idle seconds before a canary probe fires")
    p.add_argument("--health-check-timeout", type=float, default=None)
    p.add_argument("--request-deadline", type=float, default=None,
                   help="overall per-request wall clock, seconds "
                        "(0 = unbounded; stalled requests migrate)")
    p.add_argument("--stream-idle-timeout", type=float, default=None,
                   help="max silence between response frames before the "
                        "stream is declared dead and migrated")
    p.add_argument("--stream-idle-adaptive-margin", type=float,
                   default=None,
                   help="derive the idle timeout from observed "
                        "inter-token gaps (p99.9 x this margin) once "
                        "enough samples exist; the static timeout stays "
                        "the floor (0 = off; "
                        "DYN_STREAM_IDLE_ADAPTIVE_MARGIN)")
    p.add_argument("--faults", default=None, metavar="SPEC",
                   help="deterministic fault-injection spec "
                        "(runtime/faults.py grammar); exported as "
                        "DYN_FAULTS so every injector in the process — "
                        "transport, engine, KVBM offload worker — "
                        "picks it up")
    p.add_argument("--faults-seed", type=int, default=None,
                   help="seed for probabilistic fault rules "
                        "(DYN_FAULTS_SEED; default 0)")
    p.add_argument("--telemetry-interval", type=float, default=None,
                   help="seconds between MetricsSnapshot publishes on "
                        "the telemetry event subject (0 = off; "
                        "DYN_TELEMETRY_INTERVAL)")
    p.add_argument("--log-level", default="info",
                   choices=["debug", "info", "warning", "error"])


def runtime_config_from_args(args: argparse.Namespace) -> RuntimeConfig:
    cfg = RuntimeConfig.from_env()
    if args.store is not None:
        cfg.store_url = args.store
    if getattr(args, "system_port", None) is not None:
        cfg.system_port = args.system_port
    if getattr(args, "lease_ttl", None) is not None:
        cfg.lease_ttl = args.lease_ttl
    if getattr(args, "health_check", False):
        cfg.health_check_enabled = True
    if getattr(args, "health_check_interval", None) is not None:
        cfg.health_check_interval = args.health_check_interval
    if getattr(args, "health_check_timeout", None) is not None:
        cfg.health_check_timeout = args.health_check_timeout
    if getattr(args, "request_deadline", None) is not None:
        cfg.request_deadline = args.request_deadline
    if getattr(args, "stream_idle_timeout", None) is not None:
        cfg.stream_idle_timeout = args.stream_idle_timeout
    if getattr(args, "stream_idle_adaptive_margin", None) is not None:
        cfg.stream_idle_adaptive_margin = args.stream_idle_adaptive_margin
    if getattr(args, "telemetry_interval", None) is not None:
        cfg.telemetry_interval = args.telemetry_interval
    for slo_flag in ("slo_ttft", "slo_itl", "slo_target_ratio",
                     "slo_fast_window", "slo_slow_window",
                     "slo_fast_burn", "slo_slow_burn",
                     "slo_check_interval"):
        v = getattr(args, slo_flag, None)
        if v is not None:
            setattr(cfg, slo_flag, v)
    if getattr(args, "faults", None) is not None:
        # publish via env, not config: FaultInjector.from_env() is read
        # independently by the transport layer AND the KVBM manager, and
        # child components must inherit the spec for cluster game days
        import os

        from dynamo_tpu.runtime.faults import ENV_SEED, ENV_SPEC

        os.environ[ENV_SPEC] = args.faults
        if getattr(args, "faults_seed", None) is not None:
            os.environ[ENV_SEED] = str(args.faults_seed)
    return cfg


def setup_logging(level: str) -> None:
    from dynamo_tpu.runtime.logging_util import init_logging

    init_logging(level.upper())


def enable_compile_cache() -> str:
    """Persistent XLA compile cache, for every entry that builds a real
    engine; returns the directory. Placed from outside: where
    JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and nothing is
    set in code. Otherwise a fixed directory inside the checkout — the
    path is part of the cache key, so it never carries a temp name, pid
    or timestamp. A cache that cannot be set up raises: a restarted
    worker that silently recompiles every serving shape is not the
    deployment that was asked for."""
    import os

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def run_until_signal(main_coro_factory, *, shutdown=None) -> None:
    """asyncio.run a service until SIGINT/SIGTERM.

    `main_coro_factory()` must return (started) objects with an optional
    async `stop()`/`close()`; `shutdown(objs)` overrides teardown.
    """

    async def runner():
        stop_event = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop_event.set)
        objs = await main_coro_factory()
        try:
            await stop_event.wait()
        finally:
            logging.getLogger(__name__).info("shutting down")
            if shutdown is not None:
                await shutdown(objs)

    asyncio.run(runner())
