"""DistributedRuntime: the per-process cluster handle.

Reference: `lib/runtime/src/distributed.rs:43-191` — holds the etcd client
(here: store), NATS client (here: transport server/client), component
registry, metrics registries, and the system status server. Static mode
(`distributed.rs:48-56`): store_url="memory" runs everything in-process with
no coordinator, the analog of the reference's MemoryStore static mode.
"""

from __future__ import annotations

import asyncio
import logging
import socket
import time
from typing import Optional

from dynamo_tpu.runtime.component import Namespace
from dynamo_tpu.runtime.config import RuntimeConfig
from dynamo_tpu.runtime.engine import AsyncEngine
from dynamo_tpu.runtime.metrics import MetricsRegistry
from dynamo_tpu.runtime.store import KeyValueStore, MemoryStore, connect_store
from dynamo_tpu.runtime.transport import TransportClient, TransportServer

logger = logging.getLogger(__name__)

# Event-plane subject for circuit-breaker state changes: frontends
# subscribe and count opens so they can shed load *before* dialing a
# worker the breaker already knows is dead (ROADMAP robustness item).
BREAKER_EVENTS_SUBJECT = "breaker_events"


class DistributedRuntime:
    def __init__(self, config: RuntimeConfig, store: KeyValueStore,
                 transport_server: TransportServer, lease_id: int) -> None:
        self.config = config
        self.store = store
        self.transport_server = transport_server
        self.transport_client = TransportClient(
            idle_timeout=config.stream_idle_timeout,
            deadline=config.request_deadline,
            connect_retries=config.connect_retries,
            connect_backoff_base=config.connect_backoff_base,
            connect_backoff_max=config.connect_backoff_max,
            connect_neg_cache=config.connect_neg_cache,
            idle_timeout_provider=(
                self._adaptive_idle_timeout
                if config.stream_idle_adaptive_margin > 0 else None))
        # process-wide per-instance circuit breaker: every PushRouter in
        # this process shares it, so one router's failures steer them all
        from dynamo_tpu.runtime.breaker import CircuitBreaker

        self.breaker = CircuitBreaker(config.breaker_fail_limit,
                                      config.breaker_cooldown)
        self.lease_id = lease_id
        # Event plane: the StoreClient exposes pub/sub over its connection;
        # in static (memory) mode a LocalEventBus serves the process.
        from dynamo_tpu.runtime.events import EventBus, LocalEventBus

        self.events: EventBus = (
            store if isinstance(store, EventBus) else LocalEventBus()
        )
        self.breaker.on_transition = self._on_breaker_transition
        self.metrics = MetricsRegistry("dynamo")
        # the process tracer's export/drop counters render on /metrics
        # like everything else (they are plain registry Counters)
        from dynamo_tpu.runtime.tracing import tracer

        tracer().register_metrics(self.metrics)
        # the request stage family (runtime/stages.py): the transport
        # server observes the worker's leg, the HTTP service the frontend's
        from dynamo_tpu.runtime.stages import StageMetrics

        self.stage_metrics = StageMetrics(self.metrics)
        transport_server.stage_metrics = self.stage_metrics
        # surface retry/timeout/breaker counters on both observability
        # planes: the `_sys.stats` scrape and the Prometheus registry
        transport_server.extra_stats = self._robustness_stats
        self._wire_robustness_metrics()
        # KVBM pipeline counters ride the same two planes once a worker
        # calls wire_kvbm(manager)
        self._kvbm_manager = None
        self._local_engines: dict[str, AsyncEngine] = {}
        self._shutdown = asyncio.Event()
        self._status_server = None
        self.health = None  # HealthCheckManager when enabled
        # async callables replayed after a coordinator restart: the new
        # store is empty, so every lease-attached key must be re-put
        # (instance registrations, model cards, adverts)
        self._reregisters: list = []
        if hasattr(store, "on_reconnect"):
            store.on_reconnect.append(self._on_store_reconnect)
        # control-plane degradation: monotonic timestamp of the first
        # store error of the current outage, or None when healthy.
        # Routers keep serving from their last-known-instances snapshot
        # (stale-while-revalidate); these just make the staleness visible.
        self._store_degraded_since: Optional[float] = None
        self._store_degraded_where = ""

    def note_store_error(self, where: str = "") -> None:
        """Record that a control-plane store op failed. First error of an
        outage logs once; repeats only extend the staleness clock."""
        if self._store_degraded_since is None:
            self._store_degraded_since = time.monotonic()
            self._store_degraded_where = where
            logger.warning(
                "control plane DEGRADED (store unreachable at %s): "
                "serving from last-known instance snapshot", where or "?")

    def note_store_ok(self) -> None:
        if self._store_degraded_since is not None:
            stale = time.monotonic() - self._store_degraded_since
            self._store_degraded_since = None
            self._store_degraded_where = ""
            logger.warning(
                "control plane RECOVERED after %.1fs of staleness", stale)

    def store_staleness_s(self) -> float:
        """Seconds the instance snapshot has been unrefreshable; 0 when
        the store is healthy."""
        if self._store_degraded_since is None:
            return 0.0
        return time.monotonic() - self._store_degraded_since

    def _on_breaker_transition(self, key: str, old: str,
                               new: str) -> None:
        """Publish one breaker state change on the event plane. Runs
        synchronously inside the request path (record_failure /
        record_success), so it must never block or raise: local buses
        take publish_nowait; remote buses get a fire-and-forget task."""
        payload = {"instance": key, "from": old, "to": new,
                   "at": time.time()}
        bus = self.events
        try:
            if hasattr(bus, "publish_nowait"):
                bus.publish_nowait(BREAKER_EVENTS_SUBJECT, payload)
            else:
                asyncio.get_running_loop().create_task(
                    bus.publish(BREAKER_EVENTS_SUBJECT, payload))
        except Exception:
            logger.exception("breaker event publish failed")

    # minimum inter-token-gap samples before the adaptive idle timeout
    # engages — below this the percentile is noise and the hand-set
    # static value (or "wait forever") stays in force
    ADAPTIVE_IDLE_MIN_SAMPLES = 100

    def _adaptive_idle_timeout(self) -> float:
        """Derive the per-stream idle timeout from this process's
        observed inter-token gaps (docs/robustness.md): p99.9 of the ITL
        histogram × stream_idle_adaptive_margin. Prefers the engine's
        histogram (the model actually served here); falls back to the
        frontend's HTTP inter-token histogram. Returns 0.0 (defer to the
        static knob) until enough samples exist."""
        margin = self.config.stream_idle_adaptive_margin
        if margin <= 0:
            return 0.0
        metrics = self.metrics._root._metrics
        # (name, multiplier into seconds) — engine ITL is milliseconds
        from dynamo_tpu.engine.metrics import ITL_HISTOGRAM

        for name, scale in ((ITL_HISTOGRAM, 1e-3),
                            ("dynamo_http_inter_token_latency_seconds",
                             1.0)):
            h = metrics.get(name)
            if h is None or getattr(h, "count", 0) \
                    < self.ADAPTIVE_IDLE_MIN_SAMPLES:
                continue
            gap = h.quantile(0.999) * scale
            if gap > 0 and gap != float("inf"):
                return gap * margin
        return 0.0

    def _robustness_stats(self) -> dict:
        """Process-level failure-handling counters, merged into the
        `_sys.stats` scrape (service_stats.py picks them up per address)."""
        out = {"transport": dict(self.transport_client.stats),
               "breaker": self.breaker.snapshot(),
               "store": {
                   "degraded": self._store_degraded_since is not None,
                   "staleness_s": round(self.store_staleness_s(), 3),
               }}
        if self._kvbm_manager is not None:
            out["kvbm"] = self._kvbm_manager.pipeline_stats()
        return out

    def _wire_robustness_metrics(self) -> None:
        events = self.metrics.gauge(
            "transport_client_events",
            "client-side transport events (retries, timeouts) by kind")
        transitions = self.metrics.gauge(
            "breaker_transitions",
            "circuit breaker state transitions by target state")
        open_g = self.metrics.gauge(
            "breaker_open_instances",
            "instances currently filtered from routing (open/half-open)")
        degraded = self.metrics.gauge(
            "store_degraded",
            "1 while the control-plane store is unreachable and routing "
            "serves from the last-known instance snapshot")
        staleness = self.metrics.gauge(
            "store_staleness_seconds",
            "seconds since the instance snapshot could last be refreshed "
            "(0 when the store is healthy)")
        degraded.set(0)
        staleness.set(0)

        def sync() -> None:
            for kind, v in self.transport_client.stats.items():
                events.set(v, kind=kind)
            for state, n in self.breaker.transitions.items():
                transitions.set(n, state=state)
            open_g.set(self.breaker.open_count())
            stale = self.store_staleness_s()
            degraded.set(1 if self._store_degraded_since is not None else 0)
            staleness.set(stale)

        self.metrics.on_scrape(sync)

    def wire_kvbm(self, manager) -> None:
        """Export a KvbmManager's pipeline counters (docs/kvbm.md) on the
        `_sys.stats` scrape and the Prometheus registry — the same two
        planes as the robustness counters above."""
        self._kvbm_manager = manager
        g = self.metrics.gauge(
            "kvbm_pipeline",
            "KVBM offload/onboard pipeline counters by kind (blocks "
            "unless the kind is suffixed _bytes/_ms/_pages)")

        def sync() -> None:
            for kind, v in manager.pipeline_stats().items():
                g.set(v, kind=kind)

        self.metrics.on_scrape(sync)

    def replay_on_reconnect(self, fn) -> None:
        """Register an async callable that re-publishes one
        lease-attached key after a coordinator restart. Called AFTER
        the runtime's lease has been re-created (self.lease_id is fresh
        when fn runs)."""
        self._reregisters.append(fn)

    def drop_replay(self, fn) -> None:
        try:
            self._reregisters.remove(fn)
        except ValueError:
            pass

    async def _on_store_reconnect(self) -> None:
        self.lease_id = await self.store.create_lease(
            self.config.lease_ttl)
        for fn in list(self._reregisters):
            try:
                await fn()
            except Exception:
                import logging

                logging.getLogger(__name__).warning(
                    "re-registration failed after coordinator restart",
                    exc_info=True)

    # -- construction ------------------------------------------------------

    @classmethod
    async def create(cls, config: Optional[RuntimeConfig] = None
                     ) -> "DistributedRuntime":
        config = config or RuntimeConfig.from_env()
        store = await connect_store(config.store_url)
        server = TransportServer(config.listen_host, config.listen_port)
        await server.start()
        if config.advertise_host:
            server.host = config.advertise_host
        lease_id = await store.create_lease(config.lease_ttl)
        rt = cls(config, store, server, lease_id)
        if config.system_port is not None:
            from dynamo_tpu.runtime.status import SystemStatusServer

            rt._status_server = SystemStatusServer(rt, config.system_host,
                                                   config.system_port)
            await rt._status_server.start()
        if config.health_check_enabled:
            from dynamo_tpu.runtime.health_check import (
                HealthCheckConfig,
                HealthCheckManager,
            )

            rt.health = HealthCheckManager(rt, HealthCheckConfig(
                canary_wait=config.health_check_interval,
                request_timeout=config.health_check_timeout))
        logger.info("runtime up: transport=%s store=%s",
                    server.address, config.store_url)
        return rt

    # -- component model ---------------------------------------------------

    def namespace(self, name: str) -> Namespace:
        return Namespace(self, name)

    @property
    def transport_address(self) -> str:
        return self.transport_server.address

    # -- local engine registry (in-proc fast path) -------------------------

    def register_local(self, subject: str, engine: AsyncEngine) -> None:
        self._local_engines[subject] = engine

    def unregister_local(self, subject: str) -> None:
        self._local_engines.pop(subject, None)

    def local_engine(self, subject: str) -> Optional[AsyncEngine]:
        return self._local_engines.get(subject)

    # -- lifecycle ---------------------------------------------------------

    def shutdown(self) -> None:
        self._shutdown.set()

    async def wait_shutdown(self) -> None:
        await self._shutdown.wait()

    async def close(self) -> None:
        self.shutdown()
        if self.health is not None:
            await self.health.close()
        if self._status_server is not None:
            await self._status_server.stop()
        try:
            await self.store.revoke_lease(self.lease_id)
        except Exception:
            pass
        await self.transport_client.close()
        await self.transport_server.stop()
        await self.store.close()


def free_port(host: str = "127.0.0.1") -> int:
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]
