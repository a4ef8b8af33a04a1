"""Dispatch watchdog: detect a wedged engine dispatch and heal the fleet.

The failure mode it exists for: a jitted device call that never
returns. The engine loop blocks, the lease keeps refreshing
(the keepalive task still runs), routers keep sending traffic, and
every stream wedges until a client-side idle timeout fires — if one is
configured. This module is the server-side answer: a monitor THREAD
(deliberately not an asyncio task — a dispatch wedged in a synchronous
device call can block the event loop itself) that samples

  * the step recorder's last-dispatch end (`StepRecorder.last_dispatch_pc`,
    PR 8) when a recorder is armed,
  * the engine's scheduler forward-progress token (`progress_token()`),
  * queue depth (`_running` / `_waiting` non-empty = work pending),

and declares a wedge when work has been pending for more than
``DYN_WATCHDOG_STALL_S`` seconds with no dispatch end and no progress.
On trip it classifies the stall with `doctor/preflight.py classify()`
(optionally running the real child-process device preflight when
``DYN_WATCHDOG_PREFLIGHT`` is truthy — off by default so chaos tests
stay chip-free), publishes a `watchdog_events` event-plane message,
bumps ``dynamo_watchdog_trips_total{cause}``, and hands the worker to
the quarantine path (worker/quarantine.py) via `on_trip`.

Off-by-default contract (same as the flight recorders): with
``DYN_WATCHDOG_STALL_S`` unset or 0, `watchdog_from_env` returns None —
no thread, no sampling, byte-identical behavior.

If the event loop itself is wedged, the trip handler scheduled onto it
can never run — so the monitor thread keeps a hard-exit fallback: if
quarantine has not completed within another stall window, it calls
``os._exit(QUARANTINE_EXIT_CODE)`` directly. The lease stops refreshing,
the instance vanishes from every router's watch, and the supervisor
respawns it. Dead-fast beats wedged-forever.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Callable, Optional

logger = logging.getLogger(__name__)

ENV_STALL = "DYN_WATCHDOG_STALL_S"
ENV_PREFLIGHT = "DYN_WATCHDOG_PREFLIGHT"
WATCHDOG_EVENTS_SUBJECT = "watchdog_events"

_TRUTHY = {"1", "true", "yes", "on"}


class DispatchWatchdog:
    """Monitor thread over one engine; trips once, then stands down."""

    def __init__(self, engine, stall_s: float, *,
                 runtime=None,
                 instance: str = "",
                 on_trip: Optional[Callable[[dict], None]] = None,
                 poll_interval: Optional[float] = None,
                 run_preflight: bool = False,
                 hard_exit: bool = False) -> None:
        self.engine = engine
        self.stall_s = float(stall_s)
        self.runtime = runtime
        self.instance = instance
        # called on the event loop after the trip is published; the
        # worker wires quarantine here (task mode: flag + deregister;
        # subprocess mode: exit with the quarantine rc)
        self.on_trip = on_trip
        self.poll_interval = (poll_interval if poll_interval is not None
                              else max(0.05, self.stall_s / 4.0))
        self.run_preflight = run_preflight
        # subprocess workers arm the hard-exit fallback: if the loop is
        # too wedged to run on_trip, exit anyway so the lease drops
        self.hard_exit = hard_exit
        self.tripped: Optional[dict] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._loop = None
        # acknowledged by the quarantine path; gates the hard-exit
        self.quarantined = threading.Event()
        self._counter = None
        if runtime is not None and getattr(runtime, "metrics", None) \
                is not None:
            self._counter = runtime.metrics.counter(
                "watchdog_trips_total",
                "dispatch-watchdog wedge declarations by diagnosed cause")

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "DispatchWatchdog":
        import asyncio

        try:
            self._loop = asyncio.get_running_loop()
        except RuntimeError:
            self._loop = None
        self._thread = threading.Thread(
            target=self._run, name="dispatch-watchdog", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    # -- sampling ------------------------------------------------------------

    def _work_pending(self) -> int:
        running = getattr(self.engine, "_running", None) or ()
        waiting = getattr(self.engine, "_waiting", None) or ()
        return len(running) + len(waiting)

    def _last_activity_pc(self, armed_at: float) -> float:
        """Most recent evidence of forward progress, as a perf_counter.

        Prefers the step recorder's last-dispatch end (exact); always
        folds in the progress-token edge the thread itself observed, so
        the watchdog works on engines with no recorder armed."""
        last = armed_at
        rec = getattr(self.engine, "step_recorder", None)
        if rec is not None:
            try:
                pc = rec.last_dispatch_pc()
                if pc > last:
                    last = pc
            except Exception:
                pass
        return max(last, self._progress_pc)

    def _run(self) -> None:
        armed_at = time.perf_counter()
        self._progress_pc = armed_at
        last_token = None
        while not self._stop.wait(self.poll_interval):
            now = time.perf_counter()
            token_fn = getattr(self.engine, "progress_token", None)
            if token_fn is not None:
                try:
                    token = token_fn()
                except Exception:
                    token = None
                if token != last_token:
                    last_token = token
                    self._progress_pc = now
            pending = self._work_pending()
            if pending == 0:
                # idle engines don't dispatch; don't let silence accrue
                self._progress_pc = now
                continue
            stalled = now - self._last_activity_pc(armed_at)
            if stalled < self.stall_s:
                continue
            self._trip(stalled, pending)
            return

    # -- trip ----------------------------------------------------------------

    def _trip(self, stalled_s: float, pending: int) -> None:
        from dynamo_tpu.doctor.preflight import classify, device_preflight

        detail = (f"dispatch watchdog: no dispatch end or scheduler "
                  f"progress for {stalled_s:.2f}s with {pending} "
                  f"request(s) pending (stall threshold "
                  f"{self.stall_s:g}s)")
        if self.run_preflight:
            # the real child-process probe: expensive and device-touching,
            # so only when explicitly armed (bench hosts, not tests)
            verdict = device_preflight(attempts=1, timeout_s=self.stall_s
                                       * 4 + 30.0)
            if verdict is not None:
                detail = verdict
        diag = classify(detail)
        event = {
            "instance": self.instance,
            "cause": diag["kind"],
            "detail": diag["detail"],
            "stalled_s": round(stalled_s, 3),
            "pending": pending,
            "at": time.time(),
        }
        self.tripped = event
        logger.error("watchdog TRIP (%s): %s", diag["kind"], detail)
        if self._counter is not None:
            try:
                self._counter.inc(cause=diag["kind"])
            except Exception:
                pass
        if self._loop is not None and not self._loop.is_closed():
            self._loop.call_soon_threadsafe(self._trip_on_loop, event)
        else:
            self._trip_on_loop(event)
        if self.hard_exit:
            # the loop may be the thing that's wedged: give quarantine
            # one more stall window, then force the lease to drop
            if not self.quarantined.wait(max(self.stall_s, 1.0) + 5.0):
                from dynamo_tpu.worker.quarantine import QUARANTINE_EXIT_CODE

                logger.error(
                    "watchdog: quarantine did not complete (event loop "
                    "wedged too?); hard-exiting with rc %d so the lease "
                    "drops", QUARANTINE_EXIT_CODE)
                os._exit(QUARANTINE_EXIT_CODE)

    def _trip_on_loop(self, event: dict) -> None:
        """Runs on the event loop: publish the event, then quarantine."""
        rt = self.runtime
        if rt is not None and getattr(rt, "events", None) is not None:
            bus = rt.events
            try:
                if hasattr(bus, "publish_nowait"):
                    bus.publish_nowait(WATCHDOG_EVENTS_SUBJECT, event)
                else:
                    import asyncio

                    asyncio.get_running_loop().create_task(
                        bus.publish(WATCHDOG_EVENTS_SUBJECT, event))
            except Exception:
                logger.exception("watchdog event publish failed")
        if self.on_trip is not None:
            try:
                self.on_trip(event)
            except Exception:
                logger.exception("watchdog on_trip handler failed")


def watchdog_from_env(engine, *, runtime=None, instance: str = "",
                      on_trip: Optional[Callable[[dict], None]] = None,
                      hard_exit: bool = False
                      ) -> Optional[DispatchWatchdog]:
    """None unless DYN_WATCHDOG_STALL_S is a positive float — the same
    off-by-default contract as the flight recorders: unarmed means no
    thread, no per-iteration cost, byte-identical behavior."""
    raw = os.environ.get(ENV_STALL, "")
    try:
        stall_s = float(raw) if raw else 0.0
    except ValueError:
        logger.warning("ignoring non-numeric %s=%r", ENV_STALL, raw)
        return None
    if stall_s <= 0:
        return None
    preflight = os.environ.get(ENV_PREFLIGHT, "").lower() in _TRUTHY
    return DispatchWatchdog(engine, stall_s, runtime=runtime,
                            instance=instance, on_trip=on_trip,
                            run_preflight=preflight, hard_exit=hard_exit)
