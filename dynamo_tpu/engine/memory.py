"""HBM memory ledger: live device-memory flight recorder + OOM forensics.

Every other subsystem has a flight recorder — step (engine/profiler.py),
router (router/decision_log.py), KV lifecycle (kvbm/lifecycle.py) — but
HBM, the resource that actually killed bench r03 (a bare
RESOURCE_EXHAUSTED with no attribution), was invisible: the only
accounting was `hbm_cache_usage=self.pool.usage()`. This module accounts
every allocation class the engine controls and reconciles the sum
against what the device reports, so "where did HBM go" has a numeric
answer before — and especially after — an OOM.

Allocation classes:

  * ``weights`` — the post-load parameter footprint
    (`models/loader.params_footprint`, set once at engine init);
  * ``kv_pool`` — the PagePool's device KV reservation (the k/v cache
    arrays, fixed at init);
  * ``kvbm_pinned`` / ``kvbm_staged`` — pages pinned against the KVBM
    offload queue and bytes staged for onboard (live providers polled
    per snapshot, `kvbm/manager.memory_accounting`);
  * per-``(entry, shape)`` compiled-executable **workspace** observed at
    the CompileTracker dispatch sites. Honest caveat: the engine's jitted
    entry points have no public handle on their compiled executables
    (``compiled.memory_analysis()`` exists only on AOT
    ``lower().compile()`` objects), so the default attribution is the
    device `bytes_in_use` delta across a first-call dispatch, tagged
    ``source="device-delta"``; call sites that DO hold an AOT executable
    pass it and get ``memory_analysis()`` numbers
    (``source="memory_analysis"``); MockEngine passes analytic byte
    counts (``source="analytic"``) so the math is chip-free testable.

Each ``poll()`` reconciles the classes against a live
``device.memory_stats()`` read into a bounded snapshot ring. The
residual (``unattributed_bytes`` = device in-use minus everything
attributed) is always surfaced, never balanced away — a growing residual
IS the finding.

Contract (same as PRs 8–10): **off by default**. ``ledger_from_env()``
returns None unless ``DYN_MEM_LEDGER`` is truthy; every hot-path touch
is one ``if led is not None``; armed vs unarmed serving is
byte-identical (pinned by tests/test_memory_ledger.py). The
``dynamo_memory_*`` gauges (MemoryMetrics) are constructed
unconditionally with fixed names but only move when an armed ledger
polls.

Consumers: ``GET /debug/memory`` (`memory_payload`), ``python -m
dynamo_tpu.doctor memory``, the ``memory`` block in ``/fleet/status``
(runtime/telemetry.memory_summary), the ``memory`` block in bench
long/traffic records (`memory_ledger_summary`), the bench headroom gate
(`headroom_plan` — shrink the KV pool instead of burning a round the
way r03 did), and **OOM forensics**: the scheduler loop's central
exception handler calls `record_oom` on a RESOURCE_EXHAUSTED, which
dumps the last snapshot + ring + step-recorder tail + triggering
entry/shape to a crash file and (when ``DYN_OOM_EXIT`` is armed, as the
bench phases and subprocess workers do) exits rc 45 — joining 42
(engine death), 43 (canary), 44 (quarantine) in the supervisor's
`_death_cause` map.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

# a sizing helper like those below; its answer lives with the KV geometry
from dynamo_tpu.engine.pages import kv_page_bytes  # noqa: F401
from dynamo_tpu.runtime.metrics import Gauge, MetricsRegistry

logger = logging.getLogger(__name__)

# 42 = engine death, 43 = canary, 44 = quarantine (worker/quarantine.py),
# 45 = OOM with a forensic crash file on disk: the supervisor treats a
# respawn as pointless once it repeats (same footprint ⇒ same OOM).
OOM_EXIT_CODE = 45

DEFAULT_RING = 256
_TRUTHY = {"1", "true", "yes", "on"}

ENV_GATE = "DYN_MEM_LEDGER"
ENV_RING = "DYN_MEM_LEDGER_RING"
ENV_EXIT = "DYN_OOM_EXIT"
ENV_CRASH_DIR = "DYN_MEM_CRASH_DIR"

# fixed class order for rendering; unknown provider names append after
ALLOC_CLASSES = ("weights", "kv_pool", "kvbm_pinned", "kvbm_staged")

_OOM_PREFIX = "dynamo-oom-"


def _shape_label(shape) -> str:
    if isinstance(shape, (tuple, list)):
        return "x".join(str(s) for s in shape)
    return str(shape)


def is_resource_exhausted(exc) -> bool:
    """Duck-typed OOM test over an exception (or string): the
    backend surfaces XlaRuntimeError with RESOURCE_EXHAUSTED in the
    text; the seeded fault kind raises a RuntimeError carrying the same
    marker. Matches doctor/preflight.classify's oom vocabulary."""
    s = exc if isinstance(exc, str) else f"{type(exc).__name__}: {exc}"
    low = s.lower()
    return ("resource_exhausted" in low or "out of memory" in low
            or "resource exhausted" in low)


def memory_enabled(env: Optional[dict] = None) -> bool:
    e = os.environ if env is None else env
    return str(e.get(ENV_GATE, "")).strip().lower() in _TRUTHY


def device_memory_stats(device=None) -> Optional[dict]:
    """{bytes_in_use, bytes_limit, peak_bytes_in_use} from
    ``device.memory_stats()`` (a jax Device, or anything exposing the
    method — MockEngine's analytic model rides the same seam). None on
    backends without stats (CPU) — the ledger then reports the residual
    as unknown rather than fabricating a balance."""
    try:
        if device is None:
            import jax

            device = jax.devices()[0]
        stats = device.memory_stats() \
            if hasattr(device, "memory_stats") else None
    except Exception:
        return None
    if not stats:
        return None
    try:
        return {
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
        }
    except (TypeError, ValueError):
        return None


def per_device_memory_stats(devices=None) -> Optional[list[dict]]:
    """Per-device ``memory_stats()`` rows for multi-device workers —
    the device-0 view above hides exactly the imbalance a sharded
    deployment needs to see. None on single-device backends or when no
    device exposes stats (CPU), so single-chip payloads are unchanged."""
    try:
        if devices is None:
            import jax

            devices = jax.devices()
    except Exception:
        return None
    if len(devices) < 2:
        return None
    rows = []
    for d in devices:
        stats = device_memory_stats(d)
        if stats is None:
            continue
        rows.append({"device": str(getattr(d, "id", len(rows))),
                     "platform": str(getattr(d, "platform", "?")),
                     **stats})
    return rows or None


def workspace_from_executable(executable) -> Optional[int]:
    """Temp+output workspace bytes from an AOT ``compiled`` object's
    ``memory_analysis()``; None when the backend doesn't expose it."""
    try:
        ma = executable.memory_analysis()
        total = 0
        for attr in ("temp_size_in_bytes", "output_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v:
                total += int(v)
        return total or None
    except Exception:
        return None


class MemoryMetrics:
    """Always-on ``dynamo_memory_*`` gauges with fixed names
    (EngineMetrics pattern: constructed unconditionally, adopted into
    the runtime registry idempotently). They only move when an armed
    MemoryLedger polls — absent values mean "never armed", exactly like
    the other recorders' always-on counters."""

    def __init__(self) -> None:
        self.class_bytes = Gauge(
            "dynamo_memory_class_bytes",
            "HBM bytes attributed per allocation class (weights / "
            "kv_pool / kvbm_pinned / kvbm_staged / workspace); moves "
            "only while DYN_MEM_LEDGER is armed")
        self.device_bytes = Gauge(
            "dynamo_memory_device_bytes",
            "device.memory_stats() at the last ledger poll, by kind "
            "(in_use / limit / peak)")
        self.unattributed_bytes = Gauge(
            "dynamo_memory_unattributed_bytes",
            "device in-use bytes the ledger could NOT attribute to any "
            "class — the honest residual, never silently balanced")
        self.headroom_bytes = Gauge(
            "dynamo_memory_headroom_bytes",
            "device bytes_limit minus bytes_in_use at the last poll")

    def register(self, registry: MetricsRegistry, ledger=None) -> None:
        """Adopt into a runtime registry (idempotent, first engine wins
        a name). With `ledger`, every scrape triggers a fresh poll so
        /metrics and the fleet plane read current occupancy."""
        for m in (self.class_bytes, self.device_bytes,
                  self.unattributed_bytes, self.headroom_bytes):
            registry.register(m)
        if ledger is not None:
            registry.on_scrape(lambda: ledger.poll())

    def update(self, snap: dict) -> None:
        """Refresh gauges from one ledger snapshot."""
        for name, nbytes in (snap.get("classes") or {}).items():
            self.class_bytes.set(nbytes, **{"class": name})
        self.class_bytes.set(snap.get("workspace_bytes", 0),
                             **{"class": "workspace"})
        dev = snap.get("device")
        if dev:
            self.device_bytes.set(dev["bytes_in_use"], kind="in_use")
            self.device_bytes.set(dev["bytes_limit"], kind="limit")
            self.device_bytes.set(dev["peak_bytes_in_use"], kind="peak")
        if snap.get("unattributed_bytes") is not None:
            self.unattributed_bytes.set(snap["unattributed_bytes"])
        if snap.get("headroom_bytes") is not None:
            self.headroom_bytes.set(snap["headroom_bytes"])


class MemoryLedger:
    """Bounded snapshot ring reconciling attributed HBM classes against
    live device polls, plus the per-(entry, shape) workspace table and
    the current-dispatch marker OOM forensics joins on.

    Thread-safe: dispatch hooks arrive from to_thread closures and KVBM
    worker threads; one lock covers classes + workspace + ring +
    marker."""

    def __init__(self, capacity: int = DEFAULT_RING, metrics=None,
                 device=None) -> None:
        self.capacity = max(16, int(capacity))
        self._ring: deque[dict] = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._metrics = metrics
        self._device = device
        # class -> bytes (set_class) and class -> zero-arg live getter
        self._classes: dict[str, int] = {}
        self._providers: dict[str, Callable[[], int]] = {}
        self._sources: dict[str, str] = {}
        # (entry, shape-label) -> {"bytes", "source", "at"}
        self._workspace: dict[tuple, dict] = {}
        self._recorded = 0
        self._dispatches = 0
        # last dispatch marker: the entry/shape a crash file attributes
        self._current: Optional[dict] = None
        # pending first-call workspace attribution via device delta
        self._pending_ws: Optional[tuple] = None
        self._pending_base: Optional[int] = None

    # -- attribution hooks (each caller guards `if led is not None`) -------

    def set_class(self, name: str, nbytes: int, source: str = "") -> None:
        with self._lock:
            self._classes[name] = int(nbytes)
            if source:
                self._sources[name] = source

    def provider(self, name: str, fn: Callable[[], int],
                 source: str = "") -> None:
        """Register a live byte getter polled at every snapshot (KVBM
        pinned/staged — values move with the offload pipeline)."""
        with self._lock:
            self._providers[name] = fn
            if source:
                self._sources[name] = source

    def set_workspace(self, entry: str, shape, nbytes: int,
                      source: str = "analytic") -> None:
        key = (entry, _shape_label(shape))
        with self._lock:
            self._workspace[key] = {"bytes": int(nbytes),
                                    "source": source, "at": time.time()}

    def on_dispatch(self, entry: str, shape, compiled: bool = False,
                    nbytes: Optional[int] = None,
                    executable=None) -> None:
        """Hot-path hook at every CompileTracker dispatch site, called
        BEFORE the dispatch (so an OOM inside it is attributed to the
        right entry/shape). On a first-call (compiled) dispatch the
        workspace is attributed: exactly when the caller passes analytic
        `nbytes` or an AOT `executable`, else best-effort from the
        device in-use delta measured at the NEXT hook (compile events
        are rare, so the extra memory_stats read never rides the warm
        path)."""
        label = _shape_label(shape)
        dev_in_use = None
        with self._lock:
            need_dev = compiled or self._pending_ws is not None
        if need_dev and nbytes is None and executable is None:
            dev = device_memory_stats(self._device)
            dev_in_use = dev["bytes_in_use"] if dev else None
        with self._lock:
            self._dispatches += 1
            # settle the previous first-call dispatch's delta
            if self._pending_ws is not None and dev_in_use is not None \
                    and self._pending_base is not None:
                delta = max(0, dev_in_use - self._pending_base)
                prev = self._workspace.get(self._pending_ws)
                if prev is None or prev["source"] == "device-delta":
                    self._workspace[self._pending_ws] = {
                        "bytes": delta, "source": "device-delta",
                        "at": time.time()}
            self._pending_ws = None
            self._pending_base = None
            key = (entry, label)
            if compiled:
                ws = None
                if executable is not None:
                    n = workspace_from_executable(executable)
                    if n is not None:
                        ws = {"bytes": n, "source": "memory_analysis"}
                if ws is None and nbytes is not None:
                    ws = {"bytes": int(nbytes), "source": "analytic"}
                if ws is not None:
                    ws["at"] = time.time()
                    self._workspace[key] = ws
                elif dev_in_use is not None:
                    self._pending_ws = key
                    self._pending_base = dev_in_use
                elif key not in self._workspace:
                    self._workspace[key] = {"bytes": 0,
                                            "source": "unknown",
                                            "at": time.time()}
            elif nbytes is not None and key not in self._workspace:
                # analytic callers pass bytes on every dispatch; the
                # first one per key wins (shapes are deterministic)
                self._workspace[key] = {"bytes": int(nbytes),
                                        "source": "analytic",
                                        "at": time.time()}
            self._current = {"entry": entry, "shape": label,
                             "compiled": bool(compiled),
                             "at": time.time()}

    # -- views --------------------------------------------------------------

    def workspace_total(self) -> int:
        with self._lock:
            return sum(w["bytes"] for w in self._workspace.values())

    def current_dispatch(self) -> Optional[dict]:
        with self._lock:
            return dict(self._current) if self._current else None

    def poll(self) -> dict:
        """One reconciliation snapshot: classes (+ live providers) and
        workspace vs a fresh device read. The residual is explicit —
        None when the backend has no stats (unknown, not zero), the
        signed difference otherwise (negative = over-attributed)."""
        dev = device_memory_stats(self._device)
        with self._lock:
            classes = dict(self._classes)
            providers = list(self._providers.items())
            ws_total = sum(w["bytes"] for w in self._workspace.values())
        for name, fn in providers:
            try:
                classes[name] = int(fn())
            except Exception:
                classes[name] = 0
        attributed = sum(classes.values()) + ws_total
        snap: dict[str, Any] = {
            "at": time.time(),
            "classes": classes,
            "workspace_bytes": ws_total,
            "attributed_bytes": attributed,
            "device": dev,
            "unattributed_bytes":
                (dev["bytes_in_use"] - attributed) if dev else None,
            "headroom_bytes":
                (dev["bytes_limit"] - dev["bytes_in_use"]) if dev
                else None,
        }
        with self._lock:
            self._ring.append(snap)
            self._recorded += 1
        if self._metrics is not None:
            self._metrics.update(snap)
        return dict(snap)

    def snapshot(self, limit: Optional[int] = None) -> list[dict]:
        with self._lock:
            snaps = list(self._ring)
        if limit is not None and limit >= 0:
            snaps = snaps[-limit:]
        return [dict(s) for s in snaps]

    def summary(self) -> dict:
        with self._lock:
            last = dict(self._ring[-1]) if self._ring else None
            in_ring = len(self._ring)
            recorded = self._recorded
            dispatches = self._dispatches
            sources = dict(self._sources)
            shapes = [{"entry": k[0], "shape": k[1],
                       "bytes": w["bytes"], "source": w["source"]}
                      for k, w in self._workspace.items()]
            current = dict(self._current) if self._current else None
        shapes.sort(key=lambda s: -s["bytes"])
        return {
            "polls": recorded,
            "in_ring": in_ring,
            "capacity": self.capacity,
            "evicted": max(0, recorded - in_ring),
            "dispatches": dispatches,
            "last": last,
            "sources": sources,
            "workspace": {"total_bytes": sum(s["bytes"] for s in shapes),
                          "shapes": shapes},
            "current_dispatch": current,
        }

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._workspace.clear()
            self._recorded = 0
            self._dispatches = 0
            self._current = None
            self._pending_ws = None
            self._pending_base = None

    @property
    def recorded(self) -> int:
        return self._recorded

    # -- OOM forensics -------------------------------------------------------

    def crash_report(self, exc, step_recorder=None,
                     step_tail: int = 32) -> dict:
        """Everything an operator needs to attribute an OOM: the
        triggering dispatch marker, a fresh last-gasp snapshot (classes
        may still be readable even though the step failed), the snapshot
        ring, and the step-recorder tail so the memory view joins the
        step view on (entry, shape)."""
        try:
            last = self.poll()
        except Exception:
            last = None
        report = {
            "kind": "oom",
            "at": time.time(),
            "error": f"{type(exc).__name__}: {exc}"
            if not isinstance(exc, str) else exc,
            "triggering": self.current_dispatch(),
            "last_snapshot": last,
            "snapshots": self.snapshot(),
            "workspace": self.summary()["workspace"],
        }
        if step_recorder is not None:
            report["step_tail"] = step_recorder.snapshot(step_tail)
        return report


# -- construction / integration helpers -------------------------------------

def ledger_from_env(metrics=None, env: Optional[dict] = None,
                    device=None) -> Optional[MemoryLedger]:
    """None unless `DYN_MEM_LEDGER` is truthy — the off path allocates
    nothing and serving stays byte-identical. Ring size via
    `DYN_MEM_LEDGER_RING` (default 256, floor 16)."""
    if not memory_enabled(env):
        return None
    e = os.environ if env is None else env
    try:
        cap = int(e.get(ENV_RING, DEFAULT_RING))
    except (TypeError, ValueError):
        cap = DEFAULT_RING
    return MemoryLedger(capacity=cap, metrics=metrics, device=device)


def crash_dir(env: Optional[dict] = None) -> str:
    e = os.environ if env is None else env
    return e.get(ENV_CRASH_DIR) or e.get("TMPDIR") or "/tmp"


def dump_oom_report(report: dict,
                    env: Optional[dict] = None) -> Optional[str]:
    """Write the forensic crash file; returns its path (None when even
    the write fails — forensics must never mask the original OOM)."""
    path = os.path.join(
        crash_dir(env),
        f"{_OOM_PREFIX}{os.getpid()}-{int(time.time())}.json")
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(report, f, default=str)
        return path
    except Exception:
        logger.exception("memory ledger: OOM crash dump failed")
        return None


def latest_oom_report(env: Optional[dict] = None,
                      max_age_s: float = 3600.0) -> Optional[dict]:
    """Newest forensic crash file in the crash dir (bench picks this up
    for OOM-classified outage records). None when absent or stale."""
    d = crash_dir(env)
    best, best_m = None, 0.0
    try:
        for name in os.listdir(d):
            if not name.startswith(_OOM_PREFIX) \
                    or not name.endswith(".json"):
                continue
            p = os.path.join(d, name)
            m = os.path.getmtime(p)
            if m > best_m:
                best, best_m = p, m
    except OSError:
        return None
    if best is None or time.time() - best_m > max_age_s:
        return None
    try:
        with open(best, encoding="utf-8") as f:
            report = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if isinstance(report, dict):
        report.setdefault("path", best)
        return report
    return None


def maybe_exit_oom(env: Optional[dict] = None) -> bool:
    """os._exit(45) when `DYN_OOM_EXIT` is armed (bench phases and
    subprocess workers) so the supervisor's `_death_cause` reads "oom";
    in-proc/task-mode deployments leave the flag off and rely on the
    `engine._oom` marker instead (the quarantine exit_process split)."""
    e = os.environ if env is None else env
    if str(e.get(ENV_EXIT, "")).strip().lower() in _TRUTHY:
        logger.error("OOM forensics complete; exiting rc=%d",
                     OOM_EXIT_CODE)
        os._exit(OOM_EXIT_CODE)
    return False


def record_oom(engine, exc) -> Optional[str]:
    """Central OOM handler for the scheduler loops: dump the forensic
    crash file, mark the engine for the supervisor's task-mode
    `_death_cause`, and exit rc 45 when armed. Callers guard on
    `engine.memory_ledger is not None` + `is_resource_exhausted(exc)`,
    so the unarmed path stays byte-identical."""
    led = getattr(engine, "memory_ledger", None)
    if led is None:
        return None
    report = led.crash_report(
        exc, step_recorder=getattr(engine, "step_recorder", None))
    report["worker_id"] = getattr(
        getattr(engine, "config", None), "worker_id", None)
    path = dump_oom_report(report)
    try:
        engine._oom = True
    except Exception:
        pass
    logger.error("RESOURCE_EXHAUSTED in scheduler loop; forensic dump "
                 "at %s (triggering=%s)", path, report.get("triggering"))
    maybe_exit_oom()
    return path


def format_oom_attribution(report: dict) -> str:
    """One-line attribution for an OOM crash report, the way `doctor
    bench` renders outage rounds: "KV pool 78% + shape (8,4096)
    workspace" instead of a bare RESOURCE_EXHAUSTED tail."""
    parts = []
    snap = report.get("last_snapshot") or {}
    classes = snap.get("classes") or {}
    dev = snap.get("device") or {}
    limit = dev.get("bytes_limit") or 0
    kv = classes.get("kv_pool")
    if kv and limit:
        parts.append(f"KV pool {100.0 * kv / limit:.0f}%")
    elif kv:
        parts.append(f"KV pool {kv / 2 ** 20:.0f}MiB")
    trig = report.get("triggering") or {}
    if trig.get("shape"):
        shape = "(" + trig["shape"].replace("x", ",") + ")"
        parts.append(f"shape {shape} workspace")
    una = snap.get("unattributed_bytes")
    if una is not None and limit and una > 0.05 * limit:
        parts.append(f"unattributed {una / 2 ** 20:.0f}MiB")
    if not parts:
        return (report.get("error") or "RESOURCE_EXHAUSTED")[:120]
    return " + ".join(parts)


# -- surfaces ----------------------------------------------------------------

def memory_payload(engine, limit: Optional[int] = None) -> dict:
    """The `GET /debug/memory` body for one engine: enabled flag,
    summary, snapshot ring. Safe on engines without a ledger."""
    led = getattr(engine, "memory_ledger", None)
    wid = getattr(getattr(engine, "config", None), "worker_id", None)
    if led is None:
        return {"enabled": False, "worker_id": wid,
                "hint": "set DYN_MEM_LEDGER=1 to arm the memory ledger"}
    led.poll()
    out = {"enabled": True, "worker_id": wid,
           "summary": led.summary(),
           "snapshots": led.snapshot(limit),
           "oom": bool(getattr(engine, "_oom", False))}
    devices = per_device_memory_stats()
    if devices is not None:
        out["devices"] = devices
    return out


def memory_ledger_summary(engine) -> Optional[dict]:
    """Compact `memory` block for BENCH_*.json records: per-class bytes,
    device occupancy, residual. None when the ledger is off, so bench
    payloads stay unchanged by default."""
    led = getattr(engine, "memory_ledger", None)
    if led is None:
        return None
    snap = led.poll()
    out: dict[str, Any] = {
        "classes": snap["classes"],
        "workspace_bytes": snap["workspace_bytes"],
        "attributed_bytes": snap["attributed_bytes"],
        "polls": led.recorded,
    }
    if snap["device"]:
        out["device"] = snap["device"]
        out["unattributed_bytes"] = snap["unattributed_bytes"]
        out["headroom_bytes"] = snap["headroom_bytes"]
    return out


# -- bench headroom gate ------------------------------------------------------

def predict_weights_bytes(cfg, quantize=False) -> int:
    """Pre-load parameter footprint from a model config: embeddings +
    per-layer attention/MLP dense stacks + norms (+ lm_head when untied
    — assumed present, the conservative direction). int8/int4 weights
    shrink the per-element cost; norms/embeddings stay bf16."""
    h = cfg.hidden_size
    inter = cfg.intermediate_size
    kv = cfg.num_kv_heads * cfg.head_dim
    q = cfg.num_heads * cfg.head_dim
    per_layer = h * q + 2 * h * kv + q * h       # wq wk wv wo
    experts = int(getattr(cfg, "num_experts", 0) or 0)
    ffn = 3 * h * inter
    if experts:
        per_layer += h * experts + experts * ffn  # router + expert stacks
    else:
        per_layer += ffn
    if quantize:
        from dynamo_tpu.engine.quant import _bits_of

        w_item = _bits_of(quantize) / 8.0
    else:
        w_item = 2
    body = cfg.num_layers * per_layer * w_item
    embed = 2 * cfg.vocab_size * h * 2           # embed + lm_head, bf16
    norms = (2 * cfg.num_layers + 1) * h * 2
    return int(body + embed + norms)


def predict_workspace_bytes(cfg, max_batch: int,
                            max_tokens: int) -> int:
    """Max-bucket compiled-workspace estimate for the headroom gate:
    the dominant first-dispatch transients are the logits block
    (width × vocab, fp32) and a few hidden/intermediate activation
    tensors at the widest bucketed shape. Deliberately rough — the gate
    carries a margin and records its inputs, so being honest about
    magnitude beats false precision."""
    width = max(max_batch, max_tokens)
    logits = width * cfg.vocab_size * 4
    acts = width * (2 * cfg.hidden_size + cfg.intermediate_size) * 4
    return int(logits + acts)


def headroom_plan(capacity_bytes: int, weights_bytes: int,
                  kv_pool_bytes: int, workspace_bytes: int,
                  page_bytes: int, num_pages: int,
                  margin_pct: float = 5.0) -> dict:
    """The bench preflight decision: predicted peak (weights + KV pool
    + max-bucket workspace) vs device capacity less a margin. When it
    doesn't fit, the plan names the largest KV pool that does — bench
    shrinks the pool with a recorded warning instead of burning the
    round the way r03 did (`fits=False` + `num_pages_target`)."""
    budget = int(capacity_bytes * (1.0 - margin_pct / 100.0))
    predicted = int(weights_bytes + kv_pool_bytes + workspace_bytes)
    plan: dict[str, Any] = {
        "capacity_bytes": int(capacity_bytes),
        "margin_pct": margin_pct,
        "budget_bytes": budget,
        "weights_bytes": int(weights_bytes),
        "kv_pool_bytes": int(kv_pool_bytes),
        "workspace_bytes": int(workspace_bytes),
        "predicted_peak_bytes": predicted,
        "num_pages": int(num_pages),
        "fits": predicted <= budget,
    }
    if not plan["fits"] and page_bytes > 0:
        kv_budget = max(0, budget - weights_bytes - workspace_bytes)
        target = max(8, kv_budget // page_bytes)
        plan["num_pages_target"] = int(min(target, num_pages))
        plan["shrink_pct"] = round(
            100.0 * (num_pages - plan["num_pages_target"]) / num_pages, 1)
    return plan
