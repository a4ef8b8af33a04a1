"""Step flight recorder: per-dispatch goodput/padding attribution.

The jitted step loop is the one part of the engine PRs 4-5 left opaque:
traces explain *requests* and EngineMetrics explains *aggregates*, but
nothing records what each individual dispatch did — how many lanes were
real vs padded, how long the host sat between dispatches, which bucket
shape the work rode in. bench r02 runs at 0.80x of the bare device loop
and we attribute the gap to "dispatch + padding" on faith; this module
turns that into numbers.

A bounded ring-buffer **StepRecorder** sits next to CompileTracker at
every jitted dispatch site (the same 11 entries CompileTracker labels).
Each record carries:

  * `entry` / `shape` — the CompileTracker key for the dispatch;
  * `host_s` — host wall time of the dispatch closure. When
    `synced=True` the closure ended with an `np.asarray` round-trip, so
    this IS the honest device step time (`block_until_ready()` lies for pallas outputs inside fori_loops;
    only np.asarray round-trips are trustworthy). Pipelined decode
    bursts dispatch without syncing — those record `synced=False`
    (dispatch-only time) and the later `_pipeline_consume` np.asarray
    wait records as a separate `burst_sync` entry;
  * `good_tokens` vs `work_tokens` — real token-positions vs
    device token-positions including padding; `work - good` is the
    padded-token waste the ragged-attention work must recover;
  * `gap_s` — host time between the previous record's end and this
    dispatch's start (negative gaps from overlapping threads clamp
    to 0): the dispatch-overhead share of wall time;
  * `lanes`/`width`, `tokens` emitted, and the CompileTracker
    `compiled` flag so compile stalls are visible inline.

The recorder is **off by default** (`DYN_STEP_PROFILE=0`):
`recorder_from_env()` returns None, the engine stores None, and every
hot-loop touch is a single `if rec is not None` — zero allocation, a
byte-identical step loop. When on, each `record()` also feeds the
EngineMetrics counters (`dynamo_engine_goodput_tokens_total{entry}`,
`dynamo_engine_padded_tokens_total{entry}`) and the
`dynamo_engine_dispatch_gap_seconds` histogram, so /metrics,
`_sys.stats`, the fleet plane, and bench all read the same attribution.

Consumers: `GET /debug/profile` (ring snapshot + summary as JSON;
`?capture_s=N` arms a windowed `jax.profiler.trace()`, on the frontend
for in-process engines and on the worker's system port for the process
that holds the chip) and `python -m dynamo_tpu.doctor profile`.

**Host spans.** The armed recorder also hands out `span(phase, **attrs)`
for the scheduler's host work: a `jax.profiler.TraceAnnotation`
(`engine.<phase>`), so the span lands in the profiler's own trace,
in the host planes beside the device planes (which sit off the host's
clock by a constant of about a millisecond a session: a reader measures
it from the runtime's launch events and their `run_id`,
benchmarks/chip/lib/host_spans.py),
and on exit one increment each of
`dynamo_engine_host_seconds_total{phase,kind}` and
`dynamo_engine_host_spans_total{phase,kind}`. A span never crosses an
`await` (the annotation stack is per thread, and across an await the
loop's thread runs other tasks): `sched` spans wrap synchronous
stretches, `dispatch`/`sync` live inside the `asyncio.to_thread`
closures, and the two awaited phases are bracketed by `begin()`/`end()`
marker annotations (`engine.wait.begin` / `engine.wait.end`) from which
a trace reader rebuilds the interval.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Optional

# The CompileTracker entry set (docs/observability.md) plus the
# pipelined-burst sync pseudo-entry this module adds.
STEP_ENTRIES = (
    "decode_burst", "decode_guided", "spec_decode", "pp_decode",
    "pp_prefill", "prefill", "prefill_draft", "mixed_step",
    "ragged_step", "sample_first", "gather_kv", "write_kv", "burst_sync",
)

# Host phases of the scheduler, each with its kind: `sched` is the
# scheduler's own host work (on the event loop's thread, or in a closure
# thread where a whole prefill round runs off the loop), `device` is spent
# launching or waiting for the device on the thread that dispatches,
# `idle` is an awaited phase in which the engine had nothing to run.
HOST_PHASES = (
    ("admit", "sched"), ("prefill_prep", "sched"),
    ("decode_prep", "sched"), ("sample_first", "sched"),
    ("emit", "sched"), ("publish", "sched"),
    ("dispatch", "device"), ("sync", "device"),
    ("wait", "idle"), ("yield", "idle"),
)
_PHASE_KIND = dict(HOST_PHASES)

DEFAULT_RING = 2048
_TRUTHY = {"1", "true", "yes", "on"}


def _shape_label(shape) -> str:
    if isinstance(shape, (tuple, list)):
        return "x".join(str(s) for s in shape)
    return str(shape)


_TraceAnnotation = None


def _annotation(name: str, **attrs):
    """A `jax.profiler.TraceAnnotation`; JAX is imported at the first
    span, so a recorder in a process without a device (the mocker)
    never pays for it."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name, **attrs)


class _Span:
    """One host span: a TraceAnnotation on the current thread plus the
    two host counters on exit. Enter and exit on ONE thread, with no
    `await` in between."""

    __slots__ = ("_rec", "_phase", "_ann", "_t0")

    def __init__(self, rec: "StepRecorder", phase: str, attrs: dict) -> None:
        self._rec = rec
        self._phase = phase
        self._ann = _annotation("engine." + phase, **attrs)
        self._t0 = 0.0

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        elapsed = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        self._rec._count_host(self._phase, elapsed)


class StepRecorder:
    """Bounded ring of per-dispatch step records + cumulative per-entry
    totals (the totals survive ring eviction, so goodput/padding math is
    exact for the whole run while the ring stays a fixed-size window).

    Thread-safe: dispatch closures run under `asyncio.to_thread` and KV
    page ops run on kvbm worker threads, so records arrive from several
    threads; one lock covers ring + totals + the gap chain."""

    def __init__(self, capacity: int = DEFAULT_RING,
                 metrics=None) -> None:
        self.capacity = max(16, int(capacity))
        self._ring: deque[dict] = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._metrics = metrics
        # entry -> [count, host_s, good, work, tokens, compiles,
        #           synced_host_s]
        self._totals: dict[str, list] = {}
        self._recorded = 0
        self._last_end_pc = 0.0     # perf_counter of last record's end
        self._first_wall = 0.0
        self._last_wall = 0.0
        self._pc_to_wall = time.time() - time.perf_counter()
        if metrics is not None:
            metrics.arm_host_spans()

    # -- host spans ----------------------------------------------------------

    def span(self, phase: str, **attrs) -> _Span:
        """Context manager around a synchronous stretch of host work."""
        return _Span(self, phase, attrs)

    def begin(self, phase: str) -> float:
        """Marker before an awaited phase (`wait`, `yield`); returns the
        clock reading to hand to `end()`."""
        with _annotation(f"engine.{phase}.begin"):
            pass
        return time.perf_counter()

    def end(self, phase: str, t0: float) -> None:
        elapsed = time.perf_counter() - t0
        with _annotation(f"engine.{phase}.end"):
            pass
        self._count_host(phase, elapsed)

    def _count_host(self, phase: str, seconds: float) -> None:
        m = self._metrics
        if m is not None:
            kind = _PHASE_KIND[phase]
            m.host_seconds.inc(seconds, phase=phase, kind=kind)
            m.host_spans.inc(1, phase=phase, kind=kind)

    # -- hot path ------------------------------------------------------------

    def record(self, entry: str, shape, host_s: float, *,
               good_tokens: int = 0, work_tokens: int = 0,
               lanes: int = 0, width: int = 0, tokens: int = 0,
               compiled: bool = False, synced: bool = True) -> None:
        """Record one dispatch. Called AFTER the dispatch closure ends;
        `host_s` is its wall time (a `CompileTracker._Track.elapsed_s`),
        so start = now - host_s and the dispatch gap is start minus the
        previous record's end."""
        now_pc = time.perf_counter()
        start_pc = now_pc - host_s
        wall = start_pc + self._pc_to_wall
        good = int(good_tokens)
        work = int(work_tokens) if work_tokens else good
        padded = max(0, work - good)
        with self._lock:
            if self._last_end_pc:
                gap = max(0.0, start_pc - self._last_end_pc)
            else:
                gap = -1.0          # first record: no gap
            self._last_end_pc = now_pc
            self._recorded += 1
            if not self._first_wall:
                self._first_wall = wall
            self._last_wall = wall + host_s
            tot = self._totals.get(entry)
            if tot is None:
                tot = self._totals[entry] = [0, 0.0, 0, 0, 0, 0, 0.0]
            tot[0] += 1
            tot[1] += host_s
            tot[2] += good
            tot[3] += work
            tot[4] += int(tokens)
            tot[5] += 1 if compiled else 0
            if synced:
                tot[6] += host_s
            self._ring.append({
                "entry": entry,
                "shape": _shape_label(shape),
                "at": wall,
                "host_s": host_s,
                "gap_s": gap if gap >= 0.0 else None,
                "lanes": int(lanes),
                "width": int(width),
                "good_tokens": good,
                "work_tokens": work,
                "padded_tokens": padded,
                "tokens": int(tokens),
                "compiled": bool(compiled),
                "synced": bool(synced),
            })
        m = self._metrics
        if m is not None:
            if good:
                m.goodput_tokens.inc(good, entry=entry)
            if padded:
                m.padded_tokens.inc(padded, entry=entry)
            if gap >= 0.0:
                m.dispatch_gap.observe(gap)

    # -- views ---------------------------------------------------------------

    def snapshot(self, limit: Optional[int] = None) -> list[dict]:
        with self._lock:
            recs = list(self._ring)
        if limit is not None and limit >= 0:
            recs = recs[-limit:]
        return [dict(r) for r in recs]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._totals.clear()
            self._recorded = 0
            self._last_end_pc = 0.0
            self._first_wall = 0.0
            self._last_wall = 0.0

    @property
    def recorded(self) -> int:
        return self._recorded

    def last_dispatch_pc(self) -> float:
        """perf_counter of the last dispatch's end, 0.0 before the first
        record (or after clear()). The dispatch watchdog
        (engine/watchdog.py) polls this from its monitor thread to tell
        "no dispatch has finished for N seconds with work pending" —
        i.e. a wedged jitted call — from an idle engine."""
        with self._lock:
            return self._last_end_pc

    def summary(self) -> dict:
        """Aggregate attribution: cumulative per-entry totals (exact for
        the whole run), per-(entry, shape) padding table + dispatch-gap
        distribution from the ring window."""
        with self._lock:
            recs = list(self._ring)
            totals = {k: list(v) for k, v in self._totals.items()}
            recorded = self._recorded
            wall_span = max(0.0, self._last_wall - self._first_wall)

        synced_total = sum(v[6] for v in totals.values()) or 0.0
        entries = {}
        g_total = w_total = 0
        for entry, (count, host_s, good, work, toks, compiles,
                    synced_s) in sorted(totals.items()):
            g_total += good
            w_total += work
            entries[entry] = {
                "count": count,
                "host_s": host_s,
                "mean_host_ms": (host_s / count) * 1e3 if count else 0.0,
                "good_tokens": good,
                "work_tokens": work,
                "padded_tokens": work - good,
                "padded_pct": (100.0 * (work - good) / work
                               if work else 0.0),
                "tokens": toks,
                "compiles": compiles,
                "device_share_pct": (100.0 * synced_s / synced_total
                                     if synced_total else 0.0),
            }

        shapes: dict[str, dict] = {}
        gaps: list[float] = []
        for r in recs:
            key = f'{r["entry"]}:{r["shape"]}'
            s = shapes.get(key)
            if s is None:
                s = shapes[key] = {"entry": r["entry"],
                                   "shape": r["shape"], "count": 0,
                                   "host_s": 0.0, "good_tokens": 0,
                                   "work_tokens": 0, "padded_tokens": 0}
            s["count"] += 1
            s["host_s"] += r["host_s"]
            s["good_tokens"] += r["good_tokens"]
            s["work_tokens"] += r["work_tokens"]
            s["padded_tokens"] += r["padded_tokens"]
            if r["gap_s"] is not None:
                gaps.append(r["gap_s"])
        for s in shapes.values():
            s["padded_pct"] = (100.0 * s["padded_tokens"]
                               / s["work_tokens"]
                               if s["work_tokens"] else 0.0)

        gaps.sort()
        n = len(gaps)
        gap_stats = {
            "count": n,
            "mean_s": sum(gaps) / n if n else 0.0,
            "p50_s": gaps[n // 2] if n else 0.0,
            "p99_s": gaps[min(n - 1, int(n * 0.99))] if n else 0.0,
            "max_s": gaps[-1] if n else 0.0,
            "total_s": sum(gaps),
        }

        return {
            "recorded": recorded,
            "in_ring": len(recs),
            "capacity": self.capacity,
            "evicted": max(0, recorded - len(recs)),
            "wall_span_s": wall_span,
            "totals": {
                "good_tokens": g_total,
                "work_tokens": w_total,
                "padded_tokens": w_total - g_total,
                "padded_pct": (100.0 * (w_total - g_total) / w_total
                               if w_total else 0.0),
                "goodput_tok_s": (g_total / wall_span
                                  if wall_span else 0.0),
            },
            "entries": entries,
            "shapes": sorted(shapes.values(),
                             key=lambda s: -s["padded_tokens"]),
            "dispatch_gap": gap_stats,
        }


# -- construction / integration helpers -------------------------------------

def profile_enabled(env: Optional[dict] = None) -> bool:
    e = os.environ if env is None else env
    return str(e.get("DYN_STEP_PROFILE", "")).strip().lower() in _TRUTHY


def recorder_from_env(metrics=None,
                      env: Optional[dict] = None) -> Optional[StepRecorder]:
    """None unless `DYN_STEP_PROFILE` is truthy — the off path allocates
    nothing, so the step loop stays byte-identical. Ring size via
    `DYN_STEP_PROFILE_RING` (default 2048, floor 16)."""
    if not profile_enabled(env):
        return None
    e = os.environ if env is None else env
    try:
        cap = int(e.get("DYN_STEP_PROFILE_RING", DEFAULT_RING))
    except (TypeError, ValueError):
        cap = DEFAULT_RING
    return StepRecorder(capacity=cap, metrics=metrics)


def profile_payload(engine, limit: Optional[int] = None) -> dict:
    """The `GET /debug/profile` body for one engine: enabled flag,
    summary, ring snapshot. Safe on engines without a recorder."""
    rec = getattr(engine, "step_recorder", None)
    if rec is None:
        return {"enabled": False,
                "hint": "set DYN_STEP_PROFILE=1 to arm the recorder"}
    return {"enabled": True, "summary": rec.summary(),
            "records": rec.snapshot(limit)}


def step_profile_summary(engine) -> Optional[dict]:
    """Compact attribution block for BENCH_*.json records: goodput,
    padded-token share, mean dispatch gap. None when the recorder is
    off, so bench payloads stay unchanged by default."""
    rec = getattr(engine, "step_recorder", None)
    if rec is None:
        return None
    s = rec.summary()
    return {
        "recorded_steps": s["recorded"],
        "goodput_tokens": s["totals"]["good_tokens"],
        "padded_tokens": s["totals"]["padded_tokens"],
        "padded_pct": round(s["totals"]["padded_pct"], 3),
        "goodput_tok_s": round(s["totals"]["goodput_tok_s"], 2),
        "mean_dispatch_gap_s": s["dispatch_gap"]["mean_s"],
        "dispatch_gap_total_s": s["dispatch_gap"]["total_s"],
        "entries": {e: {"count": v["count"],
                        "padded_pct": round(v["padded_pct"], 3),
                        "device_share_pct":
                            round(v["device_share_pct"], 3)}
                    for e, v in s["entries"].items()},
    }


def capture_device_profile(seconds: float,
                           out_dir: Optional[str] = None) -> dict:
    """Windowed on-demand `jax.profiler.trace()` capture: blocks for
    `seconds` (capped at 60) while the profiler collects device/host
    activity, then returns where the trace landed. Works on the CPU
    backend too, so the endpoint is testable chip-free."""
    seconds = max(0.1, min(60.0, float(seconds)))
    out = out_dir or os.path.join(
        os.environ.get("TMPDIR", "/tmp"),
        f"dynamo-profile-{int(time.time())}")
    try:
        import jax

        # device planes and the runtime's host events (the engine's
        # spans among them); no Python frames, which would make most
        # of the file and slow the scheduler's thread
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=opts)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
    except Exception as exc:  # no jax / profiler unavailable
        return {"captured_s": 0.0, "error": f"{type(exc).__name__}: {exc}"}
    return {"captured_s": seconds, "out_dir": out}
