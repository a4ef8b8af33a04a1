"""Engine latency/throughput metrics — the ONE bookkeeping path.

Replaces the `TpuEngine.perf` dict-plus-manual-publish pattern: the
scheduler observes directly into these `runtime.metrics` histograms and
counters, and every consumer reads the same objects —

  * `/metrics` (Prometheus): `EngineMetrics.register(rt.metrics)` adopts
    the fully-named metrics into the runtime registry;
  * `_sys.stats` / `scheduler_stats`: `_publish_metrics` reads the same
    histograms;
  * bench and old tests: `TpuEngine.perf` is now a **derived property**
    returning this class's `perf_view()` — the legacy key set, computed
    from the metrics, so numeric deltas between `dict(eng.perf)`
    snapshots keep working with no second bookkeeping path.

Metric names are fixed at construction (`dynamo_engine_*`) rather than
registry-prefixed: the engine exists before (and without) any
DistributedRuntime, and the names must match docs/observability.md
whether or not a registry ever adopts them.
"""

from __future__ import annotations

from dynamo_tpu.engine.compile_tracker import CompileTracker
from dynamo_tpu.llm.perf import ITL_BUCKET_EDGES_MS
from dynamo_tpu.runtime.metrics import (Counter, Gauge, Histogram,
                                        MetricsRegistry)

# second-scale stage latencies: sub-ms admission checks up to multi-
# second cold prefills
_STAGE_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                  0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                  30.0)
# ITL buckets reuse the wire histogram's edges (llm/perf.py) so the
# Prometheus view, scheduler_stats percentiles, and offline analysis
# agree on bucket meaning. The +Inf edge is implicit in Histogram.
_ITL_BUCKETS_MS = tuple(e for e in ITL_BUCKET_EDGES_MS
                        if e != float("inf"))
# Dispatch gaps are the host overhead BETWEEN jitted steps — almost
# always sub-ms when the loop is healthy, so the buckets reach an order
# of magnitude finer than the stage buckets.
_GAP_BUCKETS = (0.00001, 0.000025, 0.00005, 0.0001, 0.00025, 0.0005,
                0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                1.0)
# Per-transfer KV pull bandwidth spans the host wire on loopback
# (~100 MB/s) through DCN (~GB/s) up to the device-to-device paths
# (tens of GB/s) — log-ish edges across five decades.
_BW_BUCKETS = (1e6, 3e6, 1e7, 3e7, 1e8, 3e8, 1e9, 3e9, 1e10, 3e10,
               1e11, 3e11)

# Canonical histogram names, importable by telemetry consumers
# (runtime/telemetry.py latency summaries, doctor fleet) so renames
# can't silently desynchronize the fleet view from the engine.
TTFT_HISTOGRAM = "dynamo_engine_ttft_seconds"
ITL_HISTOGRAM = "dynamo_engine_itl_ms"


class EngineMetrics:
    """Owned by one engine (TpuEngine or MockEngine)."""

    def __init__(self) -> None:
        h, c = Histogram, Counter
        self.queue_wait = h(
            "dynamo_engine_queue_wait_seconds",
            "enqueue -> admission wait per request", _STAGE_BUCKETS)
        self.admission_stall = h(
            "dynamo_engine_admission_stall_seconds",
            "blocking work (kvbm onboard/offload-drain) inside _admit",
            _STAGE_BUCKETS)
        self.prefill_chunk = h(
            "dynamo_engine_prefill_chunk_seconds",
            "one prefill chunk round (standalone, mixed, or pp)",
            _STAGE_BUCKETS)
        self.ttft = h(
            TTFT_HISTOGRAM,
            "enqueue -> first emitted token per request", _STAGE_BUCKETS)
        self.itl = h(
            ITL_HISTOGRAM,
            "inter-token gap at the emission boundary (ms)",
            _ITL_BUCKETS_MS)
        self.kv_pull = h(
            "dynamo_engine_kv_pull_seconds",
            "disagg KV pull, prefill worker -> decode worker",
            _STAGE_BUCKETS)
        # KV-transfer volume/bandwidth (disagg/handlers.py): the latency
        # histogram above says how long pulls took; these say how much
        # moved and how fast — the inputs a network-aware placement cost
        # model needs (ROADMAP "network-aware disagg placement").
        self.kv_pull_bytes = c(
            "dynamo_kv_pull_bytes_total",
            "disagg KV bytes pulled onto this decode worker, by "
            "transfer path (device/plane/wire)")
        self.kv_pull_bw = h(
            "dynamo_kv_pull_bandwidth_bytes_per_s",
            "per-transfer disagg KV pull bandwidth", _BW_BUCKETS)
        self.offload_drain = h(
            "dynamo_engine_offload_drain_seconds",
            "one kvbm offload batch: device gather + tier demote",
            _STAGE_BUCKETS)
        self.prefill_seconds = c(
            "dynamo_engine_prefill_seconds_total",
            "scheduler wall seconds in prefill phases")
        self.decode_seconds = c(
            "dynamo_engine_decode_seconds_total",
            "scheduler wall seconds in decode phases")
        self.tokens_emitted = c(
            "dynamo_engine_tokens_emitted_total",
            "tokens emitted to consumers")
        self.prefill_emitted = c(
            "dynamo_engine_prefill_emitted_total",
            "first tokens emitted at prefill completion")
        self.prefill_new_tokens = c(
            "dynamo_engine_prefill_new_tokens_total",
            "prompt tokens actually prefetched/prefilled (cache misses)")
        self.pipelined_bursts = c(
            "dynamo_engine_pipelined_bursts_total",
            "speculatively-dispatched decode bursts")
        self.chained_refills = c(
            "dynamo_engine_chained_refills_total",
            "decode bursts launched behind a first-token sampler "
            "before it was synced")
        self.refills_behind_burst = c(
            "dynamo_engine_refills_behind_burst_total",
            "sequences admitted while a block burst was in flight and "
            "prefilled behind it, ahead of its emission")
        self.sampler_greedy_dispatches = c(
            "dynamo_engine_sampler_greedy_dispatches_total",
            "dispatches of a sampling entry in which no lane drew "
            "(every temperature 0): the sampler ran its argmax and no "
            "candidate set, by entry")
        self.mixed_steps = c(
            "dynamo_engine_mixed_steps_total",
            "fused prefill-chunk + decode-burst steps")
        self.decode_steps_during_prefill = c(
            "dynamo_engine_decode_steps_during_prefill_total",
            "decode steps interleaved while requests were prefilling")
        # Block diffusion (engine.py `_block_decode`): a lane's block costs
        # `denoise` forwards plus one `commit`; counted a lane a forward,
        # so forwards over the burst's tokens is the cost of a token.
        self.block_forwards = c(
            "dynamo_engine_block_forwards_total",
            "forwards of a block-diffusion burst, a lane a forward, by "
            "kind (denoise / commit)")
        self.blocks = c(
            "dynamo_engine_blocks_total",
            "blocks denoised and committed, a lane a block")
        self.moe_routed_rows = c(
            "dynamo_moe_routed_rows_total",
            "rows the routed expert dispatch sent to experts: real "
            "token positions x experts per token x layers")
        # State slots of a model with recurrent layers (engine/pages.py
        # SlotPool); they stay 0 for every other model.
        self.state_resets = c(
            "dynamo_engine_state_resets_total",
            "first prefill chunks of a model with recurrent layers: a "
            "state slot started from zero")
        self.state_slots = Gauge(
            "dynamo_engine_state_slots",
            "state slots of a model with recurrent layers (scratch slot 0 "
            "not counted); 0 for a model without")
        self.state_slots_in_use = Gauge(
            "dynamo_engine_state_slots_in_use",
            "state slots owned by admitted sequences")
        # Step-profiler attribution (engine/profiler.py). Constructed
        # unconditionally so names are stable in /metrics and telemetry
        # snapshots; they only move when DYN_STEP_PROFILE arms the
        # StepRecorder, so the off path stays write-free.
        self.goodput_tokens = c(
            "dynamo_engine_goodput_tokens_total",
            "real token-positions computed per jitted entry (no padding)")
        self.padded_tokens = c(
            "dynamo_engine_padded_tokens_total",
            "padded token-positions wasted per jitted entry")
        self.dispatch_gap = h(
            "dynamo_engine_dispatch_gap_seconds",
            "host gap between consecutive jitted dispatches",
            _GAP_BUCKETS)
        # Host spans of the scheduler (engine/profiler.py `span`): made
        # by `arm_host_spans()` when DYN_STEP_PROFILE arms the recorder,
        # so an unarmed engine's /metrics does not carry their names.
        self.host_seconds = None
        self.host_spans = None
        # set once by the worker from TpuEngine.device_report(): value =
        # device count, labels say which platform/kind the engine's
        # arrays sit on and whether the attention kernel path is on
        self.device_info = Gauge(
            "dynamo_engine_device_info",
            "devices holding this engine's weights and KV cache "
            "(labels: platform, kind, attention_kernels)")
        self.compile = CompileTracker()

    def arm_host_spans(self) -> None:
        if self.host_seconds is None:
            self.host_seconds = Counter(
                "dynamo_engine_host_seconds_total",
                "scheduler host seconds by phase and kind (sched: the "
                "scheduler's own host work; device: launching or "
                "awaiting a dispatch; idle: nothing to run)")
            self.host_spans = Counter(
                "dynamo_engine_host_spans_total",
                "scheduler host spans by phase and kind")

    def register(self, registry: MetricsRegistry) -> None:
        """Adopt every metric into a runtime registry so one `/metrics`
        scrape renders them (idempotent; first engine wins a name)."""
        for m in (self.queue_wait, self.admission_stall,
                  self.prefill_chunk, self.ttft, self.itl, self.kv_pull,
                  self.kv_pull_bytes, self.kv_pull_bw,
                  self.offload_drain, self.prefill_seconds,
                  self.decode_seconds, self.tokens_emitted,
                  self.prefill_emitted, self.prefill_new_tokens,
                  self.pipelined_bursts, self.chained_refills,
                  self.refills_behind_burst,
                  self.sampler_greedy_dispatches, self.mixed_steps,
                  self.decode_steps_during_prefill,
                  self.block_forwards, self.blocks, self.moe_routed_rows,
                  self.state_resets, self.state_slots,
                  self.state_slots_in_use, self.goodput_tokens,
                  self.padded_tokens,
                  self.dispatch_gap, self.device_info):
            registry.register(m)
        if self.host_seconds is not None:
            registry.register(self.host_seconds)
            registry.register(self.host_spans)
        # module-owned: the attention impl switch predates any engine,
        # but its fallback attribution belongs on the same scrape
        from dynamo_tpu.engine.attention import attention_fallbacks
        registry.register(attention_fallbacks)
        self.compile.register(registry)

    # -- legacy view ---------------------------------------------------------

    def perf_view(self) -> dict:
        """The historical `engine.perf` dict, derived (not stored):
        bench/tests snapshot it with `dict(eng.perf)` and take numeric
        deltas; `itl_hist` is a fresh counts list in the
        `llm.perf.itl_new_hist` layout (finite edges + open bucket)."""
        itl_counts, _, _ = self.itl.snapshot()
        return {
            "prefill_s": self.prefill_seconds.get(),
            "decode_s": self.decode_seconds.get(),
            "prefill_new_tokens": int(self.prefill_new_tokens.get()),
            "prefill_emitted": int(self.prefill_emitted.get()),
            "tokens_emitted": int(self.tokens_emitted.get()),
            "pipelined_bursts": int(self.pipelined_bursts.get()),
            "chained_refills": int(self.chained_refills.get()),
            "refills_behind_burst": int(self.refills_behind_burst.get()),
            "prefill_chunks": self.prefill_chunk.count,
            "decode_steps_during_prefill":
                int(self.decode_steps_during_prefill.get()),
            "mixed_steps": int(self.mixed_steps.get()),
            "itl_hist": itl_counts,
            "admission_stall_ms": self.admission_stall.sum * 1e3,
        }
