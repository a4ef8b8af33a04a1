# Test tiers (ROADMAP.md). All runs pin the CPU backend — tests never
# touch a TPU.

PYTEST := JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider

.PHONY: tier0 tier1 chaos heal-smoke control-smoke mem-smoke kvbm-soak \
	trace-smoke fleet-smoke autoscale-smoke profile-smoke router-smoke \
	kv-smoke perf-gate perf-baseline fairness-smoke ragged-smoke \
	overload-smoke mesh-smoke prefix-smoke

# fast smoke: the pure-host suites + the interleave scheduler gate,
# < 60 s total (currently ~15 s)
tier0:
	$(PYTEST) tests/ -m tier0

# the full gate the driver runs (everything but slow)
tier1:
	$(PYTEST) tests/ -m 'not slow' --continue-on-collection-errors

# robustness gate (docs/robustness.md): deterministic fault injection
# (seeded — every run sees the same faults) + the chaos soak, which
# kills/stalls/wedges workers mid-stream and requires 100% of requests
# to complete token-identically — plus the self-healing suite
# (heal-smoke) and the flight-control loop gate (control-smoke).
chaos: heal-smoke control-smoke mem-smoke fairness-smoke ragged-smoke \
	overload-smoke mesh-smoke prefix-smoke
	$(PYTEST) tests/test_faults.py tests/test_chaos.py \
		tests/test_kvbm_pipeline.py

# self-healing gate (docs/robustness.md "Watchdog & self-healing" /
# "Degraded control plane"): dispatch-watchdog trip on a seeded wedge,
# quarantine (deregister + stream abort + breaker purge), supervisor
# respawn with crash-loop budget, corpse-first drain ordering,
# stale-while-revalidate store degradation, KV-index gap resync, and
# doctor preflight exit codes. Chip-free; off-by-default paths pinned.
heal-smoke:
	$(PYTEST) tests/test_healing.py

# flight-control gate (docs/flight_control.md): off-by-default purity,
# each controller against synthetic evidence, the seeded armed perf
# pass (byte-identical twice, padded tokens down at equal goodput), and
# the SLA-gated loop smoke — trafficgen replay over a live mock fleet
# with every controller armed: no SLO fast-burn after warmup, zero
# non-abandoned streams dropped, >=1 action per controller, every knob
# change explainable via doctor control. Chip-free.
control-smoke:
	$(PYTEST) tests/test_control.py

# memory-ledger gate (docs/observability.md "Memory ledger"): arm
# DYN_MEM_LEDGER over MockEngine's analytic HBM model — ledger classes
# must reconcile against mock memory_stats() EXACTLY (residual == the
# configured unattributed bytes), the unarmed path stays
# byte-identical, the seeded oom fault dumps a forensic crash file
# whose triggering dispatch joins the step-recorder tail and exits
# rc 45 into the supervisor's oom death-cause, the bench headroom gate
# shrinks a too-big KV pool, and GET /debug/memory + doctor memory
# render end to end. Chip-free.
mem-smoke:
	$(PYTEST) tests/test_memory_ledger.py

# KVBM pipeline soak (docs/kvbm.md): loop admission/eviction with the
# offload worker fault-delayed on every batch — output must stay
# token-identical to a clean engine. Includes the slow-marked soak
# body the tier gates skip.
kvbm-soak:
	$(PYTEST) tests/test_kvbm_pipeline.py tests/test_kvbm.py

# observability gate (docs/observability.md): one DYN_TRACE'd request
# through frontend → TCP transport → engine must land in a single
# connected trace; plus traceparent-through-retries, compile-tracker
# warm path, breaker events, /debug/requests, doctor trace analyzer.
trace-smoke:
	$(PYTEST) tests/test_trace_smoke.py tests/test_tracing.py \
		tests/test_trace_sampling.py tests/test_request_stages.py

# autoscaling gate (docs/autoscaling.md): the CLOSED loop — frontend +
# fleet supervisor + SLA planner on live event-plane telemetry, driven
# by the deterministic trafficgen replaying a diurnal day over real
# HTTP. Passes only if the planner scales the mock fleet up on the ramp
# AND back down after, the TTFT/ITL SLOs never fast-burn after warmup,
# and every non-abandoned stream completes token-identical to an
# unscaled reference replay. Includes the slow-marked soak.
autoscale-smoke:
	$(PYTEST) tests/test_autoscale_loop.py

# fleet telemetry gate (docs/observability.md "Fleet view"/"SLOs"):
# event-plane MetricsSnapshot merge math, worker+frontend publishing
# over a real TCP store into GET /fleet/status + doctor fleet, the
# planner running zero-HTTP off the TelemetrySource, and SLO burn-rate
# transitions on the slo_events subject.
fleet-smoke:
	$(PYTEST) tests/test_telemetry.py tests/test_slo.py

# router-observability gate (docs/observability.md "Router
# observability"): decision-ring gating (DYN_ROUTER_LOG off ⇒
# byte-identical SelectionResults, no record allocation), prefix-reuse
# accounting parity (tokens saved == overlap × block_size), consumer
# crash-proofing, GET /debug/router + doctor router end to end, KV-event
# capture/replay, and disagg KV-pull bytes/bandwidth accounting — plus
# the existing KV-router e2e suite. Chip-free (mock engines only).
router-smoke:
	$(PYTEST) tests/test_router_decisions.py tests/test_kv_router.py

# KV-lifecycle gate (docs/observability.md "KV lifecycle"): arm
# DYN_KV_LIFECYCLE over PagePool / MockKvManager / TieredStore workouts
# with analytically-known eviction causes, reuse distances, and
# premature-eviction windows; pins the unarmed byte-identical contract,
# KV-event gap detection in the router indexer, hint-driven prefetch
# attribution, and GET /debug/kv + doctor kv end to end (mock engines,
# chip-free).
kv-smoke:
	$(PYTEST) tests/test_kv_lifecycle.py

# deterministic perf gate (docs/observability.md "Perf ledger &
# regression gate"): run the chip-free perf phase (seeded virtual-clock
# replay; scored metrics are analytic recorder counters, byte-identical
# per seed) and hold it against the checked-in baseline with tight
# per-metric thresholds. Exits nonzero and renders the doctor bench
# delta table on any regression. Perf PRs that IMPROVE a metric rerun
# `make perf-baseline` and commit the updated baseline.
perf-gate:
	JAX_PLATFORMS=cpu python -m dynamo_tpu.bench.perf \
		--out /tmp/dynamo_perf_current.json
	JAX_PLATFORMS=cpu python -m dynamo_tpu.doctor bench --gate \
		benchmarks/perf_baseline.json /tmp/dynamo_perf_current.json

# regenerate the gate baseline after an intentional perf change
perf-baseline:
	JAX_PLATFORMS=cpu python -m dynamo_tpu.bench.perf \
		--out benchmarks/perf_baseline.json

# multi-tenant fairness gate (docs/multitenancy.md): quota/identity
# parsing, token-bucket 429s with Retry-After at the frontend, the
# deficit-weighted fair scheduler against hand-traced schedules,
# per-tenant KV budgets, and the noisy-neighbor SLA smoke — a bursty
# heavy tenant flooding a live mock fleet next to a quiet interactive
# tenant, gated on weighted goodput split (±10%), quiet-tenant TTFT,
# and token-identity vs an isolated replay. Also pins the unarmed
# byte-identical contract (legacy admission order, schedule artifact
# md5, clean /metrics). Chip-free.
fairness-smoke:
	$(PYTEST) tests/test_tenancy.py

# serving-class / brownout gate (docs/robustness.md "Serving classes &
# brownout"): class-table parsing and resolution precedence, the
# deadline-admission decision boundary on hand-built histograms, the
# brownout ladder under a fake clock (escalation + hysteresis
# walk-back), expired deadlines dropped before prefill, the chaos soak
# with client abandons, and the overload gauntlet — a bursty mix beyond
# mock-fleet capacity with the SLO monitor + brownout armed, gated on
# batch shedding before any interactive 503, zero engine-side drops of
# admitted streams, and the explainable stage on every surface. Also
# pins the unarmed byte-identical contract (schedule artifact md5,
# clean /metrics, no gate objects on the HTTP path). Chip-free.
overload-smoke:
	$(PYTEST) tests/test_serving_classes.py

# ragged-attention gate (docs/scheduler.md "Ragged dispatch"):
# interpret-mode Pallas kernel parity vs the XLA reference (GQA
# groups, ragged lengths, zero-length padding lanes, multi-block
# grids), the byte-identical ragged-off serving path, the strict
# compile-shape reduction on the scripted mixed workload, the
# head-dim fallback counter, and the BucketAutotuner ladder handoff.
# Chip-free.
ragged-smoke:
	$(PYTEST) tests/test_ragged_attention.py

# mesh/collective gate (docs/observability.md "Mesh & collectives"):
# wire-byte formulas vs HLO ground truth — a tp=2 megatron-sharded
# llama layer stack compiled on the forced-8-device CPU mesh must
# produce exactly the analytic all-reduce count/bytes — plus the
# unarmed byte-identical contract (no recorder object, identical
# tokens + scheduler_stats), reshard-manifest tripwire, link-tier
# topology classification, and mesh_summary fleet wiring. Chip-free.
mesh-smoke:
	$(PYTEST) tests/test_mesh_recorder.py

# prefix-plane gate (docs/observability.md "Prefix plane"): gating +
# ring floor, the unarmed AND armed byte-identical routing contract
# (seeded placements, live-RNG draw order, clean /metrics), the
# hand-traceable shadow counterfactual (tier-held chain vs device
# overlap — exact tokens saved), pull-cost economics over the
# DYN_LINK_BW_* link tiers, duplication math by depth bucket,
# tier-blind detection incl. the demoted-prefix WARN in doctor
# prefixes, perf-record prefix keys + two-run byte-identity, the
# surface-drift lint, and the full-stack GET /debug/prefixes + doctor
# smoke over a live mock fleet. Chip-free.
prefix-smoke:
	$(PYTEST) tests/test_prefix_plane.py tests/test_surface_drift.py

# step-profiler gate (docs/observability.md "Step profiler"): arm
# DYN_STEP_PROFILE on a MockEngine deployment, drive requests, read the
# ring back through GET /debug/profile + doctor profile, and assert
# decode goodput equals tokens emitted and the padded share matches the
# analytically-known _pow2 bucketing of the scripted batch mix; plus
# the zero-cost off path (no recorder state, scheduler_stats unchanged);
# and the scheduler's host spans in a jax.profiler trace of a toy engine
# plus the worker's /debug/profile?capture_s= route.
profile-smoke:
	$(PYTEST) tests/test_step_profiler.py tests/test_host_spans.py
