"""Entrypoints: assemble and run frontends and workers.

Reference: `lib/llm/src/entrypoint.rs` (`EngineConfig`, `Input`,
`run_input`) and `entrypoint/input/common.rs:261-325` (pipeline assembly).
The Python CLI layers (`python -m dynamo_tpu.frontend` etc.) call these.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Optional

from dynamo_tpu.llm.http_service import HttpService
from dynamo_tpu.llm.model_card import ModelDeploymentCard, register_llm
from dynamo_tpu.llm.model_manager import ModelManager, ModelWatcher
from dynamo_tpu.router.kv_router import (
    KvRouterConfig,
    kv_events_subject,
    metrics_subject,
)
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.engine import AsyncEngine

logger = logging.getLogger(__name__)


@dataclass
class Frontend:
    runtime: DistributedRuntime
    manager: ModelManager
    watcher: ModelWatcher
    http: HttpService
    grpc: object = None          # KserveGrpcService when --grpc-port set
    breaker_events: object = None   # Counter: event-plane breaker changes
    _breaker_task: object = None
    collector: object = None     # TelemetryCollector (fleet view)
    publisher: object = None     # TelemetryPublisher when interval > 0
    slo: object = None           # SloMonitor when objectives configured
    _slo_task: object = None
    control: object = None       # ControlPlane when DYN_CONTROL armed

    @property
    def url(self) -> str:
        return f"{self.http.scheme}://{self.http.host}:{self.http.port}"

    async def stop(self) -> None:
        if self._breaker_task is not None:
            self._breaker_task.cancel()
        if self._slo_task is not None:
            self._slo_task.cancel()
        if self.control is not None:
            await self.control.stop()
        if self.publisher is not None:
            await self.publisher.stop()
        if self.collector is not None:
            await self.collector.stop()
        if self.grpc is not None:
            await self.grpc.stop()
        await self.http.stop()
        await self.watcher.stop()
        await self.manager.close()


async def start_frontend(runtime: DistributedRuntime,
                         host: str = "127.0.0.1", port: int = 0,
                         router_config: Optional[KvRouterConfig] = None,
                         router_mode_override: Optional[str] = None,
                         namespace: Optional[str] = None,
                         tls_cert: Optional[str] = None,
                         tls_key: Optional[str] = None,
                         grpc_port: Optional[int] = None,
                         request_template: Optional[dict] = None
                         ) -> Frontend:
    """HTTP frontend: model discovery + OpenAI server (Input::Http).

    `router_mode_override` must be set before the watcher's initial MDC
    scan builds pipelines; `namespace` (if set) restricts discovery to
    cards in that namespace; `tls_cert`/`tls_key` serve HTTPS."""
    manager = ModelManager(runtime, router_config)
    manager.router_mode_override = router_mode_override
    watcher = await ModelWatcher(manager, namespace=namespace).start()
    http = HttpService(manager, host, port, tls_cert=tls_cert,
                       tls_key=tls_key, request_template=request_template)
    await http.start()
    grpc_svc = None
    if grpc_port is not None:
        from dynamo_tpu.grpc_frontend.service import KserveGrpcService

        grpc_svc = KserveGrpcService(manager, host, grpc_port)
        try:
            await grpc_svc.start()
        except BaseException:
            # no Frontend handle exists yet: unwind what already started
            # (bound HTTP port, running watcher) before re-raising
            await http.stop()
            await watcher.stop()
            await manager.close()
            raise
    # Count breaker state changes off the event plane (the runtime's own
    # breaker publishes them, and in shared-store deploys so do peers'):
    # the frontend sees worker health degrade without waiting to dial a
    # dead instance itself. Exposed on this process's /metrics as
    # `dynamo_frontend_breaker_events_total{state=...}`.
    import asyncio as _asyncio

    from dynamo_tpu.runtime.distributed import BREAKER_EVENTS_SUBJECT

    breaker_events = runtime.metrics.counter(
        "frontend_breaker_events_total",
        "breaker state changes observed on the event plane, by new state")
    sub = await runtime.events.subscribe(BREAKER_EVENTS_SUBJECT)

    async def _count_breaker_events() -> None:
        async for msg in sub:
            payload = msg.get("payload") or {}
            breaker_events.inc(state=str(payload.get("to", "unknown")))

    task = _asyncio.get_running_loop().create_task(_count_breaker_events())
    # Fleet telemetry plane (docs/observability.md "Fleet view"): the
    # frontend always runs the collector (a passive event-plane
    # subscription serving /fleet/status and doctor fleet); publishing
    # its own snapshot and the SLO monitor are opt-in via config.
    from dynamo_tpu.runtime.slo import (
        SLO_EVENTS_SUBJECT,
        SloMonitor,
        SloObjective,
    )
    from dynamo_tpu.runtime.telemetry import (
        TelemetryCollector,
        TelemetryPublisher,
        _publish_best_effort,
    )

    cfg = runtime.config
    collector = TelemetryCollector(runtime.events)
    await collector.start()
    # /debug/profile reads whatever engines serve_engine registered on
    # this runtime (late-bound: workers may start after the frontend)
    engines_supplier = \
        lambda: list(getattr(runtime, "profile_engines", []))
    http.profile_engines = engines_supplier
    # Serving classes (docs/robustness.md "Serving classes & brownout"):
    # DYN_CLASSES was parsed by HttpService.__init__; here the frontend
    # gets the deadline-admission estimator over the live engine
    # histograms and — when the config arms it — the brownout machine,
    # fed by the SLO loop below and ticked for walk-back either by the
    # control plane (when attached there) or by the SLO loop itself.
    brownout = None
    classes_cfg = http.classes
    if classes_cfg is not None:
        from dynamo_tpu.serving_classes import (
            AdmissionEstimator,
            BrownoutMachine,
        )

        http.admission = AdmissionEstimator(
            engines_supplier, classes_cfg.admission_quantile)
        if classes_cfg.brownout:
            brownout = BrownoutMachine(
                classes_cfg, engines=engines_supplier,
                bus=runtime.events, metrics=http.class_metrics)
            http.brownout = brownout
    # Flight control (docs/flight_control.md): DYN_CONTROL unset ⇒ None —
    # no plane, no controllers, /debug/control 503s, behavior untouched.
    # Armed, the plane observes whatever this process can reach: in-proc
    # engines (the same late-bound list /debug/profile uses), the
    # kv-mode routers, and the brownout machine. The planner-side
    # forecast controller is attached by whoever owns the Planner
    # (tests / run scripts) via
    # control_plane_from_env(planner=..., scale_events=...).
    from dynamo_tpu.control.plane import control_plane_from_env

    control = control_plane_from_env(
        runtime,
        engines=engines_supplier,
        routers=lambda: manager.kv_routers(),
        brownout=brownout)
    if control is not None:
        control.start()
        http.control_plane = control
    brownout_on_plane = (control is not None and brownout is not None
                         and brownout in control.controllers)
    slo = None
    slo_task = None
    objectives = []
    if cfg.slo_ttft > 0:
        objectives.append(SloObjective(
            "ttft", cfg.slo_ttft, cfg.slo_target_ratio))
    if cfg.slo_itl > 0:
        objectives.append(SloObjective(
            "itl", cfg.slo_itl, cfg.slo_target_ratio))
    if classes_cfg is not None:
        # per-class objectives ("ttft:interactive" etc) fed by the HTTP
        # path's per-class latency samples — so brownout can fire on ONE
        # class's burn even while the global windows look healthy
        for name, c in sorted(classes_cfg.classes.items()):
            if c.ttft_objective_s > 0:
                objectives.append(SloObjective(
                    f"ttft:{name}", c.ttft_objective_s,
                    cfg.slo_target_ratio))
            if c.itl_objective_s > 0:
                objectives.append(SloObjective(
                    f"itl:{name}", c.itl_objective_s,
                    cfg.slo_target_ratio))
    if objectives:
        slo = SloMonitor(objectives,
                         fast_window=cfg.slo_fast_window,
                         slow_window=cfg.slo_slow_window,
                         fast_burn=cfg.slo_fast_burn,
                         slow_burn=cfg.slo_slow_burn)
        slo.register(runtime.metrics)
        http.slo = slo

        async def _slo_loop() -> None:
            while True:
                await _asyncio.sleep(cfg.slo_check_interval)
                for ev in slo.evaluate():
                    _publish_best_effort(runtime.events,
                                         SLO_EVENTS_SUBJECT, ev)
                    if brownout is not None:
                        brownout.on_slo_event(ev)
                if brownout is not None and not brownout_on_plane:
                    brownout.tick()

        slo_task = _asyncio.get_running_loop().create_task(_slo_loop())
    http.fleet_status_provider = \
        lambda: collector.fleet_status(
            slo=slo,
            control=(control.summary if control is not None else None),
            brownout=(brownout.state if brownout is not None else None))
    publisher = None
    if cfg.telemetry_interval > 0:
        publisher = TelemetryPublisher(
            runtime.events, runtime.metrics, component="frontend",
            instance=f"{http.host}:{http.port}", role="frontend",
            interval=cfg.telemetry_interval)
        publisher.start()
    return Frontend(runtime, manager, watcher, http, grpc_svc,
                    breaker_events, task, collector, publisher,
                    slo, slo_task, control)


@dataclass
class WorkerHandle:
    runtime: DistributedRuntime
    card: ModelDeploymentCard
    served: object
    served_clear: object = None
    served_controller: object = None
    publisher: object = None     # TelemetryPublisher when interval > 0

    async def stop(self) -> None:
        if self.publisher is not None:
            await self.publisher.stop()
        if self.served_controller is not None:
            await self.served_controller.shutdown()
        if self.served_clear is not None:
            await self.served_clear.shutdown()
        await self.served.shutdown()


async def serve_engine(runtime: DistributedRuntime, engine: AsyncEngine,
                       card: ModelDeploymentCard,
                       instance_id: Optional[int] = None) -> WorkerHandle:
    """Worker side (entrypoint/input/endpoint.rs): serve a core engine on
    the card's endpoint and publish the card. Also serves the
    `clear_kv_blocks` admin endpoint (vllm main.py registers the same
    pair) when the engine supports cache clearing."""
    import inspect

    comp = runtime.namespace(card.namespace).component(card.component)
    ep = comp.endpoint(card.endpoint)
    # one source of truth: the engine's own latency/compile metrics join
    # this process's /metrics scrape (scheduler_stats and bench read the
    # same EngineMetrics objects — no second bookkeeping path). Disagg
    # workers serve a handler wrapping the engine — unwrap one level.
    em = getattr(engine, "metrics", None)
    core = engine
    if em is None:
        core = getattr(engine, "engine", None)
        em = getattr(core, "metrics", None)
    if em is not None and hasattr(em, "register"):
        em.register(runtime.metrics)
    # step-profiler surface: in-proc deployments (run/main.py, bench,
    # tests) share ONE runtime between workers and frontend, so listing
    # served engines here lets /debug/profile reach their StepRecorders
    if core is not None and hasattr(core, "step_recorder"):
        if not hasattr(runtime, "profile_engines"):
            runtime.profile_engines = []
        runtime.profile_engines.append(core)
    # KV lifecycle surface (kvbm/lifecycle.py): always-on lifecycle
    # counters join the scrape, and the tier-occupancy gauges refresh per
    # scrape from the live pools (the recorder itself stays None unless
    # DYN_KV_LIFECYCLE armed it at engine construction)
    km = getattr(core, "kv_metrics", None)
    if km is not None and hasattr(km, "register"):
        from dynamo_tpu.kvbm.lifecycle import tier_occupancy

        km.register(runtime.metrics,
                    occupancy=lambda eng=core: tier_occupancy(eng))
    # HBM memory ledger surface (engine/memory.py): the dynamo_memory_*
    # gauges join the scrape; with an armed ledger each scrape triggers
    # a fresh reconciliation poll (the ledger stays None unless
    # DYN_MEM_LEDGER armed it at engine construction)
    mm = getattr(core, "memory_metrics", None)
    if mm is not None and hasattr(mm, "register"):
        mm.register(runtime.metrics,
                    ledger=getattr(core, "memory_ledger", None))
    # Mesh & collective surface (engine/collectives.py): the
    # dynamo_collective_* / dynamo_mesh_* series join the scrape; with
    # an armed recorder each scrape re-polls per-device occupancy and
    # skew first (the recorder stays None unless DYN_MESH_RECORDER
    # armed it at engine construction)
    xm = getattr(core, "mesh_metrics", None)
    if xm is not None and hasattr(xm, "register"):
        xm.register(runtime.metrics,
                    recorder=getattr(core, "mesh_recorder", None))
    # Tenancy fairness surface (dynamo_tpu/tenancy): engine-role
    # dynamo_tenant_* series (goodput, queue wait, admissions, kv_blocks)
    # join the scrape when DYN_TENANCY armed the engine's fair scheduler
    tm = getattr(core, "tenant_metrics", None)
    if tm is not None and hasattr(tm, "register"):
        tm.register(runtime.metrics, role="engine")
    # one-token greedy canary (vllm health_check.py builds the same shape);
    # only probed when the runtime's health manager is enabled + idle.
    # The extra.canary marker lets sinks/metrics tell probes from traffic.
    from dynamo_tpu.runtime.health_check import DEFAULT_CANARY_PAYLOAD

    canary = {**DEFAULT_CANARY_PAYLOAD, "model": card.name}
    served = await ep.serve(
        engine, instance_id=instance_id,
        metadata={"dp_size": card.runtime_config.data_parallel_size},
        health_payload=canary)
    served_clear = None
    clear_fn = getattr(engine, "clear_kv_blocks", None)
    if clear_fn is not None:
        async def clear_handler(request, context):
            n = clear_fn()
            if inspect.isawaitable(n):
                n = await n
            yield {"status": "success", "cleared_pages": int(n or 0)}

        served_clear = await comp.endpoint("clear_kv_blocks").serve(
            clear_handler, instance_id=served.instance.instance_id)
    served_ctl = None
    if getattr(engine, "kvbm", None) is not None:
        kvbm = engine.kvbm

        async def controller_handler(request, context):
            # reference block_manager/controller.rs ControlMessage:
            # Status / ResetPool(level) / ResetAll
            op = (request or {}).get("op", "status")
            if op == "status":
                yield {"status": "success", **kvbm.status()}
            elif op == "reset":
                level = (request or {}).get("level", "all")
                try:
                    dropped = kvbm.reset(level)
                except ValueError as e:
                    yield {"status": "error", "error": str(e)}
                    return
                yield {"status": "success", "dropped": dropped}
            else:
                yield {"status": "error",
                       "error": f"unknown kvbm controller op {op!r}"}

        served_ctl = await comp.endpoint("kvbm_controller").serve(
            controller_handler, instance_id=served.instance.instance_id)
    await register_llm(runtime, card)
    # Telemetry plane: publish this worker's MetricsSnapshot (its engine
    # histograms joined runtime.metrics above) on the event bus so
    # frontend/planner collectors see it without an HTTP scrape.
    publisher = None
    if runtime.config.telemetry_interval > 0:
        from dynamo_tpu.runtime.telemetry import TelemetryPublisher

        publisher = TelemetryPublisher(
            runtime.events, runtime.metrics,
            component=f"{card.namespace}/{card.component}",
            instance=f"{served.instance.instance_id:x}", role="worker",
            interval=runtime.config.telemetry_interval)
        publisher.start()
    return WorkerHandle(runtime, card, served, served_clear, served_ctl,
                        publisher)


def wire_engine_events(runtime: DistributedRuntime,
                       card: ModelDeploymentCard):
    """Return (event_sink, metrics_sink) callables that publish a worker
    engine's KV events and ForwardPassMetrics onto the runtime event bus
    under the card's component subjects."""
    import asyncio

    ev_subject = kv_events_subject(card.namespace, card.component)
    m_subject = metrics_subject(card.namespace, card.component)
    bus = runtime.events

    def event_sink(ev) -> None:
        payload = ev.to_dict() if hasattr(ev, "to_dict") else ev
        if hasattr(bus, "publish_nowait"):
            bus.publish_nowait(ev_subject, payload)
        else:
            asyncio.get_running_loop().create_task(
                bus.publish(ev_subject, payload))

    def metrics_sink(m) -> None:
        payload = m.to_dict() if hasattr(m, "to_dict") else m
        if hasattr(bus, "publish_nowait"):
            bus.publish_nowait(m_subject, payload)
        else:
            asyncio.get_running_loop().create_task(
                bus.publish(m_subject, payload))

    return event_sink, metrics_sink


def build_tpu_engine(model: str, served_name: Optional[str] = None, *,
                     num_pages: int = 2048, max_batch_size: int = 8,
                     decode_steps_per_sync: int = 8, mesh=None,
                     worker_id: int = 0, dp_rank: int = 0,
                     random_init: bool = False, kvbm_host_blocks: int = 0,
                     kvbm_offload_queue: int = 0,
                     kvbm_offload_workers: int = 0,
                     kvbm_prefetch_blocks: int = 0,
                     kvbm_offload_queue_bytes: int = 0,
                     quantize: Optional[str] = None,
                     draft_model: Optional[str] = None, spec_gamma: int = 4,
                     spec_iters_per_sync: int = 8, sp_degree: int = 0,
                     sp_threshold: int = 2048, sp_layout: str = "zigzag",
                     prefill_batch_widths=None,
                     pipeline_parallel_size: int = 1,
                     pp_microbatches: int = 0,
                     dllm_denoising_steps: int = 0,
                     dllm_unmasking_strategy: str = "sequential",
                     **model_overrides):
    """(TpuEngine, ModelDeploymentCard) for a real checkpoint.

    Resolves `model` (dir or HF-cache name, loader.resolve_model), loads
    safetensors weights into the engine's layout, and fills the card so
    frontends build the matching HF tokenizer. `random_init=True` skips
    the weight read (benchmarks on synthetic weights). `model_overrides`
    tune geometry, e.g. ``max_pages_per_seq`` to bound context.
    `quantize="int8"` serves weight-only-quantized (engine/quant.py);
    `draft_model` names a second (small) checkpoint for speculative
    decoding — its page geometry is forced to the target's. `sp_degree>1`
    builds an "sp" ring over the first N local devices for sequence-
    parallel long-prompt prefill (models/llama_sp.py).
    """
    import os

    from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
    from dynamo_tpu.models.loader import (
        config_from_hf,
        load_llama_params,
        resolve_model,
    )

    path = resolve_model(model)
    cfg = config_from_hf(path, **model_overrides)
    if cfg.mask_token_id >= 0 and cfg.attn_block == 1:
        # a checkpoint that names a mask id generates by diffusion over
        # blocks, and how long a block is is the deployment's to say
        raise ValueError(
            f"{path} is a block-diffusion checkpoint (mask_token_id "
            f"{cfg.mask_token_id}): give --dllm-block-length")
    if random_init:
        params = None
    elif mesh is None:
        # single-(sub)mesh engines load straight onto the device:
        # transpose/cast/int8 run on the chip (loader docstring — the
        # host-side path takes tens of minutes at 8B scale on a small
        # host, and 8B bf16 wouldn't fit HBM un-quantized anyway). The
        # engine's own device_put/quantize passes are no-ops on the
        # result.
        from dynamo_tpu.models.loader import load_llama_params_device

        params = load_llama_params_device(path, cfg, quantize=quantize)
    else:
        # mesh path: host arrays; shard_params places per-shard and the
        # engine quantizes in place (sharded bf16 fits per chip by
        # construction)
        params = load_llama_params(path, cfg)
    sp_mesh = None
    if sp_degree > 1:
        from dynamo_tpu.engine.ring_attention import sp_mesh as make_sp

        sp_mesh = make_sp(sp_degree)
    pp_mesh = None
    if pipeline_parallel_size > 1:
        # stage slices over the first N local devices (ref: trtllm
        # --pipeline-parallel-size, trtllm_utils.py:39,167-170)
        import jax
        import numpy as _np
        from jax.sharding import Mesh as _Mesh

        devs = jax.devices()[:pipeline_parallel_size]
        if len(devs) < pipeline_parallel_size:
            raise ValueError(
                f"pipeline_parallel_size={pipeline_parallel_size} "
                f"exceeds local device count {len(jax.devices())}")
        pp_mesh = _Mesh(_np.asarray(devs), axis_names=("pp",))
        if not pp_microbatches:
            pp_microbatches = pipeline_parallel_size
    draft_cfg = draft_params = None
    if draft_model is not None:
        dpath = resolve_model(draft_model)
        draft_cfg = config_from_hf(
            dpath, page_size=cfg.page_size,
            max_pages_per_seq=cfg.max_pages_per_seq)
        draft_params = None if random_init \
            else load_llama_params(dpath, draft_cfg)
    # guided decoding needs the serving tokenizer's id→bytes map; pass a
    # LAZY provider — the O(vocab) build only runs if a guided request
    # ever arrives, keeping worker startup unchanged
    token_bytes = None
    eos_id = 0
    try:
        from dynamo_tpu.llm.guided import token_bytes_of
        from dynamo_tpu.llm.tokenizer import make_tokenizer

        has_tok_files = any(
            os.path.exists(os.path.join(path, f)) for f in
            ("tokenizer.json", "tokenizer_config.json", "tokenizer.model"))
        tok = make_tokenizer("hf" if has_tok_files else "byte",
                             path if has_tok_files else "")
        vocab = cfg.vocab_size

        def token_bytes(tok=tok, vocab=vocab):
            return token_bytes_of(tok, vocab)

        eos_id = tok.eos_token_id or 0     # property, NOT a method
    except Exception as e:  # pragma: no cover - degraded, not fatal
        import logging

        logging.getLogger(__name__).warning(
            "guided decoding disabled (tokenizer unavailable: %s)", e)
    engine = TpuEngine(
        TpuEngineConfig(model=cfg, num_pages=num_pages,
                        max_batch_size=max_batch_size,
                        decode_steps_per_sync=decode_steps_per_sync,
                        mesh=mesh, worker_id=worker_id, dp_rank=dp_rank,
                        quantize=quantize, draft_model=draft_cfg,
                        spec_gamma=spec_gamma,
                        spec_iters_per_sync=spec_iters_per_sync,
                        sp_mesh=sp_mesh,
                        sp_threshold=sp_threshold if sp_mesh else 0,
                        sp_layout=sp_layout,
                        prefill_batch_widths=prefill_batch_widths,
                        pp_mesh=pp_mesh,
                        pp_microbatches=pp_microbatches or 2,
                        dllm_denoising_steps=dllm_denoising_steps,
                        dllm_unmasking_strategy=dllm_unmasking_strategy),
        params=params, draft_params=draft_params,
        token_bytes=token_bytes, eos_token_id=eos_id)
    if kvbm_host_blocks:
        from dynamo_tpu.kvbm import KvbmConfig, KvbmManager

        KvbmManager(engine, KvbmConfig(
            host_blocks=kvbm_host_blocks,
            offload_queue_depth=kvbm_offload_queue,
            offload_workers=kvbm_offload_workers,
            prefetch_blocks=kvbm_prefetch_blocks,
            offload_queue_bytes=kvbm_offload_queue_bytes))
    # a checkpoint without tokenizer files (weight-only export, random-
    # init benchmarking) must not publish a card the frontend can't build
    has_tok = any(os.path.exists(os.path.join(path, f)) for f in
                  ("tokenizer.json", "tokenizer_config.json",
                   "tokenizer.model"))
    card = ModelDeploymentCard(
        name=served_name or os.path.basename(path.rstrip("/")),
        tokenizer_kind="hf" if has_tok else "byte",
        tokenizer_path=path if has_tok else "",
        model_path=path,
        context_length=cfg.context_length, kv_block_size=cfg.page_size)
    return engine, card
