"""Worker CLI implementation (see package docstring).

Reference: `components/src/dynamo/vllm/main.py:69-228` — parse args,
build engine, register endpoints + model card, serve until signal; the
engine monitor force-exits so the lease drops when the engine dies
(`engine_monitor.py`).
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import sys

from dynamo_tpu.cli_util import (
    add_runtime_args,
    run_until_signal,
    runtime_config_from_args,
    setup_logging,
)

logger = logging.getLogger(__name__)


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="python -m dynamo_tpu.worker",
        description="dynamo_tpu engine worker")
    add_runtime_args(p)
    eng = p.add_mutually_exclusive_group()
    eng.add_argument("--model", default=None,
                     help="checkpoint dir or cached HF name (TPU engine)")
    eng.add_argument("--mock", action="store_true",
                     help="serve the mocker engine (no chips needed)")
    eng.add_argument("--echo", action="store_true",
                     help="serve the token-echo engine")
    eng.add_argument("--encode-worker", action="store_true",
                     help="serve the multimodal image-encode endpoint "
                          "(no LM; the sglang encode-worker analog)")
    p.add_argument("--image-vocab-offset", type=int, default=128256,
                   help="encode worker: image tokens start here")
    p.add_argument("--encode-component", default="",
                   help="LM workers: enable image inputs via this "
                        "encode-worker component")
    p.add_argument("--served-model-name", default=None)
    p.add_argument("--component", default="backend")
    p.add_argument("--endpoint", default="generate")
    p.add_argument("--is-prefill-worker", action="store_true",
                   help="register under <component>_prefill and serve the "
                        "kv_pull transfer endpoint")
    p.add_argument("--enable-disagg", action="store_true",
                   help="decode side: orchestrate remote prefill against "
                        "the <component>_prefill pool")
    p.add_argument("--prefill-queue", action="store_true",
                   help="disagg jobs ride the durable queue (pull model) "
                        "instead of push routing; on prefill workers "
                        "starts the queue consumer")
    p.add_argument("--max-local-prefill-length", type=int, default=0,
                   help="prompts at or below this (minus prefix hits) "
                        "prefill locally even in disagg mode")
    p.add_argument("--migration-limit", type=int, default=0)
    p.add_argument("--router-mode", default="kv",
                   choices=["kv", "round_robin", "random"])
    p.add_argument("--instance-id", type=int, default=None)
    # engine geometry
    p.add_argument("--num-pages", type=int, default=2048)
    p.add_argument("--max-batch-size", type=int, default=8)
    p.add_argument("--decode-steps-per-sync", type=int, default=8)
    p.add_argument("--prefill-chunk", type=int, default=512)
    p.add_argument("--context-length", type=int, default=None,
                   help="override model context (max_pages_per_seq)")
    p.add_argument("--quantize", default=None,
                   choices=["int8", "w8a8", "int4"],
                   help="TPU engine quantization: int8 = weight-only "
                        "(half the weight bytes, bf16 MACs); w8a8 adds "
                        "dynamic per-row activation quant on the MXU's "
                        "native int8 path (2x the bf16 pass rate — the "
                        "decode-speed lever on pass-bound batches); "
                        "int4 = packed-nibble W4A8 (a CAPACITY lever: "
                        "~quarter weight bytes at ~10%% slower steps — "
                        "decode on this hardware is pass-bound, not "
                        "HBM-bound)")
    p.add_argument("--draft-model", default=None,
                   help="small checkpoint for speculative decoding")
    p.add_argument("--spec-gamma", type=int, default=4,
                   help="draft tokens proposed per spec iteration")
    p.add_argument("--spec-iters-per-sync", type=int, default=8,
                   help="fused spec iterations per host sync (scales "
                        "burst length and the admission lookahead)")
    p.add_argument("--dllm-block-length", type=int, default=0,
                   help="block-diffusion models (SDAR): tokens a block; "
                        "generation denoises one block at a time and "
                        "attention is causal by blocks of this length. "
                        "Required for such a checkpoint")
    p.add_argument("--dllm-denoising-steps", type=int, default=0,
                   help="forwards that denoise a block before the one "
                        "that commits it (default: the block length, one "
                        "position a step)")
    p.add_argument("--dllm-unmasking-strategy", default="sequential",
                   choices=["sequential", "low_confidence_static"],
                   help="which masked positions a denoising step fixes: "
                        "the leftmost, or those whose best token is most "
                        "probable")
    p.add_argument("--sp-degree", type=int, default=0,
                   help="ring size for sequence-parallel long-prompt "
                        "prefill (0 = off; uses the first N local "
                        "devices)")
    p.add_argument("--sp-threshold", type=int, default=2048,
                   help="min uncached prompt tokens to engage sp prefill")
    p.add_argument("--sp-layout", default="zigzag",
                   choices=["contiguous", "zigzag"])
    p.add_argument("--random-init", action="store_true",
                   help="skip weight load (synthetic benchmarking)")
    mn = p.add_argument_group(
        "multinode", "multi-host engine sharding (MultiNodeConfig analog, "
                     "ref lib/llm/src/engines.rs:28 + trtllm multinode): "
                     "every node runs this CLI with the same leader addr; "
                     "jax.distributed assembles one global device mesh")
    mn.add_argument("--num-nodes", type=int, default=1)
    mn.add_argument("--node-rank", type=int, default=0)
    mn.add_argument("--leader-addr", default=None,
                    help="host:port of node 0's jax coordinator")
    mn.add_argument("--tensor-parallel-size", type=int, default=1,
                    help="tp over the (possibly multi-host) device mesh")
    p.add_argument("--expert-parallel-size", type=int, default=1,
                   help="MoE expert parallelism: an ('ep',) mesh over "
                        "this many local devices — expert stacks shard, "
                        "attention/KV replicate, GSPMD psums the "
                        "combine (Mixtral-family models only)")
    p.add_argument("--pipeline-parallel-size", type=int, default=1,
                   help="GPipe stage count over local devices: layer "
                        "stack + paged KV shard into stage slices "
                        "(models/llama_pp.py; for weights past a TP "
                        "slice's HBM)")
    p.add_argument("--pp-microbatches", type=int, default=0,
                   help="decode lane groups in flight through the pp "
                        "stages (default: the stage count)")
    p.add_argument("--kvbm-host-blocks", type=int, default=0,
                   help="enable the KVBM host tier with this many blocks")
    p.add_argument("--kvbm-offload-queue", type=int, default=None,
                   help="async KVBM pipeline: staging-queue bound in "
                        "blocks for background offload (default: "
                        "DYN_KVBM_OFFLOAD_QUEUE or 0 = inline/sync)")
    p.add_argument("--kvbm-offload-workers", type=int, default=None,
                   help="tier-IO thread pool width (default: "
                        "DYN_KVBM_OFFLOAD_WORKERS or 0 = one thread)")
    p.add_argument("--kvbm-prefetch-blocks", type=int, default=None,
                   help="blocks prefetched per waiting request into the "
                        "staged host buffer (default: "
                        "DYN_KVBM_PREFETCH_BLOCKS or 0 = off)")
    p.add_argument("--kvbm-offload-queue-bytes", type=int, default=None,
                   help="byte bound on the staged offload queue — "
                        "tightens --kvbm-offload-queue when both are set "
                        "(default: DYN_KVBM_OFFLOAD_QUEUE_BYTES or 0 = "
                        "block count only)")
    # mocker knobs
    p.add_argument("--mock-speedup", type=float, default=1.0)
    p.add_argument("--mock-decode-ms", type=float, default=4.0)
    p.add_argument("--mock-total-blocks", type=int, default=1024)
    return p.parse_args(argv)


def build_engine_and_card(args: argparse.Namespace, event_sink, metrics_sink,
                          instance_id: int):
    """(engine, card) per the CLI's engine selection. The engine's
    worker_id must equal the served instance_id: the router keys workers
    by discovered instance_id and KV events/metrics by the engine's
    worker_id — a mismatch silently zeroes KV-aware routing."""
    from dynamo_tpu.llm.model_card import ModelDeploymentCard

    component = args.component + ("_prefill" if args.is_prefill_worker
                                  else "")
    if args.mock:
        from dynamo_tpu.mocker.engine import MockEngine, MockEngineConfig

        name = args.served_model_name or "mock-model"
        card = ModelDeploymentCard(
            name=name, namespace=args.namespace, component=component,
            endpoint=args.endpoint, tokenizer_kind="word",
            tokenizer_path=name, migration_limit=args.migration_limit,
            router_mode=args.router_mode,
            encode_component=args.encode_component)
        engine = MockEngine(
            MockEngineConfig(
                block_size=card.kv_block_size,
                total_kv_blocks=args.mock_total_blocks,
                speedup=args.mock_speedup,
                decode_ms_per_iter=args.mock_decode_ms,
                worker_id=instance_id),
            event_sink=event_sink, metrics_sink=metrics_sink)
        return engine, card
    if args.echo:
        from dynamo_tpu.engines import EchoEngine

        name = args.served_model_name or "echo"
        card = ModelDeploymentCard(
            name=name, namespace=args.namespace, component=component,
            endpoint=args.endpoint, tokenizer_kind="word",
            tokenizer_path=name, migration_limit=args.migration_limit,
            router_mode=args.router_mode)
        return EchoEngine(), card
    if not args.model:
        raise SystemExit("one of --model / --mock / --echo is required")

    from dynamo_tpu.llm.entrypoint import build_tpu_engine

    mesh = None
    if args.expert_parallel_size <= 1 and (
            args.num_nodes > 1 or args.tensor_parallel_size > 1):
        mesh = _multinode_mesh(args)
    if args.expert_parallel_size > 1:
        import jax
        import numpy as np
        from jax.sharding import Mesh

        if args.num_nodes > 1:
            raise SystemExit(
                "--expert-parallel-size is single-host for now")
        devices = jax.devices()
        ep = args.expert_parallel_size
        tp = args.tensor_parallel_size
        need = ep * tp
        if len(devices) < need:
            raise SystemExit(
                f"ep={ep} x tp={tp} needs {need} devices; found "
                f"{len(devices)}")
        if tp > 1:
            # the Mixtral multi-chip shape: experts over ep, attention
            # megatron-sharded over tp
            mesh = Mesh(np.asarray(devices[:need]).reshape(ep, tp),
                        axis_names=("ep", "tp"))
        else:
            mesh = Mesh(np.asarray(devices[:ep]), axis_names=("ep",))
    overrides = {}
    if args.context_length is not None:
        overrides["max_pages_per_seq"] = max(1, args.context_length // 16)
    if args.dllm_block_length:
        overrides["attn_block"] = args.dllm_block_length
    engine, card = build_tpu_engine(
        args.model, served_name=args.served_model_name,
        num_pages=args.num_pages, max_batch_size=args.max_batch_size,
        decode_steps_per_sync=args.decode_steps_per_sync,
        worker_id=instance_id, mesh=mesh,
        random_init=args.random_init,
        kvbm_host_blocks=args.kvbm_host_blocks,
        kvbm_offload_queue=args.kvbm_offload_queue or 0,
        kvbm_offload_workers=args.kvbm_offload_workers or 0,
        kvbm_prefetch_blocks=args.kvbm_prefetch_blocks or 0,
        kvbm_offload_queue_bytes=args.kvbm_offload_queue_bytes or 0,
        quantize=args.quantize, draft_model=args.draft_model,
        spec_gamma=args.spec_gamma,
        spec_iters_per_sync=args.spec_iters_per_sync,
        sp_degree=args.sp_degree, sp_threshold=args.sp_threshold,
        sp_layout=args.sp_layout,
        dllm_denoising_steps=(args.dllm_denoising_steps
                              or args.dllm_block_length),
        dllm_unmasking_strategy=args.dllm_unmasking_strategy,
        pipeline_parallel_size=args.pipeline_parallel_size,
        pp_microbatches=args.pp_microbatches, **overrides)
    if args.is_prefill_worker or args.enable_disagg:
        engine.refuse_if_recurrent(
            "a disaggregated role (a KV export or import)")
    if mesh is not None:
        card.runtime_config.tensor_parallel_size = args.tensor_parallel_size
    engine.config.prefill_chunk = args.prefill_chunk
    card.namespace = args.namespace
    card.component = component
    card.endpoint = args.endpoint
    card.migration_limit = args.migration_limit
    card.router_mode = args.router_mode
    # real-engine cards must carry the encode component too (the mock
    # path sets it at construction) — without it `--encode-component`
    # was silently ignored and image inputs 400'd on real models
    card.encode_component = args.encode_component
    if event_sink is not None or metrics_sink is not None:
        engine.pool.event_sink = event_sink
        engine.metrics_sink = metrics_sink
    return engine, card


class _NullMonitor:
    def start(self):
        return self

    def stop(self):
        pass


class _Stoppable:
    """Adapts a stop coroutine to the extra-handles shutdown protocol."""

    def __init__(self, stop) -> None:
        self._stop = stop

    async def shutdown(self) -> None:
        await self._stop()


async def _build_decode_handler(rt, args, card, engine):
    """Decode-side disagg wiring (vllm main.py init() analog): prefill
    pool clients + threshold router + (optionally) the queue client."""
    from dynamo_tpu.disagg.disagg_router import DisaggRouter
    from dynamo_tpu.disagg.handlers import (
        KV_PULL_ENDPOINT,
        DecodeWorkerHandler,
    )
    from dynamo_tpu.runtime.push import PushRouter

    pf_comp = args.component + "_prefill"
    ns = card.namespace
    pull_client = await (rt.namespace(ns).component(pf_comp)
                         .endpoint(KV_PULL_ENDPOINT).client())
    await pull_client.start()
    dr = await DisaggRouter(
        max_local_prefill_length=args.max_local_prefill_length
    ).start_watch(rt, ns, args.component)
    if args.prefill_queue:
        from dynamo_tpu.disagg.prefill_queue import QueuePrefillClient

        return DecodeWorkerHandler(
            engine, kv_pull_router=PushRouter(pull_client),
            disagg_router=dr,
            prefill_queue_client=QueuePrefillClient(rt, ns,
                                                    queue=pf_comp))
    gen_client = await (rt.namespace(ns).component(pf_comp)
                        .endpoint(args.endpoint).client())
    await gen_client.start()
    return DecodeWorkerHandler(
        engine, prefill_router=PushRouter(gen_client),
        kv_pull_router=PushRouter(pull_client), disagg_router=dr)


def _multinode_mesh(args: argparse.Namespace):
    """Global dp=1 x tp mesh over every chip of every node.

    Multi-host: `jax.distributed.initialize` forms the process group
    (node 0 is the coordinator; ICI/DCN collectives ride the global
    mesh exactly as on one host — the scaling-book recipe, not an
    NCCL/MPI translation). Single-host tp>1 skips the init."""
    import jax

    if args.num_nodes > 1:
        if not args.leader_addr:
            raise SystemExit("--num-nodes > 1 requires --leader-addr")
        jax.distributed.initialize(
            coordinator_address=args.leader_addr,
            num_processes=args.num_nodes,
            process_id=args.node_rank)
    from dynamo_tpu.engine.sharding import make_mesh

    tp = args.tensor_parallel_size
    # honour an explicit jax_default_device pin (the tests pin the CPU)
    default = jax.config.jax_default_device
    devices = (jax.devices(default.platform) if default is not None
               else jax.devices())
    if len(devices) < tp:
        raise SystemExit(
            f"tp={tp} needs {tp} devices; the mesh sees {len(devices)}")
    if args.num_nodes > 1 and tp != len(devices):
        # multi-host SPMD: every process must build the SAME global mesh
        # over ALL chips — a devices[:tp] slice would hand node 1 a mesh
        # of node 0's (non-addressable) devices and crash at the first
        # collective. tp here is the TOTAL across nodes.
        raise SystemExit(
            f"multi-host tp must cover every chip: tp={tp} but the "
            f"global mesh has {len(devices)} devices "
            f"({args.num_nodes} nodes)")
    return make_mesh(dp=1, tp=tp, devices=devices[:tp])


def main(argv=None) -> None:
    args = parse_args(argv)
    setup_logging(args.log_level)
    if args.model:
        from dynamo_tpu.cli_util import enable_compile_cache

        enable_compile_cache()
        if args.num_nodes == 1:
            # open the backend before the runtime takes its lease: the
            # TPU client comes up holding the GIL for longer than the
            # lease TTL on a multi-chip host, and no keepalive gets out.
            # Multi-node leaves it to jax.distributed.initialize, which
            # must come first.
            import jax

            jax.devices()

    async def start():
        from dynamo_tpu.disagg.handlers import (
            PrefillWorkerHandler,
            serve_kv_pull,
        )
        from dynamo_tpu.llm.entrypoint import serve_engine, wire_engine_events
        from dynamo_tpu.llm.model_card import ModelDeploymentCard
        from dynamo_tpu.runtime.distributed import DistributedRuntime
        from dynamo_tpu.worker.monitor import EngineDeathMonitor

        cfg = runtime_config_from_args(args)
        # unset pipeline flags fall back to the layered runtime config
        # (DYN_KVBM_* env / config file) so fleets can flip the pipeline
        # without touching every unit file
        if args.kvbm_offload_queue is None:
            args.kvbm_offload_queue = cfg.kvbm_offload_queue
        if args.kvbm_offload_workers is None:
            args.kvbm_offload_workers = cfg.kvbm_offload_workers
        if args.kvbm_prefetch_blocks is None:
            args.kvbm_prefetch_blocks = cfg.kvbm_prefetch_blocks
        if args.kvbm_offload_queue_bytes is None:
            args.kvbm_offload_queue_bytes = cfg.kvbm_offload_queue_bytes
        rt = await DistributedRuntime.create(cfg)
        if args.encode_worker:
            from dynamo_tpu.multimodal import (
                ImageEncoderConfig,
                serve_encode_worker,
            )

            comp = ("encoder" if args.component == "backend"
                    else args.component)  # default is LM-centric
            served = await serve_encode_worker(
                rt, args.namespace, comp,
                instance_id=args.instance_id,
                cfg=ImageEncoderConfig(
                    vocab_offset=args.image_vocab_offset))
            print(f"WORKER_READY {args.namespace}/{comp}/encode/"
                  f"{served.instance.instance_id:x}", flush=True)

            class _H:  # adapts ServedEndpoint to the handle protocol
                async def stop(self):
                    await served.shutdown()

            return rt, None, _H(), [], _NullMonitor()
        # card needs the final component name before sinks are wired
        probe_component = args.component + (
            "_prefill" if args.is_prefill_worker else "")
        sink_card = ModelDeploymentCard(
            name="_", namespace=args.namespace, component=probe_component)
        event_sink, metrics_sink = wire_engine_events(rt, sink_card)
        instance_id = (args.instance_id if args.instance_id is not None
                       else (os.getpid() << 16 | 1))
        # off the event loop: a real checkpoint loads for longer than the
        # lease TTL, and a blocked loop sends no keepalives — the lease
        # would be gone before the instance registers under it
        engine, card = await asyncio.to_thread(
            build_engine_and_card, args, event_sink, metrics_sink,
            instance_id)
        if hasattr(engine, "device_report"):
            # where the engine's arrays really sit, on the start-up log
            # and (device_info gauge) on this worker's /metrics scrape
            import json

            report = engine.device_report()
            engine.metrics.device_info.set(
                report["count"], platform=report["platform"],
                kind=report["kind"],
                attention_kernels=str(int(report["attention_kernels"])))
            print(f"WORKER_DEVICE {json.dumps(report)}", flush=True)
        extra = []
        serving: object = engine
        if args.is_prefill_worker:
            handler = PrefillWorkerHandler(engine, instance_id)
            serving = handler
            extra.append(await serve_kv_pull(
                rt, card.namespace, card.component, handler, instance_id))
            if args.prefill_queue:
                from dynamo_tpu.disagg.prefill_queue import (
                    PrefillQueueConsumer,
                )

                # queue scoped like the push path's component pool: two
                # models in one namespace must never steal each other's
                # prefill jobs (wrong weights + unpullable KV)
                consumer = PrefillQueueConsumer(
                    rt, handler, card.namespace,
                    queue=card.component).start()
                extra.append(_Stoppable(consumer.stop))
        elif args.enable_disagg:
            serving = await _build_decode_handler(rt, args, card, engine)
        if rt.health is not None:
            # persistent canary failure = wedged-but-alive worker: exit so
            # the lease drops and routers stop sending traffic (same exit
            # contract as the engine-death monitor)
            def _canary_dead(subject: str) -> None:
                logger.error("canary health checks failing for %s; "
                             "exiting so the lease drops", subject)
                os._exit(43)

            rt.health.on_unhealthy = _canary_dead
        if getattr(engine, "kvbm", None) is not None:
            # G4 remote tier: advertise + serve this worker's offloaded
            # blocks and pull peers' at admission
            from dynamo_tpu.kvbm.distributed import KvbmDistributed

            kvbm_dist = KvbmDistributed(
                engine.kvbm, rt, card.namespace, card.component,
                worker_id=instance_id)
            await kvbm_dist.start()
            extra.append(_Stoppable(kvbm_dist.close))
            # pipeline counters → _sys.stats scrape + Prometheus gauges
            rt.wire_kvbm(engine.kvbm)
        handle = await serve_engine(rt, serving, card,
                                    instance_id=instance_id)
        monitor = EngineDeathMonitor(engine)
        monitor.start()
        # dispatch watchdog (None unless DYN_WATCHDOG_STALL_S): a wedged
        # dispatch quarantines this process — deregister, abort streams
        # into Migration, flush KVBM — and exits rc 44 so the supervisor
        # respawns it. hard_exit covers the loop itself being wedged.
        from dynamo_tpu.engine.watchdog import watchdog_from_env

        watchdog = watchdog_from_env(engine, runtime=rt,
                                     instance=f"{instance_id:x}",
                                     hard_exit=True)
        if watchdog is not None:
            from dynamo_tpu.worker.quarantine import quarantine_worker

            def _on_trip(event: dict) -> None:
                asyncio.get_running_loop().create_task(quarantine_worker(
                    rt, handle, engine,
                    reason=f"watchdog: {event.get('cause')}",
                    exit_process=True, watchdog=watchdog))

            watchdog.on_trip = _on_trip
            watchdog.start()

            async def _stop_watchdog():
                watchdog.stop()

            extra.append(_Stoppable(_stop_watchdog))
        print(f"WORKER_READY {card.namespace}/{card.component}/"
              f"{card.endpoint}/{instance_id:x}", flush=True)
        return rt, engine, handle, extra, monitor

    async def stop(objs):
        rt, engine, handle, extra, monitor = objs
        monitor.stop()
        await handle.stop()
        for e in extra:
            await e.shutdown()
        close = getattr(engine, "close", None)
        if close is not None:
            await close()
        await rt.close()

    run_until_signal(start, shutdown=stop)


if __name__ == "__main__":
    main()
