"""Launcher implementation (dynamo-run analog — see package docstring).

Inputs  (in=):  http | text:<prompt> | stdin | batch:<file.jsonl> |
                dyn://<namespace>.<component>.<endpoint> is NOT an input
                here (workers serve via `python -m dynamo_tpu.worker`)
Outputs (out=): echo | mocker | tpu:<model> |
                dyn://<namespace>.<component>.<endpoint>

`out=dyn://...` routes to live remote workers over the runtime store
(`--store`); local outs run fully in-process on a memory store.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from typing import Optional

from dynamo_tpu.cli_util import (
    add_runtime_args,
    runtime_config_from_args,
    setup_logging,
)

USAGE = "python -m dynamo_tpu.run in=<input> out=<engine> [flags]"


def parse_io(argv: list[str]) -> tuple[str, str, list[str]]:
    """Split the positional in=/out= pair from the remaining flags
    (opt.rs parses the same shape)."""
    inp, out = "stdin", "echo"
    rest = []
    for a in argv:
        if a.startswith("in="):
            inp = a[3:]
        elif a.startswith("out="):
            out = a[4:]
        else:
            rest.append(a)
    return inp, out, rest


def parse_args(rest: list[str]) -> argparse.Namespace:
    p = argparse.ArgumentParser(prog="python -m dynamo_tpu.run",
                                usage=USAGE)
    add_runtime_args(p)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--model-name", default="run-model",
                   help="served model name for local engines")
    p.add_argument("--max-tokens", type=int, default=64)
    p.add_argument("--context-length", type=int, default=None)
    p.add_argument("--batch-output", default=None,
                   help="batch mode: output JSONL path (default stdout)")
    p.add_argument("--tokenizer", default="auto",
                   choices=["auto", "word", "byte"],
                   help="override the card's tokenizer (checkpoints "
                        "without tokenizer files: use word/byte)")
    p.add_argument("--router-mode", default="round_robin",
                   choices=["round_robin", "random"],
                   help="dyn:// routing; KV-aware routing needs the full "
                        "frontend (python -m dynamo_tpu.frontend)")
    return p.parse_args(rest)


async def build_local(out: str, args, runtime):
    """(engine, card) for out=echo|mocker|tpu:<model>, served on the
    in-proc runtime so the discovery-driven frontend path works for ALL
    inputs (matching production wiring)."""
    from dynamo_tpu.llm.model_card import ModelDeploymentCard

    if out == "echo":
        from dynamo_tpu.engines import EchoEngine

        card = ModelDeploymentCard(
            name=args.model_name, namespace=args.namespace,
            component="run", tokenizer_kind="word",
            tokenizer_path=args.model_name, router_mode="round_robin")
        return EchoEngine(), card
    if out == "mocker":
        from dynamo_tpu.llm.entrypoint import wire_engine_events
        from dynamo_tpu.mocker.engine import MockEngine, MockEngineConfig

        card = ModelDeploymentCard(
            name=args.model_name, namespace=args.namespace,
            component="run", tokenizer_kind="word",
            tokenizer_path=args.model_name)
        ev, ms = wire_engine_events(runtime, card)
        return MockEngine(MockEngineConfig(speedup=10.0,
                                           default_max_tokens=args.max_tokens),
                          event_sink=ev, metrics_sink=ms), card
    if out.startswith("tpu:") or out == "tpu":
        from dynamo_tpu.cli_util import enable_compile_cache
        from dynamo_tpu.llm.entrypoint import build_tpu_engine

        enable_compile_cache()
        model = out[4:] if out.startswith("tpu:") else args.model_name
        engine, card = build_tpu_engine(model)
        card.namespace = args.namespace
        card.component = "run"
        return engine, card
    raise SystemExit(f"unknown out={out!r}; expected echo|mocker|"
                     f"tpu:<model>|dyn://ns.comp.endpoint")


async def connect_remote(out: str, args, runtime):
    """out=dyn://ns.component.endpoint → a router over live instances
    plus a pipeline card (tokenization happens HERE, so the card's
    tokenizer must match the remote model — resolved from the remote's
    published MDC when one exists)."""
    from dynamo_tpu.llm.model_card import MDC_PREFIX, ModelDeploymentCard
    from dynamo_tpu.runtime.push import PushRouter

    spec = out[len("dyn://"):]
    try:
        ns, comp, ep = spec.split(".", 2)
    except ValueError:
        raise SystemExit(f"bad dyn:// target {out!r}: want "
                         "dyn://namespace.component.endpoint") from None
    card: Optional[ModelDeploymentCard] = None
    for kv in await runtime.store.get_prefix(f"{MDC_PREFIX}{ns}/{comp}/"):
        card = ModelDeploymentCard.from_json(kv.value)
        break
    if card is None:  # no published card: assume word-tokenizer echo-style
        card = ModelDeploymentCard(name=args.model_name, namespace=ns,
                                   component=comp, endpoint=ep,
                                   tokenizer_kind="word",
                                   tokenizer_path=args.model_name)
    client = await (runtime.namespace(ns).component(comp)
                    .endpoint(ep).client())
    await client.start()
    await client.wait_ready()
    return PushRouter(client, mode=args.router_mode), card


def build_pipeline_for(card, sink_engine, args):
    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.llm.tokenizer import make_tokenizer
    from dynamo_tpu.runtime.engine import build_pipeline

    kind, tpath = card.tokenizer_kind, card.tokenizer_path
    if args.tokenizer != "auto":
        kind, tpath = args.tokenizer, card.name
    tok = make_tokenizer(kind, tpath)
    pre = OpenAIPreprocessor(
        tok, card.name,
        context_length=args.context_length or card.context_length,
        default_max_tokens=args.max_tokens,
        tool_call_parser=card.tool_call_parser,
        reasoning_parser=card.reasoning_parser)
    return build_pipeline(pre, Backend(tok), sink=sink_engine)


async def run_one(pipeline, model: str, prompt: str, max_tokens: int,
                  stream_out=None) -> str:
    """One chat turn through the pipeline; returns the full text."""
    from dynamo_tpu.runtime.context import Context

    req = {"_kind": "chat", "body": {
        "model": model, "stream": True, "max_tokens": max_tokens,
        "messages": [{"role": "user", "content": prompt}]}}
    parts = []
    async for chunk in pipeline.generate(req, Context()):
        for ch in chunk.get("choices", ()):
            t = ch.get("delta", {}).get("content")
            if t:
                parts.append(t)
                if stream_out is not None:
                    stream_out.write(t)
                    stream_out.flush()
    if stream_out is not None:
        stream_out.write("\n")
    return "".join(parts)


async def run_batch(pipeline, model: str, path: str, max_tokens: int,
                    out_path: Optional[str]) -> int:
    """batch:<file.jsonl> — one {"text": ...} or {"messages": [...]} per
    line; outputs JSONL with the response and timing (Input::Batch)."""
    from dynamo_tpu.runtime.context import Context

    async def one(i: int, d: dict) -> dict:
        msgs = d.get("messages") or [
            {"role": "user", "content": d.get("text", d.get("prompt", ""))}]
        req = {"_kind": "chat", "body": {
            "model": model, "stream": True,
            "max_tokens": int(d.get("max_tokens") or max_tokens),
            "messages": msgs}}
        t0 = time.perf_counter()
        parts = []
        finish = None
        async for chunk in pipeline.generate(req, Context()):
            for ch in chunk.get("choices", ()):
                t = ch.get("delta", {}).get("content")
                if t:
                    parts.append(t)
                if ch.get("finish_reason"):
                    finish = ch["finish_reason"]
        return {"index": i, "text": "".join(parts),
                "finish_reason": finish,
                "elapsed_s": round(time.perf_counter() - t0, 4)}

    with open(path, encoding="utf-8") as f:
        jobs = [json.loads(line) for line in f if line.strip()]
    results = await asyncio.gather(*(one(i, d) for i, d in enumerate(jobs)))
    sink = open(out_path, "w", encoding="utf-8") if out_path else sys.stdout
    try:
        for r in sorted(results, key=lambda r: r["index"]):
            sink.write(json.dumps(r) + "\n")
    finally:
        if out_path:
            sink.close()
    return len(results)


async def amain(inp: str, out: str, args) -> None:
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    remote = out.startswith("dyn://")
    cfg = runtime_config_from_args(args)
    if not remote:
        cfg.store_url = "memory"  # fully local run
    if inp == "http" and remote:
        raise SystemExit(
            "in=http out=dyn:// — run python -m dynamo_tpu.frontend "
            "against the shared store instead")
    runtime = await DistributedRuntime.create(cfg)
    engine_handle = None
    try:
        if remote:
            sink, card = await connect_remote(out, args, runtime)
        else:
            engine, card = await build_local(out, args, runtime)
            if inp == "http":
                # production shape: serve the engine, let discovery build
                # the frontend pipeline
                from dynamo_tpu.llm.entrypoint import serve_engine

                engine_handle = await serve_engine(runtime, engine, card)
                sink = None
            else:
                sink = engine

        if inp == "http":
            from dynamo_tpu.llm.entrypoint import start_frontend

            fe = await start_frontend(runtime, host=args.host,
                                      port=args.port)
            print(f"RUN_READY {fe.url}", flush=True)
            await runtime.wait_shutdown()
            await fe.stop()
            return

        pipeline = build_pipeline_for(card, sink, args)
        if inp.startswith("text:") or inp == "text":
            prompt = inp[5:] if inp.startswith("text:") else ""
            if not prompt:
                raise SystemExit("in=text:<prompt> needs a prompt")
            await run_one(pipeline, card.name, prompt, args.max_tokens,
                          stream_out=sys.stdout)
        elif inp.startswith("batch:"):
            n = await run_batch(pipeline, card.name, inp[6:],
                                args.max_tokens, args.batch_output)
            print(f"BATCH_DONE {n}", file=sys.stderr, flush=True)
        elif inp == "stdin":
            import threading

            # a DAEMON reader thread: run_in_executor's worker would pin
            # interpreter shutdown on a blocked readline after Ctrl-C.
            # Bounded queue + blocking put = backpressure (a piped file
            # must not slurp into memory while generations run 1-by-1).
            loop = asyncio.get_running_loop()
            lines: asyncio.Queue = asyncio.Queue(maxsize=64)

            def reader():
                try:
                    for line in sys.stdin:
                        asyncio.run_coroutine_threadsafe(
                            lines.put(line), loop).result()
                    asyncio.run_coroutine_threadsafe(
                        lines.put(None), loop).result()
                except RuntimeError:
                    pass  # loop closed mid-read: just exit the thread

            threading.Thread(target=reader, daemon=True).start()
            while True:
                line = await lines.get()
                if line is None:
                    break
                prompt = line.strip()
                if not prompt:
                    continue
                await run_one(pipeline, card.name, prompt,
                              args.max_tokens, stream_out=sys.stdout)
        else:
            raise SystemExit(f"unknown in={inp!r}; expected "
                             "http|text:<prompt>|stdin|batch:<file>")
    finally:
        if engine_handle is not None:
            await engine_handle.stop()
        close = getattr(locals().get("sink"), "close", None)
        if close is not None and not remote:
            await close()
        await runtime.close()


def main(argv: Optional[list[str]] = None) -> None:
    inp, out, rest = parse_io(list(argv if argv is not None
                                   else sys.argv[1:]))
    args = parse_args(rest)
    setup_logging(args.log_level)
    try:
        asyncio.run(amain(inp, out, args))
    except KeyboardInterrupt:
        pass
