"""OpenAI-compatible HTTP frontend (aiohttp, the axum analog).

Reference: `lib/llm/src/http/service/` — `/v1/chat/completions`
(openai.rs:540), `/v1/completions` (:274), `/v1/models`, health routes,
SSE streaming with client-disconnect detection (service/disconnect.rs:
dropping the connection cancels the request context mid-stream), and
HTTP metrics with TTFT/ITL histograms (service/metrics.rs:109-262).
"""

from __future__ import annotations

import asyncio
import logging
import time
from collections import deque
from typing import Optional

from aiohttp import web

from dynamo_tpu.llm.model_manager import ModelManager
from dynamo_tpu.llm.preprocessor import (
    KIND_CHAT,
    KIND_COMPLETION,
    KIND_EMBEDDING,
    KIND_RESPONSES,
)
from dynamo_tpu.llm.protocols_openai import (
    OpenAIError,
    SSE_DONE,
    aggregate_chat_stream,
    aggregate_completion_stream,
    aggregate_responses_stream,
    new_request_id,
    sse_encode,
    sse_encode_event,
)
from dynamo_tpu.runtime.context import (
    FRONTEND_OUT,
    HTTP_PARSE,
    HTTP_RECV,
    Context,
)
from dynamo_tpu.runtime.stages import FRONTEND_LEG, FRONTEND_SPANS


class _AuditTap:
    """Engine wrapper that accumulates the response into an AuditRecord
    and publishes it at stream end (audit/stream.rs analog). Items pass
    through untouched; publish() is non-blocking."""

    def __init__(self, inner, rec, bus) -> None:
        self.inner = inner
        self.rec = rec
        self.bus = bus

    async def generate(self, request, context):
        import time as _t

        try:
            async for item in self.inner.generate(request, context):
                for ch in item.get("choices", ()):
                    delta = ch.get("delta", {})
                    if delta.get("content"):
                        self.rec.response_text += delta["content"]
                    elif ch.get("text"):
                        self.rec.response_text += ch["text"]
                    # tool calls are the most audit-sensitive output
                    # (model-initiated actions) — never drop them
                    if delta.get("tool_calls"):
                        self.rec.tool_calls.extend(delta["tool_calls"])
                    if delta.get("reasoning_content"):
                        self.rec.reasoning_text += \
                            delta["reasoning_content"]
                    if ch.get("finish_reason"):
                        self.rec.finish_reason = ch["finish_reason"]
                if item.get("usage"):
                    self.rec.usage = item["usage"]
                yield item
        except BaseException as e:
            self.rec.error = repr(e)
            raise
        finally:
            self.rec.finished_at = _t.time()
            self.bus.publish(self.rec)

logger = logging.getLogger(__name__)


class HttpService:
    def __init__(self, manager: ModelManager, host: str = "127.0.0.1",
                 port: int = 0, tls_cert: Optional[str] = None,
                 tls_key: Optional[str] = None, audit=None,
                 request_template: Optional[dict] = None) -> None:
        self.manager = manager
        self.host = host
        self.port = port
        # defaults applied to requests that omit them (request_template.rs:
        # model, temperature, max_completion_tokens)
        self.request_template = request_template or {}
        self._audit_owned = audit is None
        if audit is None:
            from dynamo_tpu.llm.audit import audit_bus_from_env

            audit = audit_bus_from_env()
        self.audit = audit  # AuditBus or None
        if bool(tls_cert) != bool(tls_key):
            # half-configured TLS must not silently serve plaintext
            raise ValueError("tls_cert and tls_key must be set together")
        self.tls_cert = tls_cert
        self.tls_key = tls_key
        self.app = web.Application()
        self.app.add_routes([
            web.post("/v1/chat/completions", self._chat),
            web.post("/v1/completions", self._completions),
            web.post("/v1/embeddings", self._embeddings),
            web.post("/v1/responses", self._responses),
            web.get("/v1/models", self._models),
            web.post("/clear_kv_blocks", self._clear_kv_blocks),
            web.get("/kvbm/status", self._kvbm_status),
            web.post("/kvbm/reset", self._kvbm_reset),
            web.get("/health", self._health),
            web.get("/live", self._live),
            web.get("/metrics", self._metrics),
            web.get("/fleet/status", self._fleet_status),
            web.get("/debug", self._debug_index),
            web.get("/debug/requests", self._debug_requests),
            web.get("/debug/profile", self._debug_profile),
            web.get("/debug/router", self._debug_router),
            web.get("/debug/kv", self._debug_kv),
            web.get("/debug/memory", self._debug_memory),
            web.get("/debug/mesh", self._debug_mesh),
            web.get("/debug/control", self._debug_control),
            web.get("/debug/tenants", self._debug_tenants),
            web.get("/debug/classes", self._debug_classes),
            web.get("/debug/prefixes", self._debug_prefixes),
            web.get("/openapi.json", self._openapi),
        ])
        # Tenancy quota plane (dynamo_tpu/tenancy, docs/multitenancy.md):
        # None unless DYN_TENANCY — over-quota requests 429 with
        # Retry-After HERE, before any engine work, and the resolved
        # tenant rides ctx.headers[x-dyn-tenant] to the workers so the
        # fair scheduler and every recorder attribute by the same name.
        from dynamo_tpu.tenancy import tenancy_from_env

        self.tenancy = tenancy_from_env()
        self.quota = None
        if self.tenancy is not None:
            from dynamo_tpu.tenancy import QuotaGate, TenantMetrics

            tm = TenantMetrics()
            tm.register(manager.runtime.metrics, role="frontend")
            self.quota = QuotaGate(self.tenancy, tm)
        # Serving-class plane (dynamo_tpu/serving_classes,
        # docs/robustness.md): None unless DYN_CLASSES — brownout-shed,
        # token-capped, and deadline-infeasible requests are bounced (or
        # downgraded) HERE, before any engine work, and the resolved
        # class rides ctx.headers[x-dyn-class] to the workers.
        # start_frontend wires the brownout machine and the admission
        # estimator once the engine supplier exists.
        from dynamo_tpu.serving_classes import classes_from_env

        self.classes = classes_from_env()
        self.class_metrics = None
        self.brownout = None               # BrownoutMachine | None
        self.admission = None              # AdmissionEstimator | None
        if self.classes is not None:
            from dynamo_tpu.serving_classes import ClassMetrics

            self.class_metrics = ClassMetrics()
            self.class_metrics.register(manager.runtime.metrics)
        # request-lifecycle debug view: in-flight dicts keyed by request
        # id plus a bounded ring of finished ones, served verbatim by
        # /debug/requests (per-stage timings, status, trace id)
        self._dbg_inflight: dict[str, dict] = {}
        self._dbg_recent: deque = deque(maxlen=128)
        self._runner: Optional[web.AppRunner] = None
        m = manager.runtime.metrics.child("http")
        self._req_counter = m.counter(
            "requests_total", "HTTP requests by endpoint/status")
        self._inflight = m.gauge("inflight_requests", "streams in flight")
        self._ttft = m.histogram(
            "time_to_first_token_seconds", "TTFT",
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                     5.0, 10.0, 30.0))
        self._itl = m.histogram(
            "inter_token_latency_seconds", "ITL",
            buckets=(0.0001, 0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0))
        self._duration = m.histogram(
            "request_duration_seconds", "total request duration")
        # ISL/OSL from the pipeline's final-chunk usage: the SLA planner
        # scrapes these to predict load (planner_core.py observe_metrics)
        self._isl = m.histogram(
            "request_input_tokens", "prompt tokens per request",
            buckets=(16, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384))
        self._osl = m.histogram(
            "request_output_tokens", "completion tokens per request",
            buckets=(1, 4, 16, 64, 128, 256, 512, 1024, 2048, 4096))
        # Fleet telemetry plane (docs/observability.md "Fleet view"):
        # start_frontend injects the TelemetryCollector's fleet_status
        # callable and, when SLO objectives are configured, the
        # SloMonitor that the TTFT/ITL observation points feed.
        self.fleet_status_provider = None  # Callable[[], dict] | None
        self.slo = None                    # SloMonitor | None
        # Step-profiler surface (engine/profiler.py): in-proc
        # deployments (run/main.py, bench, tests) wire a callable
        # returning the local engine objects so /debug/profile can read
        # their StepRecorder rings. None on frontend-only processes.
        self.profile_engines = None        # Callable[[], list] | None
        # Flight-control plane (dynamo_tpu/control): start_frontend wires
        # the armed ControlPlane here when DYN_CONTROL enables any
        # controller; None (the default) keeps /debug/control a 503.
        self.control_plane = None          # ControlPlane | None

    def _observe_latency(self, kind: str, seconds: float,
                         cls: Optional[str] = None) -> None:
        """One TTFT/ITL sample into both the histogram and (when
        configured) the SLO monitor's rolling windows. With a class
        name, the sample also feeds the per-class objective window
        ("ttft:interactive" etc — the monitor ignores names it has no
        objective for)."""
        (self._ttft if kind == "ttft" else self._itl).observe(seconds)
        if self.slo is not None:
            self.slo.observe(kind, seconds)
            if cls:
                self.slo.observe(f"{kind}:{cls}", seconds)

    def _observe_usage(self, usage: Optional[dict]) -> None:
        if not usage:
            return
        if usage.get("prompt_tokens") is not None:
            self._isl.observe(usage["prompt_tokens"])
        if usage.get("completion_tokens") is not None:
            self._osl.observe(usage["completion_tokens"])

    @property
    def scheme(self) -> str:
        return "https" if self.tls_cert else "http"

    def _apply_template(self, body: dict) -> None:
        t = self.request_template
        if not t:
            return
        if not body.get("model") and t.get("model"):
            body["model"] = t["model"]
        if body.get("temperature") is None and \
                t.get("temperature") is not None:
            body["temperature"] = t["temperature"]
        if body.get("max_tokens") is None \
                and body.get("max_completion_tokens") is None \
                and t.get("max_completion_tokens") is not None:
            body["max_tokens"] = t["max_completion_tokens"]

    def _tenant_gate(self, request: web.Request, body,
                     endpoint: str):
        """Resolve tenant identity and enforce quotas BEFORE any engine
        work. Returns (tenant_name, None) when admitted — the caller
        owes exactly one `quota.release(tenant_name)` — or
        (tenant_name, 429 response) when over quota. (None, None) when
        tenancy is unarmed."""
        if self.quota is None:
            return None, None
        from dynamo_tpu.tenancy import (estimate_request_tokens,
                                        retry_after_header)
        from dynamo_tpu.tenancy.config import TENANT_HEADER

        tenant = self.tenancy.resolve(
            request.headers.get(TENANT_HEADER),
            request.headers.get("Authorization"))
        tokens = estimate_request_tokens(
            body if isinstance(body, dict) else {})
        ok, reason, retry = self.quota.try_admit(tenant, tokens)
        if ok:
            return tenant.name, None
        self._req_counter.inc(endpoint=endpoint, status="429")
        if self.class_metrics is not None:
            # shed load must show in the fleet picture next to served
            # load — 429s land in rejections{reason="quota", class}
            from dynamo_tpu.serving_classes.config import CLASS_HEADER

            cls_name = self.classes.resolve(
                request.headers.get(CLASS_HEADER), tenant).name
            self.class_metrics.on_rejected("quota", cls_name)
        err = OpenAIError(
            f"tenant {tenant.name!r} over {reason} quota",
            status=429, err_type="rate_limit_exceeded")
        return tenant.name, web.json_response(
            err.body(), status=429,
            headers={"Retry-After": retry_after_header(retry)})

    def _class_gate(self, request: web.Request, body,
                    endpoint: str, tenant: Optional[str]):
        """Resolve the serving class and apply brownout shed / token
        cap / deadline-feasibility BEFORE any engine work
        (docs/robustness.md "Serving classes & brownout"). Returns
        (cls_name, downgraded_from, reject_response); (None, "", None)
        when classes are unarmed. May mutate body["max_tokens"] (the
        stage-2 cap on new streams)."""
        if self.classes is None:
            return None, "", None
        from dynamo_tpu.runtime.transport import DEADLINE_HEADER
        from dynamo_tpu.serving_classes.config import CLASS_HEADER
        from dynamo_tpu.tenancy import retry_after_header

        tenant_rec = (self.tenancy.get(tenant)
                      if self.tenancy is not None and tenant else None)
        cls = self.classes.resolve(
            request.headers.get(CLASS_HEADER), tenant_rec)

        def _shed(c):
            self._req_counter.inc(endpoint=endpoint, status="503")
            if self.class_metrics is not None:
                self.class_metrics.on_shed(c.name, reason="brownout")
            err = OpenAIError(
                f"class {c.name!r} shed: fleet in brownout stage "
                f"{self.brownout.state()['stage_name']!r}",
                status=503, err_type="overloaded")
            return c.name, "", web.json_response(
                err.body(), status=503,
                headers={"Retry-After":
                         retry_after_header(self.brownout.recover_s)})

        # brownout shed ladder: stage >= the class's shed_stage bounces
        # new requests with Retry-After sized to the recovery window
        if self.brownout is not None and self.brownout.sheds(cls):
            return _shed(cls)
        # deadline feasibility: explicit remaining-budget header wins,
        # else the class's implicit deadline; 0 = no deadline
        explicit = 0.0
        hdr = request.headers.get(DEADLINE_HEADER)
        if hdr:
            try:
                explicit = float(hdr)
            except ValueError:
                explicit = 0.0
        budget = explicit if explicit > 0 else cls.deadline_s
        downgraded_from = ""
        if budget > 0 and self.admission is not None:
            feasible, est, retry = self.admission.check(budget)
            if not feasible:
                if explicit <= 0 and cls.downgrade_to:
                    # only the class-implicit deadline is unmeetable:
                    # demote to the looser class instead of bouncing —
                    # the client finds out via x-dyn-class-downgraded
                    downgraded_from = cls.name
                    if self.class_metrics is not None:
                        self.class_metrics.on_downgraded(cls.name)
                    cls = self.classes.get(cls.downgrade_to)
                    if self.brownout is not None \
                            and self.brownout.sheds(cls):
                        return _shed(cls)
                else:
                    self._req_counter.inc(endpoint=endpoint,
                                          status="503")
                    if self.class_metrics is not None:
                        self.class_metrics.on_deadline_rejected(cls.name)
                    err = OpenAIError(
                        f"deadline unmeetable: estimated TTFT "
                        f"{est:.3f}s exceeds remaining budget "
                        f"{budget:.3f}s", status=503,
                        err_type="deadline_unmeetable")
                    return cls.name, "", web.json_response(
                        err.body(), status=503,
                        headers={"Retry-After":
                                 retry_after_header(retry)})
        # stage-2 brownout: cap completion budget on new streams of
        # cappable classes (running streams are never touched)
        if self.brownout is not None and isinstance(body, dict):
            cap = self.brownout.cap_for(cls)
            if cap > 0:
                cur = (body.get("max_tokens")
                       or body.get("max_completion_tokens") or 0)
                if not cur or cur > cap:
                    body["max_tokens"] = cap
        if self.class_metrics is not None:
            self.class_metrics.on_admitted(cls.name)
        return cls.name, downgraded_from, None

    def _audit_begin(self, request_id: str, endpoint: str, body):
        if self.audit is None:
            return None
        from dynamo_tpu.llm.audit import AuditRecord

        return AuditRecord(request_id=request_id, endpoint=endpoint,
                           model=(body or {}).get("model", ""),
                           request=body)

    # -- lifecycle ----------------------------------------------------------

    async def start(self) -> tuple[str, int]:
        ssl_ctx = None
        if self.tls_cert and self.tls_key:
            import ssl

            ssl_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ssl_ctx.load_cert_chain(self.tls_cert, self.tls_key)
        self._runner = web.AppRunner(self.app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port,
                           ssl_context=ssl_ctx)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]  # type: ignore
        logger.info("HTTP frontend on %s://%s:%d",
                    "https" if ssl_ctx else "http", self.host, self.port)
        return self.host, self.port

    async def stop(self) -> None:
        # handlers first (their _AuditTap finallys publish), THEN the bus;
        # a caller-injected bus may be shared — never close it here
        if self._runner is not None:
            await self._runner.cleanup()
        if self.audit is not None and self._audit_owned:
            await self.audit.close()

    # -- handlers -----------------------------------------------------------

    async def _chat(self, request: web.Request) -> web.StreamResponse:
        return await self._serve_openai(request, KIND_CHAT)

    async def _completions(self, request: web.Request) -> web.StreamResponse:
        return await self._serve_openai(request, KIND_COMPLETION)

    async def _embeddings(self, request: web.Request) -> web.StreamResponse:
        """/v1/embeddings (openai.rs:1125): unary only — the pipeline
        yields exactly one response object."""
        try:
            body = await request.json()
        except Exception:
            return self._error("embeddings", OpenAIError("invalid JSON body"))
        model = body.get("model") if isinstance(body, dict) else None
        engine = self.manager.engine_for(model) if model else None
        if engine is None:
            return self._error("embeddings", OpenAIError(
                f"model {model!r} not found", status=404,
                err_type="model_not_found"))
        tenant, reject = self._tenant_gate(request, body, "embeddings")
        if reject is not None:
            return reject
        ctx = Context(request_id=new_request_id("embd"))
        if tenant is not None:
            from dynamo_tpu.tenancy.config import TENANT_HEADER

            ctx.headers[TENANT_HEADER] = tenant
        start = time.perf_counter()
        self._inflight.add(1)
        try:
            out = None
            async for item in engine.generate(
                    {"_kind": KIND_EMBEDDING, "body": body}, ctx):
                out = item
            self._req_counter.inc(endpoint="embeddings", status="200")
            self._duration.observe(time.perf_counter() - start)
            return web.json_response(out)
        except OpenAIError as e:
            return self._error("embeddings", e)
        except asyncio.CancelledError:
            ctx.cancel()  # client disconnected: stop downstream work
            self._req_counter.inc(endpoint="embeddings", status="disconnect")
            raise
        finally:
            self._inflight.add(-1)
            if tenant is not None:
                self.quota.release(tenant)

    async def _responses(self, request: web.Request) -> web.StreamResponse:
        """/v1/responses (openai.rs:766): typed-event SSE or unary fold."""
        try:
            body = await request.json()
        except Exception:
            return self._error("responses", OpenAIError("invalid JSON body"))
        model = body.get("model") if isinstance(body, dict) else None
        engine = self.manager.engine_for(model) if model else None
        if engine is None:
            return self._error("responses", OpenAIError(
                f"model {model!r} not found", status=404,
                err_type="model_not_found"))
        tenant, reject = self._tenant_gate(request, body, "responses")
        if reject is not None:
            return reject
        request_id = new_request_id("resp")
        ctx = Context(request_id=request_id)
        if tenant is not None:
            from dynamo_tpu.tenancy.config import TENANT_HEADER

            ctx.headers[TENANT_HEADER] = tenant
        events = engine.generate(
            {"_kind": KIND_RESPONSES, "body": body,
             "request_id": request_id}, ctx)
        start = time.perf_counter()
        self._inflight.add(1)
        try:
            if not body.get("stream"):
                try:
                    full = await aggregate_responses_stream(events)
                except OpenAIError as e:
                    return self._error("responses", e)
                except asyncio.CancelledError:
                    ctx.cancel()  # client disconnected mid-aggregation
                    self._req_counter.inc(endpoint="responses",
                                          status="disconnect")
                    raise
                self._req_counter.inc(endpoint="responses", status="200")
                self._duration.observe(time.perf_counter() - start)
                self._observe_usage_responses(full.get("usage"))
                return web.json_response(full)
            resp = web.StreamResponse(headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
            })
            first_token_at: Optional[float] = None
            last_token_at: Optional[float] = None
            try:
                async for ev in events:
                    if ev.get("type") == "response.output_text.delta":
                        now = time.perf_counter()
                        if first_token_at is None:
                            first_token_at = now
                            self._observe_latency("ttft", now - start)
                            if self.quota is not None and tenant:
                                self.quota.metrics.observe_ttft(
                                    tenant, now - start)
                        elif last_token_at is not None:
                            self._observe_latency("itl", now - last_token_at)
                        last_token_at = now
                    elif ev.get("type") == "response.completed":
                        self._observe_usage_responses(
                            (ev.get("response") or {}).get("usage"))
                    if not resp.prepared:
                        await resp.prepare(request)
                    await resp.write(sse_encode_event(
                        ev.get("type", "message"), ev))
                self._req_counter.inc(endpoint="responses", status="200")
            except OpenAIError as e:
                if not resp.prepared:
                    return self._error("responses", e)
                await resp.write(sse_encode(e.body()))
            except (ConnectionResetError, asyncio.CancelledError):
                ctx.cancel()
                self._req_counter.inc(endpoint="responses",
                                      status="disconnect")
                raise
            finally:
                self._duration.observe(time.perf_counter() - start)
            await resp.write_eof()
            return resp
        finally:
            self._inflight.add(-1)
            if tenant is not None:
                self.quota.release(tenant)

    def _observe_usage_responses(self, usage: Optional[dict]) -> None:
        if not usage:
            return
        if usage.get("input_tokens") is not None:
            self._isl.observe(usage["input_tokens"])
        if usage.get("output_tokens") is not None:
            self._osl.observe(usage["output_tokens"])

    async def _fanout_admin(self, endpoint: str, payload: dict) -> dict:
        """Send one admin request to every instance of every served
        model's `endpoint`; per-instance results keyed by model."""
        from dynamo_tpu.runtime.push import PushRouter

        results: dict[str, dict] = {}
        for name in self.manager.model_names():
            entry = self.manager.get(name)
            if entry is None:
                continue
            card = entry.card
            client = await (self.manager.runtime.namespace(card.namespace)
                            .component(card.component)
                            .endpoint(endpoint).client())
            await client.start()
            router = PushRouter(client)
            per_instance: dict[str, object] = {}
            try:
                for inst in client.instances():
                    try:
                        async for out in router.direct(
                                payload, inst.instance_id, Context()):
                            per_instance[f"{inst.instance_id:x}"] = out
                    except Exception as e:  # instance died mid-call
                        per_instance[f"{inst.instance_id:x}"] = {
                            "status": "error", "error": str(e)}
            finally:
                await client.stop()
            results[name] = per_instance
        return results

    async def _clear_kv_blocks(self, request: web.Request) -> web.Response:
        """Admin route (service/clear_kv_blocks.rs): tell every worker
        instance of every served model to drop its reusable KV cache."""
        results = await self._fanout_admin("clear_kv_blocks", {})
        return web.json_response({"status": "success", "results": results})

    async def _kvbm_status(self, request: web.Request) -> web.Response:
        """KVBM controller status (block_manager/controller.rs
        ControlMessage::Status): per-tier occupancy, offload/onboard
        stats, and the async pipeline counters (queue depth, staged
        bytes, prefetch hits, admission_stall_ms — docs/kvbm.md) from
        every worker running a KVBM manager. Workers without KVBM simply
        expose no kvbm_controller endpoint and are absent."""
        results = await self._fanout_admin("kvbm_controller",
                                           {"op": "status"})
        return web.json_response({"status": "success", "results": results})

    async def _kvbm_reset(self, request: web.Request) -> web.Response:
        """KVBM controller reset (ControlMessage::ResetPool/ResetAll):
        body {"level": "g1"|"g2"|"g3"|"all"} (default all)."""
        try:
            body = await request.json()
        except Exception:
            body = {}
        level = (body or {}).get("level", "all")
        results = await self._fanout_admin(
            "kvbm_controller", {"op": "reset", "level": level})
        return web.json_response({"status": "success", "results": results})

    async def _serve_openai(self, request: web.Request,
                            kind: str) -> web.StreamResponse:
        recv_ns = time.time_ns()  # the stage clock's first stamp
        endpoint = ("chat_completions" if kind == KIND_CHAT
                    else "completions")
        try:
            body = await request.json()
        except Exception:
            return self._error(endpoint, OpenAIError("invalid JSON body"))
        if isinstance(body, dict):
            self._apply_template(body)
        model = body.get("model") if isinstance(body, dict) else None
        engine = self.manager.engine_for(model) if model else None
        if engine is None:
            return self._error(endpoint, OpenAIError(
                f"model {model!r} not found", status=404,
                err_type="model_not_found"))
        # quota gate before ANY engine work: over-quota tenants cost
        # the fleet one dict lookup and a 429, nothing downstream
        tenant, reject = self._tenant_gate(request, body, endpoint)
        if reject is not None:
            return reject
        # class gate after the quota gate: shed/deadline-infeasible
        # requests cost one histogram read and a 503, nothing downstream
        cls, downgraded_from, reject = self._class_gate(
            request, body, endpoint, tenant)
        if reject is not None:
            return reject
        stream = bool(body.get("stream"))
        request_id = new_request_id(
            "chatcmpl" if kind == KIND_CHAT else "cmpl")
        ctx = Context(request_id=request_id, stages={HTTP_RECV: recv_ns})
        if tenant is not None:
            from dynamo_tpu.tenancy.config import TENANT_HEADER

            ctx.headers[TENANT_HEADER] = tenant
        if cls is not None:
            from dynamo_tpu.serving_classes.config import CLASS_HEADER

            # post-resolution (and post-downgrade) identity: engines
            # attribute fair-share accounting by this header
            ctx.headers[CLASS_HEADER] = cls
        from dynamo_tpu.runtime.tracing import tracer

        pipeline_request = {"_kind": kind, "body": body,
                            "request_id": request_id}
        audit_rec = self._audit_begin(request_id, endpoint, body)
        if audit_rec is not None:
            # capture deltas without perturbing the stream; the record is
            # published (off hot path) when the stream finishes
            engine = _AuditTap(engine, audit_rec, self.audit)
        ctx.stamp(HTTP_PARSE)  # where the time to the first token starts
        start = time.perf_counter()
        self._inflight.add(1)
        # request span (make_request_span analog): honors an incoming W3C
        # traceparent header; the span is current for this handler task,
        # so downstream transport hops inherit the trace; entered right
        # at the try so no exception path can leak it as current. It
        # starts at the handler's first statement, so the stage spans
        # emitted under it (`_first_frame_out`) lie inside it.
        span = tracer().start_span(
            f"http {endpoint}",
            traceparent=request.headers.get("traceparent"),
            attributes={"http.target": request.path,
                        "request.id": request_id, "model": model})
        span.start_ns = recv_ns
        span.__enter__()
        rec = {"request_id": request_id, "endpoint": endpoint,
               "model": model, "stream": stream, "tenant": tenant,
               "received_at": time.time(),
               "trace_id": span.trace_id if tracer().enabled else None,
               "status": "in_flight", "first_token_s": None,
               "last_token_s": None, "duration_s": None, "usage": None}
        if cls is not None:
            rec["class"] = cls
            if downgraded_from:
                rec["downgraded_from"] = downgraded_from
        self._dbg_inflight[request_id] = rec
        try:
            chunks = engine.generate(pipeline_request, ctx)
            if stream:
                return await self._stream_sse(
                    request, endpoint, chunks, ctx, start, rec, span)
            # unary: aggregate the stream
            try:
                full = await (aggregate_chat_stream(chunks)
                              if kind == KIND_CHAT
                              else aggregate_completion_stream(chunks))
            except OpenAIError as e:
                rec["status"] = f"error:{e.status}"
                return self._error(endpoint, e)
            except asyncio.CancelledError:
                # client disconnected mid-aggregation: stop downstream work
                ctx.cancel()
                rec["status"] = "disconnect"
                self._req_counter.inc(endpoint=endpoint, status="disconnect")
                raise
            self._req_counter.inc(endpoint=endpoint, status="200")
            self._duration.observe(time.perf_counter() - start)
            self._observe_usage(full.get("usage"))
            rec["status"] = "200"
            rec["usage"] = full.get("usage")
            return web.json_response(full)
        except BaseException as e:
            span.record_error(e)
            if rec["status"] == "in_flight":
                rec["status"] = "error"
            raise
        finally:
            span.end(_reset=True)
            self._inflight.add(-1)
            if tenant is not None:
                self.quota.release(tenant)
            rec["duration_s"] = round(time.perf_counter() - start, 6)
            self._dbg_inflight.pop(request_id, None)
            self._dbg_recent.append(rec)

    def _first_frame_out(self, ctx: Context, rec: dict, span) -> None:
        """The write of a stream's first chunk with content has returned:
        the frontend's leg of the stage clock ends. The time to the first
        token is read off the clock's own two stamps (`http_parse` ->
        `frontend_out`), so the stage intervals behind `http_parse` add
        up to it and no second timer runs beside them."""
        ttft = max(ctx.stamp(FRONTEND_OUT) - ctx.stages[HTTP_PARSE], 0) / 1e9
        self._observe_latency("ttft", ttft, cls=rec.get("class"))
        rec["first_token_s"] = round(ttft, 6)
        if self.quota is not None and rec.get("tenant"):
            self.quota.metrics.observe_ttft(rec["tenant"], ttft)
        self.manager.runtime.stage_metrics.leg_ended(
            ctx.stages, FRONTEND_LEG, span, FRONTEND_SPANS)

    async def _stream_sse(self, request: web.Request, endpoint: str,
                          chunks, ctx: Context, start: float,
                          rec: Optional[dict] = None,
                          span=None) -> web.StreamResponse:
        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
        })
        if rec is None:
            rec = {}
        if rec.get("downgraded_from"):
            # tell the client its request was demoted (deadline-
            # infeasible at its original class) and to what
            resp.headers["x-dyn-class-downgraded"] = \
                rec["downgraded_from"]
            resp.headers["x-dyn-class"] = str(rec.get("class", ""))
        last_token_at: Optional[float] = None
        try:
            async for chunk in chunks:
                content = self._has_content(chunk)
                first = content and last_token_at is None
                if content:
                    now = time.perf_counter()
                    if last_token_at is not None:
                        self._observe_latency("itl", now - last_token_at,
                                              cls=rec.get("class"))
                    last_token_at = now
                    rec["last_token_s"] = round(now - start, 6)
                self._observe_usage(chunk.get("usage"))
                if chunk.get("usage"):
                    rec["usage"] = chunk["usage"]
                if not resp.prepared:
                    await resp.prepare(request)
                await resp.write(sse_encode(chunk))
                if first:
                    self._first_frame_out(ctx, rec, span)
            if not resp.prepared:
                await resp.prepare(request)
            await resp.write(SSE_DONE)
            self._req_counter.inc(endpoint=endpoint, status="200")
            rec["status"] = "200"
        except OpenAIError as e:
            rec["status"] = f"error:{e.status}"
            if not resp.prepared:
                return self._error(endpoint, e)
            await resp.write(sse_encode(e.body()))
        except asyncio.CancelledError:
            # client went away: cancel downstream work (disconnect.rs)
            ctx.cancel()
            rec["status"] = "disconnect"
            self._req_counter.inc(endpoint=endpoint, status="disconnect")
            raise
        except ConnectionResetError:
            # same, but via a write on the dead transport; a disconnect
            # is normal client behavior (abandon waves), not a server
            # error — don't re-raise into aiohttp's error logger
            ctx.cancel()
            rec["status"] = "disconnect"
            self._req_counter.inc(endpoint=endpoint, status="disconnect")
            return resp
        finally:
            self._duration.observe(time.perf_counter() - start)
        await resp.write_eof()
        return resp

    async def _debug_index(self, request: web.Request) -> web.Response:
        """Index of the live debug surfaces: which exist, which env
        knob arms each flight recorder, and whether it is currently
        armed on this process — so an operator never has to read docs
        to discover what `/debug/*` offers or why a ring is empty."""
        engines = list(self.profile_engines() or []) \
            if self.profile_engines is not None else None
        routers = self.manager.kv_routers()
        surfaces = {
            "/debug/requests": {
                "what": "in-flight + recent request lifecycle timings",
                "arm": None,                 # always on, bounded ring
                "armed": True,
                "available": True,
            },
            "/debug/profile": {
                "what": "engine step flight recorder "
                        "(goodput/padding, ?capture_s)",
                "arm": "DYN_STEP_PROFILE=1",
                "armed": any(getattr(e, "step_recorder", None)
                             is not None for e in engines or []),
                "available": engines is not None,
            },
            "/debug/router": {
                "what": "router decision flight recorder "
                        "(placement, overlap, margins)",
                "arm": "DYN_ROUTER_LOG=1",
                "armed": any(getattr(getattr(r, "router", r),
                                     "recorder", None) is not None
                             for r in routers.values()),
                "available": bool(routers),
            },
            "/debug/kv": {
                "what": "KV lifecycle flight recorder "
                        "(tiers, evictions, reuse distance, hotness)",
                "arm": "DYN_KV_LIFECYCLE=1",
                "armed": any(getattr(e, "kv_lifecycle", None)
                             is not None for e in engines or []),
                "available": engines is not None,
            },
            "/debug/memory": {
                "what": "HBM memory ledger: per-class occupancy vs "
                        "device memory_stats, workspace shapes, "
                        "unattributed residual",
                "arm": "DYN_MEM_LEDGER=1",
                "armed": any(getattr(e, "memory_ledger", None)
                             is not None for e in engines or []),
                "available": engines is not None,
            },
            "/debug/mesh": {
                "what": "mesh/collective flight recorder: per-entry "
                        "collective bytes by mesh axis, reshard "
                        "manifest, per-device skew, link-tier topology",
                "arm": "DYN_MESH_RECORDER=1",
                "armed": any(getattr(e, "mesh_recorder", None)
                             is not None for e in engines or []),
                "available": engines is not None,
            },
            "/debug/control": {
                "what": "flight-control plane: controller state + "
                        "knob-change actions with evidence",
                "arm": "DYN_CONTROL=all|bucket,kvbm,router,forecast",
                "armed": self.control_plane is not None,
                "available": self.control_plane is not None,
            },
            "/debug/tenants": {
                "what": "per-tenant quotas, streams, fair-share "
                        "deficits, KV blocks, goodput",
                "arm": "DYN_TENANCY=<path|inline json>",
                "armed": self.quota is not None,
                "available": True,
            },
            "/debug/classes": {
                "what": "serving-class table, admitted/shed/downgraded "
                        "counters, deadline-admission estimate, "
                        "brownout stage",
                "arm": "DYN_CLASSES=1|<path|inline json>",
                "armed": self.classes is not None,
                "available": True,
            },
            "/debug/prefixes": {
                "what": "fleet prefix heatmap: cross-worker duplication, "
                        "tier-blind misses, shadow routing "
                        "counterfactual (tokens a tier-aware index "
                        "would have saved)",
                "arm": "DYN_PREFIX_HEAT=1",
                "armed": any(getattr(getattr(r, "router", r),
                                     "prefix_heat", None) is not None
                             for r in routers.values()),
                "available": bool(routers),
            },
        }
        return web.json_response({"surfaces": surfaces})

    async def _debug_requests(self, request: web.Request) -> web.Response:
        """Request-lifecycle debug view: every in-flight request plus a
        ring of recently finished ones, with per-stage timings
        (first/last token offsets from receipt, total duration), final
        status, usage, and the trace id to grep in DYN_TRACE output.
        `?limit=N` bounds the recent list (newest first)."""
        try:
            limit = int(request.query.get("limit", "50"))
        except ValueError:
            limit = 50
        recent = list(self._dbg_recent)[-max(limit, 0):]
        recent.reverse()
        return web.json_response({
            "in_flight": list(self._dbg_inflight.values()),
            "recent": recent,
        })

    async def _debug_profile(self, request: web.Request) -> web.Response:
        """Step flight-recorder view (docs/observability.md "Step
        profiler"): per-engine ring snapshot + goodput/padding summary.
        `?limit=N` bounds each ring dump and `?capture_s=N`
        additionally arms a windowed on-demand `jax.profiler` capture
        (blocks this request for N seconds, serving continues; the
        engine's host spans land in the same trace). 503 when no
        in-proc engine is wired (frontend-only process — the worker's
        system port serves `/debug/profile?capture_s=N` for the
        process that holds the chip)."""
        if self.profile_engines is None:
            return web.json_response(
                {"status": "unavailable",
                 "reason": "no in-proc engine wired for profiling"},
                status=503)
        from dynamo_tpu.engine.profiler import (capture_device_profile,
                                                profile_payload)

        engines = list(self.profile_engines() or [])
        try:
            limit = int(request.query.get("limit", "256"))
        except ValueError:
            limit = 256
        payloads = [profile_payload(e, limit) for e in engines]
        body = {
            "enabled": any(p.get("enabled") for p in payloads),
            "engines": payloads,
        }
        cap = request.query.get("capture_s")
        if cap is not None:
            try:
                secs = float(cap)
            except ValueError:
                return web.json_response(
                    {"error": "capture_s must be a number"}, status=400)
            # device capture blocks for the window; run it off-loop so
            # serving (and the engines being profiled) keep moving
            body["capture"] = await asyncio.to_thread(
                capture_device_profile, secs)
        return web.json_response(body)

    async def _debug_kv(self, request: web.Request) -> web.Response:
        """KV lifecycle flight-recorder view (docs/observability.md "KV
        lifecycle"): per-engine tier occupancy (always) plus — when
        DYN_KV_LIFECYCLE arms the KvLifecycleRecorder — eviction causes,
        reuse-distance histogram, tier residency, premature evictions,
        and prefix hotness. `?limit=N` bounds each ring dump. 503 when
        no in-proc engine is wired (frontend-only process — hit the
        worker's surface)."""
        if self.profile_engines is None:
            return web.json_response(
                {"status": "unavailable",
                 "reason": "no in-proc engine wired for kv lifecycle"},
                status=503)
        from dynamo_tpu.kvbm.lifecycle import kv_payload

        try:
            limit = int(request.query.get("limit", "256"))
        except ValueError:
            limit = 256
        payloads = [kv_payload(e, limit)
                    for e in list(self.profile_engines() or [])]
        return web.json_response({
            "enabled": any(p.get("enabled") for p in payloads),
            "engines": payloads,
        })

    async def _debug_memory(self, request: web.Request) -> web.Response:
        """HBM memory ledger view (docs/observability.md "Memory
        ledger"): per-engine allocation classes reconciled against
        device memory_stats — weights, KV pool, KVBM pinned/staged,
        compile-workspace shapes — with the explicit unattributed
        residual and headroom. `?limit=N` bounds each snapshot-ring
        dump. 503 when no in-proc engine is wired (frontend-only
        process — hit the worker's surface)."""
        if self.profile_engines is None:
            return web.json_response(
                {"status": "unavailable",
                 "reason": "no in-proc engine wired for memory ledger"},
                status=503)
        from dynamo_tpu.engine.memory import memory_payload

        try:
            limit = int(request.query.get("limit", "64"))
        except ValueError:
            limit = 64
        payloads = [memory_payload(e, limit)
                    for e in list(self.profile_engines() or [])]
        return web.json_response({
            "enabled": any(p.get("enabled") for p in payloads),
            "engines": payloads,
        })

    async def _debug_mesh(self, request: web.Request) -> web.Response:
        """Communication-plane view (docs/observability.md "Mesh &
        collectives"): per-entry collective bytes attributed to mesh
        axes from compiled HLO, the expected-collective manifest with
        reshard warnings, per-device occupancy/skew, and the link-tier
        topology census. `?limit=N` bounds the event-ring dump. 503
        when no in-proc engine is wired (frontend-only process — hit
        the worker's surface)."""
        if self.profile_engines is None:
            return web.json_response(
                {"status": "unavailable",
                 "reason": "no in-proc engine wired for mesh recorder"},
                status=503)
        from dynamo_tpu.engine.collectives import mesh_payload

        try:
            limit = int(request.query.get("limit", "64"))
        except ValueError:
            limit = 64
        payloads = [mesh_payload(e, limit)
                    for e in list(self.profile_engines() or [])]
        return web.json_response({
            "enabled": any(p.get("enabled") for p in payloads),
            "engines": payloads,
        })

    async def _debug_control(self, request: web.Request) -> web.Response:
        """Flight-control view (docs/flight_control.md): armed
        controllers, tick/action counters, per-controller state, and the
        action ring — every knob change with its before/after values and
        the evidence window that justified it. `?limit=N` bounds the
        event dump. 503 unless DYN_CONTROL armed a controller on this
        process."""
        if self.control_plane is None:
            return web.json_response(
                {"status": "unavailable",
                 "reason": "flight control not armed "
                           "(set DYN_CONTROL=all or a controller list)"},
                status=503)
        try:
            limit = int(request.query.get("limit", "64"))
        except ValueError:
            limit = 64
        return web.json_response(self.control_plane.payload(limit))

    async def _debug_tenants(self, request: web.Request) -> web.Response:
        """Multi-tenant fairness view (docs/multitenancy.md): per-tenant
        quota config + live usage (streams, bucket level, admit/reject
        counts, TTFT p90) from the frontend quota gate, plus each
        in-proc engine's scheduler state — queue depths, KV blocks held,
        and fair-share service/deficit per tenant. 503 unless
        DYN_TENANCY armed tenancy on this process."""
        if self.quota is None:
            return web.json_response(
                {"status": "unavailable",
                 "reason": "tenancy not configured (set DYN_TENANCY)"},
                status=503)
        from dynamo_tpu.tenancy import tenant_state

        body = {"enabled": True, **self.quota.payload()}
        engines = list(self.profile_engines() or []) \
            if self.profile_engines is not None else []
        body["engines"] = [st for st in (tenant_state(e) for e in engines)
                           if st]
        return web.json_response(body)

    async def _debug_classes(self, request: web.Request) -> web.Response:
        """Serving-class view (docs/robustness.md "Serving classes &
        brownout"): the resolved class table and default, live
        admitted/shed/downgraded/rejection counters, the current
        deadline-admission TTFT estimate, and the brownout machine's
        stage + hot objectives. 503 unless DYN_CLASSES armed classes on
        this process."""
        if self.classes is None:
            return web.json_response(
                {"status": "unavailable",
                 "reason": "serving classes not configured "
                           "(set DYN_CLASSES)"},
                status=503)
        body = {"enabled": True,
                "default_class": self.classes.default_class,
                "classes": self.classes.payload()}
        if self.class_metrics is not None:
            body["counters"] = self.class_metrics.payload()
        if self.admission is not None:
            body["admission"] = {
                "quantile": self.admission.quantile,
                "est_ttft_s": round(self.admission.estimate_s(), 6),
            }
        if self.brownout is not None:
            body["brownout"] = self.brownout.state()
        return web.json_response(body)

    async def _debug_router(self, request: web.Request) -> web.Response:
        """Router decision flight-recorder view (docs/observability.md
        "Router observability"): per-model decision counters, index
        stats, and — when DYN_ROUTER_LOG arms the DecisionRecorder —
        the placement/overlap/margin summary plus the raw decision
        ring. `?limit=N` bounds each ring dump. 503 when no kv-mode
        model is being served (round-robin/random routing records no
        placement decisions)."""
        from dynamo_tpu.router.decision_log import router_payload

        routers = self.manager.kv_routers()
        if not routers:
            return web.json_response(
                {"status": "unavailable",
                 "reason": "no kv-mode model served by this frontend"},
                status=503)
        try:
            limit = int(request.query.get("limit", "256"))
        except ValueError:
            limit = 256
        models = [{"model": name, **router_payload(r, limit)}
                  for name, r in routers.items()]
        return web.json_response({
            "enabled": any(m.get("enabled") for m in models),
            "models": models,
        })

    async def _debug_prefixes(self, request: web.Request) -> web.Response:
        """Fleet prefix-plane view (docs/observability.md "Prefix
        plane"): per-model duplication bytes by depth bucket, tier-blind
        miss count, hottest shared prefixes, and the shadow-routing
        counterfactual ring — when DYN_PREFIX_HEAT arms the
        PrefixHeatRecorder. `?limit=N` bounds each ring dump. 503 when
        no kv-mode model is being served (round-robin/random routing
        makes no placement decisions to shadow)."""
        from dynamo_tpu.router.prefix_plane import prefix_payload

        routers = self.manager.kv_routers()
        if not routers:
            return web.json_response(
                {"status": "unavailable",
                 "reason": "no kv-mode model served by this frontend"},
                status=503)
        try:
            limit = int(request.query.get("limit", "256"))
        except ValueError:
            limit = 256
        models = [{"model": name, **prefix_payload(r, limit)}
                  for name, r in routers.items()]
        return web.json_response({
            "enabled": any(m.get("enabled") for m in models),
            "models": models,
        })

    @staticmethod
    def _has_content(chunk: dict) -> bool:
        """True for any token-bearing delta. reasoning_content and
        tool_calls count — the model IS streaming tokens during a think
        block or a jailed call region, and the planner's TTFT/ITL
        correction factors would be wildly distorted if those deltas
        looked like silence."""
        for choice in chunk.get("choices", ()):
            delta = choice.get("delta", {})
            if (delta.get("content") or delta.get("reasoning_content")
                    or delta.get("tool_calls") or choice.get("text")):
                return True
        return False

    def _error(self, endpoint: str, e: OpenAIError) -> web.Response:
        self._req_counter.inc(endpoint=endpoint, status=str(e.status))
        return web.json_response(e.body(), status=e.status)

    async def _models(self, request: web.Request) -> web.Response:
        return web.json_response({
            "object": "list",
            "data": [{"id": name, "object": "model",
                      "created": int(time.time()), "owned_by": "dynamo-tpu"}
                     for name in self.manager.model_names()],
        })

    async def _health(self, request: web.Request) -> web.Response:
        ready = bool(self.manager.model_names())
        return web.json_response(
            {"status": "healthy" if ready else "no models",
             "models": self.manager.model_names()},
            status=200 if ready else 503)

    async def _live(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "live"})

    async def _fleet_status(self, request: web.Request) -> web.Response:
        """Fleet-merged telemetry view (docs/observability.md "Fleet
        view"): per-component and merged TTFT/ITL percentiles from the
        event-plane MetricsSnapshots, plus live SLO burn rates when a
        monitor is configured. 503 until a collector is wired (frontend
        started without the telemetry plane)."""
        if self.fleet_status_provider is None:
            return web.json_response(
                {"status": "unavailable",
                 "reason": "telemetry collector not running"}, status=503)
        status = self.fleet_status_provider()
        # histogram edges can be +Inf; standard JSON has no literal for
        # it, so stringify non-finite floats instead of emitting the
        # python-only Infinity token
        import math

        def _clean(o):
            if isinstance(o, float) and not math.isfinite(o):
                return str(o)
            if isinstance(o, dict):
                return {k: _clean(v) for k, v in o.items()}
            if isinstance(o, (list, tuple)):
                return [_clean(v) for v in o]
            return o

        return web.json_response(_clean(status))

    async def _openapi(self, request: web.Request) -> web.Response:
        """OpenAPI 3.1 description of the served surface (openapi_docs.rs
        analog). Paths/methods are DERIVED from the live route table so
        the spec cannot drift from what is actually served; the summary
        map only decorates."""
        summaries = {
            "/v1/chat/completions": ("Chat completion (SSE when "
                                     "stream=true)", True),
            "/v1/completions": ("Text completion (SSE when stream=true)",
                                True),
            "/v1/embeddings": ("Embeddings", False),
            "/v1/responses": ("Responses API (typed SSE events when "
                              "stream=true)", True),
            "/v1/models": ("Served models", False),
            "/kvbm/status": ("KVBM per-tier occupancy + stats + "
                             "pipeline counters", False),
            "/kvbm/reset": ("Flush KVBM tiers (level: g1/g2/g3/all)",
                            False),
            "/clear_kv_blocks": ("Drop every worker's reusable KV cache",
                                 False),
            "/health": ("Model-serving readiness", False),
            "/live": ("Process liveness", False),
            "/metrics": ("Prometheus metrics", False),
            "/debug": ("Index of debug surfaces with arming env knob "
                       "and current armed state", False),
            "/debug/requests": ("In-flight + recent request lifecycle "
                                "timings", False),
            "/debug/profile": ("Step flight-recorder ring + goodput/"
                               "padding summary (?capture_s=N)",
                               False),
            "/debug/router": ("Router decision ring + placement/overlap "
                              "summary per kv-mode model (?limit=N)",
                              False),
            "/debug/kv": ("KV lifecycle ring: tier occupancy, eviction "
                          "causes, reuse distance, prefix hotness "
                          "(?limit=N)", False),
            "/debug/memory": ("HBM memory ledger: class occupancy vs "
                              "device stats, workspace shapes, "
                              "unattributed residual (?limit=N)", False),
            "/debug/mesh": ("Mesh/collective recorder: per-entry "
                            "collective bytes by axis, reshard "
                            "manifest, device skew, link topology "
                            "(?limit=N)", False),
            "/debug/control": ("Flight-control state: armed controllers "
                               "+ knob-change actions with evidence "
                               "(?limit=N)", False),
            "/debug/tenants": ("Per-tenant quotas, live streams, "
                               "fair-share deficits, KV blocks, goodput",
                               False),
            "/debug/classes": ("Serving-class table, admitted/shed/"
                               "downgraded counters, deadline-admission "
                               "estimate, brownout stage", False),
            "/debug/prefixes": ("Fleet prefix heatmap: duplication by "
                                "depth, tier-blind misses, shadow "
                                "routing counterfactual (?limit=N)",
                                False),
            "/openapi.json": ("This document", False),
        }
        paths: dict[str, dict] = {}
        for route in self.app.router.routes():
            info = route.resource.canonical if route.resource else None
            method = route.method.lower()
            if info is None or method == "head":
                continue
            summary, streaming = summaries.get(info, (info, False))
            op: dict = {"summary": summary,
                        "responses": {"200": {"description": "OK"}}}
            if method == "post":
                op["requestBody"] = {"content": {"application/json": {
                    "schema": {"type": "object"}}}}
            if streaming:
                op["responses"]["200"]["content"] = {
                    "text/event-stream": {}, "application/json": {}}
            paths.setdefault(info, {})[method] = op
        return web.json_response({
            "openapi": "3.1.0",
            "info": {"title": "dynamo_tpu OpenAI-compatible API",
                     "version": "1.0"},
            "paths": paths,
        })

    async def _metrics(self, request: web.Request) -> web.Response:
        return web.Response(text=self.manager.runtime.metrics.render(),
                            content_type="text/plain")
