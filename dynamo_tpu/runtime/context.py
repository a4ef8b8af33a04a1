"""Request context: id + hierarchical cancellation + the stage clock.

Reference: `lib/runtime/src/pipeline/context.rs` (Context<T> carries request
id and a cancellation token that propagates through every pipeline stage and
across network hops via a control frame).

The stage clock (docs/observability.md "Request stages") is an ordered
mapping stage -> `time.time_ns()` that rides the request from the HTTP
handler's first statement to the first SSE frame: wall clock, because it
crosses processes. A stage is stamped once, where its work ends; an
interval is named by the stamp that ends it and starts at the stamp
before it. `runtime/stages.py` turns the stamps into the
`dynamo_request_stage_seconds{stage}` family and, under DYN_TRACE, spans.
"""

from __future__ import annotations

import asyncio
import time
import uuid
from typing import Any, Optional

# the stamps, in the order a served request takes them
HTTP_RECV = "http_recv"            # the OpenAI handler's first statement
HTTP_PARSE = "http_parse"          # body read, JSON, template, gates passed
PREPROCESS = "preprocess"          # prompt rendered and tokenised
ROUTE = "route"                    # the router has chosen an instance
TRANSPORT_IN = "transport_in"      # the worker has read the request frame
WORKER_IN = "worker_in"            # the engine enqueues the sequence
ENGINE = "engine"                  # the sequence's first emission
WORKER_OUT = "worker_out"          # its frame is at the worker's socket
TRANSPORT_BACK = "transport_back"  # the caller's transport has read it
FRONTEND_OUT = "frontend_out"      # the first content chunk's write returned
STAGES = (HTTP_RECV, HTTP_PARSE, PREPROCESS, ROUTE, TRANSPORT_IN, WORKER_IN,
          ENGINE, WORKER_OUT, TRANSPORT_BACK, FRONTEND_OUT)


class Context:
    def __init__(self, request_id: Optional[str] = None,
                 parent: Optional["Context"] = None,
                 headers: Optional[dict[str, Any]] = None,
                 stages: Optional[dict[str, int]] = None) -> None:
        self.request_id = request_id or uuid.uuid4().hex
        self.headers: dict[str, Any] = headers or {}
        # the stage clock: ONE mapping a request, shared with every child
        # (a stage stamped below an operator's child context is the
        # request's), first stamp wins (a retry or a second choice does
        # not move a stage that has ended)
        self.stages: dict[str, int] = (
            stages if stages is not None
            else parent.stages if parent is not None else {})
        # Absolute expiry (event-loop clock) for the WHOLE request.
        # Stamped by the transport on first use of a configured
        # `request_deadline`, then inherited by router retries and
        # Migration replays that reuse this context — one shared budget,
        # not a fresh one per attempt.
        self.deadline: Optional[float] = None
        self._cancelled = asyncio.Event()
        self._parent = parent
        self._children: list[Context] = []
        if parent is not None:
            parent._children.append(self)
            self.deadline = parent.deadline
            if parent.is_cancelled():
                self._cancelled.set()

    def child(self) -> "Context":
        return Context(self.request_id, parent=self, headers=dict(self.headers))

    def stamp(self, stage: str, ns: Optional[int] = None) -> int:
        """Record that `stage` ended now (or at `ns`) unless it already
        has; returns the instant either way, so a caller that keeps the
        time for its own books reads the clock once."""
        if ns is None:
            ns = time.time_ns()
        self.stages.setdefault(stage, ns)
        return ns

    def cancel(self) -> None:
        """Cancel this context and all children (never propagates upward)."""
        if not self._cancelled.is_set():
            self._cancelled.set()
            for c in self._children:
                c.cancel()

    def is_cancelled(self) -> bool:
        return self._cancelled.is_set()

    async def wait_cancelled(self) -> None:
        await self._cancelled.wait()

    def raise_if_cancelled(self) -> None:
        if self.is_cancelled():
            raise asyncio.CancelledError(f"request {self.request_id} cancelled")
