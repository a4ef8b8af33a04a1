"""OpenAIPreprocessor: OpenAI request → PreprocessedRequest on the way down,
BackendOutput stream → OpenAI SSE chunks on the way up.

Reference: `lib/llm/src/preprocessor.rs:102,159,430,629-700` — chat
templating, tokenization, sampling-option application, and the postprocess
stream transform back to OpenAI deltas.
"""

from __future__ import annotations

import time
from typing import Any, AsyncIterator, Optional

from dynamo_tpu.llm.protocols_openai import (
    ChatCompletionRequest,
    CompletionRequest,
    EmbeddingRequest,
    OpenAIError,
    chat_chunk,
    completion_chunk,
    embedding_response,
    new_request_id,
    response_object,
    responses_input_to_messages,
    usage_dict,
)
from dynamo_tpu.llm.tokenizer import Tokenizer
from dynamo_tpu.protocols import PreprocessedRequest, StopConditions
from dynamo_tpu.runtime.context import PREPROCESS, Context
from dynamo_tpu.runtime.engine import Operator

KIND_CHAT = "chat"
KIND_COMPLETION = "completion"
KIND_EMBEDDING = "embedding"
KIND_RESPONSES = "responses"

DEFAULT_TEMPLATE_SUFFIX = "assistant:"


def render_chat_template(tokenizer: Tokenizer, messages: list[dict]) -> str:
    """HF chat template when the tokenizer has one; else a minimal
    role-prefixed rendering (preprocessor/prompt/template/oai.rs analog)."""
    apply = getattr(tokenizer, "apply_chat_template", None)
    if apply is not None:
        try:
            return apply(messages, add_generation_prompt=True)
        except Exception:
            pass  # template missing/broken: fall through to default
    lines = []
    for m in messages:
        content = m.get("content") or ""
        if isinstance(content, list):  # multimodal parts: text only for now
            content = " ".join(p.get("text", "") for p in content
                               if isinstance(p, dict))
        lines.append(f"{m.get('role', 'user')}: {content}")
    lines.append(DEFAULT_TEMPLATE_SUFFIX)
    return "\n".join(lines)


class OpenAIPreprocessor(Operator):
    """Front pipeline stage. Requests are dicts with ``_kind`` set by the
    HTTP layer (chat vs completion); responses are OpenAI chunk dicts."""

    def __init__(self, tokenizer: Tokenizer, model_name: str,
                 context_length: int = 0,
                 default_max_tokens: int = 1024,
                 tool_call_parser: str = "",
                 reasoning_parser: str = "",
                 encode_router=None) -> None:
        super().__init__()
        self.tokenizer = tokenizer
        self.model_name = model_name
        self.context_length = context_length
        self.default_max_tokens = default_max_tokens
        self.tool_call_parser = tool_call_parser
        self.reasoning_parser = reasoning_parser
        # multimodal: AsyncEngine routing to encode workers; image parts
        # become discrete tokens spliced into the prompt (multimodal/)
        self.encode_router = encode_router

    # -- request path -------------------------------------------------------

    def _token_text(self, tid: int) -> str:
        """Display text of ONE token id (OpenAI logprobs entries).
        Isolated decode — partial UTF-8 renders as replacement chars,
        which is the standard contract for per-token strings."""
        try:
            return self.tokenizer.decode([tid])
        except Exception:
            return ""

    def preprocess_chat(self, req: ChatCompletionRequest,
                        image_tokens: Optional[dict] = None
                        ) -> PreprocessedRequest:
        prompt = render_chat_template(self.tokenizer, req.messages)
        if image_tokens:
            # markers were injected by _resolve_images; text between them
            # tokenizes normally, image token runs splice in verbatim
            ids: list[int] = []
            rest = prompt
            for marker, toks in image_tokens.items():
                before, sep, rest = rest.partition(marker)
                if not sep:
                    # a chat template that stringifies list content (repr
                    # escapes the marker) would otherwise dump the image
                    # tokens after the generation suffix — corrupt prompt
                    raise OpenAIError(
                        "the model's chat template dropped the image "
                        "placeholder; this template does not support "
                        "multimodal content parts")
                ids.extend(self.tokenizer.encode(before) if before else [])
                ids.extend(toks)
            if rest:
                ids.extend(self.tokenizer.encode(rest))
        else:
            ids = self.tokenizer.encode(prompt)
        return self._finish_preprocess(
            prompt_ids=ids,
            sampling=req.sampling_options(), stop=req.stop_conditions())

    async def _resolve_images(self, messages: list[dict], context: Context
                              ) -> tuple[list[dict], dict]:
        """Replace image parts with unique markers; encode each image via
        the encode workers (sglang processor→encode analog). Returns
        (rewritten messages, {marker: image token ids}) — empty when the
        request has no images."""
        import asyncio

        image_tokens: dict[str, list[int]] = {}
        out_messages: list[dict] = []
        jobs: list[tuple[str, str]] = []     # (marker, url)
        idx = 0
        for m in messages:
            content = m.get("content")
            if not isinstance(content, list):
                out_messages.append(m)
                continue
            parts = []
            for part in content:
                if not (isinstance(part, dict)
                        and part.get("type") == "image_url"):
                    parts.append(part)
                    continue
                url = (part.get("image_url") or {}).get("url", "")
                if self.encode_router is None:
                    raise OpenAIError(
                        "this deployment has no encode workers: image "
                        "inputs are not supported for "
                        f"{self.model_name!r}")
                if not url.startswith("data:"):
                    raise OpenAIError(
                        "only data: image URLs are supported "
                        "(no egress to fetch remote images)")
                marker = f"\x00dyn_image_{idx}\x00"
                idx += 1
                jobs.append((marker, url))
                parts.append({"type": "text", "text": marker})
            out_messages.append({**m, "content": parts})

        async def encode_one(url: str) -> list[int]:
            toks = None
            async for resp in self.encode_router.generate(
                    {"image": url}, context):
                if resp.get("error"):
                    raise OpenAIError(
                        f"image encode failed: {resp['error']}")
                if resp.get("image_tokens") is not None:
                    toks = [int(t) for t in resp["image_tokens"]]
            if toks is None:
                raise OpenAIError("encode worker returned no tokens")
            return toks

        # images are independent: fan out across the encode workers
        results = await asyncio.gather(
            *(encode_one(url) for _, url in jobs))
        for (marker, _), toks in zip(jobs, results):
            image_tokens[marker] = toks
        return out_messages, image_tokens

    def preprocess_completion(self, req: CompletionRequest
                              ) -> PreprocessedRequest:
        if isinstance(req.prompt, list):
            ids = [int(t) for t in req.prompt]
        else:
            ids = self.tokenizer.encode(req.prompt)
        return self._finish_preprocess(
            prompt_ids=ids, sampling=req.sampling_options(),
            stop=req.stop_conditions())

    def _finish_preprocess(self, prompt_ids, sampling, stop
                           ) -> PreprocessedRequest:
        if stop.max_tokens is None:
            stop.max_tokens = self.default_max_tokens
        if not stop.ignore_eos and self.tokenizer.eos_token_id is not None:
            eos = self.tokenizer.eos_token_id
            if eos not in stop.stop_token_ids:
                stop.stop_token_ids.append(eos)
        if self.context_length and len(prompt_ids) >= self.context_length:
            raise OpenAIError(
                f"prompt ({len(prompt_ids)} tokens) exceeds the model "
                f"context length of {self.context_length}", status=400)
        return PreprocessedRequest(
            token_ids=list(prompt_ids), model=self.model_name,
            sampling=sampling, stop=stop)

    # -- pipeline stage -----------------------------------------------------

    async def forward(self, request: dict, context: Context
                      ) -> AsyncIterator[dict]:
        assert self.inner is not None
        kind = request.get("_kind", KIND_CHAT)
        created = int(time.time())
        if kind == KIND_EMBEDDING:
            async for out in self._embed(request, context):
                yield out
            return
        if kind == KIND_RESPONSES:
            async for out in self._responses(request, created, context):
                yield out
            return
        if kind == KIND_CHAT:
            oai = ChatCompletionRequest.from_dict(request["body"])
            image_tokens: dict = {}
            if any(isinstance(m.get("content"), list)
                   for m in oai.messages):
                oai.messages, image_tokens = await self._resolve_images(
                    oai.messages, context)
            pre = self.preprocess_chat(oai, image_tokens)
            context.stamp(PREPROCESS)
            request_id = request.get("request_id") or new_request_id()
            async for chunk in self._postprocess_chat(
                    pre, oai, request_id, created, context):
                yield chunk
        else:
            oai_c = CompletionRequest.from_dict(request["body"])
            pre = self.preprocess_completion(oai_c)
            context.stamp(PREPROCESS)
            request_id = request.get("request_id") or new_request_id("cmpl")
            async for chunk in self._postprocess_completion(
                    pre, oai_c, request_id, created, context):
                yield chunk

    # -- embeddings (/v1/embeddings, ref openai.rs:1125) --------------------

    async def _embed(self, request: dict, context: Context
                     ) -> AsyncIterator[dict]:
        import asyncio

        req = EmbeddingRequest.from_dict(request["body"])
        token_lists: list[list[int]] = []
        for item in req.inputs:
            ids = (list(item) if isinstance(item, list)
                   else self.tokenizer.encode(item))
            if self.context_length and len(ids) >= self.context_length:
                raise OpenAIError(
                    f"input ({len(ids)} tokens) exceeds the model context "
                    f"length of {self.context_length}", status=400)
            token_lists.append(ids)

        sem = asyncio.Semaphore(32)  # batch can be 2048 items: cap fan-out

        async def one(ids: list[int]) -> list[float]:
            pre = PreprocessedRequest(
                token_ids=ids, model=self.model_name,
                stop=StopConditions(max_tokens=1),
                extra={"embed": True})
            async with sem:
                async for out in self.inner.generate(pre.to_dict(),
                                                     context):
                    if out.get("embedding") is not None:
                        return [float(x) for x in out["embedding"]]
                    if out.get("finish_reason"):
                        break
            raise OpenAIError(
                f"model {self.model_name!r} does not support embeddings",
                status=400)

        # items are independent: bounded fan-out, order kept by position;
        # siblings are cancelled the moment one item fails (TaskGroup
        # semantics, spelled by hand — asyncio.TaskGroup needs py3.11)
        results: list = [None] * len(token_lists)

        async def slot(i: int, ids: list) -> None:
            results[i] = await one(ids)

        tasks = [asyncio.ensure_future(slot(i, ids))
                 for i, ids in enumerate(token_lists)]
        try:
            await asyncio.gather(*tasks)
        except BaseException:
            for t in tasks:
                t.cancel()
            settled = await asyncio.gather(*tasks, return_exceptions=True)
            errors = [e for e in settled
                      if isinstance(e, BaseException)
                      and not isinstance(e, asyncio.CancelledError)]
            # the HTTP layer catches OpenAIError, so surface one if any
            # item raised it; otherwise re-raise the first failure as-is
            for e in errors:
                if isinstance(e, OpenAIError):
                    raise e
            if errors:
                raise errors[0]
            raise
        yield embedding_response(req.model, results,
                                 sum(len(t) for t in token_lists),
                                 req.encoding_format)

    # -- responses (/v1/responses, ref openai.rs:766) -----------------------

    async def _responses(self, request: dict, created: int,
                         context: Context) -> AsyncIterator[dict]:
        """OpenAI Responses API over the chat pipeline: typed SSE events
        out (`response.created` / `response.output_text.delta` /
        `response.completed`); the unary path folds the completed event."""
        body = dict(request["body"])
        messages = responses_input_to_messages(body)
        chat_body = {"model": body.get("model"), "messages": messages}
        if body.get("max_output_tokens") is not None:
            chat_body["max_tokens"] = body["max_output_tokens"]
        for k in ("temperature", "top_p"):
            if body.get(k) is not None:
                chat_body[k] = body[k]
        oai = ChatCompletionRequest.from_dict(chat_body)
        pre = self.preprocess_chat(oai)
        resp_id = request.get("request_id") or new_request_id("resp")
        yield {"type": "response.created",
               "response": response_object(resp_id, oai.model, created,
                                           "in_progress")}
        parts: list[str] = []
        usage = None
        stream = self._chat_chunks(pre, oai, resp_id, created, context)
        jail = self._chat_parsers(oai)
        if jail is not None:
            # same parser semantics as /v1/chat/completions: think-block
            # text must never leak into output_text on this endpoint either
            stream = jail.apply(stream)
        async for chunk in stream:
            if chunk.get("usage"):
                usage = chunk["usage"]
            for choice in chunk.get("choices", ()):
                t = choice.get("delta", {}).get("content")
                if t:
                    parts.append(t)
                    yield {"type": "response.output_text.delta",
                           "item_id": f"msg-{resp_id}", "output_index": 0,
                           "content_index": 0, "delta": t}
        text = "".join(parts)
        yield {"type": "response.output_text.done",
               "item_id": f"msg-{resp_id}", "output_index": 0,
               "content_index": 0, "text": text}
        yield {"type": "response.completed",
               "response": response_object(resp_id, oai.model, created,
                                           "completed", text, usage)}

    def _chat_parsers(self, oai: ChatCompletionRequest):
        """Jail + reasoning wrap for this request, or None when neither
        applies (preprocessor.rs:629-700: parsers engage only when the
        model declares them; the jail only when the request has tools)."""
        from dynamo_tpu.parsers import (
            JailedStream, get_reasoning_parser, get_tool_parser)
        want_tools = bool(oai.raw.get("tools")) and bool(
            self.tool_call_parser)
        want_reasoning = bool(self.reasoning_parser)
        if not (want_tools or want_reasoning):
            return None
        return JailedStream(
            tool_config=(get_tool_parser(self.tool_call_parser)
                         if want_tools else None),
            reasoning=(get_reasoning_parser(self.reasoning_parser)
                       if want_reasoning else None))

    def _one_chat_stream(self, pre, oai, request_id, created, context):
        stream = self._chat_chunks(pre, oai, request_id, created, context)
        jail = self._chat_parsers(oai)   # fresh jail per choice: stateful
        if jail is not None:
            stream = jail.apply(stream)
        return stream

    async def _postprocess_chat(self, pre: PreprocessedRequest,
                                oai: ChatCompletionRequest, request_id: str,
                                created: int, context: Context
                                ) -> AsyncIterator[dict]:
        if oai.n <= 1:
            async for chunk in self._one_chat_stream(
                    pre, oai, request_id, created, context):
                yield chunk
            return
        # n > 1: one engine stream per choice (distinct seeds), chunks
        # interleaved with per-choice indices, one trailing usage chunk
        streams = [
            self._one_chat_stream(
                self._reseed(pre, i), oai, request_id, created, context)
            for i in range(oai.n)]
        usages: dict[int, dict] = {}
        async for chunk in self._fanout_choices(streams, usages):
            yield chunk
        # spec-shaped trailing usage chunk: choices MUST be empty — an
        # extra index-0 delta after that choice's finish is a protocol
        # violation to strict stream consumers
        yield {"id": request_id, "object": "chat.completion.chunk",
               "created": created, "model": oai.model, "choices": [],
               "usage": self._merge_usage(usages)}

    @staticmethod
    def _reseed(pre: PreprocessedRequest, i: int) -> PreprocessedRequest:
        """Choice i's request: same tokens, decorrelated seed (a fixed
        user seed must still yield n DISTINCT choices, deterministically).
        Choice 0 keeps the original seed for n=1 compatibility. Shallow
        copies only — deep-copying a 100k-token prompt n times would be
        pure waste when just sampling.seed changes."""
        import copy as _copy

        if i == 0 or pre.sampling.seed is None:
            return pre
        p2 = _copy.copy(pre)
        p2.sampling = _copy.copy(pre.sampling)
        p2.sampling.seed = pre.sampling.seed + i
        return p2

    @staticmethod
    def _merge_usage(usages: dict[int, dict]) -> dict:
        prompt = max((u.get("prompt_tokens", 0)
                      for u in usages.values()), default=0)
        completion = sum(u.get("completion_tokens", 0)
                         for u in usages.values())
        return usage_dict(prompt, completion)

    async def _fanout_choices(self, streams,
                              usages: dict[int, dict]
                              ) -> AsyncIterator[dict]:
        """Merge per-choice chunk streams: relabel indices, strip the
        per-stream usage chunks into ``usages`` (caller merges).

        Bounded queue: the engine must be paced by the consumer exactly
        as in the single-stream path, not buffer n full completions. A
        failing choice cancels its siblings IMMEDIATELY — the client
        must not wait for (and pay for) n-1 finished generations to
        learn the request failed."""
        import asyncio

        queue: asyncio.Queue = asyncio.Queue(maxsize=4)

        async def pump(i, stream):
            try:
                async for chunk in stream:
                    await queue.put((i, chunk, None))
            except asyncio.CancelledError:
                raise
            except BaseException as e:
                await queue.put((i, None, e))
                return
            await queue.put((i, None, None))

        tasks = [asyncio.get_running_loop().create_task(pump(i, st))
                 for i, st in enumerate(streams)]
        try:
            done = 0
            while done < len(streams):
                i, chunk, err = await queue.get()
                if chunk is None:
                    if err is not None:
                        raise err   # finally cancels the siblings now
                    done += 1
                    continue
                for ch in chunk.get("choices", ()):
                    ch["index"] = i
                u = chunk.pop("usage", None)
                if u:
                    usages[i] = u
                yield chunk
        finally:
            for t in tasks:
                t.cancel()

    def _lp_entry(self, tid: int, lp: float, top) -> dict:
        """One OpenAI chat logprobs.content[] entry."""
        text = self._token_text(tid)
        entry = {"token": text, "logprob": lp,
                 "bytes": list(text.encode("utf-8"))}
        if top is not None:
            entry["top_logprobs"] = [
                {"token": (t := self._token_text(int(aid))),
                 "logprob": alp, "bytes": list(t.encode("utf-8"))}
                for aid, alp in top]
        return entry

    async def _chat_chunks(self, pre: PreprocessedRequest,
                           oai: ChatCompletionRequest, request_id: str,
                           created: int, context: Context
                           ) -> AsyncIterator[dict]:
        prompt_tokens = len(pre.token_ids)
        completion_tokens = 0
        yield chat_chunk(request_id, oai.model, created, role="assistant")
        finish: Optional[str] = None
        # entries buffer while text is held back (stop-jail, multibyte
        # holdback) — same gating as the completions path
        want_lps = bool(oai.logprobs)
        pending: list[dict] = []
        async for out in self.inner.generate(pre.to_dict(), context):
            ids = out.get("token_ids", ())
            completion_tokens += len(ids)
            text = out.get("text", "")
            finish = out.get("finish_reason")
            if want_lps and out.get("log_probs"):
                tops = out.get("top_logprobs") or [None] * len(ids)
                for tid, lp, top in zip(ids, out["log_probs"], tops):
                    pending.append(self._lp_entry(tid, lp, top))
            if text:
                entries, pending = (pending, []) if want_lps else (None,
                                                                   None)
                yield chat_chunk(request_id, oai.model, created,
                                 content=text, logprob_content=entries)
            if finish:
                break
        yield chat_chunk(
            request_id, oai.model, created, finish_reason=finish or "stop",
            usage=usage_dict(prompt_tokens, completion_tokens),
            logprob_content=(pending or None) if want_lps else None)

    async def _postprocess_completion(self, pre: PreprocessedRequest,
                                      oai: CompletionRequest, request_id: str,
                                      created: int, context: Context
                                      ) -> AsyncIterator[dict]:
        if oai.n > 1:
            streams = [self._completion_chunks(
                self._reseed(pre, i), oai, request_id, created, context)
                for i in range(oai.n)]
            usages: dict[int, dict] = {}
            async for chunk in self._fanout_choices(streams, usages):
                yield chunk
            yield {"id": request_id, "object": "text_completion",
                   "created": created, "model": oai.model, "choices": [],
                   "usage": self._merge_usage(usages)}
            return
        async for chunk in self._completion_chunks(pre, oai, request_id,
                                                   created, context):
            yield chunk

    async def _completion_chunks(self, pre: PreprocessedRequest,
                                 oai: CompletionRequest, request_id: str,
                                 created: int, context: Context
                                 ) -> AsyncIterator[dict]:
        prompt_tokens = len(pre.token_ids)
        completion_tokens = 0
        finish: Optional[str] = None
        if oai.echo and isinstance(oai.prompt, str):
            yield completion_chunk(request_id, oai.model, created, oai.prompt)
        # logprobs=0 is a valid OpenAI value ("chosen token, no
        # alternatives"): gate on presence, not truthiness. Frames whose
        # text is held back (stop-jail, multibyte holdback) still carry
        # token logprobs — buffer them until a chunk flows.
        want_lps = oai.logprobs is not None
        want_top = bool(oai.logprobs)      # logprobs=N>0: N alternatives
        pending_lps: list[float] = []
        pending_toks: list[str] = []
        pending_tops: list[dict] = []

        def drain():
            nonlocal pending_lps, pending_toks, pending_tops
            lps, pending_lps = pending_lps, []
            toks, pending_toks = pending_toks, []
            tops, pending_tops = pending_tops, []
            return {"token_logprobs": lps or None, "tokens": toks or None,
                    "top_logprobs": (tops or None) if want_top else None}

        async for out in self.inner.generate(pre.to_dict(), context):
            ids = out.get("token_ids", ())
            completion_tokens += len(ids)
            text = out.get("text", "")
            finish = out.get("finish_reason")
            if want_lps and out.get("log_probs"):
                pending_lps.extend(out["log_probs"])
                for ti, tid in enumerate(ids[:len(out["log_probs"])]):
                    tok_text = self._token_text(tid)
                    pending_toks.append(tok_text)
                    if want_top:
                        top = (out.get("top_logprobs") or [])
                        alts = top[ti] if ti < len(top) else None
                        d: dict = {}
                        for a, lp in (alts or []):
                            t = self._token_text(int(a))
                            # distinct ids can decode to the same text
                            # (partial UTF-8 → U+FFFD); keep the
                            # higher-ranked alternative, never overwrite
                            if t not in d:
                                d[t] = lp
                        pending_tops.append(d)
            if text:
                kw = drain() if want_lps else {}
                yield completion_chunk(request_id, oai.model, created,
                                       text, **kw)
            if finish:
                break
        tail = drain() if want_lps else {}
        yield completion_chunk(
            request_id, oai.model, created, "", finish_reason=finish or "stop",
            usage=usage_dict(prompt_tokens, completion_tokens), **tail)
