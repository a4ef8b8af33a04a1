"""The benchmark's HTTP client: one event loop, streamed completions, times
taken from when a request was *due*.

Rewritten rather than copied from `dynamo_tpu/trafficgen/runner.py`, which
times from the moment of sending, records no generator lateness and counts
an SSE frame as one token (a frame carries a decode burst). Here a frame's
tokens are the words of its text: the benchmark's tokenizer (`lib/ckpt.py`)
makes every id one word.
"""

from __future__ import annotations

import asyncio
import json
import random
import time
from dataclasses import dataclass, field

import aiohttp

from lib.schedule import ClosedSource, Prompts, Request


@dataclass
class Result:
    phase: str
    prompt_tokens: int
    max_tokens: int
    due: float                  # perf_counter seconds
    sent: float = 0.0
    frames: list = field(default_factory=list)   # (time, tokens in frame)
    finish: str | None = None
    error: str | None = None
    done: float = 0.0           # stream ended (or failed)
    prompt: str = ""
    text: list = field(default_factory=list)
    logprobs: list = field(default_factory=list)   # asked for: the probe

    @property
    def tokens(self) -> int:
        return sum(n for _, n in self.frames)

    @property
    def ok(self) -> bool:
        """Well formed: exactly the tokens asked, ended by the limit."""
        return (self.error is None and self.finish == "length"
                and self.tokens == self.max_tokens)

    def tokens_before(self, t: float) -> int:
        return sum(n for at, n in self.frames if at <= t)


class Client:
    def __init__(self, url: str, model: str, sampling: dict) -> None:
        self.url = url + "/v1/completions"
        self.body = {"model": model, "stream": True, **sampling}
        self.results: list[Result] = []
        self.session: aiohttp.ClientSession | None = None

    async def __aenter__(self) -> "Client":
        self.session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None, sock_read=300))
        return self

    async def __aexit__(self, *exc) -> None:
        await self.session.close()

    async def complete(self, phase: str, prompt: str, prompt_tokens: int,
                       max_tokens: int, due: float, logprobs: bool = False,
                       sampling: dict | None = None) -> Result:
        """One streamed completion. Never raises for a failed request: the
        failure is the result. The prompt and the frames' text are kept
        (references, no copies): the comparison with the reference draws
        its sample from them once the window has closed. `logprobs` asks
        for the chosen token's log-probability (`"logprobs": 0`: no
        alternatives, so the programs are the window's own); `sampling`
        overrides the mix's sampling for this request."""
        res = Result(phase, prompt_tokens, max_tokens, due, prompt=prompt)
        self.results.append(res)
        body = dict(self.body, prompt=prompt, max_tokens=max_tokens,
                    **(sampling or {}))
        if logprobs:
            body["logprobs"] = 0
        res.sent = time.perf_counter()
        try:
            async with self.session.post(self.url, json=body) as resp:
                if resp.status != 200:
                    res.error = f"http {resp.status}: " \
                                f"{(await resp.text())[:200]}"
                async for raw in resp.content:
                    if res.error or not raw.startswith(b"data:"):
                        continue
                    payload = raw[5:].strip()
                    if payload == b"[DONE]":
                        break
                    now = time.perf_counter()
                    msg = json.loads(payload)
                    if "error" in msg:
                        res.error = str(msg["error"])[:200]
                        continue
                    for ch in msg.get("choices", ()):
                        text = ch.get("text") or ""
                        n = len(text.split())
                        if n:
                            res.frames.append((now, n))
                            res.text.append(text)
                        if logprobs:
                            res.logprobs += (ch.get("logprobs") or {}).get(
                                "token_logprobs") or []
                        res.finish = ch.get("finish_reason") or res.finish
        except asyncio.CancelledError:
            res.error = "cancelled"      # cut by the end of the window
            res.done = time.perf_counter()
            raise
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
            res.error = f"{type(e).__name__}: {e}"[:200]
        res.done = time.perf_counter()
        return res

    # -- phases ------------------------------------------------------------

    async def open_loop(self, phase: str, reqs: list[Request],
                        texts: list[str], start: float) -> list:
        """Send each request when it is due, whatever the earlier ones are
        doing (`texts` are made beforehand: a window's prompts are some
        hundred thousand words). Returns the tasks; the caller decides how
        long to wait."""
        tasks = []
        for r, text in zip(reqs, texts):
            due = start + r.at
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.create_task(self.complete(
                phase, text, r.prompt_tokens, r.max_tokens, due)))
        return tasks

    async def closed_loop(self, source: ClosedSource, prompts: Prompts,
                          clients: int, start: float, window_at: float,
                          end: float, stagger_s: float, seed: int) -> None:
        """`clients` callers, each sending its next request when the last
        one ends, until `end`; requests in flight then are cut. Client i
        starts i * stagger_s / clients after `start`, and its first request
        is cut to a uniform share of its length, so that the lanes do not
        start and end together."""
        rng = random.Random(
            f"{source.traffic.get('schedule_seed', seed)}:cut")
        cuts = [rng.random() for _ in range(clients)]

        async def caller(i: int) -> None:
            await asyncio.sleep(max(
                0.0, start + i * stagger_s / clients - time.perf_counter()))
            first = True
            while time.perf_counter() < end:
                r = source.next()
                n = r.max_tokens
                if first:
                    n, first = max(2, int(n * cuts[i])), False
                now = time.perf_counter()
                await self.complete(
                    "window" if now >= window_at else "ramp",
                    prompts.text(r), r.prompt_tokens, n, now)

        tasks = [asyncio.create_task(caller(i)) for i in range(clients)]
        await asyncio.sleep(max(0.0, end - time.perf_counter()))
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
