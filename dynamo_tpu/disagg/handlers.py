"""Disaggregated worker handlers: decode-first orchestration + KV pull.

Reference: `components/src/dynamo/vllm/handlers.py` —
`DecodeWorkerHandler.generate` (:140) builds a max_tokens=1 prefill request
with ``kv_transfer_params.do_remote_decode``, sends it to the prefill pool
(router-first with round-robin fallback, :183-199), attaches the returned
transfer descriptors to the local decode, and streams. The TPU transfer
plane: the prefill engine pins the sequence's pages; the decode handler
pulls them over the runtime transport (``kv_pull`` endpoint) and preloads
them into its engine's fresh pages. On one host the pull is a zero-copy
in-proc call; across hosts it rides TCP (DCN analog); intra-pod ICI
device-to-device is the planned fast path.
"""

from __future__ import annotations

import asyncio
import logging
import time
from collections import deque
from typing import Any, AsyncIterator, Optional

import numpy as np

from dynamo_tpu.disagg.disagg_router import DisaggRouter
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.engine import AsyncEngine
from dynamo_tpu.runtime.push import PushRouter
from dynamo_tpu.runtime.topology import link_for_pull_path

logger = logging.getLogger(__name__)

KV_PULL_ENDPOINT = "kv_pull"

# Same-process prefill engines by instance id: the decode handler uses a
# registry hit to pull KV DEVICE-SIDE (gather on the source devices +
# device_put to the destination's — DMA/ICI, no host bounce, no
# serialization). Cross-process falls back to the chunked host wire.
_LOCAL_PREFILL: dict[int, "PrefillWorkerHandler"] = {}

# pages per wire frame on the host path: bounds frame size (backpressure)
# and lets the consumer overlap receive with assembly. 64 pages of a 70B
# layout ≈ tens of MB — large enough to amortize, small enough to stream.
DEFAULT_PULL_CHUNK_PAGES = 64

# strong refs to in-flight fire-and-forget transfer aborts (a bare
# create_task result may be GC'd mid-flight)
_ABORT_TASKS: set = set()

# overall bound on one KV pull (all paths: device / plane / wire). A
# stalled prefill worker must degrade to local serve, not hang the decode
# request; the bound is generous because a 70B-scale wire pull is tens of
# seconds on DCN. 0 disables.
DEFAULT_PULL_DEADLINE_S = 60.0


def _bf16_bytes(arr: np.ndarray) -> tuple[bytes, list[int], str]:
    return arr.tobytes(), list(arr.shape), str(arr.dtype)


def _bf16_from(raw: bytes, shape: list[int], dtype: str) -> np.ndarray:
    import ml_dtypes  # ships with jax

    np_dtype = np.dtype(ml_dtypes.bfloat16) if dtype == "bfloat16" \
        else np.dtype(dtype)
    return np.frombuffer(raw, dtype=np_dtype).reshape(shape)


class PrefillWorkerHandler:
    """Serves `generate` on the prefill pool (handlers.py:236 analog).

    The engine does the work (max_tokens=1 + pinned pages); this wrapper
    stamps the instance id into kv_transfer_params so the decode side can
    address the owning worker's kv_pull endpoint directly."""

    def __init__(self, engine: TpuEngine, instance_id: int) -> None:
        self.engine = engine
        self.instance_id = instance_id

    async def generate(self, request: dict, context: Context
                       ) -> AsyncIterator[dict]:
        async for out in self.engine.generate(request, context):
            ktp = out.get("kv_transfer_params")
            if ktp is not None:
                ktp["instance_id"] = self.instance_id
            yield out

    async def kv_pull(self, request: dict, context: Context
                      ) -> AsyncIterator[dict]:
        """Transfer endpoint: {"transfer_id"} → CHUNKED page-data frames.

        One frame per ``chunk_pages`` pages instead of one giant frame:
        bounds peak memory on both sides, gives the transport
        backpressure, and lets the consumer assemble while later chunks
        are still in flight (a single-frame transfer is hundreds of MB
        for 70B-scale KV)."""
        tid = request["transfer_id"]
        if request.get("abort"):
            # the decode side gave up on this pull (deadline fired /
            # degraded to local serve): release the pinned pages now
            # instead of holding page-pool capacity until the TTL
            # reaper; complete_transfer is an idempotent pop
            self.engine.complete_transfer(tid)
            yield {"aborted": True}
            return
        try:
            pages, prefill_len = self.engine.take_transfer(tid)
        except KeyError:
            yield {"error": f"unknown transfer {tid}"}
            return
        if request.get("stage"):
            # device-to-device plane (transfer_plane.py): stage a device
            # copy for the peer to pull over ICI/DCN, release the pages
            # now (the copy is independent), reply with the descriptor —
            # no bulk bytes on this transport
            from dynamo_tpu.disagg.transfer_plane import (
                get_plane,
                plane_enabled,
            )

            if not plane_enabled():
                yield {"error": "kv plane disabled (DYN_KV_PLANE=0)"}
                return
            try:
                arr = await self.engine.read_kv_pages_device(pages)
                desc = get_plane().publish(tid, arr)
            except Exception as e:
                logger.exception("kv plane staging failed")
                yield {"error": f"stage failed: {e}"}
                return
            self.engine.complete_transfer(tid)
            yield {"plane": desc, "prefill_len": prefill_len}
            return
        total = len(pages)
        # chunking is OPT-IN by the requester: a peer that doesn't send
        # chunk_pages (an older decode client reads exactly one frame)
        # gets the whole transfer in one frame — compatibility is
        # bidirectional
        chunk = max(1, int(request.get("chunk_pages") or total or 1))
        try:
            for i in range(0, total, chunk):
                if i > 0:
                    # the consumer controls inter-frame pacing, so a slow
                    # pull can outlive the TTL: re-take to refresh the
                    # deadline AND confirm the reaper hasn't released the
                    # pages (streaming freed/re-pinned pages would ship
                    # another sequence's KV with no error)
                    try:
                        self.engine.take_transfer(tid)
                    except KeyError:
                        yield {"error": f"transfer {tid} expired mid-pull"}
                        return
                data = await self.engine.read_kv_pages(pages[i:i + chunk])
                raw, shape, dtype = _bf16_bytes(data)
                yield {"kv": raw, "shape": shape, "dtype": dtype,
                       "page_offset": i, "total_pages": total,
                       "prefill_len": prefill_len}
        finally:
            # release no matter how the stream ends (consumer close,
            # read failure, zero-frame path); idempotent pop
            self.engine.complete_transfer(tid)


async def serve_kv_pull(runtime, namespace: str, component: str,
                        handler: PrefillWorkerHandler,
                        instance_id: int):
    """Register the prefill worker's kv_pull endpoint (and the local
    registry entry that enables the device-side fast path)."""
    _LOCAL_PREFILL[instance_id] = handler
    ep = (runtime.namespace(namespace).component(component)
          .endpoint(KV_PULL_ENDPOINT))
    served = await ep.serve(handler.kv_pull, instance_id=instance_id)

    orig_shutdown = served.shutdown

    async def shutdown():
        _LOCAL_PREFILL.pop(instance_id, None)
        await orig_shutdown()

    served.shutdown = shutdown
    return served


class DecodeWorkerHandler:
    """Decode-first disaggregation (handlers.py:140-230 analog).

    generate():
    1. if a prefill pool exists and DisaggRouter says remote → send the
       prompt there with max_tokens=1 + do_remote_decode
    2. pull the KV pages from the owning prefill worker
    3. run local decode with the imported KV (prompt + first token,
       cached_len = prefill_len)
    Falls back to fully-local prefill+decode when the pool is empty or the
    prompt is short/mostly cached.
    """

    def __init__(self, engine: TpuEngine,
                 prefill_router: Optional[AsyncEngine] = None,
                 kv_pull_router: Optional[PushRouter] = None,
                 disagg_router: Optional[DisaggRouter] = None,
                 pull_chunk_pages: int = DEFAULT_PULL_CHUNK_PAGES,
                 pull_deadline: float = DEFAULT_PULL_DEADLINE_S,
                 prefill_queue_client=None) -> None:
        self.engine = engine
        self.prefill_router = prefill_router
        self.kv_pull_router = kv_pull_router
        self.disagg_router = disagg_router or DisaggRouter()
        self.pull_chunk_pages = pull_chunk_pages
        self.pull_deadline = pull_deadline
        # pull-model alternative to prefill_router: jobs ride the durable
        # queue, any prefill worker takes them (prefill_queue.py)
        self.prefill_queue_client = prefill_queue_client
        # "device" (same-process) | "plane" (cross-process
        # device-to-device) | "wire" (chunked host frames)
        self.last_pull_path: Optional[str] = None
        # bounded per-transfer records (bytes, seconds, bandwidth by
        # path) — the raw inputs for a future network cost model; cheap
        # enough to keep always-on
        self.transfer_log: deque = deque(maxlen=256)

    def _can_prefill_remote(self) -> bool:
        if self.kv_pull_router is None:
            return False
        if self.prefill_router is None \
                and self.prefill_queue_client is None:
            return False
        try:
            return bool(self.kv_pull_router.client.instances())
        except Exception:
            return False

    def _prefix_hit_len(self, token_ids: list[int]) -> int:
        from dynamo_tpu.tokens import TokenBlockSequence

        hashes = TokenBlockSequence(
            self.engine.model_cfg.page_size, token_ids).seq_hashes()
        return len(self.engine.pool.match_prefix(hashes)) \
            * self.engine.model_cfg.page_size

    def _abort_remote_transfer(self, ktp: dict) -> None:
        """Fire-and-forget release of a failed/expired pull's pinned
        pages on the owning prefill worker. Without it a 60 s pin of a
        transfer nobody will pull again wastes page-pool capacity there;
        the device path released on cancellation already, so the abort's
        pop is idempotent. Uses a fresh Context — the request's own may
        be cancelled or past its deadline."""
        if self.kv_pull_router is None:
            return

        async def _abort() -> None:
            try:
                async for _ in self.kv_pull_router.direct(
                        {"transfer_id": ktp["transfer_id"], "abort": True},
                        ktp["instance_id"], Context()):
                    break
            except Exception:
                logger.debug("transfer abort for %s not delivered",
                             ktp["transfer_id"], exc_info=True)

        task = asyncio.get_running_loop().create_task(
            asyncio.wait_for(_abort(), 5.0))
        _ABORT_TASKS.add(task)

        def _done(t: asyncio.Task) -> None:
            _ABORT_TASKS.discard(t)
            if not t.cancelled():
                t.exception()  # best effort: swallow the wait_for timeout

        task.add_done_callback(_done)

    async def _pull_kv(self, ktp: dict, context: Context):
        """Fetch the pinned pages. Device path when the owning prefill
        engine lives in this process (gather on its devices → device_put
        onto ours — DMA/ICI, zero host copies); chunked host frames over
        the transport otherwise."""
        self.last_pull_path = None  # introspection/tests
        src = _LOCAL_PREFILL.get(ktp["instance_id"])
        if src is not None:
            import jax

            tid = ktp["transfer_id"]
            try:
                pages, _plen = src.engine.take_transfer(tid)
            except KeyError:
                # stale registry entry (instance id reused by a remote
                # worker): fall through to the wire path
                logger.warning("transfer %s not on local engine; trying "
                               "the transport", tid)
            else:
                try:
                    dev = await src.engine.read_kv_pages_device(pages)
                    target = self.engine.kv_import_sharding()

                    def copy():
                        out = jax.device_put(dev, target)
                        out.block_until_ready()  # a 70B-scale copy: not
                        return out               # on the event loop

                    out = await asyncio.to_thread(copy)
                except asyncio.CancelledError:
                    # The pull deadline cancelled us mid-copy
                    # (CancelledError is not Exception, so the handler
                    # below never sees it). Nothing will pull this
                    # transfer again — the caller degrades to local
                    # serve — so release the pinned pages now instead of
                    # leaking them for a transfer_ttl.
                    src.engine.complete_transfer(tid)
                    raise
                except Exception:
                    # device_put/gather failure (mesh mismatch, OOM):
                    # the transfer stays pinned — the wire path below
                    # can still pull it, and its failure path falls
                    # back to local serve
                    logger.exception("device-side KV pull failed; trying "
                                     "the transport")
                else:
                    src.engine.complete_transfer(tid)
                    self.last_pull_path = "device"
                    return out
        # cross-process device-to-device plane: ask the owner to STAGE
        # the pages on its transfer server, then pull them straight onto
        # our devices (jax.experimental.transfer — no host bounce). Any
        # failure falls through to the chunked host wire.
        from dynamo_tpu.disagg.transfer_plane import (
            get_plane,
            plane_enabled,
        )

        if plane_enabled():
            staged = False
            try:
                async for frame in self.kv_pull_router.direct(
                        {"transfer_id": ktp["transfer_id"],
                         "stage": True},
                        ktp["instance_id"], context):
                    desc = frame.get("plane")
                    if desc is None:
                        logger.info("peer has no kv plane (%s); using "
                                    "the host wire", frame.get("error"))
                        break
                    staged = True
                    import jax as _jax

                    dev = list(self.engine.k_cache[0].devices())[0]

                    def pull_and_place():
                        out = get_plane().pull(desc, dev)
                        # reshard to the decode engine's cache layout
                        # (kv heads over "tp" on mesh engines) — the
                        # same placement the same-process path does
                        out = _jax.device_put(
                            out, self.engine.kv_import_sharding())
                        out.block_until_ready()
                        return out

                    out = await asyncio.to_thread(pull_and_place)
                    self.last_pull_path = "plane"
                    return out
            except asyncio.CancelledError:
                if staged:
                    # the producer released its pages at staging and the
                    # transfer API has no cancel: the staged device copy
                    # is leaked (bounded by one sequence's KV) — say so
                    logger.warning(
                        "KV plane pull for %s cancelled after staging; "
                        "one staged copy leaks on the producer",
                        ktp["transfer_id"])
                raise
            except ConnectionError:
                return None
            except Exception:
                if staged:
                    # the producer released its pages at staging — the
                    # wire has nothing left to pull, and the staged
                    # copy is leaked on its device (no cancel API)
                    logger.exception("kv plane pull failed after "
                                     "staging; serving locally")
                    return None
                logger.exception("kv plane staging failed; trying the "
                                 "host wire")
        # host/DCN path: assemble chunked frames in arrival order
        buf: Optional[np.ndarray] = None
        got = 0
        try:
            async for frame in self.kv_pull_router.direct(
                    {"transfer_id": ktp["transfer_id"],
                     "chunk_pages": self.pull_chunk_pages},
                    ktp["instance_id"], context):
                if "kv" not in frame:
                    return None
                chunk = _bf16_from(frame["kv"], frame["shape"],
                                   frame["dtype"])
                if "page_offset" not in frame:   # single-frame peer
                    self.last_pull_path = "wire"
                    return chunk
                total = int(frame["total_pages"])
                if buf is None:
                    shape = list(chunk.shape)
                    shape[3] = total
                    buf = np.empty(shape, dtype=chunk.dtype)
                off = int(frame["page_offset"])
                buf[:, :, :, off:off + chunk.shape[3]] = chunk
                got += chunk.shape[3]
                if got >= total:
                    self.last_pull_path = "wire"
                    return buf
        except ConnectionError:
            return None
        return None  # stream ended short

    def _record_pull(self, ktp: dict, kv_data, seconds: float,
                     em) -> None:
        """Account one successful pull: bytes + bandwidth into the
        engine metrics (labeled by path) and a bounded per-transfer
        record. Works for numpy and jax arrays (both carry .nbytes)."""
        nbytes = int(getattr(kv_data, "nbytes", 0) or 0)
        path = self.last_pull_path or "?"
        link = link_for_pull_path(path)
        bw = nbytes / seconds if seconds > 0 else 0.0
        if em is not None and nbytes:
            em.kv_pull_bytes.inc(nbytes, path=path, link=link)
            em.kv_pull_bw.observe(bw)
        self.transfer_log.append({
            "transfer_id": ktp.get("transfer_id"),
            "path": path,
            "link": link,
            "bytes": nbytes,
            "seconds": round(seconds, 6),
            "bandwidth_bytes_per_s": round(bw, 1),
            "prefill_len": int(ktp.get("prefill_len") or 0),
            "at": time.time(),
        })

    async def generate(self, request: dict, context: Context
                       ) -> AsyncIterator[dict]:
        token_ids = list(request.get("token_ids", ()))
        remote = (self._can_prefill_remote()
                  and self.disagg_router.prefill_remote(
                      len(token_ids), self._prefix_hit_len(token_ids)))
        if not remote:
            async for out in self.engine.generate(request, context):
                yield out
            return

        # --- 1. remote prefill (max_tokens=1, pages pinned remotely) ---
        prefill_req = dict(request)
        stop = dict(prefill_req.get("stop") or {})
        stop["max_tokens"] = 1
        stop.pop("stop_token_ids", None)
        prefill_req["stop"] = stop
        prefill_req["kv_transfer_params"] = {"do_remote_decode": True}
        first_token: Optional[int] = None
        first_lp: Optional[float] = None
        ktp: Optional[dict] = None
        if self.prefill_queue_client is not None:
            try:
                result = await self.prefill_queue_client.prefill(
                    prefill_req, context)
            except Exception:
                # store/transport hiccup: same contract as the push path
                # (ConnectionError) — fall back to fully-local serving
                logger.exception("prefill queue unavailable")
                result = None
            if result is not None:
                first_token, ktp = result
                first_lp = (ktp or {}).pop("first_token_logprob", None)
        else:
            try:
                async for out in self.prefill_router.generate(
                        prefill_req, context):
                    if out.get("token_ids"):
                        first_token = out["token_ids"][0]
                        if out.get("log_probs"):
                            first_lp = out["log_probs"][0]
                    if out.get("kv_transfer_params"):
                        ktp = out["kv_transfer_params"]
                    if out.get("finish_reason") == "error":
                        ktp = None
                        break
            except ConnectionError:
                ktp = None
        if ktp is None or first_token is None:
            # remote prefill failed: fall back to fully-local serve
            logger.warning("remote prefill failed; serving locally")
            async for out in self.engine.generate(request, context):
                yield out
            return

        # --- 2. pull the KV pages from the owning prefill worker ---
        # Deadline-bounded: a wedged prefill worker mid-pull must degrade
        # to local serve (re-prefill here), not hang this decode stream.
        # The transport's own idle/deadline timeouts (runtime config)
        # surface as ConnectionError inside _pull_kv → None; this bound
        # also covers the device/plane paths that never touch the wire.
        try:
            t_pull = time.perf_counter()
            kv_data = await asyncio.wait_for(
                self._pull_kv(ktp, context),
                self.pull_deadline or None)
            pull_s = time.perf_counter() - t_pull
            em = getattr(self.engine, "metrics", None)
            if em is not None:
                em.kv_pull.observe(pull_s)
            if kv_data is not None:
                self._record_pull(ktp, kv_data, pull_s, em)
        except asyncio.TimeoutError:
            logger.warning("KV pull for transfer %s exceeded %.1fs; "
                           "serving locally", ktp.get("transfer_id"),
                           self.pull_deadline)
            kv_data = None
        if kv_data is not None:
            logger.info("kv pull path: %s (%d tokens)",
                        self.last_pull_path, int(ktp["prefill_len"]))
        if kv_data is None:
            # tell the owning prefill worker to drop the pin now rather
            # than at transfer_ttl (best effort, off the serving path)
            self._abort_remote_transfer(ktp)
            logger.warning("KV pull failed; serving locally")
            async for out in self.engine.generate(request, context):
                yield out
            return

        # --- 3. stream the prefill token, then local decode with the
        #        imported cache ---
        def first_frame(**kw) -> dict:
            out = {"token_ids": [first_token], **kw}
            if first_lp is not None:
                # the remote prefill computed this token's logprob; a
                # logprobs client must see N logprobs for N tokens
                out["log_probs"] = [first_lp]
            return out

        orig_stop = request.get("stop") or {}
        if orig_stop.get("max_tokens") == 1:
            # no decode needed; the pulled KV is simply dropped
            yield first_frame(finish_reason="length")
            return
        if first_token in (orig_stop.get("stop_token_ids") or ()) \
                and (orig_stop.get("min_tokens") or 0) <= 1:
            # min_tokens suppresses the stop exactly like the local path's
            # _emit_token (generated=1 here)
            yield first_frame(finish_reason="stop")
            return
        yield first_frame()
        decode_req = dict(request)
        decode_req["token_ids"] = token_ids + [first_token]
        stop = dict(decode_req.get("stop") or {})
        # the remote prefill already streamed one token, so the decode
        # phase's budget shrinks by one — resolving the engine default
        # first, else an unset max_tokens would yield one extra token vs
        # the fully-local path (same for min_tokens / EOS suppression)
        eff_max = stop.get("max_tokens") or self.engine.config.default_max_tokens
        stop["max_tokens"] = max(eff_max - 1, 1)
        if stop.get("min_tokens"):
            stop["min_tokens"] = max(stop["min_tokens"] - 1, 0)
        decode_req["stop"] = stop
        decode_req["kv_transfer_params"] = {
            "kv_data": kv_data, "prefill_len": int(ktp["prefill_len"])}
        async for out in self.engine.generate(decode_req, context):
            yield out
