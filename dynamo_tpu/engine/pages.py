"""Host-side page allocator for the device KV cache: refcounted pages,
prefix-cache reuse by sequence hash, LRU eviction, KV event emission.

This is the engine-side analog of vLLM's block manager that the reference
orchestrates around (and of `lib/llm/src/mocker/kv_manager.rs` which fakes
it). Pages hold `page_size` tokens of K/V per layer on device; this class
only tracks ownership — the device arrays are indexed by the page ids it
hands out.

Invariants:
- page 0 is scratch (padding lanes scatter there; never allocated)
- a page is *registered* once it holds a complete block and is then
  immutable and shareable (prefix reuse increments its refcount)
- refcount 0 + registered ⇒ inactive LRU, evictable; refcount 0 +
  unregistered ⇒ freed immediately
- KvCacheEvents (stored/removed) are emitted exactly at register/evict,
  so the router's view mirrors reality (publisher.rs analog)
"""

from __future__ import annotations

import itertools
import math
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Optional

from dynamo_tpu.protocols import (
    KV_REMOVED,
    KV_STORED,
    KvCacheEvent,
    StoredBlock,
)

EventSink = Callable[[KvCacheEvent], None]


# ---------------------------------------------------------------------------
# KV geometry: the ONE place a model configuration becomes the cache's
# shapes and bytes. init_cache, the pp cache, the KV-import check, KVBM's
# tier blocks and the memory ledger all ask here; a new cache format (a
# latent a token, per-layer geometry) changes these three answers. The
# fourth answer is what a SEQUENCE owns, whatever its length: the state of
# a model's recurrent layers (`state_shapes`), held in slots (`SlotPool`).
# ---------------------------------------------------------------------------


def kv_layer_shape(cfg, num_pages: int) -> tuple:
    """(KVH, N, P, D): one layer's K (or V) cache of `num_pages` pages —
    the layout the paged-attention kernels want. A configuration whose
    heads are narrower than a 128-lane row says how many kv heads ride
    side by side in one (`kv_fold`, models/lfm2_moe.py: two 64-wide heads):
    (KVH / fold, N, P, fold * D), the same bytes a token, a row the
    kernels can tile (engine/attention.py `folded`). The heads of a row
    are neighbours, so k (..., KVH, D) becomes its rows by a reshape."""
    fold = getattr(cfg, "kv_fold", 1)
    return (cfg.num_kv_heads // fold, num_pages, cfg.page_size,
            cfg.head_dim * fold)


def kv_block_shape(cfg, n_pages: Optional[int] = None) -> tuple:
    """[k; v] of all layers on the wire and in the tiers:
    (2, L, KVH, P, D) for one block, (2, L, KVH, n, P, D) for a run of
    `n_pages` pages (a disaggregated prefill's export)."""
    kvh, n, p, d = kv_layer_shape(cfg, n_pages)
    run = () if n_pages is None else (n,)
    return (2, cfg.num_layers, kvh, *run, p, d)


def kv_page_bytes(cfg, dtype_itemsize: int = 2) -> int:
    """Bytes one KV page reserves on device (k + v, all layers)."""
    return math.prod(kv_block_shape(cfg)) * dtype_itemsize


def state_shapes(cfg, num_slots: int) -> tuple:
    """What a layer with a recurrent operator keeps a sequence, for
    `num_slots` slots: the pair of arrays that stands where an attention
    layer's K and V pages stand, each (shape, dtype). The configuration
    answers what a slot of one such layer holds (`slot_state`: a Mamba-2
    layer its convolution's last inputs and its float32 SSM state,
    models/nemotron_h.py; a gated short convolution the inputs of its two
    older taps, models/lfm2_moe.py; None in a dtype: the activations');
    attention layers keep pages (`kv_layer_shape`), FFNs nothing."""
    return tuple(((num_slots, *shape), dtype or cfg.dtype)
                 for shape, dtype in cfg.slot_state)


def state_slot_bytes(cfg, dtype_itemsize: int = 2) -> int:
    """Bytes one slot reserves on device, all recurrent layers."""
    import numpy as np

    return cfg.state_layers * sum(
        math.prod(shape) * (np.dtype(dtype).itemsize if dtype
                            else dtype_itemsize)
        for shape, dtype in cfg.slot_state)


class SlotPool:
    """Free list of the state slots of a model with recurrent layers: a
    sequence owns one from admission to its end. Slot 0 is scratch, as
    page 0 is: invalid lanes and padding rows point at it."""

    def __init__(self, num_slots: int) -> None:
        self.num_slots = num_slots                 # incl. scratch slot 0
        self._free = list(range(num_slots - 1, 0, -1))

    @property
    def in_use(self) -> int:
        return self.num_slots - 1 - len(self._free)

    def take(self) -> int:
        if not self._free:
            raise BlockStateInvalid("no free state slot: more sequences "
                                    "running than max_batch_size")
        return self._free.pop()

    def give(self, slot: int) -> None:
        if not 0 < slot < self.num_slots or slot in self._free:
            raise BlockStateInvalid(f"state slot {slot} returned twice "
                                    "or never taken")
        self._free.append(slot)


class BlockStateInvalid(RuntimeError):
    """An illegal block-lifecycle transition (ref `block_manager/block/
    state.rs` BlockStateInvalid). Raising loudly here is the point:
    the silent version of each of these (double-release corrupting a
    refcount, registering a freed page, evicting an in-use block) ships
    ANOTHER sequence's KV to a reader with no error."""


# Block lifecycle (ref state.rs BlockState::{Reset,Partial,Complete,
# Registered}): RESET pages live in the free list with no _Page entry;
# an allocated page is PARTIAL (being written); register_page seals it
# COMPLETE (hashes fixed, immutable) and — when it wins the seq_hash —
# REGISTERED (published for prefix reuse). Only COMPLETE/REGISTERED
# pages may go inactive and be evicted; eviction returns them to RESET.
PARTIAL = "partial"
COMPLETE = "complete"          # sealed, but another page owns the hash
REGISTERED = "registered"      # sealed + published in _registered


@dataclass
class _Page:
    page_id: int
    refcount: int = 0
    state: str = PARTIAL
    seq_hash: Optional[int] = None       # set when sealed
    local_hash: Optional[int] = None
    parent_seq_hash: Optional[int] = None


class PagePool:
    def __init__(self, num_pages: int, page_size: int, worker_id: int = 0,
                 dp_rank: int = 0,
                 event_sink: Optional[EventSink] = None) -> None:
        # page 0 reserved as scratch
        self.num_pages = num_pages
        self.page_size = page_size
        self.worker_id = worker_id
        self.dp_rank = dp_rank
        self.event_sink = event_sink
        # KVBM offload hook: called with a BATCH of (page_id, seq_hash)
        # pairs just before registered pages are evicted, while their
        # device data is still intact — one hook call per eviction batch so
        # the manager pays one device gather, not one sync per page
        self.evict_hook: Optional[Callable[[list[tuple[int, int]]], None]] \
            = None
        # KV lifecycle flight recorder (kvbm/lifecycle.py): None unless
        # DYN_KV_LIFECYCLE armed it — every touch below is one
        # `is not None` check and never changes allocator behavior
        self.lifecycle = None
        self._free: list[int] = list(range(num_pages - 1, 0, -1))
        self._pages: dict[int, _Page] = {}
        self._registered: dict[int, int] = {}       # seq_hash -> page_id
        self._inactive: OrderedDict[int, None] = OrderedDict()  # LRU page ids
        # pending-offload pins (async KVBM pipeline, docs/kvbm.md): the
        # evict hook may CLAIM evicted registered pages instead of copying
        # their device data inline. A pinned page is in limbo — out of
        # _registered/_inactive/_free — and must not be recycled until the
        # offload worker's device gather lands and releases the pin; its
        # device data stays intact because only allocated pages are ever
        # written.
        self._pending_offload: set[int] = set()
        self._event_ids = itertools.count(1)

    # -- introspection ------------------------------------------------------

    @property
    def capacity(self) -> int:
        return self.num_pages - 1

    @property
    def active_pages(self) -> int:
        return self.capacity - len(self._free) - len(self._inactive)

    @property
    def used_pages(self) -> int:
        return self.capacity - len(self._free)

    @property
    def pending_offload_pages(self) -> int:
        """Pages pinned for a not-yet-landed tier offload. They count as
        active/used (their HBM is genuinely unavailable) but free again
        without any sequence finishing, so admission watermarks should
        net them out (engine._admit does)."""
        return len(self._pending_offload)

    def usage(self) -> float:
        return self.active_pages / self.capacity if self.capacity else 1.0

    def can_allocate(self, n: int) -> bool:
        return len(self._free) + len(self._inactive) >= n

    # -- allocation ---------------------------------------------------------

    def match_prefix(self, seq_hashes: list[int]) -> list[int]:
        """Longest chain of registered pages covering the leading blocks."""
        out = []
        for h in seq_hashes:
            pid = self._registered.get(h)
            if pid is None:
                break
            out.append(pid)
        return out

    def acquire(self, page_id: int) -> None:
        page = self._pages.get(page_id)
        if page is None:
            raise BlockStateInvalid(
                f"acquire of freed/unknown page {page_id}")
        if page.refcount == 0:
            self._inactive.pop(page_id, None)
        page.refcount += 1

    def allocate_page(self) -> Optional[int]:
        """One fresh (writable) page; evicts LRU inactive if needed.
        An eviction can succeed WITHOUT freeing — the hook may pin the
        victim for deferred offload. Evict at most once and report
        exhaustion rather than looping: draining the whole LRU into
        pins would trash the prefix cache for one page; the caller
        retries after the offload worker recycles the pins."""
        if not self._free:
            if not self._evict_one() or not self._free:
                return None
        pid = self._free.pop()
        self._pages[pid] = _Page(page_id=pid, refcount=1)
        if self.lifecycle is not None:
            self.lifecycle.on_allocate(pid)
        return pid

    def allocate_sequence(self, seq_hashes: list[int], total_len: int
                          ) -> Optional[tuple[list[int], int]]:
        """Pages for a new sequence of `total_len` tokens whose complete
        blocks hash to `seq_hashes`. Returns (page_ids, cached_len) or None
        if capacity is insufficient. Guarantees cached_len < total_len so
        at least one token is computed (its logits are needed)."""
        matched = self.match_prefix(seq_hashes)
        if len(matched) * self.page_size >= total_len:
            matched = matched[:(total_len - 1) // self.page_size]
        need_pages = (total_len + self.page_size - 1) // self.page_size
        fresh_needed = need_pages - len(matched)
        # acquire matched pages FIRST: they may be sitting in _inactive and
        # must leave the LRU before any eviction can pick them as victims
        for pid in matched:
            self.acquire(pid)
        pages = list(matched)
        if len(self._free) + len(self._inactive) < fresh_needed:
            self.release_sequence(pages)
            return None
        # pre-evict the whole deficit now: one batched offload-hook call
        # instead of one device sync per page inside the allocate loop
        deficit = fresh_needed - len(self._free)
        if deficit > 0:
            self._evict_many(deficit, cause="admission-deficit")
        for _ in range(fresh_needed):
            pid = self.allocate_page()
            # reachable when the evict hook pinned the victims for
            # deferred offload: evicted-but-not-freed, so the capacity
            # estimate above was optimistic — caller retries next step
            if pid is None:
                self.release_sequence(pages)
                return None
            pages.append(pid)
        if self.lifecycle is not None:
            for h in seq_hashes[:len(matched)]:
                self.lifecycle.on_hit(h, self.page_size)
        return pages, len(matched) * self.page_size

    # -- registration / release --------------------------------------------

    def register_page(self, page_id: int, seq_hash: int, local_hash: int,
                      parent_seq_hash: int) -> None:
        """Seal a PARTIAL page (complete+immutable; ref state.rs
        Partial→Complete→Registered) and publish the stored event."""
        page = self._pages.get(page_id)
        if page is None:
            raise BlockStateInvalid(
                f"register of freed/unknown page {page_id}")
        if page.seq_hash is not None:
            # idempotent re-registration of the SAME content (shared
            # prefix pages re-walked by a second sequence) is legal;
            # resealing with different hashes is the corruption case
            if page.seq_hash != seq_hash:
                raise BlockStateInvalid(
                    f"page {page_id} already sealed as "
                    f"{page.seq_hash:#x}, re-register as {seq_hash:#x}")
            return
        page.seq_hash = seq_hash
        page.local_hash = local_hash
        page.parent_seq_hash = parent_seq_hash
        # first writer wins; duplicate content on another page stays
        # COMPLETE (unregistered-for-reuse) but still evictable
        if self._registered.setdefault(seq_hash, page_id) == page_id:
            page.state = REGISTERED
        else:
            page.state = COMPLETE
        if self.lifecycle is not None:
            self.lifecycle.on_register(page_id, seq_hash)
        if self.event_sink is not None:
            self.event_sink(KvCacheEvent(
                kind=KV_STORED, worker_id=self.worker_id,
                dp_rank=self.dp_rank, event_id=next(self._event_ids),
                parent_seq_hash=parent_seq_hash,
                blocks=[StoredBlock(seq_hash, local_hash)]))
            if self.lifecycle is not None:
                self.lifecycle.on_kv_event(KV_STORED, 1)

    def release_sequence(self, page_ids: list[int]) -> None:
        for pid in page_ids:
            page = self._pages.get(pid)
            if page is None:
                continue
            if page.refcount <= 0:
                # double-release: silently decrementing would let the
                # page be freed while a later holder still writes it
                raise BlockStateInvalid(
                    f"release of page {pid} with refcount "
                    f"{page.refcount}")
            page.refcount -= 1
            if page.refcount > 0:
                continue
            if page.seq_hash is not None \
                    and self._registered.get(page.seq_hash) == pid:
                self._inactive[pid] = None       # reusable, evict-last
                self._inactive.move_to_end(pid)
            else:
                self._discard(page)

    def clear_inactive(self) -> int:
        """Admin clear (ref `http/service/clear_kv_blocks.rs`): drop every
        reusable cached page, publishing removed events so routers forget
        them too. In-flight (refcounted) pages are untouched. The KVBM
        offload hook deliberately does NOT fire — clearing means
        forgetting, not demoting to a slower tier."""
        return self._evict_many(len(self._inactive), fire_hook=False,
                                cause="clear")

    # -- pending-offload pins (async KVBM pipeline) -------------------------

    def pin_for_offload(self, page_ids: list[int]) -> None:
        """Claim eviction victims for a deferred tier copy. ONLY legal
        from inside the evict hook, while the victims' device data is
        still intact: pinned victims skip the free-list return at the
        end of `_evict_many` and are recycled by `release_offload_pin`
        once their gather lands."""
        for pid in page_ids:
            page = self._pages.get(pid)
            if page is None:
                raise BlockStateInvalid(
                    f"offload pin of freed/unknown page {pid}")
            if page.refcount != 0 or page.state == PARTIAL:
                raise BlockStateInvalid(
                    f"offload pin of page {pid} in state {page.state} "
                    f"refcount {page.refcount}")
            self._pending_offload.add(pid)
        if self.lifecycle is not None and page_ids:
            self.lifecycle.on_pin(len(page_ids))

    def release_offload_pin(self, page_ids: list[int]) -> None:
        """The deferred gather landed (or was abandoned): recycle the
        pinned pages. Idempotent — close paths may race the worker's
        own cleanup."""
        released = 0
        for pid in page_ids:
            if pid not in self._pending_offload:
                continue
            self._pending_offload.discard(pid)
            released += 1
            page = self._pages.get(pid)
            if page is not None:
                self._discard(page)
        if self.lifecycle is not None and released:
            self.lifecycle.on_unpin(released)

    def _discard(self, page: _Page) -> None:
        self._pages.pop(page.page_id, None)
        self._free.append(page.page_id)

    def _evict_one(self) -> bool:
        return self._evict_many(1) == 1

    def _evict_many(self, n: int, fire_hook: bool = True,
                    cause: str = "capacity-pressure") -> int:
        """Evict up to n LRU inactive pages; ONE offload-hook call for the
        whole batch (device data still intact when it fires).
        ``fire_hook=False`` for admin clears: drop, don't offload.
        ``cause`` is lifecycle-recorder attribution only (capacity-
        pressure = allocate_page, admission-deficit = allocate_sequence
        pre-evict, clear = clear_inactive) — it never changes victim
        selection."""
        victims: list[_Page] = []
        while len(victims) < n and self._inactive:
            pid, _ = self._inactive.popitem(last=False)   # LRU
            victim = self._pages[pid]
            if victim.refcount != 0 or victim.state == PARTIAL:
                # the inactive LRU must only ever hold sealed, idle
                # pages — evicting an in-use or still-writable block
                # would hand its device data to the next allocator
                raise BlockStateInvalid(
                    f"evicting page {pid} in state {victim.state} "
                    f"refcount {victim.refcount}")
            victims.append(victim)
        registered = [p for p in victims if p.seq_hash is not None]
        if registered and fire_hook and self.evict_hook is not None:
            self.evict_hook([(p.page_id, p.seq_hash) for p in registered])
        for page in registered:
            self._registered.pop(page.seq_hash, None)
            if self.lifecycle is not None:
                self.lifecycle.on_evict(page.seq_hash, cause)
            if self.event_sink is not None:
                self.event_sink(KvCacheEvent(
                    kind=KV_REMOVED, worker_id=self.worker_id,
                    dp_rank=self.dp_rank, event_id=next(self._event_ids),
                    seq_hashes=[page.seq_hash]))
                if self.lifecycle is not None:
                    self.lifecycle.on_kv_event(KV_REMOVED, 1)
        for page in victims:
            # a hook that pinned the page (pin_for_offload) owns its
            # recycling; everything else frees immediately as before
            if page.page_id in self._pending_offload:
                continue
            self._discard(page)
        return len(victims)
