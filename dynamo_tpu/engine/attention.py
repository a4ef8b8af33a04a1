"""Paged attention: XLA reference implementation + TPU pallas kernel path.

The XLA path is pure lax ops, so it runs on any backend and partitions under
`jit` + sharding annotations (tensor parallelism over the kv-head axis).
The pallas path is this module's own two kernels, under the names a device
trace shows. `paged_decode_attention`: decode is the HBM-bandwidth-bound hot
loop, and the kernel reads a lane's live pages and nothing else.
`paged_prefill_attention`: a chunk's query tiles walk only the KV blocks they
can see, flash style, and no `[T, context]` score tensor exists. Both fetch a
page of all kv heads per copy, are selected automatically on TPU and run once
per "tp" shard under a tensor-parallel mesh.

Cache layout (both paths): K/V pages per layer are
``(num_kv_heads, num_pages, page_size, head_dim)``.
"""

from __future__ import annotations

import functools
import logging
import os

import jax
import jax.numpy as jnp

from dynamo_tpu.runtime.metrics import Counter

logger = logging.getLogger(__name__)

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

_IMPLS = ("auto", "xla", "pallas", "ragged")

# Global switch: "auto" | "xla" | "pallas" | "ragged". Trace-time
# constant. "ragged" arms the flat-token dispatch path (engine/ragged.py
# + the engine's ragged_step entry); kernel-vs-XLA selection within it
# still follows the "auto" backend logic. Seeded from DYN_ATTENTION_IMPL
# so deployments flip it without code.
_impl = os.environ.get("DYN_ATTENTION_IMPL", "auto").strip().lower()
if _impl not in _IMPLS:
    _impl = "auto"


def set_attention_impl(impl: str) -> None:
    global _impl
    assert impl in _IMPLS, impl
    _impl = impl


def ragged_enabled() -> bool:
    """True when the engine should route batches through the flat-token
    ragged entry instead of the prefill/decode/mixed shape zoo."""
    return _impl == "ragged"


def use_pallas() -> bool:
    if _impl == "pallas":
        return True
    if _impl == "xla":
        return False
    # auto/ragged: follow the device the process computes on — an
    # explicit jax_default_device pin (the tests pin the CPU) wins over
    # the default backend
    dev = jax.config.jax_default_device
    if dev is not None:
        return dev.platform == "tpu"
    return jax.default_backend() == "tpu"


# Fallback attribution: the kernel path can silently decline a dispatch
# (unaligned head_dim, ragged-ineligible geometry) and the profiler needs
# to know the slow path ran. Incremented at TRACE time — once per
# compiled shape that fell back, which is the actionable signal (every
# execution of that shape falls back). EngineMetrics.register adopts it
# into /metrics.
attention_fallbacks = Counter(
    "dynamo_attention_fallback_total",
    "attention dispatches that fell back to the XLA path, by reason "
    "(counted at trace time, once per compiled shape)")
_warned_reasons: set[str] = set()


def _note_fallback(reason: str) -> None:
    attention_fallbacks.inc(reason=reason)
    if reason not in _warned_reasons:
        _warned_reasons.add(reason)
        logger.warning(
            "attention falling back to the XLA path (reason=%s) — "
            "logged once; see dynamo_attention_fallback_total", reason)


@functools.lru_cache(maxsize=None)
def block_choice(max_pages: int, page_size: int) -> int:
    """Pages per compute block of `ragged.ragged_paged_attention`, whose
    grid walks every block of the page table: about a quarter of the
    maximum context, at least 256 tokens, snapped to the largest divisor
    of max_pages (the block count has to tile the table exactly). Bigger
    blocks read more padding past each row's position, smaller ones pay
    more grid steps. The decode kernel sizes its blocks from the operand
    shapes instead (`decode_geometry`). Cached: the geometry set is tiny.
    """
    want_tokens = max(256, (max_pages * page_size) // 4)
    want = max(1, want_tokens // page_size)
    ppcb = 1
    for cand in range(1, max_pages + 1):
        if max_pages % cand == 0 and cand <= want:
            ppcb = cand
    return ppcb


def _repeat_kv(x: jax.Array, groups: int, axis: int) -> jax.Array:
    """GQA: repeat kv heads to match query heads."""
    return jnp.repeat(x, groups, axis=axis) if groups > 1 else x


def visible(s_pos: jax.Array, q_pos: jax.Array, attn_block: int
            ) -> jax.Array:
    """Key `s_pos` is visible to query `q_pos` iff s_pos // attn_block <=
    q_pos // attn_block: every key up to the end of the query's own
    block. Block 1 is the causal rule, written as it always was so that
    it lowers to what it lowered to. THE visibility rule of the XLA path
    and of the prefill kernel."""
    if attn_block == 1:
        return s_pos <= q_pos
    return s_pos < (q_pos // attn_block + 1) * attn_block


def prefill_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      page_table: jax.Array, q_positions: jax.Array,
                      seq_len: jax.Array, page_size: int,
                      attn_block: int = 1,
                      scale: float | None = None) -> jax.Array:
    """Causal attention for one sequence's prefill, reading K/V from pages
    (`attn_block` > 1: causal by blocks of that many positions).

    q: (T, H, D); k_pages/v_pages: (KVH, N, P, D); page_table: (max_pages,);
    q_positions: (T,) absolute positions; seq_len: scalar valid length.
    Returns (T, H, D). Quadratic XLA attention over the whole page table,
    f32 scores `[H, T, context]`: the path off the TPU, the fallback of
    `paged_attention_prefill` and the reference `paged_prefill_attention`
    is tested against.
    """
    kvh, _, p, d = k_pages.shape
    h = q.shape[1]
    groups = h // kvh
    # Gather this sequence's K/V: (KVH, max_pages, P, D) -> (KVH, S, D)
    k = k_pages[:, page_table].reshape(kvh, -1, d)
    v = v_pages[:, page_table].reshape(kvh, -1, d)
    k = _repeat_kv(k, groups, axis=0)                      # (H, S, D)
    v = _repeat_kv(v, groups, axis=0)
    scores = jnp.einsum("thd,hsd->hts", q.astype(jnp.float32),
                        k.astype(jnp.float32))
    scores = scores / (d ** 0.5) if scale is None else scores * scale
    s_pos = jnp.arange(k.shape[1])
    mask = visible(s_pos[None, :], q_positions[:, None], attn_block) \
        & (s_pos[None, :] < seq_len)                       # (T, S)
    scores = jnp.where(mask[None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hts,hsd->thd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def mixed_attention(q_dec: jax.Array, q_chunk: jax.Array,
                    k_pages: jax.Array, v_pages: jax.Array,
                    dec_lengths: jax.Array, dec_tables: jax.Array,
                    chunk_tables: jax.Array, chunk_positions: jax.Array,
                    chunk_seq_lens: jax.Array,
                    page_size: int) -> tuple[jax.Array, jax.Array]:
    """One attention entry for a MIXED prefill+decode dispatch: the
    decode sub-batch routes through `paged_attention_decode` and the
    chunk sub-batch through `paged_attention_prefill`, against the same page
    caches, inside one traced step (models/llama.py mixed_prefill_decode
    jits the whole thing; compile shapes bucket on (decode width, chunk
    tokens)). The two sub-batches are different sequences with disjoint
    page tables, so neither side reads the other's in-flight writes and
    each sub-batch's numerics are exactly the stand-alone kernel's.

    q_dec: (B, H, D); q_chunk: (Bp, T, H, D); dec_lengths: (B,);
    dec_tables: (B, max_pages); chunk_tables: (Bp, max_pages);
    chunk_positions: (Bp, T); chunk_seq_lens: (Bp,).
    Returns (dec_out (B, H, D), chunk_out (Bp, T, H, D)).
    """
    dec_out = paged_attention_decode(
        q_dec, k_pages, v_pages, dec_lengths, dec_tables,
        page_size=page_size)
    chunk_out = paged_attention_prefill(
        q_chunk, k_pages, v_pages, chunk_tables, chunk_positions[:, 0],
        chunk_seq_lens, page_size=page_size)
    return dec_out, chunk_out


def paged_attention_decode(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, lengths: jax.Array,
                           page_tables: jax.Array, page_size: int,
                           scale: float | None = None) -> jax.Array:
    """One-token-per-sequence paged attention.

    q: (B, H, D); k_pages/v_pages: (KVH, N, P, D); lengths: (B,) valid
    lengths (0 = padding lane); page_tables: (B, max_pages). → (B, H, D).
    A cache whose rows are wider than a head: `folded`.
    """
    if k_pages.shape[-1] != q.shape[-1]:
        return folded(paged_attention_decode, q, k_pages, v_pages, lengths,
                      page_tables, page_size)
    # Mosaic tiling constraint: last dims must align to (8, 128) lanes —
    # head_dim must be a multiple of 128 for the kernel's block specs.
    if use_pallas():
        if q.shape[-1] % 128 == 0:
            return _pallas_decode(q, k_pages, v_pages, lengths,
                                  page_tables, scale)
        _note_fallback("head_dim")
    return _xla_decode(q, k_pages, v_pages, lengths, page_tables, scale)


def folded(core, q: jax.Array, k_pages: jax.Array, *rest, **kw):
    """`core(q, k_pages, ...)` where the cache's rows are wider than a
    head: `fold` kv heads side by side in one row (engine/pages.py
    `kv_layer_shape`: two 64-wide heads a 128-lane row, the cache's
    published bytes and a row the kernels can tile). Each q head is laid
    into its kv head's lanes of a row-wide vector of zeros, so that the
    products the cores make as they always did, `fold * groups` q heads
    to a row, give its scores against its own kv head alone (the zeros
    meet the neighbours' lanes; the MXU contracts 128 deep either way);
    the output's other lanes, the neighbours' values under this head's
    probabilities, are dropped. `core` takes `scale=`, the scores' factor
    of the head's own width. q (..., H, D) -> (..., H, D)."""
    d = q.shape[-1]
    fold = k_pages.shape[-1] // d
    h = q.shape[-2]
    groups = h // (k_pages.shape[0] * fold)
    mine = jax.nn.one_hot((jnp.arange(h) // groups) % fold, fold,
                          dtype=jnp.bool_)[:, :, None]      # (H, fold, 1)
    wide = jnp.where(mine, q[..., None, :], 0).reshape(
        q.shape[:-1] + (fold * d,))
    out = core(wide, k_pages, *rest, scale=1.0 / (d ** 0.5), **kw)
    out = out.reshape(out.shape[:-1] + (fold, d))
    return jnp.sum(jnp.where(mine, out, 0), axis=-2)


def _xla_decode(q, k_pages, v_pages, lengths, page_tables, scale=None):
    kvh, _, p, d = k_pages.shape
    b, h, _ = q.shape
    groups = h // kvh
    # (KVH, B, max_pages, P, D) -> (B, KVH, S, D)
    k = jnp.moveaxis(k_pages[:, page_tables], 0, 1).reshape(b, kvh, -1, d)
    v = jnp.moveaxis(v_pages[:, page_tables], 0, 1).reshape(b, kvh, -1, d)
    k = _repeat_kv(k, groups, axis=1)                      # (B, H, S, D)
    v = _repeat_kv(v, groups, axis=1)
    scores = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                        k.astype(jnp.float32))
    scores = scores / (d ** 0.5) if scale is None else scores * scale
    s_pos = jnp.arange(k.shape[2])
    mask = s_pos[None, :] < lengths[:, None]               # (B, S)
    scores = jnp.where(mask[:, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # fully-masked (padding) lanes: softmax is uniform; output is garbage
    # but the scheduler ignores padding lanes' logits.
    out = jnp.einsum("bhs,bhsd->bhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# VMEM one K+V block of the decode kernel may take, and how many such
# blocks are in flight or in use at once. Measured on v5e (PERF.md §6,
# PR 28); the block's page count follows from the operand shapes. Alone
# on the chip 2, 3 and 4 slots read alike; 3 keeps two copies in flight
# where a lane's last block holds one live page and the next lane's
# first follows it, and is what every serving run was measured with.
_DECODE_BLOCK_BYTES = 512 * 1024
_DECODE_SLOTS = 3
_DECODE_Q_BYTES = 1 << 20


@functools.lru_cache(maxsize=None)
def decode_geometry(batch: int, kvh: int, groups: int, page_size: int,
                    head_dim: int, itemsize: int) -> tuple[int, int]:
    """(pages per KV block, lanes per grid step) of `paged_decode_attention`
    for these operand shapes.

    A block is as many whole pages of all kv heads, K and V, as fit
    `_DECODE_BLOCK_BYTES`, in multiples of 128 tokens (the scores' lane
    width) where the page size divides 128: 128 tokens at 8 kv heads, 256
    at 4, 512 at 2 (a tp=4 shard of Mistral), so a block costs the same
    bytes and the same vector work whatever the model. Lanes per grid
    step: the largest divisor of the batch whose q rows, each group
    padded to a (16, 128) tile in VMEM, stay under `_DECODE_Q_BYTES`.
    """
    page_bytes = 2 * kvh * page_size * head_dim * itemsize
    ppb = max(1, _DECODE_BLOCK_BYTES // page_bytes)
    unit = max(1, 128 // page_size)
    ppb = max(unit, ppb // unit * unit)
    lane_bytes = kvh * (-(-groups // 16) * 16) * head_dim * itemsize
    lanes = max(c for c in range(1, batch + 1)
                if batch % c == 0 and (c == 1
                                       or c * lane_bytes <= _DECODE_Q_BYTES))
    return ppb, lanes


def paged_decode_attention(q, k_pages, v_pages, lengths, page_tables, *,
                           scale=None, interpret=False):
    """The decode attention kernel (signature = `_xla_decode`; `scale`:
    the scores' factor where it is not 1 / sqrt(row width), `folded`).

    Reads only what is live: a lane walks `cdiv(length, block tokens)`
    blocks of its own page table, a padding lane (`length == 0`) none,
    and a table entry past a lane's last live page is never followed (the
    slots of a last block that lie beyond it fetch that last page again;
    their scores are masked). One async copy moves a page of *all* kv
    heads, `(KVH, P, D)` out of the `(KVH, N, P, D)` cache. The
    (lane, block) items of a grid step form one queue through
    `_DECODE_SLOTS` VMEM slots: while an item is computed the next ones
    are in flight, across lanes too. Per kv head the `groups` q rows meet
    the block in one `(groups, D) x (D, tokens)` product; scores, softmax
    state and accumulator are f32. The probabilities enter the second
    product as bf16 high + low halves in one LHS, so KV in bf16 loses
    nothing against f32 probabilities (2**-17 relative). Padding lanes
    return zeros.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kvh, _, p, d = k_pages.shape
    b, h, _ = q.shape
    groups = h // kvh
    max_pages = page_tables.shape[1]
    kv_dtype = k_pages.dtype
    ppb, lanes = decode_geometry(b, kvh, groups, p, d, kv_dtype.itemsize)
    t = ppb * p
    slots = _DECODE_SLOTS
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    f32 = jnp.float32
    # q and K meet in their own dtype when it is one (bf16 products are
    # exact in the f32 accumulator), else in f32
    qk_dtype = kv_dtype if q.dtype == kv_dtype else f32
    qk_precision = jax.lax.Precision.HIGHEST if qk_dtype == f32 else None
    precision = jax.lax.Precision.HIGHEST if kv_dtype == f32 else None
    split = kv_dtype != f32           # probabilities as high + low halves
    gp = -(-groups // 8) * 8          # a kv head's q rows, in whole tiles

    def across(x, n):
        """(rows, 128) state, the same in every column, as (rows, n)."""
        return x if n == x.shape[1] else jnp.broadcast_to(
            x[:, :1], (x.shape[0], n))

    def kernel(len_ref, tab_ref, q_ref, k_hbm, v_hbm, o_ref,
               kbuf, vbuf, ksem, vsem, m_ref, l_ref, acc_ref):
        lo = pl.program_id(0) * lanes
        hi = lo + lanes

        def blocks(lane):
            return pl.cdiv(len_ref[lane], t)

        def live_from(lane):
            """First lane >= `lane` of this step that holds tokens, or hi."""
            return jax.lax.while_loop(
                lambda x: (x < hi) & (len_ref[jnp.minimum(x, hi - 1)] == 0),
                lambda x: x + 1, lane)

        def advance(lane, blk):
            more = (lane < hi) & (blk + 1 < blocks(jnp.minimum(lane, hi - 1)))
            return jax.lax.cond(
                more, lambda: (lane, blk + 1),
                lambda: (live_from(jnp.minimum(lane + 1, hi)), jnp.int32(0)))

        def start(lane, blk, slot):
            """Fetch a block into a slot, a page of all kv heads a copy;
            nothing once the cursor has left the step's lanes."""
            @pl.when(lane < hi)
            def _():
                last = pl.cdiv(len_ref[lane], p) - 1    # last live page
                for i in range(ppb):
                    page = tab_ref[lane * max_pages
                                   + jnp.minimum(blk * ppb + i, last)]
                    dst = pl.ds(i * p, p)
                    pltpu.make_async_copy(k_hbm.at[:, page],
                                          kbuf.at[slot, :, dst],
                                          ksem.at[slot]).start()
                    pltpu.make_async_copy(v_hbm.at[:, page],
                                          vbuf.at[slot, :, dst],
                                          vsem.at[slot]).start()

        def item(w, cursors):
            lane, blk, ahead_lane, ahead_blk = cursors
            slot = w % slots
            start(ahead_lane, ahead_blk, (w + slots - 1) % slots)
            row = lane - lo

            @pl.when(blk == 0)
            def _():
                m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
                l_ref[...] = jnp.zeros_like(l_ref)
                acc_ref[...] = jnp.zeros_like(acc_ref)

            mask = (blk * t + jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
                    < len_ref[lane])
            # one wait a cache for the block's ppb page copies: a wait
            # counts the bytes of its destination, here the whole slot
            pltpu.make_async_copy(kbuf.at[slot], kbuf.at[slot],
                                  ksem.at[slot]).wait()
            pltpu.make_async_copy(vbuf.at[slot], vbuf.at[slot],
                                  vsem.at[slot]).wait()
            # every kv head's product first, then one softmax update over
            # all q rows: the heads' chains overlap instead of queueing
            s = jnp.concatenate([jax.lax.dot_general(
                q_ref[row, g], kbuf[slot, g].astype(qk_dtype),
                (((1,), (1,)), ((), ())), precision=qk_precision,
                preferred_element_type=f32) for g in range(kvh)], axis=0)
            s = jnp.where(mask, s * scale, _NEG_INF)        # (kvh * gp, t)
            m_prev = m_ref[...]                             # (kvh * gp, 128)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a block in the queue holds a live token, so m_new is a real
            # score and exp() of a masked one is exactly 0
            pr = jnp.exp(s - across(m_new, t))
            l_ref[...] = alpha * l_ref[...] + jnp.sum(pr, axis=1,
                                                      keepdims=True)
            m_ref[...] = m_new
            if split:
                high = pr.astype(kv_dtype).astype(f32)
                low = pr - high
            pv = []
            for g in range(kvh):
                mine = slice(g * gp, (g + 1) * gp)
                lhs = (jnp.concatenate([high[mine], low[mine]], axis=0)
                       if split else pr[mine])
                out = jax.lax.dot_general(
                    lhs.astype(kv_dtype), vbuf[slot, g],
                    (((1,), (0,)), ((), ())), precision=precision,
                    preferred_element_type=f32)
                pv.append(out[:gp] + out[gp:] if split else out)
            acc_ref[...] = (across(alpha, d) * acc_ref[...]
                            + jnp.concatenate(pv, axis=0))

            @pl.when(blk == blocks(lane) - 1)
            def _():
                out = acc_ref[...] / across(l_ref[...], d)
                for g in range(kvh):
                    o_ref[row, g] = out[g * gp:(g + 1) * gp].astype(
                        o_ref.dtype)

            return (*advance(lane, blk), *advance(ahead_lane, ahead_blk))

        o_ref[...] = jnp.zeros_like(o_ref)
        first = live_from(lo)
        ahead = (first, jnp.int32(0))
        for slot in range(slots - 1):
            start(*ahead, slot)
            ahead = advance(*ahead)
        n_items = jax.lax.fori_loop(lo, hi, lambda i, n: n + blocks(i),
                                    jnp.int32(0))
        jax.lax.fori_loop(0, n_items, item, (first, jnp.int32(0), *ahead))

    q_block = pl.BlockSpec((lanes, kvh, gp, d),
                           lambda c, lens, tabs: (c, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // lanes,),
            in_specs=[q_block, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_block,
            scratch_shapes=[
                pltpu.VMEM((slots, kvh, t, d), kv_dtype),
                pltpu.VMEM((slots, kvh, t, d), kv_dtype),
                pltpu.SemaphoreType.DMA((slots,)),
                pltpu.SemaphoreType.DMA((slots,)),
                pltpu.VMEM((kvh * gp, 128), f32),          # m
                pltpu.VMEM((kvh * gp, 128), f32),          # l
                pltpu.VMEM((kvh * gp, d), f32),            # acc
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kvh, gp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(lengths.astype(jnp.int32), page_tables.astype(jnp.int32).reshape(-1),
      jnp.pad(q.astype(qk_dtype).reshape(b, kvh, groups, d),
              ((0, 0), (0, 0), (0, gp - groups), (0, 0))), k_pages, v_pages)
    return out[:, :, :groups].reshape(b, h, d)


def _pallas_decode(q, k_pages, v_pages, lengths, page_tables, scale=None):
    from dynamo_tpu.engine.kernels import (KV_SPEC, REP_SPEC, ROW_SPEC,
                                           per_tp_shard)

    return per_tp_shard(
        paged_decode_attention if scale is None else functools.partial(
            paged_decode_attention, scale=scale),
        (ROW_SPEC, KV_SPEC, KV_SPEC, REP_SPEC, REP_SPEC),
        ROW_SPEC)(q, k_pages, v_pages, lengths, page_tables)


def paged_attention_prefill(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, page_tables: jax.Array,
                            q_starts: jax.Array, seq_lens: jax.Array,
                            page_size: int, attn_block: int = 1,
                            scale: float | None = None) -> jax.Array:
    """Causal attention of a round of prefill chunks against the pages
    that already hold them; with `attn_block` > 1 causal by blocks
    (`visible`), every key of a row's own block visible to it.

    q: (Bp, T, H, D), row i of a sequence at position `q_starts + i`;
    k_pages/v_pages: (KVH, N, P, D); page_tables: (Bp, max_pages);
    q_starts/seq_lens: (Bp,) (`seq_len == q_start` = padding lane).
    -> (Bp, T, H, D). Rows at or past `seq_len` are finite and ignored.
    A cache whose rows are wider than a head: `folded`.
    """
    if k_pages.shape[-1] != q.shape[-1]:
        return folded(paged_attention_prefill, q, k_pages, v_pages,
                      page_tables, q_starts, seq_lens, page_size, attn_block)
    kvh, _, p, d = k_pages.shape
    _, t, h, _ = q.shape
    if use_pallas():
        if d % 128:
            _note_fallback("head_dim")
        elif prefill_geometry(kvh, h // kvh, t, p, d,
                              k_pages.dtype.itemsize) is None:
            _note_fallback("chunk_shape")
        else:
            return _pallas_prefill(q, k_pages, v_pages, page_tables,
                                   q_starts, seq_lens, attn_block, scale)
    positions = q_starts[:, None] + jnp.arange(t)[None, :]
    return jax.vmap(
        lambda q1, pt, pos1, sl: prefill_attention(
            q1, k_pages, v_pages, pt, q_positions=pos1, seq_len=sl,
            page_size=page_size, attn_block=attn_block, scale=scale)
    )(q, page_tables, positions, seq_lens)


# The prefill kernel's tiles. A kv head's `groups x tile` q rows meet a KV
# block in one product: at most _PREFILL_ROWS rows, and a block as wide as
# keeps the f32 scores of that product under _PREFILL_SCORE_BYTES and its
# K + V, all kv heads, under _PREFILL_BLOCK_BYTES. Measured on v5e
# (PERF.md §6, PR 32). _PREFILL_VMEM_BYTES is the kernel's scoped-VMEM
# limit: at Mistral's 32 q heads x 256 rows the q and output blocks, the
# slots and the f32 state come to ~35 MB, over the compiler's default 16.
_PREFILL_ROWS = 1024
_PREFILL_SCORE_BYTES = 1 << 20
_PREFILL_BLOCK_BYTES = 1 << 20
_PREFILL_SLOTS = 3
_PREFILL_VMEM_BYTES = 96 << 20


@functools.lru_cache(maxsize=None)
def prefill_geometry(kvh: int, groups: int, chunk: int, page_size: int,
                     head_dim: int, itemsize: int
                     ) -> tuple[int, int] | None:
    """(q tile, pages per KV block) of `paged_prefill_attention` for these
    operand shapes, or None where the kernel declines them.

    The q tile is the largest divisor of the chunk width, in whole (16,
    128) tiles, whose `groups x tile` rows stay within `_PREFILL_ROWS`:
    128 of a 512-token chunk at 7 q heads to a kv head (Qwen2.5-7B), the
    whole 256-token chunk at 4 (Mistral). A chunk of no whole tile (the
    few tokens of a spec-verify) is declined. The KV block is whole pages
    in multiples of 128 tokens, the scores' lane width, within the two
    byte limits above: 256 tokens in both of those models.
    """
    tiles = [c for c in range(16, chunk + 1, 16)
             if chunk % c == 0 and groups * c <= _PREFILL_ROWS]
    if not tiles or (128 % page_size and page_size % 128):
        return None
    tq = tiles[-1]
    unit = max(1, 128 // page_size)
    tokens = min(_PREFILL_SCORE_BYTES // (4 * groups * tq),
                 _PREFILL_BLOCK_BYTES // (2 * kvh * head_dim * itemsize))
    ppb = max(unit, tokens // page_size // unit * unit)
    return tq, ppb


# jitted so that the layers of a step share one trace and one lowering of
# the kernel: inline, 28 layers' worth cost a prefill program 6 s of
# lowering at every start, compile cache or not (PERF.md §6, PR 32)
@functools.partial(jax.jit,
                   static_argnames=("attn_block", "scale", "interpret"))
def paged_prefill_attention(q, k_pages, v_pages, page_tables, q_starts,
                            seq_lens, *, attn_block=1, scale=None,
                            interpret=False):
    """The prefill attention kernel (operands as `paged_attention_prefill`;
    `scale` as in `paged_decode_attention`).
    `attn_block` > 1: a row sees up to the end of its own block
    (`visible`), so a tile walks up to the block end of its last row.

    Grid (sequence, q tile). A tile of queries at positions `[p0, p1)`
    walks KV blocks `0 .. cdiv(min(p1, seq_len), block) - 1` of its
    sequence's page table and no further: nothing past the live length,
    nothing above the diagonal but inside the last block, where the
    reference's mask (`s_pos <= q_position & s_pos < seq_len`) applies.
    A tile that starts at or past `seq_len` (a padding lane, the padded
    end of a bucket) walks none and returns zeros. Pages come as in
    `paged_decode_attention`: one async copy a page of all kv heads
    through `_PREFILL_SLOTS` VMEM slots, the next blocks in flight while
    one is computed; the slots of a tile's last block past its last
    visible page fetch that page again, masked. Per kv head the
    `groups x tile` q rows meet a block in one product, q and K in their
    own dtype with an f32 accumulator; running max, sum and accumulator
    are f32; the probabilities meet V as bf16 high + low halves (f32 KV:
    as they are), so bf16 KV loses nothing against f32 probabilities.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kvh, _, p, d = k_pages.shape
    bp, t, h, _ = q.shape
    groups = h // kvh
    max_pages = page_tables.shape[1]
    kv_dtype = k_pages.dtype
    tq, ppb = prefill_geometry(kvh, groups, t, p, d, kv_dtype.itemsize)
    tk = ppb * p
    rows = groups * tq
    slots = _PREFILL_SLOTS
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    f32 = jnp.float32
    qk_dtype = kv_dtype if q.dtype == kv_dtype else f32
    qk_precision = jax.lax.Precision.HIGHEST if qk_dtype == f32 else None
    precision = jax.lax.Precision.HIGHEST if kv_dtype == f32 else None
    split = kv_dtype != f32           # probabilities as high + low halves

    def kernel(start_ref, len_ref, tab_ref, q_ref, k_hbm, v_hbm, o_ref,
               qbuf, kbuf, vbuf, ksem, vsem, m_ref, l_ref, acc_ref):
        lane = pl.program_id(0)
        seq_len = len_ref[lane]
        p0 = start_ref[lane] + pl.program_id(1) * tq
        # positions a row of this tile can see: none for a tile of padding
        # (the tile's last row sees to the end of its block)
        p1 = p0 + tq if attn_block == 1 else (
            (p0 + tq - 1) // attn_block + 1) * attn_block
        seen = jnp.where(p0 < seq_len, jnp.minimum(p1, seq_len), 0)
        n_blocks = pl.cdiv(seen, tk)
        last = pl.cdiv(seen, p) - 1                     # last visible page

        def start(blk, slot):
            @pl.when(blk < n_blocks)
            def _():
                for i in range(ppb):
                    page = tab_ref[lane * max_pages
                                   + jnp.minimum(blk * ppb + i, last)]
                    dst = pl.ds(i * p, p)
                    pltpu.make_async_copy(k_hbm.at[:, page],
                                          kbuf.at[slot, :, dst],
                                          ksem.at[slot]).start()
                    pltpu.make_async_copy(v_hbm.at[:, page],
                                          vbuf.at[slot, :, dst],
                                          vsem.at[slot]).start()

        for slot in range(slots - 1):
            start(slot, slot)
        # a kv head's rows: its q heads one after the other, `tq` rows each
        for head in range(h):
            g, r = divmod(head, groups)
            qbuf[g, r * tq:(r + 1) * tq] = q_ref[
                0, :, head * d:(head + 1) * d].astype(qk_dtype)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        q_pos = jnp.tile(
            p0 + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0), (groups, 1))

        def block(j, _):
            slot = j % slots
            start(j + slots - 1, (j + slots - 1) % slots)
            s_pos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
            mask = (visible(s_pos, q_pos, attn_block)
                    & (s_pos < seq_len))                    # (rows, tk)
            # one wait a cache for the block's ppb page copies: a wait
            # counts the bytes of its destination, here the whole slot
            pltpu.make_async_copy(kbuf.at[slot], kbuf.at[slot],
                                  ksem.at[slot]).wait()
            pltpu.make_async_copy(vbuf.at[slot], vbuf.at[slot],
                                  vsem.at[slot]).wait()
            for g in range(kvh):
                s = jax.lax.dot_general(
                    qbuf[g], kbuf[slot, g].astype(qk_dtype),
                    (((1,), (1,)), ((), ())), precision=qk_precision,
                    preferred_element_type=f32)
                s = jnp.where(mask, s * scale, _NEG_INF)
                m_prev = m_ref[g]                           # (rows, 128)
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # position 0 is visible to every row of a tile that walks
                # any block, so m_new is a real score from block 0 on and
                # exp() of a masked one is exactly 0
                pr = jnp.exp(s - jnp.tile(m_new, (1, tk // 128)))
                l_ref[g] = alpha * l_ref[g] + jnp.sum(pr, axis=1,
                                                      keepdims=True)
                m_ref[g] = m_new
                if split:
                    high = pr.astype(kv_dtype)
                    low = (pr - high.astype(f32)).astype(kv_dtype)
                    pv = sum(jax.lax.dot_general(
                        half, vbuf[slot, g], (((1,), (0,)), ((), ())),
                        preferred_element_type=f32) for half in (high, low))
                else:
                    pv = jax.lax.dot_general(
                        pr, vbuf[slot, g], (((1,), (0,)), ((), ())),
                        precision=precision, preferred_element_type=f32)
                acc_ref[g] = jnp.tile(alpha, (1, d // 128)) * acc_ref[g] + pv

        jax.lax.fori_loop(0, n_blocks, block, None)
        for head in range(h):
            g, r = divmod(head, groups)
            mine = slice(r * tq, (r + 1) * tq)
            total = l_ref[g, mine]                  # 0 where no block ran
            o_ref[0, :, head * d:(head + 1) * d] = (
                acc_ref[g, mine] / jnp.tile(
                    jnp.where(total > 0, total, 1.0), (1, d // 128))
            ).astype(o_ref.dtype)

    q_block = pl.BlockSpec((1, tq, h * d),
                           lambda b, i, starts, lens, tabs: (b, i, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bp, t // tq),
            in_specs=[q_block, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_block,
            scratch_shapes=[
                pltpu.VMEM((kvh, rows, d), qk_dtype),
                pltpu.VMEM((slots, kvh, tk, d), kv_dtype),
                pltpu.VMEM((slots, kvh, tk, d), kv_dtype),
                pltpu.SemaphoreType.DMA((slots,)),
                pltpu.SemaphoreType.DMA((slots,)),
                pltpu.VMEM((kvh, rows, 128), f32),         # m
                pltpu.VMEM((kvh, rows, 128), f32),         # l
                pltpu.VMEM((kvh, rows, d), f32),           # acc
            ]),
        out_shape=jax.ShapeDtypeStruct((bp, t, h * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        interpret=interpret,
        name="paged_prefill_attention",
    )(q_starts.astype(jnp.int32), seq_lens.astype(jnp.int32),
      page_tables.astype(jnp.int32).reshape(-1), q.reshape(bp, t, h * d),
      k_pages, v_pages)
    return out.reshape(bp, t, h, d)


def _pallas_prefill(q, k_pages, v_pages, page_tables, q_starts, seq_lens,
                    attn_block=1, scale=None):
    from dynamo_tpu.engine.kernels import KV_SPEC, REP_SPEC, per_tp_shard

    chunk_spec = jax.sharding.PartitionSpec(None, None, "tp")
    return per_tp_shard(
        functools.partial(paged_prefill_attention, attn_block=attn_block,
                          scale=scale)
        if attn_block != 1 or scale is not None
        else paged_prefill_attention,
        (chunk_spec, KV_SPEC, KV_SPEC, REP_SPEC, REP_SPEC, REP_SPEC),
        chunk_spec)(q, k_pages, v_pages, page_tables, q_starts, seq_lens)


def ragged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     token_qpos: jax.Array, token_lanes: jax.Array,
                     lane_tables: jax.Array, page_size: int) -> jax.Array:
    """Flat-token ragged paged attention — THE attention entry for the
    engine's ragged dispatch path (decode lanes, prefill chunk tokens,
    and mixed batches all ride it as rows of one (T, H, D) array).

    q: (T, H, D); token_qpos: (T,) absolute position each row attends
    up to, -1 for padding rows; token_lanes: (T,) row into lane_tables;
    lane_tables: (L, max_pages). Routes to the pallas kernel on TPU when
    the geometry tiles (engine/ragged.py), else the XLA flat reference —
    noting the fallback so the profiler can attribute the slow path.
    """
    from dynamo_tpu.engine import ragged

    if use_pallas():
        if ragged.ragged_supported(page_size, q.shape[-1]):
            from dynamo_tpu.engine.kernels import (KV_SPEC, REP_SPEC,
                                                   ROW_SPEC, per_tp_shard)

            return per_tp_shard(
                ragged.ragged_paged_attention,
                (ROW_SPEC, KV_SPEC, KV_SPEC, REP_SPEC, REP_SPEC,
                 REP_SPEC),
                ROW_SPEC)(q, k_pages, v_pages, token_qpos, token_lanes,
                          lane_tables)
        _note_fallback("ragged_ineligible")
    return ragged.ragged_attention_xla(
        q, k_pages, v_pages, token_qpos, token_lanes, lane_tables)
