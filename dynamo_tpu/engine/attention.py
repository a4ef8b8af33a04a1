"""Paged attention: XLA reference implementation + TPU pallas kernel path.

The XLA path is pure lax ops, so it runs on any backend and partitions under
`jit` + sharding annotations (tensor parallelism over the kv-head axis).
The pallas path uses the TPU paged-attention kernel
(`jax.experimental.pallas.ops.tpu.paged_attention`) for decode — the HBM-
bandwidth-bound hot loop — and is selected automatically on TPU when the
kv-head axis is not sharded (single-chip or per-shard invocation).

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
    """Pages per compute block for the paged-attention kernels.

    Measured on v5e (batch 32, ctx 1152): tiny blocks are grid-overhead-
    bound — pages_per_compute_block=8 ran the fused step at 26 ms vs
    16 ms at 32 pages/block (and 12 ms with 32-token pages). Bigger
    blocks also read more padding past each lane's length, which hurts
    short contexts (b16 ctx128: 6.8 ms at 256-token blocks vs 7.5 ms at
    512). Target: ~1/4 of max context, at least 256 tokens, snapped to
    the largest divisor of max_pages (the kernels need the block count
    to tile the page table exactly). Shared by `_pallas_decode` and
    `ragged.ragged_paged_attention`; cached — the geometry set is tiny.
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


def prefill_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      page_table: jax.Array, q_positions: jax.Array,
                      seq_len: jax.Array, page_size: int) -> jax.Array:
    """Causal attention for one sequence's prefill, reading K/V from pages.

    q: (T, H, D); k_pages/v_pages: (KVH, N, P, D); page_table: (max_pages,);
    q_positions: (T,) absolute positions; seq_len: scalar valid length.
    Returns (T, H, D). Quadratic XLA attention — prefill is MXU-bound and
    XLA fuses the mask/softmax; a flash-style pallas kernel is a later
    optimisation for very long context (ring attention covers longer still).
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
                        k.astype(jnp.float32)) / (d ** 0.5)
    s_pos = jnp.arange(k.shape[1])
    mask = (s_pos[None, :] <= q_positions[:, None]) \
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
    chunk sub-batch through `prefill_attention`, against the same page
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
    chunk_out = jax.vmap(
        lambda q1, pt, pos1, sl: prefill_attention(
            q1, k_pages, v_pages, pt, q_positions=pos1, seq_len=sl,
            page_size=page_size)
    )(q_chunk, chunk_tables, chunk_positions, chunk_seq_lens)
    return dec_out, chunk_out


def paged_attention_decode(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, lengths: jax.Array,
                           page_tables: jax.Array,
                           page_size: int) -> jax.Array:
    """One-token-per-sequence paged attention.

    q: (B, H, D); k_pages/v_pages: (KVH, N, P, D); lengths: (B,) valid
    lengths (0 = padding lane); page_tables: (B, max_pages). → (B, H, D).
    """
    # Mosaic tiling constraint: last dims must align to (8, 128) lanes —
    # head_dim must be a multiple of 128 for the kernel's block specs.
    if use_pallas():
        if q.shape[-1] % 128 == 0:
            return _pallas_decode(q, k_pages, v_pages, lengths,
                                  page_tables)
        _note_fallback("head_dim")
    return _xla_decode(q, k_pages, v_pages, lengths, page_tables)


def _xla_decode(q, k_pages, v_pages, lengths, page_tables):
    kvh, _, p, d = k_pages.shape
    b, h, _ = q.shape
    groups = h // kvh
    # (KVH, B, max_pages, P, D) -> (B, KVH, S, D)
    k = jnp.moveaxis(k_pages[:, page_tables], 0, 1).reshape(b, kvh, -1, d)
    v = jnp.moveaxis(v_pages[:, page_tables], 0, 1).reshape(b, kvh, -1, d)
    k = _repeat_kv(k, groups, axis=1)                      # (B, H, S, D)
    v = _repeat_kv(v, groups, axis=1)
    scores = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / (d ** 0.5)
    s_pos = jnp.arange(k.shape[2])
    mask = s_pos[None, :] < lengths[:, None]               # (B, S)
    scores = jnp.where(mask[:, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # fully-masked (padding) lanes: softmax is uniform; output is garbage
    # but the scheduler ignores padding lanes' logits.
    out = jnp.einsum("bhs,bhsd->bhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


@functools.cache
def _pallas_paged_attention():
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention as kernel,
    )
    return kernel


def _pallas_decode(q, k_pages, v_pages, lengths, page_tables):
    from dynamo_tpu.engine.kernels import (KV_SPEC, REP_SPEC, ROW_SPEC,
                                           per_tp_shard)

    kernel = functools.partial(
        _pallas_paged_attention(),
        pages_per_compute_block=block_choice(page_tables.shape[1],
                                             k_pages.shape[2]))
    return per_tp_shard(
        kernel, (ROW_SPEC, KV_SPEC, KV_SPEC, REP_SPEC, REP_SPEC),
        ROW_SPEC)(q, k_pages, v_pages, lengths.astype(jnp.int32),
                  page_tables.astype(jnp.int32))


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
