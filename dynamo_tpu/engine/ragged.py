"""Ragged paged attention: one kernel path for every batch shape.

The engine splits attention across three entries —
`paged_attention_decode` for decode bursts, `paged_attention_prefill`
for rounds of chunks (each with a kernel of its own in
engine/attention.py), and `mixed_attention` glue for fused
steps — and every entry carries its own padding: decode lanes pad to
the pow2 batch width, chunks pad to `(Bp, T_bucket)` rectangles, and
the compile shapes bucket on `(decode width, chunk tokens, k_steps, …)`
tuples (the CompileTracker shape zoo).

This module flattens the batch instead ("Ragged Paged Attention",
PAPERS.md): every query — a decode lane's one token or any token of a
prefill chunk — becomes one ROW of a flat `(T, H, D)` array, tagged
with the absolute position it attends up to (`token_qpos`) and the lane
whose page table it reads (`token_lanes`). Variable-length lanes ride
one grid with no per-lane padding; compile shapes bucket only on the
total token count T.

Two implementations, numerically matched:

* `ragged_attention_xla` — pure lax ops, the non-TPU / unaligned-
  geometry fallback (it is `_xla_decode` applied per flat row, so its
  numerics are exactly the existing decode reference's).
* `ragged_paged_attention` — the pallas TPU kernel: grid
  `(T, max_pages // ppcb)`, scalar-prefetched lane metadata, page
  blocks fetched via double indirection through the lane's page table,
  flash-style online softmax over the sequential KV dimension in VMEM
  scratch. `interpret=True` runs it chip-free for parity tests.

Mask convention (both paths): a row with `qpos` attends KV positions
`s <= qpos` — inclusive, because the engine writes a token's own K/V
before attention (same contract as `_decode_once`, where
`lengths = positions + 1`). Padding rows carry `qpos = -1`: fully
masked, output exactly zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.attention import _NEG_INF, _xla_decode, block_choice


def ragged_supported(page_size: int, head_dim: int) -> bool:
    """Mosaic tiling gate for the real-device kernel (same constraint as
    kernels.kv_write_supported: page/head blocks must tile (8, 128))."""
    return page_size % 8 == 0 and head_dim % 128 == 0


def ragged_attention_xla(q: jax.Array, k_pages: jax.Array,
                         v_pages: jax.Array, token_qpos: jax.Array,
                         token_lanes: jax.Array,
                         lane_tables: jax.Array) -> jax.Array:
    """XLA reference/fallback: per-flat-row decode-style gather.

    q: (T, H, D); k_pages/v_pages: (KVH, N, P, D); token_qpos: (T,)
    absolute position each row attends up to (-1 ⇒ padding row);
    token_lanes: (T,) row into lane_tables; lane_tables:
    (L, max_pages). Returns (T, H, D); padding rows are exactly zero
    (matching the kernel), unlike `_xla_decode` whose padding lanes
    emit uniform-softmax garbage the scheduler ignores.
    """
    lengths = jnp.maximum(token_qpos.astype(jnp.int32) + 1, 0)
    tables = lane_tables[token_lanes]                      # (T, max_pages)
    out = _xla_decode(q, k_pages, v_pages, lengths, tables)
    return jnp.where((token_qpos >= 0)[:, None, None], out,
                     jnp.zeros_like(out))


@functools.cache
def _pltpu():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


def ragged_paged_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, token_qpos: jax.Array,
                           token_lanes: jax.Array,
                           lane_tables: jax.Array, *,
                           interpret: bool = False) -> jax.Array:
    """Pallas ragged paged attention (signature = `ragged_attention_xla`).

    Grid is (T, max_pages // ppcb): the outer dim walks flat query rows,
    the inner dim walks the row's lane page table in compute blocks of
    `ppcb` pages (`attention.block_choice`, the measured-on-v5e divisor
    heuristic shared with the decode kernel). Each inner step DMAs ppcb
    (KVH, P, D) page blocks selected by double indirection
    `lane_tables[token_lanes[t], j*ppcb + i]` and folds them into a
    flash-style online softmax held in VMEM scratch (m/l replicated
    across a 128-lane axis, fp32 accumulator); the last step writes the
    safe-divided output row. TPU grids run sequentially, so the scratch
    carries state across the inner dim and resets at j == 0.
    """
    pl, pltpu = _pltpu()
    kvh, _, p, d = k_pages.shape
    t_rows, h, _ = q.shape
    groups = h // kvh
    max_pages = lane_tables.shape[1]
    ppcb = block_choice(max_pages, p)
    n_blocks = max_pages // ppcb                           # ppcb divides
    bs = ppcb * p                                          # tokens / block
    scale = 1.0 / (d ** 0.5)

    def kernel(lanes_ref, qpos_ref, tables_ref, q_ref, *refs):
        del tables_ref  # consumed by the BlockSpec index maps
        k_refs = refs[:ppcb]
        v_refs = refs[ppcb:2 * ppcb]
        o_ref, m_ref, l_ref, acc_ref = refs[2 * ppcb:]
        t = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        qpos = qpos_ref[t]
        qv = q_ref[0].astype(jnp.float32) * scale          # (H, D)
        if ppcb > 1:
            k = jnp.concatenate([r[:, 0] for r in k_refs], axis=1)
            v = jnp.concatenate([r[:, 0] for r in v_refs], axis=1)
        else:
            k, v = k_refs[0][:, 0], v_refs[0][:, 0]        # (KVH, bs, D)
        kvpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        mask = kvpos <= qpos                               # (1, bs)

        dots = [jax.lax.dot_general(
            qv[g * groups:(g + 1) * groups],
            k[g].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) for g in range(kvh)]
        s = jnp.concatenate(dots, axis=0) if kvh > 1 else dots[0]
        s = jnp.where(mask, s, _NEG_INF)                   # (H, bs)

        # m/l are replicated across the 128-lane scratch axis; a max
        # reduction reads the scalar back for both (l is non-negative).
        m_prev = jnp.max(m_ref[...], axis=1)               # (H,)
        l_prev = jnp.max(l_ref[...], axis=1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        # exp then re-mask: with a fully-masked block m_new stays at the
        # finite _NEG_INF floor, exp(s - m_new) = 1 there, and only the
        # mask multiply keeps phantom keys out of l/acc.
        pr = jnp.exp(s - m_new[:, None]) * mask.astype(jnp.float32)
        pv = [jax.lax.dot_general(
            pr[g * groups:(g + 1) * groups],
            v[g].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) for g in range(kvh)]
        pv = jnp.concatenate(pv, axis=0) if kvh > 1 else pv[0]
        acc_ref[...] = alpha[:, None] * acc_ref[...] + pv
        l_new = alpha * l_prev + jnp.sum(pr, axis=1)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

        @pl.when(j == n_blocks - 1)
        def _write():
            l = jnp.max(l_ref[...], axis=1)[:, None]       # (H, 1)
            o_ref[0] = jnp.where(
                l > 0.0, acc_ref[...] / jnp.maximum(l, 1e-37),
                0.0).astype(o_ref.dtype)

    # Index maps see grid indices first, prefetch refs after
    # (kernels.py convention); `i` is bound per-spec at closure time.
    def k_index(i):
        return lambda t, j, lanes, qpos, tables: (
            0, tables[lanes[t], j * ppcb + i], 0, 0)

    q_spec = pl.BlockSpec((1, h, d), lambda t, j, lanes, qpos, tables:
                          (t, 0, 0))
    kv_specs = [pl.BlockSpec((kvh, 1, p, d), k_index(i))
                for i in range(ppcb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t_rows, n_blocks),
        in_specs=[q_spec] + kv_specs + kv_specs,
        out_specs=pl.BlockSpec((1, h, d), lambda t, j, lanes, qpos,
                               tables: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),             # m
            pltpu.VMEM((h, 128), jnp.float32),             # l
            pltpu.VMEM((h, d), jnp.float32),               # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(token_lanes.astype(jnp.int32), token_qpos.astype(jnp.int32),
      lane_tables.astype(jnp.int32), q,
      *([k_pages] * ppcb), *([v_pages] * ppcb))
