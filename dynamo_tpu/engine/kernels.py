"""Hand-written pallas TPU kernels for ops XLA handles poorly.

`paged_kv_write`: scatter one token's K/V per sequence into the paged cache.
XLA lowers this scatter to ~23ms/step on a 1B model (measured, v5e) —
dominating decode. The pallas version updates only the touched pages via
block DMA: load page block, overwrite one row, store back (~0.1ms).

Layout matches the paged-attention kernel: cache (KVH, N, P, D).
Constraints: P % 8 == 0 and D % 128 == 0 (mosaic tiling); callers fall
back to the XLA scatter otherwise (models/llama.py `_write_pages`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.cache
def _pltpu():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


def kv_write_supported(page_size: int, head_dim: int) -> bool:
    return page_size % 8 == 0 and head_dim % 128 == 0


def per_tp_shard(kernel, in_specs, out_specs):
    """`kernel`, run once per "tp" shard when the step is being traced
    under a tensor-parallel mesh (the engine dispatches inside
    `jax.set_mesh`), else unchanged. GSPMD cannot partition a Mosaic
    kernel; heads are independent, so a `shard_map` over the head axis
    the caches and projections are already sharded on is exact and
    moves no data."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.shape.get("tp", 1) == 1:
        return kernel
    return jax.shard_map(kernel, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


# PartitionSpecs of the kernels' operands under tp: caches (KVH, N, P, D)
# split on kv heads, per-token rows (B, heads, D) on their head axis,
# page ids / lengths / tables replicated
KV_SPEC = jax.sharding.PartitionSpec("tp")
ROW_SPEC = jax.sharding.PartitionSpec(None, "tp")
REP_SPEC = jax.sharding.PartitionSpec()


def paged_kv_write(kc: jax.Array, vc: jax.Array, k: jax.Array, v: jax.Array,
                   page_ids: jax.Array, offsets: jax.Array
                   ) -> tuple[jax.Array, jax.Array]:
    """kc/vc: (KVH, N, P, D); k/v: (B, KVH, D); page_ids/offsets: (B,).

    Writes k[b]/v[b] into page page_ids[b] at row offsets[b]. Grid is
    sequential on TPU, so duplicate page_ids (scratch page 0 for padding
    lanes) are safe — last write wins.
    """
    return per_tp_shard(
        _paged_kv_write,
        (KV_SPEC, KV_SPEC, ROW_SPEC, ROW_SPEC, REP_SPEC, REP_SPEC),
        (KV_SPEC, KV_SPEC))(kc, vc, k, v, page_ids, offsets)


def _paged_kv_write(kc, vc, k, v, page_ids, offsets):
    pl, pltpu = _pltpu()
    kvh, n_pages, p, d = kc.shape
    b = k.shape[0]

    def kernel(pid_ref, off_ref, k_ref, v_ref, kc_in, vc_in,
               kc_out, vc_out):
        # Mosaic can't do sublane-unaligned dynamic stores; blend the new
        # row into the page block with a mask instead (pure vector ops on
        # the one touched page — only that block is DMA'd in/out).
        i = pl.program_id(0)
        off = off_ref[i]
        row = jax.lax.broadcasted_iota(jnp.int32, (1, 1, p, 1), 2)
        mask = row == off
        kc_out[...] = jnp.where(mask, k_ref[0][:, None, None, :], kc_in[...])
        vc_out[...] = jnp.where(mask, v_ref[0][:, None, None, :], vc_in[...])

    page_block = pl.BlockSpec(
        (kvh, 1, p, d),
        lambda i, pid_ref, off_ref: (0, pid_ref[i], 0, 0))
    row_block = pl.BlockSpec((1, kvh, d),
                             lambda i, pid_ref, off_ref: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[row_block, row_block, page_block, page_block],
        out_specs=[page_block, page_block],
    )
    out_kc, out_vc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(kc.shape, kc.dtype),
                   jax.ShapeDtypeStruct(vc.shape, vc.dtype)],
        input_output_aliases={4: 0, 5: 1},  # kc/vc updated in place
        name="kv_write_rows",
    )(page_ids.astype(jnp.int32), offsets.astype(jnp.int32), k, v, kc, vc)
    return out_kc, out_vc


def paged_kv_write_block(kc: jax.Array, vc: jax.Array, k: jax.Array,
                         v: jax.Array, page_ids: jax.Array,
                         offsets: jax.Array) -> tuple[jax.Array, jax.Array]:
    """kc/vc: (KVH, N, P, D); k/v: (L, R, KVH, D), the R rows of a lane's
    block, which lie in one page (the page size is a multiple of the block
    length); page_ids/offsets: (L,) the page and the block's first row.

    `paged_kv_write` with R rows to a program: one page in and out a lane
    instead of one a row (a block step writes lanes x R rows a layer).
    """
    block_spec = jax.sharding.PartitionSpec(None, None, "tp")
    return per_tp_shard(
        _paged_kv_write_block,
        (KV_SPEC, KV_SPEC, block_spec, block_spec, REP_SPEC, REP_SPEC),
        (KV_SPEC, KV_SPEC))(kc, vc, k, v, page_ids, offsets)


def _paged_kv_write_block(kc, vc, k, v, page_ids, offsets):
    pl, pltpu = _pltpu()
    kvh, n_pages, p, d = kc.shape
    lanes, rows = k.shape[:2]

    def kernel(pid_ref, off_ref, k_ref, v_ref, kc_in, vc_in,
               kc_out, vc_out):
        # as `_paged_kv_write`: no sublane-unaligned dynamic store, so each
        # new row is blended into the page block under a mask
        off = off_ref[pl.program_id(0)]
        row = jax.lax.broadcasted_iota(jnp.int32, (1, 1, p, 1), 2)
        k_page, v_page = kc_in[...], vc_in[...]
        for j in range(rows):
            mask = row == off + j
            k_page = jnp.where(mask, k_ref[0, j][:, None, None, :], k_page)
            v_page = jnp.where(mask, v_ref[0, j][:, None, None, :], v_page)
        kc_out[...] = k_page
        vc_out[...] = v_page

    page_block = pl.BlockSpec(
        (kvh, 1, p, d),
        lambda i, pid_ref, off_ref: (0, pid_ref[i], 0, 0))
    rows_block = pl.BlockSpec((1, rows, kvh, d),
                              lambda i, pid_ref, off_ref: (i, 0, 0, 0))
    out_kc, out_vc = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(lanes,),
            in_specs=[rows_block, rows_block, page_block, page_block],
            out_specs=[page_block, page_block]),
        out_shape=[jax.ShapeDtypeStruct(kc.shape, kc.dtype),
                   jax.ShapeDtypeStruct(vc.shape, vc.dtype)],
        input_output_aliases={4: 0, 5: 1},  # kc/vc updated in place
        name="kv_write_block",
    )(page_ids.astype(jnp.int32), offsets.astype(jnp.int32), k, v, kc, vc)
    return out_kc, out_vc


def paged_kv_write_pages(kc: jax.Array, vc: jax.Array,
                         k_blocks: jax.Array, v_blocks: jax.Array,
                         page_ids: jax.Array
                         ) -> tuple[jax.Array, jax.Array]:
    """Full-page KV store for prefill: kc/vc (KVH, N, P, D); k_blocks/
    v_blocks (M, KVH, P, D) — one complete page of rows per entry;
    page_ids (M,) destination pages (0 ⇒ scratch, for padding slots).

    Unlike `paged_kv_write` (row blend: DMA page in, overwrite one row,
    DMA out) this is a pure store — no read-back — and runs one program
    per PAGE rather than per token. Unwritten tail rows of a partially
    filled final page carry garbage that is (a) masked by attention's
    length mask and (b) overwritten by decode's row-blend writes later.
    Measured: row path on a (16 seqs × 128 tok) prefill round = 2048
    programs/layer ≈ 143 ms per engine prefill; page path = 128
    programs/layer.
    """
    return per_tp_shard(
        _paged_kv_write_pages,
        (KV_SPEC, KV_SPEC, ROW_SPEC, ROW_SPEC, REP_SPEC),
        (KV_SPEC, KV_SPEC))(kc, vc, k_blocks, v_blocks, page_ids)


def _paged_kv_write_pages(kc, vc, k_blocks, v_blocks, page_ids):
    pl, pltpu = _pltpu()
    kvh, n_pages, p, d = kc.shape
    m = k_blocks.shape[0]

    def kernel(pid_ref, k_ref, v_ref, kc_in, vc_in, kc_out, vc_out):
        kc_out[...] = k_ref[0][:, None]
        vc_out[...] = v_ref[0][:, None]

    page_block = pl.BlockSpec(
        (kvh, 1, p, d), lambda i, pid_ref: (0, pid_ref[i], 0, 0))
    src_block = pl.BlockSpec((1, kvh, p, d), lambda i, pid_ref: (i, 0, 0, 0))
    # aliased cache INPUTS get a constant minimal block: the kernel fully
    # overwrites each destination page, so fetching the old page contents
    # (a full page DMA-in per program) would only burn bandwidth
    dummy_block = pl.BlockSpec((1, 1, p, d), lambda i, pid_ref: (0, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m,),
        in_specs=[src_block, src_block, dummy_block, dummy_block],
        out_specs=[page_block, page_block],
    )
    out_kc, out_vc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(kc.shape, kc.dtype),
                   jax.ShapeDtypeStruct(vc.shape, vc.dtype)],
        input_output_aliases={3: 0, 4: 1},
        name="kv_write_pages",
    )(page_ids.astype(jnp.int32), k_blocks, v_blocks, kc, vc)
    return out_kc, out_vc
