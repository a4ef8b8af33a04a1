"""`greedy_tail`: a greedy row's token and its log-probability from one read
of the logits.

The argmax and the chosen token's log-probability are three reductions
over one `(R, V)` array (the largest value, where it first stands, the sum
of exponentials). XLA runs them as three passes at 300-420 GB/s each; the
bytes allow one. This kernel streams the logits once, a tile of columns at
a time, in the dtype the head wrote them, and keeps per row and per LANE
(column mod 128) a running maximum, the column block where that maximum
first stood, and a sum of exponentials rescaled when the maximum moves: the
online softmax of `attention.paged_decode_attention`, held lane-wise so a
tile costs no cross-lane reduction. The 128 lanes of a row meet once, after
the last tile: `logprob = -log(sum)`, because the chosen token IS the
maximum.

Ties go to the lowest column: within a lane a strictly larger value
replaces, and among the lanes that hold the row's maximum the smallest
column wins; that is `jnp.argmax`'s order. A last tile that overhangs V is
read only as far as V (V is a multiple of 128: whole lanes, nothing to
mask). Rows of `-1e30` (guided masks) give finite results: the statistics
start from the most negative float32, never from an infinity.

No weights and no matmul. The work is the VPU's: per 8 x 128 values a
compare, a maximum, a select, a subtract, an exponential and an add; it
keeps up with the bytes (alone on a v5e, (256, 151936) bf16: 78 MB in
0.107 ms, 89% of the HBM bandwidth; PERF.md §6, PR 49).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.kernels import _pltpu

_LANES = 128
_SLAB = 32 * 1024          # values of one row group held in registers
_TILE_BYTES = 2 << 20      # of logits a grid step (double-buffered)


def greedy_tail_supported(shape: tuple, dtype) -> bool:
    """Whole lanes and whole sublane groups: V a multiple of 128, R of 8,
    four- or two-byte floats."""
    r, v = shape
    return (v % _LANES == 0 and r % 8 == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                     jnp.dtype(jnp.bfloat16)))


def _geometry(r: int, v: int, itemsize: int) -> tuple[int, int, int]:
    """(rows a group, columns a slab, columns a tile)."""
    sub = 16 if itemsize == 2 and r % 16 == 0 else 8
    slab = _SLAB // sub
    tile = max(1, _TILE_BYTES // (r * itemsize) // slab) * slab
    return sub, slab, min(tile, v)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def greedy_tail(logits: jax.Array, tile=None, interpret=False
                ) -> tuple[jax.Array, jax.Array]:
    """logits (R, V) f32 or bf16 -> (ids (R,) i32: each row's argmax, the
    lowest column of equals; logprob (R,) f32: its log-softmax). `tile`:
    columns a grid step, a multiple of 128 (the tests' way to a last tile
    that overhangs a small V); sized from the shape when None."""
    pl, pltpu = _pltpu()
    r, v = logits.shape
    if not greedy_tail_supported(logits.shape, logits.dtype):
        raise ValueError(f"greedy_tail does not tile {logits.dtype.name}"
                         f"{list(logits.shape)}")
    sub, slab, auto = _geometry(r, v, logits.dtype.itemsize)
    tile = auto if tile is None else tile
    n_tiles = -(-v // tile)
    f32, i32 = jnp.float32, jnp.int32
    lowest = float(jnp.finfo(f32).min)

    def kernel(x_ref, ids_ref, lp_ref, m_ref, i_ref, s_ref):
        j = pl.program_id(0)

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, lowest)
            i_ref[...] = jnp.zeros_like(i_ref)
            s_ref[...] = jnp.zeros_like(s_ref)

        def columns(rows, col0, n, block0):
            """Fold columns [col0, col0 + n) of the tile into the row
            group's statistics; block0: the first one's column block."""
            x = x_ref[rows, pl.ds(col0, n)].astype(f32)
            m_old = m = m_ref[rows, :]
            i = i_ref[rows, :]
            parts = [x[:, c * _LANES:(c + 1) * _LANES]
                     for c in range(n // _LANES)]
            for c, xc in enumerate(parts):
                i = jnp.where(xc > m, block0 + c, i)
                m = jnp.maximum(m, xc)
            s = s_ref[rows, :] * jnp.exp(m_old - m)
            for xc in parts:
                s = s + jnp.exp(xc - m)
            m_ref[rows, :] = m
            i_ref[rows, :] = i
            s_ref[rows, :] = s

        def sweep(n_cols):
            """A tile of which the first n_cols columns exist."""
            full, rest = divmod(n_cols, slab)

            def group(g, carry):
                rows = pl.ds(pl.multiple_of(g * sub, sub), sub)
                block0 = j * (tile // _LANES)

                def one(k, carry):
                    columns(rows, pl.multiple_of(k * slab, slab), slab,
                            block0 + k * (slab // _LANES))
                    return carry

                jax.lax.fori_loop(0, full, one, 0)
                if rest:
                    columns(rows, full * slab, rest,
                            block0 + full * (slab // _LANES))
                return carry

            jax.lax.fori_loop(0, r // sub, group, 0)

        last = v - (n_tiles - 1) * tile
        if last == tile:
            sweep(tile)
        else:
            pl.when(j < n_tiles - 1)(lambda: sweep(tile))
            pl.when(j == n_tiles - 1)(lambda: sweep(last))

        @pl.when(j == n_tiles - 1)
        def _():
            m, i, s = m_ref[...], i_ref[...], s_ref[...]
            top = jnp.max(m, axis=1, keepdims=True)
            col = i * _LANES + jax.lax.broadcasted_iota(i32, m.shape, 1)
            first = jnp.min(jnp.where(m == top, col, v), axis=1,
                            keepdims=True)
            total = jnp.sum(s * jnp.exp(m - top), axis=1, keepdims=True)
            ids_ref[...] = jnp.broadcast_to(first, ids_ref.shape)
            lp_ref[...] = jnp.broadcast_to(-jnp.log(total), lp_ref.shape)

    stat = pl.BlockSpec((r, _LANES), lambda j: (0, 0))
    ids, lp = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((r, tile), lambda j: (0, j))],
        out_specs=[stat, stat],
        out_shape=[jax.ShapeDtypeStruct((r, _LANES), i32),
                   jax.ShapeDtypeStruct((r, _LANES), f32)],
        scratch_shapes=[pltpu.VMEM((r, _LANES), f32),      # m
                        pltpu.VMEM((r, _LANES), i32),      # i
                        pltpu.VMEM((r, _LANES), f32)],     # s
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="greedy_tail",
    )(logits)
    return ids[:, 0], lp[:, 0]
