"""Routed expert dispatch: rows sorted by expert, one grouped product.

`moe_mlp` (models/mixtral.py) sends each token's hidden state to its k
experts. The k x T routed rows are laid out expert by expert, each expert's
run padded up to whole row tiles (`route_layout`), so that a tile of rows
meets exactly one expert's weights; `grouped_matmul` is then one product
over the expert stack that reads an expert's weights once however many of
its tiles follow each other, and never the weights of an expert no row
chose. No row is dropped and there is no capacity factor: the padded length
is the worst case, a static shape.

On the TPU the product is this module's Pallas kernel, `moe_gmm` in a device
trace. Int8 stacks (engine/quant.py W8A16: per-channel scale on the output)
are read as int8 and widened tile by tile in VMEM, so HBM sees the int8
bytes and no bf16 copy of the stack ever exists. Off the TPU it is
`jax.lax.ragged_dot` over the same layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.attention import use_pallas
from dynamo_tpu.engine.quant import QTensor

_GMM_VMEM_BYTES = 96 << 20
_GMM_COLS = 256          # output columns widened and multiplied at a time


def row_tile(rows: int, experts: int, k: int, n: int) -> int:
    """Rows of a tile for `rows` routed rows over `experts` stacks of
    (k, n) matrices. The kernel's, from the mean run an expert gets:
    decode-sized rounds (16 rows an expert at 64 lanes x 4 rows x top-8 of
    128) take 32, so that an expert is nearly always one tile and its
    weights are widened once; prefill rounds take whole MXU passes. Where
    the kernel does not run (`kernel_runs`) a tile is one row: the layout
    is the sorted rows and nothing is padded."""
    if not kernel_runs(k, n):
        return 1
    mean = rows / experts
    return 32 if mean <= 32 else 128 if mean <= 256 else 256


def kernel_runs(k: int, n: int) -> bool:
    """The Pallas kernel takes matrices in whole (128, 128) tiles, on the
    TPU; everything else is `jax.lax.ragged_dot`."""
    return use_pallas() and k % 128 == 0 and n % 128 == 0


def padded_rows(rows: int, experts: int, tile: int) -> int:
    """The static length of the padded layout: every expert's run rounded
    up to whole tiles, at worst."""
    return -(-(rows + experts * (tile - 1)) // tile) * tile


def route_layout(expert_ids: jax.Array, experts: int, tile: int):
    """Where each routed row goes. expert_ids: (R,) the expert of routed
    row r. Returns (pos (R,): its row in the padded layout; tile_expert
    (tiles,): the expert a tile of rows belongs to; n_used (1,): tiles
    that hold rows; sizes (X,): each expert's padded run)."""
    rows = expert_ids.shape[0]
    n_tiles = padded_rows(rows, experts, tile) // tile
    order = jnp.argsort(expert_ids, stable=True)
    sizes = jnp.bincount(expert_ids, length=experts).astype(jnp.int32)
    padded = (sizes + tile - 1) // tile * tile
    ends, p_ends = jnp.cumsum(sizes), jnp.cumsum(padded)
    sorted_e = expert_ids[order]
    pos_sorted = (jnp.arange(rows, dtype=jnp.int32)
                  - (ends - sizes)[sorted_e] + (p_ends - padded)[sorted_e])
    pos = jnp.zeros(rows, jnp.int32).at[order].set(pos_sorted)
    tile_expert = jnp.minimum(
        jnp.searchsorted(p_ends, jnp.arange(n_tiles, dtype=jnp.int32) * tile,
                         side="right"), experts - 1).astype(jnp.int32)
    return pos, tile_expert, (p_ends[-1:] // tile).astype(jnp.int32), padded


def _parts(w):
    quant = isinstance(w, QTensor)
    if quant and w.bits != 8:
        raise ValueError(f"int{w.bits} expert stacks unsupported "
                         "(W8A16 only)")
    return (w.q, w.s) if quant else (w, None)


def grouped_matmul(x: jax.Array, w, tile_expert: jax.Array,
                   n_used: jax.Array, sizes: jax.Array, tile: int,
                   layers=None) -> jax.Array:
    """x (M, K) in the padded layout @ the expert stack w (X, K, N) (an
    array, or an int8 QTensor with scales (X, 1, N)): row tile i meets
    expert tile_expert[i]. -> (M, N) in x's dtype; rows of unused tiles
    are not defined. `layers` = (the stack of all layers (L, X, K, N), l)
    where w is its layer l: the kernel indexes the layer itself, because a
    slice handed to a custom call is a copy of the layer's stack in HBM
    (192 MB a projection at 128 experts, every forward)."""
    wq, scale = _parts(w)
    if kernel_runs(*wq.shape[1:]):
        if layers is not None:
            (wq, scale), layer = _parts(layers[0]), layers[1]
        else:
            wq, layer = wq[None], 0
            scale = None if scale is None else scale[None]
        return moe_gmm(x, wq, scale, tile_expert,
                       jnp.concatenate([n_used, jnp.asarray([layer],
                                                            jnp.int32)]),
                       tile=tile)
    y = jax.lax.ragged_dot(x, wq.astype(x.dtype), sizes,
                           preferred_element_type=jnp.float32)
    if scale is not None:
        y = y * scale[:, 0, :][jnp.repeat(tile_expert, tile)]
    return y.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def moe_gmm(x, w, scale, tile_expert, used_layer, *, tile, interpret=False):
    """The grouped product kernel. x (M, K); w (L, X, K, N), scale
    (L, X, 1, N) or None; used_layer = [tiles that hold rows, the layer].
    Grid: one step a row tile. The weight block of a step is its expert's
    whole (K, N) matrix of that layer, fetched when the expert changes from
    one step to the next and kept while it does not; the pipeline fetches
    the next expert's while this one is multiplied. Int8 weights are
    widened to the rows' dtype `_GMM_COLS` columns at a time, multiplied
    with an f32 accumulator and scaled on the way out. Steps past the used
    tiles do nothing."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    n = w.shape[3]
    cols = _GMM_COLS if n % _GMM_COLS == 0 else 128
    f32 = jnp.float32
    precision = jax.lax.Precision.HIGHEST if x.dtype == f32 else None

    def kernel(te_ref, used_ref, x_ref, w_ref, *rest):
        s_ref, o_ref = rest if scale is not None else (None, rest[0])

        @pl.when(pl.program_id(0) < used_ref[0])
        def _():
            rows = x_ref[...]
            for c in range(0, n, cols):
                y = jax.lax.dot_general(
                    rows, w_ref[0, 0, :, c:c + cols].astype(x.dtype),
                    (((1,), (0,)), ((), ())), precision=precision,
                    preferred_element_type=f32)
                if s_ref is not None:
                    y = y * s_ref[0, 0, :, c:c + cols]
                o_ref[:, c:c + cols] = y.astype(o_ref.dtype)

    def row_block(width):
        return pl.BlockSpec((tile, width), lambda i, te, used: (i, 0))

    def expert_block(*shape):
        return pl.BlockSpec((1, 1, *shape),
                            lambda i, te, used: (used[1], te[i], 0, 0))

    in_specs = [row_block(k), expert_block(k, n)]
    operands = [x, w]
    if scale is not None:
        in_specs.append(expert_block(1, n))
        operands.append(scale.astype(f32))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(m // tile,),
            in_specs=in_specs, out_specs=row_block(n)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_GMM_VMEM_BYTES),
        interpret=interpret,
        name="moe_gmm",
    )(tile_expert, used_layer, *operands)
