"""Pallas TPU kernel: matmul against nibble-packed int4 weights.

Why a kernel at all: XLA will not fuse the shift/mask unpack into a
dot's operand read (converts yes, general elementwise no), so the pure
XLA int4 path materializes the unpacked weights per decode step —
measured 25 ms/step vs int8's 6.4 ms on the r4 bench model (v5e). A
first kernel that unpacked nibbles with i32 shifts still lost to int8
(7.1 vs 6.2 ms/step): Mosaic can't legalize i8 vector shifts, and the
4× i32 widening of every weight block blew scoped VMEM (24 MB at the
block sizes that pipeline well) and swamped the VPU.

This kernel never unpacks. engine/quant.pack4 stores the low nibble
bias-8 unsigned and the high nibble two's-complement, so the signed
byte is EXACTLY ``p = 16*hi + (lo + 8)`` (|p| <= 127: exact in bf16).
The kernel runs two MXU dots per block — one on the raw bytes, one on
the AND-masked low nibbles (``lou = lo + 8``) — and the XLA epilogue
recovers both nibble products algebraically:

    y_hi = (x @ p  -  x @ lou) / 16
    y_lo =  x @ lou - 8 * rowsum(x)

Per weight byte that is one i8 AND plus two i8→bf16 converts (all
Mosaic-native), no shifts, no widening. The interleave of lo/hi
columns back to logical order happens on the small (M, N) output
(~K/M times less relayout work than interleaving the weights; Mosaic
also rejects that shape cast in-kernel).

Layout contract (shared with engine/quant.py): packed pairwise along
the LAST axis — logical column 2j in the low nibble of packed column
j, 2j+1 in the high nibble. Interleaved pairing (not split halves)
keeps a tp-sharded packed weight's local unpack equal to the logical
shard.

Reference parity: the reference ships FP8/INT8 quantized serving via
TRT-LLM engine recipes (recipes' quantization knobs); weight-only int4
with an owned kernel is this framework's TPU-first equivalent lever.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, p_ref, yp_ref, yl_ref):
    """Grid (m_tiles, n_tiles, k_tiles); k is the reduction axis.

    x_ref: (bm, bk) int8 activation block (per-row dynamically
    quantized by the wrapper); p_ref: (bk, bn2) packed weights;
    yp_ref/yl_ref: (bm, bn2) int32 output blocks (pinned in VMEM across
    the k steps — their index map ignores k — so they double as the
    accumulators). yp = xq @ bytes, yl = xq @ (bytes & 0xF), both on
    the MXU's native int8×int8→int32 path (2× the bf16 pass rate on
    v5e — decode at small batch is MXU-pass-bound, so this, not the
    HBM saving, is where int4 must win).
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        yp_ref[:] = jnp.zeros_like(yp_ref)
        yl_ref[:] = jnp.zeros_like(yl_ref)

    p = p_ref[:]
    x = x_ref[:]
    lou = jnp.bitwise_and(p, 0xF).astype(jnp.int8)   # lo + 8
    yp_ref[:] += jnp.dot(x, p, preferred_element_type=jnp.int32)
    yl_ref[:] += jnp.dot(x, lou, preferred_element_type=jnp.int32)


def _a8_prologue(x):
    """Shared W-A8 activation prologue: pad M to the int8 sublane tile,
    per-row dynamic int8 quantization. Returns (xq, sx, m0, m) — both
    A8 kernels (int4 and w8a8) must quantize identically or their
    quality/perf comparisons stop meaning anything."""
    m0 = x.shape[0]
    m = max(32, ((m0 + 31) // 32) * 32)
    if m != m0:
        x = jnp.pad(x, ((0, m - m0), (0, 0)))
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                     keepdims=True)
    sx = jnp.maximum(absmax, 1e-12) / 127.0                   # (m, 1)
    xq = jnp.round(x.astype(jnp.float32) / sx).astype(jnp.int8)
    return xq, sx, m0, m


def _w8a8_kernel(x_ref, w_ref, y_ref):
    """Grid (m_tiles, n_tiles, k_tiles); y accumulates int32 across k.
    One native int8×int8→int32 MXU dot — 2× the bf16 pass rate on v5e,
    and decode at serving batch sizes is MXU-pass-bound,
    so this (not weight bytes) is where quantized decode gains live."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        y_ref[:] = jnp.zeros_like(y_ref)

    y_ref[:] += jnp.dot(x_ref[:], w_ref[:],
                        preferred_element_type=jnp.int32)


def w8a8_matmul(x: jax.Array, q: jax.Array, s: jax.Array,
                out_dtype=None) -> jax.Array:
    """Resolve the interpret flag at CALL time so it participates in
    the jit cache key (a trace-time env read would pin whichever mode
    traced first per shape)."""
    return _w8a8_matmul_jit(x, q, s, out_dtype, _interpret())


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _w8a8_matmul_jit(x: jax.Array, q: jax.Array, s: jax.Array,
                     out_dtype, interpret: bool) -> jax.Array:
    """y ≈ x @ (q * s) with the matmul on the int8 MXU path.

    x: (M, K) float; q: (K, N) int8 weights; s: (1, N) f32 per-channel
    scales. Activations are per-row dynamically quantized to int8 (the
    one approximation vs the exact W8A16 path); everything after is
    exact integer arithmetic until the final scale."""
    kdim = x.shape[1]
    n = q.shape[1]
    out_dtype = out_dtype or x.dtype
    xq, sx, m0, m = _a8_prologue(x)
    bm = _pick_block(m, 256, 32)
    bk = _pick_block(kdim, int(os.environ.get("DYN_INT4_BK", "2048")),
                     128)
    bn = _pick_block(n, 512, 128)
    grid = (m // bm, n // bn, kdim // bk)
    y = pl.pallas_call(
        _w8a8_kernel,
        name="w8a8_matmul",
        interpret=interpret,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(xq, q)
    return (y.astype(jnp.float32) * sx * s)[:m0].astype(out_dtype)


def _interpret() -> bool:
    """DYN_PALLAS_INTERPRET=1 runs the kernels in pallas interpret mode
    (any backend) — hermetic correctness tests without a chip."""
    return os.environ.get("DYN_PALLAS_INTERPRET") == "1"


def _pick_block(dim: int, want: int, tile: int) -> int:
    """Largest divisor of `dim` that is <= want and a multiple of the
    Mosaic tile (dim itself if small). Callers guarantee dim % tile == 0
    (qm's %128 gates + the M pad), so a valid block always exists."""
    assert dim % tile == 0, (dim, tile)
    if dim <= want:
        return dim
    for cand in range(want - want % tile, 0, -tile):
        if dim % cand == 0:
            return cand
    return dim


def int4_matmul(x: jax.Array, p: jax.Array, s: jax.Array,
                out_dtype=None) -> jax.Array:
    """See w8a8_matmul: interpret resolves at call time (cache key)."""
    return _int4_matmul_jit(x, p, s, out_dtype, _interpret())


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _int4_matmul_jit(x: jax.Array, p: jax.Array, s: jax.Array,
                     out_dtype, interpret: bool) -> jax.Array:
    """y = x @ unpack4(p) * s with int4 weight HBM traffic.

    x: (M, K) float; p: (K, N//2) nibble-packed int8; s: (1, N) f32.
    M is padded to a sublane multiple internally; prefill-sized M is
    tiled by the first grid axis.
    """
    kdim = x.shape[1]
    n2 = p.shape[1]
    out_dtype = out_dtype or x.dtype
    # W4A8: per-row dynamic activation quantization (shared prologue).
    # Everything after it is EXACT integer algebra, so the only error
    # vs W4A16 is this one rounding (|x| <= 127 levels per row).
    xq, sx, m0, m = _a8_prologue(x)
    rsq = xq.astype(jnp.int32).sum(axis=-1, keepdims=True)    # (m, 1)
    bm = _pick_block(m, 256, 32)         # int8 sublane tile
    bk = _pick_block(kdim, int(os.environ.get("DYN_INT4_BK", "2048")),
                     128)                # x lane tile (also p sublane)
    bn2 = _pick_block(n2, int(os.environ.get("DYN_INT4_BN2", "512")),
                      128)               # p lane tile
    grid = (m // bm, n2 // bn2, kdim // bk)
    y_p, y_lou = pl.pallas_call(
        _kernel,
        name="int4_matmul",
        interpret=interpret,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn2), lambda i, j, k: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn2), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bn2), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n2), jnp.int32),
            jax.ShapeDtypeStruct((m, n2), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(xq, p)
    # XLA epilogue: recover nibble products (exact: yp - ylou is
    # 16 * xq@hi, and the arithmetic shift divides exact multiples),
    # interleave logical columns (even=lo nibble), then scale by
    # activation-row and weight-column scales.
    y_lo = y_lou - 8 * rsq
    y_hi = jnp.right_shift(y_p - y_lou, 4)
    y = jnp.stack([y_lo, y_hi], axis=-1).reshape(m, 2 * n2)
    return (y.astype(jnp.float32) * sx * s)[:m0].astype(out_dtype)
