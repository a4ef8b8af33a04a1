"""`paged_decode_attention` (engine/attention.py) against `_xla_decode`,
off the chip: the kernel runs in the Pallas TPU interpreter, which fills
VMEM it was not given with NaN and refuses a read outside an array.

Tolerance. f32 KV: both sides are f32 throughout and differ in the order
of the sums only: 1e-5. bf16 KV: the kernel's products, scores, softmax
and accumulator are f32 and the probabilities enter the second product
as bf16 high + low halves, so what is left is the rounding of the output
to bf16, one unit in the last place of either side: 2**-7 relative.
Padding lanes (`lengths == 0`) come back as zeros from the kernel and as
a uniform average from the reference; the scheduler reads neither.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import functools

from dynamo_tpu.engine.attention import (_xla_decode, decode_geometry,
                                         folded, paged_decode_attention)

PAGE, D, LANES = 16, 128, 8
GEOMETRIES = [(8, 4), (4, 7), (2, 4), (1, 7), (8, 5)]     # (KVH, groups)
TOLERANCE = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
             jnp.bfloat16: dict(rtol=2 ** -7, atol=2 ** -7)}


def _interpreted(*args):
    from jax.experimental.pallas import tpu as pltpu

    return paged_decode_attention(*args, interpret=pltpu.InterpretParams())


def _case(kvh, groups, dtype, seed, d=D):
    """Eight lanes whose lengths sit on every edge the kernel has, over a
    cache whose pages are handed out in random order; lanes 6 and 7 share
    the pages of their first block. Heads `d` wide, D // d of them to a
    row of the cache the kernel sees."""
    fold = D // d
    ppb, _ = decode_geometry(LANES, kvh // fold, groups * fold, PAGE, D,
                             jnp.dtype(dtype).itemsize)
    block = ppb * PAGE
    max_pages = 2 * ppb + 3               # the last block is a partial one
    lengths = [0, 1, PAGE - 1, PAGE, PAGE + 1, block, block + 1,
               max_pages * PAGE]
    rng = np.random.default_rng(seed)
    n_pages = LANES * max_pages + 1
    tables = rng.permutation(n_pages)[:LANES * max_pages].reshape(
        LANES, max_pages)
    tables[7, :ppb] = tables[6, :ppb]
    k, v = (jnp.asarray(rng.standard_normal((kvh, n_pages, PAGE, d)), dtype)
            for _ in range(2))
    q = jnp.asarray(rng.standard_normal((LANES, kvh * groups, d)), dtype)
    return (q, k, v, jnp.asarray(lengths, jnp.int32),
            jnp.asarray(tables, jnp.int32))


def rows_of(pages, fold=2):
    """A cache (KVH, N, P, d) as engine/pages.py `kv_layer_shape` lays it
    out where `fold` heads ride in one row: (KVH / fold, N, P, fold * d),
    neighbouring heads side by side."""
    kvh, n, p, d = pages.shape
    return pages.reshape(kvh // fold, fold, n, p, d).transpose(
        0, 2, 3, 1, 4).reshape(kvh // fold, n, p, fold * d)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kvh,groups", GEOMETRIES)
def test_kernel_matches_the_xla_reference(kvh, groups, dtype):
    args = _case(kvh, groups, dtype, seed=kvh * 10 + groups)
    got = np.asarray(_interpreted(*args), np.float32)
    want = np.asarray(_xla_decode(*args), np.float32)
    live = np.asarray(args[3]) > 0
    np.testing.assert_allclose(got[live], want[live], **TOLERANCE[dtype])
    assert not got[~live].any()           # padding lanes: zeros, not NaN


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kvh,groups", [(8, 4), (2, 4), (4, 7)])
def test_two_64_wide_heads_to_a_row(kvh, groups, dtype):
    """head_dim 64 (LFM2: 32 q / 8 kv heads of 64, GQA 4): the cache holds
    two kv heads side by side in a 128-lane row and the kernel runs as it
    does at 128, over q heads laid into their own head's lanes (`folded`).
    Against the reference over the cache a head a row, the same ragged
    lengths, page-boundary starts and shared pages."""
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, lengths, tables = _case(kvh, groups, dtype, seed=kvh + groups,
                                     d=64)
    kernel = functools.partial(paged_decode_attention,
                               interpret=pltpu.InterpretParams())
    got = np.asarray(folded(kernel, q, rows_of(k), rows_of(v), lengths,
                            tables), np.float32)
    want = np.asarray(_xla_decode(q, k, v, lengths, tables), np.float32)
    assert got.shape == want.shape
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], **TOLERANCE[dtype])
    assert not got[~live].any()
    # and the XLA path over the same rows is the reference itself
    np.testing.assert_allclose(
        np.asarray(folded(_xla_decode, q, rows_of(k), rows_of(v), lengths,
                          tables), np.float32)[live], want[live],
        **TOLERANCE[dtype])


def test_a_batch_of_two_grid_steps():
    """Batch 64 at Mistral widths is two grid steps of 32 lanes: the lane
    offset of a step, its rows of the flat page table and the re-priming
    of the slots, with padding lanes on both sides of the boundary and a
    second step that opens on one."""
    kvh, groups, batch = 8, 4, 64
    ppb, lanes = decode_geometry(batch, kvh, groups, PAGE, D, 2)
    assert (ppb, lanes) == (8, 32)
    block, max_pages = ppb * PAGE, 2 * ppb + 3
    rng = np.random.default_rng(64)
    lengths = rng.integers(1, max_pages * PAGE + 1, batch)
    lengths[[0, 30, 31, 32, 33, 63]] = [block, 0, 0, 0, 0, max_pages * PAGE]
    lengths[[29, 34]] = [1, block + 1]
    n_pages = batch * max_pages
    tables = rng.permutation(n_pages).reshape(batch, max_pages)
    k, v = (jnp.asarray(rng.standard_normal((kvh, n_pages, PAGE, D)),
                        jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((batch, kvh * groups, D)),
                    jnp.bfloat16)
    args = (q, k, v, jnp.asarray(lengths, jnp.int32),
            jnp.asarray(tables, jnp.int32))
    got = np.asarray(_interpreted(*args), np.float32)
    want = np.asarray(_xla_decode(*args), np.float32)
    live = lengths > 0
    np.testing.assert_allclose(got[live], want[live],
                               **TOLERANCE[jnp.bfloat16])
    assert not got[~live].any()


def test_bf16_kv_loses_nothing_against_f32_probabilities():
    """What the high + low halves of the probabilities buy: with q and the
    output in f32 over a bf16 cache nothing rounds to bf16 on the way, and
    the kernel stays within 2e-5 of the reference's f32 probabilities;
    probabilities cut to bf16 alone are off by 2**-9 of a value, 2e-3
    here (measured in the interpreter with the low half dropped)."""
    q, k, v, lengths, tables = _case(4, 7, jnp.bfloat16, seed=11)
    q = q.astype(jnp.float32)
    got = np.asarray(_interpreted(q, k, v, lengths, tables))
    want = np.asarray(_xla_decode(q, k, v, lengths, tables))
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)


def test_pages_beyond_a_lanes_length_are_never_read():
    """Every page that no lane's live tokens reach is NaN, and so is the
    page every dead table entry points at: one read of either and the
    output is NaN."""
    q, k, v, lengths, tables = _case(4, 7, jnp.float32, seed=5)
    lengths = lengths.at[7].set(3 * PAGE + 5)
    tables, poison = np.asarray(tables).copy(), int(k.shape[1]) - 1
    dead = np.arange(tables.shape[1])[None, :] >= -(
        -np.asarray(lengths)[:, None] // PAGE)
    tables[dead] = poison
    live_pages = np.unique(tables[~dead])
    nan = np.ones(k.shape[1], bool)
    nan[live_pages] = False
    k, v = (x.at[:, nan].set(jnp.nan) for x in (k, v))
    got = np.asarray(_interpreted(q, k, v, lengths, jnp.asarray(tables)))
    assert np.isfinite(got).all()
    # the reference gathers every table entry and cannot be asked here;
    # the same lanes over a clean cache give the same answer
    clean = np.asarray(_interpreted(
        q, *(jnp.nan_to_num(x) for x in (k, v)), lengths,
        jnp.asarray(tables)))
    np.testing.assert_array_equal(got, clean)


def test_geometry_follows_the_operand_shapes():
    # a block is 512 KiB of K and V whatever the model: 128 tokens at 8 kv
    # heads (Mistral, Llama-3), 256 at 4 (Qwen2.5-7B), 512 at 2 (a tp=4
    # shard of either 8-head model); one grid step holds the whole batch
    # of the benchmark's cells
    assert decode_geometry(32, 8, 4, 16, 128, 2) == (8, 32)
    assert decode_geometry(8, 4, 7, 16, 128, 2) == (16, 8)
    assert decode_geometry(8, 2, 4, 16, 128, 2) == (32, 8)
    # big pages: at least one page, whole pages only
    assert decode_geometry(4, 8, 4, 256, 128, 2)[0] == 1
    # many lanes: the q rows of a grid step stay under 1 MiB of VMEM
    ppb, lanes = decode_geometry(256, 8, 4, 16, 128, 2)
    assert 256 % lanes == 0 and lanes * 8 * 16 * 128 * 2 <= 1 << 20
