"""`paged_prefill_attention` (engine/attention.py) against the XLA
`prefill_attention`, off the chip: the kernel runs in the Pallas TPU
interpreter, which fills VMEM it was not given with NaN and refuses a
read outside an array.

Every case poisons what the kernel may not read: each page no live token
of any lane lies on is NaN, and every table entry past a lane's last live
page points at such a page. The reference gathers the whole table, so it
is asked over the same cache with the NaN taken out.

Tolerance as for the decode kernel. f32: both sides are f32 throughout
and differ in the order of the sums: 1e-5. bf16: products, scores,
softmax and accumulator are f32 and the probabilities meet V as bf16
high + low halves; what is left is the rounding of the output to bf16,
2**-7 relative. Rows at or past `seq_len` are compared with nothing (the
engine ignores them) but have to be finite, and a tile wholly of padding
comes back as zeros.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import attention
from dynamo_tpu.engine.attention import (folded, paged_attention_prefill,
                                         paged_prefill_attention,
                                         prefill_attention,
                                         prefill_geometry)

PAGE, D, T = 16, 128, 48
GEOMETRIES = {"32q-8kv": (8, 4), "28q-4kv": (4, 7), "tp4-8q-2kv": (2, 4),
              "tp4-7q-1kv": (1, 7)}                         # (KVH, groups)
TOLERANCE = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
             jnp.bfloat16: dict(rtol=2 ** -7, atol=2 ** -7)}


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 16 queries and blocks of 128 tokens, so that a 48-token
    chunk is three q tiles (in "ends-inside" a full one, a partial one and
    one of padding) and a context of a few hundred tokens several KV
    blocks: the interpreter is slow. The shapes decide as on the chip;
    `test_geometry_follows_the_operand_shapes` pins what they decide."""
    monkeypatch.setattr(attention, "_PREFILL_ROWS", 16 * 7)
    monkeypatch.setattr(attention, "_PREFILL_SCORE_BYTES", 4 * 16 * 7 * 128)
    prefill_geometry.cache_clear()
    yield
    prefill_geometry.cache_clear()


@functools.cache
def _interpreted():
    from jax.experimental.pallas import tpu as pltpu

    return jax.jit(functools.partial(paged_prefill_attention,
                                     interpret=pltpu.InterpretParams()))


def _folded(*args):
    """The kernel over a cache of two 64-wide heads a row; `scale` is a
    static argument of the kernel's own jit."""
    from jax.experimental.pallas import tpu as pltpu

    return folded(functools.partial(paged_prefill_attention,
                                    interpret=pltpu.InterpretParams()),
                  *args)


def _reference(q, k, v, tables, starts, lens):
    pos = starts[:, None] + jnp.arange(q.shape[1])[None, :]
    return jax.vmap(lambda q1, pt, p1, sl: prefill_attention(
        q1, k, v, pt, q_positions=p1, seq_len=sl, page_size=PAGE)
    )(q, tables, pos, lens)


def _chunk(position, block):
    """(cached_len, seq_len) of a T-token chunk at the named position."""
    return {"first": (0, T),
            "middle": (2 * block, 2 * block + T),
            "ends-inside": (block, block + T - 21),
            "off-edge": (block + 37, block + 37 + T)}[position]


def _round(kvh, groups, position, width, dtype, block, d=D):
    """A round of `width` lanes: lane 0 at `position`, the others at the
    other positions in turn, the last of a wide round a padding lane.
    Heads `d` wide."""
    names = ["first", "middle", "ends-inside", "off-edge"]
    at = names.index(position)
    spans = [_chunk(names[(at + i) % 4], block) for i in range(width)]
    if width > 2:
        spans[-1] = (block + 5, block + 5)
    starts, lens = (np.asarray(x, np.int32) for x in zip(*spans))
    max_pages = -(-(3 * block + T) // PAGE) + 2
    n_pages = width * max_pages + 2
    rng = np.random.default_rng(kvh * 100 + groups * 10 + at + width)
    tables = 1 + rng.permutation(n_pages - 2)[:width * max_pages].reshape(
        width, max_pages)
    dead = np.arange(max_pages)[None, :] >= -(-lens[:, None] // PAGE)
    tables[dead] = n_pages - 1                     # the poison page
    k, v = (rng.standard_normal((kvh, n_pages, PAGE, d)) for _ in range(2))
    nan = np.ones(n_pages, bool)
    nan[np.unique(tables[~dead])] = False
    k[:, nan], v[:, nan] = np.nan, np.nan
    q = rng.standard_normal((width, T, kvh * groups, d))
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(tables, jnp.int32),
            jnp.asarray(starts), jnp.asarray(lens))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("width", [1, 2, 8])
@pytest.mark.parametrize("position",
                         ["first", "middle", "ends-inside", "off-edge"])
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_kernel_matches_the_xla_reference(small_tiles, geometry, position,
                                          width, dtype):
    kvh, groups = GEOMETRIES[geometry]
    tq, ppb = prefill_geometry(kvh, groups, T, PAGE, D,
                               jnp.dtype(dtype).itemsize)
    assert (tq, ppb * PAGE) == (16, 128)
    q, k, v, tables, starts, lens = _round(kvh, groups, position, width,
                                           dtype, ppb * PAGE)
    got = np.asarray(_interpreted()(q, k, v, tables, starts, lens),
                     np.float32)
    want = np.asarray(_reference(q, jnp.nan_to_num(k), jnp.nan_to_num(v),
                                 tables, starts, lens), np.float32)
    assert np.isfinite(got).all()         # no poisoned page was read
    for lane, n in enumerate(np.asarray(lens - starts)):
        np.testing.assert_allclose(got[lane, :n], want[lane, :n],
                                   **TOLERANCE[dtype])
        assert not got[lane, -(-n // tq) * tq:].any()   # tiles of padding


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("position",
                         ["first", "middle", "ends-inside", "off-edge"])
@pytest.mark.parametrize("kvh,groups", [(4, 3), (2, 2)])
def test_two_64_wide_heads_to_a_row(small_tiles, kvh, groups, position,
                                    dtype):
    """head_dim 64: two kv heads side by side in a 128-lane row of the
    cache, the kernel as it runs at 128 over q heads laid into their own
    head's lanes (`folded`), against the reference over the cache a head a
    row; the same poisoned pages, ragged ends and page-boundary starts."""
    from tests.test_paged_decode_attention import rows_of

    tq, ppb = prefill_geometry(kvh // 2, groups * 2, T, PAGE, D,
                               jnp.dtype(dtype).itemsize)
    assert (tq, ppb * PAGE) == (16, 128)
    q, k, v, tables, starts, lens = _round(kvh, groups, position, 2, dtype,
                                           ppb * PAGE, d=64)
    got = np.asarray(_folded(q, rows_of(k), rows_of(v), tables, starts,
                             lens), np.float32)
    want = np.asarray(_reference(q, jnp.nan_to_num(k), jnp.nan_to_num(v),
                                 tables, starts, lens), np.float32)
    assert got.shape == want.shape and np.isfinite(got).all()
    for lane, n in enumerate(np.asarray(lens - starts)):
        np.testing.assert_allclose(got[lane, :n], want[lane, :n],
                                   **TOLERANCE[dtype])


def test_the_tiles_lfm2_runs_on_the_chip():
    """32 q / 8 kv heads of 64 as the kernel sees them, 4 rows of 8 q
    heads: a 128-token chunk behind 300 cached tokens, bf16, no small
    tiles."""
    from tests.test_paged_decode_attention import rows_of

    assert prefill_geometry(4, 8, 512, 16, 128, 2) == (128, 16)
    chunk = 128
    rng = np.random.default_rng(64)
    max_pages = -(-(300 + chunk) // PAGE)
    tables = rng.permutation(max_pages + 3)[:max_pages][None]
    k, v = (jnp.asarray(rng.standard_normal((8, max_pages + 3, PAGE, 64)),
                        jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((1, chunk, 32, 64)), jnp.bfloat16)
    rest = (jnp.asarray(tables, jnp.int32), jnp.asarray([300], jnp.int32),
            jnp.asarray([300 + chunk - 9], jnp.int32))
    got = np.asarray(_folded(q, rows_of(k), rows_of(v), *rest), np.float32)
    want = np.asarray(_reference(q, k, v, *rest), np.float32)
    np.testing.assert_allclose(got[0, :chunk - 9], want[0, :chunk - 9],
                               **TOLERANCE[jnp.bfloat16])
    assert np.isfinite(got).all()


@pytest.mark.parametrize("geometry,chunk,tile,block", [
    ("28q-4kv", 256, 128, 256),           # Qwen2.5-7B: two q tiles
    ("32q-8kv", 128, 128, 256),           # Mistral-7B / Llama-3-8B
])
def test_the_tiles_the_chip_runs(geometry, chunk, tile, block):
    """The geometry as the chip gets it (no small tiles): a chunk behind
    300 cached tokens, bf16."""
    kvh, groups = GEOMETRIES[geometry]
    tq, ppb = prefill_geometry(kvh, groups, chunk, PAGE, D, 2)
    assert (tq, ppb * PAGE) == (tile, block)
    rng = np.random.default_rng(chunk)
    max_pages = -(-(300 + chunk) // PAGE)
    tables = rng.permutation(max_pages + 3)[:max_pages][None]
    k, v = (jnp.asarray(rng.standard_normal((kvh, max_pages + 3, PAGE, D)),
                        jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((1, chunk, kvh * groups, D)),
                    jnp.bfloat16)
    args = (q, k, v, jnp.asarray(tables, jnp.int32),
            jnp.asarray([300], jnp.int32),
            jnp.asarray([300 + chunk - 9], jnp.int32))
    got = np.asarray(_interpreted()(*args), np.float32)
    want = np.asarray(_reference(*args), np.float32)
    np.testing.assert_allclose(got[0, :chunk - 9], want[0, :chunk - 9],
                               **TOLERANCE[jnp.bfloat16])
    assert np.isfinite(got).all()


def test_bf16_kv_loses_nothing_against_f32_probabilities(small_tiles):
    """With q and the output in f32 over a bf16 cache nothing rounds to
    bf16 on the way: the high + low halves keep the kernel within 2e-5 of
    the reference's f32 probabilities (bf16-only ones are off by 2e-3)."""
    q, k, v, tables, starts, lens = _round(4, 7, "off-edge", 2,
                                           jnp.bfloat16, 128)
    q = q.astype(jnp.float32)
    got = np.asarray(_interpreted()(q, k, v, tables, starts, lens))
    want = np.asarray(_reference(q, jnp.nan_to_num(k), jnp.nan_to_num(v),
                                 tables, starts, lens))
    for lane, n in enumerate(np.asarray(lens - starts)):
        np.testing.assert_allclose(got[lane, :n], want[lane, :n],
                                   rtol=2e-5, atol=2e-5)


def test_geometry_follows_the_operand_shapes():
    # (kvh, groups, chunk, page, head_dim, itemsize) -> (q tile, pages)
    assert prefill_geometry(4, 7, 512, 16, 128, 2) == (128, 16)   # Qwen
    assert prefill_geometry(8, 4, 256, 16, 128, 2) == (256, 16)   # Mistral
    assert prefill_geometry(2, 4, 256, 16, 128, 2) == (256, 16)   # tp=4
    assert prefill_geometry(1, 7, 512, 16, 128, 2) == (128, 16)
    # the bucket ladder's rungs: whole tiles of 16 that divide the chunk
    assert prefill_geometry(8, 4, 384, 16, 128, 2)[0] == 192
    assert prefill_geometry(8, 4, 16, 16, 128, 2)[0] == 16
    # big pages: whole pages only; f32 KV: half the tokens a block
    assert prefill_geometry(8, 4, 256, 256, 128, 2)[1] == 1
    assert prefill_geometry(8, 4, 256, 16, 128, 4) == (256, 8)
    # declined: a spec-verify's few tokens, pages that tile no 128 tokens
    assert prefill_geometry(8, 4, 5, 16, 128, 2) is None
    assert prefill_geometry(8, 4, 256, 48, 128, 2) is None


@pytest.mark.parametrize("reason,chunk,head_dim", [
    ("chunk_shape", 5, 128),              # spec-verify's gamma + 1 rows
    ("head_dim", 64, 64),
])
def test_a_declined_shape_is_counted_once_and_served_by_xla(
        monkeypatch, reason, chunk, head_dim):
    monkeypatch.setattr(attention, "_impl", "pallas")
    rng = np.random.default_rng(0)
    k, v = (jnp.asarray(rng.standard_normal((2, 9, PAGE, head_dim)),
                        jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((1, chunk, 8, head_dim)),
                    jnp.float32)
    args = (q, k, v, jnp.arange(8, dtype=jnp.int32)[None],
            jnp.asarray([32], jnp.int32), jnp.asarray([32 + chunk], jnp.int32))
    before = attention.attention_fallbacks.get(reason=reason)
    got = paged_attention_prefill(*args, page_size=PAGE)
    assert attention.attention_fallbacks.get(reason=reason) == before + 1
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_reference(*args)))
