"""`greedy_tail` (engine/greedy_tail.py) against `(argmax, log_softmax
gathered)`, off the chip: the kernel runs in the Pallas TPU interpreter,
which fills VMEM it was not given with NaN and refuses a read outside an
array, so a last tile that overhangs V shows if it is read.

Tolerance. The kernel's statistics are f32 and held a lane (column mod
128) until the last tile; what differs from XLA's log-softmax is the order
of the sums: 1e-5. Ids are equal, ties to the lowest column.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import sampling
from dynamo_tpu.engine.greedy_tail import (_geometry, greedy_tail,
                                           greedy_tail_supported)

DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def interpreted(logits, tile=None):
    from jax.experimental.pallas import tpu as pltpu

    ids, lp = greedy_tail(logits, tile=tile,
                          interpret=pltpu.InterpretParams())
    return np.asarray(ids), np.asarray(lp)


def reference(logits):
    x = jnp.asarray(logits).astype(jnp.float32)
    ids = jnp.argmax(x, axis=-1)
    lp = jnp.take_along_axis(jax.nn.log_softmax(x, axis=-1), ids[:, None],
                             axis=-1)[:, 0]
    return np.asarray(ids), np.asarray(lp)


def noise(r, v, dtype, seed=0, gain=3.0):
    rs = np.random.RandomState(seed)
    return jnp.asarray(rs.randn(r, v).astype(np.float32) * gain, dtype)


def agree(logits, tile=None):
    ids, lp = interpreted(logits, tile)
    want_ids, want_lp = reference(logits)
    np.testing.assert_array_equal(ids, want_ids)
    assert np.isfinite(lp).all()
    np.testing.assert_allclose(lp, want_lp, rtol=1e-5, atol=1e-5)


# (R, V, columns a grid step): one tile; several whole tiles; and 19 x 128
# columns, an odd multiple of 128 as SDAR's 151936 = 1187 x 128 is, so the
# last tile overhangs V whatever the tile's width
SHAPES = [(8, 1024, None), (32, 32768, 8192), (256, 2432, 1024)]


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("r,v,tile", SHAPES,
                         ids=[f"{r}x{v}" for r, v, _ in SHAPES])
def test_the_kernel_is_the_argmax_and_its_log_softmax(r, v, tile, dtype):
    agree(noise(r, v, DTYPES[dtype], seed=r), tile)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_eight_rows_of_two_byte_logits_ride_half_a_tile(dtype):
    # Qwen's lanes: 8 rows, where a bf16 tile holds 16
    agree(noise(8, 4096 + 384, DTYPES[dtype], seed=3), 2048)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("cols", [(5, 77), (5, 5 + 128), (300, 1024 + 5),
                                  (1023, 1024), (0, 2431)],
                         ids=["one_tile_two_lanes", "one_lane_two_blocks",
                              "two_tiles", "a_tiles_edge", "first_and_last"])
def test_ties_go_to_the_lowest_column(cols, dtype):
    """The row's largest value stands in two columns: inside one tile
    (other lane; same lane, another block) and in two tiles."""
    x = np.array(noise(16, 2432, jnp.float32, seed=9))
    x[:, list(cols)] = 64.0
    x[3, cols[0]] = -1.0                      # one row where the later wins
    x = jnp.asarray(x, DTYPES[dtype])
    ids, _ = interpreted(x, tile=1024)
    want = np.full(16, min(cols))
    want[3] = max(cols)
    np.testing.assert_array_equal(ids, want)
    agree(x, tile=1024)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_a_masked_row_gives_finite_results(dtype):
    """A guided mask leaves -1e30 everywhere but one column; and a row
    that is -1e30 throughout is a uniform row, not a NaN."""
    x = np.full((8, 2432), -1e30, np.float32)
    x[:7, 1500] = 2.5
    ids, lp = interpreted(jnp.asarray(x, DTYPES[dtype]), tile=1024)
    np.testing.assert_array_equal(ids, [1500] * 7 + [0])
    np.testing.assert_allclose(lp[:7], 0.0, atol=1e-6)
    np.testing.assert_allclose(lp[7], -np.log(2432.0), rtol=1e-6)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_the_last_real_column_beside_the_overhang(dtype):
    x = np.array(noise(16, 2432, jnp.float32, seed=4))
    x[:, 2431] = 50.0
    x = jnp.asarray(x, DTYPES[dtype])
    ids, lp = interpreted(x, tile=1024)
    np.testing.assert_array_equal(ids, np.full(16, 2431))
    agree(x, tile=1024)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_logits_at_the_benchmark_head_gain_stay_finite(dtype):
    agree(noise(16, 2432, DTYPES[dtype], seed=6, gain=3.0 * 16), 1024)


def test_what_the_kernel_tiles_and_what_it_leaves_to_xla():
    assert greedy_tail_supported((256, 151936), jnp.bfloat16)
    assert greedy_tail_supported((8, 152064), jnp.float32)
    assert not greedy_tail_supported((5, 4099), jnp.float32)    # lanes
    assert not greedy_tail_supported((4, 4096), jnp.float32)    # sublanes
    assert not greedy_tail_supported((8, 4096), jnp.float16)
    # SDAR's block burst: 16 rows a group, 38 tiles, the last 384 columns
    sub, slab, tile = _geometry(256, 151936, 2)
    assert (sub, tile % slab, 151936 % tile) == (16, 0, 384)


@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas"])
def test_a_shape_the_kernel_does_not_tile_is_answered_by_xla(
        monkeypatch, pallas):
    """(5, 4099), no multiple of 128 or 8: `sample_with_logprob` gives the
    same pair by XLA, also where the backend would run kernels."""
    from dynamo_tpu.engine import attention

    monkeypatch.setattr(attention, "_impl", "pallas" if pallas else "xla")
    x = noise(5, 4099, jnp.float32, seed=2)
    z = jnp.zeros((5,), jnp.float32)
    tok, lp = sampling.sample_with_logprob(
        x, jnp.zeros((5,), jnp.uint32), jnp.zeros((5,), jnp.int32), z,
        z + 1.0, jnp.zeros((5,), jnp.int32))
    want_ids, want_lp = reference(x)
    np.testing.assert_array_equal(np.asarray(tok), want_ids)
    np.testing.assert_allclose(np.asarray(lp), want_lp, rtol=1e-6, atol=1e-6)
