"""Visibility by block (`attn_block`): key j is visible to query i iff
j // B <= i // B. B = 1 is the causal rule and must give what it gave; B = 4
on the XLA path and in both kernels (interpret mode), and a block's B rows
riding `paged_decode_attention` as B x groups query rows a kv head.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import attention

KVH, GROUPS, D, P = 2, 4, 128, 16


def caches(n_pages, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    shape = (KVH, n_pages, P, D)
    return (jax.random.normal(ks[0], shape, dtype),
            jax.random.normal(ks[1], shape, dtype))


def dense(q, k_pages, v_pages, table, positions, seq_len, block):
    """Straight softmax attention of rows at `positions` over the keys of
    `table`'s pages under the block rule."""
    k = np.asarray(k_pages)[:, table].reshape(KVH, -1, D)
    v = np.asarray(v_pages)[:, table].reshape(KVH, -1, D)
    out = np.zeros(q.shape, np.float32)
    for t, pos in enumerate(positions):
        last = min((pos // block + 1) * block, seq_len)
        for h in range(q.shape[1]):
            g = h // GROUPS
            s = k[g, :last] @ np.asarray(q[t, h], np.float32) / np.sqrt(D)
            p = np.exp(s - s.max())
            out[t, h] = (p / p.sum()) @ v[g, :last]
    return out


@pytest.mark.parametrize("block", [1, 4])
def test_xla_prefill_block_rule(block):
    k_pages, v_pages = caches(8)
    table = jnp.asarray([3, 1, 4, 2])
    t, start, seq_len = 32, 16, 42
    q = jax.random.normal(jax.random.PRNGKey(1), (t, KVH * GROUPS, D))
    positions = start + jnp.arange(t)
    got = attention.prefill_attention(
        q, k_pages, v_pages, table, positions, jnp.asarray(seq_len), P,
        attn_block=block)
    live = seq_len - start
    want = dense(q, k_pages, v_pages, np.asarray(table),
                 np.asarray(positions), seq_len, block)
    np.testing.assert_allclose(got[:live], want[:live], atol=2e-5)


def test_block_1_is_the_causal_mask_it_was():
    s, q = jnp.arange(12)[None, :], jnp.arange(12)[:, None]
    assert (attention.visible(s, q, 1) == (s <= q)).all()
    seen = attention.visible(s, q, 4)
    assert bool(seen[4, 7]) and not bool(seen[3, 4]) and bool(seen[0, 3])


@pytest.mark.parametrize("block", [1, 4])
def test_prefill_kernel_equals_xla(block):
    k_pages, v_pages = caches(24, seed=2)
    tables = jnp.asarray([[5, 9, 2, 7, 11, 13, 3, 8],
                          [1, 4, 6, 10, 12, 14, 15, 16]])
    t = 32
    starts = jnp.asarray([16, 64])
    seq_lens = jnp.asarray([46, 96])         # one ends inside its chunk
    q = jax.random.normal(jax.random.PRNGKey(3), (2, t, KVH * GROUPS, D))
    got = attention.paged_prefill_attention(
        q, k_pages, v_pages, tables, starts, seq_lens, attn_block=block,
        interpret=True)
    want = jax.vmap(lambda q1, pt, st, sl: attention.prefill_attention(
        q1, k_pages, v_pages, pt, st + jnp.arange(t), sl, P,
        attn_block=block))(q, tables, starts, seq_lens)
    for lane in range(2):
        live = int(seq_lens[lane] - starts[lane])
        np.testing.assert_allclose(got[lane, :live], want[lane, :live],
                                   atol=2e-5)


@pytest.mark.parametrize("interpret_kernel", [False, True],
                         ids=["xla", "kernel"])
def test_a_blocks_rows_ride_the_decode_attention(interpret_kernel):
    """B = 4 rows of a lane see one key set (everything up to the block's
    end), so they are 4 x groups = 16 query rows a kv head of ONE decode
    lane with length = cached + 4; at the real model's 8 groups that is
    the 32 rows a kv head the block step runs with."""
    blk = 4
    k_pages, v_pages = caches(12, seed=4)
    tables = jnp.asarray([[2, 5, 7, 1], [9, 3, 8, 4], [6, 10, 11, 0]])
    cached = np.asarray([20, 36, 0])
    lengths = jnp.asarray([24, 40, 0])       # the last lane is padding
    lanes = 3
    q = jax.random.normal(jax.random.PRNGKey(5),
                          (lanes, blk, KVH * GROUPS, D))
    q2 = q.reshape(lanes, blk, KVH, GROUPS, D).transpose(
        0, 2, 1, 3, 4).reshape(lanes, KVH * blk * GROUPS, D)
    fn = (lambda *a: attention.paged_decode_attention(*a, interpret=True)) \
        if interpret_kernel else attention._xla_decode
    out = fn(q2, k_pages, v_pages, lengths, tables)
    out = out.reshape(lanes, KVH, blk, GROUPS, D).transpose(
        0, 2, 1, 3, 4).reshape(q.shape)
    for lane in range(2):
        want = dense(q[lane], k_pages, v_pages, np.asarray(tables[lane]),
                     cached[lane] + np.arange(blk), int(lengths[lane]), blk)
        np.testing.assert_allclose(out[lane], want, atol=2e-5)


def test_decode_geometry_takes_32_rows_a_kv_head():
    ppb, lanes = attention.decode_geometry(64, 4, 32, 16, 128, 2)
    assert ppb * 16 % 128 == 0 and 64 % lanes == 0 and lanes >= 1
