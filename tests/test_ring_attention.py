"""Ring attention ≡ single-device full attention (8-way CPU mesh).

The sequence axis is sharded over an "sp" ring; output must match the
unsharded flash-style reference exactly (same math, different schedule).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

from dynamo_tpu.engine.ring_attention import (
    ring_attention,
    ring_attention_local,
    sp_mesh,
)


def full_attention_reference(q, k, v, causal=True):
    """Dense single-device reference (float32 softmax)."""
    b, t, h, d = q.shape
    kvh = k.shape[2]
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def _rand(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             dtype=jnp.float32)


@pytest.mark.parametrize("sp", [2, 4, 8])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_dense(sp, causal, cpu_mesh_devices):
    b, t, h, d = 2, 64, 4, 16
    q, k, v = (_rand((b, t, h, d), s) for s in (0, 1, 2))
    mesh = sp_mesh(sp, cpu_mesh_devices)
    out = ring_attention(q, k, v, mesh, causal=causal)
    ref = full_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_gqa(cpu_mesh_devices):
    b, t, h, kvh, d = 1, 32, 8, 2, 16
    q = _rand((b, t, h, d), 0)
    k = _rand((b, t, kvh, d), 1)
    v = _rand((b, t, kvh, d), 2)
    mesh = sp_mesh(4, cpu_mesh_devices)
    out = ring_attention(q, k, v, mesh, causal=True)
    ref = full_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_bf16_inputs(cpu_mesh_devices):
    b, t, h, d = 1, 32, 2, 32
    q, k, v = (_rand((b, t, h, d), s).astype(jnp.bfloat16)
               for s in (0, 1, 2))
    mesh = sp_mesh(4, cpu_mesh_devices)
    out = ring_attention(q, k, v, mesh)
    assert out.dtype == jnp.bfloat16
    ref = full_attention_reference(q.astype(jnp.float32),
                                   k.astype(jnp.float32),
                                   v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.05)


def test_ring_output_stays_sharded(cpu_mesh_devices):
    """No gather at the end: output keeps the sequence sharding so the
    next layer's ops shard the same way."""
    b, t, h, d = 1, 64, 2, 16
    q, k, v = (_rand((b, t, h, d), s) for s in (0, 1, 2))
    mesh = sp_mesh(8, cpu_mesh_devices)
    out = ring_attention(q, k, v, mesh)
    shard_shapes = {s.data.shape for s in out.addressable_shards}
    assert shard_shapes == {(b, t // 8, h, d)}


def test_ring_rejects_indivisible_sequence(cpu_mesh_devices):
    mesh = sp_mesh(8, cpu_mesh_devices)
    q = _rand((1, 60, 2, 16), 0)
    with pytest.raises(AssertionError):
        ring_attention(q, q, q, mesh)


def test_ring_local_inside_custom_shard_map(cpu_mesh_devices):
    """ring_attention_local composes into a user shard_map (the engine's
    own prefill will call it under its mesh)."""
    import functools

    from jax.sharding import NamedSharding, PartitionSpec as P

    b, t, h, d = 1, 64, 2, 16
    q, k, v = (_rand((b, t, h, d), s) for s in (0, 1, 2))
    mesh = sp_mesh(4, cpu_mesh_devices)
    spec = P(None, "sp", None, None)
    fn = jax.jit(shard_map(
        functools.partial(ring_attention_local, axis_name="sp"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
    args = [jax.device_put(x, NamedSharding(mesh, spec))
            for x in (q, k, v)]
    out = fn(*args)
    ref = full_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# sequence-parallel model prefill ≡ paged single-device prefill

def test_sp_prefill_matches_paged_prefill(cpu_mesh_devices):
    from dynamo_tpu.models.llama import (
        LlamaConfig,
        init_cache,
        init_params,
        prefill_batch,
    )
    from dynamo_tpu.models.llama_sp import sp_prefill

    cfg = LlamaConfig.tiny(dtype=jnp.float32)  # f32: exact comparison
    params = init_params(jax.random.PRNGKey(0), cfg)
    T = 32  # 8 pages of 4; divisible by sp=4
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, T), 1, 255)

    # single-device paged reference
    k_cache, v_cache = init_cache(cfg, num_pages=32)
    n_pages = T // cfg.page_size
    page_tables = jnp.stack([
        jnp.pad(jnp.arange(1, 1 + n_pages), (0, 16 - n_pages)),
        jnp.pad(jnp.arange(1 + n_pages, 1 + 2 * n_pages),
                (0, 16 - n_pages))])
    ref_logits, k_cache, v_cache = prefill_batch(
        params, k_cache, v_cache, tokens, page_tables,
        jnp.zeros(2, jnp.int32), jnp.full((2,), T, jnp.int32), cfg)

    mesh = sp_mesh(4, cpu_mesh_devices)
    sp_logits, k_all, v_all = sp_prefill(params, tokens, cfg, mesh)

    np.testing.assert_allclose(np.asarray(sp_logits),
                               np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)
    # exported KV matches what the paged path wrote (seq 0, layer 0)
    want_k = np.asarray(k_all[0, 0])              # (T, KVH, D)
    paged_k = np.asarray(k_cache[0][:, 1:1 + n_pages])  # (KVH, n, P, D)
    paged_k = paged_k.transpose(1, 2, 0, 3).reshape(T, cfg.num_kv_heads,
                                                    cfg.head_dim)
    np.testing.assert_allclose(want_k, paged_k, rtol=2e-4, atol=2e-4)


def test_sp_prefill_kv_stays_sequence_sharded(cpu_mesh_devices):
    from dynamo_tpu.models.llama import LlamaConfig, init_params
    from dynamo_tpu.models.llama_sp import sp_prefill

    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 1, 255)
    mesh = sp_mesh(8, cpu_mesh_devices)
    _, k_all, _ = sp_prefill(params, tokens, cfg, mesh)
    shapes = {s.data.shape for s in k_all.addressable_shards}
    # each chip holds only ITS 8-token chunk of every layer's K
    assert shapes == {(cfg.num_layers, 1, 8, cfg.num_kv_heads,
                       cfg.head_dim)}


@pytest.mark.parametrize("sp", [2, 4, 8])
def test_zigzag_ring_matches_dense(sp, cpu_mesh_devices):
    b, t, h, d = 2, 64, 4, 16
    q, k, v = (_rand((b, t, h, d), s) for s in (0, 1, 2))
    mesh = sp_mesh(sp, cpu_mesh_devices)
    out = ring_attention(q, k, v, mesh, causal=True, layout="zigzag")
    ref = full_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_zigzag_gqa_matches_dense(cpu_mesh_devices):
    b, t, h, kvh, d = 1, 48, 8, 2, 16
    q = _rand((b, t, h, d), 0)
    k = _rand((b, t, kvh, d), 1)
    v = _rand((b, t, kvh, d), 2)
    mesh = sp_mesh(4, cpu_mesh_devices)
    out = ring_attention(q, k, v, mesh, causal=True, layout="zigzag")
    ref = full_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_zigzag_permutation_roundtrip():
    from dynamo_tpu.engine.ring_attention import zigzag_permutation

    perm, inv = zigzag_permutation(32, 4)
    x = np.arange(32)
    assert (x[perm][inv] == x).all()
    # device 0 holds stripes 0 and 7 (tb=4)
    assert perm[:8].tolist() == [0, 1, 2, 3, 28, 29, 30, 31]
