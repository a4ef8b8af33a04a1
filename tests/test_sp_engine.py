"""Engine-integrated sequence-parallel prefill (TpuEngineConfig.sp_mesh).

Long novel prompts take the ring-attention bulk path with paged KV
writeback; output must be identical to the plain chunked-prefill engine
(same params, greedy) — the strongest end-to-end check that the
sequence-sharded KV landed in the right pages.
"""

import jax
import numpy as np
from jax.sharding import Mesh

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models.llama import LlamaConfig, init_params
from dynamo_tpu.runtime.context import Context

set_attention_impl("xla")

CFG = LlamaConfig.tiny(max_pages_per_seq=32)  # context 128, page_size 4


def sp_mesh(devices, n=4):
    return Mesh(np.asarray(devices[:n]), axis_names=("sp",))


async def generate(eng, prompt, n_tokens=12):
    req = {"token_ids": list(prompt), "model": "m",
           "sampling": {"temperature": 0.0},
           "stop": {"max_tokens": n_tokens}}
    return [t async for o in eng.generate(req, Context())
            for t in o.get("token_ids", [])]


async def test_sp_prefill_output_matches_plain_engine(cpu_mesh_devices):
    prompt = [(i * 7) % 250 + 1 for i in range(50)]
    params = init_params(jax.random.PRNGKey(0), CFG)

    plain = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=2), params=params)
    base = await generate(plain, prompt)
    await plain.close()

    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=2,
        sp_mesh=sp_mesh(cpu_mesh_devices), sp_threshold=32),
        params=params)
    got = await generate(eng, prompt)
    # unit = sp*page_size = 16; t_sp = 16 * 2^floor(log2(49/16)) = 32
    assert got == base
    await eng.close()


async def test_sp_short_prompt_skips_bulk_path(cpu_mesh_devices):
    # below threshold: behaves exactly like the plain engine
    prompt = [(i * 3) % 250 + 1 for i in range(10)]
    params = init_params(jax.random.PRNGKey(0), CFG)
    plain = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=2), params=params)
    base = await generate(plain, prompt)
    await plain.close()
    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=2,
        sp_mesh=sp_mesh(cpu_mesh_devices), sp_threshold=32),
        params=params)
    got = await generate(eng, prompt)
    assert got == base
    await eng.close()


async def test_sp_with_prefix_cache_second_request(cpu_mesh_devices):
    # second identical request hits the prefix cache (cached_len > 0) and
    # must SKIP the sp path yet still produce identical output
    prompt = [(i * 7) % 250 + 1 for i in range(50)]
    params = init_params(jax.random.PRNGKey(0), CFG)
    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=2,
        sp_mesh=sp_mesh(cpu_mesh_devices), sp_threshold=32),
        params=params)
    a = await generate(eng, prompt)
    b = await generate(eng, prompt)
    assert a == b
    await eng.close()


async def test_sp_with_int8_quantized_params(cpu_mesh_devices):
    prompt = [(i * 5) % 250 + 1 for i in range(40)]
    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=2, quantize="int8",
        sp_mesh=sp_mesh(cpu_mesh_devices), sp_threshold=16))
    toks = await generate(eng, prompt, n_tokens=8)
    assert len(toks) == 8
    await eng.close()


def test_sp_with_tp_mesh_rejected(cpu_mesh_devices):
    import pytest

    from dynamo_tpu.engine.sharding import make_mesh

    with pytest.raises(ValueError):
        TpuEngine(TpuEngineConfig(
            model=CFG, mesh=make_mesh(dp=1, tp=2,
                                      devices=cpu_mesh_devices),
            sp_mesh=sp_mesh(cpu_mesh_devices), sp_threshold=16))


def sp_tp_mesh(devices, sp=2, tp=2):
    return Mesh(np.asarray(devices[:sp * tp]).reshape(sp, tp),
                axis_names=("sp", "tp"))


async def test_sp_tp_engine_matches_tp_only(cpu_mesh_devices):
    """The sp x tp composition: TP-sharded serving weights + SP ring
    prefill on one 2-D mesh, KV written back to the tp-sharded paged
    cache. Greedy tokens must equal the tp-only engine's."""
    from dynamo_tpu.engine.sharding import make_mesh

    prompt = [(i * 7) % 250 + 1 for i in range(50)]
    params = init_params(jax.random.PRNGKey(0), CFG)

    tp_only = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=2,
        mesh=make_mesh(dp=1, tp=2, devices=cpu_mesh_devices)),
        params=params)
    base = await generate(tp_only, prompt)
    await tp_only.close()

    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=2,
        mesh=make_mesh(dp=1, tp=2, devices=cpu_mesh_devices),
        sp_mesh=sp_tp_mesh(cpu_mesh_devices), sp_threshold=16),
        params=params)
    assert eng._sp_tp == "tp"
    got = await generate(eng, prompt)
    assert got == base and len(got) == 12
    await eng.close()


async def test_sp_tp_engine_zigzag_and_quantized(cpu_mesh_devices):
    """sp×tp composed with the zigzag ring layout AND int8 weights —
    the full stack the multi-host 70B shape would run."""
    from dynamo_tpu.engine.sharding import make_mesh

    prompt = [(i * 5) % 250 + 1 for i in range(70)]
    params = init_params(jax.random.PRNGKey(1), CFG)

    tp_only = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=2, quantize="int8",
        mesh=make_mesh(dp=1, tp=2, devices=cpu_mesh_devices)),
        params=params)
    base = await generate(tp_only, prompt)
    await tp_only.close()

    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=2, quantize="int8",
        mesh=make_mesh(dp=1, tp=2, devices=cpu_mesh_devices),
        sp_mesh=sp_tp_mesh(cpu_mesh_devices), sp_threshold=16,
        sp_layout="zigzag"), params=params)
    got = await generate(eng, prompt)
    assert got == base and len(got) == 12
    await eng.close()


def test_sp_tp_mismatched_tp_rejected(cpu_mesh_devices):
    import pytest

    from dynamo_tpu.engine.sharding import make_mesh

    with pytest.raises(ValueError, match="tp"):
        TpuEngine(TpuEngineConfig(
            model=CFG, mesh=make_mesh(dp=1, tp=2,
                                      devices=cpu_mesh_devices),
            sp_mesh=sp_tp_mesh(cpu_mesh_devices, sp=4, tp=1),
            sp_threshold=16))


async def test_sp_zigzag_engine_matches_plain(cpu_mesh_devices):
    # zigzag bulk path (unit = 2*sp*page_size = 32): same output as the
    # plain engine
    prompt = [(i * 7) % 250 + 1 for i in range(70)]
    params = init_params(jax.random.PRNGKey(0), CFG)
    plain = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=2), params=params)
    base = await generate(plain, prompt)
    await plain.close()
    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=2,
        sp_mesh=sp_mesh(cpu_mesh_devices), sp_threshold=32,
        sp_layout="zigzag"), params=params)
    got = await generate(eng, prompt)
    assert got == base
    await eng.close()


def test_sp_tp_2d_mesh_matches_unsharded(cpu_mesh_devices):
    """sp x tp on a 2-D mesh (manual megatron psums inside the ring's
    shard_map) must match the unsharded forward — weights genuinely
    sharded over tp, sequence over sp."""
    import jax.numpy as jnp
    from dynamo_tpu.engine.sharding import shard_params
    from dynamo_tpu.models.llama_sp import sp_prefill

    cfg = LlamaConfig.tiny(max_pages_per_seq=32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.arange(1, 33, dtype=np.int32))[None]  # T=32

    mesh1 = Mesh(np.asarray(cpu_mesh_devices[:4]), axis_names=("sp",))
    ref_logits, ref_k, ref_v = sp_prefill(params, tokens, cfg, mesh1)

    mesh2 = Mesh(np.asarray(cpu_mesh_devices[:4]).reshape(2, 2),
                 axis_names=("sp", "tp"))
    sharded = shard_params(params, mesh2)
    logits, k_all, v_all = sp_prefill(sharded, tokens, cfg, mesh2,
                                      tp_axis="tp")
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(np.asarray(k_all), np.asarray(ref_k),
                               rtol=3e-2, atol=3e-2)
    # weights are REALLY tp-sharded: each device holds half the heads
    shapes = {s.data.shape[-1] for s in
              sharded["layers"]["wq"].addressable_shards}
    assert shapes == {cfg.num_heads * cfg.head_dim // 2}


def test_sp_tp_zigzag_2d(cpu_mesh_devices):
    import jax.numpy as jnp
    from dynamo_tpu.engine.sharding import shard_params
    from dynamo_tpu.models.llama_sp import sp_prefill

    cfg = LlamaConfig.tiny(max_pages_per_seq=32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.arange(1, 33, dtype=np.int32))[None]
    mesh1 = Mesh(np.asarray(cpu_mesh_devices[:4]), axis_names=("sp",))
    ref, _, _ = sp_prefill(params, tokens, cfg, mesh1)
    mesh2 = Mesh(np.asarray(cpu_mesh_devices[:4]).reshape(2, 2),
                 axis_names=("sp", "tp"))
    sharded = shard_params(params, mesh2)
    got, _, _ = sp_prefill(sharded, tokens, cfg, mesh2, layout="zigzag",
                           tp_axis="tp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-2, atol=3e-2)
