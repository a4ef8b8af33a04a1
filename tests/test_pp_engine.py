"""Engine-integrated pipeline parallelism (TpuEngineConfig.pp_mesh).

A pp=2 TpuEngine serves requests through pp_prefill_paged (chunk
microbatches, stage-local paged KV) + pp_decode_multi_step (lane-group
microbatches, psum token mailbox); greedy output must equal the plain
engine's on the same weights.
Reference: trtllm --pipeline-parallel-size (trtllm_utils.py:39,167-170).
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from dynamo_tpu.engine.attention import set_attention_impl
from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
from dynamo_tpu.models.llama import LlamaConfig, init_params
from dynamo_tpu.runtime.context import Context

set_attention_impl("xla")

# float32: pp pads prompts to different chunk widths than the plain
# engine's buckets, which legitimately flips one-ulp bf16 near-ties on
# random tiny-model logits (probed: stage-local layer outputs bit-match
# in their own dtype; the drift enters at padded-shape-dependent XLA
# fusion). f32 margins make greedy equality decisive.
import jax.numpy as jnp

CFG = LlamaConfig.tiny(num_layers=4, max_pages_per_seq=32,
                       dtype=jnp.float32)


def pp_mesh(devices, n=2):
    return Mesh(np.asarray(devices[:n]), axis_names=("pp",))


async def generate(eng, prompt, n_tokens=10, **sampling):
    req = {"token_ids": list(prompt), "model": "m",
           "sampling": {"temperature": 0.0, **sampling},
           "stop": {"max_tokens": n_tokens}}
    return [t async for o in eng.generate(req, Context())
            for t in o.get("token_ids", [])]


async def test_pp_engine_matches_plain_engine(cpu_mesh_devices):
    params = init_params(jax.random.PRNGKey(0), CFG)
    prompts = [[(i * 7 + j) % 250 + 1 for j in range(21 + 5 * i)]
               for i in range(3)]

    plain = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=4,
        decode_steps_per_sync=4), params=params)
    base = [await generate(plain, p) for p in prompts]
    await plain.close()

    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=4,
        decode_steps_per_sync=4, pp_mesh=pp_mesh(cpu_mesh_devices),
        pp_microbatches=2), params=params)
    got = [await generate(eng, p) for p in prompts]
    assert got == base, (got, base)
    await eng.close()


async def test_pp_engine_concurrent_batch(cpu_mesh_devices):
    """Concurrent lanes through the pp pipeline (batched prefill wave +
    microbatched decode) match the plain engine lane-for-lane."""
    import asyncio

    params = init_params(jax.random.PRNGKey(1), CFG)
    prompts = [[(i * 11 + j) % 250 + 1 for j in range(17 + 3 * i)]
               for i in range(4)]

    plain = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=4,
        decode_steps_per_sync=4), params=params)
    base = await asyncio.gather(*(generate(plain, p) for p in prompts))
    await plain.close()

    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=4,
        decode_steps_per_sync=4, pp_mesh=pp_mesh(cpu_mesh_devices),
        pp_microbatches=2), params=params)
    got = await asyncio.gather(*(generate(eng, p) for p in prompts))
    assert got == base, (got, base)
    await eng.close()


TOKEN_BYTES = [bytes([i]) if i < 256 else None
               for i in range(CFG.vocab_size)]

# the full sampling matrix: every request below must produce IDENTICAL output on a
# pp=2 engine and the plain engine — the constrained head runs on the
# last stage, same packings as the plain constrained burst
MATRIX = [
    {"sampling": {"temperature": 0.0, "top_logprobs": 3}},
    {"sampling": {"temperature": 0.0, "repetition_penalty": 1.3,
                  "frequency_penalty": 0.2, "presence_penalty": 0.1}},
    {"sampling": {"temperature": 0.8, "min_p": 0.2, "seed": 11}},
    {"sampling": {"temperature": 0.7, "seed": 5,
                  "guided": {"regex": "[a-f]{8}"}},
     "stop": {"max_tokens": 10, "stop_token_ids": [0]}},
]


async def collect(eng, prompt, spec):
    req = {"token_ids": list(prompt), "model": "m",
           "sampling": dict(spec["sampling"]),
           "stop": dict(spec.get("stop", {"max_tokens": 10}))}
    toks, topks = [], []
    async for o in eng.generate(req, Context()):
        toks += o.get("token_ids", [])
        topks += o.get("top_logprobs", []) or []
    return toks, topks


async def test_pp_engine_full_sampling_matrix_matches_plain(
        cpu_mesh_devices):
    params = init_params(jax.random.PRNGKey(2), CFG)
    prompt = [5, 6, 7, 8, 9]

    def mk(pp):
        kw = dict(pp_mesh=pp_mesh(cpu_mesh_devices),
                  pp_microbatches=2) if pp else {}
        return TpuEngine(TpuEngineConfig(
            model=CFG, num_pages=64, max_batch_size=4,
            decode_steps_per_sync=4, **kw), params=params,
            token_bytes=TOKEN_BYTES, eos_token_id=0)

    plain = mk(False)
    base = [await collect(plain, prompt, s) for s in MATRIX]
    await plain.close()
    eng = mk(True)
    got = [await collect(eng, prompt, s) for s in MATRIX]
    await eng.close()
    for spec, (bt, btk), (gt, gtk) in zip(MATRIX, base, got):
        assert gt == bt, (spec, gt, bt)
        assert [[e[0] for e in row] for row in gtk] == \
               [[e[0] for e in row] for row in btk], spec
        for br, gr in zip(btk, gtk):
            np.testing.assert_allclose([e[1] for e in gr],
                                       [e[1] for e in br], atol=2e-4)
    # the guided lane actually obeyed its grammar
    g_toks = got[3][0]
    body = bytes(t for t in g_toks if t != 0)
    assert len(body) == 8 and all(97 <= c <= 102 for c in body), body


async def test_pp_engine_mixed_constrained_batch_concurrent(
        cpu_mesh_devices):
    """All four sampling flavors IN ONE pp decode batch, concurrently —
    microbatch grouping must keep per-lane states/counts straight."""
    import asyncio

    params = init_params(jax.random.PRNGKey(3), CFG)
    prompts = [[(i * 13 + j) % 250 + 1 for j in range(9 + 2 * i)]
               for i in range(4)]

    def mk(pp):
        kw = dict(pp_mesh=pp_mesh(cpu_mesh_devices),
                  pp_microbatches=2) if pp else {}
        return TpuEngine(TpuEngineConfig(
            model=CFG, num_pages=64, max_batch_size=4,
            decode_steps_per_sync=4, **kw), params=params,
            token_bytes=TOKEN_BYTES, eos_token_id=0)

    plain = mk(False)
    base = await asyncio.gather(
        *(collect(plain, p, s) for p, s in zip(prompts, MATRIX)))
    await plain.close()
    eng = mk(True)
    got = await asyncio.gather(
        *(collect(eng, p, s) for p, s in zip(prompts, MATRIX)))
    await eng.close()
    assert [g[0] for g in got] == [b[0] for b in base]


def test_pp_engine_config_validation(cpu_mesh_devices):
    mesh = pp_mesh(cpu_mesh_devices)
    with pytest.raises(ValueError, match="microbatches"):
        TpuEngine(TpuEngineConfig(model=CFG, num_pages=16,
                                  max_batch_size=4, pp_mesh=mesh,
                                  pp_microbatches=1))
    with pytest.raises(ValueError, match="divisible"):
        TpuEngine(TpuEngineConfig(model=CFG, num_pages=16,
                                  max_batch_size=3, pp_mesh=mesh,
                                  pp_microbatches=2))
    with pytest.raises(ValueError, match="quantize"):
        TpuEngine(TpuEngineConfig(model=CFG, num_pages=16,
                                  max_batch_size=4, pp_mesh=mesh,
                                  pp_microbatches=2, quantize="int8"))


async def test_pp_engine_kv_pages_roundtrip(cpu_mesh_devices):
    """read/write_kv_pages on a pp engine's STACKED (L, ...) cache: the
    old per-layer loop would silently rebuild the stacked cache as a
    tuple on import; now both layouts round-trip bit-exact."""
    params = init_params(jax.random.PRNGKey(5), CFG)
    eng = TpuEngine(TpuEngineConfig(
        model=CFG, num_pages=64, max_batch_size=4,
        decode_steps_per_sync=4, pp_mesh=pp_mesh(cpu_mesh_devices),
        pp_microbatches=2), params=params)
    try:
        # serve once so some pages carry real KV
        await generate(eng, [5, 6, 7, 8, 9, 10, 11, 12], n_tokens=6)
        pages = [1, 2]
        data = await eng.read_kv_pages(pages)
        assert data.shape[0] == 2 and data.shape[1] == CFG.num_layers
        # write the same data back: layout must stay STACKED and bytes
        # must be unchanged
        eng.write_kv_pages(pages, data)
        assert not isinstance(eng.k_cache, tuple)
        again = await eng.read_kv_pages(pages)
        np.testing.assert_array_equal(data, again)
    finally:
        await eng.close()
